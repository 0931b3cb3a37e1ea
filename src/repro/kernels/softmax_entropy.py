"""Fused masked-softmax + entropy Pallas kernel.

Implements the paper's GB peripherals verbatim: Algorithm 1 (max trick +
LogSumExp softmax, then element-wise attention-span mask modulation) and the
Eq. 4 entropy as a fused by-product — the EdgeBERT accelerator computes these
back-to-back in the same unit, so one VMEM round-trip serves both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sm_ent_kernel(x_ref, mask_ref, p_ref, h_ref):
    x = x_ref[...].astype(jnp.float32)                 # [R, N]
    # Step 1: max trick
    m = jnp.max(x, axis=-1, keepdims=True)
    z = x - m
    # Step 2: log-exponential-sum
    e = jnp.exp(z)
    s = jnp.sum(e, axis=-1, keepdims=True)
    # Step 3: softmax + span-mask modulation
    probs = e / s
    p_ref[...] = (probs * mask_ref[...].astype(jnp.float32)).astype(p_ref.dtype)
    # Eq. 4 entropy (of the unmasked distribution)
    ent = jnp.log(s[:, 0]) - jnp.sum(z * e, axis=-1) / s[:, 0]
    h_ref[...] = jnp.maximum(ent, 0.0)


def softmax_entropy(
    logits: jnp.ndarray,          # [rows, n]
    mask: jnp.ndarray,            # [rows, n] (ones for pure softmax)
    *,
    block_rows: int = 256,
    interpret: bool,
):
    rows, n = logits.shape
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        logits = jnp.pad(logits, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
    n_blocks = logits.shape[0] // block_rows

    probs, ent = pl.pallas_call(
        _sm_ent_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(logits.shape, logits.dtype),
            jax.ShapeDtypeStruct((logits.shape[0],), jnp.float32),
        ],
        interpret=interpret,
        name="offramp_entropy",   # the kernel's name in compiled HLO and traces
    )(logits, mask)
    return probs[:rows], ent[:rows]
