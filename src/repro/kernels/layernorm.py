"""Fused LayerNorm Pallas kernel (paper §V-D3: GB LayerNorm unit, Eq. 5).

Single pass per row tile: accumulate E[X] and E[X^2] over the feature dim in
fp32 (the accelerator's running-moment formulation), normalize, fuse gamma/
beta.  Rows are tiled (block_rows, d) in VMEM; d is kept whole per tile (MXU-
aligned models have d a multiple of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _layernorm_kernel(x_ref, g_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True) - mean * mean
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype
    )


def layernorm(
    x: jnp.ndarray,
    gamma: jnp.ndarray,
    beta: jnp.ndarray,
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool,
) -> jnp.ndarray:
    """x: [rows, d] (callers flatten leading dims)."""
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    n_blocks = x.shape[0] // block_rows

    out = pl.pallas_call(
        functools.partial(_layernorm_kernel, eps=eps),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="layernorm",   # the kernel's name in compiled HLO and traces
    )(x, gamma, beta)
    return out[:rows]
