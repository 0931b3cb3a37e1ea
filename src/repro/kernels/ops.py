"""jit'd dispatch wrappers around the Pallas kernels.

On the CPU backend kernels run in interpret mode — the kernel body executes
in Python for correctness validation; on TPU the same code emits Mosaic
(``dispatch.interpret_mode``).  `span_attention_op` implements the full
EdgeBERT deploy path: dead heads (span 0) are gathered out of the graph,
survivors run the windowed kernel bucketed by span.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adaptivfloat import AFFormat
from repro.core.adaptive_span import active_head_indices
from repro.kernels import adaptivfloat_k, block_sparse, dispatch, layernorm, softmax_entropy, span_attention


@functools.partial(jax.jit, static_argnames=("eps",))
def layernorm_op(x: jnp.ndarray, gamma: jnp.ndarray, beta: jnp.ndarray, eps: float = 1e-6):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = layernorm.layernorm(x2, gamma, beta, eps=eps, interpret=dispatch.interpret_mode())
    return out.reshape(shape)


@jax.jit
def softmax_entropy_op(logits: jnp.ndarray, mask: Optional[jnp.ndarray] = None):
    """Fused softmax + entropy over the last axis.

    Mask semantics (audited, see tests/test_kernels.py): the kernel computes
    the entropy of the FULL softmax distribution and applies `mask` only to
    the returned probs — it does NOT renormalize over unmasked entries.
    `mask=None` therefore means "no positions are padding", which is exactly
    the serving off-ramp case: the engines call this on [lanes, C] class
    logits where every class column is real (lane padding is masked upstream
    in attention via per-lane kv_len, so padded positions never reach the
    off-ramp logits).  Callers with genuinely padded logit columns must mask
    or slice BEFORE the softmax; passing `mask` here only zeroes probs.
    """
    shape = logits.shape
    x2 = logits.reshape(-1, shape[-1])
    if mask is None:
        mask = jnp.ones_like(x2)
    else:
        assert mask.shape == logits.shape, (
            f"mask shape {mask.shape} must match logits shape {logits.shape}"
        )
        mask = mask.reshape(-1, shape[-1])
    p, h = softmax_entropy.softmax_entropy(x2, mask, interpret=dispatch.interpret_mode())
    return p.reshape(shape), h.reshape(shape[:-1])


@functools.partial(jax.jit, static_argnames=("n_bits", "n_exp"))
def af_quantize_op(x: jnp.ndarray, n_bits: int = 8, n_exp: int = 3):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    out = adaptivfloat_k.quantize(x2, fmt=AFFormat(n_bits, n_exp), interpret=dispatch.interpret_mode())
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("n_bits", "n_exp"))
def af_matmul_op(x: jnp.ndarray, w_codes: jnp.ndarray, e_min: jnp.ndarray,
                 n_bits: int = 8, n_exp: int = 3):
    return adaptivfloat_k.af_matmul(
        x, w_codes, e_min, fmt=AFFormat(n_bits, n_exp), interpret=dispatch.interpret_mode()
    )


def block_sparse_matmul_op(x, w, block_mask, bk: int = 128, bn: int = 128):
    """block_mask must be a STATIC numpy occupancy array (deploy-time masks)."""
    return block_sparse.block_sparse_matmul(
        x, w, np.asarray(block_mask), bk=bk, bn=bn, interpret=dispatch.interpret_mode()
    )


def span_attention_op(
    q: jnp.ndarray,            # [B, S, H, dh]
    k: jnp.ndarray,            # [B, S, KV, dh]
    v: jnp.ndarray,            # [B, S, KV, dh]
    spans,                     # per-head integer spans (len H; 0 = off) —
                               # static sequence OR a traced int array
    *,
    causal: bool,
    bq: int = 128,
    bk: int = 128,
) -> jnp.ndarray:
    """EdgeBERT deployed attention: dead heads skipped, survivors windowed.

    Returns [B, S, H, dh] with zero context vectors for span-0 heads (the
    accelerator writes zeros to the UAB for those heads, §V-D1).

    With STATIC spans, dead heads are gathered out host-side and the kernel
    window shrinks to the max surviving span (the deploy fast path).  With
    TRACED spans (called under jit with spans as an operand) no host-side
    numpy indexing is possible: all heads run with a full static window and
    the exact spans ride in via scalar prefetch — span-0 heads come back as
    zero rows from the kernel itself, so semantics match the gather path.
    """
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV

    if isinstance(spans, jax.core.Tracer):
        qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
        kh = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
        vh = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
        sp = jnp.tile(spans.astype(jnp.int32), B)
        Sk = k.shape[1]
        out = span_attention.span_attention(
            qh,
            kh.reshape(B * H, Sk, dh),
            vh.reshape(B * H, Sk, dh),
            sp,
            Sk,                      # window covers any span; exact spans
            causal=causal,           # still mask element-wise in the kernel
            bq=bq,
            bk=bk,
            interpret=dispatch.interpret_mode(),
        ).reshape(B, H, Sq, dh)
        return out.transpose(0, 2, 1, 3)

    spans_np = np.asarray(spans, np.int32)
    active, window = active_head_indices(spans_np)
    if len(active) == 0:
        return jnp.zeros_like(q)

    # gather active heads; expand K/V per head (XLA fuses the gather)
    qh = q.transpose(0, 2, 1, 3)[:, active]                   # [B, Ha, S, dh]
    kv_idx = (active // G).astype(np.int32)
    kh = k.transpose(0, 2, 1, 3)[:, kv_idx]
    vh = v.transpose(0, 2, 1, 3)[:, kv_idx]
    Ha = len(active)
    sp = jnp.asarray(np.tile(spans_np[active], B))

    out = span_attention.span_attention(
        qh.reshape(B * Ha, Sq, dh),
        kh.reshape(B * Ha, -1, dh),
        vh.reshape(B * Ha, -1, dh),
        sp,
        int(window),
        causal=causal,
        bq=bq,
        bk=bk,
        interpret=dispatch.interpret_mode(),
    ).reshape(B, Ha, Sq, dh)

    full = jnp.zeros((B, H, Sq, dh), q.dtype)
    full = full.at[:, active].set(out)
    return full.transpose(0, 2, 1, 3)
