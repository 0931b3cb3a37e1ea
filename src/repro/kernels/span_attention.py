"""Span-windowed flash attention Pallas kernel (paper §III-B + §V-D1).

EdgeBERT writes the learned per-head spans into accelerator registers and
predicates attention compute on them.  The TPU adaptation (DESIGN.md §2):

  * heads with span 0 are gathered OUT of the call entirely (ops.py);
  * surviving heads run this kernel with a static window W (the bucket's max
    span, rounded up to the kv block): the kv-block loop visits only
    ceil((W + bq [+W bidi]) / bk) + 1 blocks per q block instead of Sk/bk —
    block-level predication, so out-of-span tiles are never DMA'd;
  * each head's exact integer span masks element-wise inside the tile
    (spans ride in via scalar prefetch), preserving ref semantics;
  * online max/LogSumExp softmax = the paper's Algorithm 1 at tile scope.

Layout: q/k/v are [BH, S, dh] with k/v pre-expanded per active head (GQA
gather fused by XLA upstream).  fp32 accumulate (the PU's 32-bit accumulator).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _span_attn_kernel(
    meta_ref,            # scalar prefetch: [2, BH] int32 — row 0 spans,
                         # row 1 per-row valid key counts (kv_lens)
    q_ref,               # [1, bq, dh]
    k_ref,               # [1, bk, dh]
    v_ref,               # [1, bk, dh]
    o_ref,               # [1, bq, dh]
    m_ref,               # VMEM [bq, 1] running max (2-D: Mosaic lays out
    l_ref,               # VMEM [bq, 1] running sum  rows, not 1-D vectors)
    acc_ref,             # VMEM [bq, dh]
    *,
    bq: int,
    bk: int,
    n_s: int,
    n_kb: int,
    sq: int,
    sk: int,
    window: int,
    causal: bool,
    scale: float,
):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = _base_block(qi, bq, bk, window, causal)
    k_blk = base + s
    last_needed = _last_block(qi, bq, bk, window, causal, n_kb)

    @pl.when(jnp.logical_and(k_blk < n_kb, k_blk <= last_needed))
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale            # [bq, dh]
        k = k_ref[0].astype(jnp.float32)                    # [bk, dh]
        scores = jax.lax.dot_general(                       # [bq, bk] = q k^T
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )

        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_blk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        d = q_pos - k_pos
        span = meta_ref[0, bh]
        kvl = meta_ref[1, bh]
        if causal:
            ok = (d >= 0) & (d < span)
        else:
            ok = (jnp.abs(d) < span)
        # kvl masks this ROW's padding (engine lanes are right-padded to the
        # bucket length); sk masks the call-level block padding
        ok = ok & (k_pos < kvl) & (k_pos < sk) & (q_pos < sq)
        scores = jnp.where(ok, scores, NEG_INF)

        m_prev = m_ref[...]                                 # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        p = jnp.where(ok, p, 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(s == n_s - 1)
    def _emit():
        l = l_ref[...]                                      # [bq, 1]
        out = acc_ref[...] / jnp.maximum(l, 1e-20)
        out = jnp.where(l > 0.0, out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def _base_block(qi, bq, bk, window, causal, np_mode=False):
    """First kv block a q block needs: covers q_start - (window-1) keys
    (bidirectional also looks forward, handled by last block)."""
    mx = np.maximum if np_mode else jnp.maximum
    q_start = qi * bq
    lo = q_start - (window - 1)
    return mx(lo // bk, 0)


def _last_block(qi, bq, bk, window, causal, n_kb, np_mode=False):
    mn = np.minimum if np_mode else jnp.minimum
    q_end = qi * bq + bq - 1
    hi = q_end if causal else q_end + (window - 1)
    return mn(hi // bk, n_kb - 1)


def span_attention(
    q: jnp.ndarray,              # [BH, Sq, dh]
    k: jnp.ndarray,              # [BH, Sk, dh] (expanded per head)
    v: jnp.ndarray,              # [BH, Sk, dh]
    spans: jnp.ndarray,          # [BH] int32 exact spans (> 0)
    window: int,                 # STATIC max span in this bucket
    *,
    causal: bool,
    bq: int = 128,
    bk: int = 128,
    interpret: bool,
    kv_lens: jnp.ndarray = None,  # [BH] int32 valid keys per row (right-
                                  # padded inputs); None = all Sk keys valid
) -> jnp.ndarray:
    BH, Sq, dh = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    bq_, bk_ = min(bq, Sq), min(bk, Sk)
    pq, pk_ = (-Sq) % bq_, (-Sk) % bk_
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk_:
        k = jnp.pad(k, ((0, 0), (0, pk_), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk_), (0, 0)))
    n_qb = q.shape[1] // bq_
    n_kb = k.shape[1] // bk_

    # static worst-case kv steps per q block (the whole point of the kernel:
    # n_s << n_kb when window << Sk)
    span_blocks = (window - 1) // bk_ + 1
    if causal:
        n_s = min((bq_ - 1) // bk_ + 1 + span_blocks, n_kb)
    else:
        n_s = min((bq_ - 1) // bk_ + 1 + 2 * span_blocks, n_kb)

    kernel = functools.partial(
        _span_attn_kernel,
        bq=bq_, bk=bk_, n_s=n_s, n_kb=n_kb, sq=Sq, sk=Sk,
        window=window, causal=causal, scale=scale,
    )

    def q_index(bh, qi, s, meta):
        return (bh, qi, 0)

    def kv_index(bh, qi, s, meta):
        base = _base_block(qi, bq_, bk_, window, causal)
        return (bh, jnp.minimum(base + s, n_kb - 1), 0)

    if kv_lens is None:
        kv_lens = jnp.full((BH,), Sk, jnp.int32)
    meta = jnp.stack(
        [spans.astype(jnp.int32), jnp.broadcast_to(kv_lens, (BH,)).astype(jnp.int32)]
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, n_qb, n_s),
            in_specs=[
                pl.BlockSpec((1, bq_, dh), q_index),
                pl.BlockSpec((1, bk_, dh), kv_index),
                pl.BlockSpec((1, bk_, dh), kv_index),
            ],
            out_specs=pl.BlockSpec((1, bq_, dh), q_index),
            scratch_shapes=[
                pltpu.VMEM((bq_, 1), jnp.float32),
                pltpu.VMEM((bq_, 1), jnp.float32),
                pltpu.VMEM((bq_, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="span_attn",   # the kernel's name in compiled HLO and traces
    )(meta, q, k, v)
    return out[:, :Sq]
