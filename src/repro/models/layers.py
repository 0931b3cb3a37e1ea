"""Shared neural layers: norms, rope, embeddings, GQA attention (span-aware,
flash-style chunked), MLPs.  Pure JAX; the Pallas kernels in repro.kernels
provide TPU-tiled versions of the hot paths and are validated against these.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.util import ceil_div

Params = Dict[str, Any]


def _dispatch():
    # lazy: pulls in pallas machinery only when a use_pallas=True path runs
    from repro.kernels import dispatch

    return dispatch


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------


def dense_init(rng, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


def embed_init(rng, shape, dtype):
    return (jax.random.normal(rng, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms (paper §V-D3 computes LN as E[X^2]-E[X]^2 running moments)
# ---------------------------------------------------------------------------


def init_norm(kind: str, d: int, dtype) -> Params:
    if kind == "rms":
        return {"scale": jnp.ones((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "norm_bias": jnp.zeros((d,), dtype)}


def apply_norm(
    p: Params, x: jnp.ndarray, kind: str, eps: float = 1e-6,
    use_pallas: bool = False,
) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rms":
        # no Pallas kernel for RMS norm; the flag is a no-op here
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    if use_pallas:
        return _dispatch().layernorm(x, p["scale"], p["norm_bias"], eps=eps)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    # E[X^2] - E[X]^2 form (matches the accelerator's running-moment unit)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True) - mean * mean
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["norm_bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, n, head_dim]; positions: [S] or broadcastable to x[..., S]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., None, :]                # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, span-aware, chunked online-softmax)
# ---------------------------------------------------------------------------


def init_attention(rng, cfg, dtype, d_in: Optional[int] = None) -> Params:
    """cfg needs: d_model, n_heads, n_kv_heads, head_dim, qkv_bias."""
    d = d_in if d_in is not None else cfg.d_model
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dtype),
        "wk": dense_init(ks[1], (d, KV * hd), dtype),
        "wv": dense_init(ks[2], (d, KV * hd), dtype),
        "wo": dense_init(ks[3], (H * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    return p


def _soft_span_block_mask(
    z: jnp.ndarray, ramp: int, q_pos: jnp.ndarray, k_pos: jnp.ndarray, causal: bool
) -> jnp.ndarray:
    """[H, qb, kb] soft span mask for one (q_block, kv_block) pair."""
    d = q_pos[:, None] - k_pos[None, :]
    if not causal:
        d = jnp.abs(d)
    m = jnp.clip((ramp + z[:, None, None] - d[None].astype(jnp.float32)) / float(ramp), 0.0, 1.0)
    return m


def attention(
    q: jnp.ndarray,              # [B, Sq, H, hd]
    k: jnp.ndarray,              # [B, Sk, KV, hd]
    v: jnp.ndarray,              # [B, Sk, KV, hd]
    *,
    causal: bool,
    q_offset: Any = 0,           # absolute position of q[0] (decode)
    span_z: Optional[jnp.ndarray] = None,   # [H] soft spans (train/eval)
    span_ramp: int = 32,
    q_block: int = 512,
    kv_block: int = 1024,
    kv_len: Optional[Any] = None,  # valid cache length for decode (<= Sk)
) -> jnp.ndarray:
    """Chunked online-softmax attention (flash-style scan; the jnp twin of the
    Pallas span_attention kernel).  Returns [B, Sq, H, hd]."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    if Sq <= 16:
        # decode fast path: no q blocking/padding; one masked softmax over the
        # whole (cache) key range. Scores [B,Sq,KV,G,Sk] — fine at decode.
        # K/V stay in their storage dtype; the dot accumulates in f32
        # (preferred_element_type) so the 16+GB cache is never up-converted.
        qf = (q * jnp.asarray(scale, q.dtype)).reshape(B, Sq, KV, G, hd)
        s = jnp.einsum(
            "bqkgd,bskd->bqkgs", qf, k, preferred_element_type=jnp.float32
        )
        q_pos = q_offset + jnp.arange(Sq)
        k_pos = jnp.arange(Sk)
        valid = (k_pos[None, :] < (jnp.asarray(kv_len) if kv_len is not None else Sk))
        if causal:
            valid = valid & (q_pos[:, None] >= k_pos[None, :])
        else:
            valid = jnp.broadcast_to(valid, (Sq, Sk))
        s = jnp.where(valid[None, :, None, None, :], s, -jnp.inf)
        if span_z is not None:
            sm = _soft_span_block_mask(span_z, span_ramp, q_pos, k_pos, causal)
            sm = sm.reshape(KV, G, Sq, Sk).transpose(2, 0, 1, 3)
            s = s + jnp.log(jnp.maximum(sm, 1e-20))[None]
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(s - m)
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        p = (p / jnp.maximum(l, 1e-20)).astype(v.dtype)
        out = jnp.einsum("bqkgs,bskd->bqkgd", p, v, preferred_element_type=jnp.float32)
        return out.reshape(B, Sq, H, hd).astype(q.dtype)

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    qf = (q * jnp.asarray(scale, q.dtype)).reshape(B, Sq, KV, G, hd)
    kf = k
    vf = v

    n_qb = ceil_div(Sq, q_block)
    n_kb = ceil_div(Sk, kv_block)
    pad_q = n_qb * q_block - Sq
    pad_k = n_kb * kv_block - Sk
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    Sq_p, Sk_p = n_qb * q_block, n_kb * kv_block

    qf = qf.reshape(B, n_qb, q_block, KV, G, hd)
    kf = kf.reshape(B, n_kb, kv_block, KV, hd)
    vf = vf.reshape(B, n_kb, kv_block, KV, hd)

    valid_k = kv_len if kv_len is not None else Sk
    valid_k = jnp.asarray(valid_k)

    def q_chunk(qb_idx, q_tile):
        # q_tile: [B, q_block, KV, G, hd]
        q_pos = q_offset + qb_idx * q_block + jnp.arange(q_block)

        def kv_chunk(carry, inputs):
            m_run, l_run, acc = carry
            kb_idx, k_tile, v_tile = inputs
            k_pos = kb_idx * kv_block + jnp.arange(kv_block)
            # scores: [B, q_block, KV, G, kv_block] — bf16 in, f32 out (MXU)
            s = jnp.einsum(
                "bqkgd,bskd->bqkgs", q_tile, k_tile,
                preferred_element_type=jnp.float32,
            )
            mask = (k_pos[None, :] < valid_k)
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
            else:
                mask = jnp.broadcast_to(mask, (q_block, kv_block))
            s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
            if span_z is not None:
                sm = _soft_span_block_mask(span_z, span_ramp, q_pos, k_pos, causal)
                sm = sm.reshape(KV, G, q_block, kv_block).transpose(2, 0, 1, 3)
                # span modulates probabilities (paper: mask element-wise times
                # softmax output) -> equivalent to adding log(mask) pre-softmax
                s = s + jnp.log(jnp.maximum(sm, 1e-20))[None]
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            # guard rows where everything is masked
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            corr = jnp.exp(jnp.where(jnp.isfinite(m_run), m_run - m_safe, -jnp.inf))
            corr = jnp.where(jnp.isfinite(m_run), corr, 0.0)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bqkgs,bskd->bqkgd", p.astype(v_tile.dtype), v_tile,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc), None

        init = (
            jnp.full((B, q_block, KV, G), -jnp.inf, jnp.float32),
            jnp.zeros((B, q_block, KV, G), jnp.float32),
            jnp.zeros((B, q_block, KV, G, hd), jnp.float32),
        )
        (m_run, l_run, acc), _ = jax.lax.scan(
            kv_chunk,
            init,
            (jnp.arange(n_kb), kf.transpose(1, 0, 2, 3, 4), vf.transpose(1, 0, 2, 3, 4)),
        )
        out = acc / jnp.maximum(l_run, 1e-20)[..., None]
        return out  # [B, q_block, KV, G, hd]

    outs = jax.lax.map(
        lambda i: q_chunk(i, qf[:, i]), jnp.arange(n_qb)
    )  # [n_qb, B, q_block, KV, G, hd]
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq_p, H, hd)
    return out[:, :Sq].astype(q.dtype)


def attention_layer(
    p: Params,
    x: jnp.ndarray,                 # [B, S, d]
    cfg,
    *,
    causal: bool,
    positions: Optional[jnp.ndarray] = None,
    span_z: Optional[jnp.ndarray] = None,
    span_ramp: int = 32,
    cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # (k, v) [B, Smax, KV, hd]
    cache_pos: Any = None,          # write position for decode
    kv_source: Optional[jnp.ndarray] = None,  # cross-attention keys/values input
    kv_len: Any = None,             # valid key length (right-padded inputs);
                                    # cache-free paths only — decode derives it
    use_pallas: bool = False,       # route eligible attention to the Pallas
                                    # span kernel (see kernels.dispatch)
) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    assert kv_len is None or cache is None, "kv_len is derived from the cache"
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    src = kv_source if kv_source is not None else x
    q = x @ p["wq"]
    k = src @ p["wk"]
    v = src @ p["wv"]
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, src.shape[1], KV, hd)
    v = v.reshape(B, src.shape[1], KV, hd)

    if positions is None:
        positions = jnp.arange(S)
    if cfg.pos == "rope" and kv_source is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    q_offset = 0
    if cache is not None:
        ck, cv = cache
        if ck.dtype == jnp.uint8:
            # AF8 KV cache: encode the new column, decode the whole cache for
            # attention (the decode is VMEM-side inside the fused kernel on
            # TPU; HBM only ever sees uint8 codes — half the traffic)
            from repro.core.adaptivfloat import af_decode_static, af_encode_static

            e_min = getattr(cfg, "kv_af8_e_min", -10)
            kc = af_encode_static(k.astype(jnp.float32), e_min)
            vc = af_encode_static(v.astype(jnp.float32), e_min)
            ck = jax.lax.dynamic_update_slice(ck, kc, (0, cache_pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, vc, (0, cache_pos, 0, 0))
            cache = (ck, cv)
            act_dtype = x.dtype
            k = af_decode_static(ck, e_min, dtype=act_dtype)
            v = af_decode_static(cv, e_min, dtype=act_dtype)
        else:
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_pos, 0, 0))
            k, v = ck, cv
            cache = (ck, cv)
        q_offset = cache_pos
        kv_len = cache_pos + S

    import contextlib

    scope = (
        jax.named_scope("fused_attn_kernel")
        if getattr(cfg, "fused_attention", False)
        else contextlib.nullcontext()
    )
    # Pallas eligibility: the hard-window span kernel cannot reproduce the
    # soft (ramped) span mask, and cache decode fuses the KV update/codec
    # with the attention math — those stay ref.  What remains is exactly the
    # serving fused-step case: cache-free self-attention on right-padded
    # lanes, which routes to the span kernel with a full window + per-row
    # kv_len masking.
    pallas_ok = (
        use_pallas and cache is None and kv_source is None and span_z is None
    )
    with scope:
        if pallas_ok:
            out = _dispatch().dense_attention(q, k, v, causal=causal, kv_len=kv_len)
        else:
            out = attention(
                q, k, v,
                causal=causal and kv_source is None,
                q_offset=q_offset,
                span_z=span_z,
                span_ramp=span_ramp,
                kv_len=kv_len,
            )
    out = out.reshape(B, S, H * hd) @ p["wo"]
    return out, cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(rng, d: int, ff: int, act: str, dtype) -> Params:
    ks = jax.random.split(rng, 3)
    if act == "swiglu":
        return {
            "w_gate": dense_init(ks[0], (d, ff), dtype),
            "w_up": dense_init(ks[1], (d, ff), dtype),
            "w_down": dense_init(ks[2], (ff, d), dtype),
        }
    return {
        "w_up": dense_init(ks[0], (d, ff), dtype),
        "w_down": dense_init(ks[1], (ff, d), dtype),
    }


def apply_mlp(
    p: Params, x: jnp.ndarray, act: str,
    use_pallas: bool = False,
    block_masks: Optional[Dict[str, Any]] = None,  # STATIC occupancy masks
                                                   # (kernels.dispatch.mlp_block_masks)
) -> jnp.ndarray:
    def mm(h_, name):
        if use_pallas and block_masks and block_masks.get(name) is not None:
            return _dispatch().sparse_matmul(
                h_, p[name], block_masks[name], name="bs_mlp_" + name[2:]
            )
        return h_ @ p[name]

    if act == "swiglu":
        g = mm(x, "w_gate")
        u = mm(x, "w_up")
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        h = mm(x, "w_up")
        if act == "gelu":
            h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
        elif act == "relu2":
            h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(x.dtype)
        else:
            raise ValueError(act)
    return mm(h, "w_down")
