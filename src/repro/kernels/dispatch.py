"""Ref/Pallas dispatch for the fused serving step (`use_pallas=`).

The serving engines build their fused per-bucket step out of the model's
layer math; this module is the single seam where that math can be routed to
the Pallas kernels instead of the reference jnp ops.  Call sites guard with
``if use_pallas:`` so the ref path stays byte-identical when the flag is off.

Rules the dispatchers obey (the engine's compile guarantees depend on them):

  * `use_pallas` is a plain Python bool closed over by the engine's jit'd
    closures — static, so flipping it costs one trace per bucket, same as
    the ref path (zero-NEW-traces per request either way);
  * everything traced stays traced: per-lane `kv_len` rides into the span
    kernel via scalar prefetch, spans/shapes/block masks are static;
  * `interpret_mode()` decides how every kernel runs: compiled by Mosaic
    on the TPU, interpreted on the CPU backend (the same `pallas_call`s
    execute their bodies in Python, so CPU tests exercise the exact kernel
    code that Mosaic compiles), and an error on any other backend;
  * every kernel runs under a stable name, its `pallas_call`'s `name` and a
    `jax.named_scope` around its glue (reshapes, padding): the compiled HLO
    and the device trace call it `bs_mlp_up`, `bs_mlp_down`, `layernorm`,
    `offramp_entropy`, `af_quant` or `span_attn`.

Eligibility notes:
  * soft (trained) spans taper probabilities over a ramp; the hard-window
    span kernel cannot reproduce that, so `span_z is not None` call sites
    keep ref attention.  Dense/no-span attention routes to the span kernel
    with a full window plus per-row kv_len masking.
  * KV-cache decode attention stays ref (cache update + AF8 codec are
    fused with the attention math there).
  * block-sparse MLP needs a STATIC occupancy mask; `mlp_block_masks`
    derives one host-side from concrete (pruned) weights at server build
    time.  All-occupied masks are reported as None (dense weights gain
    nothing from tile skipping).
  * every dispatcher stays eligible INSIDE `shard_map` (the multi-device
    serving path): `pallas_call` has no replication rule, so the sharded
    fused-step wrappers must go through `jax_compat.shard_map_norep`
    (check_vma off).  Nothing here may introduce a cross-shard
    collective — each kernel sees only its replica's `[lanes_per_replica,
    ...]` slab, which is what keeps a 1-replica mesh bit-identical to the
    unsharded step.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adaptivfloat import AFFormat
from repro.kernels import adaptivfloat_k, block_sparse
from repro.kernels import layernorm as _ln_k
from repro.kernels import softmax_entropy as _sm_k
from repro.kernels import span_attention as _span_k


def interpret_mode() -> bool:
    """True on the CPU backend, False on the TPU; any other backend raises
    rather than silently running the kernels in the Python interpreter."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run on the TPU, or interpreted on the CPU; "
            f"the default backend is {backend!r}"
        )
    return backend == "cpu"


# ---------------------------------------------------------------------------
# LayerNorm (Eq. 5 running moments)
# ---------------------------------------------------------------------------


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
              *, eps: float = 1e-6) -> jnp.ndarray:
    """Fused two-moment LayerNorm over the last axis; any leading shape."""
    shape = x.shape
    with jax.named_scope("layernorm"):
        out = _ln_k.layernorm(
            x.reshape(-1, shape[-1]), scale, bias, eps=eps, interpret=interpret_mode()
        )
        return out.reshape(shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Off-ramp entropy (Eq. 4)
# ---------------------------------------------------------------------------


def entropy(logits: jnp.ndarray) -> jnp.ndarray:
    """Entropy of softmax(logits) over the last axis -> logits.shape[:-1].

    The all-ones mask is deliberate: off-ramp logits are [lanes, C] class
    scores with no padded positions (lane padding is masked upstream, in
    attention, via kv_len) — see `ops.softmax_entropy_op`.
    """
    shape = logits.shape
    with jax.named_scope("offramp_entropy"):
        x2 = logits.reshape(-1, shape[-1])
        _, h = _sm_k.softmax_entropy(x2, jnp.ones_like(x2), interpret=interpret_mode())
        return h.reshape(shape[:-1])


# ---------------------------------------------------------------------------
# AdaptivFloat activation fake-quant
# ---------------------------------------------------------------------------


def act_quantize(
    x: jnp.ndarray, n_bits: int, n_exp: int, amax: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    shape = x.shape
    with jax.named_scope("af_quant"):
        x2 = x.reshape(-1, shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
        out = adaptivfloat_k.quantize(
            x2, fmt=AFFormat(n_bits, n_exp), amax=amax, interpret=interpret_mode()
        )
        return out.reshape(shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense (full-window) attention via the span kernel
# ---------------------------------------------------------------------------


def dense_attention(
    q: jnp.ndarray,              # [B, Sq, H, dh]
    k: jnp.ndarray,              # [B, Sk, KV, dh]
    v: jnp.ndarray,              # [B, Sk, KV, dh]
    *,
    causal: bool,
    kv_len: Any = None,          # scalar (may be traced) valid key count
    bq: int = 128,
    bk: int = 128,
) -> jnp.ndarray:
    """Span kernel with window = Sk (full attention) + per-row kv_len mask.

    This is the serving fused-step attention: lanes are right-padded to the
    bucket length and each lane's true length arrives as a traced scalar,
    which rides into the kernel through scalar prefetch.
    """
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    with jax.named_scope("span_attn"):
        qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
        kh = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, Sk, dh)
        vh = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, Sk, dh)
        spans = jnp.full((B * H,), Sk, jnp.int32)
        kvl = None
        if kv_len is not None:
            kvl = jnp.broadcast_to(
                jnp.asarray(kv_len, jnp.int32).reshape(()), (B * H,)
            )
        out = _span_k.span_attention(
            qh, kh, vh, spans, Sk,
            causal=causal, bq=bq, bk=bk, interpret=interpret_mode(), kv_lens=kvl,
        )
        return out.reshape(B, H, Sq, dh).transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# Block-sparse MLP matmuls (§V-C tile skip)
# ---------------------------------------------------------------------------

# A derived mask entry: (occupancy [K//bk, N//bn] np.bool_, bk, n)
BlockMask = Tuple[np.ndarray, int, int]


def _block_size(dim: int, want: int) -> int:
    """Largest multiple of 128 that divides ``dim`` and is at most
    ``want``, else ``dim`` itself.  Mosaic needs each of a block's last two
    dimensions to be a multiple of (8, 128) or the whole array dimension;
    the weight tile's K side is the activation tile's lane dimension, so
    both sides of the tile follow the 128 rule."""
    for b in range(min(want, dim) // 128 * 128, 0, -128):
        if dim % b == 0:
            return b
    return dim


def mlp_block_masks(
    mlp_params: Dict[str, Any], bk: int = 128, bn: int = 128
) -> Dict[str, Optional[BlockMask]]:
    """Host-side static occupancy masks for each MLP weight matrix.

    Must be called on CONCRETE weights (server build time, post-pruning).
    Fully-occupied matrices map to None — dense weights gain nothing from
    tile skipping, so those matmuls stay on the ref path.
    """
    masks: Dict[str, Optional[BlockMask]] = {}
    for name in ("w_gate", "w_up", "w_down"):
        w = mlp_params.get(name)
        if w is None:
            continue
        wn = np.asarray(w)
        K, N = wn.shape
        bk_, bn_ = _block_size(K, bk), _block_size(N, bn)
        occ = (
            np.abs(wn.reshape(K // bk_, bk_, N // bn_, bn_)).sum(axis=(1, 3)) > 0
        )
        masks[name] = (occ, bk_, bn_) if not occ.all() else None
    return masks


def sparse_matmul(x: jnp.ndarray, w: jnp.ndarray, mask: BlockMask, *,
                  name: str) -> jnp.ndarray:
    """x @ w skipping pruned (all-zero) weight tiles; any leading shape.
    ``name`` names the kernel (``bs_mlp_up``, ``bs_mlp_down``)."""
    occ, bk_, bn_ = mask
    shape = x.shape
    with jax.named_scope(name):
        x2 = x.reshape(-1, shape[-1])
        out = block_sparse.block_sparse_matmul(
            x2, w, occ, bm=128, bk=bk_, bn=bn_, interpret=interpret_mode(), name=name
        )
        return out.reshape(*shape[:-1], w.shape[1]).astype(x.dtype)
