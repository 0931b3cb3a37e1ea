"""AdaptivFloat Pallas kernels (paper §III-E + §V-C FP8 datapath).

1. ``quantize``  — tile-wise quantize-dequantize with the per-tensor exponent
   bias (amax is a scalar computed outside, matching the PU's per-tensor bias
   register).
2. ``af_matmul`` — weight-quantized matmul: AF8 codes are stored as uint8 in
   HBM (halving weight traffic), decoded at the VMEM edge, and fed to the MXU
   with fp32 accumulation — the TPU rendition of the paper's 8-bit multiply /
   32-bit accumulate processing unit.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.adaptivfloat import AFFormat


def _quant_body(x, e_min, fmt: AFFormat):
    """Quantize-dequantize math on a tile (same algebra as core.af_quantize)."""
    n_mant_scale = float(2 ** fmt.n_mant)
    e_min_f = e_min.astype(jnp.float32)
    e_max_f = e_min_f + (fmt.n_levels_exp - 1)
    a = jnp.abs(x)
    sign = jnp.sign(x)
    safe_a = jnp.maximum(a, 1e-38)
    e = jnp.clip(jnp.floor(jnp.log2(safe_a)), e_min_f, e_max_f)
    scale = jnp.exp2(e)
    mant = jnp.round(a / scale * n_mant_scale) / n_mant_scale
    val = mant * scale
    max_val = (2.0 - 1.0 / n_mant_scale) * jnp.exp2(e_max_f)
    val = jnp.minimum(val, max_val)
    min_pos = jnp.exp2(e_min_f) * (1.0 + 1.0 / n_mant_scale)
    val = jnp.where(a < 0.5 * min_pos, 0.0, jnp.maximum(val, min_pos))
    return sign * val


def _quantize_kernel(x_ref, emin_ref, o_ref, *, fmt: AFFormat):
    x = x_ref[...].astype(jnp.float32)
    # the bias is a [1, 1] block: a 1-D one becomes an illegal (8, 1) array
    # with a (1, 1) block once the fused step vmaps this call over lanes
    e_min = emin_ref[...]
    o_ref[...] = _quant_body(x, e_min, fmt).astype(o_ref.dtype)


def quantize(
    x: jnp.ndarray,           # [rows, d]
    *,
    fmt: AFFormat = AFFormat(),
    amax: Optional[jnp.ndarray] = None,
    block_rows: int = 256,
    interpret: bool,
) -> jnp.ndarray:
    """Quantize-dequantize to the AdaptivFloat grid; per-tensor bias from
    ``amax`` (default: the tensor's own max-abs)."""
    rows, d = x.shape
    if amax is None:
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    amax = jnp.maximum(amax.astype(jnp.float32), 1e-30)
    e_min = jnp.clip(
        jnp.floor(jnp.log2(amax)) - (fmt.n_levels_exp - 1), -120.0, 120.0
    ).astype(jnp.float32)

    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    n_blocks = x.shape[0] // block_rows

    out = pl.pallas_call(
        functools.partial(_quantize_kernel, fmt=fmt),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="af_quant",   # the kernel's name in compiled HLO and traces
    )(x, e_min.reshape(1, 1))
    return out[:rows]


# ---------------------------------------------------------------------------
# AF8-weight matmul
# ---------------------------------------------------------------------------


def _decode_tile(codes: jnp.ndarray, e_min, fmt: AFFormat) -> jnp.ndarray:
    c = codes.astype(jnp.int32)
    sign_bit = (c >> (fmt.n_bits - 1)) & 1
    e_field = (c >> fmt.n_mant) & (fmt.n_levels_exp - 1)
    m_field = c & ((1 << fmt.n_mant) - 1)
    n_mant_scale = float(2 ** fmt.n_mant)
    e = e_field.astype(jnp.float32) + e_min.astype(jnp.float32)
    val = jnp.exp2(e) * (1.0 + m_field.astype(jnp.float32) / n_mant_scale)
    val = jnp.where((e_field == 0) & (m_field == 0), 0.0, val)
    return jnp.where(sign_bit == 1, -val, val)


def _af_matmul_kernel(x_ref, w_ref, emin_ref, o_ref, acc_ref, *, fmt: AFFormat, n_k: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _decode_tile(w_ref[...], emin_ref[0], fmt)          # [bk, bn] fp32
    x = x_ref[...].astype(jnp.float32)                      # [bm, bk]
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k_step == n_k - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def af_matmul(
    x: jnp.ndarray,            # [M, K] float
    w_codes: jnp.ndarray,      # [K, N] uint8
    e_min: jnp.ndarray,        # scalar
    *,
    fmt: AFFormat = AFFormat(),
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    M, K = x.shape
    K2, N = w_codes.shape
    assert K == K2
    bm_, bk_, bn_ = min(bm, M), min(bk, K), min(bn, N)
    pm, pk, pn = (-M) % bm_, (-K) % bk_, (-N) % bn_
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w_codes = jnp.pad(w_codes, ((0, pk), (0, pn)))  # code 0 decodes to 0.0
    Mp, Kp, Np = x.shape[0], x.shape[1], w_codes.shape[1]
    n_k = Kp // bk_

    # scratch via pltpu VMEM (works in interpret mode too)
    from jax.experimental.pallas import tpu as pltpu

    out = pl.pallas_call(
        functools.partial(_af_matmul_kernel, fmt=fmt, n_k=n_k),
        grid=(Mp // bm_, Np // bn_, n_k),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
            pl.BlockSpec((1,), lambda i, j, k: (0,)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        interpret=interpret,
    )(x, w_codes, e_min.reshape(1).astype(jnp.float32))
    return out[:M, :N]
