"""AdaptivFloat quantization (paper §III-E; Tambe et al. [52]).

An n-bit floating-point format (1 sign, ``n_exp`` exponent, rest mantissa)
whose exponent *bias* adapts per tensor to its dynamic range:

    e_max = floor(log2(amax));  e_min = e_max - (2**n_exp - 1)
    normals: +/- 2^e * (1 + m / 2^n_mant),  e in [e_min, e_max]

Zero is represented by the all-zero exponent+mantissa code (for either sign),
sacrificing the two +/-2^e_min*(1.0) slots — this keeps ``af_encode`` /
``af_decode`` exactly invertible, which matters because the eNVM fault
injection (paper Table III) flips bits of the *stored codes*.

``af_quantize`` == ``af_decode(af_encode(x))`` (property-tested).  The Pallas
kernels in ``repro.kernels.adaptivfloat_k`` implement the same math tile-wise.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AFFormat:
    n_bits: int = 8
    n_exp: int = 3

    @property
    def n_mant(self) -> int:
        return self.n_bits - 1 - self.n_exp

    @property
    def n_levels_exp(self) -> int:
        return 2 ** self.n_exp

    def __post_init__(self):
        assert 1 <= self.n_exp <= 5
        assert self.n_bits - 1 - self.n_exp >= 0, "need >=0 mantissa bits"
        assert self.n_bits <= 8, "codes stored as uint8"


def _exp_bias_from_amax(amax: jnp.ndarray, fmt: AFFormat) -> jnp.ndarray:
    """e_min (the adaptive bias) chosen so the top binade covers amax.

    Clamped to +/-120 so exp2(e_min) never underflows to 0 (an all-zero
    tensor would otherwise produce 0/0 = NaN in the mantissa division)."""
    amax = jnp.maximum(amax.astype(jnp.float32), 1e-30)
    e_max = jnp.floor(jnp.log2(amax))
    bias = e_max - (fmt.n_levels_exp - 1)
    return jnp.clip(bias, -120.0, 120.0).astype(jnp.int32)


def af_quantize(
    x: jnp.ndarray,
    fmt: AFFormat = AFFormat(),
    amax: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Quantize-dequantize x to the AdaptivFloat grid (per-tensor bias).

    `amax` may be supplied (e.g. calibrated activation stats); defaults to the
    tensor's own max-abs (the paper's post-finetuning weight quantization).
    """
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    if amax is None:
        amax = jnp.max(jnp.abs(xf))
    e_min = _exp_bias_from_amax(amax, fmt)
    e_max = e_min + fmt.n_levels_exp - 1
    two_pow_emin = jnp.exp2(e_min.astype(jnp.float32))

    a = jnp.abs(xf)
    sign = jnp.sign(xf)
    # exponent of each element, clamped to representable binades
    safe_a = jnp.maximum(a, 1e-38)
    e = jnp.clip(jnp.floor(jnp.log2(safe_a)), e_min.astype(jnp.float32), e_max.astype(jnp.float32))
    scale = jnp.exp2(e)
    n_mant_scale = float(2 ** fmt.n_mant)
    # round mantissa; rounding to 2.0 naturally carries into the next binade
    mant = jnp.round(a / scale * n_mant_scale) / n_mant_scale
    val = mant * scale
    # clamp to the largest representable magnitude
    max_val = (2.0 - 1.0 / n_mant_scale) * jnp.exp2(e_max.astype(jnp.float32))
    val = jnp.minimum(val, max_val)
    # smallest representable magnitude is 2^e_min*(1 + 1/2^n_mant) because the
    # all-zero code is reserved for 0: round-to-nearest between 0 and min_pos
    min_pos = two_pow_emin * (1.0 + 1.0 / n_mant_scale)
    val = jnp.where(a < 0.5 * min_pos, 0.0, jnp.maximum(val, min_pos))
    return (sign * val).astype(orig_dtype)


def af_encode(
    x: jnp.ndarray,
    fmt: AFFormat = AFFormat(),
    amax: Optional[jnp.ndarray] = None,
):
    """Encode to (codes: uint8, e_min: int32 scalar). Bit layout [s|e|m]."""
    xf = x.astype(jnp.float32)
    if amax is None:
        amax = jnp.max(jnp.abs(xf))
    e_min = _exp_bias_from_amax(amax, fmt)
    e_max = e_min + fmt.n_levels_exp - 1
    n_mant_scale = float(2 ** fmt.n_mant)

    a = jnp.abs(xf)
    sign = (xf < 0).astype(jnp.uint8)
    safe_a = jnp.maximum(a, 1e-38)
    e = jnp.clip(jnp.floor(jnp.log2(safe_a)), e_min.astype(jnp.float32), e_max.astype(jnp.float32))
    scale = jnp.exp2(e)
    # significand = round(a/scale * 2^nm) in [2^nm .. 2^(nm+1)] for normals
    sig = jnp.round(a / scale * n_mant_scale)
    m = sig - n_mant_scale                      # mantissa field, may hit 2^nm (carry)
    carry = m >= n_mant_scale
    e = jnp.where(carry, e + 1, e)
    m = jnp.where(carry, 0.0, m)
    # saturate anything past the top representable value
    max_val = (2.0 - 1.0 / n_mant_scale) * jnp.exp2(e_max.astype(jnp.float32))
    sat = jnp.logical_or(a > max_val, e > e_max.astype(jnp.float32))
    e = jnp.where(sat, e_max.astype(jnp.float32), e)
    m = jnp.where(sat, n_mant_scale - 1, m)
    m = jnp.clip(m, 0.0, n_mant_scale - 1)      # sub-min garbage overridden below

    e_field = (e - e_min.astype(jnp.float32)).astype(jnp.uint8)
    m_field = m.astype(jnp.uint8)
    code = (sign << (fmt.n_bits - 1)) | (e_field << fmt.n_mant) | m_field
    # zero: |x| below half of min positive -> all-zero exp+mant (keep sign bit 0)
    min_pos = jnp.exp2(e_min.astype(jnp.float32)) * (1.0 + 1.0 / n_mant_scale)
    is_zero = a < 0.5 * min_pos
    # sub-min values round up to min_pos (code e=0, m=1)
    sub = jnp.logical_and(~is_zero, a < min_pos)
    code = jnp.where(sub, (sign << (fmt.n_bits - 1)) | jnp.uint8(1), code)
    code = jnp.where(is_zero, jnp.uint8(0), code)
    return code.astype(jnp.uint8), e_min


def af_decode(codes: jnp.ndarray, e_min: jnp.ndarray, fmt: AFFormat = AFFormat(), dtype=jnp.float32):
    """Decode uint8 codes back to floats."""
    codes = codes.astype(jnp.uint32)
    sign_bit = (codes >> (fmt.n_bits - 1)) & 1
    e_field = (codes >> fmt.n_mant) & (fmt.n_levels_exp - 1)
    m_field = codes & ((1 << fmt.n_mant) - 1)
    n_mant_scale = float(2 ** fmt.n_mant)
    e = e_field.astype(jnp.float32) + e_min.astype(jnp.float32)
    val = jnp.exp2(e) * (1.0 + m_field.astype(jnp.float32) / n_mant_scale)
    is_zero = (e_field == 0) & (m_field == 0)
    val = jnp.where(is_zero, 0.0, val)
    val = jnp.where(sign_bit == 1, -val, val)
    return val.astype(dtype)


def af_encode_static(x: jnp.ndarray, e_min: int, fmt: AFFormat = AFFormat()):
    """Encode with a STATIC exponent bias (no per-tensor scale storage) —
    used for the AF8 KV cache where per-written-column dynamic biases would
    need a scale plane; dynamic range is fixed by config instead."""
    amax = jnp.asarray(2.0 ** (e_min + fmt.n_levels_exp - 1), jnp.float32)
    codes, _ = af_encode(x, fmt, amax=amax * 1.5)  # amax inside top binade
    return codes


def af_decode_static(codes: jnp.ndarray, e_min: int, fmt: AFFormat = AFFormat(), dtype=jnp.float32):
    return af_decode(codes, jnp.asarray(e_min, jnp.int32), fmt, dtype)


def fake_quant(
    x: jnp.ndarray, fmt: AFFormat, enabled: bool = True,
    amax: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Straight-through fake-quant for activations (QAT / eval emulation);
    ``amax`` as in ``af_quantize``."""
    if not enabled:
        return x
    q = af_quantize(x, fmt, amax=amax)
    # straight-through estimator: identity gradient
    return x + jax.lax.stop_gradient(q - x)


def quantize_pytree(params: Any, fmt: AFFormat = AFFormat(), predicate=None) -> Any:
    """Quantize-dequantize every float leaf of a pytree (per-leaf bias).

    `predicate(path, leaf) -> bool` can exclude leaves (e.g. layernorm params).
    """
    flat = jax.tree_util.tree_flatten_with_path(params)
    leaves, treedef = flat
    out = []
    for path, leaf in leaves:
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            if predicate is None or predicate(path, leaf):
                leaf = af_quantize(leaf, fmt)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, [l for l in out])


def encode_pytree(params: Any, fmt: AFFormat = AFFormat()):
    """Encode every float leaf to (codes, e_min) — the on-eNVM storage form."""
    return jax.tree_util.tree_map(
        lambda l: af_encode(l, fmt)
        if hasattr(l, "dtype") and jnp.issubdtype(l.dtype, jnp.floating)
        else l,
        params,
        is_leaf=lambda l: hasattr(l, "dtype"),
    )
