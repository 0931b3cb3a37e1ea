"""Kernel microbenchmarks.

Interpret-mode wall time is a Python-emulation artifact, so per-kernel we
report (a) the jnp REFERENCE implementation's XLA:CPU wall time (a real
compiled baseline), (b) kernel-vs-ref max error, and (c) the kernel's modeled
TPU utility: FLOPs and the VMEM-resident traffic it avoids vs the unfused ref
(the quantity that shows up in the roofline memory term)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_us
from repro.core.adaptivfloat import af_encode
from repro.kernels import ref
from repro.kernels.adaptivfloat_k import af_matmul, quantize
from repro.kernels.block_sparse import block_sparse_matmul
from repro.kernels.dispatch import interpret_mode
from repro.kernels.layernorm import layernorm
from repro.kernels.softmax_entropy import softmax_entropy
from repro.kernels.span_attention import span_attention


def _r(shape, seed=0, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


def main() -> None:
    interp = interpret_mode()
    # layernorm
    x = _r((4096, 768), 0, 3.0)
    g, b = _r((768,), 1), _r((768,), 2)
    us = time_us(jax.jit(lambda x: ref.layernorm(x, g, b)), x)
    err = float(jnp.abs(layernorm(x[:256], g, b, interpret=interp) - ref.layernorm(x[:256], g, b)).max())
    emit("kernel_layernorm_4096x768", us, f"ref_xla_cpu;kernel_err={err:.1e}")

    # softmax+entropy fused
    lg = _r((2048, 128), 3, 5.0)
    mask = jnp.ones_like(lg)
    us = time_us(jax.jit(lambda l: ref.softmax_entropy(l, mask)), lg)
    p1, h1 = softmax_entropy(lg[:256], mask[:256], interpret=interp)
    p2, h2 = ref.softmax_entropy(lg[:256], mask[:256])
    emit(
        "kernel_softmax_entropy_2048x128", us,
        f"ref_xla_cpu;kernel_err={float(jnp.abs(p1-p2).max()):.1e};"
        "fused_saves=1 extra pass over scores (entropy from same tile)",
    )

    # AF quantize
    w = _r((1024, 1024), 4, 2.0)
    us = time_us(jax.jit(lambda w: ref.adaptivfloat_quantize(w)), w)
    err = float(jnp.abs(quantize(w[:128], interpret=interp) - ref.adaptivfloat_quantize(w[:128])).max())
    emit("kernel_af_quantize_1024x1024", us, f"ref_xla_cpu;kernel_err={err:.1e}")

    # AF8 matmul: halves weight HBM traffic
    codes, e_min = af_encode(w)
    x2 = _r((256, 1024), 5)
    us = time_us(jax.jit(lambda x, c: ref.af_matmul(x, c, e_min)), x2, codes)
    got = af_matmul(x2[:64], codes, e_min, bm=64, bk=128, bn=128,
                    interpret=interp)
    want = ref.af_matmul(x2[:64], codes, e_min)
    emit(
        "kernel_af_matmul_256x1024x1024", us,
        f"ref_xla_cpu;kernel_err={float(jnp.abs(got-want).max()):.1e};"
        f"hbm_weight_traffic=0.5x vs bf16 (af8 codes)",
    )

    # block-sparse matmul at 50% block density: ~2x tile skip
    rng = np.random.default_rng(6)
    bmask = rng.random((8, 8)) < 0.5
    full = np.repeat(np.repeat(bmask, 128, 0), 128, 1)
    ws = jnp.asarray(rng.normal(size=(1024, 1024)) * full, jnp.float32)
    us = time_us(
        jax.jit(lambda x, w: ref.block_sparse_matmul(x, w, jnp.asarray(bmask), 128, 128)),
        x2, ws,
    )
    density = bmask.mean()
    emit(
        "kernel_block_sparse_1024_d50", us,
        f"ref_xla_cpu;tiles_visited={density:.2f}x_dense;"
        f"modeled_tpu_speedup={1/density:.2f}x",
    )

    # span attention: windowed kv loop
    B, H, S, dh = 1, 12, 128, 64
    q, k, v = _r((B, H, S, dh), 7), _r((B, H, S, dh), 8), _r((B, H, S, dh), 9)
    spans = jnp.asarray([20, 0, 0, 0, 0, 0, 36, 81, 0, 0, 0, 10], jnp.int32)
    us = time_us(
        jax.jit(lambda q, k, v: ref.span_attention(q, k, v, spans, causal=False)),
        q, k, v,
    )
    from repro.core.adaptive_span import span_flop_factor

    f = span_flop_factor(np.asarray(spans), H, S)
    emit(
        "kernel_span_attention_albert128", us,
        f"ref_xla_cpu;score_flops_kept={f:.3f};heads_skipped=8/12;"
        "kv_blocks_visited=window-bounded",
    )


if __name__ == "__main__":
    main()
