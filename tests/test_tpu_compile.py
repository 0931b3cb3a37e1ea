"""Compile guard: the Pallas kernels and the full-width classifier fused step
compile for a TPU v5e, at ALBERT-base widths, with no chip attached.

Nothing runs: each test lowers and compiles for a *described* ``v5e:2x2``
topology, so Mosaic and XLA:TPU refuse here what they would refuse on the
chip (unaligned tiles, unsupported vector layouts, too much VMEM).  The
topology is described inside a fixture — never at import — because only one
process at a time may load the TPU library.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core.adaptivfloat import AFFormat
from repro.kernels import adaptivfloat_k, block_sparse, dispatch, layernorm
from repro.kernels import softmax_entropy, span_attention
from repro.models.model import build_model
from repro.serving import step_math

D, H, DH, FF, LANES = 768, 12, 64, 3072, 8      # ALBERT-base, 8 serving lanes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles written to the persistent cache could not be read back
    # without a chip: keep it off for this module
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _assert_named(compiled, *names):
    """Each kernel is an instruction of its own name in the compiled HLO
    (``%bs_mlp_up.1 = ... custom-call``), the name the device trace shows."""
    text = compiled.as_text()
    for name in names:
        assert re.search(rf"%{name}(\.\d+)? = [^\n]*tpu_custom_call", text), name


@pytest.mark.parametrize("bucket", [16, 128])
def test_span_attention(one_chip, bucket):
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    qkv = s((LANES * H, bucket, DH))
    ints = s((LANES * H,), jnp.int32)
    _assert_kernel(_compile(
        lambda q, k, v, spans, kv_lens: span_attention.span_attention(
            q, k, v, spans, bucket, causal=False, interpret=False, kv_lens=kv_lens
        ),
        qkv, qkv, qkv, ints, ints,
    ))


@pytest.mark.parametrize("bucket", [16, 128])
def test_dense_attention_lane_vmap(one_chip, monkeypatch, bucket):
    """The served form: one span-kernel call per lane under the fused
    step's lane vmap, each lane's length riding in by scalar prefetch."""
    monkeypatch.setattr(dispatch, "interpret_mode", lambda: False)
    qkv = jax.ShapeDtypeStruct((LANES, bucket, H, DH), jnp.float32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((LANES,), jnp.int32, sharding=one_chip)

    def lanes(q, k, v, n):
        return jax.vmap(lambda a, b, c, m: dispatch.dense_attention(
            a[None], b[None], c[None], causal=False, kv_len=m
        )[0])(q, k, v, n)

    text = _compile(lanes, qkv, qkv, qkv, lengths).as_text()
    # the lane vmap loops the kernel over lanes, so the instruction itself is
    # a ``closed_call``; its scope path still names it
    assert re.search(r'tpu_custom_call[^\n]*op_name="[^"]*/span_attn/', text)


def test_layernorm(one_chip):
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    _assert_kernel(_compile(
        lambda x, g, b: layernorm.layernorm(x, g, b, interpret=False),
        s((LANES * 128, D)), s((D,)), s((D,)),
    ))


def test_softmax_entropy(one_chip):
    x = jax.ShapeDtypeStruct((LANES, 3), jnp.float32, sharding=one_chip)
    _assert_kernel(_compile(
        lambda lg: softmax_entropy.softmax_entropy(lg, jnp.ones_like(lg), interpret=False),
        x,
    ))


def test_af_quantize(one_chip):
    x = jax.ShapeDtypeStruct((LANES * 128, D), jnp.float32, sharding=one_chip)
    _assert_kernel(_compile(
        lambda a: adaptivfloat_k.quantize(a, fmt=AFFormat(8, 3), interpret=False), x
    ))


@pytest.mark.parametrize("name,shape", [("w_up", (D, FF)), ("w_down", (FF, D))])
def test_block_sparse_at_derived_tiles(one_chip, name, shape):
    w = np.ones(shape, np.float32)
    w[:128, :128] = 0.0                              # one pruned tile
    occ, bk, bn = dispatch.mlp_block_masks({name: w})[name]
    x = jax.ShapeDtypeStruct((LANES * 128, shape[0]), jnp.float32, sharding=one_chip)
    ws = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    _assert_kernel(_compile(
        lambda a, b: block_sparse.block_sparse_matmul(
            a, b, occ, bk=bk, bn=bn, interpret=False
        ),
        x, ws,
    ))


def test_classifier_fused_step_full_width(one_chip, monkeypatch):
    """The served step at ALBERT-base widths (8 lanes x bucket 128), Pallas
    routing on, the MLP pruned at whole tiles; the CPU backend would
    interpret the kernels, so the test steers ``dispatch`` to emit Mosaic."""
    monkeypatch.setattr(dispatch, "interpret_mode", lambda: False)
    tiles = np.random.default_rng(0).random((D // 128, FF // 128)) < 0.5
    w_up = np.kron(tiles, np.ones((128, 128), np.float32))
    masks = dispatch.mlp_block_masks({"w_up": w_up, "w_down": w_up.T})
    cfg = dataclasses.replace(
        get_config("albert_edgebert"), dtype="float32", remat_policy="none"
    )
    model = build_model(cfg)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
    )
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    step = step_math.jit_at_config_precision(
        cfg,
        lambda p, h, active, lengths, thr: step_math.classifier_fused_step(
            model, p, h, active, lengths, thr, use_pallas=True, block_masks=masks
        ),
    )
    compiled = step.lower(
        params, s((LANES, 128, D), jnp.float32), s((LANES,), jnp.bool_),
        s((LANES,), jnp.int32), s((), jnp.float32),
    ).compile()
    # the trained soft spans keep attention off the span kernel
    _assert_named(compiled, "bs_mlp_up", "bs_mlp_down", "layernorm", "af_quant",
                  "offramp_entropy")


def test_sharded_fused_step_four_chips(topo, one_chip, monkeypatch):
    """The 4-replica step on a mesh over the described host's four chips:
    lane state enters and leaves with the same sharding (so a second call
    reuses the compile) and no collective crosses replicas."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    monkeypatch.setattr(dispatch, "interpret_mode", lambda: False)
    mesh = Mesh(np.array(topo.devices), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
    rep, lanes = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    cfg = dataclasses.replace(
        get_config("albert_edgebert"), dtype="float32", remat_policy="none"
    )
    model = build_model(cfg)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)),
    )
    n = len(topo.devices) * LANES
    step = step_math.jit_at_config_precision(
        cfg,
        lambda p, h, active, lengths, thr: step_math.sharded_classifier_fused_step(
            model, p, h, active, lengths, thr, mesh=mesh, use_pallas=True
        ),
    )
    compiled = step.lower(
        params,
        jax.ShapeDtypeStruct((n, 128, D), jnp.float32, sharding=lanes),
        jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=lanes),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=lanes),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
    ).compile()
    _assert_kernel(compiled)
    assert compiled.input_shardings[0][1] == compiled.output_shardings[0] == lanes
    text = compiled.as_text()
    assert not any(c in text for c in ("all-reduce", "all-gather", "all-to-all",
                                       "collective-permute"))
