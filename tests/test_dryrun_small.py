"""Sharding + dry-run machinery on a small forced-multi-device mesh.

These run in SUBPROCESSES because the device count must be set before jax
initializes (the main test process keeps the single real CPU device).

Marked ``multidevice``; forcing host devices in the subprocess works on
single-device hosts.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidevice

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}\nstdout:\n{r.stdout[-1000:]}"
    return r.stdout


def test_param_sharding_rules():
    out = _run("""
        import jax, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import get_smoke_config
        from repro.models.model import build_model
        from repro.sharding.rules import param_shardings, rules_for
        import dataclasses

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        cfg = dataclasses.replace(get_smoke_config("qwen1_5_110b"),
                                  d_ff=128, n_kv_heads=4)
        model = build_model(cfg)
        abs_p = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
        sh = param_shardings(abs_p, mesh, rules_for(cfg, mesh))
        # stacked attn wq: [L, d, H*hd] -> (None, None, model)
        assert sh["layers"]["attn"]["wq"].spec == P(None, None, "model"), sh["layers"]["attn"]["wq"].spec
        # mlp down: [L, ff, d] -> (None, model, None)
        assert sh["layers"]["mlp"]["w_down"].spec == P(None, "model", None)
        # embedding: vocab sharded
        assert sh["embed"]["tok"].spec == P("model", None)
        # norm: replicated
        assert sh["layers"]["norm1"]["scale"].spec == P()
        print("RULES_OK")
    """)
    assert "RULES_OK" in out


def test_kv_indivisible_falls_back_replicated():
    out = _run("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import get_smoke_config
        from repro.models.model import build_model
        from repro.sharding.rules import param_shardings, rules_for
        import dataclasses

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        # kv out dim = 3 heads * 6 = 18, not divisible by 4 -> replicated
        # (wq = 12*6 = 72 stays sharded)
        cfg = dataclasses.replace(get_smoke_config("qwen1_5_110b"),
                                  n_heads=12, n_kv_heads=3, head_dim=6, d_model=72, d_ff=128)
        model = build_model(cfg)
        abs_p = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
        sh = param_shardings(abs_p, mesh, rules_for(cfg, mesh))
        assert sh["layers"]["attn"]["wk"].spec == P(None, None, None)
        assert sh["layers"]["attn"]["wq"].spec == P(None, None, "model")
        print("FALLBACK_OK")
    """)
    assert "FALLBACK_OK" in out


@pytest.mark.parametrize("arch", ["deepseek_7b", "qwen3_moe_235b", "rwkv6_7b"])
def test_smoke_cell_compiles_on_mesh(arch):
    """build_cell (smoke-sized config) lowers + compiles on a (2,2) mesh."""
    out = _run(f"""
        import jax, dataclasses
        import jax.numpy as jnp
        from repro.configs.base import get_smoke_config, ShapeConfig
        from repro.launch import dryrun
        from repro.launch.mesh import make_debug_mesh

        cfg = get_smoke_config("{arch}")
        shape = ShapeConfig("tiny_train", 64, 8, "train")
        mesh = make_debug_mesh(2, 2)
        fn, args, params_abs, n_tokens = dryrun.build_cell(cfg, shape, mesh, microbatches=2)
        with mesh:
            compiled = fn.lower(*args).compile()
        print("COMPILED", compiled.cost_analysis() is not None)
    """, devices=4)
    assert "COMPILED" in out


def test_decode_cell_compiles_on_mesh():
    out = _run("""
        import jax
        from repro.configs.base import get_smoke_config, ShapeConfig
        from repro.launch import dryrun
        from repro.launch.mesh import make_debug_mesh

        cfg = get_smoke_config("zamba2_1p2b")
        shape = ShapeConfig("tiny_decode", 128, 8, "decode")
        mesh = make_debug_mesh(2, 2)
        fn, args, params_abs, n_tokens = dryrun.build_cell(cfg, shape, mesh)
        with mesh:
            compiled = fn.lower(*args).compile()
        from repro.hwmodel.hlo_analysis import analyze
        res = analyze(compiled.as_text())
        assert res.flops > 0
        print("DECODE_OK")
    """, devices=4)
    assert "DECODE_OK" in out


def test_multipod_mesh_shape():
    out = _run("""
        from repro.launch.mesh import make_production_mesh
        m = make_production_mesh(multi_pod=True)
        assert m.devices.shape == (2, 16, 16)
        assert m.axis_names == ("pod", "data", "model")
        m1 = make_production_mesh()
        assert m1.devices.shape == (16, 16)
        print("MESH_OK")
    """, devices=512)
    assert "MESH_OK" in out


def test_zero1_shards_optimizer():
    out = _run("""
        import jax
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.sharding.zero1 import zero1_param_sharding
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        # param sharded on dim1 by model; zero1 adds data on dim0
        spec = zero1_param_sharding(P(None, "model"), (128, 64), mesh)
        assert spec == P("data", "model"), spec
        # indivisible dim stays unsharded
        spec2 = zero1_param_sharding(P(None,), (7,), mesh)
        assert spec2 == P(None)
        print("ZERO1_OK")
    """, devices=8)
    assert "ZERO1_OK" in out
