"""Pipeline parallelism: GPipe schedule over a `stage` mesh axis equals the
sequential layer stack (subprocess: needs forced multi-device)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidevice

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_pipeline_matches_sequential():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.training.pipeline import pipeline_forward

        mesh = jax.make_mesh((4,), ("stage",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        n_stages, n_micro, mb, d = 4, 8, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        # one linear+tanh layer per stage
        ws = jax.random.normal(ks[0], (n_stages, d, d)) / np.sqrt(d)
        x = jax.random.normal(ks[1], (n_micro, mb, d))

        layer_fn = lambda w, h: jnp.tanh(h @ w)
        out = pipeline_forward(layer_fn, ws, x, mesh)

        # sequential reference
        ref = x
        for s in range(n_stages):
            ref = jax.vmap(lambda h: layer_fn(ws[s], h))(ref)
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-5, err
        print("PIPELINE_OK", err)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PIPELINE_OK" in r.stdout
