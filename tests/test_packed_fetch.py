"""The classifier's fused step crosses from device to host once.

``ClassifierServer``'s jitted step packs the off-ramp logits, the entropy
and the retire mask into one ``[lanes, C + 2]`` array, and ``lanes_step``
reads it with one blocking transfer.  Here, on the CPU at smoke width: what
``lanes_step`` hands back equals, bit for bit, the three outputs of the
``step_math`` step it wraps on the same inputs (XLA and Pallas, unsharded
and on 1- and 2-replica meshes); a drain answers exactly as a server that
reads the three outputs apart; and ``telemetry()["host_reads"]`` counts one
read per fused step.
"""
import dataclasses
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.jax_compat import make_auto_mesh
from repro.configs.base import get_smoke_config
from repro.data.synthetic import SyntheticCLS
from repro.models.model import build_model
from repro.serving import step_math
from repro.serving.engine import ClassifierServer, Request

HERE = os.path.dirname(__file__)
LENGTHS = (10, 16, 24, 32, 12, 30, 7, 20)


def _albert_model(threshold=0.5):
    cfg = get_smoke_config("albert_edgebert")
    cfg = dataclasses.replace(cfg, dtype="float32", remat_policy="none")
    cfg = cfg.with_edgebert(
        early_exit=dataclasses.replace(cfg.edgebert.early_exit, entropy_threshold=threshold)
    )
    model = build_model(cfg)
    return model, model.init_params(jax.random.PRNGKey(0)), cfg


def _submit_all(srv, cfg):
    batch = SyntheticCLS(cfg.vocab_size, 32, len(LENGTHS), num_classes=3, seed=0).batch(0)
    for i, n in enumerate(LENGTHS):
        srv.submit(Request(uid=i, tokens=batch["tokens"][i][:n]))


def _reference_step(srv):
    """The ``step_math`` step that ``srv``'s jitted step wraps, jitted alone
    at the configuration's precision and returning its four outputs."""
    kw = dict(use_pallas=srv.use_pallas, block_masks=srv._block_masks)
    if srv._mesh is None:
        fn = functools.partial(step_math.classifier_fused_step, srv.model, **kw)
    else:
        fn = functools.partial(step_math.sharded_classifier_fused_step, srv.model,
                               mesh=srv._mesh, **kw)
    return step_math.jit_at_config_precision(srv.cfg, fn)


def check_step_parity(replicas=1, use_pallas=False, mesh=None):
    """Drain a server, and check every ``lanes_step`` answer against the
    reference step on the inputs that step was given.  Returns the number of
    fused steps checked."""
    model, params, cfg = _albert_model()
    srv = ClassifierServer(model, params, batch_lanes=2, buckets=(16, 32),
                           use_pallas=use_pallas, replicas=replicas, mesh=mesh)
    ref, step, lanes_step = _reference_step(srv), srv._step, srv.lanes_step
    seen = []

    def recording_step(*args):
        seen.append(args)
        return step(*args)

    def checked_lanes_step(bucket, active):
        out = lanes_step(bucket, active)
        _, lg, ent, retire = ref(*seen[-1])
        for got, want in zip(out[:3], (lg, ent, retire)):
            want = np.asarray(want)
            assert got.dtype == want.dtype and got.shape == want.shape
            assert np.array_equal(got, want), (bucket, got, want)
        return out

    srv._step, srv.lanes_step = recording_step, checked_lanes_step
    _submit_all(srv, cfg)
    tel = srv.run()
    assert len(seen) == tel["dense_steps"] > 0
    return len(seen)


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("sharded", [False, True], ids=["unsharded", "mesh1"])
def test_lanes_step_equals_the_step_math_outputs(use_pallas, sharded):
    mesh = make_auto_mesh((1,), ("data",)) if sharded else None
    check_step_parity(use_pallas=use_pallas, mesh=mesh)


@pytest.mark.multidevice
def test_lanes_step_equals_the_step_math_outputs_on_two_replicas():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(HERE, "..", "src"), HERE])
    code = textwrap.dedent("""
        import test_packed_fetch as t
        for use_pallas in (False, True):
            assert t.check_step_parity(replicas=2, use_pallas=use_pallas) > 0
        print("ok")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=900, env=env)
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-3000:]


class ThreeReads(ClassifierServer):
    """The serving step read as three separate transfers of the unpacked
    ``step_math`` outputs: the answers one read must reproduce."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._unpacked = _reference_step(self)

    def lanes_step(self, bucket, active):
        st = self._bstate[bucket]
        h, lg, ent, retire = self._unpacked(
            self.params, st["h"], jnp.asarray(active), jnp.asarray(st["len"]),
            jnp.float32(self.threshold),
        )
        st["h"] = h
        st["out"] = (np.asarray(lg), np.asarray(ent), np.asarray(retire), None)
        return st["out"]


@pytest.mark.parametrize("use_pallas", [False, True])
def test_drain_answers_as_three_reads_do(use_pallas):
    model, params, cfg = _albert_model()
    servers = [cls(model, params, batch_lanes=2, buckets=(16, 32), use_pallas=use_pallas)
               for cls in (ClassifierServer, ThreeReads)]
    for srv in servers:
        _submit_all(srv, cfg)
        srv.run()
    one, three = servers
    assert set(one.done) == set(three.done) == set(range(len(LENGTHS)))
    for uid in one.done:
        assert one.done[uid].exit_layer == three.done[uid].exit_layer, uid
        assert np.array_equal(one.done[uid].result, three.done[uid].result), uid
        assert one.done[uid].entropy_trace == three.done[uid].entropy_trace, uid


def test_one_host_read_per_fused_step():
    model, params, cfg = _albert_model()
    srv = ClassifierServer(model, params, batch_lanes=2, buckets=(16, 32))
    assert srv.telemetry()["host_reads"] == 0
    for _ in range(2):                       # a second drain adds reads, not traces
        _submit_all(srv, cfg)
        tel = srv.run()
        assert tel["host_reads"] == tel["dense_steps"] > 0
        assert tel["step_traces_per_bucket"] == {16: 1, 32: 1}
