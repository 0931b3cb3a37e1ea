"""Profiler spans and wall stamps of the served classifier path.

The scheduler and the engine mark each phase of a fused step with a
``jax.profiler`` span (inert unless a trace is active), so a trace of a
serving loop shows where the host's time goes on the device trace's clock;
``Request`` carries ``queued_at`` / ``admitted_at`` / ``retired_at`` on
``time.perf_counter``.  Here, on the CPU at smoke width: every span appears,
nested as the serving step nests them; the stamps are ordered and survive
preemption; and tracing changes neither the answers nor the jit traces.
"""
import collections
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.data.synthetic import SyntheticCLS
from repro.hwmodel.edgebert_accel import albert_layer_stats
from repro.models.model import build_model
from repro.serving.dvfs import (
    BatchedDVFSArbiter,
    LatencyAwareDVFSController,
    no_early_exit_baseline,
)
from repro.serving.engine import ClassifierServer, Request

# span -> the span it runs directly inside (None: outermost)
PARENT = {
    "sched.step": None,
    "sched.choose": "sched.step",
    "engine.bucket_begin": "sched.step",
    "sched.refill": "sched.step",
    "engine.lane_load": "sched.refill",
    "dvfs.admit": "engine.lane_load",
    "engine.lanes_step": "sched.step",
    "dvfs.step": "engine.lanes_step",
    "engine.dispatch": "engine.lanes_step",
    "engine.fetch": "engine.lanes_step",
    "sched.retire": "sched.step",
    "dvfs.retire": "sched.retire",
    "engine.bucket_end": "sched.step",
}
Span = collections.namedtuple("Span", "name start end stats parent")


def _server():
    cfg = get_smoke_config("albert_edgebert")
    cfg = dataclasses.replace(cfg, dtype="float32", remat_policy="none")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    stats = albert_layer_stats(seq_len=32)
    stats.n_layers = cfg.n_layers
    ctrl = LatencyAwareDVFSController(stats, no_early_exit_baseline(stats)["latency_s"] * 2.0)
    srv = ClassifierServer(model, params, batch_lanes=2, buckets=(16, 32),
                           arbiter=BatchedDVFSArbiter(ctrl), preempt=True)
    return srv, cfg, ctrl


def _serve():
    """Three short requests, two steps, then an explicit SLO that preempts
    one of them and two longer requests in a second bucket; drained.
    Returns the requests, each one's ``admitted_at`` before the preemption,
    and the server's telemetry."""
    srv, cfg, ctrl = _server()
    toks = SyntheticCLS(cfg.vocab_size, 32, 8, num_classes=3, seed=0).batch(0)["tokens"]
    reqs = [Request(uid=i, tokens=toks[i][:12]) for i in range(3)]
    for r in reqs:
        srv.submit(r)
    srv.step()
    srv.step()
    first_admitted = {r.uid: r.admitted_at for r in reqs}
    t_layer = ctrl.cycles_for_seq_len(16) / ctrl.max_op.freq_hz
    late = [Request(uid=99, tokens=toks[4][:12], deadline_s=t_layer * cfg.n_layers * 8)]
    late += [Request(uid=10 + i, tokens=toks[5 + i][:24]) for i in range(2)]
    for r in late:
        srv.submit(r)
    while srv.step() is not None:
        pass
    return reqs + late, first_admitted, srv.telemetry()


def _program_spans(log_dir) -> list:
    """The program's spans in the newest trace under ``log_dir``, each with
    the name of the span it runs directly inside on its thread."""
    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = sorted(((e.start_ns, -e.end_ns, e) for e in line.events
                             if e.name in PARENT), key=lambda t: t[:2])
            stack = []
            for _, _, e in events:
                while stack and stack[-1].end <= e.start_ns:
                    stack.pop()
                span = Span(e.name, e.start_ns, e.end_ns, dict(e.stats),
                            stack[-1].name if stack else None)
                out.append(span)
                stack.append(span)
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The same scenario twice: under the profiler, then without it."""
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        traced = _serve()
    finally:
        jax.profiler.stop_trace()
    return {"traced": traced, "plain": _serve(), "spans": _program_spans(log_dir)}


def test_every_span_nested_as_the_step_nests_it(served):
    spans = served["spans"]
    assert {s.name for s in spans} == set(PARENT)
    for s in spans:
        assert s.parent == PARENT[s.name], s
    steps = [s for s in spans if s.name == "sched.step"]
    # one step span per fused step, plus the final call that found no work
    assert len(steps) == served["traced"][2]["dense_steps"] + 1
    assert sorted(s.stats["step_num"] for s in steps)[-1] == served["traced"][2]["dense_steps"]


def test_lane_load_carries_request_bucket_and_lane(served):
    loads = [s for s in served["spans"] if s.name == "engine.lane_load"]
    reqs = {r.uid: r for r in served["traced"][0]}
    assert loads and len(loads) == len(reqs)          # one load each; a restore is no load
    for s in loads:
        assert set(s.stats) == {"uid", "bucket", "lane"}
        assert s.stats["bucket"] == reqs[s.stats["uid"]].bucket
        assert 0 <= s.stats["lane"] < 2
    steps = [s for s in served["spans"] if s.name == "engine.lanes_step"]
    assert all(1 <= s.stats["n_active"] <= 2 for s in steps)


def test_stamps_ordered_and_first_admission_kept(served):
    reqs, first_admitted, telemetry = served["traced"]
    assert telemetry["preemptions"] >= 1
    for r in reqs:
        assert r.queued_at <= r.admitted_at <= r.retired_at, r.uid
    preempted = [r for r in reqs if r.preempted]
    assert preempted, "the scenario must preempt a lane"
    for r in preempted:
        assert r.admitted_at == first_admitted[r.uid]


def test_tracing_changes_no_answer_and_no_trace(served):
    (on, _, t_on), (off, _, t_off) = served["traced"], served["plain"]
    for a, b in zip(on, off):
        assert a.uid == b.uid and a.exit_layer == b.exit_layer
        assert np.array_equal(a.result, b.result)
    for key in ("step_traces", "embed_traces", "insert_traces"):
        assert t_on[key] == t_off[key] == 2, key     # one per bucket
    assert t_on["step_traces_per_bucket"] == {16: 1, 32: 1}
