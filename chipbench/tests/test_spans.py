"""The reduction of the program's spans, on synthetic and recorded traces,
and one traced run of ``spans.py`` on the CPU at smoke width."""
import io
import json
from contextlib import redirect_stdout

import jax
import pytest

from chipbench import devtrace, spans
from chipbench.tests import test_devtrace

S = spans.Span


def _step(t, name="sched.step"):
    """One serving step at ``t``: 10 ms, of which 2 choosing, 3 refilling
    (a 2-ms lane load in it), 4 in the engine's step (1 dispatching, 2.5
    fetching) and 1 retiring."""
    th = ("/host:CPU", 0)
    return [S(name, t, t + 0.010, th, {}), S("sched.choose", t, t + 0.002, th, {}),
            S("sched.refill", t + 0.002, t + 0.005, th, {}),
            S("engine.lane_load", t + 0.0025, t + 0.0045, th, {"uid": 1}),
            S("engine.lanes_step", t + 0.005, t + 0.009, th, {}),
            S("engine.dispatch", t + 0.005, t + 0.006, th, {}),
            S("engine.fetch", t + 0.0063, t + 0.0088, th, {}),
            S("sched.retire", t + 0.009, t + 0.010, th, {})]


def test_self_time_leaves_out_the_children():
    got = spans.reduce(_step(1.0), 0.0, 2.0)
    assert got["sched.step"]["total_s"] == pytest.approx(0.010)
    assert got["sched.step"]["self_s"] == pytest.approx(0.0)
    assert got["sched.refill"]["self_s"] == pytest.approx(0.001)
    assert got["engine.lanes_step"]["self_s"] == pytest.approx(0.0005)
    assert sum(v["self_s"] for v in got.values()) == pytest.approx(0.010)


def test_totals_count_the_window_only():
    # a step before the window, one inside, one cut by the window's end
    trace = _step(0.5) + _step(1.0) + _step(1.995)
    got = spans.reduce(trace, 0.9, 2.0)
    assert got["sched.step"]["count"] == 2
    assert got["sched.step"]["total_s"] == pytest.approx(0.010 + 0.005)
    assert got["sched.retire"]["count"] == 1


def test_idle_charged_to_the_innermost_span():
    trace = _step(1.0)
    idle = [(0.999, 1.0015), (1.003, 1.0055), (1.0095, 1.012)]
    got = spans.charge_innermost(idle, trace)
    assert got == pytest.approx({
        "none": 0.001 + 0.002, "sched.choose": 0.0015,
        "sched.refill": 0.0005, "engine.lane_load": 0.0015,
        "engine.dispatch": 0.0005, "sched.retire": 0.0005,
    })
    assert sum(got.values()) == pytest.approx(sum(e - s for s, e in idle))


def test_spans_on_two_threads_do_not_nest():
    a = S("sched.step", 0.0, 1.0, ("/host:CPU", 0), {})
    b = S("engine.fetch", 0.2, 0.3, ("/host:CPU", 1), {})
    assert spans.parents([a, b]) == [None, None]


def test_longest_steps_with_what_ran_inside():
    trace = _step(1.0) + [s._replace(start=s.start + 1, end=s.end + 1 + 0.004 * (i == 0))
                          for i, s in enumerate(_step(1.0))]
    (step, tree), (second, _) = spans.longest(trace, 0.0, 3.0)
    assert step.start == 2.0 and step.end == pytest.approx(2.014) and second.start == 1.0
    assert [(d, k.name) for d, k in tree] == [
        (1, "sched.choose"), (1, "sched.refill"), (2, "engine.lane_load"),
        (1, "engine.lanes_step"), (2, "engine.dispatch"), (2, "engine.fetch"),
        (1, "sched.retire")]
    assert spans.longest(trace, 0.0, 3.0, n=1)[0][0] is step
    assert spans.longest(trace, 5.0, 6.0) == []


def test_host_numbers():
    table = spans.reduce(_step(1.0) + _step(1.1), 0.0, 2.0)
    got = spans.host_numbers(table, 2, [0.001, 0.003, 0.010], [0.020, 0.004])
    assert got == pytest.approx({"host_step_ms": 10.0, "dvfs_host_ms": 0.0,
                                 "step_wait_ms": 2.5, "lane_load_ms": 2.0,
                                 "queue_wait_ms": 3.0, "lane_time_ms": 12.0})
    assert "lane_time_ms" not in spans.host_numbers(table, 2, [0.001])
    assert spans.host_numbers({}, 0, []) == {}


def test_program_spans_leave_the_reduction_as_it_was():
    """The benchmark's own reduction reads the ``host`` list only: a trace
    that also holds the program's spans reduces to the same numbers, idle
    gaps included."""
    plain = test_devtrace._trace()
    both = dict(plain, program=_step(0.0) + _step(5.0))
    assert devtrace.reduce(both, "step_fn") == devtrace.reduce(plain, "step_fn")


def test_load_keeps_each_kind_apart(tmp_path):
    """On a recorded trace ``devtrace.load`` keeps the benchmark's spans and
    ``spans.load`` the program's, with their metadata."""
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with StepTraceAnnotation("sched.step", step_num=7):
            with TraceAnnotation("engine.lane_load", uid=3, bucket=32, lane=1):
                jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    assert {n for n, _, _ in devtrace.load(str(tmp_path))["host"]} == {"bench.window"}
    got = {s.name: s for s in spans.load(str(tmp_path))}
    assert set(got) == {"sched.step", "engine.lane_load"}
    assert got["engine.lane_load"].stats == {"uid": 3, "bucket": 32, "lane": 1}
    assert got["sched.step"].stats["step_num"] == 7
    assert spans.parents([got["sched.step"], got["engine.lane_load"]]) == [None, 0]


def test_traced_run_on_the_cpu(monkeypatch, tmp_path):
    """``spans.py`` end to end at smoke width: every host number comes out,
    the program's steps cover the benchmark's, and nothing is left patched."""
    from repro.serving.scheduler import LaneScheduler

    from chipbench import run
    from chipbench.tests import smoke

    smoke.patch(monkeypatch.setattr, run, tmp_path / "jax_cache")
    load, poll = devtrace.load, LaneScheduler.poll
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = spans.main(["--workload", "edgebert-mixed-open", "--seed", "3000000017",
                         "--seconds", "1"])
    assert rc == 0 and devtrace.load is load and LaneScheduler.poll is poll
    out = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert out["correct"] is True
    assert set(out["host"]) == {"host_step_ms", "dvfs_host_ms", "lane_load_ms",
                                "step_wait_ms", "queue_wait_ms", "lane_time_ms"}
    assert all(v > 0 for v in out["host"].values())
    assert set(out["metrics"]) == {"lane_occupancy.open", "latency_p99_ms.open"}
    assert out["spans"]["sched.step"]["count"] >= out["fused_steps"] > 0
    assert 0.9 < out["sched_step_s"] / out["bench_step_s"] <= 1.0
    assert out["longest_steps_ms"] and out["longest_steps_ms"][0]["inside"]
