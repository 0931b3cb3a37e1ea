"""A cell of a second driver (``stub_driver.py``, a toy decoder) runs
through the harness as it stands: ``run.main`` under the smoke patch, its
answer lengths drawn from the traffic, its own step program read from the
trace, and ``readings.py``.  Its faults are in ``test_faults.py`` and its
compile check in ``test_rehearsal.py``."""
import io
import json
from contextlib import redirect_stdout
from statistics import NormalDist

import numpy as np
import pytest

from chipbench import devtrace, readings, run, traffic
from chipbench.tests import stub_driver

SEED = 2_147_483_659


def _recording(monkeypatch, name):
    """Wraps ``stub_driver.System.<name>`` to record its arguments."""
    got = []
    orig = getattr(stub_driver.System, name)

    def method(self, *args, **kw):
        got.append((self, args, kw))
        return orig(self, *args, **kw)

    monkeypatch.setattr(stub_driver.System, name, method)
    return got


def test_sound_run_is_correct(stub_run, stub_cell, monkeypatch):
    built = _recording(monkeypatch, "__init__")
    rc, out = stub_run(stub_cell, seconds=1.0, seed=SEED)
    assert rc == 0 and out["correct"] is True, out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert set(out["checks"]) == {"token_gap", "length_mismatch", "missing"}
    # the smoke patch applied the stub's own SMOKE, nothing of the classifier's
    cfg = built[0][1][0]
    assert cfg == dict(stub_driver.CONFIG, **stub_driver.SMOKE)


def _stratified(spec, n):
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of ``spec``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def test_answer_lengths_reach_submit(stub_run, stub_cell, monkeypatch):
    subs = _recording(monkeypatch, "submit")
    rc, out = stub_run(stub_cell, seconds=1.0, seed=SEED)
    assert rc == 0 and out["correct"] is True
    asked = {args[0]: kw["answer_len"] for _, args, kw in subs}
    spec = stub_driver.MIX["answer_lengths"]
    window = [asked[u] for u in range(out["attempted"])]
    # in order: request i asks for the i-th of the seed's answer lengths
    g = traffic.rng(SEED, traffic.ANSWER_WINDOW)
    assert window == list(traffic.lengths(stub_driver.MIX, len(window), g,
                                          key="answer_lengths"))
    # stratified: every seed's window asks for the same set of lengths
    assert sorted(window) == list(_stratified(spec, len(window)))
    warm = [asked[u] for u in sorted(asked) if u < 0]
    assert len(warm) == 2 * stub_driver.CONFIG["lanes"]
    assert sorted(warm) == list(_stratified(spec, len(warm)))


def test_trace_reads_the_drivers_step_program(stub_run, stub_cell, monkeypatch):
    patterns = []
    reduce = devtrace.reduce

    def recording(trace, step_pattern):
        patterns.append(step_pattern)
        return reduce(trace, step_pattern)

    monkeypatch.setattr(devtrace, "reduce", recording)
    rc, out = stub_run(stub_cell, seconds=1.0, seed=SEED, trace=1)
    assert rc == 0 and out["correct"] is True
    assert patterns == [r"decode_step_fn"]


@pytest.mark.parametrize("cell", ["edgebert-mixed-open", stub_driver.CELL["name"]])
def test_readings_record_the_drivers_notes(stub_run, cell):
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert readings.main(["--workload", cell, "--seconds", "0.5", "--seeds", "7"]) == 0
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    notes = {"edgebert-mixed-open": "threshold",
             stub_driver.CELL["name"]: "tokens_served"}[cell]
    assert line["seed"] == 7 and line[notes] > 0
    assert set(line["program"]) == set(line["control"]) | {"missing"}
