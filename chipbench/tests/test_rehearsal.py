"""Rehearsals without the chip: one cell's loop on the CPU at smoke width,
the four-replica cell on four virtual CPU devices, and every cell's warmed
programs compiled at full width for a described v5e:2x2."""
import json
import os
import subprocess
import sys

import jax
import pytest

from chipbench import run
from chipbench.tests import smoke

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def _metric_names(cell, kind):
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    return {m["name"] for m in bench[kind] if cell in m.get("workloads", [cell])}


def _assert_shape(out, cell, kind):
    assert KEYS <= set(out) and list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) <= _metric_names(cell, kind)
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


CELLS = json.loads((run.ROOT / "BENCHMARK.json").read_text())["workloads"]
ONE_CHIP = [c["name"] for c in CELLS if c["chips"] == 1]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_one_second_on_the_cpu(smoke_run, cell):
    rc, out = smoke_run(cell, seconds=1.0)
    assert rc == 0
    _assert_shape(out, cell, "end_to_end")
    assert set(out["metrics"]) == _metric_names(cell, "end_to_end")


def test_traced_run_on_the_cpu(smoke_run):
    # the CPU trace has no TPU plane: the device readers find nothing and
    # only the counters' and the host clock's metrics are left
    rc, out = smoke_run("edgebert-mixed-open", seconds=1.0, trace=1)
    assert rc == 0
    _assert_shape(out, "edgebert-mixed-open", "per_layer")
    assert set(out["metrics"]) == {"lane_occupancy.open", "latency_p99_ms.open"}


def test_answers_wait_for_a_slow_trace_stop(smoke_run, monkeypatch):
    # the drain's time starts once the trace is stopped, however long that took
    import time

    stop = jax.profiler.stop_trace

    def slow_stop():
        stop()
        time.sleep(1.0)

    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
    monkeypatch.setattr(run, "DRAIN_S", 0.5)
    rc, out = smoke_run("edgebert-mixed-open", seconds=1.0, trace=1)
    assert rc == 0
    assert out["checks"]["missing"]["value"] == 0 and out["correct"] is True


def test_refuses_without_a_chip(capsys):
    assert run.main(["--workload", ONE_CHIP[0], "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


FOUR_REPLICAS = """
import json, sys
from pathlib import Path
import jax
from chipbench import run, traffic
from chipbench.tests import smoke
cfg = smoke.shrink(json.loads((run.HERE / "configs" / "albert_edgebert.json").read_text()))
smoke.patch(setattr, run, Path(sys.argv[1]))
res = run.measure({"name": "replicas", "chips": 4}, cfg, traffic.load("mixed_backlog"),
                  2147483659, 1.0, False, jax.devices()[:4])
print(json.dumps({"lanes": res["system"].lanes, "answered": res["record"]["completed"],
                  "correct": all(v <= lim for v, lim in res["checks"].values())}))
"""


def test_four_replicas_on_virtual_devices(tmp_path):
    """The lane-sharded path (four replicas over a mesh, the driver's
    ``chips > 1`` branch) on four virtual CPU devices; no cell runs it yet
    (PERF.md, Open questions)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", FOUR_REPLICAS, str(tmp_path / "jax_cache")],
                          cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = smoke.last_line(proc.stdout)
    assert out == {"lanes": 32, "answered": out["answered"], "correct": True}
    assert out["answered"] > 0


# --------------------------------------------------------------------------
# AOT: every cell's warmed programs, full width, for a described v5e:2x2
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles written to the persistent cache could not be read back
    # without a chip: keep it off for this module
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile_cell(topo, monkeypatch, name):
    """The cell's warmed programs, compiled by its driver's
    ``compile_programs`` at full width for the described chips, with the
    program's kernels compiled for the chip, not interpreted."""
    from repro.kernels import dispatch

    monkeypatch.setattr(dispatch, "interpret_mode", lambda: False)
    _, cell, cfg, mix = run.load_cell(name)
    return run.load_driver(cfg).compile_programs(cfg, mix, topo.devices, cell["chips"])


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_cell_programs_compile_for_v5e(topo, monkeypatch, cell):
    """Every cell's warmed programs, lowered from the server the cell
    builds and compiled at full width: its driver says which and checks
    what the configuration states of them."""
    assert _compile_cell(topo, monkeypatch, cell["name"])


def test_stub_programs_compile_for_v5e(topo, monkeypatch, stub_cell):
    """The compile check takes a second driver's cell as it takes the
    classifier's: it calls that driver's ``compile_programs``."""
    from chipbench.tests import stub_driver

    called, orig = [], stub_driver.compile_programs

    def compile_programs(*args):
        called.append(args[3])
        return orig(*args)

    monkeypatch.setattr(stub_driver, "compile_programs", compile_programs)
    got = _compile_cell(topo, monkeypatch, stub_cell)
    assert called == [1] and set(got) == {"prefill", "decode"}
    assert "decode_step_fn" in got["decode"].as_text()


def test_refuses_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own files
    has no system under test: the run fails and prints no result."""
    import shutil

    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.smoke", str(tmp_path / "jax_cache"),
         "--workload", ONE_CHIP[0], "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
