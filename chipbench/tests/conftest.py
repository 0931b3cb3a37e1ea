import io
import json
import sys
from contextlib import redirect_stdout
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _runner(monkeypatch, tmp_path):
    from chipbench import run
    from chipbench.tests import smoke

    smoke.patch(monkeypatch.setattr, run, tmp_path / "jax_cache")

    def go(cell, seconds=1.0, seed=3_000_000_017, trace=0):
        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = run.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)])
        return rc, smoke.last_line(buf.getvalue())

    return go


@pytest.fixture
def smoke_run(monkeypatch, tmp_path):
    """``run.main`` for one cell on the CPU at smoke width (see
    ``smoke.patch``): returns its exit code and its last line."""
    return _runner(monkeypatch, tmp_path)


@pytest.fixture
def stub_cell(monkeypatch):
    """A cell served by ``stub_driver``, which no file of the harness
    knows: the driver registered as ``chipbench.drivers.stub``, and
    ``run.load_cell`` given the entries that a PR adding the cell would add
    to ``BENCHMARK.json``.  Returns the cell's name."""
    from chipbench import run, traffic
    from chipbench.tests import stub_driver

    name = stub_driver.CELL["name"]
    monkeypatch.setitem(sys.modules, "chipbench.drivers.stub", stub_driver)
    load_cell = run.load_cell

    def with_stub(cell):
        if cell != name:
            return load_cell(cell)
        bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
        bench["workloads"].append(stub_driver.CELL)
        for m in bench["end_to_end"]:
            if m["name"] == "latency_p50_ms":
                m["workloads"].append(name)
        cfg = json.loads(json.dumps(stub_driver.CONFIG))
        return bench, dict(stub_driver.CELL), cfg, traffic.check(dict(stub_driver.MIX), name)

    monkeypatch.setattr(run, "load_cell", with_stub)
    return name


@pytest.fixture
def stub_run(stub_cell, monkeypatch, tmp_path):
    """``smoke_run`` with the stub's cell among the cells."""
    return _runner(monkeypatch, tmp_path)
