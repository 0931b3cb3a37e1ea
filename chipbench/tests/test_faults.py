"""A run with the timed path broken underneath must come out not correct.

Each one-chip cell's driver plants its own faults (its ``FAULTS``), in the
program's call that advances every lane by one step and hands back the
answers.  The stub driver's cell (``stub_driver.py``) goes through the
same test, a cell that no file of the harness knows."""
import json

import pytest

from chipbench import run
from chipbench.tests import stub_driver

ONE_CHIP = [w["name"] for w in json.loads((run.ROOT / "BENCHMARK.json").read_text())["workloads"]
            if w["chips"] == 1]


CASES = [(cell, fault) for cell in ONE_CHIP
         for fault in run.load_driver(run.load_cell(cell)[2]).FAULTS]


def _reads_not_correct(go, monkeypatch, cell, fault):
    cfg = run.load_cell(cell)[2]
    run.load_driver(cfg).FAULTS[fault](monkeypatch, cfg)
    rc, out = go(cell, seconds=1.0)
    assert rc == 0
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell,fault", CASES, ids=[f"{c}-{f}" for c, f in CASES])
def test_fault_is_not_correct(smoke_run, monkeypatch, cell, fault):
    _reads_not_correct(smoke_run, monkeypatch, cell, fault)


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_sound_run_is_correct(smoke_run, cell):
    rc, out = smoke_run(cell, seconds=1.0)
    assert rc == 0 and out["correct"] is True


@pytest.mark.parametrize("fault", stub_driver.FAULTS)
def test_stub_fault_is_not_correct(stub_run, monkeypatch, fault):
    _reads_not_correct(stub_run, monkeypatch, stub_driver.CELL["name"], fault)
