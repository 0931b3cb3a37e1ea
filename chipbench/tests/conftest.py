import io
import sys
from contextlib import redirect_stdout
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def smoke_run(monkeypatch, tmp_path):
    """``run.main`` for one cell on the CPU at smoke width (see
    ``smoke.patch``): returns its exit code and its last line."""
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
