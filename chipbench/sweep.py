"""Find a cell's knee: offer its open-loop mix at a list of rates, one
after another in one process, and print what each rate did.

    python3 chipbench/sweep.py --workload edgebert-mixed-open --seed 1 \
        --seconds 5 --rates 200,400,800

The knee is the highest rate at which the queue does not grow over the
window: the requests waiting at its end are no more than at its first
quarter, and the answers keep up with the arrivals.  A cell's traffic file
then states its rate as a number; the benchmark itself never searches.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import run  # noqa: E402  (sets up sys.path for the program)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    args = ap.parse_args(argv)
    import numpy as np

    _, cell, cfg, mix = run.load_cell(args.workload)
    if mix["arrival"] != "poisson":
        raise SystemExit("a sweep needs an open-loop (poisson) mix")
    devices = run.find_devices(cell["chips"])
    run.setup_compile_cache()
    driver = importlib.import_module(f"chipbench.drivers.{cfg['driver']}")
    sysm = driver.System(cfg, mix, devices)
    run.warm(sysm, mix, args.seed, cfg["vocab_size"])
    gc.collect()
    gc.freeze()
    quiet = lambda name: contextlib.nullcontext()   # noqa: E731
    for rate in (float(r) for r in args.rates.split(",")):
        load = run.Load(sysm, dict(mix, rate_rps=rate), args.seconds, args.seed,
                        cfg["vocab_size"])
        t0 = time.perf_counter()
        run.serve(sysm, load, t0, args.seconds / 4, quiet, closing=False)
        early = sysm.queued
        run.serve(sysm, load, t0, args.seconds, quiet, closing=False)
        late = sysm.queued
        window = time.perf_counter() - t0
        done = [u for u, t in load.done_at.items() if t <= window]
        lat = np.array([load.done_at[u] - load.due[u] for u in done])
        run.serve(sysm, load, t0, window + run.DRAIN_S, quiet, closing=True)
        row = {"rate_rps": rate, "answered_rps": len(done) / window,
               "queued_at_quarter": early, "queued_at_end": late,
               "p50_ms": float(np.percentile(lat, 50) * 1e3) if len(lat) else None,
               "p99_ms": float(np.percentile(lat, 99) * 1e3) if len(lat) else None}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
