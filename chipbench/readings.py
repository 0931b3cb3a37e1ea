"""The readings that a cell's limits are set from: for each seed, one run
of the cell (a short window at its own load, its seeded sample compared
with the reference as every run compares it), and the control on the same
sample: the plain reference one precision below the configuration's, put
in the program's place, and beside it the reference at one bf16 pass.
All seeds run in one process.

    python3 chipbench/readings.py --workload edgebert-mixed-open \
        --seconds 3 --seeds 1,2,3
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import run  # noqa: E402  (sets up sys.path for the program)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    _, cell, cfg, mix = run.load_cell(args.workload)
    devices = run.find_devices(cell["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.measure(cell, cfg, mix, seed, args.seconds, False, devices,
                          t_start=time.perf_counter())
        sysm = res["system"]
        sample, served = res["sample"], res["answers"]
        sides = {"program": served, "control": sysm.control_answers(sample, served),
                 "bf16": sysm.control_answers(sample, served, "bf16")}
        control = sysm.check(sample, sides["control"])
        stats = {}
        for side, answers in sides.items():
            e = sysm.answer_errors(sample, answers)
            stats[side] = {"max": e.max(), "p99": np.percentile(e, 99),
                           "p95": np.percentile(e, 95), "p90": np.percentile(e, 90),
                           "p50": np.median(e), "mean": e.mean(),
                           "rms": float(np.sqrt((e ** 2).mean()))}
        print(json.dumps({"stats": {k: {m: float(x) for m, x in v.items()}
                                    for k, v in stats.items()}}), flush=True)
        print(json.dumps({
            "seed": seed, **sysm.notes(),
            "answers": len(sample), "failed": res["failed"],
            "program": {k: v for k, (v, _) in res["checks"].items()},
            "control": {k: v for k, (v, _) in control.items()},
            "bf16": {k: v for k, (v, _) in sysm.check(sample, sides["bf16"]).items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
