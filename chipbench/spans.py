"""Where the host's time goes inside the program's serving step: the
program's own profiler spans (``sched.*``, ``engine.*``, ``dvfs.*``) in one
traced run of a cell, reduced on the device trace's clock.

    python3 chipbench/spans.py --workload edgebert-mixed-open --seed <n> --seconds 51

The run is the one ``run.py --trace 1`` makes (``run.measure``); the trace
is read twice, by ``devtrace`` for the benchmark's own numbers and here for
the program's spans, and ``poll()``'s requests leave their wall stamps
(``queued_at``, ``admitted_at``, ``retired_at``) behind.  Standard error gets each span's
count and its total and self time per fused step, the device-idle time
charged to the innermost program span over it, the three longest
``sched.step`` spans with every span inside them, and the window's
``sched.step`` total against the benchmark's ``bench.step`` total.  The
last line of standard output is one JSON object: six host numbers in ms
(per fused step where so named), the span table, the three longest steps,
the run's per-layer metrics as ``run.py --trace 1`` reports them, its
device ops and its checks.

``observing`` reaches into ``devtrace.load`` and ``LaneScheduler.poll`` to
get the spans and stamps out of ``run.measure`` without editing either; it
and ``main`` go once ``devtrace``, ``run.py`` and the classifier driver read
the program's spans themselves, and ``load`` and the reductions move there.

A span belongs to the window when it starts inside ``bench.window``; its
time is cut at the window's end.  A span's self time is its time less that
of the spans directly inside it on its thread.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import contextlib
import glob
import json
import os
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import devtrace, run  # noqa: E402  (run sets up sys.path for the program)

PREFIXES = ("sched.", "engine.", "dvfs.")
STAMPS = ("queued_at", "admitted_at", "retired_at")
Span = collections.namedtuple("Span", "name start end thread stats")


def load(log_dir: str) -> list:
    """The program's spans in the newest trace under ``log_dir``, as
    ``Span(name, start_s, end_s, thread, stats)`` on the trace's clock."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend(Span(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9, (plane.name, i),
                            {k: v for k, v in e.stats})
                       for e in line.events if e.name.startswith(PREFIXES))
    return out


def parents(spans: list) -> list:
    """For each span the index of the span it runs directly inside on its
    thread, or None: spans on one thread nest."""
    out = [None] * len(spans)
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].thread, spans[i].start, -spans[i].end))
    stack = []
    for i in order:
        s = spans[i]
        while stack and (spans[stack[-1]].thread != s.thread or spans[stack[-1]].end <= s.start):
            stack.pop()
        out[i] = stack[-1] if stack else None
        stack.append(i)
    return out


def children(spans: list) -> dict:
    """Parent index (None: outermost) -> the indices of the spans directly
    inside it, in order of time."""
    out = collections.defaultdict(list)
    for i, p in enumerate(parents(spans)):
        out[p].append(i)
    for kids in out.values():
        kids.sort(key=lambda k: spans[k].start)
    return out


def reduce(spans: list, lo: float, hi: float) -> dict:
    """Per span name, of the spans starting in ``[lo, hi)``: ``count``,
    ``total_s`` and ``self_s``, each span's time cut at ``hi``."""
    out = collections.defaultdict(lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0})
    up = parents(spans)
    for i, s in enumerate(spans):
        if not lo <= s.start < hi:
            continue
        t = min(s.end, hi) - s.start
        out[s.name]["count"] += 1
        out[s.name]["total_s"] += t
        out[s.name]["self_s"] += t
        if up[i] is not None:
            out[spans[up[i]].name]["self_s"] -= t
    return dict(out)


def innermost(spans: list) -> list:
    """The time the spans cover, cut into ``(start, end, name)`` pieces
    each under the innermost span running then, in order of time."""
    kids = children(spans)
    out = []

    def walk(i):
        t = spans[i].start
        for k in kids[i]:
            if spans[k].start > t:
                out.append((t, spans[k].start, spans[i].name))
            walk(k)
            t = max(t, spans[k].end)
        if spans[i].end > t:
            out.append((t, spans[i].end, spans[i].name))

    for i in kids[None]:
        walk(i)
    return sorted(out)


def charge_innermost(idle: list, spans: list) -> collections.Counter:
    """Seconds of each idle interval charged to the innermost program span
    running over each part of it (``"none"`` where none runs).  The spans
    come from the one thread that serves."""
    pieces = innermost(spans)
    starts = [s for s, _, _ in pieces]
    out = collections.Counter()
    for gs, ge in idle:
        covered = 0.0
        i = max(bisect.bisect_right(starts, gs) - 1, 0)
        while i < len(pieces) and pieces[i][0] < ge:
            ov = min(pieces[i][1], ge) - max(pieces[i][0], gs)
            if ov > 0:
                out[pieces[i][2]] += ov
                covered += ov
            i += 1
        if ge - gs - covered > 0:
            out["none"] += ge - gs - covered
    return out


def longest(spans: list, lo: float, hi: float, n: int = 3, name: str = "sched.step") -> list:
    """The ``n`` longest ``name`` spans starting in ``[lo, hi)``, longest
    first, each as ``(span, [(depth, descendant), ...])`` with its
    descendants in order of time, depth 1 directly inside it."""
    kids = children(spans)

    def tree(i, depth):
        for k in kids[i]:
            yield depth, spans[k]
            yield from tree(k, depth + 1)

    inside = [i for i, s in enumerate(spans) if s.name == name and lo <= s.start < hi]
    inside.sort(key=lambda i: spans[i].start - spans[i].end)
    return [(spans[i], list(tree(i, 1))) for i in inside[:n]]


def host_numbers(table: dict, fused_steps: int, queue_waits, lane_times=()) -> dict:
    """The six host numbers, in ms: ``host_step_ms`` (``sched.step`` per
    fused step), ``dvfs_host_ms`` (``dvfs.*`` per fused step),
    ``lane_load_ms`` (mean ``engine.lane_load``), ``step_wait_ms``
    (``engine.fetch`` per fused step), and of the requests first admitted
    in the window ``queue_wait_ms`` (median ``queued_at`` ->
    ``admitted_at``) and ``lane_time_ms`` (median ``admitted_at`` ->
    ``retired_at``).  A number with nothing to read is left out."""
    total = lambda *names: sum(table[n]["total_s"] for n in names if n in table)  # noqa: E731
    out = {}
    if fused_steps and "sched.step" in table:
        out["host_step_ms"] = 1e3 * total("sched.step") / fused_steps
        out["dvfs_host_ms"] = 1e3 * total("dvfs.admit", "dvfs.step", "dvfs.retire") / fused_steps
        out["step_wait_ms"] = 1e3 * total("engine.fetch") / fused_steps
    if table.get("engine.lane_load", {}).get("count"):
        out["lane_load_ms"] = 1e3 * total("engine.lane_load") / table["engine.lane_load"]["count"]
    if len(queue_waits):
        out["queue_wait_ms"] = 1e3 * float(np.median(queue_waits))
    if len(lane_times):
        out["lane_time_ms"] = 1e3 * float(np.median(lane_times))
    return out


@contextlib.contextmanager
def observing(seen: dict):
    """While open, every trace ``devtrace`` loads also yields the program's
    spans, and every request ``poll()`` hands back leaves its wall stamps,
    both in ``seen``."""
    from repro.serving.scheduler import LaneScheduler

    load_trace, poll = devtrace.load, LaneScheduler.poll

    def load_both(log_dir):
        seen["trace"] = load_trace(log_dir)
        seen["spans"] = load(log_dir)
        return seen["trace"]

    def poll_stamps(self, *, pin=False):
        done = poll(self, pin=pin)
        seen["stamps"].update((r.uid, tuple(getattr(r, k, None) for k in STAMPS))
                              for r in done)
        return done

    seen.setdefault("stamps", {})
    devtrace.load, LaneScheduler.poll = load_both, poll_stamps
    try:
        yield seen
    finally:
        devtrace.load, LaneScheduler.poll = load_trace, poll


def report(seen: dict, record: dict) -> dict:
    """Reduce what ``observing`` saw in one traced run; prints the tables
    on standard error and returns the numbers."""
    trace, spans = seen["trace"], seen["spans"]
    windows = [(s, e) for n, s, e in trace["host"] if n == devtrace.WINDOW_SPAN]
    if not windows:
        raise RuntimeError("the trace has no bench.window span")
    lo, hi = windows[0]
    table = reduce(spans, lo, hi)
    steps = record["counters"]["dense_steps"]
    # requests first admitted in the window: each first admission is a lane load
    admitted = {s.stats.get("uid") for s in spans
                if s.name == "engine.lane_load" and lo <= s.start < hi}
    stamps = [t for u, t in seen["stamps"].items() if u in admitted and None not in t]
    waits = [a - q for q, a, _ in stamps]
    in_lane = [r - a for _, a, r in stamps]
    out = {"host": host_numbers(table, steps, waits, in_lane), "fused_steps": steps,
           "window_s": hi - lo, "loop_ms_per_step": 1e3 * record["seconds"] / max(steps, 1),
           "spans": {k: table[k] for k in sorted(table)}}
    bench_step = sum(min(e, hi) - s for n, s, e in trace["host"]
                     if n == "bench.step" and lo <= s < hi)
    sched_step = table.get("sched.step", {}).get("total_s", 0.0)
    out["bench_step_s"], out["sched_step_s"] = bench_step, sched_step
    log = lambda *a: print(*a, file=sys.stderr)  # noqa: E731
    log(f"window {hi - lo:.3f}s, {steps} fused steps; sched.step {sched_step:.3f}s "
        f"against bench.step {bench_step:.3f}s ({sched_step / max(bench_step, 1e-12):.4f})")
    log("span: count, total ms per fused step, self ms per fused step (by self time)")
    for k, v in sorted(table.items(), key=lambda kv: -kv[1]["self_s"]):
        log(f"  {k:20s} {v['count']:8d} {1e3 * v['total_s'] / max(steps, 1):9.4f} "
            f"{1e3 * v['self_s'] / max(steps, 1):9.4f}")
    idle = collections.Counter()
    for lines in trace["devices"].values():
        busy = devtrace.merged([(s, e) for _, s, e in lines["ops"] or lines["modules"]], lo, hi)
        idle.update(charge_innermost(devtrace.gaps(busy, lo, hi), spans))
    n = max(len(trace["devices"]), 1)
    out["idle_by_span_s"] = {k: v / n for k, v in idle.most_common()}
    if idle:
        log("device idle s charged to the innermost program span:")
        for k, v in idle.most_common():
            log(f"  {k:20s} {v / n:9.3f}")
    out["longest_steps_ms"] = []
    for step, tree in longest(spans, lo, hi):
        out["longest_steps_ms"].append(
            {"step": 1e3 * (step.end - step.start), "step_num": step.stats.get("step_num"),
             "inside": [[d, k.name, 1e3 * (k.end - k.start), k.stats] for d, k in tree]})
        log(f"long sched.step {1e3 * (step.end - step.start):.3f} ms "
            f"(step {step.stats.get('step_num')}):")
        for d, k in tree:
            log(f"  {'  ' * d}{k.name:20s} {1e3 * (k.end - k.start):9.3f} ms {k.stats}")
    for k, v in out["host"].items():
        log(f"{k}: {v:.6f}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench, cell, cfg, mix = run.load_cell(args.workload)
    devices = run.find_devices(cell["chips"])
    with observing({}) as seen:
        res = run.measure(cell, cfg, mix, args.seed, args.seconds, True, devices)
    out = report(seen, res["record"])
    out["metrics"] = run.metrics_for(bench, cell, True, res["record"])
    out["device_ops"] = (res["record"]["trace"] or {}).get("device_ops", [])
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in res["checks"].items()}
    out["correct"] = all(v <= lim for v, lim in res["checks"].values())
    out["device"] = {"platform": devices[0].platform, "kind": devices[0].device_kind}
    print(json.dumps(out, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
