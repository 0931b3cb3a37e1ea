"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists; everything else works on those lists, so the arithmetic is tested
on synthetic traces without a chip.

* A device is busy while any operation on its "XLA Ops" line runs; its
  busy time is the length of the union of those intervals inside the
  window, and its idle share is 1 minus busy over the window.
* A step's device time is the duration of its program's event on the
  device's "XLA Modules" line: the jitted fused step, whose name the
  cell's driver gives as ``System.step_program`` (the classifier's
  ``jit(step_fn)``).
* An idle gap is charged to the benchmark's own host span (``bench.*``)
  that overlaps it most, so the breakdown says what the host was doing
  while the device waited.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load(log_dir: str) -> dict:
    """The newest trace under ``log_dir`` as ``{"devices": {plane: {"ops":
    [(name, start_s, end_s)], "modules": [...]}}, "host": [(name, start_s,
    end_s)]}``, every time in seconds on the trace's one clock."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    lines[key] = [(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                                  for e in line.events]
            out["devices"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                                   for e in line.events if e.name.startswith(HOST_PREFIX))
    return out


def merged(intervals, lo: float, hi: float) -> list:
    """The union of ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def charge_gaps(idle: list, spans: list) -> collections.Counter:
    """Seconds of each idle gap charged to the host span overlapping it
    most (``"none"`` where no benchmark span overlaps).  The benchmark's
    spans come from one thread and do not overlap, except the window span
    that holds them all and is left out here."""
    spans = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
    starts = [s for s, _, _ in spans]
    out = collections.Counter()
    for gs, ge in idle:
        best, best_ov = "none", 0.0
        i = bisect.bisect_left(starts, ge) - 1
        while i >= 0 and spans[i][1] > gs:
            ov = min(spans[i][1], ge) - max(spans[i][0], gs)
            if ov > best_ov:
                best, best_ov = spans[i][2], ov
            i -= 1
        out[best] += ge - gs
    return out


def reduce(trace: dict, step_pattern: str) -> dict:
    """Busy and idle time, device time of the programs whose name
    ``step_pattern`` finds, and the breakdown of the window that the host
    span ``bench.window`` marks."""
    windows = [(s, e) for n, s, e in trace["host"] if n == WINDOW_SPAN]
    if not windows or not trace["devices"]:
        return {}
    lo, hi = windows[0]
    step_re = re.compile(step_pattern)
    busy_s, step_s, steps = [], [], []
    op_time, idle_by = collections.Counter(), collections.Counter()
    for lines in trace["devices"].values():
        ops = lines["ops"] or lines["modules"]
        busy = merged([(s, e) for _, s, e in ops], lo, hi)
        busy_s.append(sum(e - s for s, e in busy))
        for name, s, e in ops:
            if lo <= s < hi:
                op_time[name] += min(e, hi) - s
        mods = [(s, e) for n, s, e in lines["modules"] if step_re.search(n) and lo <= s < hi]
        step_s.append(sum(min(e, hi) - s for s, e in mods))
        steps.append(len(mods))
        idle_by.update(charge_gaps(gaps(busy, lo, hi), trace["host"]))
    n = len(busy_s)
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_s) / n,
        "step_device_s": sum(step_s) / n,
        "steps": sum(steps) / n,
        "devices": n,
        "device_ops": [[k, v / n] for k, v in op_time.most_common(10)],
        "idle_gaps": [[k, v / n] for k, v in idle_by.most_common(10)],
    }
