"""Run one cell of the benchmark once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``configs/<name>.json``), a traffic mix (``traffic/<name>.json``) and the
chips it needs.  The run refuses to start without that many TPU chips.
It builds the configuration's served path (weights made on the device),
warms every shape the mix reaches, then offers the mix, drawn from the
seed, for ``--seconds``: open loop, each request timed from when it fell
due to when ``poll()`` handed it back, or as a backlog, counting what
completes.
After the window it drains what is left, reads peak device memory, frees
the served path and compares a seeded sample of the answers with the
configuration's plain reference.

``--trace 1`` records the window with the JAX profiler and reports the
per-layer metrics (each read by a file under ``metrics/``, see
``load_metric``) in place of the end-to-end ones.  The last line of standard output is one JSON
object; the numbers compared for ``correct`` end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import contextlib        # noqa: E402
import gc                # noqa: E402
import importlib         # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# run as a script, the benchmark's own directory heads sys.path; the
# checkout's root takes its place, so that ``chipbench`` is a package
if sys.path and Path(sys.path[0]).resolve() == HERE:
    sys.path.pop(0)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np       # noqa: E402

from chipbench import devtrace, flops, traffic  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
SAMPLE = 256          # answers compared with the reference in every run
DRAIN_S = 60.0        # how long, once the window and its trace are closed, a due
                      # answer may still come


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_cell(name: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    return bench, cell, cfg, traffic.load(cell["traffic"])


def find_devices(chips: int):
    """The first ``chips`` TPU devices; refuses anything else."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def setup_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, keeping
    every program however fast it compiled, so that only a checkout's
    first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def load_metric(name: str):
    """The reader of metric ``name``: ``metrics/<name>.py``, or where there
    is none, that of the quantity it splits, ``metrics/<name up to its
    first dot>.py`` (``lane_occupancy.open`` -> ``lane_occupancy.py``)."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileWatch:
    """Counts backend compiles while open (JAX's own compile events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _listen(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


class Load:
    """The requests of one run and what became of them."""

    def __init__(self, sysm, mix: dict, seconds: float, seed: int, vocab: int):
        g = traffic.rng(seed, traffic.WINDOW)
        self.open = mix["arrival"] == "poisson"
        if self.open:
            self.due = traffic.arrival_times(mix, seconds, g)
            n = len(self.due)
        else:
            self.depth = mix["queue_per_lane"] * sysm.lanes
            n = mix["pool"]
        self.pool = traffic.tokens(traffic.lengths(mix, n, g), g, vocab)
        self.sent = []                   # uid -> pool index
        self.sent_at, self.done_at, self.answers = [], {}, {}
        self.longest_s = 0.0             # the longest pass of the serving loop

    def submit(self, sysm, now: float) -> None:
        uid = len(self.sent)
        self.sent.append(uid % len(self.pool))
        self.sent_at.append(now)
        sysm.submit(uid, self.pool[self.sent[uid]])

    def tokens(self, uid: int):
        return self.pool[self.sent[uid]]


def serve(sysm, load: Load, t0: float, until: float, span, *, closing: bool) -> list:
    """Offer the load and step the server until ``until`` seconds after
    ``t0``.  ``closing``: offer nothing new beyond what fell due, and stop
    once every request sent has its answer.  Returns the buckets stepped."""
    clock = time.perf_counter
    stepped = []
    while True:
        now = clock() - t0
        if now >= until:
            break
        with span("bench.submit"):
            if load.open:
                while len(load.sent) < len(load.due) and load.due[len(load.sent)] <= now:
                    load.submit(sysm, now)
            elif not closing:
                while sysm.queued < load.depth:
                    load.submit(sysm, now)
        with span("bench.step"):
            bucket = sysm.step()
        if bucket is None:
            if load.open and len(load.sent) < len(load.due):
                wait = load.due[len(load.sent)] - (clock() - t0)
                if wait > 0:
                    with span("bench.wait"):
                        time.sleep(wait)
                continue
            if closing:
                break
            continue
        stepped.append(bucket)
        with span("bench.poll"):
            answered = sysm.poll()
            t = clock() - t0
            for uid, ans in answered:
                load.done_at[uid] = t
                load.answers[uid] = ans
        load.longest_s = max(load.longest_s, t - now)
    return stepped


def warm(sysm, mix: dict, seed: int, vocab: int) -> None:
    """Compile and run every shape the mix reaches: two lanes' worth of
    requests in each of its buckets, drained."""
    g = traffic.rng(seed, traffic.WARM)
    uid, below = -1, 0
    for b in sorted(sysm.cfg["buckets"]):
        if b in sysm.buckets:
            for toks in traffic.tokens(traffic.lengths(mix, 2 * sysm.lanes, g, below + 1, b),
                                       g, vocab):
                sysm.submit(uid, toks)
                uid -= 1
        below = b
    while sysm.step() is not None:
        sysm.poll()
    sysm.poll()


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def sample_uids(load: Load, seed: int) -> list:
    """A seeded sample of the answered requests, the longest among them."""
    uids = sorted(load.answers)
    if len(uids) <= SAMPLE:
        return uids
    longest = max(uids, key=lambda u: len(load.tokens(u)))
    rest = [u for u in uids if u != longest]
    pick = traffic.rng(seed, traffic.SAMPLE).choice(len(rest), SAMPLE - 1, replace=False)
    return sorted([longest] + [rest[i] for i in pick])


def measure(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float, trace: bool,
            devices, t_start: float = T_START) -> dict:
    """One run of one cell; returns the result object to print."""
    import jax

    setup_compile_cache()
    peaks = peaks_for(devices[0].device_kind)
    driver = importlib.import_module(f"chipbench.drivers.{cfg['driver']}")
    t_build = time.perf_counter()
    sysm = driver.System(cfg, mix, devices)
    t_warm = time.perf_counter()
    built_peak = memory_peak(devices)
    vocab = cfg["vocab_size"]
    warm(sysm, mix, seed, vocab)
    print(f"setup: {t_build - t_start:.2f}s to the build, {t_warm - t_build:.2f}s to build "
          f"(weights, threshold, server), of which {sysm.reference_s:.2f}s the reference's "
          f"threshold profiling (not set-up), {time.perf_counter() - t_warm:.2f}s to warm "
          f"buckets {sysm.buckets}", file=sys.stderr)
    load = Load(sysm, mix, seconds, seed, vocab)
    if trace:
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation
    else:
        span = lambda name: contextlib.nullcontext()   # noqa: E731
    # what set-up built is never garbage: kept out of the collector's full
    # passes, which otherwise stall the serving loop for some 0.1 s each
    gc.collect()
    gc.freeze()
    before = sysm.counters()
    setup_s = time.perf_counter() - t_start - sysm.reference_s
    with CompileWatch() as compiles:
        t0 = time.perf_counter()
        with span("bench.window"):
            stepped = serve(sysm, load, t0, seconds, span, closing=False)
        window_s = time.perf_counter() - t0
    longest_s = load.longest_s
    if trace:
        jax.profiler.stop_trace()
    after = sysm.counters()
    in_window = [u for u, t in load.done_at.items() if t <= window_s]
    # stopping the trace of a long window takes tens of seconds, in which
    # nothing is served: the drain's time starts after it
    drain_until = time.perf_counter() - t0 + DRAIN_S
    serve(sysm, load, t0, drain_until, lambda n: contextlib.nullcontext(), closing=True)
    peak = memory_peak(devices)
    record = {
        "arrival": mix["arrival"], "seconds": window_s, "setup_s": setup_s,
        "lanes": sysm.lanes, "chips": len(devices), "peaks": peaks,
        "counters": {k: after[k] - before[k] for k in after},
        "completed": len(in_window),
        "answer_flops": sum(sysm.answer_flops(load.tokens(u), load.answers[u])
                            for u in in_window),
        "latencies_s": np.array([load.done_at[u] - load.due[u]
                                 for u in range(len(load.sent)) if u in load.done_at])
        if load.open else None,
        "trace": None,
    }
    if trace:
        try:
            reduced = devtrace.reduce(devtrace.load(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        if reduced:
            n = max(len(stepped), 1)
            least = sum(flops.least_seconds(*sysm.step_cost(b), peaks["bf16_flops_per_s"],
                                            peaks["hbm_bytes_per_s"])
                        for b in stepped) * reduced["steps"] / n
            reduced["least_s"] = least
        record["trace"] = reduced
    attempted = len(load.due) if load.open else len(load.sent)
    failed = attempted - len(load.answers)
    sysm.close()
    gc.collect()
    uids = sample_uids(load, seed)
    sample = [load.tokens(u) for u in uids]
    checks = sysm.check(sample, [load.answers[u] for u in uids])
    checks["missing"] = (failed, 0)
    print(f"window {window_s:.3f}s: {attempted} attempted, {len(load.answers)} answered, "
          f"{len(in_window)} in the window, {compiles.count} compiles in the window, "
          f"{len(stepped)} steps, longest loop pass {longest_s * 1e3:.1f} ms, "
          f"threshold {sysm.threshold}; device memory peak {peak} bytes, "
          f"{built_peak} before warm-up", file=sys.stderr)
    if load.open and load.sent:
        lag = np.array(load.sent_at) - load.due[:len(load.sent_at)]
        print(f"generator lag p50 {np.percentile(lag, 50) * 1e3:.3f} ms, "
              f"p99 {np.percentile(lag, 99) * 1e3:.3f} ms", file=sys.stderr)
    return {"record": record, "checks": checks, "attempted": attempted, "failed": failed,
            "memory_peak_bytes": peak, "system": sysm, "sample": sample,
            "answers": [load.answers[u] for u in uids]}


def metrics_for(bench: dict, cell: dict, trace: bool, record: dict) -> dict:
    out = {}
    for m in bench["per_layer" if trace else "end_to_end"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = load_metric(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench, cell, cfg, mix = load_cell(args.workload)
        devices = find_devices(cell["chips"])
        import jax

        res = measure(cell, cfg, mix, args.seed, args.seconds, bool(args.trace), devices)
    except NoChip as e:
        print(f"chipbench: {e}; refusing to run", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    record, checks = res["record"], res["checks"]
    correct = all(v <= lim for v, lim in checks.values())
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": jax.device_count(), "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": correct, "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics_for(bench, cell, bool(args.trace), record), "device": device}
    if args.trace and record["trace"]:
        tr = record["trace"]
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
