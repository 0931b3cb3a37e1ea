"""The trace reduction on synthetic traces."""
import pytest

from chipbench import devtrace


def test_merged_clips_and_joins():
    got = devtrace.merged([(0.5, 2.0), (1.5, 3.0), (4.0, 5.0), (9.0, 12.0)], 1.0, 10.0)
    assert got == [[1.0, 3.0], [4.0, 5.0], [9.0, 10.0]]


def test_gaps_cover_the_rest_of_the_window():
    busy = [[1.0, 3.0], [4.0, 5.0]]
    assert devtrace.gaps(busy, 0.0, 6.0) == [(0.0, 1.0), (3.0, 4.0), (5.0, 6.0)]
    assert devtrace.gaps([], 0.0, 2.0) == [(0.0, 2.0)]


def test_gaps_charged_to_the_span_overlapping_most():
    spans = [("bench.window", 0.0, 10.0), ("bench.step", 0.0, 1.2),
             ("bench.poll", 1.2, 1.3), ("bench.wait", 1.3, 4.0)]
    got = devtrace.charge_gaps([(1.0, 1.3), (2.0, 3.0), (5.0, 6.0)], spans)
    assert got == pytest.approx({"bench.step": 0.3, "bench.wait": 1.0, "none": 1.0})


def _trace():
    # two chips; a 10 s window; steps of 1 s on chip 0 and 2 s on chip 1
    host = [("bench.window", 0.0, 10.0), ("bench.step", 0.0, 4.0), ("bench.wait", 4.0, 10.0)]
    dev = {}
    for i, dur in enumerate((1.0, 2.0)):
        mods = [("jit_step_fn", 0.0, dur), ("jit_embed_fn", 2.5, 3.0),
                ("jit_step_fn", 3.0, 3.0 + dur), ("jit_step_fn", 11.0, 12.0)]
        ops = [("fusion.1", s, e) for _, s, e in mods]
        dev[f"/device:TPU:{i}"] = {"ops": ops, "modules": mods}
    return {"devices": dev, "host": host}


def test_reduce_averages_over_chips():
    r = devtrace.reduce(_trace(), "step_fn")
    assert r["window_s"] == 10.0 and r["devices"] == 2
    assert r["steps"] == 2                                    # the third is outside
    assert r["step_device_s"] == pytest.approx((2 * 1.0 + 2 * 2.0) / 2)
    assert r["busy_s"] == pytest.approx(((1 + 0.5 + 1) + (2 + 0.5 + 2)) / 2)
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(10.0 - r["busy_s"])
    assert idle["bench.wait"] == pytest.approx(((10 - 4) + (10 - 5)) / 2)


def test_reduce_needs_the_window_span():
    t = _trace()
    t["host"] = t["host"][1:]
    assert devtrace.reduce(t, "step_fn") == {}


def test_reduce_counts_the_named_program():
    """The step is the program the driver names: a decoder's decode step,
    not its prefill, and none where no program has the name."""
    t = _trace()
    for lines in t["devices"].values():
        lines["modules"] = [("jit_decode_step_fn" if n == "jit_step_fn" else "jit_prefill_fn",
                             s, e) for n, s, e in lines["modules"]]
    assert devtrace.reduce(t, r"decode_step_fn")["steps"] == 2
    assert devtrace.reduce(t, r"prefill_fn")["steps"] == 1
    assert devtrace.reduce(t, r"\bstep_fn")["steps"] == 0
