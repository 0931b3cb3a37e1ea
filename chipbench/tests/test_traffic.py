"""Every seed offers the same work in another order."""
import numpy as np

from chipbench import traffic


def test_seeds_share_lengths_and_gaps():
    mix = traffic.load("mixed_open")
    a = traffic.lengths(mix, 1000, traffic.rng(1, traffic.WINDOW))
    b = traffic.lengths(mix, 1000, traffic.rng(2**31 + 7, traffic.WINDOW))
    assert sorted(a) == sorted(b) and list(a) != list(b)
    ta = traffic.arrival_times(mix, 2.0, traffic.rng(1, traffic.WINDOW))
    tb = traffic.arrival_times(mix, 2.0, traffic.rng(5, traffic.WINDOW))
    # each seed keeps all gaps but the one after its last arrival
    ga, gb = (set(np.round(np.diff(t), 12)) for t in (ta, tb))
    assert len(ga ^ gb) <= 2
    assert len(ta) == round(mix["rate_rps"] * 2.0)
    assert 0.0 == ta[0] and ta[-1] < 2.0


def test_lognormal_buckets_and_clip():
    mix = traffic.load("mixed_open")
    n = traffic.lengths(mix, 10000, traffic.rng(0, traffic.WINDOW))
    assert n.min() == 8 and n.max() == 128
    share = [np.mean((n > lo) & (n <= hi)) for lo, hi in ((0, 16), (16, 32), (32, 64), (64, 128))]
    assert np.allclose(share, [0.13, 0.37, 0.37, 0.13], atol=0.02)
    assert traffic.buckets_reached(mix, [16, 32, 64, 128]) == [16, 32, 64, 128]


def test_uniform_one_bucket():
    mix = traffic.load("long_backlog")
    n = traffic.lengths(mix, 3200, traffic.rng(0, traffic.WINDOW))
    assert set(n) == set(range(97, 129))
    assert traffic.buckets_reached(mix, [16, 32, 64, 128]) == [128]
    assert traffic.buckets_reached(traffic.load("short_backlog"), [16, 32, 64, 128]) == [16]


def test_bucket_slice():
    mix = traffic.load("mixed_open")
    n = traffic.lengths(mix, 64, traffic.rng(0, traffic.WARM), 33, 64)
    assert n.min() >= 33 and n.max() <= 64
