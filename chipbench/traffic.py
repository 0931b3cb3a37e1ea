"""The one generator that turns a traffic file into requests.

A traffic file (``traffic/<name>.json``) holds parameters only:

* ``arrival``: ``"poisson"`` — open loop, requests fall due at
  ``rate_rps`` whatever the server does; or ``"backlog"`` — the queue is
  topped up to ``queue_per_lane`` requests per lane before every step, so
  the server never waits for work.
* ``lengths``: ``{"dist": "lognormal", "median", "sigma", "min", "max"}``
  or ``{"dist": "uniform", "min", "max"}``, in tokens.
* ``answer_lengths`` (optional, for paths that serve tokens): the tokens
  each request asks for, in the grammar of ``lengths``.  Only such a mix
  passes ``answer_len`` to the driver's ``submit``.

Every seed gets the same set of lengths and the same set of gaps between
arrivals, in another order: ``n`` draws are taken at the quantiles
``(i + 0.5) / n`` of the distribution and shuffled by the seed.  So two
seeds offer the same work and differ in its order and in the token ids,
which are uniform over the vocabulary.  The gaps of a Poisson process are
exponential with mean ``1 / rate``, as in the program's
``serving/workload.PoissonArrivals``.  Answer lengths are drawn the same
way on seed streams of their own, so that a mix without them draws every
other number as it did before they existed.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent

# seed streams: one per use, so that each is the same whatever the others draw
WINDOW, WARM, PROFILE, SAMPLE = 1, 2, 3, 4
FIRST_TOKEN = 5      # ids below this are left for special tokens
ANSWER_WINDOW, ANSWER_WARM = 6, 7


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed])


def load(name: str) -> dict:
    return check(json.loads((HERE / "traffic" / f"{name}.json").read_text()), name)


def check(mix: dict, name: str) -> dict:
    """``mix``, where this generator can draw it; raises otherwise."""
    if mix["arrival"] not in ("poisson", "backlog"):
        raise ValueError(f"traffic {name}: unknown arrival {mix['arrival']!r}")
    for key in ["lengths"] + (["answer_lengths"] if "answer_lengths" in mix else []):
        if mix[key]["dist"] not in ("lognormal", "uniform"):
            raise ValueError(f"traffic {name}: unknown {key} distribution")
        if not 1 <= mix[key]["min"] <= mix[key]["max"]:
            raise ValueError(f"traffic {name}: {key} needs 1 <= min <= max")
    return mix


def _quantile(spec: dict, u: np.ndarray) -> np.ndarray:
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    else:
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def lengths(mix: dict, n: int, g: np.random.Generator, lo: int = 1, hi: int = 1 << 30,
            key: str = "lengths"):
    """``n`` lengths of ``mix[key]`` at the stratified quantiles, shuffled;
    ``lo``/``hi`` keep only the part of the distribution inside one
    bucket."""
    spec = dict(mix[key])
    spec["min"], spec["max"] = max(spec["min"], lo), min(spec["max"], hi)
    if spec["min"] > spec["max"]:
        return np.zeros(0, np.int64)
    return g.permutation(_quantile(spec, (np.arange(n) + 0.5) / n))


def tokens(lens, g: np.random.Generator, vocab: int) -> list:
    return [g.integers(FIRST_TOKEN, vocab, size=int(n)).astype(np.int32) for n in lens]


def arrival_times(mix: dict, seconds: float, g: np.random.Generator) -> np.ndarray:
    """Due times in ``[0, seconds)`` of a Poisson stream at ``rate_rps``:
    ``rate * seconds`` exponential gaps at stratified quantiles, shuffled."""
    n = int(round(mix["rate_rps"] * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / mix["rate_rps"]
    t = np.concatenate([[0.0], np.cumsum(g.permutation(gaps))[:-1]])
    return t[t < seconds]


def buckets_reached(mix: dict, buckets) -> list:
    """The buckets that this mix's lengths land in."""
    lo, hi = mix["lengths"]["min"], mix["lengths"]["max"]
    out, below = [], 0
    for b in sorted(buckets):
        if lo <= b and hi > below:
            out.append(b)
        below = b
    return out
