"""The draws of ``edgebert-mixed-open`` are pinned: its due times, window
prompts, warm-up prompts and seeded sample, for two seeds at the
benchmark's window length, hash to what they hashed to before traffic
files could state answer lengths.  A change to the harness that moves one
of them would make the parent and the change serve different work."""
import hashlib
import json

import numpy as np
import pytest

from chipbench import run, traffic

PINNED = {
    1: {"due": "97aa42336864f49a", "prompts": "b0e403d7cec4daa7",
        "warm": "393ae05d1edbef2b", "sample": "14a16b42961973c6"},
    3_000_000_019: {"due": "b70b8d74e63c0333", "prompts": "d26f6b05b2bea9f1",
                    "warm": "f3d6e4aba8404366", "sample": "0c67f353f9d955d4"},
}


class Recorder:
    """Takes what the harness submits and serves nothing."""

    def __init__(self, cfg, mix):
        self.cfg, self.lanes = cfg, cfg["lanes"]
        self.buckets = traffic.buckets_reached(mix, cfg["buckets"])
        self.got = []

    def submit(self, uid, toks, answer_len=None):
        self.got.append((uid, toks, answer_len))

    def step(self):
        return None

    def poll(self):
        return []


def _digest(items) -> str:
    h = hashlib.sha256()
    for a in items:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def draws(seed: int, **extra) -> dict:
    """Digests of the cell's draws for ``seed``; ``extra`` keys are added
    to its traffic mix."""
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    _, _, cfg, mix = run.load_cell("edgebert-mixed-open")
    mix = traffic.check(dict(mix, **extra), "mixed_open")
    vocab = cfg["vocab_size"]
    rec = Recorder(cfg, mix)
    load = run.Load(rec, mix, bench["run_seconds"], seed, vocab)
    for t in load.due:
        load.submit(rec, t)
    load.answers = {u: None for u in range(len(load.sent))}
    warm = Recorder(cfg, mix)
    run.warm(warm, mix, seed, vocab)
    assert all((n is None) == (not extra) for _, _, n in rec.got + warm.got)
    return {"due": _digest([load.due]),
            "prompts": _digest(t for _, t, _ in rec.got),
            "warm": _digest(a for u, t, _ in warm.got for a in (np.array([u]), t)),
            "sample": _digest([np.array(run.sample_uids(load, seed), np.int64)])}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_mixed_open_draws_are_pinned(seed):
    assert draws(seed) == PINNED[seed]


def test_answer_lengths_leave_the_other_draws_alone():
    """A mix that states answer lengths draws them on streams of their own:
    its due times and prompts are those of the same mix without them."""
    got = draws(1, answer_lengths={"dist": "uniform", "min": 1, "max": 64})
    assert {k: got[k] for k in ("due", "prompts", "warm")} == \
        {k: PINNED[1][k] for k in ("due", "prompts", "warm")}
