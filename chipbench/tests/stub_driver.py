"""A second driver, for tests only, to the contract in
``chipbench/drivers/__init__.py``: it shows that a cell served by a driver
other than the classifier runs through ``run.main``, the smoke patch, the
fault tests and the compile check with no file of the harness edited.
The tests register it as ``chipbench.drivers.stub`` and add ``CELL`` to
what ``run.load_cell`` reads (``conftest.stub_cell``).

It serves a toy decoder in two programs, as a decoder path does.
``prefill_fn`` folds a prompt into one lane's state: the mean of its
token embeddings through ``W``.  ``decode_step_fn`` advances every lane by
one token: ``h' = tanh(h @ W + E[tok])``, logits ``h' @ U``, the next
token their greedy argmax.  A request asks for ``answer_len`` tokens
(the traffic's ``answer_lengths``), and its answer is those tokens.  The
reference recomputes each served token's logits in plain ``jax.numpy``,
one request at a time; ``token_gap`` is the widest gap by which a served
token's logit lies below the reference's best, and ``length_mismatch``
counts answers of another length than asked.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic

CONFIG = {
    "name": "stub_decoder", "driver": "stub", "vocab_size": 256, "hidden_size": 64,
    "lanes": 4, "buckets": [32], "weights_seed": 0,
    "checks": {"token_gap": 1e-3},
}
MIX = {
    "arrival": "poisson", "rate_rps": 200.0,
    "lengths": {"dist": "uniform", "min": 4, "max": 32},
    "answer_lengths": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2, "max": 16},
}
CELL = {"name": "stub-decoder-open", "config": "stub_decoder", "traffic": "stub_open",
        "chips": 1, "why": "a toy decoder through a second driver: prefill, then one token "
                           "per lane and step"}

SMOKE = {"hidden_size": 16}


def make_weights(cfg: dict, sharding):
    D, V = cfg["hidden_size"], cfg["vocab_size"]

    def make(seed):
        k = jax.random.split(jax.random.PRNGKey(seed), 3)
        return {"E": jax.random.normal(k[0], (V, D)), "W": jax.random.normal(k[1], (D, D)) / D,
                "U": jax.random.normal(k[2], (D, V))}

    return jax.jit(make, out_shardings=sharding)(cfg["weights_seed"])


def prefill_fn(p, h, lane, toks, n):
    """Lane ``lane`` of state ``h`` set from prompt ``toks[:n]`` (padded)."""
    keep = (jnp.arange(toks.shape[0]) < n)[:, None]
    row = jnp.tanh((p["E"][toks] * keep).sum(0) / n @ p["W"])
    return h.at[lane].set(row)


def decode_step_fn(p, h, tok):
    """Every lane one token on: the new state and the greedy next token."""
    h = jnp.tanh(h @ p["W"] + p["E"][tok])
    return h, jnp.argmax(h @ p["U"], axis=-1).astype(jnp.int32)


class System:
    step_program = r"decode_step_fn"

    def __init__(self, cfg: dict, mix: dict, devices):
        self.cfg, self.lanes = cfg, cfg["lanes"]
        self.buckets = traffic.buckets_reached(mix, cfg["buckets"])
        self.reference_s = 0.0
        self.pad = max(cfg["buckets"])
        self.params = make_weights(cfg, jax.sharding.SingleDeviceSharding(devices[0]))
        self._prefill, self._decode = jax.jit(prefill_fn), jax.jit(decode_step_fn)
        self.h = jax.device_put(jnp.zeros((self.lanes, cfg["hidden_size"])), devices[0])
        self.tok = np.zeros(self.lanes, np.int32)
        self.slots = [None] * self.lanes      # [uid, asked, tokens so far]
        self.waiting = collections.deque()
        self.done = []
        self.steps = self.lane_steps = 0

    # ------------------------------------------------------------ serving
    def submit(self, uid: int, toks: np.ndarray, answer_len=None) -> None:
        if answer_len is None:
            raise ValueError("a decoder needs each request's answer_len")
        self.waiting.append((uid, toks, answer_len))

    def _advance(self, h, tok):
        h, nxt = self._decode(self.params, h, tok)
        return h, np.array(nxt)

    def _retire(self, lane: int) -> None:
        uid, asked, toks = self.slots[lane]
        self.done.append((uid, (np.array(toks, np.int32), asked)))
        self.slots[lane] = None

    def step(self):
        for lane, slot in enumerate(self.slots):
            if slot is None and self.waiting:
                uid, toks, asked = self.waiting.popleft()
                padded = np.zeros(self.pad, np.int32)
                padded[:len(toks)] = toks
                self.h = self._prefill(self.params, self.h, lane, padded, len(toks))
                self.tok[lane] = toks[-1]
                self.slots[lane] = [uid, asked, []]
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return None
        self.h, nxt = self._advance(self.h, self.tok)
        self.tok = nxt
        for lane in active:
            self.slots[lane][2].append(int(nxt[lane]))
            if len(self.slots[lane][2]) == self.slots[lane][1]:
                self._retire(lane)
        self.steps += 1
        self.lane_steps += len(active)
        return "decode"

    def poll(self) -> list:
        out, self.done = self.done, []
        return out

    @property
    def queued(self) -> int:
        return len(self.waiting) + sum(s is not None for s in self.slots)

    def counters(self) -> dict:
        return {"dense_steps": self.steps, "layer_calls": self.lane_steps}

    def close(self) -> None:
        self.h = None

    def notes(self) -> dict:
        return {"tokens_served": self.lane_steps}

    # --------------------------------------------------------------- work
    def _token_flops(self) -> float:
        D, V = self.cfg["hidden_size"], self.cfg["vocab_size"]
        return 2.0 * D * D + 2.0 * D * V

    def step_cost(self, label) -> tuple:
        D, V = self.cfg["hidden_size"], self.cfg["vocab_size"]
        return self.lanes * self._token_flops(), 4.0 * (D * D + 2 * V * D + 2 * self.lanes * D)

    def answer_flops(self, toks, answer) -> float:
        D = self.cfg["hidden_size"]
        return 2.0 * D * D + len(answer[0]) * self._token_flops()

    # -------------------------------------------------------------- check
    def _reference_logits(self, prompt, served, dtype=jnp.float32) -> list:
        """Each served position's logits, recomputed one op at a time."""
        p = {k: jnp.asarray(v, dtype) for k, v in self.params.items()}
        h = jnp.tanh(p["E"][jnp.asarray(prompt)].mean(0) @ p["W"])
        tok, out = int(prompt[-1]), []
        for t in served:
            h = jnp.tanh(h @ p["W"] + p["E"][tok])
            out.append(np.asarray(h @ p["U"], np.float64))
            tok = int(t)
        return out

    def answer_errors(self, prompts, answers) -> np.ndarray:
        gaps = []
        for prompt, (toks, _) in zip(prompts, answers):
            logits = self._reference_logits(prompt, toks)
            gaps.append(max((lg.max() - lg[t] for lg, t in zip(logits, toks)), default=0.0))
        return np.array(gaps)

    def check(self, prompts, answers) -> dict:
        gaps = self.answer_errors(prompts, answers)
        short = sum(len(toks) != asked for toks, asked in answers)
        return {"token_gap": (float(gaps.max(initial=0.0)), self.cfg["checks"]["token_gap"]),
                "length_mismatch": (short, 0)}

    def control_answers(self, prompts, answers, precision: str = "bf16") -> list:
        """The tokens the reference in bfloat16 puts first at each served
        position of the same prompts and answers."""
        return [(np.array([int(lg.argmax()) for lg in
                           self._reference_logits(prompt, toks, jnp.bfloat16)], np.int32), asked)
                for prompt, (toks, asked) in zip(prompts, answers)]


# ---------------------------------------------------------------- faults
def _patched(method, wrap):
    def plant(monkeypatch, cfg):
        monkeypatch.setattr(System, method, wrap(getattr(System, method)))
    return plant


def _unchanged_state(orig):
    def advance(self, h, tok):
        return h, orig(self, h, tok)[1]          # the step's new state is dropped
    return advance


def _half_the_lanes(orig):
    def advance(self, h, tok):
        # the step computes the first half of the lanes only
        new, nxt = orig(self, h, tok)
        half = self.lanes // 2
        nxt[half:] = 0
        return new.at[half:].set(h[half:]), nxt
    return advance


def _altered_answer(orig):
    def advance(self, h, tok):
        new, nxt = orig(self, h, tok)
        nxt[0] = (nxt[0] + 1) % self.cfg["vocab_size"]    # lane 0's token, as produced
        return new, nxt
    return advance


def _truncated_answer(orig):
    def retire(self, lane):
        self.slots[lane][2] = self.slots[lane][2][:-1]   # the last token left out
        return orig(self, lane)
    return retire


FAULTS = {"unchanged_state": _patched("_advance", _unchanged_state),
          "half_the_lanes": _patched("_advance", _half_the_lanes),
          "altered_answer": _patched("_advance", _altered_answer),
          "truncated_answer": _patched("_retire", _truncated_answer)}


# --------------------------------------------------------------- compile
def compile_programs(cfg: dict, mix: dict, devices, chips: int) -> dict:
    one = jax.sharding.SingleDeviceSharding(devices[0])
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["lanes"]

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    p = {"E": spec((V, D)), "W": spec((D, D)), "U": spec((D, V))}
    i32 = jnp.int32
    return {
        "prefill": jax.jit(prefill_fn).lower(p, spec((L, D)), spec((), i32),
                                             spec((max(cfg["buckets"]),), i32),
                                             spec((), i32)).compile(),
        "decode": jax.jit(decode_step_fn).lower(p, spec((L, D)), spec((L,), i32)).compile(),
    }
