"""Drives the program's early-exit classifier path for one configuration:
``ClassifierServer.submit -> step -> poll``, on one chip or, with
``chips > 1``, as that many lane-sharded replicas over a mesh.

The driver builds what the configuration file states and nothing else:
the weights of its ``weights_seed``, made on the device, the entropy
threshold, the DVFS arbiter where the file asks for one, and the server.
A deployment serves one model: every run serves the same one, so that the
run's seed, which draws the traffic, does not change the work a request
brings.  The harness (``chipbench/run.py``) owns the clock, the traffic
and the trace; this module owns what is particular to a classifier: its
answers, their comparison with the plain reference, the work of a step,
and for the tests its smoke width, its planted faults and the compile of
its programs (the contract is in ``drivers/__init__.py``).
"""
from __future__ import annotations

import dataclasses
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, traffic

PROFILE_BLOCK = 8     # rows per reference call: the reference's memory stays
                      # near one served step's, below the window's peak

# a width the CPU runs in seconds: every cut dimension still a multiple of
# the 128-wide pruning tile
SMOKE = dict(hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
             intermediate_size=256, embedding_size=32, vocab_size=512,
             max_position_embeddings=128, threshold_profile={"requests": 32})


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for this configuration file."""
    from repro.configs.base import (
        EarlyExitConfig, EdgeBertConfig, ModelConfig, PruneConfig, QuantConfig, SpanConfig,
    )

    if cfg["layer_norm_eps"] != 1e-6:
        raise ValueError("the program's LayerNorm epsilon is fixed at 1e-6")
    if cfg["type_vocab_size"]:
        raise ValueError("the program has no token-type embedding")
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    quant = cfg["quantize_activations"]
    edgebert = EdgeBertConfig(
        quant=QuantConfig(enabled=quant, n_bits=cfg["af_bits"], n_exp=cfg["af_exp_bits"],
                          quantize_activations=quant),
        span=SpanConfig(enabled=cfg["span_enabled"], max_span=cfg["span_max"],
                        ramp=cfg["span_ramp"]),
        early_exit=EarlyExitConfig(enabled=True, num_classes=cfg["num_labels"],
                                   entropy_threshold=cfg["entropy_threshold"] or 0.0),
        prune=PruneConfig(enabled=cfg["encoder_sparsity"] > 0,
                          encoder_sparsity=cfg["encoder_sparsity"],
                          embedding_sparsity=cfg["embedding_sparsity"],
                          block_size=cfg["prune_block"]),
    )
    return ModelConfig(
        name=cfg["name"], family="albert", n_layers=cfg["num_hidden_layers"], d_model=D,
        n_heads=H, n_kv_heads=H, head_dim=D // H, d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], embed_dim=cfg["embedding_size"], shared_layers=True,
        act="gelu", norm="layernorm", pos="learned",
        max_seq_len=cfg["max_position_embeddings"], num_classes=cfg["num_labels"],
        tie_embeddings=True, dtype=cfg["dtype"], remat_policy="none", edgebert=edgebert,
    )


def _prune_tiles(w, share: float, b: int, key):
    """Zero ``share`` of the ``b x b`` tiles of ``w``: which ones is drawn
    from ``key``, not from the weights' seed, because the program compiles
    its block-sparse kernel for the pattern it finds."""
    K, N = w.shape
    order = jax.random.permutation(key, (K // b) * (N // b))
    keep = (order >= int(round(order.size * share))).reshape(K // b, N // b)
    return w * jnp.repeat(jnp.repeat(keep, b, 0), b, 1).astype(w.dtype)


def _prune_values(w, share: float):
    k = int(round(w.size * share))
    if k == 0:
        return w
    return w * (jnp.abs(w) > jnp.sort(jnp.abs(w).ravel())[k - 1]).astype(w.dtype)


def weight_maker(cfg: dict):
    """A function of two uint32 seed words that makes the served weights
    in the layout the program reads, pruned as the file states.  Jitted by
    the caller into one call on the device."""
    D, F, E = cfg["hidden_size"], cfg["intermediate_size"], cfg["embedding_size"]
    V, C, H = cfg["vocab_size"], cfg["num_labels"], cfg["num_attention_heads"]
    P, dtype = cfg["max_position_embeddings"], jnp.dtype(cfg["dtype"])

    def make(lo, hi):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
        ks = iter(jax.random.split(key, 20))

        def normal(shape, scale):
            return jax.random.normal(next(ks), shape, jnp.float32) * scale

        def norm():
            return {"scale": 1.0 + normal((D,), 0.1), "norm_bias": normal((D,), 0.1)}

        w_up, w_down = normal((D, F), D ** -0.5), normal((F, D), F ** -0.5)
        if cfg["encoder_sparsity"]:
            pattern = jax.random.split(jax.random.PRNGKey(cfg["prune_pattern_seed"]))
            w_up = _prune_tiles(w_up, cfg["encoder_sparsity"], cfg["prune_block"], pattern[0])
            w_down = _prune_tiles(w_down, cfg["encoder_sparsity"], cfg["prune_block"],
                                  pattern[1])
        params = {
            "embed": {"tok": _prune_values(normal((V, E), 0.02), cfg["embedding_sparsity"]),
                      "proj": normal((E, D), E ** -0.5), "pos": normal((P, D), 0.02)},
            "layer": {"norm1": norm(), "norm2": norm(),
                      "attn": {w: normal((D, D), D ** -0.5) for w in ("wq", "wk", "wv", "wo")},
                      "mlp": {"w_up": w_up, "w_down": w_down}},
            "offramp": {"offramp_pooler_w": normal((D, D), D ** -0.5),
                        "offramp_pooler_b": normal((D,), 0.1),
                        "offramp_cls_w": normal((D, C), D ** -0.5),
                        "offramp_cls_b": normal((C,), 0.1)},
        }
        if cfg["span_enabled"]:
            z = jax.random.uniform(next(ks), (1, H), jnp.float32) * cfg["span_max"]
            params["span_z"] = z
        return jax.tree_util.tree_map(lambda a: a.astype(dtype), params)

    return make


def make_weights(cfg: dict, seed: int, sharding):
    """The weights of ``seed``, made in one jitted call placed by ``sharding``."""
    fn = jax.jit(weight_maker(cfg), out_shardings=sharding)
    return fn(np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32))


def make_arbiter(model_cfg, max_bucket: int):
    """Shared-clock DVFS, wired as ``examples/serve_multitask.py`` wires it."""
    from repro.core.early_exit import OnlineExitCalibrator
    from repro.hwmodel.edgebert_accel import albert_layer_stats
    from repro.serving.dvfs import (
        BatchedDVFSArbiter, LatencyAwareDVFSController, no_early_exit_baseline,
    )

    hw = albert_layer_stats(seq_len=max_bucket)
    hw.n_layers = model_cfg.n_layers
    ctrl = LatencyAwareDVFSController(
        hw, no_early_exit_baseline(hw)["latency_s"] * 1.5,
        online_calibrator=OnlineExitCalibrator(model_cfg.n_layers, hi=float(np.log(3)) + 0.1),
    )
    return BatchedDVFSArbiter(ctrl)


def exit_layer(ents: np.ndarray, thr: float) -> int:
    below = np.nonzero(ents < thr)[0]
    return int(below[0]) + 1 if below.size else len(ents)


def mean_exit_layer(ents: np.ndarray, thr: float) -> float:
    below = ents < thr
    return float(np.where(below.any(1), below.argmax(1) + 1, ents.shape[1]).mean())


def profiled_threshold(ents: np.ndarray, mean_exit: float) -> float:
    """The entropy threshold at which the profiling set's mean exit layer
    comes nearest ``mean_exit``, midway between two neighbouring observed
    entropies so that it sits away from every one.  Fixing the mean depth
    fixes the work a request brings.  Off-ramps with random weights read
    near log 3, so no fixed threshold would exit."""
    e = np.unique(ents)
    mids = (e[:-1] + e[1:]) / 2
    lo, hi = 0, len(mids) - 1          # the mean depth falls as the threshold rises
    while lo < hi:
        mid = (lo + hi) // 2
        if mean_exit_layer(ents, mids[mid]) <= mean_exit:
            hi = mid
        else:
            lo = mid + 1
    near = [i for i in (lo - 1, lo) if 0 <= i < len(mids)]
    return float(min((abs(mean_exit_layer(ents, mids[i]) - mean_exit), mids[i])
                     for i in near)[1])


def compare(answers, ref_logits, ref_ents, thr: float, limits: dict) -> dict:
    """Each answer ``(logits, exit_layer)`` against the reference's
    all-layer off-ramps of the same prompt.

    An answer's gap is its largest |served - reference| logit at the
    served exit layer.  ``logit_err``: the widest gap, which catches one
    answer gone wrong.  ``logit_err_mean``, where the configuration states
    its limit: the mean gap, which tells a matmul one precision below the
    configuration's from sound rounding, where the widest gap of either
    lies too close to the other's.
    ``exit_mismatch``: answers whose exit layer differs from the
    reference's, other than where the reference entropy at the earlier of
    the two layers lies within ``exit_band`` of the threshold (the served
    entropy carries the logits' rounding).  Returns name -> (value, limit)."""
    gaps, mismatch = [], 0
    n_layers = ref_ents.shape[1]
    for (lg, ex), rl, re in zip(answers, ref_logits, ref_ents):
        if not 1 <= ex <= n_layers:
            mismatch += 1
            continue
        want = exit_layer(re, thr)
        if not np.isfinite(re).all() or (
                ex != want and abs(re[min(ex, want) - 1] - thr) > limits["exit_band"]):
            mismatch += 1
        gap = float(np.abs(np.asarray(lg, np.float64) - rl[ex - 1]).max())
        gaps.append(gap if np.isfinite(gap) else np.inf)
    out = {"logit_err": (max(gaps, default=0.0), limits["logit_err"]),
           "exit_mismatch": (mismatch, 0)}
    if "logit_err_mean" in limits:
        out["logit_err_mean"] = (float(np.mean(gaps)) if gaps else 0.0,
                                 limits["logit_err_mean"])
    return out


class System:
    """The served path of one configuration."""

    step_program = r"step_fn"      # ``jit(step_fn)``, the fused step

    def __init__(self, cfg: dict, mix: dict, devices):
        from repro.models.model import build_model
        from repro.serving.engine import ClassifierServer

        self.cfg = cfg
        self.chips = len(devices)
        # replicas share one mesh over the cell's chips with the weights,
        # which every replica holds whole
        mesh = jax.sharding.Mesh(np.array(devices), ("data",)) if self.chips > 1 else None
        if mesh is not None:
            sharding = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        else:
            sharding = jax.sharding.SingleDeviceSharding(devices[0])
        self.params = make_weights(cfg, cfg["weights_seed"], sharding)
        self.reference = importlib.import_module(f"chipbench.references.{cfg['reference']}")
        self.ref_pad = max(cfg["buckets"])
        self.buckets = traffic.buckets_reached(mix, cfg["buckets"])
        self.threshold = cfg["entropy_threshold"]
        self.reference_s = 0.0      # the reference's share of the build, kept out of set-up
        if self.threshold is None:
            t = time.perf_counter()
            rule = cfg["threshold_profile"]
            g = traffic.rng(cfg["weights_seed"], traffic.PROFILE)
            prompts = traffic.tokens(traffic.lengths(mix, rule["requests"], g), g,
                                     cfg["vocab_size"])
            _, ents = self.run_reference(prompts)
            self.threshold = profiled_threshold(ents, rule["mean_exit_layer"])
            self.reference_s = time.perf_counter() - t
        mc = model_config(cfg)
        mc = mc.with_edgebert(early_exit=dataclasses.replace(
            mc.edgebert.early_exit, entropy_threshold=self.threshold))
        arbiter = make_arbiter(mc, max(cfg["buckets"])) if cfg["arbiter"] else None
        self.srv = ClassifierServer(
            build_model(mc), self.params, batch_lanes=cfg["lanes"], buckets=cfg["buckets"],
            arbiter=arbiter, use_pallas=cfg["use_pallas"], replicas=self.chips, mesh=mesh,
        )
        self.lanes = self.srv.lanes
        self.layer = flops.Layer(
            d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"], classes=cfg["num_labels"],
            kept_up=1.0 - cfg["encoder_sparsity"], kept_down=1.0 - cfg["encoder_sparsity"],
        )

    # ------------------------------------------------------------ serving
    def submit(self, uid: int, toks: np.ndarray, answer_len=None) -> None:
        from repro.serving.engine import Request

        if answer_len is not None:
            raise ValueError("a classifier answers with one label: its traffic states "
                             "no answer_lengths")
        self.srv.submit(Request(uid=uid, tokens=toks))

    def step(self):
        """One fused step; its bucket, or None when nothing is left to do."""
        report = self.srv.step()
        return None if report is None else report.bucket

    def poll(self) -> list:
        return [(r.uid, (r.result, r.exit_layer)) for r in self.srv.poll()]

    @property
    def queued(self) -> int:
        return self.srv.pending

    def counters(self) -> dict:
        t = self.srv.telemetry()
        return {"layer_calls": t["layer_calls"], "dense_steps": t["dense_steps"]}

    def close(self) -> None:
        self.srv = None

    def notes(self) -> dict:
        return {"threshold": self.threshold}

    # --------------------------------------------------------------- work
    def step_cost(self, bucket: int) -> tuple:
        """(operations, bytes) one chip spends on one fused step."""
        return self.layer.step(self.lanes // self.chips, bucket)

    def answer_flops(self, toks: np.ndarray, answer) -> float:
        """Operations the request needed: its exit depth at its own length."""
        return answer[1] * self.layer.flops(len(toks))

    # -------------------------------------------------------------- check
    def run_reference(self, prompts, precision: str = "highest"):
        return self.reference.run(self.cfg, self.params, prompts, block=PROFILE_BLOCK,
                                  pad=self.ref_pad, precision=precision)

    def check(self, prompts, answers) -> dict:
        logits, ents = self.run_reference(prompts)
        return compare(answers, logits, ents, self.threshold, self.cfg["checks"])

    def answer_errors(self, prompts, answers) -> np.ndarray:
        """Per answer, the largest |answer - reference| logit at its exit."""
        logits, _ = self.run_reference(prompts)
        gaps = np.array([float(np.abs(np.asarray(lg, np.float64) - rl[ex - 1]).max())
                         for (lg, ex), rl in zip(answers, logits)])
        return np.where(np.isfinite(gaps), gaps, np.inf)

    def control_answers(self, prompts, answers, precision: str = "high") -> list:
        """The control: the reference one precision below the
        configuration's, put in the program's place, with its own exits
        (the served ``answers`` are not needed)."""
        logits, ents = self.run_reference(prompts, precision=precision)
        return [(lg[exit_layer(en, self.threshold) - 1], exit_layer(en, self.threshold))
                for lg, en in zip(logits, ents)]


# ---------------------------------------------------------------- faults
# Each is planted in the program's ``ClassifierServer.lanes_step``, the call
# that advances every lane by one fused step and hands the scheduler its
# answers.  The exchange between chips is not among them: the lane-sharded
# step has no collective, replicas never talk to each other.


def unchanged_state(orig, cfg):
    def lanes_step(self, bucket, active):
        h = self._bstate[bucket]["h"]
        out = orig(self, bucket, active)
        self._bstate[bucket]["h"] = h        # the step's new state is dropped
        return out
    return lanes_step


def half_the_lanes(orig, cfg):
    def lanes_step(self, bucket, active):
        step = self._step

        def half_step(params, h, act, lengths, thr):
            # the fused step computes the first half of the lanes only
            return step(params, h, act.at[act.shape[0] // 2:].set(False), lengths, thr)

        self._step = half_step
        try:
            return orig(self, bucket, active)
        finally:
            self._step = step
    return lanes_step


def _rewrite(orig, change):
    def lanes_step(self, bucket, active):
        lg, ent, retire, decision = orig(self, bucket, active)
        lg = change(np.array(lg))
        self._bstate[bucket]["out"] = (lg, ent, retire, decision)
        return self._bstate[bucket]["out"]
    return lanes_step


def altered_answer(orig, cfg):
    # lane 0's logits moved by ten times the limit, where they are produced
    def change(lg):
        lg[0] += 10 * cfg["checks"]["logit_err"]
        return lg
    return _rewrite(orig, change)


def swapped_answers(orig, cfg):
    # the scheduler's bookkeeping: lanes 0 and 1 hand back each other's answer
    return _rewrite(orig, lambda lg: lg[[1, 0] + list(range(2, len(lg)))])


def _planted(wrap):
    def plant(monkeypatch, cfg):
        from repro.serving.engine import ClassifierServer

        monkeypatch.setattr(ClassifierServer, "lanes_step",
                            wrap(ClassifierServer.lanes_step, cfg))
    return plant


FAULTS = {f.__name__: _planted(f)
          for f in (unchanged_state, half_the_lanes, altered_answer, swapped_answers)}


# --------------------------------------------------------------- compile
def compile_programs(cfg: dict, mix: dict, devices, chips: int) -> dict:
    """The embed, lane-insert and fused-step programs of every bucket the
    mix reaches, lowered from the server the cell builds, with its
    full-width weights (the pruned MLP's tile masks come from them, so
    they are made on the host's CPU), and compiled for the first ``chips``
    of ``devices``.  Raises where the compiled step's Pallas kernels
    (``tpu_custom_call``) are not there as ``use_pallas`` states."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from repro.models.model import build_model
    from repro.serving.engine import ClassifierServer

    mc = model_config(cfg)
    params = make_weights(cfg, cfg["weights_seed"],
                          SingleDeviceSharding(jax.devices("cpu")[0]))
    if chips == 1:
        rep = lanes = SingleDeviceSharding(devices[0])
        mesh = None
    else:
        mesh = Mesh(np.array(devices[:chips]), ("data",))
        rep, lanes = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    arbiter = make_arbiter(mc, max(cfg["buckets"])) if cfg["arbiter"] else None
    srv = ClassifierServer(build_model(mc), params, batch_lanes=cfg["lanes"],
                           buckets=cfg["buckets"], use_pallas=cfg["use_pallas"],
                           arbiter=arbiter, mesh=mesh)
    spec = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)  # noqa: E731
    p_spec = jax.tree_util.tree_map(lambda a: spec(a, rep), params)
    D, n = cfg["hidden_size"], srv.lanes
    out = {}
    for b in traffic.buckets_reached(mix, cfg["buckets"]):
        h = jax.ShapeDtypeStruct((n, b, D), jnp.float32, sharding=lanes)
        row = jax.ShapeDtypeStruct((1, b, D), jnp.float32, sharding=rep)
        out[f"embed.{b}"] = srv._embed.lower(
            p_spec, jax.ShapeDtypeStruct((1, b), jnp.int32, sharding=rep)).compile()
        out[f"insert.{b}"] = srv._insert.lower(
            h, jax.ShapeDtypeStruct((), jnp.int32, sharding=rep), row).compile()
        out[f"step.{b}"] = step = srv._step.lower(
            p_spec, h, jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=lanes),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=lanes),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)).compile()
        if ("tpu_custom_call" in step.as_text()) != cfg["use_pallas"]:
            raise AssertionError(f"bucket {b}: the compiled step's Pallas kernels do not "
                                 f"match use_pallas={cfg['use_pallas']}")
    return out
