"""Chip smoke test: serve ALBERT-base EdgeBERT on a TPU through the normal
serving path and check it against a plain float32 reference.

    python chip_smoke.py              # one chip: classifier, kernels, decoder
    python chip_smoke.py --chips 4    # only the 4-replica classifier path

Phases (one chip):
  * classifier, ALBERT-base widths (12 shared layers, d=768, 12x64 heads,
    d_ff=3072, vocab 30000), seeded weights with the MLP pruned at 128x128
    tiles, 8 lanes over buckets 16-128, a shared-clock DVFS arbiter attached.
    Served with ``use_pallas`` off and on, once as published and once with
    activation quantization off; every request's exit layer and logits are
    compared with ``Model.apply_train``'s all-layer off-ramps at the
    request's native length (dense, batch-at-once, no lanes or kernels);
  * kernels: the span kernel's dense-attention form against the jnp
    attention, at buckets 16 and 128;
  * decoder: plain and per-token early-exit decode at the deepseek_7b smoke
    config against an isolated per-request decode.
Each phase drains twice; the second drain of the same shapes must trigger
no compile, counted from JAX's own compile events.

The script refuses to run without a TPU.  Its last line is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import base64
import collections
import dataclasses
import json
import os
import re
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

LANES = 8
BUCKETS = (16, 32, 64, 128)
# request lengths: below, at and between the buckets, so padding is exercised
LENGTHS = (8, 13, 16, 27, 32, 50, 64, 100, 128)
# Served and reference paths both run float32 matmuls at "highest" precision
# (the served steps take it from the config's dtype:
# step_math.matmul_precision), so what differs is summation order: lanes
# padded to a bucket vs the native length, Pallas tiles vs XLA fusions.
#
# The config with activation quantization off is continuous in its inputs:
# f32 reassociation through 12 layers stays far below 1e-4, while a single
# bf16 matmul pass (the TPU default for f32) moves its logits by ~1e-2.  This
# is the check that pins the precision.
F32_ATOL = 1e-4
# The config as published rounds activations to 8-bit AdaptivFloat after
# every layer.  That rounding is discontinuous: a last-bit difference moves
# an element by a whole step (1/16 of its binade), and the flips cascade
# through up to 12 layers (1.5e-2 worst case measured on a TPU v5e, seed 0).
# Exit layers must still agree.
AF8_ATOL = 5e-2
ATTN_ATOL = 1e-4           # f32 online softmax vs the chunked f32 reference
DECODE_LOGIT_ATOL = 1e-4   # as tests/test_decoder_early_exit.py


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Records failed checks, so that one chip run reports on every phase."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok, msg: str) -> bool:
        if not ok:
            self.failed.append(msg)
            log(f"FAIL: {msg}")
        return bool(ok)


# ---------------------------------------------------------------------------
# Classifier
# ---------------------------------------------------------------------------


def classifier_model(seed: int, cfg=None):
    """The full-width config, float32, its seeded params with the MLP pruned
    at 128x128 tiles to the config's encoder sparsity (so the served step's
    block-sparse kernel has tiles to skip)."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.core.pruning import magnitude_mask
    from repro.models.model import build_model

    if cfg is None:
        cfg = dataclasses.replace(
            get_config("albert_edgebert"), dtype="float32", remat_policy="none"
        )
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    mlp = params["layer"]["mlp"]
    sparsity = cfg.edgebert.prune.encoder_sparsity
    params["layer"]["mlp"] = {
        k: w * magnitude_mask(w, sparsity, block_size=128).astype(w.dtype)
        if min(w.shape) % 128 == 0 else w
        for k, w in mlp.items()
    }
    return cfg, model, jax.tree_util.tree_map(jnp.asarray, params)


def requests_for(cfg, repeats: int, seed: int):
    """``repeats`` seeded SyntheticCLS sentences of each length in LENGTHS."""
    from repro.data.synthetic import SyntheticCLS

    n = len(LENGTHS) * repeats
    toks = SyntheticCLS(cfg.vocab_size, max(LENGTHS), n, num_classes=3, seed=seed).batch(0)["tokens"]
    return [toks[i][: LENGTHS[i % len(LENGTHS)]] for i in range(n)]


def reference(model, params, sentences, precision="highest"):
    """Per-request all-layer off-ramp logits [L, C] and entropies [L] from
    ``Model.apply_train`` at each request's native length."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def one_length(p, toks):
        def one(t):
            out = model.apply_train(p, {"tokens": t[None]})
            return out.all_cls_logits[:, 0], out.all_entropies[:, 0]
        return jax.vmap(one)(toks)

    groups = collections.defaultdict(list)
    for i, s in enumerate(sentences):
        groups[len(s)].append(i)
    logits, ents = [None] * len(sentences), [None] * len(sentences)
    with jax.default_matmul_precision(precision):
        for idx in groups.values():
            lg, en = one_length(params, jnp.asarray(np.stack([sentences[i] for i in idx])))
            for j, i in enumerate(idx):
                logits[i], ents[i] = np.asarray(lg[j]), np.asarray(en[j])
    return logits, ents


def exit_layer(ents: np.ndarray, thr: float) -> int:
    below = np.nonzero(ents < thr)[0]
    return int(below[0]) + 1 if below.size else len(ents)


def pick_threshold(ref_ents) -> float:
    """An entropy threshold that spreads the reference's exits over the most
    depths, then sits furthest from any observed entropy: a midpoint
    between neighbouring sorted entropies of the dense profiling pass.
    Random off-ramps sit near log 3, so no fixed value would spread them."""
    e = np.sort(np.concatenate(ref_ents))
    best = None
    for k in np.linspace(0.05, 0.95, 37) * (len(e) - 2):
        lo, hi = e[int(k)], e[int(k) + 1]
        thr = float((lo + hi) / 2)
        key = (len({exit_layer(x, thr) for x in ref_ents}), float(hi - lo))
        if best is None or key > best[0]:
            best = (key, thr)
    return best[1]


def make_arbiter(cfg):
    """Shared-clock DVFS, wired as examples/serve_multitask.py wires it."""
    from repro.core.early_exit import OnlineExitCalibrator
    from repro.hwmodel.edgebert_accel import albert_layer_stats
    from repro.serving.dvfs import (
        BatchedDVFSArbiter, LatencyAwareDVFSController, no_early_exit_baseline,
    )

    hw = albert_layer_stats(seq_len=max(BUCKETS))
    hw.n_layers = cfg.n_layers
    ctrl = LatencyAwareDVFSController(
        hw, no_early_exit_baseline(hw)["latency_s"] * 1.5,
        online_calibrator=OnlineExitCalibrator(cfg.n_layers, hi=float(np.log(3)) + 0.1),
    )
    return BatchedDVFSArbiter(ctrl)


def drain(srv, sentences, uid0=0):
    """submit -> step -> poll until idle; returns {uid: Request}."""
    from repro.serving.engine import Request

    for i, s in enumerate(sentences):
        srv.submit(Request(uid=uid0 + i, tokens=s))
    done = {}
    while srv.step() is not None:
        done.update((r.uid, r) for r in srv.poll())
    done.update((r.uid, r) for r in srv.poll())
    if len(done) != len(sentences):
        raise RuntimeError(f"drained {len(done)} of {len(sentences)} requests")
    return done


def compare(check, done, ref_logits, ref_ents, thr, atol, uid0=0):
    """Exit layers equal, except where the reference entropy sits within
    ``atol`` of the threshold (entropies carry the logits' error), and
    logits within ``atol`` at the served exit.  Returns the max error by
    exit layer and the count of exits that differ at the threshold."""
    err, near = collections.Counter(), 0
    for i, (lg, en) in enumerate(zip(ref_logits, ref_ents)):
        r = done[uid0 + i]
        want = exit_layer(en, thr)
        if r.exit_layer != want:
            k = min(r.exit_layer, want) - 1
            check(abs(en[k] - thr) <= atol,
                  f"request {i}: served exit {r.exit_layer}, reference {want}, "
                  f"reference entropy {en[k]} vs threshold {thr}")
            near += 1
        e = float(np.abs(np.asarray(r.result) - lg[r.exit_layer - 1]).max())
        err[r.exit_layer] = max(err[r.exit_layer], e)
    worst = max(err.values())
    check(worst <= atol, f"max logit error {worst} > {atol}")
    return dict(sorted(err.items())), near


def landed_kernels(hlo_text: str) -> collections.Counter:
    """Pallas kernels in compiled HLO: each ``tpu_custom_call`` carries its
    Mosaic module, which names the kernel function."""
    names = collections.Counter()
    for body in re.findall(r'tpu_custom_call.*?"body":"([A-Za-z0-9+/=]+)"', hlo_text):
        found = re.findall(rb"_[a-z_]+_kernel", base64.b64decode(body))
        names[found[0].decode() if found else "?"] += 1
    return names


def fused_step_kernels(cfg, model, params, bucket):
    """Which kernels land in the ``use_pallas=True`` fused step's compiled
    HLO at ``bucket`` (an AOT compile of the step the server jits)."""
    import jax.numpy as jnp

    from repro.kernels import dispatch
    from repro.serving import step_math

    masks = dispatch.mlp_block_masks(params["layer"]["mlp"])
    step = step_math.jit_at_config_precision(
        cfg, lambda p, h, a, n, t: step_math.classifier_fused_step(
            model, p, h, a, n, t, use_pallas=True, block_masks=masks
        ),
    )
    compiled = step.lower(
        params, jnp.zeros((LANES, bucket, cfg.d_model), jnp.float32),
        jnp.ones((LANES,), bool), jnp.full((LANES,), bucket, jnp.int32),
        jnp.float32(0.0),
    ).compile()
    return landed_kernels(compiled.as_text())


def classifier_variant(check, label, cfg, params, sentences, atol):
    """Dense reference, threshold, and a cold and a warm drain with
    ``use_pallas`` off and on, for one config.  Returns the config with
    its threshold, and the reference logits."""
    from repro.common.compilation import CompileCounter
    from repro.models.model import build_model
    from repro.serving.engine import ClassifierServer

    t0 = time.time()
    ref_logits, ref_ents = reference(build_model(cfg), params, sentences)
    # the threshold comes from this dense profiling pass
    thr = pick_threshold(ref_ents)
    want = collections.Counter(exit_layer(e, thr) for e in ref_ents)
    log(f"classifier, {label}: reference {time.time() - t0:.1f}s; median "
        "entropy by layer " + " ".join(
            f"{x:.4f}" for x in np.median(np.stack(ref_ents), axis=0))
        + f"; threshold {thr:.6f}; exit histogram {dict(sorted(want.items()))}")
    check(len(want) >= 2, f"{label}: reference exits at one depth only: {want}")
    cfg = cfg.with_edgebert(early_exit=dataclasses.replace(
        cfg.edgebert.early_exit, entropy_threshold=thr))
    model = build_model(cfg)
    for use_pallas in (False, True):
        srv = ClassifierServer(model, params, batch_lanes=LANES, buckets=BUCKETS,
                               arbiter=make_arbiter(cfg), use_pallas=use_pallas)
        t0 = time.time()
        with CompileCounter() as cold:
            done = drain(srv, sentences)
        cold_s = time.time() - t0
        err, near = compare(check, done, ref_logits, ref_ents, thr, atol)
        exits = collections.Counter(r.exit_layer for r in done.values())
        with CompileCounter() as warm:
            done = drain(srv, sentences, uid0=len(sentences))
        compare(check, done, ref_logits, ref_ents, thr, atol, uid0=len(sentences))
        tel = srv.telemetry()
        log(f"classifier, {label}, use_pallas={use_pallas}: cold drain {cold_s:.1f}s "
            f"({cold.count} compiles, {cold.seconds:.1f}s compiling; step compiles "
            f"{cold.by_name['jit(step_fn)']} for {len(tel['step_traces_per_bucket'])} "
            f"buckets); warm drain compiles {warm.count}; max |logit err| by exit "
            "layer {" + ", ".join(f"{k}: {v:.3e}" for k, v in err.items())
            + f"}} (tolerance {atol:.0e}); exits differing at the threshold {near}; "
            f"exit histogram {dict(sorted(exits.items()))}; modeled energy "
            f"{tel['energy_j']:.4e} J")
        check(warm.count == 0, f"warm drain compiled {warm.count}: {warm.by_name}")
        check(cold.by_name["jit(step_fn)"] == len(tel["step_traces_per_bucket"]),
              f"step compiles {cold.by_name['jit(step_fn)']} for "
              f"{len(tel['step_traces_per_bucket'])} buckets")
    return cfg, ref_logits


def classifier_phase(check, seed: int):
    from repro.models.model import build_model
    from repro.serving import step_math

    cfg, _, params = classifier_model(seed)
    sentences = requests_for(cfg, repeats=4, seed=seed)
    log(f"classifier: {cfg.name} L={cfg.n_layers} d={cfg.d_model} "
        f"heads={cfg.n_heads}x{cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"dtype={cfg.dtype} matmul_precision={step_math.matmul_precision(cfg)}; "
        f"{len(sentences)} requests, lengths {sorted(set(map(len, sentences)))}, "
        f"buckets {BUCKETS}, {LANES} lanes")
    served_cfg, _ = classifier_variant(
        check, "AF8 activations (as published)", cfg, params, sentences, AF8_ATOL)
    f32_cfg = cfg.with_edgebert(quant=dataclasses.replace(
        cfg.edgebert.quant, quantize_activations=False))
    _, ref32 = classifier_variant(
        check, "activation quantization off", f32_cfg, params, sentences, F32_ATOL)
    # F32_ATOL must catch a step computed below the config's precision: the
    # same reference at the backend's default (one bf16 pass) misses it
    longest = [i for i, s in enumerate(sentences) if len(s) == max(LENGTHS)]
    low, _ = reference(build_model(f32_cfg), params, [sentences[i] for i in longest],
                       precision="default")
    low_err = max(float(np.abs(a - ref32[i]).max()) for a, i in zip(low, longest))
    log(f"default-precision reference vs highest (activation quantization off): "
        f"max |logit diff| {low_err:.3e} (must exceed {F32_ATOL:.0e})")
    check(low_err > F32_ATOL, "the f32 tolerance would not catch bf16 matmuls")
    kernels = fused_step_kernels(served_cfg, build_model(served_cfg), params, max(BUCKETS))
    log(f"use_pallas=True fused step (bucket {max(BUCKETS)}) tpu_custom_call "
        f"kernels: {dict(sorted(kernels.items()))}")
    check(kernels, "no Pallas kernel in the fused step")


# ---------------------------------------------------------------------------
# Kernels the classifier config does not route (soft spans keep the jnp
# attention): the span kernel's dense form, as the fused step calls it
# ---------------------------------------------------------------------------


def attention_phase(check, seed: int):
    import jax
    import jax.numpy as jnp

    from repro.common.compilation import CompileCounter
    from repro.kernels import dispatch
    from repro.models import layers

    H, DH = 12, 64

    @jax.jit
    def both(q, k, v, n):
        def lane(a, b, c, m, fn):
            return fn(a[None], b[None], c[None], causal=False, kv_len=m)[0]
        got = jax.vmap(lambda *x: lane(*x, dispatch.dense_attention))(q, k, v, n)
        want = jax.vmap(lambda *x: lane(*x, layers.attention))(q, k, v, n)
        return got, want

    for bucket in (16, 128):
        keys = jax.random.split(jax.random.PRNGKey(seed + bucket), 3)
        q, k, v = (jax.random.normal(kk, (LANES, bucket, H, DH)) for kk in keys)
        n = jnp.asarray(np.random.default_rng(seed + bucket).integers(1, bucket + 1, LANES), jnp.int32)
        with jax.default_matmul_precision("highest"):
            got, want = both(q, k, v, n)
            with CompileCounter() as warm:
                both(q, k, v, n)[0].block_until_ready()
        mask = np.arange(bucket)[None, :, None, None] < np.asarray(n)[:, None, None, None]
        err = float(np.abs(np.where(mask, np.asarray(got) - np.asarray(want), 0.0)).max())
        log(f"span kernel (dense form) bucket {bucket}: max |err| {err:.3e} "
            f"(tolerance {ATTN_ATOL:.0e}); warm compiles {warm.count}")
        check(err <= ATTN_ATOL, f"span kernel error {err} > {ATTN_ATOL}")
        check(warm.count == 0, f"attention warm compiles {warm.by_name}")


# ---------------------------------------------------------------------------
# Decoder
# ---------------------------------------------------------------------------


def isolated_decode(model, params, prompt, max_new, bucket, threshold):
    """One request alone, as tests/test_decoder_early_exit.py decodes it:
    full-depth prefill, then per-token (early-exit) decode."""
    import jax
    import jax.numpy as jnp

    cache = model.init_cache(1, bucket)
    with jax.default_matmul_precision("highest"):
        for t in range(len(prompt) - 1):
            _, cache = model.decode_step(params, cache, jnp.asarray([[int(prompt[t])]]), t)
        pos, cur = len(prompt) - 1, int(prompt[-1])
        toks, exits, lgs = [], [], []
        for _ in range(max_new):
            if threshold is None:
                lg, cache = model.decode_step(params, cache, jnp.asarray([[cur]]), pos)
                xl = model.cfg.n_layers
            else:
                lg, cache, xl, _ = model.decode_step_ee(
                    params, cache, jnp.asarray([[cur]]), pos, threshold)
                xl = int(xl[0])
            last = np.asarray(lg[0, -1])
            cur = int(np.argmax(last))
            toks.append(cur)
            exits.append(xl)
            lgs.append(last)
            pos += 1
            if pos >= bucket - 1:
                break
    return toks, exits, lgs


def decoder_phase(check, seed: int):
    import jax

    from repro.common.compilation import CompileCounter
    from repro.configs.base import get_smoke_config
    from repro.models.model import build_model
    from repro.serving.engine import DecoderServer, Request, probe_exit_threshold

    cfg = dataclasses.replace(get_smoke_config("deepseek_7b"), dtype="float32",
                              remat_policy="none", n_layers=4)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed + 1))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(4, cfg.vocab_size, size=L).astype(np.int32)
               for L in (6, 5, 7, 4, 6, 8)]
    max_new, bucket = 4, 16
    thr = probe_exit_threshold(model, params, prompts, max_new_tokens=max_new)
    for threshold in (None, thr):
        srv = DecoderServer(model, params, batch_lanes=2, max_seq=32, eos_id=-1,
                            buckets=(bucket,), exit_threshold=threshold)
        t0 = time.time()
        runs = []
        for rnd in range(2):
            with CompileCounter() as cc:
                for i, p in enumerate(prompts):
                    srv.submit(Request(uid=100 * rnd + i, tokens=p, max_new_tokens=max_new))
                srv.run()
            runs.append(cc)
            if rnd == 0:
                cold_s = time.time() - t0
        exits_seen, err = set(), 0.0
        for i, p in enumerate(prompts):
            toks, exits, lgs = isolated_decode(model, params, p, max_new, bucket, threshold)
            for rnd in range(2):
                r = srv.done[100 * rnd + i]
                check(r.generated == toks,
                      f"decoder request {i}: tokens {r.generated} vs isolated {toks}")
                if threshold is not None:
                    check(r.token_exit_layers == exits, f"decoder request {i}: "
                          f"exits {r.token_exit_layers} vs isolated {exits}")
                    if r.generated == toks:
                        err = max(err, float(np.abs(r.result - lgs[-1]).max()))
            exits_seen.update(exits)
        check(err <= DECODE_LOGIT_ATOL, f"decoder logit error {err} > {DECODE_LOGIT_ATOL}")
        mode = "plain" if threshold is None else f"early-exit (threshold {thr:.4f})"
        log(f"decoder {mode}: {cfg.name} L={cfg.n_layers} d={cfg.d_model}; "
            f"cold drain {cold_s:.1f}s ({runs[0].count} compiles, "
            f"{runs[0].seconds:.1f}s compiling); warm drain compiles {runs[1].count}; "
            f"tokens and exits equal to isolated decode; token exit depths "
            f"{sorted(exits_seen)}; max |logit err| {err:.3e}")
        check(runs[1].count == 0, f"decoder warm compiles {runs[1].by_name}")
        if threshold is not None:
            check(len(exits_seen) > 1, f"decoder exits at one depth: {exits_seen}")


# ---------------------------------------------------------------------------
# Four chips: replicas behind one mesh, in this one process
# ---------------------------------------------------------------------------


def replicas_phase(check, seed: int, n_chips: int = 4):
    from repro.common.compilation import CompileCounter
    from repro.models.model import build_model
    from repro.serving.engine import ClassifierServer

    cfg, model, params = classifier_model(seed)
    sentences = requests_for(cfg, repeats=8, seed=seed)
    _, ref_ents = reference(model, params, sentences)
    thr = pick_threshold(ref_ents)
    cfg = cfg.with_edgebert(early_exit=dataclasses.replace(
        cfg.edgebert.early_exit, entropy_threshold=thr))
    model = build_model(cfg)
    done, counts = {}, {}
    for replicas in (1, n_chips):
        srv = ClassifierServer(model, params, batch_lanes=LANES, buckets=BUCKETS,
                               arbiter=make_arbiter(cfg), replicas=replicas)
        on_devices = set()
        orig = srv.lanes_step

        def lanes_step(bucket, active, srv=srv, orig=orig):
            on_devices.update(srv._bstate[bucket]["h"].sharding.device_set)
            return orig(bucket, active)

        srv.lanes_step = lanes_step
        t0 = time.time()
        with CompileCounter() as cold:
            done[replicas] = drain(srv, sentences)
        cold_s = time.time() - t0
        with CompileCounter() as warm:
            drain(srv, sentences, uid0=len(sentences))
        tel = srv.telemetry()
        log(f"replicas={replicas}: {srv.lanes} lanes on {len(on_devices)} device(s); "
            f"cold drain {cold_s:.1f}s ({cold.count} compiles; step compiles "
            f"{cold.by_name['jit(step_fn)']}; step traces per (bucket, replica) "
            f"{tel['step_traces_per_bucket_replica']}); warm drain compiles {warm.count}")
        check(warm.count == 0, f"replicas={replicas} warm compiles {warm.by_name}")
        check(cold.by_name["jit(step_fn)"] == len(tel["step_traces_per_bucket"]),
              f"replicas={replicas}: step compiles {cold.by_name['jit(step_fn)']}")
        check(all(v == 1 for v in tel["step_traces_per_bucket_replica"].values()),
              f"step traces {tel['step_traces_per_bucket_replica']}")
        check(len(on_devices) == replicas, f"bucket state on {on_devices}")
        counts[replicas] = cold.by_name["jit(step_fn)"]
    err, near = 0.0, 0
    for i, en in enumerate(ref_ents):
        a, b = done[1][i], done[n_chips][i]
        if a.exit_layer != b.exit_layer:
            k = min(a.exit_layer, b.exit_layer) - 1
            check(abs(en[k] - thr) <= AF8_ATOL, f"request {i}: exit "
                  f"{a.exit_layer} on 1 replica, {b.exit_layer} on {n_chips}")
            near += 1
            continue
        err = max(err, float(np.abs(np.asarray(a.result) - np.asarray(b.result)).max()))
    log(f"replicas={n_chips} vs replicas=1 on {len(sentences)} requests: max |logit "
        f"diff| {err:.3e} (tolerance {AF8_ATOL:.0e}); exits differing at the "
        f"threshold {near}; step compiles {counts}")
    check(err <= AF8_ATOL, f"replica logit difference {err} > {AF8_ATOL}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the 4-replica classifier path")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights and the requests")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); refusing "
              "to run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} "
              "device(s)", file=sys.stderr)
        return 2

    from repro.common.compilation import setup_compile_cache

    log(f"device_kind {devices[0].device_kind}; {len(devices)} device(s); "
        f"jax {jax.__version__}; compile cache {setup_compile_cache()}")
    t0 = time.time()
    check = Checks()
    if args.chips == 4:
        phases = [replicas_phase]
    else:
        phases = [classifier_phase, attention_phase, decoder_phase]
    for phase in phases:
        try:
            phase(check, args.seed)
        except Exception:                # report it, run the other phases
            traceback.print_exc()
            check(False, f"{phase.__name__} raised")
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
