"""Serving driver: early-exit classification (the paper's workload) or LM
decode, via the continuation-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch albert_edgebert --smoke \
        --requests 32 --threshold 0.4
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.common.compilation import setup_compile_cache
from repro.common.util import logger
from repro.configs.base import get_config, get_smoke_config
from repro.data.synthetic import SyntheticCLS, SyntheticLM
from repro.models.model import build_model
from repro.serving.engine import ClassifierServer, DecoderServer, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="albert_edgebert")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--threshold", type=float, default=None)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(cfg, dtype="float32", remat_policy="none")
    if args.threshold is not None and cfg.edgebert.early_exit.enabled:
        cfg = cfg.with_edgebert(
            early_exit=dataclasses.replace(
                cfg.edgebert.early_exit, entropy_threshold=args.threshold
            )
        )
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))

    t0 = time.time()
    if cfg.family == "albert" and cfg.edgebert.early_exit.enabled:
        data = SyntheticCLS(cfg.vocab_size, args.seq, args.requests,
                            num_classes=cfg.edgebert.early_exit.num_classes, seed=args.seed)
        batch = data.batch(0)
        server = ClassifierServer(model, params, batch_lanes=args.lanes)
        for i in range(args.requests):
            server.submit(Request(uid=i, tokens=batch["tokens"][i]))
        stats = server.run()
        logger.info(
            "served %d sentences: avg_exit=%.2f/%d runtime_savings=%.1f%% layer_calls=%d (%.1fs)",
            stats["sentences"], stats["avg_exit_layer"], cfg.n_layers,
            100 * stats["runtime_savings"], stats["layer_calls"], time.time() - t0,
        )
    else:
        data = SyntheticLM(cfg.vocab_size, args.seq, args.requests, seed=args.seed)
        batch = data.batch(0)
        server = DecoderServer(model, params, batch_lanes=args.lanes, max_seq=args.seq + args.max_new_tokens + 8)
        for i in range(args.requests):
            server.submit(Request(uid=i, tokens=batch["tokens"][i][:16],
                                  max_new_tokens=args.max_new_tokens))
        stats = server.run()
        logger.info("decode: %s (%.1fs)", stats, time.time() - t0)


if __name__ == "__main__":
    main()
