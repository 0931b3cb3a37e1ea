"""The control: the plain reference one precision below the configuration's
("high", three bf16 passes, for float32 at "highest"), put in the program's
place, must come out not correct by the comparison every run makes."""
import json

import jax
import numpy as np
import pytest

from chipbench import run, traffic
from chipbench.drivers import classifier
from chipbench.references import albert

CONFIGS = ["albert_edgebert"]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed", [5, 2**31 + 11, 4_000_000_019])
def test_control_fails_the_check(name, seed):
    cfg = json.loads((run.HERE / "configs" / f"{name}.json").read_text())
    params = classifier.make_weights(
        cfg, cfg["weights_seed"], jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    mix = traffic.load("mixed_open")
    g = traffic.rng(seed, traffic.SAMPLE)
    prompts = traffic.tokens(traffic.lengths(mix, 64, g), g, cfg["vocab_size"])
    ref = albert.run(cfg, params, prompts, block=16, pad=128)
    low = albert.run(cfg, params, prompts, block=16, pad=128, precision="high")
    thr = cfg["entropy_threshold"]
    if thr is None:
        thr = classifier.profiled_threshold(ref[1], cfg["threshold_profile"]["mean_exit_layer"])

    def answers(logits, ents):
        out = []
        for lg, en in zip(logits, ents):
            ex = classifier.exit_layer(en, thr)
            out.append((lg[ex - 1], ex))
        return out

    sound = classifier.compare(answers(*ref), *ref, thr, cfg["checks"])
    control = classifier.compare(answers(*low), *ref, thr, cfg["checks"])
    assert all(v <= lim for v, lim in sound.values())
    assert not all(v <= lim for v, lim in control.values()), control
    print(name, seed, "control", control)
