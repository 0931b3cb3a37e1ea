"""The start-up threshold rule and the fixed pruning pattern."""
import json

import jax
import numpy as np

from chipbench import run
from chipbench.drivers import classifier


def test_threshold_hits_the_mean_exit_layer():
    ents = np.random.default_rng(0).uniform(0.8, 1.1, size=(1024, 12))
    for target in (2.0, 5.0, 9.0):
        thr = classifier.profiled_threshold(ents, target)
        assert abs(classifier.mean_exit_layer(ents, thr) - target) < 0.05
        assert (ents != thr).all()


def test_pruning_pattern_is_the_same_for_every_seed():
    cfg = json.loads((run.HERE / "configs" / "albert_edgebert.json").read_text())
    cfg.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
               embedding_size=32, vocab_size=512, max_position_embeddings=128)
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    zero = []
    for seed in (1, 2**31 + 5):
        w = np.asarray(classifier.make_weights(cfg, seed, one)["layer"]["mlp"]["w_up"])
        tiles = np.abs(w).reshape(2, 128, 4, 128).sum((1, 3)) == 0
        zero.append(tiles)
        assert tiles.sum() == 4                                   # half of 8 tiles
    assert (zero[0] == zero[1]).all()
