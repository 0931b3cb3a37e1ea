"""Operation and byte counts against hand counts at smoke width."""
import pytest

from chipbench import flops


def test_dense_layer_by_hand():
    layer = flops.Layer(d_model=4, d_ff=8, classes=3)
    S = 2
    proj = 4 * (2 * S * 4 * 4)            # q, k, v, o
    scores = 2 * S * S * 4 + 2 * S * S * 4  # QK^T and PV over all heads
    mlp = 2 * S * 4 * 8 + 2 * S * 8 * 4
    ramp = 2 * 4 * 4 + 2 * 4 * 3
    assert layer.flops(S) == proj + scores + mlp + ramp


def test_pruned_mlp_counts_kept_tiles_only():
    dense = flops.Layer(d_model=128, d_ff=256, classes=3)
    half = flops.Layer(d_model=128, d_ff=256, classes=3, kept_up=0.5, kept_down=0.5)
    assert dense.flops(16) - half.flops(16) == 2 * 16 * 128 * 256
    assert dense.weight_bytes() - half.weight_bytes() == 4 * 128 * 256


def test_step_reads_weights_once_and_state_twice():
    layer = flops.Layer(d_model=128, d_ff=256, classes=3)
    ops, nbytes = layer.step(lanes=8, bucket=16)
    assert ops == 8 * layer.flops(16)
    weights = 4 * (4 * 128 * 128 + 2 * 128 * 256 + 128 * 128 + 128 * 3 + 4 * 128)
    assert nbytes == weights + 2 * 8 * 16 * 128 * 4


def test_albert_base_step_at_bucket_128():
    # ALBERT-base, 8 lanes x 128 tokens: about 14.5 MFLOP per token-layer
    layer = flops.Layer(d_model=768, d_ff=3072, classes=3)
    ops, nbytes = layer.step(lanes=8, bucket=128)
    assert ops / (8 * 128) == pytest.approx(14.55e6, rel=0.01)
    assert flops.least_seconds(ops, nbytes, 197e12, 819e9) == pytest.approx(ops / 197e12)
