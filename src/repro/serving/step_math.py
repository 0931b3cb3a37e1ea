"""Pure math of one fused serving step, isolated from scheduling.

The engines in ``serving/engine.py`` used to build their jit'd closures
inline, entangling three concerns: the numerical step (what one fused step
computes), trace accounting (host-side counters bumped inside traced
bodies), and scheduling (which bucket steps when).  This module owns the
first concern only: every function here is pure array math — no scheduler,
no telemetry, no host state — so the engine closures reduce to thin
wrappers that bump a trace counter and delegate.

This is also where ``use_pallas`` lands.  Each function takes the flag as a
plain Python keyword (closed over by the engine's jit'd closures, hence
static): ``True`` routes the eligible inner ops — attention, layernorm,
off-ramp entropy, activation quant, pruned MLP tiles — to the Pallas
kernels via ``repro.kernels.dispatch``; ``False`` keeps the byte-identical
reference path.  Either way the step is one compile per bucket: the flag
never becomes a traced value, so flipping it cannot add traces at runtime.

Lane structure: both engines vmap a one-lane body over the lane axis.  The
per-lane kv_len / position scalars become traced per-lane operands, which
the Pallas span kernel accepts through scalar prefetch — verified to
compose with vmap+jit in interpret mode (CPU CI) and on TPU.

Multi-device sharding: the ``sharded_*`` variants wrap the same fused-step
math in ``shard_map`` over a 1-D device mesh, splitting the lane axis into
``replicas`` contiguous slabs (lane ``i`` lives on replica
``i // lanes_per_replica``).  Params and scalars replicate; the classifier's
``[lanes, S, D]`` state shards on axis 0 and the decoder KV cache on its
lane axis 1.  Because the body may dispatch ``pallas_call`` (which has no
replication rule), the wrappers go through ``jax_compat.shard_map_norep``.
Lanes are independent, so a 1-replica sharded step is bit-identical to the
unsharded step — the parity guarantee the serving tests gate.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.common.jax_compat import shard_map_norep
from repro.core.early_exit import offramp_logits
from repro.core.entropy import entropy_from_logits
from repro.models.model import Model


def matmul_precision(cfg) -> str:
    """The matmul precision a config's dtype states: a float32 model runs
    float32 matmuls ("highest"; the TPU otherwise rounds f32 operands to
    one bf16 pass), any narrower dtype the backend default."""
    return "highest" if jnp.dtype(cfg.dtype) == jnp.float32 else "default"


def jit_at_config_precision(cfg, fn: Callable, **jit_kw) -> Callable:
    """``jax.jit(fn)`` traced under ``matmul_precision(cfg)`` — the one
    place the served steps get their precision, kernels included."""
    precision = matmul_precision(cfg)

    @functools.wraps(fn)
    def at_precision(*args, **kwargs):
        with jax.default_matmul_precision(precision):
            return fn(*args, **kwargs)

    return jax.jit(at_precision, **jit_kw)


# ---------------------------------------------------------------------------
# Classifier (early-exit encoder) fused step
# ---------------------------------------------------------------------------


def classifier_embed(model: Model, params: Any, tokens: jnp.ndarray) -> jnp.ndarray:
    """Embed one lane's padded token row: [1, S_bucket] -> [1, S_bucket, D]."""
    return model.embed(params, tokens)


def classifier_fused_step(
    model: Model,
    params: Any,
    h: jnp.ndarray,          # [lanes, S_bucket, D] static-shape hidden states
    active: jnp.ndarray,     # [lanes] bool — inactive lanes frozen by the mask
    lengths: jnp.ndarray,    # [lanes] int32 valid token count per lane
    threshold: jnp.ndarray,  # scalar entropy threshold
    *,
    use_pallas: bool = False,
    block_masks: Optional[Dict[str, Any]] = None,
):
    """Fused: encoder layer -> off-ramp logits -> entropy -> retire mask.

    Positions beyond a lane's length are bucket padding, masked out of
    attention via kv_len so a padded sentence computes the SAME function as
    at its native length.  Returns ``(h, logits, entropy, retire)``.
    """
    span_z = model._span_for_layer(params, 0)

    def one_lane(h_l, length):
        h2, _, _ = model._dense_layer_step(
            params["layer"], h_l[None], causal=False, span_z=span_z,
            kv_len=length, use_pallas=use_pallas, block_masks=block_masks,
        )
        return h2[0]

    h_new = jax.vmap(one_lane)(h, lengths)     # phases "attention", "mlp"
    h = jnp.where(active[:, None, None], h_new, h)
    with jax.named_scope("offramp"):
        lg = offramp_logits(h, model._offramp(params))
        if use_pallas:
            from repro.kernels import dispatch

            ent = dispatch.entropy(lg)
        else:
            ent = entropy_from_logits(lg)
        retire = jnp.logical_and(active, ent < threshold)
    return h, lg, ent, retire


def sharded_classifier_fused_step(
    model: Model,
    params: Any,
    h: jnp.ndarray,          # [replicas * lanes_per_replica, S_bucket, D]
    active: jnp.ndarray,
    lengths: jnp.ndarray,
    threshold: jnp.ndarray,
    *,
    mesh: Any,
    axis: str = "data",
    use_pallas: bool = False,
    block_masks: Optional[Dict[str, Any]] = None,
):
    """``classifier_fused_step`` shard_map'd over the lane axis.

    Each device computes its own contiguous ``[lanes_per_replica, S, D]``
    slab under replicated params — no collectives cross replicas, so the
    step scales linearly in device count and a 1-replica mesh reproduces
    the unsharded step bit-for-bit."""
    P = jax.sharding.PartitionSpec
    fn = shard_map_norep(
        lambda p, hh, aa, ll, th: classifier_fused_step(
            model, p, hh, aa, ll, th,
            use_pallas=use_pallas, block_masks=block_masks,
        ),
        mesh,
        in_specs=(P(), P(axis), P(axis), P(axis), P()),
        out_specs=(P(axis), P(axis), P(axis), P(axis)),
    )
    return fn(params, h, active, lengths, threshold)


def lane_insert(h: jnp.ndarray, lane: jnp.ndarray, h_new: jnp.ndarray) -> jnp.ndarray:
    """Overwrite one lane row; reused verbatim for load AND restore so
    preemption round-trips through the same compiled trace."""
    return jax.lax.dynamic_update_slice_in_dim(h, h_new, lane, axis=0)


# ---------------------------------------------------------------------------
# Decoder (LM) fused steps
# ---------------------------------------------------------------------------


def decoder_decode(
    model: Model,
    params: Any,
    cache: Any,
    tokens: jnp.ndarray,     # [lanes, 1]
    pos: jnp.ndarray,        # [lanes] per-lane cache positions
    *,
    use_pallas: bool = False,
):
    """One decode step with PER-LANE positions (vmap over the lane axis)."""
    lane_axes = jax.tree_util.tree_map(lambda _: 1, cache)

    def one_lane(cache_l, tok, p):
        cache_b = jax.tree_util.tree_map(lambda x: x[:, None], cache_l)
        lg, cache_b = model.decode_step(
            params, cache_b, tok[None, None], p, use_pallas=use_pallas
        )
        return lg[0], jax.tree_util.tree_map(lambda x: x[:, 0], cache_b)

    return jax.vmap(
        one_lane, in_axes=(lane_axes, 0, 0), out_axes=(0, lane_axes)
    )(cache, tokens[:, 0], pos)


def decoder_decode_ee(
    model: Model,
    params: Any,
    cache: Any,
    tokens: jnp.ndarray,
    pos: jnp.ndarray,
    threshold,
    *,
    use_pallas: bool = False,
):
    """Fused layer -> LM-head off-ramp -> entropy -> per-token exit.

    Same per-lane vmap as ``decoder_decode``; each lane additionally returns
    its token's 1-based exit depth and first-off-ramp entropy.
    """
    lane_axes = jax.tree_util.tree_map(lambda _: 1, cache)

    def one_lane(cache_l, tok, p):
        cache_b = jax.tree_util.tree_map(lambda x: x[:, None], cache_l)
        lg, cache_b, xl, fe = model.decode_step_ee(
            params, cache_b, tok[None, None], p, threshold,
            use_pallas=use_pallas,
        )
        return (
            lg[0],
            jax.tree_util.tree_map(lambda x: x[:, 0], cache_b),
            xl[0],
            fe[0],
        )

    return jax.vmap(
        one_lane, in_axes=(lane_axes, 0, 0), out_axes=(0, lane_axes, 0, 0)
    )(cache, tokens[:, 0], pos)


def decoder_decode_spec(
    model: Model,
    params: Any,
    cache: Any,
    tokens: jnp.ndarray,     # [lanes, 1]
    pos: jnp.ndarray,        # [lanes]
    thresholds: jnp.ndarray,  # [lanes, spec_window] per-slot entropy thresholds
    spec_window: int,
    *,
    eos_id: int = -1,
    use_pallas: bool = False,
):
    """Self-speculative fused step: per-lane vmap of the one-lane
    ``decode_step_spec`` (draft via off-ramp, verify via remaining layers,
    batched accept/rollback).  Thresholds are a per-lane, per-slot row so a
    position/entropy-band schedule prices each speculated position
    individually.

    Returns per-lane ``(tokens [lanes,W], logits [lanes,W,V], cache,
    exit_layers [lanes,W], first_ent [lanes,W], accepted [lanes,W])``.
    """
    lane_axes = jax.tree_util.tree_map(lambda _: 1, cache)

    def one_lane(cache_l, tok, p, thr):
        cache_b = jax.tree_util.tree_map(lambda x: x[:, None], cache_l)
        tk, lg, cache_b, xl, fe, acc = model.decode_step_spec(
            params, cache_b, tok[None, None], p, thr[None, :], spec_window,
            eos_id=eos_id, use_pallas=use_pallas,
        )
        return (
            tk[0],
            lg[0],
            jax.tree_util.tree_map(lambda x: x[:, 0], cache_b),
            xl[0],
            fe[0],
            acc[0],
        )

    return jax.vmap(
        one_lane, in_axes=(lane_axes, 0, 0, 0),
        out_axes=(0, 0, lane_axes, 0, 0, 0),
    )(cache, tokens[:, 0], pos, thresholds)


def sharded_decoder_decode(
    model: Model,
    params: Any,
    cache: Any,
    tokens: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    mesh: Any,
    axis: str = "data",
    use_pallas: bool = False,
):
    """``decoder_decode`` shard_map'd over the KV cache's lane axis (axis 1
    of every cache leaf); tokens and positions shard with their lanes."""
    P = jax.sharding.PartitionSpec
    cache_specs = jax.tree_util.tree_map(lambda _: P(None, axis), cache)
    fn = shard_map_norep(
        lambda p, c, t, po: decoder_decode(
            model, p, c, t, po, use_pallas=use_pallas
        ),
        mesh,
        in_specs=(P(), cache_specs, P(axis), P(axis)),
        out_specs=(P(axis), cache_specs),
    )
    return fn(params, cache, tokens, pos)


def sharded_decoder_decode_ee(
    model: Model,
    params: Any,
    cache: Any,
    tokens: jnp.ndarray,
    pos: jnp.ndarray,
    threshold,
    *,
    mesh: Any,
    axis: str = "data",
    use_pallas: bool = False,
):
    """``decoder_decode_ee`` shard_map'd like ``sharded_decoder_decode``;
    the per-token exit depths and first entropies shard with their lanes."""
    P = jax.sharding.PartitionSpec
    cache_specs = jax.tree_util.tree_map(lambda _: P(None, axis), cache)
    fn = shard_map_norep(
        lambda p, c, t, po, th: decoder_decode_ee(
            model, p, c, t, po, th, use_pallas=use_pallas
        ),
        mesh,
        in_specs=(P(), cache_specs, P(axis), P(axis), P()),
        out_specs=(P(axis), cache_specs, P(axis), P(axis)),
    )
    return fn(params, cache, tokens, pos, threshold)


def sharded_decoder_decode_spec(
    model: Model,
    params: Any,
    cache: Any,
    tokens: jnp.ndarray,
    pos: jnp.ndarray,
    thresholds: jnp.ndarray,  # [lanes, spec_window]
    spec_window: int,
    *,
    mesh: Any,
    axis: str = "data",
    eos_id: int = -1,
    use_pallas: bool = False,
):
    """``decoder_decode_spec`` shard_map'd like ``sharded_decoder_decode``;
    per-slot thresholds, accept masks, depths, and entropies all shard with
    their lanes."""
    P = jax.sharding.PartitionSpec
    cache_specs = jax.tree_util.tree_map(lambda _: P(None, axis), cache)
    fn = shard_map_norep(
        lambda p, c, t, po, th: decoder_decode_spec(
            model, p, c, t, po, th, spec_window,
            eos_id=eos_id, use_pallas=use_pallas,
        ),
        mesh,
        in_specs=(P(), cache_specs, P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), cache_specs, P(axis), P(axis), P(axis)),
    )
    return fn(params, cache, tokens, pos, thresholds)


def decoder_prefill(
    model: Model,
    params: Any,
    cache: Any,
    tokens: jnp.ndarray,     # [bucket] zero-padded prompt
    lane,                    # scalar lane index
    length,                  # scalar prompt length
    lanes: int,              # static lane count
    *,
    use_pallas: bool = False,
):
    """Write one lane's prompt[:length-1] into the KV cache (fori_loop on a
    scratch cache, merged back under a lane one-hot)."""
    lane_ids = jnp.arange(lanes)

    def body(t, c):
        tok = jnp.where(lane_ids == lane, tokens[t], 0)[:, None]
        _, c = model.decode_step(params, c, tok, t, use_pallas=use_pallas)
        return c

    scratch = jax.lax.fori_loop(0, length - 1, body, cache)

    def merge(new, old):
        mask = (lane_ids == lane).reshape((1, lanes) + (1,) * (new.ndim - 2))
        return jnp.where(mask, new, old)

    return jax.tree_util.tree_map(merge, scratch, cache)
