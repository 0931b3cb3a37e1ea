"""Pipeline parallelism (PP) via shard_map + collective_permute.

GPipe-style microbatch pipeline over a `stage` mesh axis: each device owns a
contiguous block of layers; activations flow stage->stage with
``jax.lax.ppermute`` while microbatches stream through, so the bubble is
(S-1)/(S-1+M) of the schedule.  Provided as the PP building block for meshes
where a pod axis is better spent on pipeline stages than data parallelism
(very deep models / small global batch); the production dry-run uses DP×TP×EP
which is the right config for the assigned sizes on 256 chips — PP is
demonstrated and tested on a small mesh (tests/test_pipeline.py).

The implementation is deliberately model-agnostic: it pipelines any
``layer_fn(stage_params, h) -> h``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    layer_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,          # pytree with leading [n_stages, ...] axis
    x: jnp.ndarray,             # [n_micro, mb, ...] microbatched input
    mesh: Mesh,
    *,
    axis: str = "stage",
) -> jnp.ndarray:
    """Run a GPipe forward over the `axis` mesh dimension.

    Returns [n_micro, mb, ...] outputs (as produced by the LAST stage).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro >= n_stages, "need >= n_stages microbatches to fill the pipe"

    def stage_prog(params, xs):
        # params arrive with a leading sharded [1, ...] stage dim — drop it
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        stage_id = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if in range); others use buf
            inject = jnp.where(t < n_micro, t, n_micro - 1)
            h_in = jnp.where(stage_id == 0, xs[inject], buf)
            h_out = layer_fn(params, h_in)
            # pass to the next stage (last stage's output wraps, unused)
            buf_next = jax.lax.ppermute(
                h_out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            # last stage commits its result for microbatch (t - n_stages + 1)
            commit = t - (n_stages - 1)
            do_commit = jnp.logical_and(commit >= 0, stage_id == n_stages - 1)
            idx = jnp.clip(commit, 0, n_micro - 1)
            outs = jnp.where(
                do_commit,
                outs.at[idx].set(h_out),
                outs,
            )
            return (buf_next, outs), None

        # mark carries as device-varying (shard_map VMA typing)
        buf0 = jax.lax.pvary(jnp.zeros_like(xs[0]), (axis,))
        outs0 = jax.lax.pvary(jnp.zeros_like(xs), (axis,))
        (buf, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(n_ticks))
        # broadcast the last stage's outputs to everyone (psum of one-hot)
        mask = (stage_id == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    return jax.shard_map(
        stage_prog,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )(stage_params, x)
