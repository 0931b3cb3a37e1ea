"""Mixture-of-Experts layer with sort-based static-capacity dispatch.

Tokens are flattened, their top-k expert assignments sorted by expert id, and
gathered into a dense [E, C, d] buffer that is batch-matmul'd against stacked
expert weights — the TPU-native formulation: the [tokens] -> [E, C, d]
resharding is where XLA inserts the all-to-all when experts are sharded over
the `model` mesh axis (EP).  Overflowing tokens beyond capacity C are dropped
(their residual passes through), standard GShard/Switch semantics.

qwen2-moe additionally has a dense shared expert applied to every token.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

Params = Dict[str, Any]


def init_moe(rng, cfg, dtype) -> Params:
    d, ff, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = jax.random.split(rng, 5)
    p = {
        "router": dense_init(ks[0], (d, E), jnp.float32),  # router kept fp32+dense
        "w_gate": dense_init(ks[1], (E, d, ff), dtype),
        "w_up": dense_init(ks[2], (E, d, ff), dtype),
        "w_down": dense_init(ks[3], (E, ff, d), dtype),
    }
    if cfg.shared_expert_d_ff:
        sks = jax.random.split(ks[4], 4)
        sff = cfg.shared_expert_d_ff
        p["shared"] = {
            "w_gate": dense_init(sks[0], (d, sff), dtype),
            "w_up": dense_init(sks[1], (d, sff), dtype),
            "w_down": dense_init(sks[2], (sff, d), dtype),
            "gate_proj": dense_init(sks[3], (d, 1), dtype),  # qwen2-moe shared gate
        }
    return p


def _expert_ffn(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """x: [E, C, d] -> [E, C, d] via per-expert SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", x, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def apply_moe(
    p: Params,
    x: jnp.ndarray,               # [B, S, d]
    cfg,
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output [B, S, d], router aux loss)."""
    if getattr(cfg, "moe_shardmap_dispatch", False):
        return apply_moe_shardmap(p, x, cfg, capacity_factor)
    if getattr(cfg, "moe_grouped_dispatch", False):
        # group by batch row: sorts/cumsums stay local to the data shard
        # (vmapped over B, which is batch-sharded) -> no global-argsort
        # all-gathers; only the [E, C, d] expert reshard moves data (§Perf)
        vmap_kw = {}
        if getattr(cfg, "moe_buffer_sharded", False):
            # spmd_axis_name keeps the vmapped group dim sharded through the
            # in-body sharding constraint: buffer [G, E, C, d] pinned to
            # P(batch, model, None, None) (§Perf qwen3 iteration 3)
            ba = getattr(cfg, "sp_batch_axes", ("data",))
            vmap_kw["spmd_axis_name"] = ba if len(ba) > 1 else ba[0]
        y, aux = jax.vmap(
            lambda xb: _moe_tokens(p, xb, cfg, capacity_factor), **vmap_kw
        )(x)
        return y, jnp.mean(aux)
    y, aux = _moe_tokens(p, x.reshape(-1, x.shape[-1]), cfg, capacity_factor)
    return y.reshape(x.shape), aux


def apply_moe_shardmap(
    p: Params,
    x: jnp.ndarray,               # [B, S, d]
    cfg,
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Explicit-collective EP dispatch (§Perf qwen3 iteration 5).

    Under this framework's layout, activations are REPLICATED along the model
    axis (TP shards weights, not the residual stream), so EP dispatch needs no
    all-to-all at all: every model shard routes its (identical) data-shard
    tokens against the full router, slices out the assignments that hit ITS
    experts, runs them, and a single psum over `model` merges the per-expert
    partial outputs. Collective cost per layer = ONE all-reduce of [n, d]
    activations — vs the SPMD partitioner's gathered-dispatch trainwreck.

    Requires a mesh context (jax.sharding.use_mesh / `with mesh:`); experts
    must divide the model axis; no shared expert inside the region (qwen2's
    shared expert runs densely outside).
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    axis_sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    m_size = axis_sizes.get("model", 1)
    E = cfg.n_experts
    assert E % m_size == 0, "shard_map EP needs experts % model == 0"
    ba = tuple(a for a in getattr(cfg, "sp_batch_axes", ("data",)) if a in axis_sizes)
    batch_spec = ba if len(ba) > 1 else (ba[0] if ba else None)
    all_axes = tuple(ba) + (("model",) if "model" in axis_sizes else ())

    routed = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}

    def local(p_loc, x_loc):
        B_l, S, d = x_loc.shape
        xt = x_loc.reshape(-1, d)
        e_loc = E // m_size
        e_off = jax.lax.axis_index("model") * e_loc if m_size > 1 else 0
        y, aux = _moe_tokens(
            dict(p_loc, router=p_loc["router"]), xt, cfg, capacity_factor,
            local_expert_range=(e_off, e_loc),
        )
        if m_size > 1:
            y = jax.lax.psum(y, "model")
        if ba:
            aux = jax.lax.pmean(aux, ba if len(ba) > 1 else ba[0])
        return y.reshape(B_l, S, d), aux

    y, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            {
                "router": P(),
                "w_gate": P("model", None, None),
                "w_up": P("model", None, None),
                "w_down": P("model", None, None),
            },
            P(batch_spec, None, None),
        ),
        out_specs=(P(batch_spec, None, None), P()),
    )(routed, x)

    if "shared" in p:
        sp = p["shared"]
        xt = x.reshape(-1, x.shape[-1])
        g = xt @ sp["w_gate"]
        u = xt @ sp["w_up"]
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
        shared_out = h @ sp["w_down"]
        sgate = jax.nn.sigmoid((xt @ sp["gate_proj"]).astype(jnp.float32)).astype(xt.dtype)
        y = y + (sgate * shared_out).reshape(x.shape)
    return y, aux


def _moe_tokens(
    p: Params,
    xt: jnp.ndarray,              # [N, d] flat tokens
    cfg,
    capacity_factor: float = 1.25,
    local_expert_range: Optional[Tuple[Any, int]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    d = xt.shape[-1]
    E, k = cfg.n_experts, cfg.top_k
    N = xt.shape[0]

    router_logits = (xt.astype(jnp.float32)) @ p["router"]          # [N, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, k)                      # [N, k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # --- load-balancing aux loss (Switch) ---
    me = jnp.mean(probs, axis=0)                                     # [E]
    onehot_top1 = jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(onehot_top1, axis=0)
    aux = E * jnp.sum(me * ce)

    # --- sort assignments by expert ---
    C = max(int(N * k * capacity_factor / E), 4)
    flat_expert = expert_idx.reshape(-1)                             # [N*k]
    flat_token = jnp.repeat(jnp.arange(N), k)
    flat_gate = gates.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    se = flat_expert[order]
    st = flat_token[order]
    sg = flat_gate[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_expert].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_in_expert = jnp.arange(N * k) - starts[se]
    valid = pos_in_expert < C
    if local_expert_range is not None:
        # shard_map EP: this shard owns experts [e_off, e_off + e_loc)
        e_off, e_loc = local_expert_range
        se_local = se - e_off
        valid = valid & (se_local >= 0) & (se_local < e_loc)
        dest = jnp.where(valid, se_local * C + pos_in_expert, e_loc * C)
        n_buf = e_loc * C
        buf_experts = e_loc
    else:
        dest = jnp.where(valid, se * C + pos_in_expert, E * C)      # last = drop
        n_buf = E * C
        buf_experts = E

    # --- gather to [buf_experts, C, d] ---
    buf = jnp.zeros((n_buf + 1, d), xt.dtype).at[dest].set(xt[st])
    expert_in = buf[:n_buf].reshape(buf_experts, C, d)
    if getattr(cfg, "moe_buffer_sharded", False) and local_expert_range is None:
        # pin the dispatch buffer to expert-sharding (model axis); without
        # this the vmapped-group buffer replicates across the data axis and
        # the EP all-to-all balloons ~dp-fold (§Perf qwen3 iteration 2)
        from jax.sharding import PartitionSpec as P

        expert_in = jax.lax.with_sharding_constraint(expert_in, P("model", None, None))
    expert_out = _expert_ffn(p, expert_in).reshape(n_buf, d)
    expert_out = jnp.concatenate([expert_out, jnp.zeros((1, d), xt.dtype)], axis=0)

    # --- combine back ---
    contrib = expert_out[dest] * sg[:, None].astype(xt.dtype)
    y = jnp.zeros((N, d), xt.dtype).at[st].add(jnp.where(valid[:, None], contrib, 0))

    if "shared" in p:
        sp = p["shared"]
        g = xt @ sp["w_gate"]
        u = xt @ sp["w_up"]
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
        shared_out = h @ sp["w_down"]
        sgate = jax.nn.sigmoid((xt @ sp["gate_proj"]).astype(jnp.float32)).astype(xt.dtype)
        y = y + sgate * shared_out

    return y, aux
