"""Unified model zoo: one `Model` class covering all assigned families
(dense / moe / vlm / encdec / hybrid / ssm / albert) with a common API:

    init_params(rng)                          -> params pytree
    apply_train(params, batch)                -> ModelOutput (logits / cls)
    init_cache(batch, seq)                    -> decode cache pytree
    prefill(params, tokens, cache, aux)       -> (logits, cache)
    decode_step(params, cache, tokens, pos)   -> (logits, cache)

EdgeBERT features thread through: adaptive span (span_z params modulate
attention), early-exit off-ramps (albert/cls + token-level adaptation),
AdaptivFloat activation fake-quant at block boundaries, and pruning masks
applied to params upstream (training/ serving layers).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.util import ceil_div, fold_rng
from repro.configs.base import ModelConfig
from repro.core import early_exit as ee
from repro.core.adaptivfloat import AFFormat, fake_quant
from repro.core.entropy import entropy_from_logits
from repro.models import layers as L
from repro.models import mamba2, moe, rwkv6

Params = Dict[str, Any]


class ModelOutput(NamedTuple):
    logits: Optional[jnp.ndarray] = None        # LM logits [B, S, V]
    cls_logits: Optional[jnp.ndarray] = None    # [B, C]
    aux_loss: jnp.ndarray = 0.0                 # router/span regularizers
    all_cls_logits: Optional[jnp.ndarray] = None  # [L, B, C] off-ramp sweep
    all_entropies: Optional[jnp.ndarray] = None   # [L, B]
    exit_layer: Optional[jnp.ndarray] = None      # [B]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ===========================================================================
# Parameter init
# ===========================================================================


def _init_dense_layer(rng, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(rng, 4)
    p = {
        "norm1": L.init_norm(cfg.norm, cfg.d_model, dtype),
        "attn": L.init_attention(ks[0], cfg, dtype),
        "norm2": L.init_norm(cfg.norm, cfg.d_model, dtype),
    }
    if cfg.family == "moe":
        p["moe"] = moe.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


def _init_cross_layer(rng, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(rng, 2)
    return {
        "norm1": L.init_norm(cfg.norm, cfg.d_model, dtype),
        "xattn": L.init_attention(ks[0], cfg, dtype),
        "gate_attn": jnp.zeros((), jnp.float32),
        "norm2": L.init_norm(cfg.norm, cfg.d_model, dtype),
        "mlp": L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype),
        "gate_mlp": jnp.zeros((), jnp.float32),
    }


def _init_rwkv_layer(rng, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(rng, 2)
    return {
        "norm1": L.init_norm("layernorm", cfg.d_model, dtype),
        "tmix": rwkv6.init_rwkv6(ks[0], cfg, dtype),
        "norm2": L.init_norm("layernorm", cfg.d_model, dtype),
        "cmix": rwkv6.init_channel_mix(ks[1], cfg, dtype),
    }


def _init_mamba_block(rng, cfg: ModelConfig, dtype) -> Params:
    return {
        "norm": L.init_norm(cfg.norm, cfg.d_model, dtype),
        "mixer": mamba2.init_mamba2(rng, cfg, dtype),
    }


def _stack_init(init_one, rng, n: int):
    return jax.vmap(init_one)(jax.random.split(rng, n))


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def init_params(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        dtype = _dtype(cfg)
        d = cfg.d_model
        p: Params = {}

        k_embed, k_layers, k_head, k_extra = jax.random.split(rng, 4)
        p["embed"] = {"tok": L.embed_init(k_embed, (cfg.vocab_size, cfg.embed_dim), dtype)}
        if cfg.embed_dim != d:
            p["embed"]["proj"] = L.dense_init(fold_rng(k_embed, "proj"), (cfg.embed_dim, d), dtype)
        if cfg.pos == "learned":
            p["embed"]["pos"] = L.embed_init(
                fold_rng(k_embed, "pos"), (cfg.max_seq_len, d), dtype
            )

        if cfg.family == "ssm":
            init_one = lambda k: _init_rwkv_layer(k, cfg, dtype)
        elif cfg.family == "hybrid":
            init_one = lambda k: _init_mamba_block(k, cfg, dtype)
        else:
            init_one = lambda k: _init_dense_layer(k, cfg, dtype)

        n_stack = cfg.n_layers
        if cfg.family == "vlm" and cfg.cross_attn_every:
            # n_layers counts TOTAL layers; every cross_attn_every-th is cross
            n_stack = cfg.n_layers - cfg.n_layers // cfg.cross_attn_every
        if cfg.shared_layers:
            p["layer"] = init_one(k_layers)               # one shared block
        else:
            p["layers"] = _stack_init(init_one, k_layers, n_stack)

        if cfg.family == "vlm" and cfg.cross_attn_every:
            n_cross = cfg.n_layers // cfg.cross_attn_every
            p["cross_layers"] = _stack_init(
                lambda k: _init_cross_layer(k, cfg, dtype), fold_rng(k_layers, "cross"), n_cross
            )
        if cfg.family == "encdec":
            p["enc_layers"] = _stack_init(
                lambda k: _init_dense_layer(k, cfg, dtype), fold_rng(k_layers, "enc"), cfg.n_enc_layers
            )
            p["enc_norm"] = L.init_norm(cfg.norm, d, dtype)
            p["enc_pos"] = L.embed_init(fold_rng(k_embed, "encpos"), (cfg.enc_seq_len, d), dtype)
            # decoder cross-attention weights per layer
            p["dec_cross"] = _stack_init(
                lambda k: {
                    "norm": L.init_norm(cfg.norm, d, dtype),
                    "xattn": L.init_attention(k, cfg, dtype),
                },
                fold_rng(k_layers, "deccross"),
                cfg.n_layers,
            )
        if cfg.family == "hybrid" and cfg.attn_every:
            # Zamba-style single shared attention+MLP block on concat([h, x0])
            import dataclasses

            acfg = dataclasses.replace(cfg, d_model=2 * d, qkv_bias=False)
            ks = jax.random.split(k_extra, 3)
            p["shared_attn"] = {
                "norm1": L.init_norm(cfg.norm, 2 * d, dtype),
                "attn": L.init_attention(ks[0], acfg, dtype, d_in=2 * d),
                "norm2": L.init_norm(cfg.norm, 2 * d, dtype),
                "mlp": L.init_mlp(ks[1], 2 * d, cfg.d_ff, "gelu", dtype),
                "out_proj": L.dense_init(ks[2], (2 * d, d), dtype),
            }

        p["final_norm"] = L.init_norm(cfg.norm, d, dtype)
        if not cfg.tie_embeddings and cfg.vocab_size:
            p["lm_head"] = L.dense_init(k_head, (d, cfg.vocab_size), dtype, scale=0.02)

        if cfg.num_classes:
            p["classifier"] = {
                "pooler_w": L.dense_init(fold_rng(k_head, "pool"), (d, d), dtype),
                "pooler_b": jnp.zeros((d,), dtype),
                "cls_w": L.dense_init(fold_rng(k_head, "cls"), (d, cfg.num_classes), dtype),
                "cls_b": jnp.zeros((cfg.num_classes,), dtype),
            }
        if cfg.edgebert.early_exit.enabled:
            C = cfg.edgebert.early_exit.num_classes
            op = ee.init_offramp(fold_rng(k_head, "offramp"), d, C, jnp.float32)
            p["offramp"] = {
                "offramp_pooler_w": op.pooler_w,
                "offramp_pooler_b": op.pooler_b,
                "offramp_cls_w": op.cls_w,
                "offramp_cls_b": op.cls_b,
            }
        if cfg.edgebert.span.enabled and not cfg.attention_free:
            n_span_layers = 1 if cfg.shared_layers else cfg.n_layers
            p["span_z"] = jnp.full(
                (n_span_layers, cfg.n_heads), cfg.edgebert.span.init_span, jnp.float32
            )
        return p

    # -------------------------------------------------------------- embedding
    def embed(self, p: Params, tokens: jnp.ndarray, positions=None) -> jnp.ndarray:
        cfg = self.cfg
        h = jnp.take(p["embed"]["tok"], tokens, axis=0)
        if "proj" in p["embed"]:
            h = h @ p["embed"]["proj"]
        if cfg.pos == "learned":
            if positions is None:
                positions = jnp.arange(tokens.shape[-1])
            h = h + jnp.take(p["embed"]["pos"], positions, axis=0)
        return h

    def lm_logits(self, p: Params, h: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.tie_embeddings:
            w = p["embed"]["tok"]
            if "proj" in p["embed"]:
                h = h @ p["embed"]["proj"].T
            return h @ w.T
        return h @ p["lm_head"]

    def cls_logits(self, p: Params, h: jnp.ndarray) -> jnp.ndarray:
        c = p["classifier"]
        pooled = jnp.tanh(h[..., 0, :] @ c["pooler_w"] + c["pooler_b"])
        return (pooled @ c["cls_w"] + c["cls_b"]).astype(jnp.float32)

    def _offramp(self, p: Params) -> ee.OfframpParams:
        o = p["offramp"]
        return ee.OfframpParams(
            o["offramp_pooler_w"], o["offramp_pooler_b"], o["offramp_cls_w"], o["offramp_cls_b"]
        )

    def _maybe_actquant(
        self, h: jnp.ndarray, use_pallas: bool = False, kv_len=None
    ) -> jnp.ndarray:
        """AdaptivFloat activation fake-quant with a per-tensor bias.

        With ``kv_len`` (a right-padded ``[..., S, D]`` lane) the bias comes
        from the first ``kv_len`` positions only, so bucket padding cannot
        move the quantization grid of the sentence's own tokens.
        """
        q = self.cfg.edgebert.quant
        if not (q.enabled and q.quantize_activations):
            return h
        amax = None
        if kv_len is not None:
            valid = jnp.arange(h.shape[-2]) < kv_len
            amax = jnp.max(jnp.where(valid[:, None], jnp.abs(h.astype(jnp.float32)), 0.0))
        if use_pallas:
            from repro.kernels import dispatch

            return dispatch.act_quantize(h, q.n_bits, q.n_exp, amax=amax)
        return fake_quant(h, AFFormat(q.n_bits, q.n_exp), amax=amax)

    def _sp_constrain(self, h: jnp.ndarray) -> jnp.ndarray:
        """Sequence-parallel residual stream: [B, S, D] sharded (batch->dp,
        seq->model) between blocks — turns TP all-reduces into RS+AG at half
        the volume (Megatron-SP). No-op unless cfg.sequence_parallel."""
        cfg = self.cfg
        if not cfg.sequence_parallel or h.ndim != 3:
            return h
        from jax.sharding import PartitionSpec as P

        ba = cfg.sp_batch_axes
        batch_axis = ba if len(ba) > 1 else ba[0]
        return jax.lax.with_sharding_constraint(h, P(batch_axis, "model", None))

    # ---------------------------------------------------------- layer bodies
    def _dense_layer_step(
        self,
        lp: Params,
        h: jnp.ndarray,
        *,
        causal: bool,
        span_z=None,
        positions=None,
        cache=None,
        cache_pos=None,
        kv_len=None,
        use_pallas=False,
        block_masks=None,
    ):
        cfg = self.cfg
        post_ln = cfg.family == "albert"
        aux = jnp.zeros((), jnp.float32)
        if post_ln:
            # named phases: the compiled step's ops (and the device trace)
            # carry "attention" / "mlp" in their scope
            with jax.named_scope("attention"):
                attn_out, cache = L.attention_layer(
                    lp["attn"], h, cfg, causal=causal, positions=positions,
                    span_z=span_z, span_ramp=cfg.edgebert.span.ramp,
                    cache=cache, cache_pos=cache_pos, kv_len=kv_len,
                    use_pallas=use_pallas,
                )
                h = L.apply_norm(lp["norm1"], h + attn_out, cfg.norm, use_pallas=use_pallas)
            with jax.named_scope("mlp"):
                if "moe" in lp:
                    mo, aux = moe.apply_moe(lp["moe"], h, cfg)
                else:
                    mo = L.apply_mlp(
                        lp["mlp"], h, cfg.act,
                        use_pallas=use_pallas, block_masks=block_masks,
                    )
                h = L.apply_norm(lp["norm2"], h + mo, cfg.norm, use_pallas=use_pallas)
        else:
            attn_out, cache = L.attention_layer(
                lp["attn"], L.apply_norm(lp["norm1"], h, cfg.norm, use_pallas=use_pallas),
                cfg,
                causal=causal, positions=positions,
                span_z=span_z, span_ramp=cfg.edgebert.span.ramp,
                cache=cache, cache_pos=cache_pos, kv_len=kv_len,
                use_pallas=use_pallas,
            )
            h = self._sp_constrain(h + attn_out)
            hn = L.apply_norm(lp["norm2"], h, cfg.norm, use_pallas=use_pallas)
            if "moe" in lp:
                mo, aux = moe.apply_moe(lp["moe"], hn, cfg)
            else:
                mo = L.apply_mlp(
                    lp["mlp"], hn, cfg.act,
                    use_pallas=use_pallas, block_masks=block_masks,
                )
            h = self._sp_constrain(h + mo)
        return self._maybe_actquant(h, use_pallas=use_pallas, kv_len=kv_len), aux, cache

    def _cross_layer_step(self, lp: Params, h, img, cache_kv=None):
        """Gated cross-attention layer (llama-3.2-vision style)."""
        cfg = self.cfg
        x, _ = L.attention_layer(
            lp["xattn"], L.apply_norm(lp["norm1"], h, cfg.norm), cfg,
            causal=False, kv_source=img,
        )
        h = h + jnp.tanh(lp["gate_attn"]).astype(h.dtype) * x
        m = L.apply_mlp(lp["mlp"], L.apply_norm(lp["norm2"], h, cfg.norm), cfg.act)
        h = h + jnp.tanh(lp["gate_mlp"]).astype(h.dtype) * m
        return self._maybe_actquant(h)

    def _rwkv_layer_step(self, lp: Params, h, *, states=None, decode=False):
        tm_in = L.apply_norm(lp["norm1"], h, "layernorm")
        last_tm = states["last_tm"] if states else None
        wkv = states["wkv"] if states else None
        tout, (new_last_tm, new_wkv) = rwkv6.apply_rwkv6(
            lp["tmix"], tm_in, self.cfg, last_x=last_tm, wkv_state=wkv, decode=decode
        )
        h = h + tout
        cm_in = L.apply_norm(lp["norm2"], h, "layernorm")
        last_cm = states["last_cm"] if states else None
        cout, new_last_cm = rwkv6.apply_channel_mix(lp["cmix"], cm_in, last_x=last_cm)
        h = h + cout
        new_states = {"last_tm": new_last_tm, "wkv": new_wkv, "last_cm": new_last_cm}
        return self._maybe_actquant(h), new_states

    def _mamba_block_step(self, lp: Params, h, *, states=None, decode=False):
        xin = L.apply_norm(lp["norm"], h, self.cfg.norm)
        conv_state = states["conv"] if states else None
        ssm_state = states["ssm"] if states else None
        out, (new_conv, new_ssm) = mamba2.apply_mamba2(
            lp["mixer"], xin, self.cfg, conv_state=conv_state, ssm_state=ssm_state, decode=decode
        )
        h = h + out
        return self._maybe_actquant(h), {"conv": new_conv, "ssm": new_ssm}

    def _shared_attn_step(self, sp: Params, h, x0, *, span_z=None, cache=None,
                          cache_pos=None, positions=None, use_pallas=False):
        """Zamba2 shared attention block on concat([h, x0])."""
        cfg = self.cfg
        import dataclasses

        acfg = dataclasses.replace(cfg, d_model=2 * cfg.d_model, qkv_bias=False)
        z = jnp.concatenate([h, x0], axis=-1)
        zi = L.apply_norm(sp["norm1"], z, cfg.norm, use_pallas=use_pallas)
        a, cache = L.attention_layer(
            sp["attn"], zi, acfg, causal=True, positions=positions,
            span_z=span_z, span_ramp=cfg.edgebert.span.ramp,
            cache=cache, cache_pos=cache_pos, use_pallas=use_pallas,
        )
        z = z + a
        m = L.apply_mlp(
            sp["mlp"],
            L.apply_norm(sp["norm2"], z, cfg.norm, use_pallas=use_pallas),
            "gelu",
        )
        z = z + m
        return h + z @ sp["out_proj"], cache

    # ------------------------------------------------------------- remat wrap
    def _remat(self, f):
        if self.cfg.remat_policy == "full":
            return jax.checkpoint(f)
        if self.cfg.remat_policy == "dots":
            return jax.checkpoint(
                f, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            )
        return f

    def _span_for_layer(self, p: Params, i) -> Optional[jnp.ndarray]:
        if "span_z" not in p:
            return None
        z = p["span_z"]
        if z.shape[0] == 1:
            return z[0]
        return z[i]

    # ============================================================== forward ==
    def apply_train(self, p: Params, batch: Dict[str, jnp.ndarray]) -> ModelOutput:
        cfg = self.cfg
        f = {
            "dense": self._forward_dense,
            "moe": self._forward_dense,
            "albert": self._forward_albert,
            "vlm": self._forward_vlm,
            "encdec": self._forward_encdec,
            "hybrid": self._forward_hybrid,
            "ssm": self._forward_ssm,
        }[cfg.family]
        return f(p, batch)

    # ---- dense / moe ----
    def _forward_dense(self, p: Params, batch) -> ModelOutput:
        cfg = self.cfg
        tokens = batch["tokens"]
        h = self.embed(p, tokens)
        aux_total = jnp.zeros((), jnp.float32)

        def step(carry, xs):
            h, aux = carry
            lp, span_z = xs
            h, a, _ = self._dense_layer_step(lp, h, causal=True, span_z=span_z)
            return (h, aux + a), None

        span = p.get("span_z")
        if span is None:
            step_fn = self._remat(lambda c, lp: step(c, (lp, None)))
            (h, aux_total), _ = jax.lax.scan(step_fn, (h, aux_total), p["layers"])
        else:
            span_xs = (
                span if span.shape[0] == cfg.n_layers
                else jnp.broadcast_to(span, (cfg.n_layers,) + span.shape[1:])
            )
            step_fn = self._remat(step)
            (h, aux_total), _ = jax.lax.scan(step_fn, (h, aux_total), (p["layers"], span_xs))

        h = L.apply_norm(p["final_norm"], h, cfg.norm)
        logits = self.lm_logits(p, h)
        cls = self.cls_logits(p, h) if "classifier" in p else None
        return ModelOutput(logits=logits, cls_logits=cls, aux_loss=aux_total)

    # ---- albert (shared layer, early exit) ----
    def _albert_layer_fn(self, p: Params):
        lp = p["layer"]

        def layer_fn(i, h):
            span_z = self._span_for_layer(p, 0)
            h, _, _ = self._dense_layer_step(lp, h, causal=False, span_z=span_z)
            return h

        return layer_fn

    def _forward_albert(self, p: Params, batch) -> ModelOutput:
        cfg = self.cfg
        tokens = batch["tokens"]
        h = self.embed(p, tokens)
        layer_fn = self._albert_layer_fn(p)

        if cfg.edgebert.early_exit.enabled and "offramp" in p:
            all_logits, all_ent = ee.exit_all_layers(
                layer_fn, cfg.n_layers, h, self._offramp(p)
            )
            thr = cfg.edgebert.early_exit.entropy_threshold
            exit_layer, _ = ee.exit_decisions(all_ent, thr)
            final_cls = ee.select_exit_logits(all_logits, exit_layer)
            return ModelOutput(
                cls_logits=final_cls,
                all_cls_logits=all_logits,
                all_entropies=all_ent,
                exit_layer=exit_layer,
                aux_loss=jnp.zeros((), jnp.float32),
            )

        def body(h, i):
            return layer_fn(i, h), None

        h, _ = jax.lax.scan(self._remat(body), h, jnp.arange(cfg.n_layers))
        cls = self.cls_logits(p, h) if "classifier" in p else None
        logits = self.lm_logits(p, h) if cfg.vocab_size else None
        return ModelOutput(logits=logits, cls_logits=cls, aux_loss=jnp.zeros((), jnp.float32))

    # ---- vlm: groups of (cross_attn_every-1 self layers + 1 cross layer) ----
    def _forward_vlm(self, p: Params, batch) -> ModelOutput:
        cfg = self.cfg
        tokens = batch["tokens"]
        img = batch["image_embeds"]          # [B, n_img, d] (frontend stub)
        h = self.embed(p, tokens)
        n_cross = cfg.n_layers // cfg.cross_attn_every
        n_self_per = cfg.cross_attn_every - 1

        self_layers = jax.tree_util.tree_map(
            lambda x: x.reshape((n_cross, n_self_per) + x.shape[1:]), p["layers"]
        )

        span = p.get("span_z")
        if span is None:
            def group_nospan(h, xs):
                selfs, cross = xs

                def inner(hh, lp):
                    hh, _, _ = self._dense_layer_step(lp, hh, causal=True)
                    return hh, None

                h, _ = jax.lax.scan(inner, h, selfs)
                h = self._cross_layer_step(cross, h, img)
                return h, None

            h, _ = jax.lax.scan(self._remat(group_nospan), h, (self_layers, p["cross_layers"]))
        else:
            if span.shape[0] == n_cross * n_self_per:
                span_groups = span.reshape(n_cross, n_self_per, cfg.n_heads)
            else:
                span_groups = jnp.broadcast_to(span[:1], (n_cross, n_self_per, cfg.n_heads))

            def group(h, xs):
                selfs, cross, span_g = xs

                def inner(hh, ys):
                    lp, sz = ys
                    hh, _, _ = self._dense_layer_step(lp, hh, causal=True, span_z=sz)
                    return hh, None

                h, _ = jax.lax.scan(inner, h, (selfs, span_g))
                h = self._cross_layer_step(cross, h, img)
                return h, None

            h, _ = jax.lax.scan(
                self._remat(group), h, (self_layers, p["cross_layers"], span_groups)
            )
        h = L.apply_norm(p["final_norm"], h, cfg.norm)
        return ModelOutput(logits=self.lm_logits(p, h), aux_loss=jnp.zeros((), jnp.float32))

    # ---- enc-dec (whisper) ----
    def _encode(self, p: Params, frames: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        h = frames + p["enc_pos"][None, : frames.shape[1]].astype(frames.dtype)

        def step(hh, lp):
            hh, _, _ = self._dense_layer_step(lp, hh, causal=False)
            return hh, None

        h, _ = jax.lax.scan(self._remat(step), h, p["enc_layers"])
        return L.apply_norm(p["enc_norm"], h, cfg.norm)

    def _forward_encdec(self, p: Params, batch) -> ModelOutput:
        cfg = self.cfg
        tokens = batch["tokens"]
        frames = batch["enc_input"]          # [B, enc_seq, d] (frontend stub)
        enc = self._encode(p, frames)
        h = self.embed(p, tokens)

        def step(carry, xs):
            h = carry
            lp, xp, span_z = xs
            h, _, _ = self._dense_layer_step(lp, h, causal=True, span_z=span_z)
            x, _ = L.attention_layer(
                xp["xattn"], L.apply_norm(xp["norm"], h, cfg.norm), cfg,
                causal=False, kv_source=enc,
            )
            h = h + x
            return h, None

        span = p.get("span_z")
        if span is not None:
            span_xs = (
                jnp.broadcast_to(span[:1], (cfg.n_layers, cfg.n_heads))
                if span.shape[0] == 1 else span
            )
            h, _ = jax.lax.scan(
                self._remat(step), h, (p["layers"], p["dec_cross"], span_xs)
            )
        else:
            h, _ = jax.lax.scan(
                self._remat(lambda c, xs: step(c, (xs[0], xs[1], None))),
                h, (p["layers"], p["dec_cross"]),
            )
        h = L.apply_norm(p["final_norm"], h, cfg.norm)
        return ModelOutput(logits=self.lm_logits(p, h), aux_loss=jnp.zeros((), jnp.float32))

    # ---- hybrid (zamba2) ----
    def _forward_hybrid(self, p: Params, batch) -> ModelOutput:
        cfg = self.cfg
        tokens = batch["tokens"]
        h = self.embed(p, tokens)
        x0 = h

        if cfg.hybrid_grouped and cfg.attn_every:
            # grouped scan: (attn_every mamba blocks + 1 shared attn) per
            # group, remainder blocks after — identical semantics to the cond
            # form (attn after blocks attn_every, 2*attn_every, ...), but the
            # scan body holds ONE branch, not both (§Perf zamba2 iteration)
            n_grp = cfg.n_layers // cfg.attn_every
            n_rem = cfg.n_layers % cfg.attn_every
            main = jax.tree_util.tree_map(
                lambda x: x[: n_grp * cfg.attn_every].reshape(
                    (n_grp, cfg.attn_every) + x.shape[1:]
                ),
                p["layers"],
            )

            def group(h, grp_layers):
                def inner(hh, lp):
                    hh, _ = self._mamba_block_step(lp, hh)
                    return hh, None

                h, _ = jax.lax.scan(inner, h, grp_layers)
                h, _ = self._shared_attn_step(
                    p["shared_attn"], h, x0, span_z=self._span_for_layer(p, 0)
                )
                return h, None

            h, _ = jax.lax.scan(self._remat(group), h, main)
            if n_rem:
                rem = jax.tree_util.tree_map(
                    lambda x: x[n_grp * cfg.attn_every :], p["layers"]
                )

                def tail(hh, lp):
                    hh, _ = self._mamba_block_step(lp, hh)
                    return hh, None

                h, _ = jax.lax.scan(self._remat(tail), h, rem)
        else:
            def step(carry, xs):
                h = carry
                lp, idx = xs
                h, _ = self._mamba_block_step(lp, h)
                if cfg.attn_every:
                    def with_attn(hh):
                        out, _ = self._shared_attn_step(
                            p["shared_attn"], hh, x0, span_z=self._span_for_layer(p, 0)
                        )
                        return out

                    h = jax.lax.cond(
                        (idx + 1) % cfg.attn_every == 0, with_attn, lambda hh: hh, h
                    )
                return h, None

            h, _ = jax.lax.scan(
                self._remat(step), h, (p["layers"], jnp.arange(cfg.n_layers))
            )
        h = L.apply_norm(p["final_norm"], h, cfg.norm)
        return ModelOutput(logits=self.lm_logits(p, h), aux_loss=jnp.zeros((), jnp.float32))

    # ---- ssm (rwkv6) ----
    def _forward_ssm(self, p: Params, batch) -> ModelOutput:
        cfg = self.cfg
        tokens = batch["tokens"]
        h = self.embed(p, tokens)

        def step(h, lp):
            h, _ = self._rwkv_layer_step(lp, h)
            return h, None

        h, _ = jax.lax.scan(self._remat(step), h, p["layers"])
        h = L.apply_norm(p["final_norm"], h, "layernorm")
        return ModelOutput(logits=self.lm_logits(p, h), aux_loss=jnp.zeros((), jnp.float32))

    # ---- token-level early exit (beyond-paper CALM-style adaptation) ----
    def forward_token_exit(self, p: Params, tokens: jnp.ndarray, threshold: float):
        """Per-TOKEN early exit for decoder LMs: after each layer, tokens whose
        LM-head entropy < threshold freeze (hidden-state propagation); the
        paper's per-sentence exit generalized to generation (DESIGN.md §4).

        Returns (logits [B,S,V], exit_layer [B,S]). Dense/MoE families.
        """
        cfg = self.cfg
        assert cfg.family in ("dense", "moe"), "token exit: decoder LMs"
        h = self.embed(p, tokens)
        B, S, _ = h.shape

        def head_entropy(h):
            lg = self.lm_logits(p, L.apply_norm(p["final_norm"], h, cfg.norm))
            return lg, entropy_from_logits(lg)

        def step(carry, lp):
            h, done, exit_layer, i = carry
            h_new, _, _ = self._dense_layer_step(lp, h, causal=True)
            h = jnp.where(done[..., None], h, h_new)
            _, ent = head_entropy(h)
            exit_now = jnp.logical_and(jnp.logical_not(done), ent < threshold)
            exit_layer = jnp.where(exit_now, i + 1, exit_layer)
            done = jnp.logical_or(done, exit_now)
            return (h, done, exit_layer, i + 1), None

        init = (
            h,
            jnp.zeros((B, S), bool),
            jnp.full((B, S), cfg.n_layers, jnp.int32),
            jnp.array(0, jnp.int32),
        )
        (h, done, exit_layer, _), _ = jax.lax.scan(step, init, p["layers"])
        logits, _ = head_entropy(h)
        return logits, exit_layer

    # ============================================================ decode ====
    def init_cache(self, batch_size: int, max_seq: int) -> Params:
        cfg = self.cfg
        dtype = _dtype(cfg)
        # AF8 KV cache: uint8 codes with a static exponent bias (§Perf)
        kv_dtype = jnp.uint8 if cfg.kv_cache_dtype == "af8" else dtype
        B = batch_size
        KV, hd = cfg.n_kv_heads, cfg.head_dim
        if cfg.family in ("dense", "moe", "albert"):
            n = cfg.n_layers
            return {
                "k": jnp.zeros((n, B, max_seq, KV, hd), kv_dtype),
                "v": jnp.zeros((n, B, max_seq, KV, hd), kv_dtype),
            }
        if cfg.family == "vlm":
            n_cross = cfg.n_layers // cfg.cross_attn_every
            n = cfg.n_layers - n_cross  # self layers (cross K/V cached at prefill)
            return {
                "k": jnp.zeros((n, B, max_seq, KV, hd), kv_dtype),
                "v": jnp.zeros((n, B, max_seq, KV, hd), kv_dtype),
                "img_k": jnp.zeros((n_cross, B, cfg.n_image_tokens, KV, hd), dtype),
                "img_v": jnp.zeros((n_cross, B, cfg.n_image_tokens, KV, hd), dtype),
            }
        if cfg.family == "encdec":
            n = cfg.n_layers
            return {
                "k": jnp.zeros((n, B, max_seq, KV, hd), kv_dtype),
                "v": jnp.zeros((n, B, max_seq, KV, hd), kv_dtype),
                "enc_k": jnp.zeros((n, B, cfg.enc_seq_len, KV, hd), dtype),
                "enc_v": jnp.zeros((n, B, cfg.enc_seq_len, KV, hd), dtype),
            }
        if cfg.family == "hybrid":
            di = mamba2.d_inner(cfg)
            H = mamba2.n_ssm_heads(cfg)
            n_attn = cfg.n_layers // cfg.attn_every if cfg.attn_every else 0
            cache = {
                "conv": jnp.zeros((cfg.n_layers, B, mamba2.CONV_K - 1, di + 2 * cfg.ssm_state), dtype),
                "ssm": jnp.zeros((cfg.n_layers, B, H, cfg.ssm_head_dim, cfg.ssm_state), dtype),
            }
            if n_attn:
                cache["k"] = jnp.zeros((n_attn, B, max_seq, KV, hd), kv_dtype)
                cache["v"] = jnp.zeros((n_attn, B, max_seq, KV, hd), kv_dtype)
            return cache
        if cfg.family == "ssm":
            n, d = cfg.n_layers, cfg.d_model
            H, K = cfg.n_heads, cfg.head_dim
            return {
                "last_tm": jnp.zeros((n, B, 1, d), dtype),
                "last_cm": jnp.zeros((n, B, 1, d), dtype),
                "wkv": jnp.zeros((n, B, H, K, K), jnp.float32),
            }
        raise ValueError(cfg.family)

    def decode_step(
        self,
        p: Params,
        cache: Params,
        tokens: jnp.ndarray,          # [B, 1]
        pos,                           # scalar: current position (cache fill)
        aux: Optional[Dict[str, jnp.ndarray]] = None,
        use_pallas: bool = False,
    ) -> Tuple[jnp.ndarray, Params]:
        cfg = self.cfg
        positions = pos + jnp.arange(tokens.shape[1])
        h = self.embed(p, tokens, positions=positions)

        if cfg.family in ("dense", "moe"):
            def step(carry, xs):
                h = carry
                lp, ck, cv, span_z = xs
                h, _, c = self._dense_layer_step(
                    lp, h, causal=True, positions=positions,
                    span_z=span_z, cache=(ck, cv), cache_pos=pos,
                    use_pallas=use_pallas,
                )
                return h, (c[0], c[1])

            span = p.get("span_z")
            if span is not None:
                span_xs = (
                    jnp.broadcast_to(span[:1], (cfg.n_layers, cfg.n_heads))
                    if span.shape[0] == 1 else span
                )
                h, (ks, vs) = jax.lax.scan(step, h, (p["layers"], cache["k"], cache["v"], span_xs))
            else:
                h, (ks, vs) = jax.lax.scan(
                    lambda c, xs: step(c, (xs[0], xs[1], xs[2], None)),
                    h, (p["layers"], cache["k"], cache["v"]),
                )
            cache = dict(cache, k=ks, v=vs)
        elif cfg.family == "albert":
            lp = p["layer"]

            def step(carry, xs):
                h = carry
                ck, cv = xs
                h, _, c = self._dense_layer_step(
                    lp, h, causal=True, positions=positions,
                    span_z=self._span_for_layer(p, 0), cache=(ck, cv), cache_pos=pos,
                    use_pallas=use_pallas,
                )
                return h, (c[0], c[1])

            h, (ks, vs) = jax.lax.scan(step, h, (cache["k"], cache["v"]))
            cache = dict(cache, k=ks, v=vs)
        elif cfg.family == "vlm":
            n_cross = cfg.n_layers // cfg.cross_attn_every
            n_self_per = cfg.cross_attn_every - 1
            self_layers = jax.tree_util.tree_map(
                lambda x: x.reshape((n_cross, n_self_per) + x.shape[1:]), p["layers"]
            )
            kr = cache["k"].reshape((n_cross, n_self_per) + cache["k"].shape[1:])
            vr = cache["v"].reshape((n_cross, n_self_per) + cache["v"].shape[1:])

            def group(carry, xs):
                h = carry
                selfs, cross, ck_g, cv_g, ik, iv = xs

                def inner(hh, ys):
                    lp, ck, cv = ys
                    hh, _, c = self._dense_layer_step(
                        lp, hh, causal=True, positions=positions,
                        cache=(ck, cv), cache_pos=pos,
                    )
                    return hh, (c[0], c[1])

                h, (ck_new, cv_new) = jax.lax.scan(inner, h, (selfs, ck_g, cv_g))
                # cross attention against cached image K/V
                x = self._cross_decode(cross, h, ik, iv)
                h = h + x
                return h, (ck_new, cv_new)

            h, (ks, vs) = jax.lax.scan(
                group, h,
                (self_layers, p["cross_layers"], kr, vr, cache["img_k"], cache["img_v"]),
            )
            cache = dict(
                cache,
                k=ks.reshape(cache["k"].shape),
                v=vs.reshape(cache["v"].shape),
            )
        elif cfg.family == "encdec":
            def step(carry, xs):
                h = carry
                lp, xp, ck, cv, ek, ev = xs
                h, _, c = self._dense_layer_step(
                    lp, h, causal=True, positions=positions, cache=(ck, cv), cache_pos=pos
                )
                x = self._precomputed_cross(xp, h, ek, ev)
                h = h + x
                return h, (c[0], c[1])

            h, (ks, vs) = jax.lax.scan(
                step, h,
                (p["layers"], p["dec_cross"], cache["k"], cache["v"], cache["enc_k"], cache["enc_v"]),
            )
            cache = dict(cache, k=ks, v=vs)
        elif cfg.family == "hybrid":
            x0 = h
            n_attn = cfg.n_layers // cfg.attn_every if cfg.attn_every else 0

            # scan mamba blocks; shared-attn invocations handled outside scan
            # via unrolled groups (attn_every static)
            new_conv, new_ssm = [], []
            ks_list, vs_list = [], []
            attn_idx = 0
            conv = cache["conv"]
            ssm = cache["ssm"]
            for i in range(cfg.n_layers):
                lp = jax.tree_util.tree_map(lambda x: x[i], p["layers"])
                h, st = self._mamba_block_step(
                    lp, h, states={"conv": conv[i], "ssm": ssm[i]}, decode=True
                )
                new_conv.append(st["conv"])
                new_ssm.append(st["ssm"])
                if cfg.attn_every and (i + 1) % cfg.attn_every == 0 and attn_idx < n_attn:
                    h, c = self._shared_attn_step(
                        p["shared_attn"], h, x0,
                        span_z=self._span_for_layer(p, 0),
                        cache=(cache["k"][attn_idx], cache["v"][attn_idx]),
                        cache_pos=pos, positions=positions,
                    )
                    ks_list.append(c[0])
                    vs_list.append(c[1])
                    attn_idx += 1
            cache = dict(
                cache,
                conv=jnp.stack(new_conv),
                ssm=jnp.stack(new_ssm),
            )
            if ks_list:
                cache["k"] = jnp.stack(ks_list)
                cache["v"] = jnp.stack(vs_list)
        elif cfg.family == "ssm":
            def step(carry, xs):
                h = carry
                lp, ltm, lcm, wkv = xs
                h, st = self._rwkv_layer_step(
                    lp, h, states={"last_tm": ltm, "last_cm": lcm, "wkv": wkv}, decode=True
                )
                return h, (st["last_tm"], st["last_cm"], st["wkv"])

            h, (ltm, lcm, wkv) = jax.lax.scan(
                step, h, (p["layers"], cache["last_tm"], cache["last_cm"], cache["wkv"])
            )
            cache = dict(cache, last_tm=ltm, last_cm=lcm, wkv=wkv)
        else:
            raise ValueError(cfg.family)

        h = L.apply_norm(
            p["final_norm"], h, "layernorm" if cfg.family == "ssm" else cfg.norm,
            use_pallas=use_pallas,
        )
        logits = self.lm_logits(p, h)
        return logits, cache

    def decode_step_ee(
        self,
        p: Params,
        cache: Params,
        tokens: jnp.ndarray,          # [B, 1]
        pos,                           # scalar or [B]: current cache position
        threshold,                     # entropy threshold (traced scalar ok)
        use_pallas: bool = False,
    ) -> Tuple[jnp.ndarray, Params, jnp.ndarray, jnp.ndarray]:
        """One decode step with PER-TOKEN early exit (EdgeBERT Alg. 1's
        entropy off-ramp generalized to autoregressive decode; the serving
        counterpart of the training-time ``forward_token_exit``).

        After every layer the shared LM head (post final-norm) is evaluated
        on the current hidden state; once its entropy drops below
        ``threshold`` the token FREEZES — hidden-state propagation: the
        remaining layers still write their KV rows (future tokens need
        something to attend to at every layer, so the frozen state is pushed
        through each remaining layer's KV projections), but the token's own
        representation stops evolving and the returned exit depth is what
        the modeled hardware actually executes (the DVFS layer charges
        layers ``1..exit`` only).  The computation is fully masked, so the
        fused serving step stays fixed-shape: one compile per bucket, and a
        batch-1 call computes bit-identically to a vmapped lane.

        Returns ``(logits [B,1,V], cache, exit_layer [B], first_entropy [B])``
        where ``exit_layer`` is 1-based and ``first_entropy`` is the LM-head
        entropy after layer 1 (the token's first off-ramp reading).
        """
        cfg = self.cfg
        assert cfg.family in ("dense", "moe", "albert"), (
            "per-token exit decode: KV-cache decoder families only"
        )
        positions = pos + jnp.arange(tokens.shape[1])
        h = self.embed(p, tokens, positions=positions)
        B = h.shape[0]
        V = cfg.vocab_size
        n_layers = cfg.n_layers

        def head_entropy(hh):
            lg = self.lm_logits(
                p, L.apply_norm(p["final_norm"], hh, cfg.norm, use_pallas=use_pallas)
            )
            if use_pallas:
                from repro.kernels import dispatch

                return lg, dispatch.entropy(lg)
            return lg, entropy_from_logits(lg)

        def body(carry, xs):
            h, done, logits, exit_layer, first_ent, i = carry
            if cfg.family == "albert":
                ck, cv = xs
                lp, span_z = p["layer"], self._span_for_layer(p, 0)
            else:
                lp, ck, cv, span_z = xs
            h_new, _, c = self._dense_layer_step(
                lp, h, causal=True, positions=positions,
                span_z=span_z, cache=(ck, cv), cache_pos=pos,
                use_pallas=use_pallas,
            )
            # frozen tokens keep their exited representation; the layer's KV
            # write above came from that frozen input (state propagation)
            h = jnp.where(done[..., None], h, h_new)
            lg, ent = head_entropy(h)                    # [B,1,V], [B,1]
            exit_now = jnp.logical_and(jnp.logical_not(done), ent < threshold)
            last = i == n_layers - 1
            take = jnp.logical_or(
                exit_now, jnp.logical_and(last, jnp.logical_not(done))
            )
            logits = jnp.where(take[..., None], lg, logits)
            exit_layer = jnp.where(take[:, 0], i + 1, exit_layer)
            first_ent = jnp.where(i == 0, ent[:, 0], first_ent)
            done = jnp.logical_or(done, exit_now)
            return (h, done, logits, exit_layer, first_ent, i + 1), (c[0], c[1])

        init = (
            h,
            jnp.zeros((B, 1), bool),
            jnp.zeros((B, 1, V), jnp.float32),
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.float32),
            jnp.array(0, jnp.int32),
        )
        if cfg.family == "albert":
            (h, done, logits, exit_layer, first_ent, _), (ks, vs) = jax.lax.scan(
                body, init, (cache["k"], cache["v"])
            )
        else:
            span = p.get("span_z")
            if span is not None:
                span_xs = (
                    jnp.broadcast_to(span[:1], (cfg.n_layers, cfg.n_heads))
                    if span.shape[0] == 1 else span
                )
            else:
                span_xs = None
            if span_xs is not None:
                (h, done, logits, exit_layer, first_ent, _), (ks, vs) = jax.lax.scan(
                    body, init, (p["layers"], cache["k"], cache["v"], span_xs)
                )
            else:
                (h, done, logits, exit_layer, first_ent, _), (ks, vs) = jax.lax.scan(
                    lambda cr, xs: body(cr, (xs[0], xs[1], xs[2], None)),
                    init, (p["layers"], cache["k"], cache["v"]),
                )
        cache = dict(cache, k=ks, v=vs)
        return logits, cache, exit_layer, first_ent

    def decode_step_spec(
        self,
        p: Params,
        cache: Params,
        tokens: jnp.ndarray,          # [1, 1] — one lane (see contract below)
        pos,                           # scalar cache position
        thresholds,                    # scalar, [W], or [1, W] entropy thresholds
        spec_window: int,
        eos_id: int = -1,
        use_pallas: bool = False,
    ):
        """Self-speculative fused decode step via the entropy off-ramps
        (the ROADMAP's "exit-at-k is a free draft model" item).

        Per fused step the lane runs up to ``spec_window`` slots.  Each slot
        is EXACTLY one ``decode_step_ee`` evaluation: the off-ramp at layer k
        emits the DRAFT (the frozen hidden state), the remaining layers
        k+1..L are the verifier pass (hidden-state propagation pushes the
        frozen draft through them, so the returned logits ARE the verified
        full-pipeline output), and the batched accept rule is evaluated on
        the slot outputs: a lane keeps speculating while its tokens take an
        off-ramp (``exit_layer < n_layers``) and don't emit EOS; the first
        token the verifier forces to full depth is still emitted (it is
        verified output) but TERMINATES the block.  ``accepted[j]`` marks
        the slots forming the accepted prefix; suffix slots idempotently
        recompute the lane's frozen (token, position) — the KV rows they
        write are bit-identical to what the next fused step would write, so
        KV "rollback" is simply not advancing the host position past the
        accepted prefix.  Everything is fixed-shape and masked (the batched
        accept/reject loop idiom): one compile per (bucket, spec_window).

        Because every slot is the unmodified ``decode_step_ee`` body,
        accepted tokens are bit-identical to the non-speculative path by
        construction, and ``spec_window=1`` degenerates to exactly one
        ``decode_step_ee`` call.

        Contract: one lane per call (``B == 1``) — lanes diverge in position
        as soon as acceptance diverges, and the KV write index must stay
        scalar; the serving layer vmaps this over lanes (see
        ``step_math.decoder_decode_spec``), same as the per-token EE path.

        ``thresholds`` may be a scalar (the degenerate schedule), or a
        per-slot row from an ``ExitThresholdSchedule`` (slot j gates the
        token at position ``pos + j``).

        Returns ``(tokens [1,W], logits [1,W,V], cache, exit_layers [1,W],
        first_ent [1,W], accepted [1,W])`` with ``exit_layers`` 1-based.
        """
        cfg = self.cfg
        assert cfg.family in ("dense", "moe", "albert"), (
            "speculative exit decode: KV-cache decoder families only"
        )
        W = int(spec_window)
        assert W >= 1, "spec_window must be >= 1"
        B = tokens.shape[0]
        assert B == 1, (
            "decode_step_spec is one-lane (B == 1); vmap over lanes via "
            "step_math.decoder_decode_spec"
        )
        thr = jnp.asarray(thresholds, jnp.float32)
        if thr.ndim == 0:
            thr = jnp.broadcast_to(thr, (B, W))
        elif thr.ndim == 1:
            thr = jnp.broadcast_to(thr[None, :], (B, W))
        assert thr.shape == (B, W), f"thresholds shape {thr.shape} != {(B, W)}"
        n_layers = cfg.n_layers

        def slot(carry, thr_j):
            cache_c, cur, posn, alive = carry
            accept = alive                         # accepted iff entered alive
            lg, cache_c, xl, fe = self.decode_step_ee(
                p, cache_c, cur, posn, thr_j[:, None], use_pallas=use_pallas
            )
            tok = jnp.argmax(lg[:, -1, :], axis=-1).astype(jnp.int32)
            alive = jnp.logical_and(accept, xl < n_layers)
            alive = jnp.logical_and(alive, tok != eos_id)
            cur = jnp.where(accept[:, None], tok[:, None], cur)
            posn = posn + accept[0].astype(jnp.int32)
            return (cache_c, cur, posn, alive), (tok, lg[:, -1, :], xl, fe, accept)

        init = (
            cache,
            tokens.astype(jnp.int32),
            jnp.asarray(pos, jnp.int32),
            jnp.ones((B,), bool),
        )
        (cache, _, _, _), (toks, lgs, xls, fes, accs) = jax.lax.scan(
            slot, init, jnp.moveaxis(thr, 1, 0)
        )
        return (
            jnp.moveaxis(toks, 0, 1),              # [B, W]
            jnp.moveaxis(lgs, 0, 1),               # [B, W, V]
            cache,
            jnp.moveaxis(xls, 0, 1),               # [B, W]
            jnp.moveaxis(fes, 0, 1),               # [B, W]
            jnp.moveaxis(accs, 0, 1),              # [B, W]
        )

    def _cross_decode(self, lp, h, ik, iv):
        """Cross-attention of decode queries against cached image K/V."""
        cfg = self.cfg
        B, S, _ = h.shape
        hn = L.apply_norm(lp["norm1"], h, cfg.norm)
        q = (hn @ lp["xattn"]["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
        out = L.attention(q, ik, iv, causal=False)
        out = out.reshape(B, S, -1) @ lp["xattn"]["wo"]
        x = jnp.tanh(lp["gate_attn"]).astype(h.dtype) * out
        m = L.apply_mlp(lp["mlp"], L.apply_norm(lp["norm2"], h + x, cfg.norm), cfg.act)
        return x + jnp.tanh(lp["gate_mlp"]).astype(h.dtype) * m

    def _precomputed_cross(self, xp, h, ek, ev):
        cfg = self.cfg
        B, S, _ = h.shape
        hn = L.apply_norm(xp["norm"], h, cfg.norm)
        q = (hn @ xp["xattn"]["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
        if "bq" in xp["xattn"]:
            q = q + xp["xattn"]["bq"].reshape(cfg.n_heads, cfg.head_dim)
        out = L.attention(q, ek, ev, causal=False)
        return out.reshape(B, S, -1) @ xp["xattn"]["wo"]

    # ---------------------------------------------------------------- prefill
    def prefill(self, p: Params, tokens: jnp.ndarray, cache: Params, aux=None):
        """Run the full prompt through the model, filling caches.

        Implemented as a full forward that also writes K/V (positions 0..S-1).
        Returns (last-token logits, cache).
        """
        cfg = self.cfg
        if cfg.family in ("dense", "moe", "albert"):
            h = self.embed(p, tokens)
            positions = jnp.arange(tokens.shape[1])

            def step(carry, xs):
                h = carry
                if cfg.family == "albert":
                    lp, (ck, cv) = p["layer"], xs
                    span_z = self._span_for_layer(p, 0)
                else:
                    lp, ck, cv = xs
                    span_z = None
                h, _, c = self._dense_layer_step(
                    lp, h, causal=True, positions=positions,
                    span_z=span_z, cache=(ck, cv), cache_pos=0,
                )
                return h, (c[0], c[1])

            if cfg.family == "albert":
                h, (ks, vs) = jax.lax.scan(
                    self._remat(step), h, (cache["k"], cache["v"])
                )
            else:
                h, (ks, vs) = jax.lax.scan(
                    self._remat(step), h, (p["layers"], cache["k"], cache["v"])
                )
            cache = dict(cache, k=ks, v=vs)
            h = L.apply_norm(p["final_norm"], h, cfg.norm)
            return self.lm_logits(p, h[:, -1:]), cache
        if cfg.family == "encdec":
            # encode once, cache cross K/V, then prefill decoder
            frames = aux["enc_input"]
            enc = self._encode(p, frames)

            def mk_kv(xp):
                k = (enc @ xp["xattn"]["wk"]).reshape(
                    enc.shape[0], enc.shape[1], cfg.n_kv_heads, cfg.head_dim
                )
                v = (enc @ xp["xattn"]["wv"]).reshape(
                    enc.shape[0], enc.shape[1], cfg.n_kv_heads, cfg.head_dim
                )
                return k, v

            del mk_kv  # einsum over stacked cross weights instead
            ek = jnp.einsum("bsd,ldk->lbsk", enc, p["dec_cross"]["xattn"]["wk"]).reshape(
                cfg.n_layers, enc.shape[0], enc.shape[1], cfg.n_kv_heads, cfg.head_dim
            )
            ev = jnp.einsum("bsd,ldk->lbsk", enc, p["dec_cross"]["xattn"]["wv"]).reshape(
                cfg.n_layers, enc.shape[0], enc.shape[1], cfg.n_kv_heads, cfg.head_dim
            )
            cache = dict(cache, enc_k=ek.astype(_dtype(cfg)), enc_v=ev.astype(_dtype(cfg)))
            h = self.embed(p, tokens)
            positions = jnp.arange(tokens.shape[1])

            def step(carry, xs):
                h = carry
                lp, xp, ck, cv, ek_l, ev_l = xs
                h, _, c = self._dense_layer_step(
                    lp, h, causal=True, positions=positions, cache=(ck, cv), cache_pos=0
                )
                x = self._precomputed_cross(xp, h, ek_l, ev_l)
                h = h + x
                return h, (c[0], c[1])

            h, (ks, vs) = jax.lax.scan(
                self._remat(step), h,
                (p["layers"], p["dec_cross"], cache["k"], cache["v"],
                 cache["enc_k"], cache["enc_v"]),
            )
            cache = dict(cache, k=ks, v=vs)
            h = L.apply_norm(p["final_norm"], h, cfg.norm)
            return self.lm_logits(p, h[:, -1:]), cache
        if cfg.family == "vlm":
            img = aux["image_embeds"]
            n_cross = cfg.n_layers // cfg.cross_attn_every
            ik = jnp.einsum("bsd,ldk->lbsk", img, p["cross_layers"]["xattn"]["wk"]).reshape(
                n_cross, img.shape[0], img.shape[1], cfg.n_kv_heads, cfg.head_dim
            )
            iv = jnp.einsum("bsd,ldk->lbsk", img, p["cross_layers"]["xattn"]["wv"]).reshape(
                n_cross, img.shape[0], img.shape[1], cfg.n_kv_heads, cfg.head_dim
            )
            cache = dict(cache, img_k=ik.astype(_dtype(cfg)), img_v=iv.astype(_dtype(cfg)))
            h = self.embed(p, tokens)
            positions = jnp.arange(tokens.shape[1])
            n_self_per = cfg.cross_attn_every - 1
            self_layers = jax.tree_util.tree_map(
                lambda x: x.reshape((n_cross, n_self_per) + x.shape[1:]), p["layers"]
            )
            kr = cache["k"].reshape((n_cross, n_self_per) + cache["k"].shape[1:])
            vr = cache["v"].reshape((n_cross, n_self_per) + cache["v"].shape[1:])

            def group(carry, xs):
                h = carry
                selfs, cross, ck_g, cv_g, ik_l, iv_l = xs

                def inner(hh, ys):
                    lp, ck, cv = ys
                    hh, _, c = self._dense_layer_step(
                        lp, hh, causal=True, positions=positions, cache=(ck, cv), cache_pos=0
                    )
                    return hh, (c[0], c[1])

                h, (ck_new, cv_new) = jax.lax.scan(inner, h, (selfs, ck_g, cv_g))
                h = h + self._cross_decode(cross, h, ik_l, iv_l)
                return h, (ck_new, cv_new)

            h, (ks, vs) = jax.lax.scan(
                self._remat(group), h,
                (self_layers, p["cross_layers"], kr, vr, cache["img_k"], cache["img_v"]),
            )
            cache = dict(cache, k=ks.reshape(cache["k"].shape), v=vs.reshape(cache["v"].shape))
            h = L.apply_norm(p["final_norm"], h, cfg.norm)
            return self.lm_logits(p, h[:, -1:]), cache
        if cfg.family == "ssm":
            h = self.embed(p, tokens)

            def step(h, lp):
                h, st = self._rwkv_layer_step(lp, h)
                return h, (st["last_tm"], st["last_cm"], st["wkv"])

            h, (ltm, lcm, wkv) = jax.lax.scan(self._remat(step), h, p["layers"])
            cache = dict(cache, last_tm=ltm, last_cm=lcm, wkv=wkv)
            h = L.apply_norm(p["final_norm"], h, "layernorm")
            return self.lm_logits(p, h[:, -1:]), cache
        if cfg.family == "hybrid":
            h = self.embed(p, tokens)
            x0 = h
            positions = jnp.arange(tokens.shape[1])
            n_attn = cfg.n_layers // cfg.attn_every if cfg.attn_every else 0
            new_conv, new_ssm, ks_list, vs_list = [], [], [], []
            attn_idx = 0
            for i in range(cfg.n_layers):
                lp = jax.tree_util.tree_map(lambda x: x[i], p["layers"])
                h, st = self._mamba_block_step(lp, h)
                new_conv.append(st["conv"])
                new_ssm.append(st["ssm"])
                if cfg.attn_every and (i + 1) % cfg.attn_every == 0 and attn_idx < n_attn:
                    h, c = self._shared_attn_step(
                        p["shared_attn"], h, x0,
                        span_z=self._span_for_layer(p, 0),
                        cache=(cache["k"][attn_idx], cache["v"][attn_idx]),
                        cache_pos=0, positions=positions,
                    )
                    ks_list.append(c[0])
                    vs_list.append(c[1])
                    attn_idx += 1
            cache = dict(cache, conv=jnp.stack(new_conv), ssm=jnp.stack(new_ssm))
            if ks_list:
                cache["k"] = jnp.stack(ks_list)
                cache["v"] = jnp.stack(vs_list)
            h = L.apply_norm(p["final_norm"], h, cfg.norm)
            return self.lm_logits(p, h[:, -1:]), cache
        raise ValueError(cfg.family)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


def count_params(params: Params) -> int:
    import numpy as np

    return int(
        sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(params) if hasattr(x, "shape"))
    )
