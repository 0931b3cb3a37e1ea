"""Plain float32 reference of the served ALBERT classifier with off-ramps.

Written from the configuration file alone, in straightforward ``jax.numpy``:
no kernels, lanes, buckets or scheduler, and nothing imported from the
program.  It computes, for every prompt, the off-ramp logits and entropy
after each of the ``num_hidden_layers`` applications of the one shared
encoder layer.

The model is ALBERT as the configuration states it: a factorized token
embedding (``embedding_size`` projected to ``hidden_size``) plus learned
absolute positions, then one post-LayerNorm encoder layer applied
``num_hidden_layers`` times (self-attention, residual, LayerNorm; a
tanh-GELU MLP, residual, LayerNorm).  With ``span_enabled`` each head's
attention probabilities are multiplied by the adaptive-span soft mask
``clip((ramp + z_h - |i - j|) / ramp, 0, 1)`` and renormalised
(Sukhbaatar et al. 2019).  An off-ramp reads token 0: ``tanh`` pooler,
then a linear classifier; its entropy is that of the softmax over classes.

Prompts are right-padded to one length and padded keys are masked out of
attention, which in exact arithmetic is the computation at each prompt's
native length: no valid position ever attends to a padded one, and the
off-ramp reads position 0 only.

``precision="highest"`` runs every matmul in float32 (six bf16 passes on
a TPU).  ``precision="high"`` is the control: every matmul split into
bf16 high and low halves and summed over three bf16 products with float32
accumulation, the TPU's three-pass algorithm, written out so that it
computes the same on any backend.  ``precision="bf16"`` is one bf16 pass
per matmul with float32 accumulation, the step below that.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high", "bf16")


def _einsum(eq: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")
    one = functools.partial(jnp.einsum, eq, preferred_element_type=jnp.float32)
    if precision == "bf16":
        return one(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))

    def split(x):
        # rounded in float32 first, so that no compiler may fold the
        # f32 -> bf16 -> f32 round trip away and lose the low half
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
    return one(a_hi, b_hi) + one(a_hi, b_lo) + one(a_lo, b_hi)


def _layernorm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _entropy(logits):
    z = logits - logits.max(-1, keepdims=True)
    p = jnp.exp(z) / jnp.exp(z).sum(-1, keepdims=True)
    return jnp.log(jnp.exp(z).sum(-1)) - (p * z).sum(-1)


def forward(cfg: dict, params, tokens, lengths, precision: str = "highest"):
    """Off-ramp logits ``[B, L, C]`` and entropies ``[B, L]`` after every
    layer, for right-padded ``tokens [B, P]`` with ``lengths [B]``."""
    if cfg.get("quantize_activations"):
        raise NotImplementedError("the reference has no activation quantization")
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, eps = D // H, cfg["layer_norm_eps"]
    B, P = tokens.shape
    mm = functools.partial(_einsum, precision=precision)
    emb, lp, ramp = params["embed"], params["layer"], params["offramp"]

    h = mm("bpe,ed->bpd", emb["tok"][tokens], emb["proj"]) + emb["pos"][:P]
    key_ok = jnp.arange(P)[None, :] < lengths[:, None]                    # [B, P]
    dist = jnp.abs(jnp.arange(P)[:, None] - jnp.arange(P)[None, :]).astype(jnp.float32)
    if cfg["span_enabled"]:
        r = float(cfg["span_ramp"])
        span = jnp.clip((r + params["span_z"][0][:, None, None] - dist) / r, 0.0, 1.0)
    a, m = lp["attn"], lp["mlp"]

    def layer(h, _):
        q, k, v = (mm("bpd,de->bpe", h, a[w]).reshape(B, P, H, hd)
                   for w in ("wq", "wk", "wv"))
        s = mm("bqhe,bkhe->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(key_ok[:, None, None, :], s, -jnp.inf)
        p = jnp.exp(s - s.max(-1, keepdims=True))
        if cfg["span_enabled"]:
            p = p * span[None]
        # a padded query row can lie beyond every valid key's span: it then
        # weighs nothing, and must stay finite, since its value row is
        # multiplied by zero weights in every valid row
        p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        ctx = mm("bhqk,bkhe->bqhe", p, v).reshape(B, P, D)
        h = _layernorm(h + mm("bpd,de->bpe", ctx, a["wo"]),
                       lp["norm1"]["scale"], lp["norm1"]["norm_bias"], eps)
        f = mm("bpf,fd->bpd", _gelu_tanh(mm("bpd,df->bpf", h, m["w_up"])), m["w_down"])
        h = _layernorm(h + f, lp["norm2"]["scale"], lp["norm2"]["norm_bias"], eps)
        pooled = jnp.tanh(mm("bd,de->be", h[:, 0], ramp["offramp_pooler_w"])
                          + ramp["offramp_pooler_b"])
        logits = mm("bd,dc->bc", pooled, ramp["offramp_cls_w"]) + ramp["offramp_cls_b"]
        return h, (logits, _entropy(logits))

    _, (logits, ents) = jax.lax.scan(layer, h, None, length=cfg["num_hidden_layers"])
    return logits.transpose(1, 0, 2), ents.T


@functools.lru_cache(maxsize=None)
def _jitted(cfg_items: tuple, precision: str):
    cfg = dict(cfg_items)
    return jax.jit(lambda p, t, n: forward(cfg, p, t, n, precision))


def run(cfg: dict, params, prompts, *, block: int, pad: int, precision: str = "highest"):
    """``forward`` over ``prompts`` (a list of 1-D token arrays) in blocks of
    ``block`` rows right-padded to ``pad``; returns numpy logits
    ``[N, L, C]`` and entropies ``[N, L]``.  One compiled program serves
    every block."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool, type(None)))))
    fn = _jitted(items, precision)
    logits, ents = [], []
    for i in range(0, len(prompts), block):
        rows = prompts[i:i + block]
        toks = np.zeros((block, pad), np.int32)
        lens = np.ones(block, np.int32)
        for j, t in enumerate(rows):
            toks[j, :len(t)] = t
            lens[j] = len(t)
        lg, en = fn(params, jnp.asarray(toks), jnp.asarray(lens))
        logits.append(np.asarray(lg)[:len(rows)])
        ents.append(np.asarray(en)[:len(rows)])
    return np.concatenate(logits), np.concatenate(ents)
