"""Plain float32 reference of a decoder-only Llama-style transformer.

RMSNorm with a learned gain (stored as the offset from 1), rotary
embeddings on the two halves of each head, grouped-query attention with a
causal mask, a SwiGLU MLP, and an output head tied to the embedding, as
the published SmolLM/Llama configurations describe.  Straight
``jax.numpy`` under whatever matmul precision the caller sets (the checks
set ``highest``); activations are kept in the parameters' dtype, and the
norm statistics, softmax and loss in float32.

The weights are drawn from the seed in the order the configuration's
model lays them out: the key splits into embedding, layers and head; each
layer's key splits into attention and MLP keys; weights are normal with
scale 1/sqrt(fan-in), the embedding 0.02, the norm gains zero.  Leaves
are named as the checks compare them: ``frontend/embed``,
``units/attn/wq[<layer>]``, ..., ``head/norm``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

_NAMES = {
    "wq": "units/attn/wq", "wk": "units/attn/wk", "wv": "units/attn/wv",
    "wo": "units/attn/wo", "attn_norm": "units/attn/norm",
    "w1": "units/mlp/w1", "w2": "units/mlp/w2", "w3": "units/mlp/w3",
    "mlp_norm": "units/mlp/norm",
}


def _dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, h, cfg["num_key_value_heads"], d // h, cfg["intermediate_size"]


def init(cfg, key, dtype=jnp.float32):
    d, h, k, hd, ff = _dims(cfg)
    kf, ku, _ = jax.random.split(key, 3)

    def normal(key, shape, fan_in):
        return jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))

    def layer(key):
        ks = jax.random.split(key, 16)
        ka = jax.random.split(ks[0], 8)
        km = jax.random.split(ks[1], 3)
        return {
            "wq": normal(ka[0], (d, h * hd), d),
            "wk": normal(ka[1], (d, k * hd), d),
            "wv": normal(ka[2], (d, k * hd), d),
            "wo": normal(ka[3], (h * hd, d), h * hd),
            "attn_norm": jnp.zeros((d,)),
            "w1": normal(km[0], (d, ff), d),
            "w2": normal(km[1], (ff, d), ff),
            "w3": normal(km[2], (d, ff), d),
            "mlp_norm": jnp.zeros((d,)),
        }

    params = {
        "embed": jax.random.normal(kf, (cfg["vocab_size"], d)) * 0.02,
        "layers": jax.vmap(layer)(jax.random.split(ku, cfg["num_hidden_layers"])),
        "final_norm": jnp.zeros((d,)),
    }
    return jax.tree.map(lambda x: x.astype(dtype), params)


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + g.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    s, hd = x.shape[-3], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def hidden(cfg, params, tokens):
    """Final normed hidden states [B, S, d] of tokens [B, S]."""
    d, h, k, hd, _ = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = params["embed"][tokens]
    B, S = tokens.shape
    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(x, p):
        a = _rms(x, p["attn_norm"], eps)
        q = _rope((a @ p["wq"]).reshape(B, S, h, hd), theta)
        kk = _rope((a @ p["wk"]).reshape(B, S, k, hd), theta)
        v = (a @ p["wv"]).reshape(B, S, k, hd)
        kk = jnp.repeat(kk, h // k, axis=2)
        v = jnp.repeat(v, h // k, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32)
        s = jnp.where(causal, s / math.sqrt(hd), -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, h * hd)
        x = x + o @ p["wo"]
        m = _rms(x, p["mlp_norm"], eps)
        x = x + (jax.nn.silu(m @ p["w1"]) * (m @ p["w3"])) @ p["w2"]
        return x, None

    x, _ = lax.scan(block, x, params["layers"])
    return _rms(x, params["final_norm"], eps)


def logits(cfg, params, tokens):
    x = hidden(cfg, params, tokens)
    return (x @ params["embed"].T).astype(jnp.float32)


def loss(cfg, params, batch):
    z = logits(cfg, params, batch["tokens"])
    lab = batch["labels"]
    picked = jnp.take_along_axis(z, lab[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - picked)


def tiers(cfg, params, cuts):
    """Tier of every leaf; per layer (a row vector) for the stacked layers."""
    L = cfg["num_hidden_layers"]
    layer_tier = jnp.array([sum(l >= c for c in cuts) for l in range(L)])
    return {
        "embed": 0,
        "layers": jax.tree.map(lambda _: layer_tier, params["layers"]),
        "final_norm": len(cuts),
    }


def named_norms(tree):
    """``{leaf name: L2 norm}``, one entry per layer for the stacked layers."""
    sq = lambda x: jnp.sum(jnp.square(x.astype(jnp.float32)))
    out = {"frontend/embed": jnp.sqrt(sq(tree["embed"])),
           "head/norm": jnp.sqrt(sq(tree["final_norm"]))}
    for k, name in _NAMES.items():
        x = tree["layers"][k].astype(jnp.float32)
        per = jnp.sqrt(jnp.sum(jnp.square(x).reshape(x.shape[0], -1), axis=1))
        for l in range(x.shape[0]):
            out[f"{name}[{l}]"] = per[l]
    return out
