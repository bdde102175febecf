"""Plain float32 reference of the DeepSeek-V3 block (Moonlight-16B-A3B).

Each layer is RMSNorm, multi-head latent attention, RMSNorm and a
feed-forward, each with a residual.  The first ``first_k_dense_replace``
layers have a SwiGLU of ``intermediate_size``; the rest the experts:

* MLA with no query latent (``q_lora_rank`` null): queries ``x W_q`` of
  ``qk_nope_head_dim + qk_rope_head_dim`` per head; ``x W_kva`` gives the
  latent ``c`` (``kv_lora_rank``) and one rotary key ``k_pe`` shared by
  every head; ``RMSNorm(c) W_kvb`` gives each head's ``k_nope`` and ``v``.
  Rotary embedding on adjacent pairs (DeepSeek's pairing) at
  ``rope_theta``, softmax scale 1/sqrt(qk head dim), dense causal softmax.
* Experts: ``sigmoid(x W_g)`` over all ``expert_share.router_experts``
  experts in float32; the top ``num_experts_per_tok`` of the scores plus
  the selection bias are chosen, weighted by their unbiased scores
  normalised to sum 1 and times ``routed_scaling_factor``.  This device
  holds ``n_routed_experts`` of them (from ``expert_share.first_held``);
  each held expert's SwiGLU of ``moe_intermediate_size`` is computed for
  every token and weighted by the token's weight for it (zero where not
  chosen), so chosen experts held elsewhere add nothing.  The shared
  experts (one SwiGLU of ``n_shared_experts * moe_intermediate_size``) run
  on every token.
* An untied output head and the mean token cross-entropy.

Departures from the published model, as the configuration lists them:
the selection bias (``e_score_correction_bias``) is drawn from the seed
with std ``selection_bias_std`` and held fixed (the aux-loss-free update
has no rate in the config); no auxiliary loss is added (the deepseek_v3
model code computes none, whatever ``aux_loss_alpha`` a config states);
RMSNorm gains are stored as the offset from 1.

Straight ``jax.numpy`` under whatever matmul precision the caller sets
(the checks set ``highest``); activations in the parameters' dtype, the
gate, norm statistics, softmax and loss in float32.  Weights are drawn from
the seed in the order the configuration's model lays them out, and leaves
are named as the checks compare them (``frontend/dense/attn/wq``,
``units/moe/w1[<layer>]``, ``head/unembed``, ...).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def _sizes(cfg):
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        ff=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        E=cfg["expert_share"]["router_experts"], lo=cfg["expert_share"]["first_held"],
        held=cfg["n_routed_experts"], K=cfg["num_experts_per_tok"],
        dense=cfg["first_k_dense_replace"],
    )


def _normal(key, shape, scale):
    return jax.random.normal(key, shape) * scale


def _w(key, shape):
    return _normal(key, shape, 1.0 / math.sqrt(shape[0]))


def init(cfg, key, dtype=jnp.float32):
    z = _sizes(cfg)
    d, h = z["d"], z["h"]
    V = cfg["vocab_size"]

    def mla(key):
        ks = jax.random.split(key, 4)
        return {"wq": _w(ks[0], (d, h * (z["nope"] + z["rope"]))),
                "wkva": _w(ks[1], (d, z["rank"] + z["rope"])),
                "kv_norm": jnp.zeros((z["rank"],)),
                "wkvb": _w(ks[2], (z["rank"], h * (z["nope"] + z["v"]))),
                "wo": _w(ks[3], (h * z["v"], d)),
                "norm": jnp.zeros((d,))}

    def swiglu(key, ff):
        ks = jax.random.split(key, 3)
        return {"w1": _w(ks[0], (d, ff)), "w2": _w(ks[1], (ff, d)), "w3": _w(ks[2], (d, ff))}

    def dense_layer(key):
        ka, km = jax.random.split(key)
        return {"attn": mla(ka), "mlp": dict(swiglu(km, z["ff"]), norm=jnp.zeros((d,)))}

    def expert(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return _w(k1, (d, z["fe"])), _w(k3, (d, z["fe"])), _w(k2, (z["fe"], d))

    def expert_layer(key):
        ks = jax.random.split(key, 16)
        km = jax.random.split(ks[1], 4)
        w1, w3, w2 = jax.vmap(expert)(
            jax.random.split(km[1], z["E"])[z["lo"]: z["lo"] + z["held"]])
        moe = {"router": _w(km[0], (d, z["E"])),
               "bias": _normal(km[3], (z["E"],), cfg["selection_bias_std"]),
               "w1": w1, "w3": w3, "w2": w2, "norm": jnp.zeros((d,))}
        if cfg["n_shared_experts"]:
            moe["shared"] = swiglu(km[2], cfg["n_shared_experts"] * z["fe"])
        return {"attn": mla(ks[0]), "moe": moe}

    kf, ku, kh = jax.random.split(key, 3)
    n_units = cfg["num_hidden_layers"] - z["dense"]
    params = {
        "frontend": {
            "embed": _normal(kf, (V, d), 0.02),
            "dense": jax.vmap(dense_layer)(jax.random.split(jax.random.fold_in(kf, 1), z["dense"])),
        },
        "units": jax.vmap(expert_layer)(jax.random.split(ku, n_units)),
        "head": {"norm": jnp.zeros((d,)), "unembed": _normal(kh, (d, V), 0.02)},
    }
    return jax.tree.map(lambda x: x.astype(dtype), params)


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + g.astype(jnp.float32))).astype(x.dtype)


def _rope_pairs(x, theta):
    """x [B, S, ..., r]: rotate each adjacent pair (x[2i], x[2i+1]) of
    position s by s * theta^(-2i/r), in place."""
    s, r = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs  # [S, r/2]
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (r // 2,))
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _mla(cfg, p, x):
    z = _sizes(cfg)
    B, S, _ = x.shape
    h, nope, rope = z["h"], z["nope"], z["rope"]
    q = (x @ p["wq"]).reshape(B, S, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope_pairs(q[..., nope:], cfg["rope_theta"])], -1)
    kva = x @ p["wkva"]
    c = _rms(kva[..., : z["rank"]], p["kv_norm"], cfg["rms_norm_eps"])
    k_pe = _rope_pairs(kva[..., z["rank"]:], cfg["rope_theta"])
    kv = (c @ p["wkvb"]).reshape(B, S, h, nope + z["v"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, :, None], (B, S, h, rope))], -1)
    v = kv[..., nope:]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(nope + rope)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, h * z["v"])
    return o @ p["wo"]


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def gate_weights(cfg, p, x):
    """[T, router experts]: each token's weight for every expert, zero
    where not chosen; and the chosen experts [T, K]."""
    z = _sizes(cfg)
    scores = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    _, chosen = lax.top_k(scores + p["bias"].astype(jnp.float32), z["K"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    dense = jnp.sum(jax.nn.one_hot(chosen, z["E"]) * w[..., None], axis=-2)
    return dense, chosen


def _moe(cfg, p, x):
    z = _sizes(cfg)
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    gate, _ = gate_weights(cfg, p, xt)
    gate = gate[:, z["lo"]: z["lo"] + z["held"]].astype(x.dtype)
    hid = jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["w1"])) * jnp.einsum("td,edf->tef", xt, p["w3"])
    out = jnp.einsum("te,tef,efd->td", gate, hid, p["w2"])
    if "shared" in p:
        out = out + _swiglu(p["shared"], xt)
    return out.reshape(B, S, d)


def _layer(cfg, p, x):
    eps = cfg["rms_norm_eps"]
    x = x + _mla(cfg, p["attn"], _rms(x, p["attn"]["norm"], eps))
    if "mlp" in p:
        return x + _swiglu(p["mlp"], _rms(x, p["mlp"]["norm"], eps))
    return x + _moe(cfg, p["moe"], _rms(x, p["moe"]["norm"], eps))


def hidden(cfg, params, tokens):
    """Final normed hidden states [B, S, d] of tokens [B, S].  Each layer is
    recomputed in the backward pass (``jax.checkpoint``), so the gradient
    of a published-width layer at 2048 tokens fits beside the parameters."""
    x = params["frontend"]["embed"][tokens]
    layer = jax.checkpoint(lambda x, p: (_layer(cfg, p, x), None))
    x, _ = lax.scan(layer, x, params["frontend"]["dense"])
    x, _ = lax.scan(layer, x, params["units"])
    return _rms(x, params["head"]["norm"], cfg["rms_norm_eps"])


def logits(cfg, params, tokens):
    return (hidden(cfg, params, tokens) @ params["head"]["unembed"]).astype(jnp.float32)


def loss(cfg, params, batch):
    z = logits(cfg, params, batch["tokens"])
    lab = batch["labels"]
    picked = jnp.take_along_axis(z, lab[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - picked)


def tiers(cfg, params, cuts):
    """Tier of every leaf: the embedding and the dense layers tier 1 (0),
    the head the last; per layer (a row vector) for the expert layers."""
    n_units = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    unit_tier = jnp.array([sum(u >= c for c in cuts) for u in range(n_units)])
    return {
        "frontend": jax.tree.map(lambda _: 0, params["frontend"]),
        "units": jax.tree.map(lambda _: unit_tier, params["units"]),
        "head": jax.tree.map(lambda _: len(cuts), params["head"]),
    }


def named_norms(tree):
    """``{leaf name: L2 norm}``, one entry per layer for the expert layers."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(k.key for k in path)
        x = x.astype(jnp.float32)
        if name.startswith("units/"):
            per = jnp.sqrt(jnp.sum(jnp.square(x).reshape(x.shape[0], -1), axis=1))
            for l in range(x.shape[0]):
                out[f"{name}[{l}]"] = per[l]
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out
