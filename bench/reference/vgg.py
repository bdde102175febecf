"""Plain float32 reference of VGG on CIFAR-sized images.

Straight ``jax.numpy``/``lax`` under whatever matmul precision the caller
sets (the checks set ``highest``).  The weights are drawn from the seed in
the order the configuration's model lays them out: one key per layer, He
normal weights, zero biases.  Leaves are named ``units/<layer>/w|b``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def _layers(cfg):
    out, cin = [], cfg["in_channels"]
    hw = cfg["image_size"]
    for i, cout in enumerate(cfg["conv_channels"]):
        out.append(("conv", cin, cout, i in cfg["pool_after"]))
        cin = cout
        hw //= 2 if i in cfg["pool_after"] else 1
    fin = cin * hw * hw
    for fout in cfg["fc_dims"]:
        out.append(("fc", fin, fout, False))
        fin = fout
    return out


def init(cfg, key, dtype=jnp.float32):
    layers = _layers(cfg)
    keys = jax.random.split(key, len(layers))
    params = []
    for k, (kind, cin, cout, _) in zip(keys, layers):
        if kind == "conv":
            w = jax.random.normal(k, (3, 3, cin, cout)) * math.sqrt(2.0 / (9 * cin))
        else:
            w = jax.random.normal(k, (cin, cout)) * math.sqrt(2.0 / cin)
        params.append({"w": w.astype(dtype), "b": jnp.zeros((cout,), dtype)})
    return params


def logits(cfg, params, images):
    h = images.astype(params[0]["w"].dtype)
    layers = _layers(cfg)
    for u, (p, (kind, _, _, pool)) in enumerate(zip(params, layers)):
        if kind == "conv":
            h = lax.conv_general_dilated(
                h, p["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            ) + p["b"]
            h = jnp.maximum(h, 0)
            if pool:
                h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 1),
                                      (1, 2, 2, 1), "VALID")
        else:
            h = h.reshape(h.shape[0], -1) @ p["w"] + p["b"]
            if u < len(layers) - 1:
                h = jnp.maximum(h, 0)
    return h.astype(jnp.float32)


def loss(cfg, params, batch):
    z = logits(cfg, params, batch["images"])
    lab = batch["labels"]
    picked = jnp.take_along_axis(z, lab[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - picked)


def tiers(cfg, params, cuts):
    """Tier of every leaf: units below the first cut are tier 0, and so on."""
    def tier(u):
        return sum(u >= c for c in cuts)

    return [{"w": tier(u), "b": tier(u)} for u in range(len(params))]


def named_norms(tree):
    """``{leaf name: L2 norm}`` with the leaf names of the checks."""
    out = {}
    for u, p in enumerate(tree):
        for k in ("w", "b"):
            out[f"units/{u}/{k}"] = jnp.sqrt(jnp.sum(jnp.square(p[k].astype(jnp.float32))))
    return out
