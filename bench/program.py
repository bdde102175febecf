"""Glue between a configuration file and the system under test (``repro``).

The file's numbers are what runs: the program's registered spec for the
configuration's ``arch`` is taken and every number the file gives replaces
the spec's, so the file and the run cannot drift apart.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

from bench.harness import BenchError

# published (Hugging Face) key -> the program's ModelSpec field
LM_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}
VGG_KEYS = ("conv_channels", "pool_after", "fc_dims", "image_size",
            "in_channels", "num_classes")


def model_spec(cfg: Dict[str, Any]):
    from repro.configs import get_spec

    base = get_spec(cfg["arch"])
    if cfg["family"] == "vgg":
        fields = {k: (tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                  for k in VGG_KEYS}
    else:
        fields = {f: cfg[k] for k, f in LM_KEYS.items()}
        if cfg.get("hidden_act", "silu") != "silu":
            raise BenchError(f"{cfg['name']}: only SwiGLU MLPs run here")
    spec = dataclasses.replace(base, **fields)
    if getattr(spec, "family", "vgg") != cfg["family"]:
        raise BenchError(f"{cfg['name']}: family {cfg['family']!r} but the "
                         f"program's {cfg['arch']} is {spec.family!r}")
    return spec


def leaf_norms_fn(single):
    """A function ``(p0, stacked) -> {leaf: norm}`` of ``stacked - p0``.

    ``p0`` is one model, ``stacked`` the client-stacked parameters
    ([N, ...] leaves).  Leaves of a stacked unit container ([N, L, ...])
    are split per layer (``units/attn/wq[7]``); leaf names are the
    parameter paths, as the references name theirs."""
    import jax
    import jax.numpy as jnp

    paths = jax.tree_util.tree_flatten_with_path(single)[0]

    def name_of(path):
        return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)

    def per_layer(path):
        return (len(path) > 1 and name_of(path[:1]) == "units"
                and isinstance(path[1], jax.tree_util.DictKey))

    def fn(p0, stacked):
        out = {}
        flat0 = jax.tree.leaves(p0)
        flat = jax.tree.leaves(stacked)
        for (path, _), a, x in zip(paths, flat0, flat):
            d = x.astype(jnp.float32) - a.astype(jnp.float32)[None]
            if per_layer(path):
                sq = jnp.sum(jnp.square(d).reshape(d.shape[0], d.shape[1], -1), axis=(0, 2))
                for l in range(d.shape[1]):
                    out[f"{name_of(path)}[{l}]"] = jnp.sqrt(sq[l])
            else:
                out[name_of(path)] = jnp.sqrt(jnp.sum(jnp.square(d)))
        return out

    return jax.jit(fn)
