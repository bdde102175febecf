"""Batched decode driver (serve_step) — CPU-runnable on reduced configs.

After HSFL training converges, the fed server owns the aggregated model;
this driver runs batched autoregressive decoding against a KV/state cache,
the same ``decode_step`` the decode_32k / long_500k dry-runs lower.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --batch 4 --prompt-len 16 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def load_serving_params(path: str, template):
    """Restore serving params from either checkpoint layout.

    ``launch.train`` saves the Engine-A *client-stacked* state (every
    leaf carries a leading client axis N) — including sharded/async runs,
    which drain in-flight syncs before saving.  After the top-tier cloud
    sync every client row holds the aggregated model, so the serving copy
    is row 0.  A plain single-model checkpoint restores as-is.
    """
    import numpy as np

    from ..checkpoint import load_checkpoint
    from ..checkpoint.npz import _seg
    from ..core.engine import replicate_for_clients, unreplicate

    try:
        params, _, _ = load_checkpoint(path, template)
        return params
    except ValueError:
        pass  # shapes mismatched — try the client-stacked layout
    leaves = jax.tree_util.tree_flatten_with_path(template)[0]
    key0 = "/".join(_seg(p) for p in leaves[0][0])
    with np.load(path) as z:
        if key0 not in z:
            raise KeyError(f"checkpoint missing leaf {key0!r}")
        saved = z[key0].shape
    want = np.asarray(leaves[0][1]).shape
    if len(saved) != len(want) + 1:
        raise ValueError(
            f"checkpoint leaf {key0!r} has shape {saved}, which is neither "
            f"the serving shape {want} nor client-stacked (N,)+{want}"
        )
    n = int(saved[0])
    stacked, _, _ = load_checkpoint(path, replicate_for_clients(template, n))
    return unreplicate(stacked)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from ..configs import get_reduced
    from ..models.model import SplittableModel
    from .compile_cache import configure_compile_cache

    configure_compile_cache()
    spec = get_reduced(args.arch)
    if spec.family in ("vlm", "audio"):
        raise SystemExit(f"{args.arch}: decode driver supports text-only archs")
    model = SplittableModel(spec)
    key = jax.random.PRNGKey(args.seed)
    params = model.init_params(key)
    if args.checkpoint:
        params = load_serving_params(args.checkpoint, params)
        print(f"restored {args.checkpoint}")

    B = args.batch
    caches = model.init_caches(B, args.cache_len)
    decode = jax.jit(model.decode_step)

    key, k1 = jax.random.split(key)
    prompt = jax.random.randint(k1, (B, args.prompt_len), 0, spec.vocab_size)

    # prefill via repeated decode (tiny models; exercises the cache path)
    t0 = time.time()
    tok = prompt[:, :1]
    for i in range(args.prompt_len):
        logits, caches = decode(params, prompt[:, i : i + 1], caches, jnp.int32(i))
    out_tokens = []
    tok = jnp.argmax(logits[:, : spec.vocab_size], axis=-1)[:, None]
    for i in range(args.gen):
        logits, caches = decode(
            params, tok, caches, jnp.int32(args.prompt_len + i)
        )
        if args.temperature > 0:
            key, ks = jax.random.split(key)
            tok = jax.random.categorical(
                ks, logits[:, : spec.vocab_size] / args.temperature
            )[:, None]
        else:
            tok = jnp.argmax(logits[:, : spec.vocab_size], axis=-1)[:, None]
        out_tokens.append(tok)
    dt = time.time() - t0
    gen = jnp.concatenate(out_tokens, axis=1)
    total = B * (args.prompt_len + args.gen)
    print(f"[serve] arch={spec.name} batch={B} prompt={args.prompt_len} "
          f"gen={args.gen}: {total/dt:.1f} tok/s ({dt:.2f}s)")
    print("sample tokens:", gen[0, :16].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
