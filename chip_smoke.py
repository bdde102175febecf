#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, through the normal entry points.

    python chip_smoke.py                # one chip: the four phases below
    python chip_smoke.py --four-chips   # a four-chip host: sharded Engine A

One chip, one process, one compilation cache, published widths:

1. ``train_vgg``: the paper's VGG-16/CIFAR-10, 20 clients under 5 edges,
   through ``repro.launch.train``; the default plan's local rounds and its
   first fed sync (edge tier, round 4).
2. ``train_lm``: SmolLM-135M, 4 clients under 2 edges, M = 3 tiers, a
   fixed (2, 2, 1) schedule, through ``repro.api.run``.
3. ``decode``: SmolLM-135M through the jitted ``decode_step`` that
   ``launch.serve`` uses, batch 8, cache 2048; the decoded logits are
   checked against the teacher-forced ``forward`` of the same tokens.
4. ``grouped_experts``: the DeepSeek-V3 block's expert products (the
   megablox grouped matmuls, under the client vmap as Engine A calls
   them) against a dense per-expert sum at small shapes, forward and
   gradients.

``--four-chips`` runs VGG-16 with 20 clients, 5 edges, sharded over
``data=4`` (groups of 4 straddle shards of 5: the one-hot einsum + psum
path), and the same seed and data through the single-device engine, both
at ``highest`` matmul precision; the per-round losses and final
parameters must agree.

Each phase prints one JSON line of diagnostics (device, compile seconds,
steady-state seconds, peak device bytes); these are not benchmark
metrics.  The last line is ``{"ok": true, "device": {...}}``.  The script
exits non-zero, printing no such line, when JAX finds no TPU, when any
phase raises, or when a loss or logit is not finite.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# sharded-vs-single tolerances of tests/test_sharded_exec.py (plain config)
LOSS_RTOL = 2e-5
PARAM_RTOL, PARAM_ATOL = 2e-5, 2e-6
# decode-vs-forward: both run the chip's default f32 matmul precision,
# which rounds matmul inputs to bf16, along different reduction orders
DECODE_REL_TOL = 5e-2
# grouped-vs-dense experts: on the chip the grouped products take bf16
# operands (f32 accumulation), the dense sum f32 at highest; relative
# error of each output's norm, a few bf16 roundings deep
EXPERTS_REL_TOL = 2e-2

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading
    from the persistent cache) while the clock is entered."""

    def __init__(self):
        self.seconds = 0.0

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


def _device_of(tree) -> str:
    import jax

    leaf = jax.tree.leaves(tree)[0]
    return ",".join(sorted({d.platform for d in leaf.devices()}))


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _finite(name: str, values) -> None:
    import numpy as np

    a = np.asarray(values, dtype=np.float64)
    if not np.all(np.isfinite(a)):
        raise FloatingPointError(f"{name}: non-finite values {a.ravel()[:8]}")


def _vgg_args(clients, edges, batch, rounds, seed):
    return [
        "--arch", "vgg16-cifar10", "--clients", str(clients),
        "--edges", str(edges), "--batch", str(batch), "--rounds", str(rounds),
        "--seed", str(seed), "--log-every", str(rounds),
    ]


def phase_train_vgg(clients=20, edges=5, batch=16, rounds=5, seed=0) -> dict:
    """VGG-16/CIFAR-10 Engine-A rounds through ``launch.train``."""
    from repro.launch import train as launch_train

    with CompileClock() as clock:
        rec = launch_train.train(_vgg_args(clients, edges, batch, rounds, seed))
    feds = [launch_train.fed_round(rec["plan"].intervals, r) for r in range(rounds)]
    if not any(any(f[:-1]) for f in feds) or all(any(f[:-1]) for f in feds):
        raise ValueError(
            f"{rounds} rounds of intervals {rec['plan'].intervals} do not "
            "cover both a local round and a fed-sync round"
        )
    _finite("train_vgg losses", rec["losses"])
    warm = [t for t, w in zip(rec["round_seconds"], rec["warm"]) if w]
    return {
        "phase": "train_vgg", "device": _device_of(rec["state"].params),
        "clients": clients, "edges": edges, "batch": batch,
        "compile_s": clock.seconds,
        "steady_s_per_round": statistics.median(warm) if warm else None,
        "losses": rec["losses"], "peak_bytes_in_use": _peak_bytes(),
    }


def phase_train_lm(
    variant="full", num_layers=None, clients=4, edges=2, seq=256, batch=4,
    rounds=4, seed=0,
) -> dict:
    """SmolLM-135M Engine-A rounds through ``api.run`` (M = 3, fixed)."""
    from repro.api import (
        ExperimentSpec, ModelCfg, RunCfg, SolverCfg, SystemCfg, resolve_model,
        run,
    )

    model_cfg = ModelCfg(
        arch="smollm-135m", variant=variant, num_layers=num_layers, seq=seq,
        batch=batch,
    )
    n = resolve_model(model_cfg).n_units
    spec = ExperimentSpec(
        model=model_cfg,
        system=SystemCfg(num_clients=clients, num_edges=edges, seed=seed),
        solver=SolverCfg(
            kind="fixed", cuts=(max(1, n // 5), max(2, n // 2)),
            intervals=(2, 2, 1),
        ),
        run=RunCfg(mode="train", rounds=rounds, seed=seed, lr=0.01),
    )
    with CompileClock() as clock:
        tr = run(spec).train
    losses = tr["losses"]
    _finite("train_lm losses", losses)
    if len(set(losses)) < 2:
        raise ValueError(f"train_lm losses are constant: {losses}")
    import jax

    return {
        "phase": "train_lm", "device": jax.devices()[0].platform,
        "variant": variant, "clients": clients, "seq": seq, "batch": batch,
        "compile_s": clock.seconds,
        "steady_s_per_round": statistics.median(tr["round_seconds"][1:]),
        "losses": losses, "peak_bytes_in_use": _peak_bytes(),
    }


def phase_decode(spec=None, batch=8, cache_len=2048, prefill=4, gen=4, seed=0) -> dict:
    """Prefill and generate through the jitted ``decode_step``; the prefill
    logits must match the teacher-forced ``forward`` of the same tokens."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_spec
    from repro.models.model import SplittableModel

    spec = spec or get_spec("smollm-135m")
    model = SplittableModel(spec)
    key = jax.random.PRNGKey(seed)
    params = model.init_params(key)
    caches = model.init_caches(batch, cache_len)
    decode = jax.jit(model.decode_step)
    prompt = jax.random.randint(
        jax.random.fold_in(key, 1), (batch, prefill), 0, spec.vocab_size
    )
    V = spec.vocab_size

    with CompileClock() as clock:
        for i in range(prefill):
            logits, caches = decode(
                params, prompt[:, i : i + 1], caches, jnp.int32(i)
            )
        jax.block_until_ready((logits, caches))
        ref, _ = jax.jit(model.forward)(params, {"tokens": prompt})
    got, want = np.asarray(logits[:, :V]), np.asarray(ref[:, -1, :V])
    _finite("decode logits", got)
    rel = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))
    if rel > DECODE_REL_TOL:
        raise AssertionError(
            f"decode logits differ from forward: max|diff|/max|ref| = {rel}"
        )

    step_s, tokens = [], []
    tok = jnp.argmax(logits[:, :V], axis=-1)[:, None]
    for i in range(gen):
        t0 = time.perf_counter()
        logits, caches = decode(params, tok, caches, jnp.int32(prefill + i))
        jax.block_until_ready((logits, caches))
        step_s.append(time.perf_counter() - t0)
        tok = jnp.argmax(logits[:, :V], axis=-1)[:, None]
        tokens.append(tok)
    _finite("decode logits", np.asarray(logits[:, :V]))
    out = np.asarray(jnp.concatenate(tokens, axis=1))
    if logits.shape[0] != batch or out.shape != (batch, gen):
        raise ValueError(f"decode shapes: logits {logits.shape}, tokens {out.shape}")
    return {
        "phase": "decode", "device": _device_of(logits), "batch": batch,
        "cache_len": cache_len, "compile_s": clock.seconds,
        "steady_s_per_token": statistics.median(step_s),
        "logits_shape": list(logits.shape), "forward_rel_diff": rel,
        "peak_bytes_in_use": _peak_bytes(),
    }


def phase_grouped_experts(clients=2, tokens=256, top_k=6, held=8, d=256, f=256,
                          seed=0) -> dict:
    """``layers.grouped_experts`` under a vmap over clients, each with its
    own experts, against a dense per-expert sum: each (token, choice)
    pair's output, and the gradients of a weighted sum of them by the
    tokens and by every expert weight.  About a quarter of the pairs are
    routed to experts held elsewhere and must give zero."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.layers import grouped_experts

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    xt = jax.random.normal(ks[0], (clients, tokens, d))
    gid = jnp.minimum(jax.random.randint(ks[1], (clients, tokens, top_k), 0, held + held // 3),
                      held)  # group ``held``: an expert held elsewhere
    w1, w3 = (jax.random.normal(k, (clients, held, d, f)) / np.sqrt(d) for k in ks[2:4])
    w2 = jax.random.normal(ks[4], (clients, held, f, d)) / np.sqrt(f)
    cot = jax.random.normal(ks[5], (clients, tokens, top_k, d))

    def dense(xt, gid, w1, w3, w2):
        with jax.default_matmul_precision("highest"):
            h1 = jnp.einsum("td,gdf->tgf", xt, w1)
            h3 = jnp.einsum("td,gdf->tgf", xt, w3)
            y = jnp.einsum("tgf,gfd->tgd", jax.nn.silu(h1) * h3, w2)
            return jnp.einsum("tkg,tgd->tkd", jax.nn.one_hot(gid, held, dtype=y.dtype), y)

    def outputs(fn):
        def loss(xt, gid, w1, w3, w2):
            ye = jax.vmap(fn)(xt, gid, w1, w3, w2)
            return jnp.sum(ye * cot), ye

        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 2, 3, 4), has_aux=True))
        (_, ye), grads = step(xt, gid, w1, w3, w2)
        return dict(zip(("out", "d_x", "d_w1", "d_w3", "d_w2"), (ye, *grads)))

    with CompileClock() as clock:
        got = outputs(grouped_experts)
        jax.block_until_ready(got)
    want = outputs(dense)
    rel = {}
    for name, a in got.items():
        a, b = np.asarray(a, np.float64), np.asarray(want[name], np.float64)
        _finite(f"grouped_experts {name}", a)
        rel[name] = float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
    here = np.asarray(gid) < held
    if np.any(np.asarray(got["out"])[~here] != 0):
        raise AssertionError("grouped_experts: a pair held elsewhere gave output")
    if max(rel.values()) > EXPERTS_REL_TOL:
        raise AssertionError(f"grouped_experts differs from the dense sum: {rel}")
    return {
        "phase": "grouped_experts", "device": _device_of(got["out"]),
        "clients": clients, "pairs_held": int(here.sum()), "pairs": int(here.size),
        "compile_s": clock.seconds, "rel_diff": rel, "peak_bytes_in_use": _peak_bytes(),
    }


def phase_sharded_vgg(clients=20, edges=5, batch=16, rounds=5, data=4, seed=0) -> dict:
    """The same VGG run sharded over ``data`` devices and on one device.

    Both run at ``highest`` matmul precision.  At the TPU's default, which
    rounds f32 matmul and convolution inputs to bf16, the two programs
    (20 clients on one chip, 5 on each of 4) already differ by ~1e-5 in
    the first round's loss, before any sync: that is the precision, not
    the sharding, which the comparison is about.
    """
    import jax
    import numpy as np

    from repro.launch import train as launch_train

    args = _vgg_args(clients, edges, batch, rounds, seed)
    with CompileClock() as clock, jax.default_matmul_precision("highest"):
        single = launch_train.train(args)
        sharded = launch_train.train(args + ["--shard-data", str(data)])
    _finite("single losses", single["losses"])
    _finite("sharded losses", sharded["losses"])
    np.testing.assert_allclose(
        sharded["losses"], single["losses"], rtol=LOSS_RTOL,
        err_msg="sharded losses diverge from the single-device run",
    )
    a_leaves = jax.tree.leaves(sharded["state"].params)
    b_leaves = jax.tree.leaves(single["state"].params)
    for a, b in zip(a_leaves, b_leaves):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=PARAM_RTOL, atol=PARAM_ATOL,
            err_msg="sharded params diverge from the single-device run",
        )
    leaf = max(a_leaves, key=lambda x: x.size)
    shard_devices = sorted({s.device.id for s in leaf.addressable_shards})
    if len(shard_devices) != data:
        raise AssertionError(f"client axis sits on devices {shard_devices}")
    return {
        "phase": "sharded_vgg", "devices": shard_devices, "data": data,
        "clients": clients, "edges": edges, "compile_s": clock.seconds,
        "losses_single": single["losses"], "losses_sharded": sharded["losses"],
        "max_abs_param_diff": max(
            float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
            for a, b in zip(a_leaves, b_leaves)
        ),
        "peak_bytes_in_use": _peak_bytes(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-single VGG check on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {Path(__file__).name}; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no device: {e}", file=sys.stderr)
        return 1
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {devices[0].platform!r}); "
              "this script runs only on the chip", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import configure_compile_cache

    print(json.dumps({"compile_cache": configure_compile_cache()}), flush=True)
    if args.four_chips:
        phases = [lambda: phase_sharded_vgg(seed=args.seed)]
    else:
        phases = [
            lambda: phase_train_vgg(seed=args.seed),
            lambda: phase_train_lm(seed=args.seed),
            lambda: phase_decode(seed=args.seed),
            lambda: phase_grouped_experts(seed=args.seed),
        ]
    for phase in phases:
        print(json.dumps(phase()), flush=True)
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind, "count": len(d),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
