"""async_scale: the sharded async HSFL engine and its pricing (DESIGN.md §17).

Four claims, all asserted:

1. **Staleness-0 collapse (bit-exact)** — the staleness-inflated Theorem-1
   bound at s ≡ 0 equals the synchronous bound bit-for-bit, and a REAL
   training run through the AsyncTrainer with all-zero staleness
   reproduces the synchronous fed_round dispatch's loss trajectory
   bit-for-bit (the trainer IS the production dispatch at s = 0).
2. **Async overlap beats the sync barrier at 10⁶ clients** — per-round
   wall clock on the paper-three-tier fleet scaled to a million clients:
   synchronous T_S + Σ T_m^A/I_m vs the bounded-staleness residual
   T_S + Σ max(0, T_m^A − s_m·T_S)/I_m, both from the Eq. 17/18 latency
   model and from fleet-simulator telemetry (observed per-round stage
   times on the straggler-tail scenario).
3. **Staleness-priced envelope** — a REAL async (s = 1) training run's
   measured average gradient norm sits below the staleness-inflated
   Theorem-1 bound with constants estimated from the same run, and that
   bound sits above the synchronous one (the (I+s)² − I² drift term).
4. **Sharded async round end-to-end, rehearsed on the CPU** — a
   subprocess held to the CPU (``JAX_PLATFORMS=cpu``) with
   XLA_FLAGS=--xla_force_host_platform_device_count=4 drives
   ``launch.train --shard-data 4 --staleness 1`` through the shard_map
   engine and the async queue drain.  The parent has touched JAX by then
   and may hold the accelerator, so the child never asks for it; the
   sharded round on real chips is ``chip_smoke.py --four-chips``.
"""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys

import numpy as np

from .common import emit

CUTS = (3, 8)
INTERVALS = (2, 4, 1)
STALENESS = (1, 1, 0)


def _tiny_vgg():
    from repro.configs.vgg16_cifar10 import SPEC as VGG

    return dataclasses.replace(
        VGG, conv_channels=(8, 16, 16), pool_after=(0, 1), fc_dims=(32, 10),
        name="vgg-tiny",
    )


def _collapse_rows(quick: bool, seed: int) -> list:
    import jax
    import jax.numpy as jnp

    from repro.core import build_train_step_a, init_state_a
    from repro.core.async_agg import make_async_trainer
    from repro.core.convergence import synthetic_hyperspec, theorem1_bound
    from repro.core.tiers import default_plan
    from repro.data import image_loader, make_cifar10_like, partition_iid
    from repro.launch.train import fed_round
    from repro.models.vgg import VggModel
    from repro.optim import sgd

    rows = []
    hp = synthetic_hyperspec(n_units=12, num_clients=20, seed=seed)
    base = theorem1_bound(hp, 500, INTERVALS, CUTS)
    zero = theorem1_bound(hp, 500, INTERVALS, CUTS, staleness=0)
    rows.append(("bound_s0_collapse", "thm1", base, zero, base == zero))

    spec = _tiny_vgg()
    N, rounds = 4, 6 if quick else 10
    plan = default_plan(spec.n_units, N, cuts=(2, 3), intervals=(4, 2, 1),
                        entities=(N, 2, 1))
    ds = make_cifar10_like(256, seed=seed + 3)
    model, opt = VggModel(spec), sgd(0.01)

    def batches():
        loader = image_loader(
            ds, partition_iid(len(ds), N, seed + 3), batch=8, seed=seed + 3
        )
        for _ in range(rounds):
            yield {k: jnp.asarray(v) for k, v in loader.next_round().items()}

    cache, sync_losses = {}, []
    state = init_state_a(model, plan, opt, jax.random.PRNGKey(seed))
    for r, batch in enumerate(batches()):
        fed = fed_round(plan.intervals, r)
        if fed not in cache:
            cache[fed] = jax.jit(
                build_train_step_a(model, plan, opt, fed_round=fed)
            )
        state, loss = cache[fed](state, batch)
        sync_losses.append(float(loss))

    tr = make_async_trainer(model, plan, opt, staleness=0)
    astate = init_state_a(model, plan, opt, jax.random.PRNGKey(seed))
    async_losses = []
    for r, batch in enumerate(batches()):
        astate, loss = tr.run_round(astate, batch, r)
        async_losses.append(float(loss))
    astate = tr.drain(astate)
    exact = async_losses == sync_losses and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(astate.params),
                        jax.tree.leaves(state.params))
    )
    rows.append(("train_s0_collapse", "vgg-tiny", sync_losses[-1],
                 async_losses[-1], exact))
    assert all(r[-1] for r in rows), rows
    return rows


def _overlap_rows(quick: bool, seed: int) -> list:
    from repro.api import ModelCfg, SystemCfg, resolve_model, resolve_system
    from repro.core import build_profile
    from repro.core.async_agg import async_round_time
    from repro.core.latency import aggregation_latency, split_latency
    from repro.sim import make_trace, simulate_rounds

    prof = build_profile(resolve_model(ModelCfg(arch="vgg16-cifar10")), batch=16)
    rows = []
    # analytic Eq. 17/18 pricing — cheap even at a million clients
    for n in (1_000, 100_000, 1_000_000):
        system = resolve_system(SystemCfg(
            preset="paper-three-tier", num_clients=n,
            num_edges=max(1, n // 200), seed=seed,
        ))
        split_T = split_latency(prof, system, CUTS)
        agg_T = [aggregation_latency(prof, system, CUTS, m)
                 for m in range(system.M)]
        sync, asyn = async_round_time(split_T, agg_T, INTERVALS, STALENESS)
        rows.append(("overlap_analytic", n, sync, asyn, asyn < sync))
    # fleet-simulator telemetry drives the same pricing: observed stage
    # times on the straggler-tail scenario (the sim's arrival model)
    n = 100_000 if quick else 1_000_000
    system = resolve_system(SystemCfg(
        preset="paper-three-tier", num_clients=n,
        num_edges=max(1, n // 200), seed=seed,
    ))
    trace = make_trace("straggler-tail", prof, system, rounds=4, seed=seed)
    res = simulate_rounds(trace, CUTS, INTERVALS)
    split_T = float(np.mean(res.split))
    agg_T = [float(np.mean(res.agg[m])) for m in range(res.agg.shape[0])]
    agg_T += [0.0]  # top tier: the round barrier itself
    sync, asyn = async_round_time(split_T, agg_T, INTERVALS, STALENESS)
    rows.append(("overlap_fleet_sim", n, sync, asyn, asyn <= sync))
    assert all(r[-1] for r in rows), rows
    assert any(r[1] >= 1_000_000 for r in rows), "must price a 10^6 fleet"
    return rows


def _envelope_rows(quick: bool, seed: int) -> list:
    import jax
    import jax.numpy as jnp

    from repro.core.async_agg import make_async_trainer
    from repro.core.convergence import theorem1_bound
    from repro.core.estimator import HyperEstimator
    from repro.core.tiers import default_plan
    from repro.data import image_loader, make_cifar10_like, partition_iid
    from repro.models.vgg import VggModel
    from repro.optim import sgd

    spec = _tiny_vgg()
    N, gamma = 4, 0.01
    rounds = 15 if quick else 30
    staleness = (1, 0, 0)
    ds = make_cifar10_like(256, noise=0.4, seed=seed + 3)
    loader = image_loader(
        ds, partition_iid(len(ds), N, seed + 3), batch=8, seed=seed + 3
    )
    model = VggModel(spec)
    eval_batch = {"images": jnp.asarray(ds.images[:192]),
                  "labels": jnp.asarray(ds.labels[:192])}
    gbar_fn = jax.jit(lambda p, b: jax.grad(model.loss_fn)(p, b))
    grad_fn = jax.jit(
        lambda p, b: jax.vmap(jax.value_and_grad(model.loss_fn))(p, b)
    )

    plan = default_plan(spec.n_units, N, cuts=(2, 3), intervals=(4, 1, 1),
                        entities=(N, 2, 1))
    opt = sgd(gamma)
    tr = make_async_trainer(model, plan, opt, staleness=staleness)
    from repro.core import init_state_a

    state = init_state_a(model, plan, opt, jax.random.PRNGKey(seed + 3))
    est = HyperEstimator(plan.n_units, N, gamma)
    sq_norms = []
    for r in range(rounds):
        batch = {k: jnp.asarray(v) for k, v in loader.next_round().items()}
        losses, grads = grad_fn(state.params, batch)
        est.observe(state.params, grads, float(jnp.mean(losses)))
        wbar = jax.tree.map(lambda x: jnp.mean(x, axis=0), state.params)
        g = gbar_fn(wbar, eval_batch)
        sq_norms.append(float(
            sum(jnp.sum(x * x) for x in jax.tree.leaves(g))
        ))
        state, _ = tr.run_round(state, batch, r)
    state = tr.drain(state)
    hp = est.hyperspec()
    measured = float(np.mean(sq_norms))
    b_sync = theorem1_bound(hp, rounds, plan.intervals, plan.cuts)
    b_async = theorem1_bound(hp, rounds, plan.intervals, plan.cuts,
                             staleness=staleness)
    rows = [
        ("envelope_async_run", "s=1", measured, b_async, measured <= b_async),
        ("staleness_inflates", "s=1", b_sync, b_async, b_async > b_sync),
    ]
    assert all(r[-1] for r in rows), rows
    return rows


def _sharded_round_rows(quick: bool, seed: int) -> list:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    cmd = [
        sys.executable, "-m", "repro.launch.train",
        "--arch", "smollm-135m", "--rounds", "2", "--clients", "8",
        "--edges", "4", "--batch", "2", "--shard-data", "4",
        "--staleness", "1",
        "--log-every", "1", "--seed", str(seed),
    ]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=540)
    ok = out.returncode == 0 and "sharded over" in out.stdout
    assert ok, (out.stdout[-1500:], out.stderr[-1500:])
    return [("sharded_round_cpu_rehearsal", "smollm-135m x4 cpu devices",
             2.0, 0.0, ok)]


def main(quick: bool = False, seed: int = 0) -> list:
    rows = []
    rows += _collapse_rows(quick, seed)
    rows += _overlap_rows(quick, seed)
    rows += _envelope_rows(quick, seed)
    rows += _sharded_round_rows(quick, seed)
    emit(rows, ("part", "case", "sync_or_measured", "async_or_bound", "holds"))
    assert all(r[-1] for r in rows), rows
    return rows


if __name__ == "__main__":
    main()
