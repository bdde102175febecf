"""End-to-end HSFL training driver (CPU-runnable).

Wires every substrate together: synthetic data → non-IID partitioner →
federated loader → Engine A split training with the multi-timescale
aggregation schedule → bound-constant estimation → BCD (Algorithm 2)
re-optimization of (I, μ) → checkpointing.

    PYTHONPATH=src python -m repro.launch.train --arch vgg16-cifar10 \
        --rounds 300 --non-iid --auto-optimize

``--arch vgg16-cifar10`` reproduces the paper's own setting; any of the 10
assigned architecture ids runs its REDUCED variant on an LM stream.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def fed_round(intervals, r: int) -> tuple:
    """Which tiers sync at the fed server after round ``r`` (0-based)."""
    return tuple((r + 1) % I == 0 if I > 1 else True for I in intervals)


def train(argv=None) -> dict:
    """Parse ``argv``, train, and return the run's record: the final
    ``state`` and ``plan``, the per-round ``losses``, the wall
    ``round_seconds`` of each round (measured once its outputs are
    ready), and ``warm``, True for a round whose program had run before
    (False where the round compiled)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="vgg16-cifar10")
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--edges", type=int, default=5)
    ap.add_argument("--lr", type=float, default=5e-4)
    ap.add_argument("--optimizer", choices=["sgd", "momentum", "adam"], default="sgd")
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--cuts", type=int, nargs="*", default=None)
    ap.add_argument("--intervals", type=int, nargs="*", default=None)
    ap.add_argument("--auto-optimize", action="store_true",
                    help="estimate bound constants from a probe run and let "
                         "BCD (Algorithm 2) pick (I, mu)")
    ap.add_argument("--probe-rounds", type=int, default=8)
    ap.add_argument("--eps-scale", type=float, default=4.0,
                    help="target eps as a multiple of the I=1 bound floor")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shard-data", type=int, default=0, metavar="D",
                    help="shard the client-stacked axis over D devices "
                         "(the Engine-A step under shard_map; needs XLA_FLAGS="
                         "'--xla_force_host_platform_device_count=D' on CPU)")
    ap.add_argument("--shard-pods", type=int, default=0, metavar="P",
                    help="additionally shard clients over P pods "
                         "(client axes become (pod, data))")
    ap.add_argument("--staleness", type=int, nargs="*", default=None,
                    metavar="S",
                    help="bounded-staleness async aggregation: one value "
                         "(applies to every deferrable tier) or one per "
                         "tier; 0 is the synchronous schedule "
                         "(core.async_agg)")
    args = ap.parse_args(argv)

    from .compile_cache import configure_compile_cache

    configure_compile_cache()

    from ..configs import get_reduced
    from ..core import (
        HsflProblem, SystemSpec, TierPlan, build_profile, build_train_step_a,
        init_state_a, solve_bcd,
    )
    from ..core.estimator import HyperEstimator
    from ..core.tiers import default_plan
    from ..data import (
        lm_loader, image_loader, make_cifar10_like, make_lm_stream,
        partition_iid, partition_sort_and_shard,
    )
    from ..models.vgg import build_model
    from ..optim import adam, momentum, sgd

    opt = {"sgd": sgd, "momentum": momentum, "adam": adam}[args.optimizer](args.lr)

    if args.arch == "vgg16-cifar10":
        from ..configs.vgg16_cifar10 import SPEC as spec
        ds = make_cifar10_like(4096, seed=args.seed)
        labels = ds.labels
        mk_loader = lambda parts: image_loader(ds, parts, args.batch, args.seed)
    else:
        spec = get_reduced(args.arch)
        ds = make_lm_stream(2048, 64, spec.vocab_size, seed=args.seed)
        labels = ds.tokens[:, 0] % 10
        mk_loader = lambda parts: lm_loader(ds, parts, args.batch, args.seed)
        if spec.family in ("vlm", "audio"):
            raise SystemExit(
                f"{args.arch}: frontend is a stub; use examples/train_hsfl_e2e.py "
                "with dense/moe/ssm/hybrid archs or vgg16-cifar10"
            )

    parts = (
        partition_sort_and_shard(labels, args.clients, 2, args.seed)
        if args.non_iid
        else partition_iid(len(labels), args.clients, args.seed)
    )
    loader = mk_loader(parts)
    model = build_model(spec)
    plan = default_plan(
        spec.n_units, args.clients,
        cuts=tuple(args.cuts) if args.cuts else None,
        intervals=tuple(args.intervals) + (1,) if args.intervals else None,
        entities=(args.clients, args.edges, 1),
    )

    # sharded / async execution (DESIGN.md §17)
    mesh, client_axes = None, ("data",)
    if args.shard_data:
        from .mesh import make_debug_mesh

        mesh = make_debug_mesh(
            data=args.shard_data, model=1, pods=args.shard_pods
        )
        client_axes = ("pod", "data") if args.shard_pods else ("data",)
    staleness = 0
    if args.staleness:
        staleness = (
            args.staleness[0] if len(args.staleness) == 1
            else tuple(args.staleness)
        )

    def make_dispatch(plan_):
        """Specialized per-round-type steps (see tiers.synchronize): the
        fed-server collectives only exist in the (rare) sync-round programs,
        so the hot path never pays for them.

        The async trainer generalizes exactly this dispatch — with all-zero
        staleness it picks the same specialized variants; with s_m > 0 the
        due tier's fed level is snapshotted and folded back s_m rounds
        later (core.async_agg).
        """
        if staleness:
            from ..core.async_agg import make_async_trainer

            trainer = make_async_trainer(
                model, plan_, opt, staleness=staleness,
                mesh=mesh, client_axes=client_axes,
            )
            return trainer.run_round, trainer

        cache = {}

        def dispatch(state_, batch_, r):
            fed = fed_round(plan_.intervals, r)
            if fed not in cache:
                cache[fed] = jax.jit(build_train_step_a(
                    model, plan_, opt, fed_round=fed, mesh=mesh,
                    client_axes=client_axes,
                ))
            return cache[fed](state_, batch_)

        return dispatch, None

    def make_probe_step(plan_):
        return jax.jit(build_train_step_a(
            model, plan_, opt, mesh=mesh, client_axes=client_axes))

    key = jax.random.PRNGKey(args.seed)
    state = init_state_a(model, plan, opt, key, mesh=mesh, client_axes=client_axes)
    step = make_probe_step(plan)

    if args.auto_optimize:
        print(f"[probe] estimating bound constants over {args.probe_rounds} rounds")
        est = HyperEstimator(plan.n_units, args.clients, args.lr)
        grad_fn = jax.jit(lambda p, b: jax.vmap(jax.value_and_grad(model.loss_fn))(p, b))
        pstate = state
        for _ in range(args.probe_rounds):
            batch = {k: jnp.asarray(v) for k, v in loader.next_round().items()}
            losses, grads = grad_fn(pstate.params, batch)
            est.observe(pstate.params, grads, float(jnp.mean(losses)))
            pstate, _ = step(pstate, batch)
        hp = est.hyperspec()
        prof = build_profile(spec, args.batch, seq=64 if args.arch != "vgg16-cifar10" else 1)
        system = SystemSpec.paper_three_tier(args.clients, args.edges, seed=args.seed)
        from ..core.convergence import theorem1_bound
        floor = theorem1_bound(hp, 10**9, [1] * plan.M, plan.cuts)
        prob = HsflProblem(prof, system, hp, eps=args.eps_scale * floor)
        res = solve_bcd(prob)
        print(f"[bcd] cuts={res.cuts} intervals={res.intervals} "
              f"theta={res.theta:.4g} R={res.rounds:.0f} T={res.total_latency:.1f}s")
        plan = default_plan(
            spec.n_units, args.clients, cuts=res.cuts,
            intervals=res.intervals, entities=(args.clients, args.edges, 1),
        )
        step = make_probe_step(plan)

    mode = []
    if mesh is not None:
        mode.append(f"sharded over {client_axes} ({jax.device_count()} dev)")
    if staleness:
        mode.append(f"async staleness={staleness}")
    print(f"[train] arch={spec.name} units={spec.n_units} plan cuts={plan.cuts} "
          f"I={plan.intervals} N={args.clients} J2={args.edges}"
          + (f"  [{', '.join(mode)}]" if mode else ""))
    dispatch, trainer = make_dispatch(plan)
    losses, round_seconds, warm, seen = [], [], [], set()
    for r in range(args.rounds):
        batch = {k: jnp.asarray(v) for k, v in loader.next_round().items()}
        fed = fed_round(plan.intervals, r)
        t0 = time.perf_counter()
        state, loss = dispatch(state, batch, r)
        jax.block_until_ready((state, loss))
        round_seconds.append(time.perf_counter() - t0)
        losses.append(float(loss))
        warm.append(fed in seen)
        seen.add(fed)
        if (r + 1) % args.log_every == 0 or r == 0:
            print(f"round {r+1:5d}  loss {losses[-1]:.4f}  "
                  f"({round_seconds[-1]:.3f}s)")
    if trainer is not None:
        state = trainer.drain(state)  # fold in-flight async syncs in

    if args.checkpoint:
        from ..checkpoint import save_checkpoint

        save_checkpoint(
            args.checkpoint, state.params, step=int(state.step),
            meta={"cuts": list(plan.cuts), "intervals": list(plan.intervals)},
        )
        print(f"saved checkpoint -> {args.checkpoint}")
    return {
        "state": state, "plan": plan, "losses": losses,
        "round_seconds": round_seconds, "warm": warm,
    }


def main(argv=None) -> int:
    train(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
