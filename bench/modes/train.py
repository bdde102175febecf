"""Mode ``train``: Engine-A rounds in a closed loop, as ``launch.train`` drives them.

One jitted program per sync pattern ``fed_round(plan.intervals, r)``,
each under a stable name (``hsfl_round_local`` for the rounds in which no
tier below the top meets its fed server, ``hsfl_round_fed_<pattern>``
otherwise, e.g. ``hsfl_round_fed_TTT``).  Batches come from the
program's ``FederatedLoader`` on data made from the seed.

Set-up builds the state in one jitted call, then drives it through the
window's own round function for the rounds up to and including the first
one whose syncs reach every tier: these warm every program and are the
rounds the reference follows.  The same state and loader then run the
window.  Each round is timed on the host clock from batch preparation to
the loss on the host; its phases are host spans (``batch_prep``,
``dispatch``, ``wait``, ``loss_fetch``).

Traffic keys: ``batch``, ``samples`` (data set size), ``seq`` (language
models); a cell that varies the configuration's tier plan overrides it
with ``clients``, ``edges``, ``cuts`` or ``intervals``.
"""
from __future__ import annotations

import gc
import math
import time
from functools import reduce

from bench import check, flops, harness, program

def program_name(fed) -> str:
    if not any(fed[:-1]):
        return "hsfl_round_local"
    return "hsfl_round_fed_" + "".join("T" if f else "F" for f in fed)


def full_fed_name(n_tiers: int) -> str:
    return program_name((True,) * n_tiers)


def plan_of(cfg, traffic) -> dict:
    plan = dict(cfg["plan"])
    plan.update({k: traffic[k] for k in ("clients", "edges", "cuts", "intervals")
                 if k in traffic})
    return plan


def check_rounds(intervals) -> int:
    """Rounds up to and including the first whose syncs reach every tier,
    and at least three."""
    return max(3, reduce(math.lcm, [int(i) for i in intervals], 1))


def build(cfg, traffic, seed, faults):
    """The system under test, set up from the seed: state, loader, steps."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import build_train_step_a, init_state_a
    from repro.core.tiers import TierPlan
    from repro.data import (image_loader, lm_loader, make_cifar10_like,
                            make_lm_stream, partition_iid)
    from repro.models.vgg import build_model
    from repro.optim import sgd

    spec = program.model_spec(cfg)
    model = build_model(spec)
    p = plan_of(cfg, traffic)
    N = p["clients"]
    plan = TierPlan(n_units=spec.n_units, num_clients=N, cuts=tuple(p["cuts"]),
                    intervals=tuple(p["intervals"]), entities=(N, p["edges"], 1))
    if cfg["optimizer"]["name"] != "sgd":
        raise harness.BenchError("mode train runs SGD")
    lr = cfg["optimizer"]["lr"]
    opt = sgd(lr)
    if cfg["family"] == "vgg":
        ds = make_cifar10_like(traffic["samples"], seed=seed)
        loader = image_loader(ds, partition_iid(len(ds), N, seed), traffic["batch"], seed)
    else:
        ds = make_lm_stream(traffic["samples"], traffic["seq"], cfg["vocab_size"], seed=seed)
        loader = lm_loader(ds, partition_iid(len(ds), N, seed), traffic["batch"], seed)

    init = jax.jit(lambda k: init_state_a(model, plan, opt, k))
    put = lambda host: {k: jnp.asarray(v) for k, v in host.items()}
    state = init(jax.random.PRNGKey(seed))

    steps = {}

    def step_for(fed):
        if fed not in steps:
            fn = build_train_step_a(model, plan, opt, fed_round=fed)
            wrap = faults.get("train_step")
            if wrap is not None:
                fn = wrap(fn)

            def named(state, batch):
                return fn(state, batch)

            named.__name__ = named.__qualname__ = program_name(fed)
            steps[fed] = jax.jit(named)
        return steps[fed]

    return dict(plan=plan, lr=lr, loader=loader, put=put, state=state,
                step_for=step_for)


def run(ctx):
    import jax

    from repro.launch.train import fed_round

    cfg, wl = ctx["config"], ctx["workload"]
    traffic = wl["traffic"]
    seed, devices = ctx["seed"], ctx["devices"]
    sut = build(cfg, traffic, seed, ctx["program"])
    plan, loader, put, step_for = sut["plan"], sut["loader"], sut["put"], sut["step_for"]
    state = sut.pop("state")
    spans = harness.Spans()

    def one_round(state, r):
        with spans("batch_prep"):
            host = loader.next_round()
            batch = put(host)
        fed = fed_round(plan.intervals, r)
        with spans("dispatch"):
            state, loss = step_for(fed)(state, batch)
        with spans("wait"):
            jax.block_until_ready((state, loss))
        with spans("loss_fetch"):
            loss = float(loss)
        return state, loss, host

    # set-up rounds: warm every program; the reference follows these
    K = check_rounds(plan.intervals)
    p0 = jax.jit(lambda s: jax.tree.map(lambda x: x[0], s.params))(state)
    norms = program.leaf_norms_fn(p0)

    def change(scale=1.0):
        return {k: float(v) * scale for k, v in
                jax.device_get(norms(p0, state.params)).items()}

    prog = {"losses": []}
    fed_batches = []
    for r in range(K):
        state, loss, host = one_round(state, r)
        prog["losses"].append(loss)
        fed_batches.append(host)
        if r == 0:
            prog["grad"] = change(1.0 / sut["lr"])
        if r == 2:
            prog["change3"] = change()
    prog["change"] = change()
    del p0

    # the window
    seconds = harness.start_window_trace(ctx)
    spans.events.clear()
    round_s, losses = [], []
    r = K
    t_start = time.perf_counter_ns()
    setup_s = time.time() - ctx["t_proc"]
    deadline = t_start + int(seconds * 1e9)
    with harness.CompileClock() as clock:
        while True:
            t0 = time.perf_counter_ns()
            state, loss, _ = one_round(state, r)
            t1 = time.perf_counter_ns()
            round_s.append((t1 - t0) * 1e-9)
            losses.append(loss)
            r += 1
            if t1 >= deadline:
                break
    window_s = (t1 - t_start) * 1e-9
    if ctx["trace"]:
        jax.profiler.stop_trace()
    peak = harness.peak_bytes(devices)
    del state
    sut.clear()
    gc.collect()

    trace = None
    if ctx["trace"]:
        from bench import trace_reduce

        names = sorted({program_name(fed_round(plan.intervals, i)) for i in range(K)})
        trace = trace_reduce.reduce(trace_reduce.load(ctx["trace_dir"]), names,
                                    spans.events, (t_start, t1))

    ref = harness.reference_module(cfg)
    ref_read = check.hsfl_reference(ref, cfg, plan_of(cfg, traffic), cfg["optimizer"]["lr"],
                                    fed_batches, seed)
    numbers = check.train_numbers(prog, ref_read)
    limits = wl["limits"]
    check_list = [(k, numbers[k], limits[k]) for k in limits]
    samples_per_round = plan.num_clients * traffic["batch"]
    rounds = len(round_s)
    return harness.new_record(
        correct=all(v <= lim for _, v, lim in check_list)
        and all(math.isfinite(x) for x in losses),
        attempted=rounds, failed=sum(not math.isfinite(x) for x in losses),
        setup_s=setup_s, window_s=window_s, trace=trace, check=check_list,
        peak_bytes=peak, compiles_in_window=clock.events,
        counters={"rounds": rounds, "round_s": round_s,
                  "samples": rounds * samples_per_round,
                  "samples_per_round": samples_per_round,
                  "tokens_per_sample": traffic.get("seq"),
                  "span_ms": harness.span_ms(spans.events),
                  "round_spread": harness.round_spread(round_s),
                  "full_fed_program": full_fed_name(plan.M)},
        flops={"per_sample": flops.train_flops_per_sample(cfg, traffic)},
        check_inputs={"batches": fed_batches, "program": prog, "reference": ref_read},
    )
