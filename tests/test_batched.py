"""Batched solver core (DESIGN.md §11): bit-exactness vs the scalar oracle.

Two layers of contract:

* property tests (hypothesis-style, seeded rng) over random profiles /
  systems / compression specs assert the batched Θ'/N/D/T_S/T_{m,A}/C5
  arrays equal the scalar per-cut walk bit-for-bit across the WHOLE
  lattice;
* solver-equivalence tests assert ``solve_ms``/``solve_ma``/``solve_bcd``
  on the batched backends return *identical* optima (same cuts, same
  intervals, same Θ', same Dinkelbach iterates) to ``backend="scalar"``
  on every registry system preset, including robust trace-quantile and
  compressed-wire problems, plus numpy-vs-jax table equality.
"""
import dataclasses

import numpy as np
import pytest

from repro.compress import CompressionSpec
from repro.configs.vgg16_cifar10 import SPEC as VGG
from repro.core import (
    BatchedEvaluator,
    HsflProblem,
    SystemSpec,
    build_profile,
    solve_bcd,
    solve_ma,
    solve_ms,
    synthetic_hyperspec,
)
from repro.core.batched import resolve_backend
from repro.core.convergence import theorem1_bound
from repro.core.latency import LayerProfile


# --------------------------------------------------------------------------- #
# random problem generators (the hypothesis-style search space)
# --------------------------------------------------------------------------- #


def random_profile(rng, U):
    params = rng.uniform(1e3, 1e7, U)
    return LayerProfile(
        n_units=U,
        flops_fwd=rng.uniform(1e8, 1e12, U),
        flops_bwd=rng.uniform(1e8, 2e12, U),
        act_bytes=rng.uniform(1e2, 1e6, U),
        grad_act_bytes=rng.uniform(1e2, 1e6, U),
        param_bytes=params,
        opt_bytes=params * rng.uniform(0.0, 2.0),
        frontend_param_bytes=float(rng.uniform(0.0, 1e6)),
        head_param_bytes=float(rng.uniform(0.0, 1e6)),
        batch=int(rng.integers(1, 32)),
    )


def random_system(rng, M, N):
    J2 = int(rng.integers(1, N + 1))
    entities = (N, J2) if M == 2 else (N, J2, 1)
    # occasionally squeeze a tier's memory so C5 actually bites
    mem = tuple(
        np.full(
            N if m == 0 else (J2 if m == 1 else 1),
            float(rng.choice([1e9, 1e12, 1e15])),
        )
        for m in range(M)
    )
    return SystemSpec(
        M=M,
        num_clients=N,
        entities=entities,
        compute=tuple(rng.uniform(1e11, 1e13, N) for _ in range(M)),
        act_up=tuple(rng.uniform(1e7, 1e9, N) for _ in range(M - 1)),
        act_down=tuple(rng.uniform(1e7, 1e9, N) for _ in range(M - 1)),
        model_up=tuple(
            rng.uniform(1e7, 1e9, N if m == 0 else J2) for m in range(M - 1)
        ),
        model_down=tuple(
            rng.uniform(1e7, 1e9, N if m == 0 else J2) for m in range(M - 1)
        ),
        memory=mem,
    )


def random_problem(seed):
    rng = np.random.default_rng(seed)
    M = 2 + seed % 2
    U = int(rng.integers(6, 14))
    N = int(rng.integers(3, 9))
    prof = random_profile(rng, U)
    system = random_system(rng, M, N)
    hp = synthetic_hyperspec(
        U, N,
        beta=float(rng.uniform(1, 10)),
        g2_scale=float(rng.uniform(1, 30)),
        seed=seed,
    )
    even = tuple(max(1, (m + 1) * U // M) for m in range(M - 1))
    floor = theorem1_bound(hp, 10**9, [1] * M, even)
    comp = None
    if seed % 3 == 0:
        comp = CompressionSpec(
            act_ratio=tuple(rng.uniform(0.05, 1.0, M - 1)),
            model_ratio=tuple(rng.uniform(0.05, 1.0, M - 1)),
            omega=float(rng.uniform(0.0, 0.5)),
        )
    return HsflProblem(
        prof, system, hp,
        eps=float(rng.uniform(1.5, 10)) * floor,
        compression=comp,
    )


def assert_evaluator_matches_scalar(problem, ev, intervals_draws):
    th_b = {tuple(iv): ev.theta(iv) for iv in intervals_draws}
    num_b = {tuple(iv): ev.numerator(iv) for iv in intervals_draws}
    den_b = {tuple(iv): ev.denominator(iv) for iv in intervals_draws}
    for k, cuts in enumerate(problem.iter_cut_vectors()):
        assert ev.cuts_at(k) == cuts
        assert ev.split[k] == problem.split_T(cuts)
        np.testing.assert_array_equal(ev.agg[k], problem.agg_T(cuts))
        assert bool(ev.mem_ok[k]) == problem.memory_feasible(cuts)
        for iv in intervals_draws:
            key = tuple(iv)
            assert num_b[key][k] == problem.numerator(iv, cuts)
            assert den_b[key][k] == problem.denominator(iv, cuts)
            assert th_b[key][k] == problem.theta(iv, cuts)


# --------------------------------------------------------------------------- #
# property tests: whole-lattice bit-exactness
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("seed", range(8))
def test_batched_matches_scalar_on_random_problems(seed):
    problem = random_problem(seed)
    rng = np.random.default_rng(1000 + seed)
    M = problem.M
    draws = [
        [int(rng.integers(1, 12)) for _ in range(M - 1)] + [1]
        for _ in range(3)
    ]
    ev = problem.evaluator("numpy")
    assert ev.K == problem.cut_lattice().shape[0] > 0
    assert_evaluator_matches_scalar(problem, ev, draws)


def test_batched_matches_scalar_vgg_compressed():
    prof = build_profile(VGG, batch=16)
    system = SystemSpec.paper_three_tier(seed=0)
    hp = synthetic_hyperspec(VGG.n_units, 20, beta=3.0, seed=0)
    floor = theorem1_bound(hp, 10**9, [1, 1, 1], (3, 8))
    comp = CompressionSpec.uniform(3, model_ratio=0.25, act_ratio=0.5, omega=0.1)
    problem = HsflProblem(prof, system, hp, eps=5 * floor, compression=comp)
    ev = problem.evaluator("numpy")
    assert_evaluator_matches_scalar(problem, ev, [[2, 3, 1], [1, 1, 1]])


def test_jax_tables_bit_equal_numpy():
    for comp in (None, CompressionSpec.uniform(3, 0.25, act_ratio=0.5)):
        prof = build_profile(VGG, batch=16)
        system = SystemSpec.paper_three_tier(seed=1)
        hp = synthetic_hyperspec(VGG.n_units, 20, beta=3.0, seed=1)
        floor = theorem1_bound(hp, 10**9, [1, 1, 1], (3, 8))
        problem = HsflProblem(
            prof, system, hp, eps=5 * floor, compression=comp
        )
        ev_np = BatchedEvaluator(problem, backend="numpy")
        ev_jax = BatchedEvaluator(problem, backend="jax")
        np.testing.assert_array_equal(ev_np.split, ev_jax.split)
        np.testing.assert_array_equal(ev_np.agg, ev_jax.agg)


def test_trace_latency_batch_methods_match_scalar():
    from repro.sim import make_trace, robust_problem

    prof = build_profile(VGG, batch=8)
    system = SystemSpec.paper_three_tier(num_clients=6, num_edges=2, seed=0)
    hp = synthetic_hyperspec(VGG.n_units, 6, beta=3.0, seed=0)
    floor = theorem1_bound(hp, 10**9, [1, 1, 1], (3, 8))
    base = HsflProblem(prof, system, hp, eps=5 * floor)
    for name in ("straggler-tail", "flaky-wan", "diurnal-churn"):
        trace = make_trace(name, prof, system, rounds=6, seed=2)
        rp = robust_problem(base, trace, quantile=0.95)
        lm = rp.latency_model
        lat = rp.cut_lattice()
        split_b, agg_b = lm.split_T_batch(lat), lm.agg_T_batch(lat)
        for k, cuts in enumerate(rp.iter_cut_vectors()):
            assert split_b[k] == lm.split_T(cuts), (name, cuts)
            for m in range(rp.M - 1):
                assert agg_b[k, m] == lm.agg_T(cuts, m), (name, cuts, m)


# --------------------------------------------------------------------------- #
# solver equivalence: identical optima on every backend
# --------------------------------------------------------------------------- #


def _assert_same_bcd(problem):
    r_scalar = solve_bcd(problem, backend="scalar")
    r_numpy = solve_bcd(problem, backend="numpy")
    assert r_scalar == r_numpy, (r_scalar, r_numpy)
    return r_scalar


@pytest.mark.parametrize(
    "preset",
    ["paper-three-tier", "two-tier-client-edge", "two-tier-client-cloud",
     "tpu-pod", "four-tier-wan"],
)
def test_solvers_identical_on_registry_presets(preset):
    from repro.api import ExperimentSpec, HyperCfg, ModelCfg, SystemCfg, build

    spec = ExperimentSpec(
        model=ModelCfg(arch="vgg16-cifar10", batch=8),
        system=SystemCfg(
            preset=preset,
            num_clients=12,
            num_edges=1 if preset == "two-tier-client-cloud" else 4,
            seed=0,
        ),
        hyper=HyperCfg(beta=3.0, eps_scale=8.0),
    )
    problem = build(spec).problem
    res = _assert_same_bcd(problem)
    assert np.isfinite(res.theta)

    ms_s = solve_ms(problem, list(res.intervals), backend="scalar")
    ms_b = solve_ms(problem, list(res.intervals), backend="numpy")
    assert ms_s == ms_b
    # degenerate (empty-tier) cuts sit outside the lattice; solve_ma must
    # handle them on both paths
    M = problem.M
    for cuts in (res.cuts, tuple([2] * (M - 1))):
        ma_s = solve_ma(problem, cuts, backend="scalar")
        ma_b = solve_ma(problem, cuts, backend="numpy")
        assert ma_s == ma_b


def test_solvers_identical_under_participation():
    """Deadline-priced + 1/q-inflated problems solve to identical optima
    on the scalar oracle and the batched core (DESIGN.md §12)."""
    from repro.api import (
        ExperimentSpec, HyperCfg, ModelCfg, ParticipationCfg, ScenarioCfg,
        SystemCfg, build,
    )

    spec = ExperimentSpec(
        model=ModelCfg(arch="vgg16-cifar10", batch=8),
        system=SystemCfg(preset="paper-three-tier", num_clients=8,
                         num_edges=2, seed=1),
        hyper=HyperCfg(beta=3.0, eps_scale=8.0),
        scenario=ScenarioCfg(name="straggler-tail", rounds=8, seed=1),
        participation=ParticipationCfg(target_rate=0.75),
    )
    problem = build(spec).problem
    assert problem.latency_model is not None and problem.participation is not None
    _assert_same_bcd(problem)


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(5))
def test_batched_property_seed_sweep_nightly(seed):
    """Nightly flakiness guard: the whole-lattice bit-exactness property
    and BCD backend equivalence re-rolled over 5 fixed seeds, with a
    random participation spec layered on top of the random problem."""
    from repro.core import ParticipationSpec

    problem = random_problem(100 + seed)
    rng = np.random.default_rng(2000 + seed)
    M = problem.M
    q = tuple(float(v) for v in rng.uniform(0.2, 1.0, M))
    deadline = float(rng.uniform(0.1, 10.0)) if seed % 2 else None
    problem = problem.with_participation(
        ParticipationSpec(q=q, deadline=deadline)
    )
    draws = [
        [int(rng.integers(1, 12)) for _ in range(M - 1)] + [1]
        for _ in range(3)
    ]
    assert_evaluator_matches_scalar(problem, problem.evaluator("numpy"), draws)
    err = {}
    res = {}
    for backend in ("scalar", "numpy"):
        try:
            res[backend] = solve_bcd(problem, backend=backend)
        except ValueError as e:  # infeasible random draw: both paths agree
            err[backend] = str(e)
    assert set(err) in (set(), {"scalar", "numpy"}), err
    if not err:
        assert res["scalar"] == res["numpy"]


def test_solvers_identical_robust_and_compressed():
    from repro.api import (
        CompressionCfg, ExperimentSpec, HyperCfg, ModelCfg, ScenarioCfg,
        SystemCfg, build,
    )

    spec = ExperimentSpec(
        model=ModelCfg(arch="vgg16-cifar10", batch=8),
        system=SystemCfg(preset="paper-three-tier", num_clients=8,
                         num_edges=2, seed=1),
        hyper=HyperCfg(beta=3.0, eps_scale=8.0),
        scenario=ScenarioCfg(name="straggler-tail", rounds=8, seed=1),
        compression=CompressionCfg(codec="int8", act_ratio=0.5),
    )
    problem = build(spec).problem
    assert problem.latency_model is not None and problem.compression is not None
    _assert_same_bcd(problem)


def test_run_spec_backend_knob():
    from repro.api import ExperimentSpec, ModelCfg, SolverCfg, SystemCfg, run

    base = ExperimentSpec(
        model=ModelCfg(arch="vgg16-cifar10", batch=8),
        system=SystemCfg(preset="paper-three-tier", num_clients=8, num_edges=2),
    )
    results = {}
    for backend in ("scalar", "numpy", "auto"):
        spec = base.replace(solver=SolverCfg(kind="bcd", backend=backend))
        # the knob survives the JSON round trip
        assert ExperimentSpec.from_dict(spec.to_dict()) == spec
        results[backend] = run(spec)
    assert (
        results["scalar"].cuts == results["numpy"].cuts == results["auto"].cuts
    )
    assert (
        results["scalar"].theta == results["numpy"].theta == results["auto"].theta
    )
    with pytest.raises(ValueError, match="backend"):
        SolverCfg(backend="cuda")


# --------------------------------------------------------------------------- #
# lattice memoization + backend resolution
# --------------------------------------------------------------------------- #


def test_cut_lattice_memoized_and_invalidated_by_with_compression():
    prof = build_profile(VGG, batch=8)
    system = SystemSpec.paper_three_tier(seed=0)
    hp = synthetic_hyperspec(VGG.n_units, 20, beta=3.0, seed=0)
    floor = theorem1_bound(hp, 10**9, [1, 1, 1], (3, 8))
    problem = HsflProblem(prof, system, hp, eps=5 * floor)

    lat = problem.cut_lattice()
    assert problem.cut_lattice() is lat  # one shared materialization
    assert [tuple(int(x) for x in r) for r in lat] == list(
        problem.iter_cut_vectors()
    )
    ev = problem.evaluator("numpy")
    assert problem.evaluator("numpy") is ev  # memoized per backend
    assert ev.lattice is lat

    comp = CompressionSpec.uniform(3, model_ratio=0.5)
    derived = problem.with_compression(comp)
    assert derived.cut_lattice() is not lat  # fresh caches on the new wire
    assert derived.evaluator("numpy") is not ev
    np.testing.assert_array_equal(derived.cut_lattice(), lat)  # same geometry


def test_resolve_backend():
    assert resolve_backend("numpy") == "numpy"
    with pytest.raises(ValueError, match="unknown batched backend"):
        resolve_backend("cuda")
    assert resolve_backend("auto", work_elems=10) == "numpy"
    assert resolve_backend("auto", work_elems=10**9) == "jax"


def test_solve_ma_rejects_unknown_backend():
    problem = random_problem(1)
    cuts = next(problem.iter_cut_vectors())
    with pytest.raises(ValueError, match="unknown batched backend"):
        solve_ma(problem, cuts, backend="scaler")  # typo'd "scalar"


def test_zero_participant_round_consistent_across_paths():
    """A round where every client is absent must price split=0 and skip the
    client-hosted tier's sync identically in the event oracle, the scalar
    fleet path, and the batched lattice path (it used to crash the scalar
    paths while the lattice path silently zeroed the sync)."""
    import dataclasses as _dc

    from repro.sim import TraceLatency, make_trace, simulate, simulate_rounds
    from repro.sim.fleet import simulate_lattice_rounds
    from repro.sim.scenarios import SystemTrace

    prof = build_profile(VGG, batch=4)
    system = SystemSpec.paper_three_tier(num_clients=6, num_edges=2, seed=0)
    base = make_trace("homogeneous-paper", prof, system, rounds=4, seed=0)
    empty = _dc.replace(
        base.round_state(0),
        available=np.zeros(system.num_clients, dtype=bool),
    )
    trace = SystemTrace(
        "with-dead-round", prof, system, base.rounds, 0,
        lambda r: empty if r == 1 else base.round_state(r),
    )
    cuts = (3, 8)
    ev = simulate(trace, cuts)
    fl = simulate_rounds(trace, cuts, backend="numpy")
    np.testing.assert_array_equal(ev.split, fl.split)
    np.testing.assert_array_equal(ev.agg, fl.agg)
    assert ev.split[1] == 0.0 and (ev.agg[0, 1] == 0.0)  # tier 0 is client-hosted

    lat = np.asarray([cuts], dtype=np.int64)
    split_b, agg_b = simulate_lattice_rounds(trace, lat, backend="numpy")
    np.testing.assert_array_equal(split_b[0], fl.split)
    np.testing.assert_array_equal(agg_b[0], fl.agg)

    lm = TraceLatency(trace, quantile=0.95)
    assert lm.split_T_batch(lat)[0] == lm.split_T(cuts)
    for m in range(2):
        assert lm.agg_T_batch(lat)[0, m] == lm.agg_T(cuts, m)
