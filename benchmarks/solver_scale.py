"""solver_scale: batched MS/MA/BCD lattice core vs the scalar oracle walk.

Sweeps U (cut units) × M (tiers) over the same HSFL problem family and
solves each point end-to-end with Algorithm 2 (``solve_bcd``) on

* ``backend="scalar"`` — the historical one-cut-at-a-time walk,
* ``backend="numpy"``  — the batched whole-lattice core (cold = first
  solve including the latency-table build, warm = tables memoized on the
  problem),
* ``backend="jax"``    — the jitted chain (cold includes trace+compile),

asserting the three return *identical* optima (the bit-exactness
contract of DESIGN.md §11) and reporting wall-clock speedups.  The
headline point U=128/M=4 (~3.2·10⁵ lattice rows) must show ≥20×
end-to-end batched-vs-scalar; above ``SCALAR_MAX_K`` lattice rows the
scalar walk is no longer worth running and only batched timings are
reported (logged as ``scalar_skipped`` rows, never silently dropped).

A robust row re-runs a mid-size point against trace-quantile pricing
(straggler-tail scenario) to show the batched core carries the
``TraceLatency`` path too.  Results land in ``benchmarks/run.py --json``
artifacts (rows + one recorded ``ExperimentResult``), the
``BENCH_solvers.json`` perf-trajectory seed that CI uploads.
"""
from __future__ import annotations

import time
from typing import Optional, Tuple

from repro.api import (
    ExperimentSpec, HyperCfg, ModelCfg, ScenarioCfg, SolverCfg, SystemCfg,
    build, evaluate_schedule,
)
from repro.core import solve_bcd

from .common import emit, record

# above this many lattice rows the scalar walk takes tens of minutes and
# stops being a useful comparison point
SCALAR_MAX_K = 400_000

_PRESET = {2: "two-tier-client-edge", 3: "paper-three-tier", 4: "four-tier-wan"}


def _spec(U: int, M: int, seed: int, scenario: bool = False) -> ExperimentSpec:
    return ExperimentSpec(
        model=ModelCfg(
            arch="smollm-135m", variant="reduced", num_layers=U, batch=16, seq=32
        ),
        system=SystemCfg(
            preset=_PRESET[M], num_clients=20, num_edges=5, seed=seed
        ),
        hyper=HyperCfg(beta=3.0, eps_scale=8.0, seed=seed),
        solver=SolverCfg(kind="bcd"),
        scenario=(
            ScenarioCfg(name="straggler-tail", rounds=16, seed=seed)
            if scenario else None
        ),
        name=f"solver-scale-U{U}-M{M}" + ("-robust" if scenario else ""),
    )


def _timed_bcd(U: int, M: int, seed: int, backend: str, scenario: bool = False):
    """Fresh problem (no memoized evaluator) -> (seconds, result, problem)."""
    problem = build(_spec(U, M, seed, scenario)).problem
    t0 = time.perf_counter()
    res = solve_bcd(problem, backend=backend)
    return time.perf_counter() - t0, res, problem


def _sweep_point(
    rows: list, U: int, M: int, seed: int, quick: bool, scenario: bool = False
) -> Tuple[Optional[float], object]:
    """One (U, M) grid point: all backends, identical-optimum asserts."""
    part = "robust" if scenario else "sweep"

    t_np, r_np, p_np = _timed_bcd(U, M, seed, "numpy", scenario)
    K = p_np.cut_lattice().shape[0]
    t0 = time.perf_counter()
    r_warm = solve_bcd(p_np, backend="numpy")  # evaluator memoized
    t_warm = time.perf_counter() - t0
    assert r_warm == r_np

    speedup = None
    if K <= SCALAR_MAX_K:
        t_sc, r_sc, _ = _timed_bcd(U, M, seed, "scalar", scenario)
        # the contract: not just close — identical schedules, Θ', history
        assert r_sc == r_np, (
            f"batched optimum differs from scalar oracle at U={U} M={M}: "
            f"{r_sc} vs {r_np}"
        )
        speedup = t_sc / t_np
        rows.append((part, U, M, K, "scalar", t_sc, 1.0))
        print(f"-- U={U} M={M} K={K}: scalar {t_sc:.2f}s, "
              f"batched {t_np:.3f}s ({speedup:.1f}x), warm {t_warm:.4f}s")
    else:
        rows.append((part, U, M, K, "scalar_skipped", float("nan"), float("nan")))
        print(f"-- U={U} M={M} K={K}: scalar walk skipped (K > {SCALAR_MAX_K}); "
              f"batched {t_np:.2f}s, warm {t_warm:.4f}s")
    rows.append((part, U, M, K, "numpy", t_np,
                 speedup if speedup is not None else float("nan")))
    rows.append((part, U, M, K, "numpy_warm", t_warm, float("nan")))

    if not quick:
        t_jax, r_jax, _ = _timed_bcd(U, M, seed, "jax", scenario)
        assert r_jax == r_np, f"jax optimum drifted at U={U} M={M}"
        rows.append((part, U, M, K, "jax", t_jax, float("nan")))
    return speedup, r_np


def main(quick: bool = False, seed: int = 0) -> list:
    rows: list = []
    grid = [(16, 2), (16, 3), (32, 3), (64, 3)]
    if not quick:
        grid += [(32, 4), (64, 4), (128, 3), (128, 4), (256, 3), (256, 4)]

    speedups = {}
    for U, M in grid:
        speedup, bcd = _sweep_point(rows, U, M, seed, quick)
        if speedup is not None:
            speedups[(U, M)] = speedup
        if (U, M) == ((64, 3) if quick else (128, 4)):
            built = build(_spec(U, M, seed))
            record(evaluate_schedule(built, bcd.cuts, tuple(bcd.intervals)))

    # trace-quantile pricing rides the same batched core
    _sweep_point(rows, 32 if quick else 64, 3, seed, quick, scenario=True)

    emit(rows, ("part", "units", "tiers", "lattice_K", "backend", "seconds",
                "speedup_vs_scalar"))

    if quick:
        assert speedups[(64, 3)] >= 3.0, speedups
    else:
        # the headline: one Dinkelbach step = one argmin over [K]
        assert speedups[(128, 4)] >= 20.0, speedups
    return rows


if __name__ == "__main__":
    main()
