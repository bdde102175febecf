"""Benchmark runner: one harness per paper table/figure and the
fleet-simulator scale sweep.

    PYTHONPATH=src python -m benchmarks.run [names...] [--quick] [--seed S]
                                            [--skip-training] [--list]
                                            [--json PATH]

Every harness is registered in ``HARNESSES`` with a group tag; ``--list``
prints the registry, positional names (or ``--only``) select a subset, and
``--seed`` is threaded through every harness that derives randomness
(system draws, policy draws, synthetic data, model init).

``--json PATH`` writes one machine-readable artifact for the whole run:
per-harness row tables plus every ``repro.api.ExperimentResult`` the
harnesses recorded (serialized via ``to_dict()``, provenance = the resolved
spec) — the BENCH_*.json perf-trajectory seed.

Harness -> paper artifact map (details in DESIGN.md §7):
    fig2_latency_vs_cut   Fig. 2(c)  per-round latency vs cut layer
    fig45_benchmarks      Figs. 4-5  HSFL vs the 5 baseline policies
    fig67_resources       Figs. 6-7  resource scaling + tier count
    sim_scale             (ours)     fleet simulator: oracle check + 10^6-client sweep
    solver_scale          (ours)     batched MS/MA/BCD lattice core vs the scalar
                                     oracle walk (bit-exact optima, >=20x headline)
    control_drift         (ours)     online adaptive control: time-to-eps vs every
                                     static schedule on drifting fleets + warm
                                     re-solve latency (>=10x over cold)
    heterogeneous_cuts    (ours)     per-class cut assignment: strict theta
                                     improvement on the lognormal fleet, bit-exact
                                     collapse when homogeneous, ragged q8 oracle
    compress_sweep        (ours)     compression ratio/omega priced through BCD,
                                     Thm 1 + the fused q8 kernel oracle
    participation_sweep   (ours)     straggler deadline: round-time vs
                                     rounds-to-eps crossover + masked training
    privacy_energy        (ours)     DP-noised uplinks + per-tier energy pricing:
                                     bit-exact noiseless/free collapse, solver
                                     retreat under (eps, delta) / joule budgets,
                                     sigma^2-inflated Thm 1 vs a real noised run
    ablations             Figs. 8-9  MA / MS ablations (+ real training)
    bound_check           Thm 1      empirical gradient norms vs the bound
    async_scale           (ours)     sharded async engine (DESIGN.md §17):
                                     staleness-0 bit-exact collapse, 10^6-client
                                     async-vs-sync round pricing, staleness-
                                     inflated Thm 1 envelope, CPU rehearsal
                                     of a sharded round
"""
from __future__ import annotations

import argparse
import contextlib
import ctypes
import signal
import sys
import threading
import time


class HarnessTimeout(Exception):
    """A harness overran ``--timeout`` and was interrupted."""


@contextlib.contextmanager
def _alarm(seconds: int):
    """Wall-clock limit for one harness.  0 disables the limit.

    On the main thread of a platform with SIGALRM, a signal-based alarm
    interrupts the straggler directly.  Everywhere else — a worker
    thread driving ``main()`` programmatically, or a platform without
    SIGALRM — a watchdog thread injects ``HarnessTimeout`` into the
    *calling* thread via ``PyThreadState_SetAsyncExc``; the exception
    lands at the next bytecode boundary, so a harness stuck inside one
    long C call is interrupted when that call returns.  Previously these
    callers silently ran with no limit at all.
    """
    if seconds <= 0:
        yield
        return
    if (
        hasattr(signal, "SIGALRM")
        and threading.current_thread() is threading.main_thread()
    ):
        prev = signal.signal(
            signal.SIGALRM,
            lambda *_: (_ for _ in ()).throw(
                HarnessTimeout(f"exceeded --timeout {seconds}s")
            ),
        )
        signal.alarm(seconds)
        try:
            yield
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, prev)
        return
    # watchdog-thread fallback: no signals involved, works from any thread
    target = threading.get_ident()
    done = threading.Event()

    def watch():
        if not done.wait(seconds) and not done.is_set():
            ctypes.pythonapi.PyThreadState_SetAsyncExc(
                ctypes.c_ulong(target), ctypes.py_object(HarnessTimeout)
            )

    watchdog = threading.Thread(target=watch, daemon=True, name="bench-watchdog")
    watchdog.start()
    try:
        yield
    except HarnessTimeout:
        # async-injected exceptions carry no message; re-raise with one
        raise HarnessTimeout(f"exceeded --timeout {seconds}s") from None
    finally:
        done.set()
        watchdog.join()


def _registry(args):
    from . import (
        ablations, async_scale, bound_check, compress_sweep, control_drift,
        fault_tolerance, fig2_latency_vs_cut, fig45_benchmarks,
        fig67_resources, heterogeneous_cuts, participation_sweep,
        privacy_energy, sim_scale, solver_scale,
    )

    return [
        # (name, group, thunk)
        ("fig2_latency_vs_cut", "analytic",
         lambda: fig2_latency_vs_cut.main(args.quick, seed=args.seed)),
        ("fig45_benchmarks", "analytic",
         lambda: fig45_benchmarks.main(args.quick, seed=args.seed)),
        ("fig67_resources", "analytic",
         lambda: fig67_resources.main(args.quick, seed=args.seed)),
        ("sim_scale", "analytic",
         lambda: sim_scale.main(args.quick, seed=args.seed)),
        ("solver_scale", "analytic",
         lambda: solver_scale.main(args.quick, seed=args.seed)),
        ("control_drift", "analytic",
         lambda: control_drift.main(args.quick, seed=args.seed)),
        ("heterogeneous_cuts", "analytic",
         lambda: heterogeneous_cuts.main(args.quick, seed=args.seed)),
        ("ablations", "training",
         lambda: ablations.main(args.quick, seed=args.seed)),
        ("bound_check", "training",
         lambda: bound_check.main(args.quick, seed=args.seed)),
        # runs a (tiny) real compressed training round for the omega bound
        ("compress_sweep", "training",
         lambda: compress_sweep.main(args.quick, seed=args.seed)),
        # runs a (tiny) real masked training run off the sampled fleet masks
        ("participation_sweep", "training",
         lambda: participation_sweep.main(args.quick, seed=args.seed)),
        # runs a (tiny) real DP-noised masked run for the sigma^2 envelope
        ("privacy_energy", "training",
         lambda: privacy_energy.main(args.quick, seed=args.seed)),
        # runs the fault-storm drill: guarded training + crash recovery
        ("fault_tolerance", "training",
         lambda: fault_tolerance.main(args.quick, seed=args.seed)),
        # prices + runs the sharded async engine (real s=0/s=1 training,
        # a 10^6-client overlap sweep, and a CPU-held sharded subprocess round)
        ("async_scale", "training",
         lambda: async_scale.main(args.quick, seed=args.seed)),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("names", nargs="*",
                    help="harness names to run (default: all)")
    ap.add_argument("--quick", action="store_true",
                    help="smaller grids / fewer training rounds")
    ap.add_argument("--seed", type=int, default=0,
                    help="base PRNG seed threaded through every harness")
    ap.add_argument("--skip-training", action="store_true",
                    help="skip the real-training ablation/bound harnesses")
    ap.add_argument("--only", default=None,
                    help="run a single harness (same as one positional name)")
    ap.add_argument("--list", action="store_true", dest="list_harnesses",
                    help="print the registered harnesses and exit")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a machine-readable result artifact (rows per "
                         "harness + recorded ExperimentResults) to PATH")
    ap.add_argument("--timeout", type=int, default=0, metavar="SECONDS",
                    help="per-harness wall-clock limit; an overrunning "
                         "harness is interrupted and reported as failed "
                         "while the rest of the run continues (0 = no limit)")
    args = ap.parse_args(argv)

    registry = _registry(args)
    if args.list_harnesses:
        for name, group, _ in registry:
            print(f"{name:22s} [{group}]")
        return 0

    selected = list(args.names) + ([args.only] if args.only else [])
    if selected:
        known = {n for n, _, _ in registry}
        unknown = [n for n in selected if n not in known]
        if unknown:
            print(f"unknown harness(es) {unknown!r}; --list shows the "
                  "registry", file=sys.stderr)
            return 2
        # an explicitly named harness always runs, even under --skip-training
        jobs = [(n, f) for n, _, f in registry if n in selected]
    else:
        jobs = [(n, f) for n, group, f in registry
                if not (args.skip_training and group == "training")]

    failures = []
    report = {}
    for name, fn in jobs:
        print(f"\n{'='*70}\n== {name}\n{'='*70}")
        t0 = time.time()
        try:
            with _alarm(args.timeout):
                rows = fn()
            dt = time.time() - t0
            report[name] = {"ok": True, "seconds": dt, "rows": rows}
            print(f"-- {name} ok ({dt:.1f}s)")
        except Exception as e:  # keep going; report at the end
            failures.append((name, repr(e)))
            report[name] = {"ok": False, "seconds": time.time() - t0,
                            "error": repr(e)}
            print(f"-- {name} FAILED: {e!r}", file=sys.stderr)
    if args.json:
        _write_json(args.json, args, report)
    _summary(report)
    if failures:
        print(f"\n{len(failures)} harness(es) failed: {failures}", file=sys.stderr)
        return 1
    print(f"\nall {len(jobs)} harnesses passed")
    return 0


def _summary(report: dict) -> None:
    """Pass/fail table over everything that ran, failures last."""
    if not report:
        return
    print(f"\n{'='*70}\n== summary\n{'='*70}")
    print(f"{'harness':<22s} {'status':<8s} {'seconds':>8s}")
    for name, r in sorted(report.items(), key=lambda kv: kv[1]["ok"],
                          reverse=True):
        status = "ok" if r["ok"] else "FAILED"
        print(f"{name:<22s} {status:<8s} {r['seconds']:>8.1f}"
              + ("" if r["ok"] else f"  {r['error']}"))


def _write_json(path: str, args, report: dict) -> None:
    """One artifact per run: harness row tables + recorded ExperimentResults."""
    import json

    from repro.api import jsonify

    from . import common

    doc = {
        "meta": {
            "seed": args.seed,
            "quick": bool(args.quick),
            "skip_training": bool(args.skip_training),
            "harnesses": sorted(report),
            "failed": sorted(n for n, r in report.items() if not r["ok"]),
        },
        "harnesses": jsonify(report),
        "experiments": [r.to_dict() for r in common.RESULTS],
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True, default=str)
    print(f"\nwrote JSON artifact -> {path} "
          f"({len(common.RESULTS)} experiment result(s))")


if __name__ == "__main__":
    raise SystemExit(main())
