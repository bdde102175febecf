#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It builds the cell from its seed, warms every program the window drives
(that is set-up), measures for ``--seconds`` and then checks what the timed
path produced against the plain reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, traced, ``breakdown``; last of all
``check``, each number compared beside its limit.  The same numbers are
the last lines of standard error.

With no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result.  It needs the program under ``src/`` beside ``bench/``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"no program under {ROOT / 'src'}; run from a checkout")
    if not (ROOT / "BENCHMARK.json").is_file():
        return _fail("no BENCHMARK.json at the root of the checkout")
    # the compile cache sits at a fixed path inside the checkout: the
    # program's own configure_compile_cache() takes it from this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # a capped cache evicts programs the next run needs
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    from bench import harness

    t_proc = harness.process_start_time()
    try:
        benchmark = harness.load_json(ROOT / "BENCHMARK.json")
        wl = harness.load_workload(args.workload)
        seed = harness.derive_seed(args.seed)
    except harness.BenchError as e:
        return _fail(str(e))

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        return _fail(f"JAX found no device: {e}", 1)
    if devices[0].platform != "tpu":
        return _fail(f"no TPU (JAX platform {devices[0].platform!r}); the "
                     "benchmark runs only on the chip", 1)
    if len(devices) < wl["chips"]:
        return _fail(f"cell {args.workload} needs {wl['chips']} chips, "
                     f"JAX found {len(devices)}", 1)

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    rec = run_cell(wl, seed, args.seconds, bool(args.trace), t_proc,
                   devices[: wl["chips"]])
    print_result(rec, benchmark, wl, bool(args.trace), devices)
    return 0


def run_cell(wl, seed, seconds, trace, t_proc, devices, *, program=None):
    """Set up, warm, measure and check one cell; return its record.

    ``program`` replaces parts of the system under test (tests plant
    faults through it); the benchmark itself never passes it.
    """
    from bench import harness

    mode = harness.mode_module(wl["mode"])
    ctx = dict(workload=wl, config=wl["config_data"], seed=seed,
               seconds=seconds, trace=trace, t_proc=t_proc, devices=devices,
               program=program or {},
               trace_dir=ROOT / ".bench_trace" / wl["name"])
    rec = mode.run(ctx)
    rec.chips = len(devices)
    kind = devices[0].device_kind
    rec.peaks = harness.load_peaks(kind) if devices[0].platform == "tpu" else None
    return rec


def print_result(rec, benchmark, wl, trace, devices):
    from bench import harness

    metrics = harness.read_metrics(
        harness.metrics_for(benchmark, wl["name"], trace), rec
    )
    used = devices[: wl["chips"]]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": rec.peak_bytes}
    out = {"correct": bool(rec.correct), "attempted": int(rec.attempted),
           "failed": int(rec.failed), "metrics": metrics, "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        out["breakdown"] = rec.trace["breakdown"]
    print(json.dumps({"compiles_in_window": rec.compiles_in_window,
                      "setup_s": rec.setup_s, "window_s": rec.window_s,
                      "span_ms": rec.counters.get("span_ms"),
                      "round_spread": rec.counters.get("round_spread")}), file=sys.stderr)
    check = {name: {"value": value, "limit": limit}
             for name, value, limit in rec.check}
    for name, c in check.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    out["check"] = check
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
