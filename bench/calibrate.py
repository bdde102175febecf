#!/usr/bin/env python3
"""Readings from which a cell's limits of ``correct`` are set (on the chip).

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--seconds 0.5]

In one process (set-up and compiles are shared), for each seed it runs
the cell as ``bench/run.py`` does, with a short window, and prints the
numbers its check compared and the cell's end-to-end metrics.  For each control seed it also prints what
the control reads (the reference computed in bfloat16, put in the
program's place) and what the planted fault "half
of each client's batch left out" reads.  The limits in
``bench/workloads/<cell>.json`` are set from these lines, as ``PERF.md``
records.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.5)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # a capped cache evicts programs the next run needs
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    import jax
    import jax.numpy as jnp

    from bench import check, harness
    from bench.modes.train import plan_of
    from bench.run import run_cell
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    benchmark = harness.load_json(ROOT / "BENCHMARK.json")
    wl = harness.load_workload(args.workload)
    cfg = wl["config_data"]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    devices = jax.devices()[: wl["chips"]]
    for s in [int(x) for x in args.seeds.split(",")]:
        seed = harness.derive_seed(s)
        rec = run_cell(wl, seed, args.seconds, False, harness.process_start_time(), devices)
        out = {"seed": s, "correct": rec.correct,
               "program": {k: v for k, v, _ in rec.check},
               "metrics": {k: m["value"] for k, m in harness.read_metrics(
                   harness.metrics_for(benchmark, wl["name"], False), rec).items()}}
        if s in controls:
            ref = harness.reference_module(cfg)
            inp = rec.check_inputs
            plan = plan_of(cfg, wl["traffic"])
            lr = cfg["optimizer"]["lr"]
            ctrl = check.hsfl_reference(ref, cfg, plan, lr, inp["batches"], seed,
                                        dtype=jnp.bfloat16)
            half = check.hsfl_reference(ref, cfg, plan, lr, inp["batches"], seed,
                                        batch_rows=wl["traffic"]["batch"] // 2)
            out["control"] = check.train_numbers(ctrl, inp["reference"])
            out["half_batch"] = check.train_numbers(half, inp["reference"])
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
