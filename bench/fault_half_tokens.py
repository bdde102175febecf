#!/usr/bin/env python3
"""The planted fault "half of each client's tokens left out", read at an
LM cell's own shapes (on the chip).

    python bench/fault_half_tokens.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 0.5]

``bench/calibrate.py``'s fault leaves out half of each client's batch
rows; where a client's batch is one row that leaves out all of it, so the
round takes no step and its loss is not finite.  This fault keeps every
row and the first half of each sequence: every client still steps, on
half its tokens.  For each seed it runs the cell as ``bench/run.py``
does, with a short window, and prints the numbers its check compared and
what the fault reads against the same reference.  ``PERF.md`` records
the readings beside the limits.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def half_tokens(batches):
    """Each round's batches with the first half of every sequence."""
    return [{k: v[..., : v.shape[-1] // 2] for k, v in b.items()} for b in batches]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.5)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # a capped cache evicts programs the next run needs
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))

    import jax

    from bench import check, harness
    from bench.modes.train import plan_of
    from bench.run import run_cell
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    wl = harness.load_workload(args.workload)
    cfg = wl["config_data"]
    devices = jax.devices()[: wl["chips"]]
    for s in [int(x) for x in args.seeds.split(",")]:
        seed = harness.derive_seed(s)
        rec = run_cell(wl, seed, args.seconds, False, harness.process_start_time(), devices)
        inp = rec.check_inputs
        fault = check.hsfl_reference(harness.reference_module(cfg), cfg, plan_of(cfg, wl["traffic"]),
                                     cfg["optimizer"]["lr"], half_tokens(inp["batches"]), seed)
        print(json.dumps({"seed": s, "correct": rec.correct,
                          "program": {k: v for k, v, _ in rec.check},
                          "half_tokens": check.train_numbers(fault, inp["reference"])}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
