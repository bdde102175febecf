#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python bench/rehearse_v5e.py <cell> [<cell> ...]

For each program the cell's window drives (the round programs of a
training cell) it prints one JSON line
with the bytes ``memory_analysis`` gives for one device: arguments,
outputs, temporaries, and their sum less the outputs that alias
arguments.  The compiler refuses here what it would refuse on the chip,
including a program that does not fit.  Nothing runs, so no time comes
from this.  The topology is ``v5e:2x2``; one-chip cells use its first
device.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, program
    from bench.modes.train import plan_of, program_name

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev), tree)

    def report(cell, name, compiled):
        m = compiled.memory_analysis()
        print(json.dumps({
            "cell": cell, "program": name,
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "total_bytes": m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes,
        }), flush=True)

    for cell in argv:
        wl = harness.load_workload(cell)
        cfg, traffic = wl["config_data"], wl["traffic"]
        spec = program.model_spec(cfg)
        if wl["mode"] != "train":
            raise SystemExit(f"{cell}: no rehearsal for mode {wl['mode']!r}")
        from repro.core.engine import build_train_step_a, init_state_a
        from repro.core.tiers import TierPlan
        from repro.launch.train import fed_round
        from repro.models.vgg import build_model
        from repro.optim import sgd

        model = build_model(spec)
        p = plan_of(cfg, traffic)
        N = p["clients"]
        plan = TierPlan(spec.n_units, N, tuple(p["cuts"]), tuple(p["intervals"]),
                        (N, p["edges"], 1))
        opt = sgd(cfg["optimizer"]["lr"])
        state = shaped(jax.eval_shape(
            lambda k: init_state_a(model, plan, opt, k), jax.random.PRNGKey(0)))
        b = traffic["batch"]
        if cfg["family"] == "vgg":
            hw, c = cfg["image_size"], cfg["in_channels"]
            batch = {"images": jnp.zeros((N, b, hw, hw, c), jnp.float32),
                     "labels": jnp.zeros((N, b), jnp.int32)}
        else:
            s = traffic["seq"]
            batch = {"tokens": jnp.zeros((N, b, s), jnp.int32),
                     "labels": jnp.zeros((N, b, s), jnp.int32)}
        batch = shaped(jax.eval_shape(lambda: batch))
        feds = sorted({fed_round(plan.intervals, r) for r in range(64)})
        for fed in feds:
            step = jax.jit(build_train_step_a(model, plan, opt, fed_round=fed))
            report(cell, program_name(fed), step.lower(state, batch).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
