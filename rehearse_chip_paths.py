#!/usr/bin/env python3
"""``bench/rehearse_v5e.py`` with the model on its TPU paths.

    JAX_PLATFORMS=cpu python rehearse_chip_paths.py [--no-remat] <cell> [<cell> ...]

The model picks its chip paths by asking ``jax.default_backend()``
(latent attention in the splash kernel, bf16 operands, megablox not
interpreted). The rehearsal runs on the CPU backend and so compiles the
CPU paths; this script answers "tpu" to that question first, so that
the programs compiled for the described v5e are the ones the chip runs.
``--no-remat`` compiles the cell's model with ``remat`` off, the memory
plan without per-unit recomputation. Prints the rehearsal's JSON lines.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent


def main(argv) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    from bench import program, rehearse_v5e

    jax.default_backend = lambda: "tpu"
    if "--no-remat" in argv:
        argv = [a for a in argv if a != "--no-remat"]
        spec_of = program.model_spec
        program.model_spec = lambda cfg: dataclasses.replace(spec_of(cfg), remat=False)
    return rehearse_v5e.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
