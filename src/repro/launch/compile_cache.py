"""Where JAX keeps its persistent compilation cache.

Every entry point (``launch.train``, ``launch.serve``, ``chip_smoke.py``)
calls :func:`configure_compile_cache` before its first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed here.  Otherwise the cache goes to ``.jax_cache`` at the root of
the checkout: a fixed path, because the path is part of what makes a
later process find an entry again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; return it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
