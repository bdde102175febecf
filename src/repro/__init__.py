"""repro — the HSFL paper as a production-shaped JAX/Pallas system.

``repro.api`` is the front door: a declarative, serializable
``ExperimentSpec`` that builds the solvers, the fleet simulator, and the
training engines (DESIGN.md §10).  ``repro.core`` / ``repro.sim`` /
``repro.compress`` remain the stable low-level layers underneath.

Submodules are imported lazily so ``import repro`` stays cheap.
"""
from importlib import import_module

_SUBMODULES = (
    "api",
    "checkpoint",
    "compress",
    "configs",
    "control",
    "core",
    "data",
    "energy",
    "kernels",
    "launch",
    "models",
    "obs",
    "optim",
    "privacy",
    "sim",
)

__all__ = list(_SUBMODULES)


def __getattr__(name: str):
    if name in _SUBMODULES:
        mod = import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_SUBMODULES))
