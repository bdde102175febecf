"""Production meshes (TPU v5e target).

Single pod:  (data=16, model=16)          = 256 chips
Multi-pod:   (pod=2, data=16, model=16)   = 512 chips

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS *before* any jax initialization).
The HSFL mapping (DESIGN.md §2): one index of the client axis — `data`,
or (`pod`, `data`) in multi-pod — hosts one client's parameter replicas;
`model` is Megatron-style tensor parallelism inside every tier; the `pod`
axis is an additional HSFL hierarchy level whose aggregation interval the
MA solver prices with DCN (not ICI) constants.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

POD_SHAPE = (16, 16)
MULTIPOD_SHAPE = (2, 16, 16)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the compiler propagates
    shardings from the placed inputs and the ``shard_map`` specs.  (Its
    default, ``Explicit``, carries shardings in the types, which this
    repo's unannotated gathers and reshapes do not.)"""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTIPOD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def client_axes(multi_pod: bool = False):
    """Mesh axes the client-stacked parameter axis is sharded over."""
    return ("pod", "data") if multi_pod else ("data",)


def num_clients(multi_pod: bool = False) -> int:
    """One HSFL client per (pod, data) index."""
    import math

    shape = MULTIPOD_SHAPE if multi_pod else POD_SHAPE
    return math.prod(shape) // shape[-1]


def make_debug_mesh(data: int = 2, model: int = 2, pods: int = 0):
    """Tiny host-device mesh for tests.

    Requires ``--xla_force_host_platform_device_count`` (in XLA_FLAGS)
    to have been set to at least data·model·max(pods, 1) *before* jax
    initialized its backend — the flag is read exactly once, at backend
    init, so setting it afterwards is silently ignored.  Rather than let
    ``jax.make_mesh`` fail with an opaque shape assertion (or silently
    build a 1×1 mesh), detect the already-initialized-with-too-few-
    devices state here and say what to do about it.
    """
    need = data * model * max(pods, 1)
    have = jax.device_count()
    if have < need:
        raise RuntimeError(
            f"make_debug_mesh needs {need} devices "
            f"({pods or 1}x{data}x{model}) but the jax backend initialized "
            f"with only {have}.  The host-platform device count is fixed at "
            f"backend init: set XLA_FLAGS="
            f"'--xla_force_host_platform_device_count={need}' in the "
            f"environment (or jax.config) BEFORE the first jax call — e.g. "
            f"run the sharded test/benchmark in a fresh subprocess with the "
            f"flag exported, as tests/test_sharded_exec.py does."
        )
    if pods:
        return make_mesh((pods, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
