"""Sharded Engine A: the multi-host ``shard_map`` lowering (DESIGN.md §17).

The single-host engine stacks every parameter leaf per client on axis 0
and realizes the HSFL hierarchy as ``tiers.synchronize`` group means.
This module shards that client axis over the mesh's client axes
(``data``, or ``pod × data`` multi-pod — ``launch.sharding``'s layout
contract) and lowers each aggregation level to whichever of two
strategies preserves the single-host semantics:

* **device-local** — when every aggregation group lives wholly on one
  device (``groups % num_shards == 0``), the level IS the single-host
  arithmetic on the local shard: ``tiers._group_mean`` /
  ``_group_mean_masked`` run unchanged, so the result is bit-identical
  to the unsharded engine.
* **matmul-shaped collective** — when a group spans devices (the
  fed-server level, groups=1, always does), the level becomes one
  matmul per leaf: a local weight matrix ``W[G, N_local]`` (group
  one-hot × participation weights) contracts against the local client
  stack in f32, partial products are summed with ``lax.psum`` over the
  client axes, and the participant counts are psum'd alongside so the
  zero-participant keep-last fallback survives sharding.  This is
  bit-identical *up to f32 reduction order*: the single-host mean sums
  N replicas in one reduction, the sharded mean sums N/D per device
  then D partials — the one documented deviation
  (``tests/test_sharded_exec.py`` pins it at allclose, and pins the
  device-local levels exactly).

The §16 guard survives sharding exactly: per-client finite checks and
norm² are device-local arithmetic, and the fleet median is taken over an
``all_gather`` of the per-client norm vector — the same multiset of
values the single-host median sorts.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..optim import Optimizer
from .engine import TrainState, init_state_a, _masked_select
from .tiers import (
    GuardSpec,
    TierPlan,
    _group_mean,
    _group_mean_masked,
    combine_tiers,
    tier_subtrees,
)

Params = Dict[str, Any]


def _axis_tuple(client_axes) -> Tuple[str, ...]:
    if isinstance(client_axes, str):
        return (client_axes,)
    return tuple(client_axes)


def num_client_shards(mesh: Mesh, client_axes) -> int:
    return math.prod(mesh.shape[a] for a in _axis_tuple(client_axes))


def _client_base(axis_names: Tuple[str, ...], n_local: int) -> jax.Array:
    """Global client id of this shard's slot 0.

    Clients lay out row-major over the client axes (the order
    ``jax.device_put`` shards axis 0), so the shard index is the mixed-
    radix expansion of the axis indices in the given order.
    """
    idx = jnp.zeros((), jnp.int32)
    for ax in axis_names:
        idx = idx * lax.psum(1, ax) + lax.axis_index(ax)
    return idx * n_local


def _matmul_group_mean(
    tree: Params,
    groups: int,
    n_global: int,
    axis_names: Tuple[str, ...],
    w: Optional[jax.Array],
    keep: Optional[Params] = None,
) -> Params:
    """Cross-device group mean as one matmul-shaped pass per leaf.

    ``tree`` leaves are local shards [N_local, ...]; every group of the
    ``n_global``-client fleet spans shards.  The fed-server batch
    (groups=1) is the degenerate case: one [1, N_local] × [N_local, D]
    contraction per leaf, psum'd over the client axes.
    """
    leaves = jax.tree.leaves(tree)
    n_local = leaves[0].shape[0]
    base = _client_base(axis_names, n_local)
    gs = n_global // groups
    gid = (base + jnp.arange(n_local, dtype=jnp.int32)) // gs  # [N_local]
    onehot = (
        gid[:, None] == jnp.arange(groups, dtype=jnp.int32)[None, :]
    ).astype(jnp.float32)                                      # [N_local, G]
    wl = jnp.ones((n_local,), jnp.float32) if w is None else w.astype(jnp.float32)
    ww = onehot * wl[:, None]                                  # [N_local, G]
    cnt = lax.psum(jnp.sum(ww, axis=0), axis_names)            # [G]
    if keep is None:
        keep = tree

    def f(x, k):
        flat = x.reshape(n_local, -1).astype(jnp.float32)
        # HIGHEST: a TPU's default f32 matmul rounds its inputs to bf16,
        # which would quantize every parameter in the mean
        partial_sums = jnp.einsum(
            "ng,nd->gd", ww, flat, precision=lax.Precision.HIGHEST
        )                                                      # [G, D] matmul
        tot = lax.psum(partial_sums, axis_names)
        mean = tot / jnp.maximum(cnt, 1.0)[:, None]
        mine = mean[gid].astype(x.dtype).reshape(x.shape)      # gather my group
        alive = (cnt[gid] > 0.0).reshape((n_local,) + (1,) * (x.ndim - 1))
        return jnp.where(alive, mine, k)

    return jax.tree.map(f, tree, keep)


def sharded_guard_health(
    tree: Params,
    n_local: int,
    guard: GuardSpec,
    axis_names: Tuple[str, ...],
) -> Tuple[jax.Array, Params]:
    """``tiers.guard_health`` on a client shard: local finite/norm²
    arithmetic, fleet-median blow-up reference over an all_gather of the
    per-client norm vector (identical multiset → identical median)."""
    stacked = [
        x for x in jax.tree.leaves(tree)
        if hasattr(x, "ndim") and x.ndim > 0 and x.shape[0] == n_local
    ]
    finite = jnp.ones((n_local,), dtype=bool)
    for x in stacked:
        finite &= jnp.all(jnp.isfinite(x.reshape(n_local, -1)), axis=1)

    def sanitize(x):
        if not hasattr(x, "ndim") or x.ndim == 0 or x.shape[0] != n_local:
            return x
        ok = finite.reshape((n_local,) + (1,) * (x.ndim - 1))
        return jnp.where(ok, x, jnp.zeros((), x.dtype))

    clean = jax.tree.map(sanitize, tree)
    norm2 = jnp.zeros((n_local,), dtype=jnp.float32)
    for x in jax.tree.leaves(clean):
        if hasattr(x, "ndim") and x.ndim > 0 and x.shape[0] == n_local:
            f = x.reshape(n_local, -1).astype(jnp.float32)
            norm2 = norm2 + jnp.sum(f * f, axis=1)
    norm2_all = lax.all_gather(norm2, axis_names, axis=0, tiled=True)  # [N]
    med = jnp.median(norm2_all)
    blowup = norm2 > guard.norm_factor * jnp.maximum(med, jnp.float32(1e-30))
    health = (finite & ~blowup).astype(jnp.float32)
    return health, clean


def sharded_synchronize(
    params: Params,
    plan: TierPlan,
    step: jax.Array,
    *,
    num_shards: int,
    axis_names: Tuple[str, ...],
    fed_round=None,
    compress_fn=None,
    mask=None,
    guard: Optional[GuardSpec] = None,
) -> Params:
    """``tiers.synchronize`` on client shards, inside ``shard_map``.

    Semantics (fed-wire compression placement, mask weighting,
    zero-participant keep-last, guard quarantine, ``fed_round``
    specialization / ``lax.cond`` gating) mirror ``synchronize`` level
    for level; only the per-level *strategy* changes (module
    docstring).  Device-local levels are bit-identical; cross-device
    levels deviate by f32 reduction order only.
    """
    D = num_shards
    N = plan.num_clients
    n_local = N // D
    if guard is not None:
        health, params = sharded_guard_health(params, n_local, guard, axis_names)
        mask = health if mask is None else mask.astype(jnp.float32) * health
    parts = tier_subtrees(params, plan)
    if fed_round is not None and not isinstance(fed_round, (tuple, list)):
        fed_round = (bool(fed_round),) * plan.M
    out_parts = []
    for m, part in enumerate(parts):
        levels = plan.levels(m)
        for li, (groups, interval) in enumerate(levels):
            fed = (
                compress_fn is not None
                and m < plan.M - 1
                and li == len(levels) - 1
                and plan.entities[m] > 1
            )

            def level_mean(p, groups=groups, fed=fed):
                original = p
                if fed:
                    p = jax.tree.map(compress_fn, p)
                if groups % D == 0:
                    # every group lives wholly on one device: the level
                    # IS the single-host arithmetic on the local shard
                    if mask is not None:
                        return _group_mean_masked(
                            p, groups // D, mask, keep=original
                        )
                    return _group_mean(p, groups // D)
                return _matmul_group_mean(
                    p, groups, N, axis_names, mask, keep=original
                )

            if interval <= 1:
                part = level_mean(part)
            elif fed_round is None:
                do = (step + 1) % interval == 0
                part = lax.cond(do, level_mean, lambda p: p, part)
            elif fed_round[m]:
                part = level_mean(part)
        out_parts.append(part)
    return combine_tiers(out_parts, params)


# --------------------------------------------------------------------------- #
# the sharded Engine-A step
# --------------------------------------------------------------------------- #


def _client_pspec(ca: Tuple[str, ...]):
    return ca if len(ca) > 1 else ca[0]


def sharded_state_specs(state: TrainState, num_clients: int, client_axes):
    """PartitionSpec tree for a ``TrainState``: client axis 0 over the
    client axes, scalar bookkeeping replicated (``launch.sharding``'s
    training-step layout — TP over ``model`` is the serving path)."""
    from ..launch.sharding import train_pspecs

    return train_pspecs(state, _axis_tuple(client_axes), num_clients)


def init_sharded_state_a(
    model, plan: TierPlan, opt: Optimizer, key, mesh: Mesh, client_axes=("data",)
) -> TrainState:
    """``init_state_a`` placed on the mesh: same host-side init (same key →
    bit-identical initial replicas), then device_put under the client-axis
    shardings."""
    D = num_client_shards(mesh, client_axes)
    if plan.num_clients % D != 0:
        raise ValueError(
            f"num_clients={plan.num_clients} must divide over the "
            f"{D} client shards of mesh axes {_axis_tuple(client_axes)!r}"
        )
    state = init_state_a(model, plan, opt, key)
    specs = sharded_state_specs(state, plan.num_clients, client_axes)
    shardings = jax.tree.map(
        lambda ps: NamedSharding(mesh, ps), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.tree.map(jax.device_put, state, shardings)


def build_sharded_train_step_a(
    model,
    plan: TierPlan,
    opt: Optimizer,
    mesh: Mesh,
    *,
    client_axes=("data",),
    sync_opt_state: bool = False,
    fed_round=None,
    compressor=None,
    with_mask: bool = False,
    guard: Optional[GuardSpec] = None,
    with_sync_weights: bool = False,
) -> Callable[..., Tuple[TrainState, jax.Array]]:
    """``engine.build_train_step_a`` lowered to a ``shard_map`` program.

    Same signature contract as the single-host builder for the features
    that survive sharding (fed_round / compressor / with_mask / guard /
    sync_opt_state / with_sync_weights); ``privacy`` and
    ``class_members`` are *not* accepted — ``api.build`` rejects those
    spec combinations at build time (DESIGN.md §17 capability matrix).

    The returned step takes and returns client-sharded ``TrainState``s
    (see ``init_sharded_state_a``); batches shard their client axis the
    same way.  Loss is psum-reduced and replicated.
    """
    ca = _axis_tuple(client_axes)
    D = num_client_shards(mesh, ca)
    N = plan.num_clients
    if N % D != 0:
        raise ValueError(
            f"num_clients={N} must divide over the {D} client shards of "
            f"mesh axes {ca!r}"
        )
    n_local = N // D
    compress_fn = (
        None if compressor is None
        else lambda x: jax.vmap(lambda v: compressor.transform(v))(x)
    )

    def _sync(tree, step, *, compress=None, mask=None, guarded=False):
        return sharded_synchronize(
            tree, plan, step,
            num_shards=D, axis_names=ca, fed_round=fed_round,
            compress_fn=compress, mask=mask,
            guard=(guard if guarded else None),
        )

    # the wrapper always feeds a mask array (shard_map arity is static);
    # whether the *caller* masks is the static with_mask flag, which keeps
    # the unmasked paths (plain mean loss, unmasked _group_mean sync)
    # structurally identical to the single-host engine's mask=None graph.
    has_mask = with_mask

    def _shard_step(state: TrainState, batch: Params, mask):
        losses, grads = jax.vmap(jax.value_and_grad(model.loss_fn))(
            state.params, batch
        )
        new_params, new_opt = opt.update(state.params, grads, state.opt_state)
        if guard is not None:
            health, _ = sharded_guard_health(new_params, n_local, guard, ca)
            lfin = jnp.isfinite(losses)
            health = health * lfin.astype(jnp.float32)
            w = mask.astype(jnp.float32) * health if has_mask else health
            new_params = _masked_select(new_params, state.params, w)
            new_opt = _masked_select(new_opt, state.opt_state, w)
            lsafe = jnp.where(lfin, losses, 0.0)
            tot = lax.psum(jnp.sum(lsafe * w), ca)
            s = lax.psum(jnp.sum(w), ca)
            loss = jnp.where(s > 0.0, tot / jnp.maximum(s, 1.0), 0.0)
            if not has_mask:
                # all-healthy unmasked rounds report the exact plain mean
                # (the single-host engine's zero-fault collapse contract)
                all_healthy = lax.psum(jnp.sum(w >= 1.0), ca) >= N
                loss = jnp.where(
                    all_healthy, lax.psum(jnp.sum(lsafe), ca) / N, loss
                )
            sync_mask = w
        elif not has_mask:
            loss = lax.psum(jnp.sum(losses), ca) / N
            sync_mask = None
        else:
            w = mask.astype(jnp.float32)
            new_params = _masked_select(new_params, state.params, w)
            new_opt = _masked_select(new_opt, state.opt_state, w)
            tot = lax.psum(jnp.sum(losses * w), ca)
            s = lax.psum(jnp.sum(w), ca)
            loss = jnp.where(s > 0.0, tot / jnp.maximum(s, 1.0), 0.0)
            sync_mask = mask
        new_params = _sync(
            new_params, state.step, compress=compress_fn, mask=sync_mask,
            guarded=True,
        )
        if sync_opt_state and jax.tree.leaves(new_opt):
            if opt.name == "momentum":
                new_opt = _sync(new_opt, state.step, mask=sync_mask, guarded=True)
            elif opt.name == "adam":
                new_opt = dict(new_opt)
                new_opt["m"] = _sync(
                    new_opt["m"], state.step, mask=sync_mask, guarded=True
                )
                new_opt["v"] = _sync(
                    new_opt["v"], state.step, mask=sync_mask, guarded=True
                )
        out_state = TrainState(new_params, new_opt, state.step + 1)
        if with_sync_weights:
            ww = (
                jnp.ones((n_local,), jnp.float32)
                if sync_mask is None else sync_mask.astype(jnp.float32)
            )
            return out_state, loss, ww
        return out_state, loss, jnp.zeros((n_local,), jnp.float32)

    from ..launch.sharding import batch_pspecs, train_pspecs

    ca_spec = _client_pspec(ca)

    _cache: Dict[Any, Callable] = {}

    def _get(state, batch):
        key = (
            jax.tree.structure(batch),
            tuple(x.ndim for x in jax.tree.leaves(batch)),
            jax.tree.structure(state),
        )
        fn = _cache.get(key)
        if fn is not None:
            return fn
        state_specs = train_pspecs(state, ca, N)
        batch_specs = batch_pspecs(batch, ca)
        mapped = jax.shard_map(
            _shard_step,
            mesh=mesh,
            in_specs=(state_specs, batch_specs, P(ca_spec)),
            out_specs=(state_specs, P(), P(ca_spec)),
            check_vma=False,
        )
        fn = _cache[key] = jax.jit(mapped)
        return fn

    if with_mask or with_sync_weights:
        def step(state, batch, mask=None):
            if mask is None:
                mask_arr = jnp.ones((N,), jnp.float32) if with_mask else None
            else:
                mask_arr = jnp.asarray(mask, jnp.float32)
            if mask_arr is None:
                mask_arr = jnp.ones((N,), jnp.float32)
            out_state, loss, w = _get(state, batch)(state, batch, mask_arr)
            if with_sync_weights:
                return out_state, loss, w
            return out_state, loss
    else:
        def step(state, batch):
            mask_arr = jnp.ones((N,), jnp.float32)
            out_state, loss, _ = _get(state, batch)(state, batch, mask_arr)
            return out_state, loss

    return step
