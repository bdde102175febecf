"""Tier plans: HSFL's model-splitting + multi-timescale aggregation schedule.

A ``TierPlan`` captures the paper's (μ, I) decisions plus the entity topology:

* ``cuts``       — M-1 unit boundaries; tier m owns units [cuts[m-1], cuts[m])
                   (frontend ∈ tier 1, head ∈ tier M).
* ``intervals``  — I_m per tier; I_M is forced to 1 (single cloud server).
* ``levels``     — generalized aggregation schedule: per tier, a list of
                   (num_groups, interval) levels applied round-robin. The
                   paper's scheme is [(J_m, 1), (1, I_m)] (entity sync every
                   round — Eq. 3; fed-server aggregation every I_m — Eq. 4).
                   Multi-pod adds a pod level, e.g. tier M: [(P, 1), (1, I_pod)].

Synchronization operates on client-stacked parameter pytrees (axis 0 = client).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs

Params = Dict[str, Any]


@dataclass(frozen=True)
class GuardSpec:
    """Aggregation guard: quarantine corrupt uploads (DESIGN.md §16).

    A client is *unhealthy* this round when any client-stacked leaf row
    carries a non-finite value, or when its sanitized squared parameter
    norm exceeds ``norm_factor`` × the fleet median (the blow-up check
    that catches finite corruption — scaled uploads, exponent bitflips).
    The guard converts an unhealthy client into a zero-participant via
    the §12 mask machinery: it contributes nothing to any level's mean
    but still *receives* the participating group's broadcast, which is
    what heals it.  Limitation: the median reference assumes fewer than
    half the fleet blows up the same way at once.
    """

    norm_factor: float = 1e4

    def __post_init__(self):
        if self.norm_factor <= 1.0 or not math.isfinite(self.norm_factor):
            raise ValueError(
                f"norm_factor must be finite and > 1: {self.norm_factor}"
            )


def guard_health(
    tree: Params, num_clients: int, guard: GuardSpec,
    client_axes: Tuple[str, ...] = (),
) -> Tuple[jax.Array, Params]:
    """(health mask [N] float32, sanitized tree) for a client-stacked pytree.

    Sanitization zeroes non-finite rows *before* any arithmetic touches
    them, so the guard itself never produces a NaN/Inf — on an all-healthy
    round every ``where`` selects the original values and the returned
    tree is bit-identical to the input (the ``JAX_DEBUG_NANS`` contract
    pinned in ``tests/test_faults.py``).  Leaves without a leading client
    axis (scalar bookkeeping) pass through unchecked.

    Inside ``shard_map`` over the mesh's ``client_axes``, ``tree`` is this
    shard's ``num_clients`` rows: the finite and norm² checks stay local,
    and the fleet median is taken over an ``all_gather`` of the norm²
    row — the same multiset of values, so the same median.
    """
    N = num_clients
    stacked = [
        x for x in jax.tree.leaves(tree)
        if hasattr(x, "ndim") and x.ndim > 0 and x.shape[0] == N
    ]
    finite = jnp.ones((N,), dtype=bool)
    for x in stacked:
        finite &= jnp.all(
            jnp.isfinite(x.reshape(N, -1)), axis=1
        )

    def sanitize(x):
        if not hasattr(x, "ndim") or x.ndim == 0 or x.shape[0] != N:
            return x
        ok = finite.reshape((N,) + (1,) * (x.ndim - 1))
        return jnp.where(ok, x, jnp.zeros((), x.dtype))

    clean = jax.tree.map(sanitize, tree)
    norm2 = jnp.zeros((N,), dtype=jnp.float32)
    for x in jax.tree.leaves(clean):
        if hasattr(x, "ndim") and x.ndim > 0 and x.shape[0] == N:
            f = x.reshape(N, -1).astype(jnp.float32)
            norm2 = norm2 + jnp.sum(f * f, axis=1)
    med = jnp.median(
        lax.all_gather(norm2, client_axes, axis=0, tiled=True)
        if client_axes else norm2
    )
    blowup = norm2 > guard.norm_factor * jnp.maximum(med, jnp.float32(1e-30))
    health = (finite & ~blowup).astype(jnp.float32)
    return health, clean


@dataclass(frozen=True)
class TierPlan:
    n_units: int
    num_clients: int
    cuts: Tuple[int, ...]          # len M-1, non-decreasing, in [0, n_units]
    intervals: Tuple[int, ...]     # len M (last forced 1)
    entities: Tuple[int, ...]      # J_m per tier; J_1 = num_clients, J_M = 1
    pod_interval: int = 0          # >0: extra cross-pod level on the top tier
    num_pods: int = 1

    def __post_init__(self):
        # User-facing invariants raise ValueError (not ``assert``): plans are
        # built from config files / API specs, and asserts vanish under
        # ``python -O``, silently admitting invalid plans.
        M = len(self.intervals)
        if len(self.cuts) != M - 1:
            raise ValueError(
                f"TierPlan needs exactly M-1 = {M - 1} cuts for "
                f"{M} intervals, got {len(self.cuts)}: "
                f"cuts={self.cuts!r}, intervals={self.intervals!r}"
            )
        if any(
            self.cuts[i] > self.cuts[i + 1] for i in range(len(self.cuts) - 1)
        ):
            raise ValueError(
                f"cuts must be non-decreasing (C4): {self.cuts!r}"
            )
        if any(not 0 <= c <= self.n_units for c in self.cuts):
            raise ValueError(
                f"every cut must lie in [0, n_units={self.n_units}]: "
                f"{self.cuts!r}"
            )
        if self.intervals[-1] != 1:
            raise ValueError(
                "top tier is always synchronized: intervals[-1] must be 1, "
                f"got {self.intervals!r}"
            )
        if len(self.entities) != M:
            raise ValueError(
                f"entities must list J_m for each of the {M} tiers, got "
                f"{len(self.entities)}: {self.entities!r}"
            )
        for j in self.entities:
            if j <= 0 or self.num_clients % j != 0:
                raise ValueError(
                    f"each tier's entity count must evenly divide "
                    f"num_clients={self.num_clients}: entities="
                    f"{self.entities!r} (offending J_m={j})"
                )

    @property
    def M(self) -> int:
        return len(self.intervals)

    def tier_bounds(self, m: int) -> Tuple[int, int]:
        """Unit range [lo, hi) of tier m (0-indexed)."""
        lo = 0 if m == 0 else self.cuts[m - 1]
        hi = self.n_units if m == self.M - 1 else self.cuts[m]
        return lo, hi

    def tier_of_unit(self, u: int) -> int:
        for m in range(self.M):
            lo, hi = self.tier_bounds(m)
            if lo <= u < hi:
                return m
        return self.M - 1

    def levels(self, m: int) -> List[Tuple[int, int]]:
        """Aggregation levels (num_groups, interval) for tier m."""
        lv: List[Tuple[int, int]] = []
        if self.entities[m] < self.num_clients:
            lv.append((self.entities[m], 1))  # Eq. (3): entity-local, per-round
        if m == self.M - 1:
            if self.pod_interval > 0 and self.num_pods > 1:
                # per-pod logical cloud every round; cross-pod at I_pod
                lv = [(self.num_pods, 1), (1, self.pod_interval)]
            else:
                lv.append((1, 1))
        else:
            lv.append((1, int(self.intervals[m])))  # Eq. (4): fed server
        return lv


# --------------------------------------------------------------------------- #
# pytree partition by tier
# --------------------------------------------------------------------------- #


def _slice_units(units: Any, lo: int, hi: int) -> Any:
    """Slice a unit container (stacked arrays: axis *after* the client axis,
    or python list) to the range [lo, hi)."""
    if isinstance(units, (list, tuple)):
        return list(units)[lo:hi]
    if isinstance(units, dict) and set(units) == {"enc", "dec"}:
        # audio: two stacks laid out enc ++ dec
        out = {}
        ne = jax.tree.leaves(units["enc"])[0].shape[1]
        e_lo, e_hi = min(lo, ne), min(hi, ne)
        d_lo, d_hi = max(lo, ne) - ne, max(hi, ne) - ne
        out["enc"] = jax.tree.map(lambda x: x[:, e_lo:e_hi], units["enc"])
        out["dec"] = jax.tree.map(lambda x: x[:, d_lo:d_hi], units["dec"])
        return out
    return jax.tree.map(lambda x: x[:, lo:hi], units)


def _tier_part(params: Params, plan: TierPlan, m: int) -> Params:
    """Tier m's pytree: its unit range, plus the frontend (tier 1) and the
    head (tier M)."""
    lo, hi = plan.tier_bounds(m)
    part: Params = {"units": _slice_units(params["units"], lo, hi)}
    if m == 0:
        part["frontend"] = params["frontend"]
    if m == plan.M - 1:
        part["head"] = params["head"]
    return part


def tier_subtrees(params: Params, plan: TierPlan) -> List[Params]:
    """Split a client-stacked model pytree into per-tier pytrees (views)."""
    return [_tier_part(params, plan, m) for m in range(plan.M)]


def combine_tiers(parts: List[Params], template: Params) -> Params:
    """Inverse of tier_subtrees (same cut structure)."""
    units_parts = [p["units"] for p in parts]
    tu = template["units"]
    if isinstance(tu, (list, tuple)):
        units = [u for part in units_parts for u in part]
    elif isinstance(tu, dict) and set(tu) == {"enc", "dec"}:
        units = {
            "enc": _concat_stacks([p["enc"] for p in units_parts]),
            "dec": _concat_stacks([p["dec"] for p in units_parts]),
        }
    else:
        units = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *units_parts)
    out = {"units": units, "frontend": parts[0]["frontend"], "head": parts[-1]["head"]}
    return out


def _concat_stacks(stacks: List[Any]) -> Any:
    stacks = [s for s in stacks if jax.tree.leaves(s)]
    if len(stacks) == 1:
        return stacks[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *stacks)


def _update_units(units: Any, part: Any, lo: int, hi: int) -> Any:
    """Write ``part`` (a ``_slice_units(units, lo, hi)`` result) back into
    the range [lo, hi) of ``units``.  A list has its entries replaced; a
    stacked leaf is updated along the unit axis with a static-start
    ``dynamic_update_slice``, which XLA performs in place on a buffer
    nothing else reads — no concatenate copies the other tiers."""
    if isinstance(units, (list, tuple)):
        out = list(units)
        out[lo:hi] = part
        return out

    def put(stack, new, start):
        return jax.tree.map(
            lambda x, p: lax.dynamic_update_slice_in_dim(x, p, start, axis=1),
            stack, new,
        )

    if isinstance(units, dict) and set(units) == {"enc", "dec"}:
        ne = jax.tree.leaves(units["enc"])[0].shape[1]
        return {
            "enc": put(units["enc"], part["enc"], min(lo, ne)),
            "dec": put(units["dec"], part["dec"], max(lo, ne) - ne),
        }
    return put(units, part, lo)


def _put_tier(params: Params, part: Params, plan: TierPlan, m: int) -> Params:
    """Inverse of ``_tier_part``: ``params`` with tier m replaced by ``part``."""
    lo, hi = plan.tier_bounds(m)
    out = dict(params)
    out["units"] = _update_units(params["units"], part["units"], lo, hi)
    for name in ("frontend", "head"):
        if name in part:
            out[name] = part[name]
    return out


# --------------------------------------------------------------------------- #
# synchronization (the HSFL aggregation schedule, Eqs. 3–4)
# --------------------------------------------------------------------------- #


def _group_mean(tree: Params, groups: int) -> Params:
    """Mean over client groups, broadcast back. Leaves: [N, ...].

    On a mesh (DESIGN.md §17) a level whose groups each lie on one shard
    runs this function on the shard's rows, bit-identical to one host;
    a level whose groups span shards runs ``_matmul_group_mean`` instead.
    """

    def f(x):
        n = x.shape[0]
        g = x.reshape(groups, n // groups, *x.shape[1:])
        m = jnp.mean(g, axis=1, keepdims=True, dtype=jnp.float32).astype(x.dtype)
        return jnp.broadcast_to(m, g.shape).reshape(x.shape)

    return jax.tree.map(f, tree)


def _group_mean_masked(
    tree: Params, groups: int, w: jax.Array, keep: Params = None
) -> Params:
    """Participation-weighted group mean, broadcast back (DESIGN.md §12).

    ``w`` is the per-client participation mask [N] (0/1 float32).  Each
    group averages only its participants — effective weights w_i / Σ_g w
    sum to 1 per participating group — and the aggregate is broadcast to
    *every* member (state lives at the group's server, so an absentee
    resumes from the group aggregate when it rejoins).  A zero-participant
    group keeps its members' current params — the entity's last synced
    value — matching the fleet simulator's zero-participant convention
    (nothing is uploaded, so nothing moves).

    Because a completed level leaves every member of a subgroup carrying
    the subgroup's weighted mean, re-averaging the next (coarser) level
    with the same per-client weights reproduces exact hierarchical
    participant-count weighting: Σ_i w_i x_i / Σ_i w_i = Σ_g s_g m_g / Σ_g
    s_g.  With w ≡ 1 the arithmetic (f32 multiply-by-one, same sum
    reduction, divide by the group size) is bit-identical to
    ``_group_mean``.

    ``keep`` (optional pytree matching ``tree``) supplies the fallback
    values a zero-participant group retains.  It defaults to ``tree``
    itself, which is right whenever the input *is* the clients' current
    state — but a compressed fed-server upload must pass the
    pre-compression params here, otherwise a silent group "keeps" a
    lossy-coded copy it never uploaded (DESIGN.md §9/§12).

    ``_matmul_group_mean`` reproduces these weights across shards with
    per-shard partial sums + ``lax.psum``; a zero-participant group's
    keep-fallback becomes a ``where`` against the psum'd counts.  Note the
    group mean is NOT idempotent on already-averaged rows when weights
    differ, which is why the deferred fed-server replay in
    ``core.async_agg.fed_level_apply`` re-derives the level from a
    snapshot delta instead of calling ``synchronize`` twice (§17).
    """
    w = w.astype(jnp.float32)
    if keep is None:
        keep = tree

    def f(x, k):
        n = x.shape[0]
        g = x.reshape(groups, n // groups, *x.shape[1:])
        gk = k.reshape(groups, n // groups, *x.shape[1:])
        wg = w.reshape(groups, n // groups)
        ww = wg.reshape(wg.shape + (1,) * (g.ndim - 2))
        s = jnp.sum(wg, axis=1).reshape((groups,) + (1,) * (g.ndim - 1))
        tot = jnp.sum(
            g * ww.astype(g.dtype), axis=1, keepdims=True, dtype=jnp.float32
        )
        m = (tot / jnp.maximum(s, 1.0)).astype(x.dtype)
        out = jnp.where(s > 0.0, jnp.broadcast_to(m, g.shape), gk)
        return out.reshape(x.shape)

    return jax.tree.map(f, tree, keep)


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
    contraction per leaf, psum'd over the client axes.  Equal to
    ``_group_mean`` / ``_group_mean_masked`` up to f32 reduction order:
    each shard sums its N_local rows, then the D partials are summed.
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


def synchronize(
    params: Params,
    plan: TierPlan,
    step: jax.Array,
    *,
    fed_round=None,
    compress_fn=None,
    mask=None,
    guard: Optional[GuardSpec] = None,
    client_axes: Tuple[str, ...] = (),
) -> Params:
    """Apply the per-tier aggregation schedule at round ``step`` (post-update).

    Rounds are 1-indexed in the paper; we sync when (step+1) % I == 0 so that
    interval I=k aggregates after every k-th update.

    ``fed_round`` specializes the interval-gated (I_m > 1) fed-server levels:
      * None — dynamic ``lax.cond`` on the step counter (single compiled
        step; both branches live in the HLO, so the hot path carries the
        fed-server collectives even though they amortize 1/I_m at runtime);
      * bool or per-tier sequence of bools — compile the round variant where
        tier m's fed-server level is applied iff ``fed_round[m]``. The
        production dispatch picks the variant ``tuple((t+1) % I_m == 0)``
        per round — at most 2^(M-1) compiled steps, typically 2-3 since
        optimal intervals nest (paper's Insight after Eq. 37).
    Specializing step functions instead of branching in-graph is the
    production path (see EXPERIMENTS.md sect. Perf).

    Each tier is sliced out of the tree at its first level of the round
    and written back in place at its last (``_put_tier``): a stacked unit
    leaf is updated over the tier's unit range, so no other tier is
    copied, and a tier with no level this round is neither read nor
    written.  One write per tier, not per level, leaves XLA free to fold
    a level into the next (the all-client mean of a broadcast mean).  The
    result is bit-identical to splitting the tree with ``tier_subtrees``
    and merging it with ``combine_tiers``.

    ``compress_fn`` (leaf → leaf, e.g. a vmapped ``Compressor.transform``)
    models the lossy fed-server wire of DESIGN.md §9: it is applied to the
    uploaded replicas immediately before the *fed-server* mean of tiers
    m < M−1 with more than one entity — exactly the exchanges the latency
    model prices with ``model_ratio`` — and never to the unpriced local
    entity syncs (Eq. 3) or the single-entity top tier.

    ``mask`` ([N] bool/float, 1 = the client participated this round)
    switches every level to the participation-weighted mean of
    ``_group_mean_masked`` (DESIGN.md §12): participants are averaged
    with weight 1/|group participants|, the aggregate is broadcast to all
    members, and a zero-participant group keeps its last synced params.
    ``mask=None`` is the exact full-participation path (and an all-ones
    mask is bit-identical to it, pinned in ``tests/test_participation.py``).

    ``guard`` (a ``GuardSpec``) turns on the corrupt-upload quarantine of
    DESIGN.md §16: client health (finite check + norm blow-up) is computed
    once on the incoming tree, non-finite rows are sanitized to zero, and
    the health mask multiplies into ``mask`` — an unhealthy client becomes
    a zero-participant (§12 semantics: excluded from every mean, healed by
    the participating group's broadcast).  On an all-healthy round the
    sanitized tree is bit-identical to the input and the health mask is
    all-ones, so the result collapses bit-for-bit onto the unguarded path.

    ``client_axes`` names the mesh axes the client axis is sharded over
    when this runs inside ``shard_map`` (DESIGN.md §17); ``params`` and
    ``mask`` are then this shard's rows.  Per level, groups that each lie
    on one shard (``groups`` divisible by the shard count) take the
    one-host mean on the shard's ``groups // shards`` groups; groups that
    span shards take ``_matmul_group_mean``'s einsum + ``psum``.  Empty
    (one host) is the plain path.

    ``core.async_agg.fed_level_apply`` replays a single tier's deferred
    fed-server level from a snapshot — deliberately NOT by re-invoking
    ``synchronize``, because the group mean is not bit-idempotent.
    """
    shards = math.prod(lax.axis_size(a) for a in client_axes)
    if guard is not None:
        health, params = guard_health(
            params, plan.num_clients // shards, guard, client_axes
        )
        mask = health if mask is None else mask.astype(jnp.float32) * health
    if fed_round is not None and not isinstance(fed_round, (tuple, list)):
        fed_round = (bool(fed_round),) * plan.M
    out = params
    for m in range(plan.M):
        levels = plan.levels(m)
        # levels this round: fed_round[m] False skips tier m's fed-server level
        run = [
            li for li, (_, interval) in enumerate(levels)
            if interval <= 1 or fed_round is None or fed_round[m]
        ]
        for li in run:
            groups, interval = levels[li]
            # the fed-server level is the last one of a non-top tier; it is
            # a priced wire only when several entities actually exchange.
            fed = (
                compress_fn is not None
                and m < plan.M - 1
                and li == len(levels) - 1
                and plan.entities[m] > 1
            )

            def level_mean(p, groups=groups, fed=fed):
                # keep the *pre-compression* tree as the zero-participant
                # fallback: a silent group uploads nothing, so it must
                # retain its last synced params, not a lossy-coded copy.
                original = p
                if fed:
                    p = jax.tree.map(compress_fn, p)
                if groups % shards:
                    return _matmul_group_mean(
                        p, groups, plan.num_clients, client_axes, mask,
                        keep=original,
                    )
                if mask is not None:
                    return _group_mean_masked(
                        p, groups // shards, mask, keep=original
                    )
                return _group_mean(p, groups // shards)

            with obs.scope(obs.sync_level(m, li, len(levels))):
                if li == run[0]:
                    part = _tier_part(out, plan, m)
                if interval > 1 and fed_round is None:
                    do = (step + 1) % interval == 0
                    part = lax.cond(do, level_mean, lambda p: p, part)
                else:
                    part = level_mean(part)
                if li == run[-1]:
                    out = _put_tier(out, part, plan, m)
    return out


# --------------------------------------------------------------------------- #
# ragged synchronization: per-class cut assignments (DESIGN.md §14)
# --------------------------------------------------------------------------- #


def class_tier_members(
    n_units: int,
    class_cuts: Sequence[Sequence[int]],
    class_of: Sequence[int],
) -> List[jnp.ndarray]:
    """Per-tier membership matrices ``[M][N, U]`` (float32 0/1).

    ``members[m][i, u] == 1`` iff unit u lies in tier m *for client i's
    class* — clients in different classes disagree on which units are
    client-side, which is exactly the raggedness ``ragged_synchronize``
    aggregates over.  Every (client, unit) pair belongs to exactly one
    tier, so the per-tier member matrices partition the unit axis per
    client.
    """
    class_of = [int(c) for c in class_of]
    M = len(class_cuts[0]) + 1
    C = len(class_cuts)
    bounds = [[0, *[int(x) for x in cc], n_units] for cc in class_cuts]
    u = jnp.arange(n_units)
    out: List[jnp.ndarray] = []
    for m in range(M):
        rows = []
        for c in range(C):
            lo, hi = bounds[c][m], bounds[c][m + 1]
            rows.append(((u >= lo) & (u < hi)).astype(jnp.float32))
        table = jnp.stack(rows)  # [C, U]
        out.append(table[jnp.asarray(class_of)])  # [N, U]
    return out


def _ragged_units_mean(units, keep, mem, groups, mask):
    """Per-unit member-weighted group mean over a units container.

    ``mem`` [N, U] gates both the average (a unit's tier-m mean only
    reads replicas from clients whose class holds it in tier m) and the
    receive side (non-members keep their value — that unit is synced by
    its own tier's levels).  With ``mem`` all-ones the arithmetic
    (f32 multiply-by-weight, same sum reduction, divide by
    ``max(count, 1)``) is bit-identical to ``_group_mean_masked`` — and
    through it to ``_group_mean`` when ``mask`` is None — which is what
    collapses identical-class ragged sync onto ``synchronize`` exactly.
    """
    cw = mem if mask is None else mem * mask.astype(jnp.float32)[:, None]

    def one_unit(x, k, m_col, w_col):
        # x, k: [N, ...]; m_col/w_col: [N]
        n = x.shape[0]
        g = x.reshape(groups, n // groups, *x.shape[1:])
        gk = k.reshape(g.shape)
        wg = w_col.reshape(groups, n // groups)
        mg = m_col.reshape(groups, n // groups)
        ww = wg.reshape(wg.shape + (1,) * (g.ndim - 2))
        mm = mg.reshape(ww.shape)
        s = jnp.sum(wg, axis=1).reshape((groups,) + (1,) * (g.ndim - 1))
        tot = jnp.sum(
            g * ww.astype(g.dtype), axis=1, keepdims=True, dtype=jnp.float32
        )
        mean = (tot / jnp.maximum(s, 1.0)).astype(x.dtype)
        out = jnp.where(
            (mm > 0.0) & (s > 0.0), jnp.broadcast_to(mean, g.shape), gk
        )
        return out.reshape(x.shape)

    if isinstance(units, (list, tuple)):
        return [
            jax.tree.map(
                lambda x, k, u=u: one_unit(x, k, mem[:, u], cw[:, u]),
                unit,
                keep[u],
            )
            for u, unit in enumerate(units)
        ]
    if isinstance(units, dict) and set(units) == {"enc", "dec"}:
        raise NotImplementedError(
            "ragged per-class sync over enc/dec unit stacks is not "
            "implemented — use a flat unit stack or per-unit list"
        )

    # stacked leaves [N, U, ...]: broadcast the member/weight columns
    def f(x, k):
        n, U = x.shape[0], x.shape[1]
        g = x.reshape(groups, n // groups, U, *x.shape[2:])
        gk = k.reshape(g.shape)
        wg = cw.reshape(groups, n // groups, U)
        mg = mem.reshape(groups, n // groups, U)
        ww = wg.reshape(wg.shape + (1,) * (g.ndim - 3))
        mm = mg.reshape(ww.shape)
        s = jnp.sum(ww, axis=1, keepdims=True)  # [G, 1, U, 1...]
        tot = jnp.sum(
            g * ww.astype(g.dtype), axis=1, keepdims=True, dtype=jnp.float32
        )
        mean = (tot / jnp.maximum(s, 1.0)).astype(x.dtype)
        out = jnp.where(
            (mm > 0.0) & (s > 0.0), jnp.broadcast_to(mean, g.shape), gk
        )
        return out.reshape(x.shape)

    return jax.tree.map(f, units, keep)


def ragged_synchronize(
    params: Params,
    plan: TierPlan,
    members: Sequence[jax.Array],
    step: jax.Array,
    *,
    fed_round=None,
    compress_fn=None,
    mask=None,
    guard: Optional[GuardSpec] = None,
) -> Params:
    """``synchronize`` for per-class cut assignments (DESIGN.md §14).

    ``members`` is the ``class_tier_members`` output: tier m's levels
    average unit u only over the clients whose class holds u in tier m,
    and only those clients receive the broadcast — the rest keep their
    replica untouched for their own tier's schedule.  The entity topology,
    interval gating, ``fed_round`` specialization, fed-wire compression
    and participation ``mask`` semantics are exactly those of
    ``synchronize`` (including the zero-participant keep-last fallback
    and the pre-compression ``keep`` tree).  The frontend always joins
    tier 0 and the head tier M−1, for every class.

    Unlike ``synchronize`` this operates on the *unsliced* params: the
    unit → tier map varies per client, so there is no common
    ``tier_subtrees`` partition to slice.  When every class holds the
    same cuts the member matrices are exactly the plan's tier slices and
    the result is bit-identical to ``synchronize``.

    ``guard`` applies the same quarantine as ``synchronize``: health is
    computed once on the unsliced tree and folded into ``mask``.
    """
    if guard is not None:
        health, params = guard_health(params, plan.num_clients, guard)
        mask = health if mask is None else mask.astype(jnp.float32) * health
    if isinstance(params["units"], dict) and set(params["units"]) == {
        "enc",
        "dec",
    }:
        raise NotImplementedError(
            "ragged per-class sync over enc/dec unit stacks is not "
            "implemented"
        )
    if len(members) != plan.M:
        raise ValueError(
            f"need one member matrix per tier: got {len(members)} for "
            f"M={plan.M}"
        )
    if fed_round is not None and not isinstance(fed_round, (tuple, list)):
        fed_round = (bool(fed_round),) * plan.M

    out = params
    for m in range(plan.M):
        mem = members[m]
        levels = plan.levels(m)
        for li, (groups, interval) in enumerate(levels):
            fed = (
                compress_fn is not None
                and m < plan.M - 1
                and li == len(levels) - 1
                and plan.entities[m] > 1
            )

            def level_fn(
                p,
                groups=groups,
                fed=fed,
                mem=mem,
                front=(m == 0),
                head=(m == plan.M - 1),
            ):
                original = p
                if fed:
                    p = jax.tree.map(compress_fn, p)
                new = dict(original)
                new["units"] = _ragged_units_mean(
                    p["units"], original["units"], mem, groups, mask
                )
                for name, join in (("frontend", front), ("head", head)):
                    if not join:
                        continue
                    if mask is not None:
                        new[name] = _group_mean_masked(
                            p[name], groups, mask, keep=original[name]
                        )
                    else:
                        new[name] = _group_mean(p[name], groups)
                return new

            if interval <= 1:
                out = level_fn(out)
            elif fed_round is None:
                do = (step + 1) % interval == 0
                out = lax.cond(do, level_fn, lambda p: p, out)
            elif fed_round[m]:
                out = level_fn(out)
    return out


def default_plan(
    n_units: int,
    num_clients: int = 16,
    cuts: Tuple[int, ...] = None,
    intervals: Tuple[int, ...] = None,
    entities: Tuple[int, ...] = None,
    num_pods: int = 1,
    pod_interval: int = 0,
) -> TierPlan:
    """Paper-style 3-tier client-edge-cloud plan with sensible defaults."""
    if cuts is None:
        c1 = max(1, n_units // 5)
        c2 = max(c1, n_units // 2)
        cuts = (c1, c2)
    if intervals is None:
        intervals = (8, 4, 1)
    if entities is None:
        entities = (num_clients, max(1, num_clients // 4), 1)
    return TierPlan(
        n_units=n_units,
        num_clients=num_clients,
        cuts=tuple(cuts),
        intervals=tuple(intervals),
        entities=tuple(entities),
        num_pods=num_pods,
        pod_interval=pod_interval,
    )
