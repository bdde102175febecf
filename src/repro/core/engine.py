"""HSFL execution engines.

Engine A ("sync-groups", production): every tier's parameters are stacked
per-client on axis 0. The hierarchy is realized purely as the
multi-timescale aggregation schedule of ``tiers.synchronize`` —
memory-balanced and collective-efficient on TPU. One step serves one chip
and a mesh: given a mesh, the same step runs under ``shard_map`` with the
client axis sharded over the ``data`` (and ``pod``) axes (DESIGN.md §17).

Engine B ("split-placement", reference): tier-1 params stacked per client,
tier-2 per entity, tier-3 single — the literal SFL dataflow where activations
physically move client → entity → cloud. Used to prove Engine A's math and to
ground the latency model's activation-transfer terms.

Both engines implement Algorithm 1 of the paper exactly (per-client SGD on
replicas + Eq. 3 entity sync + Eq. 4 fed-server aggregation at I_m).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..optim import Optimizer
from .tiers import (
    GuardSpec,
    TierPlan,
    combine_tiers,
    guard_health,
    ragged_synchronize,
    synchronize,
    tier_subtrees,
)

Params = Dict[str, Any]


@dataclass
class TrainState:
    params: Params
    opt_state: Any
    step: jax.Array

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.params, s.opt_state, s.step), None),
    lambda aux, ch: TrainState(*ch),
)


def replicate_for_clients(params: Params, num_clients: int) -> Params:
    """Broadcast a single-model pytree to the client-stacked layout."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_clients,) + x.shape), params
    )


def unreplicate(params: Params) -> Params:
    return jax.tree.map(lambda x: x[0], params)


# --------------------------------------------------------------------------- #
# Engine A — sync groups
# --------------------------------------------------------------------------- #


def _client_shards(plan: TierPlan, mesh, client_axes) -> int:
    """Shards of the client axis over ``client_axes``; N must divide."""
    D = math.prod(mesh.shape[a] for a in client_axes)
    if plan.num_clients % D != 0:
        raise ValueError(
            f"num_clients={plan.num_clients} must divide over the {D} "
            f"client shards of mesh axes {tuple(client_axes)!r}"
        )
    return D


def init_state_a(
    model, plan: TierPlan, opt: Optimizer, key, mesh=None,
    client_axes=("data",),
) -> TrainState:
    """Client-stacked initial state.  With a ``mesh`` the same state is
    placed with its client axis sharded over ``client_axes``
    (``launch.sharding.train_pspecs``): same key, same replicas."""
    p0 = model.init_params(key)
    params = replicate_for_clients(p0, plan.num_clients)
    state = TrainState(params=params, opt_state=opt.init(params), step=jnp.zeros((), jnp.int32))
    if mesh is None:
        return state
    from ..launch.sharding import train_pspecs

    _client_shards(plan, mesh, client_axes)
    specs = train_pspecs(state, tuple(client_axes), plan.num_clients)
    return jax.tree.map(
        lambda x, ps: jax.device_put(x, NamedSharding(mesh, ps)), state, specs
    )


def _masked_select(new, old, w: jax.Array):
    """Per-client select: participants take the updated leaf, absentees keep
    the old one.  Only client-stacked leaves (leading axis N) are masked;
    scalar bookkeeping leaves (e.g. adam's step counter) pass through."""

    def f(n, o):
        if n.ndim == 0 or n.shape[0] != w.shape[0]:
            return n
        return jnp.where(
            w.reshape((-1,) + (1,) * (n.ndim - 1)) > 0.0, n, o
        )

    return jax.tree.map(f, new, old)


def masked_mean_loss(
    losses: jax.Array, w: jax.Array, client_axes: Tuple[str, ...] = ()
) -> jax.Array:
    """Participation-weighted round loss Σ w_i·loss_i / Σ w_i (0.0 for a
    zero-participant round — the round is a no-op, DESIGN.md §12).
    Under ``shard_map`` both sums run over the ``client_axes`` too."""
    fleet = (lambda x: lax.psum(x, client_axes)) if client_axes else (lambda x: x)
    total = fleet(jnp.sum(w))
    return jnp.where(
        total > 0.0, fleet(jnp.sum(losses * w)) / jnp.maximum(total, 1.0), 0.0
    )


def build_train_step_a(
    model, plan: TierPlan, opt: Optimizer, *, sync_opt_state: bool = False,
    fed_round=None, compressor=None, with_mask: bool = False,
    class_members=None, privacy=None, guard: Optional[GuardSpec] = None,
    with_sync_weights: bool = False, mesh=None, client_axes=("data",),
) -> Callable[..., Tuple[TrainState, jax.Array]]:
    """Engine-A step: vmapped per-client update + hierarchical aggregation.

    batch leaves have a leading client axis [N, b, ...].

    ``fed_round``: None compiles one step with an in-graph ``lax.cond`` on
    the round counter; False/True compile the specialized local/sync round
    steps (see ``tiers.synchronize``) — the production dispatch is
    ``sync_step if (t+1) % I == 0 else local_step``.

    ``compressor`` (a ``repro.compress.Compressor``) puts the fed-server
    model exchange on a lossy wire: each client's uploaded replica goes
    through ``compressor.transform`` before the Eq. 4 mean — the same
    transform Engine B applies per entity, so the two engines stay equal
    (``tests/test_engines_equal.py``).  Optimizer moments are synchronized
    full-precision; only the priced parameter wire is compressed.

    The engines run the codec *key-less*, i.e. deterministic nearest
    rounding: reproducible and what the equality tests pin, with error
    second moment still ≤ the codec's ω, but not unbiased — Theorem 1's
    (1+ω) variance reading is exact only for the keyed stochastic mode,
    so empirical bound checks over this path are conservative heuristics
    (see ``benchmarks/compress_sweep.py``).

    ``with_mask=True`` returns ``step(state, batch, mask)`` instead: the
    [N] participation mask (1 = the client made the round's deadline)
    restricts the local update to participants — absentees keep their
    params and optimizer moments untouched — and every aggregation level
    averages participants only (``tiers.synchronize`` mask semantics,
    DESIGN.md §12).  The reported loss is the participation-weighted mean.
    An all-ones mask is bit-identical to the unmasked step.

    ``class_members`` (the ``tiers.class_tier_members`` matrices for a
    per-class cut assignment, DESIGN.md §14) switches every aggregation —
    params and, under ``sync_opt_state``, the optimizer moments — to
    ``tiers.ragged_synchronize``: tier m's levels average each unit only
    over the clients whose class holds it there.  With identical classes
    the member matrices are the plan's tier slices and the step is
    bit-identical to the dense path.

    ``privacy`` (a ``repro.privacy.DPMechanism``) puts the *same* fed-server
    params wire under client-level DP: each uploaded replica is per-client
    L2-clipped and Gaussian-noised *before* the codec sees it (noise under
    compression would let the codec shave noise the accountant already
    charged for — the composition order is fixed here, not configurable)
    and before the Eq. 4 mean.  Keys fold (seed, leaf, step) so every leaf
    of every round draws independent noise.  Optimizer-moment syncs and
    local entity syncs stay untouched — only the wire the (ε, δ) accountant
    meters is noised.  ``build()`` constructs no mechanism at
    ``noise_multiplier=0``, so the noiseless graph is bit-identical.

    ``guard`` (a ``tiers.GuardSpec``) arms fault tolerance (DESIGN.md §16):
    each step quarantines clients whose update is non-finite or a norm
    blow-up — their local update rolls back and every aggregation runs the
    guarded masked path, which sanitizes corrupt replicas before any
    arithmetic and heals them with the group broadcast at zero weight.
    ``guard=None`` (default) is byte-identical to today's graph, and an
    armed guard over an all-healthy round collapses bit-for-bit to the
    unguarded step (``tests/test_faults.py``).

    ``with_sync_weights=True`` makes the step additionally return the
    effective per-client sync weights [N] (participation mask × guard
    health × finite-loss; all-ones when neither masking nor a guard is
    armed) — the exact weights every aggregation level used this round.
    The async bounded-staleness runner (``core.async_agg``) captures
    these at snapshot time so a deferred fed-server apply weights clients
    identically to the in-step levels; re-deriving health at apply time
    would quarantine a different set.

    ``mesh`` runs the same step under ``jax.shard_map`` (DESIGN.md §17):
    the client axis of the state, the batch, the mask and the sync
    weights is sharded over ``client_axes`` (``launch.sharding``'s
    ``train_pspecs`` / ``batch_pspecs``; the state comes from
    ``init_state_a(..., mesh=mesh)``), the round loss is ``psum``-reduced
    and replicated, and ``tiers.synchronize`` lowers each level that
    spans shards to an einsum + ``psum``.  The caller jits the returned
    step either way.  ``privacy`` and ``class_members`` have no mesh
    lowering.
    """
    N = plan.num_clients  # the clients one program holds: all, or a shard's
    ca = ()
    if mesh is not None:
        if privacy is not None:
            raise ValueError(
                "unsupported spec combination: sharding × privacy requires "
                "privacy=None — DP noise keys fold (seed, leaf, step), so "
                "the draw cannot be reproduced bit-exactly across shard "
                "layouts (DESIGN.md §15/§17)"
            )
        if class_members is not None:
            raise ValueError(
                "unsupported spec combination: sharding × classes requires "
                "class_members=None — the ragged per-class sync has no "
                "sharded collective lowering yet (DESIGN.md §14/§17)"
            )
        ca = tuple(client_axes)
        N //= _client_shards(plan, mesh, ca)
    compress_fn = (
        None if compressor is None
        else lambda x: jax.vmap(lambda v: compressor.transform(v))(x)
    )

    def _fed_wire(step):
        # per-step fed-upload transform: DP (clip + noise) then codec.
        if privacy is None:
            return compress_fn
        salt = iter(range(1_000_000))  # trace-time leaf counter

        def fn(x):
            y = privacy.transform(x, step, salt=next(salt))
            return y if compress_fn is None else compress_fn(y)

        return fn

    def _sync(tree, step, *, compress=None, mask=None, guarded=False):
        g = guard if guarded else None
        if class_members is not None:
            return ragged_synchronize(
                tree, plan, class_members, step, fed_round=fed_round,
                compress_fn=compress, mask=mask, guard=g,
            )
        return synchronize(
            tree, plan, step, fed_round=fed_round, compress_fn=compress,
            mask=mask, guard=g, client_axes=ca,
        )

    def _mean_loss(losses):
        if ca:
            return lax.psum(jnp.sum(losses), ca) / plan.num_clients
        return jnp.mean(losses)

    def _step(state: TrainState, batch: Params, mask) -> Tuple[TrainState, jax.Array]:
        with obs.scope(obs.GRAD):
            losses, grads = jax.vmap(jax.value_and_grad(model.loss_fn))(
                state.params, batch
            )
        with obs.scope(obs.OPT):
            new_params, new_opt = opt.update(state.params, grads, state.opt_state)
        if guard is not None:
            # Guarded step (DESIGN.md §16): quarantine clients whose update
            # went non-finite or blew up in norm.  Their local update is
            # rolled back (they keep pre-step params/moments, possibly still
            # corrupt — the guarded syncs below sanitize and heal them with
            # the group broadcast at zero weight), and the reported loss is
            # the health-weighted mean over finite losses only — every
            # arithmetic op here sees sanitized values, so a healthy round
            # runs clean under JAX_DEBUG_NANS.
            health, _ = guard_health(new_params, N, guard, ca)
            lfin = jnp.isfinite(losses)
            health = health * lfin.astype(jnp.float32)
            w = (
                health if mask is None
                else mask.astype(jnp.float32) * health
            )
            new_params = _masked_select(new_params, state.params, w)
            new_opt = _masked_select(new_opt, state.opt_state, w)
            lsafe = jnp.where(lfin, losses, 0.0)
            loss = masked_mean_loss(lsafe, w, ca)
            if mask is None:
                # all-healthy unmasked rounds must report the exact plain
                # mean (bit-for-bit zero-fault collapse); lsafe == losses
                # there, so this stays NaN-free under JAX_DEBUG_NANS
                all_healthy = (
                    lax.psum(jnp.sum(w >= 1.0), ca) >= plan.num_clients
                    if ca else jnp.all(w >= 1.0)
                )
                loss = jnp.where(all_healthy, _mean_loss(lsafe), loss)
            sync_mask = w
        elif mask is None:
            loss = _mean_loss(losses)
            sync_mask = None
        else:
            w = mask.astype(jnp.float32)
            new_params = _masked_select(new_params, state.params, w)
            new_opt = _masked_select(new_opt, state.opt_state, w)
            loss = masked_mean_loss(losses, w, ca)
            sync_mask = mask
        new_params = _sync(
            new_params, state.step, compress=_fed_wire(state.step),
            mask=sync_mask, guarded=True,
        )
        if sync_opt_state and jax.tree.leaves(new_opt):
            new_opt = jax.tree.map(
                lambda x: x, new_opt
            )  # structure-preserving no-op; moments follow params below
            # momentum/adam moments are client-stacked like params: apply the
            # same schedule so replicas stay consistent after aggregation.
            if opt.name == "momentum":
                new_opt = _sync(
                    new_opt, state.step, mask=sync_mask, guarded=True
                )
            elif opt.name == "adam":
                new_opt = dict(new_opt)
                new_opt["m"] = _sync(
                    new_opt["m"], state.step, mask=sync_mask, guarded=True
                )
                new_opt["v"] = _sync(
                    new_opt["v"], state.step, mask=sync_mask, guarded=True
                )
        new_state = TrainState(new_params, new_opt, state.step + 1)
        if with_sync_weights:
            ww = (
                jnp.ones((N,), jnp.float32)
                if sync_mask is None else sync_mask.astype(jnp.float32)
            )
            return new_state, loss, ww
        return new_state, loss

    step = _step if with_mask else (lambda state, batch: _step(state, batch, None))
    if mesh is None:
        return step
    from ..launch.sharding import batch_pspecs, train_pspecs

    rows = P(ca if len(ca) > 1 else ca[0])

    def sharded_step(state: TrainState, batch: Params, *mask):
        state_specs = train_pspecs(state, ca, plan.num_clients)
        out_specs = (state_specs, P()) + ((rows,) if with_sync_weights else ())
        return jax.shard_map(
            step, mesh=mesh,
            in_specs=(state_specs, batch_pspecs(batch, ca)) + (rows,) * len(mask),
            out_specs=out_specs, check_vma=False,
        )(state, batch, *mask)

    return sharded_step


# --------------------------------------------------------------------------- #
# Engine B — split placement (reference)
# --------------------------------------------------------------------------- #


def init_state_b(model, plan: TierPlan, opt: Optimizer, key) -> TrainState:
    """Params: list of per-tier pytrees; tier m stacked over J_m entities."""
    p0 = model.init_params(key)
    full = replicate_for_clients(p0, plan.num_clients)
    parts = tier_subtrees(full, plan)
    tier_params = []
    for m, part in enumerate(parts):
        J = plan.entities[m]
        per = plan.num_clients // J
        tier_params.append(jax.tree.map(lambda x: x[::per], part))  # [J_m, ...]
    return TrainState(
        params=tier_params,
        opt_state=opt.init(tier_params),
        step=jnp.zeros((), jnp.int32),
    )


def build_train_step_b(
    model, plan: TierPlan, opt: Optimizer, *, compressor=None,
    with_mask: bool = False, class_members=None, privacy=None,
) -> Callable[..., Tuple[TrainState, jax.Array]]:
    """Engine-B step: literal split execution.

    Forward: tier-1 vmapped over N clients; activations regrouped into J_2
    entity batches; ... up to the single tier-M model over the global batch.
    Backward: one value_and_grad through the composed function; per-tier
    gradients rescaled to implement per-client SGD + Eq. 3 exactly.

    ``compressor`` compresses each entity's model upload before the Eq. 4
    fed-server mean — the literal wire the latency model prices with
    ``model_ratio`` (DESIGN.md §9).

    ``with_mask=True`` returns ``step(state, batch, mask)``: the global
    objective becomes the participation-weighted mean Σ w_i·loss_i / Σ w_i
    (per-client losses, so clients weight exactly as in Engine A), each
    tier-m entity's gradient is rescaled by Σw / Σ_{i∈j} w_i — the mean
    over its *participating* clients' gradients, zero for a
    zero-participant entity, whose sub-model therefore keeps its last
    synced params — and the Eq. 4 fed-server mean weights entities by
    their participant counts.  This mirrors ``tiers.synchronize``'s mask
    semantics, so A == B extends to partial rounds
    (``tests/test_engines_equal.py``).  MoE specs are not supported here:
    the aux-loss regrouping means are unweighted, so a masked MoE round
    would diverge from Engine A.
    """
    N = plan.num_clients
    M = plan.M
    spec = model.spec
    if class_members is not None:
        raise NotImplementedError(
            "Engine B physically places each tier's units on its hosts — a "
            "per-class cut assignment has no single placement (clients "
            "disagree on which units are client-side).  Use Engine A with "
            "class_members (ragged sync-groups), the production path for "
            "DESIGN.md §14."
        )
    if privacy is not None:
        raise NotImplementedError(
            "Engine B does not support DP-noised uploads: its fed wire "
            "carries one model per *entity*, so per-client clipping (the "
            "unit the (ε, δ) accountant meters) has no faithful placement. "
            "Use Engine A with privacy (the production DP path), or run "
            "Engine B noiseless (privacy=None)."
        )
    if with_mask and getattr(spec, "moe", None) is not None:
        raise NotImplementedError(
            "masked Engine B does not support MoE specs: the aux-loss "
            "regroup means are participation-unweighted (use Engine A for "
            "masked MoE training)"
        )

    def global_loss(tier_params, batch, w=None):
        # ---- tier 1 on each client ----
        def t1(p, b):
            carry = model.frontend_apply(p["frontend"], b)
            lo, hi = plan.tier_bounds(0)
            prefix = spec.prefix_len if spec.family == "vlm" else 0
            return model.apply_units(p["units"], carry, 0, hi - lo, prefix_len=prefix)

        # MoE capacity semantics: a server hosting several clients' tokens
        # must dispatch with per-client groups, or pooled tokens compete for
        # expert slots and the split execution diverges from per-client SFL
        # (Eq. 2/3 operate per client). moe_groups = co-located clients.
        if hasattr(model, "moe_groups"):
            model.moe_groups = 1  # t1 is vmapped per client
        carry = jax.vmap(t1)(tier_params[0], batch)  # leaves [N, b, ...]

        # ---- middle tiers on entity-regrouped activations ----
        for m in range(1, M - 1):
            J = plan.entities[m]
            per = N // J

            def regroup(x):
                return x.reshape(J, per * x.shape[1], *x.shape[2:])

            def split_back(x):
                return x.reshape(N, x.shape[1] // per, *x.shape[2:])

            carry_e = jax.tree.map(
                lambda x: regroup(x) if x.ndim >= 2 else x.reshape(J, per).mean(1),
                carry,
            )
            lo, hi = plan.tier_bounds(m)

            def tm(p, c):
                # p["units"] is pre-sliced to this tier -> local indices
                prefix = spec.prefix_len if spec.family == "vlm" else 0
                return model.apply_units(p["units"], c, 0, hi - lo, prefix_len=prefix)

            if hasattr(model, "moe_groups"):
                model.moe_groups = per  # entity batch pools `per` clients
            carry_e = jax.vmap(tm)(tier_params[m], carry_e)
            # scalars (the moe aux) carry *means*: regroup averages over an
            # entity's clients, so split_back replicates the mean back to
            # each client unchanged (a /per here would shrink aux per tier).
            carry = jax.tree.map(
                lambda x: split_back(x) if x.ndim >= 2 else jnp.repeat(x, per),
                carry_e,
            )

        # ---- top tier on the concatenated global batch ----
        def flatten(x):
            return x.reshape(N * x.shape[1], *x.shape[2:])

        carry_g = jax.tree.map(
            lambda x: flatten(x) if x.ndim >= 2 else x.mean() * N, carry
        )
        lo, hi = plan.tier_bounds(M - 1)
        pM = jax.tree.map(lambda x: x[0], tier_params[M - 1])
        prefix = spec.prefix_len if spec.family == "vlm" else 0
        if hasattr(model, "moe_groups"):
            model.moe_groups = N  # cloud batch pools all N clients
        aux_pre = carry_g.get("aux", jnp.zeros((), jnp.float32))
        carry_g = model.apply_units(pM["units"], carry_g, 0, hi - lo, prefix_len=prefix)
        if hasattr(model, "moe_groups"):
            model.moe_groups = 1  # restore
        from ..models import layers as L

        if spec.tie_embeddings:
            # tied unembedding weights live on tier 1 (per client)
            h = L.rms_norm(carry_g["h"], pM["head"]["norm"], spec.norm_eps)
            b_sz = h.shape[0] // N
            hn = h.reshape(N, b_sz, *h.shape[1:])
            emb = tier_params[0]["frontend"]["embed"]  # [N, V, d]
            logits = jnp.einsum("nbsd,nvd->nbsv", hn, emb.astype(hn.dtype))
            logits = logits.reshape(h.shape[0], h.shape[1], -1)
        else:
            logits = model.head_apply(
                {"head": pM["head"], "frontend": None}, carry_g
            )
        labels = batch["labels"].reshape(-1, batch["labels"].shape[-1])
        if spec.family == "vlm":
            logits = logits[:, spec.prefix_len :]
        lmask = (labels >= 0).astype(jnp.float32)
        if w is None:
            loss = L.cross_entropy(logits, jnp.maximum(labels, 0), lmask)
        else:
            # per-client CE then participation-weighted mean: clients enter
            # the objective exactly as Engine A's vmapped loss_fn does.
            lg = logits.reshape(N, -1, *logits.shape[1:])
            lb = labels.reshape(N, -1, *labels.shape[1:])
            lm = lmask.reshape(N, -1, *lmask.shape[1:])
            per_client = jax.vmap(
                lambda lo, la, mk: L.cross_entropy(lo, jnp.maximum(la, 0), mk)
            )(lg, lb, lm)
            return masked_mean_loss(per_client, w)
        if spec.moe is not None:
            # aux bookkeeping: pre-flatten aux arrives scaled by N (the
            # scalar flatten is x.mean()*N), so divide it back; the top
            # tier's own aux (post - pre) is shared by every client in
            # Engine A and enters at full weight.
            aux_top = carry_g["aux"] - aux_pre
            loss = loss + 0.01 * (aux_pre / N + aux_top)
        return loss

    def _step(state: TrainState, batch: Params, mask) -> Tuple[TrainState, jax.Array]:
        w = None if mask is None else mask.astype(jnp.float32)
        loss, grads = jax.value_and_grad(global_loss)(state.params, batch, w)
        # per-client SGD semantics: tier m's shared entity model moves by the
        # *mean of its clients' gradients* = (N / N_m^j) * dL/dw_m  (see
        # DESIGN); under a mask the mean runs over the entity's participants
        # only — scale Σw / Σ_{i∈j} w_i, zero for a zero-participant entity.
        scaled = []
        for m, g in enumerate(grads):
            J = plan.entities[m]
            if w is None:
                scaled.append(jax.tree.map(lambda x, J=J: x * J, g))
            else:
                wj = w.reshape(J, N // J).sum(axis=1)  # [J] participant counts
                sc = jnp.where(wj > 0.0, jnp.sum(w) / jnp.maximum(wj, 1.0), 0.0)
                scaled.append(
                    jax.tree.map(
                        lambda x, sc=sc, J=J: x
                        * sc.reshape((J,) + (1,) * (x.ndim - 1)).astype(x.dtype),
                        g,
                    )
                )
        new_params, new_opt = opt.update(state.params, scaled, state.opt_state)
        # Eq. 4 fed-server aggregation across entities at I_m
        out = []
        for m, p in enumerate(new_params):
            interval = int(plan.intervals[m])
            if plan.entities[m] > 1 and interval >= 1:
                do = (state.step + 1) % interval == 0
                J = plan.entities[m]

                def agg(t, J=J):
                    original = t  # zero-participant fallback must be the
                    # entities' last synced params, never a compressed copy
                    if compressor is not None:
                        # lossy fed-server upload, per entity (axis 0)
                        t = jax.tree.map(
                            lambda x: jax.vmap(
                                lambda v: compressor.transform(v)
                            )(x),
                            t,
                        )
                    if w is None:
                        return jax.tree.map(
                            lambda x: jnp.broadcast_to(
                                jnp.mean(x, 0, keepdims=True), x.shape
                            ),
                            t,
                        )
                    # entities weighted by participant count — the same
                    # hierarchical weighting tiers.synchronize applies in
                    # Engine A; a zero-participant *round* leaves every
                    # entity at its last synced params.
                    wj = w.reshape(J, N // J).sum(axis=1)
                    s = jnp.sum(wj)

                    def wm(x, k):
                        ww = wj.reshape((J,) + (1,) * (x.ndim - 1))
                        tot = jnp.sum(
                            x * ww.astype(x.dtype), axis=0, keepdims=True,
                            dtype=jnp.float32,
                        )
                        mn = (tot / jnp.maximum(s, 1.0)).astype(x.dtype)
                        return jnp.where(
                            s > 0.0, jnp.broadcast_to(mn, x.shape), k
                        )

                    return jax.tree.map(wm, t, original)

                p = lax.cond(do, agg, lambda t: t, p)
            out.append(p)
        return TrainState(out, new_opt, state.step + 1), loss

    if with_mask:
        return _step
    return lambda state, batch: _step(state, batch, None)


def engine_b_to_full(model, plan: TierPlan, tier_params) -> Params:
    """Materialize Engine-B tier params back into a client-stacked pytree."""
    parts = []
    for m, p in enumerate(tier_params):
        J = plan.entities[m]
        per = plan.num_clients // J
        parts.append(jax.tree.map(lambda x: jnp.repeat(x, per, axis=0), p))
    template = {
        "units": parts[0]["units"],
        "frontend": parts[0]["frontend"],
        "head": parts[-1]["head"],
    }
    return combine_tiers(parts, template)
