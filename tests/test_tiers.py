"""TierPlan + synchronize: the HSFL aggregation schedule (Eqs. 3-4)."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.core.tiers import (
    GuardSpec,
    TierPlan,
    _group_mean,
    _group_mean_masked,
    combine_tiers,
    default_plan,
    guard_health,
    synchronize,
    tier_subtrees,
)


def _params(key, N, U, d=4):
    ks = jax.random.split(key, 3)
    return {
        "frontend": {"embed": jax.random.normal(ks[0], (N, 8, d))},
        "units": {"w": jax.random.normal(ks[1], (N, U, d, d))},
        "head": {"norm": jax.random.normal(ks[2], (N, d))},
    }


def test_plan_validation():
    # user-facing invariants raise ValueError (asserts would vanish under
    # ``python -O`` — see test_plan_validation_without_assertions)
    with pytest.raises(ValueError, match="non-decreasing"):
        TierPlan(8, 8, cuts=(5, 3), intervals=(2, 2, 1), entities=(8, 4, 1))
    with pytest.raises(ValueError, match="intervals"):
        TierPlan(8, 8, cuts=(2, 4), intervals=(2, 2, 2), entities=(8, 4, 1))
    with pytest.raises(ValueError, match="evenly divide"):
        TierPlan(8, 8, cuts=(2, 4), intervals=(2, 2, 1), entities=(8, 3, 1))
    with pytest.raises(ValueError, match="cuts"):
        TierPlan(8, 8, cuts=(2,), intervals=(2, 2, 1), entities=(8, 4, 1))
    with pytest.raises(ValueError, match="n_units"):
        TierPlan(8, 8, cuts=(2, 9), intervals=(2, 2, 1), entities=(8, 4, 1))
    with pytest.raises(ValueError, match="tiers"):
        TierPlan(8, 8, cuts=(2, 4), intervals=(2, 2, 1), entities=(8, 1))


def test_plan_validation_without_assertions():
    """Invalid plans must still raise under ``python -O`` (bare asserts are
    stripped by the optimizer; the invariants are ValueError-backed)."""
    import subprocess
    import sys

    code = (
        "from repro.core.tiers import TierPlan\n"
        "for bad in [\n"
        "    dict(cuts=(5, 3), intervals=(2, 2, 1), entities=(8, 4, 1)),\n"
        "    dict(cuts=(2, 4), intervals=(2, 2, 2), entities=(8, 4, 1)),\n"
        "    dict(cuts=(2, 4), intervals=(2, 2, 1), entities=(8, 3, 1)),\n"
        "]:\n"
        "    try:\n"
        "        TierPlan(8, 8, **bad)\n"
        "    except ValueError:\n"
        "        pass\n"
        "    else:\n"
        "        raise SystemExit(f'invalid plan accepted under -O: {bad}')\n"
        "print('ok')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-O", "-c", code],
        capture_output=True, text=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr
    assert "ok" in proc.stdout


def test_tier_bounds_cover():
    plan = default_plan(10, 8, cuts=(2, 6))
    bounds = [plan.tier_bounds(m) for m in range(plan.M)]
    assert bounds == [(0, 2), (2, 6), (6, 10)]
    for u in range(10):
        m = plan.tier_of_unit(u)
        lo, hi = plan.tier_bounds(m)
        assert lo <= u < hi


def test_subtrees_roundtrip():
    N, U = 8, 10
    params = _params(jax.random.PRNGKey(0), N, U)
    plan = default_plan(U, N, cuts=(3, 7))
    parts = tier_subtrees(params, plan)
    assert parts[0]["units"]["w"].shape == (N, 3, 4, 4)
    assert parts[1]["units"]["w"].shape == (N, 4, 4, 4)
    back = combine_tiers(parts, params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("seed", range(5))
def test_synchronize_entity_level_every_round(seed):
    """Eq. 3: sub-models co-hosted by an entity are identical every round."""
    N, U = 8, 6
    params = _params(jax.random.PRNGKey(seed), N, U)
    plan = default_plan(U, N, cuts=(2, 4), intervals=(5, 3, 1), entities=(N, 4, 1))
    out = synchronize(params, plan, jnp.int32(0))  # step 0: no global for I>1
    w = out["units"]["w"]
    # tier 2 (units 2..4) entity groups of 2 clients are equal
    for g in range(4):
        np.testing.assert_allclose(w[2 * g, 2:4], w[2 * g + 1, 2:4], rtol=1e-6)
    # tier 3 (units 4..6) globally equal (cloud server, I=1)
    for n in range(1, N):
        np.testing.assert_allclose(w[0, 4:], w[n, 4:], rtol=1e-6)
    # tier 1 (units 0..2) untouched at step 0 (J_1 = N, I_1 = 5)
    assert not np.allclose(w[0, 0], w[1, 0])


@pytest.mark.parametrize("interval", [2, 3, 4])
def test_synchronize_interval_trigger(interval):
    """Eq. 4 fires exactly when (step+1) % I == 0."""
    N, U = 4, 4
    params = _params(jax.random.PRNGKey(1), N, U)
    plan = default_plan(
        U, N, cuts=(2,), intervals=(interval, 1), entities=(N, 1)
    )
    for step in range(6):
        out = synchronize(params, plan, jnp.int32(step))
        w = out["units"]["w"]
        synced = np.allclose(w[0, :2], w[1, :2])
        assert synced == (((step + 1) % interval) == 0), step


def test_synchronize_means_are_exact():
    N, U = 6, 3
    params = _params(jax.random.PRNGKey(2), N, U)
    # tier 1: global at I=1; tier 2: entity-only at step 0 (I=5 not due)
    plan = default_plan(U, N, cuts=(1, 2), intervals=(1, 5, 1), entities=(N, 3, 1))
    out = synchronize(params, plan, jnp.int32(0))
    w_in = params["units"]["w"]
    w = out["units"]["w"]
    np.testing.assert_allclose(
        w[:, 0], np.broadcast_to(w_in[:, 0].mean(0), w_in[:, 0].shape), rtol=1e-5
    )
    np.testing.assert_allclose(
        w[0, 1], w_in[[0, 1], 1].mean(0), rtol=1e-5
    )  # entity group {0,1} of tier 2


def test_pod_level_schedule():
    """Multi-pod: top tier is per-pod every round, cross-pod at pod_interval."""
    N, U = 8, 2
    params = _params(jax.random.PRNGKey(3), N, U)
    plan = TierPlan(
        n_units=U, num_clients=N, cuts=(1,), intervals=(1, 1),
        entities=(N, 1), num_pods=2, pod_interval=3,
    )
    out0 = synchronize(params, plan, jnp.int32(0))
    w = out0["units"]["w"]
    # per-pod mean on tier 2: pods {0..3}, {4..7} internally equal but differ
    np.testing.assert_allclose(w[0, 1:], w[3, 1:], rtol=1e-6)
    assert not np.allclose(w[0, 1:], w[4, 1:])
    out2 = synchronize(params, plan, jnp.int32(2))  # (2+1) % 3 == 0
    w2 = out2["units"]["w"]
    np.testing.assert_allclose(w2[0, 1:], w2[7, 1:], rtol=1e-6)


def _lossy(x):
    """A visibly lossy wire transform (round to a 1/4 grid)."""
    return jnp.round(x * 4.0) / 4.0


@pytest.mark.parametrize("step", [0, 1])
def test_sync_allones_mask_with_compression_matches_unmasked(step):
    """An all-ones mask composed with a lossy fed wire is bit-identical to
    the unmasked compressed path (DESIGN.md §9 + §12 compose exactly)."""
    N, U = 8, 6
    params = _params(jax.random.PRNGKey(11), N, U)
    plan = default_plan(U, N, cuts=(2, 4), intervals=(1, 2, 1),
                        entities=(N, 4, 1))
    ref = synchronize(params, plan, jnp.int32(step), compress_fn=_lossy)
    out = synchronize(params, plan, jnp.int32(step), compress_fn=_lossy,
                      mask=jnp.ones((N,), jnp.float32))
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sync_zero_participant_group_keeps_exact_params():
    """A zero-participant entity group keeps its members' *exact* current
    params. Nothing was uploaded, so nothing may move — not even through
    the lossy fed wire (the silent group must not 'keep' a lossy-coded
    copy it never sent)."""
    N, U = 8, 6
    params = _params(jax.random.PRNGKey(12), N, U)
    # tier 2 fed level at I=3 does not fire at step 0, so tier 2 is
    # entity-level only this round; tier 1 (client units) feds every round.
    plan = default_plan(U, N, cuts=(2, 4), intervals=(1, 3, 1),
                        entities=(N, 4, 1))
    mask = jnp.ones((N,), jnp.float32).at[0].set(0.0).at[1].set(0.0)
    out = synchronize(params, plan, jnp.int32(0), compress_fn=_lossy,
                      mask=mask)
    w_in = np.asarray(params["units"]["w"])
    w = np.asarray(out["units"]["w"])
    # entity group {0,1} of tier 2 (units 2..4) has zero participants:
    # bit-exact hold of the pre-sync params
    np.testing.assert_array_equal(w[:2, 2:4], w_in[:2, 2:4])
    # a participating group averages its participants (uncompressed Eq. 3)
    np.testing.assert_allclose(
        w[2, 2:4], w_in[2:4, 2:4].mean(0), rtol=1e-6
    )
    # the silent clients still *receive* levels whose group has
    # participants (state lives at the server): tier-1 fed mean moved them
    assert not np.array_equal(w[:2, :2], w_in[:2, :2])


def test_sync_fully_masked_round_is_identity_despite_compression():
    """With no participants anywhere, synchronize is a bit-exact identity
    even though the lossy fed transform runs inside the graph — the
    zero-participant fallback must be the pre-compression tree."""
    N, U = 8, 6
    params = _params(jax.random.PRNGKey(13), N, U)
    plan = default_plan(U, N, cuts=(2, 4), intervals=(1, 1, 1),
                        entities=(N, 4, 1))
    out = synchronize(params, plan, jnp.int32(0), compress_fn=_lossy,
                      mask=jnp.zeros((N,), jnp.float32))
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # teeth: the same round with full participation is NOT an identity
    # (the wire really is lossy)
    moved = synchronize(params, plan, jnp.int32(0), compress_fn=_lossy,
                        mask=jnp.ones((N,), jnp.float32))
    assert not np.array_equal(np.asarray(moved["units"]["w"]),
                              np.asarray(params["units"]["w"]))


@pytest.mark.parametrize("step", [0, 1, 3, 7])
def test_round_specialization_matches_dynamic(step):
    """fed_round=True/False specialized steps == the dynamic cond schedule.

    The production dispatch `sync if (t+1) % I == 0 else local` must produce
    bit-identical params to the single dynamic step at every round.
    """
    N, U = 8, 4
    params = _params(jax.random.PRNGKey(7), N, U)
    plan = default_plan(U, N, cuts=(1, 3), intervals=(4, 2, 1),
                        entities=(N, 4, 1))
    dyn = synchronize(params, plan, jnp.int32(step))
    # production dispatch: per-tier round-type tuple
    fed = tuple((step + 1) % I == 0 for I in plan.intervals)
    spec = synchronize(params, plan, jnp.int32(step), fed_round=fed)
    for d_leaf, s_leaf in zip(jax.tree.leaves(dyn), jax.tree.leaves(spec)):
        np.testing.assert_allclose(np.asarray(d_leaf), np.asarray(s_leaf),
                                   rtol=0, atol=0)


# --------------------------------------------------------------------------- #
# in-place tier sync == the split -> levels -> concatenate formulation
# --------------------------------------------------------------------------- #


def _split_sync(params, plan, step, *, fed_round=None, compress_fn=None,
                mask=None, guard=None):
    """``synchronize`` as a split into per-tier copies, the levels on each,
    and ``combine_tiers`` back: the formulation the in-place write-back
    must reproduce bit for bit."""
    if guard is not None:
        health, params = guard_health(params, plan.num_clients, guard)
        mask = health if mask is None else mask * health
    if fed_round is not None and not isinstance(fed_round, (tuple, list)):
        fed_round = (bool(fed_round),) * plan.M
    out_parts = []
    for m, part in enumerate(tier_subtrees(params, plan)):
        levels = plan.levels(m)
        for li, (groups, interval) in enumerate(levels):
            fed = (compress_fn is not None and m < plan.M - 1
                   and li == len(levels) - 1 and plan.entities[m] > 1)

            def level_mean(p, groups=groups, fed=fed):
                original = p
                if fed:
                    p = jax.tree.map(compress_fn, p)
                if mask is not None:
                    return _group_mean_masked(p, groups, mask, keep=original)
                return _group_mean(p, groups)

            if interval <= 1:
                part = level_mean(part)
            elif fed_round is None:
                part = lax.cond((step + 1) % interval == 0, level_mean,
                                lambda p: p, part)
            elif fed_round[m]:
                part = level_mean(part)
        out_parts.append(part)
    return combine_tiers(out_parts, params)


def _unit_tree(key, kind, N, U, d=4):
    """Client-stacked params whose units are one stack, an enc/dec pair of
    stacks (4 enc units, then the decoder's) or a per-unit list."""
    ks = jax.random.split(key, 5)
    leaves = lambda k, n: {"w": jax.random.normal(k, (N, n, d, d)),
                           "b": jax.random.normal(jax.random.fold_in(k, 1), (N, n, d))}
    if kind == "stacked":
        units = leaves(ks[1], U)
    elif kind == "encdec":
        units = {"enc": leaves(ks[1], 4), "dec": leaves(ks[2], U - 4)}
    else:
        units = [{"w": jax.random.normal(jax.random.fold_in(ks[3], u), (N, d, d))}
                 for u in range(U)]
    return {"frontend": {"embed": jax.random.normal(ks[0], (N, 8, d))},
            "units": units,
            "head": {"norm": jax.random.normal(ks[4], (N, d))}}


# fed_round=None runs the in-graph cond at these steps; the others are the
# sync patterns fed_round(intervals (8, 4, 1), r) dispatches
ROUNDS = {"dynamic": None, "local": (False, False, True),
          "fed_FTT": (False, True, True), "fed_TTT": (True, True, True)}


@pytest.mark.parametrize("variant", ["plain", "masked", "compress", "guard"])
@pytest.mark.parametrize("round_", list(ROUNDS))
@pytest.mark.parametrize("kind", ["stacked", "encdec", "list"])
def test_in_place_sync_is_bit_identical_to_split_and_concatenate(kind, round_, variant):
    N, U = 8, 10
    params = _unit_tree(jax.random.PRNGKey(21), kind, N, U)
    # tier 2 spans the enc/dec boundary; tier 3 holds decoder units only
    plan = default_plan(U, N, cuts=(3, 6), intervals=(8, 4, 1),
                        entities=(N, 4, 1))
    kw = {}
    if variant == "masked":
        # clients 0 and 1 form tier 2's first entity: a zero-participant group
        kw["mask"] = jnp.ones((N,), jnp.float32).at[0].set(0.0).at[1].set(0.0).at[5].set(0.0)
    elif variant == "compress":
        kw["compress_fn"] = _lossy
    elif variant == "guard":
        kw["guard"] = GuardSpec()
        bad = jax.tree.leaves(params["units"])[0]
        params = jax.tree.map(lambda x: x.at[5].set(jnp.inf) if x is bad else x, params)
    fed = ROUNDS[round_]
    in_place = jax.jit(lambda p, s: synchronize(p, plan, s, fed_round=fed, **kw))
    split = jax.jit(lambda p, s: _split_sync(p, plan, s, fed_round=fed, **kw))
    for step in ([0, 3, 7] if fed is None else [7]):
        got = in_place(params, jnp.int32(step))
        want = split(params, jnp.int32(step))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # teeth: the round really moved the params
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)))


def test_in_place_sync_leaves_an_unsynced_tier_untouched():
    """A local round reads and writes no unit of tier 1, which has no level
    in it: its units come back as the very input values."""
    N, U = 8, 10
    params = _unit_tree(jax.random.PRNGKey(22), "stacked", N, U)
    plan = default_plan(U, N, cuts=(3, 6), intervals=(8, 4, 1),
                        entities=(N, 4, 1))
    out = synchronize(params, plan, jnp.int32(0), fed_round=(False, False, True))
    for a, b in zip(jax.tree.leaves(out["units"]), jax.tree.leaves(params["units"])):
        np.testing.assert_array_equal(np.asarray(a)[:, :3], np.asarray(b)[:, :3])
        assert not np.array_equal(np.asarray(a)[:, 3:], np.asarray(b)[:, 3:])
    assert out["frontend"]["embed"] is params["frontend"]["embed"]


# --------------------------------------------------------------------------- #
# the lowered Engine-A round: no concatenate rebuilds a unit stack
# --------------------------------------------------------------------------- #

RESULT = re.compile(r"->\s*tensor<([0-9x]+)x[a-z0-9]+>")


def _lowered_round(make, fed, mesh=None):
    from repro.core import build_train_step_a, init_state_a
    from repro.optim import sgd

    model, plan, batch = make()
    opt = sgd(1e-2)
    state = jax.eval_shape(lambda: init_state_a(model, plan, opt, jax.random.PRNGKey(0)))
    step = build_train_step_a(model, plan, opt, fed_round=fed, mesh=mesh)
    return jax.jit(step).lower(state, batch).as_text(), state, plan


def _lm_round_parts():
    from repro.configs import get_reduced
    from repro.configs.shapes import concrete_inputs
    from repro.models.model import SplittableModel

    N = 4
    spec = dataclasses.replace(get_reduced("smollm-135m"), num_layers=5)
    plan = default_plan(spec.n_units, N, cuts=(1, 3), intervals=(8, 4, 1),
                        entities=(N, 2, 1))
    batch = concrete_inputs(spec, N * 2, 8, jax.random.PRNGKey(1))
    batch = {k: v.reshape(N, 2, *v.shape[1:]) for k, v in batch.items()}
    return SplittableModel(spec), plan, batch


def _vgg_round_parts():
    from repro.configs import get_reduced
    from repro.models.vgg import build_model

    N = 4
    spec = get_reduced("vgg16-cifar10")
    plan = default_plan(spec.n_units, N, cuts=(1, 3), intervals=(8, 4, 1),
                        entities=(N, 2, 1))
    s = spec.image_size
    batch = {"images": jnp.zeros((N, 2, s, s, spec.in_channels), jnp.float32),
             "labels": jnp.zeros((N, 2), jnp.int32)}
    return build_model(spec), plan, batch


def _results(text, op, shapes):
    """Result shapes of ``op`` in lowered StableHLO that are among ``shapes``."""
    out = []
    for line in text.splitlines():
        if f"stablehlo.{op}" in line:
            m = RESULT.search(line)
            dims = tuple(int(x) for x in m.group(1).split("x")) if m else ()
            if dims in shapes:
                out.append(dims)
    return out


@pytest.mark.parametrize("round_", ["local", "fed_FTT", "fed_TTT"])
@pytest.mark.parametrize("on_mesh", [False, True], ids=["host", "mesh"])
def test_lm_round_rebuilds_no_unit_stack_by_concatenate(on_mesh, round_):
    # the mesh case is the same step under shard_map on a one-device mesh
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh(data=1, model=1) if on_mesh else None
    text, state, plan = _lowered_round(_lm_round_parts, ROUNDS[round_], mesh)
    stacks = {tuple(x.shape) for x in jax.tree.leaves(state.params["units"])}
    assert all(s[:2] == (plan.num_clients, plan.n_units) for s in stacks)
    assert _results(text, "concatenate", stacks) == []
    # the syncs wrote back in place instead
    assert _results(text, "dynamic_update_slice", stacks)
    # teeth: the detector sees the split-and-concatenate merge
    merged = jax.jit(lambda p: combine_tiers(tier_subtrees(p, plan), p)).lower(
        state.params).as_text()
    assert _results(merged, "concatenate", stacks)


@pytest.mark.parametrize("round_", ["local", "fed_TTT"])
def test_vgg_round_keeps_list_units(round_):
    """Per-unit list leaves are swapped in the list: the VGG round neither
    concatenates nor updates a slice of any client-stacked parameter."""
    text, state, plan = _lowered_round(_vgg_round_parts, ROUNDS[round_])
    assert isinstance(state.params["units"], list)
    shapes = {tuple(x.shape) for x in jax.tree.leaves(state.params)}
    for op in ("concatenate", "dynamic_update_slice"):
        assert _results(text, op, shapes) == [], op
