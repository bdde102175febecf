"""Engine A (sync-groups, production) == Engine B (split-placement, literal
SFL dataflow): identical losses and parameters after every step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.shapes import concrete_inputs
from repro.core import build_train_step_a, build_train_step_b, init_state_a, init_state_b
from repro.core.engine import engine_b_to_full
from repro.core.tiers import default_plan

# multi-arch jit compiles dominate (~2 min total): out of the CI fast subset
pytestmark = pytest.mark.slow
from repro.models.model import SplittableModel
from repro.optim import sgd


@pytest.mark.parametrize(
    "arch,cuts,intervals",
    [
        ("smollm-135m", (1, 2), (3, 2, 1)),
        ("qwen2-1.5b", (1, 1), (2, 4, 1)),
        ("mamba2-1.3b", (1, 2), (2, 2, 1)),
        ("granite-moe-1b-a400m", (1, 2), (2, 3, 1)),  # MoE: dispatch+aux path
        ("jamba-1.5-large-398b", (1, 1), (4, 2, 1)),  # hybrid super-blocks
        # DeepSeek-V3 block: the dense layer in tier 1, the expert layer in
        # tier 2; routing drops nothing, so pooled tiers route as per client
        ("moonlight-16b-a3b", (0, 1), (2, 3, 1)),
    ],
)
def test_engines_match(arch, cuts, intervals):
    spec = get_reduced(arch)
    model = SplittableModel(spec)
    N = 8
    plan = default_plan(
        spec.n_units, N, cuts=cuts, intervals=intervals, entities=(N, 4, 1)
    )
    opt = sgd(1e-2)
    key = jax.random.PRNGKey(0)
    sa = init_state_a(model, plan, opt, key)
    sb = init_state_b(model, plan, opt, key)
    step_a = jax.jit(build_train_step_a(model, plan, opt))
    step_b = jax.jit(build_train_step_b(model, plan, opt))
    for t in range(4):
        batch = concrete_inputs(spec, N * 2, 16, jax.random.PRNGKey(t))
        batch = {k: v.reshape(N, 2, *v.shape[1:]) for k, v in batch.items()}
        sa, la = step_a(sa, batch)
        sb, lb = step_b(sb, batch)
        assert np.allclose(float(la), float(lb), rtol=1e-5)
        full_b = engine_b_to_full(model, plan, sb.params)
        for a, b in zip(jax.tree.leaves(sa.params), jax.tree.leaves(full_b)):
            np.testing.assert_allclose(a, b, atol=5e-6, rtol=1e-4)


# --------------------------------------------------------------------------- #
# compressed fed-server wire: both engines apply the shared transform at the
# same point, so they stay equal under every codec (DESIGN.md §9)
# --------------------------------------------------------------------------- #


def _run_engine(engine, model, plan, opt, spec, compressor, steps=3):
    key = jax.random.PRNGKey(0)
    if engine == "a":
        s = init_state_a(model, plan, opt, key)
        step = jax.jit(build_train_step_a(model, plan, opt, compressor=compressor))
    else:
        s = init_state_b(model, plan, opt, key)
        step = jax.jit(build_train_step_b(model, plan, opt, compressor=compressor))
    losses = []
    for t in range(steps):
        batch = concrete_inputs(spec, plan.num_clients * 2, 16, jax.random.PRNGKey(t))
        batch = {
            k: v.reshape(plan.num_clients, 2, *v.shape[1:]) for k, v in batch.items()
        }
        s, loss = step(s, batch)
        losses.append(float(loss))
    return s, losses


def _compressed_setup():
    spec = get_reduced("smollm-135m")
    model = SplittableModel(spec)
    N = 8
    plan = default_plan(
        spec.n_units, N, cuts=(1, 2), intervals=(2, 2, 1), entities=(N, 4, 1)
    )
    return spec, model, plan, sgd(1e-2)


def test_identity_compressor_is_bit_exact():
    """Engine A with the identity codec == Engine A without, to the bit."""
    from repro.compress import Identity

    spec, model, plan, opt = _compressed_setup()
    s0, l0 = _run_engine("a", model, plan, opt, spec, None)
    s1, l1 = _run_engine("a", model, plan, opt, spec, Identity())
    assert l0 == l1
    for a, b in zip(jax.tree.leaves(s0.params), jax.tree.leaves(s1.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("codec", ["identity", "int8", "top-k"])
def test_engines_match_compressed(codec):
    """A == B under each codec: both apply the shared reference transform to
    the fed-server upload, so they agree up to the engines' own ULP-level
    divergence (amplified to ≤ one LSB by the int8 rounding boundary)."""
    from repro.compress import Identity, Int8Stochastic, TopK

    compressor = {
        "identity": Identity(),
        "int8": Int8Stochastic(tile=256),
        "top-k": TopK(0.25),
    }[codec]
    # int8 rounding can flip one LSB (≈ absmax/127) on inputs that differ
    # at ULP level between the engines; the lossless codecs stay tight.
    atol = 2e-3 if codec == "int8" else 5e-6
    spec, model, plan, opt = _compressed_setup()
    sa, la = _run_engine("a", model, plan, opt, spec, compressor)
    sb, lb = _run_engine("b", model, plan, opt, spec, compressor)
    assert np.allclose(la, lb, rtol=1e-5, atol=1e-6)
    full_b = engine_b_to_full(model, plan, sb.params)
    mismatched = total = 0
    for a, b in zip(jax.tree.leaves(sa.params), jax.tree.leaves(full_b)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        bad = np.abs(a - b) > atol + 1e-4 * np.abs(b)
        mismatched += int(bad.sum())
        total += a.size
    if codec == "top-k":
        # a |param| near-tie at the rank-k boundary can flip a kept/dropped
        # coordinate between the ULP-divergent engines, mismatching by the
        # full value; require such flips to stay vanishingly rare instead
        # of betting no tie ever lands within the engines' divergence.
        assert mismatched <= max(1, total // 100_000), (mismatched, total)
    else:
        assert mismatched == 0, (mismatched, total)


# --------------------------------------------------------------------------- #
# partial participation: A == B under deadline-driven client masks
# (DESIGN.md §12) — both engines weight participants identically, so the
# equivalence proof extends to partial rounds.
# --------------------------------------------------------------------------- #


def _round_masks(rng, N, steps, plan):
    """Random ~60% participation masks with the adversarial rounds the mask
    semantics single out: a zero-participant *entity* round (round 1) and a
    zero-participant *global* round (round 2, a whole-round no-op)."""
    masks = rng.random((steps, N)) < 0.6
    per = N // plan.entities[1]
    masks[1, :per] = False          # entity 0 of tier 2 fully absent
    if steps > 2:
        masks[2, :] = False         # empty round: params must freeze
    for t in range(steps):
        if t != 2 and not masks[t].any():
            masks[t, int(rng.integers(N))] = True
    return masks.astype(np.float32)


def _run_masked_pair(arch, cuts, intervals, seed, steps=4):
    spec = get_reduced(arch)
    model = SplittableModel(spec)
    N = 8
    plan = default_plan(
        spec.n_units, N, cuts=cuts, intervals=intervals, entities=(N, 4, 1)
    )
    opt = sgd(1e-2)
    key = jax.random.PRNGKey(0)
    sa = init_state_a(model, plan, opt, key)
    sb = init_state_b(model, plan, opt, key)
    step_a = jax.jit(build_train_step_a(model, plan, opt, with_mask=True))
    step_b = jax.jit(build_train_step_b(model, plan, opt, with_mask=True))
    rng = np.random.default_rng(seed)
    masks = _round_masks(rng, N, steps, plan)
    for t in range(steps):
        batch = concrete_inputs(spec, N * 2, 16, jax.random.PRNGKey(t))
        batch = {k: v.reshape(N, 2, *v.shape[1:]) for k, v in batch.items()}
        mk = jnp.asarray(masks[t])
        sa, la = step_a(sa, batch, mk)
        sb, lb = step_b(sb, batch, mk)
        assert np.allclose(float(la), float(lb), rtol=1e-5, atol=1e-6), (t, la, lb)
        if not masks[t].any():
            assert float(la) == 0.0  # empty round reports loss 0
        full_b = engine_b_to_full(model, plan, sb.params)
        for a, b in zip(jax.tree.leaves(sa.params), jax.tree.leaves(full_b)):
            np.testing.assert_allclose(a, b, atol=5e-6, rtol=1e-4)


@pytest.mark.parametrize(
    "arch,cuts,intervals",
    [
        ("smollm-135m", (1, 2), (3, 2, 1)),
        ("qwen2-1.5b", (1, 1), (2, 4, 1)),
        ("mamba2-1.3b", (1, 2), (2, 2, 1)),
        # routed experts with no auxiliary loss: masked Engine B takes them
        ("moonlight-16b-a3b", (0, 1), (2, 2, 1)),
    ],
)
def test_engines_match_masked(arch, cuts, intervals):
    """A == B under random participation masks, including a
    zero-participant entity round and a zero-participant global round."""
    _run_masked_pair(arch, cuts, intervals, seed=7)


@pytest.mark.parametrize("seed", range(5))
def test_engines_match_masked_seed_sweep(seed):
    """Nightly flakiness guard: the masked A/B differential re-rolled over
    5 fixed mask seeds (fresh random participation pattern each)."""
    _run_masked_pair("smollm-135m", (1, 2), (2, 2, 1), seed=100 + seed)


def test_engine_b_masked_rejects_moe():
    spec = get_reduced("granite-moe-1b-a400m")
    model = SplittableModel(spec)
    plan = default_plan(spec.n_units, 8, cuts=(1, 2), intervals=(2, 2, 1),
                        entities=(8, 4, 1))
    with pytest.raises(NotImplementedError, match="MoE"):
        build_train_step_b(model, plan, sgd(1e-2), with_mask=True)
