"""Per-architecture smoke tests (deliverable f): every assigned arch's
REDUCED variant runs one forward + one train step + decode on CPU with
finite outputs and the right shapes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_reduced, get_spec
from repro.configs.shapes import concrete_inputs
from repro.core import build_train_step_a, init_state_a
from repro.core.tiers import default_plan
from repro.models.model import SplittableModel
from repro.optim import sgd


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_constraints(arch):
    r = get_reduced(arch)
    assert r.num_layers <= 2 or r.family == "hybrid" and r.n_units <= 2
    assert r.d_model <= 512
    if r.moe:
        assert r.moe.num_experts <= 4


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_spec_matches_assignment(arch):
    s = get_spec(arch)
    expect = {
        "qwen2.5-14b": (48, 5120, 40, 8, 13824, 152064),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "qwen3-32b": (64, 5120, 64, 8, 25600, 151936),
        "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        "smollm-135m": (30, 576, 9, 3, 1536, 49152),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "mamba2-1.3b": (48, 2048, 0, 0, 0, 50280),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
        "moonlight-16b-a3b-ep8": (27, 2048, 16, 16, 11264, 163840),
    }[arch]
    got = (s.num_layers, s.d_model, s.num_heads, s.num_kv_heads, s.d_ff, s.vocab_size)
    assert got == expect, (arch, got, expect)
    assert s.source, f"{arch} missing its public-pool citation"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    spec = get_reduced(arch)
    model = SplittableModel(spec)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S = 2, 32
    batch = concrete_inputs(spec, B, S)
    logits, aux = model.forward(params, batch)
    S_text = S - (spec.prefix_len if spec.family == "vlm" else 0)
    expect_len = S_text + (spec.prefix_len if spec.family == "vlm" else 0)
    assert logits.shape == (B, expect_len if spec.family == "vlm" else S_text if spec.family == "vlm" else S, spec.padded_vocab) or logits.shape[0] == B
    assert logits.shape[-1] == spec.padded_vocab
    assert np.all(np.isfinite(np.asarray(logits[..., : spec.vocab_size], np.float32)))


@pytest.mark.parametrize(
    "arch",
    [  # jamba's 398B reduced variant still jits ~30s of hybrid blocks on CPU
        pytest.param(a, marks=pytest.mark.slow) if a == "jamba-1.5-large-398b"
        else a
        for a in ARCH_IDS
    ],
)
def test_one_train_step(arch):
    spec = get_reduced(arch)
    model = SplittableModel(spec)
    N = 4
    plan = default_plan(spec.n_units, N, entities=(N, 2, 1))
    opt = sgd(1e-2)
    state = init_state_a(model, plan, opt, jax.random.PRNGKey(0))
    step = jax.jit(build_train_step_a(model, plan, opt))
    batch = concrete_inputs(spec, N * 2, 32)
    batch = {k: v.reshape(N, 2, *v.shape[1:]) for k, v in batch.items()}
    state2, loss = step(state, batch)
    assert np.isfinite(float(loss)) and float(loss) > 0
    # params actually moved
    moved = any(
        not np.allclose(a, b)
        for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(state2.params))
    )
    assert moved
    assert int(state2.step) == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step(arch):
    spec = get_reduced(arch)
    model = SplittableModel(spec)
    params = model.init_params(jax.random.PRNGKey(0))
    B, C = 2, 16
    caches = model.init_caches(B, C)
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, caches2 = jax.jit(model.decode_step)(params, tok, caches, jnp.int32(0))
    assert logits.shape == (B, spec.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits[:, : spec.vocab_size], np.float32)))
    changed = any(
        not np.allclose(a, b)
        for a, b in zip(jax.tree.leaves(caches), jax.tree.leaves(caches2))
    )
    assert changed


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen2-1.5b", "mamba2-1.3b", "qwen3-32b",
                                  "moonlight-16b-a3b"])
def test_decode_matches_forward(arch):
    """Teacher-forced decode reproduces the training-path logits."""
    spec = get_reduced(arch)
    model = SplittableModel(spec)
    params = model.init_params(jax.random.PRNGKey(1))
    B, S = 1, 8
    batch = concrete_inputs(spec, B, S)
    full_logits, _ = model.forward(params, batch)
    caches = model.init_caches(B, S)
    decode = jax.jit(model.decode_step)
    for i in range(S):
        step_logits, caches = decode(
            params, batch["tokens"][:, i : i + 1], caches, jnp.int32(i)
        )
    np.testing.assert_allclose(
        np.asarray(step_logits[:, : spec.vocab_size]),
        np.asarray(full_logits[:, -1, : spec.vocab_size]),
        rtol=2e-3, atol=2e-3,
    )


def test_long_context_window_variant():
    """Dense archs get a ring-buffer cache under a sliding window."""
    spec = get_reduced("qwen2.5-14b").with_window(8)
    model = SplittableModel(spec)
    params = model.init_params(jax.random.PRNGKey(0))
    caches = model.init_caches(1, 64)
    # ring buffer bounded by window, not cache_len: k/v leaves have a
    # window-sized cache axis (leaf order puts scalar "index" first, so
    # look at the 4+-dim leaves explicitly).
    kv_shapes = [x.shape for x in jax.tree.leaves(caches) if x.ndim >= 4]
    assert kv_shapes and all(s[-3] == 8 for s in kv_shapes)
    decode = jax.jit(model.decode_step)
    tok = jnp.zeros((1, 1), jnp.int32)
    for i in range(12):  # wrap past the window
        logits, caches = decode(params, tok, caches, jnp.int32(i))
    assert np.all(np.isfinite(np.asarray(logits[:, : spec.vocab_size])))


def test_total_param_count_close_to_nominal():
    """Analytic param accounting lands near each card's nominal size."""
    nominal = {
        "qwen2.5-14b": 14e9, "qwen3-32b": 32e9, "qwen2-1.5b": 1.5e9,
        "smollm-135m": 135e6, "mamba2-1.3b": 1.3e9,
        "granite-moe-1b-a400m": 1.3e9, "phi3.5-moe-42b-a6.6b": 42e9,
        "jamba-1.5-large-398b": 398e9, "paligemma-3b": 2.6e9,  # LM backbone
        "whisper-large-v3": 1.5e9, "moonlight-16b-a3b": 16e9,
    }
    for arch, nom in nominal.items():
        got = get_spec(arch).total_param_count()
        assert 0.5 * nom < got < 1.7 * nom, (arch, got / 1e9)


@pytest.mark.slow
def test_moe_grouped_gradients():
    """Grouped dispatch + scatter-add combine is differentiable and its
    gradients match the ungrouped path (no-drop capacity)."""
    import dataclasses

    import numpy as np

    from repro.configs import get_reduced
    from repro.models import layers as L

    spec = get_reduced("granite-moe-1b-a400m")
    ms = dataclasses.replace(spec.moe, capacity_factor=8.0)
    spec = dataclasses.replace(spec, moe=ms)
    p = L.init_moe(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, spec.d_model))

    def loss(params, g):
        out, aux = L.moe(params, x, spec, groups=g)
        return jnp.sum(out**2) + aux

    g1 = jax.grad(lambda p_: loss(p_, 1))(p)
    g4 = jax.grad(lambda p_: loss(p_, 4))(p)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g4))
