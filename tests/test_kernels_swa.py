"""swa_attention Pallas kernel vs pure-jnp oracle: fwd + custom-vjp bwd,
swept over shapes, windows, GQA ratios, head-dim padding and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.swa_attention import swa_attention, swa_attention_ref


def rand_qkv(key, B, S, H, K, hd, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return (
        jax.random.normal(ks[0], (B, S, H, hd), dtype),
        jax.random.normal(ks[1], (B, S, K, hd), dtype),
        jax.random.normal(ks[2], (B, S, K, hd), dtype),
    )


CASES = [
    # B, S, H, K, hd, window
    (1, 256, 4, 2, 64, 128),      # GQA + window
    (2, 384, 4, 4, 128, 256),     # MHA + window, aligned hd
    (1, 512, 8, 2, 80, 0),        # full causal, hd padding (80 -> 128)
    (1, 300, 4, 1, 64, 128),      # MQA + seq padding (300 -> 384)
    (1, 256, 6, 3, 96, 128),      # 2:1 GQA, hd pad
    (1, 640, 4, 2, 64, 512),      # window > half of seq
]


@pytest.mark.parametrize("case", CASES, ids=[str(c) for c in CASES])
def test_forward_matches_ref(case):
    B, S, H, K, hd, W = case
    q, k, v = rand_qkv(jax.random.PRNGKey(sum(case)), B, S, H, K, hd)
    out = swa_attention(q, k, v, window=W, interpret=True)
    ref = swa_attention_ref(q, k, v, W)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", CASES[:4], ids=[str(c) for c in CASES[:4]])
def test_backward_matches_ref(case):
    B, S, H, K, hd, W = case
    key = jax.random.PRNGKey(sum(case) + 1)
    q, k, v = rand_qkv(key, B, S, H, K, hd)
    dd = jax.random.normal(jax.random.fold_in(key, 9), q.shape)
    g1 = jax.grad(lambda *a: jnp.sum(swa_attention(*a, window=W, interpret=True) * dd), (0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(swa_attention_ref(*a, W) * dd), (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        scale = np.max(np.abs(np.asarray(b))) + 1e-9
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-5
        )


def test_bfloat16_forward():
    B, S, H, K, hd, W = 1, 256, 4, 2, 64, 128
    q, k, v = rand_qkv(jax.random.PRNGKey(7), B, S, H, K, hd, jnp.bfloat16)
    out = swa_attention(q, k, v, window=W, interpret=True)
    ref = swa_attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), W
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_window_equals_full_when_large():
    B, S, H, K, hd = 1, 256, 4, 2, 64
    q, k, v = rand_qkv(jax.random.PRNGKey(8), B, S, H, K, hd)
    np.testing.assert_allclose(
        swa_attention(q, k, v, window=512, interpret=True),  # window >= S -> full causal
        swa_attention(q, k, v, window=0, interpret=True),
        rtol=1e-6,
    )


def test_matches_model_layer_semantics():
    """Kernel == the model zoo's windowed attention path (mask conventions)."""
    import math

    from repro.models import layers as L
    from repro.configs import get_reduced

    spec = get_reduced("qwen2-1.5b").with_window(128)
    B, S = 1, 256
    hd, H, K = spec.hd, spec.num_heads, spec.num_kv_heads
    key = jax.random.PRNGKey(9)
    q, k, v = rand_qkv(key, B, S, H, K, hd)
    bias = L._mask_bias(jnp.arange(S), jnp.arange(S), True, 128, 0)
    ref = L._sdpa(q, k, v, bias)
    out = swa_attention(q, k, v, window=128, interpret=True)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
