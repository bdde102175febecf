"""tiered_aggregate Pallas kernels vs pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.tiered_aggregate import (
    tiered_aggregate, tiered_aggregate_q8, tiered_aggregate_ref,
)
from repro.kernels.tiered_aggregate.ops import aggregate_tree


@pytest.mark.parametrize("N,J", [(16, 4), (8, 2), (20, 5), (16, 16), (4, 1)])
@pytest.mark.parametrize("P", [257, 2048, 5000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_ref(N, J, P, dtype):
    key = jax.random.PRNGKey(N * P)
    x = jax.random.normal(key, (N, P)).astype(dtype)
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (N,)))
    for de in (0, 1):
        for dg in (0, 1):
            out = tiered_aggregate(
                x, w, jnp.array(de), jnp.array(dg), J, use_pallas=True, interpret=True
            )
            ref = tiered_aggregate_ref(x, w, jnp.array(bool(de)), jnp.array(bool(dg)), J)
            tol = 1e-5 if dtype == jnp.float32 else 2e-2
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(ref, np.float32),
                rtol=tol, atol=tol,
            )


# --------------------------------------------------------------------------- #
# edge shapes: the padding branch at P % tile != 0, non-power-of-two N,
# degenerate entity counts (J = 1 and J = N), both dtypes, small tiles so a
# short P still spans several grid steps
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("N", [6, 20])          # not powers of two
@pytest.mark.parametrize("P", [100, 257, 999])  # none divisible by tile_p
@pytest.mark.parametrize("J", ["one", "n", "mid"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_edge_shapes_match_ref(N, P, J, dtype):
    tile_p = 128
    num_entities = {"one": 1, "n": N, "mid": 2}[J]
    key = jax.random.PRNGKey(N * 10_000 + P)
    x = jax.random.normal(key, (N, P)).astype(dtype)
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (N,)))
    for de in (0, 1):
        for dg in (0, 1):
            out = tiered_aggregate(
                x, w, jnp.array(de), jnp.array(dg), num_entities,
                tile_p=tile_p, use_pallas=True, interpret=True,
            )
            ref = tiered_aggregate_ref(
                x, w, jnp.array(bool(de)), jnp.array(bool(dg)), num_entities
            )
            tol = 1e-5 if dtype == jnp.float32 else 2e-2
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(ref, np.float32),
                rtol=tol, atol=tol,
            )


# --------------------------------------------------------------------------- #
# fused q8 path: bit-for-bit against the tile-mirroring oracle
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize(
    "N,J,P,tile", [(16, 4, 2048, 256), (6, 2, 257, 128), (20, 20, 1000, 128),
                   (4, 1, 100, 128), (12, 3, 333, 128)],
)
def test_q8_kernel_bit_exact_vs_oracle(N, J, P, tile):
    from repro.kernels.tiered_aggregate.check import assert_q8_matches_oracle

    assert_q8_matches_oracle(N, J, P, tile)


@pytest.mark.parametrize(
    "N,J,P,tile", [(16, 4, 300, 128), (6, 2, 257, 128), (20, 5, 999, 128)],
)
def test_ragged_q8_kernel_bit_exact_vs_oracle(N, J, P, tile):
    """Per-class membership (DESIGN.md §14): the ragged fused kernel vs its
    tile-mirroring oracle, the jit entry's branches, and the all-ones
    collapse onto the dense kernel (where the divisions align)."""
    from repro.kernels.tiered_aggregate.check import (
        assert_ragged_q8_matches_oracle,
    )

    assert_ragged_q8_matches_oracle(N, J, P, tile)


def test_q8_aggregation_close_to_lossless():
    """Quantize-then-aggregate deviates from the f32 aggregate by < 1 LSB."""
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (8, 700))
    w = jnp.full((8,), 1 / 8)
    lossless = tiered_aggregate(x, w, jnp.array(1), jnp.array(1), 4, interpret=True)
    q8 = tiered_aggregate_q8(
        x, w, jnp.array(1), jnp.array(1), 4, tile_p=128, interpret=True
    )
    lsb = float(jnp.max(jnp.abs(x))) / 127.0
    np.testing.assert_allclose(
        np.asarray(q8), np.asarray(lossless, np.float32), atol=lsb
    )


def test_aggregate_tree_quantized_roundtrip():
    key = jax.random.PRNGKey(11)
    tree = {
        "a": jax.random.normal(key, (8, 3, 5)),
        "b": {"c": jax.random.normal(jax.random.fold_in(key, 1), (8, 7))},
    }
    w = jnp.full((8,), 1 / 8)
    out = aggregate_tree(
        tree, w, jnp.array(1), jnp.array(0), 4, tile_p=128, quantized=True,
        interpret=True,
    )
    ref = aggregate_tree(tree, w, jnp.array(1), jnp.array(0), 4, interpret=True)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0.05)


def test_flags_semantics():
    x = jnp.arange(8.0).reshape(4, 2)
    w = jnp.full((4,), 0.25)
    noop = tiered_aggregate(x, w, jnp.array(0), jnp.array(0), 2, interpret=True)
    np.testing.assert_allclose(noop, x)
    glob = tiered_aggregate(x, w, jnp.array(0), jnp.array(1), 2, interpret=True)
    np.testing.assert_allclose(glob, jnp.broadcast_to(x.mean(0), x.shape), rtol=1e-6)
    ent = tiered_aggregate(x, w, jnp.array(1), jnp.array(0), 2, interpret=True)
    np.testing.assert_allclose(ent[0], ent[1])
    np.testing.assert_allclose(ent[2], ent[3])
    assert not np.allclose(ent[0], ent[2])


def test_weighted_global_mean():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (8, 100))
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (8,)))
    out = tiered_aggregate(x, w, jnp.array(0), jnp.array(1), 4, interpret=True)
    expect = jnp.sum(x * w[:, None], axis=0)
    np.testing.assert_allclose(out[3], expect, rtol=1e-5, atol=1e-6)


def test_aggregate_tree_matches_synchronize_level():
    """Kernel applied tree-wise == the engine's _group_mean at a full sync."""
    from repro.core.tiers import _group_mean

    key = jax.random.PRNGKey(5)
    tree = {
        "a": jax.random.normal(key, (8, 3, 5)),
        "b": {"c": jax.random.normal(jax.random.fold_in(key, 1), (8, 7))},
    }
    w = jnp.full((8,), 1 / 8)
    out = aggregate_tree(tree, w, jnp.array(1), jnp.array(0), 4, interpret=True)
    ref = _group_mean(tree, 4)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
