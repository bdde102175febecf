"""Ahead-of-time compiles for a described TPU v5e chip.

Nothing runs: the TPU compiler that is installed with JAX compiles for a
chip that is described, not attached, and refuses here what the chip
would refuse (block shapes off the tiling, too much fast memory, a
program that does not fit).  Covered: the three ``tiered_aggregate``
kernels at real leaf widths (N = 20 clients, J = 5 edges), the SWA
attention kernels forward and backward at SmolLM's head dim, and the
jitted VGG-16/CIFAR-10 Engine-A local-round and sync-round steps at
20 clients, the megablox grouped products of Moonlight-16B-A3B's
held experts at the benchmark's shapes, and its latent attention in the
splash kernel, forward and backward.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N, J = 20, 5
# a VGG-16 block-5 conv kernel (3·3·512·512) and SmolLM-135M's tied
# embedding (49 152·576): the largest leaves the sync kernels would see
LEAVES = {"vgg_conv": 3 * 3 * 512 * 512, "smollm_embed": 49_152 * 576}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("leaf", sorted(LEAVES))
@pytest.mark.parametrize("kind", ["f32", "q8", "ragged_q8"])
def test_tiered_aggregate_kernel_compiles(one_chip, kind, leaf):
    from repro.kernels.tiered_aggregate.tiered_aggregate import (
        TILE_P,
        quantized_tiered_aggregate_pallas,
        ragged_quantized_tiered_aggregate_pallas,
        tiered_aggregate_pallas,
    )

    P = LEAVES[leaf]
    Pp = -(-P // TILE_P) * TILE_P
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    flag = s((), jnp.int32)
    w = s((N,), jnp.float32)
    if kind == "f32":
        c = _compile(
            lambda x, w, a, b: tiered_aggregate_pallas(x, w, a, b, J),
            s((N, P), jnp.float32), w, flag, flag,
        )
    elif kind == "q8":
        c = _compile(
            lambda q, sc, w, a, b: quantized_tiered_aggregate_pallas(
                q, sc, w, a, b, J
            ),
            s((N, Pp), jnp.int8), s((N, Pp // TILE_P), jnp.float32), w,
            flag, flag,
        )
    else:
        c = _compile(
            lambda q, sc, w, m, a, b: ragged_quantized_tiered_aggregate_pallas(
                q, sc, w, m, a, b, J
            ),
            s((N, Pp), jnp.int8), s((N, Pp // TILE_P), jnp.float32), w, w,
            flag, flag,
        )
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_swa_attention_kernel_compiles(one_chip, direction):
    from repro.kernels.swa_attention import swa_attention

    # SmolLM-135M attention: 9 query heads over 3 KV heads of dim 64
    B, S, H, K, hd, W = 1, 8192, 9, 3, 64, 4096
    q = _sds(one_chip, (B, S, H, hd), jnp.bfloat16)
    kv = _sds(one_chip, (B, S, K, hd), jnp.bfloat16)
    fwd = lambda q, k, v: swa_attention(q, k, v, window=W)
    if direction == "fwd":
        fn = fwd
    else:
        fn = jax.grad(
            lambda q, k, v: jnp.sum(fwd(q, k, v).astype(jnp.float32)), (0, 1, 2)
        )
    assert "tpu_custom_call" in _compile(fn, q, kv, kv).as_text()


@pytest.mark.parametrize(
    "fed", [(False, False, True), (False, True, True)], ids=["local", "sync"]
)
def test_vgg_engine_a_step_compiles(one_chip, fed):
    from repro.configs.vgg16_cifar10 import SPEC
    from repro.core import build_train_step_a, init_state_a
    from repro.core.tiers import default_plan
    from repro.models.vgg import build_model
    from repro.optim import sgd

    model, opt = build_model(SPEC), sgd(5e-4)
    plan = default_plan(SPEC.n_units, N, entities=(N, J, 1))
    state = jax.eval_shape(
        lambda: init_state_a(model, plan, opt, jax.random.PRNGKey(0))
    )
    state = jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), state)
    batch = {
        "images": _sds(one_chip, (N, 16, 32, 32, 3), jnp.float32),
        "labels": _sds(one_chip, (N, 16), jnp.int32),
    }
    step = build_train_step_a(model, plan, opt, fed_round=fed)
    mem = _compile(step, state, batch).memory_analysis()
    # 20 f32 replicas of VGG-16's ~15 M parameters, plus the batch
    assert 1.0e9 < mem.argument_size_in_bytes < 1.5e9


@pytest.mark.parametrize("product", ["w1_w3", "w2", "w2_transposed", "weight_grad"])
def test_expert_grouped_matmul_compiles(one_chip, product):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    from repro.models.layers import _megablox_args

    # two clients' 2048 tokens x 6 choices, folded into 2 x 8 held-expert
    # groups; hidden 2048, expert width 1408; bf16 operands, f32 out
    m, d, f, G = 2 * 2048 * 6, 2048, 1408, 16
    s = lambda shape: _sds(one_chip, shape, jnp.bfloat16)
    sizes = _sds(one_chip, (G,), jnp.int32)
    if product == "weight_grad":
        pad, tiling, _ = _megablox_args(m, d, f)
        fn = lambda x, dy, n: tgmm(x.T, dy, n, preferred_element_type=jnp.float32,
                                   tiling=tiling)
        args = (s((m, d)), s((m, f)))
    else:
        k, n_out, rhs = {"w1_w3": (d, f, (G, d, f)), "w2": (f, d, (G, f, d)),
                         "w2_transposed": (d, f, (G, f, d))}[product]
        pad, tiling, _ = _megablox_args(m, k, n_out)
        fn = lambda x, w, n: gmm(x, w, n, preferred_element_type=jnp.float32,
                                 tiling=tiling, transpose_rhs=product == "w2_transposed")
        args = (s((m, k)), s(rhs))
    assert pad == 0
    assert "tpu_custom_call" in _compile(fn, *args, sizes).as_text()


def test_mla_splash_attention_compiles(one_chip):
    """Moonlight-16B-A3B's latent attention in the splash kernel at the
    benchmark's shapes (2 clients x 1 x 2048 tokens, 16 heads, query/key
    128 + 64, value 128, bf16 operands), forward and backward under
    ``jax.checkpoint`` and a client ``vmap``.  Temporaries: 102.0 MB (the
    heads' queries, keys and values laid out for the kernel, and their
    gradients), where one float32 score tensor of the dense path is 537 MB."""
    from repro.models.layers import mla_splash_attention

    N, B, S, h = 2, 1, 2048, 16
    s = lambda *shape: _sds(one_chip, (N, B, S) + shape, jnp.bfloat16)
    attn = jax.checkpoint(mla_splash_attention)
    fn = jax.grad(lambda *a: jnp.sum(jax.vmap(attn)(*a).astype(jnp.float32)), range(5))
    c = _compile(fn, s(h, 128), s(h, 64), s(h, 128), s(64), s(h, 128))
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 128e6
