"""The ``mla_moe`` family (DeepSeek-V3 block, Moonlight-16B-A3B) on seeded
random weights at a small size: the program against the plain reference
``bench/reference/deepseek_v3.py``, routing with no dropped tokens, the
expert share against the uncut layer, the registered chip share against
its benchmark configuration, one Engine-A round whose tiers split the
dense layer from the expert layers, and latent attention in the splash
kernel (interpreted here) against its dense lines."""
import dataclasses
import json
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, program  # noqa: E402
from repro.configs import get_reduced, get_spec  # noqa: E402
from repro.core import build_train_step_a, init_state_a  # noqa: E402
from repro.core.tiers import default_plan  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models.model import SplittableModel  # noqa: E402
from repro.models.spec import MlaSpec  # noqa: E402
from repro.optim import sgd  # noqa: E402

CONFIG = json.loads((ROOT / "bench/configs/moonlight16b-a3b.json").read_text())


def small_spec(num_layers=4, held=(2, 5)):
    """The reduced chip share with three expert layers and experts 2-4 of 8."""
    spec = get_reduced("moonlight-16b-a3b-ep8")
    return dataclasses.replace(spec, num_layers=num_layers,
                               routed=dataclasses.replace(spec.routed, held=held))


def config_of(spec):
    """The benchmark configuration with the spec's sizes, for the reference."""
    m, r = spec.mla, spec.routed
    cfg = dict(CONFIG)
    cfg.update(
        hidden_size=spec.d_model, num_attention_heads=spec.num_heads,
        num_key_value_heads=spec.num_kv_heads, intermediate_size=spec.d_ff,
        vocab_size=spec.vocab_size, num_hidden_layers=spec.num_layers,
        kv_lora_rank=m.kv_rank, qk_nope_head_dim=m.nope_dim,
        qk_rope_head_dim=m.rope_dim, v_head_dim=m.v_dim,
        moe_intermediate_size=r.d_expert, n_routed_experts=r.n_held,
        num_experts_per_tok=r.top_k, n_shared_experts=r.num_shared,
        expert_share=dict(CONFIG["expert_share"], router_experts=r.num_experts,
                          first_held=r.first_held),
    )
    return cfg


def named(tree):
    return {"/".join(k.key for k in path): x
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def tokens(spec, B=2, S=24, seed=1):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0, spec.vocab_size)
    return {"tokens": tok, "labels": jnp.roll(tok, -1, axis=1)}


def test_forward_and_first_gradients_match_the_reference():
    spec = small_spec()
    cfg = config_of(spec)
    ref = harness.reference_module(cfg)
    model = SplittableModel(spec)
    key = jax.random.PRNGKey(3)
    mine, theirs = model.init_params(key), ref.init(cfg, key)
    a, b = named(mine), named(theirs)
    assert sorted(a) == sorted(b)
    for k in a:  # the reference draws the program's weights, bit for bit
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]), err_msg=k)
    batch = tokens(spec)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(model.forward)(mine, batch)
        want = jax.jit(lambda p, t: ref.logits(cfg, p, t))(theirs, batch["tokens"])
        g = jax.jit(jax.grad(model.loss_fn))(mine, batch)
        gr = jax.jit(jax.grad(lambda p, b: ref.loss(cfg, p, b)))(theirs, batch)
    # float32 throughout; only the order of the sums differs (split vs
    # concatenated rope scores, grouped vs dense experts): a few ulps of
    # logits of order 1
    np.testing.assert_allclose(np.asarray(logits[..., : spec.vocab_size]),
                               np.asarray(want), rtol=0, atol=1e-5)
    ng, nr = ref.named_norms(g), ref.named_norms(gr)
    assert sorted(ng) == sorted(nr)
    for k in nr:
        if k.startswith("units/moe/bias"):  # the selection bias gets no gradient
            assert float(ng[k]) == 0.0 and float(nr[k]) == 0.0
            continue
        # per-leaf gradient norms, float32 summation order only: 1e-4 relative
        np.testing.assert_allclose(float(ng[k]), float(nr[k]), rtol=1e-4, err_msg=k)


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    spec = get_reduced("moonlight-16b-a3b")  # every expert held
    r = spec.routed
    p = L.init_routed_moe(jax.random.PRNGKey(0), spec)
    # a zero gate and a bias that favours experts 3 and 5: all 96 tokens
    # send both choices there, 96 pairs per expert where a capacity of
    # 1.25 * 96 * 2 / 8 = 30 would drop most
    p["router"] = jnp.zeros_like(p["router"])
    p["bias"] = jnp.zeros_like(p["bias"]).at[jnp.array([3, 5])].set(1.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, spec.d_model))
    out = L.routed_moe(p, x, spec)
    xt = x.reshape(-1, spec.d_model)
    # scores are sigmoid(0) = 0.5 for both, normalised to 0.5 each
    want = L.mlp(p["shared"], xt)
    for e in (3, 5):
        want = want + 0.5 * r.routed_scale * L.mlp(
            {k: p[k][e] for k in ("w1", "w2", "w3")}, xt)
    # float32, the same products in another order
    np.testing.assert_allclose(np.asarray(out.reshape(-1, spec.d_model)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    whole = get_reduced("moonlight-16b-a3b")
    E = whole.routed.num_experts
    key = jax.random.PRNGKey(4)
    uncut = L.init_routed_moe(key, whole)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, whole.d_model))
    shared = L.mlp(uncut["shared"], x.reshape(-1, whole.d_model)).reshape(x.shape)
    total = shared
    for i in range(E):  # eight chips, one expert each
        spec = dataclasses.replace(whole, routed=dataclasses.replace(whole.routed, held=(i, i + 1)))
        part = L.init_routed_moe(key, spec)
        np.testing.assert_array_equal(np.asarray(part["w1"][0]), np.asarray(uncut["w1"][i]))
        # what every chip computes alike (the shared experts) counts once
        total = total + L.routed_moe(part, x, spec) - shared
    # float32: the eight partial sums add in another order
    np.testing.assert_allclose(np.asarray(total), np.asarray(L.routed_moe(uncut, x, whole)),
                               rtol=1e-5, atol=1e-5)


def test_the_chip_share_is_pinned_to_its_benchmark_configuration():
    cfg = harness.load_config("moonlight16b-a3b")
    spec = program.model_spec(cfg)  # the dense keys come from the file
    m, r = spec.mla, spec.routed
    pinned = {
        "hidden_size": spec.d_model, "intermediate_size": spec.d_ff,
        "num_attention_heads": spec.num_heads, "num_key_value_heads": spec.num_kv_heads,
        "num_hidden_layers": spec.num_layers, "vocab_size": spec.vocab_size,
        "rms_norm_eps": spec.norm_eps, "rope_theta": spec.rope_theta,
        "tie_word_embeddings": spec.tie_embeddings,
        "kv_lora_rank": m.kv_rank, "qk_nope_head_dim": m.nope_dim,
        "qk_rope_head_dim": m.rope_dim, "v_head_dim": m.v_dim,
        "moe_intermediate_size": r.d_expert, "num_experts_per_tok": r.top_k,
        "n_shared_experts": r.num_shared, "routed_scaling_factor": r.routed_scale,
        "norm_topk_prob": r.norm_topk, "n_routed_experts": r.n_held,
        "first_k_dense_replace": spec.first_dense, "selection_bias_std": L.SELECTION_BIAS_STD,
    }
    for key, value in pinned.items():
        assert cfg[key] == value, key
    share = cfg["expert_share"]
    assert (share["router_experts"], share["first_held"]) == (r.num_experts, r.first_held)
    assert spec.family == cfg["family"] == "mla_moe" and spec.n_units == 4
    # what the program assumes rather than reads
    assumed = {"q_lora_rank": None, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
               "n_group": 1, "topk_group": 1, "moe_layer_freq": 1, "hidden_act": "silu",
               "attention_bias": False, "num_nextn_predict_layers": 0}
    for key, value in assumed.items():
        assert cfg[key] == value, key
    # the reduced keys against the published model the share is cut from
    whole = get_spec("moonlight-16b-a3b")
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert whole.num_layers == 27 and whole.vocab_size == 163840 == 8 * cfg["vocab_size"]
    assert whole.routed.num_experts == share["router_experts"] == 64
    assert share["chips_per_layer"] * r.n_held == 64
    assert dataclasses.replace(whole.routed, held=r.held) == r
    assert (whole.mla, whole.first_dense, whole.d_ff) == (m, spec.first_dense, spec.d_ff)


@pytest.mark.parametrize("cuts", [(0, 2), (1, 2)])
def test_one_engine_a_round_with_cuts_across_the_dense_expert_boundary(cuts):
    spec = small_spec()
    model = SplittableModel(spec)
    N = 4
    # tier 1 every client alone (I_1 = 2), tier 2 over pairs of clients,
    # tier 3 over all four: after one round each tier shows its own level
    plan = default_plan(spec.n_units, N, cuts=cuts, intervals=(2, 2, 1), entities=(N, 2, 1))
    opt = sgd(0.05)
    state = init_state_a(model, plan, opt, jax.random.PRNGKey(0))
    batch = tokens(spec, B=N * 2, S=16, seed=2)
    batch = {k: v.reshape(N, 2, -1) for k, v in batch.items()}
    new, loss = jax.jit(build_train_step_a(model, plan, opt))(state, batch)
    p0 = jax.tree.map(lambda x: x[0], state.params)
    losses, grads = jax.vmap(jax.value_and_grad(model.loss_fn), in_axes=(None, 0))(p0, batch)
    local = jax.tree.map(lambda p, g: p[None] - 0.05 * g, p0, grads)  # [N, ...]
    pair = jax.tree.map(lambda x: jnp.repeat(x.reshape(2, 2, *x.shape[1:]).mean(1), 2, 0), local)
    every = jax.tree.map(lambda x: jnp.broadcast_to(x.mean(0), x.shape), local)
    # float32, one SGD step; only the order of the means differs
    close = dict(rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(loss), float(jnp.mean(losses)), rtol=1e-6)
    # the embedding and dense layer 0 live with the frontend: tier 1
    for a, b in zip(jax.tree.leaves(new.params["frontend"]), jax.tree.leaves(local["frontend"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **close)
    for u in range(spec.n_units):  # expert layers: tier 1, 2 or 3 by the cuts
        want = local if u < cuts[0] else pair if u < cuts[1] else every
        for a, b in zip(jax.tree.leaves(new.params["units"]), jax.tree.leaves(want["units"])):
            np.testing.assert_allclose(np.asarray(a[:, u]), np.asarray(b[:, u]), **close)
    for a, b in zip(jax.tree.leaves(new.params["head"]), jax.tree.leaves(every["head"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **close)


# --------------------------------------------------------------------------- #
# latent attention in the splash kernel against its dense lines
# --------------------------------------------------------------------------- #


def attn_spec():
    """Moonlight's head dims (query/key 128 + 64, value 128) on two heads."""
    return dataclasses.replace(small_spec(), num_heads=2, num_kv_heads=2,
                               mla=MlaSpec(kv_rank=32, nope_dim=128, rope_dim=64, v_dim=128))


def kernel_path(monkeypatch):
    """Send ``mla``'s whole sequences to the splash kernel off the TPU too,
    interpreted."""
    monkeypatch.setattr(L, "_takes_splash", lambda cache, S: cache is None and S % 128 == 0)
    monkeypatch.setattr(L, "mla_splash_attention",
                        partial(L.mla_splash_attention, interpret=True))


def client_vmap(f, cotangent):
    """A scalar of ``f`` under a client vmap and a per-unit remat."""
    return lambda *a: jnp.sum(jax.vmap(jax.checkpoint(f))(*a) * cotangent)


@pytest.mark.parametrize("part", ["forward", "gradients"])
@pytest.mark.parametrize("S", [256, 384])
def test_the_splash_kernel_matches_the_dense_attention(S, part):
    # 2 clients x 1 sequence; float32 operands off the TPU
    N, h = 2, 2
    shapes = [(N, 1, S, h, 128), (N, 1, S, h, 64), (N, 1, S, h, 128), (N, 1, S, 64),
              (N, 1, S, h, 128)]  # q_nope, q_pe, k_nope, k_pe, v
    args = [jax.random.normal(k, s) for k, s in zip(jax.random.split(jax.random.PRNGKey(S), 5),
                                                     shapes)]
    pos = jnp.arange(S)
    dense = lambda *a: L.mla_dense_attention(*a, pos, pos)
    kernel = lambda *a: L.mla_splash_attention(*a, interpret=True)
    if part == "forward":
        want, got = jax.vmap(dense)(*args), jax.vmap(kernel)(*args)
        assert got.shape == (N, 1, S, h * 128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-5)
        return
    ct = jax.random.normal(jax.random.PRNGKey(7), (N, 1, S, h * 128))
    want = jax.grad(client_vmap(dense, ct), range(5))(*args)
    got = jax.grad(client_vmap(kernel, ct), range(5))(*args)
    for name, a, b in zip(["q_nope", "q_pe", "k_nope", "k_pe", "v"], got, want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale,
                                   rtol=0, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("S", [256, 384])
def test_every_mla_weight_gets_the_dense_gradient_through_the_kernel(S, monkeypatch):
    spec = attn_spec()
    p = L.init_mla(jax.random.PRNGKey(1), spec)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 1, S, spec.d_model))
    ct = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    pos = jnp.arange(S)

    def grads():
        f = lambda p, x: L.mla(p, x, spec, pos)[0]
        return jax.grad(client_vmap(f, ct), (0, 1))(
            jax.tree.map(lambda w: jnp.broadcast_to(w, (2,) + w.shape), p), x)

    want = grads()
    with monkeypatch.context() as m:
        kernel_path(m)
        got = grads()
    flat_want, flat_got = named(want[0]), named(got[0])
    flat_want["x"], flat_got["x"] = want[1], got[1]
    for k in flat_want:
        scale = float(jnp.max(jnp.abs(flat_want[k]))) or 1.0
        np.testing.assert_allclose(np.asarray(flat_got[k]) / scale,
                                   np.asarray(flat_want[k]) / scale,
                                   rtol=0, atol=1e-5, err_msg=k)


def test_a_kernel_built_in_one_program_serves_the_next(monkeypatch):
    # the kernel's mask arrays are values of the trace that builds the
    # kernel: built inside a checkpoint under one jit, then a second
    # program (a remat'ed layer, then a scan over two layers) runs too
    kernel_path(monkeypatch)
    spec, S = attn_spec(), 128
    p = L.init_mla(jax.random.PRNGKey(1), spec)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, S, spec.d_model))
    layer = jax.checkpoint(lambda p, x: L.mla(p, x, spec, jnp.arange(S))[0])
    first = jax.jit(jax.grad(lambda p, x: jnp.sum(layer(p, x))))

    def second(p, x):
        x, _ = lax.scan(lambda x, _: (layer(p, x), None), layer(p, x), None, length=2)
        return jnp.sum(x)

    with jax.checking_leaks():  # no trace's value outlives it
        g1 = first(p, x)
        loss, g2 = jax.jit(jax.value_and_grad(second))(p, x)
    for leaf in jax.tree.leaves((g1, loss, g2)):
        assert bool(jnp.all(jnp.isfinite(leaf)))


def test_the_training_path_hands_mla_positions_0_to_s(monkeypatch):
    # the kernel masks by index, the dense lines by position: the two agree
    # because every whole-sequence caller passes positions 0..S-1
    spec = small_spec()
    model = SplittableModel(spec)
    seen, mla = [], L.mla

    def spy(params, x, spec, positions, cache=None):
        jax.debug.callback(lambda p: seen.append(np.asarray(p)), positions)
        return mla(params, x, spec, positions, cache)

    monkeypatch.setattr(L, "mla", spy)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = {k: v.reshape(2, 1, -1) for k, v in tokens(spec, B=2, S=24).items()}
    jax.block_until_ready(jax.jit(jax.vmap(jax.value_and_grad(model.loss_fn),
                                           in_axes=(None, 0)))(params, batch))
    assert len(seen) >= spec.first_dense + spec.n_units
    for p in seen:
        np.testing.assert_array_equal(p, np.arange(24))


def test_a_cache_or_an_untiled_length_keeps_the_dense_lines(monkeypatch):
    spec = attn_spec()
    p = L.init_mla(jax.random.PRNGKey(1), spec)
    called = []

    def kernel(q_nope, q_pe, k_nope, k_pe, v):
        called.append(q_nope.shape[1])
        return jnp.zeros(q_nope.shape[:2] + (q_nope.shape[2] * v.shape[-1],))

    monkeypatch.setattr(L, "mla_splash_attention", kernel)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def run(S, cache=None):
        x = jnp.zeros((1, S, spec.d_model))
        jax.eval_shape(lambda x: L.mla(p, x, spec, jnp.arange(S), cache), x)

    run(200)  # not a multiple of 128
    run(1, L.init_mla_cache(spec, 1, 256))  # decode
    assert called == []
    run(256)  # the kernel's case on the TPU
    assert called == [256]
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    run(256)  # off the TPU
    assert called == [256]
