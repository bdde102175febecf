"""PartitionSpec rules (pure functions — no devices required)."""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced, get_spec
from repro.configs.shapes import sds
from repro.launch import sharding as sh
from repro.models.model import SplittableModel


def abstract_params(arch, client=None):
    spec = get_reduced(arch)
    model = SplittableModel(spec)
    p = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    if client:
        p = jax.tree.map(lambda s: sds((client,) + s.shape, s.dtype), p)
    return p


def test_dense_tp_rules():
    p = abstract_params("smollm-135m", client=16)
    pps = sh.param_pspecs(p, tp=16, client_axes=("data",))
    # units stacked [N, U, ...]: wq shards its output dim when divisible
    wq = pps["units"]["attn"]["wq"]
    assert wq[0] == "data"
    emb = pps["frontend"]["embed"]
    assert emb[0] == "data" and emb[1] == "model"  # vocab sharded
    norm = pps["units"]["attn"]["norm"]
    assert norm[0] == "data" and all(e is None for e in norm[1:])


def test_wq_shards_when_divisible():
    # reduced smollm has tiny dims; check the full spec instead
    spec = get_spec("qwen2.5-14b")
    model = SplittableModel(spec)
    p = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    pps = sh.param_pspecs(p, tp=16, client_axes=None)
    assert pps["units"]["attn"]["wq"][-1] == "model"   # 5120 % 16 == 0
    assert pps["units"]["attn"]["wo"][-2] == "model"
    assert pps["units"]["mlp"]["w2"][-2] == "model"
    assert pps["frontend"]["embed"][0] == "model"


def test_moe_expert_parallelism():
    spec = get_spec("phi3.5-moe-42b-a6.6b")  # 16 experts == tp
    model = SplittableModel(spec)
    p = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    pps = sh.param_pspecs(p, tp=16, client_axes=None)
    w1 = pps["units"]["moe"]["w1"]
    assert w1[-3] == "model"  # expert axis
    spec32 = get_spec("granite-moe-1b-a400m")  # 32 experts
    p32 = jax.eval_shape(SplittableModel(spec32).init_params, jax.random.PRNGKey(0))
    assert sh.param_pspecs(p32, tp=16, client_axes=None)["units"]["moe"]["w1"][-3] == "model"


def test_indivisible_dims_stay_replicated():
    p = abstract_params("smollm-135m")  # reduced: d_model 576? reduced <=512
    pps = sh.param_pspecs(p, tp=16, client_axes=None)
    # reduced dims often don't divide 16; whatever doesn't divide is None
    def ok(path_pps, shapes):
        for ps, leaf in zip(jax.tree.leaves(path_pps, is_leaf=lambda x: isinstance(x, P)),
                            jax.tree.leaves(shapes)):
            for ax, name in zip(leaf.shape, ps):
                if name == "model":
                    assert ax % 16 == 0
    ok(pps, p)


def test_multipod_client_axes():
    p = abstract_params("qwen2-1.5b", client=32)
    pps = sh.param_pspecs(p, tp=16, client_axes=("pod", "data"))
    wq = pps["units"]["attn"]["wq"]
    assert wq[0] == ("pod", "data")


def test_batch_and_token_pspecs():
    batch = {"tokens": sds((16, 16, 128), jnp.int32)}
    bps = sh.batch_pspecs(batch, ("data",))
    assert bps["tokens"] == P("data", None, None)
    assert sh.token_pspec(128, ("data",)) == P("data", None)
    assert sh.token_pspec(1, ("data",)) == P(None, None)


def test_cache_pspecs_decode_vs_long():
    spec = get_spec("qwen3-32b")
    model = SplittableModel(spec)
    caches = jax.eval_shape(lambda: model.init_caches(128, 1024))
    cps = sh.cache_pspecs(caches, batch=128, client_axes=("data",), long_context=False)
    k = jax.tree_util.tree_map_with_path(lambda p, l: l, cps)
    # batch axis sharded in decode mode
    flat = jax.tree.leaves(cps, is_leaf=lambda x: isinstance(x, P))
    assert any("data" in [e for e in ps if e] for ps in flat if ps)
    long = sh.cache_pspecs(
        jax.eval_shape(lambda: model.init_caches(1, 1024)),
        batch=1, client_axes=("data",), long_context=True,
    )
    flatl = jax.tree.leaves(long, is_leaf=lambda x: isinstance(x, P))
    assert any("data" in [e for e in ps if e] for ps in flatl if ps)  # seq dim


def test_train_pspecs_client_axis_only():
    """The Engine-A step on a mesh shards ONLY the client
    axis: no leaf may pick up a ``model`` entry (the param_pspecs(tp=1)
    pitfall its docstring documents)."""
    p = abstract_params("smollm-135m", client=8)
    pps = sh.train_pspecs(p, ("data",), num_clients=8)
    flat = jax.tree.leaves(pps, is_leaf=lambda x: isinstance(x, P))
    assert all(ps[0] == "data" for ps in flat)
    assert all(all(e is None for e in ps[1:]) for ps in flat)
    # multi-pod: the client axis spans both mesh axes
    pps2 = sh.train_pspecs(p, ("pod", "data"), num_clients=8)
    assert all(
        ps[0] == ("pod", "data")
        for ps in jax.tree.leaves(pps2, is_leaf=lambda x: isinstance(x, P))
    )


def test_train_pspecs_replicates_non_client_leaves():
    tree = {
        "stacked": sds((8, 3, 4), jnp.float32),
        "scalar": sds((), jnp.float32),
        "counter": sds((3,), jnp.int32),  # leading dim != num_clients
    }
    pps = sh.train_pspecs(tree, ("data",), num_clients=8)
    assert pps["stacked"] == P("data", None, None)
    assert pps["scalar"] == P()
    assert pps["counter"] == P()
    # num_clients=None: every non-scalar leaf is treated as stacked
    loose = sh.train_pspecs(tree, ("data",))
    assert loose["counter"] == P("data")


def test_make_debug_mesh_too_few_devices_fails_loudly():
    """The device count is fixed at backend init — asking for more must
    raise an actionable error, not silently build a smaller mesh (this
    test process initialized jax without XLA_FLAGS)."""
    import pytest

    from repro.launch.mesh import make_debug_mesh

    with pytest.raises(
        RuntimeError, match="xla_force_host_platform_device_count"
    ):
        make_debug_mesh(data=1024, model=2)


def test_opt_pspecs_follow_params():
    p = abstract_params("qwen2-1.5b", client=4)
    pps = sh.param_pspecs(p, tp=16, client_axes=("data",))
    assert sh.opt_pspecs(None, pps, "sgd") == ()
    assert sh.opt_pspecs(None, pps, "momentum") is pps
    a = sh.opt_pspecs(None, pps, "adam")
    assert a["m"] is pps and a["t"] == P()
