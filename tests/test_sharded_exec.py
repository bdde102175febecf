"""Real multi-device execution tests for the perf-variant shardings.

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(conftest must NOT set it globally) and checks the seq-sharded KV-cache
decode (EXPERIMENTS.md sect. Perf / qwen3-decode) is bit-compatible with
the replicated-cache layout AND with unsharded single-device decode.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_reduced
    from repro.launch import sharding as sh
    from repro.launch.mesh import make_mesh
    from repro.models.model import SplittableModel

    assert len(jax.devices()) == 8
    spec = get_reduced("qwen2-1.5b")
    model = SplittableModel(spec)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    B, C = 16, 64
    tok = jax.random.randint(jax.random.fold_in(key, 1), (B, 1), 0,
                             spec.vocab_size)

    # reference: plain single-logical-device decode
    caches0 = model.init_caches(B, C)
    ref_logits, ref_caches = jax.jit(model.decode_step)(
        params, tok, caches0, jnp.int32(0)
    )

    mesh = make_mesh((2, 4), ("data", "model"))
    pps = sh.param_pspecs(params, tp=4, client_axes=None)
    params_sh = jax.device_put(params, sh.to_shardings(mesh, pps))
    outs = {}
    for seq_shard in (False, True):
        cps = sh.cache_pspecs(
            jax.eval_shape(lambda: model.init_caches(B, C)),
            batch=B, client_axes=("data",), tp=4, seq_shard=seq_shard,
        )
        caches = jax.device_put(model.init_caches(B, C),
                                sh.to_shardings(mesh, cps))
        f = jax.jit(model.decode_step)
        logits, ncaches = f(params_sh, jax.device_put(tok), caches,
                            jnp.int32(0))
        outs[seq_shard] = np.asarray(logits)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits), rtol=2e-5, atol=2e-5,
            err_msg=f"seq_shard={seq_shard} diverges from reference",
        )
    np.testing.assert_allclose(outs[False], outs[True], rtol=2e-5, atol=2e-5)
    print("SHARDED-DECODE-OK")
""")


@pytest.mark.slow
def test_seq_sharded_cache_decode_equivalence():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED-DECODE-OK" in out.stdout


MOE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_reduced
    from repro.launch.mesh import make_mesh
    from repro.models import layers as L

    spec = get_reduced("granite-moe-1b-a400m")
    ms = dataclasses.replace(spec.moe, capacity_factor=8.0)  # no drops
    spec = dataclasses.replace(spec, moe=ms)
    p = L.init_moe(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, spec.d_model))
    ref, _ = L.moe(p, x, spec, groups=1)

    mesh = make_mesh((2, 4), ("data", "model"))
    def constraint(b):
        g, e = b.shape[0], b.shape[1]
        pg = "data" if g % 2 == 0 else None
        pe = "model" if e % 4 == 0 else None
        return jax.lax.with_sharding_constraint(
            b, NamedSharding(mesh, P(pg, pe, None, None)))
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    out, _ = jax.jit(
        lambda p_, x_: L.moe(p_, x_, spec, constraint=constraint, groups=2)
    )(p, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    print("SHARDED-MOE-OK")
""")


@pytest.mark.slow
def test_grouped_moe_sharded_equivalence():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", MOE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED-MOE-OK" in out.stdout


ENGINE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.configs import get_reduced
    from repro.configs.shapes import concrete_inputs
    from repro.core.async_agg import make_async_trainer
    from repro.core.engine import build_train_step_a, init_state_a
    from repro.core.tiers import GuardSpec, default_plan
    from repro.launch.mesh import make_debug_mesh
    from repro.models.model import SplittableModel
    from repro.optim import sgd
    from repro.compress import Int8Stochastic

    assert len(jax.devices()) == 4
    N, R = 8, 4
    spec = get_reduced("smollm-135m")
    model = SplittableModel(spec)
    opt = sgd(1e-2)
    # entities (8, 2, 1): tier 0's 8 groups land device-local on D=4,
    # tier 1's 2 groups force the matmul-shaped cross-device path
    plan = default_plan(spec.n_units, N, cuts=(1, 2), intervals=(2, 2, 1),
                        entities=(N, 2, 1))
    mesh = make_debug_mesh(data=4, model=1)

    batches, masks = [], []
    for r in range(R):
        b = concrete_inputs(spec, N * 2, 16, jax.random.PRNGKey(r))
        batches.append(jax.tree.map(
            lambda x: x.reshape((N, 2) + x.shape[1:]), b
        ))
        masks.append((jnp.arange(N) % 3 != r % 3).astype(jnp.float32))

    def fed(r):
        return tuple((r + 1) % I == 0 if I > 1 else True
                     for I in plan.intervals)

    def run(sharded, **kw):
        with_mask = kw.get("with_mask", False)
        m = mesh if sharded else None
        state = init_state_a(model, plan, opt, jax.random.PRNGKey(0), mesh=m)
        mk = lambda f: jax.jit(build_train_step_a(
            model, plan, opt, fed_round=f, mesh=m, **kw))
        steps, losses = {}, []
        for r in range(R):
            f = fed(r)
            if f not in steps:
                steps[f] = mk(f)
            args = (state, batches[r]) + ((masks[r],) if with_mask else ())
            state, loss = steps[f](*args)
            losses.append(float(loss))
        return losses, state.params

    configs = {
        "plain": {},
        "mask": dict(with_mask=True),
        "compress": dict(compressor=Int8Stochastic(tile=128)),
        "guard+mask": dict(with_mask=True, guard=GuardSpec()),
    }
    for name, kw in configs.items():
        ref_losses, ref_params = run(False, **kw)
        sh_losses, sh_params = run(True, **kw)
        np.testing.assert_allclose(
            sh_losses, ref_losses, rtol=2e-5,
            err_msg=f"{name}: sharded losses diverge",
        )
        # the quantized wire amplifies reduction-order noise: a value that
        # lands on the other side of an int8 rounding boundary jumps a
        # full quant step, so the compressed config gets a step-sized atol
        atol = 2e-3 if name == "compress" else 2e-6
        for a, b in zip(jax.tree.leaves(sh_params),
                        jax.tree.leaves(ref_params)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=atol,
                err_msg=f"{name}: sharded params diverge",
            )
        print(f"config {name}: sharded == single-host")

    # async over the sharded step: s=0 is bit-identical to the sharded
    # sync dispatch (the same shard_map programs run in the same order)
    _, sync_params = run(True)
    tr = make_async_trainer(model, plan, opt, staleness=0, mesh=mesh)
    state = init_state_a(model, plan, opt, jax.random.PRNGKey(0), mesh=mesh)
    for r in range(R):
        state, _ = tr.run_round(state, batches[r], r)
    assert not tr.pending
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(sync_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # s=1: defer, then drain right at the due round — equivalent to the
    # in-step fed levels up to cross-device reduction order
    tr1 = make_async_trainer(model, plan, opt, staleness=1, mesh=mesh)
    state = init_state_a(model, plan, opt, jax.random.PRNGKey(0), mesh=mesh)
    for r in range(2):
        state, loss = tr1.run_round(state, batches[r], r)
        assert np.isfinite(float(loss))
    assert {p.tier for p in tr1.pending} == {0, 1}
    state = tr1.drain(state)
    # reference: the sharded sync engine over the same 2 rounds
    st = init_state_a(model, plan, opt, jax.random.PRNGKey(0), mesh=mesh)
    steps = {}
    for r in range(2):
        f = fed(r)
        if f not in steps:
            steps[f] = jax.jit(build_train_step_a(
                model, plan, opt, fed_round=f, mesh=mesh))
        st, _ = steps[f](st, batches[r])
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(st.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)

    # api.run's mesh branch: the same spec sharded over data=4 and on
    # one device (tier 1's 2 edges span shards: the einsum + psum level)
    from repro.api import (
        ExperimentSpec, ModelCfg, RunCfg, SolverCfg, SystemCfg, run as api_run,
    )
    from repro.api.spec import ShardingCfg

    def api_losses(sharding):
        spec = ExperimentSpec(
            model=ModelCfg(arch="smollm-135m", variant="reduced", batch=2,
                           seq=8),
            system=SystemCfg(preset="paper-three-tier", num_clients=N,
                             num_edges=2, seed=0),
            solver=SolverCfg(kind="fixed", cuts=(1, 2), intervals=(2, 2, 1)),
            run=RunCfg(mode="train", seed=0, rounds=R, lr=1e-2,
                       dataset_size=32, sharding=sharding),
        )
        return api_run(spec).train

    sh_run, ref_run = api_losses(ShardingCfg(data=4)), api_losses(None)
    assert sh_run["sharding"]["client_shards"] == 4
    assert "sharding" not in ref_run
    np.testing.assert_allclose(
        sh_run["losses"], ref_run["losses"], rtol=2e-5,
        err_msg="api.run: sharded losses diverge",
    )
    print("SHARDED-ENGINE-OK")
""")


@pytest.mark.slow
def test_sharded_engine_a_equivalence():
    """The Engine-A step on a 4-device mesh == on one device across mask x
    compression x guard, the async trainer's staleness-0 bit-exact collapse
    on the mesh, and api.run's mesh branch against its unsharded run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", ENGINE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED-ENGINE-OK" in out.stdout
