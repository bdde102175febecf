"""The program's tracing vocabulary (``repro.obs``).

The phase scopes must be metadata only: the Engine-A round compiled with
them and with every scope replaced by a null context is the same optimized
HLO once op metadata is stripped.  A scope that restructured the program
(a loop split per tier, a barrier, a checkpoint) fails here.  Latent
attention's kernel, whose backward pass runs inside the kernel's own
``custom_vjp``, carries its phase in every op, forward and backward.
"""
import contextlib
import functools
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_reduced
from repro.configs.shapes import concrete_inputs
from repro.core import build_train_step_a, init_state_a
from repro.core.tiers import default_plan
from repro.data import FederatedLoader
from repro.models import layers as L
from repro.models.model import SplittableModel
from repro.models.vgg import build_model
from repro.optim import sgd

N = 4
METADATA = re.compile(r',?\s*metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
# the source-location tables the metadata's stack_frame_ids point into
LOCATIONS = re.compile(r'^(FileNames|FunctionNames|FileLocations|StackFrames)$|^\d+ (\{.*\}|".*")$')
ROUNDS = {"local": (False, False, True), "full_fed": (True, True, True)}


def _vgg():
    spec = get_reduced("vgg16-cifar10")
    model = build_model(spec)
    plan = default_plan(spec.n_units, N, cuts=(1, 3), intervals=(4, 2, 1),
                        entities=(N, 2, 1))
    s = spec.image_size
    batch = {"images": jnp.zeros((N, 2, s, s, spec.in_channels), jnp.float32),
             "labels": jnp.zeros((N, 2), jnp.int32)}
    return model, plan, batch


def _lm():
    spec = get_reduced("smollm-135m")
    model = SplittableModel(spec)
    plan = default_plan(spec.n_units, N, cuts=(1, 2), intervals=(3, 2, 1),
                        entities=(N, 2, 1))
    batch = concrete_inputs(spec, N * 2, 8, jax.random.PRNGKey(1))
    batch = {k: v.reshape(N, 2, *v.shape[1:]) for k, v in batch.items()}
    return model, plan, batch


@pytest.fixture
def no_compile_cache():
    # JAX keeps op metadata out of the persistent cache's key: a cached
    # executable would serve the second compile and hide any difference
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _optimized_hlo(make, fed):
    model, plan, batch = make()
    opt = sgd(1e-2)
    state = jax.eval_shape(lambda: init_state_a(model, plan, opt, jax.random.PRNGKey(0)))
    step = build_train_step_a(model, plan, opt, fed_round=fed)

    def hsfl_round(state, batch):
        return step(state, batch)

    return jax.jit(hsfl_round).lower(state, batch).compile().as_text()


def _without_metadata(hlo):
    return "\n".join(line for line in METADATA.sub("", hlo).splitlines()
                     if not LOCATIONS.match(line))


@pytest.mark.parametrize("round_", sorted(ROUNDS))
@pytest.mark.parametrize("make", [_vgg, _lm], ids=["vgg", "lm"])
def test_scopes_leave_the_compiled_round_unchanged(make, round_, monkeypatch,
                                                   no_compile_cache):
    fed = ROUNDS[round_]
    named = _optimized_hlo(make, fed)
    with monkeypatch.context() as m:
        m.setattr(obs, "scope", lambda name: contextlib.nullcontext())
        plain = _optimized_hlo(make, fed)
    # the names reached the program, and only as metadata
    # (tier 1 holds one client per entity: no entity level of its own)
    for phase in (obs.GRAD, obs.OPT, "hsfl.sync.t2.entity", "hsfl.sync.t3.entity",
                  "hsfl.sync.t3.fed"):
        assert phase in named, phase
    fed_levels = ("hsfl.sync.t1.fed" in named, "hsfl.sync.t2.fed" in named)
    assert fed_levels == ((True, True) if round_ == "full_fed" else (False, False))
    assert "hsfl." not in plain
    assert "metadata=" not in _without_metadata(named)
    assert _without_metadata(named) == _without_metadata(plain)


def test_the_mesh_round_carries_every_phase():
    # the same step under shard_map names the same phases as on one host
    from repro.launch.mesh import make_debug_mesh

    model, plan, batch = _lm()
    opt = sgd(1e-2)
    state = jax.eval_shape(lambda: init_state_a(model, plan, opt, jax.random.PRNGKey(0)))
    step = build_train_step_a(model, plan, opt, fed_round=ROUNDS["full_fed"],
                              mesh=make_debug_mesh(data=1, model=1))
    text = jax.jit(step).lower(state, batch).as_text(debug_info=True)
    assert "/shard_map" in text
    for phase in (obs.GRAD, obs.OPT, "hsfl.sync.t1.fed", "hsfl.sync.t2.entity",
                  "hsfl.sync.t2.fed", "hsfl.sync.t3.entity", "hsfl.sync.t3.fed"):
        assert phase in text, phase


def test_the_splash_kernel_runs_inside_the_attention_phase(monkeypatch):
    # one mla call of two clients on the kernel path (interpreted off the
    # TPU), forward and backward: the forward, dq and dkv kernels' every op
    monkeypatch.setattr(L, "_takes_splash", lambda cache, S: cache is None)
    monkeypatch.setattr(L, "mla_splash_attention",
                        functools.partial(L.mla_splash_attention, interpret=True))
    spec = get_reduced("moonlight-16b-a3b-ep8")
    p = L.init_mla(jax.random.PRNGKey(0), spec)
    x = jnp.zeros((N, 1, 128, spec.d_model))

    def loss(p, x):
        with obs.scope(obs.GRAD):
            f = lambda x: L.mla(p, x, spec, jnp.arange(128))[0]
            return jnp.sum(jax.vmap(jax.checkpoint(f))(x))

    hlo = jax.jit(jax.grad(loss)).lower(p, x).compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', hlo) if "splash_mha_" in n]
    kernels = {re.search(r"splash_mha_(fwd|dq|dkv)", n).group(1) for n in names}
    assert kernels == {"fwd", "dq", "dkv"}
    # the innermost phase of each op's path is the one it is charged to
    innermost = {re.findall(r"hsfl\.[\w.]+", n)[-1] for n in names}
    assert innermost == {obs.MLA_ATTN}
    assert all(obs.MLA in n.split(obs.MLA_ATTN)[0] for n in names)


def test_sync_level_names():
    # tier 1 of the paper's plan: entity level, then its fed server
    assert obs.sync_level(0, 0, 2) == "hsfl.sync.t1.entity"
    assert obs.sync_level(0, 1, 2) == "hsfl.sync.t1.fed"
    # a single-level top tier is its own fed server
    assert obs.sync_level(2, 0, 1) == "hsfl.sync.t3.fed"


@pytest.fixture
def empty_spans():
    obs.host_spans()
    yield
    obs.host_spans()


def test_host_spans_are_bounded_and_cleared_on_read(empty_spans):
    for i in range(obs.MAX_HOST_SPANS + 10):
        with obs.host_span(f"s{i}"):
            pass
    spans = obs.host_spans()
    assert len(spans) == obs.MAX_HOST_SPANS
    # the oldest are dropped
    assert spans[0][0] == "s10" and spans[-1][0] == f"s{obs.MAX_HOST_SPANS + 9}"
    assert obs.host_spans() == []


def test_host_spans_are_stamped_on_the_wall_clock(empty_spans):
    t0 = time.time_ns()
    with obs.host_span("x"):
        time.sleep(0.002)
    t1 = time.time_ns()
    ((name, a, b),) = obs.host_spans()
    assert name == "x" and t0 <= a < b <= t1 and b - a >= 2_000_000


def test_host_span_records_when_the_block_raises(empty_spans):
    with pytest.raises(ValueError):
        with obs.host_span("x"):
            raise ValueError
    assert [s[0] for s in obs.host_spans()] == ["x"]


def test_loader_rounds_are_loader_spans(empty_spans):
    rows = np.arange(40, dtype=np.float32)
    loader = FederatedLoader({"x": rows}, [np.arange(20), np.arange(20, 40)], 3, seed=0)
    for _ in range(3):
        assert loader.next_round()["x"].shape == (2, 3)
    spans = obs.host_spans()
    assert [s[0] for s in spans] == [obs.LOADER] * 3
    assert all(a <= b for _, a, b in spans)
    assert spans[0][2] <= spans[1][1]
