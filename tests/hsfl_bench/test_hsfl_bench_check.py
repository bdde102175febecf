"""The comparison that decides ``correct``, driven through a whole run of
each mode at a size a CPU holds (the look for a chip skipped): sound runs
pass, runs with the timed path broken underneath fail, and the control
(the reference in bfloat16 put in the program's place) fails the cells'
limits."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import check, harness  # noqa: E402
from bench.run import run_cell  # noqa: E402

PLAN = {"clients": 4, "edges": 2, "cuts": [1, 3], "intervals": [4, 2, 1]}


def tiny(config, **sizes):
    cfg = dict(harness.load_config(config))
    cfg.update(sizes, plan=PLAN)
    return cfg


TINY = {
    "vgg": tiny("vgg16-cifar10", conv_channels=[8, 8, 16], pool_after=[0, 2],
                fc_dims=[16, 10]),
    "lm": tiny("smollm135m", vocab_size=512, hidden_size=64, intermediate_size=128,
               num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2),
}
CELL = {"vgg": "vgg16.train.paper", "lm": "smollm135m.train.seq256"}
TRAFFIC = {"vgg": {"batch": 4, "samples": 256},
           "lm": {"batch": 2, "seq": 32, "samples": 64}}


def train_cell(kind):
    return dict(name=f"tiny.{kind}", config=CELL[kind], config_data=TINY[kind],
                mode="train", chips=1, traffic=TRAFFIC[kind],
                limits=harness.load_workload(CELL[kind])["limits"])


def run(wl, program=None, seed=7, seconds=0.3):
    return run_cell(wl, seed, seconds, False, time.time(), jax.devices(), program=program)


def state_unchanged(step):
    return lambda state, batch: (state, step(state, batch)[1])


def half_batch(step):
    return lambda state, batch: step(
        state, {k: v[:, : v.shape[1] // 2] for k, v in batch.items()})


@pytest.mark.parametrize("kind", ["vgg", "lm"])
def test_reference_draws_the_programs_weights(kind):
    from bench import program
    from repro.models.vgg import build_model

    cfg = TINY[kind]
    key = jax.random.PRNGKey(11)
    mine = build_model(program.model_spec(cfg)).init_params(key)
    ref = harness.reference_module(cfg).init(cfg, key)
    if kind == "vgg":
        pairs = [(mine["units"][u][k], ref[u][k]) for u in range(len(ref)) for k in "wb"]
    else:
        pairs = [(mine["frontend"]["embed"], ref["embed"]),
                 (mine["units"]["attn"]["wq"], ref["layers"]["wq"]),
                 (mine["units"]["mlp"]["w2"], ref["layers"]["w2"])]
    for a, b in pairs:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", ["vgg", "lm"])
@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
def test_train_faults_fail(kind, fault):
    wrap = {None: None, "state_unchanged": state_unchanged, "half_batch": half_batch}[fault]
    rec = run(train_cell(kind), {"train_step": wrap} if wrap else None)
    numbers = {k: v for k, v, _ in rec.check}
    assert rec.correct == (fault is None), numbers
    assert rec.attempted > 0 and rec.compiles_in_window == 0


@pytest.mark.parametrize("kind", ["vgg", "lm"])
def test_train_control_fails_the_limits(kind):
    rec = run(train_cell(kind))
    inp = rec.check_inputs
    cfg = TINY[kind]
    ctrl = check.hsfl_reference(harness.reference_module(cfg), cfg, PLAN,
                                cfg["optimizer"]["lr"], inp["batches"], 7,
                                dtype=jnp.bfloat16)
    numbers = check.train_numbers(ctrl, inp["reference"])
    limits = train_cell(kind)["limits"]
    assert any(numbers[k] > limits[k] for k in limits), numbers
