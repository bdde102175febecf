"""The ``mla_moe`` cell's benchmark files: ``bench/flops_mla_moe.py``
against counts made by hand, its metric readers on records with and
without the phases, and its comparison that decides ``correct``, driven
through a whole run at a size a CPU holds (the look for a chip skipped)."""
import dataclasses
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import check, flops_mla_moe, harness, mla_moe, phase_time  # noqa: E402

CELL = "moonlight16b.train.seq2048"
CFG = harness.load_config("moonlight16b-a3b")
READERS = ["mla_device_ms", "moe_device_ms", "moe_route_device_ms",
           "expert_gmm_roofline", "train_mfu.mla_moe"]


def reader(name):
    return harness.load_module(ROOT / "bench/metrics" / f"{name}.py")


def test_one_expert_layer_by_hand():
    seq = 2048
    proj = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert proj == 13_762_560
    # query i sees i + 1 keys: scores over 192 dims and values of 128, 16 heads
    attn = sum(2 * 16 * (192 + 128) * (i + 1) for i in range(seq))
    assert flops_mla_moe.mla_fwd_flops(CFG, seq) == pytest.approx(2 * proj * seq + attn, rel=1e-12)
    parts = flops_mla_moe.expert_layer_fwd_flops(CFG, seq)
    assert parts["router"] == 2 * seq * 2048 * 64
    # 6 choices of 64 experts, 8 held here: 1536 pairs of 3 matmuls 2048 x 1408
    assert parts["experts"] == 1536 * 2 * 3 * 2048 * 1408
    assert parts["shared"] == 2 * seq * 3 * 2048 * 2 * 1408
    per_token = sum(parts.values()) / seq
    assert per_token == pytest.approx(85.85728e6, rel=1e-6)
    dense = flops_mla_moe.dense_layer_fwd_flops(CFG, seq) / seq
    assert dense == pytest.approx(176.428032e6, rel=1e-6)
    total = flops_mla_moe.fwd_flops_per_sequence(CFG, seq)
    assert total == pytest.approx(seq * (dense + 4 * per_token + 2 * 2048 * 20480), rel=1e-12)
    assert total / seq == pytest.approx(603.743232e6, rel=1e-6)
    # a round: 2 clients x 2048 tokens, three times the forward
    assert 2 * flops_mla_moe.train_flops_per_sequence(CFG, seq) == pytest.approx(7.4188e12, rel=1e-4)


def test_the_grouped_products_work_by_hand():
    w = flops_mla_moe.expert_gmm_work(CFG, 2048)
    weights = 8 * 3 * 2048 * 1408 * 4  # held experts, float32
    acts = 1536 * (2 * 2048 + 3 * 1408) * 4  # rows in and out, h1, h3, their product
    assert w["bytes"] == 3 * (weights + acts)
    assert w["flops"] == 3 * 1536 * 2 * 3 * 2048 * 1408


@pytest.mark.parametrize("held", [1, 4, 8, 16, 64])
def test_held_pairs_scale_with_the_share_held(held):
    cfg = dict(CFG, n_routed_experts=held)
    assert flops_mla_moe.held_pairs(cfg, 2048) == pytest.approx(2048 * 6 * held / 64)
    base = flops_mla_moe.expert_layer_fwd_flops(dict(CFG, n_routed_experts=1), 2048)
    assert flops_mla_moe.expert_layer_fwd_flops(cfg, 2048)["experts"] == pytest.approx(
        held * base["experts"])


@pytest.mark.parametrize("name", READERS)
def test_the_readers_find_nothing_without_phases(name):
    rec = SimpleNamespace(trace=None, counters={}, window_s=None, peaks=None, chips=1)
    assert reader(name).read(rec) is None


def _traced(monkeypatch, phases):
    red = {"programs": {phase_time.LOCAL_PROGRAM: {"phases": phases, "closure": 0.0}}}
    monkeypatch.setattr(phase_time, "for_record", lambda rec: red)
    monkeypatch.setattr(mla_moe, "traced_workload", lambda: harness.load_workload(CELL))
    return SimpleNamespace(trace={}, window_s=10.0, chips=1,
                           peaks=harness.load_peaks("TPU v5 lite"),
                           counters={"samples": 200, "tokens_per_sample": 2048})


def test_the_readers_of_a_traced_run(monkeypatch):
    rec = _traced(monkeypatch, {"hsfl.grad": 40.0, "hsfl.grad.mla": 30.0,
                                "hsfl.grad.moe.route": 5.0, "hsfl.grad.moe.experts": 20.0,
                                "hsfl.grad.moe.shared": 8.0, "hsfl.opt": 9.0})
    assert reader("mla_device_ms").read(rec) == 30.0
    assert reader("moe_device_ms").read(rec) == 33.0
    assert reader("moe_route_device_ms").read(rec) == 5.0
    w = flops_mla_moe.expert_gmm_work(CFG, 2048)
    least = 8 * max(w["flops"] / 197e12, w["bytes"] / 819e9)  # 4 layers x 2 clients
    assert reader("expert_gmm_roofline").read(rec) == pytest.approx(100 * least / 0.020)
    flops = 200 * flops_mla_moe.train_flops_per_sequence(CFG, 2048)
    assert reader("train_mfu.mla_moe").read(rec) == pytest.approx(100 * flops / 10.0 / 197e12)


def test_the_readers_find_nothing_where_the_program_names_no_sub_layer(monkeypatch):
    rec = _traced(monkeypatch, {"hsfl.grad": 90.0, "hsfl.opt": 9.0})
    for name in READERS[:4]:
        assert reader(name).read(rec) is None


# --------------------------------------------------------------------------- #
# the comparison that decides `correct`, at a size a CPU holds
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def tiny(request):
    """The cell at the registered reduced share (experts 0-1 of 8 held),
    three expert layers, 4 clients: the program's spec for the cell's arch
    is the reduced one while the module's tests run."""
    import repro.configs as configs

    spec = configs.get_reduced("moonlight-16b-a3b-ep8")
    spec = dataclasses.replace(spec, routed=dataclasses.replace(spec.routed, held=(0, 2)))
    m, r = spec.mla, spec.routed
    cfg = dict(CFG)
    cfg.update(hidden_size=spec.d_model, num_attention_heads=spec.num_heads,
               num_key_value_heads=spec.num_kv_heads, intermediate_size=spec.d_ff,
               vocab_size=spec.vocab_size, num_hidden_layers=4, kv_lora_rank=m.kv_rank,
               qk_nope_head_dim=m.nope_dim, qk_rope_head_dim=m.rope_dim,
               v_head_dim=m.v_dim, moe_intermediate_size=r.d_expert,
               n_routed_experts=r.n_held, num_experts_per_tok=r.top_k,
               expert_share=dict(CFG["expert_share"], router_experts=r.num_experts),
               plan={"clients": 4, "edges": 2, "cuts": [1, 2], "intervals": [4, 2, 1]})
    mp = pytest.MonkeyPatch()
    get_spec = configs.get_spec
    mp.setattr(configs, "get_spec", lambda a: spec if a == cfg["arch"] else get_spec(a))
    yield dict(name="tiny.mla_moe", config=CFG["name"], config_data=cfg, mode="train",
               chips=1, traffic={"batch": 2, "seq": 32, "samples": 64},
               limits=harness.load_workload(CELL)["limits"])
    mp.undo()


def _run(wl, program=None):
    import jax
    from bench.run import run_cell

    return run_cell(wl, 7, 0.3, False, time.time(), jax.devices(), program=program)


def test_a_sound_run_passes_and_the_control_fails(tiny):
    import jax.numpy as jnp

    rec = _run(tiny)
    assert rec.correct, rec.check
    assert rec.attempted > 0 and rec.compiles_in_window == 0
    cfg = tiny["config_data"]
    ctrl = check.hsfl_reference(harness.reference_module(cfg), cfg, cfg["plan"],
                                cfg["optimizer"]["lr"], rec.check_inputs["batches"], 7,
                                dtype=jnp.bfloat16)
    numbers = check.train_numbers(ctrl, rec.check_inputs["reference"])
    assert any(numbers[k] > v for k, v in tiny["limits"].items()), numbers


def test_half_of_each_batch_left_out_fails(tiny):
    def half_batch(step):
        return lambda state, batch: step(
            state, {k: v[:, : v.shape[1] // 2] for k, v in batch.items()})

    rec = _run(tiny, {"train_step": half_batch})
    assert not rec.correct, rec.check


def test_half_of_each_sequence_left_out_fails(tiny):
    """The fault ``bench/fault_half_tokens.py`` reads at the cell's shapes,
    where one row a client leaves the half-batch fault no step to take:
    planted in the program it fails the check, and read off the reference
    it lies past every limit."""
    from bench.fault_half_tokens import half_tokens

    def half_sequence(step):
        return lambda state, batch: step(
            state, {k: v[..., : v.shape[-1] // 2] for k, v in batch.items()})

    rec = _run(tiny, {"train_step": half_sequence})
    assert not rec.correct, rec.check
    cfg, inp = tiny["config_data"], rec.check_inputs
    assert half_tokens(inp["batches"])[0]["tokens"].shape[-1] == tiny["traffic"]["seq"] // 2
    fault = check.hsfl_reference(harness.reference_module(cfg), cfg, cfg["plan"],
                                 cfg["optimizer"]["lr"], half_tokens(inp["batches"]), 7)
    numbers = check.train_numbers(fault, inp["reference"])
    assert all(numbers[k] > v for k, v in tiny["limits"].items()), numbers
