"""The trace reduction (bench/trace_reduce.py) on intervals worked out by
hand, and on a small trace recorded on a TPU v5e (data/tiny_vgg.xplane.pb.gz:
``bench/run.py --workload vgg16.train.paper --trace 1`` with a 0.1 s
window)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "tiny_vgg.xplane.pb.gz"


def test_union_and_subtract_by_hand():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4), (6, 10)]
    assert tr.subtract([(0, 3), (5, 9)], [(2, 6)]) == [(0, 2), (6, 9)]
    assert tr.clip([(0, 4), (6, 12)], 2, 10) == [(2, 4), (6, 10)]
    assert tr.total([(0, 3), (5, 8)]) == 6


def test_program_names_are_whole_words():
    names = ["hsfl_round_local", "hsfl_round_fed_TTT"]
    assert tr.program_of("jit_hsfl_round_local(17)", names) == "hsfl_round_local"
    assert tr.program_of("jit_hsfl_round_fed_TTT", names) == "hsfl_round_fed_TTT"
    assert tr.program_of("jit_hsfl_round_local_x(3)", names) is None
    assert tr.program_of("jit_hsfl_round_fed_TTTT", names) is None


HOST = -1000  # the host's clock reads the trace's time less 1000 ns


def _hand_trace():
    # one device; window 0..100 ns; ops: compute 10-30, 25-40 (overlap),
    # a collective 35-50 (exposed 40-50), compute 60-70, collective 65-68
    # (hidden); programs: a local round 10-50 and a fed round 60-70, each
    # ended when its wait span ends; host spans on the host's clock
    spans = [("batch_prep", 0, 8), ("dispatch", 8, 10), ("wait", 10, 50),
             ("loss_fetch", 50, 52), ("batch_prep", 52, 58), ("dispatch", 58, 60),
             ("wait", 60, 70), ("loss_fetch", 70, 90)]
    trace = tr.Trace(
        ops={0: [("fusion.1", 10, 30), ("convolution.2", 25, 40),
                 ("all-reduce.3", 35, 50), ("fusion.4", 60, 70),
                 ("all-gather.5", 65, 68)]},
        modules={0: [("jit_hsfl_round_local(1)", 10, 50),
                     ("jit_hsfl_round_fed_TTT(2)", 60, 70)]},
    )
    return trace, [(n, a + HOST, b + HOST) for n, a, b in spans], (HOST, 100 + HOST)


PROGRAMS = ["hsfl_round_local", "hsfl_round_fed_TTT"]


def test_reduce_by_hand():
    trace, spans, window = _hand_trace()
    out = tr.reduce(trace, PROGRAMS, spans, window)
    ns = 1e-9
    assert out["clock_offset_ns"] == -HOST
    assert out["window_s"] == pytest.approx(100 * ns)
    # busy: 10-50 and 60-70
    assert out["busy_s"] == pytest.approx(50 * ns)
    assert out["programs"]["hsfl_round_local"]["count"] == 1
    assert out["programs"]["hsfl_round_local"]["mean_s"] == pytest.approx(40 * ns)
    assert out["programs"]["hsfl_round_fed_TTT"]["mean_s"] == pytest.approx(10 * ns)
    assert out["collective_exposed_s"] == [pytest.approx(10 * ns)]
    # idle 0-10 (batch_prep 8, dispatch 2), 50-60 (loss_fetch 2,
    # batch_prep 6, dispatch 2), 70-100 (loss_fetch 20, nothing 10)
    idle = out["idle_by_span_s"]
    assert idle["batch_prep"] == pytest.approx(14 * ns)
    assert idle["dispatch"] == pytest.approx(4 * ns)
    assert idle["loss_fetch"] == pytest.approx(22 * ns)
    assert idle[tr.UNSPANNED] == pytest.approx(10 * ns)
    assert "wait" not in idle
    assert sum(idle.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    top = dict(out["breakdown"]["device_ops"])
    assert top["fusion.1"] == pytest.approx(20 * ns)
    assert out["breakdown"]["idle_gaps"][0][0] == "loss_fetch"


def test_align_takes_the_least_offset_every_wait_allows():
    trace, spans, _ = _hand_trace()
    # the host learns of the fed round's end 3 ns late, of the local's 7
    late = [(n, a, b + (3 if b == 70 + HOST else 7 if b == 50 + HOST else 0))
            for n, a, b in spans]
    assert tr.align(trace, late, PROGRAMS) == -HOST - 3
    # a trace that lost its first round still pairs from the end
    first_lost = tr.Trace(ops=trace.ops, modules={0: trace.modules[0][1:]})
    assert tr.align(first_lost, spans, PROGRAMS) == -HOST


def test_reduce_needs_a_window_and_device_work():
    trace, spans, window = _hand_trace()
    with pytest.raises(ValueError, match="window"):
        tr.reduce(trace, PROGRAMS, spans, (window[1], window[0]))
    with pytest.raises(ValueError, match="wait span"):
        tr.reduce(trace, PROGRAMS, [s for s in spans if s[0] != "wait"], window)
    with pytest.raises(ValueError, match="no device"):
        tr.reduce(tr.Trace(ops={0: []}, modules=trace.modules), PROGRAMS, spans, window)


def _recorded_host_spans(names):
    """The benchmark's host spans as the host tracer recorded them in the
    committed trace (it was taken with the host tracer on)."""
    import gzip

    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(gzip.decompress(RECORDED.read_bytes()))
    return sorted((e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                  for p in data.planes if p.name == "/host:CPU"
                  for line in p.lines for e in line.events if e.name in names)


def test_recorded_chip_trace():
    trace = tr.load(RECORDED)
    assert trace.ops and trace.modules
    recorded = _recorded_host_spans({"window", "batch_prep", "dispatch", "wait",
                                     "loss_fetch"})
    shift = 7.5e12  # put the spans on a clock of their own, as perf_counter is
    spans = [(n, a - shift, b - shift) for n, a, b in recorded if n != "window"]
    win = [(a - shift, b - shift) for n, a, b in recorded if n == "window"][0]
    programs = ["hsfl_round_local", "hsfl_round_fed_FTT", "hsfl_round_fed_TTT"]
    out = tr.reduce(trace, programs, spans, win)
    # the clocks are put back together to within the host's notice of a
    # program's end: 1.16 ms in this run, slowed by its host tracer
    assert 0 <= shift - out["clock_offset_ns"] < 2e6
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["programs"]["hsfl_round_local"]["count"] >= 1
    assert out["idle_by_span_s"]["batch_prep"] > 0
    idle = sum(out["idle_by_span_s"].values())
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
    assert out["collective_exposed_s"] == [0.0]
    assert len(out["breakdown"]["device_ops"]) == 10
    assert all(" " not in name for name, _ in out["breakdown"]["device_ops"])
