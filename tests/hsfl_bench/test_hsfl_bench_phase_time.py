"""The phase reader (bench/phase_time.py) on traces written by hand with
the same declared XSpace fields, and on the small trace recorded on a TPU
v5e before the program named its phases (data/tiny_vgg.xplane.pb.gz)."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench import phase_time as pt  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "tiny_vgg.xplane.pb.gz"
START_NS = 1_792_000_000_000_000_000  # the profile's start on time.time_ns()
US = 1_000_000  # ps per microsecond
LOCAL, FED = "hsfl_round_local", "hsfl_round_fed_TTT"
NEW_METRICS = ["grad_device_ms", "opt_device_ms", "sync_device_ms.local",
               "sync_device_ms.fed", "loader_ms"]


def _local_ops(t, root=LOCAL):
    """One local round's ops from ``t`` us: (name, start, end, tf_op path)."""
    p = f"jit({root})/"
    return [
        ("while.1", t, t + 100, p + "hsfl.grad/while"),            # self 30
        ("fusion.2", t + 10, t + 40, p + "hsfl.grad/vmap(jvp(dot_general))"),
        ("fusion.3", t + 50, t + 90, ""),                           # inherits grad
        ("copy.4", t + 100, t + 110, ""),                           # inherits grad
        ("fusion.5", t + 110, t + 140, p + "hsfl.opt/sub"),
        ("fusion.6", t + 140, t + 170, p + "hsfl.sync.t3.fed/reduce_sum"),
        ("fusion.7", t + 170, t + 190, p + "reduce_sum"),           # unattributed
    ]


def _fed_ops(t):
    p = f"jit({FED})/"
    return [
        ("copy.0", t, t + 10, ""),  # nothing phased before it in this run
        ("fusion.1", t + 10, t + 100, p + "hsfl.grad/vmap(transpose(jvp()))/dot"),
        ("fusion.2", t + 100, t + 150, p + "hsfl.sync.t1.fed/div"),
        ("fusion.3", t + 150, t + 190, p + "hsfl.sync.t2.entity/hsfl.opt/add"),
    ]


def _hand_trace():
    """Runs: 0 (cut by the trace's start), 1 and 2 local, 3 full fed, 4 (cut
    by its end); the longest idle gap lies between runs 2 and 3."""
    modules = [(f"jit_{LOCAL}(7)", 10, 100), (f"jit_{LOCAL}(7)", 200, 390),
               (f"jit_{LOCAL}(7)", 500, 690), (f"jit_{FED}(9)", 1000, 1190),
               (f"jit_{LOCAL}(7)", 1300, 1400)]
    ops = ([("fusion.9", 10, 100, f"jit({LOCAL})/hsfl.grad/x")] + _local_ops(200)
           + _local_ops(500) + _fed_ops(1000)
           + [("fusion.9", 1300, 1400, f"jit({LOCAL})/hsfl.grad/x")])
    return modules, ops


def write_xspace(path, modules, ops, start_ns=START_NS, ref_paths=False):
    """An XSpace with one TPU plane (times in us) and the profile's start."""
    X = pt.xspace_class()
    space = X()
    plane = space.planes.add(name=b"/device:TPU:0")
    tf_op = plane.stat_metadata.add(key=1)
    tf_op.value.id, tf_op.value.name = 1, b"tf_op"
    ids = {}

    def meta(name, path):
        if (name, path) not in ids:
            k = ids[(name, path)] = len(ids) + 10
            e = plane.event_metadata.add(key=k)
            e.value.id, e.value.name = k, f"%{name} = f32[] op()".encode()
            if path and ref_paths:  # the path as a stat of its own, by reference
                s = plane.stat_metadata.add(key=k + 10_000)
                s.value.id, s.value.name = k + 10_000, path.encode()
                e.value.stats.add(metadata_id=1, ref_value=k + 10_000)
            elif path:
                e.value.stats.add(metadata_id=1, str_value=path.encode())
        return ids[(name, path)]

    for line_name, events in (("XLA Modules", [(n, a, b, "") for n, a, b in modules]),
                              ("XLA Ops", ops)):
        line = plane.lines.add(name=line_name.encode(), timestamp_ns=0)
        for name, a, b, p in events:
            line.events.add(metadata_id=meta(name, p), offset_ps=a * US,
                            duration_ps=(b - a) * US)
    if start_ns is not None:
        env = space.planes.add(name=b"Task Environment")
        s = env.stat_metadata.add(key=3)
        s.value.id, s.value.name = 3, b"profile_start_time"
        env.stats.add(metadata_id=3, uint64_value=start_ns)
    path.write_bytes(space.SerializeToString())
    return path


def _loader_span(a_us, b_us):
    return ("loader", START_NS + a_us * 1000, START_NS + b_us * 1000)


@pytest.mark.parametrize("ref_paths", [False, True], ids=["str", "ref"])
def test_phases_by_hand(tmp_path, ref_paths):
    modules, ops = _hand_trace()
    tr = pt.load(write_xspace(tmp_path / "h.xplane.pb", modules, ops, ref_paths=ref_paths))
    assert tr.start_ns == START_NS
    red = pt.reduce(tr)
    local, fed = red["programs"][LOCAL], red["programs"][FED]
    # runs 0 and 4 touch the trace's edges and are left out, with their ops
    assert (local["runs"], fed["runs"]) == (2, 1)
    assert local["device_ms"] == pytest.approx(0.190)
    # the while's self time is 100 - 30 - 40; fusion.3 and copy.4 carry no
    # tf_op and take hsfl.grad from fusion.2, the phased op before them
    assert local["phases"] == pytest.approx(
        {"hsfl.grad": 0.110, "hsfl.opt": 0.030, "hsfl.sync.t3.fed": 0.030})
    assert local["inherited_ms"] == pytest.approx(0.050) and local["inherited_ops"] == 2
    assert local["unattributed_ms"] == pytest.approx(0.020)
    assert local["unattributed_top"] == [["fusion.7", f"jit({LOCAL})/reduce_sum",
                                          pytest.approx(0.020)]]
    # phases + unattributed = the program's device time
    assert sum(local["phases"].values()) + local["unattributed_ms"] == pytest.approx(
        local["device_ms"])
    assert local["closure"] == 0.0
    # the innermost phase wins; a run inherits nothing from the run before
    assert fed["phases"] == pytest.approx(
        {"hsfl.grad": 0.090, "hsfl.sync.t1.fed": 0.050, "hsfl.opt": 0.040})
    assert fed["unattributed_ms"] == pytest.approx(0.010) and fed["inherited_ops"] == 0
    assert pt.phase_ms(local, "hsfl.sync") == pytest.approx(0.030)
    assert pt.phase_ms(fed, "hsfl.sync") == pytest.approx(0.050)
    assert pt.phase_ms(fed, "hsfl.sync.t1") == pytest.approx(0.050)
    assert pt.phase_ms(fed, "hsfl.gra") == 0.0  # whole name parts only


def test_self_time_of_nested_ops():
    ops = [("while", 0, 100, ""), ("a", 10, 40, ""), ("b", 30, 50, ""),
           ("c", 60, 100, ""), ("d", 100, 120, "")]
    # b starts inside a and ends past it: a keeps 10-30, the while keeps
    # 0-10 and 50-60, and nothing counts twice
    own = pt.self_times(ops)
    assert own == [20, 20, 20, 40, 20]
    assert sum(own) == 120


def test_phase_names():
    assert pt.phase_of("jit(p)/hsfl.grad/vmap(transpose(jvp(dot)))/dot_general") == "hsfl.grad"
    assert pt.phase_of("jit(p)/hsfl.sync.t2.entity/hsfl.opt/add") == "hsfl.opt"
    assert pt.phase_of("jit(p)/hsfl.sync.t3.fed/reduce_sum:") == "hsfl.sync.t3.fed"
    assert pt.phase_of("jit(hsfl_round_local)/vmap()/transpose:") is None
    assert pt.program_name("jit_hsfl_round_local(1234)") == LOCAL
    assert pt.program_name("jit_hsfl_round_fed_TTT") == FED


def test_gaps_and_host_spans(tmp_path):
    modules, ops = _hand_trace()
    tr = pt.load(write_xspace(tmp_path / "h.xplane.pb", modules, ops))
    spans = [_loader_span(900, 950), _loader_span(200, 210), ("other", START_NS, START_NS + 1)]
    red = pt.reduce(tr, spans=spans, top=3)
    first = red["gaps"][0]
    assert first["ms"] == pytest.approx(0.310) and first["start_ms"] == pytest.approx(0.690)
    assert (first["round"], first["program"], first["after_op"]) == (2, LOCAL, "fusion.7")
    assert [g["ms"] for g in red["gaps"][1:]] == [pytest.approx(0.110)] * 2
    # the gap from the profile's start to the first op precedes every run
    lead = pt.describe_gap(tr, 0, (0, 10 * US))
    assert lead["round"] is None and lead["after_op"] is None
    placed = pt.place_spans(spans, tr.start_ns)
    assert pt.spans_over(first, placed) == ["loader"]
    # the second loader span lies on device work: no idle under it
    assert red["idle_under"]["loader"] == pytest.approx(0.050)
    assert red["idle_under"]["other"] == pytest.approx(1e-6)


def test_no_phase_is_none_never_zero(tmp_path):
    modules, ops = _hand_trace()
    bare = [(n, a, b, p.replace("hsfl.", "x_")) for n, a, b, p in ops]
    red = pt.reduce(pt.load(write_xspace(tmp_path / "b.xplane.pb", modules, bare)))
    local = red["programs"][LOCAL]
    assert local["phases"] is None and pt.phase_ms(local, "hsfl.grad") is None
    assert local["unattributed_ms"] == pytest.approx(local["device_ms"])


def test_a_run_that_does_not_close_is_refused(tmp_path):
    modules, ops = _hand_trace()
    # run 1's module lasts 10 us past its last op: 5% of it is idle
    modules[1] = (modules[1][0], 200, 400)
    red = pt.reduce(pt.load(write_xspace(tmp_path / "c.xplane.pb", modules, ops)))
    local = red["programs"][LOCAL]
    assert local["closure"] == pytest.approx(0.05)
    assert pt.phase_ms(local, "hsfl.grad") is None


def test_recorded_chip_trace_has_no_phases():
    tr = pt.load(RECORDED)
    assert tr.ops and tr.start_ns is not None
    red = pt.reduce(tr)
    assert all(p["phases"] is None for p in red["programs"].values())
    # the device waited for the first round's batch after the trace started
    assert red["gaps"][0]["round"] is None and red["gaps"][0]["ms"] > 1


@pytest.fixture
def bench_trace(tmp_path, monkeypatch):
    """A traced run's trace where ``for_record`` looks, and its host spans."""
    d = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    modules, ops = _hand_trace()
    write_xspace(d / "host.xplane.pb", modules, ops)
    monkeypatch.setattr(pt, "TRACE_ROOT", tmp_path / ".bench_trace")
    monkeypatch.setattr(pt, "_CACHE", {})
    monkeypatch.setattr(pt, "_SPANS", [_loader_span(0, 2), _loader_span(195, 199),
                                       _loader_span(900, 902)])
    return d


def _record(traced=True):
    return SimpleNamespace(
        trace={"clock_offset_ns": 0, "programs": {}} if traced else None,
        counters={"full_fed_program": FED, "rounds": 2})


def _new_metrics(rec):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    specs = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in specs] == NEW_METRICS
    return {k: v["value"] for k, v in harness.read_metrics(specs, rec).items()}


def test_the_readers_of_a_traced_run(bench_trace, capsys):
    got = _new_metrics(_record())
    assert got == pytest.approx({
        "grad_device_ms": 0.110, "opt_device_ms": 0.030,
        "sync_device_ms.local": 0.030, "sync_device_ms.fed": 0.050,
        "loader_ms": 0.003})  # the last two loader spans: the window's rounds
    assert json.loads((bench_trace / pt.HOST_SPANS_FILE).read_text())[0][0] == "loader"
    assert "align_minus_shared_us" in capsys.readouterr().err
    # the command finds the spans beside the trace
    assert pt.main([str(bench_trace)]) == 0
    out = capsys.readouterr().out
    assert "hsfl.grad" in out and "loader span: 3 calls" in out
    assert "round 2 (hsfl_round_local), after fusion.7" in out


def test_the_readers_find_nothing_untraced(bench_trace):
    assert _new_metrics(_record(traced=False)) == {"loader_ms": pytest.approx(0.003)}


def test_the_readers_find_nothing_without_the_program_names(bench_trace, monkeypatch):
    # a program without repro.obs records no spans and names no phases
    import repro
    from repro import obs

    with obs.host_span(obs.LOADER):  # a span that must not be read
        pass
    monkeypatch.setattr(pt, "_SPANS", None)
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    modules, ops = _hand_trace()
    bare = [(n, a, b, p.replace("hsfl.", "x_")) for n, a, b, p in ops]
    write_xspace(bench_trace / "host.xplane.pb", modules, bare)
    try:
        assert _new_metrics(_record()) == {}
    finally:
        obs.host_spans()


def test_a_trace_the_reader_cannot_read_is_nothing(bench_trace, capsys):
    (bench_trace / "host.xplane.pb").write_bytes(b"\x00not a trace")
    assert _new_metrics(_record()) == {"loader_ms": pytest.approx(0.003)}
    assert "Traceback" in capsys.readouterr().err
