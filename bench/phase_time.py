"""Device time of each named phase of the Engine-A round, from a trace.

The program names the phases of a round (``repro.obs``): ``hsfl.grad``
(per-client forward and backward), ``hsfl.opt`` (the optimizer) and one
``hsfl.sync.t{m}.entity`` or ``hsfl.sync.t{m}.fed`` per aggregation level.
The names are op metadata: a device trace carries each op's JAX path as
the ``tf_op`` stat of the op's event metadata, e.g.
``jit(hsfl_round_local)/hsfl.grad/vmap(transpose(jvp(...)))/dot_general``.

* Reading: the ``XLA Ops`` and ``XLA Modules`` lines of every
  ``/device:TPU:<n>`` plane, with each op's ``tf_op``, and the profile's
  start on the host's wall clock (``profile_start_time`` of the ``Task
  Environment`` plane).  The trace is read through the few XSpace fields
  needed, declared here; TensorFlow is not imported.
* Runs: a module event is one run of a program (``jit_<program>(<id>)``).
  Only complete runs count: a run that touches the first or last instant
  of its device's events may have been cut by the trace's edge and is
  left out, with its ops.
* Self time: an op's duration less the part covered by ops nested in it on
  the same line (a ``while`` and the ops of its body).
* Phases: an op's time goes to the innermost ``hsfl.`` phase of its path.
  An op without a ``tf_op`` (XLA's own copies and kernel flips) takes the
  phase of the preceding phased op of its run; the time so assigned is
  counted as ``inherited``.  The rest is ``unattributed``.  Phases plus
  unattributed are the run's op time, which is the program's device time
  (its module event) less the idle instants inside it; a run where the two
  differ by more than 1% makes the program's phases untrustworthy.
* A program none of whose ops carries a phase has ``phases`` None, never 0:
  its executable predates the names (JAX keeps op metadata out of the
  persistent compilation cache's key, so a stale ``.jax_cache`` serves one).
* Gaps: the device's idle intervals from the profile's start to its last
  op; the longest are listed with the run and op they follow.  Host spans
  (``(name, start_ns, end_ns)`` on ``time.time_ns()``, the program's
  ``loader`` span among them) are placed on the trace's clock through the
  profile's start, and the idle time under each is summed.

    python bench/phase_time.py <dir, .xplane.pb or .xplane.pb.gz> [spans.json]

A traced benchmark run writes its host spans to ``host_spans.json`` beside
its trace, where the command finds them without the second argument.
"""
from __future__ import annotations

import bisect
import gzip
import heapq
import json
import re
import sys
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):  # run as a script from the checkout
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace_reduce  # noqa: E402

LOCAL_PROGRAM = "hsfl_round_local"
TRACE_ROOT = Path(__file__).resolve().parent.parent / ".bench_trace"
HOST_SPANS_FILE = "host_spans.json"
LOADER_SPAN = "loader"
UNATTRIBUTED = "unattributed"
CLOSURE = 0.01  # phases + unattributed within 1% of the run's device time
PHASE = re.compile(r"hsfl\.[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)*")
MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")

Op = Tuple[str, int, int, str]  # (op name, start_ps, end_ps, tf_op path)
Run = Tuple[str, int, int]  # (module name, start_ps, end_ps)
Span = Tuple[str, int, int]  # (name, start_ns, end_ns) on time.time_ns()


@dataclass
class PhaseTrace:
    ops: Dict[int, List[Op]] = field(default_factory=dict)
    modules: Dict[int, List[Run]] = field(default_factory=dict)
    start_ns: Optional[int] = None  # the profile's start on time.time_ns()


# --------------------------------------------------------------------------- #
# reading the trace
# --------------------------------------------------------------------------- #

_XSPACE = None


def xspace_class():
    """The XSpace message, declared with only the fields read here (field
    numbers of ``tsl/profiler/protobuf/xplane.proto``; maps as their
    entries)."""
    global _XSPACE
    if _XSPACE is not None:
        return _XSPACE
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_phase_xplane.proto", package="bench_phase_xplane",
        syntax="proto3")

    def message(name, *fields):
        msg = fd.message_type.add(name=name)
        for fname, number, ftype, repeated in fields:
            f = msg.field.add(name=fname, number=number,
                              label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_phase_xplane.{ftype}"
            else:
                f.type = ftype

    message("XSpace", ("planes", 1, "XPlane", True))
    message("XPlane", ("name", 2, F.TYPE_BYTES, False),
            ("lines", 3, "XLine", True),
            ("event_metadata", 4, "EventMetadataEntry", True),
            ("stat_metadata", 5, "StatMetadataEntry", True),
            ("stats", 6, "XStat", True))
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XStatMetadata", False))
    message("XLine", ("name", 2, F.TYPE_BYTES, False),
            ("timestamp_ns", 3, F.TYPE_INT64, False),
            ("events", 4, "XEvent", True))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, False),
            ("offset_ps", 2, F.TYPE_INT64, False),
            ("duration_ps", 3, F.TYPE_INT64, False))
    message("XStat", ("metadata_id", 1, F.TYPE_INT64, False),
            ("uint64_value", 3, F.TYPE_UINT64, False),
            ("int64_value", 4, F.TYPE_INT64, False),
            ("str_value", 5, F.TYPE_BYTES, False),
            ("ref_value", 7, F.TYPE_UINT64, False))
    message("XEventMetadata", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_BYTES, False),
            ("stats", 5, "XStat", True))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_BYTES, False))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _XSPACE = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_phase_xplane.XSpace"))
    return _XSPACE


def _text(b: bytes) -> str:
    return b.decode("utf-8", "replace")


def load(path: Path) -> PhaseTrace:
    """Device ops with their ``tf_op`` paths, device module runs, and the
    profile's start."""
    path = trace_reduce.find_xplane(path)
    raw = path.read_bytes()
    if path.suffix == ".gz":
        raw = gzip.decompress(raw)
    space = xspace_class().FromString(raw)
    tr = PhaseTrace()
    for plane in space.planes:
        name = _text(plane.name)
        stat_names = {e.key: _text(e.value.name) for e in plane.stat_metadata}
        for st in plane.stats:
            if stat_names.get(st.metadata_id) == "profile_start_time":
                tr.start_ns = int(st.uint64_value or st.int64_value)
        m = trace_reduce.DEVICE_PLANE.match(name)
        if not m:
            continue
        dev = int(m.group(1))
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        meta = {}
        for e in plane.event_metadata:
            op_path = ""
            for st in e.value.stats:
                if st.metadata_id in tf_op:
                    op_path = (_text(st.str_value) if st.str_value
                               else stat_names.get(st.ref_value, ""))
            # "%fusion.12 = f32[...] fusion(...)" -> "fusion.12"
            meta[e.key] = (_text(e.value.name).split(" = ", 1)[0].lstrip("%"), op_path)
        for line in plane.lines:
            lname = _text(line.name)
            if lname not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
                continue
            base = line.timestamp_ns * 1000
            events = []
            for e in line.events:
                a = base + e.offset_ps
                op, op_path = meta.get(e.metadata_id, ("", ""))
                events.append((op, a, a + e.duration_ps, op_path))
            events.sort(key=lambda ev: (ev[1], -ev[2]))
            if lname == trace_reduce.OPS_LINE:
                tr.ops[dev] = events
            else:
                tr.modules[dev] = [(n, a, b) for n, a, b, _ in events]
    return tr


# --------------------------------------------------------------------------- #
# the reduction
# --------------------------------------------------------------------------- #


def self_times(ops: Sequence[Op]) -> List[int]:
    """Each op's duration less the part covered by the ops that start
    inside it on the same line (a ``while`` and the ops of its body);
    ``ops`` sorted by (start, -end).  The self times sum to the union of
    the ops' intervals."""
    covered: Dict[int, List[Tuple[int, int]]] = {}
    stack: List[int] = []
    for i, (_, a, b, _) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= a:
            stack.pop()
        for p in stack:
            if ops[p][2] > a:
                covered.setdefault(p, []).append((a, min(b, ops[p][2])))
        stack.append(i)
    out = [b - a for _, a, b, _ in ops]
    for p, ivs in covered.items():
        out[p] -= int(trace_reduce.total(trace_reduce.union(ivs)))
    return out


def phase_of(path: str) -> Optional[str]:
    """The innermost ``hsfl.`` phase of an op's path, or None."""
    found = PHASE.findall(path)
    return found[-1] if found else None


def program_name(module: str) -> str:
    """``jit_hsfl_round_local(1234)`` -> ``hsfl_round_local``."""
    return MODULE.match(module).group(1)


def complete_runs(tr: PhaseTrace, dev: int) -> List[Run]:
    """Runs on device ``dev`` that touch neither edge of its events."""
    runs = tr.modules.get(dev, [])
    ops = tr.ops.get(dev, [])
    if not runs:
        return []
    lo = min([a for _, a, _ in runs] + [a for _, a, _, _ in ops])
    hi = max([b for _, _, b in runs] + [b for _, _, b, _ in ops])
    return [r for r in runs if r[1] > lo and r[2] < hi]


def _run_phases(ops: Sequence[Op], own: Sequence[int]):
    """Ps of each phase and of ``unattributed`` in one run's ops, the ps and
    count of ops that inherited a phase, and the unattributed ops."""
    acc: Dict[str, int] = {}
    loose: Dict[Tuple[str, str], int] = {}
    inherited = [0, 0]
    last = None
    for (name, _, _, path), t in zip(ops, own):
        phase = phase_of(path) if path else None
        if phase is not None:
            last = phase
        elif not path and last is not None:
            phase = last
            inherited[0] += t
            inherited[1] += 1
        if phase is None:
            phase = UNATTRIBUTED
            loose[(name, path)] = loose.get((name, path), 0) + t
        acc[phase] = acc.get(phase, 0) + t
    return acc, inherited, loose


def reduce(tr: PhaseTrace, spans: Sequence[Span] = (), top: int = 5) -> dict:
    """Per program of the trace: its complete ``runs`` per device,
    ``device_ms`` (module time per run), ``phases`` (ms per run of each
    phase, or None), ``unattributed_ms``,
    ``inherited_ms`` and ``inherited_ops`` (per run), ``closure`` (the
    largest relative gap of a run's phases plus unattributed from its
    device time) and the ``top`` unattributed ops.  Beside them ``gaps``,
    the ``top`` longest idle intervals of the device, and ``idle_under``,
    idle ms under each host span name."""
    acc: Dict[str, dict] = {}
    gaps: List[dict] = []
    idle_under: Dict[str, float] = {}
    devices = [d for d, ops in tr.ops.items() if ops]
    for dev in devices:
        ops = tr.ops[dev]
        own = self_times(ops)
        starts = [a for _, a, _, _ in ops]
        for module, a, b in complete_runs(tr, dev):
            p = program_name(module)
            i, j = bisect.bisect_left(starts, a), bisect.bisect_left(starts, b)
            phases, inherited, loose = _run_phases(ops[i:j], own[i:j])
            v = acc.setdefault(p, {"runs": 0, "ps": 0, "phases": {}, "bare_runs": 0,
                                   "inherited": [0, 0], "loose": {}, "closure": 0.0})
            v["runs"] += 1
            v["ps"] += b - a
            v["bare_runs"] += not any(k != UNATTRIBUTED for k in phases)
            for k, t in phases.items():
                v["phases"][k] = v["phases"].get(k, 0) + t
            v["inherited"][0] += inherited[0]
            v["inherited"][1] += inherited[1]
            for k, t in loose.items():
                v["loose"][k] = v["loose"].get(k, 0) + t
            v["closure"] = max(v["closure"], abs(sum(phases.values()) - (b - a)) / (b - a))
        idle = idle_intervals(tr, dev)
        gaps += [(b - a, dev, (a, b)) for a, b in heapq.nlargest(top, idle, key=lambda g: g[1] - g[0])]
        for name, ps in idle_by_span(idle, place_spans(spans, tr.start_ns)).items():
            idle_under[name] = idle_under.get(name, 0.0) + ps * 1e-9 / len(devices)
    out = {}
    for p, v in acc.items():
        per_run = 1e-9 / v["runs"]  # ps summed over devices -> ms per run
        phases = {k: t * per_run for k, t in v["phases"].items() if k != UNATTRIBUTED}
        out[p] = {
            "runs": v["runs"] / len(devices),
            "device_ms": v["ps"] * per_run,
            "phases": None if v["bare_runs"] else phases,
            "unattributed_ms": v["phases"].get(UNATTRIBUTED, 0) * per_run,
            "inherited_ms": v["inherited"][0] * per_run,
            "inherited_ops": v["inherited"][1] / v["runs"],
            "closure": v["closure"],
            "unattributed_top": [[name, path, t * per_run] for (name, path), t in sorted(
                v["loose"].items(), key=lambda kv: -kv[1])[:top]],
        }
    gaps = [describe_gap(tr, dev, g) for _, dev, g in sorted(gaps, reverse=True)[:top]]
    return {"programs": out, "gaps": gaps, "idle_under": idle_under}


def phase_ms(prog: dict, wanted: str) -> Optional[float]:
    """Ms per run of the phases named ``wanted`` or nested in its name
    (``hsfl.sync`` takes ``hsfl.sync.t3.fed``); None where the program's
    ops carry no phase or a run's phases do not close."""
    if prog.get("phases") is None or prog["closure"] > CLOSURE:
        return None
    return sum(ms for k, ms in prog["phases"].items()
               if k == wanted or k.startswith(wanted + "."))


# --------------------------------------------------------------------------- #
# idle gaps and host spans
# --------------------------------------------------------------------------- #


def place_spans(spans: Sequence[Span], start_ns: Optional[int]) -> List[Tuple[str, int, int]]:
    """Host spans on the trace's clock (ps from the profile's start); none
    where the trace does not say when the profile started."""
    if start_ns is None:
        return []
    return [(n, (a - start_ns) * 1000, (b - start_ns) * 1000) for n, a, b in spans]


def idle_intervals(tr: PhaseTrace, dev: int) -> List[Tuple[int, int]]:
    """Idle intervals (ps) of device ``dev`` from the profile's start (its
    first op where the start is unknown) to its last op."""
    ops = tr.ops.get(dev, [])
    if not ops:
        return []
    prev = 0 if tr.start_ns is not None else ops[0][1]
    out = []
    for _, a, b, _ in ops:  # sorted by start
        if a > prev:
            out.append((prev, a))
        if b > prev:
            prev = b
    return out


def describe_gap(tr: PhaseTrace, dev: int, gap: Tuple[int, int]) -> dict:
    """A gap with what it follows: ``start_ms`` and ``ms``; ``after_op``
    and ``after_path``, the op that ended where it starts; ``round``, the
    index among the trace's runs of the last run to start before it (0 is
    the first run after the trace started, None before any), and that
    run's ``program``."""
    a, b = gap
    op = next((o for o in tr.ops[dev] if o[2] == a), None)
    runs = sorted(tr.modules.get(dev, []), key=lambda r: r[1])
    r = bisect.bisect_right([s for _, s, _ in runs], a) - 1
    return {
        "device": dev, "start_ms": a * 1e-9, "ms": (b - a) * 1e-9,
        "start_ps": a, "end_ps": b,
        "after_op": op[0] if op else None, "after_path": op[3] if op else None,
        "round": r if r >= 0 else None,
        "program": program_name(runs[r][0]) if r >= 0 else None,
    }


def idle_by_span(gaps: Sequence[Tuple[int, int]],
                 spans: Sequence[Tuple[str, int, int]]) -> Dict[str, int]:
    """Ps of the idle ``gaps`` (sorted, disjoint) under each span name."""
    out: Dict[str, int] = {}
    starts = [a for a, _ in gaps]
    for name, a, b in spans:
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(gaps) and gaps[i][0] < b:
            lo, hi = max(a, gaps[i][0]), min(b, gaps[i][1])
            if hi > lo:
                out[name] = out.get(name, 0) + hi - lo
            i += 1
    return out


def spans_over(gap: dict, spans: Sequence[Tuple[str, int, int]]) -> List[str]:
    return sorted({n for n, a, b in spans if a < gap["end_ps"] and b > gap["start_ps"]})


# --------------------------------------------------------------------------- #
# the benchmark's record
# --------------------------------------------------------------------------- #

_SPANS: Optional[List[Span]] = None
_CACHE: Dict[tuple, dict] = {}


def run_host_spans() -> List[Span]:
    """The program's host spans of this process, drained once and kept:
    none where the program records none."""
    global _SPANS
    if _SPANS is None:
        try:
            from repro import obs
        except ImportError:
            _SPANS = []
        else:
            _SPANS = [tuple(s) for s in obs.host_spans()]
    return _SPANS


def newest_trace() -> Optional[Path]:
    found = list(TRACE_ROOT.rglob("*.xplane.pb")) if TRACE_ROOT.is_dir() else []
    return max(found, key=lambda p: p.stat().st_mtime) if found else None


def _realtime_minus_perf_ns() -> int:
    """``time.time_ns() - time.perf_counter_ns()``, from the closest of a
    few paired reads."""
    best = None
    for _ in range(5):
        a = time.perf_counter_ns()
        t = time.time_ns()
        b = time.perf_counter_ns()
        if best is None or b - a < best[0]:
            best = (b - a, t - (a + b) // 2)
    return best[1]


def for_record(rec) -> Optional[dict]:
    """The reduction of a traced run's own trace: the newest ``.xplane.pb``
    under ``.bench_trace/`` (a traced run removes its cell's directory
    before it traces), reduced once, with the run's host spans, which are
    written beside it.  None in an untraced run."""
    if rec.trace is None:
        return None
    path = newest_trace()
    if path is None:
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _CACHE:
        try:
            _CACHE[key] = _reduce_run(rec, path)
        except Exception:  # a reader finds nothing rather than end the run
            traceback.print_exc()
            _CACHE[key] = None
    return _CACHE[key]


def _reduce_run(rec, path: Path) -> dict:
    spans = run_host_spans()
    (path.parent / HOST_SPANS_FILE).write_text(json.dumps(spans))
    t0 = time.perf_counter()
    tr = load(path)
    red = reduce(tr, spans=spans)
    red["reduce_s"] = time.perf_counter() - t0
    note = {"trace": str(path), "reduce_s": red["reduce_s"],
            "closure": {p: v["closure"] for p, v in red["programs"].items()}}
    offset = rec.trace.get("clock_offset_ns")
    if tr.start_ns is not None and offset is not None:
        # align's perf_counter -> trace offset against the shared clock's
        note["align_minus_shared_us"] = (
            offset - (_realtime_minus_perf_ns() - tr.start_ns)) * 1e-3
    print("phase_time " + json.dumps(note), file=sys.stderr)
    return red


def program_phase_ms(rec, program: Optional[str], wanted: str) -> Optional[float]:
    """Ms per run of ``program``'s ops under ``wanted`` in a traced run;
    None where the trace has no phases."""
    red = for_record(rec)
    if red is None or program not in red["programs"]:
        return None
    return phase_ms(red["programs"][program], wanted)


# --------------------------------------------------------------------------- #
# the command
# --------------------------------------------------------------------------- #


def print_report(red: dict, spans: Sequence[Span], start_ns: Optional[int]) -> None:
    for p, prog in sorted(red["programs"].items()):
        print(f"{p}: {prog['runs']:g} complete runs, {prog['device_ms']:.4f} ms "
              f"of device time per run")
        dev = prog["device_ms"]
        rows = sorted((prog["phases"] or {}).items())
        rows.append((UNATTRIBUTED, prog["unattributed_ms"]))
        for name, ms in rows:
            print(f"  {name:<24} {ms:>10.4f} ms {100.0 * ms / dev:>7.2f} %")
        if prog["phases"] is None:
            print("  no op carries a phase: an executable compiled before the "
                  "names (a stale compile cache?)")
        print(f"  phases + unattributed within {100.0 * prog['closure']:.3f} % of "
              f"every run's device time; {prog['inherited_ops']:g} ops "
              f"({prog['inherited_ms']:.4f} ms) per run without tf_op took the "
              "preceding op's phase")
        for name, path, ms in prog["unattributed_top"]:
            print(f"    unattributed {name}: {ms:.4f} ms [{path[:120]}]")
    placed = place_spans(spans, start_ns)
    print("longest device-idle gaps:")
    for g in red["gaps"]:
        over = ",".join(spans_over(g, placed)) or "-"
        print(f"  {g['ms']:.4f} ms at {g['start_ms']:.3f} ms: round {g['round']} "
              f"({g['program']}), after {g['after_op']} "
              f"[{(g['after_path'] or '')[:100]}]; host spans {over}")
    loader = [(b - a) * 1e-6 for n, a, b in spans if n == LOADER_SPAN]
    if loader:
        print(f"loader span: {len(loader)} calls, mean {sum(loader) / len(loader):.4f} ms; "
              f"device idle under it {red['idle_under'].get(LOADER_SPAN, 0.0):.4f} ms")
    elif not spans:
        print("no host spans (give the spans file as the second argument)")


def main(argv: Sequence[str]) -> int:
    if len(argv) not in (1, 2):
        print(__doc__.split("\n\n")[-2].strip(), file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    path = trace_reduce.find_xplane(Path(argv[0]))
    spans_file = Path(argv[1]) if len(argv) == 2 else path.parent / HOST_SPANS_FILE
    spans = ([tuple(s) for s in json.loads(spans_file.read_text())]
             if spans_file.is_file() else [])
    tr = load(path)
    red = reduce(tr, spans=spans)
    print_report(red, spans, tr.start_ns)
    print(f"{path}: reduced in {time.perf_counter() - t0:.2f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
