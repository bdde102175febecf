"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Reading (``load``) and reducing (``reduce``) are kept apart, so that the
reduction can be checked on intervals worked out by hand.

* Device work: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane.
  Busy time is the union of those intervals inside the traced window.
* Programs: the ``XLA Modules`` line of the same planes.  The benchmark
  gives each jitted program a stable name (``hsfl_round_local``, ...);
  a module event belongs to a program when the program's name is a
  whole word of the event's name (``jit_hsfl_round_local(17)``).
* Collectives: ops whose name starts with a collective HLO opcode.  The
  exposed part is the collective time during which no other op runs on
  that device.
* Host spans: the benchmark records its own (``harness.Spans``) on the
  host's ``perf_counter`` clock, with the window's start and end; the
  trace holds the device alone.  ``align`` places the host's clock on the
  trace's, and every idle gap of a device is charged to the host spans
  it overlaps.

    python bench/trace_reduce.py <dir or .xplane.pb>   # print its structure
"""
from __future__ import annotations

import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]  # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WAIT_SPAN = "wait"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"send|recv)"
)
UNSPANNED = "no_host_span"


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)


def find_xplane(path: Path) -> Path:
    """The newest ``.xplane.pb`` under a directory, or the file itself
    (which may be gzipped: ``.xplane.pb.gz``)."""
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: Path) -> Trace:
    """Device ops and device modules of one trace."""
    data = _profile(path)
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        for line in plane.lines:
            if line.name == OPS_LINE:
                # "%fusion.12 = f32[...] fusion(...)" -> "fusion.12"
                tr.ops[dev] = [(n.split(" = ", 1)[0].lstrip("%"), a, b)
                               for n, a, b in _events(line.events)]
            elif line.name == MODULES_LINE:
                tr.modules[dev] = _events(line.events)
    return tr


def _profile(path: Path):
    from jax.profiler import ProfileData

    path = find_xplane(path)
    if path.suffix == ".gz":
        import gzip

        return ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    return ProfileData.from_file(str(path))


def _events(events) -> List[Event]:
    out = []
    for e in events:
        start = float(e.start_ns)
        out.append((e.name, start, start + float(e.duration_ns)))
    return out


# --------------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------------- #


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted intervals covering the same points."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Points of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def charge_gaps(gaps: Sequence[Interval], spans: Sequence[Event]) -> Dict[str, float]:
    """Nanoseconds of each gap charged to the host spans that overlap it;
    what no span covers goes to ``no_host_span``."""
    out: Dict[str, float] = {}
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    import bisect

    for lo, hi in gaps:
        covered = []
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(spans) and spans[i][1] < hi:
            name, a, b = spans[i]
            a, b = max(a, lo), min(b, hi)
            if b > a:
                out[name] = out.get(name, 0.0) + (b - a)
                covered.append((a, b))
            i += 1
        rest = (hi - lo) - total(union(covered))
        if rest > 0:
            out[UNSPANNED] = out.get(UNSPANNED, 0.0) + rest
    return out


def program_of(module_name: str, programs: Sequence[str]) -> Optional[str]:
    for p in programs:
        if re.search(rf"(^|[^A-Za-z0-9_]|jit_){re.escape(p)}([^A-Za-z0-9_]|$)",
                     module_name):
            return p
    return None


# --------------------------------------------------------------------------- #
# the reduction
# --------------------------------------------------------------------------- #


def align(tr: Trace, spans: Sequence[Event], programs: Sequence[str]) -> float:
    """Nanoseconds to add to a host-clock time to place it on the trace's.

    Each ``wait`` span ends only after the run of one of ``programs``
    (one run per wait) has ended on every device, so for the i-th wait and
    the i-th run, counted from the end (the trace may have lost its first
    events), ``run_end <= wait_end + offset``.  The least offset that
    holds for every pair falls short of the true one by the least delay
    with which the host learns that a program ended."""
    waits = sorted(b for name, _, b in spans if name == WAIT_SPAN)
    best = None
    for mods in tr.modules.values():
        ends = sorted(b for name, _, b in mods if program_of(name, programs))
        for e, w in zip(reversed(ends), reversed(waits)):
            best = e - w if best is None else max(best, e - w)
    if best is None:
        raise ValueError("no run of a program and no wait span to align "
                         "the host's clock by")
    return best


def reduce(tr: Trace, programs: Sequence[str], spans: Sequence[Event],
           window: Interval, top: int = 10) -> dict:
    """Busy and idle time, per-program device time, exposed collectives and
    idle gaps by host span, inside the traced window.

    ``programs`` run once per ``wait`` span; ``spans`` and ``window`` are
    on the host's clock.  Device numbers are averaged over the devices
    that ran any op."""
    if window[1] <= window[0]:
        raise ValueError(f"the window {window} is empty")
    offset = align(tr, spans, programs)
    lo, hi = window[0] + offset, window[1] + offset
    spans = [(name, a + offset, b + offset) for name, a, b in spans]
    devices = sorted(d for d, ops in tr.ops.items() if ops)
    if not devices:
        raise ValueError("no device ran any op in the trace")
    n = len(devices)
    busy = 0.0
    idle_by: Dict[str, float] = {}
    op_time: Dict[str, float] = {}
    prog: Dict[str, List[float]] = {p: [] for p in programs}
    exposed: List[float] = []
    for d in devices:
        ops = clip([(a, b) for _, a, b in tr.ops[d]], lo, hi)
        merged = union(ops)
        busy += total(merged)
        for name, a, b in tr.ops[d]:
            if b > lo and a < hi:
                op_time[name] = op_time.get(name, 0.0) + min(b, hi) - max(a, lo)
        gaps = subtract([(lo, hi)], merged)
        for k, v in charge_gaps(gaps, spans).items():
            idle_by[k] = idle_by.get(k, 0.0) + v
        coll = union(clip([(a, b) for nm, a, b in tr.ops[d]
                           if COLLECTIVE.match(nm)], lo, hi))
        compute = union(clip([(a, b) for nm, a, b in tr.ops[d]
                              if not COLLECTIVE.match(nm)], lo, hi))
        exposed.append(total(subtract(coll, compute)))
        for name, a, b in tr.modules.get(d, []):
            p = program_of(name, programs)
            if p is not None and a >= lo and b <= hi:
                prog[p].append(b - a)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy / n * ns,
        "devices": n,
        "programs": {
            p: {"count": len(v) / n, "mean_s": (sum(v) / len(v)) * ns}
            for p, v in prog.items() if v
        },
        "collective_exposed_s": [x * ns for x in exposed],
        "clock_offset_ns": offset,
        "idle_by_span_s": {k: v / n * ns for k, v in idle_by.items()},
        "breakdown": {
            "device_ops": [
                [k, v / n * ns] for k, v in
                sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
            ],
            "idle_gaps": [
                [k, v / n * ns] for k, v in
                sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]
            ],
        },
    }


def dump(path: Path, limit: int = 5) -> None:
    """Print every plane and line of a trace with a few event names."""
    data = _profile(path)
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for e in evs[:limit]:
                print(f"    {e.name!r} start {e.start_ns} dur {e.duration_ns}")


if __name__ == "__main__":
    dump(Path(sys.argv[1]))
