"""What every benchmark cell shares: finding its files, the device, the clocks.

The harness is driven by data.  A cell is ``bench/workloads/<cell>.json``;
it names a configuration (``bench/configs/<config>.json``) and a mode
(``bench/modes/<mode>.py``).  Every metric, end-to-end or per layer, is a
reader ``bench/metrics/<metric>.py`` with ``read(rec) -> float | None``.
``BENCHMARK.json`` at the root of the checkout says which metrics a cell
reports.  Adding a cell, a configuration, a mode or a metric adds files and
edits none.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import re
import time
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WORKLOAD_KEYS = {"config", "mode", "traffic", "chips", "why", "limits"}
CONFIG_KEYS = {"name", "arch", "family", "reference", "source", "precision",
               "plan", "reduced", "assumed", "deployment"}


class BenchError(Exception):
    """A cell, configuration or metric that the harness cannot use."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def _check_name(kind: str, name: str) -> None:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise BenchError(f"{kind} name {name!r} is not a legal name")


def load_workload(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    """``bench/workloads/<name>.json``, checked for the keys a cell needs."""
    _check_name("workload", name)
    path = bench / "workloads" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no workload {name!r} ({path})")
    wl = load_json(path)
    missing = WORKLOAD_KEYS - set(wl)
    extra = set(wl) - WORKLOAD_KEYS
    if missing or extra:
        raise BenchError(f"workload {name}: missing {sorted(missing)}, "
                         f"unknown {sorted(extra)}")
    _check_name("config", wl["config"])
    _check_name("mode", wl["mode"])
    if wl["chips"] not in (1, 4):
        raise BenchError(f"workload {name}: chips must be 1 or 4")
    if not (bench / "modes" / f"{wl['mode']}.py").is_file():
        raise BenchError(f"workload {name}: no mode {wl['mode']!r}")
    wl["config_data"] = load_config(wl["config"], bench)
    wl["name"] = name
    return wl


def load_config(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    _check_name("config", name)
    path = bench / "configs" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no configuration {name!r} ({path})")
    cfg = load_json(path)
    missing = CONFIG_KEYS - set(cfg)
    if missing:
        raise BenchError(f"configuration {name}: missing {sorted(missing)}")
    if cfg["name"] != name:
        raise BenchError(f"configuration {path} calls itself {cfg['name']!r}")
    if not (bench / "reference" / f"{cfg['reference']}.py").is_file():
        raise BenchError(f"configuration {name}: no reference "
                         f"{cfg['reference']!r}")
    return cfg


def workload_names(bench: Path = BENCH) -> List[str]:
    return sorted(p.stem for p in (bench / "workloads").glob("*.json"))


def load_module(path: Path, name: Optional[str] = None) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        name or f"bench_{path.stem.replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def mode_module(mode: str, bench: Path = BENCH) -> ModuleType:
    return load_module(bench / "modes" / f"{mode}.py", f"bench_mode_{mode}")


def reference_module(cfg: Dict[str, Any], bench: Path = BENCH) -> ModuleType:
    return load_module(bench / "reference" / f"{cfg['reference']}.py",
                       f"bench_ref_{cfg['reference']}")


def metrics_for(benchmark: Dict[str, Any], cell: str, trace: bool) -> List[dict]:
    """The metrics a cell reports: its end-to-end metrics (with ``--trace
    0``) or its per-layer metrics (with ``--trace 1``)."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in benchmark[key]
            if "workloads" not in m or cell in m["workloads"]]


def read_metrics(specs: List[dict], rec, bench: Path = BENCH) -> Dict[str, dict]:
    """Run each metric's reader; a reader that finds nothing is left out."""
    out = {}
    for m in specs:
        reader = load_module(bench / "metrics" / f"{m['name']}.py")
        value = reader.read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------- #
# seeds, clocks, device
# --------------------------------------------------------------------------- #


def derive_seed(seed: int) -> int:
    """A 31-bit seed for JAX and NumPy from any whole ``--seed``.

    ``jax.random.PRNGKey`` keeps only the low 32 bits of a large seed, so
    seeds 2**32 apart would collide; the seed sequence mixes every bit.
    """
    import numpy as np

    if seed < 0:
        raise BenchError(f"--seed must be a whole number >= 0, got {seed}")
    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


def process_start_time() -> float:
    """Wall-clock second at which this process started (``time.time()``
    scale), from ``/proc``; the time of the first call where that fails."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return btime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Seconds and events of JAX tracing, lowering and compiling (or loading
    from the persistent cache) while entered."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


def round_spread(round_s) -> Dict[str, float]:
    """How the window's rounds spread: the median and slowest round, and
    the rounds over twice the median with the seconds they took beyond it
    (stalls of the host show here)."""
    s = sorted(round_s)
    med = s[len(s) // 2]
    slow = [x for x in s if x > 2 * med]
    return {"p50_ms": 1e3 * med, "max_ms": 1e3 * s[-1], "slow_rounds": len(slow),
            "slow_excess_s": sum(x - med for x in slow)}


def peak_bytes(devices) -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest device, where the backend tells."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def load_peaks(device_kind: str, bench: Path = BENCH) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = load_json(bench / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         "bench/peaks.json")
    return table[device_kind]


TRACE_SECONDS = 10.0  # a traced run traces at most this much of its window


def start_window_trace(ctx) -> float:
    """Start the profiler for a ``--trace 1`` run; return the window's
    length in seconds (the traced part, with tracing on).

    Only the device is traced (``host_tracer_level`` 0): the host tracer
    records every host-side relayout of an input batch and would slow the
    host side it is meant to observe.  The benchmark's host spans come
    from its own clock (``Spans``) and are placed on the trace's clock by
    ``trace_reduce.align``."""
    if not ctx["trace"]:
        return ctx["seconds"]
    import shutil

    import jax

    shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(str(ctx["trace_dir"]), profiler_options=opts)
    return min(ctx["seconds"], TRACE_SECONDS)


class Spans:
    """The benchmark's host spans on the ``perf_counter`` clock:
    ``with spans("batch_prep"): ...`` records ``(name, start_ns, end_ns)``."""

    def __init__(self):
        self.events: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.events.append((name, t0, time.perf_counter_ns()))


def span_ms(events) -> Dict[str, float]:
    """Mean milliseconds of each span name among ``(name, start_ns, end_ns)``."""
    acc: Dict[str, List[int]] = {}
    for name, a, b in events:
        acc.setdefault(name, []).append(b - a)
    return {k: sum(v) / len(v) / 1e6 for k, v in acc.items()}


def new_record(**kw) -> SimpleNamespace:
    """What a mode hands to the metric readers and the result line."""
    base = dict(
        attempted=0, failed=0, setup_s=None, window_s=None, counters={},
        trace=None, check=[], peak_bytes=None, flops=None, peaks=None,
        chips=1, compiles_in_window=0, check_inputs=None,
    )
    base.update(kw)
    return SimpleNamespace(**base)
