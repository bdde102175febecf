"""What the readers of the ``mla_moe`` cells share: the phases the
program names inside ``hsfl.grad`` (``repro.obs``) and the configuration of
the cell a traced run traced."""
from __future__ import annotations

from typing import Optional

from bench import harness, phase_time


def phase_ms(rec, wanted: str) -> Optional[float]:
    """Ms per run of the local-round program under ``wanted`` and the
    phases nested in its name; None where the trace has no phases or no
    op carries that name (a program that does not name it)."""
    red = phase_time.for_record(rec)
    prog = None if red is None else red["programs"].get(phase_time.LOCAL_PROGRAM)
    if prog is None or not prog.get("phases"):
        return None
    if not any(k == wanted or k.startswith(wanted + ".") for k in prog["phases"]):
        return None
    return phase_time.phase_ms(prog, wanted)


def traced_workload() -> Optional[dict]:
    """The cell whose trace is the newest under ``.bench_trace/`` (a traced
    run writes its own there, under the cell's name), or None."""
    path = phase_time.newest_trace()
    if path is None:
        return None
    try:
        return harness.load_workload(path.relative_to(phase_time.TRACE_ROOT).parts[0])
    except (harness.BenchError, ValueError, IndexError):
        return None
