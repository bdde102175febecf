"""The reader of ``mla_attn_device_ms``: latent attention's scores, softmax
and value product, a phase nested in ``hsfl.grad.mla``.  It finds nothing
without phases or where the program does not name the phase (a program
from before the phase), and reads the phase alone where it is named."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, phase_time  # noqa: E402


def read(rec):
    return harness.load_module(ROOT / "bench/metrics/mla_attn_device_ms.py").read(rec)


def _traced(monkeypatch, phases):
    red = {"programs": {phase_time.LOCAL_PROGRAM: {"phases": phases, "closure": 0.0}}}
    monkeypatch.setattr(phase_time, "for_record", lambda rec: red)
    return SimpleNamespace(trace={}, window_s=10.0, chips=1, counters={})


def test_nothing_without_phases():
    assert read(SimpleNamespace(trace=None, counters={}, window_s=None, peaks=None,
                                chips=1)) is None


@pytest.mark.parametrize("phases", [
    {"hsfl.grad": 90.0, "hsfl.opt": 9.0},
    {"hsfl.grad": 40.0, "hsfl.grad.mla": 30.0, "hsfl.opt": 9.0},
], ids=["no_sub_layers", "mla_without_attn"])
def test_nothing_where_the_program_does_not_name_the_phase(monkeypatch, phases):
    assert read(_traced(monkeypatch, phases)) is None


def test_the_phase_alone_and_within_mla(monkeypatch):
    from bench import mla_moe

    rec = _traced(monkeypatch, {"hsfl.grad": 40.0, "hsfl.grad.mla": 12.0,
                                "hsfl.grad.mla.attn": 18.0, "hsfl.opt": 9.0})
    assert read(rec) == 18.0
    # the phase is nested in the name of latent attention, which keeps it
    assert mla_moe.phase_ms(rec, "hsfl.grad.mla") == 30.0
    assert mla_moe.phase_ms(rec, "hsfl.grad") == 70.0
