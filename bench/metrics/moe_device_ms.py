"""Device time per run of the local-round program (``hsfl_round_local``)
under the phases ``hsfl.grad.moe.*``: every client's expert layers,
forward and backward: routing, the grouped expert products and the shared
experts (``bench/phase_time.py``)."""
from bench import mla_moe


def read(rec):
    return mla_moe.phase_ms(rec, "hsfl.grad.moe")
