"""Device time per run of the local-round program (``hsfl_round_local``)
under the phase ``hsfl.grad.moe.route``: the gate, the top-k selection,
the sort and gather that dispatch the routed rows, and the combine,
forward and backward (``bench/phase_time.py``)."""
from bench import mla_moe


def read(rec):
    return mla_moe.phase_ms(rec, "hsfl.grad.moe.route")
