"""Device time per run of the local-round program (``hsfl_round_local``)
under the phase ``hsfl.grad.mla``: every client's latent attention,
forward and backward (``bench/phase_time.py``)."""
from bench import mla_moe


def read(rec):
    return mla_moe.phase_ms(rec, "hsfl.grad.mla")
