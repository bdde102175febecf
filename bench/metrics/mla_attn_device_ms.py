"""Device time per run of the local-round program (``hsfl_round_local``)
under the phase ``hsfl.grad.mla.attn``: latent attention's scores, causal
softmax and value product (the splash kernel on the TPU), forward and
backward (``bench/phase_time.py``).  None for a program that does not name
the phase."""
from bench import mla_moe


def read(rec):
    return mla_moe.phase_ms(rec, "hsfl.grad.mla.attn")
