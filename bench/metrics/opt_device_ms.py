"""Device time per run of the local-round program (``hsfl_round_local``)
under the phase ``hsfl.opt``: the optimizer update (``bench/phase_time.py``).
It reads 0 where XLA fused the update into the syncs' ops."""
from bench import phase_time


def read(rec):
    return phase_time.program_phase_ms(rec, phase_time.LOCAL_PROGRAM, "hsfl.opt")
