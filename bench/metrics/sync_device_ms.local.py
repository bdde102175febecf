"""Device time per run of the local-round program (``hsfl_round_local``)
under the ``hsfl.sync.*`` phases: the aggregation levels that run every
round (``bench/phase_time.py``)."""
from bench import phase_time


def read(rec):
    return phase_time.program_phase_ms(rec, phase_time.LOCAL_PROGRAM, "hsfl.sync")
