"""Device time per run of the round program whose syncs reach every tier
(``hsfl_round_fed_TTT`` for three tiers) under the ``hsfl.sync.*`` phases:
every entity and fed-server level (``bench/phase_time.py``)."""
from bench import phase_time


def read(rec):
    return phase_time.program_phase_ms(
        rec, rec.counters.get("full_fed_program"), "hsfl.sync")
