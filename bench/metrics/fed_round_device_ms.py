"""Mean device time of one run of the round program whose syncs reach
every tier (``hsfl_round_fed_TTT`` for three tiers) in the traced window:
the step with every entity and fed-server mean."""


def read(rec):
    progs = (rec.trace or {}).get("programs", {})
    p = progs.get(rec.counters.get("full_fed_program"))
    return None if p is None else 1000.0 * p["mean_s"]
