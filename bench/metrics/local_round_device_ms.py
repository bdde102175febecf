"""Mean device time of one run of the local-round program
(``hsfl_round_local``) in the traced window."""


def read(rec):
    p = (rec.trace or {}).get("programs", {}).get("hsfl_round_local")
    return None if p is None else 1000.0 * p["mean_s"]
