"""Share of the traced window in which no operation ran on the device, in
%: 1 - the union of device-op intervals over the window."""


def read(rec):
    if rec.trace is None or "rounds" not in rec.counters:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])
