"""Mean host time of the program's ``loader`` span (``repro.obs``: the
loader's ``next_round()``, gathering the round's rows) over the window's
rounds; ``batch_prep_ms`` less this is the batch's transfer.  The window's
rounds are the last ``loader`` spans the program recorded."""
from bench import phase_time


def read(rec):
    rounds = rec.counters.get("rounds")
    ns = [b - a for name, a, b in phase_time.run_host_spans()
          if name == phase_time.LOADER_SPAN]
    if not rounds or not ns:
        return None
    ns = ns[-rounds:]
    return sum(ns) / len(ns) / 1e6
