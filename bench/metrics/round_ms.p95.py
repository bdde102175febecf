"""95th percentile (nearest rank) of the wall time of every round in the
window, batch preparation and the loss on the host included."""
import math


def read(rec):
    rounds = sorted(rec.counters.get("round_s", []))
    if not rounds:
        return None
    return 1000.0 * rounds[math.ceil(0.95 * len(rounds)) - 1]
