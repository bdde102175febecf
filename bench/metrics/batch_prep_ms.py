"""Mean host time per round of the benchmark's ``batch_prep`` span: the
loader's ``next_round()`` and the transfer of the batch to the device."""


def read(rec):
    return rec.counters.get("span_ms", {}).get("batch_prep")
