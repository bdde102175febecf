"""Training FLOPs the window's rounds need (``bench/flops.py``) per second
of the window, over the chips' bf16 peak, in %: the whole step's share of
the peak, host and idle time included."""


def read(rec):
    if "samples" not in rec.counters or not rec.window_s or not rec.flops or not rec.peaks:
        return None
    flops = rec.counters["samples"] * rec.flops["per_sample"]
    return 100.0 * flops / rec.window_s / (rec.chips * rec.peaks["flops_bf16"])
