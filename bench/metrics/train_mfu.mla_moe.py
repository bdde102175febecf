"""Training FLOPs the window's rounds need for the DeepSeek-V3 block
(``bench/flops_mla_moe.py``: MLA, routed work at the expected held pairs,
shared experts, the dense layers and the head) per second of the window,
over the chips' bf16 peak, in %: the whole step's share of the peak, host
and idle time included.  The traced cell gives the configuration."""
from bench import flops_mla_moe, mla_moe


def read(rec):
    seq = rec.counters.get("tokens_per_sample")
    if "samples" not in rec.counters or not seq or not rec.window_s or not rec.peaks:
        return None
    wl = mla_moe.traced_workload()
    if wl is None or wl["config_data"].get("family") != "mla_moe":
        return None
    flops = rec.counters["samples"] * flops_mla_moe.train_flops_per_sequence(
        wl["config_data"], seq)
    return 100.0 * flops / rec.window_s / (rec.chips * rec.peaks["flops_bf16"])
