"""Share of its roofline that the grouped expert products reach, in %:
the least time the chip needs for one local round's grouped products
(the larger of their FLOPs over the bf16 peak and their bytes over the
HBM peak, ``bench/flops_mla_moe.py``, every client and expert layer)
over the device time of the phase ``hsfl.grad.moe.experts`` per run of
``hsfl_round_local``."""
from bench import flops_mla_moe, mla_moe


def read(rec):
    ms = mla_moe.phase_ms(rec, "hsfl.grad.moe.experts")
    wl = mla_moe.traced_workload()
    if not ms or wl is None or not rec.peaks:
        return None
    cfg, traffic = wl["config_data"], wl["traffic"]
    clients = traffic.get("clients", cfg["plan"]["clients"])
    work = flops_mla_moe.expert_gmm_work(cfg, traffic["batch"] * traffic["seq"])
    layers = flops_mla_moe.sizes(cfg)["experts_layers"] * clients
    least_s = layers * max(work["flops"] / rec.peaks["flops_bf16"],
                           work["bytes"] / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1000.0)
