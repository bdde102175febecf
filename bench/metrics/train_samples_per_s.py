"""Training samples (images, or sequences) completed per second: clients x
batch x rounds completed, over the whole window on the host clock."""


def read(rec):
    if "samples" not in rec.counters or not rec.window_s:
        return None
    return rec.counters["samples"] / rec.window_s
