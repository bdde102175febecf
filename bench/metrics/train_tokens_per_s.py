"""Training tokens completed per second in a language-model cell: clients
x batch x sequence length x rounds completed, over the whole window on
the host clock."""


def read(rec):
    seq = rec.counters.get("tokens_per_sample")
    if not seq or not rec.window_s:
        return None
    return rec.counters["samples"] * seq / rec.window_s
