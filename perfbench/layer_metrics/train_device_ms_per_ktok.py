"""Device-busy milliseconds per 1000 tokens trained, over the traced steps
(busy time averaged over the chips; tokens per chip)."""


def read(v):
    tokens = v.counters["tokens_traced"] / v.chips
    if v.trace is None or not tokens:
        return None
    return 1e3 * v.trace.busy_s() / (tokens / 1e3)
