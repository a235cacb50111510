"""Median time to first token above the knee, from the due time. The queue
grows all through such a run, so this swings with the smallest change: it
is recorded here and judges no PR."""
from pbench import common


def read(v):
    return common.percentile(v.counters["ttft_ms"], 50)
