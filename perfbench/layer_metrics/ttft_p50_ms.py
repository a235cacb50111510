"""Median time to first token below the knee: due time -> the return of the
Router.step after which a client can hold a token of the request (README,
'First token'). Recorded, not judged: over the ~135 requests of a window it
spreads by 4-13 % from seed to seed (PERF.md, Spreads), more than a bound of
10 % can admit."""
from pbench import common


def read(v):
    return common.percentile(v.counters["ttft_ms"], 50)
