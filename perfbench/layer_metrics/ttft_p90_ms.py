"""90th percentile of time to first token below the knee (see ttft_p50_ms);
a failed request enters as a miss of window + drain seconds. Recorded, not
judged: it spreads by 11-26 % from seed to seed."""
from pbench import common


def read(v):
    return common.percentile(v.counters["ttft_ms"], 90)
