"""90th percentile of the time per output token in a cell below its knee
on a model whose decode step costs more the more slots are live (a sparse
model reads the experts its live rows pick): the tail follows the
occupancy a seed draws. Recorded, not judged: in `serve-olmoe-chat` it
spreads by 5.6 % from seed to seed (PERF.md, PR 26), where the end-to-end
`tpot_p90_ms` is admitted under 2 %. Same definition as that metric, from
the part of the window before a capture began."""
from pbench import common


def read(v):
    return common.percentile(v.counters["tpot_ms"], 90)
