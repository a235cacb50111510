"""Peak device memory of the fullest chip, in GB (1e9 bytes): the larger of
``memory_stats()['peak_bytes_in_use']`` and what the largest loaded program
needs while it runs (arguments + temporaries + outputs not donated, as the
compiler counts them). Both are on the earlier line ``memory``; on this
backend the first equals the resident state and misses the temporaries
(pbench/common.memory_peak_bytes)."""


def read(v):
    seen = max(v.counters.get("peak_bytes_in_use", 0),
               v.counters.get("largest_program_bytes", 0))
    return seen / 1e9 if seen else None
