"""The chunkwise delta rule with a gate a key channel against the floor of
the rule itself: the least time the chip could take for the window's real
prompt tokens in every delta-rule layer (6 H dk dv operations and (2 H dk +
2 H dv) x 2 + H dk x 4 bytes a token a layer: q, k, v read, o written, the
float32 log decay a channel read; whichever peak is slower) over the own
device time under ``dstpu.gdn.chunk``. The floor counts the rule and not
its chunkwise form, so it reads the same work whatever implements it (XLA
at PR 54). None on a program without the scope or the counters and on a
configuration whose gate is one a head. perfbench/KDA.md."""
from pbench import kda


def read(v):
    return kda.chunk_roofline(v)
