"""The chunkwise gated delta rule against the floor of the rule itself: the
least time the chip could take for the window's real prompt tokens in every
linear layer (6 H dk dv operations and (2 H dk + 2 H dv) x 2 bytes a token
a layer, whichever peak is slower) over the own device time under
``dstpu.gdn.chunk``. The floor counts the rule and not its chunkwise form,
so it reads the same work whatever implements it. perfbench/GDN.md."""
from pbench import gdn


def read(v):
    return gdn.chunk_roofline(v)
