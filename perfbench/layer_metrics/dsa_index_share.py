"""Own device time under the ``dstpu.attn.index`` scope (the learned
selection of a latent layer: index queries and keys, the index scores of
every causal key, the search for each query's k-th largest where the trace
names it) over the device's busy time in the traced window. None on a
program with no such layer. perfbench/DSA.md."""
from pbench import dsa


def read(v):
    return dsa.share(v, dsa.INDEX)
