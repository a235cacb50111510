"""Own device time under the ``dstpu.gdn.mix`` scope (a gated delta-rule
mixer: its projections, conv, gates, the rule in either form, the gated
norm, out-projection, and the slot state's read and write) over the
device's busy time in the traced window. None on a program with no such
layer. perfbench/GDN.md."""
from pbench import gdn


def read(v):
    return gdn.share(v, gdn.GDN_MIX)
