"""Own device time under the ``dstpu.attn.full`` scope (a full-attention
layer's K/V write into its pool and the paged read: the chunk kernel in a
prefill or chunk program, the decode kernel in a decode step) over the
device's busy time in the traced window. None on a program that opens no
such scope. perfbench/GDN.md."""
from pbench import gdn


def read(v):
    return gdn.share(v, gdn.ATTN_FULL)
