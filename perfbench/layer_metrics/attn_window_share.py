"""Own device time under the ``dstpu.attn.window`` scope (a windowed
layer's K/V write into its slot's ring and the paged read of at most the
window's keys) over the device's busy time in the traced window. None on a
program that keeps no window as slot state. perfbench/SSM.md."""
from pbench import ssm


def read(v):
    return ssm.share(v, ssm.ATTN_WINDOW)
