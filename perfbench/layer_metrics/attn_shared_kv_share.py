"""Own device time under the ``dstpu.attn.shared_kv`` scope (the one
full-length K/V of a decoder-hybrid-decoder: the full layer's write and
read, and every cross-decoder layer's read of the same pool) over the
device's busy time in the traced window. None on a program with no layer
that reads another layer's pool. perfbench/SSM.md."""
from pbench import ssm


def read(v):
    return ssm.share(v, ssm.ATTN_SHARED_KV)
