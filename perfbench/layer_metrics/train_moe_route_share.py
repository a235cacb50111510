"""Own device time under the ``dstpu.moe.route`` (router product, sigmoid,
top-k, sort, gather of the routed rows, and their backward: the scatter-add
of dx) and ``dstpu.moe.combine`` (unsort, weight, sum, and their backward)
scopes over the device's busy time in the traced training steps. None on a
program with no MoE layer. perfbench/TRAIN_MOE.md."""
from pbench import mla_moe


def read(v):
    return mla_moe.share(v, mla_moe.ROUTE, mla_moe.COMBINE)
