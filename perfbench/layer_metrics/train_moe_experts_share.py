"""Own device time of a training step's expert products (the three grouped
products of every MoE layer, their dx and dW and their recomputation:
``dstpu.moe.experts`` scope, ragged-dot fusions by name) over the device's
busy time in the traced steps. None on a program with no such operation.
perfbench/TRAIN_MOE.md."""
from pbench import mla_moe


def read(v):
    return mla_moe.share(v, mla_moe.EXPERTS)
