"""Own device time under the ``dstpu.attn.mla`` scope (a training MLA
layer's attention outside its weight products: rotary, the flash forward
and backward, the padding and slicing of V around them, their
recomputation) over the device's busy time in the traced steps. None on a
program with no such scope. perfbench/TRAIN_MOE.md."""
from pbench import mla_moe


def read(v):
    return mla_moe.share(v, mla_moe.MLA)
