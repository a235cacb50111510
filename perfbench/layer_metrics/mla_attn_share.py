"""Own device time under the ``dstpu.attn.latent`` scope (a latent layer's
attention outside its weight products: rotary, the cache write, the read of
the selected keys through the block table, scores, softmax, the value
product, in the expanded and the absorbed form) over the device's busy time
in the traced window. None on a program with no such layer.
perfbench/DSA.md."""
from pbench import dsa


def read(v):
    return dsa.share(v, dsa.LATENT)
