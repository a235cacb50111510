"""Own device time of every operation under ``dstpu.attn.paged`` (``models/
paged.py:_Step``: the read of a KV / ring / shared layer's pools through the
block table, whatever implements it — the paged decode kernel, the paged
chunk kernel, their work lists' gathers, or the dense gather off a TPU) over
the device's busy time in the traced window. None on a program without the
name (a commit before PR 57) or without such a layer. perfbench/NAMES.md."""
from pbench import names


def read(v):
    return names.share(v, names.ATTN_PAGED)
