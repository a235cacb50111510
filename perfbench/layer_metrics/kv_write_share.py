"""Own device time of every operation under ``dstpu.kv.write`` (``models/
paged.py:_Step``: a step's new K and V rows into the layer's donated pools,
the write kernel or the scatter) over the device's busy time in the traced
window. None on a program without the name (a commit before PR 57) or
without such a layer. perfbench/NAMES.md."""
from pbench import names


def read(v):
    return names.share(v, names.KV_WRITE)
