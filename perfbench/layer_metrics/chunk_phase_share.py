"""Own device time of every operation under ``dstpu.step.chunk`` (the
prompt chunk of a fused or chunk-only dispatch, sampling included; the
decode steps that ride a fused dispatch are under ``dstpu.step.decode``)
over the device's busy time in the traced window: the split of a dispatch's
device time that ``fused_dispatch_ms``, one host span a program call,
cannot make. None on a program without the name (a commit before PR 57) or
whose window ran no chunk. perfbench/NAMES.md."""
from pbench import names


def read(v):
    return names.share(v, names.STEP_CHUNK)
