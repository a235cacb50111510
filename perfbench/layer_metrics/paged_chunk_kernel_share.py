"""Own device time of the operations named ``dstpu.kernel.paged_chunk``
(the Pallas kernel of a prompt chunk's, a bucketed prefill's or a verify
pass's paged attention, by the name its ``pallas_call`` passes and not by
the shapes of its custom call) over the device's busy time in the traced
window. None on a program without the name (a commit before PR 57) or whose
window ran no such kernel. perfbench/NAMES.md."""
from pbench import names


def read(v):
    return names.share(v, names.PAGED_CHUNK_KERNEL)
