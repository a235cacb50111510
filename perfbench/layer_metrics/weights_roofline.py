"""The dense weight products against their roofline over the traced window:
the least time the chip needs to read every dense weight (bfloat16, at its
real shape) once a pass over the stack (a decode step, a prefill or a chunk
call, counted from the device's own ``dstpu.mm.unembed`` events), plus what
a prefill's or chunk's 2 * M * P operations for its M real tokens take
beyond that read, over the own device time of every operation under a
``dstpu.mm.*`` scope. Whatever the compiler fused into a product, the rows
of dead slots and a prompt's padding are inside this share, not excused.
None on a program without the scopes. perfbench/WEIGHTS.md."""
from pbench import weights


def read(v):
    return weights.roofline(v)
