"""Own device time of every operation under a ``dstpu.mm.*`` scope (the
dense weight products of the step programs: q/k/v, attention out, MLP,
unembed, and a hybrid's mixer projections) over the device's busy time in
the traced window. None on a program without the scopes (a commit before
PR 38). perfbench/WEIGHTS.md."""
from pbench import weights


def read(v):
    return weights.share(v)
