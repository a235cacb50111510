"""The weight products of the traced training steps against the bf16 peak:
6 x matmul parameters x the tokens a chip took through those steps, over
the own device time of every operation under a ``dstpu.mm.*`` scope —
forward, backward and recomputation alike, so what the step recomputes is
waste inside this share. None on a program without the scopes.
perfbench/WEIGHTS.md."""
from pbench import weights


def read(v):
    return weights.train_roofline(v)
