"""Paged chunk-attention kernel (split-fuse prefill) against its roofline
over the traced window: causal attention of every prompt whose prefill ended
in the window, however it was chunked, per layer (pbench/flops.
paged_prefill)."""
from pbench import flops, rooflines


def read(v):
    f = b = 0
    for p in v.counters["traced_prompts"]:
        pf, pb = flops.paged_prefill(p, v.sizes)
        f, b = f + pf, b + pb
    L = v.sizes["n_layer"]
    return rooflines.share(v, "paged_chunk", f * L, b * L)
