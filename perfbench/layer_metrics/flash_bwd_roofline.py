"""Flash-attention backward kernel against its roofline, over the traced
training steps: one call per layer and step (pbench/flops.flash_bwd)."""
from pbench import flops, rooflines


def read(v):
    calls = v.counters["steps_traced"] * v.sizes["n_layer"]
    f, b = flops.flash_bwd(v.counters["micro_batch_per_chip"], v.sizes,
                           v.counters["seq_len"])
    return rooflines.share(v, "flash_bwd", calls * f, calls * b)
