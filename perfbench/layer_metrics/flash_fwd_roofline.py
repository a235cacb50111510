"""Flash-attention forward kernel against its roofline, over the traced
training steps: one call per layer and step at (micro-batch, heads, T, hd),
causal (pbench/flops.flash_fwd)."""
from pbench import flops, rooflines


def read(v):
    calls = v.counters["steps_traced"] * v.sizes["n_layer"]
    f, b = flops.flash_fwd(v.counters["micro_batch_per_chip"], v.sizes,
                           v.counters["seq_len"])
    return rooflines.share(v, "flash_fwd", calls * f, calls * b)
