"""Median duration of the program's ``dstpu.engine.dispatch`` spans of kind
``fused`` in the traced window: one prompt chunk and the decode steps that
ride it, from the assembled batch to the last posted token."""

from pbench import common


def read(v):
    return common.load_module("layer_metrics", "batch_occupancy") \
        .median_dispatch_ms(v, "fused", "fused_dispatch_ms")
