"""Median duration of the program's ``dstpu.engine.dispatch`` spans of kind
``decode`` in the traced window: one decode program call from the assembled
batch to the last posted token."""

from pbench import common


def read(v):
    return common.load_module("layer_metrics", "batch_occupancy") \
        .median_dispatch_ms(v, "decode_dispatch_ms", "decode")
