"""Share of the traced window in which the host was inside the program's
``dstpu.engine.fetch`` spans (the program call and the blocking read of its
tokens) while no operation ran on the device: launch latency, and the time
between the device finishing and the host waking."""

from pbench import common


def read(v):
    return common.load_module("layer_metrics", "host_build_share") \
        .idle_share(v, "dstpu.engine.fetch", "host_sync_share")
