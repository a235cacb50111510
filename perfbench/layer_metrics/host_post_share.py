"""Share of the traced window in which the host was inside the program's
``dstpu.engine.post`` spans (the Python loop over slots x steps that feeds
the fetched tokens to their sequences) while no operation ran on the
device."""

from pbench import common


def read(v):
    return common.load_module("layer_metrics", "host_build_share") \
        .idle_share(v, "dstpu.engine.post", "host_post_share")
