"""Own device time of the expert products (the three grouped products of
every MoE layer: ``dstpu.moe.experts`` scope, ragged-dot fusions by name)
over the device's busy time in the traced window. None on a program with no
such operation (a dense model, a commit before PR 26). perfbench/MOE.md."""
from pbench import moe


def read(v):
    experts, _, busy = moe.device_seconds(v)
    return 100.0 * experts / busy if experts > 0 and busy > 0 else None
