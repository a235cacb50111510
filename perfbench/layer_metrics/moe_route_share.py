"""Own device time under the ``dstpu.moe.route`` (norm, router product,
softmax, top-k, sort, gather) and ``dstpu.moe.combine`` (unsort, weight,
sum) scopes over the device's busy time in the traced window. None on a
program with no MoE layer. perfbench/MOE.md."""
from pbench import moe


def read(v):
    experts, route, busy = moe.device_seconds(v)
    return 100.0 * route / busy if experts > 0 and busy > 0 else None
