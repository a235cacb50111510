"""The expert products against their roofline over the traced window: the
least time the chip could take for the live rows of every decode dispatch
and every prefill of the window (pbench/moe.py: 2*n*k*3*D*F operations; the
weights of the experts n rows touch under uniform routing + the routed rows,
at the HBM peak) over the products' own device time. The decode program
multiplies all 32 slots' rows, live or not: that waste is inside this
share, not excused. perfbench/MOE.md."""
from pbench import moe


def read(v):
    experts, _, _ = moe.device_seconds(v)
    if experts <= 0:
        return None
    least, calls = moe.least_seconds(v)
    if least <= 0:
        return None
    v.say("moe_experts_roofline", experts_seconds=experts,
          least_seconds=least, layer_calls=calls)
    return 100.0 * least / experts
