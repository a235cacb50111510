"""A kernel's share of its roofline: the least time the chip could take for
the calls a traced window made (operations and bytes from pbench/flops.py,
peaks from peaks.json) over the kernel's own time in the device trace. The
kernel is found by the pattern ``trace_names.json`` gives for it."""

from . import flops, trace


def kernel_seconds(v, which):
    """(own seconds averaged over devices, events on one device) of kernel
    ``which`` in the traced window; (0, 0) when it is not to be found."""
    pattern = trace.names()["kernels"].get(which)
    if v.trace is None or not pattern:
        return 0.0, 0
    return v.trace.op_seconds(pattern["pattern"])


def share(v, which, flops_needed, bytes_needed):
    """Roofline share in %, or None when the kernel did not run."""
    secs, events = kernel_seconds(v, which)
    if secs <= 0 or flops_needed <= 0:
        return None
    least, bound = flops.roofline_s(flops_needed, bytes_needed, v.peaks)
    v.say(which + "_roofline", kernel_seconds=secs, events=events,
          flops_needed=flops_needed, bytes_needed=bytes_needed,
          least_seconds=least, bound=bound)
    return 100.0 * least / secs
