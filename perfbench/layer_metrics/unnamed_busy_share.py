"""Own device time of the operations of a serving cell whose ``tf_op`` holds
no ``dstpu.*`` name other than a ``dstpu.step.*`` (which says the phase, not
the operation) over the device's busy time in the traced window: what a
reader of the trace still has to name by hand. Needs no particular name, so
it reads a number on any traced program; None without a trace.
perfbench/NAMES.md."""
from pbench import names


def read(v):
    return names.unnamed_share(v)
