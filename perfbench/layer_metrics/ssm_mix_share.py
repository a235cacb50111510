"""Own device time under the ``dstpu.ssm.mix`` scope (a Mamba mixer: its
projections, conv, the selective scan or its one-step update, gate, and
the slot state's read and write) over the device's busy time in the traced
window. None on a program with no state-space layer. perfbench/SSM.md."""
from pbench import ssm


def read(v):
    return ssm.share(v, ssm.SSM_MIX)
