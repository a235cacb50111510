"""The rule's one-token update against the HBM peak: every live slot's
matrix state read once and written once a step a linear layer (the
program's ``state_updates`` x 2 x H dk dv x 4 bytes) over the own device
time under ``dstpu.gdn.step``. perfbench/GDN.md."""
from pbench import gdn


def read(v):
    return gdn.state_roofline(v)
