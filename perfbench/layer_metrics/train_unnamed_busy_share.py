"""Own device time of the operations of a training cell whose ``tf_op``
holds no ``dstpu.*`` name over the device's busy time in the traced steps
(``unnamed_busy_share`` for the cells that report ``train_tok_s_chip``; a
training step opens no ``dstpu.step.*``). None without a trace.
perfbench/NAMES.md."""
from pbench import names


def read(v):
    return names.unnamed_share(v)
