"""The index scores against the floor of the model's own work
(``pbench.dsa.index_work``): the window's ``index_keys`` (query, causal key)
pairs x 64 heads x 128 x 2 operations, a decode step's pairs x the key's 128
values as the program's pool keeps them (float32: 512 bytes), whichever peak
is slower, over the own device time under ``dstpu.attn.index``.
perfbench/DSA.md."""
from pbench import dsa


def read(v):
    return dsa.index_roofline(v)
