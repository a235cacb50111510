"""Bytes of cache (blocks under the block tables, window rings, recurrent
state) held by the sequences in a slot, over the tokens those sequences
have seen: the program's own counters ``cache_bytes`` / ``live_tokens`` on
the ``dstpu.engine.step`` spans of the traced window. None on a program
that does not count them (before PR 30). perfbench/SSM.md."""
from pbench import ssm


def read(v):
    return ssm.cache_bytes_per_live_token(v)
