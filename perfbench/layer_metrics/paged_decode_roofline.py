"""Paged decode-attention kernel against its roofline over the traced
window. The cached positions each dispatch has to read are counted from the
client's side (each live request's prompt + tokens so far, 8 steps a
dispatch), per layer; K and V bytes at the HBM peak (pbench/flops.
paged_decode). The pool pads head dim 64 to 128 lanes, so the kernel moves
about twice these bytes: that waste is inside this share, not excused."""
from pbench import flops, rooflines


def read(v):
    kv = v.counters["traced_kv_tokens_read"] * v.sizes["n_layer"]
    f, b = flops.paged_decode(kv, v.sizes)
    return rooflines.share(v, "paged_decode", f, b)
