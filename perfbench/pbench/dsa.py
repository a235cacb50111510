"""What a latent (MLA) layer's selected read and the learned selection
before it (DeepSeek sparse attention) cost on the device, and how far each
is from what it has to do (PR 43; written out in ``perfbench/DSA.md``). For
the readers ``layer_metrics/mla_attn_share.py``, ``dsa_index_share.py``,
``mla_latent_roofline.py`` and ``dsa_index_roofline.py``.

* Device time by the program's ``jax.named_scope`` (``monitor/tag_schema.py
  :SCOPE_SCHEMA``): an ``XLA Ops`` event's scope is in its metadata's
  ``tf_op``, which ``pbench.moe.op_scopes`` reads out of the ``.xplane.pb``.
  An event counts, its own time only, under the INNERMOST of ``SCOPES`` its
  ``tf_op`` names: the index scores are computed from inside the read's
  loop, so ``dstpu.attn.index`` may sit inside ``dstpu.attn.latent`` in a
  name, and the two shares do not overlap.
* The count of the model's work, from shapes and the program's counters
  alone, whatever implements it (``read_work``, ``index_work``): neither
  floor can be beaten, so neither share can pass 100 %. A read that scores
  every causal key and masks the unselected does more than the floor and
  reads low; a kernel that touches the selected keys alone can come near it.
* How much of each the traced window asked for, from the program's own
  ``dstpu.engine.dispatch`` / ``dstpu.engine.prefill`` spans, each weighted
  by the share of it that lies inside the window: ``attended_keys`` and
  ``index_keys``, split into a prompt's (the chunk's or prefill's real
  tokens) and the decode steps' by the host arithmetic the program itself
  does (``engine_v2._selected_read``), repeated here from the span's
  ``chunk_start`` and ``chunk_tokens`` (``chunk_keys``).

A program without the scopes or the counters (every other model, a commit
before PR 43) gives nothing: the readers then return None.
"""

import contextlib
import itertools

from . import flops, moe
from .gdn import _inside
from .weights import _number

LATENT, INDEX = "dstpu.attn.latent", "dstpu.attn.index"
SCOPES = (LATENT, INDEX)
PREFILL, DISPATCH = "dstpu.engine.prefill", "dstpu.engine.dispatch"


# ------------------------------------------------------------ the count
def read_work(s, attended_keys, decode_keys):
    """The selected read of ``attended_keys`` (query, key) pairs, summed
    over layers, of which ``decode_keys`` belong to decode steps ->
    (operations, bytes). The least a pair can cost a head depends on the
    form: a decode step's pair has its key to itself, so the absorbed form
    is the cheaper one, a score over latent and rotary key and a value
    product over the latent, 2 x ((R + dr) + R), and the key's row of R +
    dr values has to come from the cache; a prompt's pair can share its
    key's expansion with the chunk's other queries, so what cannot be
    shared is the least: the expanded form's score and value product, 2 x
    ((dn + dr) + dv), and no bytes (ISSUE 43 wrote the absorbed count for
    both; a perfect expanded-form kernel would pass 100 % of that, so it
    is no floor for a prompt)."""
    H, R, dr = s["n_head"], s["kv_lora_rank"], s["qk_rope_head_dim"]
    dn, dv = s["qk_nope_head_dim"], s["v_head_dim"]
    prompt_keys = attended_keys - decode_keys
    return (2 * H * (prompt_keys * (dn + dr + dv)
                     + decode_keys * (2 * R + dr)),
            decode_keys * (R + dr) * s["lat_itemsize"])


def index_work(s, index_keys, decode_keys):
    """The index scores of ``index_keys`` (query, causal key) pairs ->
    (operations, bytes): Hi heads of di a pair; a decode step's pair
    brings the key's di values, as wide as the program's pool keeps them
    (``idx_itemsize``)."""
    Hi, di = s["index_n_heads"], s["index_head_dim"]
    return index_keys * 2 * Hi * di, decode_keys * di * s["idx_itemsize"]


# ------------------------------------------------ the program's selection
@contextlib.contextmanager
def tapped_selection(n_layer, seen):
    """Programs TRACED inside the block call ``seen(layer, q_pos (B, C),
    selected (B, C, keys) bool)`` from the device each time a latent layer
    has chosen its keys (``parity_dsv32.py``, ``tests/unit/
    test_deepseek_v32.py``). The program carries no hook for this: the
    block wraps ``models/paged.py``'s ``_latent_read`` to learn the
    queries' positions and, for that call, ``_kth_largest`` to see the
    scores beside the threshold they give; a row with fewer than k causal
    keys has -inf for its k-th largest, so ``>=`` it and ``> -inf`` is the
    set the read takes there too. Layers are told apart by the order of
    the calls, which every program makes layer by layer."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import paged
    read, kth = paged._latent_read, paged._kth_largest
    calls = itertools.count()

    def tapped_read(lat_pool, idx_pool, tables, q_pos, *rest):
        layer = next(calls) % n_layer

        def tapped_kth(scores, k):
            thr = kth(scores, k)
            jax.debug.callback(
                lambda q, sel: seen(layer, q, sel), q_pos,
                (scores >= thr[..., None]) & (scores > -jnp.inf))
            return thr

        paged._kth_largest = tapped_kth
        try:
            return read(lat_pool, idx_pool, tables, q_pos, *rest)
        finally:
            paged._kth_largest = kth

    paged._latent_read = tapped_read
    try:
        yield
    finally:
        paged._latent_read = read


# ------------------------------------------------------ the device's time
def scope_seconds(v):
    """({scope: own device seconds in the traced window}, busy seconds),
    averaged over devices; ({}, 0.0) without a trace. Says what it matched,
    once a trace."""
    from . import trace as tracing
    tr = getattr(v, "trace", None)
    if tr is None or not getattr(tr, "path", None):
        return {}, 0.0
    if getattr(tr, "dsa_seconds", None) is not None:     # the four readers
        return tr.dsa_seconds
    scopes = moe.op_scopes(tr.path, tracing.names()["device_plane_prefix"])
    total, by = {}, {}
    for d in tr.devices:
        for e in tr.in_window(d):
            scope = scopes.get(e.name, "")
            at = {sc: scope.rfind(sc) for sc in SCOPES if sc in scope}
            if not at:
                continue
            inner = max(at, key=at.get)
            total[inner] = total.get(inner, 0.0) + e.self_s
            key = inner + ":" + tracing.short_name(e.name)
            by[key] = by.get(key, 0.0) + e.self_s
    n = max(1, len(tr.devices))
    total = {k: s / n for k, s in total.items()}
    if total:
        v.say("dsa_device_seconds", busy_s=tr.busy_s(),
              scoped_ops=len(scopes),
              **{k.replace(".", "_") + "_s": s for k, s in total.items()},
              top=sorted(((k, s / n) for k, s in by.items()),
                         key=lambda kv: -kv[1])[:14])
    tr.dsa_seconds = total, tr.busy_s()
    return tr.dsa_seconds


def share(v, scope):
    """100 x own device time under ``scope`` / busy device time, or None
    where the traced program opened no such scope."""
    total, busy = scope_seconds(v)
    return 100.0 * total[scope] / busy if total.get(scope) and busy > 0 \
        else None


# ------------------------------------------------- what the window asked
def chunk_keys(s, start, tokens):
    """(index_keys, attended_keys) of a chunk's ``tokens`` real query
    tokens from position ``start``, every latent layer: the program's own
    arithmetic (``engine_v2._selected_read``) repeated from the span's
    ``chunk_start`` / ``chunk_tokens``."""
    first, last = start + 1, start + tokens      # contexts first .. last
    k = s["index_topk"]
    whole = (first + last) * tokens / 2
    over = max(0.0, last - max(first, k + 1) + 1)    # contexts past topk
    capped = whole - over * (max(first, k + 1) + last) / 2 + over * k
    return whole * s["n_latent"], capped * s["n_latent"]


def window_counts(v):
    """{index_keys, attended_keys, decode_index_keys, decode_attended_keys}
    of the traced window from the program's spans; None where no span
    carries ``index_keys`` (a program before PR 43), the cell's sizes name
    no latent layer, or there is no trace. A dispatch's counters hold its
    chunk's pairs and its decode steps' together; the chunk's own are
    recomputed by keys (``chunk_keys``) and the rest is the decode steps',
    exactly. A prefill span is all prompt."""
    tr = getattr(v, "trace", None)
    spans = getattr(tr, "host_spans", None)
    s = getattr(v, "sizes", None) or {}
    if spans is None or "n_latent" not in s:
        return None
    out = {"index_keys": 0.0, "attended_keys": 0.0,
           "decode_index_keys": 0.0, "decode_attended_keys": 0.0}
    counted = False
    for name in (PREFILL, DISPATCH):
        for e in spans(name):
            if "index_keys" not in e.stats:
                continue
            counted = True
            w = _inside(e, tr)
            idx = _number(e.stats, "index_keys")
            att = _number(e.stats, "attended_keys")
            out["index_keys"] += w * idx
            out["attended_keys"] += w * att
            if name == DISPATCH:
                chunk = chunk_keys(s, _number(e.stats, "chunk_start"),
                                   _number(e.stats, "chunk_tokens"))
                out["decode_index_keys"] += w * (idx - chunk[0])
                out["decode_attended_keys"] += w * (att - chunk[1])
    return out if counted else None


def _asked(v, scope, count):
    total, _ = scope_seconds(v)
    counts, s = window_counts(v), getattr(v, "sizes", None) or {}
    took = total.get(scope, 0.0)
    if took <= 0 or not counts or "kv_lora_rank" not in s \
            or counts[count] <= 0:
        return None
    return took, counts, s


def latent_roofline(v):
    """100 x least seconds of the selected read the window asked for / own
    device seconds under ``dstpu.attn.latent``."""
    asked = _asked(v, LATENT, "attended_keys")
    if asked is None:
        return None
    took, counts, s = asked
    ops, moved = read_work(s, counts["attended_keys"],
                           counts["decode_attended_keys"])
    least, bound = flops.roofline_s(ops, moved, v.peaks)
    v.say("mla_latent_roofline", latent_seconds=took, least_seconds=least,
          bound=bound, attended_keys=counts["attended_keys"],
          decode_attended_keys=counts["decode_attended_keys"])
    return 100.0 * least / took


def index_roofline(v):
    """100 x least seconds of the index scores the window asked for / own
    device seconds under ``dstpu.attn.index``."""
    asked = _asked(v, INDEX, "index_keys")
    if asked is None:
        return None
    took, counts, s = asked
    ops, moved = index_work(s, counts["index_keys"],
                            counts["decode_index_keys"])
    least, bound = flops.roofline_s(ops, moved, v.peaks)
    v.say("dsa_index_roofline", index_seconds=took, least_seconds=least,
          bound=bound, index_keys=counts["index_keys"],
          decode_index_keys=counts["decode_index_keys"])
    return 100.0 * least / took
