"""What the gated delta-rule layers and the full-attention layers of a
hybrid (``olmo_hybrid``) cost on the device, and how far the rule's two forms
are from what they have to do (PR 41; written out in ``perfbench/GDN.md``).
For the readers ``layer_metrics/gdn_mix_share.py``, ``attn_full_share.py``,
``gdn_chunk_roofline.py`` and ``gdn_state_roofline.py``.

* Device time by the program's ``jax.named_scope`` (``monitor/tag_schema.py
  :SCOPE_SCHEMA``): an ``XLA Ops`` event's scope is in its metadata's
  ``tf_op``, which ``pbench.moe.op_scopes`` reads out of the ``.xplane.pb``.
  An event counts, its own time only, under EVERY one of ``SCOPES`` its
  ``tf_op`` names: ``dstpu.gdn.chunk`` and ``dstpu.gdn.step`` are opened
  inside ``dstpu.gdn.mix``, and the mixer's seconds hold theirs.
* The count of the rule itself, from shapes alone, whatever implements it
  (``rule_per_token``, ``state_bytes``): neither floor can be beaten, so
  neither share can pass 100 %.
* How much of each the traced window asked for, from the program's own
  ``dstpu.engine.dispatch`` / ``dstpu.engine.prefill`` spans, each weighted
  by the share of it that lies inside the window: a chunk's real tokens
  (``chunk_tokens``, ``tokens``), a decode-bearing dispatch's one-token
  updates (``state_updates`` = live slots x steps x linear layers).

A program without the scopes or the counters (every other model, a commit
before PR 41) gives nothing: the readers then return None.
"""

from . import flops, moe
from .weights import _number

GDN_MIX, GDN_CHUNK, GDN_STEP = ("dstpu.gdn.mix", "dstpu.gdn.chunk",
                                "dstpu.gdn.step")
ATTN_FULL = "dstpu.attn.full"
SCOPES = (GDN_MIX, GDN_CHUNK, GDN_STEP, ATTN_FULL)
PREFILL, DISPATCH = "dstpu.engine.prefill", "dstpu.engine.dispatch"
ITEMSIZE = 2                    # q, k, v, o of the rule in bfloat16


# ------------------------------------------------------------ the count
def rule_per_token(s, itemsize=ITEMSIZE):
    """The rule of one layer for one token -> (operations, bytes): k^T S,
    the rank-one update and S^T q are 2 dk dv each a head; q, k read and v
    read, o written once, nothing of the state (it stays on the chip
    across a chunk)."""
    H, dk, dv = s["linear_heads"], s["linear_dk"], s["linear_dv"]
    return 6 * H * dk * dv, (2 * H * dk + 2 * H * dv) * itemsize


def state_bytes(s):
    """One layer's matrix state of one sequence, float32."""
    return s["linear_heads"] * s["linear_dk"] * s["linear_dv"] * 4


# ------------------------------------------------------ the device's time
def scope_seconds(v):
    """({scope: own device seconds in the traced window}, busy seconds),
    averaged over devices; ({}, 0.0) without a trace. Says what it matched,
    once a trace."""
    from . import trace as tracing
    tr = getattr(v, "trace", None)
    if tr is None or not getattr(tr, "path", None):
        return {}, 0.0
    if getattr(tr, "gdn_seconds", None) is not None:     # the four readers
        return tr.gdn_seconds
    scopes = moe.op_scopes(tr.path, tracing.names()["device_plane_prefix"])
    total, by = {}, {}
    for d in tr.devices:
        for e in tr.in_window(d):
            scope = scopes.get(e.name, "")
            hits = [sc for sc in SCOPES if sc in scope]
            for sc in hits:
                total[sc] = total.get(sc, 0.0) + e.self_s
            if hits:
                key = hits[-1] + ":" + tracing.short_name(e.name)
                by[key] = by.get(key, 0.0) + e.self_s
    n = max(1, len(tr.devices))
    total = {k: s / n for k, s in total.items()}
    if total:
        v.say("gdn_device_seconds", busy_s=tr.busy_s(),
              scoped_ops=len(scopes),
              **{k.replace(".", "_") + "_s": s for k, s in total.items()},
              top=sorted(((k, s / n) for k, s in by.items()),
                         key=lambda kv: -kv[1])[:14])
    tr.gdn_seconds = total, tr.busy_s()
    return tr.gdn_seconds


def share(v, scope):
    """100 x own device time under ``scope`` / busy device time, or None
    where the traced program opened no such scope."""
    total, busy = scope_seconds(v)
    return 100.0 * total[scope] / busy if total.get(scope) and busy > 0 \
        else None


# ------------------------------------------------- what the window asked
def _inside(e, tr):
    return min(1.0, max(0.0, (min(e.end, tr.t1) - max(e.start, tr.t0))
                        / max(e.dur, 1e-12)))


def window_counts(v):
    """{prompt_tokens, rule_rows, state_updates} of the traced window from
    the program's spans; None where no span carries ``rule_rows`` (a
    program before PR 41) or there is no trace."""
    tr = getattr(v, "trace", None)
    spans = getattr(tr, "host_spans", None)
    if spans is None:
        return None
    out = {"prompt_tokens": 0.0, "rule_rows": 0.0, "state_updates": 0.0}
    counted = False
    for name, key in ((PREFILL, "tokens"), (DISPATCH, "chunk_tokens")):
        for e in spans(name):
            if "rule_rows" not in e.stats:
                continue
            counted = True
            w = _inside(e, tr)
            out["prompt_tokens"] += w * _number(e.stats, key)
            out["rule_rows"] += w * _number(e.stats, "rule_rows")
            out["state_updates"] += w * _number(e.stats, "state_updates")
    return out if counted else None


def _asked(v, scope, count):
    """(own device seconds under ``scope``, the window's counts, the
    configuration's sizes), or None where the program opened no such scope,
    its spans lack the counters, or the window asked for none of
    ``count``."""
    total, _ = scope_seconds(v)
    counts, s = window_counts(v), getattr(v, "sizes", None) or {}
    took = total.get(scope, 0.0)
    if took <= 0 or not counts or "linear_dk" not in s \
            or counts[count] <= 0:
        return None
    return took, counts, s


def chunk_roofline(v):
    """100 x least seconds of the rule for the window's real prompt tokens
    in every linear layer / own device seconds under ``dstpu.gdn.chunk``."""
    asked = _asked(v, GDN_CHUNK, "prompt_tokens")
    if asked is None:
        return None
    took, counts, s = asked
    ops, moved = rule_per_token(s)
    n = counts["prompt_tokens"] * s["n_linear"]
    least, bound = flops.roofline_s(n * ops, n * moved, v.peaks)
    v.say("gdn_chunk_roofline", chunk_seconds=took, least_seconds=least,
          bound=bound, prompt_tokens=counts["prompt_tokens"],
          rule_rows_a_prompt_token=counts["rule_rows"] / n)
    return 100.0 * least / took


def state_roofline(v):
    """100 x least seconds to read and write the state of every one-token
    update the window made / own device seconds under ``dstpu.gdn.step``."""
    asked = _asked(v, GDN_STEP, "state_updates")
    if asked is None:
        return None
    took, counts, s = asked
    least = counts["state_updates"] * 2 * state_bytes(s) \
        / v.peaks["hbm_bytes_per_s"]
    v.say("gdn_state_roofline", step_seconds=took, least_seconds=least,
          state_updates=counts["state_updates"])
    return 100.0 * least / took
