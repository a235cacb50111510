"""What the three caches of a state-space / window / shared-KV hybrid cost on
the device, and what its live sequences hold of them (PR 30; written out in
``perfbench/SSM.md``). For the readers ``layer_metrics/ssm_mix_share.py``,
``attn_window_share.py``, ``attn_shared_kv_share.py`` and
``cache_bytes_per_live_token.py``.

* Device time by the program's ``jax.named_scope`` (``monitor/tag_schema.py
  :SCOPE_SCHEMA``): an ``XLA Ops`` event's scope is in its metadata's
  ``tf_op``, which ``pbench.moe.op_scopes`` reads out of the ``.xplane.pb``;
  an event counts under the FIRST of ``SCOPES`` its ``tf_op`` names, its own
  time only (nested operations count for themselves).
* The cache held by live sequences, from the two counters the program puts
  on every ``dstpu.engine.step`` span (``cache_bytes``, ``live_tokens``).

A program without the scopes or the counters (every other model, a commit
before PR 30) gives nothing: the readers then return None.
"""

from . import moe

SSM_MIX = "dstpu.ssm.mix"
ATTN_WINDOW = "dstpu.attn.window"
ATTN_SHARED_KV = "dstpu.attn.shared_kv"
# the rest of the hybrid's layers, said beside the three that are metrics
ATTN_DIFF, GMU = "dstpu.attn.diff", "dstpu.gmu"
SCOPES = (SSM_MIX, ATTN_WINDOW, ATTN_SHARED_KV, ATTN_DIFF, GMU)
STEP = "dstpu.engine.step"


def scope_seconds(v):
    """({scope: own device seconds in the traced window}, busy seconds),
    averaged over devices; ({}, 0.0) without a trace. Says what it matched,
    once a trace."""
    from . import trace as tracing
    tr = v.trace
    if tr is None or not getattr(tr, "path", None):
        return {}, 0.0
    if getattr(tr, "ssm_seconds", None) is not None:    # the three readers
        return tr.ssm_seconds
    scopes = moe.op_scopes(tr.path, tracing.names()["device_plane_prefix"])
    total, by = {}, {}
    for d in tr.devices:
        for e in tr.in_window(d):
            scope = scopes.get(e.name, "")
            hit = next((sc for sc in SCOPES if sc in scope), None)
            if hit is None:
                continue
            total[hit] = total.get(hit, 0.0) + e.self_s
            key = hit + ":" + tracing.short_name(e.name)
            by[key] = by.get(key, 0.0) + e.self_s
    n = max(1, len(tr.devices))
    total = {k: s / n for k, s in total.items()}
    if total:
        v.say("ssm_device_seconds", busy_s=tr.busy_s(), scoped_ops=len(
            scopes), **{k.replace(".", "_") + "_s": s
                        for k, s in total.items()},
            top=sorted(((k, s / n) for k, s in by.items()),
                       key=lambda kv: -kv[1])[:12])
    tr.ssm_seconds = total, tr.busy_s()
    return tr.ssm_seconds


def share(v, scope):
    """100 x own device time under ``scope`` / busy device time, or None
    where the traced program opened no such scope."""
    total, busy = scope_seconds(v)
    return 100.0 * total[scope] / busy if total.get(scope) and busy > 0 \
        else None


def cache_bytes_per_live_token(v):
    """Bytes of cache the sequences in a slot hold over the tokens they have
    seen, summed over the engine steps of the traced window; None where the
    step spans carry no such counters."""
    if v.trace is None:
        return None
    steps = [e.stats for e in v.trace.host_spans(STEP)
             if "cache_bytes" in e.stats and "live_tokens" in e.stats]
    held = sum(int(s["cache_bytes"]) for s in steps)
    tokens = sum(int(s["live_tokens"]) for s in steps)
    if not tokens:
        return None
    v.say("cache_bytes_per_live_token", engine_steps=len(steps),
          cache_bytes_mean=held / len(steps),
          live_tokens_mean=tokens / len(steps),
          live_sequences_mean=sum(int(s["active"]) for s in steps)
          / len(steps))
    return held / tokens
