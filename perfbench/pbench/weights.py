"""What the dense weight products of a step program require and what the
device trace says they took (PR 38; written out in ``perfbench/WEIGHTS.md``).
For the readers ``layer_metrics/weights_roofline.py``, ``weights_share.py``
and ``train_weights_roofline.py``.

* Device time by the program's ``jax.named_scope`` (``monitor/tag_schema.py
  :SCOPE_SCHEMA``): every operation whose ``tf_op`` names a ``dstpu.mm.*``
  scope, its own time only. A fusion carries one ``tf_op``, so whatever the
  compiler fused into a product (a bias, a residual add, the next norm's
  statistics) is inside these seconds; the metrics sum every name, and the
  split by name is said. One walk a trace, kept on the ``Trace`` object.
* The passes: a serving program runs the whole stack once a decode step and
  once a prefill or chunk call, and ends each with the unembed. A run of
  consecutive ``dstpu.mm.unembed`` events closes a pass; a pass counts by
  the share of its ``dstpu.mm.*`` seconds that lie inside the window, and
  not at all where the trace does not hold its beginning.
* The count, which errs low: each dense weight (bfloat16, at its real
  shape) read once a pass, and for a prefill or chunk call of M real tokens
  what its 2 * M * P operations take beyond that. No activations, no
  biases, no padding.

A program without the scopes (a commit before PR 38, the recorded traces
``tiny4`` and ``moe1``) gives nothing: every reader then returns None.
"""

import re

from . import moe

PREFIX = "dstpu.mm."
UNEMBED = PREFIX + "unembed"
# every name the program opens (tests/unit/test_serving_spans.py lints the
# list against SCOPE_SCHEMA): the four of every family, then the Mamba
# mixer's and the Gated Memory Unit's
SCOPES = tuple(PREFIX + n for n in (
    "qkv", "attn_out", "mlp", "unembed",
    "in_proj", "x_proj", "dt", "out_proj", "gmu"))
_NAME = re.compile(re.escape(PREFIX) + r"[a-z_]+")
ITEMSIZE = 2                    # the cells hold their weights in bfloat16
PREFILL, DISPATCH = "dstpu.engine.prefill", "dstpu.engine.dispatch"


# ------------------------------------------------------------ the count
def matmul_params(s):
    """(weights of the layers' products, weights of the unembed) of one
    pass over the stack, from the builder's ``sizes``; None for a family
    this count does not know (sparse experts) or a dict without the keys."""
    try:
        if "n_experts" in s:
            return None
        D, F, L = s["d_model"], s["d_ff"], s["n_layer"]
        q, kv = s["n_head"] * s["d_head"], s["n_kv_head"] * s["d_head"]
        unembed = s["vocab_rows"] * D
        if "ssm_state" not in s:
            # pre-LN decoder of models/gpt2.py: qkv, out, up, down
            return L * (D * (q + 2 * kv) + q * D + 2 * D * F), unembed
        # models/phi4flash.py: a SwiGLU every layer, and the mixer's own
        from . import common
        kind = common.load_module("references", "phi4flash").layer_kind
        Din, R = s["ssm_expand"] * D, -(-D // 16)
        mamba = D * 2 * Din + Din * (R + 2 * s["ssm_state"]) + R * Din \
            + Din * D
        own = {"mamba": mamba, "memory": mamba,
               "window": D * (q + 2 * kv) + q * D,
               "full": D * (q + 2 * kv) + q * D,
               "cross": 2 * q * D, "gmu": 2 * D * Din}
        return sum(own[kind(i, L)] + 3 * D * F for i in range(L)), unembed
    except (KeyError, TypeError):
        return None


def _number(stats, key):
    try:
        return float(stats.get(key) or 0)
    except (TypeError, ValueError):
        return 0.0


def prefill_excess_s(v, layer_params):
    """Seconds the prefill and chunk calls of the window need beyond one
    read of the weights: a call of M real tokens (the program's own spans:
    ``tokens`` of a prefill, ``chunk_tokens`` of a chunk or fused
    dispatch) takes 2 * M * P operations at the bf16 peak, which passes the
    read at M > ~240 on a v5e. Weighted by the span's share of the window;
    0 where the program opens no such span or the span lacks the stat."""
    tr = v.trace
    spans = getattr(tr, "host_spans", None)
    if spans is None:
        return 0.0
    read = ITEMSIZE * layer_params / v.peaks["hbm_bytes_per_s"]
    per_token = 2.0 * layer_params / v.peaks["bf16_flops_per_s"]
    total = 0.0
    for name, key, kinds in ((PREFILL, "tokens", None),
                             (DISPATCH, "chunk_tokens", ("chunk", "fused"))):
        for e in spans(name):
            if kinds and e.stats.get("kind") not in kinds:
                continue
            tokens = _number(e.stats, key)
            if tokens <= 0 or e.dur <= 0:
                continue
            inside = (min(e.end, tr.t1) - max(e.start, tr.t0)) / e.dur
            total += max(0.0, tokens * per_token - read) \
                * min(1.0, max(0.0, inside))
    return total


# ------------------------------------------------------ the device's time
def walk(v):
    """{mm_s, busy_s, passes, by_scope}: own device seconds under any
    ``dstpu.mm.*`` in the traced window and the passes they make, averaged
    over devices; None without a trace or where no operation of the window
    carries such a scope. Says what it matched, once a trace."""
    from . import trace as tracing
    tr = getattr(v, "trace", None)
    if tr is None or not getattr(tr, "path", None):
        return None
    if getattr(tr, "mm_walk", None) is not None:        # the three readers
        return tr.mm_walk or None
    scopes = moe.op_scopes(tr.path, tracing.names()["device_plane_prefix"])
    # HLO text -> its dstpu.mm.* name, "" under another dstpu.* scope
    named = {}
    for text, scope in scopes.items():
        hit = _NAME.search(scope)
        if hit or "dstpu." in scope:
            named[text] = hit.group(0) if hit else ""
    by_scope, by_op, passes = {}, {}, 0.0
    mm = unscoped = 0.0
    for d in tr.devices:
        # a pass: the dstpu.mm.* events up to and with a run of unembed
        # events. ``begun``: the run before it is in the trace too
        begun = in_unembed = False
        inside = whole = 0.0
        for e in tr.devices[d]:
            name = named.get(e.name)
            windowed = e.end > tr.t0 and e.start < tr.t1
            if not name:
                if windowed and name is None:
                    unscoped += e.self_s
                continue
            if in_unembed and name != UNEMBED:
                if begun and whole > 0:
                    passes += inside / whole
                begun, inside, whole = True, 0.0, 0.0
            in_unembed = name == UNEMBED
            whole += e.self_s
            if windowed:
                inside += e.self_s
                mm += e.self_s
                by_scope[name] = by_scope.get(name, 0.0) + e.self_s
                key = name[len(PREFIX):] + ":" + tracing.short_name(e.name)
                seen = by_op.setdefault(key, [0.0, 0])
                seen[0] += e.self_s
                seen[1] += 1
        if in_unembed and begun and whole > 0:      # the trace ends on one
            passes += inside / whole
    if mm <= 0:
        tr.mm_walk = {}
        return None
    n = max(1, len(tr.devices))
    busy = tr.busy_s()
    tr.mm_walk = {"mm_s": mm / n, "busy_s": busy, "passes": passes / n,
                  "by_scope": {k: s / n for k, s in by_scope.items()}}
    v.say("weights_device_seconds", busy_s=busy, scoped_ops=len(scopes),
          mm_s=mm / n, passes=passes / n,
          under_no_dstpu_scope_s=unscoped / n,
          **{k.replace(".", "_") + "_s": s
             for k, s in sorted(tr.mm_walk["by_scope"].items())},
          top=sorted(([k, s / n, c // n] for k, (s, c) in by_op.items()),
                     key=lambda r: -r[1])[:14])
    return tr.mm_walk


def share(v):
    """100 x own device seconds under ``dstpu.mm.*`` / busy seconds."""
    walked = walk(v)
    if not walked or walked["busy_s"] <= 0:
        return None
    return 100.0 * walked["mm_s"] / walked["busy_s"]


def roofline(v):
    """100 x least seconds the chip needs for the weight products the traced
    window ran / own device seconds under ``dstpu.mm.*``; None where nothing
    was traced under the scopes, no pass closed or the family's weights are
    not counted."""
    walked, params = walk(v), matmul_params(getattr(v, "sizes", None))
    peaks = getattr(v, "peaks", None)
    if not walked or params is None or not peaks or walked["passes"] <= 0:
        return None
    layers, unembed = params
    read = ITEMSIZE * (layers + unembed) / peaks["hbm_bytes_per_s"]
    least = walked["passes"] * read + prefill_excess_s(v, layers)
    v.say("weights_roofline", least_seconds=least, passes=walked["passes"],
          mm_seconds=walked["mm_s"], weights=params)
    return 100.0 * least / walked["mm_s"]


def train_roofline(v):
    """100 x (6 x matmul parameters x the tokens a chip took through the
    traced steps / bf16 peak) / own device seconds under ``dstpu.mm.*``:
    forward, backward and whatever the step recomputes are all in the
    seconds, only the passes' required operations in the count."""
    walked, params = walk(v), matmul_params(getattr(v, "sizes", None))
    peaks = getattr(v, "peaks", None)
    counters = getattr(v, "counters", None) or {}
    tokens = _number(counters, "tokens_traced") / max(
        1, getattr(v, "chips", 1) or 1)
    if not walked or params is None or not peaks or tokens <= 0:
        return None
    least = 6.0 * sum(params) * tokens / peaks["bf16_flops_per_s"]
    v.say("train_weights_roofline", least_seconds=least,
          mm_seconds=walked["mm_s"], tokens_a_chip=tokens, weights=params)
    return 100.0 * least / walked["mm_s"]


if __name__ == "__main__":
    # the split of one trace by scope, for a cell that lists none of the
    # readers:  cd perfbench && python3 -m pbench.weights <trace dir>
    import json
    import sys
    import types

    from . import trace as tracing
    view = types.SimpleNamespace(
        trace=tracing.load(sys.argv[1]),
        say=lambda line, **f: print(json.dumps({line: f}, default=str)))
    if walk(view) is None:
        print(json.dumps({"weights_device_seconds": None}))
