"""How far the chunkwise delta rule with a gate a key channel (Kimi Delta
Attention; ``solar_open2``) is from what the rule has to do (PR 54; written
out in ``perfbench/KDA.md``). For the reader ``layer_metrics/
kda_chunk_roofline.py``.

The device's seconds and the window's counts are ``pbench/gdn.py``'s, read
as they are: own device time under ``dstpu.gdn.chunk`` (the same rule with a
wider gate opens the same scope), and the real prompt tokens of the traced
window from the program's ``dstpu.engine.dispatch`` / ``dstpu.engine
.prefill`` spans. What is this file's own is the count: the rule's
operations are the scalar gate's (the decay scales S's rows, dk dv
multiplications beside 6 dk dv, left out: the floor errs low), and its
bytes hold the gate itself, a float32 log decay a key channel a token,
which the scalar rule's count has no term for (one float a head:
``gdn.rule_per_token`` leaves it out).

A program without the scope or the counters (every other model, the parent
commit), and a configuration whose builder does not say ``linear_gate ==
"channel"`` (``olmo_hybrid``: ``gdn_chunk_roofline`` is its share), gives
nothing: the reader then returns None.
"""

from . import flops, gdn

GATE_ITEMSIZE = 4               # the log decay a key channel, float32


def rule_per_token(s, itemsize=gdn.ITEMSIZE):
    """The rule of one layer for one token -> (operations, bytes): k^T S,
    the rank-one update and S^T q are 2 dk dv each a head; q, k and v read
    and o written once in ``itemsize``, the (dk,) log decay a head read
    once in float32, nothing of the state (it stays on the chip across a
    chunk)."""
    H, dk, dv = s["linear_heads"], s["linear_dk"], s["linear_dv"]
    return 6 * H * dk * dv, \
        (2 * H * dk + 2 * H * dv) * itemsize + H * dk * GATE_ITEMSIZE


def chunk_roofline(v):
    """100 x least seconds of the rule for the window's real prompt tokens
    in every delta-rule layer / own device seconds under
    ``dstpu.gdn.chunk``; None where there is nothing to read."""
    s = getattr(v, "sizes", None) or {}
    if s.get("linear_gate") != "channel":
        return None
    asked = gdn._asked(v, gdn.GDN_CHUNK, "prompt_tokens")
    if asked is None:
        return None
    took, counts, s = asked
    ops, moved = rule_per_token(s)
    n = counts["prompt_tokens"] * s["n_linear"]
    least, bound = flops.roofline_s(n * ops, n * moved, v.peaks)
    v.say("kda_chunk_roofline", chunk_seconds=took, least_seconds=least,
          bound=bound, prompt_tokens=counts["prompt_tokens"],
          rule_rows_a_prompt_token=counts["rule_rows"] / n)
    return 100.0 * least / took
