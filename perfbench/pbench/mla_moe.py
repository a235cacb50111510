"""What a TRAINING step of the DeepSeek-V3 family, held as one chip's share
of an expert-parallel group, requires, and what the device trace says its
attention and its expert layer took (PR 47; written out in
``perfbench/TRAIN_MOE.md``). For the readers ``layer_metrics/mfu_routed.py``,
``train_mla_attn_share.py``, ``train_moe_experts_share.py``,
``train_moe_route_share.py``, ``train_mla_flash_roofline.py`` and
``train_moe_experts_roofline.py``.

* The count of the model's work, from shapes alone, whatever implements it:
  the parameters held (``held_params``), the operations a token requires
  forward and backward (``train_flops_per_token``: MFU's convention of
  ``pbench/flops.py``, backward = 2 x forward, recomputation not counted),
  the attention's own floor (``mla_flash_work``) and the held experts'
  (``held_experts_work``). Neither floor can be beaten, so neither share
  can pass 100 %.
* Device time by the program's ``jax.named_scope`` (``monitor/tag_schema.py
  :SCOPE_SCHEMA``): an ``XLA Ops`` event's scope is in its metadata's
  ``tf_op``, which ``pbench.moe.op_scopes`` reads out of the ``.xplane.pb``.
  A backward operation's ``tf_op`` carries its forward's scope
  (``transpose(jvp(dstpu.attn.mla))``), so forward, recomputation and
  backward all count. An event counts, its own time only, under the
  INNERMOST of ``SCOPES`` its ``tf_op`` names; a ragged-dot fusion, whose
  own metadata loses the scope, counts as the expert products by its name
  (as ``pbench/moe.py`` does).

``s`` is the dict ``builders/deepseek_v3.sizes(cfg)`` returns. A program
without the scopes (every other model, a commit before PR 47) gives
nothing: the readers then return None.
"""

from . import flops, moe

MLA = "dstpu.attn.mla"
EXPERTS = "dstpu.moe.experts"
ROUTE, COMBINE = "dstpu.moe.route", "dstpu.moe.combine"
SCOPES = (MLA, EXPERTS, ROUTE, COMBINE)


# ------------------------------------------------------------ the count
def attention_params(s):
    """Matmul parameters of one MLA layer: the query projection (direct, or
    through its latent), the joint latent + rotary-key projection, the
    per-head key and value expansions, the output projection."""
    D, H, R = s["d_model"], s["n_head"], s["kv_lora_rank"]
    dn, dr, dv = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                  s["v_head_dim"])
    Rq = s.get("q_lora_rank")
    q = D * H * (dn + dr) if Rq is None else D * Rq + Rq * H * (dn + dr)
    return q + D * (R + dr) + H * dn * R + H * R * dv + H * dv * D


def expert_params(s):
    """One routed expert: gate, up and down."""
    return 3 * s["d_model"] * s["moe_d_ff"]


def held_params(s):
    """Every parameter this chip holds: the matmul weights, the norms'
    gains and the gate's correction bias (a buffer, counted: it is held)."""
    D, R = s["d_model"], s["kv_lora_rank"]
    norms = 2 * D + R + (s["q_lora_rank"] or 0)
    attn = attention_params(s) + norms
    sparse = D * s["n_experts_published"] + s["n_experts_published"] \
        + (s["n_experts"] + s["n_shared_experts"]) * expert_params(s)
    return 2 * s["vocab_size"] * D + D \
        + s["n_dense"] * (attn + 3 * D * s["d_ff"]) \
        + s["n_sparse"] * (attn + sparse)


def held_experts_per_token(s):
    """Expected held experts a token takes under uniform routing, which
    routers with seeded random weights over uniform ids give nearly:
    top_k x held / published. A skewed router gives this chip more or
    fewer."""
    return s["top_k"] * s["n_experts"] / s["n_experts_published"]


def train_flops_per_token(s, seq_len):
    """Forward + backward of one token at sequence length ``seq_len`` at
    THIS SHARE:

        6 * (matmul parameters a token touches)
            a dense layer: attention + the dense SwiGLU
            a sparse layer: attention + shared experts + router
                            + held_experts_per_token routed experts
            the head's slice (V x D; the embedding is a lookup)
      + 6 * L * H * (dn + dr + dv) * T / 2 * 2
            causal attention: QK^T over 192-wide keys and PV over 128-wide
            values are 2 * T * width each a head when full, half that when
            causal; backward costs twice forward

    Recomputation (remat, flash's backward recompute of the scores) and the
    padding of V to the key width are not counted: this is what the passes
    require, for MFU."""
    D, L = s["d_model"], s["n_layer"]
    attn = attention_params(s)
    dense = attn + 3 * D * s["d_ff"]
    sparse = attn + s["n_shared_experts"] * expert_params(s) \
        + D * s["n_experts_published"] \
        + held_experts_per_token(s) * expert_params(s)
    touched = s["n_dense"] * dense + s["n_sparse"] * sparse \
        + s["vocab_size"] * D
    width = s["qk_nope_head_dim"] + s["qk_rope_head_dim"] + s["v_head_dim"]
    return 6 * touched + 6 * L * s["n_head"] * width * seq_len / 2


def mla_flash_work(batch, s, seq_len, itemsize=2):
    """One layer's causal attention, forward and backward, over (batch, H,
    T) -> (operations, bytes). Forward 2 products (QK^T over dn + dr, PV
    over dv), backward 5 (the scores again, dP = dO V^T, dV = P^T dO, dQ =
    dS K, dK = dS^T Q): 4 over the key width and 3 over the value width,
    2 * T * T / 2 each under the causal mask. Operands read and results
    written once: q, k, v, o forward; q, k, v, o, dO, dq, dk, dv
    backward."""
    H, T = s["n_head"], seq_len
    dk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    dv = s["v_head_dim"]
    ops = batch * H * T * T * (4 * dk + 3 * dv)
    values = batch * H * T * ((2 * dk + 2 * dv) + (4 * dk + 4 * dv))
    return ops, values * itemsize


def held_experts_work(tokens, s, itemsize=2):
    """One layer's held experts for ``tokens`` tokens of a step, forward
    and backward -> (operations, bytes): 3 products x (forward + dx + dW)
    over the expected held rows (``held_experts_per_token`` a token), 2 *
    D * F each a row; each held weight read twice (forward, dx) and its
    gradient written once; the held rows read and written once each way
    (x and y forward, dy and dx backward)."""
    D, F = s["d_model"], s["moe_d_ff"]
    rows = tokens * held_experts_per_token(s)
    ops = 3 * 3 * 2 * rows * D * F
    weights = 3 * s["n_experts"] * expert_params(s) * itemsize
    return ops, weights + 4 * rows * D * itemsize


# ------------------------------------------------------ the device's time
def scope_seconds(v):
    """({scope: own device seconds in the traced window}, busy seconds),
    averaged over devices; ({}, 0.0) without a trace. Says what it matched,
    once a trace."""
    from . import trace as tracing
    tr = getattr(v, "trace", None)
    if tr is None or not getattr(tr, "path", None):
        return {}, 0.0
    if getattr(tr, "mla_moe_seconds", None) is not None:   # the readers
        return tr.mla_moe_seconds
    scopes = moe.op_scopes(tr.path, tracing.names()["device_plane_prefix"])
    total, by = {}, {}
    for d in tr.devices:
        for e in tr.in_window(d):
            scope = scopes.get(e.name, "")
            at = {sc: scope.rfind(sc) for sc in SCOPES if sc in scope}
            if at:
                inner = max(at, key=at.get)
            elif moe.RAGGED.search(e.name.split("(", 1)[0]) \
                    or moe.RAGGED.search(scope):
                inner = EXPERTS
            else:
                continue
            total[inner] = total.get(inner, 0.0) + e.self_s
            key = inner + ":" + tracing.short_name(e.name)
            by[key] = by.get(key, 0.0) + e.self_s
    n = max(1, len(tr.devices))
    total = {k: t / n for k, t in total.items()}
    if total:
        v.say("mla_moe_device_seconds", busy_s=tr.busy_s(),
              scoped_ops=len(scopes),
              **{k.replace(".", "_") + "_s": t for k, t in total.items()},
              top=sorted(((k, t / n) for k, t in by.items()),
                         key=lambda kv: -kv[1])[:14])
    tr.mla_moe_seconds = total, tr.busy_s()
    return tr.mla_moe_seconds


def share(v, *scopes):
    """100 x own device time under ``scopes`` / busy device time, or None
    where the traced program opened the first of them nowhere."""
    total, busy = scope_seconds(v)
    if not total.get(scopes[0]) or busy <= 0:
        return None
    return 100.0 * sum(total.get(sc, 0.0) for sc in scopes) / busy


def roofline(v, scope, what, ops, bytes_moved):
    """100 x the least seconds the chip could take for (ops, bytes_moved)
    over the own device time under ``scope``; None where nothing ran under
    it or the cell's sizes are another family's."""
    total, _ = scope_seconds(v)
    took = total.get(scope, 0.0)
    if took <= 0 or ops <= 0:
        return None
    least, bound = flops.roofline_s(ops, bytes_moved, v.peaks)
    v.say(what, scope_seconds=took, flops_needed=ops,
          bytes_needed=bytes_moved, least_seconds=least, bound=bound)
    return 100.0 * least / took
