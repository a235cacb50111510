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
  (``held_rows_work``). The attention's floor follows from the shapes
  and cannot be beaten. The held experts' follows from the ROUTING: the
  rows the router sent the held experts in the traced steps and the held
  experts that got one, each traced step counted on its own parameters
  (``HeldRows``, which the runner has the builder make, PR 55), not the
  rows an even router would have sent: this cell's router sends its held
  experts a fraction of those within a dozen steps, and a floor from the
  even count stood above the time of a program that multiplies the sent
  rows alone (ledger, PR 50: 163 %). Built from what was sent, what was
  called and the gradients the optimizer is owed, it cannot be beaten
  either, so neither share can pass 100 %.
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

from . import common, flops, moe

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
    """Expected held experts a token takes under uniform routing: top_k x
    held / published. What ``train_flops_per_token`` (``mfu_routed``)
    counts, the same on both sides of any pair; a router gives this chip
    more or fewer (``HeldRows`` counts them), and this cell's gives far
    fewer once it has trained a few steps."""
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


def held_rows_work(rows, called, s, layer_steps=1, itemsize=2):
    """The held experts, forward and backward, over ``rows`` (token,
    choice) pairs sent to them, of which ``called`` experts got at least
    one: of one sparse layer and step, or the sums over ``layer_steps`` of
    them (every term is linear) -> (operations, bytes). 3 products x
    (forward + dx + dW), 2 * D * F each a row; a called expert's weights
    read twice (forward, dx), as many bytes for 1 row as for 1,000, and
    none for an expert that was sent nothing; EVERY held expert's gradient
    written once (the optimizer takes a dense gradient a leaf: zeros are
    written too); the rows read and written once each way (x and y
    forward, dy and dx backward)."""
    D, F = s["d_model"], s["moe_d_ff"]
    ops = 3 * 3 * 2 * rows * D * F
    weights = (2 * called + layer_steps * s["n_experts"]) \
        * expert_params(s) * itemsize
    return ops, weights + 4 * rows * D * itemsize


def held_experts_work(tokens, s, itemsize=2):
    """``held_rows_work`` of what an EVEN router would send ``tokens``
    tokens of a step: ``held_experts_per_token`` rows a token and every
    held expert called. The count before PR 55, TRAIN_MOE.md's table and
    tests/unit/test_deepseek_v3.py's arithmetic. No reader divides by it."""
    return held_rows_work(tokens * held_experts_per_token(s),
                          s["n_experts"], s, itemsize=itemsize)


# ------------------------------------------------- the rows that were sent
def held_rows_program(forward, s):
    """The jitted (params, ids (B, T)) -> (sparse layers, held experts) int
    count: the (token, choice) pairs whose chosen expert is held expert i
    of that layer, as the program's own router chose. ``forward(params,
    ids)`` runs the program's blocks (the builder's: it knows the family);
    the program returns no count of its routing and carries no hook for
    one, so while ``forward`` is traced a tap stands in for
    ``moe/sharded_moe.route_topk`` and notes its answer. The tap sees the
    choice and nothing after it: the count is the same whatever multiplies
    the experts (``lax.ragged_dot``, the grouped kernels, a walk over the
    held rows). A stopgap, and not safe beside another thread that traces:
    it goes when the step returns its ``group_sizes`` (PERF.md section 7)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe import sharded_moe
    held = s["experts_offset"] + jnp.arange(s["n_experts"])

    def count(params, ids):
        route, seen = sharded_moe.route_topk, []

        def tapped(*a, **k):
            weights, experts = route(*a, **k)
            seen.append(jnp.sum(experts[..., None] == held, axis=(0, 1)))
            return weights, experts

        sharded_moe.route_topk = tapped
        try:
            forward(params, ids)
        finally:
            sharded_moe.route_topk = route
        if len(seen) != s["n_sparse"]:
            raise common.CheckFailed(
                f"the tap on moe/sharded_moe.route_topk saw {len(seen)} "
                f"routings where the model has {s['n_sparse']} sparse "
                f"layers: the program no longer looks the router up in its "
                f"module at every call, or shares one trace between layers")
        return jnp.stack(seen)

    return jax.jit(count)


class HeldRows:
    """The runner's counters of the traced steps' routing
    (``builders/deepseek_v3.traced_counters``). Every step donates its
    parameters to the next, so each traced step's rows are counted on the
    parameters IT is about to use. The first's at once: the profiler has
    not opened, the device is idle, the host waits for the count. A later
    one's parameters exist only between two traced steps: a copy of them
    is made on the device there (one operation in the trace, the
    parameters' bytes read and written once, and as many held until the
    count) and counted once the profiler has closed, so the count's
    program is in no trace."""

    def __init__(self, forward, s):
        import jax
        import jax.numpy as jnp
        self.s = s
        self.program = held_rows_program(forward, s)
        self.copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
        self.counts, self.later, self.tokens = [], [], 0

    def count(self, params, batch):
        """The routing of ``batch`` at ``params``, now: the host waits."""
        import numpy as np
        self.counts.append(np.asarray(
            self.program(params, batch["input_ids"])))    # (layer, expert)
        self.tokens += batch["input_ids"].size

    def keep(self, params, batch):
        """The same, counted by ``counters``: a copy of ``params`` is
        enqueued and nothing waited for."""
        self.later.append((self.copy(params), batch))

    def counters(self, at):
        """-> {counter: number}: the sums over the steps counted or kept
        and their sparse layers; says them a layer-step beside the even
        router's. Empties itself."""
        import time
        t0 = time.perf_counter()
        while self.later:
            self.count(*self.later.pop(0))
        counts, s = self.counts, self.s
        rows = int(sum(c.sum() for c in counts))
        called = int(sum((c > 0).sum() for c in counts))
        calls = len(counts) * s["n_sparse"]
        even = self.tokens * s["n_sparse"] * held_experts_per_token(s)
        common.say(
            "held_rows", at=at, rows_a_layer_step=rows / calls,
            even_router_a_layer_step=even / calls, sent_of_even=rows / even,
            called_a_layer_step=called / calls, held_experts=s["n_experts"],
            rows_by_step_and_layer=[c.sum(axis=1).tolist() for c in counts],
            called_by_step_and_layer=[(c > 0).sum(axis=1).tolist()
                                      for c in counts],
            later_steps_seconds=time.perf_counter() - t0)
        self.counts, self.tokens = [], 0
        return {"held_rows_traced": rows,
                "held_experts_called_traced": called}

    def warm(self, params, batch):
        """Set-up: both programs compile here; the line says how the
        parameters the warm-up left route."""
        import jax
        jax.block_until_ready(self.copy(params))
        self.count(params, batch)
        self.counters("set-up: the parameters the warm-up left")


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
