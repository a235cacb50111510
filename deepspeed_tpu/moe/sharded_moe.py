"""Mixture-of-Experts: gating + expert-parallel dispatch.

Counterpart of the reference's ``deepspeed/moe/sharded_moe.py`` (TopKGate
:348, MOELayer :425, top1gating :184, top2gating :282) and
``deepspeed/moe/experts.py``. TPU-first redesign:

  * Gating is pure jnp over the full (tokens, experts) matrix — top-1/top-2
    selection, capacity enforcement by cumsum position, auxiliary
    load-balance loss, gumbel (RSample) noisy gating — no host sync, no
    dynamic shapes.
  * Dispatch/combine are dense one-hot einsums (the Mesh-TensorFlow/GShard
    formulation): ``dispatch (S,E,C) x tokens (S,M) -> (E,C,M)``. On the MXU
    a dense einsum beats gather/scatter; XLA fuses the one-hot.
  * Expert parallelism is declarative: the (E,C,M) dispatched buffer and the
    (E,...) expert weights are sharded on the 'expert' mesh axis, so the
    contraction from batch-sharded tokens to expert-sharded buffers lowers
    to exactly the all_to_all pair the reference issues by hand
    (sharded_moe.py:505-520 _AllToAll), but fused and overlapped by XLA.
  * Experts compute as one grouped GEMM over the leading E dim (the
    megablox/ragged-dot pattern with static capacity), not a Python loop
    over expert modules (reference experts.py:13 loops; fine for GPUs,
    wasteful under jit).
"""

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..utils.groups import BATCH_AXES


def _constrain(x, spec):
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return lax.with_sharding_constraint(x, spec)


# ------------------------------------------------- grouped expert FFNs
# The expert-FFN grouped product has three backends: 'ragged' =
# lax.ragged_dot (the generic-XLA path, and the parity reference);
# 'kernel' = the differentiable Pallas grouped-GEMM launches (ops/pallas/
# grouped_matmul.py: per-group tile maps, fused SwiGLU epilogue, per-group
# fp32 dw) at the tiles the knob gives; and 'forward' = the one-launch
# forward SwiGLU chain whose tiles come from the shape (each touched
# expert's weights streamed through VMEM once, in megabytes; an expert
# with no rows costs nothing). "auto" decides between 'ragged' and
# 'forward' from what it can observe — platform, dtype, shape — and off
# the TPU is byte-identical to the ragged program.

def resolve_grouped_params(knob, rows, E_loc, M, F, dtype):
    """Trace-time backend/tile resolution for the grouped expert FFN.
    ``knob``: "auto" | True (kernel, default tiles) | False (ragged_dot)
    | dict (explicit params).

    "auto": on a TPU, in a program GSPMD does not partition (a bare
    Mosaic call is refused there), a call of at most
    ``FORWARD_ROWS_PER_GROUP`` rows a group — a serving program's decode
    step or prefill bucket — whose shape forms tiles (``forward_tiles``)
    takes the forward kernel; everything else is the ragged program."""
    from ..ops.pallas import grouped_matmul as gm
    if knob is False or knob is None:
        return dict(gm.TUNE_DEFAULTS)
    if knob is True:
        return dict(gm.TUNE_DEFAULTS, backend="kernel")
    if isinstance(knob, dict):
        return {**gm.TUNE_DEFAULTS, **knob}
    from ..ops.pallas._common import gspmd_partitioned, interpret_default
    if not interpret_default() and not gspmd_partitioned() \
            and rows <= gm.FORWARD_ROWS_PER_GROUP * E_loc \
            and gm.forward_tiles(rows, M, F, dtype) is not None:
        return dict(gm.TUNE_DEFAULTS, backend="forward")
    return dict(gm.TUNE_DEFAULTS)


def _grouped_dot(xs, w, group_sizes, params):
    if params.get("backend") == "kernel":
        from ..ops.pallas.grouped_matmul import grouped_matmul
        return grouped_matmul(xs, w, group_sizes,
                              block_m=int(params["block_m"]),
                              block_n=int(params["block_n"]),
                              block_k=int(params["block_k"]))
    return lax.ragged_dot(xs, w, group_sizes)


def _grouped_swiglu_ffn(xs, w1, w3, w2, group_sizes, params):
    from ..ops.int8_weights import _is_q
    from ..ops.pallas._common import note_call
    backend = params.get("backend")
    quantized = _is_q(w1)
    int8 = not quantized and bool(params.get("int8"))
    note_call("expert", quantized or (
        not int8 and backend in ("kernel", "forward")))
    if quantized:
        # weight-only quantized experts (serving): dequant fused into
        # the grouped kernel's flush epilogue — int8/int4 bytes stream
        # HBM->VMEM, no dequantized (E, K, N) tensor materializes
        from ..ops.pallas.grouped_matmul import grouped_swiglu_wq
        return grouped_swiglu_wq(xs, w1, w3, w2, group_sizes,
                                 block_m=int(params["block_m"]),
                                 block_n=int(params["block_n"]),
                                 block_k=int(params["block_k"]))
    if int8:
        # dynamic int8 activation x weight compute (autotune lever
        # 'moe_grouped_int8'): per-row activation scales, int32
        # accumulate, straight-through fp backward
        from ..ops.pallas.quantization import grouped_int8_matmul
        g = grouped_int8_matmul(xs, w1, group_sizes)
        u = grouped_int8_matmul(xs, w3, group_sizes)
        return grouped_int8_matmul(jax.nn.silu(g) * u, w2, group_sizes)
    if backend in ("kernel", "forward"):
        from ..ops.pallas.grouped_matmul import grouped_swiglu
        # 'forward': no block given, the tiles come from the shape
        blocks = {} if backend == "forward" else {
            k: int(params[k]) for k in ("block_m", "block_n", "block_k")}
        return grouped_swiglu(xs, w1, w3, w2, group_sizes, **blocks)
    g = lax.ragged_dot(xs, w1, group_sizes)
    u = lax.ragged_dot(xs, w3, group_sizes)
    return lax.ragged_dot(jax.nn.silu(g) * u, w2, group_sizes)


def resolve_moe_int8(knob, rows, E_loc, M, F, dtype):
    """Resolve the MoE int8-compute lever ("auto" consults the
    'moe_grouped_int8' winner cache; a cold cache resolves 0 — byte-
    identical program). Returns 0/1 to merge into the grouped params."""
    if knob in (False, None):
        return 0
    if knob is True:
        return 1
    from ..ops.pallas._common import (dispatch, dtype_name,
                                      moe_grouped_bucket)
    return int(dispatch("moe_grouped_int8",
                        moe_grouped_bucket(rows, E_loc, M, F),
                        dtype_name(dtype), {"int8": 0})["int8"])


def _capacity(num_tokens, num_experts, capacity_factor, min_capacity):
    """Static per-expert capacity (reference sharded_moe.py:_capacity)."""
    cap = int(math.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, int(min_capacity))


def _gumbel(rng, shape):
    return -jnp.log(-jnp.log(
        jax.random.uniform(rng, shape, jnp.float32, 1e-20, 1.0 - 1e-20)))


def top1gating(logits, capacity_factor=1.0, min_capacity=4,
               noisy_gate_policy=None, rng=None, drop_tokens=True):
    """Switch-style top-1 gating (reference sharded_moe.py:184).

    logits: (S, E) fp32. Returns (l_aux, combine_weights (S,E,C) fp32,
    dispatch_mask (S,E,C) bool, exp_counts (E,)).
    """
    S, E = logits.shape
    C = _capacity(S, E, capacity_factor, min_capacity)
    if not drop_tokens:
        C = S  # full capacity: nothing dropped, memory = dense routing

    gates = jax.nn.softmax(logits, axis=-1)

    select_logits = logits
    if noisy_gate_policy == "RSample":
        if rng is None:
            raise ValueError("RSample noisy gating needs an rng")
        select_logits = logits + _gumbel(rng, logits.shape)
    elif noisy_gate_policy == "Jitter":
        if rng is None:
            raise ValueError("Jitter noisy gating needs an rng")
        select_logits = logits * jax.random.uniform(
            rng, logits.shape, jnp.float32, 0.99, 1.01)

    idx1 = jnp.argmax(select_logits, axis=-1)                   # (S,)
    mask1 = jax.nn.one_hot(idx1, E, dtype=jnp.float32)          # (S, E)
    exp_counts = jnp.sum(mask1, axis=0)

    # load-balance aux loss (reference :241): E * <fraction routed> . <prob>
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    # position of each token within its expert's queue; drop overflow
    locations1 = jnp.cumsum(mask1, axis=0) - mask1              # (S, E)
    mask1 = mask1 * (locations1 < C)
    loc1_s = jnp.sum(locations1 * mask1, axis=-1).astype(jnp.int32)  # (S,)

    gate1 = jnp.sum(gates * mask1, axis=-1)                     # (S,)
    cap_oh = jax.nn.one_hot(loc1_s, C, dtype=jnp.float32)       # (S, C)
    combine = (gate1[:, None] * mask1)[:, :, None] * cap_oh[:, None, :]
    dispatch = combine > 0
    return l_aux, combine, dispatch, exp_counts


def top2gating(logits, capacity_factor=1.0, min_capacity=4, rng=None,
               drop_tokens=True, top2_2nd_expert_sampling=True):
    """GShard top-2 gating (reference sharded_moe.py:282): capacity doubles,
    second expert chosen after masking the first (optionally with gumbel
    sampling), gate weights renormalized over the kept pair."""
    S, E = logits.shape
    C = _capacity(S, E, 2 * capacity_factor, min_capacity)
    if not drop_tokens:
        C = S

    gates = jax.nn.softmax(logits, axis=-1)
    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = jax.nn.one_hot(idx1, E, dtype=jnp.float32)

    logits2 = logits
    if top2_2nd_expert_sampling:
        if rng is None:
            raise ValueError("top2 2nd-expert sampling needs an rng")
        logits2 = logits + _gumbel(rng, logits.shape)
    logits2 = jnp.where(mask1 > 0, -jnp.inf, logits2)
    idx2 = jnp.argmax(logits2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, E, dtype=jnp.float32)

    exp_counts = jnp.sum(mask1 + mask2, axis=0)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    locations1 = jnp.cumsum(mask1, axis=0) - mask1
    # second-choice queue starts after all first choices (reference :300)
    locations2 = jnp.cumsum(mask2, axis=0) - mask2 + jnp.sum(mask1, axis=0)
    mask1 = mask1 * (locations1 < C)
    mask2 = mask2 * (locations2 < C)
    loc1_s = jnp.sum(locations1 * mask1, axis=-1).astype(jnp.int32)
    loc2_s = jnp.sum(locations2 * mask2, axis=-1).astype(jnp.int32)

    gate1 = jnp.sum(gates * mask1, axis=-1)
    gate2 = jnp.sum(gates * mask2, axis=-1)
    denom = jnp.clip(gate1 + gate2, 1e-9, None)
    gate1, gate2 = gate1 / denom, gate2 / denom

    cap1 = jax.nn.one_hot(loc1_s, C, dtype=jnp.float32)
    cap2 = jax.nn.one_hot(loc2_s, C, dtype=jnp.float32)
    combine = ((gate1[:, None] * mask1)[:, :, None] * cap1[:, None, :] +
               (gate2[:, None] * mask2)[:, :, None] * cap2[:, None, :])
    dispatch = combine > 0
    return l_aux, combine, dispatch, exp_counts


class TopKGate:
    """Gate config + apply (reference sharded_moe.py:348 TopKGate)."""

    def __init__(self, k=1, capacity_factor=1.0, eval_capacity_factor=1.0,
                 min_capacity=4, noisy_gate_policy=None, drop_tokens=True,
                 top2_2nd_expert_sampling=True):
        if k not in (1, 2):
            raise ValueError("only top-1 and top-2 gating supported")
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.min_capacity = min_capacity
        self.noisy_gate_policy = noisy_gate_policy
        self.drop_tokens = drop_tokens
        self.top2_2nd_expert_sampling = top2_2nd_expert_sampling

    def __call__(self, logits, rng=None, train=True):
        cf = self.capacity_factor if train else self.eval_capacity_factor
        if self.k == 1:
            return top1gating(
                logits, cf, self.min_capacity,
                self.noisy_gate_policy if train else None, rng,
                self.drop_tokens)
        return top2gating(
            logits, cf, self.min_capacity, rng, self.drop_tokens,
            self.top2_2nd_expert_sampling and train and rng is not None)


def route_topk(x, gate_w, k, renormalize=True, *, scoring="softmax",
               bias=None, n_group=1, topk_group=1, scale=1.0):
    """The MoE models' router, serving and training (``models/mixtral.py``,
    ``deepseek_v32.py``, ``deepseek_v3.py``), shared by the one-device
    ``_mlp`` and the expert-parallel path: float32 logits (the product
    itself at HIGHEST precision — on TPU a default float32 matmul
    multiplies in bf16, enough to flip a near-tie between the k-th and
    (k+1)-th expert), float32 scores over ALL experts, top-k. ``renormalize``
    divides the k scores by their sum (mixtral, HF
    ``norm_topk_prob=True``); False uses them as they are (OLMoE: they
    sum to ~k/E at random init, not to 1).

    ``scoring`` "softmax" (mixtral, OLMoE) or "sigmoid" (DeepSeek-V3's
    ``noaux_tc``). ``bias`` (E,): a correction added to the scores for
    CHOOSING and left out of the weights. ``n_group`` > 1: group-limited
    choice — the experts lie in ``n_group`` equal groups, a group's score
    is the sum of its two largest (biased) scores, and only the
    ``topk_group`` best groups' experts can be chosen. ``scale``
    multiplies the weights last. The defaults are the softmax router as
    it was, operation for operation.
    x (S, M), gate_w (M, E) -> (weights (S, k) f32, experts (S, k) i32)."""
    logits = jnp.matmul(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scoring is 'softmax' or 'sigmoid', got "
                         f"{scoring!r}")
    if bias is None and n_group == 1:
        weights, experts = lax.top_k(probs, k)
    else:
        choose = probs if bias is None \
            else probs + bias.astype(jnp.float32)
        if n_group > 1:
            S, E = choose.shape
            group = jnp.sum(lax.top_k(choose.reshape(S, n_group, -1), 2)[0],
                            axis=-1)
            kept = lax.top_k(group, topk_group)[1]               # (S, tg)
            keep = jnp.any(kept[:, :, None] == jnp.arange(n_group),
                           axis=1)                                # (S, G)
            choose = jnp.where(jnp.repeat(keep, E // n_group, axis=1),
                               choose, -jnp.inf)
        experts = lax.top_k(choose, k)[1]
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32)


def moe_swiglu_routed(xs, weights, experts, w1, w3, w2, grouped="auto",
                      int8=False, held=None, out_dtype=None):
    """The one-device dropless expert layer once the router has spoken,
    of a serving step and, under ``jax.grad``, of a training step
    (``models/deepseek_v3.py``): the routed rows sorted by expert, the
    three grouped products (``lax.ragged_dot`` or the Pallas grouped
    kernel, as :func:`resolve_grouped_params` answers), unsorted and
    summed by the routing weights, under the ``dstpu.moe.*`` scopes. xs
    (S, D), weights / experts (S, k) -> (S, D) ``out_dtype`` (xs's).
    Differentiable in xs, weights and the expert arrays (the sort's
    gather transposes to a scatter-add; ``experts`` are indices).

    ``held`` = (offset, count): THE SHARE of an expert-parallel
    deployment this device holds — w1 / w3 / w2 are experts ``offset ..
    offset + count - 1`` of those the router chose among. A routed row
    whose expert lies elsewhere sorts behind every held expert's rows and
    past the groups' sum: the grouped products fetch no weight for it and
    write nothing there (zeros on the CPU, whatever the buffer held on the
    chip: both ends of the products select it away), and it adds nothing
    and takes no gradient. What the absent experts would have added is
    left out: the partial sum another device's exchange would complete.
    (With this two-part form the absent rows are still sorted and
    gathered: S * k rows exist whatever share is held.)

    ``held`` = (offset, count, published), ``published`` the number of
    experts the router chose among: the same sum, and the absent experts'
    rows are never moved. Only the S * k int32 keys are sorted; the held
    experts' rows are gathered, multiplied and scatter-added a chunk at a
    time (:func:`_held_walk`), as many chunks as the router's rows fill
    (:func:`held_walk_taken`), none where it sent none. So few rows that
    one chunk would be all of them take the one pass above."""
    S, D = xs.shape
    k = experts.shape[1]
    E = w1.scale.shape[0] if hasattr(w1, "scale") else w1.shape[0]
    out_dtype = xs.dtype if out_dtype is None else out_dtype
    if held is not None and len(held) == 3:
        chunk = _walk_chunk(S * k, held[1], held[2])
        if chunk < S * k:
            return _held_walk(xs, weights, experts, w1, w3, w2, grouped,
                              int8, held[0], chunk, out_dtype)
        held = held[:2]
    with jax.named_scope("dstpu.moe.route"):
        flat_exp = experts.reshape(-1)
        flat_w = weights.reshape(-1).astype(out_dtype)
        if held is not None:
            offset, count = held
            local = flat_exp - offset
            mine = (local >= 0) & (local < count)
            # absent experts' rows: one group behind the held ones, which
            # the grouped products are not told of
            flat_exp = jnp.where(mine, local, count)
            flat_w = jnp.where(mine, flat_w, 0)
        x_rep = jnp.repeat(xs, k, axis=0)
        order = jnp.argsort(flat_exp, stable=True)
        xr = x_rep[order]
        group_sizes = jnp.bincount(
            flat_exp, length=E + (held is not None))[:E].astype(jnp.int32)
        if held is not None:
            # the products neither read nor WRITE a row past the groups:
            # on the chip lax.ragged_dot leaves there what the buffer
            # held, in the backward too (dx), where the gather's
            # scatter-add would carry it into xs's gradient. The select
            # transposes to a select: nothing of it gets through.
            xr = jnp.where((jnp.arange(S * k) < jnp.sum(group_sizes))[:, None],
                           xr, 0)

    F = w1.scale.shape[-1] if hasattr(w1, "scale") else w1.shape[-1]
    gp = resolve_grouped_params(grouped, S * k, E, D, F, xr.dtype)
    if int8:
        gp = dict(gp, int8=resolve_moe_int8(int8, S * k, E, D, F, xr.dtype))
    with jax.named_scope("dstpu.moe.experts"):
        o = _grouped_swiglu_ffn(xr, w1, w3, w2, group_sizes, gp)
    with jax.named_scope("dstpu.moe.combine"):
        unsorted = jnp.zeros_like(o).at[order].set(o)
        if held is not None:
            # a row past the groups is zeros by the products' contract;
            # nothing of it may reach the sum whatever a backend left there
            unsorted = jnp.where(mine[:, None], unsorted, 0)
        y = jnp.sum((unsorted.astype(out_dtype)
                     * flat_w[:, None]).reshape(S, k, D), axis=1)
    return y


def _walk_chunk(rows, count, published):
    """Rows a chunk of the held walk: half of what an even router sends
    ``count`` of ``published`` experts out of ``rows`` routed rows, up to
    the next 128. A chunk costs its positions whether a held row fills
    them or not, and a fixed part besides (the experts' weights read, their
    gradients written). Timed on the chip at a share of 1/8 of 98,304 rows
    against one and two times the even count (PERF.md section 5, PR 56):
    half is the fastest wherever the router sends less than the even count
    (8.8 / 11.4 / 15.3 ms a layer at 300 rows) and 4 ms of 23 behind two
    times at the even count itself."""
    return -(-rows * count // (2 * published * 128)) * 128


def held_walk_taken(experts, k, held):
    """How many chunks :func:`moe_swiglu_routed` walks for one layer's
    routing ``experts`` (S, k) under ``held`` = (offset, count, published),
    by the program's own rule: the held experts' rows over the chunk,
    rounded up; every chunk past the first runs under ``dstpu.moe.spill``.
    None where the one pass runs instead (no walk to take)."""
    offset, count, published = held
    rows = experts.shape[0] * k
    chunk = _walk_chunk(rows, count, published)
    if chunk >= rows:
        return None
    local = experts - offset
    return -(-jnp.sum((local >= 0) & (local < count)) // chunk)


def _held_walk(xs, weights, experts, w1, w3, w2, grouped, int8, offset,
               chunk, out_dtype):
    """:func:`moe_swiglu_routed` with a held share, the absent experts'
    rows never moved. The S * k keys are sorted once, the held experts' n
    rows in front; their sorted positions are walked in ``chunk``s, as
    many as hold a row below n and no more (a loop whose trip count is
    the router's: nothing is dropped, and a router that sends every row
    here runs every chunk, the one pass's work). Chunk i gathers the
    tokens of positions [i chunk, (i + 1) chunk) straight from xs,
    multiplies them by the held groups clipped to that range, weighs them
    and adds them to their tokens' rows of a float32 result. The backward
    walks the same chunks again, each recomputed from the layer's inputs
    (the only residuals), and adds each chunk's gradients to the sums.
    Every chunk after the first runs under ``dstpu.moe.spill``: device
    time there is held rows past the first chunk."""
    from ..ops.pallas._common import counting_calls, note_call
    S, D = xs.shape
    k = experts.shape[1]
    E, _, F = (w1.scale if hasattr(w1, "scale") else w1).shape
    gp = resolve_grouped_params(grouped, chunk, E, D, F, xs.dtype)
    if int8:
        gp = dict(gp, int8=resolve_moe_int8(int8, chunk, E, D, F, xs.dtype))
    noted = []
    with jax.named_scope("dstpu.moe.route"):
        local = experts.reshape(-1) - offset
        key = jnp.where((local >= 0) & (local < E), local, E)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        # where each held group starts in the sorted order and where the
        # last ends: bounds[-1] is n
        bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(
            jnp.bincount(key, length=E + 1)[:E]).astype(jnp.int32)])
        # positions past S * k in the last chunk lie past n like every
        # absent row: selected away whatever row they name
        order = jnp.pad(order, (0, -order.size % chunk))
        flat_w = weights.reshape(-1).astype(jnp.float32)

    def rows(i, xs, flat_w, order, bounds):
        """Chunk i: its positions, its tokens, which rows are live, the
        groups clipped to it and its operands gathered."""
        with jax.named_scope("dstpu.moe.route"):
            lo = i * chunk
            at = lax.dynamic_slice(order, (lo,), (chunk,))
            token = at // k
            live = lo + jnp.arange(chunk) < bounds[-1]
            sizes = jnp.diff(jnp.clip(bounds, lo, lo + chunk))
            return (at, token, live, sizes, xs[token],
                    jnp.where(live, flat_w[at], 0))

    def weighed(xg, wr, w1, w3, w2, live, sizes):
        """The live rows' weighed outputs (chunk, D) float32. The
        products neither read nor WRITE a row past the groups (on the
        chip ``lax.ragged_dot`` leaves there what the buffer held, in dx
        too): both ends select those rows away, and the selects
        transpose to selects."""
        with jax.named_scope("dstpu.moe.route"):
            xr = jnp.where(live[:, None], xg, 0)
        # this body is traced once a loop and pass and the layer makes one
        # chain: the first trace tells the tally, the others keep quiet
        with jax.named_scope("dstpu.moe.experts"), counting_calls() as said:
            o = _grouped_swiglu_ffn(xr, w1, w3, w2, sizes, gp)
        if not noted:
            noted.append(note_call("expert", any(
                kernel for _, kernel in said.values())))
        with jax.named_scope("dstpu.moe.combine"):
            return jnp.where(live[:, None], o, 0).astype(jnp.float32) \
                * wr[:, None]

    def chunks(bounds, body, carry):
        """``body(i, carry)`` over the chunks that hold a row: the first
        under a ``cond``, the rest a loop under ``dstpu.moe.spill``."""
        taken = -(-bounds[-1] // chunk)
        carry = lax.cond(taken > 0, lambda c: body(0, c), lambda c: c,
                         carry)
        with jax.named_scope("dstpu.moe.spill"):
            return lax.fori_loop(1, taken, body, carry)

    def forward(xs, flat_w, w1, w3, w2, order, bounds):
        def add(i, y):
            _, token, live, sizes, xg, wr = rows(i, xs, flat_w, order,
                                                 bounds)
            o = weighed(xg, wr, w1, w3, w2, live, sizes)
            with jax.named_scope("dstpu.moe.combine"):
                return y.at[token].add(o)
        y = chunks(bounds, add, jnp.zeros((S, D), jnp.float32))
        return y.astype(out_dtype), (xs, flat_w, w1, w3, w2, order, bounds)

    def backward(saved, dy):
        xs, flat_w, w1, w3, w2, order, bounds = saved

        def add(i, sums):
            dxs, dflat_w, dw = sums
            at, token, live, sizes, xg, wr = rows(i, xs, flat_w, order,
                                                  bounds)
            with jax.named_scope("dstpu.moe.combine"):
                do = dy[token].astype(jnp.float32)
            dxg, dwr, *dwi = jax.vjp(
                lambda *a: weighed(*a, live, sizes), xg, wr, w1, w3, w2)[1](
                    do)
            with jax.named_scope("dstpu.moe.experts"):
                dw = jax.tree.map(jnp.add, dw, tuple(dwi))
            with jax.named_scope("dstpu.moe.route"):
                return (dxs.at[token].add(dxg.astype(jnp.float32)),
                        dflat_w.at[at].add(jnp.where(live, dwr, 0)), dw)
        # the held experts' gradients are theirs whether a row came or not
        with jax.named_scope("dstpu.moe.experts"):
            dw = jax.tree.map(jnp.zeros_like, (w1, w3, w2))
        dxs, dflat_w, dw = chunks(bounds, add, (
            jnp.zeros((S, D), jnp.float32), jnp.zeros_like(flat_w), dw))
        return (dxs.astype(xs.dtype), dflat_w, *dw, None, None)

    walk = jax.custom_vjp(lambda *a: forward(*a)[0])
    walk.defvjp(forward, backward)
    return walk(xs, flat_w, w1, w3, w2, order, bounds)


def topk_routing(logits, k=1):
    """Capacity-free top-k routing: (weights (S, k), experts (S, k) int32,
    aux load-balance loss, counts (E,)). The aux term is the GShard/Switch
    loss — E * mean(router_prob_per_expert * first_choice_frac) — while
    ``counts`` reports ALL k dispatches per expert (the dense paths'
    exp_counts semantics)."""
    S, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    if k > 1:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    first = jnp.sum(jax.nn.one_hot(experts[:, 0], E), axis=0)
    l_aux = E * jnp.sum(jnp.mean(probs, axis=0) * first / S)
    counts = jnp.sum(jax.nn.one_hot(experts, E), axis=(0, 1))
    return weights, experts.astype(jnp.int32), l_aux, counts


def moe_layer_ragged(tokens, gate_w, wi, bi, wo, bo, k=1, *,
                     activation=jax.nn.gelu, seq_sharded=False,
                     grouped_kernel="auto"):
    """DROPLESS MoE via grouped GEMM (``lax.ragged_dot``) — the
    megablox pattern and the counterpart of the reference's CUTLASS
    ``moe_gemm`` (inference/v2/kernels/cutlass_ops): tokens sort by
    assigned expert, each expert multiplies exactly its contiguous group
    (no capacity padding, no dropped tokens), results unsort back.

    Single-shard expert compute: use under DP/TP (experts replicated or
    TP-sharded); under expert-parallel meshes the static-capacity dense
    dispatch in ``moe_layer`` is the SPMD-shaped path.
    Returns (y, l_aux, exp_counts) like ``moe_layer``.
    """
    orig_shape = tokens.shape
    M = orig_shape[-1]
    x = tokens.reshape(-1, M)
    S = x.shape[0]
    E = gate_w.shape[-1]

    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    weights, experts, l_aux, _ = topk_routing(logits, k)

    # replicate tokens k times, sort by expert for contiguous groups
    flat_exp = experts.reshape(-1)                      # (S*k,)
    flat_w = weights.reshape(-1).astype(tokens.dtype)
    x_rep = jnp.repeat(x, k, axis=0)                    # (S*k, M)
    order = jnp.argsort(flat_exp)
    xs = x_rep[order]
    exp_sorted = flat_exp[order]
    group_sizes = jnp.bincount(flat_exp, length=E).astype(jnp.int32)

    exp_counts = group_sizes
    gp = resolve_grouped_params(grouped_kernel, S * k, E, M,
                                wi.shape[-1], xs.dtype)
    h = _grouped_dot(xs, wi, group_sizes, gp)           # (S*k, F)
    h = activation(h + bi[exp_sorted])
    out = _grouped_dot(h, wo, group_sizes, gp)          # (S*k, M)
    out = out + bo[exp_sorted]

    # unsort and weighted-combine the k expert outputs per token
    unsorted = jnp.zeros_like(out).at[order].set(out)
    y = jnp.sum((unsorted * flat_w[:, None]).reshape(S, k, M), axis=1)
    y = y.astype(tokens.dtype).reshape(orig_shape)
    y = _constrain(
        y, P(BATCH_AXES, "seq" if seq_sharded else None, None)
        if len(orig_shape) == 3 else P(BATCH_AXES, None))
    return y, l_aux, exp_counts


def moe_layer(tokens, gate_w, wi, bi, wo, bo, gate: TopKGate, *, rng=None,
              train=True, activation=jax.nn.gelu, seq_sharded=False):
    """Full MoE layer over flattened tokens.

    tokens: (..., M) — leading dims flattened to S internally.
    gate_w: (M, E); wi: (E, M, F); bi: (E, F); wo: (E, F, M); bo: (E, M).

    Data flow (reference MOELayer.forward sharded_moe.py:505-520):
    gate -> dispatch einsum [all_to_all in] -> grouped expert FFN
    -> [all_to_all out] -> combine einsum. The all_to_alls materialize from
    the 'expert'-axis sharding constraints under GSPMD.
    """
    orig_shape = tokens.shape
    M = orig_shape[-1]
    x = tokens.reshape(-1, M)
    S = x.shape[0]
    E = gate_w.shape[-1]

    logits = (x.astype(jnp.float32) @ gate_w.astype(jnp.float32))
    l_aux, combine, dispatch, exp_counts = gate(logits, rng=rng, train=train)

    combine = combine.astype(tokens.dtype)
    dispatched = jnp.einsum("sec,sm->ecm", dispatch.astype(tokens.dtype), x,
                            preferred_element_type=tokens.dtype)
    # expert-sharded buffers: the einsum above becomes the first all_to_all
    dispatched = _constrain(dispatched, P("expert", None, None))
    h = activation(jnp.einsum("ecm,emf->ecf", dispatched, wi) + bi[:, None])
    h = _constrain(h, P("expert", None, "tensor"))
    out = jnp.einsum("ecf,efm->ecm", h, wo) + bo[:, None]
    out = _constrain(out, P("expert", None, None))
    # second all_to_all back to token sharding, then weighted combine
    y = jnp.einsum("sec,ecm->sm", combine, out,
                   preferred_element_type=tokens.dtype)
    y = _constrain(
        y.reshape(orig_shape),
        P(BATCH_AXES, "seq" if seq_sharded else None, None)
        if len(orig_shape) == 3 else P(BATCH_AXES, None))
    return y, l_aux, exp_counts


def resolve_hierarchical_a2a(knob, outer_size, E, ep, *, tokens=0,
                             model_dim=0, dtype=None):
    """Whether the EP exchange stages ICI -> DCN: "auto" engages iff the
    mesh has an outer (DCN) axis > 1 and the experts divide the combined
    shard grid — then defers to the 'a2a_staging' collective winner for
    this (device, topology, payload) bucket, whose cold-cache default IS
    that heuristic (a measured winner can only flip an admissible case
    back to flat, never force a non-dividing staging); True additionally
    *requires* divisibility (loud error instead of a silent flat
    fallback); False never stages."""
    if knob is False or knob is None:
        return False
    if outer_size <= 1:
        return False
    if E % (ep * outer_size) != 0:
        if knob is True:
            raise ValueError(
                f"hierarchical EP needs experts ({E}) divisible by "
                f"expert*outer shards ({ep}*{outer_size})")
        return False
    if knob == "auto":
        from ..ops.pallas._common import a2a_bucket, dispatch, dtype_name
        import jax.numpy as jnp
        win = dispatch(
            "a2a_staging", a2a_bucket(tokens, model_dim),
            dtype_name(dtype if dtype is not None else jnp.bfloat16),
            {"staged": int(outer_size > 1)})
        return bool(win["staged"])
    return True


def moe_swiglu_ragged_ep(tokens, gate_w, w1, w3, w2, k=2, *,
                         expert_axis="expert", outer_axis="data_outer",
                         hierarchical="auto", dcn_quantize=False,
                         grouped_kernel="auto", int8_matmul=False,
                         return_counts=False, renormalize=True):
    """EXPERT-PARALLEL dropless SwiGLU MoE for the serving models
    (mixtral, olmoe): the same pack / all_to_all / per-shard grouped-GEMM
    / exchange-back machinery as :func:`moe_layer_ragged_ep`, with the
    SwiGLU expert FFN (w1 gate, w3 up, w2 down, no biases) and
    :func:`route_topk`'s softmax-then-top-k combine weights
    (``renormalize``: mixtral yes, olmoe no). The expert product
    runs the Pallas grouped kernel or ``lax.ragged_dot`` per the
    ``grouped_kernel`` knob ("auto": :func:`resolve_grouped_params`).

    Exists because GSPMD cannot partition ``lax.ragged_dot`` over the
    expert (group) dim of the weights: with moe_w* sharded
    P('expert', ...) under plain jit, rows routed to off-shard experts
    silently come back as garbage (measured: identical shard-0 rows,
    O(1)-wrong rows elsewhere) — the root cause of the EPxTP mixtral
    serving mismatch. The expert axis must be MANUAL (shard_map) with an
    explicit exchange; any 'tensor' sharding of the FFN dim stays
    GSPMD-managed (that partitioning is sound — TP-only serving matched
    exactly).

    The region is FULL-manual (every mesh axis) rather than
    expert-subgroup-manual: jaxlib < 0.6's partitioner check-fails on
    manual subgroups (the SPMD-pipe limitation), and full manual also
    makes the TP composition explicit — the FFN dim stays 'tensor'-
    sharded inside the region and the down projection's partial sums
    psum over 'tensor' (the Megatron row-parallel reduction).

    POD SCALE — hierarchical ICI->DCN exchange: when the mesh carries a
    ``data_outer`` (cross-slice DCN) axis and ``hierarchical`` resolves
    on, experts shard over the combined (outer, expert) grid and the
    flat all_to_all splits into two tiled hops: an ICI-local exchange
    over ``expert_axis`` delivering each token to its target inner rank,
    then one DCN hop over ``outer_axis`` delivering it to its target
    slice — per-slice traffic aggregated per inner rank, the PR-3
    two-stage collective discipline. ``dcn_quantize`` applies the qgZ
    int8 block round trip (``comm.quantized.dcn_precision_clamp``) to
    the token payload of the DCN legs ONLY (both directions; the ICI
    hop and the int32 expert ids stay exact).

    tokens: (..., M); token count needn't divide the shard grid (zero
    rows pad the split, their gate weights are masked to zero and they
    ride with the invalid expert id so they can never skew
    ``group_sizes``, the FFN groups, or the combine). Returns y shaped
    like tokens (plus global per-expert dispatch counts when
    ``return_counts`` — the padding-audit observable).
    """
    mesh = jax.sharding.get_abstract_mesh()
    ep = 1 if mesh.empty else mesh.shape.get(expert_axis, 1)
    wo = 1 if mesh.empty else mesh.shape.get(outer_axis, 1)
    orig_shape = tokens.shape
    M = orig_shape[-1]
    flat = tokens.reshape(-1, M)
    S = flat.shape[0]
    E = gate_w.shape[-1]
    if ep == 1:
        raise ValueError("moe_swiglu_ragged_ep needs an expert mesh axis "
                         "> 1; use the dense ragged_dot path otherwise")
    hier = resolve_hierarchical_a2a(hierarchical, wo, E, ep,
                                    tokens=S, model_dim=M,
                                    dtype=tokens.dtype)
    if dcn_quantize == "auto":
        # qgZ on the DCN token legs: measured per payload bucket, OFF on
        # a cold cache (quantization changes numerics — never on blind)
        from ..ops.pallas._common import (dispatch, dtype_name,
                                          grad_comm_bucket)
        payload_mb = max(1, (S * M * flat.dtype.itemsize) >> 20)
        dcn_quantize = bool(dispatch(
            "dcn_quantize", grad_comm_bucket(payload_mb),
            dtype_name(flat.dtype), {"quantize": 0})["quantize"])
    ep_total = ep * wo if hier else ep
    assert E % ep_total == 0, \
        f"experts {E} not divisible by expert shards {ep_total}"
    E_loc = E // ep_total
    pad = (-S) % ep_total
    if pad:
        # jnp.pad, NOT concatenate-with-zeros: on jaxlib < 0.6 a traced
        # concatenate feeding a manual (shard_map) region gets its layout
        # mis-propagated by the SPMD partitioner and the shards read
        # transposed data (verified with an identity shard_map)
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    tn = "tensor" if "tensor" in mesh.shape else None
    shard_axes = (outer_axis, expert_axis) if hier else (expert_axis,)

    def shard_fn(x, gate_w, w1, w3, w2):
        S_loc = x.shape[0]
        cap = S_loc * k                                  # exact transport
        shard = lax.axis_index(expert_axis)
        if hier:
            shard = lax.axis_index(outer_axis) * ep + shard
        # pad-row audit: rows past the true token count carry zero gate
        # weight and the invalid expert id — they occupy transport slots
        # (static capacity) but never enter group_sizes, the grouped
        # FFN, or the combine
        valid = (shard * S_loc + jnp.arange(S_loc)) < S
        valid_rep = jnp.repeat(valid, k)

        with jax.named_scope("dstpu.moe.route"):
            weights, experts = route_topk(x, gate_w, k, renormalize)

            flat_exp = experts.reshape(-1)
            flat_w = jnp.where(valid_rep, weights.reshape(-1), 0.0) \
                .astype(x.dtype)
            dest = flat_exp // E_loc
            local_e = jnp.where(valid_rep, flat_exp % E_loc, E_loc)
            x_rep = jnp.repeat(x, k, axis=0)

            order = jnp.argsort(dest, stable=True)
            dest_s = dest[order]
            pos_in_bucket = jnp.arange(cap) - jnp.searchsorted(
                dest_s, dest_s, side="left")
            if hier:
                # buckets keyed (inner rank, outer slice): stage 1 exchanges
                # over the ICI expert axis, stage 2 moves each token's
                # aggregated per-slice bucket across DCN once
                i_dest_s = dest_s % ep
                o_dest_s = dest_s // ep
                send_x = jnp.zeros((ep, wo, cap, M), x.dtype)
                send_e = jnp.full((ep, wo, cap), E_loc, jnp.int32)
                send_x = send_x.at[i_dest_s, o_dest_s, pos_in_bucket].set(
                    x_rep[order])
                send_e = send_e.at[i_dest_s, o_dest_s, pos_in_bucket].set(
                    local_e[order])
                recv_x = lax.all_to_all(send_x, expert_axis, 0, 0,
                                        tiled=False)
                recv_e = lax.all_to_all(send_e, expert_axis, 0, 0,
                                        tiled=False)
                if dcn_quantize:
                    from ..comm.quantized import dcn_precision_clamp
                    recv_x = dcn_precision_clamp(recv_x)
                recv_x = lax.all_to_all(recv_x, outer_axis, 1, 1,
                                        tiled=False)
                recv_e = lax.all_to_all(recv_e, outer_axis, 1, 1,
                                        tiled=False)
            else:
                send_x = jnp.zeros((ep, cap, M), x.dtype)
                send_e = jnp.full((ep, cap), E_loc, jnp.int32)
                send_x = send_x.at[dest_s, pos_in_bucket].set(x_rep[order])
                send_e = send_e.at[dest_s, pos_in_bucket].set(local_e[order])
                recv_x = lax.all_to_all(send_x, expert_axis, 0, 0,
                                        tiled=False)
                recv_e = lax.all_to_all(send_e, expert_axis, 0, 0,
                                        tiled=False)
            rx = recv_x.reshape(ep_total * cap, M)
            re = recv_e.reshape(ep_total * cap)

            g_order = jnp.argsort(re, stable=True)
            xs = rx[g_order]
            es = re[g_order]
            group_sizes = jnp.bincount(re, length=E_loc).astype(jnp.int32)
        F_dim = w1.scale.shape[-1] if hasattr(w1, "scale") \
            else w1.shape[-1]
        gp = resolve_grouped_params(grouped_kernel, ep_total * cap,
                                    E_loc, M, F_dim, x.dtype)
        if int8_matmul:
            gp = dict(gp, int8=resolve_moe_int8(
                int8_matmul, ep_total * cap, E_loc, M, F_dim, x.dtype))
        with jax.named_scope("dstpu.moe.experts"):
            out = _grouped_swiglu_ffn(xs, w1, w3, w2, group_sizes, gp)
        with jax.named_scope("dstpu.moe.combine"):
            if tn is not None:
                # row-parallel down projection: F is 'tensor'-sharded, so
                # the local grouped product holds partial sums (no-op tp=1)
                out = lax.psum(out, tn)
            out = jnp.where((es < E_loc)[:, None], out, 0.0)

            back = jnp.zeros_like(out).at[g_order].set(out)
            if hier:
                back = back.reshape(ep, wo, cap, M)
                if dcn_quantize:
                    from ..comm.quantized import dcn_precision_clamp
                    back = dcn_precision_clamp(back)
                ret = lax.all_to_all(back, outer_axis, 1, 1, tiled=False)
                ret = lax.all_to_all(ret, expert_axis, 0, 0, tiled=False)
                ret_flat = ret[i_dest_s, o_dest_s, pos_in_bucket]
            else:
                back = back.reshape(ep, cap, M)
                ret = lax.all_to_all(back, expert_axis, 0, 0, tiled=False)
                ret_flat = ret[dest_s, pos_in_bucket]
            unsorted = jnp.zeros_like(ret_flat).at[order].set(ret_flat)
            y = jnp.sum(
                (unsorted * flat_w[:, None]).reshape(S_loc, k, M), axis=1)
        counts = lax.psum(
            lax.dynamic_update_slice(jnp.zeros((E,), jnp.int32),
                                     group_sizes, (shard * E_loc,)),
            shard_axes)
        return y.astype(tokens.dtype), counts

    y, counts = jax.shard_map(
        shard_fn,
        in_specs=(P(shard_axes), P(), P(shard_axes, None, tn),
                  P(shard_axes, None, tn), P(shard_axes, tn, None)),
        out_specs=(P(shard_axes), P()), check_vma=False,
    )(flat, gate_w, w1, w3, w2)
    if pad:
        y = y[:S]
    y = y.reshape(orig_shape)
    return (y, counts) if return_counts else y


def moe_layer_ragged_ep(tokens, gate_w, wi, bi, wo, bo, k=1, *,
                        activation=jax.nn.gelu, expert_axis="expert",
                        batch_axes=BATCH_AXES, seq_sharded=False,
                        grouped_kernel="auto"):
    """EXPERT-PARALLEL dropless MoE: shard_map over the expert axis with an
    explicit all_to_all exchange and per-shard grouped GEMM
    (``lax.ragged_dot``) — the reference's CUTLASS ``moe_gemm`` composed
    with its ``_AllToAll`` dispatch (sharded_moe.py:95,505), megablox
    style, with NO token dropping and NO capacity padding in the FFN.

    tokens: (..., M) with the leading (token) dim sharded over
    ``batch_axes``; wi/bi/wo/bo carry a leading E dim sharded over
    ``expert_axis`` (E % ep == 0); gate_w (M, E) replicated.

    Mechanics per expert-shard (manual over the batch axes): route the
    S_loc local tokens over all E experts; pack tokens destined for each
    expert shard into a (ep, S_loc*k) transport buffer (worst-case sized:
    transport pays for exactness — the FFN does not: after the
    all_to_all, rows sort by LOCAL expert and ``ragged_dot`` multiplies
    only the valid rows); all_to_all back and weighted-combine. Invalid
    rows ride with expert id E_loc so they sort last, outside every
    ragged group; their (undefined) outputs are masked before combine.

    Returns (y, l_aux, exp_counts(E,)) like ``moe_layer``.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.shape.get(expert_axis, 1) == 1:
        return moe_layer_ragged(tokens, gate_w, wi, bi, wo, bo, k=k,
                                activation=activation,
                                seq_sharded=seq_sharded,
                                grouped_kernel=grouped_kernel)
    ep = mesh.shape[expert_axis]
    E = gate_w.shape[-1]
    assert E % ep == 0, f"experts {E} not divisible by expert axis {ep}"
    E_loc = E // ep
    orig_shape = tokens.shape
    M = orig_shape[-1]
    # the region is FULL-manual (every mesh axis — jaxlib < 0.6's
    # partitioner check-fails on manual subgroups, and an EP x ring /
    # EP x TP composition would otherwise gather the non-manual axes):
    # the flat token dim is sharded over the batch axes plus, when the
    # caller runs sequence-parallel, the 'seq' axis (so EP x ring keeps
    # its sequence shards — the (B, T, M) -> (B*T, M) reshape is
    # batch-major, seq-minor); the FFN dim stays 'tensor'-sharded with
    # the down projection's partial sums psum'd (row-parallel).
    token_axes = tuple(a for a in (batch_axes if isinstance(
        batch_axes, tuple) else (batch_axes,)) if a in mesh.shape)
    if expert_axis not in token_axes:
        token_axes = token_axes + (expert_axis,)
    if seq_sharded and "seq" in mesh.shape:
        token_axes = token_axes + ("seq",)
    tn = "tensor" if "tensor" in mesh.shape else None

    def shard_fn(x, gate_w, wi, bi, wo, bo):
        x = x.reshape(-1, M)
        S_loc = x.shape[0]
        cap = S_loc * k                                  # exact transport
        logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
        weights, experts, _, counts = topk_routing(logits, k)
        counts = lax.psum(counts, token_axes)
        # The GShard aux loss is nonlinear in the per-expert statistics,
        # so psum the raw sums (prob mass + first-choice counts) across
        # shards FIRST and form the loss once from global-batch values —
        # a pmean of per-shard losses biases the balance gradient
        # whenever routing differs across shards.
        probs = jax.nn.softmax(logits, axis=-1)
        probsum = lax.psum(jnp.sum(probs, axis=0), token_axes)
        first = lax.psum(
            jnp.sum(jax.nn.one_hot(experts[:, 0], E), axis=0),
            token_axes)
        n_shards = 1
        for a in token_axes:
            n_shards *= mesh.shape[a]
        S_glob = S_loc * n_shards
        l_aux = E * jnp.sum((probsum / S_glob) * (first / S_glob))

        flat_exp = experts.reshape(-1)                   # (S_loc*k,)
        flat_w = weights.reshape(-1).astype(tokens.dtype)
        dest = flat_exp // E_loc                         # target shard
        local_e = flat_exp % E_loc                       # expert on shard
        x_rep = jnp.repeat(x, k, axis=0)

        # pack per-destination: stable sort by dest, then position within
        # the destination bucket = rank among same-dest rows
        order = jnp.argsort(dest, stable=True)
        dest_s = dest[order]
        pos_in_bucket = jnp.arange(cap) - jnp.searchsorted(
            dest_s, dest_s, side="left")
        send_x = jnp.zeros((ep, cap, M), x.dtype)
        send_e = jnp.full((ep, cap), E_loc, jnp.int32)   # E_loc = invalid
        send_x = send_x.at[dest_s, pos_in_bucket].set(x_rep[order])
        send_e = send_e.at[dest_s, pos_in_bucket].set(local_e[order])

        # exchange: shard g receives every shard's bucket for g
        recv_x = lax.all_to_all(send_x, expert_axis, 0, 0, tiled=False)
        recv_e = lax.all_to_all(send_e, expert_axis, 0, 0, tiled=False)
        rx = recv_x.reshape(ep * cap, M)
        re = recv_e.reshape(ep * cap)

        # group by local expert (invalid rows sort last, outside groups)
        g_order = jnp.argsort(re, stable=True)
        xs = rx[g_order]
        es = re[g_order]
        group_sizes = jnp.bincount(re, length=E_loc).astype(jnp.int32)
        gp = resolve_grouped_params(grouped_kernel, ep * cap, E_loc, M,
                                    wi.shape[-1], xs.dtype)
        h = _grouped_dot(xs, wi, group_sizes, gp)
        safe_e = jnp.minimum(es, E_loc - 1)
        h = activation(h + bi[safe_e])
        out = _grouped_dot(h, wo, group_sizes, gp)
        if tn is not None:
            # row-parallel down projection: F is 'tensor'-sharded, so
            # the local grouped product holds partial sums (no-op tp=1);
            # bo is replicated and must land AFTER the reduction
            out = lax.psum(out, tn)
        out = out + bo[safe_e]
        out = jnp.where((es < E_loc)[:, None], out, 0.0)

        # unsort, exchange back, unpack to original (S_loc*k) order
        back = jnp.zeros_like(out).at[g_order].set(out)
        back = back.reshape(ep, cap, M)
        ret = lax.all_to_all(back, expert_axis, 0, 0, tiled=False)
        ret_flat = ret[dest_s, pos_in_bucket]            # sorted order
        unsorted = jnp.zeros_like(ret_flat).at[order].set(ret_flat)
        y = jnp.sum(
            (unsorted * flat_w[:, None]).reshape(S_loc, k, M), axis=1)
        return y.astype(tokens.dtype), l_aux, counts

    flat = tokens.reshape(-1, M)
    token_spec = P(tuple(token_axes))
    y, l_aux, counts = jax.shard_map(
        shard_fn,
        in_specs=(token_spec, P(), P(expert_axis, None, tn),
                  P(expert_axis, tn), P(expert_axis, tn, None),
                  P(expert_axis, None)),
        out_specs=(token_spec, P(), P()), check_vma=False,
    )(flat, gate_w, wi, bi, wo, bo)
    y = y.reshape(orig_shape)
    y = _constrain(
        y, P(BATCH_AXES, "seq" if seq_sharded else None, None)
        if len(orig_shape) == 3 else P(BATCH_AXES, None))
    return y, l_aux, counts
