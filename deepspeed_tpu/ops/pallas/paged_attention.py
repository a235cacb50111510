"""Paged (blocked-KV) decode attention as a Pallas TPU kernel.

Counterpart of the reference's FastGen ragged kernels
(``deepspeed/inference/v2/kernels/ragged_ops/`` — blocked flash over a
paged KV cache behind ``RaggedBatchWrapper``): one new token per sequence
slot attends over that sequence's KV blocks, located through a per-slot
block table.

The jnp fallback path gathers every slot's blocks into a dense
(B, S, H, d) copy and runs masked-dense attention — O(B * MB * BS) HBM
traffic in COPIES per layer, then attention over the fully padded length.
This kernel instead streams each KV block through VMEM exactly once,
located through the block table in SMEM, with online softmax across
blocks. Its grid is not the table but the list of runs of consecutive
table entries of one slot that hold something to attend
(:func:`decode_work_list`, made on the device from ``lengths`` once a
decode step, its length a device scalar): a grid step costs its ~0.3 us
whether or not its block is live, and a table is mostly tail — entries
past a sequence's length, and whole rows of inactive slots — so a step
for every entry cost several times the live blocks' DMA (PERF.md, PR 27);
and a live step costs that 0.3 us beside its DMA however little it moves,
so a step takes as many entries as make its DMA worth the step
(:func:`decode_entries_per_step`, from the block's bytes; PERF.md, PR 39).

GQA is native: q heads fold to (KVH, G, d) and both dots batch over KVH —
no repeat_kv materialization.

Layout: q (B, H, d); cache (NB, KVH, BS, d) — heads-major so the kernel's
(KVH, BS, d) block needs no in-VMEM transpose; block_tables (B, MB) int32
(inactive/overflow entries point at scratch block 0); lengths (B,) int32 =
the new token's position (the kernel attends cache slots 0..lengths
inclusive, matching the dense path's semantics).

The pools stay in that layout from allocation to the last dispatch: new
rows go in through :func:`paged_kv_write` (an aliased Pallas write, not an
XLA scatter with a layout of its own; its grid is the rows that go
somewhere, :func:`kv_write_row_list`, for the decode grid's reason: PERF.md,
PR 29), and at program boundaries the block axis takes the shape
:func:`pool_block_dims` gives it.
"""

import functools
import math
import operator
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_default as _interpret_default
from .flash_attention import _lanes, _vmem_params

NEG_INF = -1e30

# ---------------------------------------------------------------- tunables
# Cold-cache (r05-style proven) parameters for the two serving autotune
# ops (autotuning/kernel_registry.py registers the search spaces):
#   paged_decode  mode: 'kernel' everywhere — the decode kernel has been
#                 the shipped path since it landed (interpret mode off-TPU)
#   paged_chunk   mode: 'kernel' on TPU (the blocked-flash chunk program),
#                 'dense' elsewhere — emulating the blocked stream in the
#                 Pallas interpreter is slower than one dense gather on
#                 CPU, and the dense path is the proven parity fallback
#                 block_c: 0 = the query tile is sized from the call's
#                 shapes (chunk_tile); a measured winner names its tokens
PAGED_DECODE_DEFAULTS = {"mode": "kernel"}


def paged_chunk_tune_defaults():
    """Cold-cache defaults for the 'paged_chunk' autotune op (the mode
    is backend-dependent; the winner cache is keyed by device_kind, so
    the split can never leak across chips)."""
    on_tpu = jax.default_backend() == "tpu"
    return {"mode": "kernel" if on_tpu else "dense", "block_c": 0}


def resolve_paged_decode(setting, B, MB, BS, KVH, G, d, dtype):
    """Resolve an engine/model ``paged_kernel`` setting for the decode
    step: "auto" consults the autotune winner cache for this
    decode-shape bucket (batch slots, blocks-per-seq, block size,
    kv-heads, GQA group, head dim); True/False force. Returns whether
    the Pallas kernel path is used."""
    if setting == "auto":
        from ._common import dispatch, dtype_name, paged_decode_bucket
        win = dispatch("paged_decode",
                       paged_decode_bucket(B, MB, BS, KVH, G, d),
                       dtype_name(dtype), dict(PAGED_DECODE_DEFAULTS))
        return win["mode"] == "kernel"
    return bool(setting)


def resolve_paged_chunk(setting, block_c, C, MB, BS, KVH, G, d, dtype):
    """Resolve the chunk-program kernel choice + its q-tile size.

    ``setting``: "auto" | True | False (engine ``paged_kernel``;
    callers pass False when the kernel path is statically impossible,
    e.g. ALiBi models); ``block_c``: "auto" | int (engine
    ``paged_block_c``). "auto" fields resolve against the winner cache
    for this chunk-shape bucket; cold-cache defaults come from
    :func:`paged_chunk_tune_defaults`. Returns (use_kernel, block_c),
    ``block_c`` 0 where nothing names a tile: :func:`chunk_tile` then
    sizes it from the call's shapes.

    The dispatch (which may run a measured search under
    on_first_use/search) is only consulted when its answer can matter
    — a forced-off kernel never pays a search for a tile it will
    discard."""
    use = None if setting == "auto" else bool(setting)
    if use is False:
        return False, (0 if block_c == "auto" else int(block_c))
    win = None
    if use is None or block_c == "auto":
        from ._common import dispatch, dtype_name, paged_chunk_bucket
        win = dispatch("paged_chunk",
                       paged_chunk_bucket(C, MB, BS, KVH, G, d),
                       dtype_name(dtype), paged_chunk_tune_defaults())
    if use is None:
        use = win["mode"] == "kernel"
    bc = int(win["block_c"]) if block_c == "auto" else int(block_c)
    return use, bc


def alibi_slopes(n_head):
    """Per-head ALiBi slopes (the bloom formula): for the leading
    power-of-two count cp, slope_h = 2^(-8(h+1)/cp); extra heads
    interleave the 2cp sequence: 2^(-4(2(h-cp)+1)/cp)."""
    cp = 2 ** math.floor(math.log2(n_head))
    return [2.0 ** (-8.0 * (h + 1) / cp) if h < cp
            else 2.0 ** (-4.0 * (2 * (h - cp) + 1) / cp)
            for h in range(n_head)]


alibi_slopes_formula = alibi_slopes


def _attended_entries(xp, lengths, MB, BS, window):
    """(first, last) table entry each slot's new token attends, in
    ``xp`` (jax.numpy on the device, numpy on the host)."""
    last = xp.clip(lengths // BS, 0, MB - 1)
    if not window:
        return xp.zeros_like(last), last
    return xp.minimum(xp.maximum(lengths - window + 1, 0) // BS, last), last


# A grid step of the decode kernel costs ~0.3 us beside its DMA whatever
# it moves, so a step takes table entries until its K + V bytes reach
# _STEP_BYTES (at 1.0-1.3 MB a step a block visit reads 87-89 % of the HBM
# peak, at 0.33-0.52 MB 55-70 %), and no more than leave its buffers
# inside _STEP_VMEM. _AHEAD items' blocks are on their way while one is
# computed on, each in a buffer of its own: with one, a slot's short last
# run (a window's ninth ring entry) drained the queue, -16 % a block visit
# at nine entries a slot; a third wins nothing (the kernel alone on a
# v5e, benchmarks/paged_decode_sweep.py: PERF.md, PR 39).
_STEP_BYTES = 1 << 20
_STEP_VMEM = 8 << 20
_AHEAD = 2


def decode_entries_per_step(KVH, BS, d, dtype, MB):
    """N, the table entries of one slot a grid step of
    :func:`paged_decode_attention` takes: from the bytes a block's K and V
    hold in the pools and nothing else. A pool whose rows are narrower
    than the 128 lanes gives 1: several entries a step are fetched by
    copies the kernel issues itself, and Mosaic cannot cut a block out
    of an array in HBM whose minor dimension is padded ("Slice shape
    along dimension 3 must be aligned to tiling (128), but is 64": jax
    0.9.0; PERF.md, PR 39)."""
    if d % 128:
        return 1
    block = 2 * KVH * BS * d * jnp.dtype(dtype).itemsize
    n = min(-(-_STEP_BYTES // block), _STEP_VMEM // ((_AHEAD + 1) * block))
    return max(1, min(n, MB))


def decode_grid_steps(lengths, active, MB, BS, window=0, steps=1,
                      per_step=1):
    """The count ``n`` of :func:`decode_work_list` at ``per_step`` entries
    an item, on the host in numpy and summed over ``steps`` consecutive
    decode steps (a dispatch's: every slot's length grows by one a step).
    At ``per_step`` 1 it is the entries the kernel visits, which the
    engine's telemetry sets against the ``steps * B * MB`` table entries;
    at the kernel's own N, its grid steps."""
    lengths, active = np.asarray(lengths), np.asarray(active, bool)
    first, last = _attended_entries(
        np, lengths[active][:, None] + np.arange(steps), MB, BS, window)
    return int(np.sum(-(-(last - first + 1) // per_step)))


class DecodeWork(NamedTuple):
    """:func:`decode_work_list`'s items: ``int32[items + _AHEAD]`` arrays,
    the items' count ``n`` on the device, and the static ``per_step``
    they were cut by."""
    slot_of: jax.Array
    entry_of: jax.Array
    count_of: jax.Array
    n: jax.Array
    per_step: int


def decode_work_list(lengths, MB, BS, window=0, active=None, per_step=1):
    """The decode kernel's grid, as data: the runs of up to ``per_step``
    consecutive table entries of one slot that hold something the new
    token attends.

    lengths: (B,) int32, the new token's position per slot; MB: table
    entries a slot; ``window`` as :func:`paged_decode_attention` takes
    it; ``active``: (B,) bool, the slots that hold a sequence (all, when
    not given). Active slot b attends entries ``first[b] .. lengths[b]
    // BS`` (``first`` is 0 without a window, else the entry holding
    position ``lengths[b] - window + 1``), ``e`` of them, and
    contributes ``ceil(e / per_step)`` items, each full but the last; an
    inactive slot contributes nothing. Returns a :class:`DecodeWork`,
    slot-major: item i is entries ``entry_of[i] .. entry_of[i] +
    count_of[i] - 1`` of slot ``slot_of[i]``. From item ``n`` on
    ``slot_of`` reads B, so a slot's last item is the one whose
    successor names another slot, and ``count_of`` reads 0 (for
    ``_AHEAD`` items), so the kernel fetches nothing past the last item.

    A handful of small integer operations: compute it once a decode step
    and hand it to every layer's call (layers with another ``window``
    take a list of their own)."""
    B, N = lengths.shape[0], int(per_step)
    first, last = _attended_entries(jnp, lengths, MB, BS, window)
    n_items = (last - first + N) // N
    if active is not None:
        n_items = jnp.where(active, n_items, 0)
    ends = jnp.cumsum(n_items)                           # (B,) running sum
    item = jnp.arange(B * -(-MB // N) + _AHEAD, dtype=jnp.int32)
    # item i belongs to the first slot whose running sum passes i. As
    # compares and sums over (items, B): a searchsorted or a gather here
    # is a loop on the TPU, ~140 us a decode step (PERF.md, PR 27)
    slot_of = jnp.sum(ends[None, :] <= item[:, None], axis=1,
                      dtype=jnp.int32)
    mine = slot_of[:, None] == jnp.arange(B, dtype=jnp.int32)[None, :]

    def of(x):
        return jnp.sum(jnp.where(mine, x[None, :], 0), axis=1,
                       dtype=jnp.int32)

    # a slot's k-th item starts at entry first + k * N
    entry_of = N * item + of(first - N * (ends - n_items))
    count_of = jnp.clip(of(last) - entry_of + 1, 0, N)
    count_of = jnp.where(item < ends[-1], count_of, 0)
    return DecodeWork(slot_of, jnp.clip(entry_of, 0, MB - 1),
                      count_of.astype(jnp.int32), ends[-1].astype(jnp.int32),
                      N)


def _decode_kernel(tbl_ref, len_ref, slot_ref, entry_ref, count_ref, q_ref,
                   k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *bufs, N, BS,
                   KVH, G, scale, window, alibi, alibi_scale=1.0,
                   alibi_bf16=False):
    """Grid step i is item i of the work list: slot ``slot_ref[i]``
    against the ``count_ref[i]`` KV blocks its table names from entry
    ``entry_ref[i]`` on, as one run of ``count * BS`` keys. A slot's
    items are consecutive, so its q and o tiles stay resident from its
    first item to its last.

    At N = 1 ``k_ref`` / ``v_ref`` are the item's block, brought by the
    pipeline. At N > 1 they are the pools, in HBM, and ``bufs`` are
    ``_AHEAD + 1`` buffers of N blocks each with their DMA semaphores: a
    step waits for its own blocks, asked for ``_AHEAD`` steps before (the
    first step asks for the first items'), and asks for those of the
    item ``_AHEAD`` on before it computes. Only live entries are ever
    moved."""
    i = pl.program_id(0)
    b = slot_ref[i]
    j = entry_ref[i]
    B, MB = tbl_ref.shape
    H = KVH * G
    L = len_ref[b]

    if N > 1:
        k_buf, v_buf, sem = bufs
        D = _AHEAD + 1

        def each_block(item, do):
            """``do`` (start or wait) the copies of ``item``'s run: K
            and V of each entry it has, into buffer ``item % D``."""
            for t in range(N):
                @pl.when(t < count_ref[item])
                def _(t=t):
                    blk = tbl_ref[jnp.minimum(slot_ref[item], B - 1),
                                  jnp.minimum(entry_ref[item] + t, MB - 1)]
                    for p, (pool, buf) in enumerate(((k_ref, k_buf),
                                                     (v_ref, v_buf))):
                        do(pltpu.make_async_copy(
                            pool.at[blk],
                            buf.at[item % D, :, pl.ds(t * BS, BS), :],
                            sem.at[p, item % D]))

        start, wait = (operator.methodcaller(m) for m in ("start", "wait"))

        @pl.when(i == 0)
        def _first():
            for item in range(_AHEAD):
                each_block(item, start)

        each_block(i + _AHEAD, start)
        each_block(i, wait)

    @pl.when((i == 0) | (slot_ref[jnp.maximum(i - 1, 0)] != b))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(c):
        """The online-softmax update over the run's c blocks."""
        S = c * BS
        if N > 1:
            kb = k_buf[i % D, :, :S, :]               # (KVH, S, d)
            vb = v_buf[i % D, :, :S, :]
        else:
            kb, vb = k_ref[0], v_ref[0]
        # q arrives (1, KVH, G, d) — the caller reshaped (B, H, d) to
        # (B, KVH, G, d) OUTSIDE the kernel (in-kernel singleton reshapes
        # are unsupported shape casts in Mosaic, and a dot needs a
        # non-contracting lhs dim, which G provides even when == 1)
        q = q_ref[0]
        s = jax.lax.dot_general(
            q, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # (KVH, G, S)
        pos = j * BS + jax.lax.broadcasted_iota(jnp.int32, (KVH, G, S), 2)
        if alibi:
            # ALiBi: slope_h * k_pos (softmax-shift equivalent to
            # slope_h * (k_pos - q_pos); matches the dense paths).
            # Slopes are computed IN-KERNEL from the head index (a
            # captured constant array is rejected by pallas_call): the
            # bloom formula splits at the leading power of two cp.
            h = (jax.lax.broadcasted_iota(jnp.int32, (KVH, G, S), 0) * G
                 + jax.lax.broadcasted_iota(jnp.int32, (KVH, G, S), 1)
                 ).astype(jnp.float32)
            cp = float(2 ** math.floor(math.log2(H)))
            expo = jnp.where(h < cp, -(h + 1.0) * (8.0 / cp),
                             -(2.0 * (h - cp) + 1.0) * (4.0 / cp))
            ab = jnp.exp2(expo) * pos.astype(jnp.float32)
            if alibi_bf16:
                # HF falcon quantizes the alibi tensor through bf16 and
                # adds it pre-scaling (models/llama.py _alibi_bias)
                ab = ab.astype(jnp.bfloat16).astype(jnp.float32)
            if alibi_scale != 1.0:
                ab = ab * alibi_scale
            s = s + ab
        ok = pos <= L
        if window:
            # sliding window: the query (at position L) only attends
            # positions > L - window
            ok = ok & (pos > L - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[..., 0]                            # (KVH, G)
        l_prev = l_ref[..., 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])                 # (KVH, G, S) f32
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # (KVH, G, d)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
        m_ref[...] = jnp.broadcast_to(m_new[..., None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[..., None], l_ref.shape)

    # one copy of the update a run length: a short run (a slot's last
    # item, a sequence of one block) computes over its own keys alone
    if N == 1:
        attend(1)
    else:
        for c in range(1, N + 1):
            pl.when(count_ref[i] == c)(functools.partial(attend, c))

    @pl.when(slot_ref[i + 1] != b)
    def _store():
        l = jnp.maximum(l_ref[..., 0], 1e-30)             # (KVH, G)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "N", "scale", "window", "alibi", "alibi_scale", "alibi_bf16",
    "interpret"))
def _decode_call(tables, lengths, slot_of, entry_of, count_of, n, q, kp, vp,
                 *, N, scale, window, alibi, alibi_scale, alibi_bf16,
                 interpret):
    """The decode kernel as one jitted callable, for
    :func:`_kv_write_call`'s reason: a decode program holds layers x
    steps of it."""
    B, KVH, G, d = q.shape
    BS = kp.shape[2]

    # the pipeline may look one item ahead of the last, where ``slot_of``
    # reads B: keep every index it can form inside its array
    def qo_index(i, tbl, lens, slot, entry, count):
        return (jnp.minimum(slot[i], B - 1), 0, 0, 0)

    state = [pltpu.VMEM((KVH, G, 128), jnp.float32),     # running max
             pltpu.VMEM((KVH, G, 128), jnp.float32),     # running denom
             pltpu.VMEM((KVH, G, d), jnp.float32)]       # output accumulator
    if N > 1:
        kv_spec = pl.BlockSpec(memory_space=pl.ANY)
        state += [pltpu.VMEM((_AHEAD + 1, KVH, N * BS, d), kp.dtype),
                  pltpu.VMEM((_AHEAD + 1, KVH, N * BS, d), vp.dtype),
                  pltpu.SemaphoreType.DMA((2, _AHEAD + 1))]  # (K | V, buffer)
    else:
        kv_spec = pl.BlockSpec(
            (1, KVH, BS, d), lambda i, tbl, lens, slot, entry, count:
            (tbl[jnp.minimum(slot[i], B - 1), entry[i]], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, KVH, G, d), qo_index), kv_spec, kv_spec],
        out_specs=pl.BlockSpec((1, KVH, G, d), qo_index),
        scratch_shapes=state,
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, N=N, BS=BS, KVH=KVH, G=G,
                          scale=scale, window=window, alibi=alibi,
                          alibi_scale=alibi_scale, alibi_bf16=alibi_bf16),
        name="dstpu.kernel.paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, d), q.dtype),
        # operands 0-4 are the scalar-prefetched integers; q is 5
        input_output_aliases={5: 0},
        interpret=interpret,
    )(tables, lengths, slot_of, entry_of, count_of, q, kp, vp)


def paged_decode_attention(q, k_cache, v_cache, block_tables, lengths, *,
                           work=None, scale=None, interpret=None,
                           window=0, alibi_slopes=None, alibi_scale=1.0,
                           alibi_bf16=False):
    """One decode step of attention over a paged KV cache.

    q: (B, H, d); k_cache/v_cache: (NB, KVH, BS, d) with H % KVH == 0;
    block_tables: (B, MB) int32; lengths: (B,) int32 = the new token's
    position. Returns (B, H, d) in q's dtype. The new token's K/V must
    already be written to the cache (the callers do the dynamic-slot
    write first). ``window`` > 0 restricts attention to the trailing
    ``window`` positions (mistral); ``alibi_slopes`` (len H floats) adds
    the bloom per-head linear position bias. ``work``: the step's
    :func:`decode_work_list` for this ``window`` (made here when not
    given, with every slot active; a model makes it once for all its
    layers). The grid is that list, so its length is a device scalar and
    the call cannot be ``vmap``ped; a slot the list leaves out takes no
    grid step, and its output row is its q row (the output aliases q):
    finite, and read by nobody.

    Multi-layer pools: view (L, NB, ...) as (L*NB, ...) (a free reshape)
    and offset the tables by ``layer * NB`` — a lax.scan over layers then
    never slices the pool per layer, which would copy ~the whole cache
    every layer (scan xs/ys cannot alias).
    """
    B, H, d = q.shape
    NB, KVH, BS, _ = k_cache.shape
    MB = block_tables.shape[1]
    G = H // KVH
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _interpret_default()

    if alibi_slopes is not None:
        # the kernel recomputes slopes IN-KERNEL from the head count
        # (pallas rejects captured constant arrays); reject custom
        # slopes rather than silently ignoring them
        expect = alibi_slopes_formula(H)
        if len(alibi_slopes) != H or any(
                abs(a - b) > 1e-6 * max(abs(b), 1e-9)
                for a, b in zip(alibi_slopes, expect)):
            raise NotImplementedError(
                "paged_decode_attention computes bloom-formula ALiBi "
                "slopes in-kernel; custom per-head slopes are not "
                "supported")
    if work is None:
        work = decode_work_list(
            lengths, MB, BS, window,
            per_step=decode_entries_per_step(KVH, BS, d, k_cache.dtype, MB))
    out = _decode_call(
        block_tables, lengths, work.slot_of, work.entry_of, work.count_of,
        work.n, q.reshape(B, KVH, G, d), k_cache, v_cache, N=work.per_step,
        scale=float(scale), window=int(window),
        alibi=alibi_slopes is not None, alibi_scale=float(alibi_scale),
        alibi_bf16=bool(alibi_bf16), interpret=bool(interpret))
    return out.reshape(B, H, d)


def paged_decode_attention_reference(q, k_cache, v_cache, block_tables,
                                     lengths, *, scale=None, window=0,
                                     alibi_slopes=None):
    """Dense gather fallback (the pre-kernel path), for parity tests."""
    B, H, d = q.shape
    NB, KVH, BS, _ = k_cache.shape
    MB = block_tables.shape[1]
    S = MB * BS
    G = H // KVH
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    kc = k_cache.transpose(0, 2, 1, 3)                 # (NB, BS, KVH, d)
    vc = v_cache.transpose(0, 2, 1, 3)
    gk = kc[block_tables].reshape(B, S, KVH, d)
    gv = vc[block_tables].reshape(B, S, KVH, d)
    gk = jnp.repeat(gk, G, axis=2)
    gv = jnp.repeat(gv, G, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q, gk,
                   preferred_element_type=jnp.float32) * scale
    if alibi_slopes is not None:
        sl = jnp.asarray(alibi_slopes, jnp.float32)
        s = s + sl[None, :, None] * jnp.arange(S, dtype=jnp.float32)[
            None, None, :]
    mask = jnp.arange(S)[None, :] <= lengths[:, None]
    if window:
        mask = mask & (jnp.arange(S)[None, :]
                       > lengths[:, None] - window)
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bshd->bhd", p, gv)


# ------------------------------------------------------- new-token write


def kv_write_row_list(blocks, offsets):
    """The write kernel's grid, as data: the rows of the new K/V that go
    somewhere, and where.

    blocks / offsets: (N,) int32 destinations. A row aimed at scratch
    block 0 is dead (a pad of a prefill or chunk, an inactive decode
    slot, an inactive slot's verify span): nothing attends block 0, so
    the row needs no write. Returns ``(row_of, block_of, offset_of,
    n_live)``: three ``int32[N + 1]`` arrays, the live rows' indices and
    destinations in their order, all 0 from item ``n_live`` on (the
    pipeline may look one item ahead), and the live rows' count, on the
    device.

    Compares and sums over (items, rows), as :func:`decode_work_list`
    and for its reason; the destinations are compacted here, once, so a
    grid step reads its block id with one scalar load (looked up through
    ``row_of`` in every index map a step cost 26 ns more, 9 % of a full
    chunk's write: PERF.md, PR 29). Compute it once a program step and
    hand it to every layer's :func:`paged_kv_write`."""
    N = blocks.shape[0]
    live = blocks != 0
    ends = jnp.cumsum(live, dtype=jnp.int32)             # (N,) running count
    item = jnp.arange(N + 1, dtype=jnp.int32)
    # live item i is the live row whose running count is i + 1: at most
    # one row an item, none from item n_live on
    mine = live[None, :] & (ends[None, :] == item[:, None] + 1)

    def of(x):
        return jnp.sum(jnp.where(mine, x.astype(jnp.int32)[None, :], 0),
                       axis=1, dtype=jnp.int32)

    return of(item[:N]), of(blocks), of(offsets), ends[-1]


def kv_write_live_rows(lengths, block_tables, BS, steps=1):
    """The ``n_live`` of :func:`kv_write_row_list` for a decode batch, on
    the host in numpy and summed over ``steps`` consecutive decode steps
    (every slot's length grows by one a step; a verify span is ``steps``
    positions of one step): the rows whose table entry names a block
    other than scratch, as ``models/paged.py`` ``batch_step`` aims them.
    What the engine's telemetry sets against ``steps * B`` rows."""
    tables = np.asarray(block_tables)
    pos = np.asarray(lengths)[:, None] + np.arange(steps)
    return int(np.count_nonzero(np.take_along_axis(
        tables, np.minimum(pos // BS, tables.shape[1] - 1), axis=1)))


def _kv_write_kernel(row_ref, blk_ref, off_ref, kn_ref, vn_ref, kp_ref,
                     vp_ref, ko_ref, vo_ref, *, R):
    """Grid step i puts the i-th live row of the new K/V (row
    ``row_ref[i]``) into the R-row tile of the pools that holds
    (blk[i], off[i]); a dead row (one aimed at scratch block 0) is in no
    step, and block 0 is not written. Consecutive live rows of one tile
    keep the output block resident (Pallas fetches and writes back a
    block only when its index changes), so the tile is loaded from the
    pool on its first row only and the rows accumulate in VMEM."""
    i = pl.program_id(0)
    blk, off = blk_ref[i], off_ref[i]
    prev = jnp.maximum(i - 1, 0)
    fresh = (i == 0) | (blk != blk_ref[prev]) \
        | (off // R != off_ref[prev] // R)

    @pl.when(fresh)
    def _load():
        ko_ref[...] = kp_ref[...]
        vo_ref[...] = vp_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape, 2) == off % R
    ko_ref[...] = jnp.where(row, kn_ref[...], ko_ref[...])
    vo_ref[...] = jnp.where(row, vn_ref[...], vo_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_write_call(row_of, block_of, offset_of, n_live, kn, vn, kp, vp, *,
                   interpret):
    """The aliased write as one jitted callable: the serving programs
    unroll layers x steps in Python, and a jitted callee is traced and
    lowered once per program, not once per call site."""
    NB, KVH, BS, d = kp.shape
    N = kn.shape[0]
    # rows of one packed sublane tile (16 for bf16); a block size the
    # tile does not divide is rewritten whole
    R = 32 // kp.dtype.itemsize
    if BS % R:
        R = BS

    def tile(i, row, blk, off):
        return (blk[i], 0, off[i] // R, 0)

    def new_row(i, row, blk, off):
        return (row[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_live,),
        in_specs=[pl.BlockSpec((1, KVH, 1, d), new_row),
                  pl.BlockSpec((1, KVH, 1, d), new_row),
                  pl.BlockSpec((1, KVH, R, d), tile),
                  pl.BlockSpec((1, KVH, R, d), tile)],
        out_specs=[pl.BlockSpec((1, KVH, R, d), tile),
                   pl.BlockSpec((1, KVH, R, d), tile)],
    )
    return tuple(pl.pallas_call(
        functools.partial(_kv_write_kernel, R=R),
        name="dstpu.kernel.kv_write",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                   jax.ShapeDtypeStruct(vp.shape, vp.dtype)],
        # operands 0-2 are the scalar-prefetched integers; the pools
        # are 5/6
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(row_of, block_of, offset_of, kn.reshape(N, KVH, 1, d),
      vn.reshape(N, KVH, 1, d), kp, vp))


def paged_kv_write(pools, new, blocks, offsets, *, rows=None, kernel=True,
                   interpret=None):
    """Write the new tokens' K and V rows into the paged pools:
    ``pool[blocks[n], :, offsets[n]] = new[n]`` for both pools at once.

    pools: (k_pool, v_pool), each (NB, KVH, BS, hd); new: (k, v), each
    (N, KVH, hd) — one row per decode slot, or the C rows of a prefill /
    chunk / verify span; blocks/offsets: (N,) int32 destination block
    and in-block slot. A row aimed at scratch block 0 is **dead**: a pad,
    an inactive slot, an inactive slot's verify span; block 0's contents
    are never attended. ``rows``: the step's
    :func:`kv_write_row_list` of these destinations (made here when not
    given; a model makes it once for all its layers). Returns the
    updated pools.

    Where the paged attention runs as a kernel (``kernel``, as the
    caller resolved it, on a TPU) this is one ``pallas_call`` that
    aliases both pools and rewrites only the sublane tile holding each
    live destination row, found through the scalar-prefetched block
    ids: the pools stay in the row-major layout the paged kernels read,
    where the XLA scatter asks for a layout of its own and costs two
    whole-pool copies a call. Its grid is the live rows (its length a
    device scalar, as the decode kernel's): a dead row takes no grid
    step, and **the kernel no longer writes block 0** — with no live
    row both pools come back untouched. Elsewhere it is that scatter
    (which does write block 0): the same values wherever anything
    reads. Live rows aimed at one tile must be consecutive among the
    live rows (they are: a sequence's positions are, and live sequences
    never share a destination block)."""
    (kp, vp), (kn, vn) = pools, new
    kn, vn = kn.astype(kp.dtype), vn.astype(vp.dtype)
    if interpret is None:
        interpret = _interpret_default()
        kernel = kernel and not interpret
    if not kernel:
        return (kp.at[blocks, :, offsets].set(kn),
                vp.at[blocks, :, offsets].set(vn))
    if rows is None:
        rows = kv_write_row_list(blocks, offsets)
    return _kv_write_call(*rows, kn, vn, kp, vp, interpret=bool(interpret))


# ------------------------------------------------ the pools' layout


# The largest size a non-minor dimension may have before the TPU compiler
# moves it into the 128 lanes of an array whose minor dimension is
# narrower than they are (found by compiling for a described v5e: 64 stays,
# 127 moves; PERF.md, PR 25).
_LANE_SAFE = 64


def pool_block_dims(num_blocks, head_dim, kernel_layout):
    """The shape the block axis of a KV pool takes at program boundaries.

    The paged kernels read a pool (NB, KVH, BS, hd) row-major. Left to
    itself the TPU compiler gives a program argument of that shape
    another layout when hd is under the 128 lanes — the block axis
    becomes the minor one — and copies every pool whole into and out of
    row-major around the kernels, every dispatch. An argument none of
    whose leading dimensions passes ``_LANE_SAFE`` keeps row-major, and
    merging leading dimensions of a row-major array is a bitcast. So
    where the kernels run as kernels (``kernel_layout``) the block axis
    is split into such factors, padded up by the fewest blocks that
    allow it; the programs merge it again on the way in
    (:func:`as_pools`) and split it on the way out
    (:func:`like_boundary`). (A pinned ``jax.experimental.layout.Format``
    says the same thing directly, but an executable that comes back from
    the persistent compilation cache has forgotten it: jax 0.9.0,
    libtpu 0.0.34; PERF.md, PR 25.) Returns a tuple whose product is
    >= num_blocks; ``(num_blocks,)`` where nothing is to be done."""
    n = int(num_blocks)
    if not kernel_layout or head_dim >= 128 or n <= _LANE_SAFE:
        return (n,)
    if n > _LANE_SAFE ** 2:
        return pool_block_dims(-(-n // _LANE_SAFE), head_dim, True) \
            + (_LANE_SAFE,)
    inner = min((a for a in range(1, _LANE_SAFE + 1)
                 if -(-n // a) <= _LANE_SAFE), key=lambda a: -(-n // a) * a)
    return (-(-n // inner), inner)


def as_pools(cache):
    """Inside a program: the cache's leaves as the (NB, KVH, BS, hd)
    pools the model and the kernels take (a bitcast, or nothing). A leaf
    whose block axis was not split (a pool as it is, a slot's state of
    fewer dimensions) passes as it came."""
    return jax.tree.map(
        lambda p: p.reshape((-1,) + p.shape[-3:]) if p.ndim > 4 else p,
        cache)


def like_boundary(pools, cache):
    """Inside a program, on the way out: ``pools`` in the shape the
    cache came in with."""
    return jax.tree.map(lambda p, c: p.reshape(c.shape), pools, cache)


# ------------------------------------------------- chunked-prefill kernel

# A grid step of the chunk kernel is one query tile of a block of KV heads
# against a run of consecutive table entries, each sized from what it moves
# (the kernel alone on a v5e at the served shapes,
# benchmarks/paged_chunk_sweep.py: PERF.md, PR 58). A head's score tile is
# rows (tokens x the G query heads a KV head folds) x keys in float32:
# _CHUNK_ROWS x _CHUNK_KEYS of it is the vector registers' 256 KB, and a
# tile that stays in them beat every larger one under MHA (30 heads of 128
# at 8 k: 2.2 ms at 128 rows, 2.7 at 512, 3.6 at 1,024). The K/V of a run is
# read once a query tile whatever the tile holds, so a tile is never under
# _CHUNK_TOKENS tokens (G = 8: 512 rows a head). Heads then fill the step
# up to _CHUNK_TILE_BYTES of q: their products are independent, which lets
# one head's MXU work run beside another's exponentials (2 x 512 rows beat
# 1 x 1,024 by a quarter at every context). A run's entries are blocks the
# pipeline brings, K and V, an operand of the call each: _CHUNK_ENTRIES at
# most.
_CHUNK_ROWS = 128
_CHUNK_KEYS = 512
_CHUNK_TOKENS = 64
_CHUNK_TILE_BYTES = 256 << 10
_CHUNK_ENTRIES = 8


class ChunkTile(NamedTuple):
    """A grid step of :func:`paged_chunk_attention`: ``block_c`` chunk
    tokens x ``heads`` KV heads against ``entries`` table entries."""
    block_c: int
    heads: int
    entries: int


def chunk_tile(C, KVH, G, d, BS, MB, dtype, block_c=0):
    """The :class:`ChunkTile` of a C-token chunk of KVH x G heads of d
    over a table of MB blocks of BS tokens: read off the call's shapes and
    nothing else. ``block_c`` > 0 pins the tokens a query tile (the
    engine's ``paged_block_c``, an autotune winner); the heads and the
    entries follow it."""
    if block_c:
        BC = max(1, min(int(block_c), C))
    else:
        BC = min(C, max(_CHUNK_TOKENS, _CHUNK_ROWS // G))
    rows = _CHUNK_TILE_BYTES // (_lanes(d) * jnp.dtype(dtype).itemsize)
    heads = max(h for h in range(1, KVH + 1)
                if KVH % h == 0 and (h == 1 or h * BC * G <= rows))
    return ChunkTile(BC, heads,
                     max(1, min(_CHUNK_KEYS // BS, _CHUNK_ENTRIES, MB)))


def _chunk_attended(xp, start, true_len, C, MB, BS, window, tile):
    """(first entry, grid steps) of each query tile of a chunk, in ``xp``
    (jax.numpy on the device, numpy on the host): tile i holds positions
    ``q_lo .. q_hi`` = ``start + i * BC ..`` and attends table entries
    ``max(0, q_lo - window + 1) // BS .. min(q_hi, limit - 1) // BS``,
    ``entries`` of them a step. A tile of pads alone (``q_lo >= limit``)
    takes one step, so that its rows come out finite."""
    BC, _, N = tile
    q_lo = start + xp.arange(-(-C // BC)) * BC
    limit = start + true_len
    last = xp.minimum(q_lo + BC - 1, limit - 1) // BS
    first = xp.maximum(q_lo - window + 1, 0) // BS if window \
        else xp.zeros_like(last)
    first = xp.clip(first, 0, MB - 1)
    steps = xp.where(q_lo < limit,
                     (xp.clip(last, first, MB - 1) - first + N) // N, 1)
    return first, steps


def chunk_grid_steps(start, true_len, C, KVH, MB, BS, window, tile):
    """The grid steps one :func:`paged_chunk_attention` call takes, on the
    host in numpy: :func:`chunk_work_list`'s ``n`` times the blocks of KV
    heads. What the engine's telemetry sets against the query tiles x table
    entries a call took before the work list."""
    _, steps = _chunk_attended(np, int(start), int(true_len), C, MB, BS,
                               window, tile)
    return int(np.sum(steps)) * (KVH // tile.heads)


class ChunkWork(NamedTuple):
    """:func:`chunk_work_list`'s items: two ``int32[items + 1]`` arrays,
    the items' count ``n`` on the device, and the static tile they were
    cut by."""
    tile_of: jax.Array
    entry_of: jax.Array
    n: jax.Array
    tile: ChunkTile


def chunk_work_list(start, true_len, C, MB, BS, window, tile):
    """The chunk kernel's grid, as data: item i is query tile
    ``tile_of[i]`` against the ``tile.entries`` table entries from
    ``entry_of[i]`` on, for every run of entries that holds a key some
    query of the tile attends (:func:`_chunk_attended`). Tile-major, a
    tile's items consecutive; from item ``n`` on ``tile_of`` reads the
    tiles' count, so a tile's last item is the one whose successor names
    another tile.

    A handful of small integer operations (compares and sums, as
    :func:`decode_work_list`): compute it once a chunk program and hand it
    to every layer's call (layers with another ``window`` take a list of
    their own)."""
    N = tile.entries
    first, steps = _chunk_attended(
        jnp, jnp.asarray(start, jnp.int32), jnp.asarray(true_len, jnp.int32),
        C, MB, BS, window, tile)
    NC = first.shape[0]
    ends = jnp.cumsum(steps)
    item = jnp.arange(NC * -(-MB // N) + 1, dtype=jnp.int32)
    tile_of = jnp.sum(ends[None, :] <= item[:, None], axis=1,
                      dtype=jnp.int32)
    mine = tile_of[:, None] == jnp.arange(NC, dtype=jnp.int32)[None, :]
    # a tile's k-th item starts at entry first + k * N
    entry_of = N * item + jnp.sum(
        jnp.where(mine, (first - N * (ends - steps))[None, :], 0), axis=1,
        dtype=jnp.int32)
    return ChunkWork(tile_of, jnp.clip(entry_of, 0, MB - 1),
                     ends[-1].astype(jnp.int32), tile)


def _chunk_kernel(tbl_ref, meta_ref, tile_ref, entry_ref, q_ref, *refs, N,
                  BS, G, BC, scale, window):
    """Grid step (h, i) is item i of the work list for block h of the KV
    heads: query tile ``tile_ref[i]`` (BC chunk tokens x G query heads a
    KV head, folded rows) against the N KV blocks the table names from
    entry ``entry_ref[i]`` on, as one run of ``N * BS`` keys (entries past
    the tile's last are past every query or the frontier: masked). A
    tile's items are consecutive, so its q and o tiles stay resident from
    its first item to its last, and the output is divided and stored at
    the last. Causal masking is structural: a run entirely before the
    tile's first query (and inside the valid-key range) takes the
    mask-free fast path; only diagonal/limit-straddling runs build the
    per-element mask."""
    k_refs, v_refs = refs[:N], refs[N:2 * N]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * N:]
    i = pl.program_id(1)
    tile, j = tile_ref[i], entry_ref[i]
    start = meta_ref[0]
    limit = meta_ref[0] + meta_ref[1]            # keys < limit are real
    S = N * BS

    @pl.when((i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != tile))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = start + tile * BC                     # tile's first q position
    q_hi = q_lo + BC - 1                         # tile's last q position
    k_lo = j * BS
    k_hi = k_lo + S - 1
    # mask-free fast path: every key visible to every query
    full = (k_hi <= q_lo) & (k_hi < limit)
    if window:
        full = full & (k_lo > q_hi - window)

    def _run(blocks):
        """The run's N blocks as one (heads, S, d) array."""
        if N == 1:
            return blocks[0][0]
        return jnp.concatenate([b[0] for b in blocks], axis=1)

    def _accumulate(s):
        """Online-softmax state update from scaled+masked scores
        s (heads, BC*G, S) fp32."""
        vb = _run(v_refs)
        m_prev = m_ref[..., 0]                   # (heads, BC*G)
        l_prev = l_ref[..., 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)  # (heads, BC*G, d)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
        m_ref[...] = jnp.broadcast_to(m_new[..., None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[..., None], l_ref.shape)

    def _scores():
        return jax.lax.dot_general(
            q_ref[...], _run(k_refs), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale

    @pl.when(full)
    def _full_run():
        _accumulate(_scores())

    @pl.when(jnp.logical_not(full))
    def _masked_run():
        s = _scores()
        rows = s.shape[1]                        # BC*G
        # row r of the folded q dim is chunk token r // G
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // G
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        ok = (kpos <= qpos) & (kpos < limit)
        if window:
            ok = ok & (kpos > qpos - window)
        _accumulate(jnp.where(ok[None], s, NEG_INF))

    @pl.when(tile_ref[i + 1] != tile)
    def _store():
        l = jnp.maximum(l_ref[..., 0], 1e-30)
        o_ref[...] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def _chunk_call(table, meta, tile_of, entry_of, n, qf, kp, vp, *, tile, G,
                scale, window, interpret):
    """The chunk kernel's ``pallas_call`` over the folded queries ``qf``
    (KVH, rows, d): the grid is (blocks of KV heads, the work list's
    ``n`` items)."""
    BC, HB, N = tile
    KVH, rows, d = qf.shape
    BS, MB = kp.shape[2], table.shape[0]
    NC = rows // (BC * G)
    R = BC * G

    # the pipeline may look one item ahead of the last, where ``tile_of``
    # reads NC: keep every index it can form inside its array
    def qo_index(h, i, tbl, meta, tile_of, entry_of):
        return (h, jnp.minimum(tile_of[i], NC - 1), 0)

    def kv_index(t):
        def index(h, i, tbl, meta, tile_of, entry_of):
            return (tbl[jnp.minimum(entry_of[i] + t, MB - 1)], h, 0, 0)
        return index

    kv_specs = [pl.BlockSpec((1, HB, BS, d), kv_index(t)) for t in range(N)]
    isz, lanes = qf.dtype.itemsize, _lanes(d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(KVH // HB, n),
        in_specs=[pl.BlockSpec((HB, R, d), qo_index)] + kv_specs + kv_specs,
        out_specs=pl.BlockSpec((HB, R, d), qo_index),
        scratch_shapes=[
            pltpu.VMEM((HB, R, 128), jnp.float32),        # running max
            pltpu.VMEM((HB, R, 128), jnp.float32),        # running denom
            pltpu.VMEM((HB, R, d), jnp.float32),          # out accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_chunk_kernel, N=N, BS=BS, G=G, BC=BC,
                          scale=scale, window=window),
        name="dstpu.kernel.paged_chunk",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        interpret=interpret,
        # q and out tiles, the run's K and V, then the float32 state and
        # the score tile with its exponentials, counted as half a buffer
        # pair each
        **_vmem_params(*(2 * [HB * R * lanes * isz]
                         + 2 * [HB * N * BS * lanes * kp.dtype.itemsize]
                         + [HB * R * (lanes + 256) * 2]
                         + [HB * R * max(N * BS, 128) * 6])),
    )(table, meta, tile_of, entry_of, qf, *([kp] * N), *([vp] * N))


def paged_chunk_attention(q, k_cache, v_cache, table, start, true_len, *,
                          scale=None, window=0, block_c="auto", work=None,
                          interpret=None):
    """A C-token query chunk of ONE sequence attends over that
    sequence's paged KV blocks — the blocked-flash role of the
    reference's ragged_ops for the Dynamic SplitFuse chunk program.

    q: (C, H, d) chunk queries (positions start..start+C-1, right-pad
    rows are don't-care); k_cache/v_cache: (NB, KVH, BS, d) pools that
    ALREADY hold the chunk's own K/V (callers scatter first, exactly
    like the decode path); table: (MB,) int32 — the sequence's block
    table, scratch-padded; start/true_len: scalar int32. Returns
    (C, H, d) in q's dtype.

    The grid is the work list (:func:`chunk_work_list`: made here when
    ``work`` is not given; a model makes it once for all its layers), so
    its length is a device scalar and the call cannot be ``vmap``ped: a
    grid step for every run of table entries that holds a key some query
    of the tile attends, none for the table's tail past ``start +
    true_len``, for entries after the tile's last query or before its
    window. Each run's KV blocks are located through the block table via
    scalar-prefetch index maps and streamed through VMEM; runs fully
    before the diagonal take a mask-free path; only straddling runs
    build the per-element mask. ``window`` > 0 restricts attention to the
    trailing window (mistral). GQA is native: q folds to (KVH, C*G, d)
    and both dots batch over the KV heads of a step — the dense path's
    repeat_kv copies never exist. ``block_c``: chunk-token tile ("auto" =
    the autotune winner cache's choice for this shape bucket, see
    autotuning/kernel_registry.py 'paged_chunk'; that and 0 = from the
    call's shapes, :func:`chunk_tile`).
    """
    C, H, d = q.shape
    NB, KVH, BS, _ = k_cache.shape
    MB = table.shape[0]
    G = H // KVH
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _interpret_default()
    if work is None:
        if block_c == "auto":
            block_c = resolve_paged_chunk(
                True, "auto", C, MB, BS, KVH, G, d, q.dtype)[1]
        work = chunk_work_list(
            start, true_len, C, MB, BS, window,
            chunk_tile(C, KVH, G, d, BS, MB, k_cache.dtype, block_c))
    BC = work.tile.block_c
    C_pad = -(-C // BC) * BC
    if C_pad != C:
        q = jnp.pad(q, ((0, C_pad - C), (0, 0), (0, 0)))
    # fold (chunk, group) query rows: (C_pad, KVH, G, d) -> (KVH, C_pad*G, d)
    qf = q.reshape(C_pad, KVH, G, d).transpose(1, 0, 2, 3) \
        .reshape(KVH, C_pad * G, d)
    meta = jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(true_len, jnp.int32)])
    out = _chunk_call(table, meta, work.tile_of, work.entry_of, work.n, qf,
                      k_cache, v_cache, tile=work.tile, G=G,
                      scale=float(scale), window=int(window),
                      interpret=bool(interpret))
    out = out.reshape(KVH, C_pad, G, d).transpose(1, 0, 2, 3) \
        .reshape(C_pad, H, d)
    return out[:C]


def paged_chunk_attention_reference(q, k_cache, v_cache, table, start,
                                    true_len, *, scale=None, window=0):
    """Dense-gather fallback (the pre-kernel chunk path): gather the
    sequence's whole key range through its table into one (S, H, d)
    array and run masked dense attention. Parity reference for the
    kernel and the registry's 'dense' mode."""
    C, H, d = q.shape
    NB, KVH, BS, _ = k_cache.shape
    MB = table.shape[0]
    S = MB * BS
    G = H // KVH
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    gk = k_cache[table].transpose(0, 2, 1, 3).reshape(S, KVH, d)
    gv = v_cache[table].transpose(0, 2, 1, 3).reshape(S, KVH, d)
    gk = jnp.repeat(gk, G, axis=1)
    gv = jnp.repeat(gv, G, axis=1)
    s = jnp.einsum("thd,shd->hts", q, gk,
                   preferred_element_type=jnp.float32) * scale
    q_pos = (start + jnp.arange(C))[:, None]
    k_pos = jnp.arange(S)[None, :]
    ok = (k_pos <= q_pos) & (k_pos < start + true_len)
    if window:
        ok = ok & (q_pos - k_pos < window)
    s = jnp.where(ok[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("hts,shd->thd", p, gv)
