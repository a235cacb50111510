"""Grouped (ragged) matmul over per-expert weight groups as Pallas TPU
kernels — the dropless-MoE expert FFN.

Counterpart of the reference's CUTLASS grouped ``moe_gemm``
(``inference/v2/kernels/cutlass_ops``) and the megablox ``gmm`` pattern:
rows are sorted by routed expert and ``group_sizes[e]`` rows multiply
expert ``e``'s weight block. The XLA path for this is ``lax.ragged_dot``
— one op per projection, each re-streaming the full (E, K, N) weight
tensor and re-deciding tiling generically (on a v5e it streams OLMoE's
experts at 54-57 % of the HBM peak; PERF.md). These kernels own the whole
grouped product in ONE launch:

  * the row dimension is cut into m-tiles and each tile is assigned to
    the group(s) whose rows it holds via scalar-prefetched tile maps
    (``group_ids``/``m_tile_ids`` — a tile straddling a group boundary
    is visited once per group, so compute stays proportional to rows,
    never to experts x rows); each expert's weight tile streams through
    VMEM exactly once per (m-tile, n-tile) visit;
  * a fused SwiGLU variant (``grouped_swiglu``) runs the whole
    w1/w3 -> silu*mul -> w2 expert chain with the gate/up products
    sharing one streamed activation tile and the silu*mul epilogue
    applied in-register (the g/u intermediates never hit HBM
    separately);
  * its forward-only form (``grouped_swiglu`` with no block given) is the
    serving programs': the three products in one launch whose tiles come
    from the shape (``forward_tiles``: whole contraction, megabytes of
    weights a step), over a grid of visits that holds groups with rows
    only (``forward_visits``) — each touched expert's weights cross
    HBM -> VMEM once and an expert no row chose costs nothing;
  * the backward accumulates dw PER GROUP in fp32 (``_tgmm``: out block
    keyed by group id, row-masked accumulation over the group's
    m-tiles, weight-dtype cast fused in the epilogue) and emits dx
    through the same grouped kernel with the weight operand transposed
    in its index map (no materialized (E, N, K) transpose).

Rows beyond ``sum(group_sizes)`` produce ZEROS (the ``lax.ragged_dot``
contract — MoE transport padding relies on it). Off-TPU the kernels run
in Pallas interpreter mode; shapes whose dims cannot form tile-aligned
blocks fall back to ``lax.ragged_dot`` with identical semantics.

Which of them a model's ``grouped_kernel="auto"`` takes is decided from
platform, dtype and shape in ``moe/sharded_moe.py:resolve_grouped_params``
(off the TPU: the ragged program, byte for byte); ``TUNE_DEFAULTS`` is
what an explicit knob starts from, and the baseline of the autotune
registry's op ``"moe_grouped_mm"``, which no model asks any more.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_default as _interpret_default
from ._common import round_up as _round_up
from ._common import sds as _sds

# what an explicit knob starts from: the XLA ragged_dot path, and the
# tiles of the differentiable kernels (the autotune registry's baseline
# for 'moe_grouped_mm', autotuning/kernel_registry.py)
TUNE_DEFAULTS = {"backend": "ragged",
                 "block_m": 128, "block_n": 128, "block_k": 128}


def _pick_block(dim, want):
    """Largest divisor of ``dim`` <= want in 128-lane units (K and N
    each sit in a lane position in at least one of the fwd/dx/dw
    kernels); ``dim`` itself always qualifies when it fits. None = no
    valid block (caller falls back to ragged_dot)."""
    if dim <= want:
        return dim
    b = (want // 128) * 128
    while b >= 128:
        if dim % b == 0:
            return b
        b -= 128
    return None


# ------------------------------------------------------------- metadata
def _group_metadata(group_sizes, m_pad, tm, E):
    """Logical-tile maps for a grouped matmul over rows padded to
    ``m_pad`` (a ``tm`` multiple).

    Returns (group_ids, m_tile_ids, starts, ends, num_tiles): logical
    tile i computes group ``group_ids[i]``'s rows inside physical m-tile
    ``m_tile_ids[i]``. Each group covers the tiles its row range
    [starts, ends) touches (a boundary tile shared by two groups is
    visited by both); empty groups are clamped to one (masked-empty)
    visit so their dw blocks still get written; the LAST group's range
    extends to ``m_pad`` so every physical tile is visited and padding
    rows come out zero. Static size ``tiles_m + E``; entries past
    ``num_tiles`` are masked no-ops in the kernels."""
    tiles_m = m_pad // tm
    G = tiles_m + E
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    starts = ends - group_sizes.astype(jnp.int32)
    r_starts = jnp.minimum(starts // tm, tiles_m - 1)
    r_ends = -(-ends // tm)                       # ceil
    r_ends = r_ends.at[E - 1].set(tiles_m)        # tail coverage
    tiles_per = jnp.maximum(r_ends - r_starts, 1)
    gids = jnp.repeat(jnp.arange(E, dtype=jnp.int32), tiles_per,
                      total_repeat_length=G)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(tiles_per)[:-1].astype(jnp.int32)])
    within = jnp.arange(G, dtype=jnp.int32) - offs[gids]
    mtids = jnp.minimum(r_starts[gids] + within, tiles_m - 1)
    num_tiles = jnp.sum(tiles_per).astype(jnp.int32).reshape(1)
    return gids, mtids, starts, ends, num_tiles


def _row_mask(mt, g, st_ref, en_ref, valid, tm):
    """(tm, 1) bool: rows of physical tile ``mt`` inside group ``g``'s
    row range — and nothing at all on a padded logical tile."""
    rows = mt * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (rows >= st_ref[g]) & (rows < en_ref[g]) & valid


# ------------------------------------------------------------- gmm fwd
def _gmm_kernel(gid_ref, mtid_ref, st_ref, en_ref, nt_ref,
                x_ref, w_ref, o_ref, acc, *, tm, nk, trans_w):
    i = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]                     # (tm, tk)
    w = w_ref[0]                       # (tk, tn) | (tn, tk) when trans_w
    cw = 1 if trans_w else 0
    acc[...] += lax.dot_general(
        x, w, (((1,), (cw,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _flush():
        g = gid_ref[i]
        mt = mtid_ref[i]
        mask = _row_mask(mt, g, st_ref, en_ref, i < nt_ref[0], tm)
        prev_mt = jnp.where(i == 0, -1, mtid_ref[jnp.maximum(i - 1, 0)])
        prev = jnp.where(mt != prev_mt,
                         jnp.zeros_like(o_ref[...]), o_ref[...])
        o_ref[...] = jnp.where(mask, acc[...].astype(o_ref.dtype), prev)


def _gmm(x, w, group_sizes, *, tm, tn, tk, trans_w, interpret):
    """out[s, n] = sum_k x[s, k] w[g(s), k, n] (w (E, N, K) contracted on
    its last dim when ``trans_w``). x rows pre-padded to a tm multiple;
    rows outside every group come out zero."""
    M, K = x.shape
    E = w.shape[0]
    N = w.shape[1] if trans_w else w.shape[2]
    gids, mtids, starts, ends, num = _group_metadata(group_sizes, M, tm, E)
    G = int(gids.shape[0])
    grid = (N // tn, G, K // tk)

    w_spec = pl.BlockSpec((1, tn, tk),
                          lambda j, i, kk, gid, mtid, st, en, nt:
                          (gid[i], j, kk)) if trans_w else \
        pl.BlockSpec((1, tk, tn),
                     lambda j, i, kk, gid, mtid, st, en, nt:
                     (gid[i], kk, j))
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, nk=K // tk, trans_w=trans_w),
        name="dstpu.kernel.gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, i, kk, gid, mtid, st, en, nt:
                             (mtid[i], kk)),
                w_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn),
                lambda j, i, kk, gid, mtid, st, en, nt: (mtid[i], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=_sds((M, N), x.dtype, x),
        interpret=interpret,
    )(gids, mtids, starts, ends, num, x, w)
    return out


# ------------------------------------------------- fused SwiGLU up chain
def _swiglu_up_kernel(gid_ref, mtid_ref, st_ref, en_ref, nt_ref,
                      x_ref, w1_ref, w3_ref, o_ref, gacc, uacc, *,
                      tm, nk):
    i = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        gacc[...] = jnp.zeros_like(gacc)
        uacc[...] = jnp.zeros_like(uacc)

    x = x_ref[...]
    gacc[...] += lax.dot_general(x, w1_ref[0], (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    uacc[...] += lax.dot_general(x, w3_ref[0], (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _flush():
        g = gid_ref[i]
        mt = mtid_ref[i]
        mask = _row_mask(mt, g, st_ref, en_ref, i < nt_ref[0], tm)
        # silu*mul epilogue in fp32, one round to the output dtype
        gg = gacc[...]
        h = (gg * jax.nn.sigmoid(gg)) * uacc[...]
        prev_mt = jnp.where(i == 0, -1, mtid_ref[jnp.maximum(i - 1, 0)])
        prev = jnp.where(mt != prev_mt,
                         jnp.zeros_like(o_ref[...]), o_ref[...])
        o_ref[...] = jnp.where(mask, h.astype(o_ref.dtype), prev)


def _swiglu_up(x, w1, w3, group_sizes, *, tm, tn, tk, interpret):
    """h[s, f] = silu(x w1[g(s)])[s, f] * (x w3[g(s)])[s, f] in one
    launch — the gate and up products share each streamed x tile."""
    M, K = x.shape
    E, _, F = w1.shape
    gids, mtids, starts, ends, num = _group_metadata(group_sizes, M, tm, E)
    G = int(gids.shape[0])
    w_spec = pl.BlockSpec((1, tk, tn),
                          lambda j, i, kk, gid, mtid, st, en, nt:
                          (gid[i], kk, j))
    return pl.pallas_call(
        functools.partial(_swiglu_up_kernel, tm=tm, nk=K // tk),
        name="dstpu.kernel.swiglu_up",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(F // tn, G, K // tk),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, i, kk, gid, mtid, st, en, nt:
                             (mtid[i], kk)),
                w_spec, w_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn),
                lambda j, i, kk, gid, mtid, st, en, nt: (mtid[i], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32),
                            pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=_sds((M, F), x.dtype, x),
        interpret=interpret,
    )(gids, mtids, starts, ends, num, x, w1, w3)


# ------------------------------------------------------------- dw (tgmm)
def _tgmm_kernel(gid_ref, mtid_ref, st_ref, en_ref, nt_ref,
                 x_ref, g_ref, o_ref, acc, *, tm, last_i):
    i = pl.program_id(2)

    gid = gid_ref[i]
    prev_g = jnp.where(i == 0, -1, gid_ref[jnp.maximum(i - 1, 0)])
    next_g = jnp.where(i == last_i, -1,
                       gid_ref[jnp.minimum(i + 1, last_i)])

    @pl.when(gid != prev_g)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    mask = _row_mask(mtid_ref[i], gid, st_ref, en_ref, i < nt_ref[0], tm)
    x = jnp.where(mask, x_ref[...], 0)            # rows outside the group
    acc[...] += lax.dot_general(                  # contribute nothing
        x, g_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(gid != next_g)
    def _flush():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def _tgmm(x, dy, group_sizes, E, *, tm, tn, tk, out_dtype, interpret):
    """dw[e, k, n] = sum_{s in group e} x[s, k] dy[s, n] — the per-group
    weight-grad accumulation: the out block is keyed by group id, fp32
    accumulation runs over the group's row tiles (boundary tiles row-
    masked), and the weight-dtype cast lands in the flush epilogue.
    Empty groups write zeros (their single clamped visit is all-masked).
    """
    M, K = x.shape
    N = dy.shape[1]
    gids, mtids, starts, ends, num = _group_metadata(group_sizes, M, tm, E)
    G = int(gids.shape[0])
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, last_i=G - 1),
        name="dstpu.kernel.tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(K // tk, N // tn, G),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda ki, ni, i, gid, mtid, st, en, nt:
                             (mtid[i], ki)),
                pl.BlockSpec((tm, tn),
                             lambda ki, ni, i, gid, mtid, st, en, nt:
                             (mtid[i], ni)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn),
                lambda ki, ni, i, gid, mtid, st, en, nt: (gid[i], ki, ni)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=_sds((E, K, N), out_dtype, x),
        interpret=interpret,
    )(gids, mtids, starts, ends, num, x, dy)


# ------------------------------------- forward SwiGLU, tiles from the shape
# A serving program takes no gradient and its groups are small: a decode
# step of OLMoE is 256 routed rows over 64 experts, and what it costs is
# the touched experts' weights crossing HBM -> VMEM. The forward chain below
# is ONE launch that streams them once, in tiles of megabytes chosen from
# the shape, and visits no expert that has no rows.

# what the tiles of one launch may fill of a v5e core's 128 MiB of VMEM:
# the budget ``forward_tiles`` chooses under and the limit the launch is
# given (Mosaic's default scoped limit, 16 MiB, holds no two tiles of
# megabytes)
FORWARD_VMEM_BYTES = 48 << 20
_VMEM_SLACK = 4 << 20

# the most rows a group at which "auto" takes the forward kernel: the
# largest the chip has timed it at, and it won at every one below (PERF.md,
# PR 32: kernel alone, OLMoE's widths, 4 ... 512 rows a group)
FORWARD_ROWS_PER_GROUP = 512


def forward_tiles(rows, D, F, dtype, vmem_bytes=FORWARD_VMEM_BYTES):
    """(tm, tf, tk) of the forward SwiGLU chain x (rows, D) -> (rows, F)
    -> (rows, D) from its shape alone, or None where none forms (the
    caller's ragged products then).

    ``tk`` is D whole: a visit contracts in one step and keeps no partial
    sum across steps of the up products. ``tf`` is the widest slice of F
    (a multiple of 128 that divides it) whose three weight tiles — w1 and
    w3 (D, tf), w2 (tf, D) — fit ``vmem_bytes`` double-buffered beside the
    row tiles and the float32 intermediates. ``tm`` is the rows rounded to
    the dtype's sublane multiple, 128 at most: a masked product over more
    rows than that takes the MXU longer than its weights take to arrive."""
    dt = jnp.dtype(dtype)
    if dt not in (jnp.bfloat16, jnp.float32) or D % 128 or F % 128 \
            or rows < 1:
        return None
    tm = min(128, _round_up(rows, 32 // dt.itemsize))
    # x and out row tiles (double-buffered) and the float32 accumulator
    fixed = tm * D * (4 * dt.itemsize + 4)
    # a column of the slice: three weight tiles twice, g / u / h in float32
    per_column = 6 * D * dt.itemsize + 12 * tm
    tf = _pick_block(F, (vmem_bytes - fixed - _VMEM_SLACK) // per_column)
    return None if tf is None else (tm, tf, D)


def _running_sum(x):
    """Inclusive running sum of a short int32 vector as one compare and
    one sum: a single fusion on the TPU, where ``cumsum`` is a
    reduce-window between two copies."""
    i = jnp.arange(x.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where(i[None, :] <= i[:, None], x[None, :], 0),
                   axis=1, dtype=jnp.int32)


def forward_visits(group_sizes, m_pad, tm):
    """The forward launch's grid, as data: one visit for every (group that
    has rows, row tile its rows touch) pair and none for an empty group —
    ``lax.ragged_dot`` reads no weight of an expert no row chose, and
    neither does this (``_group_metadata`` keeps one masked visit for an
    empty group, which ``_tgmm`` needs to write its dw block and a forward
    pays 3 x D x F weights for).

    Returns ``(wid, mtid, lo, hi, n)``: visit i multiplies row tile
    ``mtid[i]`` by expert ``wid[i]``'s weights and keeps rows ``lo[i] <=
    row < hi[i]``; ``n`` visits, of a static ``m_pad // tm + E`` at most.
    The rows past ``sum(group_sizes)`` are a last group of their own with
    nothing to keep (``lo = hi = 0``: its visits multiply nothing) on the
    weights of the last group that has rows, so their tiles are written —
    as zeros — and nothing is fetched for them. A few compares and sums
    over (visits, groups), non-negative integers throughout: a ``repeat``,
    a ``searchsorted`` or a gather here is a loop on the TPU (PERF.md,
    PR 27), and every operation is one a layer call makes."""
    E = group_sizes.shape[0]
    tiles_m = m_pad // tm
    sizes = group_sizes.astype(jnp.int32)
    # entry E: the rows past the groups, up to m_pad
    sizes = jnp.concatenate([sizes, m_pad - jnp.sum(sizes, keepdims=True)])
    ends = _running_sum(sizes)
    starts = ends - sizes
    live = sizes > 0
    first = lax.div(starts, tm)
    tiles_per = jnp.where(live, lax.div(ends - 1, tm) - first + 1, 0)
    cum = _running_sum(tiles_per)
    entry = jnp.arange(E + 1, dtype=jnp.int32)
    item = jnp.arange(tiles_m + E, dtype=jnp.int32)
    # visit i belongs to the first entry whose running sum passes i
    gid = jnp.sum(cum[None, :] <= item[:, None], axis=1, dtype=jnp.int32)
    mine = gid[:, None] == entry[None, :]

    def of_visit(per_entry):
        return jnp.sum(jnp.where(mine, per_entry[None, :], 0), axis=1,
                       dtype=jnp.int32)

    real = entry < E
    mtid = jnp.clip(item + of_visit(first - (cum - tiles_per)),
                    0, tiles_m - 1)
    last = jnp.max(jnp.where(live & real, entry, 0))
    return (jnp.minimum(gid, last), mtid,
            of_visit(jnp.where(real, starts, 0)),
            of_visit(jnp.where(real, ends, 0)), cum[-1])


def _swiglu_forward_kernel(wid_ref, mtid_ref, lo_ref, hi_ref,
                           x_ref, w1_ref, w3_ref, w2_ref, o_ref, acc, *,
                           tm, nf):
    """Grid step (i, f): visit i's row tile against slice f of its
    expert's F. Visits of a row tile are consecutive, so its x and o tiles
    stay resident from its first visit to its last."""
    i = pl.program_id(0)
    f = pl.program_id(1)
    mt = mtid_ref[i]
    lo, hi = lo_ref[i], hi_ref[i]

    @pl.when(hi > lo)
    def _products():
        x = x_ref[...]
        dims = (((1,), (0,)), ((), ()))
        g = lax.dot_general(x, w1_ref[0], dims,
                            preferred_element_type=jnp.float32)
        u = lax.dot_general(x, w3_ref[0], dims,
                            preferred_element_type=jnp.float32)
        # silu * mul in float32, one round to the down product's operand
        h = ((g * jax.nn.sigmoid(g)) * u).astype(x.dtype)
        part = lax.dot_general(h, w2_ref[0], dims,
                               preferred_element_type=jnp.float32)

        if nf == 1:
            acc[...] = part
        else:
            @pl.when(f == 0)
            def _first():
                acc[...] = part

            @pl.when(f > 0)
            def _rest():
                acc[...] += part

    @pl.when(f == nf - 1)
    def _write():
        # the visit's rows; the others keep what an earlier visit of the
        # tile wrote, or are zero on the tile's first visit
        rows = mt * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        prev_mt = jnp.where(i == 0, -1, mtid_ref[jnp.maximum(i - 1, 0)])
        prev = jnp.where(mt != prev_mt, jnp.zeros_like(o_ref[...]),
                         o_ref[...])
        o_ref[...] = jnp.where((rows >= lo) & (rows < hi),
                               acc[...].astype(o_ref.dtype), prev)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _swiglu_forward(x, w1, w3, w2, group_sizes, *, tiles, interpret):
    """The whole chain in one launch: a visit fetches its expert's w1, w3
    and w2 slices side by side, the row tile crosses them with ``o``
    accumulated in VMEM over the slices of F, and g, u and h never leave
    it. Jitted on its own: a serving program makes one call a layer and
    step (96 in OLMoE's decode dispatch), and an inner jit is traced once
    a shape and lowered once a program."""
    tm, tf, _ = tiles
    x, M = _pad_rows(x, tm)
    Mp, D = x.shape
    F, Do = w2.shape[1:]
    *maps, n = forward_visits(group_sizes, Mp, tm)
    w_up = pl.BlockSpec((1, D, tf), lambda i, f, wid, *_: (wid[i], 0, f))
    row = lambda i, f, wid, mtid, *_: (mtid[i], 0)
    out = pl.pallas_call(
        functools.partial(_swiglu_forward_kernel, tm=tm, nf=F // tf),
        name="dstpu.kernel.swiglu_forward",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n, F // tf),
            in_specs=[pl.BlockSpec((tm, D), row), w_up, w_up,
                      pl.BlockSpec((1, tf, Do),
                                   lambda i, f, wid, *_: (wid[i], f, 0))],
            out_specs=pl.BlockSpec((tm, Do), row),
            scratch_shapes=[pltpu.VMEM((tm, Do), jnp.float32)],
        ),
        out_shape=_sds((Mp, Do), x.dtype, x),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=FORWARD_VMEM_BYTES),
        interpret=interpret,
    )(*maps, x, w1, w3, w2)
    return out[:M]


def _ragged_swiglu(x, w1, w3, w2, group_sizes):
    g = lax.ragged_dot(x, w1, group_sizes)
    u = lax.ragged_dot(x, w3, group_sizes)
    return lax.ragged_dot(jax.nn.silu(g) * u, w2, group_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _swiglu_forward_diff(x, w1, w3, w2, group_sizes, tiles, interpret):
    """The forward launch wherever no gradient is taken; a differentiated
    call is ``lax.ragged_dot``'s program, forward and backward (the chip
    has never timed a backward of these kernels)."""
    return _swiglu_forward(x, w1, w3, w2, group_sizes, tiles=tiles,
                           interpret=interpret)


def _swiglu_forward_diff_fwd(x, w1, w3, w2, group_sizes, tiles, interpret):
    return jax.vjp(lambda *ops: _ragged_swiglu(*ops, group_sizes),
                   x, w1, w3, w2)


_swiglu_forward_diff.defvjp(
    _swiglu_forward_diff_fwd, lambda tiles, interpret, vjp, dy:
    (*vjp(dy), None))


# ---------------------------------------------------------------- public
def _blocks_fit(M, K, N, bm, bn, bk):
    """Resolve (tm, tn, tk) or None — K/N must form 128-aligned divisor
    blocks (each appears in a lane position in at least one of the
    fwd/dx/dw kernels); the row dim is padded to tm outside."""
    tn = _pick_block(N, bn)
    tk = _pick_block(K, bk)
    if tn is None or tk is None or min(M, K, N) < 8:
        return None
    tm = min(bm, _round_up(M, 8))
    return tm, tn, tk


def _pad_rows(x, tm):
    M = x.shape[0]
    pad = _round_up(M, tm) - M
    return (jnp.pad(x, ((0, pad), (0, 0))) if pad else x), M


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gmm_diff(x, w, group_sizes, tm, tn, tk, interpret):
    xp, M = _pad_rows(x, tm)
    return _gmm(xp, w, group_sizes, tm=tm, tn=tn, tk=tk, trans_w=False,
                interpret=interpret)[:M]


def _gmm_diff_fwd(x, w, group_sizes, tm, tn, tk, interpret):
    return (_gmm_diff(x, w, group_sizes, tm, tn, tk, interpret),
            (x, w, group_sizes))


def _gmm_diff_bwd(tm, tn, tk, interpret, res, dy):
    x, w, group_sizes = res
    E = w.shape[0]
    xp, M = _pad_rows(x, tm)
    dyp, _ = _pad_rows(dy, tm)
    # dx contracts the OUT dim (tn) and emits the contract dim (tk):
    # same grouped kernel, weight operand transposed in its index map
    dx = _gmm(dyp, w, group_sizes, tm=tm, tn=tk, tk=tn, trans_w=True,
              interpret=interpret)[:M]
    dw = _tgmm(xp, dyp, group_sizes, E, tm=tm, tn=tn, tk=tk,
               out_dtype=w.dtype, interpret=interpret)
    return dx, dw, None


_gmm_diff.defvjp(_gmm_diff_fwd, _gmm_diff_bwd)


def grouped_matmul(x, w, group_sizes, *, block_m=128, block_n=128,
                   block_k=128, interpret=None):
    """``lax.ragged_dot`` drop-in: x (S, K) rows sorted by group, w
    (E, K, N), group_sizes (E,) int32 -> (S, N); rows beyond
    ``sum(group_sizes)`` are zero. Differentiable (dx through the
    transposed-weight kernel, dw through the per-group fp32 ``_tgmm``).
    Shapes whose dims cannot form tile-aligned blocks fall back to
    ``lax.ragged_dot`` with identical math.
    """
    if x.ndim != 2 or w.ndim != 3 or x.shape[1] != w.shape[1]:
        raise ValueError(
            f"grouped_matmul expects x (S, K) and w (E, K, N); got "
            f"{x.shape} / {w.shape}")
    fit = _blocks_fit(x.shape[0], x.shape[1], w.shape[2],
                      block_m, block_n, block_k)
    if fit is None:
        return lax.ragged_dot(x, w, group_sizes)
    tm, tn, tk = fit
    if interpret is None:
        interpret = _interpret_default()
    return _gmm_diff(x, w, group_sizes.astype(jnp.int32), tm, tn, tk,
                     bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _swiglu_diff(x, w1, w3, w2, group_sizes, tm, tn, tk, interpret):
    xp, M = _pad_rows(x, tm)
    h = _swiglu_up(xp, w1, w3, group_sizes, tm=tm, tn=tn, tk=tk,
                   interpret=interpret)
    return _gmm(h, w2, group_sizes, tm=tm, tn=tk, tk=tn, trans_w=False,
                interpret=interpret)[:M]


def _swiglu_diff_fwd(x, w1, w3, w2, group_sizes, tm, tn, tk, interpret):
    return (_swiglu_diff(x, w1, w3, w2, group_sizes, tm, tn, tk,
                         interpret),
            (x, w1, w3, w2, group_sizes))


def _swiglu_diff_bwd(tm, tn, tk, interpret, res, dy):
    """Backward with the flash-style remat trade: g and u are recomputed
    from x (two grouped products) instead of living in HBM between
    forward and backward; every matmul is the grouped kernel and each
    dw accumulates per group in fp32."""
    x, w1, w3, w2, group_sizes = res
    E = w1.shape[0]
    xp, M = _pad_rows(x, tm)
    dyp, _ = _pad_rows(dy, tm)
    kw = dict(tm=tm, interpret=interpret)
    g = _gmm(xp, w1, group_sizes, tn=tn, tk=tk, trans_w=False, **kw)
    u = _gmm(xp, w3, group_sizes, tn=tn, tk=tk, trans_w=False, **kw)
    gf = g.astype(jnp.float32)
    sg = jax.nn.sigmoid(gf)
    sil = (gf * sg).astype(x.dtype)
    dh = _gmm(dyp, w2, group_sizes, tn=tn, tk=tk, trans_w=True, **kw)
    dhf = dh.astype(jnp.float32)
    dg = (dhf * u.astype(jnp.float32)
          * (sg * (1 + gf * (1 - sg)))).astype(x.dtype)
    du = (dhf * sil.astype(jnp.float32)).astype(x.dtype)
    dx = (_gmm(dg, w1, group_sizes, tn=tk, tk=tn, trans_w=True, **kw)
          + _gmm(du, w3, group_sizes, tn=tk, tk=tn, trans_w=True,
                 **kw))[:M]
    dw1 = _tgmm(xp, dg, group_sizes, E, tn=tn, tk=tk,
                out_dtype=w1.dtype, **kw)
    dw3 = _tgmm(xp, du, group_sizes, E, tn=tn, tk=tk,
                out_dtype=w3.dtype, **kw)
    h = (sil.astype(jnp.float32) * u.astype(jnp.float32)).astype(x.dtype)
    dw2 = _tgmm(h, dyp, group_sizes, E, tn=tk, tk=tn,
                out_dtype=w2.dtype, **kw)
    return dx, dw1, dw3, dw2, None


_swiglu_diff.defvjp(_swiglu_diff_fwd, _swiglu_diff_bwd)


def grouped_swiglu(x, w1, w3, w2, group_sizes, *, block_m=None,
                   block_n=None, block_k=None, interpret=None):
    """The whole SwiGLU expert chain as grouped kernels:
    ``gmm(silu(gmm(x, w1)) * gmm(x, w3), w2)``. x (S, K); w1/w3 (E, K, F);
    w2 (E, F, K'); -> (S, K'); rows beyond ``sum(group_sizes)`` are zero.

    With no block given it is the forward launch with tiles from the shape
    (``forward_tiles``; non-empty groups only; a differentiated call is
    ``lax.ragged_dot``'s program). With any block given (the others 128)
    it is the differentiable pair of launches — gate/up fused (shared x
    tiles, in-register silu*mul epilogue), then down — with the grouped
    backward. Shapes whose dims cannot form tile-aligned blocks fall back
    to ``lax.ragged_dot`` with identical math.
    """
    E, K, F = w1.shape
    if x.ndim != 2 or x.shape[1] != K or w3.shape != w1.shape or \
            w2.shape[:2] != (E, F):
        raise ValueError(
            f"grouped_swiglu shape mismatch: x {x.shape}, w1 {w1.shape}, "
            f"w3 {w3.shape}, w2 {w2.shape}")
    if interpret is None:
        interpret = _interpret_default()
    gs = group_sizes.astype(jnp.int32)
    if block_m is None and block_n is None and block_k is None:
        tiles = forward_tiles(x.shape[0], K, F, x.dtype) \
            if w2.shape[2] == K else None
        if tiles is None:
            return _ragged_swiglu(x, w1, w3, w2, group_sizes)
        return _swiglu_forward_diff(x, w1, w3, w2, gs, tiles,
                                    bool(interpret))
    block_m, block_n, block_k = (128 if b is None else b
                                 for b in (block_m, block_n, block_k))
    fit = _blocks_fit(x.shape[0], K, F, block_m, block_n, block_k)
    # the down projection re-uses the same tiles with roles swapped, so
    # its output dim (w2's last) must form blocks too
    fit_dn = fit and _pick_block(w2.shape[2], block_k)
    if fit is None or fit_dn is None or fit_dn != fit[2]:
        return _ragged_swiglu(x, w1, w3, w2, group_sizes)
    tm, tn, tk = fit
    return _swiglu_diff(x, w1, w3, w2, gs, tm, tn, tk, bool(interpret))


# ----------------------------------------- weight-only quantized forward
def _unpack4(p):
    """(tk//2, tn) packed int4 tile -> (tk, tn) int8 codes (layout in
    ops/pallas/quantization.py: low nibble = even row, high = odd)."""
    lo = jnp.right_shift(jnp.left_shift(p, 4), 4)
    hi = jnp.right_shift(p, 4)
    return jnp.stack([lo, hi], axis=1).reshape(2 * p.shape[0], p.shape[1])


def _gmm_wq_kernel(gid_ref, mtid_ref, st_ref, en_ref, nt_ref,
                   x_ref, w_ref, s_ref, o_ref, acc, *, tm, nk, int4):
    """_gmm_kernel with a quantized weight operand: int8/int4 expert
    tiles widen in VMEM and the per-(expert, output-channel) scale
    multiplies the f32 accumulator once in the flush — each logical
    tile writes only ITS group's rows, so a row tile straddling two
    experts still gets each expert's own scale."""
    i = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[0]
    if int4:
        w = _unpack4(w)
    acc[...] += lax.dot_general(
        x, w.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _flush():
        g = gid_ref[i]
        mt = mtid_ref[i]
        mask = _row_mask(mt, g, st_ref, en_ref, i < nt_ref[0], tm)
        s = s_ref[0, 0]                # (tn,) this expert's scales
        prev_mt = jnp.where(i == 0, -1, mtid_ref[jnp.maximum(i - 1, 0)])
        prev = jnp.where(mt != prev_mt,
                         jnp.zeros_like(o_ref[...]), o_ref[...])
        o_ref[...] = jnp.where(mask,
                               (acc[...] * s[None, :]).astype(o_ref.dtype),
                               prev)


def _gmm_wq(x, q, s, group_sizes, *, tm, tn, tk, int4, interpret):
    """Grouped matmul with quantized weights: q (E, K, N) int8 (or
    (E, K//2, N) packed int4), s (E, 1, N) per-channel scales."""
    M, K = x.shape
    E, _, N = s.shape[0], q.shape[1], s.shape[2]
    gids, mtids, starts, ends, num = _group_metadata(group_sizes, M, tm, E)
    G = int(gids.shape[0])
    w_blk = (1, tk // 2, tn) if int4 else (1, tk, tn)
    w_spec = pl.BlockSpec(w_blk,
                          lambda j, i, kk, gid, mtid, st, en, nt:
                          (gid[i], kk, j))
    s_spec = pl.BlockSpec((1, 1, tn),
                          lambda j, i, kk, gid, mtid, st, en, nt:
                          (gid[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_wq_kernel, tm=tm, nk=K // tk, int4=int4),
        name="dstpu.kernel.gmm_wq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, G, K // tk),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, i, kk, gid, mtid, st, en, nt:
                             (mtid[i], kk)),
                w_spec, s_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn),
                lambda j, i, kk, gid, mtid, st, en, nt: (mtid[i], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=_sds((M, N), x.dtype, x),
        interpret=interpret,
    )(gids, mtids, starts, ends, num, x, q, s)


def _swiglu_up_wq_kernel(gid_ref, mtid_ref, st_ref, en_ref, nt_ref,
                         x_ref, w1_ref, s1_ref, w3_ref, s3_ref, o_ref,
                         gacc, uacc, *, tm, nk, int4):
    i = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        gacc[...] = jnp.zeros_like(gacc)
        uacc[...] = jnp.zeros_like(uacc)

    x = x_ref[...].astype(jnp.float32)
    w1 = w1_ref[0]
    w3 = w3_ref[0]
    if int4:
        w1 = _unpack4(w1)
        w3 = _unpack4(w3)
    gacc[...] += lax.dot_general(x, w1.astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    uacc[...] += lax.dot_general(x, w3.astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _flush():
        g = gid_ref[i]
        mt = mtid_ref[i]
        mask = _row_mask(mt, g, st_ref, en_ref, i < nt_ref[0], tm)
        # dequant scales first (per accumulator), THEN silu*mul — the
        # epilogue nonlinearity sees the same values the fp math would
        gg = gacc[...] * s1_ref[0, 0][None, :]
        uu = uacc[...] * s3_ref[0, 0][None, :]
        h = (gg * jax.nn.sigmoid(gg)) * uu
        prev_mt = jnp.where(i == 0, -1, mtid_ref[jnp.maximum(i - 1, 0)])
        prev = jnp.where(mt != prev_mt,
                         jnp.zeros_like(o_ref[...]), o_ref[...])
        o_ref[...] = jnp.where(mask, h.astype(o_ref.dtype), prev)


def _swiglu_up_wq(x, q1, s1, q3, s3, group_sizes, *, tm, tn, tk, int4,
                  interpret):
    M, K = x.shape
    E, F = s1.shape[0], s1.shape[2]
    gids, mtids, starts, ends, num = _group_metadata(group_sizes, M, tm, E)
    G = int(gids.shape[0])
    w_blk = (1, tk // 2, tn) if int4 else (1, tk, tn)
    w_spec = pl.BlockSpec(w_blk,
                          lambda j, i, kk, gid, mtid, st, en, nt:
                          (gid[i], kk, j))
    s_spec = pl.BlockSpec((1, 1, tn),
                          lambda j, i, kk, gid, mtid, st, en, nt:
                          (gid[i], 0, j))
    return pl.pallas_call(
        functools.partial(_swiglu_up_wq_kernel, tm=tm, nk=K // tk,
                          int4=int4),
        name="dstpu.kernel.swiglu_up_wq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(F // tn, G, K // tk),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, i, kk, gid, mtid, st, en, nt:
                             (mtid[i], kk)),
                w_spec, s_spec, w_spec, s_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn),
                lambda j, i, kk, gid, mtid, st, en, nt: (mtid[i], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32),
                            pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=_sds((M, F), x.dtype, x),
        interpret=interpret,
    )(gids, mtids, starts, ends, num, x, q1, s1, q3, s3)


def grouped_swiglu_wq(x, w1, w3, w2, group_sizes, *, block_m=128,
                      block_n=128, block_k=128, interpret=None):
    """``grouped_swiglu`` with quantized expert weights (``Int8Weight``
    / ``Int4Weight``, all three the same width): int8/int4 tiles stream
    HBM->VMEM, per-(expert, channel) scales fold into the flush
    epilogues, fp32 accumulation throughout. Serving-only (no vjp).
    Shapes the tiling rules reject fall back to dequant + ragged_dot
    (materializing the dequantized experts for that call only)."""
    from ..int8_weights import Int4Weight, Int8Weight
    ws = (w1, w3, w2)
    if not all(isinstance(w, (Int8Weight, Int4Weight)) for w in ws):
        raise TypeError("grouped_swiglu_wq needs Int8Weight/Int4Weight "
                        "expert weights")
    int4s = [isinstance(w, Int4Weight) for w in ws]
    int4 = int4s[0]
    K = x.shape[1]
    F = w1.scale.shape[-1]
    Kd = w2.scale.shape[-1]
    fit = _blocks_fit(x.shape[0], K, F, block_m, block_n, block_k)
    fit_dn = fit and _pick_block(Kd, block_k)
    ok = (fit is not None and fit_dn is not None and fit_dn == fit[2]
          and all(i4 == int4 for i4 in int4s)
          and (not int4 or (fit[2] % 2 == 0 and fit[1] % 2 == 0)))
    if not ok:
        g = lax.ragged_dot(x, w1.dequant(x.dtype), group_sizes)
        u = lax.ragged_dot(x, w3.dequant(x.dtype), group_sizes)
        return lax.ragged_dot(jax.nn.silu(g) * u, w2.dequant(x.dtype),
                              group_sizes)
    tm, tn, tk = fit
    if interpret is None:
        interpret = _interpret_default()
    gs = group_sizes.astype(jnp.int32)
    xp, M = _pad_rows(x, tm)
    h = _swiglu_up_wq(xp, w1.q, w1.scale, w3.q, w3.scale, gs,
                      tm=tm, tn=tn, tk=tk, int4=int4,
                      interpret=bool(interpret))
    return _gmm_wq(h, w2.q, w2.scale, gs, tm=tm, tn=tk, tk=tn,
                   int4=int4, interpret=bool(interpret))[:M]
