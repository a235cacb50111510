"""Fused unembed + online-softmax-stats kernel for the training head.

Counterpart of the reference's fused softmax/logits kernels
(csrc/transformer/general_kernels.cu + softmax.cu — the GPU head fuses
what cuBLAS + eltwise passes would materialize). TPU motivation is HBM
traffic: XLA's chunked CE materializes the (rows, V) logits in fp32 and
re-reads them for logsumexp — ~15 GB per step at the 350M bench point.
This kernel computes the unembed matmul block-by-block over the vocab,
carrying the online max/sumexp (the flash-attention recurrence, over
vocab instead of keys) and the gold-logit readout in VMEM, and writes
the logits ONCE, in bf16 — the only HBM footprint. logz and the gold
logit come out exact (fp32 block scores before the bf16 round).

The grad-in-forward CE (models/common.fused_linear_xent_kernel) then
forms d_logits from the bf16 logits — identical numerics to what the
MXU would see anyway (bf16-truncated operands) — and feeds the two
backward matmuls.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import AUTO as _AUTO
from ._common import ce_bucket as _ce_bucket
from ._common import dispatch as _dispatch
from ._common import dtype_name as _dtype_name
from ._common import interpret_default as _interpret_default
from ._common import round_up as _round_up
from ._common import sds as _sds

NEG_INF = -1e30
STAT_LANES = 8

# r05-proven hand-set vocab-walk tiles; overridden by the autotune
# winner cache when callers leave block_m/block_n at "auto"
TUNE_DEFAULTS = {"block_m": 512, "block_n": 512}


def _ce_kernel(x_ref, w_ref, t_ref, logits_ref, logz_ref, gold_ref,
               m_scr, l_scr, g_scr, *, bn, V):
    j = pl.program_id(1)
    nv = pl.num_programs(1)
    x = x_ref[...]                                   # (bm, D) bf16
    w = w_ref[...]                                   # (bn, D) bf16
    s = lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)  # (bm, bn)
    bm = s.shape[0]
    col = j * bn + lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    s = jnp.where(col < V, s, NEG_INF)
    logits_ref[...] = s.astype(logits_ref.dtype)

    t = t_ref[...]                                   # (bm, 1) int32
    # col < V guard: targets landing in the padded tail [V, Vp) must
    # contribute 0, not the pad columns' NEG_INF
    gold_blk = jnp.sum(jnp.where((col == t) & (col < V), s, 0.0), axis=1)
    blk_max = jnp.max(s, axis=1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        g_scr[...] = jnp.zeros_like(g_scr)

    m_prev = m_scr[:, 0]
    l_prev = l_scr[:, 0]
    m_new = jnp.maximum(m_prev, blk_max)
    l_new = (l_prev * jnp.exp(m_prev - m_new)
             + jnp.sum(jnp.exp(s - m_new[:, None]), axis=1))
    m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)
    g_scr[...] = g_scr[...] + jnp.broadcast_to(gold_blk[:, None],
                                               g_scr.shape)

    @pl.when(j == nv - 1)
    def _final():
        logz = m_new + jnp.log(l_new)
        logz_ref[...] = jnp.broadcast_to(logz[:, None], logz_ref.shape)
        gold_ref[...] = g_scr[...]


def unembed_logits_stats(h, w, targets, *, block_m=_AUTO, block_n=_AUTO,
                         interpret=None):
    """h: (N, D) bf16 rows; w: (V, D); targets: (N,) int32.

    Returns (logits (N, V) in h.dtype, logz (N,) f32, gold (N,) f32) —
    logz and gold computed from the pre-round fp32 block scores.
    Rows of ``targets`` outside [0, V) contribute gold = 0.
    ``block_m``/``block_n`` left at "auto" (the default) resolve via the
    autotune winner cache at trace time, falling back to 512/512.
    """
    N, D = h.shape
    V = w.shape[0]
    if _AUTO in (block_m, block_n):
        win = _dispatch("fused_ce", _ce_bucket(N, D, V),
                        _dtype_name(h.dtype), TUNE_DEFAULTS)
        if block_m == _AUTO:
            block_m = int(win["block_m"])
        if block_n == _AUTO:
            block_n = int(win["block_n"])
    if interpret is None:
        interpret = _interpret_default()
    bm = min(block_m, N)
    while N % bm:
        bm //= 2
    Vp = _round_up(V, block_n)
    if Vp != V:
        w = jnp.pad(w, ((0, Vp - V), (0, 0)))
    grid = (N // bm, Vp // block_n)
    t2 = targets.astype(jnp.int32)[:, None]
    logits, logz, gold = pl.pallas_call(
        functools.partial(_ce_kernel, bn=block_n, V=V),
        name="dstpu.kernel.fused_ce",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, D), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, D), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((bm, STAT_LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, STAT_LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            _sds((N, Vp), h.dtype, h),
            _sds((N, STAT_LANES), jnp.float32, h),
            _sds((N, STAT_LANES), jnp.float32, h),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, STAT_LANES), jnp.float32),
            pltpu.VMEM((bm, STAT_LANES), jnp.float32),
            pltpu.VMEM((bm, STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(h, w, t2)
    return logits[:, :V], logz[:, 0], gold[:, 0]
