"""Block-sparse attention as Pallas TPU kernels.

Counterpart of the reference's Triton blocksparse tier
(``ops/sparse_attention/matmul.py`` + ``softmax.py``): attention
restricted to a (H, nq, nk) boolean block LAYOUT (fixed / BigBird /
Longformer configs in ops/sparse_attention/sparsity_config.py). The
masked-dense realization (ops/sparse_attention/sparse_self_attention.py)
computes every block and masks — O(T^2) compute and bandwidth regardless
of density, which defeats the component's purpose. These kernels iterate
ONLY the present blocks of each row (forward, dq) / column (dk/dv):
compute scales with layout density, the entire point of block sparsity.

Mechanics: the layout is preprocessed (host-side numpy, cacheable) into
per-row present-block id lists `rows (H, nq, max_nnz)` + counts
`row_cnt (H, nq)` and the column-wise transpose for the backward; the
lists ride scalar prefetch (SMEM) and the in-kernel fori_loop runs
`cnt` iterations of the flash-style streaming softmax, dynamically
slicing K/V (VMEM-resident per head) at `ids[jj] * block`. Numerics
match the masked-dense reference (same fp32 softmax, fully-masked rows
produce zero output).
"""

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_default as _interpret_default

NEG_INF = -1e30


def layout_lists(layout, causal, nq, nk):
    """(H, nq, nk) bool layout -> row/col present-block lists.

    Returns dict of int32 arrays: rows (H, nq, mr), row_cnt (H, nq),
    cols (H, nk, mc), col_cnt (H, nk). With ``causal`` blocks above the
    diagonal are dropped here (block b_q attends b_k <= b_q)."""
    lay = np.asarray(layout[:, :nq, :nk], bool).copy()
    if causal:
        tri = np.tril(np.ones((nq, nk), bool))
        lay &= tri[None]
    H = lay.shape[0]
    mr = max(1, int(lay.sum(axis=2).max()))
    mc = max(1, int(lay.sum(axis=1).max()))
    rows = np.zeros((H, nq, mr), np.int32)
    row_cnt = np.zeros((H, nq), np.int32)
    cols = np.zeros((H, nk, mc), np.int32)
    col_cnt = np.zeros((H, nk), np.int32)
    for h in range(H):
        for i in range(nq):
            ids = np.nonzero(lay[h, i])[0]
            rows[h, i, :len(ids)] = ids
            row_cnt[h, i] = len(ids)
        for j in range(nk):
            ids = np.nonzero(lay[h, :, j])[0]
            cols[h, j, :len(ids)] = ids
            col_cnt[h, j] = len(ids)
    return {"rows": rows, "row_cnt": row_cnt,
            "cols": cols, "col_cnt": col_cnt}


def _causal_mask(qi, j, bq, bk, T_q, T_k):
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return qpos >= kpos


# ------------------------------------------------------------------ forward
def _fwd_kernel(rows_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                bq, bk, H, causal):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    h = bh % H
    q = q_ref[0]                                          # (bq, d) bf16/f32
    d = q.shape[-1]
    cnt = cnt_ref[h, qi]

    def body(jj, carry):
        acc, m, l = carry
        j = rows_ref[h, qi, jj]
        kb = k_ref[0, pl.ds(j * bk, bk), :]
        vb = v_ref[0, pl.ds(j * bk, bk), :]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = jnp.where(_causal_mask(qi, j, bq, bk, None, None),
                          s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc = jnp.zeros((bq, d), jnp.float32)
    m = jnp.full((bq,), NEG_INF, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, cnt, body, (acc, m, l))
    # fully-masked rows (cnt==0 or causal-trimmed) -> zero output, like
    # the masked-dense reference
    safe_l = jnp.maximum(l, 1e-30)
    o_ref[0] = jnp.where(l[:, None] > 0, acc / safe_l[:, None],
                         0.0).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(
        jnp.where(l > 0, m + jnp.log(safe_l), NEG_INF)[:, None],
        (bq, lse_ref.shape[-1]))


# ----------------------------------------------------------------- backward
def _bwd_dq_kernel(rows_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, *, bq, bk, H, causal):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    h = bh % H
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0][:, 0]
    delta = delta_ref[0][:, 0]
    d = q.shape[-1]
    cnt = cnt_ref[h, qi]

    def body(jj, dq):
        j = rows_ref[h, qi, jj]
        kb = k_ref[0, pl.ds(j * bk, bk), :]
        vb = v_ref[0, pl.ds(j * bk, bk), :]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = jnp.where(_causal_mask(qi, j, bq, bk, None, None),
                          s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, cnt, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(cols_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, *, bq, bk, H,
                    causal):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    h = bh % H
    kb = k_ref[0]                                         # (bk, d)
    vb = v_ref[0]
    d = kb.shape[-1]
    cnt = cnt_ref[h, ki]

    def body(ii, carry):
        dk, dv = carry
        i = cols_ref[h, ki, ii]
        q = q_ref[0, pl.ds(i * bq, bq), :]
        do = do_ref[0, pl.ds(i * bq, bq), :]
        lse = lse_ref[0, pl.ds(i * bq, bq), :][:, 0]
        delta = delta_ref[0, pl.ds(i * bq, bq), :][:, 0]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = jnp.where(_causal_mask(i, ki, bq, bk, None, None),
                          s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        pb = p.astype(do.dtype)
        dv = dv + jax.lax.dot_general(pb, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None])).astype(q.dtype)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, cnt, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# --------------------------------------------------------------- plumbing
def _fwd(q, k, v, lists, bq, bk, H, causal, interpret):
    BH, T, d = q.shape
    nq = T // bq
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, r, c: (b, i, 0)),
            pl.BlockSpec((1, T, d), lambda b, i, r, c: (b, 0, 0)),
            pl.BlockSpec((1, T, d), lambda b, i, r, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, r, c: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, r, c: (b, i, 0)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, H=H, causal=causal),
        name="dstpu.kernel.bsa_fwd",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((BH, T, d), q.dtype),
                   jax.ShapeDtypeStruct((BH, T, 128), jnp.float32)],
        interpret=interpret,
    )(lists["rows"], lists["row_cnt"], q, k, v)


def _bwd(q, k, v, o, lse, do, lists, bq, bk, H, causal, interpret):
    BH, T, d = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[..., None], lse.shape)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, bq=bq, bk=bk, H=H,
                          causal=causal),
        name="dstpu.kernel.bsa_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, T // bq),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i, r, c: (b, i, 0)),
                pl.BlockSpec((1, T, d), lambda b, i, r, c: (b, 0, 0)),
                pl.BlockSpec((1, T, d), lambda b, i, r, c: (b, 0, 0)),
                pl.BlockSpec((1, bq, d), lambda b, i, r, c: (b, i, 0)),
                pl.BlockSpec((1, bq, 128), lambda b, i, r, c: (b, i, 0)),
                pl.BlockSpec((1, bq, 128), lambda b, i, r, c: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, d),
                                   lambda b, i, r, c: (b, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        interpret=interpret,
    )(lists["rows"], lists["row_cnt"], q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, bq=bq, bk=bk, H=H,
                          causal=causal),
        name="dstpu.kernel.bsa_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, T // bk),
            in_specs=[
                pl.BlockSpec((1, T, d), lambda b, j, c, n: (b, 0, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, c, n: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, c, n: (b, j, 0)),
                pl.BlockSpec((1, T, d), lambda b, j, c, n: (b, 0, 0)),
                pl.BlockSpec((1, T, 128), lambda b, j, c, n: (b, 0, 0)),
                pl.BlockSpec((1, T, 128), lambda b, j, c, n: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, d), lambda b, j, c, n: (b, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, j, c, n: (b, j, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((BH, T, d), q.dtype),
                   jax.ShapeDtypeStruct((BH, T, d), q.dtype)],
        interpret=interpret,
    )(lists["cols"], lists["col_cnt"], q, k, v, do, lse, delta)
    return dq, dk, dv


def block_sparse_attention(q, k, v, layout, block, *, causal=False,
                           scale=None, lists=None, interpret=None):
    """Attention restricted to a (H, T//block, T//block) bool layout.

    q/k/v: (B, T, H, d); T must divide by ``block``. ``lists`` may carry
    the precomputed :func:`layout_lists` (callers should cache it per
    (layout, T) — building it is host-side numpy). Matches
    sparse_self_attention.sparse_attention numerics (zero output for
    fully-masked rows). Differentiable: flash-style dq / dk+dv kernels
    over the row / column block lists."""
    B, T, H, d = q.shape
    assert T % block == 0, f"seq {T} not divisible by block {block}"
    nq = nk = T // block
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _interpret_default()
    if lists is None:
        lists = layout_lists(np.asarray(layout), causal, nq, nk)
    # static per-layout data: closed over as jaxpr constants so the
    # custom_vjp is over (q, k, v) only
    clists = {k2: jnp.asarray(np.asarray(v2), jnp.int32)
              for k2, v2 in lists.items()}
    bq = bk = block
    causal = bool(causal)
    interpret = bool(interpret)

    @jax.custom_vjp
    def bsa(qf, kf, vf):
        o, _ = _fwd(qf, kf, vf, clists, bq, bk, H, causal, interpret)
        return o

    def bsa_fwd(qf, kf, vf):
        o, lse = _fwd(qf, kf, vf, clists, bq, bk, H, causal, interpret)
        return o, (qf, kf, vf, o, lse)

    def bsa_bwd(res, do):
        qf, kf, vf, o, lse = res
        return _bwd(qf, kf, vf, o, lse, do, clists, bq, bk, H, causal,
                    interpret)

    bsa.defvjp(bsa_fwd, bsa_bwd)

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, d)

    q = q * jnp.asarray(scale, q.dtype)
    o = bsa(fold(q), fold(k), fold(v))
    return o.reshape(B, H, T, d).transpose(0, 2, 1, 3)
