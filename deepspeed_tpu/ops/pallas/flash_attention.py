"""Fused causal attention (flash attention) as a Pallas TPU kernel.

Counterpart of the reference's fused attention kernels: training softmax
(csrc/transformer/softmax_kernels.cu), inference attention
(csrc/transformer/inference/csrc/softmax.cu) and the memory-efficient
Evoformer kernel (csrc/deepspeed4science/evoformer_attn/) — all of which
exist because materializing the (T, T) score matrix is HBM-bound. Same
motivation here: the online-softmax streaming form never materializes
scores, so HBM traffic drops from O(T^2) to O(T * d) per head and the MXU
stays busy on the two matmuls.

Layout: (batch, seq, heads, head_dim) at the API (the model's layout).
Kernels process a GROUP of ``block_h`` (batch*head) instances per grid step
as batched dots — at GPT-2 head dims (64..128) a single head's (bq, d) x
(d, bk) dot is far too little work per grid step, and the sequential TPU
grid makes per-step overhead (DMA issue, semaphores) the bottleneck;
batching heads amortizes it. The MXU path keeps q/k/v/p in bf16 with fp32
accumulation (fp32 dot inputs run the MXU at 1/8 rate); softmax
bookkeeping stays fp32 on the VPU. The backward recomputes attention
probabilities from the saved logsumexp instead of storing them (the
standard flash backward).

Off-TPU (unit tests / dryrun) the kernels run in Pallas interpreter mode.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import AUTO as _AUTO
from ._common import dispatch as _dispatch
from ._common import dtype_name as _dtype_name
from ._common import flash_bucket as _flash_bucket
from ._common import interpret_default as _interpret_default
from ._common import note_call as _note_call
from ._common import round_up as _round_up
from ._common import sds as _sds

# the r05-proven hand-set tile/variant defaults — what an "auto" tunable
# resolves to when the autotune winner cache has no entry for this
# (device_kind, shape-bucket, dtype)
TUNE_DEFAULTS = {"block_q": 128, "block_k": 128, "block_h": 2,
                 "block_q_bwd": 0, "block_k_bwd": 0, "bwd_qmajor": False}


def _block_sizes(T, block_q, block_k):
    """Pick block sizes and the padded sequence length.

    Any T works: rather than shrinking blocks to a divisor of T (which
    degenerates to tiny blocks that violate the TPU (8,128) tiling and
    explode the grid for prime T), the sequence is padded up to a common
    multiple of the blocks and padded keys are masked in-kernel."""
    bq = min(block_q, _round_up(T, 8))
    bk = min(block_k, _round_up(T, 8))
    T_pad = _round_up(T, math.lcm(bq, bk))
    return bq, bk, T_pad


NEG_INF = -1e30

# The standard-layout kernels keep a whole sequence of an instance in VMEM
# (forward: K and V; backward: q, dO, o, lse and the float32 dq), double
# buffered. Mosaic's scoped default (16 MB) holds that at GPT-2's shapes
# (T 1024 - 2048, d 64); a long sequence at a wide head does not fit it
# (T 8192 at 256 lanes of keys and 128 of values: 15 MB forward, ~45 MB
# backward, with two buffers of each), so such a call asks for what its
# blocks need, of the 128 MiB a v5e core has. A call that fits the default
# is compiled as it always was.
_VMEM_DEFAULT = 16 << 20


def _vmem_params(*block_bytes):
    """``pallas_call`` keywords for a call whose resident blocks take
    ``block_bytes`` each: nothing while two buffers of each and the score
    tiles' room fit Mosaic's default."""
    need = 2 * sum(block_bytes) + (8 << 20)
    if need <= _VMEM_DEFAULT:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(need + (8 << 20), 112 << 20))}


def _lanes(d):
    return _round_up(d, 128)

# Trailing lane dim for per-row scalar tensors (lse, delta). Per-row
# scalars are not 2D-tileable at head-group sizes < 8, so they carry a
# small replicated lane dim. 8 lanes (not 128): the value lives in
# sublanes either side of the HBM round trip, so no in-kernel relayout,
# and the HBM footprint/traffic is 16x smaller than a full 128-lane
# block (201 MB -> 12.6 MB fp32 at 350M bs=24 shapes).
LSE_LANES = 8

# batched dot helpers: x (G, a, c) contract c against y's dim, batch over G
_DN_QK = (((2,), (2,)), ((0,), (0,)))    # (G,bq,d) x (G,bk,d) -> (G,bq,bk)
_DN_PV = (((2,), (1,)), ((0,), (0,)))    # (G,bq,bk) x (G,bk,d) -> (G,bq,d)
_DN_T = (((1,), (1,)), ((0,), (0,)))     # (G,bq,bk) x (G,bq,d) -> (G,bk,d)
# transposed-operand variants (q/k/v carried as (G, d, T) blocks, i.e. T in
# lanes — the layout the surrounding einsums prefer; see *_kernel_t)
_DN_QK_T = (((1,), (1,)), ((0,), (0,)))  # (G,d,bq) x (G,d,bk) -> (G,bq,bk)
_DN_PV_T = (((2,), (2,)), ((0,), (0,)))  # (G,bq,bk) x (G,d,bk) -> (G,bq,d)
_DN_DO_V = (((2,), (1,)), ((0,), (0,)))  # (G,bq,d) x (G,d,bk) -> (G,bq,bk)
_DN_DV_T = (((1,), (1,)), ((0,), (0,)))  # (G,bq,d) x (G,bq,bk) -> (G,d,bk)
_DN_DK_T = (((2,), (1,)), ((0,), (0,)))  # (G,d,bq) x (G,bq,bk) -> (G,d,bk)
_DN_DQ_T = (((2,), (2,)), ((0,), (0,)))  # (G,d,bk) x (G,bq,bk) -> (G,d,bq)


# ----------------------------------------------------------------- biases
# Additive score biases (ALiBi, padding masks, evoformer pair bias) ride
# as extra kernel operands shaped (rows, Tq|1, Tk) — never expanded to
# the (B*H, T, T) score shape. Which bias row(s) a grid group g needs is
# an affine map in block units:
#     f(g) = (g*bh // P) * Q + ((g*bh) % R) // bh
# parametrized per bias (a group of ``bh`` (b, h) instances shares one
# row, spans ``bh`` rows, or cycles rows with a period — all folds used
# by the models reduce to this form; see _bias_cfg). A cfg is the static
# tuple (per_rows, P, Q, R, tq_full, grad):
#   per_rows: rows the block carries (1 = whole group shares a row,
#             bh = one row per instance)
#   tq_full:  bias varies along the query dim (pair bias) vs broadcast
#             (key masks, ALiBi)
#   grad:     backward emits an accumulated d_bias output (evoformer
#             pair-bias training); requires a monotone f over the grid
_B_PER, _B_P, _B_Q, _B_R, _B_TQ, _B_GRAD = range(6)


def _bias_row(cfg, bh, g):
    """Block-row index of bias ``cfg`` for group ``g`` (traced or int)."""
    return (g * bh // cfg[_B_P]) * cfg[_B_Q] \
        + ((g * bh) % cfg[_B_R]) // bh


def _bias_cfg(Bb, Hb, B, H, bh, tq_full, grad, h_outer):
    """Cfg tuple for a (Bb, Hb, Tq, Tk) bias under the (b, h) fold
    (``h_outer``: the qkv_t kernels fold (H, B); others fold (B, H)).
    Bb in {1, B}; Hb in {1, H}. A size-1 model dim takes the broadcast
    branch (full and broadcast coincide there, but the full-branch row
    maps would index past the 1-row folded array)."""
    full_b, full_h = Bb == B > 1, Hb == H > 1
    if full_b and full_h:
        cfg = (bh, bh, 1, bh)
    elif h_outer:
        if full_b:                       # per-batch, group spans b
            cfg = (bh, 1, 0, B)
        elif full_h:                     # per-head, fixed within a group
            cfg = (1, B, 1, bh)
        else:
            cfg = (1, 1, 0, bh)
    else:
        if full_b:                       # per-batch, fixed within a group
            cfg = (1, H, 1, bh)
        elif full_h:                     # per-head, group spans h
            cfg = (bh, 1, 0, H)
        else:
            cfg = (1, 1, 0, bh)
    return cfg + (bool(tq_full), bool(grad))


def _bias_constraint(Bb, Hb, B, H, h_outer):
    """The number ``bh`` must DIVIDE so one bias block covers a group (a
    group must not straddle two rows of a shared dim), or None when the
    bias imposes no constraint. Note a divisor of 1 is a real
    constraint (bh = 1): e.g. a per-batch bias on an H == 1 model —
    groups span batch items there, so each instance needs its own
    row."""
    full_b = Bb == B and Bb > 1
    full_h = Hb == H and Hb > 1
    if (full_b and full_h) or (Bb == 1 and Hb == 1):
        return None
    if Bb > 1 and Hb == 1:          # per-batch bias
        return B if h_outer else H
    if Hb > 1 and Bb == 1:          # per-head bias
        return B if h_outer else H
    return None


def _fwd_bias_specs(cfgs, biases, bq, T_pad, bh):
    """Forward operand BlockSpecs: (per_rows, bq, T_pad); the kernel
    walks the key dim itself (k/v are full-T blocks too).

    Biases always carry a FULL query dim: a size-1 sublane dim
    broadcast inside the online-softmax carry loop crashes Mosaic's
    layout inference (verified on v5e), so the wrapper expands
    query-broadcast biases (key masks, ALiBi) to (rows, T, T) up
    front."""
    return [pl.BlockSpec(
        (cfg[_B_PER], bq, T_pad),
        lambda g, i, c=cfg: (_bias_row(c, bh, g), i, 0))
        for cfg, b in zip(cfgs, biases)]


def _bwd_bias_specs(cfgs, biases, bk, T_pad, bh):
    """Backward operand BlockSpecs: (per_rows, T_pad, bk); the kernel
    walks the query dim itself."""
    return [pl.BlockSpec(
        (cfg[_B_PER], T_pad, bk),
        lambda g, j, c=cfg: (_bias_row(c, bh, g), 0, j))
        for cfg, b in zip(cfgs, biases)]


def _fwd_bias_add(s, bias_refs, cfgs, j, bk):
    """s (G, bq, bk) += each bias's (rows, bq, bk) block, f32.

    The key dim is the LANE dim of the bias block: Mosaic needs dynamic
    lane offsets in 128 units, so the wrapper forces bk to a multiple of
    128 whenever biases are present (single-block refs load the static
    full block)."""
    for ref, cfg in zip(bias_refs, cfgs):
        blk = ref[...] if ref.shape[2] == bk \
            else ref[:, :, pl.ds(j * bk, bk)]
        s = s + blk.astype(jnp.float32)
    return s


def _bwd_bias_add(s, bias_refs, cfgs, i, bq):
    for ref, cfg in zip(bias_refs, cfgs):
        s = s + ref[:, pl.ds(i * bq, bq), :].astype(jnp.float32)
    return s


def _alibi_add(s, alibi_cfg, apos_blk, g, bh):
    """s (1, bq, bk) += slope_h * k_pos.

    The slope is evaluated in-kernel with the bloom formula from the
    instance's head index — a per-grid-step SCALAR (the wrapper forces
    block_h=1 under ALiBi). k_pos arrives as a tiny shared
    (1, T_pad, T_pad) f32 operand (``apos_blk`` is its (1, bq, bk)
    tile): Mosaic constant-folds iota->float chains into an f32
    ``tpu.iota`` that fails verification (and, unverified, crashes its
    layout pass) inside the softmax carry loop, so positions must come
    from a ref, exactly like the bias operands that compile fine. Net
    HBM cost is one O(T^2) array shared by every (batch, head) — not
    the (H, T, T) or (B, H, T, T) a materialized bias would need.
    alibi_cfg = (h_outer, H, B, scale, bf16) — see the wrapper."""
    h_outer, H, B, a_scale, a_bf16 = alibi_cfg
    idx = g * bh                              # bh == 1: instance index
    h = (idx // B if h_outer else idx % H).astype(jnp.float32)
    cp = float(2 ** math.floor(math.log2(H)))
    expo = jnp.where(h < cp, -(h + 1.0) * (8.0 / cp),
                     -(2.0 * (h - cp) + 1.0) * (4.0 / cp))
    slope = jnp.exp2(expo)                    # scalar
    ab = slope * apos_blk
    if a_bf16:
        # HF falcon quantizes the alibi tensor through bf16 and adds it
        # pre-scaling (models/llama.py _alibi_bias)
        ab = ab.astype(jnp.bfloat16).astype(jnp.float32)
    if a_scale != 1.0:
        ab = ab * a_scale
    return s + ab


def _mask_block(qi_start, kj_start, bq, bk, causal, t_real, T,
                window=0):
    """(bq, bk) boolean mask for causal / padded-key / sliding-window
    masking; None when none applies (static no-op)."""
    if not causal and t_real >= T and not window:
        return None
    qpos = qi_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kj_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = None
    if causal:
        ok = qpos >= kpos
    if window:
        win = qpos - kpos < window
        ok = win if ok is None else jnp.logical_and(ok, win)
    if t_real < T:
        valid = kpos < t_real
        ok = valid if ok is None else jnp.logical_and(ok, valid)
    return ok


def _apply_mask(s, ok):
    """s: (G, bq, bk); ok: (bq, bk) or None."""
    if ok is None:
        return s
    return jnp.where(ok[None], s, NEG_INF)


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, *rest, bq, bk, scale,
                causal, t_real, window=0, bias_cfgs=(),
                alibi_cfg=None):
    n_in = len(bias_cfgs) + (1 if alibi_cfg else 0)
    bias_refs = rest[:len(bias_cfgs)]
    apos_ref = rest[len(bias_cfgs)] if alibi_cfg else None
    o_ref, lse_ref = rest[n_in:]
    qi = pl.program_id(1)
    gi = pl.program_id(0)
    q = q_ref[...]                                        # (G, bq, d) bf16
    G = q.shape[0]
    T = k_ref.shape[1]
    nk = T // bk
    # causal: query block qi attends k blocks 0..ceil((qi+1)*bq / bk)-1.
    # Blocks fully below the diagonal skip mask generation entirely (the
    # iota/compare/select per element is real VPU cost in a VPU-bound
    # kernel); only the straddling blocks mask. With padded keys
    # (t_real < T) every block takes the masked path.
    kmax = pl.cdiv((qi + 1) * bq, bk) if causal else nk
    kfull = (qi * bq) // bk if (causal and t_real >= T) else (
        nk if (not causal and t_real >= T) else 0)
    kmin = 0
    if window:
        # blocks entirely below the window's lower edge are dead; every
        # live block takes the masked path (the window edge can cross
        # any of them)
        kmin = jnp.maximum(0, (qi * bq - window + 1) // bk)
        kfull = kmin

    def make_body(masked):
        def body(j, carry):
            acc, m, l = carry
            kb = k_ref[:, pl.ds(j * bk, bk), :]
            vb = v_ref[:, pl.ds(j * bk, bk), :]
            s = jax.lax.dot_general(q, kb, _DN_QK,
                                    preferred_element_type=jnp.float32)
            if scale != 1.0:
                s = s * scale
            if bias_cfgs:
                s = _fwd_bias_add(s, bias_refs, bias_cfgs, j, bk)
            if alibi_cfg:
                apb = apos_ref[...] if apos_ref.shape[2] == bk \
                    else apos_ref[:, :, pl.ds(j * bk, bk)]
                s = _alibi_add(s, alibi_cfg, apb, gi, G)
            if masked:
                s = _apply_mask(s, _mask_block(qi * bq, j * bk, bq, bk,
                                               causal, t_real, T,
                                               window))
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jax.lax.dot_general(
                p.astype(vb.dtype), vb, _DN_PV,
                preferred_element_type=jnp.float32)
            return acc, m_new, l
        return body

    acc = jnp.zeros((G, bq, v_ref.shape[-1]), jnp.float32)
    m = jnp.full((G, bq), NEG_INF, jnp.float32)
    l = jnp.zeros((G, bq), jnp.float32)
    carry = jax.lax.fori_loop(kmin, kfull, make_body(False), (acc, m, l))
    acc, m, l = jax.lax.fori_loop(kfull, kmax, make_body(True), carry)
    o_ref[...] = (acc / l[..., None]).astype(o_ref.dtype)
    # lse replicated across LSE_LANES lanes (see constant above); the
    # wrapper trims to one lane before anything is saved
    lse_ref[...] = jnp.broadcast_to((m + jnp.log(l))[..., None],
                                    (G, bq, lse_ref.shape[-1]))


def _fwd(q, k, v, scale, causal, bq, bk, bh, t_real, interpret, window=0,
         biases=(), bias_cfgs=(), alibi_cfg=None):
    BH, T, d = q.shape
    dv = v.shape[-1]        # the values', and the output's, own width
    grid = (BH // bh, T // bq)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, scale=scale,
                          causal=causal, t_real=t_real, window=window,
                          bias_cfgs=bias_cfgs, alibi_cfg=alibi_cfg),
        name="dstpu.kernel.flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bh, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((bh, T, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((bh, T, dv), lambda b, i: (b, 0, 0)),
        ] + _fwd_bias_specs(bias_cfgs, biases, bq, T, bh)
          + ([pl.BlockSpec((1, bq, T), lambda b, i: (0, i, 0))]
             if alibi_cfg else []),
        out_specs=[
            pl.BlockSpec((bh, bq, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((bh, bq, LSE_LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            _sds((BH, T, dv), q.dtype, q),
            _sds((BH, T, LSE_LANES), jnp.float32, q),
        ],
        interpret=interpret,
        # K and V whole; the q and o blocks; the lse block
        **_vmem_params(*([bh * T * _lanes(w) * q.dtype.itemsize
                          for w in (d, dv)]
                         + [bh * bq * _lanes(w) * q.dtype.itemsize
                            for w in (d, dv)]
                         + [bh * bq * 128 * 4])),
    )(q, k, v, *biases)
    return o, lse


# ------------------------------------------------- forward, transposed q/k/v
def _fwd_kernel_t(q_ref, k_ref, v_ref, *rest, bq, bk, scale,
                  causal, t_real, window=0, bias_cfgs=(),
                  alibi_cfg=None):
    """Forward with q/k/v blocked (G, d, T) — T in lanes.

    The surrounding qkv projection einsums emit T-minor layouts (hd=64
    fills only half a 128-lane register, so XLA puts T in lanes); the
    standard (G, T, d) operand forces a relayout copy per tensor per
    layer (~46 ms/step at 350M bs=24 counting forward, remat recompute
    and backward). Consuming the producer's layout directly makes those
    copies bitcasts. Score-space math is IDENTICAL to _fwd_kernel —
    softmax stats stay (G, bq) sublane vectors — only the q/k dots
    contract the sublane dim (MXU-native transposed matmul) and the pv
    dot contracts lanes x lanes. Output o stays (G, bq, d): its consumer
    (the wo projection) takes it without a copy either way.

    Biases are NOT transposed: score space is (bq, bk) in both layouts,
    so bias blocks are consumed in the standard orientation."""
    n_in = len(bias_cfgs) + (1 if alibi_cfg else 0)
    bias_refs = rest[:len(bias_cfgs)]
    apos_ref = rest[len(bias_cfgs)] if alibi_cfg else None
    o_ref, lse_ref = rest[n_in:]
    qi = pl.program_id(1)
    gi = pl.program_id(0)
    q = q_ref[...]                                        # (G, d, bq) bf16
    G = q.shape[0]
    T = k_ref.shape[2]
    nk = T // bk
    kmax = pl.cdiv((qi + 1) * bq, bk) if causal else nk
    kfull = (qi * bq) // bk if (causal and t_real >= T) else (
        nk if (not causal and t_real >= T) else 0)
    kmin = 0
    if window:
        kmin = jnp.maximum(0, (qi * bq - window + 1) // bk)
        kfull = kmin

    def make_body(masked):
        def body(j, carry):
            acc, m, l = carry
            kb = k_ref[:, :, pl.ds(j * bk, bk)]
            vb = v_ref[:, :, pl.ds(j * bk, bk)]
            s = jax.lax.dot_general(q, kb, _DN_QK_T,
                                    preferred_element_type=jnp.float32)
            if scale != 1.0:
                s = s * scale
            if bias_cfgs:
                s = _fwd_bias_add(s, bias_refs, bias_cfgs, j, bk)
            if alibi_cfg:
                apb = apos_ref[...] if apos_ref.shape[2] == bk \
                    else apos_ref[:, :, pl.ds(j * bk, bk)]
                s = _alibi_add(s, alibi_cfg, apb, gi, G)
            if masked:
                s = _apply_mask(s, _mask_block(qi * bq, j * bk, bq, bk,
                                               causal, t_real, T,
                                               window))
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jax.lax.dot_general(
                p.astype(vb.dtype), vb, _DN_PV_T,
                preferred_element_type=jnp.float32)
            return acc, m_new, l
        return body

    d = q_ref.shape[1]
    acc = jnp.zeros((G, bq, d), jnp.float32)
    m = jnp.full((G, bq), NEG_INF, jnp.float32)
    l = jnp.zeros((G, bq), jnp.float32)
    carry = jax.lax.fori_loop(kmin, kfull, make_body(False), (acc, m, l))
    acc, m, l = jax.lax.fori_loop(kfull, kmax, make_body(True), carry)
    o_ref[...] = (acc / l[..., None]).astype(o_ref.dtype)
    lse_ref[...] = jnp.broadcast_to((m + jnp.log(l))[..., None],
                                    (G, bq, lse_ref.shape[-1]))


def _fwd_t(q, k, v, scale, causal, bq, bk, bh, t_real, interpret,
           window=0, biases=(), bias_cfgs=(), alibi_cfg=None):
    BH, d, T = q.shape
    grid = (BH // bh, T // bq)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_t, bq=bq, bk=bk, scale=scale,
                          causal=causal, t_real=t_real, window=window,
                          bias_cfgs=bias_cfgs, alibi_cfg=alibi_cfg),
        name="dstpu.kernel.flash_fwd_t",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bh, d, bq), lambda b, i: (b, 0, i)),
            pl.BlockSpec((bh, d, T), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((bh, d, T), lambda b, i: (b, 0, 0)),
        ] + _fwd_bias_specs(bias_cfgs, biases, bq, T, bh)
          + ([pl.BlockSpec((1, bq, T), lambda b, i: (0, i, 0))]
             if alibi_cfg else []),
        out_specs=[
            pl.BlockSpec((bh, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((bh, bq, LSE_LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            _sds((BH, T, d), q.dtype, q),
            _sds((BH, T, LSE_LANES), jnp.float32, q),
        ],
        interpret=interpret,
    )(q, k, v, *biases)
    return o, lse


# ----------------------------------------------------------------- backward
def _dbias_init(dbias_refs, grad_cfgs, bh, ki):
    """Zero dbias accumulator blocks at the right step. per_rows==bh
    blocks are fresh every grid step (injective index map); per_rows==1
    blocks persist across the run of grid steps sharing a bias row —
    zero at the run's first step (monotone maps only, enforced in the
    wrapper)."""
    g = pl.program_id(0)
    for ref, cfg in zip(dbias_refs, grad_cfgs):
        if cfg[_B_PER] == 1:
            gp = jnp.maximum(g - 1, 0)
            start = jnp.logical_or(
                g == 0, _bias_row(cfg, bh, g) != _bias_row(cfg, bh, gp))

            @pl.when(jnp.logical_and(ki == 0, start))
            def _init(ref=ref):
                ref[...] = jnp.zeros_like(ref)
        else:
            ref[...] = jnp.zeros_like(ref)


def _dbias_update(dbias_refs, grad_cfgs, ds_f, i, ki, bq, bk):
    """Accumulate ds (f32, pre-cast) into each grad bias's block,
    summing over whichever score dims the bias broadcasts (query-
    broadcast biases use 2D (rows, Tk) accumulators)."""
    for ref, cfg in zip(dbias_refs, grad_cfgs):
        contrib = ds_f
        if cfg[_B_PER] == 1:
            contrib = jnp.sum(contrib, axis=0, keepdims=True)
        if cfg[_B_PER] == 1:                  # full-k persistent block
            if ref.shape[2] == bk:            # single k block: static
                ref[:, pl.ds(i * bq, bq), :] += contrib
            else:
                ref[:, pl.ds(i * bq, bq), pl.ds(ki * bk, bk)] += contrib
        else:                                 # per-step (rows, T_pad, bk)
            ref[:, pl.ds(i * bq, bq), :] += contrib


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, od_ref,
                *rest, bq, bk, scale, causal, t_real,
                ext_delta, single_k, window=0, bias_cfgs=(),
                alibi_cfg=None):
    """Fused flash backward: dq, dk, dv from ONE s/p computation.

    Grid is (BH/bh, T/bk) over key blocks; an inner loop walks the query
    blocks this key block attends. The two-kernel formulation (separate
    dq and dk/dv passes, as in the reference's backward and round 2
    here) computes s = q k^T and p = exp(s - lse) TWICE; fusing halves
    the score-matrix work — the dominant VPU+MXU cost of the backward.

    dq accumulates ACROSS grid steps in a VMEM-resident fp32 block (the
    TPU grid is sequential; the constant-index output block persists),
    initialized at the first key block. dk/dv accumulate in registers
    over the inner loop.
    """
    n_bias = len(bias_cfgs)
    n_in = n_bias + (1 if alibi_cfg else 0)
    bias_refs = rest[:n_bias]
    apos_ref = rest[n_bias] if alibi_cfg else None
    dq_ref, dk_ref, dv_ref = rest[n_in:n_in + 3]
    dbias_refs = rest[n_in + 3:]
    grad_cfgs = tuple(c for c in bias_cfgs if c[_B_GRAD])
    ki = pl.program_id(1)
    gi = pl.program_id(0)
    kb = k_ref[...]                                         # (G, bk, d) bf16
    G = kb.shape[0]
    vb = v_ref[...]
    T = q_ref.shape[1]
    nq = T // bq
    if dbias_refs:
        _dbias_init(dbias_refs, grad_cfgs, G, ki)
    qmin = (ki * bk) // bq if causal else 0
    # q blocks straddling the diagonal need the causal mask; blocks fully
    # below it don't. With padded keys every block masks.
    qfull = pl.cdiv((ki + 1) * bk, bq) if (causal and t_real >= T) else (
        qmin if t_real >= T else nq)
    qend = nq
    if window:
        # highest q position attending this key block: (ki+1)*bk - 2 +
        # window; blocks above are dead, and every live block masks
        qend = jnp.minimum(nq, ((ki + 1) * bk - 2 + window) // bq + 1)
        qfull = qend

    if not single_k:
        @pl.when(ki == 0)
        def _init():
            dq_ref[...] = jnp.zeros_like(dq_ref)

    def make_body(masked):
        def body(i, carry):
            dk, dv = carry
            q = q_ref[:, pl.ds(i * bq, bq), :]
            do = do_ref[:, pl.ds(i * bq, bq), :]
            lse = lse_ref[:, pl.ds(i * bq, bq), :][..., 0]  # (G, bq)
            if ext_delta:
                # od_ref carries a precomputed (broadcast) delta — the
                # lse-cotangent path folds its shift in outside
                delta = od_ref[:, pl.ds(i * bq, bq), :][..., 0]
            else:
                # od_ref is o: delta = rowsum(do * o), computed on the
                # VPU from blocks already resident — no (BH, T, 128)
                # broadcast materialization, no separate reduce pass
                ob = od_ref[:, pl.ds(i * bq, bq), :]
                delta = jnp.sum(do.astype(jnp.float32)
                                * ob.astype(jnp.float32), axis=-1)
            s = jax.lax.dot_general(q, kb, _DN_QK,
                                    preferred_element_type=jnp.float32)
            if scale != 1.0:
                s = s * scale
            if bias_cfgs:
                s = _bwd_bias_add(s, bias_refs, bias_cfgs, i, bq)
            if alibi_cfg:
                apb = apos_ref[:, pl.ds(i * bq, bq), :]
                s = _alibi_add(s, alibi_cfg, apb, gi, G)
            if masked:
                s = _apply_mask(s, _mask_block(i * bq, ki * bk, bq, bk,
                                               causal, t_real, T,
                                               window))
            p = jnp.exp(s - lse[..., None])                 # (G, bq, bk) f32
            pb = p.astype(do.dtype)
            dv = dv + jax.lax.dot_general(pb, do, _DN_T,
                                          preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, vb, _DN_QK,
                                     preferred_element_type=jnp.float32)
            ds_f = p * (dp - delta[..., None])
            ds = ds_f.astype(q.dtype)
            if dbias_refs:
                # d(bias) = ds (bias enters s additively, post-scale)
                _dbias_update(dbias_refs, grad_cfgs, ds_f, i, ki, bq, bk)
            dk = dk + jax.lax.dot_general(ds, q, _DN_T,
                                          preferred_element_type=jnp.float32)
            dq_val = jax.lax.dot_general(ds, kb, _DN_PV,
                                         preferred_element_type=jnp.float32)
            if single_k:
                # one key block: each dq slice is written exactly once, so
                # the output can be emitted in the model dtype directly —
                # no fp32 (BH, T, d) HBM buffer + cast copy outside
                dq_ref[:, pl.ds(i * bq, bq), :] = dq_val.astype(dq_ref.dtype)
            else:
                dq_ref[:, pl.ds(i * bq, bq), :] += dq_val
            return dk, dv
        return body

    dk = jnp.zeros(kb.shape, jnp.float32)
    dv = jnp.zeros(vb.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(qmin, qfull, make_body(True), (dk, dv))
    dk, dv = jax.lax.fori_loop(qfull, qend, make_body(False), (dk, dv))
    # ds was computed from unscaled-q dots (scale applied to s post-dot),
    # so dk needs the scale factor once here (dq's lands in the wrapper)
    if scale != 1.0:
        dk = dk * scale
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _dbias_out(biases, bias_cfgs, bk, T_pad, bh, like):
    """(out_specs, out_shapes) for the grad biases' accumulators, and
    the post-call distributor mapping kernel outputs back to a
    per-bias cotangent list (zeros for non-grad biases)."""
    specs, shapes = [], []
    for b, cfg in zip(biases, bias_cfgs):
        if not cfg[_B_GRAD]:
            continue
        if cfg[_B_PER] == 1:
            # persistent accumulator: full (Tq, Tk) block per bias row
            specs.append(pl.BlockSpec(
                (1, b.shape[1], T_pad),
                lambda g, j, c=cfg: (_bias_row(c, bh, g), 0, 0)))
        else:
            specs.append(pl.BlockSpec(
                (cfg[_B_PER], b.shape[1], bk),
                lambda g, j, c=cfg: (_bias_row(c, bh, g), 0, j)))
        shapes.append(_sds(b.shape, jnp.float32, like))
    return specs, shapes


def _scatter_dbias(biases, bias_cfgs, grads):
    """Align kernel dbias outputs with the biases tuple (zeros for
    non-differentiable biases), cast to each bias's dtype."""
    out, it = [], iter(grads)
    for b, cfg in zip(biases, bias_cfgs):
        out.append(next(it).astype(b.dtype) if cfg[_B_GRAD]
                   else jnp.zeros(b.shape, b.dtype))
    return tuple(out)


def _bwd(q, k, v, o, lse_t, do, scale, causal, bq, bk, bh, t_real,
         interpret, dlse=None, window=0, biases=(), bias_cfgs=(),
         alibi_cfg=None):
    BH, T, d = q.shape
    dv = v.shape[-1]        # v, o, dO and dv's width; q, k, dq, dk keep d
    # (BH, T, 1) -> LSE_LANES lanes for the operand block; XLA lowers
    # this to one small relayout/broadcast per layer (~8 ms/step total)
    lse = jnp.broadcast_to(lse_t, (BH, T, LSE_LANES))
    if dlse is not None:
        # lse cotangent shifts delta (see _flash_bwd): precompute the
        # shifted delta outside and broadcast to the operand lanes
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1) - dlse.astype(jnp.float32)
        od = jnp.broadcast_to(delta[..., None], (BH, T, LSE_LANES))
    else:
        # common case (lse output unused): the kernel computes delta
        # from o/do blocks in VMEM — no broadcast materialization
        od = o
    single_k = (T // bk) == 1
    db_specs, db_shapes = _dbias_out(biases, bias_cfgs, bk, T, bh, q)
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, bq=bq, bk=bk, scale=scale,
                          causal=causal, t_real=t_real,
                          ext_delta=dlse is not None, single_k=single_k,
                          window=window, bias_cfgs=bias_cfgs,
                          alibi_cfg=alibi_cfg),
        name="dstpu.kernel.flash_bwd",
        grid=(BH // bh, T // bk),
        in_specs=[
            pl.BlockSpec((bh, T, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((bh, bk, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((bh, bk, dv), lambda b, j: (b, j, 0)),
            pl.BlockSpec((bh, T, dv), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((bh, T, LSE_LANES), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((bh, T, LSE_LANES if dlse is not None else dv),
                         lambda b, j: (b, 0, 0)),
        ] + _bwd_bias_specs(bias_cfgs, biases, bk, T, bh)
          + ([pl.BlockSpec((1, T, bk), lambda b, j: (0, 0, j))]
             if alibi_cfg else []),
        out_specs=[
            pl.BlockSpec((bh, T, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((bh, bk, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((bh, bk, dv), lambda b, j: (b, j, 0)),
        ] + db_specs,
        out_shape=[
            # dq accumulates fp32 across key-block grid steps; with a
            # single key block each slice is written once, so it is
            # emitted in the model dtype with no cast copy
            _sds((BH, T, d), q.dtype if single_k else jnp.float32, q),
            _sds((BH, T, d), q.dtype, q),
            _sds((BH, T, dv), q.dtype, q),
        ] + db_shapes,
        interpret=interpret,
        # q, dO, o (or delta), lse whole; dq whole in float32 unless one
        # key block; the k / dk and the v / dv blocks
        **_vmem_params(*([bh * T * _lanes(w) * q.dtype.itemsize
                          for w in (d, dv, dv)]
                         + [bh * T * 128 * 4]
                         + [bh * T * _lanes(d)
                            * (q.dtype.itemsize if single_k else 4)]
                         + [bh * bk * _lanes(w) * q.dtype.itemsize
                            for w in (d, d, dv, dv)])),
    )(q, k, v, do, lse, od, *biases)
    dq, dk, dv = outs[:3]
    dbiases = _scatter_dbias(biases, bias_cfgs, outs[3:])
    if alibi_cfg:
        # the trailing operand is the shared ALiBi position grid — a
        # constant with no gradient
        dbiases = dbiases + (jnp.zeros(biases[-1].shape,
                                       biases[-1].dtype),)
    if scale != 1.0:
        dq = dq * scale
    return dq.astype(q.dtype), dk, dv, dbiases


# ------------------------------------------------ backward, transposed q/k/v
def _bwd_kernel_t(q_ref, k_ref, v_ref, do_ref, lse_ref, od_ref,
                  *rest, bq, bk, scale, causal, t_real,
                  ext_delta, single_k, window=0, bias_cfgs=(),
                  alibi_cfg=None):
    """Fused backward with q/k/v, do AND dq/dk/dv blocked (G, d, T).

    Same structure as _bwd_kernel (key-block grid, inner loop over query
    blocks, one s/p computation feeding dq+dk+dv), with every seq-major
    tensor consumed/produced T-in-lanes so the surrounding einsums'
    preferred layouts connect via bitcasts, not copies.

    do and o stay in the natural (G, T, d) layout — the forward emits o
    that way and the cotangent arrives the same way — keeping
    delta = rowsum(do * o) a lane reduction (sublane-vector result).
    Measured alternatives at 350M bs=24 (both kept the step SLOWER):
    do consumed (G, d, T) + delta precomputed outside (+8 ms: the
    delta fusion/broadcast outweighs the saved do relayout), and the
    in-kernel softmax identity delta = sum_j p_ij dp_ij (+11 ms VPU in
    an already-VPU-bound kernel). ext_delta (as in _bwd_kernel): False = in-kernel
    rowsum(do * o) with od_ref carrying o; True = precomputed delta via
    od_ref (the lse-cotangent path folds -dlse in outside).

    Biases and dbias accumulators stay in score-space orientation
    (rows, Tq|1, Tk) — identical to _bwd_kernel.
    """
    n_bias = len(bias_cfgs)
    n_in = n_bias + (1 if alibi_cfg else 0)
    bias_refs = rest[:n_bias]
    apos_ref = rest[n_bias] if alibi_cfg else None
    dq_ref, dk_ref, dv_ref = rest[n_in:n_in + 3]
    dbias_refs = rest[n_in + 3:]
    grad_cfgs = tuple(c for c in bias_cfgs if c[_B_GRAD])
    ki = pl.program_id(1)
    gi = pl.program_id(0)
    kb = k_ref[...]                                         # (G, d, bk)
    G = kb.shape[0]
    vb = v_ref[...]
    T = q_ref.shape[2]
    nq = T // bq
    if dbias_refs:
        _dbias_init(dbias_refs, grad_cfgs, G, ki)
    qmin = (ki * bk) // bq if causal else 0
    qfull = pl.cdiv((ki + 1) * bk, bq) if (causal and t_real >= T) else (
        qmin if t_real >= T else nq)
    qend = nq
    if window:
        qend = jnp.minimum(nq, ((ki + 1) * bk - 2 + window) // bq + 1)
        qfull = qend

    if not single_k:
        @pl.when(ki == 0)
        def _init():
            dq_ref[...] = jnp.zeros_like(dq_ref)

    def make_body(masked):
        def body(i, carry):
            dk, dv = carry
            q = q_ref[:, :, pl.ds(i * bq, bq)]              # (G, d, bq)
            do = do_ref[:, pl.ds(i * bq, bq), :]            # (G, bq, d)
            lse = lse_ref[:, pl.ds(i * bq, bq), :][..., 0]  # (G, bq)
            if ext_delta:
                delta = od_ref[:, pl.ds(i * bq, bq), :][..., 0]
            else:
                ob = od_ref[:, pl.ds(i * bq, bq), :]        # (G, bq, d)
                delta = jnp.sum(do.astype(jnp.float32)
                                * ob.astype(jnp.float32), axis=-1)
            s = jax.lax.dot_general(q, kb, _DN_QK_T,
                                    preferred_element_type=jnp.float32)
            if scale != 1.0:
                s = s * scale
            if bias_cfgs:
                s = _bwd_bias_add(s, bias_refs, bias_cfgs, i, bq)
            if alibi_cfg:
                apb = apos_ref[:, pl.ds(i * bq, bq), :]
                s = _alibi_add(s, alibi_cfg, apb, gi, G)
            if masked:
                s = _apply_mask(s, _mask_block(i * bq, ki * bk, bq, bk,
                                               causal, t_real, T,
                                               window))
            p = jnp.exp(s - lse[..., None])                 # (G, bq, bk) f32
            pb = p.astype(do.dtype)
            dv = dv + jax.lax.dot_general(do, pb, _DN_DV_T,
                                          preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, vb, _DN_DO_V,
                                     preferred_element_type=jnp.float32)
            ds_f = p * (dp - delta[..., None])
            ds = ds_f.astype(q.dtype)
            if dbias_refs:
                _dbias_update(dbias_refs, grad_cfgs, ds_f, i, ki, bq, bk)
            dk = dk + jax.lax.dot_general(q, ds, _DN_DK_T,
                                          preferred_element_type=jnp.float32)
            dq_val = jax.lax.dot_general(kb, ds, _DN_DQ_T,
                                         preferred_element_type=jnp.float32)
            if single_k:
                dq_ref[:, :, pl.ds(i * bq, bq)] = dq_val.astype(dq_ref.dtype)
            else:
                dq_ref[:, :, pl.ds(i * bq, bq)] += dq_val
            return dk, dv
        return body

    d = q_ref.shape[1]
    dk = jnp.zeros((G, d, bk), jnp.float32)
    dv = jnp.zeros((G, d, bk), jnp.float32)
    dk, dv = jax.lax.fori_loop(qmin, qfull, make_body(True), (dk, dv))
    dk, dv = jax.lax.fori_loop(qfull, qend, make_body(False), (dk, dv))
    if scale != 1.0:
        dk = dk * scale
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _bwd_kernel_t_qmajor(q_ref, k_ref, v_ref, do_ref, lse_ref, od_ref,
                         dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, bq,
                         bk, scale, causal, t_real, ext_delta, window=0):
    """Fused backward, transposed layout, walked QUERY-major.

    The k-major kernel (_bwd_kernel_t) accumulates dq across grid steps
    in a VMEM-resident fp32 OUTPUT block — which must then round-trip
    HBM in fp32 and pay a cast copy outside. This variant applies the
    same VMEM-resident-accumulation trick to the dkv side instead: the
    grid walks query blocks (the forward's access pattern), dq for each
    block completes in ONE grid step and is written once, directly in
    the model dtype (no fp32 HBM buffer, no cast copy), while dk/dv
    accumulate in fp32 VMEM scratch across the sequential grid and cast
    in the final step's epilogue. delta = rowsum(do * o) is computed
    once per QUERY block (the k-major kernel recomputes it for every
    (q, k) pair when bk < T). Inner-loop bounds are exactly the forward
    kernel's causal/window/padding bounds. Bias operands are not
    supported here — biased paths keep the k-major kernel."""
    qi = pl.program_id(1)
    nq = pl.num_programs(1)
    q = q_ref[...]                                          # (G, d, bq)
    G = q.shape[0]
    kb_all = k_ref
    T = k_ref.shape[2]
    nk = T // bk
    kmax = pl.cdiv((qi + 1) * bq, bk) if causal else nk
    kfull = (qi * bq) // bk if (causal and t_real >= T) else (
        nk if (not causal and t_real >= T) else 0)
    kmin = 0
    if window:
        kmin = jnp.maximum(0, (qi * bq - window + 1) // bk)
        kfull = kmin

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    do = do_ref[...]                                        # (G, bq, d)
    lse = lse_ref[...][..., 0]                              # (G, bq)
    if ext_delta:
        delta = od_ref[...][..., 0]
    else:
        ob = od_ref[...]                                    # (G, bq, d)
        delta = jnp.sum(do.astype(jnp.float32)
                        * ob.astype(jnp.float32), axis=-1)

    def make_body(masked):
        def body(j, dq):
            kb = kb_all[:, :, pl.ds(j * bk, bk)]
            vb = v_ref[:, :, pl.ds(j * bk, bk)]
            s = jax.lax.dot_general(q, kb, _DN_QK_T,
                                    preferred_element_type=jnp.float32)
            if scale != 1.0:
                s = s * scale
            if masked:
                s = _apply_mask(s, _mask_block(qi * bq, j * bk, bq, bk,
                                               causal, t_real, T,
                                               window))
            p = jnp.exp(s - lse[..., None])                 # (G, bq, bk)
            pb = p.astype(do.dtype)
            dv_scr[:, :, pl.ds(j * bk, bk)] += jax.lax.dot_general(
                do, pb, _DN_DV_T, preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, vb, _DN_DO_V,
                                     preferred_element_type=jnp.float32)
            ds_f = p * (dp - delta[..., None])
            ds = ds_f.astype(q.dtype)
            dk_scr[:, :, pl.ds(j * bk, bk)] += jax.lax.dot_general(
                q, ds, _DN_DK_T, preferred_element_type=jnp.float32)
            return dq + jax.lax.dot_general(
                kb, ds, _DN_DQ_T, preferred_element_type=jnp.float32)
        return body

    d = q_ref.shape[1]
    dq = jnp.zeros((G, d, bq), jnp.float32)
    dq = jax.lax.fori_loop(kmin, kfull, make_body(False), dq)
    dq = jax.lax.fori_loop(kfull, kmax, make_body(True), dq)
    if scale != 1.0:
        dq = dq * scale
    dq_ref[...] = dq.astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _flush():
        dk = dk_scr[...]
        if scale != 1.0:
            dk = dk * scale
        dk_ref[...] = dk.astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_t_qmajor(q, k, v, o, lse_t, do, scale, causal, bq, bk, bh,
                  t_real, interpret, dlse=None, window=0):
    BH, d, T = q.shape
    lse = jnp.broadcast_to(lse_t, (BH, T, LSE_LANES))
    if dlse is not None:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1) - dlse.astype(jnp.float32)
        od = jnp.broadcast_to(delta[..., None], (BH, T, LSE_LANES))
    else:
        od = o
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel_t_qmajor, bq=bq, bk=bk, scale=scale,
                          causal=causal, t_real=t_real,
                          ext_delta=dlse is not None, window=window),
        name="dstpu.kernel.flash_bwd_t_qmajor",
        grid=(BH // bh, T // bq),
        in_specs=[
            pl.BlockSpec((bh, d, bq), lambda b, i: (b, 0, i)),
            pl.BlockSpec((bh, d, T), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((bh, d, T), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((bh, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((bh, bq, LSE_LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((bh, bq, LSE_LANES if dlse is not None else d),
                         lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bh, d, bq), lambda b, i: (b, 0, i)),
            pl.BlockSpec((bh, d, T), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((bh, d, T), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            # every output in the model dtype: dq slices are written
            # exactly once (their grid step), dk/dv cast from the fp32
            # VMEM accumulators in the last step's epilogue
            _sds((BH, d, T), q.dtype, q),
            _sds((BH, d, T), q.dtype, q),
            _sds((BH, d, T), q.dtype, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((bh, d, T), jnp.float32),
            pltpu.VMEM((bh, d, T), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, od)
    return dq, dk, dv, ()


def _bwd_t(q, k, v, o, lse_t, do, scale, causal, bq, bk, bh, t_real,
           interpret, dlse=None, window=0, biases=(), bias_cfgs=(),
           alibi_cfg=None):
    BH, d, T = q.shape
    lse = jnp.broadcast_to(lse_t, (BH, T, LSE_LANES))
    single_k = (T // bk) == 1
    if dlse is not None:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1) - dlse.astype(jnp.float32)
        od = jnp.broadcast_to(delta[..., None], (BH, T, LSE_LANES))
    else:
        od = o
    db_specs, db_shapes = _dbias_out(biases, bias_cfgs, bk, T, bh, q)
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel_t, bq=bq, bk=bk, scale=scale,
                          causal=causal, t_real=t_real,
                          ext_delta=dlse is not None, single_k=single_k,
                          window=window, bias_cfgs=bias_cfgs,
                          alibi_cfg=alibi_cfg),
        name="dstpu.kernel.flash_bwd_t",
        grid=(BH // bh, T // bk),
        in_specs=[
            pl.BlockSpec((bh, d, T), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((bh, d, bk), lambda b, j: (b, 0, j)),
            pl.BlockSpec((bh, d, bk), lambda b, j: (b, 0, j)),
            pl.BlockSpec((bh, T, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((bh, T, LSE_LANES), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((bh, T, LSE_LANES if dlse is not None else d),
                         lambda b, j: (b, 0, 0)),
        ] + _bwd_bias_specs(bias_cfgs, biases, bk, T, bh)
          + ([pl.BlockSpec((1, T, bk), lambda b, j: (0, 0, j))]
             if alibi_cfg else []),
        out_specs=[
            pl.BlockSpec((bh, d, T), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((bh, d, bk), lambda b, j: (b, 0, j)),
            pl.BlockSpec((bh, d, bk), lambda b, j: (b, 0, j)),
        ] + db_specs,
        out_shape=[
            _sds((BH, d, T), q.dtype if single_k else jnp.float32, q),
            _sds((BH, d, T), q.dtype, q),
            _sds((BH, d, T), q.dtype, q),
        ] + db_shapes,
        interpret=interpret,
    )(q, k, v, do, lse, od, *biases)
    dq, dk, dv = outs[:3]
    dbiases = _scatter_dbias(biases, bias_cfgs, outs[3:])
    if alibi_cfg:
        # the trailing operand is the shared ALiBi position grid — a
        # constant with no gradient
        dbiases = dbiases + (jnp.zeros(biases[-1].shape,
                                       biases[-1].dtype),)
    if scale != 1.0:
        dq = dq * scale
    return dq.astype(q.dtype), dk, dv, dbiases


# ------------------------------------------------- blockwise (ring) variant
# Carry-in/carry-out blockwise flash step: one (q-chunk, kv-chunk) pair of a
# ring-attention schedule, chaining the running online-softmax state
# (m, l, acc) across chunk pairs instead of combining normalized partial
# outputs outside. The mask mode is STATIC per call — ``causal=True`` is the
# diagonal-causal pair (q and kv chunks share the same global offset),
# ``causal=False`` the fully-visible pair; fully-masked pairs are simply
# never called (sequence/ring.py computes the static schedule). The ring
# backward reuses the existing fused backward kernel per pair with the
# GLOBAL lse/o (``flash_block_bwd``), the standard flash-bwd recompute.

RING_TUNE_DEFAULTS = {"block_q": 128, "block_k": 128, "block_h": 2}


def _fwd_block_kernel(q_ref, k_ref, v_ref, mi_ref, li_ref, acci_ref,
                      mo_ref, lo_ref, acco_ref, *, bq, bk, causal, t_real):
    """_fwd_kernel with the softmax state as operands/results instead of
    locally initialized + finalized: m/l ride (G, bq, LSE_LANES) blocks
    (lane-replicated like lse), acc a (G, bq, d) fp32 block."""
    qi = pl.program_id(1)
    q = q_ref[...]                                        # (G, bq, d)
    G = q.shape[0]
    T = k_ref.shape[1]
    nk = T // bk
    kmax = pl.cdiv((qi + 1) * bq, bk) if causal else nk
    kfull = (qi * bq) // bk if (causal and t_real >= T) else (
        nk if (not causal and t_real >= T) else 0)
    m = mi_ref[...][..., 0]                               # (G, bq) f32
    l = li_ref[...][..., 0]
    acc = acci_ref[...]                                   # (G, bq, d) f32

    def make_body(masked):
        def body(j, carry):
            acc, m, l = carry
            kb = k_ref[:, pl.ds(j * bk, bk), :]
            vb = v_ref[:, pl.ds(j * bk, bk), :]
            s = jax.lax.dot_general(q, kb, _DN_QK,
                                    preferred_element_type=jnp.float32)
            if masked:
                s = _apply_mask(s, _mask_block(qi * bq, j * bk, bq, bk,
                                               causal, t_real, T))
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jax.lax.dot_general(
                p.astype(vb.dtype), vb, _DN_PV,
                preferred_element_type=jnp.float32)
            return acc, m_new, l
        return body

    carry = jax.lax.fori_loop(0, kfull, make_body(False), (acc, m, l))
    acc, m, l = jax.lax.fori_loop(kfull, kmax, make_body(True), carry)
    acco_ref[...] = acc
    mo_ref[...] = jnp.broadcast_to(m[..., None], (G, bq, mo_ref.shape[-1]))
    lo_ref[...] = jnp.broadcast_to(l[..., None], (G, bq, lo_ref.shape[-1]))


def _block_bh(block_h, BH):
    bh = max(1, min(block_h, BH))
    while BH % bh:
        bh -= 1
    return bh


def _head_pad(d):
    """A head width as the kernels carry it: up to 64, or to a multiple of
    the 128 lanes (``flash_attention_with_lse`` says why)."""
    return _round_up(d, 64) if d <= 64 else _round_up(d, 128)


def _block_pads(T, d, block_q, block_k):
    bq, bk, T_pad = _block_sizes(T, block_q, block_k)
    return bq, bk, T_pad, _head_pad(d)


def flash_block_state(BH, T, d):
    """Fresh (m, l, acc) carry for ``flash_block_fwd``: per-query running
    max/sum-exp ((BH, T) fp32) and the unnormalized output accumulator
    ((BH, T, d) fp32)."""
    return (jnp.full((BH, T), NEG_INF, jnp.float32),
            jnp.zeros((BH, T), jnp.float32),
            jnp.zeros((BH, T, d), jnp.float32))


def flash_block_finalize(state):
    """(m, l, acc) -> (o fp32, lse fp32); call after the last chunk pair."""
    m, l, acc = state
    ls = jnp.clip(l, 1e-30, None)
    return acc / ls[..., None], m + jnp.log(ls)


def flash_block_fwd(q, k, v, state, *, causal=False, block_q=128,
                    block_k=128, block_h=2, interpret=None):
    """One ring chunk pair: q/k/v (BH, T, d) folded operands (q PRE-SCALED
    by the caller — the ring folds the softmax scale once), ``state`` from
    :func:`flash_block_state` (or a previous pair). Returns the updated
    state. ``causal=True`` = the diagonal-causal pair (equal chunk
    lengths, shared offset); fully-masked pairs must be skipped by the
    caller, that is the schedule's job."""
    BH, T, d = q.shape
    if k.shape[1] != T:
        raise ValueError(
            f"flash_block_fwd needs equal chunk lengths, got q {T} vs "
            f"kv {k.shape[1]} (the ring schedule pairs equal chunks)")
    if interpret is None:
        interpret = _interpret_default()
    m, l, acc = state
    bq, bk, T_pad, d_pad = _block_pads(T, d, block_q, block_k)
    bh = _block_bh(block_h, BH)

    def pad3(x):
        if T_pad == T and d_pad == d:
            return x
        return jnp.pad(x, ((0, 0), (0, T_pad - T), (0, d_pad - d)))

    def padl(x, fill):
        x = x if T_pad == T else jnp.pad(
            x, ((0, 0), (0, T_pad - T)), constant_values=fill)
        return jnp.broadcast_to(x[..., None], (BH, T_pad, LSE_LANES))

    grid = (BH // bh, T_pad // bq)
    mo, lo, acco = pl.pallas_call(
        functools.partial(_fwd_block_kernel, bq=bq, bk=bk, causal=causal,
                          t_real=T),
        name="dstpu.kernel.flash_block_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bh, bq, d_pad), lambda b, i: (b, i, 0)),
            pl.BlockSpec((bh, T_pad, d_pad), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((bh, T_pad, d_pad), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((bh, bq, LSE_LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((bh, bq, LSE_LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((bh, bq, d_pad), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bh, bq, LSE_LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((bh, bq, LSE_LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((bh, bq, d_pad), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            _sds((BH, T_pad, LSE_LANES), jnp.float32, q),
            _sds((BH, T_pad, LSE_LANES), jnp.float32, q),
            _sds((BH, T_pad, d_pad), jnp.float32, q),
        ],
        interpret=interpret,
    )(pad3(q), pad3(k), pad3(v), padl(m, NEG_INF), padl(l, 0.0),
      pad3(acc))
    return (mo[..., 0][:, :T], lo[..., 0][:, :T], acco[:, :T, :d])


def flash_block_bwd(q, k, v, o, lse, do, *, causal=False, block_q=128,
                    block_k=128, block_h=2, interpret=None):
    """Ring chunk-pair backward via the existing fused backward kernel:
    given the GLOBAL per-query ``lse`` ((BH, T) fp32) and final ``o``, the
    kernel recomputes this pair's probabilities as exp(s - lse) and its
    in-VMEM delta = rowsum(do * o) IS the global delta, so (dq, dk, dv)
    are this pair's exact contributions. q pre-scaled like the forward."""
    BH, T, d = q.shape
    if interpret is None:
        interpret = _interpret_default()
    bq, bk, T_pad, d_pad = _block_pads(T, d, block_q, block_k)
    bh = _block_bh(block_h, BH)

    def pad3(x):
        if T_pad == T and d_pad == d:
            return x.astype(q.dtype) if x.dtype != q.dtype else x
        x = jnp.pad(x, ((0, 0), (0, T_pad - T), (0, d_pad - d)))
        return x.astype(q.dtype) if x.dtype != q.dtype else x

    lse_p = lse if T_pad == T else jnp.pad(lse, ((0, 0), (0, T_pad - T)))
    dq, dk, dv, _ = _bwd(pad3(q), pad3(k), pad3(v), pad3(o), lse_p[..., None],
                         pad3(do), 1.0, causal, bq, bk, bh, T, interpret)
    return dq[:, :T, :d], dk[:, :T, :d], dv[:, :T, :d]


# --------------------------------------------------------------- public API
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14,
                                    15, 16, 17))
def _flash(q, k, v, biases, scale, causal, bq, bk, bh, t_real, interpret,
           bwd_bq, bwd_bk, qkv_t=False, window=0, bias_cfgs=(),
           alibi_cfg=None, bwd_qmajor=False):
    fwd = _fwd_t if qkv_t else _fwd
    o, lse = fwd(q, k, v, scale, causal, bq, bk, bh, t_real, interpret,
                 window, biases, bias_cfgs, alibi_cfg)
    return o, lse[..., 0]


def _flash_fwd(q, k, v, biases, scale, causal, bq, bk, bh, t_real,
               interpret, bwd_bq, bwd_bk, qkv_t=False, window=0,
               bias_cfgs=(), alibi_cfg=None, bwd_qmajor=False):
    from jax.ad_checkpoint import checkpoint_name
    # symbolic_zeros=True wraps primal args in CustomVJPPrimal
    q, k, v = q.value, k.value, v.value
    biases = tuple(b.value for b in biases)
    fwd = _fwd_t if qkv_t else _fwd
    o, lse = fwd(q, k, v, scale, causal, bq, bk, bh, t_real, interpret,
                 window, biases, bias_cfgs, alibi_cfg)
    # Name o/lse HERE, inside the fwd rule, so the named vars are both
    # the primal outputs and the vjp residuals: under jax.checkpoint a
    # save-policy keeping 'flash_o'/'flash_lse' then satisfies the
    # backward's residual needs (q/k/v recompute from the cheap qkv
    # matmul) WITHOUT re-running this kernel — the remat re-run the
    # whole-block policies otherwise pay (~52 ms/step at 350M bs=24).
    # lse is trimmed to one lane so the saved residual is (BH, T, 1)
    # fp32 (keeping the full LSE_LANES block measured 80 ms/step WORSE
    # at 350M bs=24 — the fatter stacked residual perturbs XLA's
    # scheduling far beyond the ~8 ms relayout it saves).
    lse_t = lse[..., :1]
    o = checkpoint_name(o, "flash_o")
    lse_t = checkpoint_name(lse_t, "flash_lse")
    # q/k/v named as residuals too: the 'save_flash_qkv' policy keeps
    # them, so backward skips the ln1+qkv-projection recompute entirely
    # (at +3x48 MB/layer saved residuals; policies not listing these
    # names behave exactly as before)
    qr = checkpoint_name(q, "flash_q")
    kr = checkpoint_name(k, "flash_k")
    vr = checkpoint_name(v, "flash_v")
    return (o, lse_t[..., 0]), (qr, kr, vr, o, lse_t, biases)


def _flash_bwd(scale, causal, bq, bk, bh, t_real, interpret, bwd_bq,
               bwd_bk, qkv_t, window, bias_cfgs, alibi_cfg, bwd_qmajor,
               res, cts):
    # backward may run its own (smaller) blocks: the fused dq/dk/dv pass
    # is ~2x the forward's work, so causal above-diagonal skipping wins
    # more there than grid-step overhead costs
    bq, bk = bwd_bq or bq, bwd_bk or bk
    do, dlse = cts
    from jax.custom_derivatives import SymbolicZero
    # training drops the lse output -> its cotangent arrives symbolic
    # and the kernel takes the delta-from-o fast path
    if isinstance(dlse, SymbolicZero):
        dlse = None
    if isinstance(do, SymbolicZero):
        do = jnp.zeros(do.shape, do.dtype)
    q, k, v, o, lse_t, biases = res
    # lse is a real (differentiable) output: d lse_i / d s_ij = p_ij, so a
    # cotangent on lse enters the shared ds = p * (dp - delta) term as
    # ds += p * dlse — i.e. exactly a shift of delta by -dlse. Folding it
    # there costs zero extra kernel work.
    if bwd_qmajor and qkv_t and not biases and alibi_cfg is None:
        return _bwd_t_qmajor(
            q, k, v, o, lse_t, do, scale, causal, bq, bk, bh, t_real,
            interpret, dlse=dlse, window=window)
    bwd = _bwd_t if qkv_t else _bwd
    dq, dk, dv, dbiases = bwd(
        q, k, v, o, lse_t, do, scale, causal, bq, bk, bh, t_real,
        interpret, dlse=dlse, window=window, biases=biases,
        bias_cfgs=bias_cfgs, alibi_cfg=alibi_cfg)
    return dq, dk, dv, dbiases


_flash.defvjp(_flash_fwd, _flash_bwd, symbolic_zeros=True)


# o-only variant: training drops lse, but a custom_vjp output cannot be
# DCE'd out of the remat closed-call — the lane-trim slice alone measured
# ~6 ms/step at 350M bs=24. This twin never emits the lse output (the
# residual still saves it for the backward).
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14,
                                    15, 16, 17))
def _flash_o(q, k, v, biases, scale, causal, bq, bk, bh, t_real,
             interpret, bwd_bq, bwd_bk, qkv_t=False, window=0,
             bias_cfgs=(), alibi_cfg=None, bwd_qmajor=False):
    fwd = _fwd_t if qkv_t else _fwd
    o, _ = fwd(q, k, v, scale, causal, bq, bk, bh, t_real, interpret,
               window, biases, bias_cfgs, alibi_cfg)
    return o


def _flash_o_fwd(q, k, v, biases, scale, causal, bq, bk, bh, t_real,
                 interpret, bwd_bq, bwd_bk, qkv_t=False, window=0,
                 bias_cfgs=(), alibi_cfg=None, bwd_qmajor=False):
    (o, _), res = _flash_fwd(q, k, v, biases, scale, causal, bq, bk, bh,
                             t_real, interpret, bwd_bq, bwd_bk, qkv_t,
                             window, bias_cfgs, alibi_cfg, bwd_qmajor)
    return o, res


def _flash_o_bwd(scale, causal, bq, bk, bh, t_real, interpret, bwd_bq,
                 bwd_bk, qkv_t, window, bias_cfgs, alibi_cfg, bwd_qmajor,
                 res, do):
    from jax.custom_derivatives import SymbolicZero
    bq, bk = bwd_bq or bq, bwd_bk or bk
    if isinstance(do, SymbolicZero):
        do = jnp.zeros(do.shape, do.dtype)
    q, k, v, o, lse_t, biases = res
    if bwd_qmajor and qkv_t and not biases and alibi_cfg is None:
        return _bwd_t_qmajor(
            q, k, v, o, lse_t, do, scale, causal, bq, bk, bh, t_real,
            interpret, dlse=None, window=window)
    bwd = _bwd_t if qkv_t else _bwd
    dq, dk, dv, dbiases = bwd(
        q, k, v, o, lse_t, do, scale, causal, bq, bk, bh, t_real,
        interpret, dlse=None, window=window, biases=biases,
        bias_cfgs=bias_cfgs, alibi_cfg=alibi_cfg)
    return dq, dk, dv, dbiases


_flash_o.defvjp(_flash_o_fwd, _flash_o_bwd, symbolic_zeros=True)


def flash_attention_with_lse(q, k, v, *, causal=True, scale=None,
                             block_q=128, block_k=128, block_h=2,
                             interpret=None, heads_major=False,
                             block_q_bwd=None, block_k_bwd=None,
                             qkv_t=False, window=0, bias=None,
                             bias_grad=False, alibi=None,
                             alibi_scale=1.0, alibi_bf16=False,
                             bwd_qmajor=False, _folded_biases=None,
                             _with_lse=True):
    """Fused attention over (batch, seq, heads, head_dim) inputs, returning
    ``(o, lse)`` where lse is the per-query logsumexp, (B, H, T) fp32.

    ``heads_major=True``: inputs/outputs are (batch, heads, seq, head_dim)
    — the kernel's native layout. The fold becomes a pure reshape (no
    transpose), and no T-minor layout pressure propagates into the
    caller's matmuls (XLA otherwise warps the producing matmul's output
    layout to feed the custom call, costing ~2x on its emitter).

    **The values may have a width of their own**: ``v`` (..., dv) beside
    ``q`` and ``k`` (..., d), as MLA's 128 beside 192; ``o`` is then dv
    wide. The kernels carry V, the accumulator, o, dO and dv at
    ``_lanes(dv)`` and q, k, dq, dk at ``_lanes(d)``, so P·V, dP = dO·Vᵀ
    and dV = Pᵀ·dO run over the values' columns and nothing is padded to
    the keys' width or sliced back. Read off the shapes: a call of one
    width is the program it always was, letter for letter
    (``tests/unit/test_deepseek_v3.py``). The standard layout's kernels
    alone take two widths; with ``qkv_t``, ``window``, ``bias`` or
    ``alibi`` such a call raises. Each call is noted in the trace-time
    tally, ``_common.note_call("flash", dv != d)``.

    Additive score biases (counterpart of the reference's bias-taking
    attention kernels — evoformer_attn kernel_forward.h:986 bias1/bias2,
    inference softmax.cu:562 alibi+mask):
      ``bias``: (B|1, H|1, T|1, T) added to the scaled scores before the
        softmax, never expanded to the (B, H, T, T) score shape (kernel
        operands carry only the given dims; the broadcast happens on
        score tiles in VMEM). WITHOUT ``bias_grad=True`` the bias is a
        CONSTANT (stop-gradient): differentiating through it yields
        zeros — set ``bias_grad=True`` for learned biases (evoformer
        pair bias), which makes the fused backward accumulate d_bias
        in-kernel.
      ``alibi``: (H,) ALiBi slopes — validated against the bloom formula
        and computed IN-KERNEL per score tile as slope_h * k_pos from
        iotas (softmax-shift-equivalent to the relative form): no HBM
        bias array at all, like the paged decode kernel.
        ``alibi_scale``/``alibi_bf16`` reproduce HF falcon's pre-scaling
        bf16-quantized variant (models/llama.py _alibi_bias).
      Masked positions (causal/window/padding) override any bias.

    Equivalent math to softmax(scale * q k^T + bias + causal_mask) v with
    fp32 accumulation, O(T) memory. Differentiable (custom flash
    backward). Sequences that don't divide the block sizes are
    zero-padded and the padded keys masked in-kernel (slicing the output
    transposes to zero-padded cotangents, so the backward stays correct).
    ``block_h`` (b, h) instances are processed per grid step (clamped to
    a divisor of batch*heads, and of any dim a bias shares).

    lse is exposed (rather than kept as a hidden vjp residual) so callers
    under ``jax.checkpoint`` can tag o/lse/q/k/v with ``checkpoint_name``
    and a save-policy can keep exactly the flash residuals — making the
    backward reuse them instead of recomputing the forward kernel.
    """
    if qkv_t:
        # transposed operands: (batch, heads, head_dim, seq) — the qkv
        # projection einsum's natural T-minor layout; the kernel consumes
        # it directly so no relayout copies exist at the call boundary
        B, H, d, T = q.shape
    elif heads_major:
        B, H, T, d = q.shape
    else:
        B, T, H, d = q.shape
    dv = v.shape[2 if qkv_t else 3]
    if dv != d and (qkv_t or window or bias is not None or alibi is not None
                    or _folded_biases):
        raise NotImplementedError(
            f"values of their own width ({dv} beside keys of {d}) are the "
            "standard-layout kernels' alone: qkv_t, a window, a bias and "
            "ALiBi take one head width (pad v to the keys' and slice o)")
    if _AUTO in (block_q, block_k, block_h, block_q_bwd, block_k_bwd,
                 bwd_qmajor):
        # measured dispatch: tunables set to "auto" take the cached
        # winner for this (device_kind, shape-bucket, dtype); explicit
        # values always win over the cache, and a miss falls back to
        # the r05-proven defaults. Trace-time only.
        win = _dispatch("flash_attention",
                        _flash_bucket(T, d, causal, qkv_t),
                        _dtype_name(q.dtype), TUNE_DEFAULTS)
        if block_q == _AUTO:
            block_q = int(win["block_q"])
        if block_k == _AUTO:
            block_k = int(win["block_k"])
        if block_h == _AUTO:
            block_h = int(win["block_h"])
        if block_q_bwd == _AUTO:
            block_q_bwd = int(win["block_q_bwd"]) or None
        if block_k_bwd == _AUTO:
            block_k_bwd = int(win["block_k_bwd"]) or None
        if bwd_qmajor == _AUTO:
            bwd_qmajor = bool(win["bwd_qmajor"])
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _interpret_default()
    bq, bk, T_pad = _block_sizes(T, block_q, block_k)
    # backward may use its own blocks; T must pad to a common multiple of
    # ALL block sizes or the backward grid would not cover every key
    # block (silently dropping dk/dv contributions)
    bwd_bq, bwd_bk, _ = _block_sizes(T, block_q_bwd or bq,
                                     block_k_bwd or bk)
    T_pad = _round_up(T, math.lcm(bq, bk, bwd_bq, bwd_bk))
    if qkv_t and any(x % 128 for x in (T_pad, bq, bk, bwd_bq, bwd_bk)):
        # In the transposed layout T (and every block) sits in the LANE
        # dim, which Mosaic requires in 128 units — shapes/blocks that
        # don't comply fall back to the standard kernel (one transpose;
        # correctness over the layout win at tiny T or small blocks)
        q, k, v = (jnp.swapaxes(x, -1, -2) for x in (q, k, v))
        return flash_attention_with_lse(
            q, k, v, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, block_h=block_h, interpret=interpret,
            heads_major=True, block_q_bwd=block_q_bwd,
            block_k_bwd=block_k_bwd, qkv_t=False, window=window,
            bias=bias, bias_grad=bias_grad, alibi=alibi,
            alibi_scale=alibi_scale, alibi_bf16=alibi_bf16,
            bwd_qmajor=False, _folded_biases=_folded_biases,
            _with_lse=_with_lse)

    _note_call("flash", dv != d)
    # -------- bias descriptors -> bh constraints (before bh is picked)
    descs = []                                  # (arr4d, grad)
    alibi_cfg = None
    if alibi is not None:
        # the kernels evaluate the bloom slope formula in-kernel from
        # each instance's head index (_alibi_add); reject custom slopes
        # rather than silently ignoring them (the paged kernel's rule)
        from .paged_attention import alibi_slopes_formula
        expect = alibi_slopes_formula(H)
        got = [float(x) for x in np.asarray(alibi).reshape(-1)] \
            if not isinstance(alibi, (list, tuple)) else list(alibi)
        if len(got) != H or any(
                abs(a - b) > 1e-6 * max(abs(b), 1e-9)
                for a, b in zip(got, expect)):
            raise NotImplementedError(
                "flash_attention computes bloom-formula ALiBi slopes "
                "in-kernel; custom per-head slopes are not supported "
                "(pass them as a bias instead)")
        alibi_cfg = (bool(qkv_t), H, B, float(alibi_scale),
                     bool(alibi_bf16))
    if bias is not None:
        if bias.ndim != 4:
            raise ValueError(
                f"bias must be 4D (B|1, H|1, T|1, T); got {bias.shape}")
        Bb, Hb, Tqb, Tk = bias.shape
        if Bb not in (1, B) or Hb not in (1, H) or Tqb not in (1, T) \
                or Tk != T:
            raise ValueError(
                f"bias shape {bias.shape} not broadcastable to "
                f"({B}, {H}, {T}, {T})")
        descs.append((bias, bool(bias_grad)))
    constraints = [_bias_constraint(a.shape[0], a.shape[1], B, H, qkv_t)
                   for a, _ in descs]
    constraints += [c for _, c, _ in (_folded_biases or [])]
    if descs or _folded_biases or alibi_cfg is not None:
        # bias blocks (and the ALiBi position grid) carry the key dim in
        # LANES; in-kernel dynamic lane offsets must be 128-aligned on
        # Mosaic, so multi-block key walks need 128-multiple key blocks
        # (single-block refs load statically). Fixpoint: rounding one
        # pass's block can grow T_pad and turn the OTHER pass's block
        # multi-block — recheck until both are either single-block or
        # 128-aligned.
        while True:
            T_pad = _round_up(T, math.lcm(bq, bk, bwd_bq, bwd_bk))
            if bk < T_pad and bk % 128:
                bk = _round_up(bk, 128)
            elif bwd_bk < T_pad and bwd_bk % 128:
                bwd_bk = _round_up(bwd_bk, 128)
            else:
                break

    bh = max(1, min(block_h, B * H))
    if alibi_cfg is not None:
        bh = 1          # scalar-slope ALiBi path (see _alibi_add)
    while (B * H) % bh or any(c is not None and c % bh
                              for c in constraints):
        bh -= 1
    # TPU tiling wants the lane (last) dim in 64/128 units: zero-pad other
    # head dims (zero columns add 0 to scores and produce zero output
    # columns, and zero cotangent columns backward — exact). d <= 64 pads
    # to 64, kept native: the smaller DMA footprint beats the MXU's
    # preference for 128 (evoformer's d=32 pays 2x, not 4x). The rule
    # applies under qkv_t too: d moves to sublanes for q/k/v but stays
    # the lane dim of the o output block. Values of their own width, and
    # the output with them, are padded from that width by the same rule.
    d_pad, dv_pad = _head_pad(d), _head_pad(dv)

    def fold(x):
        if qkv_t:
            # flatten (H, B) — not (B, H): XLA lays the qkv einsum output
            # out with b inner of the two (b stride < h stride), so the
            # (H*B) flatten is a free bitcast while (B*H) is an interleave
            # copy (~1 ms/layer/tensor at 350M). The kernel's G dim is
            # order-agnostic.
            x = jnp.swapaxes(x, 0, 1).reshape(H * B, d, T)
            if T_pad != T or d_pad != d:
                x = jnp.pad(x, ((0, 0), (0, d_pad - d), (0, T_pad - T)))
            return x
        if not heads_major:
            x = x.transpose(0, 2, 1, 3)
        w = x.shape[-1]
        w_pad = _head_pad(w)
        x = x.reshape(B * H, T, w)
        if T_pad != T or w_pad != w:
            x = jnp.pad(x, ((0, 0), (0, T_pad - T), (0, w_pad - w)))
        return x

    # -------- fold + pad biases; build their static cfgs
    biases_folded, cfgs = [], []
    for arr, grad in descs:
        Bb, Hb, Tqb, Tk = arr.shape
        if Tqb == 1:
            # Expand query-broadcast biases (key masks, ALiBi) to a full
            # query dim: a size-1 sublane broadcast inside the softmax
            # carry loop crashes Mosaic's layout inference (verified on
            # v5e — see _fwd_bias_specs). Costs (rows, T, T) HBM for
            # what is logically (rows, T); acceptable at mask/ALiBi
            # scales and still far below the dense path's (B, H, T, T)
            # score materialization.
            arr = jnp.broadcast_to(arr, (Bb, Hb, T, Tk))
            Tqb = T
        cfg = _bias_cfg(Bb, Hb, B, H, bh, True, grad, bool(qkv_t))
        if qkv_t and Bb == B and Hb == H:
            arr = arr.swapaxes(0, 1)     # match the kernels' (H, B) fold
        f = arr.reshape(Bb * Hb, Tqb, Tk)
        if Tk != T_pad or Tqb != T_pad:
            f = jnp.pad(f, ((0, 0), (0, T_pad - Tqb),
                            (0, T_pad - Tk)))
        biases_folded.append(f)
        cfgs.append(cfg)
    for arr, _c, cfg_fn in (_folded_biases or []):
        # pre-folded biases (the evoformer adapter): 3D (rows, Tq, Tk),
        # full query dim required (expand upstream — see above)
        cfg = cfg_fn(bh)
        rows, Tqb, Tk = arr.shape
        if Tqb != T:
            raise ValueError(
                f"folded bias must carry a full query dim ({T}); got "
                f"{arr.shape} — expand query-broadcast biases upstream")
        if Tk != T_pad or Tqb != T_pad:
            arr = jnp.pad(arr, ((0, 0), (0, T_pad - Tqb),
                                (0, T_pad - Tk)))
        biases_folded.append(arr)
        cfgs.append(cfg)
    for cfg in cfgs:
        if cfg[_B_GRAD]:
            # grad accumulation relies on the row map visiting each bias
            # block in one contiguous run (per_rows==1) or exactly once
            # (per_rows==bh) — check statically over the real grid
            fs = [_bias_row(cfg, bh, g) for g in range((B * H) // bh)]
            mono = all(a <= b for a, b in zip(fs, fs[1:]))
            if cfg[_B_PER] != 1:
                mono = mono and len(set(fs)) == len(fs)
            if not mono:
                raise ValueError(
                    "bias_grad unsupported for this broadcast pattern "
                    "(bias rows revisited non-contiguously across the "
                    "grid); materialize the bias per (batch, head) "
                    "instead")

    if alibi_cfg is not None:
        # shared ALiBi position grid P[i, j] = j, one O(T^2) f32 array
        # for ALL (batch, head) instances — the kernels scale it by the
        # per-instance slope in VMEM (see _alibi_add)
        biases_folded.append(jnp.broadcast_to(
            jnp.arange(T_pad, dtype=jnp.float32)[None, :],
            (T_pad, T_pad))[None])

    # fold the softmax scale into q OUTSIDE the kernel (and the custom_vjp,
    # so autodiff chains dq): one (BH, T, d) multiply instead of a
    # per-score-element multiply inside a VPU-bound kernel
    if window and not causal:
        raise ValueError("sliding window requires causal attention")
    q = q * jnp.asarray(scale, q.dtype)
    # q-major backward: transposed-operand, bias-free paths only (the
    # biased kernels need the k-major dbias accumulation structure)
    qmaj = bool(bwd_qmajor) and bool(qkv_t) and not biases_folded \
        and alibi_cfg is None
    args = (fold(q), fold(k), fold(v), tuple(biases_folded), 1.0,
            bool(causal), bq, bk, bh, T, bool(interpret), bwd_bq, bwd_bk,
            bool(qkv_t), int(window), tuple(cfgs), alibi_cfg, qmaj)
    if _with_lse:
        o, lse = _flash(*args)
    else:
        # o-only twin: a custom_vjp output can't be DCE'd out of the
        # remat closed-call, so the dropped lse (and its lane-trim
        # slice, ~6 ms/step at 350M) must never be emitted at all
        o, lse = _flash_o(*args), None
    if T_pad != T or dv_pad != dv:
        o = o[:, :T, :dv]
        lse = lse[:, :T] if lse is not None else None
    if qkv_t:
        # (H, B, ...) is the kernel's fold order; swap back to the
        # conventional (B, H, ...). (Exposing the (H, B, ...) form to the
        # caller measured neutral at 350M: it removes this interleave
        # copy but the hbte wo einsum pays it back in a worse emitter.)
        o = o.reshape(H, B, T, d).swapaxes(0, 1)
        return o, (lse.reshape(H, B, T).swapaxes(0, 1)
                   if lse is not None else None)
    o = o.reshape(B, H, T, dv)
    if not heads_major:
        o = o.transpose(0, 2, 1, 3)
    return o, lse.reshape(B, H, T) if lse is not None else None


def flash_attention(q, k, v, *, causal=True, scale=None, block_q=128,
                    block_k=128, block_h=2, interpret=None,
                    heads_major=False, block_q_bwd=None,
                    block_k_bwd=None, qkv_t=False, window=0, bias=None,
                    bias_grad=False, alibi=None, alibi_scale=1.0,
                    alibi_bf16=False, bwd_qmajor=False,
                    _folded_biases=None):
    """Fused attention over (batch, seq, heads, head_dim); see
    :func:`flash_attention_with_lse` (this never emits the lse output).
    ``window`` > 0 = mistral sliding-window attention (causal only);
    ``bias``/``alibi`` = additive score biases (ALiBi, padding masks,
    pair biases) applied in-kernel. ``bwd_qmajor``: query-major fused
    backward (dq written once in the model dtype, dk/dv VMEM-resident;
    qkv_t bias-free paths only — silently k-major otherwise)."""
    o, _ = flash_attention_with_lse(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, block_h=block_h, interpret=interpret,
        heads_major=heads_major, block_q_bwd=block_q_bwd,
        block_k_bwd=block_k_bwd, qkv_t=qkv_t, window=window, bias=bias,
        bias_grad=bias_grad, alibi=alibi, alibi_scale=alibi_scale,
        alibi_bf16=alibi_bf16, bwd_qmajor=bwd_qmajor,
        _folded_biases=_folded_biases, _with_lse=False)
    return o


def attention_reference(q, k, v, *, causal=True, scale=None, bias=None):
    """Dense reference used by parity tests (same fp32 score math).
    ``bias``: (B|1, H|1, T|1, T) additive, pre-mask."""
    B, T, H, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bthd,bshd->bhts", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), jnp.bool_))
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p.astype(q.dtype), v)
