"""Layout-owning MLP projection matmul as Pallas TPU kernels.

Counterpart of the reference's epilogue-fusing GEMM wrappers
(``csrc/transformer/cublas_wrappers.cu`` + ``general_kernels.cu`` — the
GPU path earns its throughput by fusing what stock cuBLAS + eltwise
passes would materialize). The TPU-shape of the same problem is LAYOUT,
not epilogue math: at GPT-2 MLP shapes the qkv/attention tier emits
T-minor activations (T in lanes — hd=64 fills only half a 128-lane
register, so XLA propagates T-in-lanes pressure through the block
carry), and XLA's emitter for the down-projection under that layout
(``EmitOutputBatchInLanesKernelOutputFeatureInLanes``) runs the matmul
at roughly half rate — a measured ~13 ms/step at the 350M bench point —
while the backward pays transpose/cast copies re-laying the cotangents.

These kernels own both boundaries end to end:

  * the forward accepts the activation in EITHER orientation — (B, T, K)
    row-major, or (B, K, T) with T in lanes (the layout the surrounding
    einsums naturally emit; ``x_t=True``) — and emits the output in
    either orientation (``out_t``) with fp32 accumulation, so no
    relayout copy exists on either side of the projection;
  * the backward dx kernel emits the activation cotangent directly in
    the activation's own orientation (the transpose XLA would otherwise
    insert as a copy is the kernel's output indexing), and the dw kernel
    accumulates fp32 across the (batch, token) grid and casts to the
    weight dtype in its epilogue (no fp32 (K, M) HBM buffer + cast
    copy).

Off-TPU the kernels run in Pallas interpreter mode (unit tests); shapes
whose blocks cannot satisfy the TPU tiling rules fall back to a jnp
einsum with identical math (fp32 accumulation, output-dtype round).

The layout/epilogue choice itself (XLA einsums vs 'down' vs 'both',
fused-vs-XLA dw, tile sizes) is a MODEL-level decision and is
autotunable: ``models/gpt2.py`` resolves ``cfg.mlp_kernel="auto"``
against the persistent winner cache via the measured-dispatch layer
(``_common.dispatch``, registry op ``"mlp_matmul"`` in
``autotuning/kernel_registry.py``) and passes the winning mode and
block sizes into this module explicitly.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_default as _interpret_default
from ._common import sds as _sds


def _pick_block(dim, want, lane):
    """Largest divisor of ``dim`` that is <= want and tile-aligned
    (lane dims in 128 units, sublane dims in 8); ``dim`` itself (a
    single full block) is always acceptable. None = no valid block."""
    if dim <= want:
        return dim
    unit = 128 if lane else 8
    b = (want // unit) * unit
    while b >= unit:
        if dim % b == 0:
            return b
        b -= unit
    return None


# --------------------------------------------------------------- forward/dx
def _mm_kernel(a_ref, b_ref, o_ref, acc, *, a_t, b_t, out_t, nk):
    """One (n, m) output block: acc (f32) += a_blk . b_blk over the k
    grid (k innermost); write-out (cast to o dtype) at the last k step."""
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    a = a_ref[0]                       # (bn, bk) | (bk, bn) when a_t
    b = b_ref[...]                     # (bk, bm) | (bm, bk) when b_t
    ca = 0 if a_t else 1               # a's contract dim
    cb = 1 if b_t else 0               # b's contract dim
    if out_t:                          # (bm, bn) = b . a
        acc[...] += lax.dot_general(
            b, a, (((cb,), (ca,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:                              # (bn, bm) = a . b
        acc[...] += lax.dot_general(
            a, b, (((ca,), (cb,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _flush():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def _mm(a, b, *, a_t, b_t, out_t, bn, bm, bk, out_dtype, interpret):
    """Batched ``out[p, n, m] = sum_k a_log[p, n, k] * b_log[k, m]``.

    a: (P, N, K) (or (P, K, N) when ``a_t``); b: (K, M) (or (M, K) when
    ``b_t``); out: (P, N, M) (or (P, M, N) when ``out_t``). fp32
    accumulation, output cast in the kernel epilogue.
    """
    P = a.shape[0]
    if a_t:
        K, N = a.shape[1], a.shape[2]
    else:
        N, K = a.shape[1], a.shape[2]
    M = b.shape[0] if b_t else b.shape[1]
    grid = (P, N // bn, M // bm, K // bk)

    a_spec = pl.BlockSpec((1, bk, bn), lambda p, i, j, k: (p, k, i)) \
        if a_t else pl.BlockSpec((1, bn, bk), lambda p, i, j, k: (p, i, k))
    b_spec = pl.BlockSpec((bm, bk), lambda p, i, j, k: (j, k)) \
        if b_t else pl.BlockSpec((bk, bm), lambda p, i, j, k: (k, j))
    o_spec = pl.BlockSpec((1, bm, bn), lambda p, i, j, k: (p, j, i)) \
        if out_t else pl.BlockSpec((1, bn, bm), lambda p, i, j, k: (p, i, j))
    o_shape = (P, M, N) if out_t else (P, N, M)
    acc_shape = (bm, bn) if out_t else (bn, bm)

    return pl.pallas_call(
        functools.partial(_mm_kernel, a_t=a_t, b_t=b_t, out_t=out_t,
                          nk=K // bk),
        name="dstpu.kernel.mlp_mm",
        grid=grid,
        in_specs=[a_spec, b_spec],
        out_specs=o_spec,
        out_shape=_sds(o_shape, out_dtype, a),
        scratch_shapes=[pltpu.VMEM(acc_shape, jnp.float32)],
        interpret=interpret,
    )(a, b)


# --------------------------------------------------------------------- dw
def _dw_kernel(a_ref, g_ref, o_ref, acc, *, a_t, g_t, last_p, last_n):
    """One (bkK, bm) weight-grad block; accumulates f32 over the (p, n)
    grid steps (innermost dims — the output block index is constant
    across them) and casts to the weight dtype at the last step."""
    p = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(jnp.logical_and(p == 0, i == 0))
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    a = a_ref[0]                       # (bn, bkK) | (bkK, bn) when a_t
    g = g_ref[0]                       # (bn, bm)  | (bm, bn)  when g_t
    ca = 1 if a_t else 0               # contract the token dim
    cg = 1 if g_t else 0
    acc[...] += lax.dot_general(
        a, g, (((ca,), (cg,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(p == last_p, i == last_n))
    def _flush():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _dw(a, g, *, a_t, g_t, bkK, bm, bn, out_dtype, interpret):
    """dw[k, m] = sum_{p, n} a_log[p, n, k] * g_log[p, n, m] — the
    weight gradient with fp32 accumulation across the whole (batch,
    token) extent and the cast-to-weight-dtype epilogue fused."""
    P = a.shape[0]
    if a_t:
        K, N = a.shape[1], a.shape[2]
    else:
        N, K = a.shape[1], a.shape[2]
    M = g.shape[1] if g_t else g.shape[2]
    grid = (K // bkK, M // bm, P, N // bn)

    a_spec = pl.BlockSpec((1, bkK, bn), lambda k, j, p, i: (p, k, i)) \
        if a_t else pl.BlockSpec((1, bn, bkK), lambda k, j, p, i: (p, i, k))
    g_spec = pl.BlockSpec((1, bm, bn), lambda k, j, p, i: (p, j, i)) \
        if g_t else pl.BlockSpec((1, bn, bm), lambda k, j, p, i: (p, i, j))

    return pl.pallas_call(
        functools.partial(_dw_kernel, a_t=a_t, g_t=g_t, last_p=P - 1,
                          last_n=N // bn - 1),
        name="dstpu.kernel.mlp_dw",
        grid=grid,
        in_specs=[a_spec, g_spec],
        out_specs=pl.BlockSpec((bkK, bm), lambda k, j, p, i: (k, j)),
        out_shape=_sds((K, M), out_dtype, a),
        scratch_shapes=[pltpu.VMEM((bkK, bm), jnp.float32)],
        interpret=interpret,
    )(a, g)


# -------------------------------------------------------------- jnp fallback
def _ref_proj(x, w, x_t, out_t):
    """jnp reference with the kernels' exact numerics: fp32 accumulation,
    one round to the output dtype."""
    eq = ("bkt,km->b" + ("mt" if out_t else "tm")) if x_t \
        else ("btk,km->b" + ("mt" if out_t else "tm"))
    return jnp.einsum(eq, x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


# ------------------------------------------------------------------ public
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8))
def _proj(x, w, x_t, out_t, bt, bo, bk, fuse_dw, interpret):
    return _mm(x, w, a_t=x_t, b_t=False, out_t=out_t, bn=bt, bm=bo,
               bk=bk, out_dtype=x.dtype, interpret=interpret)


def _proj_fwd(x, w, x_t, out_t, bt, bo, bk, fuse_dw, interpret):
    return _proj(x, w, x_t, out_t, bt, bo, bk, fuse_dw, interpret), (x, w)


def _proj_bwd(x_t, out_t, bt, bo, bk, fuse_dw, interpret, res, dy):
    x, w = res
    K, M = w.shape
    # dx[p, n, k] = sum_m dy[p, n, m] w[k, m]: contract M; emitted
    # straight in x's orientation — the backward transpose XLA inserts
    # on the einsum vjp is this kernel's output indexing instead
    dx = _mm(dy, w, a_t=out_t, b_t=True, out_t=x_t, bn=bt, bm=bk,
             bk=bo, out_dtype=x.dtype, interpret=interpret)
    if fuse_dw:
        dw = _dw(x, dy, a_t=x_t, g_t=out_t, bkK=bk, bm=bo, bn=bt,
                 out_dtype=w.dtype, interpret=interpret)
    else:
        # let XLA own the weight grad: inside the layer scan it fuses
        # this contraction into the grad-stacking DUS at full MXU rate
        # (the round-3 trace finding); the kernel variant exists for
        # points where that fusion does not form
        xe = "bkt" if x_t else "btk"
        ge = "bmt" if out_t else "btm"
        dw = jnp.einsum(f"{xe},{ge}->km", x, dy,
                        preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dw


_proj.defvjp(_proj_fwd, _proj_bwd)


def mlp_matmul(x, w, *, x_t=False, out_t=False, block_t=256,
               block_o=256, block_k=512, fuse_dw=True, interpret=None):
    """Batched projection ``y[b, t, m] = sum_k x[b, t, k] w[k, m]`` with
    kernel-owned operand/output layouts.

    x: (B, T, K), or (B, K, T) with the token dim in lanes when
    ``x_t=True`` (the layout the qkv/MLP einsums naturally emit); w:
    (K, M); returns (B, T, M), or (B, M, T) when ``out_t=True``. fp32
    accumulation, output rounded once to x.dtype (exactly what the MXU
    does for the jnp matmul). Differentiable: dx comes back in x's own
    orientation and dw accumulates fp32 with the weight-dtype cast
    fused (``fuse_dw=False`` leaves dw to XLA — inside a layer scan it
    fuses into the grad-stacking DUS at full rate).

    Shapes whose dims cannot form tile-aligned blocks fall back to a
    jnp einsum with identical math.
    """
    if x.ndim != 3 or w.ndim != 2:
        raise ValueError(
            f"mlp_matmul expects x (B, ., .) and w (K, M); got "
            f"{x.shape} / {w.shape}")
    K = x.shape[1] if x_t else x.shape[2]
    T = x.shape[2] if x_t else x.shape[1]
    if w.shape[0] != K:
        raise ValueError(
            f"contract dim mismatch: x carries K={K}, w is {w.shape}")
    M = w.shape[1]
    if interpret is None:
        interpret = _interpret_default()
    # every dim appears in lanes in at least one of the fwd/dx/dw
    # blocks, so all three use lane-unit (128) granularity unless they
    # are a single full block
    bt = _pick_block(T, block_t, lane=True)
    bo = _pick_block(M, block_o, lane=True)
    bk = _pick_block(K, block_k, lane=True)
    if None in (bt, bo, bk) or min(T, M, K) < 8:
        return _ref_proj(x, w, x_t, out_t)
    return _proj(x, w, bool(x_t), bool(out_t), bt, bo, bk,
                 bool(fuse_dw), bool(interpret))


# ----------------------------------------- weight-only quantized forward
def _unpack_int4_tile(p):
    """(bk//2, bm) packed int4 tile -> (bk, bm) int8 codes (layout in
    ops/pallas/quantization.py: low nibble = even row, high = odd)."""
    lo = jnp.right_shift(jnp.left_shift(p, 4), 4)
    hi = jnp.right_shift(p, 4)
    return jnp.stack([lo, hi], axis=1).reshape(2 * p.shape[0], p.shape[1])


def _mm_wq_kernel(a_ref, b_ref, s_ref, o_ref, acc, *, a_t, out_t, nk,
                  int4):
    """_mm_kernel with a quantized weight operand: the b tile arrives as
    int8 codes (or two-per-byte int4), is widened in VMEM, and the
    per-output-channel scale multiplies the f32 accumulator ONCE in the
    flush epilogue — legal because the scale lives on the non-contracted
    dim, so it commutes with the K accumulation. No dequantized (K, M)
    tensor ever exists in HBM."""
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    a = a_ref[0].astype(jnp.float32)   # (bn, bk) | (bk, bn) when a_t
    b = b_ref[...]                     # (bk, bm) int8 | (bk//2, bm) packed
    if int4:
        b = _unpack_int4_tile(b)
    bf = b.astype(jnp.float32)
    ca = 0 if a_t else 1
    if out_t:                          # (bm, bn) = b . a
        acc[...] += lax.dot_general(
            bf, a, (((0,), (ca,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:                              # (bn, bm) = a . b
        acc[...] += lax.dot_general(
            a, bf, (((ca,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _flush():
        s = s_ref[0]                   # (bm,) per-output-channel scales
        scaled = acc[...] * (s[:, None] if out_t else s[None, :])
        o_ref[0] = scaled.astype(o_ref.dtype)


def _mm_wq(a, q, s, *, a_t, out_t, bn, bm, bk, out_dtype, int4,
           interpret):
    """Quantized-weight _mm: q is int8 (K, M) or packed int4 (K//2, M),
    s is the (1, M) per-output-channel scale."""
    P = a.shape[0]
    if a_t:
        K, N = a.shape[1], a.shape[2]
    else:
        N, K = a.shape[1], a.shape[2]
    M = s.shape[1]
    grid = (P, N // bn, M // bm, K // bk)

    a_spec = pl.BlockSpec((1, bk, bn), lambda p, i, j, k: (p, k, i)) \
        if a_t else pl.BlockSpec((1, bn, bk), lambda p, i, j, k: (p, i, k))
    b_blk = (bk // 2, bm) if int4 else (bk, bm)
    b_spec = pl.BlockSpec(b_blk, lambda p, i, j, k: (k, j))
    s_spec = pl.BlockSpec((1, bm), lambda p, i, j, k: (0, j))
    o_spec = pl.BlockSpec((1, bm, bn), lambda p, i, j, k: (p, j, i)) \
        if out_t else pl.BlockSpec((1, bn, bm), lambda p, i, j, k: (p, i, j))
    o_shape = (P, M, N) if out_t else (P, N, M)
    acc_shape = (bm, bn) if out_t else (bn, bm)

    return pl.pallas_call(
        functools.partial(_mm_wq_kernel, a_t=a_t, out_t=out_t,
                          nk=K // bk, int4=int4),
        name="dstpu.kernel.mlp_mm_wq",
        grid=grid,
        in_specs=[a_spec, b_spec, s_spec],
        out_specs=o_spec,
        out_shape=_sds(o_shape, out_dtype, a),
        scratch_shapes=[pltpu.VMEM(acc_shape, jnp.float32)],
        interpret=interpret,
    )(a, q, s)


def _ref_proj_wq(x, w, x_t, out_t):
    """jnp fallback with the wq kernel's numerics (f32 codes x f32
    activation, one scale multiply, one output round). Materializes the
    dequantized weight for this call only — taken for shapes the tiling
    rules reject (e.g. tiny decode batches)."""
    wf = w.dequant(jnp.float32)
    eq = ("bkt,km->b" + ("mt" if out_t else "tm")) if x_t \
        else ("btk,km->b" + ("mt" if out_t else "tm"))
    return jnp.einsum(eq, x.astype(jnp.float32), wf,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def wq_matmul(x, w, *, x_t=False, out_t=False, block_t=256, block_o=256,
              block_k=512, interpret=None):
    """Forward-only ``x @ dequant(w)`` for a quantized weight operand
    (``Int8Weight`` / ``Int4Weight`` from ``ops/int8_weights.py``):
    int8/int4 weight tiles stream HBM->VMEM, dequant is fused into the
    kernel epilogue (fp32 accumulate, scale-then-cast in the flush).
    Serving-only — no vjp; the training path keeps full-precision
    weights.

    x: (B, T, K) (or 2D (T, K), lifted to B=1); returns (B, T, M)
    honouring ``x_t``/``out_t`` exactly like ``mlp_matmul``.
    """
    from ..int8_weights import Int4Weight, Int8Weight
    if not isinstance(w, (Int8Weight, Int4Weight)):
        raise TypeError(f"wq_matmul needs Int8Weight/Int4Weight, "
                        f"got {type(w).__name__}")
    int4 = isinstance(w, Int4Weight)
    squeeze = x.ndim == 2
    if squeeze:
        x = x[None]
    K = x.shape[1] if x_t else x.shape[2]
    T = x.shape[2] if x_t else x.shape[1]
    M = w.scale.shape[-1]
    if interpret is None:
        interpret = _interpret_default()
    bt = _pick_block(T, block_t, lane=True)
    bo = _pick_block(M, block_o, lane=True)
    bk = _pick_block(K, block_k, lane=True)
    if None in (bt, bo, bk) or min(T, M, K) < 8 or (int4 and bk % 2):
        out = _ref_proj_wq(x, w, x_t, out_t)
    else:
        out = _mm_wq(x, w.q, w.scale.reshape(1, M), a_t=x_t, out_t=out_t,
                     bn=bt, bm=bo, bk=bk, out_dtype=x.dtype, int4=int4,
                     interpret=bool(interpret))
    return out[0] if squeeze else out
