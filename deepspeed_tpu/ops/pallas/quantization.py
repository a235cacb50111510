"""Blockwise int8 quantization kernels (Pallas) + quantized collectives.

Counterpart of reference ``csrc/quantization/`` (pt_binding.cpp:298,
quantize_intX.cu, quant_reduce.cu, swizzled_quantize.cu): symmetric
per-block int8 quant used by ZeRO++ to compress weight all-gathers
(``zero_quantized_weights``, partition_parameters.py:725 CUDAQuantizer)
and gradient reduce-scatters (``zero_quantized_gradients``,
runtime/comm/coalesced_collectives.py:32 all_to_all_quant_reduce).

TPU design: one VPU pass computes per-block absmax scales and the scaled
round in VMEM; the collectives then move int8 (4x fewer bytes over
ICI/DCN) and dequantize on arrival. Off-TPU the same kernels run in
Pallas interpreter mode; `quantize_blockwise(..., use_pallas=False)` is
the jnp reference implementation (bitwise-identical math).

Weight-only serving additions (ISSUE 18):

* ``quantize_channelwise(w, bits=8|4)`` — symmetric per-output-channel
  scales (absmax over the contracted axis -2, /127 for int8, /7 for
  int4). Because the scale lives on the NON-contracted dim, dequant
  commutes with the K-accumulation and can be applied once in a matmul
  kernel's flush epilogue instead of per weight tile.

* int4 packing layout (``pack_int4``/``unpack_int4``): two signed
  4-bit values per int8 byte, packed along the CONTRACTED axis (-2) so
  a (bk, bm) weight tile reads as a contiguous (bk//2, bm) byte tile:

      byte[r, c] = (q[2r+1, c] << 4) | (q[2r, c] & 0xF)

  i.e. even source rows in the low nibble, odd rows in the high
  nibble. Unpacking is two arithmetic shifts — ``(b << 4) >> 4``
  sign-extends the low nibble, ``b >> 4`` the high one — then a
  stack+reshape restores row order. Values are clipped to the
  symmetric range [-7, 7] (-8 is unused) so negation round-trips.
  The contracted axis must be even; callers pad or fall back to int8.

* ``int8_matmul`` — dynamic activationxweight int8 compute (per-row
  activation scales, per-column weight scales, int32 accumulation)
  with a straight-through fp backward, used by the ``mlp_int8`` /
  ``moe_grouped_int8`` autotune candidate levers.
"""

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import interpret_default as _interpret_default
from ._common import round_up as _round_up
from ._common import sds as _sds


QUANT_BLOCK = 2048  # elements per scale block (reference default group size)
# Rows per VMEM tile: 256 x 2048 el x 4 B = 2 MiB input, well under the
# ~16 MiB VMEM budget even with the int8+scale outputs resident.
_TILE_ROWS = 256


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[:].astype(jnp.float32)                  # (blocks, block)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[:] = q.astype(jnp.int8)
    s_ref[:] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[:] = (q_ref[:].astype(jnp.float32) * s_ref[:]).astype(o_ref.dtype)


def _pad_reshape(flat, block):
    n = flat.shape[0]
    nblocks = -(-n // block)
    pad = nblocks * block - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(nblocks, block), pad


def quantize_blockwise(x, block=QUANT_BLOCK, use_pallas=True,
                       interpret=None):
    """x: any-shape float array -> (q int8 (nblocks, block), scales
    (nblocks, 1) f32, meta). Symmetric absmax scaling per block."""
    flat = x.reshape(-1)
    blocked, pad = _pad_reshape(flat, block)
    meta = {"shape": x.shape, "dtype": x.dtype, "pad": pad}
    if interpret is None:
        interpret = _interpret_default()
    if use_pallas:
        # Grid over row tiles so arbitrarily large tensors stream through
        # VMEM (a full ZeRO shard does not fit at once).
        nb = blocked.shape[0]
        rows = min(_TILE_ROWS, nb)
        nbp = _round_up(nb, rows)
        padded = (jnp.pad(blocked, ((0, nbp - nb), (0, 0)))
                  if nbp != nb else blocked)
        q, s = pl.pallas_call(
            _quant_kernel,
            name="dstpu.kernel.quantize",
            grid=(nbp // rows,),
            in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
            out_specs=[
                pl.BlockSpec((rows, block), lambda i: (i, 0)),
                pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                _sds((nbp, block), jnp.int8, padded),
                _sds((nbp, 1), jnp.float32, padded),
            ],
            interpret=interpret,
        )(padded)
        if nbp != nb:
            q, s = q[:nb], s[:nb]
    else:
        xf = blocked.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        s = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        q = jnp.clip(jnp.round(xf / s), -127, 127).astype(jnp.int8)
    return q, s, meta


def dequantize_blockwise(q, s, meta, use_pallas=True, interpret=None):
    """Inverse of quantize_blockwise."""
    if interpret is None:
        interpret = _interpret_default()
    if use_pallas:
        nb, block = q.shape
        rows = min(_TILE_ROWS, nb)
        nbp = _round_up(nb, rows)
        qp = jnp.pad(q, ((0, nbp - nb), (0, 0))) if nbp != nb else q
        sp = jnp.pad(s, ((0, nbp - nb), (0, 0))) if nbp != nb else s
        out = pl.pallas_call(
            _dequant_kernel,
            name="dstpu.kernel.dequantize",
            grid=(nbp // rows,),
            in_specs=[
                pl.BlockSpec((rows, block), lambda i: (i, 0)),
                pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
            out_shape=_sds((nbp, block), meta["dtype"], qp),
            interpret=interpret,
        )(qp, sp)
        if nbp != nb:
            out = out[:nb]
    else:
        out = (q.astype(jnp.float32) * s).astype(meta["dtype"])
    flat = out.reshape(-1)
    if meta["pad"]:
        flat = flat[:flat.shape[0] - meta["pad"]]
    return flat.reshape(meta["shape"])


def quantization_error(x, block=QUANT_BLOCK):
    """Max abs error of a quant/dequant round trip (diagnostics)."""
    q, s, meta = quantize_blockwise(x, block)
    return jnp.max(jnp.abs(dequantize_blockwise(q, s, meta) - x))


# ------------------------------------------- weight-only channel scales
def quantize_channelwise(w, bits=8):
    """Symmetric per-output-channel quantization of a weight
    ``(..., In, Out)``: scale[..., 0, o] = absmax over In of column o
    divided by the code range (127 for int8, 7 for int4).

    Returns ``(q int8 (..., In, Out), scale f32 (..., 1, Out))``. For
    ``bits=4`` the codes stay one-per-byte here; ``pack_int4`` packs
    them two-per-byte (the storage format the fused kernels stream).
    """
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits!r}")
    qmax = 127.0 if bits == 8 else 7.0
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -qmax, qmax).astype(jnp.int8)
    return q, scale


def dequantize_channelwise(q, scale, dtype):
    """Inverse of quantize_channelwise (codes one-per-byte)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def pack_int4(q):
    """Pack int4 codes (int8 storage, values in [-7, 7]) two-per-byte
    along axis -2: ``(..., In, Out) -> (..., In//2, Out)`` with
    ``byte[r] = (q[2r+1] << 4) | (q[2r] & 0xF)``. In must be even."""
    k = q.shape[-2]
    if k % 2:
        raise ValueError(f"int4 pack needs an even contracted dim, got {k}")
    lo = jnp.take(q, jnp.arange(0, k, 2), axis=-2).astype(jnp.uint8)
    hi = jnp.take(q, jnp.arange(1, k, 2), axis=-2).astype(jnp.uint8)
    return ((hi << 4) | (lo & 0xF)).astype(jnp.int8)


def unpack_int4(p):
    """Inverse of pack_int4: ``(..., In//2, Out) -> (..., In, Out)``
    int8 codes, sign-extended by arithmetic shifts."""
    lo = jnp.right_shift(jnp.left_shift(p, 4), 4)
    hi = jnp.right_shift(p, 4)
    stacked = jnp.stack([lo, hi], axis=-2)           # (..., In//2, 2, Out)
    shape = p.shape[:-2] + (2 * p.shape[-2],) + p.shape[-1:]
    return stacked.reshape(shape)


# ---------------------------------------------- dynamic int8 compute
def _rowwise_int8(x):
    """Per-row symmetric int8 codes for an activation ``(..., K)``."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


@jax.custom_vjp
def int8_matmul(x, w):
    """``x (..., K) @ w (K, M)`` computed as int8 x int8 -> int32 with
    per-row activation scales and per-column weight scales (fp32
    rescale at the end). Backward is straight-through in full
    precision, so the lever is usable in training steps and autotune
    make_steps without a bespoke gradient."""
    return _int8_matmul_fwd_val(x, w)


def _int8_matmul_fwd_val(x, w):
    qx, sx = _rowwise_int8(x)
    qw, sw = quantize_channelwise(w, bits=8)          # (K, M) -> (1, M)
    acc = jax.lax.dot_general(
        qx, qw, (((qx.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * sx * sw).astype(x.dtype)


def _int8_matmul_fwd(x, w):
    return _int8_matmul_fwd_val(x, w), (x, w)


def _int8_matmul_bwd(res, dy):
    x, w = res
    dyf = dy.astype(jnp.float32)
    dx = jnp.einsum("...m,km->...k", dyf, w.astype(jnp.float32))
    dw = jnp.einsum("...k,...m->km", x.astype(jnp.float32), dyf)
    return dx.astype(x.dtype), dw.astype(w.dtype)


int8_matmul.defvjp(_int8_matmul_fwd, _int8_matmul_bwd)


@jax.custom_vjp
def grouped_int8_matmul(x, w, group_sizes):
    """Ragged grouped matmul ``x (S, K) x w (E, K, N)`` (rows grouped by
    expert via ``group_sizes``) with int8 x int8 -> int32 compute:
    per-row activation scales, per-(expert, column) weight scales.
    Straight-through fp backward (ragged_dot vjp)."""
    return _gi8_fwd_val(x, w, group_sizes)


def _gi8_fwd_val(x, w, group_sizes):
    qx, sx = _rowwise_int8(x)
    qw, sw = quantize_channelwise(w, bits=8)          # (E,K,N) -> (E,1,N)
    acc = jax.lax.ragged_dot(qx, qw, group_sizes,
                             preferred_element_type=jnp.int32)
    sw_rows = jnp.repeat(sw[:, 0, :], group_sizes, axis=0,
                         total_repeat_length=x.shape[0])
    return (acc.astype(jnp.float32) * sx * sw_rows).astype(x.dtype)


def _gi8_fwd(x, w, group_sizes):
    return _gi8_fwd_val(x, w, group_sizes), (x, w, group_sizes)


def _gi8_bwd(res, dy):
    x, w, group_sizes = res
    _, vjp = jax.vjp(
        lambda a, b: jax.lax.ragged_dot(a, b, group_sizes), x, w)
    dx, dw = vjp(dy.astype(x.dtype))
    return dx, dw, None


grouped_int8_matmul.defvjp(_gi8_fwd, _gi8_bwd)


# ------------------------------------------------- quantized collectives
def quantized_all_gather(x, axis_name, block=QUANT_BLOCK, use_pallas=True):
    """all_gather moving int8+scales instead of full precision — the
    ZeRO++ quantized-weight gather (reference partition_parameters.py:1156
    all_gather_coalesced with quantization). Call inside shard_map.

    Returns the gathered array stacked on a leading axis (like
    lax.all_gather)."""
    q, s, meta = quantize_blockwise(x, block, use_pallas=use_pallas)
    qg = jax.lax.all_gather(q, axis_name)
    sg = jax.lax.all_gather(s, axis_name)
    return jax.vmap(lambda qq, ss: dequantize_blockwise(
        qq, ss, meta, use_pallas=use_pallas))(qg, sg)


def quantized_psum_scatter(x, axis_name, block=QUANT_BLOCK,
                           use_pallas=True):
    """reduce_scatter with int8 transport: quantize per destination piece,
    all_to_all, dequantize, sum locally — the single-hop form of the
    reference's all_to_all_quant_reduce (coalesced_collectives.py:32),
    which exists precisely because int8 cannot be summed over the wire
    without overflow: dequantize-then-reduce per hop. Call inside
    shard_map; returns this rank's reduced piece (shape x.shape[0]//world,
    *x.shape[1:])."""
    world = jax.lax.axis_size(axis_name)
    assert x.shape[0] % world == 0, (
        f"leading dim {x.shape[0]} not divisible by axis size {world}")
    piece_shape = (x.shape[0] // world,) + x.shape[1:]
    piece = x.reshape((world,) + piece_shape)

    def qfn(p):
        q, s, _ = quantize_blockwise(p, block, use_pallas=use_pallas)
        return q, s

    q, s = jax.vmap(qfn)(piece)            # (world, nb, block), (world, nb, 1)
    qx = jax.lax.all_to_all(q, axis_name, 0, 0)
    sx = jax.lax.all_to_all(s, axis_name, 0, 0)
    meta32 = {"shape": piece_shape, "dtype": jnp.float32,
              "pad": q.shape[1] * block - math.prod(piece_shape)}

    def dfn(qq, ss):
        return dequantize_blockwise(qq, ss, meta32, use_pallas=use_pallas)

    deq = jax.vmap(dfn)(qx, sx)            # (world,) + piece_shape, f32
    return jnp.sum(deq, axis=0).astype(x.dtype)
