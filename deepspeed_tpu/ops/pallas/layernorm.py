"""Fused LayerNorm (forward + backward) as Pallas TPU kernels.

Counterpart of the reference's fused normalize kernels
(csrc/transformer/normalize_kernels.cu:2134 fused bias-add-LN fwd/bwd,
the reason DeepSpeedTransformerLayer exists): LayerNorm expressed as
separate jnp mean/var reductions costs XLA three HBM passes over the
activations forward (mean pass, variance pass, normalize pass) and more
backward. Each kernel here holds a (rows, D) tile in VMEM and makes ONE
pass: forward reads x once and writes y once; backward reads x/dy once,
writes dx once, and accumulates dscale/dbias in a VMEM-resident block
across the sequential TPU grid (no cross-block atomics needed — grid
steps execute in order, unlike the reference's CUDA block reductions).

Statistics (mean/rstd) are NOT saved as residuals: the backward
recomputes them from the x tile it is already reading — pure VPU work,
zero extra HBM traffic, and nothing extra for `jax.checkpoint` inside
`lax.scan` to spill.

All statistics math runs fp32 on the VPU regardless of input dtype.
Off-TPU the kernels run in Pallas interpreter mode (parity tests).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import AUTO as _AUTO
from ._common import dispatch as _dispatch
from ._common import dtype_name as _dtype_name
from ._common import interpret_default as _interpret_default
from ._common import ln_bucket as _ln_bucket
from ._common import round_up as _round_up

# r05-proven hand-set row tiling; the autotune winner cache can override
# it when callers pass block_rows="auto" (the default)
TUNE_DEFAULTS = {"block_rows": 256}


def _resolve_block_rows(block_rows, x):
    """block_rows="auto" -> cached winner for this (rows, D) bucket,
    else the 256 default; explicit ints pass through untouched."""
    if block_rows != _AUTO:
        return block_rows
    win = _dispatch("layernorm",
                    _ln_bucket(math.prod(x.shape[:-1]), x.shape[-1]),
                    _dtype_name(x.dtype), TUNE_DEFAULTS)
    return int(win["block_rows"])


def _ln_fwd_kernel(x_ref, s_ref, b_ref, y_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                    # (R, D)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _ln_bwd_kernel(x_ref, s_ref, dy_ref, dx_ref, ds_ref, db_ref, *, eps):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)                    # (R, D)
    dy = dy_ref[...].astype(jnp.float32)
    D = x.shape[1]
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    g = dy * s_ref[...].astype(jnp.float32)
    mg = jnp.mean(g, axis=1, keepdims=True)
    mgx = jnp.mean(g * xhat, axis=1, keepdims=True)
    dx_ref[...] = (rstd * (g - mg - xhat * mgx)).astype(dx_ref.dtype)
    # dscale/dbias: reduce over ALL rows. The constant-index output block
    # stays resident in VMEM across the sequential grid — initialize on
    # the first step, accumulate on every step.
    @pl.when(i == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
    ds_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _run_fwd(x, scale, bias, eps, br, interpret):
    N, D = x.shape
    return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        name="dstpu.kernel.ln_fwd",
        grid=(N // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        interpret=interpret,
    )(x, scale.reshape(1, D), bias.reshape(1, D))


def _run_bwd(x, scale, dy, eps, br, interpret):
    N, D = x.shape
    dx, ds, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps),
        name="dstpu.kernel.ln_bwd",
        grid=(N // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((br, D), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D), x.dtype),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
        ],
        interpret=interpret,
    )(x, scale.reshape(1, D), dy)
    return dx, ds[0], db[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln(x, scale, bias, eps, br, interpret):
    return _run_fwd(x, scale, bias, eps, br, interpret)


def _ln_fwd(x, scale, bias, eps, br, interpret):
    return _run_fwd(x, scale, bias, eps, br, interpret), (x, scale)


def _ln_bwd(eps, br, interpret, res, dy):
    x, scale = res
    dx, ds, db = _run_bwd(x, scale, dy, eps, br, interpret)
    return dx, ds.astype(scale.dtype), db.astype(scale.dtype)


_ln.defvjp(_ln_fwd, _ln_bwd)


def _ln_jnp(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln_hybrid(x, scale, bias, eps, br, interpret):
    return _ln_jnp(x, scale, bias, eps)


def _ln_hybrid_fwd(x, scale, bias, eps, br, interpret):
    return _ln_jnp(x, scale, bias, eps), (x, scale)


_ln_hybrid.defvjp(_ln_hybrid_fwd, _ln_bwd)


def _row_blocked(x, run, block_rows):
    """Shared scaffolding for one-pass row-blocked kernels over the last
    dim: (..., D) -> reshape (N, D), pad N to the row-block multiple,
    ``run(x2, br)`` produces (N_pad, D), unpad + reshape back.
    D must be lane-tileable (% 128)."""
    D = x.shape[-1]
    if D % 128:
        raise ValueError(f"fused norm kernels need D % 128 == 0, got {D}")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, D)
    N = x2.shape[0]
    br = max(8, min(block_rows, _round_up(N, 8)))
    N_pad = _round_up(N, br)
    if N_pad != N:
        # zero-pad rows OUTSIDE any custom_vjp: sliced-output cotangents
        # arrive zero-padded, so padded rows add 0 to param grads and
        # their dx is dropped by the slice below
        x2 = jnp.pad(x2, ((0, N_pad - N), (0, 0)))
    y = run(x2, br)
    if N_pad != N:
        y = y[:N]
    return y.reshape(*lead, D)


def layernorm_fused_bwd(x, scale, bias, *, eps=1e-5, block_rows=_AUTO,
                        interpret=None):
    """Hybrid LayerNorm: plain-jnp forward (stays fusable with XLA's
    surrounding elementwise ops, leaves layout choices free) + the
    one-pass Pallas backward (dx + VMEM-accumulated dscale/dbias in a
    single read of x/dy). Same numerics as :func:`fused_layernorm`.
    ``block_rows="auto"`` (default) resolves via the autotune winner
    cache, falling back to 256."""
    block_rows = _resolve_block_rows(block_rows, x)
    if interpret is None:
        interpret = _interpret_default()
    return _row_blocked(
        x, lambda x2, br: _ln_hybrid(x2, scale, bias, float(eps), br,
                                     bool(interpret)), block_rows)


def fused_layernorm(x, scale, bias, *, eps=1e-5, block_rows=_AUTO,
                    interpret=None):
    """LayerNorm over the last dim of ``x`` (any leading shape), fp32
    statistics, output in ``x.dtype``. Differentiable (fused one-pass
    backward). Requires the feature dim to be a multiple of 128 (TPU lane
    tiling); callers should fall back to a jnp layernorm otherwise.
    ``block_rows="auto"`` (default) resolves via the autotune winner
    cache, falling back to 256."""
    block_rows = _resolve_block_rows(block_rows, x)
    if interpret is None:
        interpret = _interpret_default()
    return _row_blocked(
        x, lambda x2, br: _ln(x2, scale, bias, float(eps), br,
                              bool(interpret)), block_rows)


# ------------------------------------------------------------------ rmsnorm
def _rms_fwd_kernel(x_ref, s_ref, y_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                    # (R, D)
    var = jnp.mean(x * x, axis=1, keepdims=True)
    y_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(y_ref.dtype)


def fused_rmsnorm(x, scale, *, eps=1e-5, block_rows=256, interpret=None):
    """One-pass RMSNorm Pallas kernel over the last dim (the serving
    models' norm; reference csrc/transformer/inference/csrc/rms_norm.cu).
    Forward-only: like fused_layernorm, XLA's fused jnp form won inside
    real programs on v5e when both were timed, so models default to jnp
    and this kernel documents the measured alternative."""
    if interpret is None:
        interpret = _interpret_default()
    D = x.shape[-1]

    def run(x2, br):
        return pl.pallas_call(
            functools.partial(_rms_fwd_kernel, eps=eps),
            name="dstpu.kernel.rms_fwd",
            grid=(x2.shape[0] // br,),
            in_specs=[
                pl.BlockSpec((br, D), lambda i: (i, 0)),
                pl.BlockSpec((1, D), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
            interpret=interpret,
        )(x2, scale.reshape(1, D))

    return _row_blocked(x, run, block_rows)
