"""Helpers shared by the model zoo (GPT2, Llama, ...)."""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def resolve_flash(value):
    """Resolve a use_flash_attention config value: "auto" -> pallas flash
    on TPU, dense elsewhere; True/False force."""
    import jax
    if value == "auto":
        return jax.default_backend() == "tpu"
    return bool(value)


def _pieces(x, dtype):
    """float32 x as a stack of pieces of ``dtype`` that sum to it: itself
    where the dtype is its own, else (hi, lo), x to ~16 bits. ``hi`` is
    rounded by ``reduce_precision``: a cast there and back is excess
    precision to the TPU compiler, which takes it out, and ``lo`` with it
    (my chip run, PR 30: the products then saw ``hi`` alone)."""
    if dtype == x.dtype:
        return x[None]
    info = jnp.finfo(dtype)
    hi = lax.reduce_precision(x, info.nexp, info.nmant)
    return jnp.stack([hi, x - hi]).astype(dtype)


def constrain_fn():
    """Sharding constraints are advisory: no-ops without an active mesh
    (single-device tests / eager use) and under fully-manual meshes
    (inside shard_map, e.g. the 1-bit trainer), GSPMD directives
    otherwise."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return lambda x, spec: x
    from jax.sharding import AxisType
    if not any(t == AxisType.Auto for t in mesh.axis_types):
        return lambda x, spec: x
    return lax.with_sharding_constraint


def resolve_remat_policy(name):
    """Model remat_policy name -> jax.checkpoint policy.

    Under ``jax.checkpoint`` inside ``lax.scan`` jax does NOT keep a
    custom_vjp's residuals — a whole-block remat re-runs the flash
    forward kernel in backward. The flash fwd rule therefore names its
    output/residual tensors ('flash_o'/'flash_lse'), and policies that
    save them let the backward reassemble the flash residuals from saved
    o/lse plus recomputed q/k/v (one cheap qkv matmul) with ZERO extra
    flash kernel runs:
      'save_attn'    keep checkpoint_name('attn_out') tensors
      'save_mid'     keep the post-attention residual stream ('attn_mid'):
                     backward recomputes only ln2+MLP, not the attention
                     half (+50 MB/layer at 350M bs=24)
      'save_mid_up'  also keep the MLP pre-activation ('mlp_up'): backward
                     recomputes only layernorms/gelu, no matmuls
                     (+250 MB/layer)
      'save_flash'   'save_mid' + the flash o/lse residuals: no flash
                     fwd re-run in backward (+50 MB/layer over save_mid)
      'save_carry_flash'  keep the block OUTPUT ('block_out') + flash
                     o/lse instead of attn_mid; 'save_both_flash' keeps
                     both. Measured at 350M bs=24: save_flash 751 ms,
                     save_both_flash 752 ms, save_carry_flash 777 ms —
                     'save_flash' is the bench default; the variants
                     stay for other model/batch points.
    """
    named = {
        "save_attn": ("attn_out",),
        "save_mid": ("attn_mid",),
        "save_mid_up": ("attn_mid", "mlp_up"),
        "save_flash": ("attn_mid", "flash_o", "flash_lse"),
        "save_carry_flash": ("block_out", "flash_o", "flash_lse"),
        "save_both_flash": ("block_out", "attn_mid", "flash_o", "flash_lse"),
        "save_flash_up": ("attn_mid", "flash_o", "flash_lse", "mlp_up"),
        # + saved q/k/v kernel operands: no ln1+qkv-projection recompute
        # in backward (+144 MB/layer at 350M bs=24)
        "save_flash_qkv": ("attn_mid", "flash_o", "flash_lse",
                           "flash_q", "flash_k", "flash_v"),
    }
    if name in named:
        return jax.checkpoint_policies.save_only_these_names(*named[name])
    return getattr(jax.checkpoint_policies, name, None)


def next_token_xent(logits, ids):
    """Mean next-token cross entropy from dense (B, T, V) fp32 logits."""
    targets = ids[:, 1:]
    logits = logits[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def _xent_chunks(hidden, targets, chunk):
    """Pad + reshape (B, T, D)/(B, T) into per-chunk scan operands:
    xs (n, B, c, D), ts (n, B, c), valid (n, 1, c)."""
    B, T, D = hidden.shape
    n = -(-T // chunk)
    pad = n * chunk - T
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    valid = (jnp.arange(n * chunk) < T).reshape(n, 1, chunk)
    xs = hidden.reshape(B, n, chunk, D).swapaxes(0, 1)
    ts = targets.reshape(B, n, chunk).swapaxes(0, 1)
    return xs, ts, valid, n


def fused_linear_xent(head_fn, chunk, head_params, hidden, targets):
    """Mean next-token CE over (B, T, D) hidden states with the head's
    gradients computed IN FORWARD (the reference's fused CE plays the
    same trick on GPU; see also Liger-style fused linear cross entropy).

    Because the op's output is a scalar, its backward receives a scalar
    cotangent g — so the forward can compute pre-scaled d_hidden and
    d_head_params via per-chunk ``jax.vjp`` and the backward is just a
    multiply by g. vs. the remat'd chunked path this removes one full
    unembed-matmul pass (the backward logits recompute) and one softmax
    pass; logits never materialize beyond one (B, chunk, V) block.

    Under plain evaluation (no AD) the primal path computes the loss
    only — no gradient work.

    head_fn(head_params, x_chunk) -> fp32 logits must read only the
    leaves present in ``head_params`` (the caller passes the subset of
    the model tree the head touches, so the d_params accumulator is
    head-sized, not model-sized).
    """
    return _fused_xent(head_fn, chunk, head_params, hidden, targets)


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_xent(head_fn, chunk, head_params, hidden, targets):
    B, T, D = hidden.shape
    xs, ts, valid, _ = _xent_chunks(hidden, targets, chunk)

    def body(acc, xtm):
        x, t, m = xtm
        logits = head_fn(head_params, x)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(jnp.where(m, logz - gold, 0.0)), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts, valid))
    return total / (B * T)


def _fused_xent_fwd(head_fn, chunk, head_params, hidden, targets):
    B, T, D = hidden.shape
    xs, ts, valid, n = _xent_chunks(hidden, targets, chunk)
    denom = B * T

    acc0 = (jnp.zeros((), jnp.float32),
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                         head_params))

    def body(carry, xtm):
        acc_loss, acc_hp = carry
        x, t, m = xtm
        logits, vjp = jax.vjp(head_fn, head_params, x)
        logz = jax.nn.logsumexp(logits, axis=-1)            # (B, c) f32
        gold = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        acc_loss = acc_loss + jnp.sum(jnp.where(m, logz - gold, 0.0))
        p = jnp.exp(logits - logz[..., None])
        onehot = t[..., None] == jnp.arange(logits.shape[-1])[None, None]
        d_logits = jnp.where(m[..., None], p - onehot, 0.0) / denom
        if hidden.dtype == jnp.bfloat16:
            # materialize d_logits in bf16: the consuming matmuls
            # truncate fp32 operands to bf16 on the MXU anyway (default
            # precision), so this halves its HBM traffic at zero
            # additional numeric cost. fp32 models keep fp32 exactness.
            d_logits = d_logits.astype(jnp.bfloat16).astype(logits.dtype)
        d_hp, d_x = vjp(d_logits)
        acc_hp = jax.tree.map(lambda a, d: a + d.astype(jnp.float32),
                              acc_hp, d_hp)
        return (acc_loss, acc_hp), d_x

    (total, d_hp), d_xs = lax.scan(body, acc0, (xs, ts, valid))
    d_hidden = d_xs.swapaxes(0, 1).reshape(B, n * chunk, D)[:, :T]
    d_hp = jax.tree.map(lambda d, p: d.astype(p.dtype), d_hp, head_params)
    res = (d_hp, d_hidden.astype(hidden.dtype), targets.shape)
    return total / denom, res


def _fused_xent_bwd(head_fn, chunk, res, g):
    import numpy as np
    d_hp, d_hidden, tshape = res
    scale = lambda t: (g * t.astype(jnp.float32)).astype(t.dtype)
    return (jax.tree.map(scale, d_hp), scale(d_hidden),
            np.zeros(tshape, jax.dtypes.float0))


_fused_xent.defvjp(_fused_xent_fwd, _fused_xent_bwd)


def fused_linear_xent_kernel(norm_fn, chunk, norm_params, w, hidden,
                             targets):
    """``fused_linear_xent`` with the unembed computed by the Pallas
    online-stats kernel (ops/pallas/fused_ce.py): fp32 logits never
    touch HBM — the kernel emits bf16 logits + exact fp32 logz/gold in
    one pass, and d_logits forms from the bf16 copy (identical numerics
    to the MXU's own bf16 operand truncation).

    norm_fn(norm_params, x) -> normed hidden (the pre-unembed final
    norm); w: the (V, D) unembed matrix (tied or not). Head bias is not
    supported here — callers fall back to the generic path."""
    return _fused_xent_k(norm_fn, chunk, norm_params, w, hidden, targets)


def _sharded_unembed_stats(h, w, targets):
    """The Pallas unembed kernel over batch-sharded rows: each device
    scores its own (batch-major) rows against the whole unembed matrix."""
    from ..ops.pallas._common import dividing_axes, shard_kernel
    from ..ops.pallas.fused_ce import unembed_logits_stats
    from ..utils.groups import BATCH_AXES
    rows = dividing_axes(h.shape[0], BATCH_AXES)
    return shard_kernel(
        unembed_logits_stats,
        (P(rows, None), P(None, None), P(rows)),
        (P(rows, None), P(rows), P(rows)))(h, w, targets)


@_partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_xent_k(norm_fn, chunk, norm_params, w, hidden, targets):
    # primal/eval path: loss only, no gradient work
    B, T, D = hidden.shape
    xs, ts, valid, _ = _xent_chunks(hidden, targets, chunk)

    def body(acc, xtm):
        x, t, m = xtm
        h = norm_fn(norm_params, x)
        _, logz, gold = _sharded_unembed_stats(
            h.reshape(-1, D), w, t.reshape(-1))
        per = (logz - gold).reshape(x.shape[0], x.shape[1])
        return acc + jnp.sum(jnp.where(m, per, 0.0)), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32),
                        (xs, ts, valid))
    return total / (B * T)


def _fused_xent_k_fwd(norm_fn, chunk, norm_params, w, hidden, targets):
    B, T, D = hidden.shape
    xs, ts, valid, n = _xent_chunks(hidden, targets, chunk)
    denom = B * T
    V = w.shape[0]

    acc0 = (jnp.zeros((), jnp.float32),
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                         norm_params),
            jnp.zeros(w.shape, jnp.float32))

    def body(carry, xtm):
        acc_loss, acc_np, acc_w = carry
        x, t, m = xtm
        c = x.shape[1]
        h, norm_vjp = jax.vjp(norm_fn, norm_params, x)
        hf = h.reshape(-1, D)
        tf = t.reshape(-1)
        logits, logz, gold = _sharded_unembed_stats(hf, w, tf)
        per = (logz - gold).reshape(x.shape[0], c)
        acc_loss = acc_loss + jnp.sum(jnp.where(m, per, 0.0))
        p = jnp.exp(logits.astype(jnp.float32) - logz[:, None])
        onehot = tf[:, None] == jnp.arange(V)[None]
        mflat = jnp.broadcast_to(m, (x.shape[0], c)).reshape(-1, 1)
        d_logits = (jnp.where(mflat, p - onehot, 0.0) / denom).astype(
            hidden.dtype)
        d_w = jnp.einsum("nv,nd->vd", d_logits, hf,
                         preferred_element_type=jnp.float32)
        d_h = jnp.einsum("nv,vd->nd", d_logits, w,
                         preferred_element_type=jnp.float32).astype(
            hidden.dtype).reshape(h.shape)
        d_np, d_x = norm_vjp(d_h)
        acc_np = jax.tree.map(lambda a, d: a + d.astype(jnp.float32),
                              acc_np, d_np)
        return (acc_loss, acc_np, acc_w + d_w), d_x

    (total, d_np, d_w), d_xs = lax.scan(body, acc0, (xs, ts, valid))
    d_hidden = d_xs.swapaxes(0, 1).reshape(B, n * chunk, D)[:, :T]
    d_np = jax.tree.map(lambda d, p: d.astype(p.dtype), d_np, norm_params)
    res = (d_np, d_w.astype(w.dtype), d_hidden.astype(hidden.dtype),
           targets.shape)
    return total / denom, res


def _fused_xent_k_bwd(norm_fn, chunk, res, g):
    import numpy as np
    d_np, d_w, d_hidden, tshape = res
    scale = lambda t: (g * t.astype(jnp.float32)).astype(t.dtype)
    return (jax.tree.map(scale, d_np), scale(d_w), scale(d_hidden),
            np.zeros(tshape, jax.dtypes.float0))


_fused_xent_k.defvjp(_fused_xent_k_fwd, _fused_xent_k_bwd)


def chunked_softmax_xent(head_fn, params, hidden, targets, chunk):
    """Mean next-token CE over (B, T, D) hidden states computed ``chunk``
    tokens at a time: ``head_fn(params, x_chunk)`` produces fp32 logits
    for just that chunk and remat recomputes them in backward, so peak
    logits memory is (B, chunk, V) instead of (B, T, V). Any T: the
    sequence is zero-padded to a chunk multiple and padded positions are
    masked out of the sum. Exact same value as the dense computation."""
    B, T, D = hidden.shape
    n = -(-T // chunk)
    pad = n * chunk - T
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    valid = (jnp.arange(n * chunk) < T).reshape(n, 1, chunk)  # (n, 1, c)
    xs = hidden.reshape(B, n, chunk, D).swapaxes(0, 1)      # (n, B, c, D)
    ts = targets.reshape(B, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_loss(x, t, m):
        logits = head_fn(params, x)                         # (B, c, V) f32
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(m, logz - gold, 0.0))

    def body(acc, xtm):
        x, t, m = xtm
        return acc + chunk_loss(x, t, m), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32),
                        (xs, ts, valid))
    return total / (B * T)
