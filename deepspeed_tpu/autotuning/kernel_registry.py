"""Tunable-parameter registry over the Pallas kernels.

One entry per autotunable op. Each entry owns, for a given shape
bucket (the strings built by ``ops/pallas/_common``):

  ``defaults(b)``     the r05-proven hand-set parameters — what dispatch
                      falls back to on a cache miss, and the baseline
                      candidate every search times first
  ``candidates(b)``   the measured search space (curated, not a full
                      grid: each candidate is a lever an earlier
                      round named, so a search run doubles as an A/B)
  ``make_step(b, dtype, params)``
                      -> (step_fn, args): a data-dependent train-shaped
                      step (forward AND backward where the kernel has
                      one) suitable for lax.scan chaining inside ONE
                      jit — per-candidate timing must amortize the
                      host dispatch or it measures that, not the
                      kernel
  ``parity(b, dtype, params)``
                      numerics check of the candidate against the dense
                      reference (raises on mismatch) — run on every
                      winner before it is cached, and re-run by
                      ``benchmarks/kernel_parity.py`` for every cached
                      winner so a stale/wrong cache entry fails loudly

Buckets are exact in variant-gating dims (feature/head/vocab) and
power-of-two in data-volume dims; ``parse_bucket`` recovers the dict.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

# Single source of truth for each op's r05 KERNEL-level defaults is the
# kernel module itself (its TUNE_DEFAULTS — what dispatch falls back to
# on a cache miss); the registry re-exports and extends them with the
# MODEL-level knobs it alone owns (layernorm variant, mlp path), so
# flipping a proven default in ops/ flips the search baseline too.
from ..ops.pallas.flash_attention import RING_TUNE_DEFAULTS as \
    _RING_KERNEL_DEFAULTS
from ..ops.pallas.flash_attention import TUNE_DEFAULTS as FLASH_DEFAULTS
from ..ops.pallas.fused_ce import TUNE_DEFAULTS as CE_DEFAULTS
from ..ops.pallas.grouped_matmul import TUNE_DEFAULTS as \
    MOE_GROUPED_DEFAULTS
from ..ops.pallas.layernorm import TUNE_DEFAULTS as _LN_KERNEL_DEFAULTS

# small perturbation chaining step i's gradients into step i+1's inputs:
# keeps the scan body data-dependent (XLA cannot DCE or reorder the
# repetitions) without drifting activations out of a realistic range
_EPS = 1e-3

_TOL = dict(rtol=5e-2, atol=5e-2)


def parse_bucket(bucket):
    """'T1024,d64,c1,q1' -> {'T': 1024, 'd': 64, 'c': 1, 'q': 1}."""
    out = {}
    for part in bucket.split(","):
        i = 1
        while i < len(part) and not (part[i].isdigit() or part[i] == "-"):
            i += 1
        out[part[:i]] = int(part[i:])
    return out


def _close(a, b, what, tol=_TOL):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               err_msg=what, **tol)


def _dedup(cands):
    seen, out = set(), []
    for c in cands:
        key = tuple(sorted((k, repr(v)) for k, v in c.items()))
        if key not in seen:
            seen.add(key)
            out.append(dict(c))
    return out


# ------------------------------------------------------------------ flash


def _flash_defaults(b):
    return dict(FLASH_DEFAULTS)


def _flash_candidates(b):
    """The round-6 lever set: full-T blocks + block_h=1 (the measured
    r05 headline config), the 128/256 tilings, 512-wide backward
    blocks, and the q-major fused backward on qkv_t layouts."""
    T, qkv_t = b["T"], bool(b["q"])
    full = min(T, 1024)
    cands = [dict(FLASH_DEFAULTS)]
    cands.append(dict(FLASH_DEFAULTS, block_q=full, block_k=full,
                      block_h=1))
    cands.append(dict(FLASH_DEFAULTS, block_q=min(256, T),
                      block_k=min(256, T), block_h=1))
    if T > 512:
        cands.append(dict(FLASH_DEFAULTS, block_q=full, block_k=full,
                          block_h=1, block_q_bwd=512, block_k_bwd=512))
    if qkv_t:
        cands.append(dict(FLASH_DEFAULTS, block_q=full, block_k=full,
                          block_h=1, bwd_qmajor=True))
        if T > 512:
            cands.append(dict(FLASH_DEFAULTS, block_q=full, block_k=full,
                              block_h=1, block_q_bwd=512,
                              block_k_bwd=512, bwd_qmajor=True))
    return _dedup(cands)


def _flash_shapes(b):
    # representative (batch, heads): enough instances that block_h=2
    # divides, small enough that a search step stays cheap
    B, H = 2, 2
    return B, H, b["T"], b["d"]


def _flash_fn(b, params):
    from ..ops.pallas.flash_attention import flash_attention
    causal, qkv_t = bool(b["c"]), bool(b["q"])

    def f(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, qkv_t=qkv_t,
            heads_major=not qkv_t,
            block_q=int(params["block_q"]),
            block_k=int(params["block_k"]),
            block_h=int(params["block_h"]),
            block_q_bwd=int(params["block_q_bwd"]) or None,
            block_k_bwd=int(params["block_k_bwd"]) or None,
            bwd_qmajor=bool(params["bwd_qmajor"]))
    return f


def _flash_args(b, dtype, rng):
    B, H, T, d = _flash_shapes(b)
    shape = (B, H, d, T) if b["q"] else (B, H, T, d)
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def _flash_step(b, dtype, params):
    f = _flash_fn(b, params)

    def loss(q, k, v):
        return jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)

    g = jax.grad(loss, (0, 1, 2))

    def step(carry):
        q, k, v = carry
        dq, dk, dv = g(q, k, v)
        return (q + _EPS * dq.astype(q.dtype),
                k + _EPS * dk.astype(k.dtype),
                v + _EPS * dv.astype(v.dtype))

    return step, _flash_args(b, dtype, jax.random.key(0))


def _flash_parity(b, dtype, params):
    from ..ops.pallas.flash_attention import attention_reference
    bp = dict(b, T=min(b["T"], 1024))    # cap parity cost; blocks clamp
    q, k, v = _flash_args(bp, dtype, jax.random.key(1))
    f = _flash_fn(bp, params)
    causal = bool(bp["c"])

    if bp["q"]:
        to_std = lambda x: x.transpose(0, 3, 1, 2)   # (B,H,d,T)->(B,T,H,d)
        from_std = lambda x: x.transpose(0, 2, 1, 3)  # ->(B,H,T,d)
    else:
        to_std = lambda x: x.swapaxes(1, 2)
        from_std = lambda x: x.swapaxes(1, 2)

    def ref(q, k, v):
        return from_std(attention_reference(
            to_std(q), to_std(k), to_std(v), causal=causal))

    do = jax.random.normal(jax.random.key(2),
                           jax.eval_shape(ref, q, k, v).shape, dtype)
    of, pull_f = jax.vjp(f, q, k, v)
    orf, pull_r = jax.vjp(ref, q, k, v)
    _close(of, orf, f"flash tuned fwd {params}")
    for a, bb, n in zip(pull_f(do), pull_r(do), "qkv"):
        _close(a, bb, f"flash tuned d{n} {params}")


# ------------------------------------------------------------------- mlp
MLP_DEFAULTS = {"mode": "xla", "fuse_dw": True,
                "block_t": 256, "block_o": 256, "block_k": 512}


def _mlp_defaults(b):
    return dict(MLP_DEFAULTS)


def _mlp_candidates(b):
    """Layout/epilogue choice for the MLP projections: XLA einsums
    (r05 default), the layout-owning down-projection kernel, both
    projections kernel-owned, and the fused-vs-XLA dw epilogue."""
    cands = [dict(MLP_DEFAULTS)]
    for mode in ("down", "both"):
        cands.append(dict(MLP_DEFAULTS, mode=mode))
        cands.append(dict(MLP_DEFAULTS, mode=mode, fuse_dw=False))
    cands.append(dict(MLP_DEFAULTS, mode="down", block_t=512,
                      block_o=512))
    return _dedup(cands)


def _mlp_fn(b, params):
    mode = params["mode"]

    def f(h, wu, wd):
        if mode == "xla":
            u = h @ wu
            out = jax.nn.gelu(u) @ wd
            return out
        from ..ops.pallas.mlp_matmul import mlp_matmul
        kw = dict(fuse_dw=bool(params["fuse_dw"]),
                  block_t=int(params["block_t"]),
                  block_o=int(params["block_o"]),
                  block_k=int(params["block_k"]))
        if mode == "both":
            u = mlp_matmul(h, wu, out_t=True, **kw)
        else:
            u = jnp.einsum("btd,df->bft", h, wu)
        up = jax.nn.gelu(u)
        return mlp_matmul(up, wd, x_t=True, **kw)
    return f


def _mlp_args(b, dtype, rng):
    T, D, F = min(b["T"], 512), b["D"], b["F"]
    ks = jax.random.split(rng, 3)
    h = jax.random.normal(ks[0], (2, T, D), dtype)
    wu = jax.random.normal(ks[1], (D, F), dtype) * (1 / math.sqrt(D))
    wd = jax.random.normal(ks[2], (F, D), dtype) * (1 / math.sqrt(F))
    return h, wu, wd


def _mlp_step(b, dtype, params):
    f = _mlp_fn(b, params)

    def loss(h, wu, wd):
        return jnp.sum(f(h, wu, wd).astype(jnp.float32) ** 2)

    g = jax.grad(loss, (0, 1, 2))

    def step(carry):
        h, wu, wd = carry
        dh, dwu, dwd = g(h, wu, wd)
        return (h + _EPS * dh.astype(h.dtype),
                wu + _EPS * dwu.astype(wu.dtype),
                wd + _EPS * dwd.astype(wd.dtype))

    return step, _mlp_args(b, dtype, jax.random.key(0))


def _mlp_parity(b, dtype, params):
    h, wu, wd = _mlp_args(b, dtype, jax.random.key(1))
    f = _mlp_fn(b, params)
    ref = _mlp_fn(b, dict(params, mode="xla"))
    _close(f(h, wu, wd), ref(h, wu, wd), f"mlp tuned fwd {params}")

    def lf(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    ga = jax.grad(lf(f), (0, 1, 2))(h, wu, wd)
    gr = jax.grad(lf(ref), (0, 1, 2))(h, wu, wd)
    for a, bb, n in zip(ga, gr, ("dh", "dwu", "dwd")):
        _close(a, bb, f"mlp tuned {n} {params}",
               dict(rtol=5e-2, atol=5e-1 if n != "dh" else 5e-2))


# ------------------------------------------------------------ mlp int8
# W8A8 dense-MLP compute lever (quantize.int8_matmul="auto"): both
# projections through ops/pallas/quantization.int8_matmul — dynamic
# rowwise activation codes x channelwise weight codes, int32
# accumulate, straight-through fp grads. The {int8: 0} default IS the
# exact fp program (cold-cache contract); a measured winner flipping to
# 1 must first survive the parity gate below, so the cache can never
# hold an int8 winner whose numerics drifted past the gate.

MLP_INT8_DEFAULTS = {"int8": 0}

# quantization error tolerance for the W8A8 gate: symmetric 8-bit codes
# carry ~0.4% rms error per operand; through two projections + gelu the
# forward drifts ~1-2%, and the straight-through weight grads (up^T dy,
# where 'up' came through the quantized forward) reach O(60) magnitude
# in these step shapes with a few-per-mille tail at ~5% elementwise
# drift. The gate exists to catch BROKEN numerics (wrong scales, sign
# flips, garbage tiles — errors of order the activations themselves),
# not to bound the quantization envelope, so the grad term is wide.
_INT8_FWD_TOL = dict(rtol=1e-1, atol=1e-1)
_INT8_GRAD_TOL = dict(rtol=2e-1, atol=4.0)


def _mlp8_defaults(b):
    return dict(MLP_INT8_DEFAULTS)


def _mlp8_candidates(b):
    return _dedup([dict(MLP_INT8_DEFAULTS), {"int8": 1}])


def _mlp8_fn(params):
    use8 = bool(params["int8"])

    def f(h, wu, wd):
        if use8:
            from ..ops.pallas.quantization import int8_matmul
            u = int8_matmul(h, wu)
            return int8_matmul(jax.nn.gelu(u), wd)
        return jax.nn.gelu(h @ wu) @ wd
    return f


def _mlp8_step(b, dtype, params):
    f = _mlp8_fn(params)

    def loss(h, wu, wd):
        return jnp.sum(f(h, wu, wd).astype(jnp.float32) ** 2)

    g = jax.grad(loss, (0, 1, 2))

    def step(carry):
        h, wu, wd = carry
        dh, dwu, dwd = g(h, wu, wd)
        return (h + _EPS * dh.astype(h.dtype),
                wu + _EPS * dwu.astype(wu.dtype),
                wd + _EPS * dwd.astype(wd.dtype))

    return step, _mlp_args(b, dtype, jax.random.key(0))


def _mlp8_parity(b, dtype, params):
    h, wu, wd = _mlp_args(b, dtype, jax.random.key(1))
    f = _mlp8_fn(params)
    ref = _mlp8_fn(MLP_INT8_DEFAULTS)
    exact = not params["int8"]
    _close(f(h, wu, wd), ref(h, wu, wd), f"mlp_int8 fwd {params}",
           _TOL if exact else _INT8_FWD_TOL)

    def lf(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    ga = jax.grad(lf(f), (0, 1, 2))(h, wu, wd)
    gr = jax.grad(lf(ref), (0, 1, 2))(h, wu, wd)
    for a, bb, n in zip(ga, gr, ("dh", "dwu", "dwd")):
        _close(a, bb, f"mlp_int8 {n} {params}",
               _TOL if exact else _INT8_GRAD_TOL)


# ------------------------------------------------------------- layernorm
# 'jnp' is the r05-proven model-level choice (fused_layernorm=False:
# XLA's fused form wins inside real programs on v5e)
LN_DEFAULTS = {"variant": "jnp", **_LN_KERNEL_DEFAULTS}


def _ln_defaults(b):
    return dict(LN_DEFAULTS)


def _ln_candidates(b):
    """jnp (XLA-fused, the measured r05 winner inside real programs) vs
    the fully fused Pallas kernel vs the hybrid jnp-fwd/Pallas-bwd, at
    the row tilings the row-blocked scaffold accepts."""
    cands = [dict(LN_DEFAULTS)]
    if b["D"] % 128 == 0:
        for br in (128, 256, 512):
            cands.append({"variant": "fused", "block_rows": br})
        cands.append({"variant": "bwd", "block_rows": 256})
    return _dedup(cands)


def _ln_fn(b, params):
    variant = params["variant"]

    def f(x, s, bias):
        if variant == "fused":
            from ..ops.pallas.layernorm import fused_layernorm
            return fused_layernorm(x, s, bias,
                                   block_rows=int(params["block_rows"]))
        if variant == "bwd":
            from ..ops.pallas.layernorm import layernorm_fused_bwd
            return layernorm_fused_bwd(
                x, s, bias, block_rows=int(params["block_rows"]))
        from ..ops.pallas.layernorm import _ln_jnp
        return _ln_jnp(x, s, bias, 1e-5)
    return f


def _ln_args(b, dtype, rng):
    R, D = min(b["R"], 4096), b["D"]
    ks = jax.random.split(rng, 3)
    x = jax.random.normal(ks[0], (R, D), dtype)
    s = 1 + 0.1 * jax.random.normal(ks[1], (D,), dtype)
    bias = 0.1 * jax.random.normal(ks[2], (D,), dtype)
    return x, s.astype(dtype), bias.astype(dtype)


def _ln_step(b, dtype, params):
    f = _ln_fn(b, params)

    def loss(x, s, bias):
        return jnp.sum(f(x, s, bias).astype(jnp.float32) ** 2)

    g = jax.grad(loss, (0, 1, 2))

    def step(carry):
        x, s, bias = carry
        dx, ds, db = g(x, s, bias)
        return (x + _EPS * dx.astype(x.dtype),
                s + _EPS * ds.astype(s.dtype),
                bias + _EPS * db.astype(bias.dtype))

    return step, _ln_args(b, dtype, jax.random.key(0))


def _ln_parity(b, dtype, params):
    from ..ops.pallas.layernorm import _ln_jnp
    x, s, bias = _ln_args(b, dtype, jax.random.key(1))
    f = _ln_fn(b, params)
    _close(f(x, s, bias), _ln_jnp(x, s, bias, 1e-5),
           f"layernorm tuned fwd {params}")

    def lf(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    ga = jax.grad(lf(f), (0, 1, 2))(x, s, bias)
    gr = jax.grad(lf(lambda x, s, b_: _ln_jnp(x, s, b_, 1e-5)),
                  (0, 1, 2))(x, s, bias)
    for a, bb, n in zip(ga, gr, ("dx", "dscale", "dbias")):
        _close(a, bb, f"layernorm tuned {n} {params}")


# ------------------------------------------------------------ ring_block
# The carry-state blockwise flash step (ring attention's per-chunk-pair
# kernel, ops/pallas/flash_attention.py flash_block_fwd). The bucket's T
# is the ring CHUNK length (T_global / (2 * ring) under zigzag), so block
# tiles resolve per chunk shape, not per global sequence.
RING_DEFAULTS = dict(_RING_KERNEL_DEFAULTS)


def _ring_defaults(b):
    return dict(RING_DEFAULTS)


def _ring_candidates(b):
    T = b["T"]
    full = min(T, 1024)
    cands = [dict(RING_DEFAULTS)]
    cands.append(dict(RING_DEFAULTS, block_q=full, block_k=full,
                      block_h=1))
    cands.append(dict(RING_DEFAULTS, block_q=min(256, T),
                      block_k=min(256, T), block_h=1))
    return _dedup(cands)


def _ring_args(b, dtype, rng):
    G, T, d = 4, b["T"], b["d"]
    ks = jax.random.split(rng, 4)
    q, k1, v1, k2 = (jax.random.normal(k, (G, T, d), dtype) for k in ks)
    return q, k1, v1, k2


def _ring_chain(b, params, q, k1, v1, k2):
    """Two chained chunk pairs (diagonal-causal then full — one ring
    step's worth of state carry) finalized to an output."""
    from ..ops.pallas.flash_attention import (flash_block_finalize,
                                              flash_block_fwd,
                                              flash_block_state)
    G, T, d = q.shape
    kw = dict(block_q=int(params["block_q"]),
              block_k=int(params["block_k"]),
              block_h=int(params["block_h"]))
    st = flash_block_state(G, T, d)
    st = flash_block_fwd(q, k1, v1, st, causal=True, **kw)
    st = flash_block_fwd(q, k2, v1, st, causal=False, **kw)
    o, _ = flash_block_finalize(st)
    return o


def _ring_step(b, dtype, params):
    def step(carry):
        q, k1, v1, k2 = carry
        o = _ring_chain(b, params, q, k1, v1, k2)
        # fwd-only op (the ring backward reuses the tuned flash bwd):
        # chain the output back into q for data dependence
        return (q + _EPS * o.astype(q.dtype), k1, v1, k2)

    return step, _ring_args(b, dtype, jax.random.key(0))


def _ring_parity(b, dtype, params):
    bp = dict(b, T=min(b["T"], 1024))
    q, k1, v1, k2 = _ring_args(bp, dtype, jax.random.key(1))
    o = _ring_chain(bp, params, q, k1, v1, k2)
    # dense reference over the concatenated kv: causal on chunk 1 (the
    # diagonal pair), fully visible chunk 2 — the carried-state algebra
    # must reproduce one softmax over both
    T = q.shape[1]
    k = jnp.concatenate([k1, k2], axis=1)
    v = jnp.concatenate([v1, v1], axis=1)
    s = jnp.einsum("gtd,gsd->gts", q, k,
                   preferred_element_type=jnp.float32)
    mask = jnp.concatenate(
        [jnp.tril(jnp.ones((T, T), jnp.bool_)),
         jnp.ones((T, T), jnp.bool_)], axis=1)
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("gts,gsd->gtd", p,
                     v.astype(jnp.float32))
    _close(o, ref, f"ring_block tuned chain {params}")


# -------------------------------------------------------------- fused_ce


def _ce_defaults(b):
    return dict(CE_DEFAULTS)


def _ce_candidates(b):
    cands = [dict(CE_DEFAULTS)]
    for bm, bn in ((256, 512), (512, 1024), (1024, 512), (256, 256)):
        cands.append({"block_m": bm, "block_n": bn})
    return _dedup(cands)


def _ce_args(b, dtype, rng):
    N, D, V = min(b["N"], 2048), b["D"], b["V"]
    ks = jax.random.split(rng, 3)
    h = jax.random.normal(ks[0], (N, D), dtype)
    w = jax.random.normal(ks[1], (V, D), dtype) * (1 / math.sqrt(D))
    t = jax.random.randint(ks[2], (N,), 0, V, jnp.int32)
    return h, w, t


def _ce_step(b, dtype, params):
    from ..ops.pallas.fused_ce import unembed_logits_stats

    def step(carry):
        h, w, t = carry
        # forward-only op (the grad-in-forward CE forms d_logits outside
        # the kernel): chain logz back into h for data dependence
        _, logz, gold = unembed_logits_stats(
            h, w, t, block_m=int(params["block_m"]),
            block_n=int(params["block_n"]))
        h = h + _EPS * (logz - gold)[:, None].astype(h.dtype)
        return (h, w, t)

    return step, _ce_args(b, dtype, jax.random.key(0))


def _ce_parity(b, dtype, params):
    from deepspeed_tpu.ops.pallas.fused_ce import unembed_logits_stats
    h, w, t = _ce_args(dict(b, N=min(b["N"], 512)), dtype,
                       jax.random.key(1))
    logits, logz, gold = unembed_logits_stats(
        h, w, t, block_m=int(params["block_m"]),
        block_n=int(params["block_n"]))
    ref = jnp.einsum("nd,vd->nv", h, w,
                     preferred_element_type=jnp.float32)
    _close(logits, ref.astype(logits.dtype), f"fused_ce logits {params}",
           dict(rtol=2e-2, atol=2e-2))
    _close(logz, jax.nn.logsumexp(ref, axis=-1),
           f"fused_ce logz {params}", dict(rtol=2e-2, atol=2e-2))
    _close(gold, jnp.take_along_axis(ref, t[:, None], axis=1)[:, 0],
           f"fused_ce gold {params}", dict(rtol=2e-2, atol=2e-2))


# ---------------------------------------------------- moe grouped gemm
# The dropless-MoE expert FFN (ops/pallas/grouped_matmul.py routed
# through moe/sharded_moe.py): one grouped product per projection with
# per-group tile maps vs the generic lax.ragged_dot. The bucket's S is
# the rows entering the grouped product on ONE shard (tokens * top-k,
# incl. the EP transport capacity), E the LOCAL expert count, M/F the
# model/FFN dims. The 'ragged' default IS the pre-kernel program, so a
# cold cache changes nothing (the established cold-cache contract).


def _moe_defaults(b):
    return dict(MOE_GROUPED_DEFAULTS)


def _moe_candidates(b):
    """kernel-vs-ragged_dot plus the grouped tile sweep: the ragged
    baseline (current behavior), the 128-cube kernel tiling, and wider
    row/column tiles for the large-token buckets."""
    cands = [dict(MOE_GROUPED_DEFAULTS)]
    for bm, bn, bk in ((128, 128, 128), (256, 256, 128),
                       (512, 256, 256)):
        cands.append({"backend": "kernel", "block_m": bm, "block_n": bn,
                      "block_k": bk})
    return _dedup(cands)


def _moe_args(b, dtype, rng):
    S, E = min(b["S"], 2048), b["E"]
    M, F = b["M"], b["F"]
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (S, M), dtype) * 0.3
    w1 = jax.random.normal(ks[1], (E, M, F), dtype) * (1 / math.sqrt(M))
    w3 = jax.random.normal(ks[2], (E, M, F), dtype) * (1 / math.sqrt(M))
    w2 = jax.random.normal(ks[3], (E, F, M), dtype) * (1 / math.sqrt(F))
    # deterministic UNEVEN groups summing to S (the kernels only consult
    # group_sizes; a balanced split would hide boundary-tile handling)
    sizes = np.bincount(np.arange(S) * 7919 % E, minlength=E)
    return x, w1, w3, w2, jnp.asarray(sizes, jnp.int32)


def _moe_fn(params):
    from ..moe.sharded_moe import _grouped_swiglu_ffn

    def f(x, w1, w3, w2, group_sizes):
        return _grouped_swiglu_ffn(x, w1, w3, w2, group_sizes,
                                   dict(params))
    return f


def _moe_step(b, dtype, params):
    f = _moe_fn(params)
    x, w1, w3, w2, gs = _moe_args(b, dtype, jax.random.key(0))

    def loss(x, w1, w3, w2):
        return jnp.sum(f(x, w1, w3, w2, gs).astype(jnp.float32) ** 2)

    g = jax.grad(loss, (0, 1, 2, 3))

    def step(carry):
        x, w1, w3, w2 = carry
        dx, d1, d3, d2 = g(x, w1, w3, w2)
        return (x + _EPS * dx.astype(x.dtype),
                w1 + _EPS * d1.astype(w1.dtype),
                w3 + _EPS * d3.astype(w3.dtype),
                w2 + _EPS * d2.astype(w2.dtype))

    return step, (x, w1, w3, w2)


def _moe_parity(b, dtype, params):
    bp = dict(b, S=min(b["S"], 512))     # cap parity cost
    x, w1, w3, w2, gs = _moe_args(bp, dtype, jax.random.key(1))
    f = _moe_fn(params)
    ref = _moe_fn(dict(MOE_GROUPED_DEFAULTS))   # backend 'ragged'
    _close(f(x, w1, w3, w2, gs), ref(x, w1, w3, w2, gs),
           f"moe_grouped fwd {params}")

    def lf(fn):
        return lambda *a: jnp.sum(fn(*a, gs).astype(jnp.float32) ** 2)

    ga = jax.grad(lf(f), (0, 1, 2, 3))(x, w1, w3, w2)
    gr = jax.grad(lf(ref), (0, 1, 2, 3))(x, w1, w3, w2)
    for a, bb, n in zip(ga, gr, ("dx", "dw1", "dw3", "dw2")):
        _close(a, bb, f"moe_grouped {n} {params}",
               dict(rtol=5e-2, atol=5e-1 if n != "dx" else 5e-2))


# ------------------------------------------------- moe grouped int8
# W8A8 expert-FFN compute lever (quantize.moe_int8_matmul="auto"): the
# three grouped products through grouped_int8_matmul (int8 ragged_dot,
# per-expert channelwise weight codes repeated onto rows by
# group_sizes). {int8: 0} is the exact fp grouped-SwiGLU (cold-cache
# contract); winners flipping to 1 must survive the parity gate.

MOE_INT8_DEFAULTS = {"int8": 0}


def _moe8_defaults(b):
    return dict(MOE_INT8_DEFAULTS)


def _moe8_candidates(b):
    return _dedup([dict(MOE_INT8_DEFAULTS), {"int8": 1}])


def _moe8_fn(params):
    from ..moe.sharded_moe import _grouped_swiglu_ffn

    def f(x, w1, w3, w2, group_sizes):
        return _grouped_swiglu_ffn(
            x, w1, w3, w2, group_sizes,
            dict(MOE_GROUPED_DEFAULTS, int8=int(params["int8"])))
    return f


def _moe8_step(b, dtype, params):
    f = _moe8_fn(params)
    x, w1, w3, w2, gs = _moe_args(b, dtype, jax.random.key(0))

    def loss(x, w1, w3, w2):
        return jnp.sum(f(x, w1, w3, w2, gs).astype(jnp.float32) ** 2)

    g = jax.grad(loss, (0, 1, 2, 3))

    def step(carry):
        x, w1, w3, w2 = carry
        dx, d1, d3, d2 = g(x, w1, w3, w2)
        return (x + _EPS * dx.astype(x.dtype),
                w1 + _EPS * d1.astype(w1.dtype),
                w3 + _EPS * d3.astype(w3.dtype),
                w2 + _EPS * d2.astype(w2.dtype))

    return step, (x, w1, w3, w2)


def _moe8_parity(b, dtype, params):
    bp = dict(b, S=min(b["S"], 512))     # cap parity cost
    x, w1, w3, w2, gs = _moe_args(bp, dtype, jax.random.key(1))
    f = _moe8_fn(params)
    ref = _moe8_fn(MOE_INT8_DEFAULTS)
    exact = not params["int8"]
    _close(f(x, w1, w3, w2, gs), ref(x, w1, w3, w2, gs),
           f"moe_grouped_int8 fwd {params}",
           _TOL if exact else _INT8_FWD_TOL)

    def lf(fn):
        return lambda *a: jnp.sum(fn(*a, gs).astype(jnp.float32) ** 2)

    ga = jax.grad(lf(f), (0, 1, 2, 3))(x, w1, w3, w2)
    gr = jax.grad(lf(ref), (0, 1, 2, 3))(x, w1, w3, w2)
    for a, bb, n in zip(ga, gr, ("dx", "dw1", "dw3", "dw2")):
        _close(a, bb, f"moe_grouped_int8 {n} {params}",
               _TOL if exact else _INT8_GRAD_TOL)


# ------------------------------------------------- paged serving kernels
# The v2 engine's decode step and SplitFuse chunk program (ops/pallas/
# paged_attention.py). Buckets are the engine's decode shapes — (batch
# slots | chunk tokens, blocks-per-seq, block size, kv-heads, GQA
# group, head dim) — so each compiled per-bucket serving program
# resolves its own winner. Both are forward-only serving ops: steps
# chain the attention output back into q for data dependence.


def _pgd_defaults(b):
    from ..ops.pallas.paged_attention import PAGED_DECODE_DEFAULTS
    return dict(PAGED_DECODE_DEFAULTS)


def _pgd_candidates(b):
    """The serving lever: blocked-stream Pallas kernel vs the
    dense-gather program (the measured choice the engine's
    paged_kernel="auto" takes per decode-shape bucket)."""
    return _dedup([_pgd_defaults(b), {"mode": "dense"}])


def _pgd_args(b, dtype, rng):
    B, MB, BS = b["B"], b["MB"], b["BS"]
    KVH, G, d = b["kh"], b["g"], b["d"]
    NB = 2 * MB + 1
    ks = jax.random.split(rng, 5)
    q = jax.random.normal(ks[0], (B, KVH * G, d), dtype)
    kc = jax.random.normal(ks[1], (NB, KVH, BS, d), dtype)
    vc = jax.random.normal(ks[2], (NB, KVH, BS, d), dtype)
    tables = jax.random.randint(ks[3], (B, MB), 0, NB, jnp.int32)
    lengths = jax.random.randint(ks[4], (B,), 0, MB * BS, jnp.int32)
    return q, kc, vc, tables, lengths


def _pgd_fn(params):
    from ..ops.pallas.paged_attention import (
        paged_decode_attention, paged_decode_attention_reference)
    return paged_decode_attention_reference if params["mode"] == "dense" \
        else paged_decode_attention


def _pgd_step(b, dtype, params):
    f = _pgd_fn(params)

    def step(carry):
        q, kc, vc, tables, lengths = carry
        o = f(q, kc, vc, tables, lengths)
        return (q + _EPS * o.astype(q.dtype), kc, vc, tables, lengths)

    return step, _pgd_args(b, dtype, jax.random.key(0))


def _pgd_parity(b, dtype, params):
    from ..ops.pallas.paged_attention import (
        paged_decode_attention_reference)
    q, kc, vc, tables, lengths = _pgd_args(b, dtype, jax.random.key(1))
    got = _pgd_fn(params)(q, kc, vc, tables, lengths)
    ref = paged_decode_attention_reference(q, kc, vc, tables, lengths)
    _close(got, ref, f"paged_decode tuned {params}")


def _pgc_defaults(b):
    from ..ops.pallas.paged_attention import paged_chunk_tune_defaults
    return paged_chunk_tune_defaults()


def _pgc_candidates(b):
    """Kernel-vs-dense plus the chunk-token tile sweep. ``block_c`` 0 is
    the tile the kernel sizes from the shapes (``chunk_tile``: the cold
    default); a sweep entry pins the tokens a query tile, and the kernel
    sizes the KV heads and table entries of a step round it. Sweep
    entries carry the CLAMPED tile (min(bc, C) — what the wrapper
    executes) and only tiles the kernel accepts: a tile below the whole
    chunk holds whole sublane tiles of folded rows (tokens x G a multiple
    of 8). Two nominal tiles that clamp to one program,
    or that the shape rule already gives, are never both timed, and the
    cached winner records the tile that actually ran."""
    from ..ops.pallas.paged_attention import chunk_tile
    C, G = b["C"], b["g"]
    d = _pgc_defaults(b)
    cands = [d, {"mode": "dense", "block_c": 0}]
    eff_seen = {chunk_tile(C, b["kh"], G, b["d"], b["BS"], b["MB"],
                           "bfloat16").block_c}
    for bc in (64, 128, 256, 512):
        eff = min(bc, C)
        if eff not in eff_seen and (eff == C or eff * G % 8 == 0):
            eff_seen.add(eff)
            cands.append({"mode": "kernel", "block_c": eff})
    return _dedup(cands)


def _pgc_shapes(b):
    C, MB, BS = b["C"], b["MB"], b["BS"]
    # a mid-sequence chunk straddling block boundaries, partially real
    S = MB * BS
    start = min(max(S // 2, 1), max(S - C, 0))
    true_len = max(1, min(C - 1, S - start))
    return start, true_len


def _pgc_args(b, dtype, rng):
    C, MB, BS = b["C"], b["MB"], b["BS"]
    KVH, G, d = b["kh"], b["g"], b["d"]
    NB = 2 * MB + 1
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (C, KVH * G, d), dtype)
    kc = jax.random.normal(ks[1], (NB, KVH, BS, d), dtype)
    vc = jax.random.normal(ks[2], (NB, KVH, BS, d), dtype)
    table = jax.random.randint(ks[3], (MB,), 0, NB, jnp.int32)
    return q, kc, vc, table


def _pgc_fn(b, params):
    from ..ops.pallas.paged_attention import (
        paged_chunk_attention, paged_chunk_attention_reference)
    start, true_len = _pgc_shapes(b)

    def f(q, kc, vc, table):
        if params["mode"] == "dense":
            return paged_chunk_attention_reference(
                q, kc, vc, table, jnp.int32(start), jnp.int32(true_len))
        return paged_chunk_attention(
            q, kc, vc, table, jnp.int32(start), jnp.int32(true_len),
            block_c=int(params["block_c"]))
    return f


def _pgc_step(b, dtype, params):
    f = _pgc_fn(b, params)

    def step(carry):
        q, kc, vc, table = carry
        o = f(q, kc, vc, table)
        return (q + _EPS * o.astype(q.dtype), kc, vc, table)

    return step, _pgc_args(b, dtype, jax.random.key(0))


def _pgc_parity(b, dtype, params):
    from ..ops.pallas.paged_attention import (
        paged_chunk_attention_reference)
    start, true_len = _pgc_shapes(b)
    q, kc, vc, table = _pgc_args(b, dtype, jax.random.key(1))
    got = _pgc_fn(b, params)(q, kc, vc, table)
    ref = paged_chunk_attention_reference(
        q, kc, vc, table, jnp.int32(start), jnp.int32(true_len))
    # pad q rows (>= true_len) attend partly-garbage positions by
    # design; their outputs are discarded by the chunk program
    _close(got[:true_len], ref[:true_len],
           f"paged_chunk tuned {params}")


# ------------------------------------------------- pipeline step shape
# The pipeline executors' two schedule-level knobs (runtime/pipe/):
# microbatch count M (more microbatches amortize the fill/drain bubble
# but shrink the per-tick batch below MXU efficiency — the knee is a
# MEASURED property of the chip) and the host-offload round trip. The
# step emulates the lock-step executor's cost structure on one device:
# a scan over the schedule's tick count, each tick a block fwd+bwd at
# the candidate's per-tick token count (plus the host staging round
# trip when the candidate offloads), so one chain step prices one
# global batch through the pipe and candidates are directly comparable.


def _pipe_micro_grid(S, B):
    """Candidate microbatch counts that the bucket's batch grid can
    actually run (B % m == 0 — GPT2Pipe's hard requirement; a cached
    winner the model cannot execute would turn 'auto' into a crash).
    Never empty: 1 divides everything."""
    grid = [m for m in (S, 2 * S, 4 * S) if m <= B and B % m == 0]
    return grid or [1]


def _pipe_defaults(b):
    grid = _pipe_micro_grid(b["S"], b["B"])
    # the 2S guidance when the grid admits it, else the largest valid
    return {"micro": 2 * b["S"] if 2 * b["S"] in grid else grid[-1],
            "offload": 0}


def _pipe_candidates(b):
    cands = [_pipe_defaults(b)]
    for m in _pipe_micro_grid(b["S"], b["B"]):
        cands.append({"micro": m, "offload": 0})
    from ..runtime.swap_tensor import host_stage
    if host_stage.available():
        for c in list(cands):
            cands.append(dict(c, offload=1))
    return _dedup(cands)


def _pipe_tokens(b, params):
    """Per-tick token count for the candidate, capped so a search step
    stays affordable; the cap formula is shared by every candidate so
    clamped comparisons stay fair."""
    micro = max(1, int(params["micro"]))
    return max(1, min((b["B"] * b["T"]) // micro, 1 << 13))


def _pipe_step(b, dtype, params):
    from ..runtime.swap_tensor import host_stage
    D = b["D"]
    F = 4 * D
    micro = max(1, int(params["micro"]))
    n_ticks = micro + 2 * (b["S"] - 1)
    rows = _pipe_tokens(b, params)
    offload = bool(params.get("offload"))
    ks = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(ks[0], (rows, D), dtype) * 0.3
    w1 = jax.random.normal(ks[1], (D, F), dtype) / math.sqrt(D)
    w2 = jax.random.normal(ks[2], (F, D), dtype) / math.sqrt(F)

    def block(x, w1, w2):
        return x + jax.nn.gelu(x @ w1) @ w2

    def tick_loss(x, w1, w2):
        return jnp.sum(block(x, w1, w2).astype(jnp.float32) ** 2)

    g = jax.grad(tick_loss, (0, 1, 2))

    def step(carry):
        x, w1, w2 = carry

        def tick(c, _):
            x_, w1_, w2_ = c
            if offload:
                # the ring round trip: stage the tick's activation to
                # host and read it back (what the executor's offloaded
                # input ring costs per tick)
                x_ = host_stage.to_device(host_stage.to_host(x_))
            dx, d1, d2 = g(x_, w1_, w2_)
            return (x_ + _EPS * dx.astype(x_.dtype),
                    w1_ + _EPS * d1.astype(w1_.dtype),
                    w2_ + _EPS * d2.astype(w2_.dtype)), None

        (x, w1, w2), _ = jax.lax.scan(tick, (x, w1, w2), None,
                                      length=n_ticks)
        return (x, w1, w2)

    return step, (x, w1, w2)


def _pipe_parity(b, dtype, params):
    """The candidate changes scheduling shape, not math: the host
    round trip must be an identity, and the microbatch count must
    divide the bucket's batch grid."""
    from ..runtime.swap_tensor import host_stage
    micro = max(1, int(params["micro"]))
    if b["B"] % micro:
        raise AssertionError(
            f"pipe_microbatch candidate micro={micro} does not divide "
            f"batch bucket B={b['B']} — the model could never run it")
    x = jax.random.normal(jax.random.key(2), (64, b["D"]), dtype)
    if params.get("offload"):
        _close(host_stage.to_device(host_stage.to_host(x)), x,
               f"pipe_microbatch offload round trip {params}",
               dict(rtol=0, atol=0))


# ------------------------------------------------ prefix-cache policy
# The serving prefix cache (inference/v2/prefix_cache.py) is host-side
# scheduling policy, not a kernel — but whether it pays for itself, and
# where the min-match knee sits, is a MEASURED property of the chip:
# the lever trades skipped prefill compute against CoW copies and
# scheduling overhead. Like pipe_microbatch, the step emulates the cost
# structure on one device: a prefill-shaped forward over however much
# of a synthetic shared-prefix prompt the candidate's policy does NOT
# serve from cache (the bucket's traffic model: prompts span the pool's
# per-slot block share and half of each prompt is a shared template).
# The eviction watermark rides along untimed (it moves host-side
# latency, not device compute); wm=0 candidates are listed first so
# ties resolve to the hand-set on-demand policy.


def _pfx_defaults(b):
    from ..inference.v2.prefix_cache import PREFIX_CACHE_DEFAULTS
    return dict(PREFIX_CACHE_DEFAULTS)


def _pfx_prompt_blocks(b):
    """Synthetic per-request prompt blocks for the bucket: the pool's
    per-slot share (capped for step affordability)."""
    return max(2, min(b["NB"] // max(1, b["B"]), 64))


def _pfx_candidates(b):
    cands = [_pfx_defaults(b)]
    half = _pfx_prompt_blocks(b) // 2
    for wm in (0, 25):
        for mm in (1, 2, 4):
            if mm > max(1, half):
                continue          # a knee the traffic can never reach
            cands.append({"enabled": 1, "min_match_blocks": mm,
                          "evict_watermark_pct": wm})
    return _dedup(cands)


def _pfx_step(b, dtype, params):
    BS = b["BS"]
    pb = _pfx_prompt_blocks(b)
    shared = pb // 2
    skip = 0
    if int(params["enabled"]) and shared >= int(
            params["min_match_blocks"]):
        skip = shared
    rows = max(BS, (pb - skip) * BS)
    D = 128
    ks = jax.random.split(jax.random.key(0), 2)
    x = jax.random.normal(ks[0], (pb * BS, D), dtype) * 0.3
    w = jax.random.normal(ks[1], (D, D), dtype) / math.sqrt(D)

    def step(carry):
        x, w = carry
        # the recomputed suffix's prefill-shaped forward; the cached
        # prefix contributes nothing (that is the lever)
        y = jax.nn.gelu(x[:rows] @ w) @ w.T
        x = x.at[:rows].add(_EPS * y.astype(x.dtype))
        return (x, w)

    return step, (x, w)


def _pfx_parity(b, dtype, params):
    """The candidate changes admission policy, not math — check the
    policy invariants on a live tree: knob ranges, and the hard rule
    that a match never covers the whole prompt (the last token is
    always recomputed so the first sampled token comes from a real
    forward)."""
    mm = int(params["min_match_blocks"])
    if mm < 1:
        raise AssertionError(
            f"prefix_cache candidate min_match_blocks={mm} < 1")
    wm = int(params["evict_watermark_pct"])
    if not 0 <= wm <= 100:
        raise AssertionError(
            f"prefix_cache candidate evict_watermark_pct={wm} "
            f"outside [0, 100]")
    from ..inference.v2.blocked_allocator import BlockedAllocator
    from ..inference.v2.prefix_cache import PrefixCache
    BS = b["BS"]
    alloc = BlockedAllocator(4)
    pc = PrefixCache(alloc, BS, min_match_blocks=mm,
                     evict_watermark_pct=wm)
    toks = list(range(2 * BS))
    pc.release(toks, alloc.allocate(2))
    m = pc.match(toks)
    if m.cached_len > len(toks) - 1:
        raise AssertionError(
            f"prefix_cache match covered the whole prompt "
            f"(cached_len={m.cached_len}, T={len(toks)})")
    if mm == 1 and m.cached_len != 2 * BS - 1:
        raise AssertionError(
            f"prefix_cache full-prompt re-match expected BS-1 partial "
            f"tail (cached_len {2 * BS - 1}), got {m.cached_len}")


# -------------------------------------------- speculative-decode policy
# Draft-model speculation (inference/v2/speculative.py) is scheduling
# policy like prefix_cache, but its payoff is an acceptance-rate bet:
# one verify round costs a (k+1)-position target pass plus k draft
# decode steps, and commits 1 + (accepted) tokens. The step prices that
# trade on one device with the same matmul-rows emulation as
# prefix_cache: per-COMMITTED-token work for the candidate under a
# fixed synthetic acceptance model (per-token acceptance p=0.7 — the
# shared-template serving traffic the bench's high-acceptance workload
# models; r=0.125 draft/target cost ratio, the "narrow draft" sizing
# the README recommends). k too large for the traffic's acceptance
# decays committed tokens toward 1 + p/(1-p) while the verify span
# keeps growing — the cost term prices exactly that knee.


def _spec_defaults(b):
    from ..inference.v2.speculative import SPEC_DEFAULTS
    return dict(SPEC_DEFAULTS)


def _spec_candidates(b):
    cands = [_spec_defaults(b)]
    cands.append({"enabled": 0, "spec_k": 0, "floor_pct": 35})
    for k in (2, 4, 8):
        cands.append({"enabled": 1, "spec_k": k, "floor_pct": 35})
    return _dedup(cands)


def _spec_per_token_cost(params):
    """Target-pass-equivalents per committed token under the synthetic
    acceptance model: verify touches k+1 positions, the draft adds
    k*r, and the round commits the expected accepted prefix + bonus.
    Disabled = plain decode = 1.0 by construction."""
    k = int(params["spec_k"])
    if not int(params["enabled"]) or k < 1:
        return 1.0
    p, r = 0.7, 0.125
    committed = 1.0 + sum(p ** j for j in range(1, k + 1))
    return ((k + 1) + k * r) / committed


def _spec_step(b, dtype, params):
    rows = max(8, int(8 * b["B"] * _spec_per_token_cost(params)))
    D = 128
    ks = jax.random.split(jax.random.key(3), 2)
    x = jax.random.normal(ks[0], (rows, D), dtype) * 0.3
    w = jax.random.normal(ks[1], (D, D), dtype) / math.sqrt(D)

    def step(carry):
        x, w = carry
        y = jax.nn.gelu(x @ w) @ w.T
        x = x + _EPS * y.astype(x.dtype)
        return (x, w)

    return step, (x, w)


def _spec_parity(b, dtype, params):
    """The candidate changes scheduling, not math — check knob ranges
    and the acceptance rule's invariants (greedy acceptance is the
    byte-identity guardrail, so its host kernel is pinned here too)."""
    k = int(params["spec_k"])
    if int(params["enabled"]) and k < 1:
        raise AssertionError(
            f"spec_decode candidate enabled with spec_k={k} < 1")
    fl = int(params["floor_pct"])
    if not 0 <= fl <= 100:
        raise AssertionError(
            f"spec_decode candidate floor_pct={fl} outside [0, 100]")
    from ..inference.v2.speculative import longest_accept
    if longest_accept([5, 6, 7], [5, 6, 7, 8]) != 3:
        raise AssertionError("longest_accept full-accept broken")
    if longest_accept([5, 9, 7], [5, 6, 7, 8]) != 1:
        raise AssertionError(
            "longest_accept must stop at the FIRST mismatch")
    if longest_accept([9, 6, 7], [5, 6, 7, 8]) != 0:
        raise AssertionError("longest_accept first-token reject broken")


# ------------------------------------------------- op: kv_handoff
# Disaggregated prefill/decode serving (inference/v2/kv_transfer.py +
# router phase-aware dispatch). The knob is WHERE decode happens, not a
# kernel shape: colocated decode pays for the split-fuse prefill chunks
# interleaved into its batch (each long prefill steals decode
# iterations from every co-resident sequence), disaggregated decode
# pays the one-time KV-block stream over DCN instead. The cost model
# prices exactly that trade per committed decode token; the candidate
# emulation scales a fixed matmul step by it, same device-honest idiom
# as spec_decode.


def _kvh_defaults(b):
    # colocated is the cold default: the disabled program must stay
    # byte-identical to the pre-disaggregation engine
    return {"disaggregate": 0}


def _kvh_candidates(b):
    return [{"disaggregate": 0}, {"disaggregate": 1}]


def _kvh_per_token_cost(b, params):
    """Decode-iteration-equivalents per committed token. Colocated: a
    P-token prompt arriving mid-decode injects ceil(P/C) split-fuse
    chunk dispatches into the decode stream, amortized over G decode
    tokens per sequence. Disaggregated: the KV stream for the same
    prompt costs wire_bytes/DCN_rate, measured in decode-step units,
    amortized over the same G."""
    P, C, G = 1024.0, 256.0, 128.0           # prompt, chunk, gen tokens
    if not int(params["disaggregate"]):
        return 1.0 + (P / C) / G
    # KV wire bytes for the prompt: 2 (k+v) * layers * kv_heads *
    # head_dim * itemsize, padded to the block grid
    L, Hkv, hd, itemsize, BS = 24.0, 8.0, 128.0, 2.0, 64.0
    wire = 2.0 * L * Hkv * hd * itemsize * math.ceil(P / BS) * BS
    # DCN effective rate per decode-step-time: ~25 GB/s link, ~4 ms
    # decode step -> bytes movable in one decode iteration
    dcn_bytes_per_step = 25e9 * 0.004
    return 1.0 + (wire / dcn_bytes_per_step) / G


def _kvh_step(b, dtype, params):
    rows = max(8, int(8 * b["B"] * _kvh_per_token_cost(b, params)))
    D = 128
    ks = jax.random.split(jax.random.key(11), 2)
    x = jax.random.normal(ks[0], (rows, D), dtype) * 0.3
    w = jax.random.normal(ks[1], (D, D), dtype) / math.sqrt(D)

    def step(carry):
        x, w = carry
        y = jax.nn.gelu(x @ w) @ w.T
        x = x + _EPS * y.astype(x.dtype)
        return (x, w)

    return step, (x, w)


def _kvh_parity(b, dtype, params):
    """The candidate changes placement, not math — pin the knob range
    and the wire format's integrity contract: a handoff payload must
    round-trip state + KV bytes exactly, and a corrupted payload must
    be REJECTED, never imported (silent KV corruption would break the
    colocated-vs-disaggregated byte-identity guarantee)."""
    d = int(params["disaggregate"])
    if d not in (0, 1):
        raise AssertionError(
            f"kv_handoff candidate disaggregate={d} outside (0, 1)")
    from ..inference.v2 import kv_transfer
    state = {"uid": 7, "prompt": [1, 2, 3], "generated": [4],
             "cached_len": 0}
    tree = {"k": [np.arange(12, dtype=np.float32).reshape(3, 4)],
            "v": [np.ones((3, 4), np.float32) * 0.5]}
    payload = kv_transfer.pack_handoff(state, tree)
    got_state, flat = kv_transfer.unpack_handoff(payload)
    if got_state != state:
        raise AssertionError("kv_handoff state round-trip broken")
    for key, ref in (("k/0", tree["k"][0]), ("v/0", tree["v"][0])):
        if not np.array_equal(np.asarray(flat[key]), ref):
            raise AssertionError(
                f"kv_handoff KV leaf {key} not byte-identical")
    bad = bytearray(payload)
    bad[-1] ^= 0xFF
    try:
        kv_transfer.unpack_handoff(bytes(bad))
    except kv_transfer.KVWireError:
        pass
    else:
        raise AssertionError(
            "kv_handoff accepted a corrupted payload (CRC must reject)")


# ---------------------------------------------------------------- table
REGISTRY = {
    "flash_attention": {
        "defaults": _flash_defaults,
        "candidates": _flash_candidates,
        "make_step": _flash_step,
        "parity": _flash_parity,
    },
    "mlp_matmul": {
        "defaults": _mlp_defaults,
        "candidates": _mlp_candidates,
        "make_step": _mlp_step,
        "parity": _mlp_parity,
    },
    "layernorm": {
        "defaults": _ln_defaults,
        "candidates": _ln_candidates,
        "make_step": _ln_step,
        "parity": _ln_parity,
    },
    "fused_ce": {
        "defaults": _ce_defaults,
        "candidates": _ce_candidates,
        "make_step": _ce_step,
        "parity": _ce_parity,
    },
    "ring_block": {
        "defaults": _ring_defaults,
        "candidates": _ring_candidates,
        "make_step": _ring_step,
        "parity": _ring_parity,
    },
    "moe_grouped_mm": {
        "defaults": _moe_defaults,
        "candidates": _moe_candidates,
        "make_step": _moe_step,
        "parity": _moe_parity,
    },
    "mlp_int8": {
        "defaults": _mlp8_defaults,
        "candidates": _mlp8_candidates,
        "make_step": _mlp8_step,
        "parity": _mlp8_parity,
    },
    "moe_grouped_int8": {
        "defaults": _moe8_defaults,
        "candidates": _moe8_candidates,
        "make_step": _moe8_step,
        "parity": _moe8_parity,
    },
    "paged_decode": {
        "defaults": _pgd_defaults,
        "candidates": _pgd_candidates,
        "make_step": _pgd_step,
        "parity": _pgd_parity,
    },
    "paged_chunk": {
        "defaults": _pgc_defaults,
        "candidates": _pgc_candidates,
        "make_step": _pgc_step,
        "parity": _pgc_parity,
    },
    "pipe_microbatch": {
        "defaults": _pipe_defaults,
        "candidates": _pipe_candidates,
        "make_step": _pipe_step,
        "parity": _pipe_parity,
    },
    "prefix_cache": {
        "defaults": _pfx_defaults,
        "candidates": _pfx_candidates,
        "make_step": _pfx_step,
        "parity": _pfx_parity,
    },
    "spec_decode": {
        "defaults": _spec_defaults,
        "candidates": _spec_candidates,
        "make_step": _spec_step,
        "parity": _spec_parity,
    },
    "kv_handoff": {
        "defaults": _kvh_defaults,
        "candidates": _kvh_candidates,
        "make_step": _kvh_step,
        "parity": _kvh_parity,
    },
}

# collective/schedule ops (collective_ops.py — step builders run under a
# virtual or real mesh, winners keyed by topology signature folded into
# the bucket string) ride the SAME registry: dispatch, the measured
# search, the cache, and the kernel_parity harness treat them uniformly
from .collective_ops import COLLECTIVE_REGISTRY  # noqa: E402

REGISTRY.update(COLLECTIVE_REGISTRY)
