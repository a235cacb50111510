"""Plain reference of Olmo-Hybrid (``model_type`` olmo_hybrid: gated
delta-rule layers and full-attention layers, three to one): forward pass,
next-token loss and the serving cells' token check in straightforward
``jax.numpy``, float32, matmul precision "highest". No kernel, no cache, no
chunkwise form, no batching, nothing imported from the program.

The equations (ISSUE 41 section 1; Gated DeltaNet, Yang et al., arXiv
2412.06464; OLMo 2, arXiv 2501.00656, for the norm placement and the
QK-norm), for L layers, ``layer_types[i]`` linear or full, RMSNorm ``rms(x;
g) = x / sqrt(mean(x^2) + eps) * g`` with eps 1e-6, no bias anywhere, an
untied head:

    h <- h + rms(Mix_i(h); g1_i);   h <- h + rms(MLP_i(h); g2_i)
    MLP(x) = (silu(x Wg) * (x Wu)) Wd;   logits = rms(h; g_f) W_head^T

  Full    q = rms(x Wq; gq), k = rms(x Wk; gk) over the whole projection,
          before the split into heads; v = x Wv; H heads of hd = D / H;
          o = softmax(q k^T / sqrt(hd) + causal mask) v; Mix = o Wo.
          NO positional encoding (the published rope_theta is null).
  Linear  [q | k | v] = silu(conv_K(x [Wq | Wk | Wv])), depthwise causal
          conv a channel, no bias: u'_t = sum_j w[j] u_{t-(K-1)+j};
          q_t <- q_t / sqrt(|q_t|^2 + eps) / sqrt(dk), k_t <- k_t /
          sqrt(|k_t|^2 + eps), a head (eps 1e-6 under the root);
          beta_t = 2 sigmoid(x_t Wb);  alpha_t = exp(-exp(A_log)
          softplus(x_t Wa + dt_bias)), a head;
          S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
          (S in R^{dv x dk} a head; held here transposed, (dk, dv), as the
          program's cache holds it);  o_t = S_t q_t;
          Mix = (rms(o_t; g_o) * silu(x_t Wz)) Wo, rms a head over dv.

Departures from the published modeling code, as far as it is known here:
none intended; each size the config.json does not give is listed under
``assumed`` in ``perfbench/configs/olmo-hybrid-7b.json``.

It reads the program's parameter tree, whose layout is the one thing shared
with the code under test (every projection input-major, x @ W):
  wte (V, D) | lm_head (V, D) | norm_f (D,) | layers: a list of L dicts, each
  with norm1, norm2 (D,), w1 (D, 2F) [gate | up], w2 (F, D), and
    linear: in_proj (D, 2 H dk + 2 H dv) [q | k | v | z], ab_proj (D, 2H)
            [a | b], conv_w (K, 2 H dk + H dv), A_log (H,) [f32], dt_b (H,)
            [f32], o_norm (dv,), out_proj (H dv, D)
    full:   wqkv (D, 3D) [q | k | v], q_norm, k_norm (D,), wo (D, D)
A layer is linear where it has ``in_proj``. Parameters may arrive in
bfloat16; each is cast to float32 as it is used.

Kept small enough to run on the chip beside the engine at T = 8,704:
attention in blocks of ``ROW_BLOCK`` queries, the rule a sequential
``lax.scan`` over time, the 100,352-row unembedding in blocks of rows and
only at the positions asked for. What is a position's own (projections,
MLP) is computed whole: in blocks under a ``lax.map`` a layer, each loop
kept buffers of its own and sixteen layers asked for 10.2 GB of
temporaries, whole they ask for 1.6 (compiled for a v5e, PR 41).

``state_dtype``, ``beta_scale``, ``qk_norm`` and ``rope_theta`` default to
the published values; the tier-1 tests and ``perfbench/parity_olmo_hybrid.py``
pass others to show that the comparison tells the model from its neighbours.
"""

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ACTIVATIONS = {"silu": jax.nn.silu}
EPS = 1e-6                 # rms_norm_eps, and the L2 norms'
VOCAB_BLOCK = 4096         # rows of the head unembedded at once
ROW_BLOCK = 512            # queries a block of attention


def _f32(x):
    return x.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _blocked(fn, *xs):
    """``fn`` over the leading axis of ``xs`` (T, ...) in blocks of
    ROW_BLOCK positions: nothing wider than a block exists at once (the
    attention's scores)."""
    T = xs[0].shape[0]
    if T <= ROW_BLOCK:
        return fn(*xs)
    n = -(-T // ROW_BLOCK)

    def cut(x):
        x = jnp.pad(x, ((0, n * ROW_BLOCK - T),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((n, ROW_BLOCK) + x.shape[1:])

    out = lax.map(lambda block: fn(*block), tuple(cut(x) for x in xs))
    return jax.tree.map(
        lambda y: y.reshape((n * ROW_BLOCK,) + y.shape[2:])[:T], out)


def is_linear(p):
    return "in_proj" in p


def delta_rule(q, k, v, alpha, beta, state, state_dtype=jnp.float32):
    """The gated delta rule, a token at a time. q, k (T, H, dk), v (T, H,
    dv), alpha, beta (T, H), state (H, dk, dv) -> (o (T, H, dv), state)."""

    def step(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        kS = jnp.sum(k_t[:, :, None] * S, axis=1)                # (H, dv)
        S = a_t[:, None, None] * (S - b_t[:, None, None]
                                  * k_t[:, :, None] * kS[:, None, :]) \
            + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        if state_dtype != jnp.float32:
            # a round trip through the dtype that the compiler cannot
            # take out as excess precision
            info = jnp.finfo(state_dtype)
            S = lax.reduce_precision(S, info.nexp, info.nmant)
        return S, jnp.sum(q_t[:, :, None] * S, axis=1)

    state, o = lax.scan(step, state, (q, k, v, alpha, beta))
    return o, state


def _l2(x, eps):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _linear(x, p, H, act, state_dtype, beta_scale, eps):
    """(T, D) -> Mix (T, D) of a gated delta-rule layer."""
    T = x.shape[0]
    dv = p["out_proj"].shape[0] // H
    K, conv_ch = p["conv_w"].shape
    dk = (conv_ch - H * dv) // (2 * H)

    uz, ab = _mm(x, _f32(p["in_proj"])), _mm(x, _f32(p["ab_proj"]))
    u, z = uz[:, :conv_ch], uz[:, conv_ch:]
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    w = _f32(p["conv_w"])
    qkv = act(sum(padded[j:j + T] * w[j] for j in range(K)))
    q = _l2(qkv[:, :H * dk].reshape(T, H, dk), eps) * dk ** -0.5
    k = _l2(qkv[:, H * dk:2 * H * dk].reshape(T, H, dk), eps)
    v = qkv[:, 2 * H * dk:].reshape(T, H, dv)
    alpha = jnp.exp(-jnp.exp(_f32(p["A_log"]))
                    * jax.nn.softplus(ab[:, :H] + _f32(p["dt_b"])))
    beta = beta_scale * jax.nn.sigmoid(ab[:, H:])
    o, _ = delta_rule(q, k, v, alpha, beta,
                      jnp.zeros((H, dk, dv), jnp.float32), state_dtype)
    o = _rms(o, p["o_norm"], eps) * act(z.reshape(T, H, dv))
    return _mm(o.reshape(T, H * dv), _f32(p["out_proj"]))


def _rope(x, theta):
    """Rotary embedding, half-split pairing, positions 0 .. T-1; x (T, H,
    hd). Only the rope neighbour calls it: the model has none."""
    T, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _full(x, p, H, qk_norm, rope_theta, eps):
    """(T, D) -> Mix (T, D) of a full-attention layer."""
    T, D = x.shape
    hd = D // H
    qkv = _mm(x, _f32(p["wqkv"]))
    q, k, v = qkv[:, :D], qkv[:, D:2 * D], qkv[:, 2 * D:]
    if qk_norm:
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    q, k, v = (a.reshape(T, H, hd) for a in (q, k, v))
    if rope_theta:
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    s = jnp.arange(T)[None, None, :]

    def attend(q, t):
        sc = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(hd))
        pr = jax.nn.softmax(jnp.where(s <= t[None, :, None], sc, -jnp.inf),
                            axis=-1)
        o = jnp.einsum("hts,shd->thd", pr, v, precision=HIGHEST)
        return _mm(o.reshape(-1, D), _f32(p["wo"]))

    return _blocked(attend, q, jnp.arange(T))


def hidden_states(params, ids, *, n_head, activation="silu",
                  state_dtype=jnp.float32, beta_scale=2.0, qk_norm=True,
                  rope_theta=None, eps=EPS):
    """(B, T) token ids -> (B, T, D) float32 states after the last block."""
    act = ACTIVATIONS[activation]

    def mlp(x, p):
        gu = _mm(x, _f32(p["w1"]))
        F = gu.shape[1] // 2
        return _mm(act(gu[:, :F]) * gu[:, F:], _f32(p["w2"]))

    def sequence(row):
        x = _f32(params["wte"][row])                         # (T, D)
        for p in params["layers"]:
            mix = _linear(x, p, n_head, act, state_dtype, beta_scale, eps) \
                if is_linear(p) \
                else _full(x, p, n_head, qk_norm, rope_theta, eps)
            x = x + _rms(mix, p["norm1"], eps)
            x = x + _rms(mlp(x, p), p["norm2"], eps)
        return x

    return jnp.stack([sequence(ids[b]) for b in range(ids.shape[0])])


def _row_blocks(E):
    """The head as (blocks, rows, D): the largest divisor of its row count
    that is at most VOCAB_BLOCK rows a block."""
    V = E.shape[0]
    rows = max(r for r in range(1, min(V, VOCAB_BLOCK) + 1) if V % r == 0)
    return E.reshape(V // rows, rows, E.shape[1])


def logits_at(params, x, eps=EPS):
    """(n, D) states -> (n, V) float32 logits through the final RMSNorm and
    the untied head, a block of rows at a time."""
    xn = _rms(x, params["norm_f"], eps)
    out = lax.map(lambda e: _mm(xn, _f32(e).T),
                  _row_blocks(params["lm_head"]))
    return out.transpose(1, 0, 2).reshape(x.shape[0], -1)


def logits(params, ids, **kw):
    """(B, T) -> (B, T, V): every position's logits (small sizes only)."""
    x = hidden_states(params, ids, **kw)
    return jnp.stack([logits_at(params, row, kw.get("eps", EPS))
                      for row in x])


def _row_stats(params, x, tokens, eps):
    """Per position of x (n, D): (max, mean, std, logsumexp) of its V
    logits and the logit of ``tokens`` (n,), with no (n, V) array."""
    xn = _rms(x, params["norm_f"], eps)
    n = x.shape[0]
    blocks = _row_blocks(params["lm_head"])
    rows = blocks.shape[1]

    def block(acc, xs):
        mx, s1, s2, se, got = acc
        b, e = xs
        lg = _mm(xn, _f32(e).T)                              # (n, rows)
        new = jnp.maximum(mx, lg.max(axis=1))
        se = se * jnp.exp(mx - new) + jnp.exp(lg - new[:, None]).sum(axis=1)
        at = tokens - b * rows
        mine = jnp.take_along_axis(
            lg, jnp.clip(at, 0, rows - 1)[:, None], axis=1)[:, 0]
        return (new, s1 + lg.sum(axis=1), s2 + (lg * lg).sum(axis=1), se,
                jnp.where((at >= 0) & (at < rows), mine, got)), None

    zero = jnp.zeros((n,), jnp.float32)
    (mx, s1, s2, se, got), _ = lax.scan(
        block, (jnp.full((n,), -jnp.inf), zero, zero, zero, zero),
        (jnp.arange(blocks.shape[0]), blocks))
    V = params["lm_head"].shape[0]
    mean = s1 / V
    std = jnp.sqrt(jnp.maximum(s2 / V - mean * mean, 0.0))
    return mx, mean, std, mx + jnp.log(se), got


def loss(params, ids, *, n_head, activation="silu", **kw):
    """Mean next-token cross entropy of (B, T) ids."""
    x = hidden_states(params, ids, n_head=n_head, activation=activation,
                      **kw)
    eps = kw.get("eps", EPS)
    rows = [_row_stats(params, x[b, :-1], ids[b, 1:], eps)
            for b in range(ids.shape[0])]
    return jnp.mean(jnp.stack([lse - got for _, _, _, lse, got in rows]))


def token_gaps(params, ids, positions, tokens, *, n_head,
               activation="silu", **kw):
    """For one sequence ``ids`` (1, T) and the tokens emitted after
    ``positions``: how far each emitted token's logit sits below that
    position's maximum, in standard deviations of the position's logits.
    0 means the emitted token is the reference's own argmax."""
    x = hidden_states(params, ids, n_head=n_head, activation=activation,
                      **kw)
    mx, _, std, _, got = _row_stats(params, x[0][positions], tokens,
                                    kw.get("eps", EPS))
    return (mx - got) / std
