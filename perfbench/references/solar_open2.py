"""Plain reference of Solar-Open2 (``model_type`` solar_open2) as ONE chip's
share of an expert-parallel deployment: forward pass, next-token loss and
the serving cells' token check in straightforward ``jax.numpy``, float32,
matmul precision "highest". No kernel, no cache, no chunkwise form, nothing
imported from the program.

The equations (ISSUE 54; Kimi Delta Attention, arXiv 2510.26692, for the
delta rule with a gate a key channel; DeepSeek-V3, arXiv 2412.19437, for
the ``noaux_tc`` gate), pre-norm blocks, RMSNorm ``rms(x; g) = x /
sqrt(mean(x^2) + eps) * g`` with eps 1e-5, no bias anywhere, no positional
encoding, an untied head:

    h <- h + Mix_i(rms(h; g1_i));   h <- h + MoE_i(rms(h; g2_i))
    logits = rms(h; g_f) W_head^T

  GQA   (a layer that has ``wqkvg``) q = x Wq, H heads of hd; k = x Wk,
        v = x Wv, Hkv heads of hd, query head h reads K/V head h // (H /
        Hkv); o = softmax(q k^T / sqrt(hd) + causal mask) v, NO rotary;
        o <- o * sigmoid(x Wg), elementwise, Wg (D, H hd); Mix = o Wo.
  KDA   [q~ | k~ | v~] = silu(conv_K(x [Wq | Wk | Wv])), a depthwise causal
        conv a channel, no bias: u'_t = sum_j w[j] u_{t-(K-1)+j}; H heads of
        dk, dk, dv; q_t = q~_t / sqrt(|q~_t|^2 + 1e-6) / sqrt(dk), k_t =
        k~_t / sqrt(|k~_t|^2 + 1e-6), a head;
        g_t = -exp(A_log_h) softplus(x_t Wf_a Wf_b + dt_bias) in R^(H x dk):
        A GATE A KEY CHANNEL, a_t = exp(g_t); beta_t = 2 sigmoid(x_t Wb), a
        head;
        S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
        (S in R^(dk x dv) a head, as the program's cache holds it);
        o_t = S_t^T q_t;
        Mix = (rms(o_t; g_o) * sigmoid(x_t Wg_a Wg_b)) Wo, rms a head over dv.
  Gate  s = sigmoid(x Wr) over ALL published experts (float32); the top_k
        largest s + b are chosen (no groups); weights = the chosen experts'
        s (without b) / their sum * routed_scale.
  MoE   sum_{e held} w_e W2_e (silu(W1_e x) * W3_e x) + Shared(x): THE
        SHARE. The chip holds experts offset .. offset + count - 1 of the
        published count (count = the expert arrays' leading axis); what the
        absent experts would add is left out and the partial sum goes on,
        as on one chip of the deployment before its exchange.

It reads the program's parameter tree, whose layout is the one thing shared
with the code under test (every projection input-major, x @ W):
  wte (V, D) | lm_head (V, D) | norm_f (D,) | layers: a list of dicts with
    norm1, norm2 (D,), gate (D, E) f32, gate_bias (E,) f32, moe_w1, moe_w3
    (held, D, F), moe_w2 (held, F, D), ws1 (D, 2F) [gate | up], ws2 (F, D)
    and
    GQA: wqkvg (D, H hd + 2 Hkv hd + H hd) [q | k | v | g], wo (H hd, D)
    KDA: in_proj (D, 2 H dk + H dv) [q | k | v], conv_w (K, 2 H dk + H dv),
         f_a (D, r), f_b (r, H dk), A_log (H,) f32, dt_bias (H dk,) f32,
         b_proj (D, H), g_a (D, r), g_b (r, H dv), o_norm (dv,), out_proj
         (H dv, D)
Parameters may arrive in bfloat16; each is cast to float32 as it is used,
one expert at a time.

Kept small enough to run on the chip beside the live engine at T = 33,792:
attention a K/V head at a time in blocks of ``ROW_BLOCK`` queries, the rule
``HEAD_BLOCK`` heads at a time as a sequential ``lax.scan`` over time, the
experts in blocks of ``POS_BLOCK`` positions, one expert's weights upcast
at a time, the head in blocks of rows and only at the positions asked
for.

The heads are read off the tree (``A_log``; ``wqkvg`` against ``wo``). What
the config.json gives and the tree's shapes do not are the keyword defaults
below: the published values. The tier-1 tests and
``perfbench/parity_solar_open2.py`` pass others, to run tiny sizes and to
show that the comparison tells the model from its neighbours.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ACTIVATIONS = {"silu": jax.nn.silu}
EPS = 1e-5                 # rms_norm_eps
L2_EPS = 1e-6              # under the root of q's and k's L2 norm
VOCAB_BLOCK = 4096         # rows of the head unembedded at once
ROW_BLOCK = 256            # queries a block of attention, a K/V head's
HEAD_BLOCK = 8             # delta-rule heads whose q, k, v exist at once
POS_BLOCK = 2048           # positions a block of a per-position product

# config.json of the published model
PUBLISHED = dict(top_k=8, routed_scale=1.0, experts_offset=0)
# the neighbours: each other value is a different model
VARIANTS = dict(
    state_dtype=jnp.float32,   # jnp.bfloat16: the matrix state rounded
    gate_per_channel=True,     # False: a head's channels share their mean
    gate_scoring="sigmoid",    # "softmax"
    bias_weighs=False,         # True: the correction bias also weighs
    beta_scale=2.0,            # 1.0: beta in (0, 1)
    attn_gate=True,            # False: no output gate on the GQA layer
    shared=True)               # False: no shared expert


def _f32(x):
    return x.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _blocked(fn, size, *xs):
    """``fn`` over the leading axis of ``xs`` (T, ...) in blocks of ``size``
    positions: nothing wider than a block exists at once."""
    T = xs[0].shape[0]
    if T <= size:
        return fn(*xs)
    n = -(-T // size)

    def cut(x):
        x = jnp.pad(x, ((0, n * size - T),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((n, size) + x.shape[1:])

    out = lax.map(lambda block: fn(*block), tuple(cut(x) for x in xs))
    return jax.tree.map(
        lambda y: y.reshape((n * size,) + y.shape[2:])[:T], out)


def is_kda(p):
    return "in_proj" in p


def delta_rule(q, k, v, log_a, beta, state, state_dtype=jnp.float32):
    """The delta rule with a gate a key channel, a token at a time. q, k
    (T, H, dk), v (T, H, dv), log_a (T, H, dk), beta (T, H), state (H, dk,
    dv) -> (o (T, H, dv), state)."""

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S                   # Diag(a) S
        kS = jnp.sum(k_t[:, :, None] * S, axis=1)           # (H, dv)
        S = S + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - kS)[:, None, :]
        if state_dtype != jnp.float32:
            # a round trip through the dtype that the compiler cannot
            # take out as excess precision
            info = jnp.finfo(state_dtype)
            S = lax.reduce_precision(S, info.nexp, info.nmant)
        return S, jnp.sum(q_t[:, :, None] * S, axis=1)

    state, o = lax.scan(step, state, (q, k, v, log_a, beta))
    return o, state


def _cols(w, j, n, axis=1):
    """Block j of ``w`` cut into n equal blocks along ``axis``, float32."""
    size = w.shape[axis] // n
    return _f32(lax.dynamic_slice_in_dim(w, j * size, size, axis))


def _kda(x, p, c, act, eps):
    """(T, D) normed -> Mix (T, D) of a delta-rule layer, HEAD_BLOCK heads
    at a time (a head's q, k, v, gates and state are its own)."""
    T = x.shape[0]
    H = p["A_log"].shape[0]
    dv = p["out_proj"].shape[0] // H
    K, ch = p["conv_w"].shape
    dk = (ch - H * dv) // (2 * H)
    HB = math.gcd(H, HEAD_BLOCK)
    n = H // HB
    beta = c["beta_scale"] * jax.nn.sigmoid(_mm(x, _f32(p["b_proj"])))
    xf, xg = _mm(x, _f32(p["f_a"])), _mm(x, _f32(p["g_a"]))
    A = jnp.exp(_f32(p["A_log"])).reshape(n, HB)
    w_q, w_k, w_v = (p["in_proj"][:, a:b] for a, b in (
        (0, H * dk), (H * dk, 2 * H * dk), (2 * H * dk, ch)))
    c_q, c_k, c_v = (p["conv_w"][:, a:b] for a, b in (
        (0, H * dk), (H * dk, 2 * H * dk), (2 * H * dk, ch)))

    def conv(w, cw, j):
        u = jnp.pad(_mm(x, _cols(w, j, n)), ((K - 1, 0), (0, 0)))
        cw = _cols(cw, j, n)
        return act(sum(u[i:i + T] * cw[i] for i in range(K))).reshape(
            T, HB, -1)

    def heads(acc, j):
        q = _l2(conv(w_q, c_q, j)) * dk ** -0.5
        k = _l2(conv(w_k, c_k, j))
        v = conv(w_v, c_v, j)
        f = _mm(xf, _cols(p["f_b"], j, n)) + _cols(p["dt_bias"], j, n, 0)
        g = -A[j][:, None] * jax.nn.softplus(f).reshape(T, HB, dk)
        if not c["gate_per_channel"]:
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True),
                                 g.shape)
        o, _ = delta_rule(
            q, k, v, g, lax.dynamic_slice_in_dim(beta, j * HB, HB, 1),
            jnp.zeros((HB, dk, dv), jnp.float32), c["state_dtype"])
        z = _mm(xg, _cols(p["g_b"], j, n)).reshape(T, HB, dv)
        o = _rms(o, p["o_norm"], eps) * jax.nn.sigmoid(z)
        return acc + _mm(o.reshape(T, HB * dv),
                         _cols(p["out_proj"], j, n, 0)), None

    out, _ = lax.scan(heads, jnp.zeros_like(x), jnp.arange(n))
    return out


def _gqa(x, p, c, n_head):
    """(T, D) normed -> Mix (T, D) of a softmax layer, a K/V head and the
    query heads that read it at a time."""
    T = x.shape[0]
    H = n_head
    hd = p["wo"].shape[0] // H
    Hkv = (p["wqkvg"].shape[1] // hd - 2 * H) // 2
    G = H // Hkv
    w_q, w_k, w_v, w_g = (p["wqkvg"][:, a * hd:b * hd] for a, b in (
        (0, H), (H, H + Hkv), (H + Hkv, H + 2 * Hkv),
        (H + 2 * Hkv, 2 * H + 2 * Hkv)))
    s = jnp.arange(T)[None, None, :]

    def group(acc, j):
        q = _mm(x, _cols(w_q, j, Hkv)).reshape(T, G, hd)
        k, v = _mm(x, _cols(w_k, j, Hkv)), _mm(x, _cols(w_v, j, Hkv))
        gate = _mm(x, _cols(w_g, j, Hkv))

        def attend(q, gate, t):
            sc = jnp.einsum("tgd,sd->gts", q, k, precision=HIGHEST) \
                / jnp.sqrt(jnp.float32(hd))
            pr = jax.nn.softmax(
                jnp.where(s <= t[None, :, None], sc, -jnp.inf), axis=-1)
            o = jnp.einsum("gts,sd->tgd", pr, v,
                           precision=HIGHEST).reshape(-1, G * hd)
            return o * jax.nn.sigmoid(gate) if c["attn_gate"] else o

        o = _blocked(attend, ROW_BLOCK, q, gate, jnp.arange(T))
        return acc + _mm(o, _cols(p["wo"], j, Hkv, 0)), None

    out, _ = lax.scan(group, jnp.zeros_like(x), jnp.arange(Hkv))
    return out


def route(x, gate_w, bias, c):
    """(T, D) -> (T, E) float32 routing weights, zero where not chosen."""
    logits = _mm(x, _f32(gate_w))
    E = logits.shape[1]
    s = jax.nn.sigmoid(logits) if c["gate_scoring"] == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    choose = s + _f32(bias)
    chosen = jnp.sum(jax.nn.one_hot(lax.top_k(choose, c["top_k"])[1], E),
                     axis=1) > 0
    w = jnp.where(chosen, choose if c["bias_weighs"] else s, 0.0)
    return w / jnp.sum(w, axis=-1, keepdims=True) * c["routed_scale"]


def moe(x, p, c, act):
    """(T, D) normed -> (T, D): the held experts' part and the shared
    expert."""
    held = p["moe_w1"].shape[0]

    def block(x):
        w = route(x, p["gate"], p["gate_bias"], c)
        w = lax.dynamic_slice_in_dim(w, c["experts_offset"], held, axis=1)

        def expert(acc, xs):
            w1, w3, w2, we = xs
            h = act(_mm(x, _f32(w1))) * _mm(x, _f32(w3))
            return acc + we[:, None] * _mm(h, _f32(w2)), None

        out, _ = lax.scan(expert, jnp.zeros_like(x),
                          (p["moe_w1"], p["moe_w3"], p["moe_w2"], w.T))
        if c["shared"]:
            gu = _mm(x, _f32(p["ws1"]))
            F = gu.shape[1] // 2
            out = out + _mm(act(gu[:, :F]) * gu[:, F:], _f32(p["ws2"]))
        return out

    return _blocked(block, POS_BLOCK, x)


def _constants(kw):
    unknown = set(kw) - set(PUBLISHED) - set(VARIANTS)
    if unknown:
        raise TypeError(f"unknown keywords {sorted(unknown)}")
    return {**PUBLISHED, **VARIANTS, **kw}


def hidden_states(params, ids, *, n_head, activation="silu", eps=EPS, **kw):
    """(B, T) token ids -> (B, T, D) float32 states after the last block."""
    act, c = ACTIVATIONS[activation], _constants(kw)

    def sequence(row):
        x = _f32(params["wte"][row])                         # (T, D)
        for p in params["layers"]:
            xn = _rms(x, p["norm1"], eps)
            x = x + (_kda(xn, p, c, act, eps) if is_kda(p)
                     else _gqa(xn, p, c, n_head))
            x = x + moe(_rms(x, p["norm2"], eps), p, c, act)
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
    """Mean next-token cross entropy of (B, T) ids. In blocks of
    POS_BLOCK positions, so that 32 k positions' statistics fit."""
    x = hidden_states(params, ids, n_head=n_head, activation=activation,
                      **kw)
    eps = kw.get("eps", EPS)

    def nll(x, tokens):
        _, _, _, lse, got = _row_stats(params, x, tokens, eps)
        return lse - got

    return jnp.mean(jnp.stack([
        _blocked(nll, POS_BLOCK, x[b, :-1], ids[b, 1:])
        for b in range(ids.shape[0])]))


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
