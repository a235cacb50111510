"""Plain reference of Phi-4-mini-flash-reasoning (SambaY, a decoder-hybrid-
decoder): forward pass, next-token loss and the serving cells' token check
in straightforward ``jax.numpy``, float32, matmul precision "highest". No
kernel, no cache, no ring, no batching, nothing imported from the program.

The published equations it follows (Ren et al. 2025, "Decoder-Hybrid-Decoder
Architecture for Efficient Reasoning with Long Generation", arXiv
2507.06607; DIFF Transformer, arXiv 2410.05258; Mamba, arXiv 2312.00752; HF
``modeling_phi4flash.py``), for L layers (32 published), pre-norm, NO
positional encoding of any kind:

    h <- h + Mix_i(LN1_i(h));   h <- h + MLP_i(LN2_i(h))
    MLP(x) = (silu(g) * u) W2,  [g | u] = x W1            (no bias)
    logits = LN_f(h) E^T                                  (tied embedding)

with LN = LayerNorm with scale and bias, eps 1e-5, and Mix_i by the layer's
place: i < L/2 even: Mamba; i < L/2 odd: differential attention under a
causal window of W keys; i = L/2: Mamba, whose scan output is also kept as
the memory m; i = L/2 + 1: differential attention, causal, full;
i >= L/2 + 2 even: Gated Memory Unit over m; i >= L/2 + 2 odd: differential
cross-attention over layer L/2 + 1's keys and values.

  Mamba   [u | z] = x W_in;  u' = silu(conv_K(u) + b_c)   (causal, per
          channel: u'_t = sum_k w[:, k] u_{t-(K-1)+k});
          [dt | B | C] = u' W_x  (R, N, N);  D_t = softplus(dt W_dt + b_dt);
          A = -exp(A_log);  s_t = exp(D_t A) s_{t-1} + (D_t u'_t) (x) B_t;
          y_t = s_t C_t + D u'_t;  Mix = (y * silu(z)) W_out;  m = y.
  GMU     Mix = (m * silu(x W_1)) W_2, m_t the same position's memory.
  DiffAttn q = x W_q + b_q (H heads of hd), own k, v (H_kv heads) where the
          layer has them. Adjacent heads pair: q1 = q[0::2], q2 = q[1::2],
          k1, k2, v1, v2 likewise; query head j of a half reads KV head
          j // (H / H_kv) of the same half. A1 = softmax(q1 k1^T / sqrt(hd)
          + mask), A2 likewise; V = [v1 | v2]; lam = exp(lq1 . lk1) -
          exp(lq2 . lk2) + lam0, lam0 = 0.8 - 0.6 exp(-0.3 i);
          o = rms(A1 V - lam A2 V; g) (1 - lam0)  (over the 2 hd lanes, eps
          1e-5);  Mix = o W_o + b_o.

It reads the program's parameter tree, whose layout is the one thing shared
with the code under test (every projection input-major, x @ W):
  wte (V, D) | ln_f_s, ln_f_b (D,) | layers: a list of L dicts, each with
  ln1_s, ln1_b, ln2_s, ln2_b (D,), w1 (D, 2F) [gate | up], w2 (F, D), and
    Mamba:  in_proj (D, 2 Din) [u | z], conv_w (Din, K), conv_b (Din,),
            x_proj (Din, R + 2N), dt_w (R, Din), dt_b (Din,) [f32],
            A_log (N, Din) [f32], D_skip (Din,) [f32], out_proj (Din, D)
    attention with its own K/V: wqkv (D, (H + 2 H_kv) hd) [q | k | v],
            bqkv, wo (D, D), bo, lq1, lk1, lq2, lk2 (hd,), subln (2 hd,)
    cross-attention: wq (D, H hd), bq, wo, bo, the four lambda vectors, subln
    GMU:    g_in (D, Din), g_out (Din, D)
Parameters may arrive in bfloat16; each is cast to float32 as it is used.

Kept small enough to run on the chip beside the engine at T = 4096:
attention a head pair at a time, the SSM a sequential ``lax.scan`` over
time, and the 200,064-row unembedding in blocks of rows, so that neither
the float32 embedding (2 GB) nor (positions x rows) logits (2.5 GB at 3,072
positions) ever exist for ``token_gaps`` and ``loss``.

``window``, ``learned_lambda``, ``memory_after_gate`` and ``state_dtype``
default to the published values; the tier-1 tests and
``perfbench/parity_phi4flash.py`` pass others to show that the comparison
tells the model from its neighbours.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ACTIVATIONS = {"silu": jax.nn.silu}
WINDOW = 512               # sliding_window
EPS = 1e-5                 # layer_norm_eps, and the sub-norm's
VOCAB_BLOCK = 4096         # rows of the embedding unembedded at once


def _f32(x):
    return x.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _ln(x, s, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * _f32(s) + _f32(b)


def layer_kind(i, n_layer):
    """'mamba' | 'window' | 'memory' | 'full' | 'gmu' | 'cross'."""
    half = n_layer // 2
    if i < half:
        return "window" if i % 2 else "mamba"
    if i == half:
        return "memory"
    if i == half + 1:
        return "full"
    return "cross" if i % 2 else "gmu"


def _mamba(x, p, state_dtype):
    """(T, D) -> (Mix (T, D), the scan output y (T, Din), silu(z))."""
    T = x.shape[0]
    A_log = _f32(p["A_log"])                                 # (N, Din)
    N, d_in = A_log.shape
    K, R = p["conv_w"].shape[1], p["dt_w"].shape[0]
    uz = _mm(x, _f32(p["in_proj"]))
    u, z = uz[:, :d_in], uz[:, d_in:]
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    w = _f32(p["conv_w"])
    conv = sum(padded[k:k + T] * w[:, k] for k in range(K)) \
        + _f32(p["conv_b"])
    u1 = jax.nn.silu(conv)
    dbc = _mm(u1, _f32(p["x_proj"]))
    dt = jax.nn.softplus(_mm(dbc[:, :R], _f32(p["dt_w"]))
                         + _f32(p["dt_b"]))                  # (T, Din)
    Bm, Cm = dbc[:, R:R + N], dbc[:, R + N:]
    A = -jnp.exp(A_log)

    def step(s, xs):
        d_t, u_t, b_t, c_t = xs
        s = jnp.exp(d_t[None, :] * A) * s \
            + (d_t * u_t)[None, :] * b_t[:, None]
        if state_dtype != jnp.float32:
            # a round trip through the dtype that the compiler cannot
            # take out as excess precision
            info = jnp.finfo(state_dtype)
            s = lax.reduce_precision(s, info.nexp, info.nmant)
        return s, jnp.sum(s * c_t[:, None], axis=0)

    _, y = lax.scan(step, jnp.zeros((N, d_in), jnp.float32),
                    (dt, u1, Bm, Cm))
    y = y + _f32(p["D_skip"]) * u1
    gate = jax.nn.silu(z)
    return _mm(y * gate, _f32(p["out_proj"])), y, gate


def _diff_attention(q, k, v, p, i, window, learned_lambda, eps):
    """q (T, H, hd), k / v (S = T, H_kv, hd) -> (T, H hd / 1): causal,
    under ``window`` keys if not 0, a head pair at a time."""
    T, H, hd = q.shape
    q1, q2 = q[:, 0::2], q[:, 1::2]
    k1, k2 = k[:, 0::2], k[:, 1::2]
    v1, v2 = v[:, 0::2], v[:, 1::2]
    rep = q1.shape[1] // k1.shape[1]
    t, s = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = s <= t
    if window:
        mask = mask & (t - s < window)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * i)
    lam = lam0
    if learned_lambda:
        lam = jnp.exp(jnp.sum(_f32(p["lq1"]) * _f32(p["lk1"]))) \
            - jnp.exp(jnp.sum(_f32(p["lq2"]) * _f32(p["lk2"]))) + lam0
    g = _f32(p["subln"])

    def softmax(a, b):
        sc = _mm(a, b.T) / jnp.sqrt(jnp.float32(hd))
        return jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)

    def head(j):
        kv = j // rep
        V = jnp.concatenate([v1[:, kv], v2[:, kv]], axis=-1)  # (T, 2 hd)
        a = _mm(softmax(q1[:, j], k1[:, kv]), V) \
            - lam * _mm(softmax(q2[:, j], k2[:, kv]), V)
        a = a * lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps) * g
        return a * (1.0 - lam0)

    out = lax.map(head, jnp.arange(H // 2))                  # (H/2, T, 2hd)
    return out.transpose(1, 0, 2).reshape(T, H * hd)


def hidden_states(params, ids, *, n_head, activation="silu", window=WINDOW,
                  learned_lambda=True, memory_after_gate=False,
                  state_dtype=jnp.float32, eps=EPS):
    """(B, T) token ids -> (B, T, D) float32 states after the last block."""
    act = ACTIVATIONS[activation]
    layers = params["layers"]
    L = len(layers)

    def sequence(row):
        x = _f32(params["wte"][row])                         # (T, D)
        T, D = x.shape
        hd = D // n_head
        memory = shared = None
        for i, p in enumerate(layers):
            kind = layer_kind(i, L)
            h = _ln(x, p["ln1_s"], p["ln1_b"], eps)
            if kind in ("mamba", "memory"):
                mix, y, gate = _mamba(h, p, state_dtype)
                if kind == "memory":
                    memory = y * gate if memory_after_gate else y
            elif kind == "gmu":
                mix = _mm(memory * act(_mm(h, _f32(p["g_in"]))),
                          _f32(p["g_out"]))
            else:
                if kind == "cross":
                    q = _mm(h, _f32(p["wq"])) + _f32(p["bq"])
                    k, v = shared
                else:
                    qkv = _mm(h, _f32(p["wqkv"])) + _f32(p["bqkv"])
                    q = qkv[:, :D]
                    k, v = (a.reshape(T, -1, hd) for a in jnp.split(
                        qkv[:, D:], 2, axis=1))
                    if kind == "full":
                        shared = (k, v)
                a = _diff_attention(
                    q.reshape(T, n_head, hd), k, v, p, i,
                    window if kind == "window" else 0, learned_lambda, eps)
                mix = _mm(a, _f32(p["wo"])) + _f32(p["bo"])
            x = x + mix
            h = _ln(x, p["ln2_s"], p["ln2_b"], eps)
            gu = _mm(h, _f32(p["w1"]))
            F = gu.shape[1] // 2
            x = x + _mm(act(gu[:, :F]) * gu[:, F:], _f32(p["w2"]))
        return x

    return jnp.stack([sequence(ids[b]) for b in range(ids.shape[0])])


def _row_blocks(E):
    """The tied embedding as (blocks, rows, D): the largest divisor of its
    row count that is at most VOCAB_BLOCK rows a block."""
    V = E.shape[0]
    rows = max(r for r in range(1, min(V, VOCAB_BLOCK) + 1) if V % r == 0)
    return E.reshape(V // rows, rows, E.shape[1])


def _final(params, x, eps):
    return _ln(x, params["ln_f_s"], params["ln_f_b"], eps)


def logits_at(params, x, eps=EPS):
    """(n, D) states -> (n, V) float32 logits through the final LayerNorm
    and the tied embedding, a block of rows at a time."""
    xn = _final(params, x, eps)
    out = lax.map(lambda e: _mm(xn, _f32(e).T), _row_blocks(params["wte"]))
    return out.transpose(1, 0, 2).reshape(x.shape[0], -1)


def logits(params, ids, **kw):
    """(B, T) -> (B, T, V): every position's logits (small sizes only)."""
    x = hidden_states(params, ids, **kw)
    return jnp.stack([logits_at(params, row, kw.get("eps", EPS))
                      for row in x])


def _row_stats(params, x, tokens, eps):
    """Per position of x (n, D): (max, mean, std, logsumexp) of its V
    logits and the logit of ``tokens`` (n,), with no (n, V) array."""
    xn = _final(params, x, eps)
    n = x.shape[0]

    blocks = _row_blocks(params["wte"])
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
    V = params["wte"].shape[0]
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
