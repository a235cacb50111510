"""Plain reference of DeepSeek-V3.2-Exp (``model_type`` deepseek_v32) as ONE
chip's share of an expert-parallel deployment: forward pass, next-token loss
and the serving cells' token check in straightforward ``jax.numpy``, float32,
matmul precision "highest". No kernel, no cache, no absorbed form, nothing
imported from the program.

The equations (ISSUE 43; DeepSeek-V3, arXiv 2412.19437, for MLA, the gate and
YaRN; the model's public inference code for the lightning indexer), pre-norm
blocks, RMSNorm ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``, eps 1e-6, no
bias but the index key's LayerNorm, an untied head:

    h = x + Attn(rms(x; g1));  out = h + FFN(rms(h; g2))
    logits = rms(out; g_f) W_head^T

  MLA     c_q = rms(x Wq_a);  [q_nope | q_pe]_h = c_q Wq_b, H heads of dn + dr;
          [c | k_r] = x Wkv_a;  c_kv = rms(c);  q_pe, k_pe = rope(q_pe, k_r),
          YaRN on INTERLEAVED pairs, k_pe one head shared by all;
          k_nope_h = c_kv Wk_b^h, v_h = c_kv Wv_b^h;
          score(t, h, s) = (q_nope_h(t) . k_nope_h(s) + q_pe_h(t) . k_pe(s))
          * sigma,  sigma = (dn + dr)^-0.5 * m^2,  m = 0.1 mscale_all_dim
          ln(factor) + 1;  softmax over the SELECTED causal keys only;
          Attn = concat_h(sum_s p v_h(s)) Wo.
  DSA     q^I = c_q Wi_q, Hi heads of di;  k^I = LayerNorm(x Wi_k) (one head);
          rope on the FIRST dr dims of both, SPLIT halves;
          w = (x Wi_w) * Hi^-0.5 * di^-0.5;
          I(t, s) = sum_j w_j(t) relu(q^I_j(t) . k^I(s)),  s <= t;
          selected(t) = the min(index_topk, t + 1) causal keys of largest
          I(t, .): s is selected where I(t, s) >= the k-th largest causal
          I(t, .) (keys that tie with the k-th are all kept; with random
          weights there are none).
  Gate    s = sigmoid(x Wg) over ALL published experts (float32); choose on
          s + b: a group's score is the sum of its two largest, the
          topk_group best of n_group groups stay, then the top_k largest
          s + b inside them; weights = the chosen experts' s (without b) /
          their sum * routed_scaling_factor.
  FFN     sum_{e held} w_e W2_e (silu(W1_e x) * W3_e x) + Shared(x): THE
          SHARE. The chip holds experts offset .. offset + count - 1 of the
          published count (count = the expert arrays' leading axis); what
          the absent experts would add is left out and the partial sum
          goes on, as on one chip of the deployment before its exchange.
          The leading ``first_k_dense`` layers are one SwiGLU instead (a
          layer is dense where it has ``w1``).

It reads the program's parameter tree, whose layout is the one thing shared
with the code under test (every projection input-major, x @ W):
  wte (V, D) | lm_head (V, D) | norm_f (D,) | layers: a list of dicts with
    norm1, norm2 (D,), wq_a (D, Rq), q_norm (Rq,), wq_b (Rq, H (dn + dr))
    [a head: nope | rope], wkv_a (D, R + dr) [latent | rope key], kv_norm
    (R,), wk_b (H, dn, R), wv_b (H, R, dv), wo (H dv, D), wi_q (Rq, Hi di)
    [a head: rope dims first], wi_k (D, di), ik_norm_w, ik_norm_b (di,),
    wi_w (D, Hi), and
    dense: w1 (D, 2F) [gate | up], w2 (F, D)
    sparse: gate (D, E) f32, gate_bias (E,) f32, moe_w1, moe_w3 (held, D, Fm),
            moe_w2 (held, Fm, D), ws1 (D, 2 Fs) [gate | up], ws2 (Fs, D)
Parameters may arrive in bfloat16; each is cast to float32 as it is used,
a block of heads, of columns or one expert at a time.

Kept small enough to run on the chip beside the live engine at T = 16,896:
queries in blocks of rows, heads in blocks of ``HEAD_BLOCK``, the dense
SwiGLU in blocks of ``FF_BLOCK`` columns, one expert's weights upcast at a
time, the head in blocks of rows and only at the positions asked for. The
selection is kept as one (T, T) boolean a layer.

What the config.json gives and the tree's shapes do not (``index_topk``, the
gate's groups, the rotary constants, the held experts' offset) are the
keyword defaults below: the published values. The tier-1 tests and
``perfbench/parity_dsv32.py`` pass others, to run tiny sizes and to show that
the comparison tells the model from its neighbours.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ACTIVATIONS = {"silu": jax.nn.silu}
EPS = 1e-6                 # rms_norm_eps and the index key's LayerNorm
VOCAB_BLOCK = 4096         # rows of the head unembedded at once
ROW_BLOCK = 512            # queries a block of attention
INDEX_ROW_BLOCK = 128      # queries a block of index scores (Hi x rows x T)
HEAD_BLOCK = 8             # heads whose q, k, v exist at once
FF_BLOCK = 2048            # columns of the dense SwiGLU at once

# config.json of the published model (and its rope_scaling group)
PUBLISHED = dict(
    index_topk=2048, n_group=8, topk_group=4, top_k=8, routed_scale=2.5,
    experts_offset=0, rope_theta=10000.0, rope_factor=40.0,
    rope_original=4096, beta_fast=32.0, beta_slow=1.0, mscale=1.0,
    mscale_all_dim=1.0)
# the neighbours: each False / other value is a different model
VARIANTS = dict(
    select=True,           # False: dense MLA over every causal key
    bias_weighs=False,     # True: the correction bias also in the weights
    group_limit=True,      # False: top_k over all experts
    gate_scoring="sigmoid",  # "softmax"
    shared=True,           # False: no shared expert
    mscale_squared=True,   # False: sigma without m^2
    yarn=True,             # False: plain rope
    index_dtype=None)      # "bfloat16": index queries and keys rounded


def _f32(x):
    return x.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * _f32(w) + _f32(b)


def _blocked(fn, rows, *xs):
    """``fn`` over the leading axis of ``xs`` (T, ...) in blocks of ``rows``
    positions: nothing wider than a block of scores exists at once."""
    T = xs[0].shape[0]
    if T <= rows:
        return fn(*xs)
    n = -(-T // rows)

    def cut(x):
        x = jnp.pad(x, ((0, n * rows - T),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((n, rows) + x.shape[1:])

    out = lax.map(lambda block: fn(*block), tuple(cut(x) for x in xs))
    return jax.tree.map(
        lambda y: y.reshape((n * rows,) + y.shape[2:])[:T], out)


def _cols(w, j, n, axis):
    """Block j of n along ``axis`` of a weight, upcast once it is cut."""
    size = w.shape[axis] // n
    return _f32(lax.dynamic_slice_in_dim(w, j * size, size, axis))


# ----------------------------------------------------------------- rotary
def rope_frequencies(dim, c):
    """The dim / 2 inverse frequencies, YaRN-scaled: correction dims from
    beta_fast and beta_slow at the original context, a linear ramp between
    them, f / factor blended in by the ramp."""
    f = c["rope_theta"] ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not c["yarn"]:
        return f

    def correction_dim(rotations):
        return dim * math.log(c["rope_original"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(c["rope_theta"]))

    low = max(math.floor(correction_dim(c["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(c["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    smooth = 1.0 - ramp
    return f / c["rope_factor"] * (1.0 - smooth) + f * smooth


def _angles(T, dim, c):
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * rope_frequencies(dim, c)[None, :]
    return jnp.cos(ang), jnp.sin(ang)              # cos, sin unscaled


def rope_interleaved(x, cos, sin):
    """Pairs (2i, 2i + 1); x (T, ..., dr), cos / sin (T, dr / 2)."""
    shape = x.shape
    x = x.reshape(shape[:-1] + (shape[-1] // 2, 2))
    a, b = x[..., 0], x[..., 1]
    cos = cos.reshape((shape[0],) + (1,) * (x.ndim - 3) + cos.shape[1:])
    sin = sin.reshape(cos.shape)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(shape)


def rope_halves(x, cos, sin):
    """Pairs (i, i + dr / 2); x (T, ..., dr)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos = cos.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + cos.shape[1:])
    sin = sin.reshape(cos.shape)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def softmax_scale(head_dim, c):
    scale = head_dim ** -0.5
    if c["yarn"] and c["mscale_squared"]:
        m = 0.1 * c["mscale_all_dim"] * math.log(c["rope_factor"]) + 1.0
        scale *= m * m
    return scale


# -------------------------------------------------------------- selection
def _round_to(x, dtype):
    if dtype is None:
        return x
    info = jnp.finfo(jnp.dtype(dtype))
    return lax.reduce_precision(x, info.nexp, info.nmant)


def index_scores(x, cq, p, c, eps):
    """(T, T) float32 I(t, s), -inf where s > t."""
    T = x.shape[0]
    Hi = p["wi_w"].shape[1]
    di = p["wi_k"].shape[1]
    dr = p["wkv_a"].shape[1] - p["kv_norm"].shape[0]
    cos, sin = _angles(T, dr, c)
    k = _layer_norm(_mm(x, _f32(p["wi_k"])), p["ik_norm_w"], p["ik_norm_b"],
                    eps)
    k = jnp.concatenate([rope_halves(k[:, :dr], cos, sin), k[:, dr:]], -1)
    k = _round_to(k, c["index_dtype"])
    w = _mm(x, _f32(p["wi_w"])) * Hi ** -0.5 * di ** -0.5
    wi_q = _f32(p["wi_q"])
    s = jnp.arange(T)[None, :]

    def rows(cq, cos, sin, w, t):
        q = _mm(cq, wi_q).reshape(-1, Hi, di)
        q = jnp.concatenate([rope_halves(q[..., :dr], cos, sin),
                             q[..., dr:]], -1)
        q = _round_to(q, c["index_dtype"])
        dots = jnp.einsum("thd,sd->ths", q, k, precision=HIGHEST)
        score = jnp.sum(w[:, :, None] * jax.nn.relu(dots), axis=1)
        return jnp.where(s <= t[:, None], score, -jnp.inf)

    return _blocked(rows, INDEX_ROW_BLOCK, cq, cos, sin, w, jnp.arange(T))


def selection(scores, topk):
    """(T, T) bool: key s is read by query t. ``scores`` are -inf where s >
    t; row t keeps the keys that reach its min(topk, t + 1)-th largest."""
    T = scores.shape[0]

    def rows(sc, t):
        k = jnp.minimum(topk, t + 1)
        ordered = -jnp.sort(-sc, axis=-1)
        kth = jnp.take_along_axis(ordered, (k - 1)[:, None], axis=-1)
        return (sc >= kth) & (sc > -jnp.inf)

    return _blocked(rows, INDEX_ROW_BLOCK, scores, jnp.arange(T))


# -------------------------------------------------------------- the layer
def _attention(x, p, c, eps, want_mask=False):
    """(T, D) normed input -> Attn (T, D) of a latent layer."""
    T, D = x.shape
    H, dn, R = p["wk_b"].shape
    dv = p["wv_b"].shape[2]
    dr = p["wkv_a"].shape[1] - R
    cos, sin = _angles(T, dr, c)
    scale = softmax_scale(dn + dr, c)

    cq = _rms(_mm(x, _f32(p["wq_a"])), p["q_norm"], eps)
    ckr = _mm(x, _f32(p["wkv_a"]))
    ckv = _rms(ckr[:, :R], p["kv_norm"], eps)
    k_pe = rope_interleaved(ckr[:, R:], cos, sin)              # (T, dr)

    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    mask = selection(index_scores(x, cq, p, c, eps), c["index_topk"]) \
        if c["select"] else causal
    if want_mask:
        return mask

    HB = math.gcd(H, HEAD_BLOCK)

    def heads(acc, j):
        q = _mm(cq, _cols(p["wq_b"], j, H // HB, 1)).reshape(T, HB, dn + dr)
        q_nope, q_pe = q[..., :dn], rope_interleaved(q[..., dn:], cos, sin)
        k_nope = jnp.einsum("tr,hdr->thd", ckv,
                            _cols(p["wk_b"], j, H // HB, 0),
                            precision=HIGHEST)
        v = jnp.einsum("tr,hrd->thd", ckv, _cols(p["wv_b"], j, H // HB, 0),
                       precision=HIGHEST)

        def rows(q_nope, q_pe, mask):
            sc = (jnp.einsum("thd,shd->hts", q_nope, k_nope,
                             precision=HIGHEST)
                  + jnp.einsum("thd,sd->hts", q_pe, k_pe,
                               precision=HIGHEST)) * scale
            pr = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("hts,shd->thd", pr, v, precision=HIGHEST)

        o = _blocked(rows, ROW_BLOCK, q_nope, q_pe, mask)
        return acc + _mm(o.reshape(T, HB * dv),
                         _cols(p["wo"], j, H // HB, 0)), None

    out, _ = lax.scan(heads, jnp.zeros((T, D), jnp.float32),
                      jnp.arange(H // HB))
    return out


def route(x, gate_w, bias, c):
    """(T, D) -> (T, E) float32 routing weights, zero where not chosen."""
    logits = _mm(x, _f32(gate_w))
    E = logits.shape[1]
    s = jax.nn.sigmoid(logits) if c["gate_scoring"] == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    choose = s + _f32(bias)
    if c["group_limit"]:
        G = c["n_group"]
        grouped = choose.reshape(-1, G, E // G)
        group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)    # (T, G)
        kept = lax.top_k(group_score, c["topk_group"])[1]
        keep = jnp.sum(jax.nn.one_hot(kept, G), axis=1) > 0         # (T, G)
        choose = jnp.where(jnp.repeat(keep, E // G, axis=1), choose,
                           -jnp.inf)
    chosen = jnp.sum(jax.nn.one_hot(lax.top_k(choose, c["top_k"])[1], E),
                     axis=1) > 0
    w = jnp.where(chosen, choose if c["bias_weighs"] else s, 0.0)
    return w / jnp.sum(w, axis=-1, keepdims=True) * c["routed_scale"]


def _swiglu(x, w1, w2, act):
    """(T, D) through one SwiGLU, w1 (D, 2F) [gate | up], in column blocks."""
    F = w2.shape[0]
    size = math.gcd(F, FF_BLOCK)

    def block(acc, j):
        g = _mm(x, _f32(lax.dynamic_slice_in_dim(w1, j * size, size, 1)))
        u = _mm(x, _f32(lax.dynamic_slice_in_dim(w1, F + j * size, size, 1)))
        return acc + _mm(act(g) * u, _cols(w2, j, F // size, 0)), None

    out, _ = lax.scan(block, jnp.zeros_like(x), jnp.arange(F // size))
    return out


def _ffn(x, p, act, c):
    if "w1" in p:
        return _swiglu(x, p["w1"], p["w2"], act)
    held = p["moe_w1"].shape[0]
    w = route(x, p["gate"], p["gate_bias"], c)
    w = lax.dynamic_slice_in_dim(w, c["experts_offset"], held, axis=1)

    def expert(acc, xs):
        w1, w3, w2, we = xs
        h = act(_mm(x, _f32(w1))) * _mm(x, _f32(w3))
        return acc + we[:, None] * _mm(h, _f32(w2)), None

    out, _ = lax.scan(expert, jnp.zeros_like(x),
                      (p["moe_w1"], p["moe_w3"], p["moe_w2"], w.T))
    if c["shared"]:
        out = out + _swiglu(x, p["ws1"], p["ws2"], act)
    return out


def _constants(kw):
    unknown = set(kw) - set(PUBLISHED) - set(VARIANTS)
    if unknown:
        raise TypeError(f"unknown keywords {sorted(unknown)}")
    return {**PUBLISHED, **VARIANTS, **kw}


def hidden_states(params, ids, *, n_head=None, activation="silu", eps=EPS,
                  **kw):
    """(B, T) token ids -> (B, T, D) float32 states after the last block.
    ``n_head`` is the runner's; the heads are read off the tree."""
    act, c = ACTIVATIONS[activation], _constants(kw)

    def sequence(row):
        x = _f32(params["wte"][row])                         # (T, D)
        for p in params["layers"]:
            x = x + _attention(_rms(x, p["norm1"], eps), p, c, eps)
            x = x + _ffn(_rms(x, p["norm2"], eps), p, act, c)
        return x

    return jnp.stack([sequence(ids[b]) for b in range(ids.shape[0])])


def selection_masks(params, ids, *, layers=None, eps=EPS, **kw):
    """One sequence ``ids`` (T,) -> a list of (T, T) bool, one for each of
    ``layers`` (all of them): the keys each query reads. Small sizes, and
    the parity script, which asks for a layer at a time."""
    act, c = ACTIVATIONS["silu"], _constants(kw)
    n = len(params["layers"])
    layers = tuple(range(n)) if layers is None else tuple(layers)
    x = _f32(params["wte"][ids])
    masks = []
    for i, p in enumerate(params["layers"][:max(layers) + 1]):
        xn = _rms(x, p["norm1"], eps)
        if i in layers:
            masks.append(_attention(xn, p, c, eps, want_mask=True))
        if i < max(layers):
            x = x + _attention(xn, p, c, eps)
            x = x + _ffn(_rms(x, p["norm2"], eps), p, act, c)
    return masks


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


def loss(params, ids, *, n_head=None, activation="silu", **kw):
    """Mean next-token cross entropy of (B, T) ids."""
    x = hidden_states(params, ids, n_head=n_head, activation=activation,
                      **kw)
    eps = kw.get("eps", EPS)
    rows = [_row_stats(params, x[b, :-1], ids[b, 1:], eps)
            for b in range(ids.shape[0])]
    return jnp.mean(jnp.stack([lse - got for _, _, _, lse, got in rows]))


def token_gaps(params, ids, positions, tokens, *, n_head=None,
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
