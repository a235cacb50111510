"""Plain reference of the DeepSeek-V3 family (``model_type`` deepseek_v3;
Kanana-2-30B-A3B is HF ``DeepseekV3`` with the query projection direct) as
ONE chip's share of an expert-parallel group: forward pass, next-token loss
and its gradients in straightforward ``jax.numpy``, float32, matmul
precision "highest". No kernel, nothing imported from the program.

The equations (ISSUE 47; HF ``modeling_deepseek_v3`` with ``q_lora_rank``
null; DeepSeek-V3, arXiv 2412.19437, for MLA and the gate), pre-norm blocks,
RMSNorm ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``, eps 1e-6, no bias, an
untied head:

    h = x + Attn(rms(x; g1));  out = h + FFN(rms(h; g2))
    logits = rms(out; g_f) W_head^T;  loss = mean next-token cross entropy

  MLA     [q_nope | q_pe]_h = x Wq, H heads of dn + dr (with a query latent,
          where the tree has ``wq_a``: c_q = rms(x Wq_a), then c_q Wq_b);
          [c | k_r] = x Wkv_a;  c' = rms(c; g_kv);
          k_nope_h = c' Wk_b^h,  v_h = c' Wv_b^h;
          q_pe, k_pe = rope(q_pe, k_r), theta 1e6, no scaling, on
          INTERLEAVED pairs (2i, 2i + 1), k_pe one head shared by all;
          score(t, h, s) = (q_nope_h(t) . k_nope_h(s) + q_pe_h(t) . k_pe(s))
          * (dn + dr)^-0.5;  causal softmax;  Attn = concat_h(P v_h) Wo.
  Gate    s = sigmoid(x Wg) over ALL published experts; choose the top_k
          largest of s + b (n_group = topk_group = 1: no group limit; with
          groups, a group's score is the sum of its two largest s + b and
          only the topk_group best groups' experts can be chosen);
          weights = the chosen experts' s (without b) / their sum
          * routed_scaling_factor.
  FFN     sum_{e chosen and held} w_e W2_e (silu(W1_e x) * W3_e x)
          + Shared(x): THE SHARE. The chip holds experts offset .. offset +
          count - 1 of the published count (count = the expert arrays'
          leading axis); what the absent experts would add is left out and
          the partial sum goes on, as on one chip of the group before its
          exchange. Shared is ONE SwiGLU of width n_shared x moe width.
          The leading dense layers are one SwiGLU instead (a layer is dense
          where it has ``w1``).

Departures from the published description, each for memory alone and none
for the mathematics: every held expert is computed on EVERY token and
masked by the routing (no sort, no gather); attention runs in blocks of
``ROW_BLOCK`` queries against every key; a layer, a block of queries and an
expert are recomputed in the backward pass (``jax.checkpoint``), so that
8,192 positions fit on the chip beside a live engine. The correction bias's
gradient is the zero the mathematics gives (it only chooses).

It reads the program's parameter tree, whose layout is the one thing shared
with the code under test (every projection input-major, x @ W):
  wte (V, D) | lm_head (V, D) | norm_f (D,) | layers: a list of dicts with
    norm1, norm2 (D,), wq (D, H (dn + dr)) [a head: nope | rope] (or wq_a
    (D, Rq), q_norm (Rq,), wq_b (Rq, H (dn + dr))), wkv_a (D, R + dr)
    [latent | rope key], kv_norm (R,), wk_b (H, dn, R), wv_b (H, R, dv),
    wo (H dv, D), and
    dense: w1 (D, 2F) [gate | up], w2 (F, D)
    sparse: gate (D, E), gate_bias (E,) f32, moe_w1, moe_w3 (held, D, Fm),
            moe_w2 (held, Fm, D), ws1 (D, 2 Fs) [gate | up], ws2 (Fs, D)
Parameters may arrive in bfloat16; each is cast to float32 as it is used.

What config.json gives and the tree's shapes do not are the keyword
defaults below: the published values. The tier-1 tests and
``perfbench/parity_kanana2.py`` pass others, to run tiny sizes and to show
that the comparison tells the model from its neighbours.
"""

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ACTIVATIONS = {"silu": jax.nn.silu}
EPS = 1e-6                 # rms_norm_eps
ROW_BLOCK = 512            # queries a block of attention
VOCAB_ROWS = 1024          # positions unembedded at once

# config.json of the published model
PUBLISHED = dict(top_k=6, n_group=1, topk_group=1, routed_scale=2.448,
                 experts_offset=0, rope_theta=1e6)
# the neighbours: each other value is a different model
VARIANTS = dict(
    bias_weighs=False,     # True: the correction bias also in the weights
    renormalise=True,      # False: the chosen scores are not divided
    scale_width=None,      # a number: softmax scale width^-0.5, not dn + dr
    rope_interleave=True,  # False: rotary on split halves
    shared_width=None,     # a number: the shared SwiGLU cut to that width
    leak=False)            # True: an absent expert's rows through a held one


def _f32(x):
    return x.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, _f32(b), precision=HIGHEST)


def _rms(x, g):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) \
        * _f32(g)


def _rope(x, cos, sin, interleave):
    """x (T, ..., dr), cos / sin (T, dr / 2)."""
    shape = x.shape
    cos = cos.reshape(shape[:1] + (1,) * (x.ndim - 2) + cos.shape[-1:])
    sin = sin.reshape(cos.shape)
    if interleave:
        x = x.reshape(shape[:-1] + (shape[-1] // 2, 2))
        a, b = x[..., 0], x[..., 1]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(shape)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(x, w1, w2, act, width=None):
    F = w2.shape[0]
    gu = _mm(x, w1)
    h = act(gu[:, :F]) * gu[:, F:]
    if width is not None:
        return _mm(h[:, :width], w2[:width])
    return _mm(h, w2)


def _attention(x, p, kw):
    """x (T, D) normed -> (T, D): one sequence."""
    T = x.shape[0]
    H, dn, R = p["wk_b"].shape
    dv = p["wv_b"].shape[-1]
    dr = p["wkv_a"].shape[1] - R
    q = _mm(x, p["wq"]) if "wq" in p \
        else _mm(_rms(_mm(x, p["wq_a"]), p["q_norm"]), p["wq_b"])
    q = q.reshape(T, H, dn + dr)
    ckr = _mm(x, p["wkv_a"])
    c = _rms(ckr[:, :R], p["kv_norm"])
    f = kw["rope_theta"] ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * f
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    il = kw["rope_interleave"]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cos, sin, il)],
                        axis=-1)
    k_pe = _rope(ckr[:, R:], cos, sin, il)                       # (T, dr)
    k_nope = jnp.einsum("tr,hdr->thd", c, _f32(p["wk_b"]),
                        precision=HIGHEST)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None],
                                                  (T, H, dr))], axis=-1)
    v = jnp.einsum("tr,hrd->thd", c, _f32(p["wv_b"]), precision=HIGHEST)
    scale = (kw["scale_width"] or dn + dr) ** -0.5
    rows = min(ROW_BLOCK, T)
    assert T % rows == 0, "the sequence is whole blocks of queries"

    @jax.checkpoint
    def block(args):
        qb, t0 = args
        s = jnp.einsum("thd,shd->hts", qb, k, precision=HIGHEST) * scale
        causal = (t0 + jnp.arange(rows))[:, None] >= jnp.arange(T)[None]
        pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", pr, v, precision=HIGHEST)

    o = lax.map(block, (q.reshape(T // rows, rows, H, dn + dr),
                        jnp.arange(0, T, rows)))
    return _mm(o.reshape(T, H * dv), p["wo"])


def route(x, p, kw):
    """(T, D) normed -> weights (T, E) float32 over ALL published experts,
    zero where an expert is not chosen."""
    s = jax.nn.sigmoid(_mm(x, p["gate"]))
    choose = s + _f32(p["gate_bias"])
    T, E = s.shape
    G = kw["n_group"]
    if G > 1:
        group = jnp.sum(lax.top_k(choose.reshape(T, G, E // G), 2)[0],
                        axis=-1)
        kept = lax.top_k(group, kw["topk_group"])[1]
        keep = jnp.any(kept[:, :, None] == jnp.arange(G), axis=1)
        choose = jnp.where(jnp.repeat(keep, E // G, axis=1), choose,
                           -jnp.inf)
    chosen = jnp.any(lax.top_k(choose, kw["top_k"])[1][:, :, None]
                     == jnp.arange(E), axis=1)                   # (T, E)
    w = jnp.where(chosen, s + _f32(p["gate_bias"])
                  if kw["bias_weighs"] else s, 0.0)
    if kw["renormalise"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * kw["routed_scale"]


def _moe(x, p, act, kw):
    w = route(x, p, kw)
    count = p["moe_w1"].shape[0]
    off = kw["experts_offset"]
    if kw["leak"]:
        # a wrong model: an absent expert's rows go through the held
        # expert of the same index modulo the count
        held_w = w.reshape(x.shape[0], -1, count).sum(axis=1)
    else:
        held_w = w[:, off:off + count]

    @jax.checkpoint
    def expert(y, ew):
        w1, w3, w2, we = ew
        h = act(_mm(x, w1)) * _mm(x, w3)
        return y + we[:, None] * _mm(h, w2), None

    y, _ = lax.scan(expert, jnp.zeros_like(x),
                    (p["moe_w1"], p["moe_w3"], p["moe_w2"], held_w.T))
    return y + _swiglu(x, p["ws1"], p["ws2"], act, kw["shared_width"])


def hidden_states(params, ids, *, activation="silu", **kw):
    """(B, T) ids -> (B, T, D) float32 states after the last block, before
    the final norm; a sequence at a time."""
    kw = {**PUBLISHED, **VARIANTS, **kw}
    kw.pop("n_head", None)     # the tree's shapes say it
    act = ACTIVATIONS[activation]

    @jax.checkpoint
    def layer(x, p):
        x = x + _attention(_rms(x, p["norm1"]), p, kw)
        h = _rms(x, p["norm2"])
        return x + (_swiglu(h, p["w1"], p["w2"], act) if "w1" in p
                    else _moe(h, p, act, kw))

    def one(row):
        x = _f32(params["wte"][row])
        for p in params["layers"]:
            x = layer(x, p)
        return x

    return jnp.stack([one(row) for row in ids])


def logits_at(params, x):
    """(..., D) states -> (..., V) float32 logits through the final norm
    and the untied head."""
    return _mm(_rms(x, params["norm_f"]), params["lm_head"].T)


def loss(params, ids, **kw):
    """Mean next-token cross entropy of (B, T) ids over the vocabulary
    slice the tree holds."""
    x = hidden_states(params, ids, **kw)[:, :-1]
    B, T, D = x.shape
    rows = min(VOCAB_ROWS, T)
    pad = -T % rows
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(B, -1, rows, D)
    t = jnp.pad(ids[:, 1:], ((0, 0), (0, pad))).reshape(B, -1, rows)
    live = (jnp.arange(T + pad) < T).reshape(-1, rows)

    @jax.checkpoint
    def block(args):
        xb, tb, m = args                               # (B, rows, ...)
        logits = logits_at(params, xb)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, tb[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(m, nll, 0.0))

    return jnp.sum(lax.map(block, (x.swapaxes(0, 1), t.swapaxes(0, 1),
                                   live))) / (B * T)


def loss_and_grads(params, ids, **kw):
    """The loss and its gradient with respect to every leaf of the tree, in
    float32 (the correction bias's is zero: it only chooses)."""
    return jax.value_and_grad(lambda p: loss(p, ids, **kw))(
        jax.tree.map(_f32, params))
