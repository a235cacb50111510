"""Plain reference of OLMoE (allenai/OLMoE-1B-7B): forward pass and
next-token loss in straightforward ``jax.numpy``, float32, matmul precision
"highest". No kernel, no cache, no batching, no sorting of tokens by expert,
nothing imported from the program.

The published equations it follows (HF ``modeling_olmoe.py`` and the model's
``config.json``; Muennighoff et al. 2024, "OLMoE: Open Mixture-of-Experts
Language Models"), per layer, pre-norm:

    h  = rms(x; g_1)
    q  = rms(h Wq; g_q)      k = rms(h Wk; g_k)      v = h Wv
         the QK-norm is over the WHOLE projection (all heads together),
         before the split into heads and before rope
    q, k -> heads -> rotary (half-split: lane i pairs with lane i + hd/2,
         the full head rotates, theta 10000)
    x  = x + softmax(q k^T / sqrt(hd), causal) v Wo
    h  = rms(x; g_2)
    p  = softmax(h Wr)       float32, over all E experts
    w  = p on its k largest entries, 0 elsewhere; NOT renormalised
         (``norm_topk_prob: false``: the k weights sum to ~k/E at random
         init, not to 1)
    x  = x + sum_e w_e * (silu(h W1_e) * (h W3_e)) W2_e
    logits = rms(x; g_f) W_head^T     (untied head)

with rms(a; g) = a / sqrt(mean(a^2) + eps) * g, eps 1e-5. There is no
bias, no shared expert, no ``clip_qkv`` (null in the published config).

Departures: every expert is applied to every token and weighted by its
routing weight, which is zero for the E - k experts the router did not
pick (the published code gathers the picked tokens; the sum is the same).
The experts are taken ONE AT A TIME (``lax.scan``), each cast to float32 as
it is used, and attention one head at a time, so that neither a layer's
float32 experts (1.6 GB) nor a (T, E, F) or (H, T, T) tensor ever exists:
the reference runs on the chip beside the engine at T = 4096. The auxiliary
load-balancing and router-z losses of training are left out (``loss`` is
the plain next-token cross entropy).

It reads the program's parameter tree, whose layout is the one thing shared
with the code under test (the SERVED tree of ``InferenceEngineV2``; the
training tree, whose expert arrays are stacked on a leading layer axis, is
read by the same indexing):
  wte (V, D) | norm_f (D,) | lm_head (V, D)
  blocks, stacked on a leading layer axis: rms1 (L, D), wq (L, D, D),
    wk (L, D, KVH*hd), wv (L, D, KVH*hd), wo (L, D, D), rms2 (L, D),
    q_norm (L, D), k_norm (L, KVH*hd), moe_gate (L, D, E) [float32]
  blocks, one array per layer in a list of L: moe_w1 (E, D, F) [gate],
    moe_w3 (E, D, F) [up], moe_w2 (E, F, D) [down]
All projections are stored input-major (x @ W). Parameters may arrive in
bfloat16; each is cast to float32 as it is used.

``top_k``, ``renormalize`` and ``qk_norm`` default to the published values;
the tier-1 tests pass others to show that the comparison tells them apart.
"""

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ACTIVATIONS = {"silu": jax.nn.silu}
TOP_K = 8                  # num_experts_per_tok
ROPE_THETA = 10000.0
RMS_EPS = 1e-5


def _f32(x):
    return x.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _rope(x, theta):
    """(T, H, hd) at positions 0..T-1, half-split pairs."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v):
    """(T, H, hd) x (T, KVH, hd) -> (T, H*hd), causal, a head at a time."""
    T, H, hd = q.shape
    rep = H // k.shape[1]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(i):
        s = _mm(q[:, i], k[:, i // rep].T) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(causal, s, -jnp.inf)
        return _mm(jax.nn.softmax(s, axis=-1), v[:, i // rep])

    out = lax.map(head, jnp.arange(H))                       # (H, T, hd)
    return out.transpose(1, 0, 2).reshape(T, H * hd)


def routing_weights(h, gate, top_k, renormalize):
    """(T, D) -> (T, E): the softmax probability of each token's ``top_k``
    experts, zero for the others."""
    probs = jax.nn.softmax(_mm(h, _f32(gate)), axis=-1)
    vals, idx = lax.top_k(probs, top_k)
    if renormalize:
        vals = vals / vals.sum(axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32)
                   * vals[..., None], axis=-2)


def _experts(h, w, w1, w3, w2, act):
    """sum_e w[:, e] * (act(h W1_e) * (h W3_e)) W2_e, one expert at a
    time."""
    def one(acc, xs):
        we, a, b, c = xs
        y = _mm(act(_mm(h, _f32(a))) * _mm(h, _f32(b)), _f32(c))
        return acc + we[:, None] * y, None

    acc, _ = lax.scan(one, jnp.zeros_like(h), (w.T, w1, w3, w2))
    return acc


def hidden_states(params, ids, *, n_head, activation, top_k=TOP_K,
                  renormalize=False, qk_norm=True, eps=RMS_EPS,
                  rope_theta=ROPE_THETA):
    """(B, T) token ids -> (B, T, D) float32 states after the last block."""
    act = ACTIVATIONS[activation]
    blocks = params["blocks"]
    n_layer = blocks["rms1"].shape[0]

    def sequence(row):
        x = _f32(params["wte"][row])                         # (T, D)
        T, D = x.shape
        hd = D // n_head
        for i in range(n_layer):
            p = {k: v[i] for k, v in blocks.items()}
            h = _rms(x, p["rms1"], eps)
            q, k, v = (_mm(h, _f32(p[n])) for n in ("wq", "wk", "wv"))
            if qk_norm:
                q = _rms(q, p["q_norm"], eps)
                k = _rms(k, p["k_norm"], eps)
            q = _rope(q.reshape(T, n_head, hd), rope_theta)
            k = _rope(k.reshape(T, -1, hd), rope_theta)
            a = _attention(q, k, v.reshape(T, -1, hd))
            x = x + _mm(a, _f32(p["wo"]))
            h = _rms(x, p["rms2"], eps)
            w = routing_weights(h, p["moe_gate"], top_k, renormalize)
            x = x + _experts(h, w, p["moe_w1"], p["moe_w3"], p["moe_w2"],
                             act)
        return x

    return jnp.stack([sequence(ids[b]) for b in range(ids.shape[0])])


def logits_at(params, x, eps=RMS_EPS):
    """(..., D) states -> (..., V) float32 logits through the final
    RMSNorm and the untied head."""
    return _mm(_rms(x, params["norm_f"], eps), _f32(params["lm_head"]).T)


def logits(params, ids, **kw):
    """(B, T) -> (B, T, V): every position's logits (small sizes only)."""
    return logits_at(params, hidden_states(params, ids, **kw),
                     kw.get("eps", RMS_EPS))


def loss(params, ids, *, n_head, activation, **kw):
    """Mean next-token cross entropy of (B, T) ids."""
    x = hidden_states(params, ids, n_head=n_head, activation=activation,
                      **kw)
    rows = logits_at(params, x[:, :-1], kw.get("eps", RMS_EPS))
    logz = jax.nn.logsumexp(rows, axis=-1)
    gold = jnp.take_along_axis(rows, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def token_gaps(params, ids, positions, tokens, *, n_head, activation, **kw):
    """For one sequence ``ids`` (1, T) and the tokens emitted after
    ``positions``: how far each emitted token's logit sits below that
    position's maximum, in standard deviations of the position's logits.
    0 means the emitted token is the reference's own argmax."""
    x = hidden_states(params, ids, n_head=n_head, activation=activation,
                      **kw)
    rows = logits_at(params, x[0][positions], kw.get("eps", RMS_EPS))
    got = jnp.take_along_axis(rows, tokens[:, None], axis=1)[:, 0]
    return (rows.max(axis=1) - got) / rows.std(axis=1)
