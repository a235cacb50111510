"""Plain reference of a pre-LayerNorm decoder with learned positions and a
tied head (GPT-2, OPT): forward pass and next-token loss in straightforward
``jax.numpy``, float32, matmul precision "highest". No kernel, no cache, no
batching, no remat, nothing imported from the program.

Follows the published descriptions (Radford et al. 2019; Zhang et al. 2022,
OPT with ``do_layer_norm_before``). Departures: OPT's 2-slot position offset
is left out (the configuration file says so), and the head has the padded
vocabulary rows of the configuration.

It reads the program's parameter tree, whose layout is the one thing shared
with the code under test:
  wte (V, D) | wpe (T, D) | lnf_scale, lnf_bias (D,)
  blocks, each with a leading layer axis: ln1_scale/bias (L, D),
    wqkv (L, D, 3D) [q | k | v, heads contiguous], bqkv (L, 3D), wo (L, D, D),
    bo (L, D), ln2_scale/bias (L, D), wup (L, D, F), bup (L, F),
    wdown (L, F, D), bdown (L, D)
Parameters may arrive in bfloat16; each layer is cast to float32 as it is
used, so the float32 copy of the whole model never exists.
"""

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ACTIVATIONS = {
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),   # gelu_new
    "relu": jax.nn.relu,
}


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _layernorm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def hidden_states(params, ids, *, n_head, activation, eps=1e-5):
    """(B, T) token ids -> (B, T, D) float32 states after the last block."""
    act = ACTIVATIONS[activation]
    B, T = ids.shape
    x = params["wte"][ids].astype(jnp.float32) \
        + params["wpe"][:T].astype(jnp.float32)[None]
    D = x.shape[-1]
    hd = D // n_head
    causal = jnp.tril(jnp.ones((T, T), bool))

    def block(x, layer):
        p = _f32(layer)
        h = _layernorm(x, p["ln1_scale"], p["ln1_bias"], eps)
        qkv = jnp.matmul(h, p["wqkv"], precision=HIGHEST) + p["bqkv"]
        q, k, v = (qkv[..., i * D:(i + 1) * D].reshape(B, T, n_head, hd)
                   for i in range(3))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                       precision=HIGHEST).reshape(B, T, D)
        x = x + jnp.matmul(a, p["wo"], precision=HIGHEST) + p["bo"]
        h = _layernorm(x, p["ln2_scale"], p["ln2_bias"], eps)
        h = act(jnp.matmul(h, p["wup"], precision=HIGHEST) + p["bup"])
        x = x + jnp.matmul(h, p["wdown"], precision=HIGHEST) + p["bdown"]
        return x, None

    x, _ = lax.scan(block, x, params["blocks"])
    return x


def logits_at(params, x, eps=1e-5):
    """(..., D) states -> (..., V) float32 logits through the final
    LayerNorm and the tied embedding."""
    h = _layernorm(x, params["lnf_scale"].astype(jnp.float32),
                   params["lnf_bias"].astype(jnp.float32), eps)
    return jnp.matmul(h, params["wte"].astype(jnp.float32).T,
                      precision=HIGHEST)


def loss(params, ids, *, n_head, activation):
    """Mean next-token cross entropy of (B, T) ids."""
    x = hidden_states(params, ids, n_head=n_head, activation=activation)
    logits = logits_at(params, x[:, :-1])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def token_gaps(params, ids, positions, tokens, *, n_head, activation):
    """For one sequence ``ids`` (1, T) and the tokens emitted after
    ``positions``: how far each emitted token's logit sits below that
    position's maximum, in standard deviations of the position's logits.
    0 means the emitted token is the reference's own argmax."""
    x = hidden_states(params, ids, n_head=n_head, activation=activation)
    rows = logits_at(params, x[0][positions])               # (n, V)
    got = jnp.take_along_axis(rows, tokens[:, None], axis=1)[:, 0]
    return (rows.max(axis=1) - got) / rows.std(axis=1)
