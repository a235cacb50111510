"""``moe/sharded_moe.py:route_topk``: the softmax router of the Mixtral /
OLMoE cells exactly as it was, and DeepSeek-V3's ``noaux_tc`` scoring
(ISSUE 43) against a ten-line reference: sigmoid scores, a correction bias
that chooses and does not weigh, group-limited choice, renormalisation and a
scale."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deepspeed_tpu.moe.sharded_moe import route_topk


def _inputs(S=64, M=32, E=16, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(S, M)), jnp.float32),
            jnp.asarray(rng.normal(size=(M, E)) * 0.5, jnp.float32),
            jnp.asarray(rng.normal(size=(E,)) * 0.3, jnp.float32))


def _softmax_router_as_it_was(x, gate_w, k, renormalize=True):
    """The router of PR 26 .. PR 42, line for line."""
    logits = jnp.matmul(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = lax.top_k(probs, k)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


@pytest.mark.parametrize("renormalize", [True, False])
def test_softmax_path_is_the_program_it_was(renormalize):
    """Bit for bit, and the same program: the OLMoE cells' router gains no
    operation from the new keywords' defaults."""
    x, w, _ = _inputs()
    got = route_topk(x, w, 4, renormalize)
    want = _softmax_router_as_it_was(x, w, 4, renormalize)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and (np.asarray(a) == np.asarray(b)).all()
    assert str(jax.make_jaxpr(lambda x, w: route_topk(
        x, w, 4, renormalize))(x, w)) == str(jax.make_jaxpr(
            lambda x, w: _softmax_router_as_it_was(x, w, 4, renormalize))(
                x, w))


def _noaux_tc(x, gate_w, bias, k, n_group, topk_group, scale):
    """DeepSeek-V3's gate in numpy, a token at a time."""
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(gate_w, np.float64))))
    out_w, out_e = [], []
    for row in s:
        choose = row + np.asarray(bias, np.float64)
        groups = choose.reshape(n_group, -1)
        score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        keep = np.argsort(-score)[:topk_group]
        masked = np.full_like(groups, -np.inf)
        masked[keep] = groups[keep]
        e = np.argsort(-masked.reshape(-1))[:k]
        out_e.append(e)
        out_w.append(row[e] / row[e].sum() * scale)
    return np.array(out_w), np.array(out_e)


@pytest.mark.parametrize("n_group, topk_group", [(4, 2), (1, 1), (8, 3)])
def test_sigmoid_group_limited_router(n_group, topk_group):
    x, w, b = _inputs()
    got_w, got_e = route_topk(x, w, 4, True, scoring="sigmoid", bias=b,
                              n_group=n_group, topk_group=topk_group,
                              scale=2.5)
    want_w, want_e = _noaux_tc(x, w, b, 4, n_group, topk_group, 2.5)
    assert (np.asarray(got_e) == want_e).all()
    assert np.abs(np.asarray(got_w) - want_w).max() < 1e-6
    assert np.allclose(np.asarray(got_w).sum(axis=1), 2.5, atol=1e-5)
    if n_group > 1:
        # every token's experts lie in topk_group groups at most
        per = 16 // n_group
        assert all(len(set(e // per)) <= topk_group for e in want_e)


def test_bias_chooses_and_does_not_weigh():
    x, w, b = _inputs()
    plain_w, plain_e = route_topk(x, w, 4, True, scoring="sigmoid")
    bias_w, bias_e = route_topk(x, w, 4, True, scoring="sigmoid", bias=b)
    assert (np.asarray(plain_e) != np.asarray(bias_e)).any()
    s = jax.nn.sigmoid(jnp.matmul(x, w, precision=lax.Precision.HIGHEST))
    picked = jnp.take_along_axis(s, bias_e, axis=1)
    assert np.abs(np.asarray(bias_w) - np.asarray(
        picked / picked.sum(axis=1, keepdims=True))).max() < 1e-6
    # a constant bias moves nothing
    same_w, same_e = route_topk(x, w, 4, True, scoring="sigmoid",
                                bias=jnp.full((16,), 0.7))
    assert (np.asarray(same_e) == np.asarray(plain_e)).all()
    assert np.abs(np.asarray(same_w) - np.asarray(plain_w)).max() < 1e-6
    unscaled_w, _ = route_topk(x, w, 4, False, scoring="sigmoid", bias=b)
    assert np.abs(np.asarray(unscaled_w) - np.asarray(picked)).max() < 1e-6


def test_unknown_scoring_is_refused():
    x, w, _ = _inputs()
    with pytest.raises(ValueError, match="scoring"):
        route_topk(x, w, 4, scoring="tanh")
