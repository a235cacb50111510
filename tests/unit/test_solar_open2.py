"""Solar-Open2 (ISSUE 54): the program against its plain reference
``perfbench/references/solar_open2.py`` on seeded weights, tiny (d 64, two
periods of one gated GQA layer and three delta-rule layers whose gate is a
vector over the key channels, 4 heads, dk 8 != dv 16, 16 experts of which 4
are held), the rule with such a gate against the token-by-token recurrence,
its step kernel in the Pallas interpreter, and the shares of an expert
layer. The engine is ``test_solar_open2_engine.py``.

Logits are compared, not tokens. Everything runs in float32 (weights, cache,
programs), so the program and the reference differ by summation order only.
``TOL`` is 1e-5 of a logit whose standard deviation is ~0.16: the measured
differences are 1e-7 to 2e-7, and the nearest wrong models are 3.5e-5 (the
gate's bias also weighing), 8e-5 (the matrix state in bfloat16), 1e-4 (a
softmax router) and ~1e-2 (one gate a head, beta in (0, 1), no output gate)
away: ``test_reference_tells_its_neighbours_apart`` holds that end.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.solar_open2 import (SOLAR_OPEN2_250B, SolarOpen2,
                                              SolarOpen2Config)
from deepspeed_tpu.ops.gated_delta_rule import CHUNK, chunk_rule, step_rule
from deepspeed_tpu.ops.pallas.gated_delta_rule import (
    chunk_rule_kernel, live_slot_list, step_rule_kernel)
from solar_open2_cases import (CFG, DK, DV, H, NEIGHBOURS, SIZES,  # noqa: F401
                               TOL, model, params, prompts_of, ref,
                               reference_logits)

# op by op these are thousands of small dispatches: a program each
chunk_rule, step_rule = jax.jit(chunk_rule), jax.jit(step_rule)
delta_rule = jax.jit(ref.delta_rule)


# ---------------------------------------------------------------- the model
def test_layer_kinds_and_parameter_count(model):
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == CFG.num_params()
    assert [not ref.is_kda(p) for p in shapes["layers"]] \
        == [CFG.is_gqa(i) for i in range(CFG.n_layer)] \
        == [True, False, False, False] * 2
    full = SOLAR_OPEN2_250B
    assert len(full.gqa_layers) == 12 and full.n_layer == 48
    assert full.conv_channels == 3 * 8192
    # ISSUE 54 counts 250.3 B whole and 3,308 M for the cell's share
    assert abs(full.num_params() - 250.3e9) < 0.1e9
    share = dataclasses.replace(full, n_layer=4, gqa_layers=(0,),
                                experts_held=40, vocab_size=24576)
    assert abs(share.num_params() - 3308e6) < 1e6


def test_apply_equals_reference(model, params):
    ids = np.stack(prompts_of(150, 150, seed=1))
    got = np.asarray(jax.jit(model.apply)(params, ids))
    want = reference_logits(params, ids)
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.1


@pytest.mark.parametrize("variant", NEIGHBOURS, ids=lambda v: next(iter(v)))
def test_reference_tells_its_neighbours_apart(params, variant):
    """Each neighbour of the published model is further from the reference
    than the comparison's tolerance, so a program that was one of them
    would fail these tests: a matrix state kept in bfloat16, one gate a
    head in place of the vector, and a softmax router among them."""
    ids = np.stack(prompts_of(150, seed=1))
    near = reference_logits(params, ids, **variant)
    assert np.abs(near - reference_logits(params, ids)).max() > 3 * TOL


def test_config_refuses_what_it_cannot_hold():
    with pytest.raises(ValueError, match="gqa_layers"):
        dataclasses.replace(CFG, gqa_layers=())
    with pytest.raises(ValueError, match="held experts"):
        dataclasses.replace(CFG, experts_offset=14)
    with pytest.raises(ValueError, match="n_kv_heads"):
        dataclasses.replace(CFG, n_kv_heads=3)


# ------------------------------------------- the rule with a gate a channel
def rule_inputs(T, seed=0, repeat=False, floor=-16.0):
    """Decays down to exp(``floor``) a step, log-uniform from exp(-1e-3)."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    k = unit(rng.normal(size=(T, H, DK)))
    if repeat:                      # every key the same: A's entries are b
        k = np.broadcast_to(k[:1], k.shape)
    log_a = -np.exp(rng.uniform(np.log(1e-3), np.log(-floor),
                                size=(T, H, DK)))
    x = (unit(rng.normal(size=(T, H, DK))) * DK ** -0.5, k,
         rng.normal(size=(T, H, DV)), log_a,
         rng.uniform(0, 2, size=(T, H)), rng.normal(size=(H, DK, DV)))
    return tuple(a.astype(np.float32) for a in x)


def batched(x):
    return tuple(jnp.asarray(a)[None] for a in x)


def close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("T", [1, 5, CHUNK, 100, 2 * CHUNK, 200])
def test_chunkwise_rule_equals_recurrence(T):
    """A (dk,) gate, at lengths that are and are not multiples of 64, from
    a state that is not zero, decays from exp(-1e-3) to exp(-16) a step."""
    x = rule_inputs(T, seed=T)
    want_o, want_S = delta_rule(*x)
    o, S = chunk_rule(*batched(x))
    close(o[0], want_o)
    close(S[0], want_S)


def test_chunkwise_rule_with_repeated_keys():
    """Keys that repeat make A's entries near b: the inverse is a solve,
    never a series."""
    x = rule_inputs(150, seed=3, repeat=True, floor=-0.1)
    want_o, want_S = delta_rule(*x)
    o, S = chunk_rule(*batched(x))
    close(o[0], want_o, 1e-4)
    close(S[0], want_S, 1e-4)


def test_no_decay_is_divided_by():
    """Every channel decays by exp(-16) a step: a form that divided by a
    cumulated decay would meet exp(16 x 63) inside a chunk."""
    q, k, v, log_a, b, S0 = rule_inputs(130, seed=4)
    log_a = np.full_like(log_a, -16.0)
    log_a[:, :, ::2] = -1e-3            # beside channels that barely decay
    x = (q, k, v, log_a, b, S0)
    want_o, want_S = delta_rule(*x)
    o, S = chunk_rule(*batched(x))
    close(o[0], want_o)
    close(S[0], want_S)


def test_equal_channels_give_the_scalar_rule():
    q, k, v, log_a, b, S0 = rule_inputs(150, seed=5, floor=-2.0)
    scalar = log_a[..., :1]
    wide = np.broadcast_to(scalar, log_a.shape)
    o, S = chunk_rule(*batched((q, k, v, wide, b, S0)))
    for gate in (scalar, scalar[..., 0]):       # (.., H, 1) and (.., H)
        want_o, want_S = chunk_rule(*batched((q, k, v, gate, b, S0)))
        close(o, want_o, 5e-6)
        close(S, want_S, 5e-6)
    so, sS = step_rule(q[0], k[0], v[0], wide[0], b[0], S0[None])
    want_o, want_S = step_rule(q[0], k[0], v[0], scalar[0], b[0], S0[None])
    close(so, want_o, 5e-6)
    close(sS, want_S, 5e-6)


def test_step_rule_equals_recurrence():
    x = rule_inputs(6, seed=6)
    want_o, want_S = delta_rule(*x)
    S = jnp.asarray(x[5])[None]
    for t in range(6):
        o, S = step_rule(*(jnp.asarray(a[t])[None] for a in x[:5]), S)
        close(o[0], want_o[t])
    close(S[0], want_S)


def test_padding_moves_nothing():
    """Rows with ``log_a`` 0 and ``b`` 0 behind the real tokens leave the
    state of the last real one."""
    q, k, v, log_a, b, S0 = rule_inputs(100, seed=7)
    log_a[70:], b[70:] = 0.0, 0.0
    _, S = chunk_rule(*batched((q, k, v, log_a, b, S0)))
    _, want = chunk_rule(*batched(
        tuple(a[:70] for a in (q, k, v, log_a, b)) + (S0,)))
    close(S, want, 1e-6)


# ------------------------------------------------------------- the kernels
@pytest.mark.parametrize("gate", ["channel", "head", "head-no-axis"])
def test_step_kernel_equals_xla(gate):
    """The step kernel = ``step_rule`` on the live slots, whichever gate,
    and every dead slot's state bit for bit as it was."""
    B = 5
    rng = np.random.default_rng(8)
    q, k = (rng.normal(size=(B, H, DK)).astype(np.float32) for _ in "qk")
    v = rng.normal(size=(B, H, DV)).astype(np.float32)
    log_a = -rng.uniform(0, 16, size=(B, H, DK)).astype(np.float32)
    log_a = {"channel": log_a, "head": log_a[..., :1],
             "head-no-axis": log_a[..., 0]}[gate]
    b = rng.uniform(0, 2, size=(B, H)).astype(np.float32)
    ssm = rng.normal(size=(B, H, DK, DV)).astype(np.float32)
    active = np.array([True, False, True, True, False])
    want_o, want_S = step_rule(q, k, v, log_a, b, ssm)
    o, S = step_rule_kernel(q, k, v, log_a, b, jnp.asarray(ssm),
                            live_slot_list(jnp.asarray(active)))
    close(np.asarray(o)[active], np.asarray(want_o)[active], 1e-5)
    close(np.asarray(S)[active], np.asarray(want_S)[active], 1e-5)
    assert np.array_equal(np.asarray(S)[~active], ssm[~active])


def test_chunk_kernel_takes_a_gate_a_head_only():
    """(.., H, 1) is the kernel's rule; (.., H, dk) has no chunk kernel yet
    and says so rather than run another rule (ROADMAP R4)."""
    q, k, v, log_a, b, S0 = rule_inputs(70, seed=9, floor=-2.0)
    x = batched((q, k, v, log_a[..., :1], b, S0))
    want_o, want_S = chunk_rule(*x)
    o, S = chunk_rule_kernel(*x)
    close(o, want_o, 1e-5)
    close(S, want_S, 1e-5)
    with pytest.raises(NotImplementedError, match="key channel"):
        chunk_rule_kernel(*batched((q, k, v, log_a, b, S0)))


# --------------------------------------------------------------- the shares
def test_eight_shares_add_up_to_the_whole_layer():
    """One MoE layer cut eight ways (``experts_offset`` 0, 2, ..., 14 of
    the tiny size's 16 experts): the eight partial sums, the shared expert
    counted once, equal the uncut reference layer; and no share is nothing."""
    whole = dataclasses.replace(CFG, experts_offset=0, experts_held=16)
    p = SolarOpen2(whole).init(jax.random.key(2))["layers"][1]
    # at this width the seeded bias would choose for every token alike
    p["gate"] = p["gate"] * 30
    x = jax.random.normal(jax.random.key(3), (1, 40, CFG.d_model))
    act = jax.nn.silu
    c = {**ref.PUBLISHED, **ref.VARIANTS, "top_k": CFG.moe_top_k}
    want = np.asarray(ref.moe(x[0], p, c, act))
    shared = np.asarray(ref.moe(
        x[0], {**p, **{k: p[k][:0] for k in ("moe_w1", "moe_w3", "moe_w2")}},
        c, act))
    total, parts = shared.copy(), 0
    for r in range(8):
        cfg = dataclasses.replace(CFG, experts_offset=2 * r, experts_held=2)
        mine = {**p, **{k: p[k][2 * r:2 * r + 2]
                        for k in ("moe_w1", "moe_w3", "moe_w2")}}
        part = np.asarray(SolarOpen2(cfg)._moe(x, mine))[0] - shared
        parts += np.abs(part).max() > 1e-4
        total += part
    assert parts == 8
    assert np.abs(total - want).max() < 1e-8
    assert np.abs(want - shared).max() > 3e-4
