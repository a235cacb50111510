"""The trainable DeepSeek-V3 family as one chip's share (ISSUE 47;
Kanana-2-30B-A3B's form: no query latent, ungrouped sigmoid gate, two shared
experts): the program against its plain reference
``perfbench/references/deepseek_v3.py`` on seeded weights, tiny (d 64, one
dense and two sparse layers, 4 heads of 16 + 8 wide keys and 16 wide values,
16 routed experts of which this "chip" holds experts 4 .. 7), forward and
backward; the share; the buffer leaf the optimizer does not own; the flash
kernel at unequal key and value widths; the benchmark's counts and readers.

Everything runs in float32 (weights and programs), so the program and the
reference differ by summation order only: ``TOL`` is 1e-4 relative L2 of a
gradient group (measured ~5e-7) and 2e-5 of the loss; every wrong model of
``test_reference_tells_its_neighbours_apart`` is 1000 x over it.
"""

import dataclasses
import hashlib
import importlib
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.models import (DEEPSEEK_V3_TINY, GPT2, GPT2_TINY,  # noqa: E402
                                  KANANA_2_30B_A3B, DeepseekV3)

ref = importlib.import_module("references.deepseek_v3")
mla_moe = importlib.import_module("pbench.mla_moe")
pb_common = importlib.import_module("pbench.common")

CFG = DEEPSEEK_V3_TINY
# what the reference cannot read off the tree's shapes, at the tiny size
KW = dict(top_k=CFG.moe_top_k, experts_offset=CFG.experts_offset,
          routed_scale=CFG.routed_scaling_factor, rope_theta=CFG.rope_theta)
TOL = 1e-4
ENGINE = {"train_micro_batch_size_per_gpu": 2,
          "gradient_accumulation_steps": 1, "steps_per_print": 0,
          "optimizer": {"type": "AdamW",
                        "params": {"lr": 2e-3, "weight_decay": 0.01}},
          "gradient_clipping": 1.0, "bf16": {"enabled": True},
          "zero_optimization": {"stage": 2}}


@pytest.fixture(scope="module")
def model():
    return DeepseekV3(CFG)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(0))


@pytest.fixture(scope="module")
def ids():
    return np.asarray(jax.random.randint(jax.random.key(1), (2, 64), 0,
                                         CFG.vocab_size))


def _loss_and_grads(model, params, ids):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: model.loss(p, {"input_ids": ids})))(params)


def _ref_loss_and_grads(params, ids, **kw):
    return jax.jit(lambda p: ref.loss_and_grads(p, ids, **kw))(params)


@pytest.fixture(scope="module")
def program(model, params, ids):
    return _loss_and_grads(model, params, ids)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _worst(got, want):
    return max(jax.tree.leaves(jax.tree.map(_rel, got, want)))


# ---------------------------------------------------------------- the model
def test_parameter_counts(model):
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == CFG.num_params()
    assert ["w1" in p for p in shapes["layers"]] == [True, False, False]
    assert shapes["layers"][1]["gate_bias"].dtype == jnp.float32
    # ISSUE 47 counts the whole model at 30.67 B, an attention at 26.35 M, a
    # routed expert at 4.72 M, and the cut at 575.96 M
    assert abs(KANANA_2_30B_A3B.num_params() - 30.67e9) < 0.005e9
    assert abs(KANANA_2_30B_A3B.layer_params()[0] - 26.35e6) < 0.005e6
    cut = dataclasses.replace(KANANA_2_30B_A3B, n_layer=5, experts_held=16,
                              vocab_size=16032)
    assert abs(cut.num_params() - 575.96e6) < 0.005e6
    assert abs(cut.softmax_scale - 192 ** -0.5) < 1e-12


def test_program_equals_reference_loss_and_every_gradient(params, ids,
                                                          program):
    loss, grads = program
    want_loss, want = _ref_loss_and_grads(params, ids, **KW)
    assert abs(float(loss) - float(want_loss)) < 2e-5
    assert _worst(grads, want) < TOL
    # every parameter group takes a gradient; the buffer's is exactly zero
    norms = jax.tree.map(lambda g: float(jnp.linalg.norm(g)), want)
    for p in norms["layers"]:
        assert p.pop("gate_bias", 0.0) == 0.0
    assert min(jax.tree.leaves(norms)) > 1e-6


def test_query_latent_is_the_same_code(ids):
    """``q_lora_rank`` a number: V3's form, through the same attention."""
    cfg = dataclasses.replace(CFG, q_lora_rank=32, n_layer=2)
    model = DeepseekV3(cfg)
    params = model.init(jax.random.key(3))
    assert {"wq_a", "q_norm", "wq_b"} <= set(params["layers"][0])
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()
    loss, grads = _loss_and_grads(model, params, ids)
    want_loss, want = _ref_loss_and_grads(params, ids, **KW)
    assert abs(float(loss) - float(want_loss)) < 2e-5
    assert _worst(grads, want) < TOL


NEIGHBOURS = [dict(bias_weighs=True), dict(renormalise=False),
              dict(routed_scale=1.0), dict(scale_width=16),
              dict(rope_interleave=False), dict(shared_width=32),
              dict(leak=True)]


@pytest.mark.parametrize("variant", NEIGHBOURS, ids=lambda v: next(iter(v)))
def test_reference_tells_its_neighbours_apart(params, ids, program, variant):
    """The wrong models of ISSUE 47, step 7(a): the bias also weighing, no
    renormalisation, the 2.448 left out, a softmax scale of the no-position
    width alone, rotary on split halves, a shared expert of one expert's
    width, absent experts' rows leaking through held ones. Each is further
    from the program than 1000 x the tolerance in some gradient group."""
    _, grads = program
    _, wrong = _ref_loss_and_grads(params, ids, **{**KW, **variant})
    assert _worst(grads, wrong) > 1000 * TOL


def test_what_remat_keeps_and_the_fused_loss_change_nothing(params, ids,
                                                            program):
    loss, grads = program
    model = DeepseekV3(dataclasses.replace(
        CFG, remat_policy="save_flash", loss_chunk=16))
    got_loss, got = _loss_and_grads(model, params, ids)
    assert abs(float(loss) - float(got_loss)) < 2e-5
    assert _worst(got, grads) < TOL


# ----------------------------------------------------------------- the share
def test_the_shares_add_up_to_the_uncut_layer(params):
    """The routed parts the EIGHT shares of a layer compute (experts 2 r, 2
    r + 1 each, routing over all 16), with the shared experts every chip
    computes alike counted once, are the uncut reference layer; and so are
    their gradients with respect to the layer's input."""
    rng = jax.random.key(5)
    D, F, E = CFG.d_model, CFG.moe_d_ff, CFG.n_routed_experts
    x = jax.random.normal(rng, (2, 24, D), jnp.float32)
    ct = jax.random.normal(jax.random.key(6), (2, 24, D), jnp.float32)
    p = dict(params["layers"][1])
    # at d 64 the seeded gate's scores hardly differ between tokens: a gate
    # twenty times the seed's spreads the tokens over the shares
    p["gate"] = p["gate"] * 20.0
    ks = jax.random.split(rng, 3)
    whole = {"moe_w1": jax.random.normal(ks[0], (E, D, F)) * 0.1,
             "moe_w3": jax.random.normal(ks[1], (E, D, F)) * 0.1,
             "moe_w2": jax.random.normal(ks[2], (E, F, D)) * 0.1}
    kw = {**ref.PUBLISHED, **ref.VARIANTS, **KW, "experts_offset": 0}

    def uncut(x):
        return ref._moe(x.reshape(-1, D), {**p, **whole}, jax.nn.silu,
                        kw).reshape(x.shape)

    def shared(x):
        return DeepseekV3._swiglu(x, p["ws1"], p["ws2"])

    def part(r):
        share = DeepseekV3(dataclasses.replace(
            CFG, experts_offset=2 * r, experts_held=2))
        mine = {k: w[2 * r:2 * r + 2] for k, w in whole.items()}
        return lambda x: share._moe(x, {**p, **mine}) - shared(x)

    def total(x):
        return shared(x) + sum(part(r)(x) for r in range(8))

    def out_and_dx(f):
        y, vjp = jax.vjp(f, x)
        return y, vjp(ct)[0]

    with jax.default_matmul_precision("highest"):
        parts = jax.jit(lambda: [jnp.abs(part(r)(x)).max()
                                 for r in range(8)])()
        want, want_dx = jax.jit(lambda: out_and_dx(uncut))()
        got, got_dx = jax.jit(lambda: out_and_dx(total))()
    assert min(float(m) for m in parts) > 1e-3
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    assert _rel(got_dx, want_dx) < TOL


# a share as the one pass takes it and as the walk does (160 routed rows, a
# chunk of 128: half an even router's 40, up to the next 128)
HELD = pytest.mark.parametrize("held", [(4, 4), (4, 4, 16)],
                               ids=["one_pass", "walk"])


@HELD
@pytest.mark.parametrize("backend", [False, True], ids=["ragged", "kernel"])
def test_absent_rows_give_and_take_zero_under_grad(backend, held):
    """``moe_swiglu_routed(held=)`` under ``jax.grad``: a routed row whose
    expert lies elsewhere adds nothing to the output and takes no gradient,
    and the held rows' gradients are those of the dense sum over held
    experts, through ``lax.ragged_dot`` and through the differentiable
    Pallas grouped kernels (interpreted)."""
    from deepspeed_tpu.moe import sharded_moe
    rng = np.random.default_rng(0)
    S, k, D, F = 40, 4, 128, 128
    xs = jnp.asarray(rng.normal(size=(S, D)), jnp.float32)
    experts = jnp.asarray(np.stack([rng.permutation(16)[:k]
                                    for _ in range(S)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(size=(S, k)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.normal(size=(4, D, F)) * 0.1, jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(4, F, D)) * 0.1, jnp.float32)
    ct = jnp.asarray(rng.normal(size=(S, D)), jnp.float32)

    def routed(xs, weights, w1, w3, w2):
        return jnp.sum(ct * sharded_moe.moe_swiglu_routed(
            xs, weights, experts, w1, w3, w2, backend, held=held))

    def dense(xs, weights, w1, w3, w2):
        local = experts - held[0]
        y = 0.0
        for e in range(held[1]):
            we = jnp.sum(jnp.where(local == e, weights, 0.0), axis=1)
            h = jax.nn.silu(jnp.matmul(xs, w1[e], precision="highest")) \
                * jnp.matmul(xs, w3[e], precision="highest")
            y = y + we[:, None] * jnp.matmul(h, w2[e], precision="highest")
        return jnp.sum(ct * y)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(routed, argnums=(0, 1, 2, 3, 4)))(
            xs, weights, w1, w3, w2)
    want = jax.jit(jax.grad(dense, argnums=(0, 1, 2, 3, 4)))(
        xs, weights, w1, w3, w2)
    for g, w in zip(got, want):
        assert _rel(g, w) < TOL
    local = np.asarray(experts) - held[0]
    absent = (local < 0) | (local >= held[1])
    assert absent.any() and (np.asarray(got[1])[absent] == 0).all()


@HELD
def test_what_a_backend_leaves_past_the_groups_reaches_nothing(monkeypatch,
                                                               held):
    """On the chip ``lax.ragged_dot`` never writes the rows past its groups'
    sum, the absent experts' rows: they hold what the buffer held, in the
    forward (the output) and in the backward (dx). PR 47's first chip run
    had NaN from there in every gradient below the top expert layer, and
    the engine skipped every step as an overflow. The CPU writes zeros, so
    here a backend that leaves NaN at both ends stands in for the chip:
    output and gradients are those of the clean products."""
    from deepspeed_tpu.moe import sharded_moe
    rng = np.random.default_rng(1)
    S, k, D, F = 40, 4, 128, 128
    xs = jnp.asarray(rng.normal(size=(S, D)), jnp.float32)
    experts = jnp.asarray(np.stack([rng.permutation(16)[:k]
                                    for _ in range(S)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(size=(S, k)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.normal(size=(4, D, F)) * 0.1, jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(4, F, D)) * 0.1, jnp.float32)
    clean = sharded_moe._grouped_swiglu_ffn

    def leaves_nan(xr, w1, w3, w2, group_sizes, params):
        live = (jnp.arange(xr.shape[0]) < jnp.sum(group_sizes))[:, None]

        @jax.custom_vjp
        def products(xr, w1, w3, w2, live):
            return jnp.where(live, clean(xr, w1, w3, w2, group_sizes,
                                         params), jnp.nan)

        def fwd(xr, w1, w3, w2, live):
            out, vjp = jax.vjp(lambda *a: clean(*a, group_sizes, params),
                               xr, w1, w3, w2)
            return jnp.where(live, out, jnp.nan), (vjp, live)

        def bwd(res, g):
            vjp, live = res
            dx, *dw = vjp(g)
            return (jnp.where(live, dx, jnp.nan), *dw, None)
        products.defvjp(fwd, bwd)
        return products(xr, w1, w3, w2, live)

    def run():
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(sharded_moe.moe_swiglu_routed(
                a[0], a[1], experts, *a[2:], False, held=held) ** 2),
            argnums=(0, 1, 2, 3, 4)))(xs, weights, w1, w3, w2)
    want = run()
    monkeypatch.setattr(sharded_moe, "_grouped_swiglu_ffn", leaves_nan)
    got = run()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------------------------------------ the walk
# 256 tokens x 4 choices of 16 experts of which experts 4 .. 7 are held:
# 1,024 routed rows, a chunk of 128 = half an even router's 256. ``n`` held
# rows, spread over the held experts and the tokens; None = an even draw.
WALK_ROWS = {"even": None, "1.6x_even": 410, "every_row": 1024, "no_row": 0,
             "a_chunk_less_one": 127, "a_chunk": 128, "a_chunk_and_one": 129,
             "two_chunks": 256}


def _walk_case(n):
    rng = np.random.default_rng(56)
    S, k, D, F = 256, 4, 128, 128
    if n is None:
        experts = np.stack([rng.permutation(16)[:k] for _ in range(S)])
    else:
        absent = np.r_[0:4, 8:16]
        experts = absent[rng.integers(0, absent.size, (S, k))]
        experts.reshape(-1)[rng.permutation(S * k)[:n]] = \
            rng.integers(4, 8, n)
    operands = (rng.normal(size=(S, D)), rng.uniform(size=(S, k)),
                rng.normal(size=(4, D, F)) * 0.1,
                rng.normal(size=(4, D, F)) * 0.1,
                rng.normal(size=(4, F, D)) * 0.1, rng.normal(size=(S, D)))
    return jnp.asarray(experts, jnp.int32), \
        [jnp.asarray(a, jnp.float32) for a in operands]


@pytest.mark.parametrize("rows", WALK_ROWS)
def test_the_walk_equals_the_one_pass(rows, monkeypatch):
    """ISSUE 56: with a three-part ``held`` only the held experts' rows are
    gathered, multiplied and scattered, a chunk at a time, and nothing is
    dropped: output and every gradient (xs, weights, w1, w3, w2) are the
    one pass's whether the router sends the share an even load, more than
    a chunk (through ``dstpu.moe.spill``), every row or none, and with the
    last held row at a chunk's edge or one either side. The chunks the
    program runs are those ``held_walk_taken`` says."""
    from deepspeed_tpu.moe import sharded_moe
    experts, (*operands, ct) = _walk_case(WALK_ROWS[rows])
    n = int(((experts >= 4) & (experts < 8)).sum())
    assert WALK_ROWS[rows] in (None, n)
    ran, chain = [], sharded_moe._grouped_swiglu_ffn

    def counted(*a):
        jax.debug.callback(lambda: ran.append(1))
        return chain(*a)
    monkeypatch.setattr(sharded_moe, "_grouped_swiglu_ffn", counted)

    def run(held, grad=True):
        def y(*a):
            return sharded_moe.moe_swiglu_routed(
                a[0], a[1], experts, *a[2:], False, held=held)
        with jax.default_matmul_precision("highest"):
            if not grad:
                return jax.jit(y)(*operands)
            return jax.jit(jax.value_and_grad(
                lambda *a: jnp.sum(ct * y(*a)), argnums=(0, 1, 2, 3, 4)))(
                    *operands)
    want, got = run((4, 4)), run((4, 4, 16))
    assert abs(float(got[0]) - float(want[0])) \
        < 2e-5 * max(1.0, abs(float(want[0])))
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert _rel(g, w) < TOL if n else not np.asarray(g).any()
    jax.effects_barrier()
    del ran[:]
    y = jax.block_until_ready(run((4, 4, 16), grad=False))
    jax.effects_barrier()
    taken = sharded_moe.held_walk_taken(experts, 4, (4, 4, 16))
    assert len(ran) == int(taken) == -(-n // 128)
    assert np.abs(np.asarray(y)
                  - np.asarray(run((4, 4), grad=False))).max() < 2e-5
    # the one pass has no chunks to count; so few rows that a chunk would
    # be all of them take it
    assert sharded_moe.held_walk_taken(experts[:32], 4, (4, 4, 16)) is None


# ``moe_swiglu_routed`` without a share, with the two-part share and with a
# three-part share over too few rows to walk, as the parent of PR 56 (08cda69)
# lowered them, by the sha256 of ``lower().as_text()``: the walk is a path
# of its own and these callers (OLMoE; ``deepseek_v32.py``,
# ``solar_open2.py``) run the program they ran. A PR that changes the one
# pass on purpose regenerates them (the assertion message holds the value).
ONE_PASS_TEXT = {
    ("none", "fwd"): "ffba925a319b8dda", ("none", "bwd"): "3a7f08ee7b9c3976",
    ("two_part", "fwd"): "5e084ac73e84e752",
    ("two_part", "bwd"): "7413bf6c3ad4bd07",
    ("too_few_rows_to_walk", "fwd"): "299b160549fcd200",
    ("too_few_rows_to_walk", "bwd"): "53434d12d6e5d03a",
}


@pytest.mark.parametrize("share, passes", ONE_PASS_TEXT)
def test_the_one_pass_is_the_parents_text(share, passes):
    from deepspeed_tpu.moe import sharded_moe
    held = {"none": None, "two_part": (4, 4),
            "too_few_rows_to_walk": (4, 4, 16)}[share]
    S = 32 if share == "too_few_rows_to_walk" else 64
    k, D, F, E = 4, 128, 128, 4 if held else 16

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def loss(xs, weights, experts, w1, w3, w2):
        return jnp.sum(sharded_moe.moe_swiglu_routed(
            xs, weights, experts, w1, w3, w2, False, held=held))
    fn = jax.grad(loss, argnums=(0, 1, 3, 4, 5)) if passes == "bwd" else loss
    text = jax.jit(fn).lower(
        f32(S, D), f32(S, k), jax.ShapeDtypeStruct((S, k), jnp.int32),
        f32(E, D, F), f32(E, D, F), f32(E, F, D)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == ONE_PASS_TEXT[share, passes]


def test_the_walks_scopes_reach_its_backward():
    """The walk's backward is written out (a ``custom_vjp``): its gather and
    scatter-add, its products and the cotangent's gather still carry
    ``dstpu.moe.route`` / ``experts`` / ``combine``, and every chunk after a
    layer's first, forward and backward, lies under ``dstpu.moe.spill``
    with those inside it."""
    from deepspeed_tpu.moe import sharded_moe
    experts, (*operands, ct) = _walk_case(None)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(
        ct * sharded_moe.moe_swiglu_routed(
            a[0], a[1], experts, *a[2:], False, held=(4, 4, 16))),
        argnums=(0, 1, 2, 3, 4))).lower(*operands).as_text(debug_info=True)
    backward = re.findall(r'loc\("jit\(<lambda>\)/(transpose\(jvp\([^"]*)"',
                          text)
    for scope in mla_moe.SCOPES[1:]:
        assert any(scope in n and "spill" not in n for n in backward), scope
        assert any(-1 < n.find("dstpu.moe.spill") < n.find(scope)
                   for n in backward), scope
    assert "ragged_dot" in text


# ----------------------------------------------- the leaf nobody optimizes
@pytest.fixture(scope="module")
def engine():
    model = DeepseekV3(dataclasses.replace(CFG, dtype="bfloat16"))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, seed=0,
                                               config=ENGINE)
    return engine


def test_three_steps_fall_and_leave_the_bias_as_it_was(engine):
    """Three ``initialize()`` steps under ZeRO-2 in bfloat16 on one batch:
    finite, falling losses; the correction bias float32 and bit-equal, in
    the parameters and in the master copy, with no moments; every other
    leaf cast, moved and with moments."""
    state = engine.state
    before = [np.asarray(p["gate_bias"]) for p in state["params"]["layers"][1:]]
    w_before = np.asarray(state["master"]["layers"][1]["gate"])
    n = len(jax.devices())
    batch = {"input_ids": np.asarray(jax.random.randint(
        jax.random.key(2), (2 * n, 48), 0, CFG.vocab_size))}
    losses = [float(engine.train_batch(batch)) for _ in range(3)]
    assert np.isfinite(losses).all()
    assert losses[2] < losses[1] < losses[0]
    state = engine.state
    for p, m, mom, b in zip(state["params"]["layers"][1:],
                            state["master"]["layers"][1:],
                            state["opt"]["m"]["layers"][1:], before):
        assert p["gate_bias"].dtype == m["gate_bias"].dtype == jnp.float32
        assert (np.asarray(p["gate_bias"]) == b).all()
        assert (np.asarray(m["gate_bias"]) == b).all()
        assert mom["gate_bias"] is None
        assert p["gate"].dtype == jnp.bfloat16
        assert mom["gate"].dtype == jnp.float32
    assert not (np.asarray(state["master"]["layers"][1]["gate"])
                == w_before).all()
    moments = jax.tree.leaves(state["opt"]["m"])
    assert len(moments) == len(jax.tree.leaves(state["params"])) - 2


def test_offload_refuses_a_buffer_leaf():
    model = DeepseekV3(dataclasses.replace(CFG, dtype="bfloat16"))
    config = {**ENGINE, "zero_optimization": {
        "stage": 2, "offload_optimizer": {"device": "cpu"}}}
    with pytest.raises(NotImplementedError, match="buffer"):
        deepspeed_tpu.initialize(model=model, seed=0, config=config)


# What the step program and the state of a model WITHOUT buffer leaves were
# at the parent commit (6b3294e, PR 46), as fingerprints of the jaxpr's text
# and of the state's shapes, dtypes and shardings on this suite's 8-device
# mesh: the hook costs such a model nothing, operation for operation. A PR
# that changes the dense step on purpose regenerates them (run this test, the
# assertion message holds the new values).
PARENT = {(2, 1): ("cc8f22728593d4a9", "bfeea9497496dbd8"),
          (3, 2): ("6ad16347f7e881f3", "ed66370a1fb09078")}


def _fingerprint(stage, gas):
    model = GPT2(dataclasses.replace(GPT2_TINY, dtype="bfloat16"))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, seed=0, config={
            **ENGINE, "gradient_accumulation_steps": gas,
            "zero_optimization": {"stage": stage}})
    n = len(jax.devices())
    batch = {"input_ids": np.zeros((2 * n * gas, 32), np.int32)}
    batch = engine._shard_batch(jax.tree.map(engine._add_gas_dim, batch),
                                with_gas_dim=True)
    with jax.set_mesh(engine.mesh):
        jaxpr = jax.make_jaxpr(engine._train_step_jit, static_argnums=(3,))(
            engine.state, batch, engine._current_lr(), None)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    state = str(jax.tree.map(
        lambda x: (x.shape, str(x.dtype), str(getattr(x.sharding, "spec",
                                                      ""))), engine.state))
    return tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                 for t in (text, state))


@pytest.mark.parametrize("stage,gas", sorted(PARENT))
def test_a_dense_model_steps_as_it_did_at_the_parent(stage, gas):
    assert _fingerprint(stage, gas) == PARENT[stage, gas]


# ------------------------------------------------------------------- flash
def _mla_operands(T, dk=192, dv=128, B=1, H=2):
    ks = jax.random.split(jax.random.key(7), 4)
    q, k = (jax.random.normal(ks[i], (B, T, H, dk), jnp.float32)
            for i in range(2))
    v, ct = (jax.random.normal(ks[i], (B, T, H, dv), jnp.float32)
             for i in (2, 3))
    return q, k, v, ct


def _flash_loss(ct, pad=0, **kw):
    """sum(o * ct) of an interpreted flash call, V as it is or with ``pad``
    zero columns that the output is sliced of."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    kw = {"causal": True, "block_q": 128, "block_k": 128, "block_h": 1,
          "interpret": True, **kw}

    def loss(q, k, v):
        dv = v.shape[-1]
        if pad:
            v = jnp.pad(v, ((0, 0),) * 3 + ((0, pad),))
        o = flash_attention(q, k, v, scale=q.shape[-1] ** -0.5, **kw)
        assert o.shape == v.shape
        return jnp.sum(o[..., :dv] * ct)
    return loss


def test_flash_with_192_wide_keys_and_128_wide_values():
    """The flash kernel (interpreted) at the published head widths, V at its
    own width as ``DeepseekV3._attention`` hands it over, against the dense
    float32 softmax: forward and the gradients of q, k and v."""
    T, dk = 256, 192
    q, k, v, ct = _mla_operands(T)

    def dense(q, k, v):
        s = jnp.einsum("bthd,bshd->bhts", q, k, precision="highest") \
            * dk ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        return jnp.sum(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1),
                                  v, precision="highest") * ct)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(_flash_loss(ct),
                                         argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(dense, argnums=(0, 1, 2)))(q, k, v)
    assert abs(float(got[0]) - float(want[0])) < 1e-3 * abs(float(want[0]))
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape and _rel(g, w) < TOL


def _equals_the_padded_call(T, block_h, dk=192, dv=128):
    """Two widths against V padded to the keys' width and the output sliced,
    which is what the model did: the zero columns gave zeros and took zero
    cotangents, so leaving them out changes no bit, forward or backward."""
    q, k, v, ct = _mla_operands(T, dk, dv)
    got, want = (
        jax.jit(jax.value_and_grad(_flash_loss(ct, pad, block_h=block_h),
                                   argnums=(0, 1, 2)))(q, k, v)
        for pad in (0, dk - dv))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _refuses(what):
    """The transposed-operand kernels, a window, a bias and ALiBi take one
    head width: with two the call says so and computes nothing."""
    T, H = 128, 2
    q, k, v, ct = _mla_operands(T, 24, 16, H=H)
    kw = {"qkv_t": True, "window": 64,
          "bias": jnp.zeros((1, H, T, T), jnp.float32),
          "alibi": [2.0 ** -4, 2.0 ** -8]}[what]
    if what == "qkv_t":
        q, k, v = (x.transpose(0, 2, 3, 1) for x in (q, k, v))
    with pytest.raises(NotImplementedError, match="one head width"):
        jax.eval_shape(_flash_loss(ct, **{what: kw}), q, k, v)


# An equal-width call as PR 51's tree (117a64c) lowered it, by the sha256 of
# the text: ``lower().as_text()`` of the interpreted call (the kernels'
# bodies are in it) and, for ``compiled``, the jaxpr of the call as the chip
# compiles it (its blocks and the VMEM it asks for are in it). A PR that
# changes these kernels on purpose regenerates them (run the test, the
# assertion message holds the new value).
PARENT_TEXT = {
    ("std", "fwd", "plain"): "ad4072fd427876c1",
    ("std", "fwd", "bias"): "11f48e4cd245807f",
    ("std", "bwd", "plain"): "73964dd08344f320",
    ("std", "bwd", "bias"): "895bae0ea0fe37b5",
    ("qkv_t", "fwd", "plain"): "377cd5c0440b6dc2",
    ("qkv_t", "fwd", "bias"): "1476c4e1b436c536",
    ("qkv_t", "bwd", "plain"): "05aef29b64dfe5be",
    ("qkv_t", "bwd", "bias"): "b30dfa6f10d56a94",
    # the model's old call: 192 wide on both sides, 256 lanes in the kernels
    ("std", "bwd", "d192"): "3e58b1ee455e7fe2",
    # the same at the cell's size, bfloat16, as the chip compiles it
    ("std", "bwd", "compiled"): "fbb9972189581fec",
}


def _lowers_to_the_parents_text(layout, passes, what):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    B, T, H, d, dt, blocks = 2, 256, 2, 64, jnp.float32, 128
    if what == "d192":
        d = 192
    if what == "compiled":
        B, T, H, d, dt, blocks = 2, 8192, 32, 192, jnp.bfloat16, 1024
    x = jax.ShapeDtypeStruct(
        (B, H, d, T) if layout == "qkv_t" else (B, T, H, d), dt)
    args = [x] * 3 + [jax.ShapeDtypeStruct((1, H, T, T), jnp.float32)] \
        * (what == "bias")

    def loss(q, k, v, *bias):
        o = flash_attention(
            q, k, v, causal=True, qkv_t=layout == "qkv_t", block_q=blocks,
            block_k=blocks, block_h=1 if what == "compiled" else 2,
            bias=bias[0] if bias else None, interpret=what != "compiled")
        return jnp.sum(o.astype(jnp.float32))
    fn = jax.grad(loss, argnums=(0, 1, 2)) if passes == "bwd" else loss
    text = str(jax.make_jaxpr(fn)(*args)) if what == "compiled" \
        else jax.jit(fn).lower(*args).as_text()
    # PR 57 gave each ``pallas_call`` a ``name=``, which a jaxpr prints (PR
    # 51's read ``name=None``): the call differs from the parent's in that
    # parameter alone, so it is put back before the hash is taken
    text = re.sub(r"name=dstpu\.kernel\.[a-z_]+", "name=None", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_TEXT[layout, passes, what]


TWO_WIDTHS = {
    **{f"equals_the_padded_call-T{T}-block_h{bh}":
       (_equals_the_padded_call, (T, bh))
       for T in (256, 200) for bh in (1, 2)},
    # 24 and 16 both go to 64 lanes: two widths at the call, one inside
    "equals_the_padded_call-under_the_lanes":
        (_equals_the_padded_call, (96, 2, 24, 16)),
    **{f"refuses-{what}": (_refuses, (what,))
       for what in ("qkv_t", "window", "bias", "alibi")},
    **{"one_width_is_the_parents_text-" + "-".join(key):
       (_lowers_to_the_parents_text, key) for key in PARENT_TEXT},
}


@pytest.mark.parametrize("case", TWO_WIDTHS)
def test_a_value_width_of_its_own(case):
    """ISSUE 53: the standard-layout flash kernels take V, and give o, at
    the values' own width; what cannot take two widths says so; a call of
    one width is the program it was."""
    check, args = TWO_WIDTHS[case]
    check(*args)


def test_the_engine_says_what_its_step_traced(monkeypatch):
    """The training engine puts the one trace-time tally round its step:
    the flash calls this model's trace makes (keys of 24, values of 16: two
    widths each), one for the dense layer's block and one for the two
    sparse layers', which are alike and so traced once under
    ``jax.checkpoint``, with their one expert chain (``lax.ragged_dot`` on
    the CPU, no kernel); said once in the log when the step is traced and
    kept on the engine; a step that is not traced again says no more."""
    from deepspeed_tpu.runtime import engine as engine_module
    said = []
    monkeypatch.setattr(engine_module, "log_dist",
                        lambda text, **_: said.append(text))
    model = DeepseekV3(dataclasses.replace(
        CFG, dtype="bfloat16", use_flash_attention=True, flash_block_q=32,
        flash_block_k=32))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, seed=0,
                                               config=ENGINE)
    batch = {"input_ids": np.zeros((2 * len(jax.devices()), 48), np.int32)}
    for _ in range(2):
        engine.train_batch(batch)
    assert engine.traced_calls == {
        "train_step": {"flash": [2, 2], "expert": [1, 0]}}
    assert [t for t in said if "traced" in t] == [
        "train_step traced: expert: 1 calls, 0 kernel; "
        "flash: 2 calls, 2 two-width"]


def test_the_models_flash_path_equals_its_dense_path(params, ids, program):
    loss, grads = program
    model = DeepseekV3(dataclasses.replace(
        CFG, use_flash_attention=True, flash_block_q=32, flash_block_k=32))
    got_loss, got = _loss_and_grads(model, params, ids)
    assert abs(float(loss) - float(got_loss)) < 2e-5
    assert _worst(got, grads) < TOL


def test_a_call_that_fits_the_default_vmem_asks_for_nothing():
    """The flash kernels of cells 1 and 2 (T 1024 / 2048, d 64) are compiled
    as they always were; the new cell's (T 8192, d 256 after padding) ask
    for what their resident blocks need."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    assert fa._vmem_params(*(2 * [2048 * 128 * 2] + 2 * [1024 * 128 * 2]
                             + [1024 * 128 * 4])) == {}
    asked = fa._vmem_params(*(2 * [8192 * 256 * 2]))
    limit = asked["compiler_params"].vmem_limit_bytes
    assert 2 * 2 * 8192 * 256 * 2 < limit <= 112 << 20


@pytest.mark.parametrize("cell, batch, heads, T", [
    ("train-gpt2m-z2", 24, 16, 1024), ("train-opt1.3b-z3-dp4", 4, 32, 2048)])
def test_the_accepted_training_cells_never_reach_the_vmem_request(
        monkeypatch, cell, batch, heads, T):
    """Cells 1 and 2 call flash as their traffic files say: transposed
    operands (``flash_qkv_t``), tiles of 1024, one instance a grid step, head
    dim 64. That is ``_fwd_t`` / ``_bwd_t``, which this family's request for
    VMEM does not touch: traced forward and backward at their exact shapes,
    ``_vmem_params`` is never asked, so their kernels compile as they did."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    job = pb_common.load_cell(cell)[3]
    over = job["model_overrides"]
    assert (job["seq_len"], job["micro_batch_per_chip"]) == (T, batch)
    assert over["flash_qkv_t"] and not over["flash_bwd_qmajor"]
    asked = []
    monkeypatch.setattr(fa, "_vmem_params",
                        lambda *b: asked.append(b) or {})
    q = jax.ShapeDtypeStruct((batch, heads, 64, T), jnp.bfloat16)

    def loss(q, k, v):
        o = fa.flash_attention(
            q, k, v, causal=True, block_q=over["flash_block_q"],
            block_k=over["flash_block_k"], block_h=over["flash_block_h"],
            block_q_bwd=over["flash_block_q_bwd"] or None,
            block_k_bwd=over["flash_block_k_bwd"] or None,
            heads_major=False, qkv_t=True, bwd_qmajor=False)
        return jnp.sum(o.astype(jnp.float32))
    jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert asked == []


# ------------------------------------------------- the benchmark's arithmetic
@pytest.fixture(scope="module")
def sizes():
    cfg = pb_common.load_json("configs", "kanana-2-30b-a3b.json")
    return pb_common.load_module("builders", "deepseek_v3").sizes(cfg)


def test_counts_equal_the_issues_arithmetic(sizes):
    s = sizes
    assert abs(mla_moe.held_params(s) - 575.96e6) < 0.005e6
    assert abs(mla_moe.attention_params(s) - 26.35e6) < 0.005e6
    assert abs(mla_moe.expert_params(s) - 4.72e6) < 0.005e6
    assert mla_moe.held_experts_per_token(s) == 0.75
    per_token = mla_moe.train_flops_per_token(s, 8192)
    assert abs(per_token - 2.79e9) < 0.005e9
    # 45.7 TFLOP a step of 16,384 tokens = 0.23 s at the bf16 peak
    assert abs(16384 * per_token - 45.7e12) < 0.05e12
    assert abs(16384 * per_token / 197e12 - 0.232) < 0.001
    # attention: 83.9 MFLOP forward a token a layer; 7 products of which 4
    # are 192 wide and 3 are 128 wide
    ops, moved = mla_moe.mla_flash_work(2, s, 8192)
    assert ops == 2 * 32 * 8192 * 8192 * (4 * 192 + 3 * 128)
    assert abs(ops / 16384 / (1 + 2.6) - 83.9e6) < 0.05e6
    assert moved == 2 * 32 * 8192 * 6 * (192 + 128) * 2
    # the experts: 12,288 expected held rows, 9 products of 2 D F each a row
    ops, moved = mla_moe.held_experts_work(16384, s)
    assert ops == 18 * 12288 * 2048 * 768
    assert moved == 3 * 16 * 3 * 2048 * 768 * 2 + 4 * 12288 * 2048 * 2
    # the model object counts what the benchmark counts
    model = pb_common.load_module("builders", "deepseek_v3").model(
        pb_common.load_json("configs", "kanana-2-30b-a3b.json"))
    assert model.config.num_params() == mla_moe.held_params(s)


READERS = ["mfu_routed", "train_mla_attn_share", "train_moe_experts_share",
           "train_moe_route_share", "train_mla_flash_roofline",
           "train_moe_experts_roofline"]


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_on_a_program_without_its_scopes(name, sizes):
    """No trace, a trace file without the scopes (the recorded dense
    fixture), and another family's sizes: None, never an exception."""
    reader = pb_common.load_module("layer_metrics", name)
    trace = importlib.import_module("pbench.trace")
    peaks = pb_common.peaks_for("TPU v5 lite")
    counters = {"steps_traced": 2, "tokens_traced": 32768, "seq_len": 8192,
                "micro_batch_per_chip": 2}
    dense = trace.Trace(os.path.join(REPO, "perfbench", "fixtures",
                                     "dense1.xplane.pb"))
    gpt2 = pb_common.load_module("builders", "gpt2_family").sizes(
        pb_common.load_json("configs", "gpt2-medium.json"))
    for tr, s, c in ((None, sizes, counters), (dense, sizes, counters),
                     (dense, gpt2, counters), (None, gpt2, {})):
        view = types.SimpleNamespace(
            trace=tr, sizes=s, counters=c, peaks=peaks, chips=1,
            say=lambda *a, **k: None)
        assert reader.read(view) is None
    if name == "mfu_routed":
        view = types.SimpleNamespace(
            trace=None, sizes=sizes, peaks=peaks, chips=1,
            counters={**counters, "tok_s_chip_outside_capture": 10000.0},
            say=lambda *a, **k: None)
        assert abs(reader.read(view) - 100 * 10000 * 2.79e9 / 197e12) < 0.01


def test_scopes_reach_the_backward_operations(model, params, ids):
    """A backward operation's ``op_name`` carries its forward's scope: the
    benchmark's readers count forward, recomputation and backward under
    one name."""
    text = jax.jit(jax.grad(
        lambda p: model.loss(p, {"input_ids": ids}))).lower(params).as_text(
            debug_info=True)
    for scope in mla_moe.SCOPES + ("dstpu.mm.qkv", "dstpu.mm.mlp"):
        assert re.search(r"jvp\(" + re.escape(scope), text), scope
        assert re.search(r"transpose\(jvp\(.*" + re.escape(scope), text), \
            scope
