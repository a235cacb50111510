"""Olmo-Hybrid (ISSUE 41): the program against its plain reference
``perfbench/references/olmo_hybrid.py`` on seeded weights, tiny (d 64, two
periods of three gated delta-rule layers and one full-attention layer, 4
heads, dk 8 != dv 16, BS = 4), and the engine's handling of a cache that is
two kinds side by side: K/V pools under the block tables for two layers,
a conv tail and a matrix state by slot for six.

Logits are compared, not tokens. Everything runs in float32 (weights,
cache, programs), so the program and the reference differ by summation
order only: the chunkwise rule sums a chunk's tokens in another order than
the reference's token-by-token scan. ``TOL`` is 2e-5 of a logit whose
standard deviation is ~0.16: the measured differences are 4e-7 to 2e-6, and
the nearest wrong model, the matrix state kept in bfloat16, is 2e-3 to 4e-3
away (100x over it; ``beta`` without its factor 2, no QK-norm and a rope
are ~0.2): ``test_reference_tells_its_neighbours_apart`` holds that end,
and is where a lower-precision state fails a comparison (on the chip the
program's own bfloat16 products are further from the reference than that
neighbour is: PERF.md section 4).
"""

import dataclasses
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from deepspeed_tpu.inference.v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.v2.replica import Replica  # noqa: E402
from deepspeed_tpu.models import paged  # noqa: E402
from deepspeed_tpu.models.olmo_hybrid import (FULL, LINEAR,  # noqa: E402
                                              OLMO_HYBRID_7B,
                                              OLMO_HYBRID_TINY, OlmoHybrid)
from deepspeed_tpu.ops.gated_delta_rule import (CHUNK,  # noqa: E402
                                                chunk_rule, step_rule)
from deepspeed_tpu.ops.pallas.gated_delta_rule import (  # noqa: E402
    chunk_rule_kernel, head_group, live_slot_list, step_rule_kernel)

ref = importlib.import_module("references.olmo_hybrid")

CFG = dataclasses.replace(OLMO_HYBRID_TINY, dtype="float32")
H, DK, DV = CFG.linear_heads, CFG.linear_dk, CFG.linear_dv
BS, SLOTS, C = 4, 3, 8
TOL = 2e-5
ENGINE = dict(dtype="float32", max_batch_size=SLOTS, kv_block_size=BS,
              splitfuse_tokens=C, num_kv_blocks=96,
              decode_steps_per_dispatch=4)


@pytest.fixture(scope="module")
def model():
    return OlmoHybrid(CFG)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(0))


def reference_rows(params, prompt, tokens, **variant):
    """The reference's logits at the positions that emitted ``tokens``."""
    seq = np.concatenate([prompt, tokens])[None, :-1].astype(np.int32)
    rows = np.asarray(ref.logits(params, seq, n_head=CFG.n_head,
                                 **variant))[0]
    return rows[len(prompt) - 1:]


class TapEngine(importlib.import_module("pbench.tap").tap_engine()):
    """The tap picks a dispatch's rows out as the NEWEST it has seen, so it
    reads every decode dispatch before the next goes out (as
    tests/unit/test_phi4flash.py does)."""

    def _plain_decode(self, uids=None):
        out = super()._plain_decode(uids)
        self._settle()
        return out


def engine_of(model, params, **engine):
    return TapEngine(model, {**ENGINE, **engine}, params=params)


def serve(eng, prompts, max_new, order=None):
    """Run ``prompts`` through ``eng`` -> [(tokens, logits rows)] in the
    prompts' order. ``order``: lists of prompt indices put together, each
    list stepped until it is done."""
    uids = {}
    for group in order or [range(len(prompts))]:
        for i in group:
            uids[i] = eng.put(prompts[i], max_new[i])
        while eng.has_work:
            eng.step()
    return [(eng.get(uids[i]), np.stack(eng.rows[uids[i]]))
            for i in range(len(prompts))]


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
            for n in lengths]


# ---------------------------------------------------------------- the model
def test_layer_kinds_and_parameter_count(model):
    assert CFG.layer_types == (LINEAR, LINEAR, LINEAR, FULL) * 2
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == CFG.num_params()
    assert [ref.is_linear(p) for p in shapes["layers"]] \
        == [t == LINEAR for t in CFG.layer_types]
    full = OLMO_HYBRID_7B
    assert full.layer_types.count(LINEAR) == 24 and full.n_layer == 32
    assert (full.d_head, full.conv_channels) == (128, 11520)
    # ISSUE 41 counts 7,431 M by hand
    assert abs(full.num_params() - 7431e6) < 2e6
    cut = dataclasses.replace(full, layer_types=full.layer_types[:16])
    assert abs(cut.num_params() - 4101e6) < 1e6


def test_apply_equals_reference(model, params):
    ids = np.stack(prompts_of(150, 150, seed=1))
    got = np.asarray(model.apply(params, ids))
    want = np.asarray(ref.logits(params, ids, n_head=CFG.n_head))
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.1


NEIGHBOURS = [{"state_dtype": jnp.bfloat16}, {"beta_scale": 1.0},
              {"qk_norm": False}, {"rope_theta": 10000.0}]


@pytest.mark.parametrize("variant", NEIGHBOURS, ids=lambda v: next(iter(v)))
def test_reference_tells_its_neighbours_apart(params, variant):
    """Each neighbour of the published model (ISSUE 41 section 5) is
    further from the reference than the comparison's tolerance, so a
    program that was one of them would fail these tests: a matrix state
    kept in bfloat16 among them, the lower precision."""
    ids = np.stack(prompts_of(150, seed=1))
    want = np.asarray(ref.logits(params, ids, n_head=CFG.n_head))
    near = np.asarray(ref.logits(params, ids, n_head=CFG.n_head, **variant))
    assert np.abs(near - want).max() > 50 * TOL


# ----------------------------------------------------------------- the rule
def rule_inputs(T, seed=0, repeat=False):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    k = unit(rng.normal(size=(T, H, DK)))
    if repeat:                      # every key the same: A's entries are b
        k = np.broadcast_to(k[:1], k.shape)
    x = (unit(rng.normal(size=(T, H, DK))) * DK ** -0.5, k,
         rng.normal(size=(T, H, DV)), -rng.uniform(0, 2, size=(T, H)),
         rng.uniform(0, 2, size=(T, H)), rng.normal(size=(H, DK, DV)))
    return tuple(a.astype(np.float32) for a in x)


@pytest.mark.parametrize("T", [1, 5, CHUNK, 100, 2 * CHUNK, 200])
def test_chunkwise_rule_equals_sequential(T):
    """At lengths that are and are not multiples of 64, from a state that
    is not zero, to float32 rounding."""
    q, k, v, log_a, b, S0 = rule_inputs(T)
    want_o, want_S = ref.delta_rule(q, k, v, np.exp(log_a), b, S0)
    o, S = chunk_rule(*(x[None] for x in (q, k, v, log_a, b, S0)))
    assert o.shape == (1, T, H, DV)
    assert np.abs(o[0] - want_o).max() < 2e-5 * np.abs(want_o).max()
    assert np.abs(S[0] - want_S).max() < 2e-5 * np.abs(want_S).max()


def test_chunkwise_rule_survives_repeated_keys():
    """Sixty-four times the same key: ``I + A`` is all ``b`` under its
    diagonal, which a power series of A does not invert in float32 and a
    triangular solve does."""
    q, k, v, log_a, b, S0 = rule_inputs(2 * CHUNK, repeat=True)
    want_o, want_S = ref.delta_rule(q, k, v, np.exp(log_a), b, S0)
    o, S = chunk_rule(*(x[None] for x in (q, k, v, log_a, b, S0)))
    assert np.abs(o[0] - want_o).max() < 1e-4 * np.abs(want_o).max()
    assert np.abs(S[0] - want_S).max() < 1e-4 * np.abs(want_S).max()


def test_one_token_update_equals_sequential():
    q, k, v, log_a, b, S0 = rule_inputs(3, seed=2)
    S = S0[None]
    for t in range(3):
        o, S = step_rule(q[t][None], k[t][None], v[t][None],
                         log_a[t][None], b[t][None], S)
        want_o, want_S = ref.delta_rule(q[:t + 1], k[:t + 1], v[:t + 1],
                                        np.exp(log_a[:t + 1]), b[:t + 1], S0)
        assert np.abs(o[0] - want_o[-1]).max() < 1e-5
        assert np.abs(S[0] - want_S).max() < 1e-5


def test_padding_leaves_the_state_untouched():
    """Rows with ``log_a = 0, b = 0`` behind the real ones, whatever their
    q, k, v: the state is that after the last real row, bit for bit the
    state's own if no row is real."""
    q, k, v, log_a, b, S0 = rule_inputs(100, seed=3)
    real = 37
    log_a[real:], b[real:] = 0.0, 0.0
    o, S = chunk_rule(*(x[None] for x in (q, k, v, log_a, b, S0)))
    want_o, want_S = ref.delta_rule(q[:real], k[:real], v[:real],
                                    np.exp(log_a[:real]), b[:real], S0)
    assert np.abs(S[0] - want_S).max() < 2e-5 * np.abs(want_S).max()
    assert np.abs(o[0, :real] - want_o).max() < 2e-5 * np.abs(want_o).max()
    log_a[:], b[:] = 0.0, 0.0
    _, S = chunk_rule(*(x[None] for x in (q, k, v, log_a, b, S0)))
    assert np.array_equal(np.asarray(S[0]), S0)


# -------------------------------------------------------------- the kernels
# ``ops/pallas/gated_delta_rule.py`` against the XLA forms above, in the
# Pallas interpreter: the same products in the same precision, so they
# differ by the order of a few sums (measured 2e-7 to 5e-6 of the largest
# entry; the XLA form is itself 2e-5 from the sequential rule)
KERNEL_TOL = 2e-5


def batched(x):
    return tuple(a[None] for a in x)


@pytest.mark.parametrize("T", [1, 5, CHUNK, 100, 2 * CHUNK, 200, 1024])
def test_chunk_kernel_equals_xla_form(T):
    """o AND the state, from a state that is not zero, at lengths that
    are and are not whole 64-token chunks (sixteen of them at 1,024)."""
    x = batched(rule_inputs(T, seed=4))
    want_o, want_S = chunk_rule(*x)
    o, S = chunk_rule_kernel(*x)
    assert o.shape == (1, T, H, DV) and S.shape == (1, H, DK, DV)
    assert np.abs(o - want_o).max() < KERNEL_TOL * np.abs(want_o).max()
    assert np.abs(S - want_S).max() < KERNEL_TOL * np.abs(want_S).max()


def test_chunk_kernel_takes_rows_and_head_groups():
    """Two rows of a batch, each from its own state, and heads that are
    more than one group (12 heads: two groups of 6, as 30 are five)."""
    assert [head_group(h) for h in (4, 12, 30, 32)] == [4, 6, 6, 8]
    parts = [rule_inputs(70, seed=s) for s in (6, 7, 8)]
    # heads are axis 1 of q, k, v, log_a, b and axis 0 of the state
    x = [np.concatenate(leaves, axis=0 if i == 5 else 1)
         for i, leaves in enumerate(zip(*parts))]
    x = [np.stack([a, a[::-1]]) for a in x]        # the second row: other data
    want_o, want_S = chunk_rule(*x)
    o, S = chunk_rule_kernel(*x)
    assert o.shape == (2, 70, 3 * H, DV)
    assert np.abs(o - want_o).max() < KERNEL_TOL * np.abs(want_o).max()
    assert np.abs(S - want_S).max() < KERNEL_TOL * np.abs(want_S).max()


def test_chunk_kernel_survives_repeated_keys():
    """``test_chunkwise_rule_survives_repeated_keys``' data: the kernel's
    inverse is the triangular solve's algebra too, never a series."""
    q, k, v, log_a, b, S0 = rule_inputs(2 * CHUNK, repeat=True)
    want_o, want_S = ref.delta_rule(q, k, v, np.exp(log_a), b, S0)
    o, S = chunk_rule_kernel(*batched((q, k, v, log_a, b, S0)))
    assert np.abs(o[0] - want_o).max() < 1e-4 * np.abs(want_o).max()
    assert np.abs(S[0] - want_S).max() < 1e-4 * np.abs(want_S).max()
    xo, xS = chunk_rule(*batched((q, k, v, log_a, b, S0)))
    assert np.abs(o - xo).max() < KERNEL_TOL * np.abs(xo).max()
    assert np.abs(S - xS).max() < KERNEL_TOL * np.abs(xS).max()


@pytest.mark.parametrize("real", [0, 37, 64], ids=lambda n: f"{n}-real")
def test_chunk_kernel_padded_tail_leaves_the_state(real):
    """Rows with ``log_a = 0, b = 0`` behind ``real`` real ones: the
    state is that after the last real row, and a chunk (or a call) that
    is padding alone hands S back bit for bit."""
    q, k, v, log_a, b, S0 = rule_inputs(3 * CHUNK, seed=3)
    log_a[real:], b[real:] = 0.0, 0.0
    _, S = chunk_rule_kernel(*batched((q, k, v, log_a, b, S0)))
    if not real:
        assert np.array_equal(np.asarray(S[0]), S0)
        return
    _, stop = chunk_rule_kernel(*batched(
        tuple(a[:real] for a in (q, k, v, log_a, b)) + (S0,)))
    # the padding's two whole chunks (and the rest of a first one that is
    # partly real) moved nothing
    want = np.asarray(chunk_rule(*batched(
        tuple(a[:real] for a in (q, k, v, log_a, b)) + (S0,)))[1])
    assert np.abs(S - want).max() < KERNEL_TOL * np.abs(want).max()
    if real == CHUNK:
        assert np.array_equal(np.asarray(S), np.asarray(stop))


LIVE_SETS = {"none": [], "one": [3], "some": [0, 2, 3], "all": [0, 1, 2, 3, 4]}


@pytest.mark.parametrize("live", sorted(LIVE_SETS))
def test_step_kernel_touches_live_slots_only(live):
    """The step kernel = ``step_rule`` on the live slots, and every dead
    slot's ``ssm`` row is bit for bit what it was."""
    slots = 5
    q, k, v, log_a, b, _ = rule_inputs(slots, seed=7)
    ssm = np.random.default_rng(8).normal(
        size=(slots, H, DK, DV)).astype(np.float32)
    active = np.zeros(slots, bool)
    active[LIVE_SETS[live]] = True
    slot_of, n = live_slot_list(jnp.asarray(active))
    assert int(n) == active.sum()
    assert list(np.asarray(slot_of)[:int(n)]) == LIVE_SETS[live]
    assert not np.asarray(slot_of)[int(n):].any()
    want_o, want_S = step_rule(q, k, v, log_a, b, ssm)
    o, S = step_rule_kernel(q, k, v, log_a, b, jnp.asarray(ssm),
                            (slot_of, n))
    o, S = np.asarray(o), np.asarray(S)
    assert np.array_equal(S[~active], ssm[~active])
    assert np.isfinite(o).all()
    if active.any():
        assert np.abs(o[active] - np.asarray(want_o)[active]).max() < 1e-5
        assert np.abs(S[active] - np.asarray(want_S)[active]).max() < 1e-5


# --------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def mixed(model, params):
    """Three prompts at once through 8-token chunks: 5 tokens (one padded
    chunk), 21 (three chunks, the last padded), 70 (nine chunks, past the
    rule's 64-token chunk), the later ones chunked into their slot while
    the earlier decode in theirs (fused dispatches)."""
    prompts = prompts_of(5, 21, 70)
    eng = engine_of(model, params)
    kinds = []
    real = eng._dispatch_span

    def noting(kind, *a, **kw):
        kinds.append(kind)
        return real(kind, *a, **kw)

    eng._dispatch_span = noting
    out = serve(eng, prompts, [40, 40, 40])
    return eng, prompts, out, kinds


@pytest.mark.parametrize("which", [0, 1, 2],
                         ids=["one-chunk", "three-chunks", "nine-chunks"])
def test_engine_equals_reference(params, mixed, which):
    _, prompts, out, kinds = mixed
    tokens, rows = out[which]
    assert len(tokens) == 40 and rows.shape == (40, CFG.vocab_size)
    want = reference_rows(params, prompts[which], tokens)
    assert np.abs(rows - want).max() < TOL
    # and the comparison would have seen the nearest neighbours
    for variant in NEIGHBOURS[:2]:
        far = reference_rows(params, prompts[which], tokens, **variant)
        assert np.abs(far - want).max() > 50 * TOL
    assert {"chunk", "fused", "decode"} <= set(kinds)


@pytest.mark.parametrize("count", ["kept", 1, 8])
def test_streams_equal_whatever_the_fused_count(model, params, mixed,
                                                monkeypatch, count):
    """The decode steps a fused dispatch carries change no token: the
    engine's own count (``mixed``, and here beside a budget that ends
    inside a fused dispatch), a single step, and the eight every fused
    dispatch once took from the config. The second sequence ends with
    steps of its dispatch left over: they run into its own state and
    nobody reads them."""
    from deepspeed_tpu.inference.v2 import engine_v2
    if count == "kept":
        count = engine_v2._FUSED_STEPS
    monkeypatch.setattr(engine_v2, "_FUSED_STEPS", count)
    _, prompts, want, _ = mixed
    eng = engine_of(model, params)
    steps, real = set(), eng._dispatch_span
    eng._dispatch_span = lambda kind, active, n, *a, **kw: (
        steps.add((kind, n)), real(kind, active, n, *a, **kw))[1]
    out = serve(eng, prompts, [40, 4, 40])
    for (tokens, _), (whole, _), n in zip(out, want, (40, 4, 40)):
        assert np.array_equal(tokens, whole[:n])
    mgr = eng.state_mgr
    assert mgr.allocator.free_blocks == mgr.allocator.total_blocks
    assert steps == {("chunk", 0), ("fused", count),
                     ("decode", ENGINE["decode_steps_per_dispatch"])}
    assert eng.telemetry_snapshot()["fused_dispatches"] > 4


def test_engine_on_the_kernel_path_equals_reference(model, params, mixed):
    """``paged_kernel=True``: chunks through the chunk kernel (off the
    TPU "auto" keeps a chunk dense, and the rule follows the attention),
    decode steps through the step kernel as in ``mixed``."""
    _, prompts, _, _ = mixed
    eng = engine_of(model, params, paged_kernel=True)
    out = serve(eng, prompts, [12] * 3)
    for prompt, (tokens, rows) in zip(prompts, out):
        want = reference_rows(params, prompt, tokens)
        assert np.abs(rows - want).max() < TOL
    snap = eng.telemetry_snapshot()
    assert snap["rule_kernel_share"] == 1.0
    # and ``mixed``, at "auto" on a CPU: the steps' kernel, the chunks' XLA
    assert 0.5 < mixed[0].telemetry_snapshot()["rule_kernel_share"] < 1.0


def test_cache_is_two_kinds_and_only_full_layers_are_paged(mixed):
    eng = mixed[0]
    shapes = jax.tree.map(lambda x: x.shape, eng.cache)
    pool = (96, CFG.n_head, BS, CFG.d_head)
    assert shapes["k"] == shapes["v"] == [pool] * 2
    assert shapes["conv"] == [(SLOTS, CFG.linear_conv - 1,
                               CFG.conv_channels)] * 6
    assert shapes["ssm"] == [(SLOTS, H, DK, DV)] * 6
    assert eng.cache["ssm"][0].dtype == jnp.float32
    assert eng._ring_blocks == 0 and eng._state_layers == 6
    assert eng._block_bytes == 2 * 2 * np.prod(pool[1:]) * 4
    assert eng._slot_bytes == 6 * 4 * (
        (CFG.linear_conv - 1) * CFG.conv_channels + H * DK * DV)
    geom = paged.geometry(eng.model)
    assert geom.kinds == ((paged.STATE,) * 3 + (paged.KV,)) * 2
    assert (geom.n_kv_heads, geom.d_head) == (CFG.n_head, CFG.d_head)


def test_bucketed_prefill_equals_chunked(model, params, mixed):
    """The whole prompt in one padded program (two 64-token chunks of the
    rule, the second mostly padding) leaves the state of the last real
    token."""
    _, prompts, out, _ = mixed
    whole = serve(engine_of(model, params, splitfuse_tokens=0,
                            prompt_bucket=96), prompts, [12] * 3)
    for (_, a), (_, b) in zip(whole, out):
        assert np.abs(a - b[:12]).max() < TOL


def test_reused_slot_equals_fresh_engine(model, params):
    """A chunk at position 0 starts from zero state whatever the slot
    held: the one slot of an engine serves a sequence as it did when
    nothing had been in it."""
    other, probe = prompts_of(21, 9, seed=5)
    eng = engine_of(model, params, max_batch_size=1)
    fresh, _, reused = serve(eng, [probe, other, probe], [20, 20, 20],
                             order=[[0], [1], [2]])
    assert np.array_equal(reused[0], fresh[0])
    assert np.array_equal(reused[1], fresh[1])


def test_live_slot_unmoved_by_dead_and_new_ones(mixed):
    """Slots that die, stay empty and are taken again beside a live
    sequence never touch it: dead slots may compute, into their own
    state only."""
    eng, prompts, out, _ = mixed
    others = prompts_of(6, 11, 7, seed=9)
    got = serve(eng, [prompts[2]] + others, [40, 3, 9, 5])
    assert np.array_equal(got[0][0], out[2][0])
    assert np.abs(got[0][1] - out[2][1]).max() < TOL


@pytest.mark.parametrize("paged_kernel", [True, False],
                         ids=["kernels", "xla"])
def test_dispatch_spans_count_the_rule(model, params, monkeypatch,
                                       paged_kernel):
    """``state_updates`` and ``rule_rows`` on every dispatch span: live
    slots x steps x 6 linear layers, and the chunk's padded rows x 6; and
    ``rule_calls`` / ``rule_kernel_calls``: the calls of the rule the
    span's program makes (6 a chunk call, 6 a decode step), noted when
    the program is traced (so 0 on the dispatch that traces it), all of
    them kernels where the step runs kernels and none where it does not."""
    from deepspeed_tpu.inference.v2 import engine_v2
    said = []
    real = engine_v2.span

    def recording(name, **stats):
        if name == "dstpu.engine.dispatch":
            said.append(stats)
        return real(name, **stats)

    monkeypatch.setattr(engine_v2, "span", recording)
    eng = InferenceEngineV2(model, {**ENGINE, "paged_kernel": paged_kernel},
                            params=params)
    for p in prompts_of(5, 21):
        eng.put(p, 6)
    while eng.has_work:
        eng.step()
    assert {st["kind"] for st in said} >= {"chunk", "fused"}
    traced = set()
    for st in said:
        assert st["state_updates"] == st["active"] * st["steps"] * 6
        assert st["rule_rows"] == (0 if st["kind"] == "decode" else C * 6)
        calls = 6 * (st["steps"] + (st["kind"] != "decode"))
        want = calls if st["kind"] in traced else 0
        traced.add(st["kind"])
        assert st["rule_calls"] == want
        assert st["rule_kernel_calls"] == (want if paged_kernel else 0)
    assert sum(st["rule_calls"] for st in said) > 0
    assert sum(st["chunk_tokens"] for st in said) == 26
    assert sum(st["rule_rows"] for st in said) == (1 + 3) * C * 6
    assert eng.telemetry_snapshot()["rule_kernel_share"] \
        == float(paged_kernel)


def test_cache_bytes_counter(mixed):
    eng = mixed[0]
    held = eng.telemetry_snapshot()["cache_bytes_per_live_token"]
    # a sequence holds a slot's state from its first step: more than a
    # block's bytes a token
    assert held > eng._block_bytes / BS
    assert held == round(eng.telemetry._cache_bytes
                         / eng.telemetry._live_tokens)


# ------------------------------------------------------------- the refusals
@pytest.mark.parametrize("knobs, named", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"spec_draft": True}, "spec_draft"),
    ({"draft": True}, "draft model"),
    ({"kv_host_offload": True, "device_kv_blocks": 8}, "kv_host_offload"),
], ids=lambda x: x if isinstance(x, str) else "")
def test_engine_refuses_by_name(model, params, knobs, named):
    knobs = dict(knobs)
    extra = {"draft_model": model, "draft_params": params} \
        if knobs.pop("draft", False) or "spec_draft" in knobs else {}
    with pytest.raises(ValueError, match=named) as e:
        InferenceEngineV2(model, {**ENGINE, **knobs}, params=params,
                          **extra)
    assert "slot" in str(e.value)


def test_kv_transfer_refused_and_auto_is_off(model, params):
    eng = InferenceEngineV2(model, ENGINE, params=params)
    assert eng.prefix_cache is None and eng.draft_model is None
    for role in ("prefill", "decode"):
        with pytest.raises(RuntimeError, match="kv_transfer"):
            Replica("r", eng, role=role)
    assert Replica("r", eng).role == "colocated"


def test_unknown_layer_type_is_refused():
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(CFG, layer_types=(LINEAR, "sliding_attention"))
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(CFG, layer_types=(LINEAR,) * 4)
