"""Olmo-Hybrid (ISSUE 41) through the engine: a cache that is two kinds side
by side (K/V pools under the block tables for two layers, a conv tail and a
matrix state by slot for six), the spans' counts of the rule, and what such
a cache refuses. The model itself against its reference is
``test_olmo_hybrid.py``; logits are compared, not tokens, as there."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.replica import Replica
from deepspeed_tpu.models import paged
from deepspeed_tpu.models.olmo_hybrid import LINEAR
from olmo_hybrid_cases import (BS, C, CFG, DK, DV, ENGINE, H,  # noqa: F401
                               NEIGHBOURS, SLOTS, TOL, engine_of, model,
                               params, prompts_of, reference_rows, serve)


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
    assert eng._ring_blocks == 0 \
        and eng._account.layers[paged.STATE] == 6
    assert eng._account.block_bytes == 2 * 2 * np.prod(pool[1:]) * 4
    assert eng._account.slot_bytes == 6 * 4 * (
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
    assert held > eng._account.block_bytes / BS
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
