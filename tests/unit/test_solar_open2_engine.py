"""Solar-Open2 (ISSUE 54) through the engine: a cache that is K/V pools
under the block tables for the GQA layers and a conv tail and a matrix
state by slot for the delta-rule layers, beside an expert layer held as a
share; the spans' counts of the rule and the experts; and what such a cache
refuses. The model itself against its reference is ``test_solar_open2.py``;
logits are compared, not tokens, as there."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.replica import Replica
from deepspeed_tpu.models import paged
from solar_open2_cases import (BS, C, CUT, DK, DV, ENGINE, H,  # noqa: F401
                               NEIGHBOURS, SLOTS, TOL, cut, cut_params,
                               engine_of, prompts_of, reference_rows, serve)

N_KDA = CUT.n_layer - len(CUT.gqa_layers)        # 3 of the 5 layers


@pytest.fixture(scope="module")
def mixed(cut, cut_params):
    """Three prompts at once through 8-token chunks: 5 tokens (one padded
    chunk), 21 (three chunks, the last padded), 70 (nine chunks, past the
    rule's 64-token chunk), the later ones chunked into their slot while
    the earlier decode in theirs (fused dispatches)."""
    from deepspeed_tpu.inference.v2 import engine_v2
    prompts = prompts_of(5, 21, 70)
    eng = engine_of(cut, cut_params)
    kinds, said = [], []
    real, real_span = eng._dispatch_span, engine_v2.span

    def noting(kind, *a, **kw):
        kinds.append(kind)
        return real(kind, *a, **kw)

    def recording(name, **stats):
        if name == "dstpu.engine.dispatch":
            said.append(stats)
        return real_span(name, **stats)

    eng._dispatch_span = noting
    engine_v2.span = recording
    try:
        out = serve(eng, prompts, [24, 24, 24])
    finally:
        engine_v2.span = real_span
    return eng, prompts, out, kinds, said


@pytest.mark.parametrize("which", [0, 1, 2],
                         ids=["one-chunk", "three-chunks", "nine-chunks"])
def test_engine_equals_reference(cut_params, mixed, which):
    """Prefill in chunks, then decoding through the cache, against the
    reference's full forward."""
    _, prompts, out, kinds, _ = mixed
    tokens, rows = out[which]
    assert len(tokens) == 24 and rows.shape == (24, CUT.vocab_size)
    want = reference_rows(cut_params, prompts[which], tokens)
    assert np.abs(rows - want).max() < TOL
    # and the comparison would have seen the nearest neighbours: a
    # bfloat16 state, one gate a head, a softmax router
    for variant in NEIGHBOURS[:3]:
        far = reference_rows(cut_params, prompts[which], tokens, **variant)
        assert np.abs(far - want).max() > 3 * TOL
    assert {"chunk", "fused", "decode"} <= set(kinds)


def test_freed_slots_taken_again_equal_fresh_ones(cut_params, mixed):
    """A chunk at position 0 starts from zero state whatever the slot
    held: every slot of ``mixed`` has been freed, and the sequences that
    take them again, one of them the 70-token prompt once more, read as
    on a fresh engine; slots that die and are taken again beside it never
    touch a live one."""
    eng, prompts, out, _ = mixed[:4]
    mgr = eng.state_mgr
    assert mgr.allocator.free_blocks == mgr.allocator.total_blocks
    others = prompts_of(6, 11, 7, seed=9)
    got = serve(eng, [prompts[2]] + others, [24, 3, 9, 5])
    assert np.array_equal(got[0][0], out[2][0])
    assert np.abs(got[0][1] - out[2][1]).max() < TOL
    want = reference_rows(cut_params, others[1], got[2][0])
    assert np.abs(got[2][1] - want).max() < TOL


def test_cache_is_pools_and_slot_state(mixed):
    eng = mixed[0]
    shapes = jax.tree.map(lambda x: x.shape, eng.cache)
    pool = (96, CUT.n_kv_heads, BS, CUT.d_head)
    assert shapes["k"] == shapes["v"] == [pool] * 2
    assert shapes["conv"] == [(SLOTS, CUT.linear_conv - 1,
                               CUT.conv_channels)] * N_KDA
    assert shapes["ssm"] == [(SLOTS, H, DK, DV)] * N_KDA
    assert eng.cache["ssm"][0].dtype == jnp.float32
    account = eng._account
    assert account.layers[paged.STATE] == N_KDA \
        and account.layers[paged.KV] == 2
    assert account.block_bytes == 2 * 2 * np.prod(pool[1:]) * 4
    assert account.slot_bytes == N_KDA * 4 * (
        (CUT.linear_conv - 1) * CUT.conv_channels + H * DK * DV)
    geom = paged.geometry(eng.model)
    assert geom.kinds == (paged.KV,) + (paged.STATE,) * 3 + (paged.KV,)
    assert (geom.n_head, geom.n_kv_heads, geom.d_head) \
        == (CUT.n_head, CUT.n_kv_heads, CUT.d_head)


def test_dispatch_spans_count_the_rule_and_the_experts(mixed):
    """``state_updates`` and ``rule_rows`` on every dispatch span: live
    slots x steps x 3 delta-rule layers, and the chunk's padded rows x 3;
    ``rule_calls`` / ``rule_kernel_calls`` say which form ran: a chunk's
    rule is XLA (a gate a key channel has no chunk kernel), a decode
    step's the step kernel; ``expert_calls``: one a layer a program call.
    The calls are noted when a program is traced, so 0 on the dispatch
    that traces it."""
    eng, said = mixed[0], mixed[4]
    assert {st["kind"] for st in said} >= {"chunk", "fused", "decode"}
    traced = set()
    for st in said:
        assert st["state_updates"] == st["active"] * st["steps"] * N_KDA
        chunk = st["kind"] != "decode"
        assert st["rule_rows"] == (C * N_KDA if chunk else 0)
        first = st["kind"] not in traced
        traced.add(st["kind"])
        assert st["rule_calls"] == (
            0 if first else N_KDA * (st["steps"] + chunk))
        assert st["rule_kernel_calls"] == (
            0 if first else N_KDA * st["steps"])
        assert st["expert_calls"] == (
            0 if first else CUT.n_layer * (st["steps"] + chunk))
    assert sum(st["rule_calls"] for st in said) > 0
    assert sum(st["chunk_tokens"] for st in said) == 5 + 21 + 70
    assert 0.0 < eng.telemetry_snapshot()["rule_kernel_share"] < 1.0


def test_cache_bytes_counter(mixed):
    eng = mixed[0]
    held = eng.telemetry_snapshot()["cache_bytes_per_live_token"]
    # a sequence holds a slot's state from its first step: more than a
    # block's bytes a token
    assert held > eng._account.block_bytes / BS


# ------------------------------------------------------------- the refusals
@pytest.mark.parametrize("feature", ["prefix_cache", "spec_draft",
                                     "kv_host_offload", "kv_transfer"])
def test_refusals_name_the_slot_state(cut, feature):
    account = paged.Account(cut, SLOTS, 64, BS, jnp.float32)
    assert account.refusal(feature) == paged._REFUSALS["slot", feature]
    assert "slot_state" in account.refusal(feature)


@pytest.mark.parametrize("knobs, named", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"spec_draft": True}, "spec_draft"),
    ({"draft": True}, "draft model"),
    ({"kv_host_offload": True, "device_kv_blocks": 8}, "kv_host_offload"),
], ids=lambda x: x if isinstance(x, str) else "")
def test_engine_refuses_by_name(cut, cut_params, knobs, named):
    knobs = dict(knobs)
    extra = {"draft_model": cut, "draft_params": cut_params} \
        if knobs.pop("draft", False) or "spec_draft" in knobs else {}
    with pytest.raises(ValueError, match=named) as e:
        InferenceEngineV2(cut, {**ENGINE, **knobs}, params=cut_params,
                          **extra)
    assert "slot" in str(e.value)


def test_kv_transfer_refused_and_auto_is_off(mixed):
    eng = mixed[0]
    assert eng.prefix_cache is None and eng.draft_model is None
    for role in ("prefill", "decode"):
        with pytest.raises(RuntimeError, match="kv_transfer"):
            Replica("r", eng, role=role)
    assert Replica("r", eng).role == "colocated"
