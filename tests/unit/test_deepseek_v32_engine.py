"""DeepSeek-V3.2-Exp as one chip's share (ISSUE 43) through the engine: a
cache whose blocks hold a latent and an index key and are read through a
selection the model makes for every query, the spans' counts of that read,
and what such a cache refuses. The model itself against its reference is
``test_deepseek_v32.py``; logits are compared, not tokens, as there."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from deepseek_v32_cases import (BS, CFG, ENGINE, SLOTS, TOL, TOPK,  # noqa: F401
                                TapEngine, model, params, prompts_of,
                                reference_rows, serve)
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.replica import Replica
from deepspeed_tpu.models import paged
from deepspeed_tpu.models.deepseek_v32 import DeepseekV32


# --------------------------------------------------------------- the engine
@pytest.fixture(scope="module", params=["auto", True], ids=["xla", "kernel"])
def mixed(request, params):
    """Three prompts at once through 16-token chunks: 5 tokens (one padded
    chunk, all keys selected to the end), 21 (two chunks, past index_topk
    in the second), 70 (five chunks across block boundaries), the later
    ones chunked while the earlier decode (fused dispatches), then 8-step
    decode dispatches to position 110. ``paged_kernel`` "auto" is the XLA
    read off a TPU, True the Pallas kernel (interpreted) in every chunk."""
    prompts = prompts_of(5, 21, 70)
    eng = TapEngine(DeepseekV32(CFG), {**ENGINE, "paged_kernel": request.param},
                    params=params)
    from deepspeed_tpu.inference.v2 import engine_v2
    spans, real = [], engine_v2.span

    def recording(name, **stats):
        if name == "dstpu.engine.dispatch":
            spans.append((stats["kind"], stats))
        return real(name, **stats)

    engine_v2.span = recording
    try:
        out = serve(eng, prompts, [40, 40, 40])
    finally:
        engine_v2.span = real
    return eng, prompts, out, spans


@pytest.mark.parametrize("which", [0, 1, 2],
                         ids=["one-chunk", "two-chunks", "five-chunks"])
def test_engine_equals_reference(params, mixed, which):
    _, prompts, out, spans = mixed
    tokens, rows = out[which]
    assert len(tokens) == 40 and rows.shape == (40, CFG.vocab_size)
    want = reference_rows(params, prompts[which], tokens)
    assert np.abs(rows - want).max() < TOL
    # and the comparison would have seen the nearest neighbours
    for variant in ({"select": False}, {"index_topk": TOPK // 2}):
        far = reference_rows(params, prompts[which], tokens, **variant)
        assert np.abs(far - want).max() > 50 * TOL
    assert {"chunk", "fused", "decode"} <= {kind for kind, _ in spans}


@pytest.mark.parametrize("count", [1, 8])
def test_streams_equal_whatever_the_fused_count(params, mixed, monkeypatch,
                                                count):
    """The decode steps a fused dispatch carries change no token: a single
    step, and the eight every fused dispatch once took from the config,
    give the streams of ``mixed`` (the engine's own count), here beside a
    budget that ends inside a fused dispatch: the steps left over run for
    a sequence that is gone, and nobody reads them."""
    from deepspeed_tpu.inference.v2 import engine_v2
    monkeypatch.setattr(engine_v2, "_FUSED_STEPS", count)
    base, prompts, want, spans = mixed
    eng = TapEngine(DeepseekV32(CFG),
                    {**ENGINE, "paged_kernel": base.config.paged_kernel},
                    params=params)
    steps, real = set(), eng._dispatch_span
    eng._dispatch_span = lambda kind, active, n, *a, **kw: (
        steps.add((kind, n)), real(kind, active, n, *a, **kw))[1]
    out = serve(eng, prompts, [40, 4, 40])
    for (tokens, _), (whole, _), n in zip(out, want, (40, 4, 40)):
        assert np.array_equal(tokens, whole[:n])
    mgr = eng.state_mgr
    assert mgr.allocator.free_blocks == mgr.allocator.total_blocks
    assert steps == {("chunk", 0), ("fused", count), ("decode", 8)}
    assert eng.telemetry_snapshot()["fused_dispatches"] > 4


@pytest.mark.parametrize("paged_kernel", ["auto", True], ids=["xla", "kernel"])
def test_bucketed_prefill_equals_reference(params, paged_kernel):
    """No split-fuse: a prompt goes through the bucketed prefill program,
    the chunk program at start 0 (70 tokens in a 96-token bucket)."""
    eng = TapEngine(DeepseekV32(CFG),
                    {**ENGINE, "splitfuse_tokens": 0, "prompt_bucket": 32,
                     "paged_kernel": paged_kernel}, params=params)
    prompts = prompts_of(70, 9, seed=4)
    for (tokens, rows), p in zip(serve(eng, prompts, [12, 12]), prompts):
        assert np.abs(rows - reference_rows(params, p, tokens)).max() < TOL


def test_cache_is_latent_blocks_under_the_tables(mixed):
    eng = mixed[0]
    assert set(eng.cache) == {"lat", "idx"} \
        and eng._account.slot_bytes == 0
    assert [x.shape[1:] for x in eng.cache["lat"]] \
        == [(BS, CFG.lat_row)] * CFG.n_layer
    assert eng._account.layers == {paged.LATENT: CFG.n_layer} \
        and not eng._slot_state
    assert eng._account.block_bytes == CFG.n_layer * BS * 4 * (
        CFG.lat_row + CFG.index_head_dim)
    assert eng.telemetry_snapshot()["cache_bytes_per_live_token"] \
        >= eng._account.block_bytes / BS
    geom = paged.geometry(eng.model)
    assert set(geom.kinds) == {paged.LATENT}
    assert not paged.uses_decode_kernel(eng.model, SLOTS, 32, BS,
                                        jnp.float32)


@pytest.mark.parametrize("paged_kernel", ["auto", True], ids=["xla", "kernel"])
def test_dispatch_spans_count_the_selected_read(params, monkeypatch,
                                                paged_kernel):
    """``index_keys`` and ``attended_keys`` on every dispatch span, against
    a count made a query at a time: the causal keys of each real query
    token, and min(that, index_topk), x 3 latent layers; and
    ``latent_read_calls`` / ``latent_read_kernel_calls``: the selected reads
    the span's program makes, 3 a chunk or a prefill and 3 a decode step (0
    on the dispatch that traces the program), of which the chunk's are the
    Pallas kernel where the engine's ``paged_kernel`` gives one
    (``test_fused_dispatch_counts_its_reads`` has the fused dispatch)."""
    from deepspeed_tpu.inference.v2 import engine_v2
    said = []
    real = engine_v2.span

    def recording(name, **stats):
        if name in ("dstpu.engine.dispatch", "dstpu.engine.prefill"):
            said.append((name, stats))
        return real(name, **stats)

    monkeypatch.setattr(engine_v2, "span", recording)
    kernel = paged_kernel is True
    config = {**ENGINE, "paged_kernel": paged_kernel}
    eng = InferenceEngineV2(DeepseekV32(CFG), config, params=params)
    eng.put(prompts_of(37, seed=6)[0], 11)
    while eng.has_work:
        eng.step()
    L, steps = CFG.n_layer, ENGINE["decode_steps_per_dispatch"]
    assert [st["kind"] for _, st in said] == ["chunk"] * 3 + ["decode"] * 2
    # one sequence: prompt tokens 0 .. 36 in chunks, then decode steps at
    # positions 37 .. (a dispatch runs all 8 steps; the last runs past
    # the budget, and the span counts what the device does)
    contexts, traced = [], set()
    for _, st in said:
        contexts += [len(contexts) + 1 + j
                     for j in range(st["chunk_tokens"] + st["steps"])]
        mine = contexts[-(st["chunk_tokens"] + st["steps"]):]
        assert st["index_keys"] == L * sum(mine)
        assert st["attended_keys"] == L * sum(min(c, TOPK) for c in mine)
        want = {"chunk": (L, L * kernel), "decode": (L * steps, 0)}[
            st["kind"]] if st["kind"] in traced else (0, 0)
        traced.add(st["kind"])
        assert (st["latent_read_calls"],
                st["latent_read_kernel_calls"]) == want, st
    assert sum(st["chunk_tokens"] for _, st in said) == 37
    # 2 chunks' and 1 decode dispatch's reads were counted
    assert eng.telemetry_snapshot()["latent_kernel_share"] \
        == round(2 * L * kernel / (2 * L + L * steps), 4)
    bucketed = InferenceEngineV2(
        DeepseekV32(CFG), {**config, "splitfuse_tokens": 0,
                           "prompt_bucket": 32}, params=params)
    del said[:]
    for _ in range(2):                      # the second finds it traced
        bucketed.put(prompts_of(37, seed=6)[0], 2)
    while bucketed.has_work:
        bucketed.step()
    first, second = [st for name, st in said
                     if name == "dstpu.engine.prefill"]
    for st in (first, second):
        assert st["index_keys"] == L * 37 * 38 // 2
        assert st["attended_keys"] == L * sum(min(c, TOPK)
                                              for c in range(1, 38))
    assert (first["latent_read_calls"], second["latent_read_calls"],
            second["latent_read_kernel_calls"]) == (0, L, L * kernel)


def test_fused_dispatch_counts_its_reads(mixed):
    """A fused dispatch's program holds a chunk and the engine's count of
    decode steps for its company: 3 x (1 + steps) selected reads (5 x (1 +
    2) = 15 with the cell's five latent layers), of which the chunk's 3
    (5) are the kernel where the engine runs kernels; the telemetry's
    ``latent_kernel_share`` is their share of all the engine's reads."""
    from deepspeed_tpu.inference.v2.engine_v2 import _FUSED_STEPS
    eng, _, _, spans = mixed
    L = CFG.n_layer
    kernel = eng.config.paged_kernel is True
    fused = [st for kind, st in spans if kind == "fused"]
    assert len(fused) > 1
    assert (fused[0]["latent_read_calls"],
            fused[0]["latent_read_kernel_calls"]) == (0, 0)   # it traces
    for st in fused[1:]:
        assert st["steps"] == _FUSED_STEPS != ENGINE[
            "decode_steps_per_dispatch"]
        assert (st["latent_read_calls"], st["latent_read_kernel_calls"]) \
            == (L * (1 + _FUSED_STEPS), L * kernel)
    reads = sum(st["latent_read_calls"] for _, st in spans)
    mine = sum(st["latent_read_kernel_calls"] for _, st in spans)
    assert eng.telemetry_snapshot()["latent_kernel_share"] \
        == round(mine / reads, 4)
    assert (mine > 0) == kernel


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
    assert "latent" in str(e.value)


def test_kv_transfer_refused_and_auto_is_off(model, params):
    eng = InferenceEngineV2(model, ENGINE, params=params)
    assert eng.prefix_cache is None and eng.draft_model is None
    for role in ("prefill", "decode"):
        with pytest.raises(RuntimeError, match="kv_transfer"):
            Replica("r", eng, role=role)
    assert Replica("r", eng).role == "colocated"


def test_a_share_outside_the_published_experts_is_refused():
    with pytest.raises(ValueError, match="held experts"):
        dataclasses.replace(CFG, experts_offset=14)
    with pytest.raises(ValueError, match="held experts"):
        dataclasses.replace(CFG, n_group=3)
