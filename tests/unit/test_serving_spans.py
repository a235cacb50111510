"""The serving timeline inside the engine (ISSUE 24): ``dstpu.*`` spans on
the profiler's clock from ``Router.step`` down to build / fetch / post, the
queue-wait and occupancy counters that ride them, the perfbench readers
that reduce them, and the SPAN_SCHEMA lint (both directions, like the tag
schema's in test_telemetry.py).

Engines follow test_router.py's fast pattern: tiny GPT2, module-cached
params; one profiler capture per engine kind, shared by the tests."""

import copy
import os
import re
import sys
import types

import numpy as np
import pytest

import jax

from deepspeed_tpu.autotuning import kernel_dispatch
from deepspeed_tpu.inference.v2 import InferenceEngineV2, Router
from deepspeed_tpu.inference.v2 import engine_v2, router as router_mod
from deepspeed_tpu.inference.v2.replica import Replica
from deepspeed_tpu.models import GPT2, GPT2Config
from deepspeed_tpu.monitor.tag_schema import (
    KERNEL_SCHEMA, KERNEL_SHARES, KERNEL_TALLY, SCOPE_SCHEMA, SHAPE_PATHS,
    SPAN_SCHEMA)
from deepspeed_tpu.monitor.telemetry import ServingTelemetry
from deepspeed_tpu.utils import groups

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))
from pbench import (common as pb_common, dsa as pb_dsa,  # noqa: E402
                    gdn as pb_gdn, mla_moe as pb_mla_moe,
                    moe as pb_moe, names as pb_names, ssm as pb_ssm,
                    trace as pb_trace, weights as pb_weights)

_CFG = GPT2Config(n_layer=2, n_head=4, d_model=64, max_seq_len=128,
                  vocab_size=256, remat=False, dtype="float32")
_BASE = {"dtype": "float32", "kv_block_size": 8, "prompt_bucket": 16,
         "max_batch_size": 4, "decode_steps_per_dispatch": 2,
         # dense-gather attention: in interpret mode the Pallas kernels
         # put a million thunk events into a CPU trace
         "paged_kernel": False}
LEAVES = ("dstpu.engine.build", "dstpu.engine.fetch", "dstpu.engine.post")
N_REQUESTS = 6
READERS = ("queue_wait_p50_ms", "batch_occupancy", "decode_dispatch_ms",
           "fused_dispatch_ms", "prefill_wall_share", "host_build_share",
           "host_sync_share", "host_post_share")


def _router(splitfuse_tokens):
    kernel_dispatch.reset()
    groups.reset()
    model = GPT2(_CFG)
    engine = InferenceEngineV2(
        model, params=model.init(jax.random.key(0)),
        config=dict(_BASE, splitfuse_tokens=splitfuse_tokens))
    return Router([Replica("r0", engine)]), engine


def _serve(router, n=N_REQUESTS, seed=0):
    """``n`` requests, one more put every other round so that admissions
    land between decode-only steps."""
    rng = np.random.RandomState(seed)
    todo = [rng.randint(1, 250, size=int(k)).astype(np.int32)
            for k in rng.randint(5, 40, size=n)]
    while todo or router.has_work:
        if todo:
            router.put(todo.pop(), max_new_tokens=6)
        router.step()
        router.step()


def _captured(tmp_path_factory, splitfuse_tokens):
    router, engine = _router(splitfuse_tokens)
    _serve(router, 2, seed=1)                  # compile outside the capture
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with pb_trace.capture(trace_dir):
        _serve(router)
    # rehearse=False: XLA:CPU runs these tiny programs inline on the
    # Python thread, and a rehearsal reads a thread that ran operations as
    # a device and drops its spans
    tr = pb_trace.Trace(pb_trace.find_xplane(trace_dir), rehearse=False)
    return tr, engine


@pytest.fixture(scope="module")
def bucketed(tmp_path_factory):
    return _captured(tmp_path_factory, 0)


@pytest.fixture(scope="module")
def splitfuse(tmp_path_factory):
    return _captured(tmp_path_factory, 16)


def _inside(e, parents):
    return any(p.start <= e.start and e.end <= p.end for p in parents)


def _check_nesting(tr):
    spans = {name: tr.host_spans(name) for name in SPAN_SCHEMA}
    leaves = sorted((e for n in LEAVES for e in spans[n]),
                    key=lambda e: e.start)
    for a, b in zip(leaves, leaves[1:]):
        assert a.end <= b.start, f"{a.name} overlaps {b.name}"
    settles = spans["dstpu.engine.settle"]
    boxes = spans["dstpu.engine.prefill"] + spans["dstpu.engine.dispatch"] \
        + settles
    for name in ("dstpu.engine.fetch", "dstpu.engine.post"):
        for e in spans[name]:
            assert _inside(e, boxes), f"{name} outside prefill/dispatch/settle"
    for name in ("dstpu.engine.build", "dstpu.engine.prefill",
                 "dstpu.engine.admit", "dstpu.engine.dispatch",
                 "dstpu.engine.settle"):
        for e in spans[name]:
            assert _inside(e, spans["dstpu.engine.step"]), name
    for e in spans["dstpu.engine.step"]:
        assert _inside(e, spans["dstpu.router.step"])
    unposted = []
    for d in spans["dstpu.engine.dispatch"]:
        assert not _inside(d, settles)
        assert sum(_inside(e, [d]) for e in spans["dstpu.engine.fetch"]) == 1
        posts = sum(_inside(e, [d]) for e in spans["dstpu.engine.post"])
        assert posts <= 1
        if not posts:
            unposted.append(d)
    # one dispatch span a program call: the first plain decode of a run
    # posts nothing (there is none before it to read), every chained one
    # posts the one before, and the last of the run is read under a
    # settle span: one fetch and one post, in that order (ISSUE 35)
    for d in unposted:
        assert d.stats["kind"] == "decode" and not d.stats["chained"]
    assert len(settles) == len(unposted)
    for box in settles:
        f, p = ([e for e in spans[name] if _inside(e, [box])] for name in
                ("dstpu.engine.fetch", "dstpu.engine.post"))
        assert len(f) == len(p) == 1 and f[0].end <= p[0].start
    for d in spans["dstpu.engine.dispatch"]:
        assert d.stats["chained"] in (0, 1)
        assert d.stats["chained"] <= (d.stats["kind"] == "decode")
        assert d.stats["late_steps"] == 0       # no request sets an EOS
    return spans


def test_bucketed_timeline(bucketed):
    tr, _ = bucketed
    spans = _check_nesting(tr)
    for name in SPAN_SCHEMA:                # this path can emit every span
        assert spans[name], f"no {name} span in the trace"
    assert {e.stats["kind"] for e in spans["dstpu.engine.dispatch"]} \
        == {"decode"}
    # max_new_tokens=6 at 2 steps a dispatch: three dispatches a run at
    # the least, all but the first enqueued behind an unread one
    chained = sum(e.stats["chained"] for e in spans["dstpu.engine.dispatch"])
    assert 2 * chained >= len(spans["dstpu.engine.dispatch"])
    # a prefill is build + fetch + post, inside its admit
    for p in spans["dstpu.engine.prefill"]:
        assert _inside(p, spans["dstpu.engine.admit"])
        for name in LEAVES:
            assert sum(_inside(e, [p]) for e in spans[name]) == 1


def test_splitfuse_timeline(splitfuse):
    tr, _ = splitfuse
    spans = _check_nesting(tr)
    assert not spans["dstpu.engine.prefill"]
    kinds = {e.stats["kind"] for e in spans["dstpu.engine.dispatch"]}
    assert {"fused", "chunk"} <= kinds <= {"fused", "chunk", "decode"}
    for e in spans["dstpu.engine.dispatch"]:
        if e.stats["kind"] == "chunk":
            assert e.stats["active"] == 0 and e.stats["steps"] == 0
        if e.stats["kind"] != "decode":
            assert 0 < e.stats["chunk_tokens"] <= 16


@pytest.mark.parametrize("kind", ["bucketed", "splitfuse"])
def test_stats_round_trip(kind, request):
    tr, engine = request.getfixturevalue(kind)
    for name, entry in SPAN_SCHEMA.items():
        for e in tr.host_spans(name):
            assert set(entry["stats"]) <= set(e.stats), (name, e.stats)
    admits = tr.host_spans("dstpu.engine.admit")
    assert len(admits) == N_REQUESTS
    assert len({e.stats["uid"] for e in admits}) == N_REQUESTS
    for e in admits:
        assert isinstance(e.stats["wait_us"], int) and e.stats["wait_us"] >= 0
        assert e.stats["prompt_tokens"] >= 5
    decoding = [e for e in tr.host_spans("dstpu.engine.dispatch")
                if e.stats["steps"]]
    for e in decoding:
        assert 1 <= e.stats["active"] <= e.stats["slots"] == 4
        assert e.stats["steps"] == 2
    steps = tr.host_spans("dstpu.engine.step")
    assert max(e.stats["admitted_total"] for e in steps) >= N_REQUESTS
    assert all(e.stats["active"] <= e.stats["slots"] for e in steps)
    # the occupancy counter agrees with the spans it is opened beside
    # (the two warm-up requests ran before the capture)
    snap = engine.telemetry_snapshot()
    assert 0 < snap["batch_occupancy_pct"] <= 100
    assert snap["queue_ms_p50"] >= 0 and "queue_ms_p90" in snap
    assert engine.telemetry.admitted == N_REQUESTS + 2


def test_on_admit_queue_wait():
    st = ServingTelemetry(interval=4, max_samples=8)
    assert "queue_ms_p50" not in st.percentiles()   # nothing admitted yet
    st.on_submit(0, waited_s=2.0)
    assert 2000.0 <= st.on_admit(0) < 2500.0        # the router's share
    assert st.on_admit("never submitted") == 0.0
    for uid in (1, 2):
        st.on_submit(uid)
        assert 0.0 <= st.on_admit(uid) < 500.0
    assert st.queue_ms_p50 == 0.0                   # cached: not yet due
    st.on_submit(3, waited_s=1.0)
    st.on_admit(3)                                  # 4th admission
    assert 0.0 < st.queue_ms_p50 < 1500.0 <= st.queue_ms_p90
    for uid in range(10, 30):
        st.on_submit(uid)
        st.on_admit(uid)
    assert len(st._queue_ms) == 8 and st.admitted == 24
    p = st.percentiles()
    assert p["queue_ms_p50"] == st.queue_ms_p50 < 500.0
    st.on_decode_batch(3, 4)
    assert "decode_grid_share" not in st.percentiles()
    st.on_decode_batch(1, 4, grid_steps=6, table_entries=64)
    assert st.percentiles()["batch_occupancy_pct"] == 50.0
    assert st.percentiles()["decode_grid_share"] == 0.0938
    assert "decode_entries_per_step" not in st.percentiles()
    st.on_decode_batch(2, 4, grid_steps=9, table_entries=64, kernel_steps=4)
    assert st.percentiles()["decode_grid_share"] == round(15 / 128, 4)
    assert st.percentiles()["decode_entries_per_step"] == 3.75
    assert "kv_write_live_share" not in st.percentiles()
    st.on_kv_write(3, 8)
    st.on_kv_write(13, 24)
    assert st.percentiles()["kv_write_live_share"] == 0.5
    assert "moe_kernel_share" not in st.percentiles()
    st.on_calls({"expert": [0, 0]})                 # a program's first call
    st.on_calls({"expert": [96, 96]})
    st.on_calls({"expert": [32, 0]})
    assert st.percentiles()["moe_kernel_share"] == 0.75
    assert "rule_kernel_share" not in st.percentiles()
    st.on_calls({"rule": [0, 0]})                   # a program's first call
    st.on_calls({"rule": [108, 108]})               # fused: 12 x (1 + 8)
    st.on_calls({"rule": [12, 0]})
    assert st.percentiles()["rule_kernel_share"] == 0.9
    assert "latent_kernel_share" not in st.percentiles()
    st.on_calls({"latent_read": [0, 0]})            # a program's first call
    st.on_calls({"latent_read": [45, 5]})           # fused: 5 x (1 + 8)
    st.on_calls({"latent_read": [5, 5]})            # a chunk alone
    assert st.percentiles()["latent_kernel_share"] == 0.2


def test_chain_counters_are_host_arithmetic():
    """``decode_chain_share`` / ``late_stop_share`` (ISSUE 35) from the
    counts alone; absent until a plain decode dispatch has gone out."""
    st = ServingTelemetry()
    st.on_late_steps(0)
    assert not {"decode_chain_share", "late_stop_share"} & set(
        st.percentiles())
    st.on_plain_decode(0, 8)        # the first of a run: nothing unread
    st.on_plain_decode(1, 8)
    st.on_plain_decode(1, 12)
    st.on_plain_decode(True, 4)
    st.on_late_steps(4)             # one slot of four steps had ended
    snap = st.percentiles()
    assert snap["decode_chain_share"] == 0.75
    assert snap["late_stop_share"] == 0.125


@pytest.mark.parametrize("count", [engine_v2._FUSED_STEPS, 3])
def test_fused_span_says_the_steps_it_ran(monkeypatch, count):
    """A fused dispatch's ``steps`` is the engine's count for a chunk's
    company (ISSUE 45), not the config's: ``active`` x ``steps`` is the
    tokens its decode part returned plus those it dropped (a budget that
    ended inside it), and ``fused_dispatches`` of the telemetry counts
    such dispatches."""
    st = ServingTelemetry()
    assert "fused_dispatches" not in st.percentiles()
    st.on_fused_dispatch()
    st.on_fused_dispatch()
    assert st.percentiles()["fused_dispatches"] == 2
    monkeypatch.setattr(engine_v2, "_FUSED_STEPS", count)
    router, engine = _router(16)
    events, real_span = [], engine_v2.span
    real_post = engine._post_decode_tokens

    def recording_span(name, **stats):
        if name == "dstpu.engine.dispatch":
            events.append(("span", stats))
        return real_span(name, **stats)

    def recording_post(batch, toks):
        left = [q.max_new_tokens - len(q.generated)
                for q in batch.seqs if q is not None]
        out = real_post(batch, toks)
        events.append(("post", dict(
            steps=toks.shape[0], returned=len(out),
            dropped=sum(max(0, toks.shape[0] - n) for n in left))))
        return out

    monkeypatch.setattr(engine_v2, "span", recording_span)
    monkeypatch.setattr(engine, "_post_decode_tokens", recording_post)
    # all at once: prompts stream in beside one, two and three decoders
    rng = np.random.RandomState(3)
    for n, new in zip((7, 30, 12, 40, 9, 25), (9, 5, 14, 6, 11, 7)):
        router.put(rng.randint(1, 250, size=n).astype(np.int32),
                   max_new_tokens=new)
    while router.has_work:
        router.step()
    # a fused dispatch settles what was unread before its span opens, so
    # the post that follows the span is its own
    fused = [(st, events[i + 1][1]) for i, (what, st) in enumerate(events)
             if what == "span" and st["kind"] == "fused"]
    assert {st["active"] for st, _ in fused} >= {1, 2, 3}
    for st, post in fused:
        assert st["steps"] == post["steps"] == count
        assert st["active"] * count == post["returned"] + post["dropped"]
    assert sum(post["dropped"] for _, post in fused) > 0
    assert engine.telemetry_snapshot()["fused_dispatches"] == len(fused)
    plain = {st["steps"] for what, st in events
             if what == "span" and st["kind"] == "decode"}
    assert plain == {_BASE["decode_steps_per_dispatch"]}


def test_late_steps_ride_the_span_that_reads_them(monkeypatch):
    """A ends by an EOS in the middle of a dispatch; the dispatch behind it
    ran for A too. The span under which THAT one is read says so, every
    dispatch but the first of the run is chained, and the telemetry's
    counts are the spans' sums."""
    kernel_dispatch.reset()
    groups.reset()
    model = GPT2(_CFG)
    # 4x init's matrices: at std 0.02 greedy decoding repeats one token
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 4.0 if x.ndim >= 2 and not any(
            k in jax.tree_util.keystr(path) for k in ("wte", "wpe")) else x,
        model.init(jax.random.key(0)))
    engine = InferenceEngineV2(model, params=params,
                               config=dict(_BASE, splitfuse_tokens=0))
    a, b = (np.arange(3, 14, dtype=np.int32),
            np.arange(40, 49, dtype=np.int32))
    ref = engine.generate_all([a], 14)[0].tolist()
    # generated[0] is the prefill's; dispatch d holds generated[1 + 2d :
    # 3 + 2d]: an odd j is the first of its two
    j = next(j for j in range(3, 12, 2) if ref.index(ref[j]) == j)
    tel = engine.telemetry
    calls0, chained0 = tel._plain_dispatches, tel._chained_dispatches
    said = []
    real = engine_v2.span

    def recording(name, **stats):
        if name == "dstpu.engine.dispatch":
            said.append(stats)
        return real(name, **stats)

    monkeypatch.setattr(engine_v2, "span", recording)
    uids = [engine.put(a, 14, eos_token_id=ref[j]), engine.put(b, 14)]
    while engine.has_work:
        engine.step()
    assert engine.get(uids[0]).tolist() == ref[:j + 1]
    assert len(engine.get(uids[1])) == 14
    assert [d["chained"] for d in said] == [0] + [1] * (len(said) - 1)
    # A's EOS is read under span (j - 1) // 2 + 1; the dispatch that span
    # enqueued is the late one, read under the span after it
    late = [d["late_steps"] for d in said]
    assert late[(j - 1) // 2 + 2] == 2 and sum(late) == 2
    assert said[(j - 1) // 2 + 1]["active"] == 2    # it rode along
    assert said[(j - 1) // 2 + 2]["active"] == 1
    assert tel._late_steps == 2
    assert tel._plain_dispatches - calls0 == len(said)
    assert tel._chained_dispatches - chained0 == len(said) - 1


@pytest.mark.parametrize("per_step", [1, 3])
@pytest.mark.parametrize("splitfuse_tokens", [0, 16])
def test_decode_grid_counter(monkeypatch, splitfuse_tokens, per_step):
    """``grid_steps`` / ``table_entries`` / ``kernel_steps`` on every
    decode-bearing dispatch span, and ``decode_grid_share`` and
    ``decode_entries_per_step`` of the telemetry, are the numpy formula
    over the batches the engine dispatched: a live slot's blocks up to
    its new token, each of the dispatch's steps one token on, against
    steps x slots x table entries, and in runs of ``per_step`` a slot
    (the tiny model's rows are narrower than the lanes, so its own N is
    1: the test says 3 for it)."""
    from deepspeed_tpu.models import paged
    monkeypatch.setattr(paged, "decode_entries_per_step",
                        lambda *a: per_step)
    router, engine = _router(splitfuse_tokens)
    batches, stats = [], []
    real_batch, real_span = engine.state_mgr.decode_batch, engine_v2.span

    def recording_batch(*a, **kw):
        batch = real_batch(*a, **kw)
        if batch.active.any():
            batches.append((batch.lengths.copy(), batch.active.copy()))
        return batch

    def recording_span(name, **st):
        if name == "dstpu.engine.dispatch" and st["steps"]:
            stats.append(st)
        return real_span(name, **st)

    monkeypatch.setattr(engine.state_mgr, "decode_batch", recording_batch)
    monkeypatch.setattr(engine_v2, "span", recording_span)
    _serve(router)
    BS, MB, slots, steps = 8, 128 // 8, 4, 2
    assert len(batches) == len(stats) > N_REQUESTS
    want = [int(sum((lengths[active] + t) // BS + 1
                    for t in range(steps)).sum())
            for lengths, active in batches]
    runs = [int(sum(-(-((lengths[active] + t) // BS + 1) // per_step)
                    for t in range(steps)).sum())
            for lengths, active in batches]
    assert [st["grid_steps"] for st in stats] == want
    assert [st["kernel_steps"] for st in stats] == runs
    assert {st["table_entries"] for st in stats} == {steps * slots * MB}
    assert all(st["active"] * steps <= st["kernel_steps"]
               <= st["grid_steps"] for st in stats)
    snap = engine.telemetry_snapshot()
    assert snap["decode_grid_share"] == round(
        sum(want) / (len(want) * steps * slots * MB), 4)
    assert snap["decode_entries_per_step"] == round(
        sum(want) / sum(runs), 4)
    assert (want == runs) == (per_step == 1)


@pytest.mark.parametrize("block_c", ["auto", 8])
def test_chunk_grid_counter(monkeypatch, block_c):
    """``chunk_grid_steps`` / ``chunk_table_steps`` on every dispatch span
    that carries a chunk (ISSUE 58): the grid steps one paged-chunk kernel
    call takes over the chunk's ``chunk_tokens`` real tokens of 16 from
    ``chunk_start`` — counted here key by key: for each query tile the
    table entries from 0 to its last real query's, in runs of the tile's
    entries, times the blocks of KV heads — against query tiles x table
    entries; 0 / 0 where the dispatch has no chunk."""
    from deepspeed_tpu.models import paged
    from deepspeed_tpu.ops.pallas.paged_attention import chunk_tile
    kernel_dispatch.reset()
    groups.reset()
    model = GPT2(_CFG)
    engine = InferenceEngineV2(
        model, params=model.init(jax.random.key(0)),
        config=dict(_BASE, splitfuse_tokens=16, paged_block_c=block_c))
    stats, real_span = [], engine_v2.span

    def recording_span(name, **st):
        if name == "dstpu.engine.dispatch":
            stats.append(st)
        return real_span(name, **st)

    monkeypatch.setattr(engine_v2, "span", recording_span)
    _serve(Router([Replica("r0", engine)]))
    C, BS, MB, KVH, d = 16, 8, 128 // 8, _CFG.n_head, _CFG.d_head
    tile = chunk_tile(C, KVH, 1, d, BS, MB, "float32",
                      0 if block_c == "auto" else block_c)
    assert tile == paged._chunk_kernel(paged.geometry(model), C, MB, BS)[1]
    assert tile.block_c == (16 if block_c == "auto" else 8)
    chunks = [st for st in stats if st["chunk_tokens"]]
    assert len(chunks) >= N_REQUESTS and len(chunks) < len(stats)
    for st in chunks:
        start, n = st["chunk_start"], st["chunk_tokens"]
        want = 0
        for t in range(C // tile.block_c):
            q_lo = start + t * tile.block_c
            last = min(q_lo + tile.block_c, start + n) - 1
            want += -(-(last // BS + 1) // tile.entries) \
                if q_lo < start + n else 1
        assert st["chunk_grid_steps"] == want * (KVH // tile.heads)
        assert st["chunk_table_steps"] == C // tile.block_c * MB
        assert st["chunk_grid_steps"] \
            < st["chunk_table_steps"] * (KVH // tile.heads)
    assert all(st["chunk_grid_steps"] == st["chunk_table_steps"] == 0
               for st in stats if not st["chunk_tokens"])


@pytest.mark.parametrize("splitfuse_tokens", [0, 16])
def test_kv_write_counter(monkeypatch, splitfuse_tokens):
    """``write_rows`` / ``write_rows_offered`` on every dispatch span, and
    ``kv_write_live_share`` of the telemetry: of the slots x steps rows a
    decode-bearing dispatch hands the KV write (and a chunk's C), those
    whose table entry names a block other than scratch — counted here
    slot by slot, step by step, from the batches the engine dispatched."""
    router, engine = _router(splitfuse_tokens)
    batches, stats = [], []
    real_batch, real_span = engine.state_mgr.decode_batch, engine_v2.span

    def recording_batch(*a, **kw):
        batch = real_batch(*a, **kw)
        if batch.active.any():
            batches.append((batch.lengths.copy(),
                            batch.block_tables.copy()))
        return batch

    def recording_span(name, **st):
        if name == "dstpu.engine.dispatch":
            stats.append(st)
        return real_span(name, **st)

    monkeypatch.setattr(engine.state_mgr, "decode_batch", recording_batch)
    monkeypatch.setattr(engine_v2, "span", recording_span)
    _serve(router)
    BS, slots, C = 8, 4, splitfuse_tokens
    decoding = [st for st in stats if st["steps"]]
    assert len(batches) == len(decoding) > N_REQUESTS
    assert (len(decoding) < len(stats)) == bool(C)      # chunk-only ones
    for st, (lengths, tables) in zip(decoding, batches):
        # the config's of a plain decode, the engine's own of a fused one
        steps = st["steps"]
        assert steps == (engine_v2._FUSED_STEPS if st["kind"] == "fused"
                         else _BASE["decode_steps_per_dispatch"])
        live = sum(bool(tables[b, min((lengths[b] + t) // BS,
                                      tables.shape[1] - 1)])
                   for b in range(slots) for t in range(steps))
        assert 0 < live <= st["active"] * steps
        assert st["write_rows"] == live + st["chunk_tokens"]
        assert st["write_rows_offered"] == steps * slots + (
            C if st["kind"] == "fused" else 0)
    for st in stats:
        if not st["steps"]:
            assert st["kind"] == "chunk" and st["write_rows_offered"] == C
            assert 0 < st["write_rows"] == st["chunk_tokens"] <= C
    assert engine.telemetry_snapshot()["kv_write_live_share"] == round(
        sum(st["write_rows"] for st in stats)
        / sum(st["write_rows_offered"] for st in stats), 4)


@pytest.mark.parametrize("knob, splitfuse_tokens", [
    ("auto", 0), (True, 0), (True, 16), (False, 0), ("dense", 0)])
def test_expert_kernel_counter(monkeypatch, knob, splitfuse_tokens):
    """``expert_calls`` / ``expert_kernel_calls`` on every dispatch and
    prefill span, and ``moe_kernel_share`` of the telemetry: the expert
    layer calls (MoE layers x steps, and a chunk's or a prefill's one a
    layer) of the span's program, and those of them whose products are a
    Pallas grouped kernel — noted when the program is traced, so the call
    that traces a program still reads 0 of 0, as a dense model's always
    do. Off the TPU "auto" is ``lax.ragged_dot``: share 0;
    ``grouped_kernel=True``: share 1."""
    from deepspeed_tpu.models import Mixtral, MixtralConfig
    router, engine = _router(splitfuse_tokens)
    if knob != "dense":
        model = Mixtral(MixtralConfig(
            n_layer=2, n_head=2, n_kv_heads=2, d_model=128, d_ff=128,
            max_seq_len=128, vocab_size=256, num_experts=4, moe_top_k=2,
            remat=False, dtype="float32"))
        model._moe_cfg = types.SimpleNamespace(
            grouped_kernel=knob, hierarchical_a2a="auto",
            dcn_quantize=False)
        groups.reset()
        engine = InferenceEngineV2(
            model, config=dict(_BASE, splitfuse_tokens=splitfuse_tokens))
        router = Router([Replica("r0", engine)])
    stats, real_span = [], engine_v2.span

    def recording_span(name, **st):
        if name in ("dstpu.engine.dispatch", "dstpu.engine.prefill"):
            stats.append(dict(st, name=name))
        return real_span(name, **st)

    monkeypatch.setattr(engine_v2, "span", recording_span)
    _serve(router)
    layers = 2
    seen, kinds = set(), set()
    for st in stats:
        kind = st.get("kind", "prefill")
        program = (kind, st.get("padded"))      # a bucket is a program
        first = program not in seen
        seen.add(program)
        kinds.add(kind)
        want = 0 if first or knob == "dense" else layers * (
            st.get("steps", 0) + (kind != "decode"))
        assert st["expert_calls"] == want, st
        assert st["expert_kernel_calls"] == (want if knob is True else 0), st
        # no gated delta rule in either family: 0 of 0 (ISSUE 42)
        assert (st["rule_calls"], st["rule_kernel_calls"]) == (0, 0), st
        # nor a latent layer read through a selection (ISSUE 44)
        assert (st["latent_read_calls"],
                st["latent_read_kernel_calls"]) == (0, 0), st
    # split-fuse: chunks, fused with the decode steps while any slot
    # decodes, and plain decode dispatches between prompts
    assert kinds - {"decode"} == ({"fused", "chunk"} if splitfuse_tokens
                                  else {"prefill"}) and "decode" in kinds
    snapshot = engine.telemetry_snapshot()
    assert "rule_kernel_share" not in snapshot
    assert "latent_kernel_share" not in snapshot
    if knob == "dense":
        assert "moe_kernel_share" not in snapshot
    else:
        assert snapshot["moe_kernel_share"] == float(knob is True)


def test_span_budget_without_capture(monkeypatch):
    """No capture running: a decode-only engine step opens at most 6 spans
    (router.step, engine.step, build, dispatch, fetch, post), an admission
    5 more, and per-token code none. The first decode dispatch of a run
    reads nothing, so it posts nothing."""
    router, engine = _router(0)
    _serve(router, 2, seed=1)
    opened = []
    real = engine_v2.span

    def counting(name, **stats):
        frame = sys._getframe(1)
        opened.append((name, frame.f_code.co_name))
        return real(name, **stats)

    monkeypatch.setattr(engine_v2, "span", counting)
    monkeypatch.setattr(router_mod, "span", counting)
    router.put(np.arange(1, 20, dtype=np.int32), max_new_tokens=12)
    router.step()
    assert len(opened) == 10, opened        # 6 - post + the admission's 5
    while router.has_work:
        opened.clear()
        router.step()
        assert len(opened) <= 6, opened
        assert len({n for n, _ in opened}) == len(opened)
    assert not {fn for _, fn in opened} & {
        "_post_token", "_post_tokens", "_post_decode_tokens", "on_token"}


@pytest.mark.parametrize("metric", READERS)
def test_reader(metric, bucketed, splitfuse):
    """Each reader gives a number on a trace of the path it is for and
    None where the program opened no ``dstpu.*`` span (the parent commit,
    a training trace)."""
    said = []
    view = types.SimpleNamespace(
        say=lambda line, **fields: said.append((line, fields)))
    reader = pb_common.load_module("layer_metrics", metric)
    view.trace = pb_trace.Trace(os.path.join(
        REPO, "perfbench", "fixtures", "tiny4.xplane.pb"), rehearse=True)
    assert reader.read(view) is None and not said
    view.trace = None
    assert reader.read(view) is None
    view.trace = (splitfuse if metric == "fused_dispatch_ms"
                  else bucketed)[0]
    value = reader.read(view)
    assert isinstance(value, float) and value >= 0.0, value
    assert said and said[0][0] == metric
    if metric.endswith("_share") or metric == "batch_occupancy":
        assert value <= 100.0


MOE_READERS = ("moe_experts_share", "moe_route_share",
               "moe_experts_roofline")


@pytest.mark.parametrize("metric", MOE_READERS)
def test_moe_reader(metric, bucketed):
    """The readers of the expert layer (ISSUE 26) give None where no
    expert product ran — no trace, the recorded training trace, a dense
    model's serving trace (cells 1-4, the parent commit) — and the
    recorded values on the MoE trace ``fixtures/moe1.xplane.pb``
    (a two-layer OLMoE served on the v5e, ``fixtures/record_moe.py``)."""
    want = pb_common.load_json("fixtures", "moe1.expected.json")
    said = []
    view = types.SimpleNamespace(
        say=lambda line, **fields: said.append((line, fields)),
        sizes=want["sizes"], peaks=pb_common.peaks_for("TPU v5 lite"),
        counters={"traced_prompts": want["traced_prompts"]})
    reader = pb_common.load_module("layer_metrics", metric)
    fixtures = os.path.join(REPO, "perfbench", "fixtures")
    for dense in (None, bucketed[0], pb_trace.Trace(
            os.path.join(fixtures, "tiny4.xplane.pb"))):
        view.trace = dense
        assert reader.read(view) is None and not said
    view.trace = pb_trace.Trace(os.path.join(fixtures, "moe1.xplane.pb"))
    value = reader.read(view)
    assert value == pytest.approx(want["values"][metric], rel=1e-9)
    assert 0.0 < value <= 100.0
    assert said[0][0] == "moe_device_seconds"
    scoped = pb_moe.op_scopes(view.trace.path, "/device:TPU:")
    # (a served layer walks no held share: nothing under dstpu.moe.spill)
    assert {sc for name in scoped.values()
            for sc in SCOPE_SCHEMA if sc in name} \
        == {sc for sc in SCOPE_SCHEMA if sc.startswith("dstpu.moe.")} \
        - {"dstpu.moe.spill"}


SSM_READERS = ("ssm_mix_share", "attn_window_share", "attn_shared_kv_share")


@pytest.mark.parametrize("metric", SSM_READERS)
def test_ssm_reader_reads_nothing_without_its_scope(metric, bucketed):
    """The readers of the hybrid's layers (ISSUE 30) give None, and say
    nothing, where the traced program opened none of their scopes: no
    trace, a dense model's serving trace (cells 3, 4, 6 and the parent
    commit), the recorded training trace, the recorded MoE trace."""
    said = []
    view = types.SimpleNamespace(
        say=lambda line, **fields: said.append((line, fields)))
    reader = pb_common.load_module("layer_metrics", metric)
    fixtures = os.path.join(REPO, "perfbench", "fixtures")
    for other in (None, bucketed[0],
                  pb_trace.Trace(os.path.join(fixtures, "tiny4.xplane.pb")),
                  pb_trace.Trace(os.path.join(fixtures, "moe1.xplane.pb"))):
        view.trace = other
        assert reader.read(view) is None and not said


def test_ssm_reader_shares_by_first_scope_named():
    """Own time goes to the first of ``pbench.ssm.SCOPES`` an operation's
    ``tf_op`` names, over the device's busy time."""
    ev = types.SimpleNamespace
    ops = {"a": "jit(decode)/dstpu.ssm.mix/dot_general",
           "b": "jit(decode)/dstpu.attn.window/pallas_call",
           "c": "jit(decode)/dstpu.attn.shared_kv/jit(_kv_write_call)/x",
           "d": "jit(decode)/dstpu.gmu/mul", "e": "jit(decode)/argmax"}
    trace = ev(path="p", devices=[0], ssm_seconds=None, busy_s=lambda: 2.0,
               in_window=lambda d: [ev(name=n, self_s=0.25) for n in ops])
    said = []
    view = ev(trace=trace, say=lambda line, **f: said.append(line))
    real = pb_moe.op_scopes
    pb_moe.op_scopes = lambda path, prefix: ops
    try:
        got = [pb_common.load_module("layer_metrics", m).read(view)
               for m in SSM_READERS]
    finally:
        pb_moe.op_scopes = real
    assert got == [12.5, 12.5, 12.5] and said == ["ssm_device_seconds"]
    assert set(trace.ssm_seconds[0]) == {
        pb_ssm.SSM_MIX, pb_ssm.ATTN_WINDOW, pb_ssm.ATTN_SHARED_KV,
        pb_ssm.GMU}


GDN_READERS = ("gdn_mix_share", "attn_full_share", "gdn_chunk_roofline",
               "gdn_state_roofline")


@pytest.mark.parametrize("metric", GDN_READERS)
def test_gdn_reader_reads_nothing_without_its_scope(metric, bucketed):
    """The readers of the delta-rule hybrid's layers (ISSUE 41) give None,
    and say nothing, where the traced program opened none of their scopes:
    no trace, a dense model's serving trace (whose dispatch spans carry
    ``rule_rows`` = 0), the recorded training and MoE traces (a program
    before PR 41: no such counter at all)."""
    said = []
    view = types.SimpleNamespace(
        say=lambda line, **fields: said.append((line, fields)),
        sizes=_cell_sizes("olmo-hybrid-7b"),
        peaks=pb_common.peaks_for("TPU v5 lite"))
    reader = pb_common.load_module("layer_metrics", metric)
    fixtures = os.path.join(REPO, "perfbench", "fixtures")
    for other in (None, bucketed[0],
                  pb_trace.Trace(os.path.join(fixtures, "tiny4.xplane.pb")),
                  pb_trace.Trace(os.path.join(fixtures, "moe1.xplane.pb"))):
        view.trace = other
        assert reader.read(view) is None and not said


def test_gdn_readers_count_nested_scopes_and_the_rules_floors():
    """Own time goes to EVERY one of ``pbench.gdn.SCOPES`` an operation's
    ``tf_op`` names (the rule's two forms are inside the mixer); the floors
    are the rule's own count for what the window's spans say was asked."""
    ev = types.SimpleNamespace
    ops = {"a": "jit(fused)/dstpu.gdn.mix/dstpu.mm.in_proj/dot_general",
           "b": "jit(fused)/dstpu.gdn.mix/dstpu.gdn.chunk/while/body/dot",
           "c": "jit(fused)/dstpu.gdn.mix/dstpu.gdn.step/mul",
           "d": "jit(fused)/dstpu.attn.full/jit(_kv_write_call)/x",
           "e": "jit(fused)/dstpu.mm.mlp/dot_general"}
    spans = {"dstpu.engine.dispatch": [
        ev(start=1.0, end=2.0, dur=1.0, stats={
            "kind": "fused", "chunk_tokens": 1000, "rule_rows": 12288,
            "state_updates": 96}),
        # half inside the window
        ev(start=9.5, end=10.5, dur=1.0, stats={
            "kind": "decode", "chunk_tokens": 0, "rule_rows": 0,
            "state_updates": 192})], "dstpu.engine.prefill": []}
    trace = ev(path="p", devices=[0], gdn_seconds=None, busy_s=lambda: 2.0,
               t0=0.0, t1=10.0, host_spans=lambda name: spans[name],
               in_window=lambda d: [ev(name=n, self_s=0.25) for n in ops])
    said = []
    sizes = _cell_sizes("olmo-hybrid-7b")
    peaks = pb_common.peaks_for("TPU v5 lite")
    view = ev(trace=trace, sizes=sizes, peaks=peaks,
              say=lambda line, **f: said.append(line))
    real = pb_moe.op_scopes
    pb_moe.op_scopes = lambda path, prefix: ops
    try:
        got = [pb_common.load_module("layer_metrics", m).read(view)
               for m in GDN_READERS]
    finally:
        pb_moe.op_scopes = real
    assert got[:2] == [37.5, 12.5]
    assert (sizes["n_linear"], sizes["n_full"]) == (12, 4)
    token = max(6 * 30 * 96 * 192 / peaks["bf16_flops_per_s"],
                (2 * 2880 + 2 * 5760) * 2 / peaks["hbm_bytes_per_s"])
    assert got[2] == pytest.approx(100 * 1000 * 12 * token / 0.25)
    state = 2 * 30 * 96 * 192 * 4 / peaks["hbm_bytes_per_s"]
    assert got[3] == pytest.approx(100 * (96 + 96) * state / 0.25)
    assert all(0 < x < 100 for x in got)
    assert said == ["gdn_device_seconds", "gdn_chunk_roofline",
                    "gdn_state_roofline"]


DSA_READERS = ("mla_attn_share", "dsa_index_share", "mla_latent_roofline",
               "dsa_index_roofline")


@pytest.mark.parametrize("metric", DSA_READERS)
def test_dsa_reader_reads_nothing_without_its_scope(metric, bucketed):
    """The readers of the latent layers' selected read (ISSUE 43) give None,
    and say nothing, where the traced program opened none of their scopes:
    no trace, a dense model's serving trace (whose dispatch spans carry
    ``index_keys`` = 0), the recorded training and MoE traces (a program
    before PR 43: no such counter at all)."""
    said = []
    view = types.SimpleNamespace(
        say=lambda line, **fields: said.append((line, fields)),
        sizes=_cell_sizes("deepseek-v3.2-exp"),
        peaks=pb_common.peaks_for("TPU v5 lite"))
    reader = pb_common.load_module("layer_metrics", metric)
    fixtures = os.path.join(REPO, "perfbench", "fixtures")
    for other in (None, bucketed[0],
                  pb_trace.Trace(os.path.join(fixtures, "tiny4.xplane.pb")),
                  pb_trace.Trace(os.path.join(fixtures, "moe1.xplane.pb"))):
        view.trace = other
        assert reader.read(view) is None and not said


def test_dsa_readers_count_the_innermost_scope_and_the_models_floors():
    """Own time goes to the INNERMOST of ``pbench.dsa.SCOPES`` an
    operation's ``tf_op`` names (the index scores are traced from inside
    the read's loop); the floors are the model's own count for what the
    window's spans say was asked: a prompt's pairs at the expanded form's
    count, a decode step's at the absorbed form's and its cache rows."""
    ev = types.SimpleNamespace
    ops = {"a": "jit(fused)/dstpu.attn.latent/while/body/dot_general",
           "b": "jit(fused)/dstpu.attn.latent/while/body/dstpu.attn.index/dot",
           "c": "jit(fused)/dstpu.attn.index/cos",
           "d": "jit(fused)/dstpu.mm.qkv/dot_general",
           "e": "jit(fused)/dstpu.moe.experts/x"}
    # a chunk of 1,000 tokens from position 3,000 beside 8 decode steps of
    # 10 slots at contexts of ~5,000, five latent layers
    chunk_idx, chunk_att = 5 * 1000 * 3500.5, 5 * 1000 * 2048
    dec_idx, dec_att = 5 * 80 * 5000, 5 * 80 * 2048
    spans = {"dstpu.engine.dispatch": [
        ev(start=1.0, end=2.0, dur=1.0, stats={
            "kind": "fused", "chunk_tokens": 1000, "chunk_start": 3000,
            "steps": 8, "active": 10, "index_keys": chunk_idx + dec_idx,
            "attended_keys": chunk_att + dec_att}),
        # half inside the window, all decode
        ev(start=9.5, end=10.5, dur=1.0, stats={
            "kind": "decode", "chunk_tokens": 0, "chunk_start": 0,
            "steps": 8, "active": 16,
            "index_keys": 8.0e6, "attended_keys": 1.0e6})],
        "dstpu.engine.prefill": []}
    trace = ev(path="p", devices=[0], dsa_seconds=None, busy_s=lambda: 2.0,
               t0=0.0, t1=10.0, host_spans=lambda name: spans[name],
               in_window=lambda d: [ev(name=n, self_s=0.25) for n in ops])
    said = []
    sizes = _cell_sizes("deepseek-v3.2-exp")
    peaks = pb_common.peaks_for("TPU v5 lite")
    view = ev(trace=trace, sizes=sizes, peaks=peaks,
              say=lambda line, **f: said.append(line))
    real = pb_moe.op_scopes
    pb_moe.op_scopes = lambda path, prefix: ops
    try:
        got = [pb_common.load_module("layer_metrics", m).read(view)
               for m in DSA_READERS]
    finally:
        pb_moe.op_scopes = real
    assert got[:2] == [12.5, 25.0]
    # the decode part is the span's total less the chunk's own pairs, by
    # keys: a decode token at context 5,000 brings far more than its share
    # of the dispatch's tokens
    att, att_d = chunk_att + dec_att + 0.5e6, dec_att + 0.5e6
    ops_read = 2 * 128 * ((att - att_d) * (192 + 128) + att_d * (576 + 512))
    floor = max(ops_read / peaks["bf16_flops_per_s"],
                att_d * 1152 / peaks["hbm_bytes_per_s"])
    assert got[2] == pytest.approx(100 * floor / 0.25)
    # index keys lie in the pool as float32: 512 bytes a decode pair
    idx, idx_d = chunk_idx + dec_idx + 4.0e6, dec_idx + 4.0e6
    floor = max(idx * 2 * 64 * 128 / peaks["bf16_flops_per_s"],
                idx_d * 512 / peaks["hbm_bytes_per_s"])
    assert got[3] == pytest.approx(100 * floor / 0.5)
    assert all(0 < x < 100 for x in got)
    assert said == ["dsa_device_seconds", "mla_latent_roofline",
                    "dsa_index_roofline"]


@pytest.mark.parametrize("kind", ["bucketed", "splitfuse"])
def test_cache_bytes_per_live_token_reader(kind, request):
    """``cache_bytes_per_live_token`` is the step spans' two counters,
    summed over the traced window: for a family whose whole cache is
    blocks under the block tables, the blocks its live sequences hold
    times a block's bytes in every layer's K and V."""
    tr, engine = request.getfixturevalue(kind)
    said = []
    view = types.SimpleNamespace(
        trace=tr, say=lambda line, **f: said.append((line, f)))
    reader = pb_common.load_module("layer_metrics",
                                   "cache_bytes_per_live_token")
    value = reader.read(view)
    steps = [e.stats for e in tr.host_spans("dstpu.engine.step")]
    assert engine._account.slot_bytes == 0
    assert engine._account.block_bytes \
        == 2 * _CFG.n_layer * _CFG.d_model * 8 * 4
    assert all(int(s["cache_bytes"]) % engine._account.block_bytes == 0
               for s in steps)
    assert value == sum(int(s["cache_bytes"]) for s in steps) \
        / sum(int(s["live_tokens"]) for s in steps)
    # a sequence holds its whole budget's blocks from admission: more
    # than a token's bytes a live token
    assert value > engine._account.block_bytes / 8
    assert said[0][0] == "cache_bytes_per_live_token"
    view.trace = None
    assert reader.read(view) is None


WEIGHT_READERS = ("weights_roofline", "weights_share",
                  "train_weights_roofline")
_FIXTURES = os.path.join(REPO, "perfbench", "fixtures")


def _cell_sizes(config):
    """The ``sizes`` a cell of this configuration hands its readers."""
    cfg = pb_common.load_json("configs", config + ".json")
    return pb_common.load_module("builders", cfg["builder"]).sizes(cfg)


def _weights_view(trace, sizes, said=None):
    return types.SimpleNamespace(
        trace=trace, sizes=sizes, chips=1, workload="a-cell",
        peaks=pb_common.peaks_for("TPU v5 lite"),
        counters={"tokens_traced": 4096, "traced_prompts": [40]},
        say=(lambda line, **f: said.append((line, f))) if said is not None
        else (lambda line, **f: None))


def _restated(tr, **changes):
    """A shallow copy of a Trace with some attributes replaced."""
    out = copy.copy(tr)
    for k, v in changes.items():
        setattr(out, k, v)
    return out


def _unscoped_traces(bucketed, splitfuse):
    """Traces of programs that open no ``dstpu.mm.*`` scope on a device, as
    the parent commit's are to this PR's readers, with what a cell's slice
    can lack: any dispatch at all, the spans' stats, the family's sizes."""
    tr = bucketed[0]
    bare = [(line, pb_trace.Event(e.name, e.start, e.end, {
        k: v for k, v in e.stats.items()
        if k not in ("kind", "active", "steps", "tokens", "chunk_tokens")}))
        for line, e in tr.host]
    return {
        "tiny4-gpt2m": (pb_trace.Trace(os.path.join(
            _FIXTURES, "tiny4.xplane.pb")), _cell_sizes("gpt2-medium")),
        "tiny4-opt": (pb_trace.Trace(os.path.join(
            _FIXTURES, "tiny4.xplane.pb")), _cell_sizes("opt-1.3b")),
        "moe1-phi4": (pb_trace.Trace(os.path.join(
            _FIXTURES, "moe1.xplane.pb")), _cell_sizes("phi-4-mini-flash")),
        "moe1-olmoe": (pb_trace.Trace(os.path.join(
            _FIXTURES, "moe1.xplane.pb")), _cell_sizes("olmoe-1b-7b")),
        "no-trace": (None, _cell_sizes("gpt2-medium")),
        "no-dispatch": (_restated(tr, t0=tr.t0, t1=tr.t0 + 1e-9),
                        _cell_sizes("gpt2-medium")),
        "splitfuse": (splitfuse[0], _cell_sizes("opt-1.3b")),
        "bare-spans": (_restated(tr, host=bare), _cell_sizes("gpt2-medium")),
        "olmoe-sizes": (tr, _cell_sizes("olmoe-1b-7b")),
        "no-sizes": (tr, {}),
    }


@pytest.mark.parametrize("metric", WEIGHT_READERS)
@pytest.mark.parametrize("case", [
    "tiny4-gpt2m", "tiny4-opt", "moe1-phi4", "moe1-olmoe", "no-trace",
    "no-dispatch", "splitfuse", "bare-spans", "olmoe-sizes", "no-sizes"])
def test_weights_reader_stands_on_a_program_without_its_scopes(
        metric, case, bucketed, splitfuse):
    """ISSUE 38's first criterion, on the CPU: each reader of the dense
    weight products returns None, says nothing and does not raise on a
    trace that names no ``dstpu.mm.*`` (PR 37 was refused because one of
    its readers raised on the parent's program in cell 4, whose slice often
    holds no fused dispatch)."""
    trace, sizes = _unscoped_traces(bucketed, splitfuse)[case]
    said = []
    reader = pb_common.load_module("layer_metrics", metric)
    assert reader.read(_weights_view(trace, sizes, said)) is None
    assert not said
    assert reader.read(types.SimpleNamespace(trace=trace)) is None


def _device_event(name, start):
    return types.SimpleNamespace(name=name, start=float(start),
                                 end=start + 1.0, self_s=1.0)


def _host_span(start, end, **stats):
    return types.SimpleNamespace(start=float(start), end=float(end),
                                 dur=float(end - start), stats=stats)


_SYNTH_SIZES = dict(n_layer=2, n_head=4, n_kv_head=4, d_head=64, d_model=256,
                    d_ff=1024, vocab_size=512, vocab_rows=512)


def _synthetic(monkeypatch, order, spans=None, **changes):
    """One device whose operations, a second each, are ``order`` (q a qkv
    product, m a Mamba in-projection nested in ``dstpu.ssm.mix``, u an
    unembed, a a paged read, x an operation under no scope), a window of
    [3, 11.5) and 9 busy seconds."""
    ops = {"q": "jit(decode)/dstpu.mm.qkv/dot_general",
           "m": "jit(decode)/dstpu.ssm.mix/dstpu.mm.in_proj/dot_general",
           "u": "jit(decode)/jvp(dstpu.mm.unembed)/dot_general",
           "a": "jit(decode)/dstpu.attn.window/pallas_call",
           "x": "jit(decode)/argmax"}
    monkeypatch.setattr(pb_moe, "op_scopes", lambda path, prefix: ops)
    trace = types.SimpleNamespace(
        path="p", t0=3.0, t1=11.5, mm_walk=None, busy_s=lambda: 9.0,
        devices={"d0": [_device_event(n, i) for i, n in enumerate(order)]},
        host_spans=lambda name: (spans or {}).get(name, []))
    for k, v in changes.items():
        setattr(trace, k, v)
    return trace


def test_weights_walk_counts_passes_by_runs_of_unembed(monkeypatch):
    """The arithmetic of ``pbench/weights.py`` on a made-up device: own
    seconds under any ``dstpu.mm.*`` inside the window; a pass is closed by
    a RUN of unembed events, counts by the share of its seconds inside the
    window, and not at all where the trace lacks its beginning (the first)
    or its unembed (the last); a prefill's or chunk's operations count
    only beyond one read of the weights, by the span's share of the
    window."""
    order = "qu" "qmau" "x" "quu" "qmu" "q"
    spans = {
        "dstpu.engine.prefill": [_host_span(4, 6, tokens=1000, padded=1024)],
        "dstpu.engine.dispatch": [
            _host_span(11, 12, kind="chunk", chunk_tokens=300, steps=0),
            _host_span(7, 8, kind="decode", chunk_tokens=0, steps=2),
            _host_span(8, 9, kind="fused", chunk_tokens=100, steps=2)]}
    said = []
    view = _weights_view(_synthetic(monkeypatch, order, spans),
                         _SYNTH_SIZES, said)
    got = pb_weights.walk(view)
    assert got["mm_s"] == 7.0 and got["busy_s"] == 9.0
    assert got["passes"] == pytest.approx(2 / 3 + 1 + 2 / 3)
    assert got["by_scope"] == {"dstpu.mm.in_proj": 2.0, "dstpu.mm.qkv": 2.0,
                               "dstpu.mm.unembed": 3.0}
    assert said[0][0] == "weights_device_seconds"
    assert said[0][1]["under_no_dstpu_scope_s"] == 1.0
    layers, unembed = pb_weights.matmul_params(_SYNTH_SIZES)
    assert (layers, unembed) == (2 * 12 * 256 * 256, 512 * 256)
    peak, bw = view.peaks["bf16_flops_per_s"], view.peaks["hbm_bytes_per_s"]
    read = 2 * layers / bw
    least = got["passes"] * 2 * (layers + unembed) / bw \
        + (1000 * 2 * layers / peak - read) \
        + 0.5 * (300 * 2 * layers / peak - read)    # 100 tokens: under it
    reads = {m: pb_common.load_module("layer_metrics", m).read(view)
             for m in WEIGHT_READERS}
    assert reads["weights_share"] == pytest.approx(100 * 7 / 9)
    assert reads["weights_roofline"] == pytest.approx(100 * least / 7)
    assert reads["train_weights_roofline"] == pytest.approx(
        100 * 6 * (layers + unembed) * 4096 / peak / 7)
    assert [line for line, _ in said].count("weights_device_seconds") == 1


@pytest.mark.parametrize("lacks", ["spans", "stats", "host_spans", "unembed",
                                   "sizes", "peaks", "counters"])
def test_weights_reader_on_scopes_without_the_rest(monkeypatch, lacks):
    """With the scopes in the trace and something else missing, a reader
    gives the number that does not need it, or None; it never raises."""
    order = "qu" "qmau" "x" "quu" "qmu" "q"
    bare = {"dstpu.engine.prefill": [_host_span(4, 6)],
            "dstpu.engine.dispatch": [_host_span(11, 12),
                                      _host_span(8, 9, kind="fused",
                                                 chunk_tokens="many")]}
    trace = _synthetic(monkeypatch,
                       order.replace("u", "x") if lacks == "unembed"
                       else order, bare if lacks == "stats" else None)
    if lacks == "host_spans":
        del trace.host_spans
    view = _weights_view(trace, {"d_model": 256} if lacks == "sizes"
                         else _SYNTH_SIZES)
    if lacks == "peaks":
        del view.peaks
    if lacks == "counters":
        view.counters = {}
    reads = {m: pb_common.load_module("layer_metrics", m).read(view)
             for m in WEIGHT_READERS}
    assert reads["weights_share"] == pytest.approx(
        100 * (4 if lacks == "unembed" else 7) / 9)
    if lacks in ("unembed", "sizes", "peaks"):
        assert reads["weights_roofline"] is None
    else:       # the passes alone: no span adds a prefill's operations
        layers, unembed = pb_weights.matmul_params(_SYNTH_SIZES)
        assert reads["weights_roofline"] == pytest.approx(
            100 * (7 / 3) * 2 * (layers + unembed)
            / view.peaks["hbm_bytes_per_s"] / 7)
    assert (reads["train_weights_roofline"] is None) == (
        lacks in ("sizes", "peaks", "counters"))


def test_weights_count_of_the_cells():
    """The dense weights of one pass, from the cells' own ``sizes``: the
    block and head of GPT-2 medium and OPT-1.3B, Phi-4-mini-flash's whole
    stack against the program's own parameter count, nothing for OLMoE."""
    layers, unembed = pb_weights.matmul_params(_cell_sizes("gpt2-medium"))
    assert (layers, unembed) == (24 * 12 * 1024 ** 2, 50304 * 1024)
    layers, unembed = pb_weights.matmul_params(_cell_sizes("opt-1.3b"))
    assert layers == 24 * 12 * 2048 ** 2 and unembed % 2048 == 0
    from deepspeed_tpu.models.phi4flash import PHI4_MINI_FLASH
    layers, unembed = pb_weights.matmul_params(
        _cell_sizes("phi-4-mini-flash"))
    assert unembed == 200064 * 2560
    # what the program holds beyond its products: norms, biases, the conv,
    # A, D, dt's bias and the lambda vectors, under a thousandth of it
    rest = PHI4_MINI_FLASH.num_params() - layers - unembed
    assert 0 < rest < 1e-3 * PHI4_MINI_FLASH.num_params()
    assert pb_weights.matmul_params(_cell_sizes("olmoe-1b-7b")) is None
    assert pb_weights.matmul_params(None) is None


@pytest.mark.parametrize("metric", WEIGHT_READERS)
def test_weights_reader_on_the_recorded_trace(metric):
    """The recorded values on ``fixtures/dense1.xplane.pb`` (a two-layer
    GPT-2 served on the v5e, ``fixtures/record_dense.py``): the device's own
    ``tf_op`` table, the fusions under the names the TPU compiler gives
    them, and a roofline under 100 %."""
    want = pb_common.load_json("fixtures", "dense1.expected.json")
    said = []
    view = _weights_view(pb_trace.Trace(os.path.join(
        _FIXTURES, "dense1.xplane.pb")), want["sizes"], said)
    view.counters = want["counters"]
    value = pb_common.load_module("layer_metrics", metric).read(view)
    assert value == pytest.approx(want["values"][metric], rel=1e-9)
    assert 0.0 < value < 100.0
    assert said[0][0] == "weights_device_seconds"
    assert said[0][1]["passes"] == pytest.approx(want["walk"]["passes"])
    scoped = pb_moe.op_scopes(view.trace.path, "/device:TPU:")
    assert {sc for name in scoped.values()
            for sc in SCOPE_SCHEMA if sc in name} \
        == {pb_weights.PREFIX + n
            for n in ("qkv", "attn_out", "mlp", "unembed")}
    # and the readers of the other scopes find nothing of theirs in it
    for other in MOE_READERS + SSM_READERS:
        assert pb_common.load_module("layer_metrics", other).read(
            types.SimpleNamespace(trace=view.trace, say=view.say)) is None


def _scopes_named(text):
    return {sc for sc in SCOPE_SCHEMA if sc.startswith(pb_weights.PREFIX)
            and sc in text}


def test_gpt2_decode_trace_names_the_weight_scopes(bucketed, splitfuse):
    """The served GPT-2's programs carry the four ``dstpu.mm.*`` names of
    its family into the profiler's file (on the CPU the names are in the
    programs' metadata the trace keeps; the device's ``tf_op`` table that
    ``op_scopes`` reads exists on the chip alone: ``fixtures/dense1``)."""
    want = {pb_weights.PREFIX + n
            for n in ("qkv", "attn_out", "mlp", "unembed")}
    for tr, _ in (bucketed, splitfuse):
        with open(tr.path, "rb") as f:
            assert _scopes_named(f.read().decode("latin-1")) == want


def test_phi4flash_decode_trace_names_every_weight_scope(tmp_path):
    """A served tiny Phi-4-mini-flash names all nine: its family opens the
    five of the Mamba mixer and the GMU too."""
    import dataclasses
    from deepspeed_tpu.models.phi4flash import PHI4FLASH_TINY, Phi4Flash
    groups.reset()
    model = Phi4Flash(dataclasses.replace(PHI4FLASH_TINY, dtype="float32"))
    engine = InferenceEngineV2(model, dict(
        dtype="float32", max_batch_size=2, kv_block_size=4, prompt_bucket=8,
        num_kv_blocks=48, decode_steps_per_dispatch=1))
    prompt = np.arange(1, 7, dtype=np.int32)
    engine.generate_all([prompt], 2)            # compile outside the capture
    with pb_trace.capture(str(tmp_path)):
        engine.generate_all([prompt], 3)
    with open(pb_trace.find_xplane(str(tmp_path)), "rb") as f:
        named = _scopes_named(f.read().decode("latin-1"))
    assert named == set(pb_weights.SCOPES) == {
        sc for sc in SCOPE_SCHEMA if sc.startswith(pb_weights.PREFIX)}


def _op_names(compiled):
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def test_train_step_names_the_weight_scopes_forward_and_backward():
    """Forward, backward and rematerialised operations of a tiny GPT-2's
    loss gradient keep the scope inside ``jvp(...)`` / ``transpose(jvp(...))``
    / ``checkpoint/rematted_computation`` paths, which is why the readers
    match by substring."""
    model = GPT2(GPT2Config(n_layer=2, n_head=4, d_model=64, max_seq_len=64,
                            vocab_size=256, remat=True, dtype="float32"))
    ids = np.zeros((2, 64), np.int32)
    names = _op_names(jax.jit(jax.grad(lambda p: model.loss(
        p, {"input_ids": ids}, train=True, rng=jax.random.key(1)))).lower(
            model.init(jax.random.key(0))).compile())
    for scope in ("qkv", "attn_out", "mlp", "unembed"):
        mine = {n for n in names if pb_weights.PREFIX + scope in n}
        assert any("transpose(jvp(" in n for n in mine), scope
        assert any("transpose(" not in n for n in mine), scope
    assert any("rematted_computation/dstpu.mm.mlp" in n for n in names)


def test_ssm_selection_is_unchanged_by_the_nested_weight_scopes():
    """``pbench/ssm.py`` gives an operation to the FIRST of its ``SCOPES``
    the ``tf_op`` names: with ``dstpu.mm.*`` nested inside ``dstpu.ssm.mix``
    / ``attn.diff`` / ``gmu`` every operation of the tiny Phi-4's program
    is selected as it was with the nested names taken out again."""
    import dataclasses
    from deepspeed_tpu.models.phi4flash import PHI4FLASH_TINY, Phi4Flash
    model = Phi4Flash(dataclasses.replace(PHI4FLASH_TINY, dtype="float32"))
    names = _op_names(jax.jit(model.apply).lower(
        model.init(jax.random.key(0)), np.zeros((1, 16), np.int32)).compile())

    def select(name):
        return next((sc for sc in pb_ssm.SCOPES if sc in name), None)

    nested = [n for n in names if pb_weights.PREFIX in n and select(n)]
    assert {select(n) for n in nested} == {
        pb_ssm.SSM_MIX, pb_ssm.ATTN_DIFF, pb_ssm.GMU}
    for n in names:
        assert select(n) == select(re.sub(r"dstpu\.mm\.[a-z_]+/?", "", n))
    # the MLP and the unembed are under no scope of pbench/ssm.py
    assert any(select(n) is None for n in names
               if pb_weights.PREFIX + "mlp" in n)


_SPAN_RE = re.compile(r"""\bspan\(\s*["'](dstpu\.[A-Za-z0-9_.]+)["']""")
# a scope is opened by its literal, or handed to phi4flash's ``_mm`` as the
# last argument of the call
_SCOPE_RE = re.compile(
    r"""(?:\bnamed_scope\(\s*|,\s*)["'](dstpu\.[A-Za-z0-9_.]+)["']\s*\)""")


# ------------------------------------------------ the program's own names
NAME_READERS = ("paged_attn_share", "kv_write_share",
                "paged_chunk_kernel_share", "chunk_phase_share")
UNNAMED_READERS = ("unnamed_busy_share", "train_unnamed_busy_share")


def _fixture_trace(name):
    return pb_trace.Trace(os.path.join(_FIXTURES, name + ".xplane.pb"))


@pytest.mark.parametrize("metric", NAME_READERS + UNNAMED_READERS)
@pytest.mark.parametrize("case", ["tiny4", "moe1", "dense1", "no-trace",
                                  "no-dispatch", "bare-spans"])
def test_name_readers_stand_on_a_program_without_the_names(
        metric, case, bucketed):
    """ISSUE 57 (PR 37's rule on the CPU): on a trace whose program gives
    none of PR 57's names — the three fixtures recorded on the chip before
    it, as the parent commit's programs are — the four readers of a name
    return None and the two of the unnamed rest a number, which is all of
    the busy time that PRs 26–54 left without a ``dstpu.*`` scope; no
    trace, an empty window and spans without stats give None or that
    number, and nothing raises."""
    tr = bucketed[0]
    bare = [(line, pb_trace.Event(e.name, e.start, e.end, {}))
            for line, e in tr.host]
    trace = {"no-trace": lambda: None,
             "no-dispatch": lambda: _restated(
                 _fixture_trace("dense1"), t0=0.0, t1=1e-9),
             "bare-spans": lambda: _restated(tr, host=bare)}.get(
                 case, lambda: _fixture_trace(case))()
    said = []
    view = _weights_view(trace, _cell_sizes("gpt2-medium"), said)
    value = pb_common.load_module("layer_metrics", metric).read(view)
    # by hand from ``python3 -m pbench.names perfbench/fixtures/<name>``:
    # tiny4 has no tf_op table; moe1 names the three dstpu.moe.*, dense1
    # the four dstpu.mm.*. A CPU capture has no device plane: no busy time
    want = {"tiny4": 100.0, "moe1": 100 * 442.503 / 557.690,
            "dense1": 100 * 252.191 / 288.901}.get(case) \
        if metric in UNNAMED_READERS else None
    assert value == (pytest.approx(want, rel=1e-5) if want else None)
    assert [line for line, _ in said] == (
        ["names_device_seconds"] if trace is not None else [])
    assert pb_common.load_module("layer_metrics", metric).read(
        types.SimpleNamespace(trace=trace)) == value


@pytest.mark.parametrize("metric", NAME_READERS + UNNAMED_READERS)
def test_name_readers_on_the_recorded_trace(metric):
    """The recorded values on ``fixtures/names1.xplane.pb`` (the two-layer
    GPT-2 of ``dense1`` served by a split-fuse engine on the v5e,
    ``fixtures/record_names.py``): the device's own ``tf_op`` table with the
    phases, the paged read and write and the three paged kernels under the
    names the program gave them, through the TPU compiler."""
    want = pb_common.load_json("fixtures", "names1.expected.json")
    said = []
    view = _weights_view(_fixture_trace("names1"), want["sizes"], said)
    value = pb_common.load_module("layer_metrics", metric).read(view)
    assert value == pytest.approx(want["values"][metric], rel=1e-9)
    assert 0.0 < value < 100.0
    walked = pb_names.walk(view)
    for table in ("by_scope", "by_kernel"):
        assert walked[table] == pytest.approx(want["walk"][table], rel=1e-9)
    assert set(walked["by_kernel"]) == {
        pb_names.KERNEL + k for k in ("paged_decode", "paged_chunk",
                                      "kv_write")} < set(KERNEL_SCHEMA)
    assert {pb_names.ATTN_PAGED, pb_names.KV_WRITE, pb_names.STEP_CHUNK,
            "dstpu.step.decode"} < set(walked["by_scope"])
    # what is under no phase is what XLA made after the program was traced
    # (copy-done, slice-done, a fusion of its own), and is unnamed with the
    # operations under a phase alone; a kernel is under its read or write
    steps = sum(s for k, s in walked["by_scope"].items()
                if k.startswith(pb_names.STEP))
    assert 0.75 * walked["busy_s"] < steps <= walked["busy_s"]
    assert walked["busy_s"] - steps < walked["unnamed_s"]
    assert walked["by_scope"][pb_names.KV_WRITE] \
        >= walked["by_kernel"][pb_names.KERNEL + "kv_write"]
    assert walked["by_scope"][pb_names.ATTN_PAGED] >= sum(
        walked["by_kernel"][pb_names.KERNEL + k]
        for k in ("paged_decode", "paged_chunk"))
    assert {e.stats["kind"] for e in view.trace.host_spans(
        "dstpu.engine.dispatch")} == {"chunk", "fused", "decode"}
    # the readers of PR 38's names find theirs in it too
    assert pb_weights.share(view) > 0


def _named_device(monkeypatch, ops, t0=0.0, t1=None):
    """One device whose operations, a second each, have the ``tf_op``s
    ``ops``, in a window of [t0, t1) and with as many busy seconds."""
    events = [pb_trace.Event(f"%fusion.{i} = f32[8] fusion(op {i})", i,
                             i + 1.0, {}) for i in range(len(ops))]
    monkeypatch.setattr(pb_moe, "op_scopes", lambda path, prefix: {
        e.name: op for e, op in zip(events, ops) if op})
    t1 = len(ops) if t1 is None else t1
    return types.SimpleNamespace(
        path="p", t0=t0, t1=t1, devices={"d0": events},
        busy_s=lambda: float(t1 - t0), host_spans=lambda name: [],
        in_window=lambda d: [e for e in events
                             if e.end > t0 and e.start < t1])


def test_names_walk_counts_every_name_wherever_it_stands(monkeypatch):
    """The arithmetic of ``pbench/names.py`` on a made-up device: an
    operation counts under every name of its ``tf_op`` (nesting), a
    backward and a rematerialised operation under the names inside
    ``transpose(jvp(...))`` and after ``rematted_computation/``, a kernel
    under its ``dstpu.kernel.*`` too; under a ``dstpu.step.*`` alone, or
    under nothing, it is unnamed; outside the window it is not there."""
    ops = [
        "jit(fused)/dstpu.step.chunk/dstpu.attn.full/dstpu.attn.paged/"
        "dstpu.kernel.paged_chunk/pallas_call",
        "jit(fused)/dstpu.step.chunk/dstpu.attn.full/dstpu.kv.write/"
        "dstpu.kernel.kv_write/pallas_call",
        "jit(fused)/dstpu.step.decode/dstpu.attn.full/dstpu.attn.paged/"
        "dstpu.kernel.paged_decode/pallas_call",
        "jit(fused)/dstpu.step.decode/dstpu.mm.mlp/dot_general",
        "jit(fused)/dstpu.step.decode/argmax",             # the phase alone
        None,                                              # no tf_op at all
        "jit(train_step)/transpose(jvp(dstpu.attn.flash))/"
        "dstpu.kernel.flash_bwd_t/pallas_call",
        "jit(train_step)/checkpoint/rematted_computation/dstpu.attn.flash/"
        "dstpu.kernel.flash_fwd_t/pallas_call",
        "jit(train_step)/dstpu.optim.update/mul",
        "jit(fused)/dstpu.step.chunk/dstpu.mm.qkv/dot_general",  # outside
    ]
    said = []
    view = types.SimpleNamespace(
        trace=_named_device(monkeypatch, ops, t1=9.0),
        say=lambda line, **f: said.append((line, f)))
    got = pb_names.walk(view)
    assert got["busy_s"] == 9.0 and got["unnamed_s"] == 2.0
    assert got["unnamed_ops"] == {"fusion": 2.0}
    assert got["by_scope"] == {
        "dstpu.step.chunk": 2.0, "dstpu.step.decode": 3.0,
        "dstpu.attn.full": 3.0, "dstpu.attn.paged": 2.0,
        "dstpu.kv.write": 1.0, "dstpu.mm.mlp": 1.0,
        "dstpu.attn.flash": 2.0, "dstpu.optim.update": 1.0,
        "dstpu.kernel.paged_chunk": 1.0, "dstpu.kernel.kv_write": 1.0,
        "dstpu.kernel.paged_decode": 1.0, "dstpu.kernel.flash_bwd_t": 1.0,
        "dstpu.kernel.flash_fwd_t": 1.0}
    assert got["by_kernel"] == {k: s for k, s in got["by_scope"].items()
                                if k.startswith("dstpu.kernel.")}
    reads = {m: pb_common.load_module("layer_metrics", m).read(view)
             for m in NAME_READERS + UNNAMED_READERS}
    assert reads == {
        "paged_attn_share": pytest.approx(100 * 2 / 9),
        "kv_write_share": pytest.approx(100 * 1 / 9),
        "paged_chunk_kernel_share": pytest.approx(100 * 1 / 9),
        "chunk_phase_share": pytest.approx(100 * 2 / 9),
        "unnamed_busy_share": pytest.approx(100 * 2 / 9),
        "train_unnamed_busy_share": pytest.approx(100 * 2 / 9)}
    assert [line for line, _ in said] == ["names_device_seconds"]  # one walk
    assert pb_names.components(
        "jit(f)/transpose(jvp(dstpu.attn.flash))/dstpu.kernel.flash_bwd/x") \
        == ["dstpu.attn.flash", "dstpu.kernel.flash_bwd"]
    assert pb_names.components(None) == [] == pb_names.components("jit(f)/x")


class _Recorded:
    """A jitted program that remembers the shapes of its first call."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        if self.args is None:
            self.args = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                if hasattr(x, "shape") else x, args)
        return self.fn(*args)

    def text(self):
        return self.fn.lower(*self.args).as_text(debug_info=True)


def test_served_programs_name_their_phases_reads_writes_and_kernels():
    """A tiny GPT-2's plain ``decode`` and ``fused`` programs carry, in their
    lowered text, a ``dstpu.step.decode`` a decode step and a
    ``dstpu.step.chunk``, ``dstpu.attn.paged`` and ``dstpu.kv.write`` of
    ``models/paged.py:_Step``, and the paged kernels under the names their
    ``pallas_call`` passes (interpret mode keeps the scope)."""
    kernel_dispatch.reset()
    groups.reset()
    model = GPT2(_CFG)
    engine = InferenceEngineV2(
        model, params=model.init(jax.random.key(0)),
        config=dict(_BASE, splitfuse_tokens=16, paged_kernel=True))
    engine._get_decode()
    engine._get_splitfuse()
    engine._decode_jit = _Recorded(engine._decode_jit)
    engine._splitfuse_jit = _Recorded(engine._splitfuse_jit)
    for n in (30, 20, 9):
        engine.put(np.arange(1, n, dtype=np.int32), max_new_tokens=5)
    while engine.has_work:
        engine.step()
    both = {"dstpu.attn.paged", "dstpu.kv.write", "dstpu.step.decode",
            "dstpu.kernel.paged_decode", "dstpu.mm.qkv", "dstpu.mm.attn_out",
            "dstpu.mm.mlp", "dstpu.mm.unembed"}
    with jax.set_mesh(engine.mesh):
        decode, text = engine._decode_jit.text(), engine._splitfuse_jit.text()
    assert set(pb_names.COMPONENT.findall(decode)) == both
    assert set(pb_names.COMPONENT.findall(text)) == both | {
        "dstpu.step.chunk", "dstpu.kernel.paged_chunk"}
    # outermost the phase, then the read, then the kernel; the sampling of
    # a step is under its phase and nothing else
    assert "jit(fused)/dstpu.step.chunk/dstpu.attn.paged/" \
        "dstpu.kernel.paged_chunk/pallas_call" in text
    assert "jit(fused)/dstpu.step.decode/dstpu.kv.write/" in text
    paths = set(re.findall(r'"(jit\(fused\)/[^"]*)"', text))
    assert all(pb_names.components(p)[0].startswith(pb_names.STEP)
               for p in paths if pb_names.components(p))
    for phase in ("dstpu.step.chunk", "dstpu.step.decode"):
        assert any(pb_names.components(p) == [phase] for p in paths), phase


def test_train_step_names_its_attention_its_update_and_its_kernels():
    """A tiny GPT-2's training step: ``dstpu.attn.flash`` round the
    attention between its products, forward, recomputed and backward, with
    the flash kernels' own names inside it, and ``dstpu.optim.update``
    round everything after the gradients."""
    import deepspeed_tpu
    groups.reset()
    model = GPT2(GPT2Config(
        n_layer=2, n_head=4, d_model=64, max_seq_len=64, vocab_size=256,
        remat=True, dtype="float32", use_flash_attention=True))
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2}})
    batch = jax.tree.map(engine._add_gas_dim,
                         {"input_ids": np.zeros((8, 64), np.int32)})
    batch = engine._shard_batch(batch, with_gas_dim=True)
    with jax.set_mesh(engine.mesh):
        text = engine._train_step_jit.lower(
            engine.state, batch, engine._current_lr(), None).as_text(
                debug_info=True)
    # a call inside a checkpoint or a custom_vjp has a location of its own:
    # the text holds the inner path and the call site apart
    paths = set(re.findall(r'"([^"]*dstpu\.[^"]*)"', text))
    named = {n for p in paths for n in pb_names.components(p)}
    assert {"dstpu.attn.flash", "dstpu.optim.update"} < named
    kernels = {n for n in named if n.startswith(pb_names.KERNEL)}
    assert kernels and kernels <= set(KERNEL_TALLY["flash"])
    # forward, recomputed and backward (the flash kernels' custom_vjp keeps
    # the scope its forward was traced under; on this eight-device mesh the
    # call is a shard_map body, whose location is one more piece)
    flash = [p for p in paths if "dstpu.attn.flash" in p]
    assert any("rematted_computation/dstpu.attn.flash" in p for p in flash)
    assert any(p.startswith("checkpoint/dstpu.attn.flash/") for p in flash)
    assert any("_fwd" in n for n in kernels) \
        and any("_bwd" in n for n in kernels)
    # the update is after the gradients: under no scope of the model's
    update = [p for p in paths if "dstpu.optim.update" in p]
    assert update and all(pb_names.components(p) == ["dstpu.optim.update"]
                          for p in update)


def _decode_paths(model, B=2, MB=3, BS=8):
    """The ``op_name`` paths of one decode step of ``model``, lowered from
    shapes alone (nothing compiles)."""
    import jax.numpy as jnp

    def tree():
        params = model.init(jax.random.key(0))
        return model.serving_params(params) \
            if hasattr(model, "serving_params") else params

    slots = {"slots": B} if getattr(model, "slot_state", False) else {}
    cache = jax.eval_shape(
        lambda: model.init_paged_cache(1 + B * MB, BS, **slots))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    text = jax.jit(model.apply_paged_decode).lower(
        jax.eval_shape(tree), i32(B), i32(B), cache,
        i32(B, MB)).as_text(debug_info=True)
    return sorted(set(re.findall(r'"(jit\([^"]*)"', text)))


_NEW_NAME = re.compile(
    r"dstpu\.(?:step|kernel|kv)\.[a-z0-9_]+/?|dstpu\.attn\.paged/?"
    r"|dstpu\.attn\.flash/?|dstpu\.optim\.update/?")


def _selections(monkeypatch, ops):
    """What each reader module that selects by scope makes of a device
    whose operations have the ``tf_op``s ``ops``."""
    def view():
        return types.SimpleNamespace(
            trace=_named_device(monkeypatch, ops),
            say=lambda line, **f: None)
    walked = pb_weights.walk(view())
    return {"moe": pb_moe.device_seconds(view()),
            "ssm": pb_ssm.scope_seconds(view()),
            "gdn": pb_gdn.scope_seconds(view()),
            "dsa": pb_dsa.scope_seconds(view()),
            "mla_moe": pb_mla_moe.scope_seconds(view()),
            "weights": walked and (walked["mm_s"], walked["by_scope"])}


@pytest.mark.parametrize("family", ["gpt2", "phi4flash", "olmo_hybrid",
                                    "deepseek_v32", "olmoe"])
def test_scope_selections_are_unchanged_by_the_new_names(monkeypatch, family):
    """``pbench/moe.py``, ``ssm.py``, ``gdn.py``, ``dsa.py``, ``mla_moe.py``
    and ``weights.py`` pick an operation's scope out of their own sets: with
    ``dstpu.attn.paged`` / ``dstpu.kv.write`` nested inside a family's
    ``dstpu.attn.*``, a ``dstpu.step.*`` outermost and a ``dstpu.kernel.*``
    innermost, each gives every operation of a tiny model's decode step the
    seconds it gave with the new names taken out again (PR 38's test of
    ``ssm.py``, for all six and ISSUE 57's names)."""
    import dataclasses
    from deepspeed_tpu import models
    cls, cfg = {
        "gpt2": (GPT2, models.GPT2_TINY),
        "phi4flash": (models.Phi4Flash, models.PHI4FLASH_TINY),
        "olmo_hybrid": (models.OlmoHybrid, models.OLMO_HYBRID_TINY),
        "deepseek_v32": (models.DeepseekV32, models.DEEPSEEK_V32_TINY),
        "olmoe": (models.OLMoE, models.OLMOE_TINY)}[family]
    groups.reset()
    paths = _decode_paths(cls(dataclasses.replace(cfg, dtype="float32")))
    # as the engine's program and a kernel's call would have them
    nested = [p.replace("/", "/dstpu.step.decode/", 1)
              + "/dstpu.kernel.paged_decode/pallas_call" for p in paths]
    stripped = [_NEW_NAME.sub("", p) for p in nested]
    if family != "deepseek_v32":        # a latent layer has its own scope
        assert any("dstpu.attn.paged" in p for p in paths)
        assert any("dstpu.kv.write" in p for p in paths)
    assert not any(_NEW_NAME.search(p) for p in stripped)
    assert _selections(monkeypatch, nested) \
        == _selections(monkeypatch, stripped)
    got = _selections(monkeypatch, nested)
    assert got["weights"][0] > 0 and (got["moe"][0] > 0) == (
        family in ("deepseek_v32", "olmoe"))


# read by name, with no list of names, by pbench/names.py (ISSUE 57)
_GENERAL_SCOPES = {"dstpu.attn.paged", "dstpu.kv.write", "dstpu.step.prefill",
                   "dstpu.step.chunk", "dstpu.step.decode",
                   "dstpu.attn.flash", "dstpu.optim.update"}


def _opened(rx):
    found = set()
    pkg = os.path.join(REPO, "deepspeed_tpu")
    for dirpath, _, names in os.walk(pkg):
        for n in names:
            if n.endswith(".py"):
                with open(os.path.join(dirpath, n), encoding="utf-8") as f:
                    found.update(rx.findall(f.read()))
    return found


def test_scope_schema_lint_both_directions():
    opened = _opened(_SCOPE_RE)
    assert opened - set(SCOPE_SCHEMA) == set(), "scopes not in SCOPE_SCHEMA"
    assert set(SCOPE_SCHEMA) - opened == set(), "registered, never opened"
    assert all(n.startswith("dstpu.") and m for n, m in SCOPE_SCHEMA.items())
    # the benchmark's readers look for the same names; dstpu.moe.spill lies
    # round route / experts / combine, which they count it under, and is
    # read in a trace by hand (ISSUE 56); the seven of ISSUE 57 are read by
    # pbench/names.py, which keeps no list and so needs no edit for the next
    assert {pb_moe.SCOPE_EXPERTS, *pb_moe.SCOPES_ROUTE, *pb_ssm.SCOPES,
            *pb_gdn.SCOPES, *pb_dsa.SCOPES, *pb_mla_moe.SCOPES,
            *pb_weights.SCOPES} \
        == set(SCOPE_SCHEMA) - {"dstpu.moe.spill"} - _GENERAL_SCOPES
    assert {pb_names.ATTN_PAGED, pb_names.KV_WRITE, pb_names.STEP_CHUNK} \
        < _GENERAL_SCOPES <= set(SCOPE_SCHEMA)
    assert all(pb_names.components(f"jit(f)/jvp({n})/mul") == [n]
               for n in (*SCOPE_SCHEMA, *KERNEL_SCHEMA))


def _pallas_call_sites():
    """[(file, line, the ``name=`` literal or None)] of every ``pallas_call(``
    in the package's code: tokens, so that a docstring or a comment that
    says the word is no site."""
    import tokenize
    sites = []
    pkg = os.path.join(REPO, "deepspeed_tpu")
    for dirpath, _, files in os.walk(pkg):
        for n in files:
            if not n.endswith(".py"):
                continue
            path = os.path.join(dirpath, n)
            with open(path, "rb") as f:
                toks = [t for t in tokenize.tokenize(f.readline)
                        if t.type not in (tokenize.NL, tokenize.NEWLINE,
                                          tokenize.COMMENT, tokenize.INDENT,
                                          tokenize.DEDENT)]
            for i, t in enumerate(toks):
                if not (t.type == tokenize.NAME and t.string == "pallas_call"
                        and toks[i + 1].string == "("):
                    continue
                depth, name = 0, None
                for j in range(i + 1, len(toks)):
                    depth += (toks[j].string in "([{" and toks[j].type
                              == tokenize.OP) - (toks[j].string in ")]}"
                                                 and toks[j].type
                                                 == tokenize.OP)
                    if depth == 0:
                        break
                    if depth == 1 and toks[j].string == "name" \
                            and toks[j + 1].string == "=" \
                            and toks[j + 2].type == tokenize.STRING:
                        name = toks[j + 2].string.strip("\"'")
                sites.append((os.path.relpath(path, REPO), t.start[0], name))
    return sites


def test_kernel_schema_lint_three_ways():
    """ISSUE 57, item 1: every ``pallas_call(`` under ``deepspeed_tpu/``
    passes a registered ``name=``, no two sites share one, and every
    registered name is at a site; the tally's words (``note_call``) are the
    ones the engines count under, and name registered kernels."""
    sites = _pallas_call_sites()
    assert len(sites) >= 30
    unnamed = [(f, line) for f, line, name in sites if name is None]
    assert not unnamed, f"pallas_call sites without name=: {unnamed}"
    at = [name for _, _, name in sites]
    assert sorted(at) == sorted(set(at)), "a kernel name at two sites"
    assert set(at) - set(KERNEL_SCHEMA) == set(), "names not in KERNEL_SCHEMA"
    assert set(KERNEL_SCHEMA) - set(at) == set(), "registered, at no site"
    assert len(sites) == len(KERNEL_SCHEMA)
    assert all(n.startswith(pb_names.KERNEL) and m
               for n, m in KERNEL_SCHEMA.items())
    assert set(KERNEL_TALLY) == set(KERNEL_SHARES) | set(SHAPE_PATHS)
    listed = [n for names in KERNEL_TALLY.values() for n in names]
    assert len(listed) == len(set(listed)) and set(listed) < set(KERNEL_SCHEMA)
    assert pb_names.PAGED_CHUNK_KERNEL in KERNEL_SCHEMA


def test_span_schema_lint_both_directions():
    opened = _opened(_SPAN_RE)
    assert opened - set(SPAN_SCHEMA) == set(), "spans not in SPAN_SCHEMA"
    assert set(SPAN_SCHEMA) - opened == set(), "registered, never opened"
    for name, entry in SPAN_SCHEMA.items():
        assert name.startswith("dstpu.") and entry["meaning"]
        assert isinstance(entry["stats"], tuple)
