"""Prefix cache (tier-1): allocator refcount hardening, radix-tree
match/claim/insert/evict invariants, the release-path exactly-once
audit, engine greedy byte-identity with the cache on vs off (shared
prefixes, divergent prompts, partial-tail CoW), eviction-under-pressure
admission, the sliding-window loud refusal, and warm/cold winner-cache
dispatch for the prefix_cache policy op (a cold "auto" engine is
byte-identical to prefix_cache=False)."""

import os

import numpy as np
import pytest

import jax

from deepspeed_tpu.autotuning import KernelCache, kernel_dispatch
from deepspeed_tpu.inference.v2 import InferenceEngineV2, PrefixCache
from deepspeed_tpu.inference.v2.blocked_allocator import BlockedAllocator
from deepspeed_tpu.inference.v2.prefix_cache import (PREFIX_CACHE_DEFAULTS,
                                                     prefix_cache_bucket)
from deepspeed_tpu.models import GPT2, GPT2Config
from deepspeed_tpu.utils import groups


@pytest.fixture(autouse=True)
def _pristine_dispatch(tmp_path, monkeypatch):
    """Private winner cache + reset process-global dispatch state."""
    monkeypatch.setenv("DSTPU_AUTOTUNE_CACHE",
                       str(tmp_path / "kernel_autotune.json"))
    monkeypatch.delenv("DSTPU_AUTOTUNE", raising=False)
    kernel_dispatch.reset()
    yield
    kernel_dispatch.reset()


# ---------------------------------------------------------------------------
# allocator hardening (satellite: double-free / free-while-referenced raise)
# ---------------------------------------------------------------------------

class TestAllocatorHardening:
    def test_double_free_raises(self):
        a = BlockedAllocator(8)
        blocks = a.allocate(2)
        a.free(blocks)
        with pytest.raises(ValueError, match="double-free"):
            a.free(blocks)

    def test_duplicate_in_one_free_raises(self):
        a = BlockedAllocator(8)
        (b,) = a.allocate(1)
        with pytest.raises(ValueError, match="double-free"):
            a.free([b, b])

    def test_free_while_referenced_raises(self):
        a = BlockedAllocator(8)
        (b,) = a.allocate(1)
        a.ref(b)                       # shared (e.g. adopted by the tree)
        with pytest.raises(ValueError, match="still referenced"):
            a.free([b])
        a.unref(b)
        a.free([b])                    # sole ownership again: fine

    def test_free_validates_whole_list_before_mutating(self):
        a = BlockedAllocator(8)
        good, = a.allocate(1)
        with pytest.raises(ValueError):
            a.free([good, 999])        # bad id later in the list
        assert a.refcount(good) == 1   # nothing half-applied
        a.free([good])

    def test_unref_past_zero_raises(self):
        a = BlockedAllocator(8)
        (b,) = a.allocate(1)
        assert a.unref(b) is True      # freed at zero
        with pytest.raises(ValueError, match="double-free"):
            a.unref(b)

    def test_ref_of_unallocated_raises(self):
        a = BlockedAllocator(8)
        with pytest.raises(ValueError, match="not allocated"):
            a.ref(3)

    def test_scratch_block_is_reserved(self):
        a = BlockedAllocator(4)
        assert BlockedAllocator.SCRATCH not in a.allocate(3)
        with pytest.raises(ValueError, match="scratch"):
            a.free([BlockedAllocator.SCRATCH])

    def test_allocate_reclaims_from_evictor_under_pressure(self):
        class Evictor:
            def __init__(self, alloc, held):
                self.alloc, self.held = alloc, held

            @property
            def evictable_blocks(self):
                return len(self.held)

            def evict(self, n):
                for _ in range(min(n, len(self.held))):
                    self.alloc.unref(self.held.pop())

        a = BlockedAllocator(6)        # 5 usable
        held = a.allocate(5)           # pool exhausted, held by "tree"
        a.set_evictor(Evictor(a, held))
        assert a.free_blocks == 0 and a.available_blocks == 5
        got = a.allocate(3)            # must evict 3, then succeed
        assert len(got) == 3 and a.available_blocks == 2
        with pytest.raises(RuntimeError, match="out of KV blocks"):
            a.allocate(5)              # 2 evictable + 0 free < 5


# ---------------------------------------------------------------------------
# radix tree: match / claim / insert / evict
# ---------------------------------------------------------------------------

BS = 4


def _mk(num_blocks=32, mm=1, max_blocks=0):
    a = BlockedAllocator(num_blocks)
    return a, PrefixCache(a, BS, min_match_blocks=mm,
                          max_blocks=max_blocks)


def _toks(*ints):
    return np.asarray(ints, np.int32)


class TestRadixTree:
    def test_empty_tree_is_a_miss(self):
        _, c = _mk()
        m = c.match(_toks(*range(10)))
        assert not m.hit and m.blocks == [] and m.cached_len == 0

    def test_full_block_match_after_release(self):
        a, c = _mk()
        toks = np.arange(3 * BS, dtype=np.int32)
        blocks = a.allocate(3)
        c.release(toks, blocks)
        assert c.tree_blocks == 3
        # tree holds its own refs; the sequence's were dropped
        assert all(a.refcount(b) == 1 for b in blocks)
        m = c.match(np.concatenate([toks, _toks(77)]))
        assert m.blocks == blocks and m.cached_len == 3 * BS
        assert m.cow_src is None       # divergent token, no partial tail

    def test_last_prompt_token_is_always_recomputed(self):
        a, c = _mk()
        toks = np.arange(3 * BS, dtype=np.int32)
        c.release(toks, a.allocate(3))
        # identical prompt: the T-1 cap turns the last block into a
        # BS-1 partial tail served by CoW, never a full-block match
        m = c.match(toks)
        assert len(m.blocks) == 2 and m.cow_plen == BS - 1
        assert m.cached_len == 3 * BS - 1 == len(toks) - 1

    def test_partial_tail_cow_on_mid_block_divergence(self):
        a, c = _mk()
        toks = np.arange(2 * BS, dtype=np.int32)
        blocks = a.allocate(2)
        c.release(toks, blocks)
        probe = np.concatenate([toks[:BS + 2], _toks(90, 91, 92, 93)])
        m = c.match(probe)
        assert m.blocks == blocks[:1] and m.cow_src == blocks[1]
        assert m.cow_plen == 2 and m.cached_len == BS + 2

    def test_min_match_blocks_gates_short_hits(self):
        a, c = _mk(mm=2)
        c.release(np.arange(BS, dtype=np.int32), a.allocate(1))
        m = c.match(np.concatenate([np.arange(BS, dtype=np.int32),
                                    _toks(50, 51)]))
        assert not m.hit and m.blocks == [] and m.cow_src is None

    def test_claim_refs_blocks_and_cow_release_drops_source(self):
        a, c = _mk()
        toks = np.arange(2 * BS, dtype=np.int32)
        blocks = a.allocate(2)
        c.release(toks, blocks)
        m = c.match(toks)              # 1 full block + BS-1 CoW tail
        c.claim(m)
        assert a.refcount(blocks[0]) == 2     # tree + sequence
        assert a.refcount(blocks[1]) == 2     # tree + CoW claim
        c.cow_release(m.cow_src)
        assert a.refcount(blocks[1]) == 1 and c.cow_copies == 1
        assert c.hits == 1 and c.lookups == 1

    def test_match_is_pure(self):
        a, c = _mk()
        toks = np.arange(2 * BS, dtype=np.int32)
        blocks = a.allocate(2)
        c.release(toks, blocks)
        before = [a.refcount(b) for b in blocks]
        c.match(toks)                  # admission probe, not claimed
        assert [a.refcount(b) for b in blocks] == before
        assert c.lookups == 0 and c.hits == 0

    def test_insert_dedups_against_existing_nodes(self):
        a, c = _mk()
        toks = np.arange(2 * BS, dtype=np.int32)
        first = a.allocate(2)
        c.release(toks, first)
        dup = a.allocate(2)            # a second seq recomputed the same KV
        c.release(toks, dup)
        assert c.tree_blocks == 2      # nothing adopted twice
        assert all(a.refcount(b) == 0 for b in dup)   # dup died at unref
        assert all(a.refcount(b) == 1 for b in first)

    def test_eviction_is_lru_over_unreferenced_leaves(self):
        a, c = _mk()
        t1 = np.arange(2 * BS, dtype=np.int32)
        t2 = np.concatenate([t1[:BS], _toks(60, 61, 62, 63)])
        c.release(t1, a.allocate(2))   # chain: n0 -> n1
        c.release(t2, a.allocate(2))   # n0 -> n2 (n0 deduped, older n1)
        assert c.tree_blocks == 3 and c.evictable_blocks == 3
        c.evict(1)
        # leaves only: the shared parent n0 (has children) survives
        assert c.tree_blocks == 2
        m = c.match(np.concatenate([t1[:BS], _toks(99, 98)]))
        assert len(m.blocks) == 1     # parent still matchable
        # a claimed leaf is pinned; eviction walks past it
        m2 = c.match(np.concatenate([t2, _toks(7)]))
        c.claim(m2)
        assert c.evictable_blocks == 0  # every remaining node on t2 path
        assert c.evict(5) == 0

    def test_max_blocks_caps_tree_growth(self):
        a, c = _mk(mm=1, max_blocks=2)
        c.release(np.arange(4 * BS, dtype=np.int32), a.allocate(4))
        assert c.tree_blocks <= 2


class TestReleaseExactlyOnce:
    def test_release_unrefs_every_sequence_block_once(self):
        a, c = _mk()
        counts = {}
        inner = a.unref

        def audited(b):
            counts[b] = counts.get(b, 0) + 1
            return inner(b)

        a.unref = audited
        toks = np.arange(2 * BS, dtype=np.int32)
        first = a.allocate(3)          # 2 full + 1 partial tail block
        c.release(np.concatenate([toks, _toks(5, 6)]), first)
        for b in first:
            assert counts.get(b, 0) == 1, f"block {b}: {counts}"
        # duplicate-content release: adopted nothing, still exactly once
        # (a block the first release freed may be REallocated here — a
        # new ownership epoch, so the audit restarts)
        counts.clear()
        dup = a.allocate(3)
        c.release(np.concatenate([toks, _toks(5, 6)]), dup)
        for b in dup:
            assert counts.get(b, 0) == 1, f"block {b}: {counts}"
        # pool accounting closes: free + tree == total
        assert a.free_blocks + c.tree_blocks == a.total_blocks


# ---------------------------------------------------------------------------
# engine end-to-end: greedy byte-identity, eviction, refusals
# ---------------------------------------------------------------------------

_CFG = GPT2Config(n_layer=2, n_head=4, d_model=64, max_seq_len=128,
                  vocab_size=256, remat=False, dtype="float32")
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = GPT2(_CFG).init(jax.random.key(0))
    return _PARAMS


_BASE = {"dtype": "float32", "kv_block_size": 8, "prompt_bucket": 16,
         "max_batch_size": 4, "splitfuse_tokens": 16,
         "decode_steps_per_dispatch": 2,   # small unroll = fast compiles
         "prefix_cache_min_match": 1}


def _engine(**kw):
    groups.reset()
    return InferenceEngineV2(GPT2(_CFG), params=_params(),
                             config=dict(_BASE, **kw))


def _run_sequential(eng, prompts, max_new=6):
    """One prompt at a time, each to completion — later prompts see the
    prefixes earlier ones released into the cache."""
    out = []
    for p in prompts:
        out.append(eng.generate_all([p], max_new_tokens=max_new)[0])
    return out


@pytest.fixture(scope="module")
def off_ref():
    """ONE shared cache-off reference engine for every identity test:
    with the cache off a finished request leaves no state behind, so
    its greedy outputs depend only on the prompt — safe to reuse the
    compiled programs across scenarios instead of paying a fresh
    engine compile per test."""
    eng = _engine(prefix_cache=False)

    def run(prompts, max_new=6):
        return _run_sequential(eng, prompts, max_new)

    return run


class TestEngineGreedyIdentity:
    def _identity(self, prompts, off_ref, **on_kw):
        on = _engine(prefix_cache=True, **on_kw)
        got = _run_sequential(on, prompts)
        for a, b in zip(got, off_ref(prompts)):
            np.testing.assert_array_equal(a, b)
        return on.prefix_cache.stats()

    def test_shared_prefix_hits_and_stays_byte_identical(self, off_ref):
        rs = np.random.RandomState(0)
        template = rs.randint(0, 256, (17,)).astype(np.int32)
        prompts = [np.concatenate([template,
                                   rs.randint(0, 256, (6,)).astype(np.int32)])
                   for _ in range(3)]
        s = self._identity(prompts, off_ref)
        assert s["hits"] >= 2 and s["cached_tokens"] >= 2 * 16

    def test_divergent_prompts_stay_byte_identical(self, off_ref):
        rs = np.random.RandomState(1)
        prompts = [rs.randint(0, 256, (n,)).astype(np.int32)
                   for n in (5, 21, 33)]
        s = self._identity(prompts, off_ref)
        assert s["lookups"] == 3       # every admission consulted the tree

    def test_partial_tail_cow_byte_identical(self, off_ref):
        rs = np.random.RandomState(2)
        p1 = rs.randint(0, 256, (20,)).astype(np.int32)
        # diverges 4 tokens into p1's second block -> CoW slice copy
        p2 = np.concatenate([p1[:12], rs.randint(0, 256, (8,))]) \
            .astype(np.int32)
        s = self._identity([p1, p2], off_ref)
        assert s["cow_copies"] == 1 and s["hits"] == 1

    def test_identical_prompt_resubmitted_byte_identical(self, off_ref):
        # the T-1 cap end-to-end: the whole prompt is cached except the
        # recomputed last token, and decode still matches exactly
        rs = np.random.RandomState(3)
        p = rs.randint(0, 256, (24,)).astype(np.int32)
        s = self._identity([p, p], off_ref)
        assert s["hits"] == 1 and s["cow_copies"] == 1
        assert s["cached_tokens"] == len(p) - 1

    def test_legacy_bucketed_prefill_path_byte_identical(self):
        # splitfuse off: misses keep the legacy whole-prompt prefill,
        # hits route through the chunk path with an offset — outputs
        # must agree with the cache-off engine either way
        rs = np.random.RandomState(4)
        template = rs.randint(0, 256, (17,)).astype(np.int32)
        prompts = [np.concatenate([template,
                                   rs.randint(0, 256, (5,)).astype(np.int32)])
                   for _ in range(2)]
        on = _engine(prefix_cache=True, splitfuse_tokens=0)
        got = _run_sequential(on, prompts)
        off = _engine(prefix_cache=False, splitfuse_tokens=0)
        ref = _run_sequential(off, prompts)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a, b)
        assert on.prefix_cache.stats()["hits"] >= 1


class TestEvictionUnderPressure:
    def test_full_pool_of_cached_leaves_still_admits(self, off_ref):
        rs = np.random.RandomState(5)
        p1 = rs.randint(0, 256, (40,)).astype(np.int32)
        p2 = rs.randint(0, 256, (40,)).astype(np.int32)
        eng = _engine(prefix_cache=True, num_kv_blocks=8)  # 7 usable
        got = _run_sequential(eng, [p1, p2])
        s = eng.prefix_cache.stats()
        # p1's release filled most of the pool with tree blocks; p2
        # (unshared, needs 6 of the 7) could only admit by evicting
        assert s["evicted_blocks"] >= 1
        # pool size only gates admission — with one request in flight
        # at a time the greedy outputs match the shared reference
        for a, b in zip(got, off_ref([p1, p2])):
            np.testing.assert_array_equal(a, b)
        # exactly-once audit at engine scale: after everything retired,
        # every surviving ref belongs to the tree and accounting closes
        alloc = eng.state_mgr.allocator
        assert alloc.free_blocks + eng.prefix_cache.tree_blocks \
            == alloc.total_blocks


_WCFG = GPT2Config(n_layer=2, n_head=4, d_model=64, max_seq_len=128,
                   vocab_size=256, remat=False, dtype="float32",
                   attn_layer_windows=(8, 8))


class TestRefusals:
    def test_sliding_window_model_refuses_forced_cache(self):
        groups.reset()
        with pytest.raises(ValueError, match="sliding-window"):
            InferenceEngineV2(GPT2(_WCFG),
                              config=dict(_BASE, prefix_cache=True))

    def test_sliding_window_model_resolves_auto_off(self):
        groups.reset()
        eng = InferenceEngineV2(GPT2(_WCFG),
                                config=dict(_BASE, prefix_cache="auto"))
        assert eng.prefix_cache is None

    def test_kv_host_offload_is_incompatible(self):
        with pytest.raises(ValueError, match="kv_host_offload"):
            _engine(prefix_cache=True, kv_host_offload=True,
                    device_kv_blocks=8)

    def test_config_junk_rejected(self):
        with pytest.raises(ValueError):
            _engine(prefix_cache="yes-please")
        with pytest.raises(ValueError):
            _engine(prefix_cache=True, prefix_cache_min_match=0)
        with pytest.raises(ValueError):
            _engine(prefix_cache=True, prefix_cache_blocks=-1)


# ---------------------------------------------------------------------------
# warm/cold winner-cache dispatch (test_paged_kernel.py style)
# ---------------------------------------------------------------------------

def _lower_step_programs(eng):
    """Byte-level text of the engine's OWN jitted decode + chunk-only
    programs, lowered with fixed shapes."""
    B = eng.config.max_batch_size
    MB = eng.max_blocks_per_seq
    i32, f32 = np.int32, np.float32
    z = np.zeros
    rng = jax.random.key(0)
    with jax.set_mesh(eng.mesh):
        dec = eng._get_decode().lower(
            eng.params, eng.cache, z((B,), i32), z((B,), i32),
            z((B, MB), i32), rng, z((B,), f32), z((B,), i32),
            True, eng._no_prev, z((B,), bool)).as_text()
        C = eng.config.splitfuse_tokens
        chk = eng._get_chunk_only().lower(
            eng.params, eng.cache, z((1, C), i32), z((C,), i32),
            z((C,), i32), i32(0), i32(0), z((MB,), i32), f32(0),
            i32(0), rng, True).as_text()
    return dec, chk


class TestPrefixDispatchColdWarm:
    def test_cold_auto_is_byte_identical_to_disabled(self):
        """Acceptance: prefix_cache="auto" on a cold winner cache must
        not perturb the engine — no PrefixCache constructed, and the
        compiled step programs lower byte-identical to
        prefix_cache=False."""
        kernel_dispatch.configure(mode="cache_only")   # empty cache
        auto = _engine(prefix_cache="auto", prefix_cache_min_match="auto")
        assert auto.prefix_cache is None
        t_auto = _lower_step_programs(auto)
        kernel_dispatch.configure(mode="cache_only")
        off = _engine(prefix_cache=False)
        assert t_auto == _lower_step_programs(off)

    def test_warm_cache_enables_with_cached_policy(self):
        path = os.environ["DSTPU_AUTOTUNE_CACHE"]
        dk = kernel_dispatch.device_kind()
        NB = 1 + _BASE["max_batch_size"] * (128 // _BASE["kv_block_size"])
        c = KernelCache()
        c.put(dk, "prefix_cache",
              prefix_cache_bucket(_BASE["max_batch_size"], NB,
                                  _BASE["kv_block_size"]), "float32",
              {"enabled": 1, "min_match_blocks": 2,
               "evict_watermark_pct": 25})
        c.save(path)
        kernel_dispatch.configure(mode="cache_only")
        eng = _engine(prefix_cache="auto", prefix_cache_min_match="auto")
        assert eng.prefix_cache is not None
        assert eng.prefix_cache.min_match_blocks == 2
        assert eng.prefix_cache.evict_watermark_pct == 25

    def test_explicit_false_never_consults_dispatch(self):
        kernel_dispatch.configure(mode="cache_only")
        _engine(prefix_cache=False)
        assert not any("prefix_cache" in str(k)
                       for k in kernel_dispatch._STATE["resolved"])

    def test_cold_defaults_are_the_hand_set_values(self):
        assert PREFIX_CACHE_DEFAULTS == {"enabled": 0,
                                         "min_match_blocks": 1,
                                         "evict_watermark_pct": 0}
