"""v2 serving engine tests: blocked allocator, state manager, paged decode
parity with the dense engine, continuous batching. Reference coverage
model: tests/unit/inference/v2/ (kernels + ragged + engine)."""

import functools

import numpy as np
import jax
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2 import (BlockedAllocator, DSStateManager,
                                        InferenceEngineV2, engine_v2)
from deepspeed_tpu.models import GPT2, GPT2Config
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.groups import TopologyConfig

# compile-heavy: excluded from the fast core set (pytest -m 'not slow'),
# class by class; TestChainedDecode at the end is tier-1
slow = pytest.mark.slow



CFG = GPT2Config(n_layer=2, n_head=4, d_model=64, max_seq_len=128,
                 vocab_size=256, remat=False, dtype="float32")


@slow
class TestBlockedAllocator:
    def test_allocate_free_cycle(self):
        a = BlockedAllocator(8)
        assert a.total_blocks == 7
        got = a.allocate(3)
        assert len(set(got)) == 3 and 0 not in got
        assert a.free_blocks == 4
        a.free(got)
        assert a.free_blocks == 7

    def test_exhaustion_raises(self):
        a = BlockedAllocator(4)
        a.allocate(3)
        with pytest.raises(RuntimeError):
            a.allocate(1)

    def test_double_free_raises(self):
        a = BlockedAllocator(4)
        got = a.allocate(2)
        a.free(got[:1])
        with pytest.raises(ValueError):
            a.free(got[:1])
        with pytest.raises(ValueError):
            a.free([0])


@slow
class TestStateManager:
    def test_admit_retire_frees_blocks(self):
        m = DSStateManager(num_blocks=9, block_size=4, max_batch=2,
                           max_blocks_per_seq=4)
        slot, seq = m.admit(1, np.arange(5), max_new_tokens=3)
        # 5+3=8 tokens -> 2 blocks
        assert len(seq.blocks) == 2
        assert m.allocator.free_blocks == 6
        m.retire(1)
        assert m.allocator.free_blocks == 8
        assert m.free_slot() == slot

    def test_can_admit_respects_blocks_and_slots(self):
        m = DSStateManager(num_blocks=5, block_size=4, max_batch=1,
                           max_blocks_per_seq=4)
        assert m.can_admit(8, 0)
        m.admit(1, np.arange(8), max_new_tokens=0)
        assert not m.can_admit(1, 0)  # no slot
        m.retire(1)
        assert m.can_admit(16, 0)
        assert not m.can_admit(16, 1)  # 17 tokens -> 5 blocks > 4 free

    def test_decode_batch_layout(self):
        m = DSStateManager(num_blocks=9, block_size=4, max_batch=3,
                           max_blocks_per_seq=2)
        _, seq = m.admit(7, np.arange(6), max_new_tokens=2)
        seq.generated.append(42)
        b = m.decode_batch()
        assert b.active.tolist() == [True, False, False]
        assert b.tokens[0] == 42
        assert b.lengths[0] == 6  # prompt in cache, new token not yet
        assert (b.block_tables[1] == 0).all()


def _v1_greedy(model, params, prompts, n):
    groups.reset()
    eng = deepspeed_tpu.init_inference(
        model, params=params, config={"dtype": "float32",
                                      "prompt_bucket": 16})
    out = eng.generate(prompts, max_new_tokens=n, temperature=0.0)
    groups.reset()
    return out


@slow
class TestEngineV2:
    def test_paged_greedy_matches_dense(self):
        model = GPT2(CFG)
        params = model.init(jax.random.key(0))
        prompts = [np.arange(5) % 256, (np.arange(9) * 3) % 256,
                   (np.arange(3) + 100) % 256]
        ref = _v1_greedy(model, params, prompts, 6)
        eng = InferenceEngineV2(model, params=params,
                                config={"dtype": "float32",
                                        "kv_block_size": 8,
                                        "prompt_bucket": 16,
                                        "max_batch_size": 4})
        outs = eng.generate_all(prompts, max_new_tokens=6)
        for i, o in enumerate(outs):
            np.testing.assert_array_equal(o, ref[i])

    def test_continuous_batching_more_requests_than_slots(self):
        model = GPT2(CFG)
        params = model.init(jax.random.key(0))
        prompts = [((np.arange(4) + 11 * i) % 256) for i in range(6)]
        eng = InferenceEngineV2(model, params=params,
                                config={"dtype": "float32",
                                        "kv_block_size": 8,
                                        "prompt_bucket": 8,
                                        "max_batch_size": 2})
        free0 = eng.state_mgr.allocator.free_blocks
        outs = eng.generate_all(prompts, max_new_tokens=5)
        ref = _v1_greedy(model, params, prompts, 5)
        for i, o in enumerate(outs):
            np.testing.assert_array_equal(o, ref[i])
        # all blocks returned to the free list
        assert eng.state_mgr.allocator.free_blocks == free0

    def test_block_boundary_crossing(self):
        """Generation crossing multiple block boundaries stays correct."""
        model = GPT2(CFG)
        params = model.init(jax.random.key(0))
        prompts = [np.arange(6) % 256]
        ref = _v1_greedy(model, params, prompts, 12)
        eng = InferenceEngineV2(model, params=params,
                                config={"dtype": "float32",
                                        "kv_block_size": 4,
                                        "prompt_bucket": 8,
                                        "max_batch_size": 2})
        outs = eng.generate_all(prompts, max_new_tokens=12)
        np.testing.assert_array_equal(outs[0], ref[0])

    def test_eos_retires_early_and_frees(self):
        model = GPT2(CFG)
        params = model.init(jax.random.key(0))
        prompt = np.arange(4) % 256
        ref = _v1_greedy(model, params, [prompt], 1)
        eos = int(ref[0, 0])  # first greedy token
        eng = InferenceEngineV2(model, params=params,
                                config={"dtype": "float32",
                                        "kv_block_size": 8,
                                        "prompt_bucket": 8,
                                        "max_batch_size": 2})
        free0 = eng.state_mgr.allocator.free_blocks
        uid = eng.put(prompt, max_new_tokens=10, eos_token_id=eos)
        while eng.has_work:
            eng.step()
        out = eng.get(uid)
        assert out.tolist() == [eos]
        assert eng.state_mgr.allocator.free_blocks == free0

    def test_tp_paged_matches_single(self):
        model = GPT2(CFG)
        params = model.init(jax.random.key(0))
        prompts = [np.arange(7) % 256]
        groups.reset()
        topo = groups.initialize(TopologyConfig(tensor_parallel_size=4))
        eng = InferenceEngineV2(model, params=params, topology=topo,
                                config={"dtype": "float32",
                                        "kv_block_size": 8,
                                        "prompt_bucket": 8,
                                        "tensor_parallel": 4})
        outs = eng.generate_all(prompts, max_new_tokens=6)
        ref = _v1_greedy(model, params, prompts, 6)
        np.testing.assert_array_equal(outs[0], ref[0])

    def test_ep_sharded_mixtral_matches_single(self):
        """EP x TP serving (reference module_inject/layers.py EP+TP
        inference MoE): mixtral experts sharded over 'expert' and
        heads/FFN over 'tensor' in the v2 decode/prefill programs must
        reproduce the single-device greedy tokens exactly."""
        from deepspeed_tpu.models.mixtral import Mixtral, MixtralConfig
        mcfg = MixtralConfig(n_layer=2, n_head=4, n_kv_heads=2,
                             d_model=64, max_seq_len=128, vocab_size=512,
                             remat=False, num_experts=4, moe_top_k=2,
                             dtype="float32")
        model = Mixtral(mcfg)
        params = model.init(jax.random.key(5))
        prompts = [np.arange(9) % 500, (np.arange(13) + 41) % 500]

        groups.reset()
        single = InferenceEngineV2(model, params=params,
                                   config={"dtype": "float32",
                                           "kv_block_size": 16,
                                           "max_batch_size": 2})
        ref = single.generate_all(prompts, max_new_tokens=6)

        groups.reset()
        eng = InferenceEngineV2(model, params=params,
                                config={"dtype": "float32",
                                        "kv_block_size": 16,
                                        "max_batch_size": 2,
                                        "tensor_parallel": 2,
                                        "expert_parallel": 2})
        outs = eng.generate_all(prompts, max_new_tokens=6)
        for a, b in zip(ref, outs):
            np.testing.assert_array_equal(a, b)

    def test_ep_splitfuse_mixtral_matches_single(self):
        """EP serving through the SplitFuse chunk program: mixtral at
        expert_parallel=2 with chunked prefill must reproduce the
        single-shard greedy tokens — the chunk program's expert FFN
        routes through the ragged EP all_to_all path too (the PR-5
        GSPMD ragged_dot mis-partition fix)."""
        from deepspeed_tpu.models.mixtral import Mixtral, MixtralConfig
        mcfg = MixtralConfig(n_layer=2, n_head=4, n_kv_heads=2,
                             d_model=64, max_seq_len=128, vocab_size=512,
                             remat=False, num_experts=4, moe_top_k=2,
                             dtype="float32")
        model = Mixtral(mcfg)
        params = model.init(jax.random.key(5))
        prompts = [np.arange(20) % 500, (np.arange(7) + 41) % 500]
        base = {"dtype": "float32", "kv_block_size": 16,
                "max_batch_size": 2, "splitfuse_tokens": 16}

        groups.reset()
        single = InferenceEngineV2(model, params=params,
                                   config=dict(base))
        ref = single.generate_all(prompts, max_new_tokens=5)

        groups.reset()
        eng = InferenceEngineV2(model, params=params,
                                config=dict(base, expert_parallel=2))
        outs = eng.generate_all(prompts, max_new_tokens=5)
        for a, b in zip(ref, outs):
            np.testing.assert_array_equal(a, b)


@slow
class TestPerRequestSampling:
    def test_mixed_greedy_and_sampled_batch(self):
        """Greedy and sampled requests share one decode program; greedy
        rows must match the all-greedy reference exactly."""
        model = GPT2(CFG)
        params = model.init(jax.random.key(0))
        prompts = [np.arange(5) % 256, (np.arange(7) * 3) % 256]
        ref = _v1_greedy(model, params, [prompts[0]], 6)
        eng = InferenceEngineV2(model, params=params,
                                config={"dtype": "float32",
                                        "kv_block_size": 8,
                                        "prompt_bucket": 16,
                                        "max_batch_size": 2})
        u_greedy = eng.put(prompts[0], max_new_tokens=6)  # default greedy
        u_sampled = eng.put(prompts[1], max_new_tokens=6,
                            temperature=1.0, top_k=50)
        while eng.has_work:
            eng.step()
        out_g = eng.get(u_greedy)
        out_s = eng.get(u_sampled)
        np.testing.assert_array_equal(out_g, ref[0])
        assert out_s.shape == (6,)
        assert np.isfinite(out_s).all()

    def test_sampled_differs_across_requests(self):
        model = GPT2(CFG)
        params = model.init(jax.random.key(0))
        eng = InferenceEngineV2(model, params=params,
                                config={"dtype": "float32",
                                        "kv_block_size": 8,
                                        "prompt_bucket": 8,
                                        "max_batch_size": 4})
        prompt = np.arange(4) % 256
        uids = [eng.put(prompt, max_new_tokens=8, temperature=1.2,
                        top_k=0) for _ in range(3)]
        while eng.has_work:
            eng.step()
        outs = [eng.get(u).tolist() for u in uids]
        # independent rng per step + per slot: all three identical would
        # mean per-slot sampling is broken
        assert len({tuple(o) for o in outs}) > 1, outs


@slow
class TestSplitFuse:
    """Dynamic SplitFuse (reference blogs/deepspeed-fastgen §3B): prompts
    stream through fixed-size chunk programs fused with running decodes
    — same outputs as the bucketed-prefill engine, no head-of-line
    blocking, one compiled program for every prompt length."""

    def _engines(self, chunk=16, **kw):
        model = GPT2(CFG)
        params = model.init(jax.random.key(0))
        groups.reset()
        legacy = InferenceEngineV2(
            model, params=params,
            config=dict({"dtype": "float32", "kv_block_size": 8,
                         "prompt_bucket": 16, "max_batch_size": 4}, **kw))
        groups.reset()
        sf = InferenceEngineV2(
            model, params=params,
            config=dict({"dtype": "float32", "kv_block_size": 8,
                         "prompt_bucket": 16, "max_batch_size": 4,
                         "splitfuse_tokens": chunk}, **kw))
        return legacy, sf

    def test_chunked_matches_bucketed_greedy(self):
        # prompts spanning <1 chunk, exactly 1 chunk, and several chunks
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 256, (n,)).astype(np.int32)
                   for n in (5, 16, 37, 50)]
        legacy, sf = self._engines(chunk=16)
        want = legacy.generate_all(prompts, max_new_tokens=6)
        got = sf.generate_all(prompts, max_new_tokens=6)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(g, w)

    def test_no_head_of_line_blocking(self):
        """A running decode keeps producing tokens at every scheduler
        step WHILE a long prompt chunk-prefills (the legacy engine
        stalls decodes for the whole bucketed prefill)."""
        legacy, sf = self._engines(chunk=16)
        rng = np.random.RandomState(1)
        a = sf.put(rng.randint(0, 256, (6,)), max_new_tokens=24)
        sf.step()                      # admit + first chunk finishes A's
        while not np.asarray(sf.get(a)).size:
            sf.step()                  # A now decoding
        long_prompt = rng.randint(0, 256, (64,))   # 4 chunks of 16
        b = sf.put(long_prompt, max_new_tokens=4)

        def b_prefilling():
            return (any(r.uid == b for r in sf._pending)
                    or b in sf._prefill_q)

        a_tokens_during_prefill = 0
        chunk_steps = 0
        while b_prefilling():
            out = sf.step()
            chunk_steps += 1
            a_tokens_during_prefill += sum(1 for uid, _ in out if uid == a)
        assert chunk_steps >= 4        # the prompt really streamed
        # A produced decode tokens during EVERY chunk dispatch
        assert a_tokens_during_prefill >= chunk_steps

    def test_splitfuse_single_program(self):
        """All prompt lengths share ONE fused compilation (the legacy
        path compiles one prefill per bucket)."""
        _, sf = self._engines(chunk=16)
        rng = np.random.RandomState(2)
        sf.generate_all([rng.randint(0, 256, (n,))
                         for n in (3, 20, 40)], max_new_tokens=2)
        fused = sf._splitfuse_jit
        assert fused is not None
        # every dispatch reused the same traced program: one compiled
        # signature despite three different prompt lengths
        if callable(getattr(fused, "_cache_size", None)):
            assert fused._cache_size() == 1
        # and the legacy bucketed prefill never ran
        assert sf._prefill_jit is None

    def test_splitfuse_sampled_requests(self):
        # temperature>0 paths through the fused program still work and
        # respect per-request sampling state
        legacy, sf = self._engines(chunk=16)
        rng = np.random.RandomState(3)
        p = rng.randint(0, 256, (20,)).astype(np.int32)
        uid = sf.put(p, max_new_tokens=5, temperature=0.8)
        while not sf.is_done(uid):
            sf.step()
        toks = sf.get(uid)
        assert toks.shape == (5,)
        assert (toks >= 0).all() and (toks < 256).all()


# ---------------------------------------------------------------------------
# The chained plain decode dispatch (ISSUE 35): dispatch k+1 is enqueued
# before the host reads dispatch k, with k's last tokens fed device to
# device. Tier-1. The reference throughout is the model's own forward over
# the whole sequence so far, token by token (``_greedy``): no cache, no
# paging, no engine.
# ---------------------------------------------------------------------------

STEPS = 4            # decode steps a dispatch
ROOM = 40            # prompt + max_new_tokens of every request below


@functools.lru_cache(maxsize=None)
def _family(name):
    """-> (model, params, engine sizes) of one tiny model family, float32.
    Every matrix but the embeddings is 4x what ``init`` gives: at std 0.02
    the tied embedding wins every step and greedy decoding repeats the
    prompt's last token for ever, which no wrong cache row could change."""
    import dataclasses
    from deepspeed_tpu.models import (MIXTRAL_TINY, Mixtral, PHI4FLASH_TINY,
                                      Phi4Flash)
    if name == "gpt2":
        model, bs, bucket = GPT2(CFG), 8, 16
    elif name == "mixtral":     # llama's paths, and the expert counters
        model, bs, bucket = Mixtral(dataclasses.replace(
            MIXTRAL_TINY, d_model=64, dtype="float32")), 8, 16
    else:                       # RING / STATE caches by slot
        model, bs, bucket = Phi4Flash(dataclasses.replace(
            PHI4FLASH_TINY, dtype="float32")), 4, 8
    sizes = dict(dtype="float32", kv_block_size=bs, prompt_bucket=bucket,
                 max_batch_size=2, decode_steps_per_dispatch=STEPS,
                 # two sequences' worth and the scratch block: a third
                 # request can only have the blocks one of them gave back
                 num_kv_blocks=1 + 2 * -(-ROOM // bs), prefix_cache=False)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 4.0 if x.ndim >= 2 and not any(
            k in jax.tree_util.keystr(path) for k in ("wte", "wpe", "embed"))
        else x, model.init(jax.random.key(0)))
    return model, params, sizes


_ENGINES = {}


def _engine(name):
    """The engine of a family, built once: every test leaves it empty, so
    it stays reusable."""
    if name not in _ENGINES:
        model, params, sizes = _family(name)
        groups.reset()
        _ENGINES[name] = InferenceEngineV2(model, params=params,
                                           config=sizes)
    return _ENGINES[name]


@functools.lru_cache(maxsize=None)
def _forward(name):
    model = _family(name)[0]
    return jax.jit(lambda params, ids: model.apply(params, ids))


def _greedy(name, prompt, n):
    """``n`` greedy tokens after ``prompt`` by the model's plain forward
    over the whole sequence, once a token (padded to a multiple of ROOM:
    causal)."""
    params = _family(name)[1]
    ids = np.zeros((1, ROOM * -(-(len(prompt) + n) // ROOM)), np.int32)
    ids[0, :len(prompt)] = prompt
    for at in range(len(prompt), len(prompt) + n):
        logits = _forward(name)(params, ids)
        ids[0, at] = int(np.argmax(np.asarray(logits)[0, at - 1]))
    return ids[0, len(prompt):len(prompt) + n].copy()


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(1, 250, size=n).astype(
        np.int32)


def _drive(eng, arrivals, hook=None):
    """``arrivals``: [(step it is put before, prompt, max_new, eos)]. Steps
    until nothing is left -> (tokens by request, the tokens step() returned
    by request), in the order of ``arrivals``."""
    todo = sorted(enumerate(arrivals), key=lambda a: a[1][0])
    uids, pairs, i = {}, {}, 0
    while todo or eng.has_work:
        while todo and todo[0][1][0] <= i:
            k, (_, prompt, max_new, eos) = todo.pop(0)
            uids[k] = eng.put(prompt, max_new_tokens=max_new,
                              eos_token_id=eos)
        for uid, tok in eng.step():
            pairs.setdefault(uid, []).append(tok)
        if hook is not None:
            hook(eng, uids)
        i += 1
        assert i < 400, "never drained"
    got = [eng.get(uids[k]) for k in range(len(arrivals))]
    return got, [pairs.get(uids[k], []) for k in range(len(arrivals))]


def _assert_empty(eng):
    mgr = eng.state_mgr
    assert not eng.has_work and eng._unread is None and not eng._settled
    assert mgr.allocator.free_blocks == mgr.allocator.total_blocks
    assert mgr.free_slots == mgr.max_batch and not mgr._seqs


def _with_mid_dispatch_eos(name, seeds, max_new=24):
    """A prompt whose greedy output first shows some token in the middle
    of a decode dispatch, past the first dispatch -> (prompt, eos, tokens
    up to and with it)."""
    for prompt in (_prompt(s, 6 + s % 7) for s in seeds):
        toks = _greedy(name, prompt, max_new).tolist()
        for j in range(STEPS + 1, len(toks) - STEPS):
            # generated[0] is the prefill's; dispatch d holds
            # generated[1 + d*STEPS : 1 + (d+1)*STEPS]
            if (j - 1) % STEPS < STEPS - 1 and toks.index(toks[j]) == j:
                return prompt, toks[j], toks[:j + 1]
    raise AssertionError("no prompt of these ends mid-dispatch")


FAMILY_NAMES = ("gpt2", "mixtral", "phi4flash")


class TestChainedDecode:
    @pytest.mark.parametrize("name", FAMILY_NAMES)
    def test_greedy_equals_the_models_forward_with_admissions_mid_run(
            self, name):
        eng = _engine(name)
        arrivals = [(0, _prompt(1, 7), 21, -1), (0, _prompt(2, 12), 9, -1),
                    (2, _prompt(3, 5), 14, -1), (3, _prompt(4, 9), 24, -1),
                    (7, _prompt(5, 11), 6, -1)]
        want = [_greedy(name, a[1], a[2]) for a in arrivals]
        tel = eng.telemetry
        calls0, chained0 = tel._plain_dispatches, tel._chained_dispatches
        got, pairs = _drive(eng, arrivals)
        for g, w, pr, a in zip(got, want, pairs, arrivals):
            np.testing.assert_array_equal(g, w)
            assert len(g) == a[2]
            # step() handed out every token but the prefill's, once
            assert pr == g[1:].tolist()
        _assert_empty(eng)
        assert (tel._chained_dispatches - chained0) * 2 \
            > tel._plain_dispatches - calls0
        assert 0 < eng.telemetry_snapshot()["decode_chain_share"] < 1
        # one decode program: the first call's zeros and a chained call's
        # device tokens share an executable
        assert eng._get_decode()._cache_size() == 1
        if name != "phi4flash":     # the v1 engine serves no slot state
            model, params, _ = _family(name)
            ref = _v1_greedy(model, params, [arrivals[3][1]], 24)
            np.testing.assert_array_equal(got[3], ref[0])

    @pytest.mark.parametrize("name", FAMILY_NAMES)
    def test_budget_stop_and_eos_stop_mid_dispatch(self, name):
        eng = _engine(name)
        tel = eng.telemetry
        # a budget is host arithmetic: 1 + 2 * STEPS + 2 tokens end in the
        # middle of the third dispatch, which is the last one enqueued
        late0, calls0 = tel._late_steps, tel._plain_dispatches
        prompt = _prompt(11, 8)
        want = _greedy(name, prompt, 3 + 2 * STEPS)
        got, pairs = _drive(eng, [(0, prompt, 3 + 2 * STEPS, -1)])
        np.testing.assert_array_equal(got[0], want)
        assert tel._late_steps == late0
        assert tel._plain_dispatches - calls0 == 3
        # an EOS is seen when its dispatch is read: one more dispatch ran
        # for the sequence, and none of its tokens surfaces
        prompt, eos, want = _with_mid_dispatch_eos(name, range(20, 28))
        other = _prompt(12, 9)
        want_other = _greedy(name, other, 30)
        got, pairs = _drive(eng, [(0, prompt, 24, eos),
                                  (0, other, 30, -1)])
        assert got[0].tolist() == want and pairs[0] == want[1:]
        assert want[-1] == eos and eos not in want[:-1]
        np.testing.assert_array_equal(got[1], want_other)
        assert tel._late_steps - late0 == STEPS
        assert 0 < eng.telemetry_snapshot()["late_stop_share"] < 0.5
        _assert_empty(eng)

    @pytest.mark.parametrize("name", FAMILY_NAMES)
    def test_last_live_sequence_ends_by_eos_under_an_unread_dispatch(
            self, name):
        """The EOS of the ONLY live sequence is found while the dispatch
        behind it, which carries it too, is enqueued and unread: nothing
        is active any more, and that dispatch is still read."""
        from deepspeed_tpu.inference.v2 import Router
        eng = _engine(name)
        tel = eng.telemetry
        prompt, eos, want = _with_mid_dispatch_eos(name, range(20, 28))
        late0 = tel._late_steps
        # stepped by hand (first: were that dispatch stranded, the loops
        # below would never end): the step that reads the EOS leaves the
        # late dispatch unread, and the next has nothing to run but that
        uid = eng.put(prompt, max_new_tokens=24, eos_token_id=eos)
        pairs = []
        while not eng.is_done(uid):
            pairs += eng.step()
        assert eng._unread is not None and eng.state_mgr.n_active == 0
        assert eng.has_work and eng.step() == [] and not eng.has_work
        assert [t for _, t in pairs] == want[1:]
        assert eng.get(uid).tolist() == want
        assert tel._late_steps - late0 == STEPS
        _assert_empty(eng)
        got = eng.generate_all([prompt], max_new_tokens=24,
                               eos_token_id=eos)
        assert got[0].tolist() == want
        _assert_empty(eng)
        # behind a router, and a drain of the replica comes to its end
        router = Router([eng])
        uid = router.put(prompt, max_new_tokens=24, eos_token_id=eos)
        router.step()
        router.drain(router.replicas[0])
        rounds = 0
        while router.has_work:
            router.step()
            rounds += 1
            assert rounds < 100, "never drained"
        assert router.get(uid).tolist() == want
        assert router.replicas[0].dead and router.replicas[0].drained
        _assert_empty(eng)

    @pytest.mark.parametrize("name", FAMILY_NAMES)
    def test_slot_and_blocks_change_hands_under_an_unread_dispatch(
            self, name, monkeypatch):
        """A ends by EOS; C gets A's slot and A's blocks while the dispatch
        that still carries A is unread. Neither gets the other's tokens."""
        eng = _engine(name)
        a_prompt, eos, a_want = _with_mid_dispatch_eos(name, range(20, 28))
        b_prompt, c_prompt = _prompt(31, 10), _prompt(32, 7)
        b_want, c_want = _greedy(name, b_prompt, 28), \
            _greedy(name, c_prompt, 13)
        seen = []
        real = eng._post_decode_tokens

        def watching(batch, toks):
            mgr = eng.state_mgr
            seen.append([(q.uid, mgr._slots[slot], list(mgr._seqs[
                mgr._slots[slot]].blocks) if mgr._slots[slot] is not None
                else None) for slot, q in enumerate(batch.seqs)
                if q is not None])
            return real(batch, toks)

        monkeypatch.setattr(eng, "_post_decode_tokens", watching)
        blocks = {}

        def hook(eng, uids):
            for k, uid in uids.items():
                seq = eng.state_mgr._seqs.get(uid)
                if seq is not None and seq.blocks:
                    blocks[k] = set(seq.blocks)

        got, pairs = _drive(eng, [(0, a_prompt, 24, eos),
                                  (0, b_prompt, 28, -1),
                                  (0, c_prompt, 13, -1)], hook)
        assert got[0].tolist() == a_want and pairs[0] == a_want[1:]
        np.testing.assert_array_equal(got[1], b_want)
        np.testing.assert_array_equal(got[2], c_want)
        assert pairs[2] == c_want[1:].tolist()
        # it happened: a dispatch built over A was posted while C held A's
        # slot, and C holds blocks A had
        a_uid = min(u for rec in seen for u, _, _ in rec)
        handed = [(u, now) for rec in seen for u, now, _ in rec
                  if u == a_uid and now not in (None, a_uid)]
        assert handed, seen
        assert blocks[0] & blocks[2]
        _assert_empty(eng)

    @pytest.mark.parametrize("name", FAMILY_NAMES)
    def test_has_work_generate_all_and_router_leave_no_token(self, name):
        from deepspeed_tpu.inference.v2 import Router
        eng = _engine(name)
        prompts = [_prompt(41, 6), _prompt(42, 13), _prompt(43, 9)]
        want = [_greedy(name, p, 11) for p in prompts]
        # a step that enqueues the first dispatch returns nothing, and the
        # engine says it has work until that dispatch is read
        uid = eng.put(prompts[0], max_new_tokens=1 + STEPS)
        assert eng.step() == [] and eng._unread is not None
        assert eng.state_mgr.n_active == 1 and eng.has_work
        out = eng.step()                      # nothing to enqueue: settles
        assert [t for _, t in out] == want[0][1:1 + STEPS].tolist()
        assert eng.is_done(uid) and not eng.has_work
        np.testing.assert_array_equal(eng.get(uid), want[0][:1 + STEPS])
        for g, w in zip(eng.generate_all(prompts, 11), want):
            np.testing.assert_array_equal(g, w)
        _assert_empty(eng)
        router = Router([eng])
        uids = [router.put(p, max_new_tokens=11) for p in prompts]
        pairs = {}
        while router.has_work:
            for u, tok in router.step():
                pairs.setdefault(u, []).append(tok)
        for u, w in zip(uids, want):
            assert pairs[u] == w[1:].tolist()
            np.testing.assert_array_equal(router.get(u), w)
        _assert_empty(eng)


def _decoding_unread(eng, *requests):
    """Put ``requests`` ((prompt, max_new) each) and step until a decode
    dispatch that carries them all is enqueued and unread -> uids, the
    pairs returned so far."""
    uids = [eng.put(p, max_new_tokens=n) for p, n in requests]
    pairs = []
    for _ in range(8):
        pairs += eng.step()
        if eng._unread is not None and all(
                any(q is not None and q.uid == u
                    for q in eng._unread[0].seqs) for u in uids):
            return uids, pairs
    raise AssertionError("no unread decode dispatch")


def _finish(eng, pairs):
    while eng.has_work:
        pairs += eng.step()
    return pairs


def _by_uid(pairs, uid):
    return [t for u, t in pairs if u == uid]


class TestSettleBeforeAnythingElse:
    """What is not the next plain decode reads the unread dispatch first."""

    def _want(self, *requests):
        return [_greedy("gpt2", p, n) for p, n in requests]

    def test_cancel(self):
        eng = _engine("gpt2")
        reqs = [(_prompt(51, 8), 25), (_prompt(52, 11), 25)]
        want = self._want(*reqs)
        (a, b), pairs = _decoding_unread(eng, *reqs)
        assert eng.cancel(a) is True
        assert eng._unread is None            # read, and posted to b
        assert not _by_uid(eng._settled, a) and _by_uid(eng._settled, b)
        n_a = len(_by_uid(pairs, a))
        pairs = _finish(eng, pairs)
        assert len(_by_uid(pairs, a)) == n_a  # nothing of a after cancel
        assert _by_uid(pairs, b) == want[1][1:].tolist()
        np.testing.assert_array_equal(eng.get(b), want[1])
        with pytest.raises(KeyError):
            eng.get(a)
        _assert_empty(eng)

    def test_hold_decode(self):
        eng = _engine("gpt2")
        reqs = [(_prompt(53, 8), 19), (_prompt(54, 6), 19)]
        want = self._want(*reqs)
        (a, b), pairs = _decoding_unread(eng, *reqs)
        eng.hold_decode(a)
        assert eng._unread is None
        held = len(eng.get(a, flush=False))
        for _ in range(3):                    # b alone, chained again
            pairs += eng.step()
        assert len(eng.get(a, flush=False)) == held
        assert len(eng.get(b, flush=False)) > held
        eng.release_decode_hold(a)            # a comes back from the host
        pairs = _finish(eng, pairs)
        for uid, w in zip((a, b), want):
            assert _by_uid(pairs, uid) == w[1:].tolist()
            np.testing.assert_array_equal(eng.get(uid), w)
        _assert_empty(eng)

    def test_split_fuse_prompt(self):
        model, params, sizes = _family("gpt2")
        groups.reset()
        eng = InferenceEngineV2(model, params=params,
                                config=dict(sizes, splitfuse_tokens=16))
        reqs = [(_prompt(55, 9), 22), (_prompt(56, 27), 13)]
        want = self._want(*reqs)
        (a,), pairs = _decoding_unread(eng, reqs[0])
        b = eng.put(reqs[1][0], max_new_tokens=reqs[1][1])
        kinds = []
        real = eng._dispatch_span
        eng._dispatch_span = lambda kind, *x, **kw: (
            kinds.append((kind, kw.get("chained", 0))), real(kind, *x, **kw)
        )[1]
        pairs += eng.step()                   # settles, then a fused chunk
        assert kinds == [("fused", 0)] and eng._unread is None
        pairs = _finish(eng, pairs)
        assert ("decode", 1) in kinds         # and chains again after it
        for uid, w in zip((a, b), want):
            assert _by_uid(pairs, uid) == w[1:].tolist()
            np.testing.assert_array_equal(eng.get(uid), w)
        _assert_empty(eng)

    def test_speculative_round_never_leaves_one_unread(self):
        model, params, sizes = _family("gpt2")
        draft = GPT2(GPT2Config(n_layer=1, n_head=2, d_model=32,
                                max_seq_len=128, vocab_size=256,
                                remat=False, dtype="float32"))
        groups.reset()
        eng = InferenceEngineV2(
            model, params=params, draft_model=draft,
            draft_params=draft.init(jax.random.key(1)),
            config=dict(sizes, splitfuse_tokens=16, spec_draft=True,
                        spec_k=4))
        # the second is sampled: it rides the plain set's dispatch, which
        # is read at once beside the speculative round
        reqs = [(_prompt(57, 9), 18), (_prompt(58, 7), 18)]
        want = self._want(reqs[0])
        a = eng.put(*reqs[0])
        b = eng.put(reqs[1][0], max_new_tokens=18, temperature=0.9, top_k=8)
        pairs = []
        while eng.has_work:
            pairs += eng.step()
            assert eng._unread is None
        assert _by_uid(pairs, a) == want[0][1:].tolist()
        assert len(_by_uid(pairs, b)) == 17
        assert eng.telemetry.spec_rounds > 0
        assert eng.telemetry._chained_dispatches == 0
        eng.get(a), eng.get(b)
        _assert_empty(eng)

    def test_kv_handoff_in_and_out(self):
        from deepspeed_tpu.inference.v2 import kv_transfer
        model, params, sizes = _family("gpt2")
        groups.reset()
        src = InferenceEngineV2(model, params=params, config=sizes)
        dst = _engine("gpt2")
        reqs = [(_prompt(59, 8), 17), (_prompt(60, 12), 17)]
        want = self._want(*reqs)
        # out: a is parked after its prefill, b decodes chained beside it
        a = src.put(*reqs[0])
        src.hold_decode(a)
        (b,), src_pairs = _decoding_unread(src, reqs[1])
        payload = kv_transfer.export_sequence(src, a)
        assert src._unread is None
        src.release_handoff(a)
        # in: x decodes chained on the other engine when a arrives
        (x,), pairs = _decoding_unread(dst, reqs[1])
        assert kv_transfer.import_sequence(dst, payload) == a
        assert dst._unread is None
        pairs = _finish(dst, pairs)
        assert _by_uid(pairs, a) == want[0][1:].tolist()
        assert _by_uid(pairs, x) == want[1][1:].tolist()
        np.testing.assert_array_equal(dst.get(a), want[0])
        np.testing.assert_array_equal(dst.get(x), want[1])
        assert _by_uid(_finish(src, src_pairs), b) == want[1][1:].tolist()
        src.get(b)
        _assert_empty(src)
        _assert_empty(dst)


class TestChainedHazards:
    def test_prefix_cache_never_holds_what_a_late_dispatch_writes(self):
        """A ends by EOS under a prefix cache: the tree takes prompt +
        generated[:-1] while the dispatch behind still writes A's tail.
        A request that hits that prefix reads what A's own steps wrote."""
        model, params, sizes = _family("gpt2")
        a_prompt, eos, a_want = _with_mid_dispatch_eos("gpt2", range(20, 28))
        groups.reset()
        eng = InferenceEngineV2(model, params=params, config=dict(
            sizes, prefix_cache=True, prefix_cache_min_match=1,
            num_kv_blocks=33))
        got, _ = _drive(eng, [(0, a_prompt, 24, eos),
                              (0, _prompt(61, 9), 26, -1)])
        assert got[0].tolist() == a_want
        assert eng.telemetry._late_steps == STEPS
        # everything A registered, and two tokens of its own after
        c_prompt = np.concatenate([a_prompt, a_want[:-1], [7, 9]]).astype(
            np.int32)
        assert eng.prefix_cache.match(c_prompt).cached_len \
            >= (len(c_prompt) - 2) // 8 * 8 > 0
        want = _greedy("gpt2", c_prompt, 10)
        got = eng.generate_all([c_prompt], 10)[0]
        np.testing.assert_array_equal(got, want)
        assert eng.prefix_cache.hits >= 1

    def test_sampled_output_is_deterministic_for_a_seed(self):
        model, params, sizes = _family("gpt2")
        runs = []
        for _ in range(2):
            groups.reset()
            eng = InferenceEngineV2(model, params=params,
                                    config=dict(sizes, seed=5))
            uids = [eng.put(_prompt(70 + i, 8), max_new_tokens=14,
                            temperature=0.8 if i else 0.0, top_k=16)
                    for i in range(3)]
            while eng.has_work:
                eng.step()
            assert eng.telemetry._chained_dispatches > 0
            runs.append([eng.get(u).tolist() for u in uids])
        assert runs[0] == runs[1]
        # the greedy one beside them is the model's own
        want = _greedy("gpt2", _prompt(70, 8), 14)
        assert runs[0][0] == want.tolist()
        assert len({tuple(r) for r in runs[0]}) == 3


# ---------------------------------------------------------------------------
# The decode steps of a FUSED dispatch (ISSUE 45): the engine's own count
# (``engine_v2._FUSED_STEPS``), not the plain decode's. Tier-1; the
# reference is ``_greedy`` as above.
# ---------------------------------------------------------------------------

def _chunked_engine(slots):
    model, params, sizes = _family("gpt2")
    groups.reset()
    return InferenceEngineV2(model, params=params, config=dict(
        sizes, splitfuse_tokens=16, max_batch_size=slots, num_kv_blocks=64))


class TestFusedStepCount:
    @pytest.mark.parametrize("count", [1, 2, 4, 8])
    def test_streams_are_the_models_forward_whatever_the_count(
            self, monkeypatch, count):
        """Prompts of one to four chunks arrive while others decode: no
        token depends on the decode steps a fused dispatch carries (the
        counts the engine's own was timed against, the eight every fused
        dispatch once took from the config among them). The first
        request's budget ends inside a fused dispatch at every count but
        1: it is retired once and the dispatch's later tokens for it are
        dropped."""
        assert engine_v2._FUSED_STEPS in (1, 2, 4, 8)
        monkeypatch.setattr(engine_v2, "_FUSED_STEPS", count)
        eng = _chunked_engine(4)
        arrivals = [(0, _prompt(81, 7), 4, -1), (0, _prompt(82, 60), 12, -1),
                    (1, _prompt(83, 9), 30, -1), (2, _prompt(84, 37), 9, -1),
                    (4, _prompt(85, 20), 17, -1), (9, _prompt(86, 33), 5, -1)]
        want = [_greedy("gpt2", a[1], a[2]) for a in arrivals]
        said, real_span = [], eng._dispatch_span

        def noting(kind, active, steps, *a, **kw):
            said.append((kind, steps))
            return real_span(kind, active, steps, *a, **kw)

        eng._dispatch_span = noting
        retired, real_retire = [], eng.state_mgr.retire
        eng.state_mgr.retire = lambda uid: (retired.append(uid),
                                            real_retire(uid))[1]
        got, pairs = _drive(eng, arrivals)
        for g, w, pr in zip(got, want, pairs):
            np.testing.assert_array_equal(g, w)
            assert pr == g[1:].tolist()
        assert len(retired) == len(set(retired)) == len(arrivals)
        _assert_empty(eng)
        # the fused dispatches ran the count, the plain ones the config's
        assert {steps for kind, steps in said if kind == "fused"} == {count}
        assert {steps for kind, steps in said if kind == "decode"} \
            == {STEPS}
        assert eng.telemetry_snapshot()["fused_dispatches"] \
            == sum(kind == "fused" for kind, _ in said) > 4
        # one fused program, whatever the prompt lengths and the live slots
        assert eng._get_splitfuse()._cache_size() == 1
