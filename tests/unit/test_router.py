"""Serving-fleet router (tier-1): admission control + typed load
shedding, deadline enforcement through the flush()/unref path, the
replica health state machine with chaos-tested failover (armed
replica_death mid-decode -> byte-identical replay on a survivor),
drained scale-down, prefix-affinity dispatch, the Serve/Router/* tag
emission, and the engine cancel() pool-accounting audit.

Engines here follow the test_prefix_cache.py fast pattern: tiny GPT2,
module-cached params, small pools — every test runs inside tier-1."""

import numpy as np
import pytest

import jax

from deepspeed_tpu.autotuning import kernel_dispatch
from deepspeed_tpu.inference.v2 import (DeadlineExceeded,
                                        InferenceEngineV2, Overloaded,
                                        Router, RouterConfig)
from deepspeed_tpu.inference.v2.replica import Replica
from deepspeed_tpu.models import GPT2, GPT2Config
from deepspeed_tpu.monitor.tag_schema import TAG_SCHEMA
from deepspeed_tpu.utils import fault_injection, groups


@pytest.fixture(autouse=True)
def _pristine_dispatch(tmp_path, monkeypatch):
    """Private winner cache + reset process-global dispatch state, and
    no armed faults leaking across tests."""
    monkeypatch.setenv("DSTPU_AUTOTUNE_CACHE",
                       str(tmp_path / "kernel_autotune.json"))
    monkeypatch.delenv("DSTPU_AUTOTUNE", raising=False)
    kernel_dispatch.reset()
    fault_injection.reset()
    yield
    fault_injection.reset()
    kernel_dispatch.reset()


_CFG = GPT2Config(n_layer=2, n_head=4, d_model=64, max_seq_len=128,
                  vocab_size=256, remat=False, dtype="float32")
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = GPT2(_CFG).init(jax.random.key(0))
    return _PARAMS


_BASE = {"dtype": "float32", "kv_block_size": 8, "prompt_bucket": 16,
         "max_batch_size": 2, "splitfuse_tokens": 16,
         "decode_steps_per_dispatch": 2,   # small unroll = fast compiles
         "prefix_cache_min_match": 1}


def _engine(**kw):
    groups.reset()
    return InferenceEngineV2(GPT2(_CFG), params=_params(),
                             config=dict(_BASE, **kw))


# Engine compiles dominate this file's runtime, so clean-completion
# tests share one module-cached pair (every request leaves through
# get()/typed exits, so the engines stay reusable; each test builds its
# OWN Router + Replica wrappers around them). Tests that poison an
# engine — kill/step-death strand sequences, telemetry-count asserts —
# build fresh ones.
_FLEET = None
_REF = None


def _fleet():
    global _FLEET
    if _FLEET is None:
        _FLEET = (_engine(prefix_cache=True), _engine(prefix_cache=True))
    return _FLEET


def _ref_outputs():
    """Uninterrupted single-replica reference for _prompts(1, 4) at
    max_new 8 (shared by the roundtrip + chaos byte-identity tests)."""
    global _REF
    if _REF is None:
        _REF = [_fleet()[0].generate_all([p], max_new_tokens=8)[0]
                for p in _prompts(1, 4)]
    return _REF


def _prompts(seed, n, lo=6, hi=20):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 255, size=rs.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def _run(router, max_rounds=400):
    rounds = 0
    while router.has_work:
        router.step()
        rounds += 1
        assert rounds < max_rounds, "router failed to drain"
    return rounds


def _pool_closed(eng):
    """The overload/deadline acceptance invariant: every block is back
    in the free list or adopted by the prefix tree — nothing leaked."""
    alloc = eng.state_mgr.allocator
    tree = eng.prefix_cache.tree_blocks if eng.prefix_cache else 0
    assert alloc.free_blocks + tree == alloc.total_blocks, (
        f"leaked blocks: free={alloc.free_blocks} tree={tree} "
        f"total={alloc.total_blocks}")


# ---------------------------------------------------------------------------
# config validation (the planner-lint construction-probe contract)
# ---------------------------------------------------------------------------

class TestRouterConfig:
    def test_auto_knobs_accept_auto_and_reject_junk(self):
        RouterConfig(router_queue_depth="auto", shed_policy="auto",
                     prefix_affinity="auto")
        for field in ("router_queue_depth", "shed_policy",
                      "prefix_affinity"):
            with pytest.raises(ValueError):
                RouterConfig(**{field: "___junk___"})

    def test_numeric_validation(self):
        with pytest.raises(ValueError):
            RouterConfig(router_queue_depth=0)
        with pytest.raises(ValueError):
            RouterConfig(breach_rounds=0)
        with pytest.raises(ValueError):
            RouterConfig(shed_low_pct=80, shed_high_pct=50)
        with pytest.raises(ValueError):
            RouterConfig(slo_ttft_ms=-1)

    def test_queue_depth_resolution(self):
        r = Router(list(_fleet()))
        # "auto" = 4x aggregate slots (2 replicas x max_batch 2)
        assert r.resolved_queue_depth() == 16
        r.replicas[1].mark_dead("test")
        assert r.resolved_queue_depth() == 8   # capacity-proportional
        r2 = Router([r.replicas[0]], router_queue_depth=5)
        assert r2.resolved_queue_depth() == 5


# ---------------------------------------------------------------------------
# basics: multi-replica roundtrip, byte-identity, prefix affinity
# ---------------------------------------------------------------------------

class TestRouterBasics:
    def test_roundtrip_matches_single_engine(self):
        prompts = _prompts(1, 4)
        want = _ref_outputs()
        router = Router(list(_fleet()))
        uids = [router.put(p, max_new_tokens=8) for p in prompts]
        _run(router)
        for uid, w in zip(uids, want):
            assert router.is_done(uid)
            np.testing.assert_array_equal(router.get(uid), w)
        snap = router.snapshot()
        assert snap["admitted"] == snap["completed"] == 4
        assert snap["shed"] == snap["expired"] == 0
        assert snap["failovers"] == snap["replayed"] == 0
        # work actually spread over the fleet
        assert all(r.steps > 0 for r in router.replicas)
        for rep in router.replicas:
            _pool_closed(rep.engine)

    def test_prefix_affinity_routes_to_the_cached_replica(self):
        # shared fleet is safe here: earlier tests cached only random
        # prompts, which cannot share a full 8-token block with the
        # arange template, so the affinity signal is unambiguous
        router = Router(list(_fleet()))
        template = np.arange(1, 33, dtype=np.int32)   # 4 full blocks
        uid = router.put(template, max_new_tokens=4)
        _run(router)
        home = router._reqs[uid].replica
        router.get(uid)
        assert home is not None
        # the shared-prefix follow-ups all land on the template's home
        for i in range(3):
            ext = np.concatenate(
                [template, np.asarray([100 + i], np.int32)])
            u2 = router.put(ext, max_new_tokens=4)
            router.step()              # dispatch boundary
            assert router._reqs[u2].replica == home, \
                "affinity ignored the radix-tree match"
            _run(router)
            router.get(u2)


# ---------------------------------------------------------------------------
# chaos acceptance: replica death mid-decode, drain, step-failure health
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestChaosFailover:
    def test_replica_death_mid_decode_replays_byte_identical(self):
        """The ISSUE-17 chaos acceptance test: armed ``replica_death``
        kills one of two replicas mid-decode; every in-flight request
        completes on the survivor, greedy outputs byte-identical to an
        uninterrupted single-replica run, counters match, zero drops."""
        prompts = _prompts(1, 4)
        want = _ref_outputs()
        # fresh engines: the victim's engine keeps stranded sequences
        # after the kill, so the shared fleet must not be used here
        router = Router([_engine(prefix_cache=True),
                         _engine(prefix_cache=True)])
        uids = [router.put(p, max_new_tokens=8) for p in prompts]
        for _ in range(3):              # get decodes genuinely mid-flight
            router.step()
        victim = next(r for r in router.replicas if r.has_work)
        n_inflight = len(victim.inflight)
        assert n_inflight > 0, "nothing in flight before the kill"
        fault_injection.arm("replica_death", fails=1)
        _run(router)
        snap = router.snapshot()
        assert snap["failovers"] == 1
        assert snap["replayed"] == n_inflight
        assert snap["completed"] == 4            # zero dropped requests
        assert snap["replicas"][victim.name] == "dead"
        assert not victim.drained                # died, not drained
        survivors = [r for r in router.replicas if not r.dead]
        assert len(survivors) == 1 and survivors[0].live
        for uid, w in zip(uids, want):
            np.testing.assert_array_equal(router.get(uid), w)
        _pool_closed(survivors[0].engine)

    def test_drain_finishes_inflight_without_replay(self):
        """The drain() variant of the acceptance test: scale-down
        finishes in-flight work (no replay) and removes the replica;
        new work lands on the survivor."""
        prompts = _prompts(2, 4)
        router = Router(list(_fleet()))
        uids = [router.put(p, max_new_tokens=6) for p in prompts]
        router.step()
        router.drain("r0")
        assert router.snapshot()["draining"] == 1
        _run(router)
        snap = router.snapshot()
        assert snap["completed"] == 4
        assert snap["failovers"] == 0 and snap["replayed"] == 0
        assert snap["replicas"]["r0"] == "dead"
        assert router.replicas[0].drained        # clean exit, not death
        u_new = router.put(prompts[0], max_new_tokens=4)
        _run(router)
        assert len(router.get(u_new)) == 4
        assert router._reqs.get(u_new) is None   # flushed by get
        assert router.snapshot()["replicas"]["r1"] == "live"

    def test_step_failures_break_the_heartbeat_then_fail_over(self):
        """Retryable ``serve_step`` faults are absorbed below the
        health threshold; max_step_failures CONSECUTIVE failures mean
        no recent step progress — the replica dies and the router
        replays on the survivor."""
        router = Router([_engine(), _engine()], max_step_failures=3)
        uid = router.put(_prompts(3, 1)[0], max_new_tokens=6)
        fault_injection.arm("serve_step", fails=2)   # absorbed: 2 < 3
        _run(router)
        assert router.replicas[0].live
        assert router.replicas[0].step_failures == 2
        assert len(router.get(uid)) == 6
        assert router.snapshot()["failovers"] == 0

        uid2 = router.put(_prompts(4, 1)[0], max_new_tokens=6)
        fault_injection.arm("serve_step", fails=3)   # breaks heartbeat
        _run(router)
        snap = router.snapshot()
        assert snap["failovers"] == 1 and snap["replayed"] == 1
        # exactly one replica broke its heartbeat; the other served the
        # replay (which one depends on the round-robin cursor)
        assert sum(r.dead for r in router.replicas) == 1
        assert sum(r.live for r in router.replicas) == 1
        assert len(router.get(uid2)) == 6

    def test_dispatch_fault_requeues_and_retries(self):
        """Retryable ``serve_dispatch``: an injected dispatch failure
        leaves no partial state — the request re-queues at the front
        and lands cleanly next round."""
        router = Router([_fleet()[0]])
        fault_injection.arm("serve_dispatch", fails=1)
        uid = router.put(_prompts(5, 1)[0], max_new_tokens=4)
        router.step()                                # dispatch fails
        assert router._reqs[uid].state == "queued"
        assert router.snapshot()["dispatch_retries"] == 1
        _run(router)
        assert len(router.get(uid)) == 4
        assert router.snapshot()["failovers"] == 0

    def test_all_replicas_dead_fails_loudly(self):
        router = Router([_engine()])
        router.put(_prompts(6, 1)[0], max_new_tokens=4)
        fault_injection.arm("replica_death", fails=1)
        with pytest.raises(RuntimeError, match="no live replicas"):
            _run(router)


# ---------------------------------------------------------------------------
# overload acceptance: admission bound, watermark shedding, advisory point
# ---------------------------------------------------------------------------

class TestRouterOverload:
    def test_admission_and_shedding_protect_the_admitted_class(self):
        """The ISSUE-17 overload acceptance test: traffic past capacity
        -> the queue bound rejects at put() and the watermark sheds the
        lowest class with typed Overloaded rejections, the admitted
        class completes with p99 TPOT within noise of the uncontended
        baseline, and the pool accounting closes."""
        eng = _fleet()[0]
        router = Router([eng], router_queue_depth=8, breach_rounds=1,
                        shed_high_pct=75, shed_low_pct=50)
        # warm + uncontended baseline (class 0): compiles amortized
        base_uids = [router.put(p, max_new_tokens=6)
                     for p in _prompts(7, 4)]
        _run(router)
        for uid in base_uids:
            router.get(uid)
        baseline = router.snapshot()["classes"][0]["tpot_ms_p99"]
        assert baseline is not None

        # overload: class 1 (admitted) + class 2 (sheddable) past the
        # high watermark, then one past the hard bound
        keep = [router.put(p, max_new_tokens=6, klass=1)
                for p in _prompts(8, 4)]
        low = [router.put(p, max_new_tokens=6, klass=2)
               for p in _prompts(9, 4)]
        with pytest.raises(Overloaded) as exc:
            router.put(_prompts(10, 1)[0], max_new_tokens=6, klass=2)
        assert exc.value.klass == 2 and exc.value.queue_depth == 8
        _run(router)
        snap = router.snapshot()
        # queue was 8 >= 75% watermark: shed down to 4 — all of class 2
        # (4 watermark sheds + the 1 admission rejection above = 5)
        assert snap["classes"][2]["shed"] == 5
        assert snap["classes"][2]["completed"] == 0
        for uid in low:
            with pytest.raises(Overloaded) as err:
                router.get(uid)
            assert err.value.klass == 2          # typed, never a success
        # the admitted class rode through untouched
        assert snap["classes"][1]["completed"] == 4
        assert snap["classes"][1]["shed"] == 0
        for uid in keep:
            assert len(router.get(uid)) == 6
        admitted = snap["classes"][1]["tpot_ms_p99"]
        assert admitted is not None
        # within noise of uncontended (generous CI bound: the shed
        # class never dispatched, so the admitted class saw an idle
        # engine)
        assert admitted <= max(10 * baseline, baseline + 500), \
            f"admitted-class p99 TPOT {admitted} vs baseline {baseline}"
        assert snap["replicas"]["r0"] == "live"
        # no leaked blocks: shed requests never touched the engine
        _pool_closed(eng)

    @pytest.mark.chaos
    def test_router_overload_point_is_advisory(self):
        """The blast-radius contract for the serving plane, enforced
        behaviorally (the lint's exact-list advisory drive covers the
        checkpoint points): fault_injection.arm("router_overload") with
        an unlimited budget forces overload rounds on EVERY step —
        nothing may raise, no replica may die, and admitted work below
        the low watermark completes untouched."""
        router = Router([_fleet()[0]])
        fault_injection.arm("router_overload", fails=10_000)
        uids = [router.put(p, max_new_tokens=4) for p in _prompts(11, 3)]
        _run(router)
        assert fault_injection.injector.hits("router_overload") > 0
        snap = router.snapshot()
        assert snap["completed"] == 3 and snap["shed"] == 0
        assert all(s == "live" for s in snap["replicas"].values())
        for uid in uids:
            assert len(router.get(uid)) == 4

    def test_shed_policy_newest_first_ignores_class(self):
        router = Router([_fleet()[0]], router_queue_depth=4,
                        breach_rounds=1, shed_high_pct=75,
                        shed_low_pct=25, shed_policy="newest-first")
        uids = [router.put(p, max_new_tokens=4, klass=k)
                for k, p in enumerate(_prompts(12, 4))]
        router.step()
        # depth 4 >= 3 (75%): shed to 1 — the three NEWEST, class-blind
        states = [router._reqs[u].state for u in uids]
        assert states[1] == states[2] == states[3] == "shed"
        assert states[0] in ("queued", "inflight", "done")
        _run(router)
        assert len(router.get(uids[0])) == 4


# ---------------------------------------------------------------------------
# deadline enforcement (fake clock: no wall-time flakiness)
# ---------------------------------------------------------------------------

class TestDeadlines:
    def _router(self, eng=None, **kw):
        if eng is None:
            eng = _engine(prefix_cache=False)
        router = Router([eng], **kw)
        self.clock = {"t": 0.0}
        router._now = lambda: self.clock["t"]
        return router, eng

    def test_queued_ttft_deadline_expires_before_dispatch(self):
        # shared fleet is fine: the request must never reach the engine
        router, eng = self._router(eng=_fleet()[0])
        uid = router.put(_prompts(13, 1)[0], max_new_tokens=4,
                         ttft_deadline_ms=100)
        self.clock["t"] = 0.2                    # 200ms > 100ms
        router.step()
        assert router.is_done(uid)
        with pytest.raises(DeadlineExceeded) as exc:
            router.get(uid)
        assert exc.value.which == "ttft"
        # never dispatched: the engine never saw the request
        assert not eng.state_mgr._seqs and not eng._pending
        assert router.snapshot()["expired"] == 1

    def test_inflight_deadline_flushes_through_cancel(self):
        """Mid-decode expiry: the request is withdrawn through
        engine.cancel() -> state_mgr.flush() (unref, no insert) — the
        pool accounting closes and the request is never returned as a
        success."""
        router, eng = self._router()
        uid = router.put(_prompts(14, 1)[0], max_new_tokens=32,
                         deadline_ms=5000)
        req = router._reqs[uid]
        for _ in range(6):                       # prefill chunks, then the
            router.step()                        # first decode dispatch is
            if req.n_tokens:                     # read one step after it
                break                            # is enqueued
        assert req.state == "inflight" and req.n_tokens > 0
        assert eng._unread is not None           # genuinely decoding
        self.clock["t"] = 10.0                   # 10s > 5s deadline
        router.step()
        assert router.is_done(uid)
        with pytest.raises(DeadlineExceeded) as exc:
            router.get(uid)
        assert exc.value.which == "total"
        snap = router.snapshot()
        assert snap["expired"] == 1 and snap["completed"] == 0
        # allocator pool accounting closed, no leaked blocks
        alloc = eng.state_mgr.allocator
        assert alloc.free_blocks == alloc.total_blocks
        assert not eng.state_mgr._seqs
        assert uid not in eng._results
        # the engine's TTFT/TPOT windows exclude the expired request
        assert eng.telemetry.completed == 0
        assert eng.telemetry.rejected == 1
        assert router.replicas[0].live           # replica unharmed
        assert not router.has_work


# ---------------------------------------------------------------------------
# engine cancel(): the flush()/unref path the router's expiry rides
# ---------------------------------------------------------------------------

class TestEngineCancel:
    def test_cancel_every_lifecycle_stage(self):
        eng = _engine(prefix_cache=True)
        alloc = eng.state_mgr.allocator

        # queued (never admitted): dropped from the pending queue
        u1 = eng.put(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
        assert eng.cancel(u1) is True
        assert not eng._pending
        with pytest.raises(KeyError):
            eng.is_done(u1)

        # mid-chunked-prefill (long prompt > one SplitFuse chunk):
        # removed from the prefill queue, blocks unreffed, NO tree
        # insert (contents past the frontier are unverified)
        long_prompt = np.arange(1, 41, dtype=np.int32) % 255 + 1
        u2 = eng.put(long_prompt, max_new_tokens=8)
        eng.step()
        assert u2 in eng._prefill_q
        assert eng.cancel(u2) is True
        assert u2 not in eng._prefill_q
        _pool_closed(eng)

        # decoding: same unref path
        u3 = eng.put(np.arange(50, 60, dtype=np.int32), max_new_tokens=16)
        for _ in range(2):
            eng.step()
        assert len(eng.get(u3, flush=False)) > 0
        assert eng.cancel(u3) is True
        _pool_closed(eng)
        assert eng.telemetry.rejected >= 1
        assert eng.telemetry.completed == 0

        # finished-but-unfetched: result forgotten
        u4 = eng.put(np.arange(70, 80, dtype=np.int32), max_new_tokens=2)
        while eng.has_work:
            eng.step()
        assert eng.cancel(u4) is True
        with pytest.raises(KeyError):
            eng.get(u4)

        # unknown uid: False, no side effects
        assert eng.cancel(12345) is False

        # the engine still serves cleanly after all that
        out = eng.generate_all([np.arange(5, 15, dtype=np.int32)],
                               max_new_tokens=4)
        assert len(out[0]) == 4
        _pool_closed(eng)


# ---------------------------------------------------------------------------
# telemetry: Serve/Router/* tags ride the linted schema
# ---------------------------------------------------------------------------

class _Mon:
    enabled = True

    def __init__(self):
        self.events = []

    def write_events(self, events):
        self.events.extend(events)


class TestRouterTelemetry:
    def test_emitted_tags_are_documented_and_complete(self):
        mon = _Mon()
        router = Router([_fleet()[0]], monitor=mon, emit_interval=1)
        uids = [router.put(p, max_new_tokens=4) for p in _prompts(15, 2)]
        _run(router)
        for uid in uids:
            router.get(uid)
        tags = {t for t, _v, _s in mon.events}
        undocumented = tags - set(TAG_SCHEMA)
        assert not undocumented, undocumented
        assert {"Serve/Router/shed", "Serve/Router/expired",
                "Serve/Router/replayed", "Serve/Router/failovers",
                "Serve/Router/queue_depth",
                "Serve/Router/draining"} <= tags
        # events are stepped by the completed-request count
        assert all(isinstance(s, int) for _t, _v, s in mon.events)

    def test_router_off_engine_snapshot_is_byte_identical(self):
        """The router adds a layer — a plain engine run must produce
        exactly the pre-router snapshot keys (no 'rejected' key, no
        router counters bleeding in)."""
        eng = _engine(prefix_cache=False)
        eng.generate_all(_prompts(16, 2), max_new_tokens=4)
        snap = eng.telemetry_snapshot()
        # + the admission counters of ISSUE 24 (present once a request
        # has been admitted / a decode batch dispatched)
        assert set(snap) == {"ttft_ms_p50", "ttft_ms_p99",
                             "tpot_ms_p50", "tpot_ms_p99",
                             "completed", "active", "queue_ms_p50",
                             "queue_ms_p90", "batch_occupancy_pct",
                             # + ISSUE 27's, beside the occupancy
                             "decode_grid_share",
                             # + ISSUE 39's, the entries a grid step takes
                             "decode_entries_per_step",
                             # + ISSUE 29's, the KV write's live rows
                             "kv_write_live_share",
                             # + ISSUE 30's, what live sequences hold
                             "cache_bytes_per_live_token",
                             # + ISSUE 35's, the chained decode dispatch
                             "decode_chain_share", "late_stop_share",
                             # + ISSUE 45's, the dispatches that hold a
                             # chunk beside decode steps of their own
                             "fused_dispatches"}

    def test_per_class_latency_windows_are_bounded(self):
        """A server that runs for a day must not append for a day: the
        per-class TTFT/TPOT samples are bounded windows like
        ServingTelemetry's, and snapshot() keeps its shape."""
        from deepspeed_tpu.inference.v2 import router as router_mod
        st = router_mod._new_class_stats()
        for key in ("ttft_ms", "tpot_ms"):
            assert st[key].maxlen == router_mod._MAX_SAMPLES == 4096
            st[key].extend(range(5000))
            assert len(st[key]) == 4096 and st[key][0] == 904
        router = Router(list(_fleet()))
        router._class_stats[0] = st
        snap = router.snapshot()["classes"][0]
        assert set(snap) == {"admitted", "completed", "shed", "expired",
                             "replayed", "ttft_ms_p50", "ttft_ms_p99",
                             "tpot_ms_p50", "tpot_ms_p99"}
        assert snap["ttft_ms_p50"] == pytest.approx(904 + 4095 / 2)


# replica-handle unit coverage that needs no engine compile
class TestReplicaHandle:
    def test_named_replica_wrapping_and_duplicate_names_raise(self):
        e = _fleet()[0]
        rep = Replica("decode-a", e)
        router = Router([rep])
        assert router.replicas[0].name == "decode-a"
        with pytest.raises(ValueError, match="duplicate"):
            Router([Replica("x", e), Replica("x", e)])

    def test_oversized_request_refused_at_the_router(self):
        router = Router([_fleet()[0]])
        with pytest.raises(ValueError, match="never fit"):
            router.put(np.arange(1, 100, dtype=np.int32),
                       max_new_tokens=120)
