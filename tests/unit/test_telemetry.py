"""Telemetry layer tests (ISSUE 9 tentpole): metric-tag schema lint
(both directions, the fault-points-lint discipline), step analytics /
MFU / goodput, cluster aggregation + straggler detection, the crash
flight recorder (chaos: kill mid-save, read the black box), on-demand
profiling arming, serving TTFT/TPOT accounting, and the
off-the-critical-path guarantee (dp=2 virtual mesh, telemetry on vs
off within noise)."""

import json
import os
import re
import signal
import time

import numpy as np
import pytest

import deepspeed_tpu
import jax

from deepspeed_tpu.monitor import flight_recorder
from deepspeed_tpu.monitor.flight_recorder import FlightRecorder
from deepspeed_tpu.monitor.tag_schema import TAG_SCHEMA, check_tag
from deepspeed_tpu.monitor.telemetry import (
    TelemetryCollector, ClusterAggregator, ServingTelemetry,
    ProfilerControl, aggregate_cluster, collective_breakdown,
    peak_flops_per_chip, percentile)
from deepspeed_tpu.runtime.config import TelemetryConfig
from deepspeed_tpu.utils import fault_injection

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PKG = os.path.join(REPO, "deepspeed_tpu")

_TAG_RE = re.compile(
    r"""["']((?:Train|Serve)/[A-Za-z0-9_]+/[A-Za-z0-9_]+)["']""")


def _py_files(root):
    for dirpath, _, names in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for n in names:
            if n.endswith(".py"):
                yield os.path.join(dirpath, n)


class _StubMonitor:
    """Duck-typed MonitorMaster stand-in for collectors."""

    enabled = True

    def __init__(self):
        self.events = []

    def write_events(self, event_list):
        self.events.extend(event_list)


# ------------------------------------------------------------ schema lint
class TestTagSchemaLint:
    """The test_fault_points_lint.py discipline applied to metric tags:
    every tag production code emits is documented in TAG_SCHEMA, and
    every TAG_SCHEMA entry is emitted somewhere — neither half can rot
    under a refactor."""

    def _emitted(self):
        tags = set()
        for path in _py_files(PKG):
            if os.path.basename(path) == "tag_schema.py":
                continue   # the registry itself never counts
            with open(path, encoding="utf-8") as f:
                tags.update(_TAG_RE.findall(f.read()))
        return tags

    def test_every_emitted_tag_is_documented(self):
        undocumented = self._emitted() - set(TAG_SCHEMA)
        assert not undocumented, (
            f"tags emitted in production code but missing from "
            f"monitor/tag_schema.py TAG_SCHEMA: {sorted(undocumented)}")

    def test_every_documented_tag_is_emitted(self):
        dead = set(TAG_SCHEMA) - self._emitted()
        assert not dead, (
            f"TAG_SCHEMA entries no production code emits (stale "
            f"registry or lost emission site): {sorted(dead)}")

    def test_check_tag(self):
        assert check_tag("Train/Samples/lr") == "Train/Samples/lr"
        with pytest.raises(KeyError):
            check_tag("Train/Bogus/nope")


# ------------------------------------------------------------- pure math
class TestAggregation:
    def test_percentile_guard(self):
        assert percentile([], 50) is None
        assert percentile([3.0], 99) == 3.0

    def test_straggler_detection(self):
        agg = aggregate_cluster({
            "h0": {"mean_step_ms": 100.0},
            "h1": {"mean_step_ms": 101.0},
            "h2": {"mean_step_ms": 180.0},
            "h3": {"mean_step_ms": 99.0}})
        assert agg["hosts"] == 4
        assert agg["straggler_node"] == "h2"
        assert agg["straggler_host"] == 2
        # slowest minus the pod median (of 99, 100, 101, 180)
        assert agg["straggler_delta_ms"] == pytest.approx(
            180.0 - 100.5)
        assert agg["cluster_step_ms_p99"] <= 180.0

    def test_ring_order_beats_lexical_sort(self):
        """Regression (review finding): string process ids sort
        lexically ('10' before '2'), misnumbering the straggler on
        pods >= 10 hosts — the ring ``order`` is authoritative."""
        ring = [str(i) for i in range(12)]
        by_host = {h: {"mean_step_ms": 100.0} for h in ring}
        by_host["9"] = {"mean_step_ms": 500.0}
        agg = aggregate_cluster(by_host, order=ring)
        assert agg["straggler_host"] == 9
        assert agg["straggler_node"] == "9"
        # order also drops hosts not in the ring and missing metrics
        agg2 = aggregate_cluster(by_host, order=ring[:4] + ["ghost"])
        assert agg2["hosts"] == 4

    def test_empty_and_partial_hosts(self):
        assert aggregate_cluster({}) is None
        agg = aggregate_cluster({"h0": {"mean_step_ms": 10.0},
                                 "h1": {}, "h2": None})
        assert agg["hosts"] == 1

    def test_straggler_index_survives_missing_host(self):
        """Regression (review finding): a host whose publish is lost
        for a round must not renumber the straggler — the index is the
        RING position, not the position in the filtered list."""
        ring = [str(i) for i in range(12)]
        by_host = {h: {"mean_step_ms": 100.0} for h in ring}
        by_host["9"] = {"mean_step_ms": 500.0}
        del by_host["3"]                    # lost publish
        agg = aggregate_cluster(by_host, order=ring)
        assert agg["hosts"] == 11
        assert agg["straggler_node"] == "9"
        assert agg["straggler_host"] == 9   # ring index, not 8

    def test_collective_breakdown_counts_pairs_once(self):
        """Regression (review finding): overlap_report's n_collectives
        counts HLO entries — an async collective is a -start AND a
        -done entry. 1 sync + 1 async = 3 entries, 1 pair: 2 logical
        collectives, 50% exposed (dividing by entries read 33%)."""
        assert collective_breakdown(3, 1) == (2, 50.0)
        assert collective_breakdown(4, 2) == (2, 0.0)    # fully async
        assert collective_breakdown(2, 0) == (2, 100.0)  # fully exposed
        assert collective_breakdown(0, 0) == (0, 0.0)

    def test_peak_flops_table(self, monkeypatch):
        monkeypatch.delenv("DSTPU_PEAK_FLOPS", raising=False)
        assert peak_flops_per_chip("TPU v5 lite") == 197e12
        assert peak_flops_per_chip("TPU v5p") == 459e12
        # a device the table does not know has no peak — not v5e's
        assert peak_flops_per_chip("cpu") is None
        assert peak_flops_per_chip("TPU v9x") is None
        monkeypatch.setenv("DSTPU_PEAK_FLOPS", "1e15")
        assert peak_flops_per_chip("cpu") == 1e15


# -------------------------------------------------------- fs cluster ring
class TestClusterAggregatorFS:
    def _pair(self, tmp_path):
        peers = ["h0", "h1"]
        return [ClusterAggregator(node=p, peers=peers,
                                  root=str(tmp_path)) for p in peers]

    def test_two_node_gather(self, tmp_path):
        a0, a1 = self._pair(tmp_path)
        assert a0.transport == "fs" and a0.is_root and not a1.is_root
        a1.gather({"node": "h1", "step": 3, "mean_step_ms": 50.0})
        got = a0.gather({"node": "h0", "step": 3, "mean_step_ms": 20.0},
                        wait_s=2.0)
        assert set(got) == {"h0", "h1"}
        agg = aggregate_cluster(got)
        assert agg["straggler_node"] == "h1"
        assert agg["straggler_delta_ms"] == pytest.approx(15.0)

    def test_missing_peer_is_partial_not_fatal(self, tmp_path):
        a0, _ = self._pair(tmp_path)
        got = a0.gather({"node": "h0", "step": 1, "mean_step_ms": 9.0},
                        wait_s=0.0)
        assert list(got) == ["h0"]

    def test_single_process_no_ring(self, monkeypatch):
        for v in ("DSTPU_TELEM_DIR", "DSTPU_TELEM_NODE",
                  "DSTPU_TELEM_PEERS", "DSTPU_HOT_NODE",
                  "DSTPU_HOT_PEERS"):
            monkeypatch.delenv(v, raising=False)
        agg = ClusterAggregator()
        assert agg.transport is None
        got = agg.gather({"step": 1, "mean_step_ms": 5.0})
        assert len(got) == 1


# --------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = FlightRecorder(size=8, node="t")
        for i in range(50):
            rec.record("step", step=i)
        ev = rec.events()
        assert len(ev) == 8
        assert ev[-1]["step"] == 49 and ev[0]["step"] == 42

    def test_dump_and_read(self, tmp_path):
        rec = FlightRecorder(size=16, node="n7")
        rec.set_root(str(tmp_path))
        rec.record("restore", tier="hot", tag="global_step5")
        path = rec.dump(reason="test")
        assert path == flight_recorder.dump_path(str(tmp_path), "n7")
        back = flight_recorder.read_dump(str(tmp_path), "n7")
        assert back["reason"] == "test" and back["node"] == "n7"
        assert back["events"][-1]["kind"] == "restore"
        assert back["events"][-1]["tier"] == "hot"

    def test_concurrent_dumps_never_tear(self, tmp_path):
        """Regression (review finding): a main-thread crash dump can
        race a pool-thread interval dump in the same process — a shared
        pid-only tmp name interleaved both writers' JSON. Per-call
        unique tmp names make each os.replace publish one complete
        dump."""
        import threading
        rec = FlightRecorder(size=64, node="r")
        rec.set_root(str(tmp_path))
        for i in range(40):
            rec.record("step", step=i)

        def hammer():
            for _ in range(25):
                assert rec.dump(reason="race") is not None

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        back = flight_recorder.read_dump(str(tmp_path), "r")
        assert back is not None and back["reason"] == "race"
        assert len(back["events"]) == 40

    def test_set_root_is_first_wins(self, tmp_path):
        rec = FlightRecorder(node="x")
        rec.set_root(str(tmp_path / "a"))
        rec.set_root(str(tmp_path / "b"))
        assert rec.root == str(tmp_path / "a")

    def test_crash_never_raises(self, tmp_path, monkeypatch):
        rec = FlightRecorder(node="c")
        rec.set_root(str(tmp_path))
        rec.crash(RuntimeError("boom"))
        back = flight_recorder.read_dump(str(tmp_path), "c")
        assert back["reason"] == "crash"
        assert "boom" in back["events"][-1]["error"]
        # even a failing dump must not mask the real exception
        monkeypatch.setattr(rec, "dump",
                            lambda **kw: (_ for _ in ()).throw(OSError))
        rec.crash(RuntimeError("again"))   # no raise

    def test_sigterm_chains_previous_handler(self, tmp_path):
        hits = []
        prev = signal.signal(signal.SIGTERM,
                             lambda s, f: hits.append(s))
        try:
            rec = FlightRecorder(node="sig")
            rec.set_root(str(tmp_path))
            assert rec.install_sigterm()
            os.kill(os.getpid(), signal.SIGTERM)
            back = flight_recorder.read_dump(str(tmp_path), "sig")
            assert back is not None and back["reason"] == "sigterm"
            assert hits == [signal.SIGTERM]   # previous handler ran
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_fault_listener_records_injected_points(self):
        cfg = TelemetryConfig(enabled=True, interval_steps=100)
        tel = TelemetryCollector(cfg)
        try:
            fault_injection.reset()
            fault_injection.arm("reshape", fails=1)
            with pytest.raises(fault_injection.FaultError):
                fault_injection.fire("reshape")
            fault_injection.fire("reshape")    # healed, clean: silent
            points = [e for e in tel.flight.events()
                      if e["kind"] == "fault_point"]
            assert points == [{"t": points[0]["t"],
                               "kind": "fault_point",
                               "point": "reshape", "injected": True}]
        finally:
            fault_injection.reset()
            tel.close()


# ----------------------------------------------------------- profiler arm
class TestProfilerControl:
    def test_parse(self):
        assert ProfilerControl._parse("3:7") == (3, 7)
        assert ProfilerControl._parse(None) is None
        assert ProfilerControl._parse("7:3") is None
        assert ProfilerControl._parse("junk") is None

    def test_step_range_capture(self, tmp_path, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: calls.append(("start", d)))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: calls.append(("stop",)))
        monkeypatch.setenv("DSTPU_PROFILE_STEPS", "2:4")
        rec = FlightRecorder(node="p")
        pc = ProfilerControl(logdir=str(tmp_path), flight=rec)
        for step in range(6):
            pc.on_step(step)
        assert [c[0] for c in calls] == ["start", "stop"]
        assert calls[0][1] == os.path.join(str(tmp_path), "xprof")
        kinds = [e["kind"] for e in rec.events()]
        assert kinds == ["profile_start", "profile_stop"]

    def test_non_numeric_port_never_fatal(self):
        """Regression (review finding): DSTPU_PROFILE_PORT=xprof must
        degrade with a warning, not crash engine construction."""
        from deepspeed_tpu.monitor.telemetry import _maybe_start_server
        assert _maybe_start_server("xprof") is False
        assert _maybe_start_server(None) is False

    def test_trigger_file_arms_next_steps(self, tmp_path, monkeypatch):
        monkeypatch.delenv("DSTPU_PROFILE_STEPS", raising=False)
        pc = ProfilerControl(logdir=str(tmp_path))
        pc.check_trigger(str(tmp_path), step=10)
        assert pc.range is None
        with open(os.path.join(str(tmp_path), "PROFILE"), "w") as f:
            f.write("3")
        pc.check_trigger(str(tmp_path), step=10)
        assert pc.range == (11, 14)
        assert not os.path.exists(os.path.join(str(tmp_path),
                                               "PROFILE"))


# ------------------------------------------------------- collector (unit)
class TestTelemetryCollector:
    def _collector(self, monitor=None, interval=2, costs=None):
        cfg = TelemetryConfig(enabled=True, interval_steps=interval,
                              cluster_agg=False)
        return TelemetryCollector(
            cfg, monitor=monitor, n_devices=2, device_kind="TPU v5 lite",
            costs_fn=(lambda: costs) if costs else None)

    def test_flush_emits_documented_tags(self):
        mon = _StubMonitor()
        costs = {"flops_per_chip": 197e12 * 0.010, "source": "hlo",
                 "collectives": 10, "exposed_comm_pct": 40.0}
        tel = self._collector(monitor=mon, costs=costs)
        try:
            for step in range(1, 5):
                tel.on_step(step, wall_s=0.020, tokens=1000)
            tel.drain()
            assert mon.events, "no telemetry events reached the monitor"
            for tag, _, _ in mon.events:
                assert tag in TAG_SCHEMA, f"undocumented tag {tag}"
            by_tag = {t: v for t, v, _ in mon.events}
            # 10ms of flops per step at 20ms step time -> 50% MFU
            assert by_tag["Train/Telemetry/mfu_pct"] == \
                pytest.approx(50.0, rel=0.01)
            assert by_tag["Train/Telemetry/exposed_comm_pct"] == 40.0
            assert by_tag["Train/Telemetry/collectives"] == 10
            # 1000 tokens / 0.02 s / 2 chips
            assert by_tag["Train/Telemetry/tokens_per_sec_chip"] == \
                pytest.approx(25000.0, rel=0.01)
        finally:
            tel.close()

    def test_goodput_accounting(self):
        tel = self._collector()
        try:
            tel._t0 = time.perf_counter() - 10.0     # 10s elapsed
            tel.note_overhead("checkpoint_save", 1.5)
            tel.note_overhead("checkpoint_restore", 0.5)
            assert tel.goodput_pct() == pytest.approx(80.0, abs=1.0)
            kinds = [e["kind"] for e in tel.flight.events()]
            assert kinds == ["checkpoint_save", "checkpoint_restore"]
        finally:
            tel.close()

    def test_on_restore_records_tier(self):
        tel = self._collector()
        try:
            tel.on_restore("hot", "global_step7", 0.25)
            ev = tel.flight.events()[-1]
            assert ev["kind"] == "restore" and ev["tier"] == "hot"
            assert tel._overhead_s["checkpoint_restore"] == 0.25
        finally:
            tel.close()

    def test_costs_failure_degrades(self):
        def bad():
            raise RuntimeError("no program yet")

        cfg = TelemetryConfig(enabled=True, interval_steps=1,
                              cluster_agg=False)
        tel = TelemetryCollector(cfg, costs_fn=bad)
        try:
            tel.on_step(1, 0.01, tokens=10)
            assert "mfu_pct" not in tel.last
            assert tel.last["step_time_ms_p50"] == pytest.approx(10.0)
        finally:
            tel.close()

    def test_reset_window_clears_samples_and_tokens(self):
        tel = self._collector(interval=100)
        try:
            tel.on_step(1, 0.5, tokens=999)
            tel.reset_window()
            assert len(tel._step_ms) == 0 and tel._tokens == 0
            tel.on_step(2, 0.01, tokens=100)
            tel._flush(2)
            # warmup tokens/times gone: 100 tokens / 0.01 s / 2 chips
            assert tel.last["tokens_per_sec_chip"] == \
                pytest.approx(5000.0, rel=0.01)
        finally:
            tel.close()

    def test_fs_cluster_events_emit_on_main_thread_flush(
            self, tmp_path, monkeypatch):
        """Regression (review finding): a pool-side fs gather must not
        call the (non-thread-safe) monitor writers — its events park
        and emit at the NEXT main-thread flush."""
        monkeypatch.setenv("DSTPU_TELEM_DIR", str(tmp_path))
        monkeypatch.setenv("DSTPU_TELEM_NODE", "h0")
        monkeypatch.setenv("DSTPU_TELEM_PEERS", "h0")
        mon = _StubMonitor()
        cfg = TelemetryConfig(enabled=True, interval_steps=2,
                              cluster_agg=True)
        tel = TelemetryCollector(cfg, monitor=mon, n_devices=1)
        try:
            assert tel.cluster is not None \
                and tel.cluster.transport == "fs"
            tel.on_step(1, 0.01)
            tel.on_step(2, 0.01)      # flush 1: round runs on the pool
            tel.drain()
            tags1 = {t for t, _, _ in mon.events}
            assert "Train/Telemetry/straggler_delta_ms" not in tags1
            assert tel.last["cluster"]["hosts"] == 1   # computed though
            tel.on_step(3, 0.01)
            tel.on_step(4, 0.01)      # flush 2: parked events emit
            tel.drain()
            tags2 = {t for t, _, _ in mon.events}
            assert "Train/Telemetry/straggler_delta_ms" in tags2
            assert "Train/Telemetry/cluster_hosts" in tags2
        finally:
            tel.close()

    def test_dead_collector_unregisters_fault_listener(self):
        """Regression (review finding): the process-global fault
        injector must not pin dead collectors (and through costs_fn,
        whole engines) — the weak hook unhooks itself."""
        import gc
        n0 = len(fault_injection.injector._listeners)
        tel = self._collector()
        hook = tel._fault_listener
        assert len(fault_injection.injector._listeners) == n0 + 1
        del tel
        gc.collect()
        hook("reshape", True)      # dead weakref -> self-unregister
        assert len(fault_injection.injector._listeners) == n0
        assert hook not in fault_injection.injector._listeners

    def test_snapshot_without_monitor(self):
        tel = self._collector(monitor=None)
        try:
            tel.on_step(2, 0.01, tokens=10)
            snap = tel.snapshot()
            assert snap["steps_in_window"] == 1
            assert 0.0 <= snap["goodput_pct_live"] <= 100.0
        finally:
            tel.close()


# ----------------------------------------------------------- serving side
class TestServingTelemetry:
    def test_ttft_tpot_accounting(self):
        st = ServingTelemetry(interval=1)
        st.on_submit(1)
        time.sleep(0.02)
        st.on_token(1)                     # first token -> TTFT
        time.sleep(0.01)
        for _ in range(4):
            st.on_token(1)                 # one dispatch, 4 tokens
        st.on_dispatch(active=1)
        p = st.percentiles()
        assert p["ttft_ms_p50"] >= 15.0
        assert p["tpot_ms_p50"] is not None
        assert p["tpot_ms_p50"] <= p["ttft_ms_p50"]
        st.on_finish(1)
        assert st.percentiles()["completed"] == 1

    def test_emits_through_monitor(self):
        mon = _StubMonitor()
        st = ServingTelemetry(monitor=mon, interval=1)
        st.on_submit(5)
        st.on_token(5)
        st.on_finish(5)
        st.maybe_emit()
        tags = {t for t, _, _ in mon.events}
        assert "Serve/Telemetry/completed" in tags
        assert "Serve/Telemetry/ttft_ms_p50" in tags
        for t in tags:
            assert t in TAG_SCHEMA

    def test_unknown_uid_ignored(self):
        st = ServingTelemetry()
        st.on_token(99)
        st.on_finish(99)
        assert st.percentiles()["completed"] == 1

    def test_shed_heavy_traffic_does_not_poison_the_windows(self):
        """Regression (ISSUE-17 satellite): under shed-heavy traffic the
        TTFT/TPOT windows must hold ONLY requests served to completion.
        Before on_reject existed, shed/expired requests lingered in
        _live/_started and the next dispatch amortized wall time across
        their stale state, and 'completed' never matched reality."""
        st = ServingTelemetry(interval=1)
        for uid in range(10):
            st.on_submit(uid)
        # two served to completion (2 dispatch-amortized tokens each)
        for uid in (0, 1):
            st.on_token(uid)
            st.on_token(uid)
        st.on_dispatch(active=2)
        ttft_after_serves = len(st._ttft_ms)
        tpot_after_serves = len(st._tpot_ms)
        for uid in (0, 1):
            st.on_finish(uid)
        # one shed AFTER producing a token (deadline-expired mid-decode)
        st.on_token(5)
        st.on_reject(5)
        # the rest shed while still queued
        for uid in (2, 3, 4, 6, 7, 8, 9):
            st.on_reject(uid)
        p = st.percentiles()
        assert p["completed"] == 2
        assert p["rejected"] == 8
        assert not st._live and not st._started   # accounting emptied
        # a dispatch after the rejects must add no poison samples: the
        # windows still hold only what the two served requests produced
        st.on_dispatch(active=0)
        assert len(st._ttft_ms) == ttft_after_serves + 1   # + uid 5's
        assert len(st._tpot_ms) == tpot_after_serves
        # double-reject and reject-after-finish are idempotent no-ops
        st.on_reject(5)
        st.on_reject(0)
        assert st.percentiles()["rejected"] == 8

    def test_rejected_key_absent_without_rejects(self):
        """Router-off byte-identity: the 'rejected' key may only appear
        once a cancel/shed actually happened — a plain engine run's
        snapshot stays identical to pre-router serving."""
        st = ServingTelemetry()
        st.on_submit(1)
        st.on_token(1)
        st.on_finish(1)
        assert "rejected" not in st.percentiles()
        st.on_submit(2)
        st.on_reject(2)
        assert st.percentiles()["rejected"] == 1

    def test_handoff_anchoring_spans_replicas(self):
        """Regression (ISSUE-20 satellite): a prefill->decode handoff
        must keep ONE latency story per request. The prefill side keeps
        its TTFT sample (the first token was produced there) and
        forgets the request WITHOUT counting a rejection; the decode
        side registers the request anchored at the ORIGINAL submit
        stamp and must never take a second TTFT sample."""
        tel_p = ServingTelemetry(interval=1)
        tel_d = ServingTelemetry(interval=1)
        tel_p.on_submit(7, klass=2)
        time.sleep(0.01)
        tel_p.on_token(7)                  # TTFT sampled on P
        stamp = tel_p.submit_stamp(7)
        assert stamp is not None
        assert tel_p.klass_of(7) == 2
        ttft_samples = len(tel_p._ttft_ms)
        tel_p.on_handoff_out(7)
        p = tel_p.percentiles()
        assert p.get("rejected", 0) == 0   # handoff is not a shed
        assert len(tel_p._ttft_ms) == ttft_samples  # sample survives
        assert 7 not in tel_p._live and 7 not in tel_p._started
        assert p["handoffs_out"] == 1
        tel_d.on_handoff_in(7, klass=2, submit_ts=stamp)
        assert tel_d.klass_of(7) == 2
        assert tel_d.submit_stamp(7) == stamp   # original anchor
        tel_d.on_token(7)
        tel_d.on_token(7)
        tel_d.on_dispatch(active=1)
        d = tel_d.percentiles()
        assert "ttft_ms_p50" not in d or d["ttft_ms_p50"] is None, \
            "decode side must not take a second TTFT sample"
        assert d["tpot_ms_p50"] is not None
        assert d["handoffs_in"] == 1
        tel_d.on_finish(7)
        assert tel_d.percentiles()["completed"] == 1

    def test_handoff_keys_absent_without_handoffs(self):
        """Disagg-off byte-identity: the handoffs_in/out keys may only
        appear once a handoff actually happened — a colocated engine's
        snapshot stays identical to pre-disaggregation serving."""
        st = ServingTelemetry()
        st.on_submit(1)
        st.on_token(1)
        st.on_finish(1)
        p = st.percentiles()
        assert "handoffs_in" not in p and "handoffs_out" not in p

    def test_dispatch_skips_queued_requests(self):
        """Regression (review finding): on_dispatch runs per engine
        step — it must visit only requests past their first token, not
        the whole admission backlog (O(queued) per step at 10k queued
        requests)."""
        st = ServingTelemetry()
        for uid in range(50):
            st.on_submit(uid)               # queued, never started
        st.on_submit("hot")
        st.on_token("hot")
        st.on_token("hot")
        assert set(st._started) == {"hot"}
        st.on_dispatch(active=1)
        assert st.percentiles()["tpot_ms_p50"] is not None
        st.on_finish("hot")
        assert not st._started              # pruned on finish
        assert len(st._live) == 50          # queue untouched


# ----------------------------------------------- engine integration + chaos
def _tiny_engine(tmp_path=None, telemetry=None, tp=1):
    from deepspeed_tpu.models.gpt2 import GPT2, GPT2_TINY
    from deepspeed_tpu.utils import groups
    from deepspeed_tpu.utils.groups import TopologyConfig
    topo = None
    if tp > 1:
        topo = groups.initialize(TopologyConfig(tensor_parallel_size=tp))
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 0,
    }
    if telemetry is not None:
        config["telemetry"] = telemetry
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2(GPT2_TINY), config=config,
        **({"topology": topo} if topo is not None else {}))
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(
        0, 1024, (engine.config.train_batch_size, 128)).astype(np.int32)}
    return engine, batch


class TestEngineTelemetry:
    @pytest.mark.parametrize("peak", [None, "1e12"])
    def test_step_analytics_flow_through_fanout(self, monkeypatch, peak):
        """The CPU mesh is a device the peak table does not know: MFU is
        reported only when the operator names a peak."""
        if peak:
            monkeypatch.setenv("DSTPU_PEAK_FLOPS", peak)
        else:
            monkeypatch.delenv("DSTPU_PEAK_FLOPS", raising=False)
        engine, batch = _tiny_engine(
            telemetry={"enabled": True, "interval_steps": 3,
                       "cluster_agg": False})
        stub = _StubMonitor()
        engine.monitor.monitors.append(stub)
        engine.monitor.enabled = True
        try:
            for _ in range(6):
                engine.train_batch(batch)
            engine.telemetry.drain()
            tags = {t for t, _, _ in stub.events}
            assert "Train/Telemetry/step_time_ms_p50" in tags
            assert "Train/Telemetry/goodput_pct" in tags
            assert ("Train/Telemetry/mfu_pct" in tags) == bool(peak)
            for t in tags:
                assert t in TAG_SCHEMA, f"undocumented tag {t}"
            snap = engine.telemetry_report()
            if peak:
                assert snap["flops_source"] == "hlo"
                assert snap["mfu_pct"] > 0
            else:
                assert snap["peak_flops_per_chip"] is None
                assert "mfu_pct" not in snap
            assert snap["tokens_per_sec_chip"] > 0
            assert "collectives" in snap
        finally:
            engine.telemetry.close()

    def test_disabled_by_default_without_monitor(self, monkeypatch):
        for v in ("DSTPU_TELEMETRY", "DSTPU_FLIGHTREC_DIR",
                  "ELASTIC_GENERATION"):
            monkeypatch.delenv(v, raising=False)
        engine, _ = _tiny_engine()
        assert engine.telemetry is None
        assert engine.telemetry_report() is None

    def test_auto_enable_is_rank_symmetric(self, monkeypatch, tmp_path):
        """Regression (review finding): 'auto' must resolve from the
        CONFIG monitor flag, not MonitorMaster.enabled (rank-0-gated) —
        the allgather cluster transport is collective, so rank-0-only
        arming would hang a multi-process pod at the first flush."""
        import jax
        for v in ("DSTPU_TELEMETRY", "DSTPU_FLIGHTREC_DIR",
                  "ELASTIC_GENERATION"):
            monkeypatch.delenv(v, raising=False)
        monkeypatch.setattr(jax, "process_index", lambda: 1)
        from deepspeed_tpu.models.gpt2 import GPT2, GPT2_TINY
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2(GPT2_TINY), config={
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "csv_monitor": {"enabled": True,
                                "output_path": str(tmp_path)},
                "telemetry": {"cluster_agg": False},
            })
        try:
            assert not engine.monitor.enabled     # rank 1 writes nothing
            assert engine.telemetry is not None   # but telemetry is armed
        finally:
            engine.telemetry.close()


@pytest.mark.chaos
class TestChaosFlightRecorder:
    def test_kill_mid_save_leaves_black_box(self, tmp_path):
        """The ISSUE-9 acceptance chaos: a worker killed mid-run leaves
        a flight-recorder dump whose last events include the fired
        fault point AND the tier its generation restored from."""
        ckpt = str(tmp_path / "ckpt")
        engine, batch = _tiny_engine(
            telemetry={"enabled": True, "interval_steps": 100,
                       "cluster_agg": False})
        try:
            engine.train_batch(batch)
            engine.save_checkpoint(ckpt)
            # resume: the restore (tier=durable) enters the flight ring
            engine2, batch2 = _tiny_engine(
                telemetry={"enabled": True, "interval_steps": 100,
                           "cluster_agg": False})
            try:
                engine2.load_checkpoint(ckpt)
                assert engine2.last_restore_tier == "durable"
                engine2.train_batch(batch2)
                fault_injection.reset()
                fault_injection.arm("write", fails=1, kill=True)
                with pytest.raises(fault_injection.SimulatedKill):
                    engine2.save_checkpoint(ckpt)
            finally:
                fault_injection.reset()
                engine2.telemetry.close()
            dump = flight_recorder.read_dump(
                os.path.join(ckpt, "flightrec"),
                engine2.telemetry.flight.node)
            assert dump is not None, "no flight-recorder dump written"
            assert dump["reason"] == "crash"
            kinds = [e["kind"] for e in dump["events"]]
            assert kinds[-1] == "crash"
            restores = [e for e in dump["events"]
                        if e["kind"] == "restore"]
            assert restores and restores[-1]["tier"] == "durable"
            faults = [e for e in dump["events"]
                      if e["kind"] == "fault_point"]
            assert faults and faults[-1]["point"] == "write"
            assert any(k == "step" for k in kinds)
        finally:
            engine.telemetry.close()

    def test_agent_attaches_flight_record(self, tmp_path):
        """Agent side of the black box: a failed host's dump is read on
        membership change and attached to the classification."""
        from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent
        root = str(tmp_path / "fr")
        rec = FlightRecorder(node="h1")
        rec.set_root(root)
        rec.record("fault_point", point="write", injected=True)
        rec.record("crash", error="FaultError: injected")
        rec.dump(reason="crash")
        agent = DSElasticAgent(lambda hosts: [], ["h0", "h1"],
                               flightrec_root=root)
        env = agent.worker_env("h1")
        assert env["DSTPU_FLIGHTREC_DIR"] == root
        assert env["DSTPU_FLIGHTREC_NODE"] == "h1"
        agent._handle_membership_change({"h1": "dead"})
        assert "h1" in agent.last_failure_records
        tail = agent.last_failure_records["h1"]["events"]
        assert tail[-1]["kind"] == "crash"
        assert agent.hosts == ["h0"]


class TestOffCriticalPath:
    def test_dp2_step_time_within_noise(self, monkeypatch):
        """ISSUE-9 acceptance: per-step wall time with telemetry on is
        within noise of telemetry off (dp=2 virtual mesh). Two medians of
        CPU wall times cannot show that on a loaded machine, so the
        evidence is what the step path DOES: between flushes a
        train_batch with telemetry on appends to the ring and to the
        flight recorder and nothing else — no flush, no cost capture
        (``cost_analysis`` compile), no background work, no device sync
        that the same steps with telemetry off do not make; the flush and
        the one-time cost capture land on interval boundaries only."""
        calls = []
        for fn in ("block_until_ready", "device_get"):
            real = getattr(jax, fn)
            monkeypatch.setattr(
                jax, fn, lambda *a, _r=real, _n=fn, **k:
                (calls.append("sync:" + _n), _r(*a, **k))[1])

        def watch(obj, name):
            real = getattr(obj, name)

            def wrapper(*a, **k):
                calls.append(name)
                return real(*a, **k)
            monkeypatch.setattr(obj, name, wrapper)

        def run(telemetry):
            engine, batch = _tiny_engine(telemetry=telemetry, tp=4)
            tel = engine.telemetry
            if tel is not None:
                assert tel.interval == 5
                for name in ("_flush", "_capture_costs", "_submit",
                             "_emit"):
                    watch(tel, name)
                watch(tel, "_costs_fn")
            # warmup past compile AND past the first flush (the lazy
            # cost capture compiles once at step==interval)
            for _ in range(6):
                engine.train_batch(batch)
            if tel is not None:
                assert calls.count("_flush") == 1
                assert calls.count("_costs_fn") == 1
            between = []
            for _ in range(3):                   # steps 7, 8, 9
                calls.clear()
                ring = len(tel._step_ms) if tel is not None else 0
                events = len(tel.flight._events) if tel is not None else 0
                engine.train_batch(batch)
                if tel is not None:
                    assert len(tel._step_ms) == ring + 1
                    assert len(tel.flight._events) == events + 1
                between.append(sorted(calls))
            calls.clear()
            engine.train_batch(batch)            # step 10: a flush
            flush = sorted(c for c in calls if not c.startswith("sync:"))
            if tel is not None:
                tel.drain()
                assert tel.last, "telemetry never flushed"
                tel.close()
            return between, flush

        off, off_flush = run(telemetry={"enabled": False})
        on, on_flush = run(telemetry={"enabled": True, "interval_steps": 5,
                                      "cluster_agg": False})
        assert off_flush == []
        # between flushes: the same (sync) calls as with telemetry off,
        # and none of the collector's heavy paths
        assert on == off, (
            f"telemetry on the critical path: a step between flushes "
            f"made {on} (on) vs {off} (off)")
        # the boundary step flushes once; the cost capture is asked and
        # answers from its one-time result (no second costs_fn call)
        assert on_flush.count("_flush") == 1
        assert "_costs_fn" not in on_flush
