"""Modeled-vs-measured reconciliation tests (ISSUE 13): the
two-direction planner<->tracer vocabulary lint, drift-report pairing,
the seed-cache -> changed ``calibrate_links`` golden, the reconcile
CLI, the telemetry/flight wiring, and the dp=2 virtual-mesh end-to-end
profiled run."""

import gzip
import inspect
import json
import os
import re

import numpy as np
import pytest

import deepspeed_tpu
import jax

from deepspeed_tpu.autotuning import planner, reconcile
from deepspeed_tpu.autotuning.kernel_cache import KernelCache
from deepspeed_tpu.autotuning.planner import (
    ModelDesc, PodDesc, calibrate_links)
from deepspeed_tpu.profiling import step_trace
from deepspeed_tpu.profiling.step_trace import StepDecomposition


def _model():
    return ModelDesc(params=1 << 20, n_layer=2, d_model=64, n_head=4,
                     max_seq_len=128, name="test")


def _pod(**kw):
    kw.setdefault("n_chips", 8)
    kw.setdefault("hbm_bytes", 1 << 34)
    return PodDesc(**kw)


def _decomp(**kw):
    terms = {k: 0.0 for k in step_trace.DECOMP_TERMS}
    terms.update({"compute": 10.0, "grad_reduce": 2.0,
                  "tp_reduce": 1.0})
    kw.setdefault("terms", terms)
    kw.setdefault("unmodeled", {"copy_layout": 0.5})
    kw.setdefault("total_device_ms", sum(kw["terms"].values()) + 0.5)
    kw.setdefault("coverage_pct", 96.3)
    kw.setdefault("collectives", [
        {"op": "all-reduce", "term": "grad_reduce", "axes": ["data"],
         "leg": "ici", "count_per_step": 2, "total_ms": 4.0,
         "exposed_ms": 2.0, "hidden_ms": 2.0},
        {"op": "all-reduce", "term": "tp_reduce", "axes": ["tensor"],
         "leg": "ici", "count_per_step": 4, "total_ms": 2.0,
         "exposed_ms": 1.0, "hidden_ms": 1.0},
    ])
    kw.setdefault("kernels", {"flash_attention": 3.0})
    return StepDecomposition(**kw)


# ------------------------------------------------------ vocabulary lint
class TestVocabularyLint:
    """The test_planner_lint.py discipline: the planner's ``_score``
    terms and the tracer's decomposition keys may never silently
    diverge — both directions are greped from source, not trusted."""

    def _score_terms_from_source(self):
        src = inspect.getsource(planner._score)
        found = set(re.findall(r'terms\["(\w+)"\]', src))
        found |= set(re.findall(r'terms = \{"(\w+)"', src))
        return found

    def test_score_terms_constant_matches_score_source(self):
        assert self._score_terms_from_source() == \
            set(planner.SCORE_TERMS), (
                "planner.SCORE_TERMS is out of sync with the terms "
                "_score actually emits — update the constant (and the "
                "tracer/reconciler vocabulary with it)")

    def test_every_score_term_maps_to_a_decomposition_key(self):
        assert set(reconcile.TERM_MAP) == set(planner.SCORE_TERMS)
        for term, key in reconcile.TERM_MAP.items():
            assert key in step_trace.DECOMP_TERMS, (
                f"_score term {term!r} maps to {key!r} which the "
                f"tracer never measures")

    def test_every_decomposition_key_maps_back_or_is_unmodeled(self):
        modeled = set(reconcile.TERM_MAP.values())
        for key in step_trace.DECOMP_TERMS:
            assert key in modeled, (
                f"decomposition key {key!r} reaches no _score term and "
                f"is not declared in step_trace.UNMODELED_KEYS")
        assert set(step_trace.UNMODELED_KEYS).isdisjoint(modeled)
        # tuple-level identity keeps ordering honest too
        assert tuple(planner.SCORE_TERMS) == step_trace.DECOMP_TERMS


# -------------------------------------------------------- drift report
class TestDriftReport:
    def test_every_term_gets_a_measured_row(self):
        rep = reconcile.reconcile(
            _decomp(), _model(), _pod(),
            {"data": 2, "tensor": 4}, batch_tokens=16 * 128)
        assert {r["term"] for r in rep.rows} == set(planner.SCORE_TERMS)
        by_term = {r["term"]: r for r in rep.rows}
        assert by_term["compute"]["measured_ms"] == pytest.approx(10.0)
        # an unexercised term pairs 0 modeled against 0 measured
        assert by_term["expert_a2a"]["measured_ms"] == 0.0
        for r in rep.rows:
            assert r["drift_ms"] == pytest.approx(
                r["measured_ms"] - r["modeled_ms"], abs=1e-6)

    def test_rows_ranked_by_absolute_drift(self):
        rep = reconcile.reconcile(
            _decomp(), _model(), _pod(),
            {"data": 2, "tensor": 4}, batch_tokens=16 * 128)
        drifts = [abs(r["drift_ms"]) for r in rep.rows]
        assert drifts == sorted(drifts, reverse=True)
        assert rep.top()["term"] == rep.rows[0]["term"]

    def test_summary_is_telemetry_shaped(self):
        rep = reconcile.reconcile(
            _decomp(), _model(), _pod(),
            {"data": 2, "tensor": 4}, batch_tokens=16 * 128)
        s = rep.summary()
        assert set(s) == {"top_term", "top_term_index", "top_drift_ms",
                          "wall_err_pct", "coverage_pct",
                          "modeled_wall_ms", "measured_wall_ms",
                          "steps"}
        assert planner.SCORE_TERMS[s["top_term_index"]] == s["top_term"]
        assert s["coverage_pct"] == pytest.approx(96.3)

    def test_table_lists_unmodeled_time(self):
        rep = reconcile.reconcile(
            _decomp(), _model(), _pod(),
            {"data": 2, "tensor": 4}, batch_tokens=16 * 128)
        text = rep.table()
        assert "copy_layout" in text and "(unmodeled)" in text
        for term in planner.SCORE_TERMS:
            assert term in text

    def test_to_dict_round_trips_json(self):
        rep = reconcile.reconcile(
            _decomp(), _model(), _pod(),
            {"data": 2, "tensor": 4}, batch_tokens=16 * 128)
        parsed = json.loads(json.dumps(rep.to_dict()))
        assert parsed["mesh"]["tensor"] == 4
        assert len(parsed["rows"]) == len(planner.SCORE_TERMS)


# ------------------------------------------------------------- seeding
class TestSeeding:
    def _report(self, pod):
        d = _decomp()
        rep = reconcile.reconcile(d, _model(), pod,
                                  {"data": 2, "tensor": 4},
                                  batch_tokens=16 * 128)
        rep._model = _model()
        rep._batch_tokens = 16 * 128
        return d, rep

    def test_seed_rows_shape(self):
        d, rep = self._report(_pod(device_kind="TestChip"))
        rows = reconcile.seed_rows(d, rep, device_kind="TestChip")
        ops = {r["op"] for r in rows}
        assert ops == {"comm_link", "op_cost"}
        link = [r for r in rows if r["op"] == "comm_link"]
        # only the ICI leg carried measured time in the fixture
        assert len(link) == 1 and link[0]["params"]["kind"] == "ici"
        assert link[0]["bucket"] == \
            "pp1,do1,dp2,ep1,sp1,tp4,kici"
        assert link[0]["params"]["source"] == "reconcile"
        assert link[0]["params"]["beta_gbps"] > 0
        costs = {r["params"]["op"]: r["params"]["ms_per_step"]
                 for r in rows if r["op"] == "op_cost"}
        assert costs["flash_attention"] == pytest.approx(3.0)
        assert costs["compute_step"] == pytest.approx(10.0)

    def test_seeding_changes_calibrate_links(self, tmp_path):
        """The ISSUE-13 golden: measured comm_link rows round-trip into
        a DIFFERENT calibrate_links result than the nominal fallback —
        the planner now prices meshes from measured numbers."""
        pod = _pod(device_kind="TestChip")
        baseline = calibrate_links(pod, cache=KernelCache())
        d, rep = self._report(pod)
        rows = reconcile.seed_rows(d, rep, device_kind="TestChip")
        path = str(tmp_path / "cache.json")
        assert reconcile.seed_cache(rows, path=path) == len(rows)
        seeded = calibrate_links(pod, cache=KernelCache.load(path))
        assert seeded["ici"] != baseline["ici"], (
            "seeded comm_link row did not change the ICI calibration")
        # beta is the measured-effective one from the seeded row
        row = [r for r in rows if r["op"] == "comm_link"][0]
        assert seeded["ici"][1] == pytest.approx(
            row["params"]["beta_gbps"] * 1e9)

    def test_device_kind_refusal_intact(self, tmp_path):
        """A cache measured on one chip must never calibrate another."""
        pod = _pod(device_kind="TestChip")
        d, rep = self._report(pod)
        rows = reconcile.seed_rows(d, rep, device_kind="TestChip")
        path = str(tmp_path / "cache.json")
        reconcile.seed_cache(rows, path=path)
        other = _pod(device_kind="OtherChip")
        got = calibrate_links(other, cache=KernelCache.load(path))
        assert got == calibrate_links(other, cache=KernelCache())

    def test_pseudo_ops_stay_out_of_the_registry(self):
        """comm_link/op_cost are cache-file-only: REGISTRY and the knob
        table must never learn them (test_autotune asserts REGISTRY ==
        _BUCKETS; this is the same fence from the other side)."""
        from deepspeed_tpu.autotuning.kernel_registry import REGISTRY
        assert "comm_link" not in REGISTRY
        assert "op_cost" not in REGISTRY


# ---------------------------------------------------------------- CLI
def _write_trace(root, events):
    d = os.path.join(root, "plugins", "profile", "t")
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, "host.trace.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return path


def _canned_events():
    meta = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0 (Core 0)"}},
        {"ph": "M", "pid": 1, "tid": 10, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
    ]

    def ev(name, ts, dur):
        return {"ph": "X", "pid": 1, "tid": 10, "name": name,
                "ts": ts, "dur": dur, "args": {}}

    return meta + [
        ev("fusion.1", 0, 8000),
        ev("all-reduce.2", 8100, 1000),
        ev("custom-call.3", 9200, 500),
    ]


class TestReconcileCLI:
    def test_drift_table_and_json(self, tmp_path, capsys):
        from deepspeed_tpu.profiling import reconcile as cli
        _write_trace(str(tmp_path), _canned_events())
        rc = cli.main([str(tmp_path), "--mesh", "dp=2,tp=4",
                       "--steps", "1"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "grad_reduce" in out and "modeled_ms" in out
        rc = cli.main([str(tmp_path), "--mesh", "dp=2,tp=4", "--json"])
        assert rc == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed["decomposition"]["schema"] == \
            step_trace.SCHEMA_VERSION
        terms = {r["term"] for r in parsed["drift"]["rows"]}
        assert terms == set(planner.SCORE_TERMS)

    def test_seed_cache_flag_round_trips(self, tmp_path, capsys):
        from deepspeed_tpu.profiling import reconcile as cli
        _write_trace(str(tmp_path), _canned_events())
        cache = str(tmp_path / "cache.json")
        rc = cli.main([str(tmp_path), "--mesh", "dp=2", "--seed-cache",
                       "--cache", cache])
        assert rc == 0
        assert "seeded" in capsys.readouterr().out
        loaded = KernelCache.load(cache)
        ops = {e.get("op") for e in loaded.entries.values()}
        assert "comm_link" in ops

    def test_missing_trace_exits_2(self, tmp_path, capsys):
        from deepspeed_tpu.profiling import reconcile as cli
        assert cli.main([str(tmp_path / "empty")]) == 2


# ---------------------------------------------------- telemetry wiring
class TestTelemetryWiring:
    def _collector(self, monitor=None):
        from deepspeed_tpu.monitor.telemetry import TelemetryCollector
        from deepspeed_tpu.runtime.config import TelemetryConfig
        cfg = TelemetryConfig(enabled=True, interval_steps=2,
                              cluster_agg=False)
        return TelemetryCollector(cfg, monitor=monitor, n_devices=2)

    def test_profiler_stop_fires_on_trace(self, tmp_path, monkeypatch):
        from deepspeed_tpu.monitor.telemetry import ProfilerControl
        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        monkeypatch.setenv("DSTPU_PROFILE_STEPS", "2:4")
        pc = ProfilerControl(
            logdir=str(tmp_path),
            on_trace=lambda d, n, s: calls.append((d, n, s)))
        for step in range(6):
            pc.on_step(step)
        assert calls == [(os.path.join(str(tmp_path), "xprof"), 2, 4)]

    def test_on_trace_failure_never_raises(self, tmp_path, monkeypatch):
        from deepspeed_tpu.monitor.telemetry import ProfilerControl

        def boom(*a):
            raise RuntimeError("parser exploded")

        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        monkeypatch.setenv("DSTPU_PROFILE_STEPS", "0:1")
        pc = ProfilerControl(logdir=str(tmp_path), on_trace=boom)
        for step in range(3):
            pc.on_step(step)        # must not raise
        assert pc.range is None

    def test_reconcile_summary_reaches_snapshot_and_events(self):
        summary = {"top_term": "compute", "top_term_index": 0,
                   "top_drift_ms": 5.0, "wall_err_pct": 12.5,
                   "coverage_pct": 97.0, "modeled_wall_ms": 40.0,
                   "measured_wall_ms": 45.0, "steps": 2}

        class _Mon:
            enabled = True
            events = []

            def write_events(self, evs):
                self.events.extend(evs)

        mon = _Mon()
        tel = self._collector(monitor=mon)
        try:
            tel.set_reconcile(lambda d, n: summary)
            tel._on_trace_ready("/nowhere/xprof", 2, 4)
            tel.drain()
            assert tel.last["reconcile"] == summary
            # events park until the next main-thread flush
            assert not any(t.startswith("Train/Reconcile/")
                           for t, _, _ in mon.events)
            for step in range(5, 7):
                tel.on_step(step, 0.01)
            by_tag = {t: v for t, v, _ in mon.events}
            assert by_tag["Train/Reconcile/wall_err_pct"] == 12.5
            assert by_tag["Train/Reconcile/top_drift_term"] == 0
            assert by_tag["Train/Reconcile/coverage_pct"] == 97.0
            # snapshot carries reconcile across later flushes
            assert tel.snapshot()["reconcile"] == summary
            # flight: both an event and the sticky crash context
            kinds = [e["kind"] for e in tel.flight.events()]
            assert "reconcile" in kinds
            assert tel.flight.context()["reconcile"] == summary
        finally:
            tel.close()

    def test_reconcile_none_warns_once_no_event(self, monkeypatch):
        from deepspeed_tpu.monitor import telemetry as tmod
        warns = []
        monkeypatch.setattr(tmod.logger, "warning",
                            lambda msg, *a, **k: warns.append(str(msg)))
        tel = self._collector()
        try:
            tel.set_reconcile(lambda d, n: None)
            tel._on_trace_ready("/nowhere", 1, 1)
            tel.drain()
            tel._on_trace_ready("/nowhere", 1, 2)
            tel.drain()
            assert len([w for w in warns
                        if "no step decomposition" in w]) == 1
            assert "reconcile" not in tel.last
            assert tel._pending_reconcile_events is None
        finally:
            tel.close()

    def test_flight_dump_context_only_when_set(self, tmp_path):
        from deepspeed_tpu.monitor.flight_recorder import FlightRecorder
        rec = FlightRecorder(node="ctx")
        rec.set_root(str(tmp_path))
        rec.record("step", step=1)
        with open(rec.dump("interval")) as f:
            assert "context" not in json.load(f)
        rec.set_context("reconcile", {"top_term": "compute"})
        with open(rec.dump("crash")) as f:
            dump = json.load(f)
        assert dump["context"]["reconcile"]["top_term"] == "compute"


# ------------------------------------------------- end-to-end (dp=2 mesh)
def _tiny_engine(telemetry=None, tp=1):
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


class TestEndToEnd:
    def test_profiled_dp2_run_reconciles(self, tmp_path, monkeypatch):
        """The ISSUE-13 acceptance path on the dp=2 virtual mesh: a
        step-ranged capture feeds the parser automatically, every
        measured op lands in a _score term or a named unmodeled bucket,
        and the drift report pairs every _score term with a measured
        value.

        No threshold on ``coverage_pct`` itself: XLA:CPU's two traced
        steps hold ~0.3 ms of op time, 48 of its ~120 ops sub-microsecond
        copies, so one descheduled copy moves the share by points (94.8
        alone, 90.98 / 92.6 / 99.2 beside ten busy processes; `> 90`
        failed under ``-n 6``), and a collective that waits for a
        descheduled peer thread outgrows the compute term. That the
        decomposition covers >90 % is a statement about a chip's trace;
        here the share is held to its own definition."""
        monkeypatch.setenv("DSTPU_PROFILE_STEPS", "1:3")
        engine, batch = _tiny_engine(
            telemetry={"enabled": True, "interval_steps": 2,
                       "cluster_agg": False,
                       "flightrec_dir": str(tmp_path)},
            tp=4)
        try:
            mesh = dict(engine.mesh.shape)
            assert mesh.get("data") == 2 and mesh.get("tensor") == 4
            for _ in range(5):
                engine.train_batch(batch)
            engine.telemetry.drain()

            snap = engine.telemetry_report()
            assert "reconcile" in snap, (
                "profiled run produced no reconcile summary "
                "(trace->parser wiring broke)")
            summary = snap["reconcile"]
            assert summary["measured_wall_ms"] > 0

            rep = engine.reconcile_report()
            assert rep is not None
            dec = rep["decomposition"]
            assert dec["cpu_fallback"] is True    # tier-1 runs on CPU
            assert dec["terms"]["compute"] > 0
            assert set(dec["unmodeled"]) == set(step_trace.UNMODELED_KEYS)
            attributed = sum(dec["terms"].values())
            assert dec["total_device_ms"] == pytest.approx(
                attributed + sum(dec["unmodeled"].values()), abs=1e-5)
            assert summary["coverage_pct"] == pytest.approx(
                100.0 * attributed / dec["total_device_ms"], abs=0.01)
            assert 0.0 < summary["coverage_pct"] <= 100.0
            drift = rep["drift"]
            terms = {r["term"] for r in drift["rows"]}
            assert terms == set(planner.SCORE_TERMS)
            # flight recorder saw the profile + reconcile events
            kinds = [e["kind"] for e in engine.telemetry.flight.events()]
            assert "profile_start" in kinds
            assert "profile_stop" in kinds
            assert "reconcile" in kinds
        finally:
            engine.telemetry.close()

    def test_tracing_off_leaves_snapshot_unchanged(self, monkeypatch):
        """Byte-identity guard: without DSTPU_PROFILE_STEPS the
        snapshot carries no reconcile key and the flight context stays
        empty — telemetry output is exactly the pre-PR shape."""
        monkeypatch.delenv("DSTPU_PROFILE_STEPS", raising=False)
        engine, batch = _tiny_engine(
            telemetry={"enabled": True, "interval_steps": 2,
                       "cluster_agg": False})
        try:
            for _ in range(4):
                engine.train_batch(batch)
            engine.telemetry.drain()
            snap = engine.telemetry_report()
            assert "reconcile" not in snap
            assert engine.reconcile_report() is None
            assert engine.telemetry.flight.context() == {}
        finally:
            engine.telemetry.close()
