"""Step-anatomy trace parser tests (ISSUE 13 tentpole): canned Chrome
trace fixtures (device tracks, async collective start/done pairs, host
copies, Pallas kernel names, replica-group axes) driving
``profiling/step_trace.py``, the stable JSON schema, the CPU-client
fallback, the never-raise degrade path, and the refactored
``benchmarks/trace_summary.py`` CLI (``--json`` + human table)."""

import gzip
import json
import os

import numpy as np
import pytest

import jax

from deepspeed_tpu.profiling import step_trace
from deepspeed_tpu.profiling.step_trace import (
    StepDecomposition, decompose, decompose_dir, family_of,
    find_trace_file, kernel_op_for, DECOMP_TERMS, UNMODELED_KEYS)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ----------------------------------------------------------- fixtures
def proc(pid, name):
    return {"ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": name}}


def thread(pid, tid, name):
    return {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
            "args": {"name": name}}


def ev(name, ts, dur, pid=1, tid=10, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name,
            "ts": ts, "dur": dur, "args": args}


def device_meta():
    """One TPU-style device track with the leaf-op thread."""
    return [proc(1, "/device:TPU:0 (Core 0)"), thread(1, 10, "XLA Ops"),
            thread(1, 11, "Steps")]


def write_trace(root, events):
    """Nest a gzipped trace the way jax.profiler lays them out."""
    d = os.path.join(root, "plugins", "profile", "2026_08_04")
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, "host.trace.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return path


def tensor_mesh():
    """2x4 mesh over (data, tensor) on the conftest virtual devices."""
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    return jax.sharding.Mesh(devs, ("data", "tensor"))


def outer_mesh():
    """2x4 mesh over (data_outer, data) — the DCN-crossing layout."""
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    return jax.sharding.Mesh(devs, ("data_outer", "data"))


# --------------------------------------------------------- classification
class TestClassifiers:
    def test_family_of(self):
        assert family_of("all-reduce.3") == "collective"
        assert family_of("custom-call.7") == "pallas/custom-call"
        assert family_of("dot.2") == "matmul"
        assert family_of("fusion.11") == "fusion(elementwise/other)"
        assert family_of("transpose.4") == "copy/layout"
        assert family_of("dynamic-update-slice.1") == \
            "gather/scatter/DUS"
        assert family_of("parameter.0") == "other"

    def test_kernel_op_is_the_registered_name(self):
        from deepspeed_tpu.monitor.tag_schema import KERNEL_SCHEMA
        for name in KERNEL_SCHEMA:
            op = name[len("dstpu.kernel."):]
            assert kernel_op_for(
                f"custom-call.7 jit(f)/jvp(dstpu.attn.flash)/{name}/"
                f"pallas_call") == op
        assert kernel_op_for("dstpu.kernel.flash_fwd_t") == "flash_fwd_t"
        assert kernel_op_for("custom-call.9 dstpu.kernel.gmm") == "gmm"
        # a name nobody registered, and what the old hints guessed from
        assert kernel_op_for("dstpu.kernel.nobodys") is None
        assert kernel_op_for("flash_attention_fwd_kernel") is None
        assert kernel_op_for("plain_matmul") is None


# ------------------------------------------------------------ self time
class TestSelfTime:
    def test_nested_envelope_never_double_counts(self):
        events = device_meta() + [
            ev("fusion.1", 0, 100),
            ev("dot.2", 10, 40),
        ]
        d = decompose(events, steps=1)
        per = {r["op"]: r["ms"] for r in d.per_op}
        assert per["fusion.1"] == pytest.approx(0.060)   # 100 - 40 us
        assert per["dot.2"] == pytest.approx(0.040)
        assert d.terms["compute"] == pytest.approx(0.100)
        assert d.total_device_ms == pytest.approx(0.100)

    def test_steps_normalization(self):
        events = device_meta() + [ev("dot.1", 0, 200)]
        d = decompose(events, steps=2)
        assert d.terms["compute"] == pytest.approx(0.100)
        assert d.steps == 2


# ----------------------------------------------------------- collectives
class TestCollectives:
    def test_async_pair_exposed_vs_hidden(self):
        events = device_meta() + [
            ev("all-reduce-start.5", 0, 10),
            ev("dot.1", 10, 90),
            ev("all-reduce-done.5", 100, 5),
        ]
        d = decompose(events, steps=1)
        (row,) = d.collectives
        assert row["op"] == "all-reduce"
        assert row["term"] == "grad_reduce"
        # window 105us, gap 100-10=90 hidden, 15 exposed
        assert row["total_ms"] == pytest.approx(0.105)
        assert row["hidden_ms"] == pytest.approx(0.090)
        assert row["exposed_ms"] == pytest.approx(0.015)
        # terms carry EXPOSED time only
        assert d.terms["grad_reduce"] == pytest.approx(0.015)
        assert d.collective_hidden_ms == pytest.approx(0.090)

    def test_sync_collective_fully_exposed(self):
        events = device_meta() + [ev("all-reduce.2", 0, 50)]
        d = decompose(events, steps=1)
        (row,) = d.collectives
        assert row["exposed_ms"] == pytest.approx(0.050)
        assert row["hidden_ms"] == 0.0
        assert d.terms["grad_reduce"] == pytest.approx(0.050)

    def test_unmatched_start_counts_exposed(self):
        events = device_meta() + [ev("all-reduce-start.9", 0, 30)]
        d = decompose(events, steps=1)
        assert d.terms["grad_reduce"] == pytest.approx(0.030)

    def test_replica_groups_pick_tensor_axis(self):
        mesh = tensor_mesh()
        rg = "replica_groups={{0,1,2,3},{4,5,6,7}}"
        events = device_meta() + [
            ev("all-reduce.1", 0, 40, long_name=f"all-reduce.1 {rg}")]
        d = decompose(events, steps=1, mesh=mesh)
        (row,) = d.collectives
        assert row["axes"] == ["tensor"]
        assert row["term"] == "tp_reduce"
        assert row["leg"] == "ici"
        assert d.terms["tp_reduce"] == pytest.approx(0.040)

    def test_data_outer_groups_are_the_dcn_leg(self):
        mesh = outer_mesh()
        rg = "replica_groups={{0,4},{1,5},{2,6},{3,7}}"
        events = device_meta() + [
            ev("all-reduce.1", 0, 40, long_name=f"all-reduce.1 {rg}")]
        d = decompose(events, steps=1, mesh=mesh)
        (row,) = d.collectives
        assert row["axes"] == ["data_outer"]
        assert row["leg"] == "dcn"
        assert row["term"] == "grad_reduce"

    def test_all_to_all_is_expert_term(self):
        events = device_meta() + [ev("all-to-all.4", 0, 20)]
        d = decompose(events, steps=1)
        assert d.terms["expert_a2a"] == pytest.approx(0.020)

    def test_permute_defaults_by_mesh_shape(self):
        events = device_meta() + [ev("collective-permute.2", 0, 10)]
        # seq-parallel mesh, no pipe -> ring rotation
        devs = np.array(jax.devices()[:2]).reshape(2)
        seq_mesh = jax.sharding.Mesh(devs, ("seq",))
        d = decompose(events, steps=1, mesh=seq_mesh)
        assert d.terms["ring_rotate"] == pytest.approx(0.010)
        # no mesh knowledge -> pipe handoff default
        d2 = decompose(events, steps=1)
        assert d2.terms["pipe_handoff"] == pytest.approx(0.010)


# ------------------------------------------------------------ host copies
class TestHostCopies:
    def test_host_copy_async_window_is_offload(self):
        events = device_meta() + [
            ev("copy-start.3", 0, 10, long_name="copy-start.3 S(5)"),
            ev("copy-done.3", 40, 5, long_name="copy-done.3 S(5)"),
        ]
        d = decompose(events, steps=1)
        # window 45, gap 30 hidden -> 15us exposed staging
        assert d.terms["host_offload"] == pytest.approx(0.015)
        assert d.host_copy_ms == pytest.approx(0.015)

    def test_sync_host_copy(self):
        events = device_meta() + [
            ev("copy.7", 0, 25, long_name="copy.7 S(5){1,0}")]
        d = decompose(events, steps=1)
        assert d.terms["host_offload"] == pytest.approx(0.025)

    def test_device_copy_is_unmodeled_layout(self):
        events = device_meta() + [
            ev("copy.8", 0, 25), ev("transpose.2", 30, 15)]
        d = decompose(events, steps=1)
        assert d.unmodeled["copy_layout"] == pytest.approx(0.040)
        assert d.terms["host_offload"] == 0.0
        # unmodeled time drags coverage below 100
        assert d.coverage_pct == 0.0


# ---------------------------------------------------------------- kernels
class TestKernels:
    def test_pallas_time_keyed_by_kernel_name(self):
        events = device_meta() + [
            ev("custom-call.7", 0, 80,
               long_name="custom-call.7 jit(train_step)/"
               "dstpu.attn.flash/dstpu.kernel.flash_fwd_t/pallas_call"),
            ev("custom-call.9", 100, 20,
               long_name="custom-call.9 dstpu.moe.experts/"
               "dstpu.kernel.gmm/pallas_call"),
        ]
        d = decompose(events, steps=1)
        assert d.kernels == {
            "flash_fwd_t": pytest.approx(0.080),
            "gmm": pytest.approx(0.020)}
        # kernel time is still compute (a breakdown, not a new term)
        assert d.terms["compute"] == pytest.approx(0.100)


# ---------------------------------------------------------- track selection
class TestTracks:
    @pytest.mark.parametrize("thread_name", [
        "tf_XLATfrtCpuClient/5", "tf_XLAPjRtCpuClient/5"])   # jax 0.9's
    def test_cpu_client_fallback_filters_runtime_frames(self, thread_name):
        events = [
            proc(2, "/host:CPU"), thread(2, 20, thread_name),
            ev("dot.3", 0, 50, pid=2, tid=20),
            ev("TfrtCpuExecutable::Execute", 0, 500, pid=2, tid=20),
            ev("ParseArguments", 60, 10, pid=2, tid=20),
        ]
        d = decompose(events, steps=1)
        assert d.cpu_fallback is True
        assert d.terms["compute"] == pytest.approx(0.050)
        ops = {r["op"] for r in d.per_op}
        assert "TfrtCpuExecutable::Execute" not in ops

    def test_device_track_wins_over_cpu_threads(self):
        events = device_meta() + [
            proc(2, "/host:CPU"), thread(2, 20, "tf_XLATfrtCpuClient/1"),
            ev("dot.1", 0, 50),
            ev("dot.9", 0, 999, pid=2, tid=20),
        ]
        d = decompose(events, steps=1)
        assert d.cpu_fallback is False
        assert d.terms["compute"] == pytest.approx(0.050)

    def test_no_tracks_returns_none(self):
        assert decompose([proc(3, "python")], steps=1) is None
        assert decompose([], steps=1) is None


# ------------------------------------------------------------- JSON schema
class TestSchema:
    def test_stable_field_set(self):
        events = device_meta() + [ev("dot.1", 0, 10)]
        d = decompose(events, steps=1)
        got = set(d.to_dict())
        assert got == {
            "schema", "steps", "trace_path", "device_tracks",
            "cpu_fallback", "total_device_ms", "terms", "unmodeled",
            "collectives", "kernels", "per_op", "host_copy_ms",
            "collective_total_ms", "collective_exposed_ms",
            "collective_hidden_ms", "occupancy_pct", "span_ms",
            "coverage_pct"}
        assert d.to_dict()["schema"] == step_trace.SCHEMA_VERSION
        parsed = json.loads(d.to_json())
        assert parsed["terms"]["compute"] == pytest.approx(0.010)

    def test_terms_keys_are_the_full_vocabulary(self):
        d = decompose(device_meta() + [ev("dot.1", 0, 10)], steps=1)
        assert set(d.terms) == set(DECOMP_TERMS)
        assert set(d.unmodeled) == set(UNMODELED_KEYS)


# ----------------------------------------------------------- io + degrade
class TestTraceIO:
    def test_find_and_decompose_dir(self, tmp_path):
        path = write_trace(str(tmp_path),
                           device_meta() + [ev("dot.1", 0, 10)])
        assert find_trace_file(str(tmp_path)) == path
        assert find_trace_file(path) == path
        d = decompose_dir(str(tmp_path), steps=1)
        assert d is not None and d.trace_path == path

    def test_missing_trace_degrades_to_none(self, tmp_path, caplog):
        assert decompose_dir(str(tmp_path / "nope")) is None
        assert find_trace_file(str(tmp_path)) is None

    def test_corrupt_trace_never_raises(self, tmp_path):
        d = os.path.join(str(tmp_path), "plugins", "profile", "x")
        os.makedirs(d)
        with gzip.open(os.path.join(d, "bad.trace.json.gz"), "wt") as f:
            f.write("{not json")
        assert decompose_dir(str(tmp_path)) is None


# ----------------------------------------------------------- CLI surfaces
def _load_trace_summary():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "trace_summary", os.path.join(REPO, "benchmarks",
                                      "trace_summary.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestTraceSummaryCLI:
    def _trace(self, tmp_path):
        return write_trace(str(tmp_path), device_meta() + [
            ev("fusion.1", 0, 100),
            ev("dot.2", 10, 40),
            ev("all-reduce.3", 120, 30),
        ])

    def test_human_table_default(self, tmp_path, capsys):
        ts = _load_trace_summary()
        self._trace(tmp_path)
        assert ts.main([str(tmp_path), "--steps", "1"]) == 0
        out = capsys.readouterr().out
        assert "fusion.1" in out
        assert "families (ms/step):" in out
        assert "planner terms (exposed ms/step):" in out
        assert "grad_reduce" in out

    def test_json_output_is_the_decomposition(self, tmp_path, capsys):
        ts = _load_trace_summary()
        self._trace(tmp_path)
        assert ts.main([str(tmp_path), "--steps", "1", "--json"]) == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed["schema"] == step_trace.SCHEMA_VERSION
        assert parsed["terms"]["grad_reduce"] == pytest.approx(0.030)

    def test_positional_steps_compat(self, tmp_path, capsys):
        ts = _load_trace_summary()
        self._trace(tmp_path)
        assert ts.main([str(tmp_path), "2"]) == 0
        assert "over 2 steps" in capsys.readouterr().out
