"""Tier-1 pipeline coverage, cheap to compile.

The SPMD pipeline tests with auto axes > 1 (test_pipe.py) are
compile-heavy and marked slow. Here the schedule streams are pure
python, and the executor tests use a pipe-ONLY virtual mesh (every
non-pipe axis size 1).

Covers ISSUE-10's structural acceptance bars:
the ZB-H1 tick order (schedule stream vs the executor's index maps),
W-pass work occupying the drain ticks, the executor bubble model
strictly below the GPipe figure, and pp=2 loss/grad parity of the
1F1B and zero-bubble executors against the single-stage program.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.runtime.pipe import (
    TrainSchedule, ZeroBubbleSchedule, ForwardPass, BackwardActGrad,
    BackwardWeightGrad, ReduceGrads, OptimizerStep,
    executor_bubble_fraction, executor_tick_units,
    pipeline_1f1b_grads, pipeline_zb_grads, PipeOffload)
from deepspeed_tpu.runtime.pipe.spmd import (
    zb_b_index, zb_deferred_window, zb_f_index, zb_num_ticks,
    zb_w_deferred_index)
from deepspeed_tpu.runtime.swap_tensor import host_stage
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.groups import TopologyConfig

SHAPES = [(2, 2), (2, 4), (2, 8), (4, 4), (4, 8), (3, 5), (4, 3)]


# ------------------------------------------------------ schedule stream
class TestZeroBubbleStream:
    @pytest.mark.parametrize("S,M", SHAPES)
    def test_tick_parity_with_executor_maps(self, S, M):
        """The acceptance-bar tick test: the imperative ZB-H1 stream
        (schedule.py, written in the reference phase style) and the SPMD
        executor's affine index maps (spmd.py zb_*_index — the traced
        masks) must describe the SAME per-(stage, tick) op multiset."""
        for s in range(S):
            sched = ZeroBubbleSchedule(M, S, s)
            K = zb_deferred_window(s, M, S)
            assert K == sched.deferred_window()
            for t in range(zb_num_ticks(M, S)):
                want = []
                f = zb_f_index(t, s, M, S)
                if 0 <= f < M:
                    want.append(("F", f))
                b = zb_b_index(t, s, M, S)
                if 0 <= b < M:
                    want.append(("B", b))
                    if b < M - K:
                        want.append(("W", b))
                w = zb_w_deferred_index(t, s, M, S)
                if max(M - K, 0) <= w < M:
                    want.append(("W", w))
                assert sched.tick_ops(t) == want, (s, t)

    @pytest.mark.parametrize("S,M", SHAPES)
    def test_complete_and_causal(self, S, M):
        """Every microbatch gets exactly one F, one B and one W per
        stage; B(m) never precedes F(m); W(m) never precedes B(m)."""
        for s in range(S):
            sched = ZeroBubbleSchedule(M, S, s)
            seen = {"F": {}, "B": {}, "W": {}}
            for t in range(sched.num_ticks()):
                for kind, m in sched.tick_ops(t):
                    assert m not in seen[kind], (kind, m)
                    seen[kind][m] = t
            for kind in seen:
                assert set(seen[kind]) == set(range(M)), (s, kind)
            for m in range(M):
                assert seen["F"][m] <= seen["B"][m] <= seen["W"][m]

    @pytest.mark.parametrize("S,M", [(2, 4), (4, 8), (3, 6)])
    def test_w_occupies_drain_ticks(self, S, M):
        """The zero-bubble property, structurally: each non-final
        stage's forward-drain ticks (t >= s + M, where 1F1B burns
        garbage forwards) carry deferred W work instead."""
        for s in range(S - 1):
            sched = ZeroBubbleSchedule(M, S, s)
            drain = range(s + M, sched.num_ticks())
            assert len(list(drain)) > 0
            for t in drain:
                kinds = [k for k, _ in sched.tick_ops(t)]
                assert "F" not in kinds
                assert "W" in kinds, (s, t)

    def test_steps_instruction_stream(self):
        scheds = [ZeroBubbleSchedule(4, 2, s) for s in range(2)]
        for sched in scheds:
            steps = list(sched)
            assert steps[-1] == [ReduceGrads(), OptimizerStep()]
            flat = [i for st in steps for i in st]
            assert sum(isinstance(i, ForwardPass) for i in flat) == 4
            assert sum(isinstance(i, BackwardActGrad)
                       for i in flat) == 4
            assert sum(isinstance(i, BackwardWeightGrad)
                       for i in flat) == 4

    def test_buffers_bounded_by_stages_not_microbatches(self):
        assert ZeroBubbleSchedule(64, 4, 0).num_pipe_buffers() == \
            ZeroBubbleSchedule(8, 4, 0).num_pipe_buffers()


# ------------------------------------------------------- bubble model
class TestBubbleModel:
    @pytest.mark.parametrize("S,M", [(2, 4), (2, 8), (4, 8), (4, 16),
                                     (8, 16)])
    def test_zb_strictly_below_gpipe(self, S, M):
        """The acceptance bar: the zero-bubble executor's bubble
        fraction is strictly below the GPipe (S-1)/(M+S-1) figure."""
        gp = executor_bubble_fraction("gpipe", M, S)
        assert gp == pytest.approx((S - 1) / (M + S - 1))
        assert executor_bubble_fraction("zb", M, S) < gp

    def test_1f1b_executor_is_flat(self):
        # the unconditional-lane executor: 3 units every tick
        assert executor_tick_units("1f1b", 8, 4) == [3] * (8 + 6)

    def test_known_point(self):
        # hand-checked S=4, M=8: gpipe wall 33, zb wall 30
        assert sum(executor_tick_units("gpipe", 8, 4)) == 33
        assert sum(executor_tick_units("zb", 8, 4)) == 30
        assert executor_bubble_fraction("zb", 8, 4) == \
            pytest.approx(1 - 24 / 30)

    def test_train_schedule_bubble_unchanged(self):
        assert TrainSchedule(8, 4, 0).bubble_fraction() == \
            pytest.approx(3 / 11)


# ------------------------------------------------ executor parity pp=2
def _pipe_only_mesh(S):
    groups.reset()
    topo = groups.initialize(
        TopologyConfig(pipe_parallel_size=S, data_parallel_size=1),
        devices=jax.devices()[:S], force=True)
    return topo.mesh


def _toy_problem(S, M, L=4, D=8, B=2, seed=0):
    rng = np.random.RandomState(seed)
    w = jnp.asarray(rng.randn(L, D, D) * 0.2, jnp.float32)
    x = jnp.asarray(rng.randn(M, B, D), jnp.float32)
    tgt = jnp.asarray(rng.randn(M, B, D), jnp.float32)
    aux = jnp.zeros((L, 1), jnp.uint32)
    hp = jnp.asarray(rng.randn(D) * 0.3, jnp.float32)

    def block(c, wi, a):
        return jnp.tanh(c @ wi)

    def head_loss(h, y, t):
        return jnp.mean((y * h - jax.lax.stop_gradient(t)) ** 2)

    def ref_loss(w, hp, x):
        def f(c, wi):
            return block(c, wi, None), None

        def run(mb):
            y, _ = jax.lax.scan(f, mb, w)
            return y
        y = jax.vmap(run)(x)
        return jnp.mean(jax.vmap(
            lambda ym, tm: head_loss(hp, ym, tm))(y, tgt))

    return w, x, tgt, aux, hp, block, head_loss, ref_loss


class TestSteadyStateExecutorsPP2:
    """pp=2 loss/grad parity on a pipe-only virtual mesh (no auto axis
    > 1 in the partial-manual program)."""

    @pytest.mark.parametrize("fn,kw", [
        (pipeline_1f1b_grads, {}),
        (pipeline_zb_grads, {}),
        (pipeline_zb_grads, {"offload": PipeOffload(activations=True)}),
        (pipeline_zb_grads, {"offload": PipeOffload(
            activations=True, double_buffer=False)}),
    ], ids=["1f1b", "zb", "zb_offload", "zb_offload_nodb"])
    def test_matches_sequential(self, fn, kw):
        S, M = 2, 4
        mesh = _pipe_only_mesh(S)
        (w, x, tgt, aux, hp, block, head_loss,
         ref_loss) = _toy_problem(S, M)
        l_ref, g_ref = jax.value_and_grad(ref_loss, (0, 1, 2))(w, hp, x)
        with jax.set_mesh(mesh):
            ws = jax.device_put(w, NamedSharding(mesh, P("pipe")))
            auxs = jax.device_put(aux, NamedSharding(mesh, P("pipe")))
            xs = jax.device_put(x, NamedSharding(mesh, P()))
            loss, (dl, dh, dx) = jax.jit(
                lambda w_, a_, h_, x_: fn(
                    block, head_loss, w_, a_, h_, x_, tgt, **kw))(
                        ws, auxs, hp, xs)
        assert float(loss) == pytest.approx(float(l_ref), abs=1e-5)
        for got, want, name in ((dl, g_ref[0], "dlayers"),
                                (dh, g_ref[1], "dhead"),
                                (dx, g_ref[2], "dx")):
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want),
                                       rtol=1e-5, atol=1e-6,
                                       err_msg=name)

    def test_zb_odd_microbatches_small_m(self):
        """M < 2(S-1) clamps the deferral window; parity must hold."""
        S, M = 2, 2
        mesh = _pipe_only_mesh(S)
        (w, x, tgt, aux, hp, block, head_loss,
         ref_loss) = _toy_problem(S, M)
        l_ref = float(ref_loss(w, hp, x))
        with jax.set_mesh(mesh):
            ws = jax.device_put(w, NamedSharding(mesh, P("pipe")))
            auxs = jax.device_put(aux, NamedSharding(mesh, P("pipe")))
            loss, _ = jax.jit(lambda w_, a_, h_, x_: pipeline_zb_grads(
                block, head_loss, w_, a_, h_, x_, tgt))(
                    ws, auxs, hp, x)
        assert float(loss) == pytest.approx(l_ref, abs=1e-5)


# ------------------------------------------------------ engine-level
class TestGPT2PipeEnginePP2:
    """End-to-end pp=2 engine parity on the pipe-only mesh: the
    tier-1-runnable slice of what test_pipe.py's slow xfail tests cover
    at data > 1."""

    def _run(self, model_cls, pipe, sched=None, batches=2):
        import deepspeed_tpu
        from deepspeed_tpu.models import GPT2, GPT2Pipe  # noqa: F401
        from deepspeed_tpu.models.gpt2 import GPT2Config
        cfg = GPT2Config(n_layer=2, n_head=4, d_model=64, max_seq_len=32,
                         vocab_size=256, dtype="float32", remat=True,
                         pipe_microbatches=4)
        groups.reset()
        topo = groups.initialize(
            TopologyConfig(pipe_parallel_size=pipe,
                           data_parallel_size=1),
            devices=jax.devices()[:max(pipe, 1)], force=True)
        conf = {"train_micro_batch_size_per_gpu": 8,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0}}
        if sched:
            conf["pipeline"] = {"schedule": sched}
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model_cls(cfg), topology=topo, config=conf)
        ids = np.random.RandomState(0).randint(
            0, 256, (batches, 8, 32)).astype(np.int32)
        return engine, [float(engine.train_batch({"input_ids": b}))
                        for b in ids]

    def test_zb_engine_matches_dense(self):
        from deepspeed_tpu.models import GPT2, GPT2Pipe
        _, ref = self._run(GPT2, 1)
        engine, zb = self._run(GPT2Pipe, 2, "zb")
        np.testing.assert_allclose(zb, ref, rtol=2e-4)
        rep = engine.pipeline_report()
        assert rep["schedule"] == "zb" and rep["stages"] == 2
        assert rep["bubble_pct"] < rep["gpipe_bubble_pct"]

    def test_verify_report_has_pipeline_and_rotation(self):
        from deepspeed_tpu.models import GPT2Pipe
        engine, _ = self._run(GPT2Pipe, 2, "zb", batches=1)
        ids = np.random.RandomState(1).randint(
            0, 256, (8, 32)).astype(np.int32)
        rep = engine.verify_comm_overlap({"input_ids": ids})
        # the steady-state stage rotation is IN the scan loop
        assert rep["in_loop_by_op"].get("collective-permute", 0) >= 1
        assert "host_copies" in rep
        p = rep["pipeline"]
        assert p["bubble_pct"] < p["gpipe_bubble_pct"]


# -------------------------------------------------------- host staging
class TestHostStage:
    def test_platform_contract(self):
        """The CPU backend lists pinned_host/unpinned_host beside its
        default memory but compiles no placement, so staging must report
        itself unavailable there and degrade to an identity; a platform
        whose host kind is placeable (TPU) reports that kind."""
        dev = jax.devices()[0]
        listed = {m.kind for m in dev.addressable_memories()}
        default, host = host_stage.memory_kinds()
        assert default == dev.default_memory().kind
        if dev.platform == "cpu":
            assert host is None and not host_stage.available()
            x = jnp.ones((4,))
            # identity degradation: same value, usable under jit
            np.testing.assert_array_equal(
                np.asarray(host_stage.to_host(x)), np.asarray(x))
            y = jax.jit(lambda v: host_stage.to_device(
                host_stage.to_host(v)) * 2)(x)
            np.testing.assert_array_equal(np.asarray(y), 2 * np.ones(4))
            assert y.sharding.memory_kind == default
        else:
            assert host in listed and host != default
            assert host_stage.available()

    def test_with_host_memory_kind_passthrough_on_single_space(self):
        mesh = _pipe_only_mesh(2)
        sh = NamedSharding(mesh, P())
        out = host_stage.with_host_memory_kind(sh)
        if host_stage.host_memory_kind() is None:
            assert out is sh
        else:
            assert out.memory_kind == host_stage.host_memory_kind()

    def test_offload_policy_degrades_cleanly(self):
        from deepspeed_tpu.runtime.activation_checkpointing import (
            checkpointing as ckpt)
        pol = ckpt.offload_policy()
        if host_stage.host_memory_kind() is None:
            assert pol is None
            # cpu_checkpointing falls back to the remat policy
            assert ckpt.resolve_policy("nothing_saveable",
                                       cpu_checkpointing=True) is not None
        else:
            assert pol is not None


# ------------------------------------------------------- 13B tracing
class Test13BConfig:
    def test_13b_traces_pp2_zb_with_offload(self):
        """The 13B point traces (shape-level) at pp=2 under the
        zero-bubble schedule with activation offload requested — the
        'traces' half of the acceptance bar; the 'runs' half is the
        multichip artifact's pipe row and the probe's offload A/B
        (real byte movement needs a backend with a host memory kind;
        on CPU the staging is identity by design)."""
        import types
        from dataclasses import replace
        from deepspeed_tpu.models import GPT2Pipe
        from deepspeed_tpu.models.gpt2 import PRESETS
        cfg = replace(PRESETS["13B"], dtype="bfloat16", remat=True,
                      pipe_microbatches=4, use_flash_attention=False)
        assert cfg.num_params() > 12e9
        model = GPT2Pipe(cfg)
        model._pipe_cfg = types.SimpleNamespace(
            schedule="zb", micro_batches=4, offload_activations=True,
            offload_moments=False, offload_double_buffer=True)
        groups.reset()
        topo = groups.initialize(
            TopologyConfig(pipe_parallel_size=2, data_parallel_size=1),
            devices=jax.devices()[:2], force=True)
        ids = jax.ShapeDtypeStruct((8, cfg.max_seq_len), jnp.int32)
        with jax.set_mesh(topo.mesh):
            params = jax.eval_shape(model.init, jax.random.key(0))
            out = jax.eval_shape(
                lambda p, i: model.loss(p, {"input_ids": i},
                                        rng=jax.random.key(1)),
                params, ids)
        assert out.shape == () and out.dtype == jnp.float32

    def test_hbm_fit_heuristic_flags_13b_on_small_chip(self):
        """The offload 'auto' decision chain: a 13B state estimate
        does not fit a 16 GB chip at pp=2, so with a host memory kind
        present 'auto' turns offload on; an unknown budget never
        does."""
        from deepspeed_tpu.runtime.config import PipelineConfig
        p = PipelineConfig()
        n = 12.85e9
        est = n * (2 + 4) / 2 + n * 12 / 2   # bf16+fp32grad, fp32 opt
        hbm = 16 << 30
        assert not p.hbm_fits(est, hbm)
        assert p.resolve_offload_activations(
            True, pipe_world=2, est_state_bytes=est, hbm_bytes=hbm)
        # unknown HBM -> fits -> auto stays off; unavailable -> off
        assert not p.resolve_offload_activations(
            True, pipe_world=2, est_state_bytes=est, hbm_bytes=None)
        assert not p.resolve_offload_activations(
            False, pipe_world=2, est_state_bytes=est, hbm_bytes=hbm)


# ------------------------------------------------- flight recorder pp
class TestPipeRestoreFlightRecorder:
    def test_pp2_restore_after_reshape_recorded(self, tmp_path):
        """Save under dp=1, restore onto a pp=2 topology: the flight
        recorder must carry the reshape (with the pp>1 topology) and
        the restore tier — the record a post-restore crash dump needs."""
        import deepspeed_tpu
        from deepspeed_tpu.models import GPT2, GPT2Pipe
        from deepspeed_tpu.models.gpt2 import GPT2Config
        cfg = GPT2Config(n_layer=2, n_head=4, d_model=64,
                         max_seq_len=32, vocab_size=256,
                         dtype="float32", remat=False,
                         pipe_microbatches=2)
        base = {"train_micro_batch_size_per_gpu": 4,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0},
                "telemetry": {"enabled": True, "interval_steps": 1}}
        ids = np.random.RandomState(0).randint(
            0, 256, (4, 32)).astype(np.int32)
        groups.reset()
        topo = groups.initialize(
            TopologyConfig(data_parallel_size=1),
            devices=jax.devices()[:1], force=True)
        e1, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2(cfg), topology=topo, config=base)
        e1.train_batch({"input_ids": ids})
        e1.save_checkpoint(str(tmp_path))

        groups.reset()
        topo2 = groups.initialize(
            TopologyConfig(pipe_parallel_size=2, data_parallel_size=1),
            devices=jax.devices()[:2], force=True)
        e2, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2Pipe(cfg), topology=topo2,
            config={**base, "pipeline": {"schedule": "zb"}})
        path, _ = e2.load_checkpoint(str(tmp_path))
        assert path is not None
        events = e2.telemetry.flight.events()
        kinds = [e["kind"] for e in events]
        assert "restore" in kinds
        reshapes = [e for e in events if e["kind"] == "reshape"]
        assert reshapes, kinds
        assert reshapes[-1]["current"]["pipe"] == 2
        # and the pp=2 engine still trains after the reshaped restore
        loss = float(e2.train_batch({"input_ids": ids}))
        assert np.isfinite(loss)
