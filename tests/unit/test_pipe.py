"""Pipeline parallelism tests.

Mirrors the reference's split (SURVEY §4): pure-python schedule/topology
unit tests (tests/unit/runtime/pipe/test_topology.py style) plus end-to-end
pipelined training on a real multi-device mesh, asserting numerical parity
with the non-pipelined model — a stronger check than the reference's
loss-goes-down test.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.runtime.pipe import (
    ProcessTopology, PipeDataParallelTopology, PipelineParallelGrid,
    TrainSchedule, InferenceSchedule, LayerSpec, TiedLayerSpec,
    PipelineModule, ForwardPass, BackwardPass, SendActivation,
    RecvActivation, SendGrad, RecvGrad, ReduceGrads, OptimizerStep,
    spmd_pipeline)
from deepspeed_tpu.runtime.pipe.module import partition_balanced
from deepspeed_tpu.runtime.pipe.spmd import (split_microbatches,
                                             merge_microbatches)
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.groups import TopologyConfig

# compile-heavy: excluded from the fast core set (pytest -m 'not slow')
pytestmark = pytest.mark.slow


# ---------------------------------------------------------------- topology
class TestProcessTopology:
    def test_rank_coord_roundtrip(self):
        topo = ProcessTopology(["pipe", "data"], [2, 4])
        assert topo.world_size == 8
        for r in range(8):
            c = topo.get_coord(r)
            assert topo.get_rank(pipe=c.pipe, data=c.data) == r

    def test_row_major(self):
        # first axis slowest — matches Mesh device order
        topo = ProcessTopology(["pipe", "data"], [2, 3])
        assert topo.get_rank(pipe=0, data=0) == 0
        assert topo.get_rank(pipe=0, data=2) == 2
        assert topo.get_rank(pipe=1, data=0) == 3

    def test_comm_lists(self):
        topo = PipeDataParallelTopology(2, 4)
        pipe_groups = topo.get_axis_comm_lists("pipe")
        assert len(pipe_groups) == 4
        for g in pipe_groups:
            assert len(g) == 2
        # each rank in exactly one group
        all_ranks = sorted(r for g in pipe_groups for r in g)
        assert all_ranks == list(range(8))

    def test_filter_match(self):
        topo = PipeDataParallelTopology(2, 4)
        assert topo.filter_match(pipe=1) == [4, 5, 6, 7]

    def test_grid(self):
        topo = PipeDataParallelTopology(4, 2)
        grid = PipelineParallelGrid(topo, rank=5)
        assert grid.get_stage_id() == 2
        assert grid.get_data_parallel_id() == 1
        assert grid.stage_to_global(3) == 7
        assert not grid.is_first_stage() and not grid.is_last_stage()
        assert grid.ppermute_perm() == [(0, 1), (1, 2), (2, 3), (3, 0)]


# ---------------------------------------------------------------- schedule
def _simulate(schedules):
    """Execute per-stage instruction streams against FIFO channels; assert
    the dataflow is deadlock-free and yields each microbatch's F before its
    B on every stage. Returns per-stage executed order."""
    S = len(schedules)
    streams = [list(sched) for sched in schedules]  # lists of steps
    # flatten to instruction queues
    queues = [[i for step in s for i in step] for s in streams]
    acts = [[] for _ in range(S + 1)]   # acts[s] = channel s-1 -> s
    grads = [[] for _ in range(S + 1)]  # grads[s] = channel s -> s-1
    done_f = [set() for _ in range(S)]
    done_b = [set() for _ in range(S)]
    executed = [[] for _ in range(S)]
    pos = [0] * S
    progress = True
    while progress:
        progress = False
        for s in range(S):
            while pos[s] < len(queues[s]):
                ins = queues[s][pos[s]]
                if isinstance(ins, RecvActivation):
                    if not acts[s] or acts[s][0] != ins.micro_batch:
                        break
                    acts[s].pop(0)
                elif isinstance(ins, RecvGrad):
                    if not grads[s + 1] or grads[s + 1][0] != ins.micro_batch:
                        break
                    grads[s + 1].pop(0)
                elif isinstance(ins, SendActivation):
                    acts[s + 1].append(ins.micro_batch)
                elif isinstance(ins, SendGrad):
                    grads[s].append(ins.micro_batch)
                elif isinstance(ins, ForwardPass):
                    assert ins.micro_batch not in done_f[s]
                    if s > 0:
                        assert ins.micro_batch in done_f[s - 1]
                    done_f[s].add(ins.micro_batch)
                elif isinstance(ins, BackwardPass):
                    assert ins.micro_batch in done_f[s], "B before F"
                    if s < S - 1:
                        assert ins.micro_batch in done_b[s + 1]
                    done_b[s].add(ins.micro_batch)
                executed[s].append(ins)
                pos[s] += 1
                progress = True
    for s in range(S):
        assert pos[s] == len(queues[s]), f"stage {s} deadlocked at {pos[s]}"
    return done_f, done_b


class TestTrainSchedule:
    @pytest.mark.parametrize("stages,micro", [(2, 2), (2, 4), (4, 4),
                                              (4, 8), (3, 5), (1, 3)])
    def test_1f1b_dataflow(self, stages, micro):
        scheds = [TrainSchedule(micro, stages, s) for s in range(stages)]
        done_f, done_b = _simulate(scheds)
        for s in range(stages):
            assert done_f[s] == set(range(micro))
            assert done_b[s] == set(range(micro))

    def test_warmup_depth(self):
        # peak in-flight = min(S - s, M): the 1F1B memory property
        sched = TrainSchedule(8, 4, 0)
        assert sched.num_pipe_buffers() == 4
        sched = TrainSchedule(8, 4, 3)
        assert sched.num_pipe_buffers() == 1
        sched = TrainSchedule(2, 4, 0)
        assert sched.num_pipe_buffers() == 2

    def test_last_stage_alternates(self):
        sched = TrainSchedule(4, 4, 3)
        kinds = [type(i).__name__ for step in sched for i in step
                 if isinstance(i, (ForwardPass, BackwardPass))]
        assert kinds == ["ForwardPass", "BackwardPass"] * 4

    def test_ends_with_step(self):
        steps = list(TrainSchedule(2, 2, 0))
        assert steps[-1] == [ReduceGrads(), OptimizerStep()]

    def test_bubble_fraction(self):
        assert TrainSchedule(8, 4, 0).bubble_fraction() == pytest.approx(
            3 / 11)


class TestInferenceSchedule:
    def test_forward_only(self):
        scheds = [InferenceSchedule(4, 3, s) for s in range(3)]
        for sched in scheds:
            for step in sched:
                for ins in step:
                    assert not isinstance(ins, (BackwardPass, SendGrad,
                                                RecvGrad))
        done_f, _ = _simulate(scheds)
        for s in range(3):
            assert done_f[s] == set(range(4))


# ------------------------------------------------------------------ module
class _Affine:
    def __init__(self, dim, scale=1.0):
        self.dim = dim
        self.scale = scale

    def init(self, rng):
        return {"w": jax.random.normal(rng, (self.dim, self.dim)) * 0.1}

    def apply(self, params, x):
        return jnp.tanh(x @ params["w"] * self.scale)


class TestPartitionBalanced:
    def test_uniform(self):
        assert partition_balanced([1] * 8, 4) == [0, 2, 4, 6, 8]

    def test_weighted(self):
        bounds = partition_balanced([10, 1, 1, 1, 1, 10], 2)
        # best split keeps the two heavy layers apart
        assert bounds[0] == 0 and bounds[-1] == 6
        w = [10, 1, 1, 1, 1, 10]
        sums = [sum(w[bounds[i]:bounds[i + 1]]) for i in range(2)]
        assert max(sums) == 12  # optimal: [10,1,1] | [1,1,10]

    def test_each_part_nonempty(self):
        for n, p in [(4, 4), (5, 3), (9, 4)]:
            bounds = partition_balanced([1] * n, p)
            assert len(bounds) == p + 1
            assert all(bounds[i] < bounds[i + 1] for i in range(p))


class TestPipelineModule:
    def test_partition_uniform(self):
        mod = PipelineModule([LayerSpec(_Affine, 8) for _ in range(8)],
                             num_stages=4, partition_method="uniform")
        assert mod.parts == [0, 2, 4, 6, 8]
        assert mod.stage_of_layer(5) == 2

    def test_partition_parameters(self):
        layers = [LayerSpec(_Affine, 32)] + \
                 [LayerSpec(_Affine, 8) for _ in range(3)]
        mod = PipelineModule(layers, num_stages=2,
                             partition_method="parameters")
        # the big layer gets its own stage
        assert mod.parts[1] == 1

    def test_partition_type_regex(self):
        class Marker(_Affine):
            pass
        layers = [LayerSpec(_Affine, 4), LayerSpec(Marker, 4),
                  LayerSpec(_Affine, 4), LayerSpec(Marker, 4)]
        mod = PipelineModule(layers, num_stages=2,
                             partition_method="type:marker")
        counts = [sum(1 for i in mod.stage_layer_indices(s)
                      if isinstance(mod.layers[i], Marker))
                  for s in range(2)]
        assert counts == [1, 1]

    def test_apply_matches_manual(self):
        mod = PipelineModule([LayerSpec(_Affine, 6) for _ in range(4)],
                             num_stages=2)
        params = mod.init(jax.random.key(0))
        x = jnp.ones((2, 6))
        y = mod.apply(params, x)
        # stagewise composition gives the same result
        h = mod.apply_stage(params, x, 0)
        y2 = mod.apply_stage(params, h, 1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-6)

    def test_tied_layers_share_params(self):
        layers = [TiedLayerSpec("emb", _Affine, 6),
                  LayerSpec(_Affine, 6),
                  TiedLayerSpec("emb", _Affine, 6)]
        mod = PipelineModule(layers, num_stages=1)
        params = mod.init(jax.random.key(0))
        assert params[2] is None  # ties back to layer 0
        y = mod.apply(params, jnp.ones((2, 6)))
        assert y.shape == (2, 6)


# ----------------------------------------------------------- spmd executor
def _make_mesh(pipe, data):
    groups.reset()
    topo = groups.initialize(TopologyConfig(
        pipe_parallel_size=pipe, data_parallel_size=data), force=True)
    return topo.mesh


class TestSpmdPipeline:
    def test_matches_sequential(self):
        mesh = _make_mesh(pipe=2, data=4)
        L, D, M, B = 4, 16, 3, 8
        rng = np.random.RandomState(0)
        w = jnp.asarray(rng.randn(L, D, D) * 0.2, jnp.float32)
        x = jnp.asarray(rng.randn(M, B, D), jnp.float32)

        def block(x, w):
            return jnp.tanh(x @ w)

        def ref(w, x):
            def f(c, wi):
                return block(c, wi), None
            y, _ = jax.lax.scan(f, x, w)
            return y
        expect = jax.vmap(lambda mb: ref(w, mb))(x)

        with jax.set_mesh(mesh):
            ws = jax.device_put(w, NamedSharding(mesh, P("pipe")))
            xs = jax.device_put(x, NamedSharding(mesh, P(None, "data")))
            out = jax.jit(lambda w, x: spmd_pipeline(block, w, x))(ws, xs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)

    def test_grads_match_sequential(self):
        mesh = _make_mesh(pipe=2, data=4)
        L, D, M, B = 2, 8, 4, 4
        rng = np.random.RandomState(1)
        w = jnp.asarray(rng.randn(L, D, D) * 0.2, jnp.float32)
        x = jnp.asarray(rng.randn(M, B, D), jnp.float32)

        def block(x, w):
            return jnp.tanh(x @ w)

        def ref_loss(w, x):
            def f(c, wi):
                return block(c, wi), None
            def run(mb):
                y, _ = jax.lax.scan(f, mb, w)
                return y
            return jnp.sum(jax.vmap(run)(x) ** 2)

        g_ref = jax.grad(ref_loss)(w, x)
        with jax.set_mesh(mesh):
            ws = jax.device_put(w, NamedSharding(mesh, P("pipe")))
            xs = jax.device_put(x, NamedSharding(mesh, P(None, "data")))
            g = jax.jit(jax.grad(
                lambda w, x: jnp.sum(spmd_pipeline(block, w, x) ** 2)))(
                    ws, xs)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-5)

    def test_split_merge_roundtrip(self):
        x = jnp.arange(24.0).reshape(12, 2)
        mb = split_microbatches(x, 3)
        assert mb.shape == (3, 4, 2)
        np.testing.assert_array_equal(np.asarray(merge_microbatches(mb)),
                                      np.asarray(x))


# -------------------------------------------------------------- end-to-end
class TestGPT2Pipe:
    def _cfg(self, **kw):
        from deepspeed_tpu.models import GPT2Config
        base = dict(n_layer=4, n_head=4, d_model=64, max_seq_len=32,
                    vocab_size=256, dtype="float32", remat=False,
                    pipe_microbatches=2)
        base.update(kw)
        return GPT2Config(**base)

    def test_loss_matches_dense(self):
        from deepspeed_tpu.models import GPT2, GPT2Pipe
        cfg = self._cfg()
        dense, piped = GPT2(cfg), GPT2Pipe(cfg)
        params = dense.init(jax.random.key(0))
        ids = np.random.RandomState(0).randint(0, 256, (4, 32)).astype(
            np.int32)
        batch = {"input_ids": ids}
        loss_ref = float(dense.loss(params, batch, train=False))

        mesh = _make_mesh(pipe=2, data=4)
        with jax.set_mesh(mesh):
            specs = piped.partition_specs(groups.get_topology())
            sharded = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                params, specs, is_leaf=lambda x: isinstance(x, P))
            loss = float(jax.jit(
                lambda p: piped.loss(p, batch, train=False))(sharded))
        assert loss == pytest.approx(loss_ref, rel=1e-5)

    def test_engine_train_parity(self):
        """Pipelined engine training matches the dense engine step-for-step
        (same params, same data, fp32)."""
        import deepspeed_tpu
        from deepspeed_tpu.models import GPT2, GPT2Pipe

        ids = np.random.RandomState(0).randint(0, 256, (4, 8, 32)).astype(
            np.int32)

        def run(model_cls, pipe):
            groups.reset()
            topo = groups.initialize(TopologyConfig(
                pipe_parallel_size=pipe, data_parallel_size=-1), force=True)
            dp = topo.get_data_parallel_world_size()
            config = {
                # same global batch (8) whatever the pipe/data split
                "train_micro_batch_size_per_gpu": 8 // dp,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0},
            }
            model = model_cls(self._cfg())
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=model, topology=topo, config=config)
            losses = []
            for i in range(4):
                losses.append(float(engine.train_batch(
                    {"input_ids": ids[i]})))
            return losses

        ref = run(GPT2, pipe=1)
        got = run(GPT2Pipe, pipe=2)
        np.testing.assert_allclose(got, ref, rtol=2e-4)

    def test_zero_stages_with_pipe(self):
        """ZeRO partitioning composes with pipe sharding."""
        import deepspeed_tpu
        from deepspeed_tpu.models import GPT2Pipe

        ids = np.random.RandomState(1).randint(0, 256, (3, 4, 32)).astype(
            np.int32)
        losses = {}
        for stage in [0, 2, 3]:
            groups.reset()
            topo = groups.initialize(TopologyConfig(
                pipe_parallel_size=2, data_parallel_size=-1), force=True)
            model = GPT2Pipe(self._cfg())
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=model, topology=topo, config={
                    "train_micro_batch_size_per_gpu": 1,  # global batch 4
                    "gradient_accumulation_steps": 1,
                    "steps_per_print": 0,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": stage},
                })
            losses[stage] = [float(engine.train_batch({"input_ids": b}))
                             for b in ids]
        np.testing.assert_allclose(losses[2], losses[0], rtol=2e-4)
        np.testing.assert_allclose(losses[3], losses[0], rtol=2e-4)

    def test_pipe_with_tp(self):
        """pipe=2 x tensor=2 x data=2: 3D parallelism in one program."""
        import deepspeed_tpu
        from deepspeed_tpu.models import GPT2Pipe

        groups.reset()
        topo = groups.initialize(TopologyConfig(
            pipe_parallel_size=2, tensor_parallel_size=2,
            data_parallel_size=-1), force=True)
        model = GPT2Pipe(self._cfg())
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, topology=topo, config={
                "train_micro_batch_size_per_gpu": 4,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
            })
        ids = np.random.RandomState(2).randint(0, 256, (8, 32)).astype(
            np.int32)
        l0 = float(engine.train_batch({"input_ids": ids}))
        l1 = float(engine.train_batch({"input_ids": ids}))
        assert np.isfinite(l0) and np.isfinite(l1)
        assert l1 < l0  # optimizing the same batch must reduce loss


class Test1F1BSchedule:
    """pipe_schedule='1f1b': the interleaved executor
    (runtime/pipe/spmd.py pipeline_1f1b_grads; reference
    runtime/pipe/engine.py:1382 _exec_schedule + schedule.py:189
    TrainSchedule as executed behavior, not schedule objects)."""

    def _setup(self, sched, M, n_layer=4, pipe=4, data=2):
        from dataclasses import replace
        from deepspeed_tpu.models import GPT2Pipe
        from deepspeed_tpu.models.gpt2 import GPT2Config
        cfg = GPT2Config(n_layer=n_layer, n_head=4, d_model=128,
                         max_seq_len=32, vocab_size=256, dtype="float32",
                         remat=True, pipe_microbatches=M,
                         pipe_schedule=sched)
        groups.reset()
        topo = groups.initialize(TopologyConfig(data_parallel_size=data,
                                                pipe_parallel_size=pipe))
        model = GPT2Pipe(cfg)
        params = model.init(jax.random.key(0))
        rng = np.random.RandomState(0)
        batch = {"input_ids": jnp.asarray(
            rng.randint(0, 256, (16, 32)), jnp.int32)}
        return topo, model, params, batch

    @pytest.mark.parametrize("steady", ["1f1b", "zb"])
    def test_loss_and_grad_parity_with_gpipe(self, steady):
        res = {}
        for sched in ("gpipe", steady):
            topo, model, params, batch = self._setup(sched, M=8)
            with jax.set_mesh(topo.mesh):
                loss, grads = jax.jit(jax.value_and_grad(
                    lambda p: model.loss(p, batch,
                                         rng=jax.random.key(1))))(params)
            res[sched] = (float(loss), grads)
        l0, g0 = res["gpipe"]
        l1, g1 = res[steady]
        assert abs(l0 - l1) < 1e-5
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4),
            g0, g1)

    def test_zb_live_activations_bounded_by_stages(self):
        """The ZB executor keeps the 1F1B memory class: input ring +
        S-slot dy ring, never O(M) residuals — growing M must not grow
        live temp memory the way GPipe's autodiff residuals do."""
        grown = {}
        for sched in ("gpipe", "zb"):
            temps = []
            for M in (4, 16):
                topo, model, params, batch = self._setup(sched, M=M)
                with jax.set_mesh(topo.mesh):
                    c = jax.jit(jax.value_and_grad(
                        lambda p: model.loss(p, batch,
                                             rng=jax.random.key(1)))
                                ).lower(params).compile()
                temps.append(c.memory_analysis().temp_size_in_bytes)
            grown[sched] = temps[1] - temps[0]
        assert grown["zb"] < 0.5 * grown["gpipe"], grown

    def test_live_activations_bounded_by_stages(self):
        """The property 1F1B exists for: growing the microbatch count
        grows GPipe's live residual memory (every tick's activations are
        saved for autodiff) but NOT 1F1B's (fixed 2S-slot input ring,
        backward chases forward). Measured from XLA's own buffer
        assignment, not inferred."""
        grown = {}
        for sched in ("gpipe", "1f1b"):
            temps = []
            for M in (4, 16):
                topo, model, params, batch = self._setup(sched, M=M)
                with jax.set_mesh(topo.mesh):
                    c = jax.jit(jax.value_and_grad(
                        lambda p: model.loss(p, batch,
                                             rng=jax.random.key(1)))
                                ).lower(params).compile()
                temps.append(c.memory_analysis().temp_size_in_bytes)
            grown[sched] = temps[1] - temps[0]
        # gpipe grows with M; 1f1b must grow far less (ring is
        # M-independent; small scheduling buffers may still vary)
        assert grown["1f1b"] < 0.5 * grown["gpipe"], grown

    def test_ring_capacity_is_stage_bound(self):
        from deepspeed_tpu.runtime.pipe.spmd import _ring_capacity
        assert _ring_capacity(4) == 8      # independent of microbatches


class TestZBOffloadMemory:
    """Backend-gated acceptance check: with a REAL host memory kind
    (TPU), the offloaded zero-bubble step's device temp bytes must
    drop vs offload-off — the live-HBM saving the 13B recipe depends
    on. Skipped where the platform has a single memory space (the CPU
    test mesh: staging is identity by design — host_stage docs)."""

    def test_offload_drops_device_temp_bytes(self):
        from deepspeed_tpu.runtime.swap_tensor import host_stage
        if not host_stage.available():
            pytest.skip("no distinct host memory kind on this backend")
        import deepspeed_tpu
        from deepspeed_tpu.models import GPT2Pipe
        from deepspeed_tpu.models.gpt2 import GPT2Config
        cfg = GPT2Config(n_layer=4, n_head=4, d_model=256,
                         max_seq_len=256, vocab_size=512,
                         dtype="float32", remat=True,
                         pipe_microbatches=8)
        temps = {}
        for offload in (False, True):
            groups.reset()
            topo = groups.initialize(
                TopologyConfig(pipe_parallel_size=2,
                               data_parallel_size=1),
                devices=jax.devices()[:2], force=True)
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=GPT2Pipe(cfg), topology=topo, config={
                    "train_micro_batch_size_per_gpu": 16,
                    "gradient_accumulation_steps": 1,
                    "steps_per_print": 0,
                    "optimizer": {"type": "AdamW",
                                  "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 0},
                    "pipeline": {"schedule": "zb",
                                 "offload_activations": offload}})
            ids = np.random.RandomState(0).randint(
                0, 512, (16, 256)).astype(np.int32)
            batch = jax.tree.map(engine._add_gas_dim,
                                 {"input_ids": ids})
            batch = engine._shard_batch(batch, with_gas_dim=True)
            with jax.set_mesh(engine.mesh):
                c = engine._train_step_jit.lower(
                    engine.state, batch, engine._current_lr(),
                    None).compile()
            temps[offload] = c.memory_analysis().temp_size_in_bytes
        assert temps[True] < temps[False], temps
