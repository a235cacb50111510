"""DeepSpeedEngine — the training engine.

Counterpart of reference ``runtime/engine.py:181 DeepSpeedEngine`` (init
pipeline SURVEY §3.1, fwd/bwd/step §3.2). TPU-first redesign:

  * The train state (bf16 params, fp32 master, optimizer state, loss-scale
    state, step) is ONE pytree whose leaves carry NamedShardings computed by
    the ZeRO plan (runtime/zero/partitioning.py). What the reference does
    with hooks + buckets + streams, XLA does from the sharding annotations:
    stage-1 partitioned update + step-end allgather, stage-2 reduce_scatter,
    stage-3 per-layer gather, all overlapped by XLA's latency-hiding
    scheduler (the `overlap_comm` analogue).
  * `train_batch()` is one jitted program: `lax.scan` over gradient
    accumulation micro-steps, grad clip, overflow-safe optimizer update with
    in-state dynamic loss scaling (no host sync per step, unlike the
    reference's CheckOverflow).
  * The staged `forward()/backward()/step()` API is kept for parity: forward
    computes loss+grads in one jitted call (autodiff is a transform, not a
    tape), backward accumulates into a sharded grad buffer, step applies the
    update at the accumulation boundary (reference
    is_gradient_accumulation_boundary semantics).
"""

import functools
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm as dist
from ..ops.optimizers import build_optimizer
from ..utils import groups
from ..utils.groups import TopologyConfig, BATCH_AXES
from ..utils.logging import logger, log_dist
from ..utils.timer import (SynchronizedWallClockTimer, ThroughputTimer,
                           TRAIN_BATCH_TIMER)
from .config import DeepSpeedConfig, _take, CommOverlapConfig
from .fp16.loss_scaler import create_loss_scaler, grads_finite
from .lr_schedules import build_scheduler
from .zero.partitioning import ZeroShardingPlan
from .zero import overlap as comm_overlap


def _tree_cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def _is_spec(x):
    return isinstance(x, P)


class DeepSpeedEngine:
    def __init__(self, model, config, optimizer=None, lr_scheduler=None,
                 topology=None, seed=0):
        # --- topology & config (reference engine.py:1112
        #     _configure_distributed_model) ---
        if isinstance(config, dict):
            raw = config
        elif isinstance(config, DeepSpeedConfig):
            raw = config._raw
        else:
            # str path: read the json directly — batch-triad validation
            # belongs to the dp-aware DeepSpeedConfig built below
            import json as _json
            with open(config) as _f:
                raw = _json.load(_f)
        # comm-overlap XLA flags (zero/overlap.py) must land before the
        # backend initializes — which happens at the first jax.devices()
        # call when this engine builds its own topology below — so the
        # block is parsed ahead of the full config. "auto" only applies
        # flags when a multi-process/overlap env hint exists: flags at
        # dp=1 would perturb the measured single-chip headline.
        co_early = _take(raw, CommOverlapConfig, "comm_overlap")
        want_flags = co_early.set_xla_flags and (
            co_early.enabled is True
            or (co_early.enabled == "auto"
                and (os.environ.get("COORDINATOR_ADDRESS")
                     or os.environ.get("DSTPU_COMM_OVERLAP") == "1")))
        if want_flags:
            platform = comm_overlap.platform_guess()
            # bucket_mb="auto" resolves from the winner cache LATER (at
            # _install_comm_overlap, after the backend is up — dispatch
            # needs device_kind); the pre-backend flags take the cold-
            # cache default, which is what "auto" resolves to anyway
            flag_mb = (co_early.bucket_mb
                       if isinstance(co_early.bucket_mb, int) else 32)
            self._overlap_flags = comm_overlap.apply_xla_flags(
                comm_overlap.xla_overlap_flags(
                    platform, prefetch=co_early.prefetch,
                    bucket_mb=flag_mb),
                comm_overlap.overlap_env_var(platform))
        else:
            self._overlap_flags = (False, "not requested")
        # auto-parallelism: ``parallelism: "auto"`` hands the mesh choice
        # to the planner (autotuning/planner.py) when no explicit
        # topology was constructed — an explicit ``topology=`` argument
        # always wins. The adopted plan is stashed so _resolve_pipeline
        # can consume its schedule/microbatch/offload picks wherever the
        # pipeline knobs were themselves left on 'auto'.
        self._auto_plan = None
        self.plan_report = None
        if topology is None and raw.get("parallelism", "") == "auto":
            from ..autotuning import planner as _planner
            report = _planner.plan_for_engine(model, raw)
            best = report.top() if report is not None else None
            if best is not None:
                self._auto_plan = best
                self.plan_report = report
                topology = groups.initialize(TopologyConfig(
                    **best.topology_kwargs()))
                m = best.mesh
                log_dist(
                    "parallelism=auto: planned mesh "
                    + "x".join(f"{a}={m[a]}" for a in
                               ("pipe", "data_outer", "data", "expert",
                                "seq", "tensor"))
                    + f" schedule={best.schedule} M={best.micro_batches}"
                    + f" offload={best.offload}"
                    + f" (modeled {best.wall_ms:.3g} ms/step,"
                    + f" {report.considered} considered,"
                    + f" {report.pruned_hbm} HBM-pruned)", ranks=[0])
            else:
                log_dist(
                    "parallelism=auto: planner produced no feasible "
                    "plan; falling back to the explicit config axes",
                    ranks=[0])
        if topology is None:
            zero_raw = raw.get("zero_optimization", {})
            shard = int(zero_raw.get("mics_shard_size", -1))
            if shard in (-1, 0):
                shard = int(zero_raw.get("hpz_partition_size", 1))
                shard = shard if shard > 1 else -1
            topology = groups.initialize(TopologyConfig(
                tensor_parallel_size=raw.get("tensor_parallel", {}).get("size", 1),
                pipe_parallel_size=raw.get("pipeline", {}).get("stages", 1),
                seq_parallel_size=raw.get("sequence_parallel_size", 1),
                expert_parallel_size=raw.get("expert_parallel_size", 1),
                zero_shard_size=shard,
            ))
        self.topology = topology
        self.mesh = topology.mesh
        dp_world = topology.get_data_parallel_world_size()
        # `raw` is the parsed dict in every non-DeepSpeedConfig branch —
        # no second read of a json path
        self.config = (config if isinstance(config, DeepSpeedConfig)
                       else DeepSpeedConfig(raw, dp_world_size=dp_world))
        dist.configure(self.config)

        # measured kernel dispatch: the autotune mode/cache is process-
        # global (kernel choice must agree across every trace), so the
        # engine pushes its config block down BEFORE any program traces;
        # empty fields inherit the DSTPU_AUTOTUNE* env defaults
        from ..autotuning import kernel_dispatch
        kernel_dispatch.configure_from_config(self.config.autotune)

        # comm-overlap resolution (the XLA flags were handled above,
        # pre-backend; this decides the program-level annotations).
        # hierarchical 'auto' consults the 'grad_staging' collective op's
        # winner cache with the do>1 heuristic as the cold-cache default
        # — same answer as before until a measured winner disagrees
        co = self.config.comm_overlap
        self._overlap_on = co.resolve_enabled(dp_world)
        self._overlap_hier = self._overlap_on and \
            self._resolve_grad_staging(co, topology, model)
        self.comm_overlap_report = None

        self.model = model
        # sequence/context-parallel knobs (config 'sequence' block):
        # models with attention_backend='ring' read this when seq-sharded
        # (gpt2.block_attn -> sequence/ring.py layout/kernel/overlap)
        try:
            self.model._sequence_cfg = self.config.sequence
        except (AttributeError, TypeError):   # frozen/slotted models
            log_dist(
                "sequence config block could not be installed on the "
                "model (attribute assignment rejected); ring attention "
                "will use the module defaults", ranks=[0])
        # dropless-MoE knobs (config 'moe' block): grouped-GEMM kernel
        # dispatch + hierarchical ICI->DCN expert all_to_all staging
        # (moe/sharded_moe.py; mixtral._mlp and the MoE layers consult
        # model._moe_cfg per dispatch)
        moe_cfg = self.config.moe
        qz = self.config.quantize
        if qz.moe_dcn is not None:
            # 'quantize' block override: moe_dcn=None defers to
            # moe.dcn_quantize, anything else steers the MoE DCN legs
            import dataclasses as _dc
            moe_cfg = _dc.replace(moe_cfg, dcn_quantize=qz.moe_dcn)
        try:
            self.model._moe_cfg = moe_cfg
        except (AttributeError, TypeError):   # frozen/slotted models
            log_dist(
                "moe config block could not be installed on the model "
                "(attribute assignment rejected); MoE layers will use "
                "the module defaults", ranks=[0])
        # W8A8 compute levers (quantize block): models consult these at
        # trace time (gpt2._mlp / mixtral._moe_knobs); False defaults
        # keep the compiled programs byte-identical
        try:
            self.model._int8_matmul = qz.int8_matmul
            self.model._moe_int8 = qz.moe_int8_matmul
        except (AttributeError, TypeError):   # frozen/slotted models
            log_dist(
                "quantize config block could not be installed on the "
                "model (attribute assignment rejected); int8 matmul "
                "levers will use the module defaults", ranks=[0])
        self.zero_stage = self.config.zero.stage
        self.param_dtype = self.config.precision_dtype
        # pipeline block (config 'pipeline'): schedule / microbatch /
        # host-offload resolution happens ONCE here (pre-state: the
        # moments placement changes the optimizer-state shardings) and
        # is installed on the model as _pipe_cfg for GPT2Pipe to
        # consult at trace time
        self._pipe = self._resolve_pipeline()
        try:
            self.model._pipe_cfg = self._pipe
        except (AttributeError, TypeError):   # frozen/slotted models
            log_dist(
                "pipeline config block could not be installed on the "
                "model (attribute assignment rejected); pipelined "
                "models will use their module defaults", ranks=[0])
        model_dtype = getattr(getattr(model, "config", None), "dtype",
                              None)
        if model_dtype is not None and \
                jnp.dtype(model_dtype) != jnp.dtype(self.param_dtype):
            # the engine computes in param_dtype (fp32 master handled
            # internally); a model whose own dtype knob disagrees mixes
            # activation dtypes mid-scan and fails with an opaque carry
            # type error — tell the user which knob to change
            raise ValueError(
                f"model config dtype {jnp.dtype(model_dtype).name!r} != "
                f"engine precision {jnp.dtype(self.param_dtype).name!r} "
                f"(from the bf16/fp16 config blocks); set the model's "
                f"dtype to match, or enable/disable bf16 accordingly")
        self.global_step = 0
        self.micro_steps = 0

        # --- optimizer / scheduler (reference engine.py:1246,:915) ---
        if optimizer is None:
            if self.config.optimizer is None:
                raise ValueError("no optimizer: pass one or set config['optimizer']")
            optimizer = build_optimizer(self.config.optimizer.type,
                                        self.config.optimizer.params)
        self.optimizer = optimizer
        if lr_scheduler is None and self.config.scheduler is not None:
            lr_scheduler = build_scheduler(self.config.scheduler.type,
                                           self.config.scheduler.params)
        self.lr_scheduler = lr_scheduler

        self.loss_scaler = create_loss_scaler(self.config.fp16,
                                              self.param_dtype)

        # --- sharding plan + state materialization (reference zero.Init +
        #     _configure_zero_optimizer) ---
        self._build_state(seed)
        # program name -> what its trace noted (_noting_calls)
        self.traced_calls = {}
        self._build_programs()

        from .checkpoint_engine.engines import create_checkpoint_engine
        self.checkpoint_engine = create_checkpoint_engine(
            self.config.checkpoint_engine)

        # peer-replicated in-memory hot tier (checkpoint_engine/
        # hot_tier.py): 'auto' is on iff an elastic launcher exported
        # the ring env (DSTPU_HOT_PEERS/DSTPU_HOT_TIER_ROOT/
        # DSTPU_HOT_TRANSPORT — deliberately NOT bare multi-process;
        # see the config field comment); restores try it before any
        # persistent-storage read
        self.hot_store = None
        ce_cfg = self.config.checkpoint_engine
        if ce_cfg.resolve_hot_tier():
            from .checkpoint_engine.hot_tier import HotTierStore
            replicas = ce_cfg.hot_replicas
            if replicas == "auto":
                # measured replication degree for this per-host shard
                # payload (op 'hot_replicas'; K=1 — the hand-set ring
                # default — on a cold cache)
                from ..ops.pallas._common import (dispatch, dtype_name,
                                                  hot_replicas_bucket)
                shard_mb = self._layer_grad_mb(
                    self.model, self.param_dtype)
                mcfg = getattr(self.model, "config", None)
                shard_mb *= max(1, int(getattr(mcfg, "n_layer", 1)))
                shard_mb = max(1, shard_mb // max(1, jax.process_count()))
                replicas = int(dispatch(
                    "hot_replicas", hot_replicas_bucket(shard_mb,
                                                        self.mesh),
                    dtype_name(self.param_dtype), {"k": 1})["k"])
            # the store clamps replicas (config ints AND the autotuned
            # winner above both flow through here) to ring size - 1 with
            # a one-time warning, and reads slice membership from
            # DSTPU_HOT_SLICES (the elastic agent exports it) for
            # cross-slice replica placement
            self.hot_store = HotTierStore(
                root=ce_cfg.hot_root or None,
                replicas=int(replicas),
                keep_last=ce_cfg.hot_keep_last,
                counters=self.checkpoint_engine.counters,
                max_inflight_pushes=ce_cfg.hot_max_inflight_pushes)
        # which tier served the most recent load_checkpoint (None before
        # any load / when nothing was found): 'hot' | 'replica' |
        # 'durable'
        self.last_restore_tier = None
        # preemption-graceful drain (tentpole of the slice-survivability
        # work): a SIGTERM — TPU maintenance notice, or the elastic
        # agent forwarding one — only SETS this flag; the in-flight
        # train_batch finishes, then _preempt_drain forces one
        # hot+replica push and a flight dump and exits with the
        # distinct PREEMPTED_EXIT_CODE the agent maps to 'preempted'
        # (healthy host kept, no backoff penalty)
        self._preempt_requested = False
        self._last_ckpt_save_dir = None
        if ce_cfg.resolve_preempt_drain():
            self._install_preempt_drain()

        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.config.train_batch_size,
            steps_per_output=self.config.steps_per_print)

        # monitoring fan-out (reference engine.py:253 MonitorMaster; events
        # written at step boundaries like engine.py:1993-2001)
        from ..monitor.monitor import MonitorMaster
        self.monitor = MonitorMaster(self.config.monitor_config)

        # pod telemetry (monitor/telemetry.py): step analytics (MFU /
        # tokens-per-chip / p50-p99 from host wall times, no device
        # sync), goodput accounting fed by the checkpoint paths below,
        # cluster aggregation, and the crash flight recorder + on-demand
        # profiler. 'auto' arms it when a monitor backend, the elastic
        # agent, or an explicit env hint is present.
        self.telemetry = None
        tcfg = self.config.telemetry
        # 'auto' must resolve from the rank-symmetric CONFIG flag, not
        # MonitorMaster.enabled (rank-0-gated): the cluster allgather
        # transport is collective, so arming telemetry on rank 0 only
        # would hang the pod at the first flush
        if tcfg.resolve_enabled(self.config.monitor_config.enabled):
            from ..monitor.telemetry import TelemetryCollector
            self.telemetry = TelemetryCollector(
                tcfg, monitor=self.monitor,
                n_devices=int(self.mesh.size),
                device_kind=jax.devices()[0].device_kind,
                costs_fn=self._telemetry_step_costs)
            # SIGTERM black-box dump: only chained when something will
            # actually read it (an elastic agent supervises us, or a
            # dump dir was exported) — unconditional installs would
            # chain a handler per engine built in one process
            if (os.environ.get("ELASTIC_GENERATION") is not None
                    or os.environ.get("DSTPU_FLIGHTREC_DIR")
                    or tcfg.flightrec_dir):
                self.telemetry.flight.install_sigterm()
            self._telemetry_lower_args = None
            # pipelined runs: arm the per-flush pipeline metrics
            # (bubble fraction, steady-tick wall, offload payload)
            pinfo = self.pipeline_report()
            if pinfo is not None:
                self.telemetry.set_pipeline(pinfo)
            # step-anatomy reconciliation: when ProfilerControl stops a
            # step-ranged capture, hand the trace to the parser + the
            # planner reconciler (pool-side; advisory)
            self.telemetry.set_reconcile(self._telemetry_reconcile)

        # data efficiency (reference engine.py:336-367): the curriculum
        # scheduler changes the SEQUENCE LENGTH the jitted step sees
        # (shape buckets — difficulty_step bounds distinct programs) and
        # random-LTD the kept-token count of middle layers
        self.curriculum_scheduler = None
        self._curriculum_difficulty = None
        if self.config.curriculum_config is not None:
            from .data_pipeline.curriculum_scheduler import (
                CurriculumScheduler)
            self.curriculum_scheduler = CurriculumScheduler(
                self.config.curriculum_config)
        self.random_ltd_scheduler = None
        if self.config.random_ltd_config is not None:
            from .data_pipeline.random_ltd import RandomLTDScheduler
            self.random_ltd_scheduler = RandomLTDScheduler(
                self.config.random_ltd_config)
            if not self._loss_accepts_ltd():
                raise ValueError(
                    "random_ltd is enabled but the model's loss() takes "
                    "no ltd_keep argument (models/gpt2.py implements it)")
        log_dist(
            f"engine ready: zero_stage={self.zero_stage} dtype={self.param_dtype} "
            f"dp={dp_world} tp={topology.get_model_parallel_world_size()} "
            f"sp={topology.get_sequence_parallel_world_size()} "
            f"ep={topology.get_expert_parallel_world_size()} "
            f"micro_bs={self.config.train_micro_batch_size_per_gpu} "
            f"gas={self.config.gradient_accumulation_steps} "
            f"overlap={self._overlap_on}", ranks=[0])

    # ---------------------------------------------------------------- buffers
    # A model may declare leaves of its tree that are BUFFERS, not
    # parameters (``model.buffer_names()``: the leaves' keys; the reference
    # fork's frozen parameters): they stay in ``state["params"]`` and
    # ``state["master"]`` as ``init`` made them, dtype and all, and the
    # optimizer never sees them: no cast, no gradient, no decay, no
    # moments, no update. A model that declares none (``_buffers`` None)
    # runs the programs it always ran, operation for operation.
    def _cast(self, tree, dtype):
        """The parameter tree in ``dtype``, a buffer leaf as it is."""
        if self._buffers is None:
            return _tree_cast(tree, dtype)
        return jax.tree.map(lambda b, x: x if b else x.astype(dtype),
                            self._buffers, tree)

    def _owned(self, tree):
        """What the optimizer owns of a tree shaped like the parameters
        (arrays, specs or shardings): a buffer's place is None, an empty
        node that ``jax.tree.map`` passes over."""
        if self._buffers is None:
            return tree
        return jax.tree.map(lambda b, x: None if b else x, self._buffers,
                            tree)

    def _with_buffers(self, owned, full):
        """``owned`` (see ``_owned``) with ``full``'s buffer leaves back in
        their places."""
        if self._buffers is None:
            return owned
        rest = iter(jax.tree.leaves(owned))
        return jax.tree.map(lambda b, x: x if b else next(rest),
                            self._buffers, full)

    # ------------------------------------------------------------------ state
    def _build_state(self, seed):
        rng = jax.random.key(seed)
        abstract = jax.eval_shape(self.model.init, rng)
        names = getattr(self.model, "buffer_names", frozenset)()
        self._buffers = jax.tree_util.tree_map_with_path(
            lambda path, _: getattr(path[-1], "key", None) in names,
            abstract) if names else None
        shapes = jax.tree.map(lambda l: l.shape, abstract)
        tp_specs = self.model.partition_specs(self.topology)
        self._tp_specs = tp_specs
        # MiCS: everything shards over the inner group, replicates over
        # data_outer (zero/mics.py:64). hpZ/ZeRO++: only the stage-3 bf16
        # param shard is intra-slice; optimizer state stays global-DP
        # (utils/groups.py:505 secondary group).
        from ..utils.groups import DP_AXES, INNER_DP_AXES
        zc = self.config.zero
        mics = zc.mics_shard_size not in (-1, 0)
        hpz = zc.hpz_partition_size > 1
        want = max(zc.mics_shard_size, zc.hpz_partition_size)
        if (mics or hpz) and self.topology.axis_size("data_outer") == 1 \
                and self.topology.axis_size("data") > want:
            log_dist(
                f"mics/hpz shard size {want} configured but the topology "
                "was built without zero_shard_size; sharding over the full "
                "DP group instead", ranks=[0])
        self.plan = ZeroShardingPlan(
            self.zero_stage, self.mesh, tp_specs, shapes,
            partition_axes=INNER_DP_AXES if mics else DP_AXES,
            param_partition_axes=INNER_DP_AXES if hpz else None)
        param_sh = self.plan.shardings("param")
        master_sh = self.plan.shardings("master")
        self.param_shardings = param_sh
        self.master_shardings = master_sh
        self.grad_shardings = self._owned(self.plan.shardings("grad"))

        self.use_master = self.param_dtype != jnp.float32

        # ZeRO-Offload (reference stage_1_and_2.py:1181 CPU-offload grads +
        # cpu_adam, stage3.py:584 NVMe tensor swapping): master + Adam
        # moments leave the device entirely — the host optimizer owns them
        # and the device state holds ONLY bf16 params.
        self.offload_opt_cfg = self.config.zero.offload_optimizer
        self.offload_param_cfg = self.config.zero.offload_param
        self.offload_enabled = (self.offload_opt_cfg.enabled
                                or self.offload_param_cfg.enabled)
        if self.offload_enabled and self._buffers is not None:
            raise NotImplementedError(
                "ZeRO-Offload's host optimizer owns every leaf: a model "
                "with buffer leaves trains with the optimizer on the device")
        self.host_optimizer = None
        # multi-process offload: each process device_gets and host-steps
        # ONLY its addressable master shards (reference
        # stage_1_and_2.py:1181 — every DP rank cpu-steps its partition)
        self._offload_multi = self.offload_enabled and \
            jax.process_count() > 1

        with jax.set_mesh(self.mesh):
            if self.offload_enabled:
                # fp32 init materialized once, fetched to host, then freed:
                # the device never holds master/opt state after init
                master_dev = jax.jit(
                    lambda r: _tree_cast(self.model.init(r), jnp.float32),
                    out_shardings=master_sh)(rng)
                params = jax.jit(
                    lambda m: _tree_cast(m, self.param_dtype),
                    out_shardings=param_sh)(master_dev)
                if self._offload_multi:
                    host_master = self._collect_local_shards(
                        master_dev, record_meta=True)
                else:
                    host_master = jax.device_get(master_dev)
                del master_dev
                from .zero.offload import HostOffloadOptimizer
                self.host_optimizer = HostOffloadOptimizer(
                    host_master, self.config.optimizer,
                    self.offload_opt_cfg, self.offload_param_cfg)
                del host_master
                master = None
                opt_state = None
                opt_sh = None
            else:
                params = jax.jit(
                    lambda r: self._cast(self.model.init(r),
                                         self.param_dtype),
                    out_shardings=param_sh)(rng)
                if self.use_master:
                    master = jax.jit(lambda p: self._cast(p, jnp.float32),
                                     out_shardings=master_sh)(params)
                else:
                    # fp32 training: master IS params (sharded per master
                    # plan from stage>=1; the update allgathers into param
                    # specs)
                    master = jax.jit(lambda p: p,
                                     out_shardings=master_sh)(params)
                opt_sh = self._opt_state_shardings(self._owned(master))
                opt_state = jax.jit(self.optimizer.init,
                                    out_shardings=opt_sh)(
                                        self._owned(master))
        self.opt_shardings = opt_sh

        # replicated scalars are CREATED by a jitted program rather than
        # device_put from host: device_put cannot target non-addressable
        # shardings on a multi-process mesh, a same-value computation can
        def _scalars():
            return (self.loss_scaler.init_state(),
                    jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                    jax.random.key(seed + 1))

        rep = jax.tree.map(lambda _: NamedSharding(self.mesh, P()),
                           jax.eval_shape(_scalars))
        scale_state, step0, skipped0, rng0 = jax.jit(
            _scalars, out_shardings=rep)()
        self.state = {
            "params": params,
            "master": master,
            "opt": opt_state,
            "scale": scale_state,
            "step": step0,
            # overflow-skip counter lives on device so counting it never
            # forces a host sync (reference syncs CheckOverflow every step)
            "skipped": skipped0,
            "rng": rng0,
        }
        self.state_shardings = {
            "params": param_sh,
            "master": None if self.offload_enabled else master_sh,
            "opt": opt_sh,
            "scale": jax.tree.map(
                lambda _: NamedSharding(self.mesh, P()), scale_state),
            "step": NamedSharding(self.mesh, P()),
            "skipped": NamedSharding(self.mesh, P()),
            "rng": NamedSharding(self.mesh, P()),
        }
        # grad accumulation buffer for the staged API (lazy)
        self._acc_grads = None
        self._pending_loss = None

    def _opt_state_shardings(self, master):
        """Optimizer state sharding: subtrees structurally matching the
        param tree inherit master shardings (m/v/etc.); scalars
        replicate. With ``pipeline.offload_moments`` resolved on, the
        moment subtrees are re-targeted at the host memory kind
        (sharding-with-memory-kind — the reference's swap_tensor
        optimizer tier expressed as placement; XLA streams them through
        the update)."""
        master_def = jax.tree.structure(master)
        state_shape = jax.eval_shape(self.optimizer.init, master)
        repl = NamedSharding(self.mesh, P())
        moment_sh = self._owned(self.master_shardings)
        if getattr(self._pipe, "offload_moments", False):
            from .swap_tensor import host_stage
            moment_sh = jax.tree.map(host_stage.with_host_memory_kind,
                                     moment_sh)
        out = {}
        for key, sub in state_shape.items():
            if jax.tree.structure(sub) == master_def:
                out[key] = moment_sh
            else:
                out[key] = jax.tree.map(lambda _: repl, sub)
        return out

    # -------------------------------------------------------------- programs
    def _loss_accepts_step(self):
        import inspect
        try:
            return "step" in inspect.signature(self.model.loss).parameters
        except (TypeError, ValueError):
            return False

    def _loss_accepts_ltd(self):
        import inspect
        try:
            return "ltd_keep" in inspect.signature(
                self.model.loss).parameters
        except (TypeError, ValueError):
            return False

    def _model_loss(self, params, batch, rng, step=None, ltd_keep=None):
        kwargs = {}
        if self.topology.get_sequence_parallel_world_size() > 1:
            kwargs["seq_sharded"] = True
        # schedule-aware models (e.g. compression wrappers) take the
        # traced global step for schedule_offset gating
        if step is not None and self._loss_accepts_step():
            kwargs["step"] = step
        if ltd_keep is not None:
            kwargs["ltd_keep"] = ltd_keep
        return self.model.loss(params, batch, rng=rng, train=True, **kwargs)

    def _noting_calls(self, step):
        """``step`` — a program that traces the model's loss — under the
        one trace-time tally (``ops/pallas/_common.py`` ``counting_calls``:
        a mechanism's calls, and those of them that took the path its pair
        counts), said once it is traced, remat's re-traces included, and
        kept in ``self.traced_calls`` under the program's name. A model that
        notes nothing says nothing."""
        from ..monitor.tag_schema import SHAPE_PATHS
        from ..ops.pallas._common import counting_calls

        @functools.wraps(step)
        def program(*args):
            with counting_calls() as counts:
                out = step(*args)
            self.traced_calls[step.__name__] = counts
            if counts:
                log_dist(f"{step.__name__} traced: " + "; ".join(
                    f"{name}: {calls} calls, {taken} "
                    f"{SHAPE_PATHS.get(name, 'kernel')}"
                    for name, (calls, taken) in sorted(counts.items())),
                    ranks=[0])
            return out
        return program

    def _build_programs(self):
        gas = self.config.gradient_accumulation_steps
        clip = self.config.gradient_clipping
        opt = self.optimizer
        scaler = self.loss_scaler
        owned, with_buffers = self._owned, self._with_buffers
        grad_specs = owned(self.plan.grad_specs)
        param_specs = owned(self.plan.param_specs)
        pdtype = self.param_dtype
        use_master = self.use_master
        constrain = jax.lax.with_sharding_constraint
        # accumulate/reduce dtype: fp32 default (the reference
        # grad_accum_dtype default); data_types.grad_accum_dtype "bf16"
        # halves the full-model transient grad tree — the knob the 1.3B
        # ZeRO-3 single-chip point needs to fit 16 GB HBM (the optimizer
        # still computes its update in fp32)
        gdtype = jnp.dtype({"fp32": "float32", "bf16": "bfloat16",
                            "fp16": "float16", None: "float32"}.get(
            self.config.grad_accum_dtype, self.config.grad_accum_dtype))

        # per-layer comm annotations consumed by the model's block scan
        # (must precede tracing, which happens at the first jitted call)
        self._install_comm_overlap(gdtype)

        def micro_loss_and_grads(params, micro_batch, rng, scale,
                                 step=None, ltd_keep=None):
            def scaled(p):
                return self._model_loss(p, micro_batch, rng,
                                        step=step, ltd_keep=ltd_keep) \
                    * scale
            loss_scaled, grads = jax.value_and_grad(scaled)(params)
            grads = _tree_cast(owned(grads), gdtype)
            return loss_scaled / scale, grads

        def unscale_clip_grads(grads, scale):
            """Shared unscale + overflow check + global-norm clip — ONE
            definition so the fused, offload, and staged paths cannot
            drift. Returns (grads, finite, gnorm); the global norm's
            cross-shard psum falls out of GSPMD."""
            # keep each leaf's own dtype through the unscale (the fp32
            # scalar would silently promote a bf16 grad tree to fp32 —
            # exactly the materialization grad_accum_dtype=bf16 avoids)
            grads = jax.tree.map(
                lambda g, s: constrain((g / scale).astype(g.dtype), s),
                grads, grad_specs)
            finite = grads_finite(grads)
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g in jax.tree.leaves(grads))
            gnorm = jnp.sqrt(sq)
            if clip and clip > 0:
                coef = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree.map(
                    lambda g: (g * coef).astype(g.dtype), grads)
            return grads, finite, gnorm

        @jax.named_scope("dstpu.optim.update")
        def apply_update(state, grads, lr):
            """grads: fp32 tree, already averaged over GAS; scale included."""
            scale = state["scale"]["scale"]
            grads, finite, gnorm = unscale_clip_grads(grads, scale)
            master = owned(state["master"])
            new_master, new_opt = opt.update(grads, state["opt"], master,
                                             lr=lr)
            # skip-on-overflow: keep old state where not finite
            sel = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new, old)
            new_master = sel(new_master, master)
            new_opt = sel(new_opt, state["opt"])
            new_params = jax.tree.map(
                lambda m, s: constrain(m.astype(pdtype), s),
                new_master, param_specs) if use_master else jax.tree.map(
                lambda m, s: constrain(m, s), new_master, param_specs)
            new_master = with_buffers(new_master, state["master"])
            new_params = with_buffers(new_params, state["params"])
            new_scale = scaler.update(state["scale"], ~finite)
            new_state = dict(state)
            new_state.update(params=new_params, master=new_master,
                             opt=new_opt, scale=new_scale,
                             step=state["step"] + 1,
                             skipped=state["skipped"]
                             + jnp.where(finite, 0, 1).astype(jnp.int32),
                             rng=jax.random.fold_in(state["rng"], 0))
            metrics = {"grad_norm": gnorm, "overflow": ~finite,
                       "loss_scale": scale}
            return new_state, metrics

        def train_step(state, batch, lr, ltd_keep=None):
            """batch leaves: (gas, per_step_batch, ...); ltd_keep is a
            STATIC kept-token count (random-LTD) — distinct values are
            distinct programs, bounded by the schedule's seq_step"""
            scale = state["scale"]["scale"]

            if gas == 1:
                # no accumulation buffer: skip the zeros-init + add round
                # trip through HBM (O(model size) fp32 traffic per step)
                micro = jax.tree.map(lambda x: x[0], batch)
                loss, grads = micro_loss_and_grads(
                    state["params"], micro,
                    jax.random.fold_in(state["rng"], 0), scale,
                    step=state["step"], ltd_keep=ltd_keep)
                grads = jax.tree.map(lambda g, s: constrain(g, s),
                                     grads, grad_specs)
                new_state, metrics = apply_update(state, grads, lr)
                metrics["loss"] = loss
                return new_state, metrics

            def body(carry, micro):
                acc, rng, i = carry
                loss, grads = micro_loss_and_grads(
                    state["params"], micro, jax.random.fold_in(rng, i),
                    scale, step=state["step"], ltd_keep=ltd_keep)
                grads = jax.tree.map(lambda g, s: constrain(g, s),
                                     grads, grad_specs)
                acc = jax.tree.map(lambda a, g: a + g / gas, acc, grads)
                return (acc, rng, i + 1), loss

            zero_grads = jax.tree.map(
                lambda s: jnp.zeros(s.shape, gdtype),
                jax.eval_shape(lambda p: _tree_cast(owned(p), gdtype),
                               state["params"]))
            zero_grads = jax.tree.map(lambda g, s: constrain(g, s),
                                      zero_grads, grad_specs)
            (grads, _, _), losses = jax.lax.scan(
                body, (zero_grads, state["rng"], 0), batch)
            # accumulated grads carry the loss scale; apply_update divides
            # it out once.
            new_state, metrics = apply_update(state, grads, lr)
            metrics["loss"] = jnp.mean(losses)
            return new_state, metrics

        def micro_step(state, batch, micro_idx):
            scale = state["scale"]["scale"]
            rng = jax.random.fold_in(state["rng"], micro_idx)
            loss, grads = micro_loss_and_grads(state["params"], batch, rng,
                                               scale, step=state["step"])
            grads = jax.tree.map(lambda g, s: constrain(g, s), grads,
                                 grad_specs)
            return loss, grads

        def acc_add(acc, grads):
            return jax.tree.map(lambda a, g: a + g / gas, acc, grads)

        def grad_step(state, batch, ltd_keep=None):
            """ZeRO-Offload device half: loss + clipped, UNSCALED fp32
            grads + overflow flag. The update happens on the host
            (zero/offload.py HostOffloadOptimizer)."""
            scale = state["scale"]["scale"]

            def micro(carry, micro_batch):
                acc, rng, i = carry
                loss, grads = micro_loss_and_grads(
                    state["params"], micro_batch,
                    jax.random.fold_in(rng, i), scale, step=state["step"],
                    ltd_keep=ltd_keep)
                grads = jax.tree.map(lambda g, s: constrain(g, s),
                                     grads, grad_specs)
                acc = jax.tree.map(lambda a, g: a + g / gas, acc, grads)
                return (acc, rng, i + 1), loss

            if gas == 1:
                first = jax.tree.map(lambda x: x[0], batch)
                loss, grads = micro_loss_and_grads(
                    state["params"], first,
                    jax.random.fold_in(state["rng"], 0), scale,
                    step=state["step"], ltd_keep=ltd_keep)
                losses = loss
            else:
                zeros = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, jnp.float32),
                    jax.eval_shape(lambda p: _tree_cast(p, jnp.float32),
                                   state["params"]))
                zeros = jax.tree.map(lambda g, s: constrain(g, s),
                                     zeros, grad_specs)
                (grads, _, _), losses = jax.lax.scan(
                    micro, (zeros, state["rng"], 0), batch)
            grads, finite, gnorm = unscale_clip_grads(grads, scale)
            metrics = {"loss": jnp.mean(losses), "grad_norm": gnorm,
                       "overflow": ~finite, "loss_scale": scale}
            return grads, metrics

        def offload_finalize(state, overflow):
            """Counter/scale half of the step (device-side, tiny)."""
            new_state = dict(state)
            new_state.update(
                scale=scaler.update(state["scale"], overflow),
                step=state["step"] + 1,
                skipped=state["skipped"]
                + jnp.where(overflow, 1, 0).astype(jnp.int32),
                rng=jax.random.fold_in(state["rng"], 0))
            return new_state

        @jax.named_scope("dstpu.optim.update")
        def finish_grads(grads, scale):
            """Staged-API ZeRO-Offload: unscale/clip the accumulated grads
            on device before the host update."""
            grads, finite, gnorm = unscale_clip_grads(grads, scale)
            return grads, {"grad_norm": gnorm, "overflow": ~finite,
                           "loss_scale": scale}

        st_sh = lambda: self.state_shardings
        with jax.set_mesh(self.mesh):
            if self.offload_enabled:
                self._grad_step_jit = jax.jit(
                    self._noting_calls(grad_step), static_argnums=(2,),
                    in_shardings=(st_sh(), None),
                    out_shardings=(self.grad_shardings, None))
                self._offload_finalize_jit = jax.jit(
                    offload_finalize, donate_argnums=(0,),
                    in_shardings=(st_sh(), None),
                    out_shardings=st_sh())
                self._finish_grads_jit = jax.jit(
                    finish_grads, donate_argnums=(0,),
                    in_shardings=(self.grad_shardings, None),
                    out_shardings=(self.grad_shardings, None))
                # multi-process push-back: updated fp32 master shards ->
                # replicated/resharded bf16 params (GSPMD emits the
                # all-gather); the fp32 input is transient and donated
                self._offload_push_jit = jax.jit(
                    lambda m: _tree_cast(m, self.param_dtype),
                    donate_argnums=(0,),
                    in_shardings=(self.master_shardings,),
                    out_shardings=self.param_shardings)
            self._train_step_jit = None if self.offload_enabled else jax.jit(
                self._noting_calls(train_step), donate_argnums=(0,),
                static_argnums=(3,),
                in_shardings=(st_sh(), None, None),
                out_shardings=(st_sh(), None))
            self._micro_step_jit = jax.jit(
                self._noting_calls(micro_step),
                in_shardings=(st_sh(), None, None),
                out_shardings=(None, self.grad_shardings))
            eval_kwargs = {}
            if self.topology.get_sequence_parallel_world_size() > 1:
                eval_kwargs["seq_sharded"] = True
            self._eval_loss_jit = jax.jit(functools.partial(
                self.model.loss, train=False, **eval_kwargs))
            self._acc_add_jit = jax.jit(
                acc_add, donate_argnums=(0,),
                in_shardings=(self.grad_shardings, self.grad_shardings),
                out_shardings=self.grad_shardings)
            self._apply_update_jit = jax.jit(
                apply_update, donate_argnums=(0, 1),
                in_shardings=(st_sh(), self.grad_shardings, None),
                out_shardings=(st_sh(), None))

    # ---------------------------------------------------------- pipeline
    def _resolve_pipeline(self):
        """Resolve the ``pipeline`` config block against this topology
        and backend (runtime/config.py PipelineConfig docs the knobs):
        schedule, microbatch count (winner cache via the
        'pipe_microbatch' autotune op when 0/auto), and the host-offload
        placements — activations need a distinct host memory kind
        (swap_tensor/host_stage.py) and 'auto' additionally needs the
        HBM-fit heuristic to say the state does NOT fit."""
        from types import SimpleNamespace
        from .swap_tensor import host_stage
        pcfg = self.config.pipeline
        S = self.topology.get_pipe_parallel_world_size()
        mcfg = getattr(self.model, "config", None)
        model_sched = getattr(mcfg, "pipe_schedule", None)
        schedule = pcfg.resolve_schedule(model_sched)
        # parallelism=auto: the adopted plan's picks fill the knobs
        # still on block-level 'auto' — an explicit pipeline.schedule
        # wins, but the model-config default does not (opting into the
        # planner makes it the authority for the schedule choice)
        ap = getattr(self, "_auto_plan", None)
        if ap is not None and pcfg.schedule == "auto" \
                and ap.schedule != "none":
            schedule = ap.schedule
        avail = host_stage.available()
        est = self._estimate_pipe_state_bytes()
        hbm = self._device_hbm_bytes()
        acts = pcfg.resolve_offload_activations(
            avail, pipe_world=S, est_state_bytes=est, hbm_bytes=hbm)
        moments = pcfg.resolve_offload_moments(avail)
        if ap is not None and avail:
            if pcfg.offload_activations == "auto" and ap.offload:
                acts = True
            if pcfg.offload_moments == "auto" and ap.offload:
                moments = True
        if pcfg.offload_moments is True and not avail:
            log_dist(
                "pipeline.offload_moments=true but this backend has a "
                "single memory space; moments stay device-resident",
                ranks=[0])
        if pcfg.offload_activations is True and not avail:
            log_dist(
                "pipeline.offload_activations=true but this backend "
                "has a single memory space; staging degrades to "
                "identity (no bytes move)", ranks=[0])
        micro = pcfg.micro_batches or getattr(
            mcfg, "pipe_microbatches", 0)
        if not micro and S > 1 and ap is not None:
            # the plan's M already priced the bubble/efficiency knee;
            # degrade to a dividing count like the dispatch path does
            micro = int(ap.micro_batches)
            B = max(1, self.config.train_batch_size
                    // self.config.gradient_accumulation_steps)
            if B % micro:
                micro = next((m for m in (2 * S, S, 1) if B % m == 0),
                             1)
                log_dist(
                    f"pipeline: planned micro_batches "
                    f"{ap.micro_batches} does not divide the global "
                    f"batch {B}; using {micro}", ranks=[0])
        if not micro and S > 1 and mcfg is not None \
                and hasattr(mcfg, "d_model"):
            # 'auto' M: the measured knee between bubble amortization
            # (more microbatches) and per-tick MXU efficiency (fewer) —
            # cold cache = the 2S guidance, same program as before
            from ..ops.pallas._common import (dispatch, dtype_name,
                                              pipe_bucket)
            # the pipelined loss sees ONE accumulation micro-step's
            # rows, not the global batch — bucket and divisibility
            # must use what the model will actually split
            B = max(1, self.config.train_batch_size
                    // self.config.gradient_accumulation_steps)
            bucket = pipe_bucket(S, B, mcfg.max_seq_len, mcfg.d_model)
            winner = dispatch("pipe_microbatch", bucket,
                              dtype_name(self.param_dtype),
                              {"micro": 2 * S, "offload": int(acts)})
            micro = int(winner["micro"])
            if B % micro:
                # the bucket pow2-rounds B, so a cached winner can fail
                # the REAL batch's divisibility — 'auto' must degrade
                # to a dividing count, never crash the trace
                micro = next((m for m in (2 * S, S, 1) if B % m == 0),
                             1)
                log_dist(
                    f"pipeline: tuned micro_batches "
                    f"{winner['micro']} does not divide the global "
                    f"batch {B}; using {micro}", ranks=[0])
        if S > 1:
            log_dist(
                f"pipeline: stages={S} schedule={schedule} "
                f"micro_batches={micro or 2 * S} offload_acts={acts} "
                f"offload_moments={moments} "
                f"(host_kind={host_stage.host_memory_kind()})",
                ranks=[0])
        return SimpleNamespace(
            stages=S, schedule=schedule, micro_batches=int(micro),
            offload_activations=bool(acts),
            offload_moments=bool(moments),
            offload_double_buffer=bool(pcfg.offload_double_buffer))

    def _estimate_pipe_state_bytes(self):
        """Rough per-chip train-state bytes for the HBM-fit heuristic:
        working params + grads (divided over pipe x tensor) plus the
        fp32 master + Adam moments (divided over the ZeRO partition
        group from stage >= 1). A heuristic for the offload 'auto'
        knob, not an allocator."""
        import jax.numpy as _jnp
        mcfg = getattr(self.model, "config", None)
        count = getattr(mcfg, "num_params", None)
        if not callable(count):
            return None
        n = count()
        pp = max(1, self.topology.get_pipe_parallel_world_size())
        tp = max(1, self.topology.get_model_parallel_world_size())
        dp = max(1, self.topology.get_data_parallel_world_size())
        shard = pp * tp
        pbytes = _jnp.dtype(self.param_dtype).itemsize
        gname = self.config.grad_accum_dtype
        gbytes = {"bf16": 2, "fp16": 2}.get(gname, 4)
        opt_shard = shard * (dp if self.zero_stage >= 1 else 1)
        return int(n * (pbytes + gbytes) / shard + n * 12 / opt_shard)

    def _device_hbm_bytes(self):
        """Per-chip device memory budget: DSTPU_HBM_BYTES override,
        else the backend's own bytes_limit, else None (the heuristic
        then counts everything as fitting)."""
        env = os.environ.get("DSTPU_HBM_BYTES")
        if env:
            try:
                return int(float(env))
            except ValueError:
                logger.warning(
                    f"ignoring non-numeric DSTPU_HBM_BYTES={env!r}")
        try:
            stats = jax.devices()[0].memory_stats()
            return int(stats["bytes_limit"]) if stats else None
        except Exception:  # noqa: BLE001 - CPU/older backends
            return None

    def pipeline_report(self):
        """Schedule/offload analytics for the active pipeline (None at
        pipe=1): the analytic executor bubble fractions
        (runtime/pipe/schedule.py lock-step wall model — the number
        telemetry emits as Train/Pipeline/bubble_pct) and the host
        staging payload the offload moves per step."""
        pr = self._pipe
        S = pr.stages
        if S <= 1:
            return None
        from .pipe.schedule import executor_bubble_fraction
        sched = pr.schedule if pr.schedule in ("gpipe", "1f1b", "zb") \
            else "gpipe"
        M = pr.micro_batches or 2 * S
        gas = max(1, self.config.gradient_accumulation_steps)
        # ticks per OPTIMIZER step: each accumulation micro-step runs
        # one full schedule pass (telemetry's step wall covers all gas)
        ticks = gas * (M + 2 * (S - 1) if sched in ("1f1b", "zb")
                       else 2 * (M + S - 1))
        info = {
            "stages": S, "micro_batches": M, "schedule": sched,
            "ticks": ticks,
            "bubble_pct": round(
                100 * executor_bubble_fraction(sched, M, S), 3),
            "gpipe_bubble_pct": round(
                100 * executor_bubble_fraction("gpipe", M, S), 3),
            "offload_activations": pr.offload_activations,
            "offload_moments": pr.offload_moments,
            "offload_bytes_per_step": 0,
        }
        mcfg = getattr(self.model, "config", None)
        from .swap_tensor import host_stage
        if pr.offload_activations and host_stage.available() \
                and mcfg is not None and hasattr(mcfg, "d_model"):
            # the ring traffic: each tick stages one microbatch's
            # activation D2H (ring write) and reads one back H2D —
            # the copy-overhead budget the offload must hide, PER CHIP
            # (the batch dim shards over dp, so a chip's ring only
            # stages its own slice). Zero on single-memory-space
            # backends: there staging is identity and reporting
            # phantom bytes would poison the A/B
            dp = max(1, self.topology.get_data_parallel_world_size())
            rows = max(1, self.config.train_batch_size
                       // (gas * dp * M))
            act = rows * mcfg.max_seq_len * mcfg.d_model * \
                jnp.dtype(self.param_dtype).itemsize
            info["offload_bytes_per_step"] = int(2 * ticks * act)
        return info

    # ------------------------------------------------------- comm overlap
    @staticmethod
    def _layer_grad_mb(model, dtype):
        """Per-layer gradient payload in MB — the shape-bucket key the
        grad-collective autotune ops (comm_bucket / grad_staging /
        dcn_quantize) are cached under. 1 when the model can't say."""
        mcfg = getattr(model, "config", None)
        count = getattr(mcfg, "num_params", None)
        if not callable(count):
            return 1
        n_layer = max(1, int(getattr(mcfg, "n_layer", 1)))
        per = count() * jnp.dtype(dtype).itemsize / n_layer
        return max(1, int(per) >> 20)

    def _resolve_grad_staging(self, co, topology, model):
        """comm_overlap.hierarchical: explicit bool wins; 'auto' is the
        'grad_staging' winner for this (device, topology, layer-payload)
        bucket — the do>1 heuristic on a cold cache (byte-identical to
        the pre-planner resolution)."""
        do = topology.axis_size("data_outer")
        if co.hierarchical != "auto":
            return bool(co.hierarchical)
        from ..ops.pallas._common import (dispatch, dtype_name,
                                          grad_comm_bucket)
        dt = self.config.precision_dtype
        win = dispatch(
            "grad_staging",
            grad_comm_bucket(self._layer_grad_mb(model, dt),
                             topology.mesh),
            dtype_name(dt), {"hierarchical": int(do > 1)})
        return bool(win["hierarchical"])

    def _install_comm_overlap(self, gdtype):
        """Install the per-layer comm hook on the model (zero/overlap.py):
        forward gathers the ZeRO-3 layer shard explicitly (the prefetch
        target), backward constrains the layer cotangent to its per-layer
        grad sharding so the reduce-scatter lands INSIDE the backward
        scan — grad comm for layer i overlapping compute of layer i-1 —
        optionally staged hierarchically over ('data','expert') then
        'data_outer'."""
        co = self.config.comm_overlap
        if not self._overlap_on:
            return
        blocks_grad = (self.plan.grad_specs.get("blocks")
                       if isinstance(self.plan.grad_specs, dict) else None)
        blocks_tp = (self._tp_specs.get("blocks")
                     if isinstance(self._tp_specs, dict) else None)
        if blocks_grad is None or blocks_tp is None or \
                not hasattr(self.model, "block_forward"):
            log_dist(
                "comm_overlap: model has no scanned 'blocks' params; "
                "per-layer annotations skipped (XLA flags unaffected)",
                ranks=[0])
            return
        is_spec = lambda x: isinstance(x, P)
        grad_layer = jax.tree.map(comm_overlap.drop_layer_dim, blocks_grad,
                                  is_leaf=is_spec)
        # 'auto' knobs resolve against the collective winner cache under
        # the gradient bucket for this model+topology; every cold-cache
        # default equals the hand-set value, so a miss compiles the
        # byte-identical program
        from ..ops.pallas._common import (dispatch, dtype_name,
                                          grad_comm_bucket,
                                          scan_unroll_bucket)
        dt_name = dtype_name(self.param_dtype)
        gbucket = grad_comm_bucket(
            self._layer_grad_mb(self.model, self.param_dtype), self.mesh)
        bucket_mb = co.bucket_mb
        if bucket_mb == "auto":
            bucket_mb = int(dispatch("comm_bucket", gbucket, dt_name,
                                     {"bucket_mb": 32})["bucket_mb"])
        dcn_quantize = co.dcn_quantize
        # 'quantize' block override (one roof for the low-precision
        # levers): grad_dcn=None defers to comm_overlap.dcn_quantize
        qz_grad = self.config.quantize.grad_dcn
        if qz_grad is not None:
            dcn_quantize = qz_grad
        if dcn_quantize == "auto":
            dcn_quantize = bool(dispatch("dcn_quantize", gbucket, dt_name,
                                         {"quantize": 0})["quantize"])
        gather_layer = None
        prefetch_on = (co.prefetch and self.zero_stage >= 3
                       and not self.offload_param_cfg.enabled)
        if prefetch_on:
            gather_layer = jax.tree.map(comm_overlap.drop_layer_dim,
                                        blocks_tp, is_leaf=is_spec)
            # unrolled scan bodies give the i+1 gather layer i's matmuls
            # to hide under; 'auto' = the 'scan_unroll' winner (2 — the
            # hand-set minimum overlap has shipped with — on a miss)
            unroll = co.scan_unroll
            if unroll == "auto":
                mcfg = getattr(self.model, "config", None)
                unroll = int(dispatch(
                    "scan_unroll",
                    scan_unroll_bucket(getattr(mcfg, "n_layer", 1),
                                       getattr(mcfg, "d_model", 0),
                                       self.mesh),
                    dt_name, {"unroll": 2})["unroll"])
            self.model._scan_unroll_min = int(unroll)
        self.model._layer_comm_hook = comm_overlap.make_layer_comm_hook(
            grad_layer, gather_specs=gather_layer,
            hierarchical=self._overlap_hier,
            dcn_quantize=dcn_quantize,
            bucket_bytes=bucket_mb << 20, gdtype=gdtype)
        log_dist(
            f"comm_overlap on: bucket_mb={bucket_mb} "
            f"prefetch={prefetch_on} hierarchical={self._overlap_hier} "
            f"dcn_quantize={dcn_quantize} "
            f"xla_flags={self._overlap_flags[1]}", ranks=[0])

    def verify_comm_overlap(self, batch, require_async=False):
        """Compile the train-step program on ``batch`` and report the
        collective schedule XLA ACTUALLY emitted (``compiled.as_text()``
        through zero/overlap.overlap_report): collective count, async
        start/done pairs, in-scan-loop placement — broken down per op in
        ``in_loop_by_op``, so a seq-parallel ring step shows its KV
        ``collective-permute`` rotation INSIDE the scan body — and the
        mesh axes each collective's replica groups map to.
        ``require_async`` raises if a dp>=2 step carries no async pairs —
        the overlap flags did not take effect (TPU/GPU only: CPU lowers
        collectives synchronously in HLO)."""
        batch = jax.tree.map(self._add_gas_dim, batch)
        batch = self._shard_batch(batch, with_gas_dim=True)
        with jax.set_mesh(self.mesh):
            if self.offload_enabled:
                compiled = self._grad_step_jit.lower(
                    self.state, batch, None).compile()
            else:
                compiled = self._train_step_jit.lower(
                    self.state, batch, self._current_lr(), None).compile()
        report = comm_overlap.overlap_report(compiled.as_text(),
                                             mesh=self.mesh)
        # pipelined step: attach the schedule analytics (bubble
        # fractions, offload payload) next to what the HLO shows — the
        # in-loop collective-permute count is the pipe's steady-state
        # rotation, host_copies its staging traffic
        pinfo = self.pipeline_report()
        if pinfo is not None:
            report["pipeline"] = pinfo
        self.comm_overlap_report = report
        if require_async and report["n_collectives"] \
                and not report["async_pairs"]:
            raise RuntimeError(
                f"comm_overlap: step has {report['n_collectives']} "
                f"collectives but no async start/done pairs — overlap "
                f"flags did not take effect (set DSTPU_COMM_OVERLAP=1 "
                f"in the environment before the backend initializes)")
        return report

    # -------------------------------------------------------------- telemetry
    def _telemetry_step_costs(self):
        """Step FLOPs + collective-schedule breakdown for the telemetry
        layer, from the COMPILED train-step program: flops via
        ``Compiled.cost_analysis()`` (the flops-profiler source — XLA's
        own count for the exact program that runs, per participating
        chip under SPMD), exposed-comm share via the PR-3
        ``overlap_report`` HLO parse (collectives with no async
        start/done pair). Called once, lazily, at the first telemetry
        flush — one extra AOT compile amortized over the run. Falls
        back to the analytic ``model.config.flops_per_token()`` when
        lowering is impossible (e.g. before any step ran)."""
        args = getattr(self, "_telemetry_lower_args", None)
        if args is None:
            return None
        # one-shot: the stash pins a full device-resident global batch
        # in HBM — released the moment the capture runs (the telemetry
        # layer's _costs_tried keeps the step path from re-stashing)
        self._telemetry_lower_args = None
        batch, lr, ltd = args
        with jax.set_mesh(self.mesh):
            if self.offload_enabled:
                compiled = self._grad_step_jit.lower(
                    self.state, batch, ltd).compile()
            else:
                compiled = self._train_step_jit.lower(
                    self.state, batch, lr, ltd).compile()
        from ..profiling.flops_profiler import compiled_costs
        costs = compiled_costs(compiled)
        flops = float(costs.get("flops", 0.0) or 0.0)
        source = "hlo"
        if flops <= 0:
            fpt = getattr(getattr(self.model, "config", None),
                          "flops_per_token", None)
            if callable(fpt):
                tokens = self.config.train_batch_size * \
                    self.model.config.max_seq_len
                flops = fpt() * tokens / max(1, int(self.mesh.size))
                source = "analytic"
        out = {"flops_per_chip": flops or None, "source": source,
               "collectives": None, "exposed_comm_pct": None}
        try:
            report = comm_overlap.overlap_report(compiled.as_text(),
                                                 mesh=self.mesh)
            from ..monitor.telemetry import collective_breakdown
            out["collectives"], out["exposed_comm_pct"] = \
                collective_breakdown(report["n_collectives"],
                                     report["async_pairs"])
        except Exception:  # noqa: BLE001 - breakdown is best-effort
            pass
        return out

    def telemetry_report(self):
        """The most recent telemetry snapshot (None when telemetry is
        off). Benches/tests call ``engine.telemetry.drain()`` first when
        they need queued background work folded in."""
        return None if self.telemetry is None else \
            self.telemetry.snapshot()

    def _telemetry_reconcile(self, trace_dir, steps):
        """TelemetryCollector's reconcile hook: parse the finished
        profiler capture into a StepDecomposition, score this engine's
        actual mesh/schedule with the planner's ``_score``, and stash
        the full drift report for :meth:`reconcile_report`. Returns the
        compact summary the collector emits, or None when the platform
        produced no parseable trace (the collector warns once)."""
        from ..autotuning import reconcile as _rec
        decomp, report = _rec.from_engine(self, trace_dir, steps=steps)
        self._last_reconcile = (decomp, report)
        return None if report is None else report.summary()

    def reconcile_report(self):
        """The most recent modeled-vs-measured drift report as a dict
        (``{"decomposition": ..., "drift": ...}``), or None before any
        profiled capture reconciled. Drain telemetry first — the parse
        runs on the collector's background pool."""
        pair = getattr(self, "_last_reconcile", None)
        if pair is None:
            return None
        decomp, report = pair
        return {
            "decomposition": None if decomp is None else decomp.to_dict(),
            "drift": None if report is None else report.to_dict(),
        }

    # ----------------------------------------------------------------- batch
    def deepspeed_io(self, dataset, batch_size=None, shuffle=True,
                     seed=None):
        """Build the engine's data loader (reference engine.py:1715
        ``deepspeed_io``). With data efficiency enabled, a
        DeepSpeedDataSampler drives it: deterministic across restarts
        (``sampler.state_dict``), curriculum-aware, resumable. The
        single-controller engine feeds GLOBAL batches, so the sampler
        runs at dp_rank 0 / dp_size 1 and train_batch shards them."""
        from .dataloader import DeepSpeedDataLoader, SamplerDataLoader
        batch_size = batch_size or self.config.train_batch_size
        seed = (self.config.data_efficiency_seed if seed is None
                else seed)
        if (self.config.data_efficiency_enabled
                or self.curriculum_scheduler is not None):
            from .data_pipeline.data_sampler import DeepSpeedDataSampler
            sampler = DeepSpeedDataSampler(
                total_samples=len(dataset),
                micro_batch_size=batch_size,
                data_parallel_rank=0, data_parallel_size=1,
                gradient_accumulation_steps=1,
                shuffle=shuffle, seed=seed,
                curriculum_scheduler=self.curriculum_scheduler)
            # a load_checkpoint that ran before the sampler existed
            # stashed the saved position (global consumed samples —
            # topology-independent); install it now
            stash = getattr(self, "_resume_sampler_state", None)
            if stash is not None:
                sampler.load_state_dict(stash)
                self._resume_sampler_state = None
            self.data_sampler = sampler
            return SamplerDataLoader(dataset, sampler)
        return DeepSpeedDataLoader(dataset, batch_size, shuffle=shuffle,
                                   seed=seed)

    @property
    def curriculum_difficulty(self):
        """Difficulty of the most recent train_batch (None before the
        first step / without a curriculum)."""
        return self._curriculum_difficulty

    def _current_lr(self):
        if self.lr_scheduler is not None:
            return jnp.asarray(self.lr_scheduler(self.global_step),
                               jnp.float32)
        # constant lr: reuse one device scalar (a fresh host->device
        # transfer per step adds real latency through remote transports);
        # invalidated if the user mutates optimizer.lr mid-training
        cached = getattr(self, "_lr_cache", None)
        if cached is None or cached[0] != self.optimizer.lr:
            self._lr_cache = (self.optimizer.lr,
                              jnp.asarray(self.optimizer.lr, jnp.float32))
        return self._lr_cache[1]

    def _add_gas_dim(self, x):
        """(train_batch_size, ...) -> (gas, train_batch_size//gas, ...)."""
        gas = self.config.gradient_accumulation_steps
        x = np.asarray(x)
        assert x.shape[0] == self.config.train_batch_size, (
            f"batch dim {x.shape[0]} != train_batch_size "
            f"{self.config.train_batch_size}")
        return x.reshape((gas, x.shape[0] // gas) + x.shape[1:])

    def _shard_batch(self, batch, with_gas_dim):
        """Host batch -> global sharded arrays. Leaves (B_total, ...) or
        (gas, B, ...) when with_gas_dim."""
        seq_sharded = self.topology.get_sequence_parallel_world_size() > 1

        def put(x):
            x = np.asarray(x)
            dims = [None] * x.ndim
            b_dim = 1 if with_gas_dim else 0
            dims[b_dim] = BATCH_AXES
            if seq_sharded and x.ndim > b_dim + 1:
                dims[b_dim + 1] = "seq"
            return jax.device_put(
                x, NamedSharding(self.mesh, P(*dims)))

        return jax.tree.map(put, batch)

    def train_batch(self, batch):
        """One full optimizer step over a global batch.

        batch leaves: (train_batch_size, ...) host arrays; reshaped to
        (gas, train_batch_size // gas, ...) and scanned.

        Telemetry rides this path without touching it: the host wall
        time of the call (async dispatch — in steady state queue
        backpressure makes it track the device step) feeds the step
        ring, and any terminal exception (including the chaos suite's
        SimulatedKill) dumps the flight recorder before re-raising.
        """
        if self.telemetry is None:
            return self._train_batch_inner(batch)
        tokens = 0
        try:
            # shape only — np.asarray here would be a blocking D2H copy
            # of the whole leaf on every step for device-resident batches
            shape = next((getattr(x, "shape", None)
                          for x in jax.tree.leaves(batch)), None)
            if shape:
                tokens = int(shape[0]) * (
                    int(shape[1]) if len(shape) > 1 else 1)
        except Exception:  # noqa: BLE001 - tokens are advisory
            pass
        t0 = time.perf_counter()
        try:
            loss = self._train_batch_inner(batch)
        except BaseException as e:
            self.telemetry.on_crash(e)
            raise
        self.telemetry.on_step(self.global_step,
                               time.perf_counter() - t0, tokens=tokens)
        return loss

    def _train_batch_inner(self, batch):
        gas = self.config.gradient_accumulation_steps
        self.tput_timer.start()
        if self.curriculum_scheduler is not None:
            # curriculum (reference engine curriculum hook): the batch is
            # truncated to the scheduled difficulty BEFORE sharding when
            # the metric IS sequence length, so the jitted step compiles
            # one program per distinct difficulty (difficulty_step bounds
            # the count). Non-seqlen metrics only record the difficulty —
            # samplers/users consume it (truncating e.g. a vocab-rarity
            # percentile as a length would train on garbage).
            diff = self.curriculum_scheduler.update_difficulty(
                self.global_step + 1)
            self._curriculum_difficulty = diff
            if self.config.curriculum_config.get(
                    "curriculum_type", "seqlen") == "seqlen":
                batch = jax.tree.map(
                    lambda x: x[:, :diff] if getattr(x, "ndim", 0) >= 2
                    else x, batch)
        ltd_keep = None
        if self.random_ltd_scheduler is not None:
            ltd_keep = int(self.random_ltd_scheduler.update_seq(
                self.global_step))
        batch = jax.tree.map(self._add_gas_dim, batch)
        batch = self._shard_batch(batch, with_gas_dim=True)
        if self.telemetry is not None \
                and not self.telemetry._costs_tried \
                and getattr(self, "_telemetry_lower_args", None) is None:
            # stashed refs for the one-time lazy step-cost capture
            # (_telemetry_step_costs): same sharded shapes as the
            # program that runs, so lower() hits the compile cache.
            # Never re-stashed once the capture ran — the stash holds
            # a device-resident global batch
            self._telemetry_lower_args = (
                batch,
                None if self.offload_enabled else self._current_lr(),
                ltd_keep)
        with jax.set_mesh(self.mesh):
            if self.offload_enabled:
                grads, metrics = self._grad_step_jit(self.state, batch,
                                                     ltd_keep)
                metrics = self._host_optimizer_step(grads, metrics)
            else:
                self.state, metrics = self._train_step_jit(
                    self.state, batch, self._current_lr(), ltd_keep)
        self.global_step += 1
        self.micro_steps += gas
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self.tput_timer.stop(global_step=True,
                             sync_arrays=metrics["loss"])
        self._maybe_print(metrics)
        # liveness beat for the elastic agent: a worker that stops
        # completing steps (hung collective, wedged host) is killed and
        # restarted from 'latest' just like one that died outright
        from ..utils import touch_heartbeat
        touch_heartbeat()
        if self._preempt_requested:
            # SIGTERM arrived mid-step; the step above completed, so
            # state is at a clean boundary — drain and exit
            self._preempt_drain()
        return metrics["loss"]

    # ----------------------------------------------------- preemption drain
    def _install_preempt_drain(self):
        """Chain a SIGTERM handler that only requests a drain. Installed
        BEFORE the flight recorder's install_sigterm, so on a real
        signal the recorder dumps first and then falls through to us
        (its handler calls the previous disposition). Main-thread only
        — a non-main-thread engine build keeps the prior disposition."""
        import signal as _signal
        import threading as _threading
        if _threading.current_thread() is not _threading.main_thread():
            return False

        def _handler(signum, frame):
            # flag only — no logging/IO in signal context; the message
            # and the drain itself run at the next step boundary
            self._preempt_requested = True
            if callable(prev):
                prev(signum, frame)

        try:
            prev = _signal.signal(_signal.SIGTERM, _handler)
            return True
        except (ValueError, OSError):
            return False

    def _preempt_drain(self):
        """The graceful half of a preemption: force one hot+replica
        push of the CURRENT step (zero persistent-storage reads on the
        other side of the maintenance window), dump the flight
        recorder with the preemption recorded at the tail, and exit
        with the distinct code the elastic agent classifies as
        'preempted' (healthy host, no backoff penalty). The forced
        save is advisory — a failing push must not turn a clean
        preemption into a crash-looking death."""
        from ..elasticity.elastic_agent import PREEMPTED_EXIT_CODE
        self._preempt_requested = False
        logger.warning(
            f"preemption notice (SIGTERM) at step {self.global_step}: "
            f"forcing a hot+replica push, dumping the flight recorder, "
            f"exiting {PREEMPTED_EXIT_CODE} (preempted)")
        try:
            if self._last_ckpt_save_dir is not None:
                self.save_checkpoint(self._last_ckpt_save_dir)
            if self.hot_store is not None:
                self.hot_store.wait()
        except Exception as e:  # noqa: BLE001 - drain is best-effort
            logger.warning(f"preemption drain: forced push failed ({e}); "
                           f"exiting preempted anyway")
        if self.telemetry is not None:
            self.telemetry.flight.record(
                "preempted", step=self.global_step,
                drained=self._last_ckpt_save_dir is not None)
            self.telemetry.flight.dump(reason="preempted")
        raise SystemExit(PREEMPTED_EXIT_CODE)

    def _collect_local_shards(self, tree, record_meta=False):
        """Multi-process offload: per leaf, the 1D concatenation of THIS
        process's addressable shards (fp32). ``record_meta`` stores the
        (device, index, shape, size) piece layout so gradients can be
        validated against it and updated pieces pushed back."""
        metas = []

        def leaf(garr):
            shards = sorted(garr.addressable_shards,
                            key=lambda s: s.device.id)
            metas.append([(s.device, s.index, s.data.shape) for s in shards])
            return np.concatenate(
                [np.ravel(np.asarray(s.data)) for s in shards])

        out = jax.tree.map(leaf, tree)
        if record_meta:
            self._offload_shard_meta = metas
        else:
            for i, (got, want) in enumerate(
                    zip(metas, self._offload_shard_meta)):
                if [(g[1], g[2]) for g in got] != \
                        [(w[1], w[2]) for w in want]:
                    raise AssertionError(
                        f"offload leaf {i}: gradient shard layout "
                        f"{[(g[1], g[2]) for g in got]} does not match "
                        f"the master layout — grad and master shardings "
                        f"must partition identically for the host step")
        return out

    def _push_local_master(self, leaf_idx, w_flat):
        """Rebuild one global fp32 master leaf from this process's updated
        pieces (every process calls this for every leaf — the global
        array assembly is a collective contract, not a transfer)."""
        meta = self._offload_shard_meta[leaf_idx]
        sharding = jax.tree.leaves(self.master_shardings)[leaf_idx]
        shape = jax.tree.leaves(self.state["params"])[leaf_idx].shape
        bufs, off = [], 0
        for dev, index, pshape in meta:
            n = int(np.prod(pshape))
            bufs.append(jax.device_put(
                w_flat[off:off + n].reshape(pshape), dev))
            off += n
        return jax.make_array_from_single_device_arrays(
            shape, sharding, bufs)

    def _host_optimizer_step(self, grads, metrics):
        """ZeRO-Offload host half: pull grads, CPU-Adam the host master,
        push refreshed bf16 params leaf-by-leaf (reference
        stage_1_and_2.py:1745 step with cpu_offload; the leafwise push
        overlaps the next leaf's NVMe reads). Multi-process: each process
        steps only its addressable master shards; the refreshed params
        are re-assembled from per-process pieces and cast/resharded by a
        tiny jitted program (the all-gather the reference does with
        all_gather_dp_groups falls out of GSPMD)."""
        overflow = bool(np.asarray(metrics["overflow"]))
        if not overflow:
            lr = float(np.asarray(self._current_lr()))
            if self._offload_multi:
                host_grads = self._collect_local_shards(grads)
                del grads
                master_leaves = []

                def on_leaf_multi(path, w_flat, shape):
                    master_leaves.append(self._push_local_master(
                        len(master_leaves), w_flat))

                self.host_optimizer.step(host_grads, lr, on_leaf_multi)
                master_global = jax.tree.unflatten(
                    jax.tree.structure(self.state["params"]),
                    master_leaves)
                with jax.set_mesh(self.mesh):
                    self.state["params"] = self._offload_push_jit(
                        master_global)
            else:
                host_grads = jax.device_get(grads)
                del grads
                np_dtype = np.dtype(self.param_dtype)
                shardings_flat = jax.tree.leaves(self.param_shardings)
                leaves_out = []

                def on_leaf(path, w_flat, shape):
                    arr = w_flat.reshape(shape)
                    if arr.dtype != np_dtype:
                        arr = arr.astype(np_dtype)
                    leaves_out.append(jax.device_put(
                        arr, shardings_flat[len(leaves_out)]))

                self.host_optimizer.step(host_grads, lr, on_leaf)
                self.state["params"] = jax.tree.unflatten(
                    jax.tree.structure(self.state["params"]), leaves_out)
        self.state = self._offload_finalize_jit(
            self.state, jnp.asarray(overflow))
        return metrics

    # ------------------------------------------- staged fwd/bwd/step (parity)
    def forward(self, batch):
        """loss = engine(batch): computes loss AND grads (one fused jitted
        call — autodiff is a transform, not a tape) for the current micro
        batch; grads are staged for step()."""
        batch = self._shard_batch(batch, with_gas_dim=False)
        micro_idx = jnp.asarray(
            self.micro_steps % max(1, self.config.gradient_accumulation_steps),
            jnp.int32)
        with jax.set_mesh(self.mesh):
            loss, grads = self._micro_step_jit(self.state, batch, micro_idx)
            if self._acc_grads is None:
                zeros = jax.jit(
                    lambda g: jax.tree.map(jnp.zeros_like, g),
                    out_shardings=self.grad_shardings)(grads)
                self._acc_grads = zeros
            self._acc_grads = self._acc_add_jit(self._acc_grads, grads)
        self._pending_loss = loss
        return loss

    __call__ = forward

    def backward(self, loss=None):
        """Grads were produced in forward(); kept for API parity
        (reference engine.py:1968)."""
        self.micro_steps += 1
        return loss if loss is not None else self._pending_loss

    def is_gradient_accumulation_boundary(self):
        return self.micro_steps % self.config.gradient_accumulation_steps == 0

    def step(self):
        """Apply the optimizer at accumulation boundaries (reference
        engine.py:2170: non-boundary steps are no-ops)."""
        if not self.is_gradient_accumulation_boundary():
            return
        assert self._acc_grads is not None, "step() before forward()"
        with jax.set_mesh(self.mesh):
            if self.offload_enabled:
                metrics = self._staged_offload_step()
            else:
                self.state, metrics = self._apply_update_jit(
                    self.state, self._acc_grads, self._current_lr())
        self._acc_grads = None
        self.global_step += 1
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self._maybe_print(metrics)
        return metrics

    def _staged_offload_step(self):
        """Staged-API ZeRO-Offload: unscale/clip the accumulated grads on
        device (prebuilt program), then run the host update."""
        grads, metrics = self._finish_grads_jit(
            self._acc_grads, self.state["scale"]["scale"])
        metrics["loss"] = self._pending_loss
        return self._host_optimizer_step(grads, metrics)

    # ------------------------------------------------------------------ misc
    def _write_monitor_events(self, metrics):
        if not self.monitor.enabled:
            return
        events = [("Train/Samples/lr", float(self._current_lr()),
                   self.global_step)]
        loss = metrics.get("loss")
        if loss is not None:
            events.append(("Train/Samples/train_loss", float(loss),
                           self.global_step))
        if self.loss_scaler.dynamic:
            events.append(("Train/Samples/loss_scale",
                           float(metrics["loss_scale"]), self.global_step))
        self.monitor.write_events(events)

    def _write_ckpt_monitor_events(self, kind, latency_ms):
        """Checkpoint health counters -> monitor fan-out (save/load
        latency plus the cumulative retry/fallback/GC counters the
        chaos acceptance criteria track)."""
        if not self.monitor.enabled:
            return
        c = self.checkpoint_engine.counters
        step = self.global_step
        # full literal tags (no f-string assembly): the metric-schema
        # lint greps production code for every documented tag
        latency_tag = ("Train/Checkpoint/save_latency_ms"
                       if kind == "save"
                       else "Train/Checkpoint/load_latency_ms")
        self.monitor.write_events([
            (latency_tag, latency_ms, step),
            ("Train/Checkpoint/retries", c["retries"], step),
            ("Train/Checkpoint/fallbacks", c["fallbacks"], step),
            ("Train/Checkpoint/save_errors", c["save_errors"], step),
            ("Train/Checkpoint/load_fallbacks", c["load_fallbacks"],
             step),
            ("Train/Checkpoint/gc_removed", c["gc_removed"], step),
            ("Train/Checkpoint/hot_pushes", c["hot_pushes"], step),
            ("Train/Checkpoint/hot_push_errors", c["hot_push_errors"],
             step),
            ("Train/Checkpoint/hot_restores", c["hot_restores"], step),
            ("Train/Checkpoint/hot_fallbacks", c["hot_fallbacks"],
             step),
            ("Train/Checkpoint/durable_restores", c["durable_restores"],
             step),
            ("Train/Checkpoint/replica_pushes", c["replica_pushes"],
             step),
            ("Train/Checkpoint/replica_restores", c["replica_restores"],
             step),
            ("Train/Checkpoint/replica_fallbacks", c["replica_fallbacks"],
             step),
        ])

    def _maybe_print(self, metrics):
        self._write_monitor_events(metrics)
        if (self.config.steps_per_print and
                self.global_step % self.config.steps_per_print == 0):
            loss = metrics.get("loss")
            loss_s = f"loss={float(loss):.4f} " if loss is not None else ""
            log_dist(
                f"step={self.global_step} {loss_s}"
                f"lr={float(self._current_lr()):.3e} "
                f"grad_norm={float(metrics['grad_norm']):.3f} "
                f"scale={float(metrics['loss_scale']):.0f} "
                f"overflow={bool(metrics['overflow'])}", ranks=[0])

    def get_flops_profile(self, batch):
        """Flops/bytes of the compiled train-step program on ``batch``
        (reference engine.py:2240-2252 flops-profiler hook; here the costs
        come from XLA's own cost analysis of the program that runs)."""
        from ..profiling.flops_profiler import FlopsProfiler, \
            compiled_costs
        batch = jax.tree.map(self._add_gas_dim, batch)
        batch = self._shard_batch(batch, with_gas_dim=True)
        prof = FlopsProfiler(self.model)
        prof.start_profile()
        prof.set_params(self.state["params"])
        with jax.set_mesh(self.mesh):
            compiled = self._train_step_jit.lower(
                self.state, batch, self._current_lr()).compile()
        costs = compiled_costs(compiled)
        prof.record("train_step", costs.get("flops", 0.0),
                    costs.get("bytes accessed", 0.0))
        return prof

    def get_lr(self):
        return [float(self._current_lr())]

    def get_global_grad_norm(self):
        return None  # computed in-step; exposed via metrics

    @property
    def params(self):
        return self.state["params"]

    @property
    def skipped_steps(self):
        return int(np.asarray(self.state["skipped"]))

    # ------------------------------------------------------------ checkpoint
    def _ckpt_tree(self):
        """State staged for saving: fp32 master + optimizer + scale +
        counters. bf16 params are re-derived on load (cast of master).
        Under ZeRO-Offload the master/opt live on the host (read back from
        NVMe when tiered)."""
        if self.offload_enabled:
            return {"master": self.host_optimizer.master_tree(),
                    "opt": self.host_optimizer.state_tree(),
                    "scale": self.state["scale"],
                    "step": self.state["step"],
                    "skipped": self.state["skipped"],
                    "rng_data": jax.random.key_data(self.state["rng"])}
        return {"master": self.state["master"], "opt": self.state["opt"],
                "scale": self.state["scale"], "step": self.state["step"],
                "skipped": self.state["skipped"],
                "rng_data": jax.random.key_data(self.state["rng"])}

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        """See :meth:`_save_checkpoint_inner` — this wrapper feeds the
        telemetry layer (goodput overhead accounting, flight-recorder
        dump dir, crash dumps) without touching save semantics."""
        if self.telemetry is None:
            return self._save_checkpoint_inner(save_dir, tag,
                                               client_state, save_latest)
        # the ISSUE-9 dump location: {ckpt_root}/flightrec/host{n}.json
        # (config/env dirs win — set_root is first-wins)
        self.telemetry.flight.set_root(
            os.path.join(save_dir, "flightrec"))
        t0 = time.perf_counter()
        try:
            out = self._save_checkpoint_inner(save_dir, tag,
                                              client_state, save_latest)
        except BaseException as e:
            self.telemetry.note_overhead("checkpoint_save",
                                         time.perf_counter() - t0)
            self.telemetry.on_crash(e)
            raise
        self.telemetry.note_overhead("checkpoint_save",
                                     time.perf_counter() - t0)
        self.telemetry.record_event("checkpoint_saved", tag=str(out))
        return out

    def _save_checkpoint_inner(self, save_dir, tag=None,
                               client_state=None, save_latest=True):
        """reference engine.py:3124. Layout:
        {save_dir}/{tag}/shard-{process}.npz + {save_dir}/latest (shared
        FS, like the reference assumes).

        Each process writes ONLY its addressable shards (the reference's
        per-rank _save_zero_checkpoint, engine.py:3545) — no
        process_allgather of the full model state over DCN, no single
        writer. The shard files carry a chunk index so ANY ZeRO stage /
        mesh / process count reassembles the global logical tensors on
        load — the property the reference needs checkpoint/
        ds_to_universal.py for. Durable-latest: single-process, the
        'latest' pointer is written by the checkpoint engine only after
        the shard's bytes are durable (async overlap preserved);
        multi-process, every process drains its own writes and a
        cross-process barrier runs before rank 0 publishes 'latest', so
        it can never name a checkpoint whose other-rank shards are still
        in flight.
        """
        import os
        from ..utils import fault_injection
        from .checkpoint_engine import serialization as ser
        t_start = time.perf_counter()
        tag = tag or f"global_step{self.global_step}"
        self.checkpoint_engine.create(tag)
        # D2H staging of LOCAL shards only (the VELOC _d2h_trf analogue;
        # synchronous, bandwidth-bound), then the engine writes async if
        # configured.
        fault_injection.fire("d2h")
        chunks, index, meta = ser.extract_local_chunks(self._ckpt_tree())
        sampler = getattr(self, "data_sampler", None)
        extra = {
            "index": index,
            "__tree_meta__": meta,
            "user_extra": {
                "global_step": self.global_step,
                "micro_steps": self.micro_steps,
                "zero_stage": self.zero_stage,
                "nprocs": jax.process_count(),
                "lr_scheduler": (self.lr_scheduler.state_dict()
                                 if self.lr_scheduler is not None else None),
                "client_state": client_state or {},
                # reshape-on-resume metadata: the topology/batch shape
                # this generation was written under (diagnostic + the
                # global-batch preservation rule) and the sampler
                # position (topology-independent: consumed samples are
                # global). Specs are NEVER loaded from here — resume
                # re-derives them from the model + current mesh.
                "topology": self._topology_desc(),
                "batch": {
                    "train_batch_size": self.config.train_batch_size,
                    "micro": self.config.train_micro_batch_size_per_gpu,
                    "gas": self.config.gradient_accumulation_steps,
                },
                "zero_plan": self.plan.describe(),
                "sampler": (sampler.state_dict()
                            if sampler is not None else None),
            },
        }
        path = os.path.join(save_dir, tag,
                            f"shard-{jax.process_index()}.npz")

        # hot tier: replicate this shard to the ring neighbors off the
        # critical path (advisory — a hot-tier failure can never cost
        # the durable save). The dcn transport is collective, so it
        # runs in-caller at this save boundary (every process is here).
        self._last_ckpt_save_dir = save_dir
        if self.hot_store is not None:
            if (os.environ.get("DSTPU_HOT_TRANSPORT") == "dcn"
                    and jax.process_count() > 1):
                self.hot_store.push_collective(tag, chunks, extra)
            else:
                self.hot_store.push_async(tag, chunks, extra)
            if self.plan.cross_slice_replica():
                # MiCS: master/opt replicate over data_outer — register
                # the sibling-slice copy THIS process already holds in
                # HBM as a replica-tier restore source. Its extra omits
                # nprocs: the replica set's completeness is enforced by
                # per-leaf chunk coverage, not by the canonical
                # shard-file count
                rchunks, ridx, rmeta = ser.extract_replica_chunks(
                    self._ckpt_tree())
                rextra = {
                    "index": ridx,
                    "__tree_meta__": rmeta,
                    "user_extra": dict(extra["user_extra"],
                                       nprocs=None,
                                       zero_replica=True),
                }
                self.hot_store.push_zero_replica(tag, rchunks, rextra)

        from .checkpoint_engine import manager as ckpt_manager
        keep_last = getattr(self.config.checkpoint_engine, "keep_last", 0)
        seq = self.global_step   # captured NOW: with async engines two
        # in-flight saves can reach durability out of order; the seq
        # guard keeps 'latest' from regressing to the older one

        def mark_latest():
            ckpt_manager.publish_latest(save_dir, tag, seq=seq)
            # retention GC rides the durability path (the writer thread
            # for async engines), so it can never run before the new
            # generation is durable; gc_tags itself re-verifies the
            # newest tag before deleting anything and never raises
            ckpt_manager.gc_tags(save_dir, keep_last,
                                 counters=self.checkpoint_engine.counters)

        rank0 = jax.process_index() == 0
        if save_latest and jax.process_count() > 1:
            # 'latest' must only ever name a checkpoint whose EVERY shard
            # is durable. on_durable fires when THIS process's shard is
            # down; other ranks may still be writing (especially async) —
            # so drain local writes, then agree cross-process before
            # rank 0 publishes. The agreement is an allgather of per-rank
            # success flags (itself the barrier): a rank whose save
            # failed must still REACH the collective — raising before it
            # would deadlock every surviving rank — and a failure on ANY
            # rank vetoes publication, so 'latest' cannot name a
            # generation with a missing shard.
            err = None
            try:
                self.checkpoint_engine.save((chunks, extra), path)
                self.checkpoint_engine.wait()
            except Exception as e:  # noqa: BLE001 - re-raised after sync
                err = e
            from jax.experimental import multihost_utils
            flags = multihost_utils.process_allgather(
                np.asarray([0.0 if err is not None else 1.0],
                           np.float32))
            all_ok = bool(np.asarray(flags).min() >= 1.0)
            # a no-op engine (checkpoint=none) writes nothing: publishing
            # 'latest' would dangle at an empty tag directory
            if rank0 and all_ok and os.path.exists(path):
                mark_latest()
            elif rank0 and not all_ok:
                log_dist(
                    f"not publishing 'latest' for tag {tag!r}: a rank's "
                    f"shard write failed; the previous durable "
                    f"generation remains the recovery point", ranks=[0])
            if err is not None:
                raise err
        else:
            self.checkpoint_engine.save(
                (chunks, extra), path,
                on_durable=(mark_latest if save_latest and rank0
                            else None))
        self.checkpoint_engine.commit(tag)
        self._write_ckpt_monitor_events(
            "save", (time.perf_counter() - t_start) * 1e3)
        return tag

    def _topology_desc(self):
        t = self.topology
        return {"world": int(self.mesh.size),
                "dp": t.get_data_parallel_world_size(),
                "tp": t.get_model_parallel_world_size(),
                "ep": t.get_expert_parallel_world_size(),
                "seq": t.get_sequence_parallel_world_size(),
                "pipe": t.get_pipe_parallel_world_size()}

    def load_checkpoint(self, load_dir, tag=None,
                        load_optimizer_states=True,
                        load_lr_scheduler_states=True,
                        elastic_reshape=True):
        """See :meth:`_load_checkpoint_inner` — telemetry wrapper:
        restore latency feeds goodput, the serving tier lands in the
        flight recorder (the fact a post-restore crash dump must
        carry), and terminal failures dump before re-raising."""
        if self.telemetry is None:
            return self._load_checkpoint_inner(
                load_dir, tag, load_optimizer_states,
                load_lr_scheduler_states, elastic_reshape)
        self.telemetry.flight.set_root(
            os.path.join(load_dir, "flightrec"))
        t0 = time.perf_counter()
        try:
            out = self._load_checkpoint_inner(
                load_dir, tag, load_optimizer_states,
                load_lr_scheduler_states, elastic_reshape)
        except BaseException as e:
            self.telemetry.note_overhead("checkpoint_restore",
                                         time.perf_counter() - t0)
            self.telemetry.on_crash(e)
            raise
        if out[0] is not None:
            self.telemetry.on_restore(self.last_restore_tier, out[0],
                                      time.perf_counter() - t0)
        return out

    def _load_checkpoint_inner(self, load_dir, tag=None,
                               load_optimizer_states=True,
                               load_lr_scheduler_states=True,
                               elastic_reshape=True):
        """reference engine.py:2750. Returns (path, client_state).

        Recovery semantics: with no explicit ``tag``, the HOT TIER's
        surviving in-memory replicas are tried first (the common
        single-host loss restores with zero persistent-storage reads),
        then the durable candidates: the 'latest'-named generation
        first, then every other durable tag newest-first — a corrupt or
        truncated shard (CRC mismatch, torn zip, missing chunks) makes
        the loader FALL BACK to the previous durable generation instead
        of crashing the restart. Only when a checkpoint exists but NO
        generation is loadable does it raise (resuming silently from
        scratch would be worse). An explicit ``tag`` is never
        substituted. ``self.last_restore_tier`` records which tier
        ('hot'/'replica'/'durable') served the load; with ``'hot'`` or
        ``'replica'`` the returned
        path names the generation but may not exist on persistent
        storage (a hot generation whose durable commit never landed is
        deliberately restorable). Under an elastic agent
        (``ELASTIC_GENERATION`` in the env), a checkpoint that exists
        but has NO loadable generation exits with
        ``CORRUPT_CKPT_EXIT_CODE`` so the agent classifies the failure
        as corrupt-checkpoint (healthy host kept, backoff applied)
        instead of dropping the host as dead.

        Reshape-on-resume (``elastic_reshape``, default on): a
        checkpoint written under a DIFFERENT dp×tp×ep topology or ZeRO
        stage loads anyway — state re-partitions from the global logical
        tensors onto the current plan, gradient-accumulation steps
        rescale so the GLOBAL batch size is preserved, the sampler
        position carries over (consumed samples are global), and the RNG
        key is folded deterministically for the new mesh."""
        import os
        from .checkpoint_engine import serialization as ser
        from .checkpoint_engine import manager as ckpt_manager
        t_start = time.perf_counter()
        # drain, not wait: a previously FAILED async save must not block
        # reading the durable generations that did land
        self.checkpoint_engine.drain()
        if self.hot_store is not None:
            self.hot_store.wait()

        def loader(tag_dir):
            legacy = os.path.join(tag_dir, "state.npz")
            if os.path.exists(legacy):
                return self.checkpoint_engine.load(legacy)
            return ser.load_sharded(tag_dir)

        try:
            tier, cand, flat, header = ckpt_manager.load_best_tiered(
                load_dir, tag, hot_store=self.hot_store, loader=loader,
                counters=self.checkpoint_engine.counters)
        except ser.CheckpointCorruptionError:
            if os.environ.get("ELASTIC_GENERATION") is not None:
                # supervised by an elastic agent: exit with the
                # corrupt-checkpoint code so the agent keeps this
                # (healthy) host and backs off instead of shrinking the
                # world around a storage problem
                from ..elasticity.elastic_agent import (
                    CORRUPT_CKPT_EXIT_CODE)
                logger.error(
                    f"no loadable checkpoint generation under "
                    f"{load_dir}; exiting {CORRUPT_CKPT_EXIT_CODE} for "
                    f"the elastic agent's corrupt-checkpoint handling")
                raise SystemExit(CORRUPT_CKPT_EXIT_CODE)
            raise
        self.last_restore_tier = tier
        if cand is None:
            return None, {}
        path = os.path.join(load_dir, cand)
        # structural template only — no device transfer
        template = jax.eval_shape(self._ckpt_tree)
        tree = ser.unflatten_into(template, flat, header.get("meta"))
        extra = header["extra"]

        master = tree["master"]
        with jax.set_mesh(self.mesh):
            state = dict(self.state)
            if self.offload_enabled:
                self.host_optimizer.load_master_tree(master)
                if load_optimizer_states:
                    self.host_optimizer.load_state_tree(tree["opt"])
                np_dtype = np.dtype(self.param_dtype)
                state["params"] = jax.tree.map(
                    lambda m, s: jax.device_put(
                        np.asarray(m, np.float32).astype(np_dtype), s),
                    master, self.param_shardings)
            else:
                new_master = jax.device_put(master, self.master_shardings)
                new_params = jax.jit(
                    lambda m: self._cast(m, self.param_dtype),
                    out_shardings=self.param_shardings)(new_master)
                state["master"] = new_master
                state["params"] = new_params
                if load_optimizer_states:
                    state["opt"] = jax.device_put(tree["opt"],
                                                  self.opt_shardings)
            state["scale"] = jax.device_put(tree["scale"],
                                            self.state_shardings["scale"])
            state["step"] = jax.device_put(
                jnp.asarray(tree["step"], jnp.int32),
                self.state_shardings["step"])
            state["skipped"] = jax.device_put(
                jnp.asarray(tree.get("skipped", 0), jnp.int32),
                self.state_shardings["skipped"])
            state["rng"] = jax.device_put(
                jax.random.wrap_key_data(tree["rng_data"]),
                self.state_shardings["rng"])
        self.state = state
        self.global_step = int(extra.get("global_step", 0))
        self.micro_steps = int(extra.get("micro_steps", 0))
        if (load_lr_scheduler_states and self.lr_scheduler is not None
                and extra.get("lr_scheduler") is not None):
            self.lr_scheduler.load_state_dict(extra["lr_scheduler"])
        # sampler position: consumed samples are GLOBAL, so the position
        # carries across any topology. Applied to a live sampler when
        # one exists; stashed otherwise and installed by deepspeed_io
        # when the sampler is built after the resume.
        sampler_state = extra.get("sampler")
        if sampler_state is not None:
            live = getattr(self, "data_sampler", None)
            if live is not None:
                live.load_state_dict(sampler_state)
            else:
                self._resume_sampler_state = sampler_state
        if elastic_reshape:
            self._reshape_on_resume(extra)
        self._write_ckpt_monitor_events(
            "load", (time.perf_counter() - t_start) * 1e3)
        return path, extra.get("client_state", {})

    def _preserve_saved_global_batch(self, extra):
        """The global-batch preservation rule: the checkpoint's
        train_batch_size wins over a batch DERIVED from a
        micro-batch-only config (an EXPLICIT train_batch_size in the
        user's raw config is their call and is respected, with a
        warning). With the per-host micro batch fixed,
        gradient-accumulation steps rescale to
        ``saved_train_batch / (micro * dp)`` — an indivisible
        combination raises instead of silently training at a different
        effective batch. Returns True when the step programs were
        rebuilt under the new gas."""
        from .constants import TRAIN_BATCH_SIZE
        saved_batch = extra.get("batch") or {}
        target = saved_batch.get("train_batch_size")
        if not target or target == self.config.train_batch_size:
            return False
        if TRAIN_BATCH_SIZE in getattr(self.config, "_raw", {}):
            log_dist(
                f"resume: checkpoint global batch {target} != the "
                f"explicitly configured train_batch_size "
                f"{self.config.train_batch_size}; the explicit config "
                f"wins (drop train_batch_size from the config to "
                f"preserve the checkpoint's batch across topologies)",
                ranks=[0])
            return False
        micro = self.config.train_micro_batch_size_per_gpu
        dp = self.topology.get_data_parallel_world_size()
        new_gas = target // max(1, micro * dp)
        if new_gas < 1 or new_gas * micro * dp != target:
            raise ValueError(
                f"reshape-on-resume: cannot preserve the global "
                f"batch size {target} at dp={dp} with "
                f"micro_batch={micro} (needs gradient_"
                f"accumulation_steps={target}/{micro * dp}); "
                f"pick a micro batch that divides it")
        log_dist(
            f"resume: preserving global batch {target}: "
            f"gradient_accumulation_steps "
            f"{self.config.gradient_accumulation_steps} -> {new_gas} "
            f"at dp={dp}", ranks=[0])
        self.config.gradient_accumulation_steps = new_gas
        self.config.train_batch_size = target
        self.tput_timer.batch_size = target
        # gas is closed over by every jitted step program
        self._build_programs()
        return True

    def _reshape_on_resume(self, extra):
        """Adapt the resumed run to a topology change (runtime/zero/
        partitioning.py reshape_diff documents what re-partitioned; the
        device_put in load_checkpoint already re-sharded the global
        logical tensors onto the current plan). Returns True when the
        checkpoint was written under a different topology.

        The global-batch preservation rule: the checkpoint's
        train_batch_size wins. With the per-host micro batch fixed,
        gradient-accumulation steps rescale to
        ``saved_train_batch / (micro * new_dp)`` — an indivisible
        combination raises instead of silently training at a different
        effective batch. The RNG key folds with the new dp world so the
        resumed world's per-microstep streams are deterministic (a
        same-topology resume keeps the key bitwise)."""
        from ..utils import fault_injection
        from .zero.partitioning import reshape_diff
        saved_topo = extra.get("topology") or {}
        cur_topo = self._topology_desc()
        stage_changed = ("zero_stage" in extra
                        and extra["zero_stage"] != self.zero_stage)
        topo_changed = bool(saved_topo) and saved_topo != cur_topo
        # global-batch preservation runs REGARDLESS of a topology
        # change: a run that was itself reshaped saves gas≠1 under its
        # own topology, and a fresh same-topology engine built from the
        # micro-batch-only config would silently shrink the effective
        # batch on resume
        rescaled = self._preserve_saved_global_batch(extra)
        if rescaled:
            # accumulation boundaries re-align to the new gas
            self.micro_steps = self.global_step * \
                self.config.gradient_accumulation_steps
        if not topo_changed and not stage_changed:
            return rescaled
        fault_injection.fire("reshape")
        diff = reshape_diff(extra.get("zero_plan"), self.plan)
        log_dist(
            f"reshape-on-resume: checkpoint topology {saved_topo} / "
            f"stage {extra.get('zero_stage')} -> {cur_topo} / stage "
            f"{self.zero_stage}; {len(diff['resharded'])} leaves "
            f"re-partitioned (group {diff['old_partition_group']} -> "
            f"{diff['new_partition_group']}), "
            f"{len(diff['replicated'])} replicated on the new mesh",
            ranks=[0])
        if topo_changed:
            self.micro_steps = self.global_step * \
                self.config.gradient_accumulation_steps
            # deterministic RNG fold for the new mesh: every surviving
            # world derives the same key, distinct from the old world's
            fold = int(cur_topo["dp"]) * 1000003 + int(cur_topo["world"])
            rep = self.state_shardings["rng"]
            with jax.set_mesh(self.mesh):
                self.state["rng"] = jax.jit(
                    lambda r: jax.random.fold_in(r, fold),
                    out_shardings=rep)(self.state["rng"])
        if self.monitor.enabled:
            self.monitor.write_events([
                ("Train/Checkpoint/reshape", 1, self.global_step),
            ])
        if self.telemetry is not None:
            self.telemetry.record_event(
                "reshape", saved=saved_topo, current=cur_topo,
                stage=self.zero_stage)
        return True

    def save_checkpoint_terminate(self):
        """Fork parity (engine.py:3114): drain async checkpoint work."""
        dist.barrier()
        self.checkpoint_engine.wait()
        self.checkpoint_engine.shutdown()
        if self.hot_store is not None:
            self.hot_store.shutdown()
        if self.telemetry is not None:
            self.telemetry.close()
        dist.barrier()

    def save_16bit_model(self, save_dir, dtype=None):
        """Consolidated HF export (reference engine.py:3625
        ``save_16bit_model`` + utils/zero_to_fp32.py): write the CURRENT
        model weights — whatever the ZeRO stage or mesh sharding — as a
        standard HuggingFace checkpoint directory that ``transformers``
        loads directly.

        TPU-first: no per-rank partitioned files to stitch. The bf16
        param tree already exists as global jax.Arrays; a single host
        gather (process_allgather across hosts) consolidates it, and
        rank 0 writes model.safetensors + config.json via
        checkpoint/hf_export.py. Returns the save path (all ranks).
        """
        from ..checkpoint.hf_export import export_hf
        params = self.state["params"]
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            params = multihost_utils.process_allgather(params, tiled=True)
        else:
            params = jax.tree.map(lambda a: np.asarray(a), params)
        if jax.process_index() == 0:
            export_hf(self.model, params, save_dir,
                      dtype=dtype or jnp.dtype(self.param_dtype).name)
        dist.barrier()
        return save_dir

    def eval_loss(self, batch):
        batch = self._shard_batch(batch, with_gas_dim=False)
        with jax.set_mesh(self.mesh):
            return self._eval_loss_jit(self.state["params"], batch)
