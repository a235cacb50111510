"""JSON config -> typed config objects.

Counterpart of the reference's ``runtime/config.py:706 DeepSpeedConfig``
(pydantic there; plain dataclasses here — no extra deps, static and
hashable so configs can feed jit). Implements the same batch-size triad
resolution (train_batch = micro_batch * grad_accum * dp_world) with the
reference's error semantics, precision blocks, ZeRO block, and the fork's
checkpoint-engine selection keys (reference runtime/config.py:909-926).
"""

import json
from dataclasses import dataclass, field, fields, asdict

from . import constants as C
from ..utils.logging import logger


class DeepSpeedConfigError(Exception):
    pass


@dataclass
class FP16Config:
    enabled: bool = False
    loss_scale: float = 0.0          # 0 = dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    min_loss_scale: float = 1.0


@dataclass
class BF16Config:
    enabled: bool = False


@dataclass
class OffloadConfig:
    """Reference zero/offload_config.py DeepSpeedZeroOffloadOptimizerConfig /
    ...ParamConfig: where the offloaded state lives."""
    device: str = "none"              # none | cpu | nvme
    nvme_path: str = "/tmp/dstpu_swap"
    pin_memory: bool = True           # accepted for compatibility
    buffer_count: int = 4             # accepted for compatibility

    @classmethod
    def normalize(cls, val):
        """Accept bool (true -> cpu), reference-style dict, or None."""
        if isinstance(val, cls):
            return val
        if val is None or val is False:
            return cls()
        if val is True:
            return cls(device="cpu")
        if isinstance(val, dict):
            known = {f.name for f in fields(cls)}
            out = cls(**{k: v for k, v in val.items() if k in known})
            out.device = str(out.device).lower()
            if out.device not in ("none", "cpu", "nvme"):
                raise DeepSpeedConfigError(
                    f"offload device must be none|cpu|nvme, got "
                    f"{out.device!r}")
            return out
        raise DeepSpeedConfigError(f"bad offload config: {val!r}")

    @property
    def enabled(self):
        return self.device != "none"


@dataclass
class ZeroConfig:
    """Mirrors reference zero/config.py:82 DeepSpeedZeroConfig knobs that are
    meaningful under XLA. Bucket sizes/overlap are accepted for config
    compatibility; XLA's scheduler handles what streams+buckets did."""
    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = int(5e8)
    allgather_bucket_size: int = int(5e8)
    overlap_comm: bool = True
    round_robin_gradients: bool = False
    sub_group_size: int = int(1e9)
    prefetch_bucket_size: int = int(5e7)
    param_persistence_threshold: int = int(1e5)
    model_persistence_threshold: int = int(1e10)
    max_live_parameters: int = int(1e9)
    offload_optimizer: object = False   # bool | dict -> OffloadConfig
    offload_param: object = False       # bool | dict -> OffloadConfig
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False
    hpz_partition_size: int = 1
    mics_shard_size: int = -1

    def __post_init__(self):
        self.offload_optimizer = OffloadConfig.normalize(
            self.offload_optimizer)
        self.offload_param = OffloadConfig.normalize(self.offload_param)
        if self.stage not in (0, 1, 2, 3):
            raise DeepSpeedConfigError(f"invalid ZeRO stage {self.stage}")
        mics = self.mics_shard_size not in (-1, 0)
        hpz = self.hpz_partition_size > 1
        if mics and hpz and self.mics_shard_size != self.hpz_partition_size:
            raise DeepSpeedConfigError(
                f"mics_shard_size={self.mics_shard_size} and "
                f"hpz_partition_size={self.hpz_partition_size} disagree; "
                "both subdivide the same inner data axis — set one (or "
                "equal values)")


@dataclass
class TensorParallelConfig:
    size: int = 1


@dataclass
class PipelineConfig:
    """Pipeline-parallelism block (runtime/pipe/ — the engine resolves
    it and installs ``model._pipe_cfg``; GPT2Pipe consults it per loss):

      stages              pipe mesh axis size (the topology builder
                          reads this when no explicit topology is given).
      micro_batches       microbatches in flight. 0 = auto: the
                          'pipe_microbatch' autotune op's winner for
                          this (stages, batch, seq, d_model) bucket
                          when the winner cache has one, else 2*stages
                          (amortizes the fill/drain bubble).
      schedule            'auto' (defer to the model's own
                          pipe_schedule knob — back-compat; the bench/
                          probe paths set 'zb' explicitly) | 'gpipe'
                          (fill-drain + autodiff backward) | '1f1b'
                          (interleaved, O(stages) live activations) |
                          'zb' (zero-bubble: 1F1B with the backward
                          W/B split filling the drain ticks —
                          runtime/pipe/spmd.py pipeline_zb_grads).
      offload_activations host placement of the steady-state
                          executors' activation rings (and the GPipe
                          path's saved residuals via the offload remat
                          policy): 'auto' = on iff the backend has a
                          distinct host memory kind AND the estimated
                          train state does not fit HBM (the 13B-on-
                          small-pods case); true forces (identity on
                          single-memory-space backends, with a
                          warning); false off.
      offload_moments     optimizer-moment placement on host memory
                          via sharding-with-memory-kind: 'auto' = off
                          (moments offload changes the optimizer
                          update's memory traffic every step — opt in
                          explicitly or let the HBM-fit heuristic of a
                          13B recipe set it); true requires the
                          backend kind (degrades with a warning).
      offload_double_buffer
                          prefetch the next tick's ring read one tick
                          early so the H2D copy hides under compute
                          (the comm-overlap discipline applied to host
                          copies); false fetches at use (A/B lever).
    """
    stages: int = 1
    micro_batches: int = 0            # 0 = auto (winner cache, else 2S)
    partition_method: str = "uniform"
    activation_checkpoint_interval: int = 0
    schedule: str = "auto"            # auto | gpipe | 1f1b | zb
    offload_activations: object = "auto"   # "auto" | bool
    offload_moments: object = "auto"       # "auto" | bool
    offload_double_buffer: bool = True

    def __post_init__(self):
        if self.schedule not in ("auto", "gpipe", "1f1b", "zb"):
            raise DeepSpeedConfigError(
                f"pipeline.schedule must be auto|gpipe|1f1b|zb, got "
                f"{self.schedule!r}")
        for name in ("offload_activations", "offload_moments"):
            if getattr(self, name) not in (True, False, "auto"):
                raise DeepSpeedConfigError(
                    f"pipeline.{name} must be true|false|'auto', got "
                    f"{getattr(self, name)!r}")
        if not isinstance(self.micro_batches, int) \
                or self.micro_batches < 0:
            raise DeepSpeedConfigError(
                f"pipeline.micro_batches must be an int >= 0 (0 = "
                f"auto), got {self.micro_batches!r}")
        if not isinstance(self.stages, int) or self.stages < 1:
            raise DeepSpeedConfigError(
                f"pipeline.stages must be an int >= 1, got "
                f"{self.stages!r}")

    def resolve_schedule(self, model_schedule=None):
        """'auto' defers to the model's own pipe_schedule knob (so the
        existing model-config surface keeps its meaning); an explicit
        block schedule wins over the model."""
        if self.schedule != "auto":
            return self.schedule
        return model_schedule or "gpipe"

    @staticmethod
    def hbm_fits(est_state_bytes, hbm_bytes, margin=0.8):
        """The HBM-fit heuristic behind offload 'auto': does the
        estimated per-chip train state fit in ``margin`` of HBM?
        Unknown sizes (None/0) count as fitting — 'auto' must never
        turn offload on blind."""
        if not est_state_bytes or not hbm_bytes:
            return True
        return est_state_bytes <= margin * hbm_bytes

    def resolve_offload_activations(self, available, pipe_world=1,
                                    est_state_bytes=None, hbm_bytes=None):
        """'auto': on iff the backend can stage to host, a pipe axis is
        actually present, and the HBM-fit heuristic says the state does
        NOT fit — the reference only swaps when memory forces it."""
        if self.offload_activations != "auto":
            return bool(self.offload_activations)
        return bool(available and pipe_world > 1
                    and not self.hbm_fits(est_state_bytes, hbm_bytes))

    def resolve_offload_moments(self, available):
        """'auto' = off (see the field doc); True degrades to off with
        the host_stage warning when the backend has one memory space."""
        if self.offload_moments == "auto":
            return False
        return bool(self.offload_moments) and bool(available)


@dataclass
class OptimizerConfig:
    type: str = "AdamW"
    params: dict = field(default_factory=dict)


@dataclass
class SchedulerConfig:
    type: str = None
    params: dict = field(default_factory=dict)


@dataclass
class CheckpointEngineConfig:
    """Fork parity: reference runtime/config.py:909-926 registers
    datastates/async/none/torch_sn_async engine configs; we expose one
    block with a type switch, plus the crash-consistency knobs
    (retry/degrade policy and retention)."""
    type: str = "sync"                # sync | async | native | none
    host_cache_bytes: int = 1 << 30   # pinned-host staging budget (async/native)
    writer_threads: int = 2
    max_inflight: int = 2
    # retry/degrade policy: each shard write gets save_retries retries
    # with capped exponential backoff, then the engine's degraded writer
    # (native -> python; async pool dead -> in-caller sync write)
    save_retries: int = 2
    retry_backoff_s: float = 0.05
    retry_backoff_cap_s: float = 2.0
    # retention: keep the newest keep_last durable tags, GC older ones
    # only after the newest verifies (CRC + chunk coverage). 0 = keep all.
    keep_last: int = 0
    # hot tier (checkpoint_engine/hot_tier.py): peer-replicated
    # in-memory generations so the common single-host loss restores with
    # zero persistent-storage reads.
    #   hot_tier      "auto" (on iff an elastic launcher exported the
    #                 ring env — DSTPU_HOT_PEERS/DSTPU_HOT_TIER_ROOT/
    #                 DSTPU_HOT_TRANSPORT) | true | false. 'auto' is
    #                 deliberately NOT on for a bare multi-process
    #                 world: the default fs transport writes into
    #                 node-local tmpfs, which only survives a host loss
    #                 when the launcher wired the ring (or the dcn
    #                 transport moves bytes between hosts) — pushing
    #                 replicas nobody could ever restore from would be
    #                 pure per-save overhead
    #   hot_replicas  K: ring neighbors receiving each shard replica
    #   hot_root      store root ("" = DSTPU_HOT_TIER_ROOT env, else
    #                 tmpfs /dev/shm — host RAM, the point of the tier)
    #   hot_keep_last hot-tier retention (a bounded RAM cache, not an
    #                 archive)
    hot_tier: object = "auto"
    hot_replicas: object = 1          # int >= 0 | "auto" (winner cache)
    hot_root: str = ""
    hot_keep_last: int = 2
    # async-push backlog bound (hot_tier.push_async): at most this many
    # pending pushes; the oldest queued one is dropped (counted as an
    # advisory hot_push_errors) and a newer push of the same tag
    # supersedes a still-queued one
    hot_max_inflight_pushes: int = 4
    # preemption-graceful drain: on SIGTERM (TPU maintenance notice /
    # elastic-agent forward) finish the in-flight step, force one
    # hot+replica push and a flight-recorder dump, then exit with
    # PREEMPTED_EXIT_CODE so the agent classifies 'preempted' (no
    # backoff). "auto" = on iff supervised (ELASTIC_GENERATION in env
    # or DSTPU_PREEMPT_DRAIN exported) | true | false.
    preempt_drain: object = "auto"

    def __post_init__(self):
        if self.save_retries < 0:
            raise DeepSpeedConfigError(
                f"checkpoint_engine.save_retries must be >= 0, got "
                f"{self.save_retries}")
        if self.keep_last < 0:
            raise DeepSpeedConfigError(
                f"checkpoint_engine.keep_last must be >= 0 (0 disables "
                f"retention GC), got {self.keep_last}")
        if self.hot_tier not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"checkpoint_engine.hot_tier must be true|false|'auto', "
                f"got {self.hot_tier!r}")
        if self.hot_replicas != "auto" and (
                not isinstance(self.hot_replicas, int)
                or isinstance(self.hot_replicas, bool)
                or self.hot_replicas < 0):
            raise DeepSpeedConfigError(
                f"checkpoint_engine.hot_replicas must be an int >= 0 or "
                f"'auto', got {self.hot_replicas!r}")
        if self.hot_keep_last < 1:
            raise DeepSpeedConfigError(
                f"checkpoint_engine.hot_keep_last must be >= 1 (the "
                f"tier must hold at least the newest generation), got "
                f"{self.hot_keep_last}")
        if not isinstance(self.hot_max_inflight_pushes, int) \
                or isinstance(self.hot_max_inflight_pushes, bool) \
                or self.hot_max_inflight_pushes < 1:
            raise DeepSpeedConfigError(
                f"checkpoint_engine.hot_max_inflight_pushes must be an "
                f"int >= 1 (the bound must admit at least one pending "
                f"push), got {self.hot_max_inflight_pushes!r}")
        if self.preempt_drain not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"checkpoint_engine.preempt_drain must be "
                f"true|false|'auto', got {self.preempt_drain!r}")

    def resolve_preempt_drain(self):
        """'auto' arms the SIGTERM drain iff something supervises us —
        an elastic agent (ELASTIC_GENERATION) or an operator export
        (DSTPU_PREEMPT_DRAIN). Unsupervised runs keep the default
        SIGTERM disposition: nothing would classify the distinct exit
        code, and hijacking the signal would only delay teardown."""
        import os
        if self.preempt_drain != "auto":
            return bool(self.preempt_drain)
        return bool(os.environ.get("ELASTIC_GENERATION") is not None
                    or os.environ.get("DSTPU_PREEMPT_DRAIN"))

    def resolve_hot_tier(self, nprocs=1):
        """'auto' turns the tier on iff an elastic launcher (or the
        operator) exported the ring env. ``nprocs`` is accepted for
        call-site symmetry but deliberately unused — see the hot_tier
        field comment."""
        import os
        if self.hot_tier != "auto":
            return bool(self.hot_tier)
        return bool(os.environ.get("DSTPU_HOT_PEERS")
                    or os.environ.get("DSTPU_HOT_TIER_ROOT")
                    or os.environ.get("DSTPU_HOT_TRANSPORT"))


@dataclass
class CommOverlapConfig:
    """Communication-overlap block (the reference's ``overlap_comm`` +
    ZeRO++ hierarchical collectives, expressed TPU-natively — see
    runtime/zero/overlap.py for what each knob turns into):

      enabled       "auto" (on iff dp_world > 1) | true | false. Turns on
                    XLA's latency-hiding scheduler / async-collective
                    flags and the per-layer grad-reduction annotations.
      bucket_mb     layer-granular reduce gate: a scan layer whose grad
                    bytes are below this emits no in-scan collective (its
                    reduction coalesces into the post-backward one, the
                    reference's bucketing of small grads); also feeds the
                    GPU combine-threshold flags. 0 = annotate everything;
                    "auto" = the 'comm_bucket' autotune winner for this
                    (device, topology, layer-payload) bucket, 32 on a
                    cold cache (byte-identical to the hand-set default).
      prefetch      ZeRO-3: explicit per-layer param gather at the top of
                    the scan body + unroll hint + backward all-gather
                    pipelining flag, so layer i+1's gather flies under
                    layer i's matmuls (PartitionedParameterCoordinator
                    prefetch, declaratively).
      hierarchical  "auto" (on iff the mesh has data_outer > 1) | bool.
                    Two-stage grad reduction: reduce-scatter over the
                    inner ('data','expert') ICI axes, then the cross-
                    slice 'data_outer' (DCN) hop on the already-scattered
                    shard (ZeRO++/MiCS hierarchical partitioning).
      dcn_quantize  int8 block-quantize round trip on the inner-reduced
                    gradient shard feeding the DCN hop (ZeRO++ qgZ
                    numerics). Requires a hierarchical data_outer stage
                    — ignored (with a warning) otherwise; wire-level
                    int8 for explicit pipelines lives in
                    comm/quantized.py. "auto" = the 'dcn_quantize'
                    autotune winner (off on a cold cache — quantization
                    changes numerics, never turned on blind by default).
      scan_unroll   unroll factor of the layer scan when comm overlap is
                    on (gives XLA unrolled iterations to slide gathers /
                    reductions across): int >= 1 | "auto" (the
                    'scan_unroll' winner; 2 on a cold cache — the
                    hand-set value overlap has shipped with).
      set_xla_flags whether the engine may append overlap flags to
                    XLA_FLAGS (only effective before backend init; the
                    DSTPU_COMM_OVERLAP=1 env does it at import time).
    """
    enabled: object = "auto"          # "auto" | bool
    bucket_mb: object = 32            # int >= 0 | "auto" (winner cache)
    prefetch: bool = True
    hierarchical: object = "auto"     # "auto" | bool
    dcn_quantize: object = False      # bool | "auto" (winner cache)
    scan_unroll: object = "auto"      # int >= 1 | "auto" (winner cache)
    set_xla_flags: bool = True

    def __post_init__(self):
        if self.enabled not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"comm_overlap.enabled must be true|false|'auto', got "
                f"{self.enabled!r}")
        if self.hierarchical not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"comm_overlap.hierarchical must be true|false|'auto', "
                f"got {self.hierarchical!r}")
        if self.bucket_mb != "auto" and (
                not isinstance(self.bucket_mb, int)
                or isinstance(self.bucket_mb, bool)
                or self.bucket_mb < 0):
            raise DeepSpeedConfigError(
                f"comm_overlap.bucket_mb must be an int >= 0 or 'auto', "
                f"got {self.bucket_mb!r}")
        if self.dcn_quantize not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"comm_overlap.dcn_quantize must be true|false|'auto', "
                f"got {self.dcn_quantize!r}")
        if self.scan_unroll != "auto" and (
                not isinstance(self.scan_unroll, int)
                or isinstance(self.scan_unroll, bool)
                or self.scan_unroll < 1):
            raise DeepSpeedConfigError(
                f"comm_overlap.scan_unroll must be an int >= 1 or "
                f"'auto', got {self.scan_unroll!r}")

    def resolve_enabled(self, dp_world_size):
        if self.enabled == "auto":
            return dp_world_size > 1
        return bool(self.enabled)

    def resolve_hierarchical(self, data_outer_size):
        if self.hierarchical == "auto":
            return data_outer_size > 1
        return bool(self.hierarchical)


@dataclass
class SequenceConfig:
    """Sequence/context-parallelism block (sequence/ring.py — consumed by
    models whose ``attention_backend='ring'`` when the mesh has seq > 1):

      layout        'zigzag' (default): each rank holds one early + one
                    mirrored late sequence chunk, so causal work is
                    identical across ranks and fully-masked chunk pairs
                    are statically skipped (~2x causal FLOPs saved vs
                    computing-then-masking). 'contiguous': the naive
                    layout (every pair computed, positionally masked) —
                    the A/B fallback.
      block_kernel  'auto' (default): ring steps run the carry-state
                    blockwise Pallas flash kernel with tiles resolved
                    from the autotune winner cache (op 'ring_block';
                    r05 defaults on a miss) | true (kernel, r05 tiles) |
                    false (dense einsum block steps — reference path).
      double_buffer issue each step's KV ppermute BEFORE the step's
                    kernels so the rotation hides under compute (the
                    comm-overlap discipline); false serializes
                    rotate-then-compute (A/B lever).
      rotate_chunks split each KV rotation into this many head-dim
                    ppermutes so the first chunk lands early: int >= 1 |
                    "auto" (the 'ring_rotate' autotune winner; 1 — the
                    fused single-ppermute program — on a cold cache).
    """
    layout: str = "zigzag"
    block_kernel: object = "auto"
    double_buffer: bool = True
    rotate_chunks: object = "auto"    # int >= 1 | "auto" (winner cache)

    def __post_init__(self):
        if self.layout not in ("zigzag", "contiguous"):
            raise DeepSpeedConfigError(
                f"sequence.layout must be 'zigzag'|'contiguous', got "
                f"{self.layout!r}")
        if self.block_kernel not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"sequence.block_kernel must be true|false|'auto', got "
                f"{self.block_kernel!r}")
        if self.rotate_chunks != "auto" and (
                not isinstance(self.rotate_chunks, int)
                or isinstance(self.rotate_chunks, bool)
                or self.rotate_chunks < 1):
            raise DeepSpeedConfigError(
                f"sequence.rotate_chunks must be an int >= 1 or 'auto', "
                f"got {self.rotate_chunks!r}")


@dataclass
class MoEConfig:
    """Dropless-MoE block (moe/sharded_moe.py + ops/pallas/
    grouped_matmul.py — the engine installs it on the model as
    ``model._moe_cfg``; mixtral consults it per dispatch, and for
    MoE-layer models (GPT2MoE) an explicit non-"auto"
    ``grouped_kernel`` here overrides the model-config knob):

      grouped_kernel   expert-FFN engine for the ragged (dropless)
                       paths: "auto" (default — from platform, dtype
                       and shape: on a TPU a SwiGLU call of few rows a
                       group, a decode step or a prefill bucket, takes
                       the forward grouped kernel with tiles chosen
                       from the shape; everything else, and every call
                       off the TPU, the lax.ragged_dot program:
                       sharded_moe.resolve_grouped_params) | true
                       (Pallas grouped-GEMM kernel, default tiles) |
                       false (ragged_dot).
      hierarchical_a2a "auto" (default — the EP all_to_all stages
                       ICI -> DCN iff the mesh has a data_outer axis
                       > 1 and the experts divide the combined
                       (outer, expert) shard grid) | true (require the
                       staging; loud error if experts don't divide) |
                       false (always the flat single-hop exchange).
      dcn_quantize     qgZ int8 block round trip on the token payload
                       of the DCN legs ONLY (both directions of the
                       data_outer hop; the ICI hop stays exact) —
                       requires a hierarchical stage, ignored without
                       one (same discipline as comm_overlap).
    """
    grouped_kernel: object = "auto"    # "auto" | bool
    hierarchical_a2a: object = "auto"  # "auto" | bool
    dcn_quantize: object = False       # bool | "auto" (winner cache)

    def __post_init__(self):
        if self.grouped_kernel not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"moe.grouped_kernel must be true|false|'auto', got "
                f"{self.grouped_kernel!r}")
        if self.hierarchical_a2a not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"moe.hierarchical_a2a must be true|false|'auto', got "
                f"{self.hierarchical_a2a!r}")
        if self.dcn_quantize not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"moe.dcn_quantize must be true|false|'auto', got "
                f"{self.dcn_quantize!r}")


@dataclass
class QuantizeConfig:
    """One roof for the training engine's low-precision levers
    (runtime/engine.py consumes it at build). Every field is a planner
    knob — "auto" spellings resolve from the autotune winner cache with
    cold-cache defaults equal to the hand-set values, so a config that
    only adds ``{"quantize": {}}`` compiles byte-identical programs.

      grad_dcn         int8 block-quantize round trip on the DCN
                       (data_outer) leg of the staged ZeRO grad
                       reduction. None (default) defers to
                       comm_overlap.dcn_quantize; true|false|"auto"
                       OVERRIDE it (one quantize block can steer a
                       config whose comm_overlap block is shared).
      moe_dcn          same, for the MoE hierarchical all_to_all's DCN
                       legs; None defers to moe.dcn_quantize.
      int8_matmul      W8A8 dense-MLP compute (ops/pallas/quantization
                       .int8_matmul — dynamic rowwise activation codes x
                       channelwise weight codes, int32 accumulate,
                       straight-through fp grads). false (default) |
                       true | "auto" (the 'mlp_int8' winner cache per
                       shape bucket; winners must pass the registry
                       parity gate before caching, cold cache = off).
      moe_int8_matmul  W8A8 expert-FFN compute (grouped_int8_matmul
                       over lax.ragged_dot): false | true | "auto"
                       (the 'moe_grouped_int8' winner cache).
    """
    grad_dcn: object = None          # None | bool | "auto"
    moe_dcn: object = None           # None | bool | "auto"
    int8_matmul: object = False      # bool | "auto"
    moe_int8_matmul: object = False  # bool | "auto"

    def __post_init__(self):
        if self.grad_dcn not in (None, True, False, "auto"):
            raise DeepSpeedConfigError(
                f"quantize.grad_dcn must be null|true|false|'auto', got "
                f"{self.grad_dcn!r}")
        if self.moe_dcn not in (None, True, False, "auto"):
            raise DeepSpeedConfigError(
                f"quantize.moe_dcn must be null|true|false|'auto', got "
                f"{self.moe_dcn!r}")
        if self.int8_matmul not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"quantize.int8_matmul must be true|false|'auto', got "
                f"{self.int8_matmul!r}")
        if self.moe_int8_matmul not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"quantize.moe_int8_matmul must be true|false|'auto', "
                f"got {self.moe_int8_matmul!r}")


@dataclass
class AutotuneConfig:
    """Measured kernel dispatch (autotuning/kernel_dispatch.py): kernel
    tunables set to "auto" (flash blocks / mlp_kernel / fused_layernorm
    / fused-CE tiles) resolve against a persistent winner cache keyed by
    (device_kind, op, shape-bucket, dtype).

      mode         "" = inherit the DSTPU_AUTOTUNE env (default
                   cache_only) | off | cache_only | on_first_use |
                   search. cache_only never measures — a cold key falls
                   back to the r05-proven defaults; on_first_use runs a
                   measured search per missing key at first trace and
                   persists the winner; search re-measures every key
                   once per process (cache pre-warming/re-validation).
      cache_path   winner cache file ("" = DSTPU_AUTOTUNE_CACHE env or
                   ~/.cache/deepspeed_tpu/kernel_autotune.json). Entries
                   record the chip they were measured on; a cache from
                   another device_kind (e.g. interpret-mode CPU) is
                   refused, not applied.
      chain_lengths / reps
                   search timing knobs: candidates are timed as the
                   slope between two lax.scan chain lengths inside one
                   jit (dispatch-latency cancellation), best-of-reps.
    """
    mode: str = ""
    cache_path: str = ""
    chain_lengths: object = (8, 24)
    reps: int = 3

    def __post_init__(self):
        if self.mode not in ("", "off", "cache_only", "on_first_use",
                             "search"):
            raise DeepSpeedConfigError(
                f"autotune.mode must be ''|off|cache_only|on_first_use|"
                f"search, got {self.mode!r}")
        try:
            k1, k2 = (int(v) for v in self.chain_lengths)
        except (TypeError, ValueError):
            raise DeepSpeedConfigError(
                f"autotune.chain_lengths must be two ints, got "
                f"{self.chain_lengths!r}")
        if not 0 < k1 < k2:
            raise DeepSpeedConfigError(
                f"autotune.chain_lengths needs 0 < k1 < k2, got "
                f"{(k1, k2)}")
        self.chain_lengths = (k1, k2)
        if not isinstance(self.reps, int) or self.reps < 1:
            raise DeepSpeedConfigError(
                f"autotune.reps must be an int >= 1, got {self.reps!r}")


@dataclass
class TelemetryConfig:
    """Pod telemetry block (monitor/telemetry.py + flight_recorder.py —
    the always-on observability layer the engine wires through
    MonitorMaster):

      enabled          "auto" (default: on iff a monitor backend is
                       configured, DSTPU_TELEMETRY=1, a flight-recorder
                       dir is exported (DSTPU_FLIGHTREC_DIR), or the
                       process runs under an elastic agent
                       (ELASTIC_GENERATION)) | true | false.
      interval_steps   steps between telemetry flushes (percentiles,
                       MFU, goodput, cluster aggregation, opportunistic
                       flight dumps). The step path itself only appends
                       to a ring.
      cluster_agg      "auto" (on iff the jax world is multi-process or
                       a fs-transport ring is exported via
                       DSTPU_TELEM_DIR + DSTPU_TELEM_PEERS /
                       DSTPU_HOT_PEERS) | true | false — the pod-wide
                       p50/p99 + straggler-delta aggregation.
      flight_recorder_size
                       bounded in-memory event ring (steps, fault
                       points, restores + tier, reshapes, profiler
                       actions) dumped to
                       ``{ckpt_root}/flightrec/host{n}.json`` on
                       crash/SIGTERM and opportunistically each flush.
      profile_port     jax.profiler server port for live xprof attach
                       (0 = DSTPU_PROFILE_PORT env or off). Step-ranged
                       captures arm via DSTPU_PROFILE_STEPS=a:b or a
                       PROFILE trigger file in the flight-recorder dir.
      flightrec_dir    explicit dump dir ("" = DSTPU_FLIGHTREC_DIR env,
                       else derived from the first save_checkpoint's
                       save_dir).
    """
    enabled: object = "auto"          # "auto" | bool
    interval_steps: int = 20
    cluster_agg: object = "auto"      # "auto" | bool
    flight_recorder_size: int = 256
    profile_port: int = 0
    flightrec_dir: str = ""

    def __post_init__(self):
        if self.enabled not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"telemetry.enabled must be true|false|'auto', got "
                f"{self.enabled!r}")
        if self.cluster_agg not in (True, False, "auto"):
            raise DeepSpeedConfigError(
                f"telemetry.cluster_agg must be true|false|'auto', got "
                f"{self.cluster_agg!r}")
        if not isinstance(self.interval_steps, int) \
                or self.interval_steps < 1:
            raise DeepSpeedConfigError(
                f"telemetry.interval_steps must be an int >= 1, got "
                f"{self.interval_steps!r}")
        if not isinstance(self.flight_recorder_size, int) \
                or self.flight_recorder_size < 8:
            raise DeepSpeedConfigError(
                f"telemetry.flight_recorder_size must be an int >= 8, "
                f"got {self.flight_recorder_size!r}")
        if not isinstance(self.profile_port, int) or self.profile_port < 0:
            raise DeepSpeedConfigError(
                f"telemetry.profile_port must be an int >= 0, got "
                f"{self.profile_port!r}")

    def resolve_enabled(self, monitor_enabled=False):
        """'auto' turns telemetry on when someone can see it (a monitor
        backend) or someone supervises it (elastic agent / exported
        flight-recorder dir)."""
        if self.enabled != "auto":
            return bool(self.enabled)
        import os
        return bool(monitor_enabled
                    or os.environ.get("DSTPU_TELEMETRY") == "1"
                    or os.environ.get("DSTPU_FLIGHTREC_DIR")
                    or os.environ.get("ELASTIC_GENERATION") is not None)

    def resolve_cluster_agg(self):
        if self.cluster_agg != "auto":
            return bool(self.cluster_agg)
        import os
        import jax
        if jax.process_count() > 1:
            return True
        return bool(os.environ.get("DSTPU_TELEM_DIR")
                    and (os.environ.get("DSTPU_TELEM_PEERS")
                         or os.environ.get("DSTPU_HOT_PEERS")))


@dataclass
class ActivationCheckpointingConfig:
    partition_activations: bool = False   # accepted for parity; XLA shards
    contiguous_memory_optimization: bool = False
    cpu_checkpointing: bool = False
    number_checkpoints: int = 0
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native knob: remat policy name for jax.checkpoint
    policy: str = "nothing_saveable"


@dataclass
class CommsLoggerConfig:
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False


def _take(d, cls, key):
    sub = d.get(key, {})
    if isinstance(sub, cls):
        return sub
    if not isinstance(sub, dict):
        raise DeepSpeedConfigError(f"'{key}' must be a dict, got {type(sub)}")
    known = {f for f in cls.__dataclass_fields__}
    unknown = set(sub) - known
    if unknown:
        logger.warning(f"config block '{key}': ignoring unknown keys {sorted(unknown)}")
    return cls(**{k: v for k, v in sub.items() if k in known})


class DeepSpeedConfig:
    """Resolved, validated run config.

    Batch triad resolution follows reference runtime/config.py: given any two
    of (train_batch_size, train_micro_batch_size_per_gpu,
    gradient_accumulation_steps) the third is derived; given one, the others
    default to fill; all three must satisfy
    train_batch == micro_batch * grad_accum * dp_world.
    """

    def __init__(self, config, dp_world_size=1):
        if isinstance(config, str):
            with open(config) as f:
                config = json.load(f)
        if not isinstance(config, dict):
            raise DeepSpeedConfigError(
                f"expected dict or json path, got {type(config)}")
        self._raw = dict(config)
        self.dp_world_size = dp_world_size

        self.train_batch_size = config.get(C.TRAIN_BATCH_SIZE)
        self.train_micro_batch_size_per_gpu = config.get(
            C.TRAIN_MICRO_BATCH_SIZE_PER_GPU)
        self.gradient_accumulation_steps = config.get(
            C.GRADIENT_ACCUMULATION_STEPS)
        self._resolve_batch_size()

        self.steps_per_print = config.get(C.STEPS_PER_PRINT,
                                          C.STEPS_PER_PRINT_DEFAULT)
        self.gradient_clipping = config.get(C.GRADIENT_CLIPPING,
                                            C.GRADIENT_CLIPPING_DEFAULT)
        self.prescale_gradients = config.get(C.PRESCALE_GRADIENTS, False)
        self.gradient_predivide_factor = config.get(
            C.GRADIENT_PREDIVIDE_FACTOR, 1.0)
        self.wall_clock_breakdown = config.get(C.WALL_CLOCK_BREAKDOWN, False)

        self.fp16 = _take(config, FP16Config, C.FP16)
        self.bf16 = _take(config, BF16Config, C.BF16)
        if self.fp16.enabled and self.bf16.enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")
        self.zero = _take(config, ZeroConfig, C.ZERO_OPTIMIZATION)
        self.tensor_parallel = _take(config, TensorParallelConfig,
                                     C.TENSOR_PARALLEL)
        self.pipeline = _take(config, PipelineConfig, C.PIPELINE)
        self.seq_parallel_size = config.get(C.SEQUENCE_PARALLEL_SIZE, 1)
        self.expert_parallel_size = config.get(C.EXPERT_PARALLEL_SIZE, 1)
        # "auto": when no explicit topology is given, run the
        # auto-parallelism planner (autotuning/planner.py) over the model
        # + visible pod and adopt its rank-1 mesh/schedule; "" keeps the
        # hand-set axis sizes above (the historical behavior).
        self.parallelism = config.get("parallelism", "")
        if self.parallelism not in ("", "auto"):
            raise DeepSpeedConfigError(
                f"parallelism must be ''|'auto', got "
                f"{self.parallelism!r}")

        opt = config.get(C.OPTIMIZER)
        self.optimizer = None if opt is None else _take(
            {"o": opt}, OptimizerConfig, "o")
        sched = config.get(C.SCHEDULER)
        self.scheduler = None if sched is None else _take(
            {"s": sched}, SchedulerConfig, "s")

        self.checkpoint_engine = _take(config, CheckpointEngineConfig,
                                       C.CHECKPOINT_ENGINE)
        self.comm_overlap = _take(config, CommOverlapConfig, "comm_overlap")
        self.sequence = _take(config, SequenceConfig, "sequence")
        self.moe = _take(config, MoEConfig, "moe")
        self.quantize = _take(config, QuantizeConfig, "quantize")
        self.autotune = _take(config, AutotuneConfig, "autotune")
        self.telemetry = _take(config, TelemetryConfig, "telemetry")
        self.activation_checkpointing = _take(
            config, ActivationCheckpointingConfig, C.ACTIVATION_CHECKPOINTING)
        self.comms_logger = _take(config, CommsLoggerConfig, C.COMMS_LOGGER)
        from ..monitor.config import DeepSpeedMonitorConfig
        self.monitor_config = DeepSpeedMonitorConfig.from_dict(config)
        self.monitor_csv = self.monitor_config.csv_monitor  # back-compat

        dtypes = config.get(C.DATA_TYPES, {})
        self.grad_accum_dtype = dtypes.get(C.GRAD_ACCUM_DTYPE)
        self.seq_parallel_comm_dtype = config.get(C.SEQ_PARALLEL_COMM_DTYPE,
                                                  "float32")

        # data efficiency (reference runtime/data_pipeline/config.py
        # schema, condensed; consumed by the engine — curriculum changes
        # the batches the jitted step sees, random-ltd the kept-token
        # count — reference engine.py:336-367 + deepspeed_io:1715):
        #   data_efficiency: {enabled, seed,
        #     data_sampling: {enabled, curriculum_learning: {enabled,
        #         curriculum_type, min_difficulty, max_difficulty,
        #         schedule_type, schedule_config}},
        #     data_routing: {enabled, random_ltd: {enabled,
        #         random_ltd_min_value, random_ltd_max_value,
        #         random_ltd_schedule}}}
        # Legacy top-level curriculum_learning (v1 API) also accepted.
        de = config.get("data_efficiency", {}) or {}
        self.data_efficiency_enabled = bool(de.get("enabled", False))
        self.data_efficiency_seed = int(de.get("seed", 1234))
        sampling = de.get("data_sampling", {}) or {}
        cl = sampling.get("curriculum_learning", {}) or {}
        legacy_cl = config.get("curriculum_learning", {}) or {}
        self.curriculum_config = None
        if self.data_efficiency_enabled and sampling.get(
                "enabled", True) and cl.get("enabled", False):
            self.curriculum_config = {
                k: v for k, v in cl.items() if k != "enabled"}
        elif legacy_cl.get("enabled", False):
            self.curriculum_config = {
                k: v for k, v in legacy_cl.items() if k != "enabled"}
        routing = de.get("data_routing", {}) or {}
        ltd = routing.get("random_ltd", {}) or {}
        self.random_ltd_config = None
        if self.data_efficiency_enabled and routing.get(
                "enabled", True) and ltd.get("enabled", False):
            self.random_ltd_config = {
                k: v for k, v in ltd.items() if k != "enabled"}

    # reference runtime/config.py batch resolution logic, same error text style
    def _resolve_batch_size(self):
        train = self.train_batch_size
        micro = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps
        dp = self.dp_world_size
        for name, v in ((C.TRAIN_BATCH_SIZE, train),
                        (C.TRAIN_MICRO_BATCH_SIZE_PER_GPU, micro),
                        (C.GRADIENT_ACCUMULATION_STEPS, gas)):
            if v is not None and (not isinstance(v, int) or v <= 0):
                raise DeepSpeedConfigError(
                    f"{name} must be a positive integer, got {v!r}")

        if all(v is not None for v in (train, micro, gas)):
            if train != micro * gas * dp:
                raise DeepSpeedConfigError(
                    f"Check batch related parameters. train_batch_size is not equal "
                    f"to micro_batch_per_gpu * gradient_acc_step * world_size "
                    f"{train} != {micro} * {gas} * {dp}")
        elif train is not None and micro is not None:
            gas = train // (micro * dp)
            if gas * micro * dp != train:
                raise DeepSpeedConfigError(
                    f"train_batch_size {train} not divisible by "
                    f"micro_batch {micro} * dp world size {dp}")
        elif train is not None and gas is not None:
            micro = train // (gas * dp)
            if micro * gas * dp != train:
                raise DeepSpeedConfigError(
                    f"train_batch_size {train} not divisible by "
                    f"gradient_accumulation_steps {gas} * dp world size {dp}")
        elif micro is not None:
            gas = 1 if gas is None else gas
            train = micro * gas * dp
        elif train is not None:
            micro = train // dp
            gas = 1
            if micro * dp != train:
                raise DeepSpeedConfigError(
                    f"train_batch_size {train} not divisible by dp world size {dp}")
        else:
            raise DeepSpeedConfigError(
                "Either train_batch_size or train_micro_batch_size_per_gpu "
                "must be provided")
        self.train_batch_size = train
        self.train_micro_batch_size_per_gpu = micro
        self.gradient_accumulation_steps = gas

    @property
    def precision_dtype(self):
        import jax.numpy as jnp
        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    def to_dict(self):
        out = dict(self._raw)
        out[C.TRAIN_BATCH_SIZE] = self.train_batch_size
        out[C.TRAIN_MICRO_BATCH_SIZE_PER_GPU] = self.train_micro_batch_size_per_gpu
        out[C.GRADIENT_ACCUMULATION_STEPS] = self.gradient_accumulation_steps
        return out

    def print_config(self):
        logger.info("DeepSpeedConfig:")
        for k, v in sorted(self.__dict__.items()):
            if k.startswith("_"):
                continue
            logger.info(f"  {k} = {v}")
