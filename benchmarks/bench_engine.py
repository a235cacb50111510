"""Shared engine setup for the bench-adjacent tools (profile_step,
hlo_dump): ONE place reads the BENCH_* env knobs and builds the exact
engine/batch `bench.py` measures, so the tools can never drift from the
measured config."""

import os

os.environ.setdefault("LIBTPU_INIT_ARGS",
                      "--xla_tpu_scoped_vmem_limit_kib=32768")

# BENCH_COMM_OVERLAP=1: arm the comm-overlap XLA flags (latency-hiding
# scheduler + async collectives) via the import-time env hook BEFORE the
# backend initializes. Only effective for the FIRST engine of a process
# — in-process variant re-timings change the program-level annotations
# but inherit the headline's flags.
if os.environ.get("BENCH_COMM_OVERLAP") == "1":
    os.environ.setdefault("DSTPU_COMM_OVERLAP", "1")

import numpy as np  # noqa: E402


def build_bench_config():
    """The headline bench model config from the BENCH_* env knobs —
    the single source bench.py and the tools share (every knob, incl.
    the backward flash blocks and LN/unroll experiments)."""
    from deepspeed_tpu.models import PRESETS
    from dataclasses import replace

    preset = os.environ.get("BENCH_PRESET", "350M")
    seq_len = int(os.environ.get("BENCH_SEQ", "1024"))

    # BENCH_AUTOTUNE=1: every tunable kernel knob goes to "auto" so the
    # measured-dispatch winner cache picks variants (the engine's
    # autotune block below sets the mode); any explicitly-set BENCH_*
    # knob still wins over "auto". BENCH_AUTOTUNE=0 pins the r05
    # defaults AND autotune mode off (the drift sentinel).
    tune = os.environ.get("BENCH_AUTOTUNE", "") == "1"

    def knob(env, default, parse=int):
        v = os.environ.get(env)
        if v is None:
            return "auto" if tune else parse(default)
        return parse(v)

    cfg = replace(
        PRESETS[preset], max_seq_len=seq_len,
        use_flash_attention=os.environ.get("BENCH_FLASH", "1") == "1",
        flash_block_q=knob("BENCH_FLASH_BQ", "1024"),
        flash_block_k=knob("BENCH_FLASH_BK", "1024"),
        flash_block_h=knob("BENCH_FLASH_BH", "1"),
        flash_block_q_bwd=knob("BENCH_FLASH_BQ_BWD", "0"),
        flash_block_k_bwd=knob("BENCH_FLASH_BK_BWD", "0"),
        remat=os.environ.get("BENCH_REMAT", "1") == "1",
        remat_policy=os.environ.get("BENCH_REMAT_POLICY", "save_flash"),
        scan_unroll=int(os.environ.get("BENCH_SCAN_UNROLL", "1")),
        fused_layernorm={"0": False, "1": True, "bwd": "bwd",
                         "auto": "auto"}.get(
            knob("BENCH_FUSED_LN", "0", parse=str), False),
        loss_chunk=int(os.environ.get("BENCH_LOSS_CHUNK", "512")),
        fused_loss=os.environ.get("BENCH_FUSED_LOSS", "1") == "1",
        fused_loss_kernel=os.environ.get("BENCH_FUSED_LOSS_KERNEL",
                                         "1") == "1",
        # layout-owning Pallas MLP projection matmul (ops/pallas/
        # mlp_matmul.py): 0 (XLA, default) | down | both | auto
        mlp_kernel={"0": False, "auto": "auto", "down": "down",
                    "both": "both"}.get(
            knob("BENCH_MLP_KERNEL", "0", parse=str), False),
        mlp_kernel_fuse_dw=os.environ.get("BENCH_MLP_FUSE_DW", "1") == "1",
        # query-major fused flash backward (dkv VMEM-resident retune)
        flash_bwd_qmajor=(
            "auto" if tune and "BENCH_FLASH_BWD_QMAJOR" not in os.environ
            else os.environ.get("BENCH_FLASH_BWD_QMAJOR", "0") == "1"),
        # long-context backend: BENCH_ATTN_BACKEND=ring routes attention
        # through sequence/ring.py (zigzag context parallelism) whenever
        # the engine runs seq-sharded (BENCH_SP below); 'dense' default
        attention_backend=os.environ.get("BENCH_ATTN_BACKEND", "dense"))
    # BENCH_MODEL=moe: the dropless-MoE training point — GPT2MoE over
    # the same preset dims with the ragged (grouped-GEMM) backend;
    # BENCH_MOE_KERNEL picks the expert-product engine (1 = the Pallas
    # grouped kernel, 0 = lax.ragged_dot, unset/auto = winner cache) —
    # the moe_kernel_on/off A/B lever
    if os.environ.get("BENCH_MODEL", "") == "moe":
        import dataclasses
        from deepspeed_tpu.models import GPT2MoEConfig
        cfg = GPT2MoEConfig(
            **dataclasses.asdict(cfg),
            num_experts=int(os.environ.get("BENCH_MOE_EXPERTS", "4")),
            moe_top_k=int(os.environ.get("BENCH_MOE_TOPK", "2")),
            moe_backend="ragged",
            moe_grouped_kernel={"1": True, "0": False}.get(
                os.environ.get("BENCH_MOE_KERNEL", ""), "auto"))
    return cfg


def build_bench_engine():
    """Returns (engine, batch) for the headline bench config, honoring
    the same BENCH_* env knobs (incl. BENCH_ZERO_STAGE/BENCH_OFFLOAD)
    as bench.py."""
    import jax  # noqa: F401  (device init after LIBTPU_INIT_ARGS)
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2, GPT2MoE, GPT2MoEConfig
    from deepspeed_tpu.utils import groups
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    cfg = build_bench_config()
    seq_len = cfg.max_seq_len
    preset = os.environ.get("BENCH_PRESET", "350M")
    # 1.3B on one 16 GB chip needs the memory knobs: micro 8, bf16 Adam
    # moments, bf16 grad accumulation (the update still computes fp32)
    big = preset == "1.3B"
    micro = int(os.environ.get("BENCH_MICRO_BS", "8" if big else "24"))
    stage = int(os.environ.get("BENCH_ZERO_STAGE", "2"))
    offload = os.environ.get("BENCH_OFFLOAD", "")
    moments = os.environ.get("BENCH_MOMENTS_DTYPE",
                             "bfloat16" if big else "")
    gdtype = os.environ.get("BENCH_GRAD_DTYPE", "bf16" if big else "")
    if offload not in ("", "cpu", "nvme"):
        raise SystemExit(f"BENCH_OFFLOAD must be ''|cpu|nvme, "
                         f"got {offload!r}")
    model = (GPT2MoE(cfg) if isinstance(cfg, GPT2MoEConfig)
             else GPT2(cfg))
    groups.reset()
    # BENCH_SP: sequence-parallel (ring) axis size — 'auto' = all visible
    # devices when the ring backend is selected (one chip -> sp=1, where
    # the ring path degrades to the flash kernel: the ring_on/off A/B is
    # then a long-seq baseline pair; on a pod it measures the real ring)
    topo = None
    sp = os.environ.get("BENCH_SP", "")
    if sp in ("", "auto"):
        sp_n = (len(jax.devices())
                if cfg.attention_backend == "ring" else 1)
    else:
        sp_n = int(sp)
    if sp_n > 1:
        from deepspeed_tpu.utils.groups import TopologyConfig
        topo = groups.initialize(TopologyConfig(seq_parallel_size=sp_n))
    opt_params = {"lr": 2e-4, "weight_decay": 0.01}
    if moments:
        opt_params["moments_dtype"] = moments
    # comm_overlap block (runtime/zero/overlap.py): ''/auto = engine
    # default (on iff dp>1), 1/0 force. BENCH_COMM_BUCKET_MB tunes the
    # layer-granular reduce gate in isolation.
    ov = os.environ.get("BENCH_COMM_OVERLAP", "")
    overlap_cfg = {}
    if ov in ("0", "1"):
        overlap_cfg["enabled"] = ov == "1"
    if os.environ.get("BENCH_COMM_BUCKET_MB"):
        overlap_cfg["bucket_mb"] = int(os.environ["BENCH_COMM_BUCKET_MB"])
    if os.environ.get("BENCH_COMM_PREFETCH"):
        overlap_cfg["prefetch"] = os.environ["BENCH_COMM_PREFETCH"] == "1"
    # measured kernel dispatch (autotuning/kernel_dispatch.py):
    # BENCH_AUTOTUNE=1 searches cold keys at first trace (inside warmup,
    # so search compiles never land in the timed section) and persists
    # winners; =0 pins dispatch off (the r05-default drift sentinel);
    # unset inherits the env default (cache_only)
    at = os.environ.get("BENCH_AUTOTUNE", "")
    autotune_cfg = {}
    if at == "1":
        autotune_cfg["mode"] = os.environ.get("BENCH_AUTOTUNE_MODE",
                                              "on_first_use")
    elif at == "0":
        autotune_cfg["mode"] = "off"
    # BENCH_INT8_MATMUL=1/0: the training-side W8A8 compute lever
    # (quantize.int8_matmul — ops/pallas/quantization.int8_matmul in
    # gpt2._mlp; 'auto' defers to the mlp_int8 winner cache); unset
    # omits the quantize block entirely (byte-identical programs)
    quantize_cfg = {}
    i8 = os.environ.get("BENCH_INT8_MATMUL", "")
    if i8 in ("0", "1"):
        quantize_cfg["int8_matmul"] = i8 == "1"
    elif i8 == "auto":
        quantize_cfg["int8_matmul"] = "auto"
    # BENCH_TELEMETRY=1: arm the telemetry block (monitor/telemetry.py)
    # so bench.py can read MFU/goodput/step percentiles straight off
    # engine.telemetry_report() — no monitor backend needed
    telemetry_cfg = {}
    if os.environ.get("BENCH_TELEMETRY", "") == "1":
        telemetry_cfg = {
            "enabled": True,
            "interval_steps": int(os.environ.get(
                "BENCH_TELEMETRY_INTERVAL", "5"))}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        **({"topology": topo} if topo is not None else {}),
        config={
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": 1,
            "steps_per_print": 0,
            "optimizer": {"type": "AdamW", "params": opt_params},
            "gradient_clipping": 1.0,
            "bf16": {"enabled": True},
            **({"data_types": {"grad_accum_dtype": gdtype}}
               if gdtype else {}),
            "zero_optimization": (
                {"stage": stage,
                 "offload_optimizer": (
                     {"device": "nvme",
                      "nvme_path": os.environ.get("BENCH_NVME_PATH",
                                                  "/tmp/dstpu_nvme")}
                     if offload == "nvme" else {"device": "cpu"})}
                if offload else {"stage": stage}),
            **({"comm_overlap": overlap_cfg} if overlap_cfg else {}),
            **({"quantize": quantize_cfg} if quantize_cfg else {}),
            **({"autotune": autotune_cfg} if autotune_cfg else {}),
            **({"telemetry": telemetry_cfg} if telemetry_cfg else {}),
        })
    bsz = engine.config.train_batch_size
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, cfg.vocab_size, (bsz, seq_len))
             .astype(np.int32)}
    return engine, batch
