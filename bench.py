"""Headline benchmark: GPT-2 350M ZeRO-2 bf16 training throughput.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N}

Target (BASELINE.json): tokens/sec/chip within 15% of 8xA100 running the
reference DeepSpeed. The reference tree publishes no number for this config
(BASELINE.md: "published" is empty), so the baseline is the analytic
per-chip A100 figure: 312 TFLOP/s bf16 peak x 40% MFU (a strong DeepSpeed
ZeRO-2 MFU at 350M scale) / flops-per-token. vs_baseline > 1.0 beats it.

Runs on however many chips are visible (the driver gives one v5e chip);
throughput is reported per chip.

After the headline, ``extras.variants`` measures the round-6 levers —
each rebuilt+retimed under its own env overrides, failures isolated so a
variant can never cost the headline number:
  mlp_kernel_down  the layout-owning Pallas wdown projection
                   (BENCH_MLP_KERNEL=down)
  flash_bwd_qmajor the query-major fused flash backward
                   (BENCH_FLASH_BWD_QMAJOR=1)
  gpt2_1.3B_zero3  the BASELINE.md row-3 model point (ZeRO-3, bf16
                   moments+grad accumulation to fit one 16 GB chip),
                   where per-step fixed costs amortize
  comm_overlap_on/off  the comm-overlap program annotations
                   (BENCH_COMM_OVERLAP=1/0; runtime/zero/overlap.py)
                   A/B'd at whatever dp the driver exposes
  autotune_on/off  the measured kernel dispatch (BENCH_AUTOTUNE=1/0;
                   autotuning/kernel_dispatch.py): _on searches cold
                   keys at first trace and runs on the cached winners,
                   _off pins the r05 hand-set defaults; the winner
                   table lands in extras.autotune
  ring_on/off      long-context A/B at seq 4096 (BENCH_ATTN_BACKEND=
                   ring + BENCH_SP=auto vs the standard flash path;
                   sequence/ring.py zigzag context parallelism — real
                   ring numbers need >1 chip, at 1 chip the pair is a
                   long-seq baseline)
  moe_kernel_on/off  dropless-MoE expert-FFN A/B (BENCH_MODEL=moe +
                   BENCH_MOE_KERNEL=1/0): GPT2MoE ragged routing with
                   the Pallas grouped-GEMM kernel (ops/pallas/
                   grouped_matmul.py) vs lax.ragged_dot
  weight_quant_on/off  the training-side int8 compute A/B
                   (BENCH_INT8_MATMUL=1/0; quantize.int8_matmul routes
                   both MLP projections through ops/pallas/
                   quantization.int8_matmul — dynamic rowwise activation
                   codes x per-channel weight codes, int32 accumulate)
  pipe_zb/gpipe/zb_offload  the pp=2 schedule + host-offload pair
                   (benchmarks/pipeline_probe.py subprocess on a
                   virtual pipe mesh — zero-bubble vs gpipe wall time,
                   offload-on host-copy/memory read; BENCH_PIPE_PROBE=0
                   skips)
Disable with BENCH_VARIANTS=none, or pick a subset
(BENCH_VARIANTS=mlp_down,bwd_qmajor,1.3B,overlap,autotune,ring_on,
moe_on,moe_off,pipe — 'pipe' selects the subprocess probe rows).

``extras.telemetry`` embeds the observability layer's own read of a
measured run (ISSUE 9): single-chip MFU (cost_analysis flops), goodput,
step percentiles from ``engine.telemetry_report()``, and the pod-wide
straggler delta from a 2-host virtual-mesh probe
(benchmarks/telemetry_probe.py). BENCH_TELEMETRY=0 skips it.

On a TPU the full report is also written into the tree as
``BENCH_local.json``. A run on anything else prints its report with the
platform named and writes no artifact: a CPU timing is not a chip result.
A kernel-parity failure makes the exit code non-zero.
"""

import gc
import json
import os
import sys

# autotuning protocol (dstpu --autotuning, launcher/runner.py): a trial
# passes its knobs as --exp '{"BENCH_MICRO_BS": 16, ...}'; they apply as
# the equivalent env overrides BEFORE the bench reads them
if "--exp" in sys.argv:
    _exp = json.loads(sys.argv[sys.argv.index("--exp") + 1])
    os.environ.update({k: str(v) for k, v in _exp.items()})

# measured win on v5e at the 350M point (571 vs 577 ms/step): a 2x
# scoped-VMEM budget lets XLA form deeper fusions; 40 MB+ regresses.
# Must be set before libtpu initializes (first device touch).
os.environ.setdefault("LIBTPU_INIT_ARGS",
                      "--xla_tpu_scoped_vmem_limit_kib=32768")

import time

import jax

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmarks"))
from bench_engine import build_bench_engine  # noqa: E402
from deepspeed_tpu.monitor.telemetry import peak_flops_per_chip  # noqa: E402

A100_PEAK_MFU = 312e12 * 0.40     # the BASELINE.md per-chip bar


def _measure(steps, warmup):
    """Build the engine for the CURRENT env knobs and time ``steps``.
    Returns the raw numbers a caller folds into its own report shape."""
    engine, batch = build_bench_engine()
    cfg = engine.model.config
    n_dev = len(jax.devices())
    bsz = engine.config.train_batch_size

    loss = None
    for _ in range(warmup):
        loss = engine.train_batch(batch)
    jax.block_until_ready(engine.state)

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    jax.block_until_ready(engine.state)
    dt = time.perf_counter() - t0

    tokens = bsz * cfg.max_seq_len * steps
    tok_per_sec_chip = tokens / dt / n_dev
    fpt = cfg.flops_per_token()
    peak = peak_flops_per_chip(jax.devices()[0].device_kind)
    out = {
        "_fpt": fpt,                  # popped by main(); not serialized
        "tokens_per_sec_chip": round(tok_per_sec_chip, 1),
        "step_time_s": round(dt / steps, 4),
        "vs_baseline": round(tok_per_sec_chip / (A100_PEAK_MFU / fpt), 3),
        "mfu_vs_v5e_peak": (round(tok_per_sec_chip * fpt / peak, 3)
                            if peak else "not measured"),
        "final_loss": float(loss),
        "devices": n_dev,
        "seq_len": cfg.max_seq_len,
        "global_batch": bsz,
        "steps": steps,
    }
    del engine, batch
    gc.collect()
    return out


# the round-6 lever configs; each is measured in isolation on top of
# whatever knobs the headline ran with. bwd_qmajor_512: at full-T
# backward blocks the q-major and k-major kernels coincide (one grid
# step per group); the q-major design's win — causal skipping at finer
# grain WITHOUT the k-major multi-block fp32-dq HBM round trip — only
# shows at sub-T blocks, so both points are measured.
_VARIANTS = {
    "mlp_down": ("mlp_kernel_down", {"BENCH_MLP_KERNEL": "down"}),
    "bwd_qmajor": ("flash_bwd_qmajor", {"BENCH_FLASH_BWD_QMAJOR": "1"}),
    "bwd_qmajor_512": ("flash_bwd_qmajor_512",
                       {"BENCH_FLASH_BWD_QMAJOR": "1",
                        "BENCH_FLASH_BQ_BWD": "512",
                        "BENCH_FLASH_BK_BWD": "512"}),
    "1.3B": ("gpt2_1.3B_zero3", {"BENCH_PRESET": "1.3B",
                                 "BENCH_ZERO_STAGE": "3"}),
    # comm-overlap A/B at whatever dp the driver exposes (the BENCH_DP
    # pair): 'overlap' forces the program-level annotations on (per-layer
    # in-scan grad reduction + ZeRO-3 gather prefetch; at dp=1 this
    # measures their pure overhead), 'overlap_off' pins them off (== the
    # headline at default 'auto', a drift sentinel at dp>1). XLA flags
    # only land when the driver also sets BENCH_COMM_OVERLAP=1 /
    # DSTPU_COMM_OVERLAP=1 before the process starts — in-process
    # variants inherit the headline's flags.
    "overlap": ("comm_overlap_on", {"BENCH_COMM_OVERLAP": "1"}),
    "overlap_off": ("comm_overlap_off", {"BENCH_COMM_OVERLAP": "0"}),
    # measured kernel dispatch A/B: 'autotune' flips every tunable
    # kernel knob to "auto" and lets on_first_use search fill the winner
    # cache at first trace (search compiles land in warmup, not the
    # timed section); 'autotune_off' pins dispatch off — the r05-default
    # drift sentinel the tuned number is read against. The winner table
    # itself is embedded in this artifact (extras.autotune) so tuned
    # defaults finally travel with the measurements.
    "autotune": ("autotune_on", {"BENCH_AUTOTUNE": "1"}),
    "autotune_off": ("autotune_off", {"BENCH_AUTOTUNE": "0"}),
    # training-side W8A8 compute A/B (quantize.int8_matmul forced
    # on/off; ops/pallas/quantization.int8_matmul in both MLP
    # projections — dynamic rowwise activation codes x channelwise
    # weight codes, int32 accumulate). _off pins the quantize block to
    # false explicitly so an ambient BENCH_INT8_MATMUL can't silently
    # turn the A/B into int8-vs-int8.
    "weight_quant_on": ("weight_quant_on", {"BENCH_INT8_MATMUL": "1"}),
    "weight_quant_off": ("weight_quant_off", {"BENCH_INT8_MATMUL": "0"}),
    # long-context A/B at 4x the headline sequence (micro bs scaled down
    # to fit): 'ring_on' routes attention through the zigzag ring
    # (sequence/ring.py) with the seq axis spanning every visible device
    # (BENCH_SP=auto; at 1 chip sp=1 and the ring path degrades to the
    # flash kernel, making the pair a long-seq baseline — the real ring
    # number needs the multichip driver), 'ring_off' the standard flash
    # path at the same shape.
    "ring_on": ("ring_on", {"BENCH_ATTN_BACKEND": "ring",
                            "BENCH_SP": "auto", "BENCH_SEQ": "4096",
                            "BENCH_MICRO_BS": "4"}),
    # ring_off pins the baseline backend explicitly (like autotune_off /
    # overlap_off) so an ambient BENCH_ATTN_BACKEND=ring can't silently
    # turn the A/B into ring-vs-ring
    "ring_off": ("ring_off", {"BENCH_ATTN_BACKEND": "dense",
                              "BENCH_SP": "1", "BENCH_SEQ": "4096",
                              "BENCH_MICRO_BS": "4"}),
    # dropless-MoE expert-FFN A/B: GPT2MoE (preset dims, 4 experts,
    # top-2, ragged dropless routing) with the expert product through
    # the Pallas grouped-GEMM kernel (_on) vs lax.ragged_dot (_off) —
    # the moe_grouped_mm lever measured in a real train step. ZeRO-3 +
    # bf16 moments/grads because 4x-expert MLPs put the point near the
    # 1.3B memory envelope on one 16 GB chip.
    "moe_on": ("moe_kernel_on", {"BENCH_MODEL": "moe",
                                 "BENCH_MOE_KERNEL": "1",
                                 "BENCH_ZERO_STAGE": "3",
                                 "BENCH_MICRO_BS": "8",
                                 "BENCH_MOMENTS_DTYPE": "bfloat16",
                                 "BENCH_GRAD_DTYPE": "bf16"}),
    "moe_off": ("moe_kernel_off", {"BENCH_MODEL": "moe",
                                   "BENCH_MOE_KERNEL": "0",
                                   "BENCH_ZERO_STAGE": "3",
                                   "BENCH_MICRO_BS": "8",
                                   "BENCH_MOMENTS_DTYPE": "bfloat16",
                                   "BENCH_GRAD_DTYPE": "bf16"}),
    # measured-dispatch MoE: moe_grouped_kernel="auto" under
    # on_first_use, so the moe_grouped_mm bucket gets a real search on
    # this chip and its winner lands in the extras.autotune table
    "moe_autotune": ("moe_autotune", {"BENCH_MODEL": "moe",
                                      "BENCH_AUTOTUNE": "1",
                                      "BENCH_ZERO_STAGE": "3",
                                      "BENCH_MICRO_BS": "8",
                                      "BENCH_MOMENTS_DTYPE": "bfloat16",
                                      "BENCH_GRAD_DTYPE": "bf16"}),
}


def _run_variants(names, steps, warmup):
    out = {}
    for name in names:
        if name not in _VARIANTS:
            out[name] = {"error": f"unknown variant {name!r}"}
            continue
        label, overrides = _VARIANTS[name]
        saved = {k: os.environ.get(k) for k in overrides}
        os.environ.update(overrides)
        try:
            out[label] = _measure(steps, warmup)
            out[label].pop("_fpt", None)
        except Exception as e:       # isolate: a variant OOM/compile
            out[label] = {"error":   # failure must not cost the headline
                          f"{type(e).__name__}: {e}"[:300]}
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            gc.collect()
    return out


def _pipeline_variants():
    """The CPU-sized pp variant pair (ISSUE 10): a pp=2 pipe-only mesh
    in a subprocess (the telemetry-probe pattern — pipeline needs >= 2
    devices, the driver gives one chip) A/B-ing the zero-bubble
    schedule vs gpipe and the host-offload lever. Rows land in
    extras.variants as pipe_*; failures are isolated like every
    variant. BENCH_PIPE_PROBE=0 skips."""
    import subprocess
    import sys as _sys
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"   # virtual pipe mesh: the pair is a
    # scheduling read on one chip; pod-scale numbers live in MULTICHIP
    env.pop("XLA_FLAGS", None)
    out = {}
    try:
        probe = subprocess.run(
            [_sys.executable,
             os.path.join(here, "benchmarks", "pipeline_probe.py"),
             "--pipe", os.environ.get("BENCH_PIPE", "2"),
             "--steps", os.environ.get("BENCH_PIPE_STEPS", "3"),
             "--warmup", "1",
             "--rows", "zb,gpipe,zb_offload"],
            env=env, capture_output=True, text=True, timeout=900)
        parsed = json.loads(probe.stdout.strip().splitlines()[-1])
        for name, row in parsed.get("rows", {}).items():
            out[f"pipe_{name}"] = row
        out["pipe_meta"] = {k: parsed.get(k) for k in
                            ("pipe", "backend", "host_kind", "preset",
                             "seq_len", "global_batch")}
    except Exception as e:  # noqa: BLE001 - isolate, like variants
        out["pipe_probe"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    return out


def _telemetry_extras(steps, warmup):
    """``extras.telemetry`` (ISSUE 9): the telemetry layer's own read
    of a measured run — single-chip MFU/goodput/step percentiles from
    ``engine.telemetry_report()`` (tiny preset so it never competes
    with the headline for HBM), plus the pod-wide straggler-delta
    aggregation from a 2-host virtual-mesh probe
    (benchmarks/telemetry_probe.py). Failures are isolated like every
    variant: telemetry must never cost the headline number."""
    import subprocess
    import sys as _sys
    out = {}
    saved = {k: os.environ.get(k)
             for k in ("BENCH_TELEMETRY", "BENCH_PRESET",
                       "BENCH_MICRO_BS", "BENCH_SEQ")}
    os.environ.update({"BENCH_TELEMETRY": "1", "BENCH_PRESET": "tiny",
                       "BENCH_MICRO_BS": "8", "BENCH_SEQ": "128"})
    try:
        engine, batch = build_bench_engine()
        for _ in range(warmup):
            engine.train_batch(batch)
        engine.telemetry.reset_window()     # compile out of the window
        for _ in range(steps):
            engine.train_batch(batch)
        engine.telemetry.drain()
        snap = engine.telemetry_report() or {}
        out["local"] = {k: snap.get(k) for k in (
            "mfu_pct", "flops_source", "goodput_pct",
            "tokens_per_sec_chip", "step_time_ms_p50",
            "step_time_ms_p99", "collectives", "exposed_comm_pct",
            "peak_assumed")}
        del engine, batch
        gc.collect()
    except Exception as e:  # noqa: BLE001 - isolate, like variants
        out["local"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        probe = subprocess.run(
            [_sys.executable,
             os.path.join(here, "benchmarks", "telemetry_probe.py"),
             "--hosts", "2", "--steps", "5", "--warmup", "2"],
            capture_output=True, text=True, timeout=600)
        line = probe.stdout.strip().splitlines()[-1]
        parsed = json.loads(line)
        out["cluster"] = parsed.get("cluster")
        out["cluster_hosts"] = parsed.get("hosts")
    except Exception as e:  # noqa: BLE001
        out["cluster"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    return out


def _reconcile_extras(steps, warmup):
    """``extras.reconcile`` (ISSUE 13): a step-ranged profiler capture
    on the tiny preset, parsed into a StepDecomposition and reconciled
    against the planner's ``_score`` breakdown for the mesh the run
    actually used. The artifact carries the drift summary (which term
    the cost model gets most wrong on this chip) and the measured term
    split. Isolated like every variant — reconcile must never cost the
    headline number."""
    out = {}
    saved = {k: os.environ.get(k)
             for k in ("BENCH_TELEMETRY", "BENCH_PRESET",
                       "BENCH_MICRO_BS", "BENCH_SEQ",
                       "DSTPU_PROFILE_STEPS")}
    # arm the capture BEFORE engine build (ProfilerControl reads the
    # env at construction): trace the two steps after warmup
    os.environ.update({
        "BENCH_TELEMETRY": "1", "BENCH_PRESET": "tiny",
        "BENCH_MICRO_BS": "8", "BENCH_SEQ": "128",
        "DSTPU_PROFILE_STEPS": f"{warmup + 1}:{warmup + 3}"})
    try:
        engine, batch = build_bench_engine()
        for _ in range(max(steps, warmup + 4)):
            engine.train_batch(batch)
        engine.telemetry.drain()            # reconcile runs pool-side
        snap = engine.telemetry_report() or {}
        out["summary"] = snap.get("reconcile")
        rep = engine.reconcile_report()
        if rep is not None:
            dec = rep.get("decomposition") or {}
            out["terms_measured_ms"] = dec.get("terms")
            out["coverage_pct"] = dec.get("coverage_pct")
            out["cpu_fallback"] = dec.get("cpu_fallback")
            drift = rep.get("drift") or {}
            out["drift_rows"] = drift.get("rows")
            out["modeled_wall_ms"] = drift.get("modeled_wall_ms")
            out["measured_wall_ms"] = drift.get("measured_wall_ms")
        del engine, batch
        gc.collect()
    except Exception as e:  # noqa: BLE001 - isolate, like variants
        out["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


def main():
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    warmup = int(os.environ.get("BENCH_WARMUP", "3"))
    stage = int(os.environ.get("BENCH_ZERO_STAGE", "2"))
    offload = os.environ.get("BENCH_OFFLOAD", "")
    preset = os.environ.get("BENCH_PRESET", "350M")

    # tuned v5e config: pallas flash attention with a full-KV inner
    # loop + per-layer save_flash remat, grad-in-forward fused CE over
    # the Pallas unembed/online-stats kernel (fp32 logits never in
    # HBM). ONE config source shared with profile_step/hlo_dump:
    # benchmarks/bench_engine.py reads every BENCH_* knob.
    head = _measure(steps, warmup)
    head_fpt = head.pop("_fpt")

    # on-chip Pallas kernel parity gate (real-Mosaic numerics vs the
    # dense references; CI only exercises interpreter mode). Runs after
    # timing so its compiles never pollute the measurement. Returns a
    # dict enumerating every shipped kernel path.
    kernels_parity = "skipped"
    if os.environ.get("BENCH_KERNEL_PARITY", "1") == "1" \
            and jax.default_backend() != "cpu":
        from kernel_parity import run as _kernel_parity
        kernels_parity = _kernel_parity()

    variants = {}
    vnames = os.environ.get(
        "BENCH_VARIANTS",
        "mlp_down,bwd_qmajor,bwd_qmajor_512,1.3B,overlap,overlap_off,"
        "autotune,autotune_off,ring_on,ring_off,moe_on,moe_off,"
        "moe_autotune,weight_quant_on,weight_quant_off,pipe")
    if vnames and vnames != "none":
        # 'pipe' selects the subprocess probe below, not an in-process
        # re-timing — keep it out of the env-override variant loop
        variants = _run_variants(
            [v for v in vnames.split(",") if v and v != "pipe"],
            int(os.environ.get("BENCH_VARIANT_STEPS", "5")),
            int(os.environ.get("BENCH_VARIANT_WARMUP", "2")))

    # the pp=2 schedule/offload pair (subprocess virtual mesh): rides
    # extras.variants like every lever — and obeys the same subset
    # mechanism ('pipe' must be in the BENCH_VARIANTS selection;
    # BENCH_PIPE_PROBE=0 is the independent off switch)
    if os.environ.get("BENCH_PIPE_PROBE", "1") == "1" \
            and vnames != "none" and "pipe" in vnames.split(","):
        variants.update(_pipeline_variants())

    # the tuned winner table travels WITH the artifact: whatever the
    # autotune variants (or a pre-warmed cache) measured on this chip is
    # readable from the bench JSON alone — no separate cache file needed
    # to flip defaults next round
    autotune_info = {"cache_path": None, "table": {}}
    try:
        from deepspeed_tpu.autotuning import kernel_dispatch
        dk = kernel_dispatch.device_kind()
        autotune_info = {"cache_path": kernel_dispatch.cache_path(),
                         "table": kernel_dispatch.table(),
                         # the device-kind refusal rule, made legible in
                         # the artifact itself: winners measured on CPU
                         # (interpret-mode emulation) exercise code paths
                         # but must never steer a real TPU's defaults
                         "device_kind": dk,
                         "cpu_artifact": dk.lower() == "cpu"}
    except Exception as e:          # report, don't hide the bench
        autotune_info["error"] = f"{type(e).__name__}: {e}"[:200]

    # telemetry self-measurement (MFU/goodput + the 2-host virtual-mesh
    # straggler probe) — the trajectory artifacts pick the new metrics
    # up from here automatically. BENCH_TELEMETRY=0 skips.
    telemetry_info = {}
    if os.environ.get("BENCH_TELEMETRY", "") != "0":
        telemetry_info = _telemetry_extras(
            int(os.environ.get("BENCH_TELEMETRY_STEPS", "6")),
            int(os.environ.get("BENCH_TELEMETRY_WARMUP", "2")))

    # modeled-vs-measured reconciliation (ISSUE 13): profile a short
    # tiny-preset run and diff the planner's term breakdown against the
    # trace's step decomposition. BENCH_RECONCILE=0 skips.
    reconcile_info = {}
    if os.environ.get("BENCH_RECONCILE", "1") != "0":
        reconcile_info = _reconcile_extras(
            int(os.environ.get("BENCH_RECONCILE_STEPS", "6")),
            int(os.environ.get("BENCH_RECONCILE_WARMUP", "2")))

    dev = jax.devices()[0]
    report = {
        "metric": (f"gpt2-{preset} zero{stage}"
                   + (f"-offload-{offload}" if offload else "")
                   + " bf16 training throughput"),
        "value": head["tokens_per_sec_chip"],
        "unit": "tokens/sec/chip",
        "vs_baseline": head["vs_baseline"],
        "extras": {
            "platform": dev.platform, "device_kind": dev.device_kind,
            "devices": head["devices"], "seq_len": head["seq_len"],
            "global_batch": head["global_batch"],
            "steps": head["steps"], "step_time_s": head["step_time_s"],
            "mfu_vs_v5e_peak": head["mfu_vs_v5e_peak"],
            "final_loss": head["final_loss"],
            "baseline_tokens_per_sec_chip_8xA100_est": round(
                A100_PEAK_MFU / head_fpt, 1),
            "kernels_parity": kernels_parity,
            "variants": variants,
            "autotune": autotune_info,
            "telemetry": telemetry_info,
            "reconcile": reconcile_info,
        },
    }

    # the tree-local copy of the artifact — from a chip run only: a CPU
    # run under the headline's metric name is not a result to keep
    if dev.platform == "tpu":
        local = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_local.json")
        with open(local, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")

    print(json.dumps(report))
    parity_ok = kernels_parity == "skipped" or all(
        v == "ok" for v in kernels_parity.values())
    return 0 if parity_ok else 1


if __name__ == "__main__":
    sys.exit(main())
