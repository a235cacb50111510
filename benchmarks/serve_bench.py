"""Serving benchmark: decode throughput + per-token latency on real TPU.

The reference's FastGen identity is measured serving throughput
(BASELINE.md rows 3-5: effective throughput under SLA). This bench drives
the v2 continuous-batching engine end to end — prefill a batch of
prompts, then timed decode steps over the paged KV cache (the Pallas
paged-attention kernels) — and prints one JSON line per configuration.

The headline scenario is ``bench_mixed_traffic``: Poisson arrivals of
mixed long-prefill + decode-heavy requests, p50/p99 **TTFT** (submit to
first token) and **TPOT** (steady-state inter-token) reported
separately per engine variant (paged kernel on/off x SplitFuse on/off)
— the FastGen demonstration that split-fuse holds p99 TPOT flat while
long prompts stream through.

EVERY row also lands in ``SERVE_local.json`` at the repo root — written
even when a run is interrupted mid-sweep (the same lost-artifact lesson
as ``bench.py``'s BENCH_local.json: three rounds of driver artifacts
vanished).

``bench_shared_prefix`` is the prefix-cache scenario: Poisson arrivals
drawing from N prompt templates (per-request suffixes, configurable
share ratio), prefix_cache on vs off — cache-on should collapse TTFT
p50 (template prefills served from cached KV blocks) with p99 TPOT
within noise, and the row carries the engine's own hit-rate/CoW/
eviction counters.

``bench_router_traffic`` (``SERVE_REPLICAS=N``) is the serving-fleet
robustness scenario: mixed-class Poisson traffic through the replica
Router (inference/v2/router.py) as baseline / mid-run replica-kill /
mid-run drain — per-class admitted/shed/expired/replayed counts and
TTFT/TPOT percentiles per row, failover accounting asserted closed.

Run on the chip:  python benchmarks/serve_bench.py
Env: SERVE_MODELS=gpt2-350M,llama-1b  SERVE_BATCHES=1,8
     SERVE_PROMPT=1024  SERVE_DECODE=128  SERVE_MIXED=1
     SERVE_MIXED_MODEL=gpt2-350M  SERVE_EP_MOE=1
     SERVE_PREFIX=1  SERVE_PREFIX_MODEL=gpt2-350M  SERVE_PREFIX_N=24
     SERVE_PREFIX_SHARE=0.75  SERVE_REPLICAS=2  SERVE_ROUTER_N=24
     SERVE_ROUTER_MODEL=gpt2-350M  SERVE_ROUTER_RATE=2.0
     SERVE_WQ=1  SERVE_WQ_MODEL=gpt2-350M   (weight_quant off/int8/int4
     sweep — TPOT p50/p99 + weight HBM delta per variant; 0 disables)
     SERVE_SPEC=1  SERVE_SPEC_MODEL=gpt2-350M  SERVE_SPEC_KS=2,4
     (speculative decoding sweep — off baseline, oracle-draft spec_k
     rows with acceptance-rate + tokens-per-verify-step counters, and
     the adversarial random-token fallback row; 0 disables)
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from deepspeed_tpu.inference.v2 import Overloaded, Router  # noqa: E402
from deepspeed_tpu.inference.v2.engine_v2 import (  # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.models import GPT2, PRESETS  # noqa: E402
from deepspeed_tpu.models.llama import Llama, LlamaConfig  # noqa: E402
from deepspeed_tpu.utils import fault_injection, groups  # noqa: E402

# every bench row accumulates here; write_local_report() flushes the
# tree-local artifact (also mid-run on interruption — see main())
RESULTS = []


def _record(row):
    RESULTS.append(row)
    print(json.dumps(row))
    return row


def write_local_report(error=None):
    """Write SERVE_local.json at the repo root with whatever rows exist
    so far. Never raises (an unwritable tree must not mask the bench's
    own output)."""
    report = {
        "metric": "v2 serving suite (throughput + TTFT/TPOT percentiles)",
        "rows": RESULTS,
        "devices": len(jax.devices()),
        "backend": jax.default_backend(),
    }
    if error:
        report["interrupted"] = error
    try:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "SERVE_local.json")
        with open(path, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
    except OSError as e:
        print(json.dumps({"local_artifact_error": str(e)[:200]}))
    return report


def _pct(arr, p, nd=1):
    """Guarded percentile: None instead of a crash/NaN when no request
    produced the statistic (e.g. every request finished inside its
    first dispatch, leaving no inter-token gaps)."""
    if arr is None or len(arr) == 0:
        return None
    return round(float(np.percentile(np.asarray(arr, np.float64), p)), nd)


def _poisson_drive(engine, prompts, arrivals, decode_tokens):
    """Shared open-loop driver (bench_sla + bench_mixed_traffic):
    submit ``prompts[i]`` once ``arrivals[i]`` seconds have elapsed,
    run the scheduler until drained. Returns (tok_times: uid -> [t0,
    t1, ...] per-token wall timestamps, submit: uid -> arrival_s,
    wall_s)."""
    tok_times, submit = {}, {}
    n = len(prompts)
    start = time.perf_counter()
    i = 0
    while i < n or engine.has_work:
        now = time.perf_counter() - start
        while i < n and arrivals[i] <= now:
            uid = engine.put(prompts[i], max_new_tokens=decode_tokens,
                             eos_token_id=-1)
            submit[uid] = arrivals[i]
            tok_times[uid] = []
            i += 1
        if not engine.has_work:
            time.sleep(min(0.005, max(0.0, arrivals[i] - now)))
            continue
        out = engine.step()
        t = time.perf_counter() - start
        for uid, _tok in out:
            tok_times[uid].append(t)
    return tok_times, submit, time.perf_counter() - start


def build_model(name):
    if name == "tiny":
        # smoke-test point (CPU / CI): exercises every serving program
        # in seconds; not a measurement target
        from deepspeed_tpu.models import GPT2Config
        return GPT2(GPT2Config(n_layer=2, n_head=4, d_model=64,
                               max_seq_len=1024, vocab_size=512,
                               remat=False, dtype="float32"))
    if name == "tiny-wq":
        # weight-quant smoke point: like "tiny" but d_model=128 so the
        # stacked block matmul weights clear quantize_tree's min_size
        # floor (1<<16 elements) — at d_model=64 nothing quantizes and
        # every weight_quant row would be a vacuous ratio-1.0
        from deepspeed_tpu.models import GPT2Config
        return GPT2(GPT2Config(n_layer=2, n_head=4, d_model=128,
                               max_seq_len=1024, vocab_size=512,
                               remat=False, dtype="float32"))
    if name == "gpt2-350M":
        from dataclasses import replace
        return GPT2(replace(PRESETS["350M"], max_seq_len=2048))
    if name == "llama-1b":
        return Llama(LlamaConfig(n_layer=16, n_head=16, n_kv_heads=8,
                                 d_model=2048, d_ff=5632, max_seq_len=2048,
                                 vocab_size=32000))
    if name == "llama2-7b-serve":
        from dataclasses import replace
        from deepspeed_tpu.models.llama import LLAMA_PRESETS
        return Llama(replace(LLAMA_PRESETS["llama2-7b"],
                             max_seq_len=2048))
    if name == "mixtral-tiny":
        # MoE serving point: small enough to serve on one chip while
        # exercising the grouped-GEMM expert path end to end
        from deepspeed_tpu.models.mixtral import Mixtral, MixtralConfig
        return Mixtral(MixtralConfig(
            n_layer=8, n_head=16, n_kv_heads=8, d_model=1024, d_ff=3584,
            max_seq_len=2048, vocab_size=32000, num_experts=8,
            moe_top_k=2))
    raise ValueError(name)


def bench_one(name, batch, prompt_len, decode_tokens, block_size=128):
    groups.reset()
    model = build_model(name)
    engine = InferenceEngineV2(
        model,
        RaggedInferenceEngineConfig(max_batch_size=batch,
                                    kv_block_size=block_size,
                                    prompt_bucket=prompt_len))
    rng = np.random.RandomState(0)
    V = model.config.vocab_size

    def run(n_decode):
        for _ in range(batch):
            engine.put(rng.randint(0, V, (prompt_len,)),
                       max_new_tokens=n_decode, eos_token_id=-1)
        # first step admits + prefills; subsequent steps decode
        while engine.has_work:
            engine.step()
        for uid in list(engine._results):
            engine.get(uid)

    run(4)   # warm both programs (prefill bucket + decode)

    # timed: prefill separately from decode so decode rate is clean
    t0 = time.perf_counter()
    for _ in range(batch):
        engine.put(rng.randint(0, V, (prompt_len,)),
                   max_new_tokens=decode_tokens, eos_token_id=-1)
    engine.step()             # admission + prefills + first decode
    t_prefill = time.perf_counter() - t0

    steps = 0
    produced = 0          # tokens emitted INSIDE the timed window only —
    t0 = time.perf_counter()   # the first (untimed) step already decoded
    while engine.has_work:     # decode_steps_per_dispatch tokens per seq
        produced += len(engine.step())
        steps += 1
    # force completion
    for uid in list(engine._results):
        np.asarray(engine.get(uid))
    t_decode = time.perf_counter() - t0

    out = {
        "model": name,
        "batch": batch,
        "prompt_len": prompt_len,
        "decode_tokens": decode_tokens,
        # None when every token fit in the first (untimed) dispatch —
        # raise SERVE_DECODE above decode_steps_per_dispatch to measure
        "decode_tokens_per_sec": (round(produced / t_decode, 1)
                                  if produced else None),
        # a sequence's own next-token latency: decode wall / its tokens
        "ms_per_token": (round(1e3 * t_decode / (produced / batch), 3)
                         if produced else None),
        "dispatches": steps,
        "prefill_s": round(t_prefill, 3),
        "devices": len(jax.devices()),
    }
    return _record(out)


def bench_splitfuse(name, prompt_len, chunk, decode_tokens,
                    block_size=128):
    """Dynamic SplitFuse point: decode latency of a RUNNING stream while
    a long prompt chunk-prefills through the fused program — the FastGen
    no-head-of-line-blocking property (blogs/deepspeed-fastgen §3B).
    Reports decode tokens/sec of the running stream during prefill
    dispatches vs during pure-decode dispatches."""
    groups.reset()
    model = build_model(name)
    engine = InferenceEngineV2(
        model,
        RaggedInferenceEngineConfig(max_batch_size=2,
                                    kv_block_size=block_size,
                                    prompt_bucket=chunk,
                                    splitfuse_tokens=chunk))
    rng = np.random.RandomState(0)
    V = model.config.vocab_size
    # stream A: short prompt, long decode
    a = engine.put(rng.randint(0, V, (64,)), max_new_tokens=512,
                   eos_token_id=-1)
    for _ in range(3):
        engine.step()                    # A prefilled + decoding (warm)
    # measure pure-decode rate for A
    t0 = time.perf_counter()
    pure = sum(len(engine.step()) for _ in range(8))
    t_pure = time.perf_counter() - t0
    # admit the long prompt; measure A's decode rate DURING its prefill
    b = engine.put(rng.randint(0, V, (prompt_len,)),
                   max_new_tokens=decode_tokens, eos_token_id=-1)
    during = 0
    chunk_steps = 0
    t0 = time.perf_counter()
    while (any(r.uid == b for r in engine._pending)
           or b in engine._prefill_q):
        out = engine.step()
        chunk_steps += 1
        during += sum(1 for uid, _ in out if uid == a)
    t_during = time.perf_counter() - t0
    out = {
        "model": name, "mode": "splitfuse",
        "chunk_tokens": chunk, "long_prompt": prompt_len,
        "chunk_dispatches": chunk_steps,
        "stream_decode_tok_s_pure": round(pure / t_pure, 1),
        "stream_decode_tok_s_during_prefill": (
            round(during / t_during, 1) if t_during else None),
        "devices": len(jax.devices()),
    }
    return _record(out)


def bench_quant(name="llama2-7b", decode_tokens=32, block_size=128):
    """ZeRO-Inference capacity point: serve a model whose bf16 weights +
    KV cache EXCEED single-chip HBM (llama2-7b bf16 ~13.5 GB weights +
    ~4.6 GB cache > 16 GB v5e) by holding the block weights as int8 +
    per-channel scales (~6.7 GB), dequantized one layer at a time
    (reference README.md:30 ZeRO-Inference)."""
    from deepspeed_tpu.models.llama import LLAMA_PRESETS
    from dataclasses import replace
    groups.reset()
    model = Llama(replace(LLAMA_PRESETS[name], max_seq_len=2048))
    engine = InferenceEngineV2(
        model,
        RaggedInferenceEngineConfig(max_batch_size=1,
                                    kv_block_size=block_size,
                                    prompt_bucket=128,
                                    quantize_weights=True))
    rng = np.random.RandomState(0)
    V = model.config.vocab_size
    uid = engine.put(rng.randint(0, V, (128,)), max_new_tokens=4,
                     eos_token_id=-1)
    while not engine.is_done(uid):
        engine.step()           # warm (compile + first tokens)
    engine.get(uid)
    uid = engine.put(rng.randint(0, V, (128,)),
                     max_new_tokens=decode_tokens, eos_token_id=-1)
    t0 = time.perf_counter()
    while not engine.is_done(uid):
        engine.step()
    dt = time.perf_counter() - t0
    toks = engine.get(uid)
    n_params = model.config.num_params()
    out = {
        "model": name, "mode": "zero-inference-int8",
        "params_b": round(n_params / 1e9, 2),
        "weights_gb_bf16": round(n_params * 2 / 2**30, 1),
        "weights_gb_int8": round(n_params / 2**30, 1),
        "decode_tokens_per_sec": round(len(toks) / dt, 2),
        "note": ("bf16 weights + paged KV exceed the 16 GB chip; int8 "
                 "weight-only serving fits"),
        "devices": len(jax.devices()),
    }
    return _record(out)


def _weight_quant_one(name, wq, batch, prompt_len, decode_tokens,
                      chunk, block_size, seed):
    """One fused weight-quant serving run (engine ``weight_quant`` =
    False | 'int8' | 'int4'): closed-loop batch decode with per-token
    wall timestamps -> TPOT p50/p99 across requests, plus the param
    pool's actual HBM footprint (the pool IS quantized — Int8Weight/
    Int4Weight leaves — so the bytes are counted, not projected) and
    the weight bytes a single decoded token streams."""
    groups.reset()
    model = build_model(name)
    engine = InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            max_batch_size=batch, kv_block_size=block_size,
            prompt_bucket=min(prompt_len, 512), splitfuse_tokens=chunk,
            weight_quant=wq))
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    r = np.random.RandomState(seed)
    V = model.config.vocab_size
    w = engine.put(r.randint(0, V, (prompt_len,)), max_new_tokens=8,
                   eos_token_id=-1)
    while not engine.is_done(w):
        engine.step()                  # warm prefill + decode programs
    engine.get(w)

    tok_times = {}
    for _ in range(batch):
        uid = engine.put(r.randint(0, V, (prompt_len,)),
                         max_new_tokens=decode_tokens, eos_token_id=-1)
        tok_times[uid] = []
    t0 = time.perf_counter()
    produced = 0
    while engine.has_work:
        out = engine.step()
        t = time.perf_counter() - t0
        for uid, _tok in out:
            tok_times[uid].append(t)
        produced += len(out)
    wall = time.perf_counter() - t0
    for uid in list(engine._results):
        np.asarray(engine.get(uid))

    tpot = [1e3 * (ts[-1] - ts[0]) / (len(ts) - 1)
            for ts in tok_times.values()
            if len(ts) >= 2 and ts[-1] != ts[0]]
    return {
        "model": name, "mode": "weight-quant",
        "variant": {"weight_quant": wq or "off"},
        "batch": batch, "prompt_len": prompt_len,
        "decode_tokens": decode_tokens, "splitfuse_tokens": chunk,
        "weight_hbm_mb": round(weight_bytes / 2**20, 2),
        # every decode step streams the full weight pool once: the
        # HBM-bandwidth bound per generated token (per sequence)
        "weight_bytes_per_token_mb": round(weight_bytes / 2**20, 2),
        "tpot_ms_p50": _pct(tpot, 50), "tpot_ms_p99": _pct(tpot, 99),
        "decode_tokens_per_sec": (round(produced / wall, 1)
                                  if produced else None),
        "devices": len(jax.devices()),
    }


def bench_weight_quant(name="tiny", batch=4, prompt_len=128,
                       decode_tokens=32, chunk=0, block_size=64, seed=0):
    """Fused weight-only low-precision serving sweep (SERVE_WQ): the
    same closed-loop decode at weight_quant off / int8 / int4. The
    quantized rows carry their HBM delta vs the off row — the W8A16
    capacity/bandwidth claim is the ~2x (int8) / ~4x (int4) weight
    shrink with TPOT within noise of off on bandwidth-bound shapes.
    A variant that crashes records its error and the sweep continues."""
    rows = []
    off_mb = None
    for wq in (False, "int8", "int4"):
        try:
            row = _weight_quant_one(name, wq, batch, prompt_len,
                                    decode_tokens, chunk, block_size,
                                    seed)
            if wq is False:
                off_mb = row["weight_hbm_mb"]
            elif off_mb:
                row["weight_hbm_delta_mb"] = round(
                    row["weight_hbm_mb"] - off_mb, 2)
                row["weight_hbm_ratio_vs_off"] = round(
                    row["weight_hbm_mb"] / off_mb, 3)
            rows.append(_record(row))
        except Exception as e:  # noqa: BLE001 — keep sweeping
            rows.append(_record({
                "model": name, "mode": "weight-quant",
                "variant": {"weight_quant": wq or "off"},
                "error": f"{type(e).__name__}: {e}"[:300]}))
        write_local_report()           # partial sweep already durable
    return rows


def _build_draft(name):
    """Narrow draft counterpart of a bench model (~1/8 the compute of
    the target: fewer/narrower layers, same vocab)."""
    from dataclasses import replace
    from deepspeed_tpu.models import GPT2Config
    if name in ("tiny", "tiny-wq"):
        return GPT2(GPT2Config(n_layer=1, n_head=2, d_model=32,
                               max_seq_len=1024, vocab_size=512,
                               remat=False, dtype="float32"))
    if name == "gpt2-350M":
        return GPT2(replace(PRESETS["350M"], n_layer=4, n_head=8,
                            d_model=512, max_seq_len=2048))
    if name == "llama-1b":
        return Llama(LlamaConfig(n_layer=4, n_head=8, n_kv_heads=4,
                                 d_model=512, d_ff=1408,
                                 max_seq_len=2048, vocab_size=32000))
    raise ValueError(f"no draft sizing for {name}")


def _spec_one(name, spec_k, workload, batch, prompt_len, decode_tokens,
              chunk, block_size, seed):
    """One speculative serving run: closed-loop batch decode with
    per-token wall timestamps. ``spec_k=0`` = speculation off (the
    baseline row). Workloads: "shared-template" is the synthetic
    high-acceptance traffic (the draft shares the target's weights —
    the oracle-draft bound, every round commits k+1 tokens);
    "random-token" is the adversarial low-acceptance traffic (an
    independently-initialized draft + the acceptance floor pinned at
    1.0, so the per-sequence fallback latch engages after
    SPEC_MIN_ROUNDS and the row measures speculation's worst-case
    overhead over plain decode)."""
    groups.reset()
    model = build_model(name)
    spec_on = spec_k > 0
    kw = {}
    if spec_on:
        if workload == "shared-template":
            draft = build_model(name)      # oracle: same config+seed
        else:
            draft = _build_draft(name)
        kw = dict(draft_model=draft)
    engine = InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            max_batch_size=batch, kv_block_size=block_size,
            prompt_bucket=min(prompt_len, 512), splitfuse_tokens=chunk,
            spec_draft=spec_on, spec_k=max(1, spec_k)), **kw)
    if spec_on and workload == "shared-template":
        # oracle draft: share the target's weights outright — the
        # draft's argmax always equals the target's, so every round
        # commits k+1 tokens (the tokens-per-verify-step upper bound)
        engine.draft_params = engine.params
    if spec_on and workload == "random-token":
        engine._spec_floor = 1.0           # adversarial: always latch
    r = np.random.RandomState(seed)
    V = model.config.vocab_size
    w = engine.put(r.randint(0, V, (prompt_len,)), max_new_tokens=8,
                   eos_token_id=-1)
    while not engine.is_done(w):
        engine.step()                 # warm every program variant
    engine.get(w)

    tok_times = {}
    for _ in range(batch):
        uid = engine.put(r.randint(0, V, (prompt_len,)),
                         max_new_tokens=decode_tokens, eos_token_id=-1)
        tok_times[uid] = []
    t0 = time.perf_counter()
    produced = 0
    while engine.has_work:
        out = engine.step()
        t = time.perf_counter() - t0
        for uid, _tok in out:
            tok_times[uid].append(t)
        produced += len(out)
    wall = time.perf_counter() - t0
    for uid in list(engine._results):
        np.asarray(engine.get(uid))

    tpot = [1e3 * (ts[-1] - ts[0]) / (len(ts) - 1)
            for ts in tok_times.values()
            if len(ts) >= 2 and ts[-1] != ts[0]]
    tel = engine.telemetry.percentiles()
    row = {
        "model": name, "mode": "speculative",
        "variant": {"spec": "on" if spec_on else "off",
                    "spec_k": spec_k, "workload": workload},
        "batch": batch, "prompt_len": prompt_len,
        "decode_tokens": decode_tokens, "splitfuse_tokens": chunk,
        "tpot_ms_p50": _pct(tpot, 50), "tpot_ms_p99": _pct(tpot, 99),
        "decode_tokens_per_sec": (round(produced / wall, 1)
                                  if produced else None),
        # zero-verify-step guard: the telemetry only carries spec keys
        # once a verify round ran, so off rows (and spec-on rows whose
        # traffic never speculated) report None — never a NaN from a
        # 0/0 percentile window
        "spec_rounds": tel.get("spec_rounds"),
        "acceptance_rate_pct": tel.get("spec_acceptance_pct"),
        "tokens_per_verify_step": tel.get("spec_tokens_per_verify_step"),
        "devices": len(jax.devices()),
    }
    if spec_on and workload == "random-token":
        row["acceptance_floor"] = 1.0
        row["fallback_engaged"] = tel.get("spec_rounds") is not None
    da = engine.state_mgr.draft_allocator
    if da is not None:
        assert da.free_blocks == da.total_blocks, "leaked draft blocks"
    return row


def bench_speculative(name="tiny", batch=4, prompt_len=64,
                      decode_tokens=32, chunk=16, block_size=16,
                      spec_ks=(2, 4), seed=0):
    """Speculative-decoding sweep (SERVE_SPEC): plain decode baseline,
    then draft-on at each ``spec_k`` under the synthetic
    high-acceptance workload (oracle draft — the tokens-per-verify-step
    upper bound, > 1.5 expected at spec_k=4), then the adversarial
    random-token row where the acceptance-floor fallback engages and
    p99 TPOT must stay within noise of the baseline. A variant that
    crashes records its error and the sweep continues."""
    rows = []
    variants = [(0, "shared-template")]
    variants += [(k, "shared-template") for k in spec_ks]
    variants += [(max(spec_ks), "random-token")]
    for spec_k, workload in variants:
        try:
            rows.append(_record(_spec_one(
                name, spec_k, workload, batch, prompt_len,
                decode_tokens, chunk, block_size, seed)))
        except Exception as e:  # noqa: BLE001 — keep sweeping
            rows.append(_record({
                "model": name, "mode": "speculative",
                "variant": {"spec": "on" if spec_k else "off",
                            "spec_k": spec_k, "workload": workload},
                "error": f"{type(e).__name__}: {e}"[:300]}))
        write_local_report()           # partial sweep already durable
    return rows


def bench_kv_offload(name="gpt2-350M", batch=4, prompt_len=512,
                     decode_tokens=64, block_size=64, device_blocks=20,
                     quantize=False, splitfuse=0, max_batch=None):
    """ZeRO-Inference KV host offload (reference README.md:30): the
    batch's total KV footprint exceeds the device block pool; blocks
    page between host RAM and the device (inference/v2/kv_offload.py)
    with next-group H2D prefetched under the current group's compute.
    Reports decode rate resident vs offloaded + swap volumes, so the
    transfer cost can be read against the host link's bandwidth.
    """
    rng = np.random.RandomState(0)

    def run(offload):
        groups.reset()
        model = build_model(name)
        V = model.config.vocab_size
        cfg = dict(max_batch_size=max_batch or batch,
                   kv_block_size=block_size,
                   prompt_bucket=min(prompt_len, 512),
                   splitfuse_tokens=splitfuse,
                   quantize_weights=quantize)
        if offload:
            cfg.update(kv_host_offload=True,
                       device_kv_blocks=device_blocks,
                       num_kv_blocks=1 + batch * -(-(
                           prompt_len + decode_tokens) // block_size))
        engine = InferenceEngineV2(model,
                                   RaggedInferenceEngineConfig(**cfg))
        for _ in range(batch):
            engine.put(rng.randint(0, V, (prompt_len,)),
                       max_new_tokens=decode_tokens, eos_token_id=-1)
        t0 = time.perf_counter()
        engine.step()                       # admit + prefill (+1st decode)
        t_prefill = time.perf_counter() - t0
        produced = 0
        t0 = time.perf_counter()
        while engine.has_work:
            produced += len(engine.step())
        for uid in list(engine._results):
            np.asarray(engine.get(uid))
        t_decode = time.perf_counter() - t0
        stats = {}
        if engine.kv_pool is not None:
            blk_bytes = (np.prod(engine.kv_pool._blk_shape) * 2
                         * engine.kv_pool.n_layer
                         * np.dtype(engine.kv_pool.dtype).itemsize)
            stats = {"swapped_in_blocks": engine.kv_pool.swapped_in,
                     "swapped_out_blocks": engine.kv_pool.swapped_out,
                     "swap_gb": round((engine.kv_pool.swapped_in
                                       + engine.kv_pool.swapped_out)
                                      * blk_bytes / 2**30, 2)}
        return (produced / t_decode if produced else None,
                t_prefill, stats)

    res_rate, res_prefill, _ = (None, None, None) if quantize \
        else run(offload=False)
    off_rate, off_prefill, stats = run(offload=True)
    total_blocks = batch * -(-(prompt_len + decode_tokens) // block_size)
    out = {
        "model": name, "mode": "kv-host-offload",
        "batch": batch, "prompt_len": prompt_len,
        "decode_tokens": decode_tokens,
        "logical_kv_blocks": total_blocks,
        "device_kv_blocks": device_blocks,
        "oversubscription": round(total_blocks / (device_blocks - 1), 2),
        "decode_tok_s_resident": (round(res_rate, 1) if res_rate
                                  else None),
        "decode_tok_s_offload": (round(off_rate, 1) if off_rate
                                 else None),
        "quantize_weights": quantize,
        **stats,
        "devices": len(jax.devices()),
    }
    return _record(out)


def bench_sla(name="gpt2-350M", rates=(1.0, 2.0, 4.0), n_requests=24,
              prompt_len=256, decode_tokens=48, sla_ms=100.0,
              splitfuse=0, block_size=64, seed=0):
    """SLA-grade serving benchmark (reference
    blogs/deepspeed-fastgen/README.md:160-186): Poisson request
    arrivals at each rate; report per-token latency p50/p95, end-to-end
    p50/p95, and goodput — completed queries/s whose mean inter-token
    latency met the SLA. The host's per-dispatch overhead is measured
    with a no-op dispatch and reported alongside so the engine cost can
    be separated from it."""
    groups.reset()
    model = build_model(name)
    V = model.config.vocab_size

    # measure the host's per-dispatch overhead (scalar round trip)
    one = jax.jit(lambda x: x + 1)
    one(np.float32(0)).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        float(one(np.float32(0)))
    dispatch_ms = (time.perf_counter() - t0) / 10 * 1e3

    results = []
    for rate in rates:
        groups.reset()
        engine = InferenceEngineV2(
            model, RaggedInferenceEngineConfig(
                max_batch_size=8, kv_block_size=block_size,
                prompt_bucket=min(prompt_len, 512),
                splitfuse_tokens=splitfuse))
        r = np.random.RandomState(seed)
        arrivals = np.cumsum(r.exponential(1.0 / rate, n_requests))
        prompts = [r.randint(0, V, (prompt_len,)) for _ in range(n_requests)]
        # warm EVERY program the run will hit: chunk-only, decode, and —
        # critically under SplitFuse — the FUSED chunk+decode program,
        # which only traces when a prompt chunk arrives while another
        # sequence is DECODING (without this the first mid-run overlap
        # pays a full XLA compile inside the timed window). w1 gets a
        # long decode budget so it is guaranteed still running when w2's
        # chunks dispatch.
        w1 = engine.put(prompts[0], max_new_tokens=64, eos_token_id=-1)
        for _ in range(1 + prompt_len // max(1, splitfuse or prompt_len)):
            engine.step()               # w1 fully prefilled + decoding
        w2 = engine.put(prompts[1], max_new_tokens=4, eos_token_id=-1)
        while not (engine.is_done(w1) and engine.is_done(w2)):
            engine.step()
        engine.get(w1), engine.get(w2)

        tok_times, submit, wall = _poisson_drive(
            engine, prompts[:n_requests], arrivals, decode_tokens)

        per_tok = []
        e2e = []
        met = 0
        for uid, ts in tok_times.items():
            if not ts:
                continue
            # inter-token latency: includes queueing for the first token
            gaps = np.diff([submit[uid]] + ts)
            mean_tok_ms = 1e3 * (ts[-1] - submit[uid]) / len(ts)
            per_tok.extend(1e3 * gaps)
            e2e.append(ts[-1] - submit[uid])
            if mean_tok_ms <= sla_ms:
                met += 1
        row = {
            "model": name, "mode": "sla",
            "splitfuse_tokens": splitfuse,
            "arrival_rate_qps": rate,
            "n_requests": n_requests,
            "prompt_len": prompt_len, "decode_tokens": decode_tokens,
            "token_latency_ms_p50": _pct(per_tok, 50, 1),
            "token_latency_ms_p95": _pct(per_tok, 95, 1),
            "e2e_s_p50": _pct(e2e, 50, 2),
            "e2e_s_p95": _pct(e2e, 95, 2),
            "sla_ms_per_token": sla_ms,
            "goodput_qps": round(met / wall, 2),
            "offered_qps": round(n_requests / wall, 2),
            "dispatch_overhead_ms": round(dispatch_ms, 1),
            "devices": len(jax.devices()),
        }
        results.append(_record(row))
    return results


def _mixed_one(name, rate, n_requests, long_prompt, short_prompt,
               long_every, decode_tokens, splitfuse, paged_kernel,
               block_size, max_batch, seed):
    """One mixed-traffic run; returns the TTFT/TPOT percentile row."""
    groups.reset()
    model = build_model(name)
    # the long prompt + its decode budget (and the 64-token warm-up
    # budget) must fit the model's context, whatever model/env combo
    # was asked for — clamp instead of erroring every variant
    long_prompt = min(long_prompt,
                      model.config.max_seq_len - max(decode_tokens, 64))
    short_prompt = min(short_prompt, long_prompt)
    engine = InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            max_batch_size=max_batch, kv_block_size=block_size,
            prompt_bucket=min(long_prompt, 512),
            splitfuse_tokens=splitfuse, paged_kernel=paged_kernel))
    r = np.random.RandomState(seed)
    V = model.config.vocab_size
    arrivals = np.cumsum(r.exponential(1.0 / rate, n_requests))
    prompts = [r.randint(0, V, (long_prompt if i % long_every == 0
                                else short_prompt,))
               for i in range(n_requests)]

    # warm EVERY program the mix will hit: the short and long prefill
    # shapes (bucketed path) / the chunk + FUSED chunk-while-decoding
    # programs (SplitFuse path), and the decode loop — a mid-run XLA
    # compile would land inside some request's TTFT
    w1 = engine.put(r.randint(0, V, (short_prompt,)),
                    max_new_tokens=64, eos_token_id=-1)
    for _ in range(2 + short_prompt // max(1, splitfuse or short_prompt)):
        engine.step()                  # w1 prefilled + decoding
    w2 = engine.put(r.randint(0, V, (long_prompt,)), max_new_tokens=4,
                    eos_token_id=-1)
    while not (engine.is_done(w1) and engine.is_done(w2)):
        engine.step()
    engine.get(w1), engine.get(w2)

    tok_times, submit, wall = _poisson_drive(engine, prompts, arrivals,
                                             decode_tokens)

    ttft, tpot = [], []
    first_dispatch_finishers = 0
    for uid, ts in tok_times.items():
        if not ts:
            continue
        ttft.append(1e3 * (ts[0] - submit[uid]))
        if len(ts) < 2 or ts[-1] == ts[0]:
            # the whole budget arrived in one dispatch: there is no
            # inter-token gap to measure — counted, not divided by zero
            first_dispatch_finishers += 1
            continue
        tpot.append(1e3 * (ts[-1] - ts[0]) / (len(ts) - 1))
    return {
        "model": name, "mode": "mixed-traffic",
        "variant": {"paged_kernel": "on" if paged_kernel else "off",
                    "splitfuse": "on" if splitfuse else "off"},
        "arrival_rate_qps": rate, "n_requests": n_requests,
        "long_prompt": long_prompt, "short_prompt": short_prompt,
        "long_every": long_every, "decode_tokens": decode_tokens,
        "splitfuse_tokens": splitfuse,
        "ttft_ms_p50": _pct(ttft, 50), "ttft_ms_p99": _pct(ttft, 99),
        "tpot_ms_p50": _pct(tpot, 50), "tpot_ms_p99": _pct(tpot, 99),
        "first_dispatch_finishers": first_dispatch_finishers,
        "completed": len([1 for ts in tok_times.values() if ts]),
        "wall_s": round(wall, 2),
        "devices": len(jax.devices()),
        # the engine's OWN per-request accounting (monitor/telemetry.py
        # ServingTelemetry — what a production fan-out would export),
        # next to the harness-measured percentiles as a cross-check
        "engine_telemetry": engine.telemetry_snapshot(),
    }


def bench_mixed_traffic(name="gpt2-350M", rate=2.0, n_requests=24,
                        long_prompt=1024, short_prompt=64, long_every=4,
                        decode_tokens=64, chunk=256, block_size=64,
                        max_batch=8, seed=0):
    """Sustained mixed traffic (ROADMAP item 1's harness): Poisson
    arrivals where every ``long_every``-th request carries a
    ``long_prompt``-token prompt and the rest are short decode-heavy
    requests. Reports p50/p99 TTFT and TPOT SEPARATELY for the 2x2 of
    paged kernel on/off x SplitFuse on/off — split-fuse holding p99
    TPOT flat while long prefills stream is the FastGen headline
    property; the paged-kernel pair isolates the blocked-flash chunk
    kernel's effect on both tails. A variant that crashes records its
    error and the sweep continues (partial artifacts beat lost ones)."""
    rows = []
    for splitfuse in (chunk, 0):
        for paged in (True, False):
            try:
                rows.append(_record(_mixed_one(
                    name, rate, n_requests, long_prompt, short_prompt,
                    long_every, decode_tokens, splitfuse, paged,
                    block_size, max_batch, seed)))
            except Exception as e:  # noqa: BLE001 — keep sweeping
                rows.append(_record({
                    "model": name, "mode": "mixed-traffic",
                    "variant": {"paged_kernel": "on" if paged else "off",
                                "splitfuse": "on" if splitfuse
                                else "off"},
                    "error": f"{type(e).__name__}: {e}"[:300]}))
            write_local_report()       # partial sweep already durable
    return rows


def _shared_prefix_one(name, rate, n_requests, n_templates, template_len,
                       suffix_len, share_ratio, decode_tokens, chunk,
                       block_size, max_batch, prefix_cache, seed):
    """One shared-prefix traffic run; returns the percentile row with
    the engine's prefix-cache counters (hit rate, cached tokens, CoW
    copies, evictions) measured over the driven traffic only — warm-up
    requests are snapshotted out."""
    groups.reset()
    model = build_model(name)
    template_len = min(
        template_len,
        model.config.max_seq_len - suffix_len - max(decode_tokens, 64))
    engine = InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            max_batch_size=max_batch, kv_block_size=block_size,
            prompt_bucket=min(template_len + suffix_len, 512),
            splitfuse_tokens=chunk, prefix_cache=prefix_cache))
    r = np.random.RandomState(seed)
    V = model.config.vocab_size
    templates = [r.randint(0, V, (template_len,))
                 for _ in range(n_templates)]
    arrivals = np.cumsum(r.exponential(1.0 / rate, n_requests))
    prompts = []
    shared_count = 0
    for _ in range(n_requests):
        suffix = r.randint(0, V, (suffix_len,))
        if r.rand() < share_ratio:
            shared_count += 1
            prompts.append(np.concatenate(
                [templates[r.randint(n_templates)], suffix]))
        else:
            prompts.append(r.randint(0, V, (template_len + suffix_len,)))

    # warm every program outside the driven requests' TTFT: chunk,
    # fused chunk+decode, decode — and for the cache-on variant the CoW
    # copy program (second warm-up shares the first's prompt, diverging
    # mid-block). One donor request per template then runs to
    # completion so the driven phase measures the WARM cache (hit rate
    # ~= share_ratio): inserts happen at release, so without donors the
    # first arrival of every template — plus every sharer admitted
    # while it is still in flight — is a structural miss. The cache-off
    # variant runs the identical donors, so the two rows differ only in
    # the cache.
    warm = r.randint(0, V, (template_len + suffix_len,))
    w1 = engine.put(warm, max_new_tokens=decode_tokens, eos_token_id=-1)
    for _ in range(2):
        engine.step()              # w1 prefilling/decoding
    w2 = engine.put(np.concatenate([warm[:-3], r.randint(0, V, (3,))]),
                    max_new_tokens=4, eos_token_id=-1)
    while not (engine.is_done(w1) and engine.is_done(w2)):
        engine.step()
    engine.get(w1), engine.get(w2)
    donors = [engine.put(
        np.concatenate([t, r.randint(0, V, (suffix_len,))]),
        max_new_tokens=2, eos_token_id=-1) for t in templates]
    while not all(engine.is_done(d) for d in donors):
        engine.step()
    for d in donors:
        engine.get(d)
    base = engine.prefix_cache.stats() if engine.prefix_cache else None

    tok_times, submit, wall = _poisson_drive(engine, prompts, arrivals,
                                             decode_tokens)

    ttft, tpot = [], []
    for uid, ts in tok_times.items():
        if not ts:
            continue
        ttft.append(1e3 * (ts[0] - submit[uid]))
        if len(ts) >= 2 and ts[-1] != ts[0]:
            tpot.append(1e3 * (ts[-1] - ts[0]) / (len(ts) - 1))
    row = {
        "model": name, "mode": "shared-prefix",
        "variant": {"prefix_cache": "on" if prefix_cache else "off"},
        "arrival_rate_qps": rate, "n_requests": n_requests,
        "n_templates": n_templates, "template_len": template_len,
        "suffix_len": suffix_len, "share_ratio": share_ratio,
        "shared_requests": shared_count,
        "decode_tokens": decode_tokens, "splitfuse_tokens": chunk,
        "ttft_ms_p50": _pct(ttft, 50), "ttft_ms_p99": _pct(ttft, 99),
        "tpot_ms_p50": _pct(tpot, 50), "tpot_ms_p99": _pct(tpot, 99),
        "completed": len([1 for ts in tok_times.values() if ts]),
        "wall_s": round(wall, 2),
        "devices": len(jax.devices()),
        "engine_telemetry": engine.telemetry_snapshot(),
    }
    if engine.prefix_cache is not None:
        s = engine.prefix_cache.stats()
        lookups = s["lookups"] - base["lookups"]
        hits = s["hits"] - base["hits"]
        row["cache_hit_rate"] = round(100.0 * hits / lookups, 1) \
            if lookups else 0.0
        row["cached_tokens"] = s["cached_tokens"] - base["cached_tokens"]
        row["cached_tokens_per_sec"] = round(
            row["cached_tokens"] / max(wall, 1e-9), 1)
        row["cow_copies"] = s["cow_copies"] - base["cow_copies"]
        row["prefix_evictions"] = \
            s["evicted_blocks"] - base["evicted_blocks"]
        row["tree_blocks"] = s["tree_blocks"]
    else:
        row["cache_hit_rate"] = 0.0
    return row


def bench_shared_prefix(name="gpt2-350M", rate=2.0, n_requests=24,
                        n_templates=4, template_len=512, suffix_len=64,
                        share_ratio=0.75, decode_tokens=64, chunk=256,
                        block_size=64, max_batch=8, seed=0):
    """Shared-prefix Poisson traffic (ROADMAP item 3a's harness):
    ``n_templates`` prompt templates, each request drawing a template +
    per-request suffix with probability ``share_ratio`` (else a fully
    random prompt of the same length). Reports TTFT/TPOT p50/p99 and
    the cache hit rate for prefix_cache on vs off — the pass signal is
    TTFT p50 collapsing on the cache-on row while p99 TPOT stays within
    noise (cached prefixes skip prefill chunks; decode work is
    unchanged). A variant that crashes records its error and the sweep
    continues; every row is durable in SERVE_local.json immediately."""
    rows = []
    for prefix_cache in (True, False):
        try:
            rows.append(_record(_shared_prefix_one(
                name, rate, n_requests, n_templates, template_len,
                suffix_len, share_ratio, decode_tokens, chunk,
                block_size, max_batch, prefix_cache, seed)))
        except Exception as e:  # noqa: BLE001 — keep sweeping
            rows.append(_record({
                "model": name, "mode": "shared-prefix",
                "variant": {"prefix_cache": "on" if prefix_cache
                            else "off"},
                "error": f"{type(e).__name__}: {e}"[:300]}))
        write_local_report()           # partial sweep already durable
    return rows


def _router_drive(router, prompts, arrivals, decode_tokens, classes,
                  kill_at_step=None, drain_at_step=None):
    """Open-loop Poisson driver against the ROUTER (the front-end owns
    the queue, so back-pressure shows up as typed Overloaded rejections
    at put() — counted, not crashed). Optionally arms one
    ``replica_death`` (mid-run kill) or drains replica 0 once
    ``*_at_step`` router rounds have run."""
    uids, rejected_at_put = [], 0
    n = len(prompts)
    start = time.perf_counter()
    i = 0
    steps = 0
    injected = False
    while i < n or router.has_work:
        now = time.perf_counter() - start
        while i < n and arrivals[i] <= now:
            try:
                uids.append(router.put(
                    prompts[i], max_new_tokens=decode_tokens,
                    eos_token_id=-1, klass=classes[i]))
            except Overloaded:
                rejected_at_put += 1
            i += 1
        if not router.has_work:
            time.sleep(min(0.005, max(0.0, arrivals[i] - now)))
            continue
        router.step()
        steps += 1
        if not injected and steps >= (kill_at_step or 0) > 0:
            injected = True
            fault_injection.arm("replica_death", fails=1)
        if not injected and steps >= (drain_at_step or 0) > 0:
            injected = True
            router.drain(router.replicas[0].name)
    return time.perf_counter() - start, rejected_at_put, steps


def _router_one(name, n_replicas, scenario, rate, n_requests, prompt_len,
                decode_tokens, chunk, block_size, max_batch, seed):
    """One fleet-traffic run: N in-process replica engines (shared
    weights) behind the Router, mixed-class Poisson arrivals (class =
    request index mod 3), one row with the router's per-class
    accounting + latency percentiles. ``scenario``:

      baseline      — nothing injected
      replica-kill  — one armed replica_death mid-run (failover +
                      byte-identical replay path under real traffic)
      drain         — router.drain(r0) mid-run (scale-down: finish
                      in-flight, no replay)
    """
    model = build_model(name)
    groups.reset()
    params = model.init(jax.random.key(0))
    engines = []
    for _ in range(n_replicas):
        groups.reset()
        engines.append(InferenceEngineV2(
            model, params=params,
            config=RaggedInferenceEngineConfig(
                max_batch_size=max_batch, kv_block_size=block_size,
                prompt_bucket=min(prompt_len, 512),
                splitfuse_tokens=chunk, prefix_cache=True)))
    router = Router(engines)
    r = np.random.RandomState(seed)
    V = model.config.vocab_size
    prompts = [r.randint(0, V, (prompt_len,)) for _ in range(n_requests)]
    classes = [i % 3 for i in range(n_requests)]
    arrivals = np.cumsum(r.exponential(1.0 / rate, n_requests))
    mid = max(2, n_requests // 2)
    try:
        wall, rejected_at_put, steps = _router_drive(
            router, prompts, arrivals, decode_tokens, classes,
            kill_at_step=mid if scenario == "replica-kill" else None,
            drain_at_step=mid if scenario == "drain" else None)
    finally:
        fault_injection.reset()
    snap = router.snapshot()
    # zero-drop invariant: every admitted request left through exactly
    # one typed exit (completed/expired/queued-shed); admission
    # rejections are the shed counter's remainder
    closed = (snap["completed"] + snap["expired"]
              + (snap["shed"] - rejected_at_put)) == snap["admitted"]
    return {
        "model": name, "mode": "router-traffic",
        "variant": {"fleet": n_replicas, "scenario": scenario},
        "arrival_rate_qps": rate, "n_requests": n_requests,
        "prompt_len": prompt_len, "decode_tokens": decode_tokens,
        "splitfuse_tokens": chunk,
        "queue_depth": router.resolved_queue_depth(),
        "router_steps": steps, "wall_s": round(wall, 2),
        "admitted": snap["admitted"], "completed": snap["completed"],
        "shed": snap["shed"], "expired": snap["expired"],
        "replayed": snap["replayed"], "failovers": snap["failovers"],
        "rejected_at_put": rejected_at_put,
        "accounting_closed": closed,
        "replicas": snap["replicas"],
        # per-class rows: admitted/completed/shed/expired/replayed and
        # p50/p99 TTFT+TPOT measured by the router itself
        "classes": {str(k): v for k, v in snap["classes"].items()},
        "devices": len(jax.devices()),
    }


def bench_router_traffic(name="gpt2-350M", n_replicas=2, rate=2.0,
                         n_requests=24, prompt_len=256, decode_tokens=64,
                         chunk=256, block_size=64, max_batch=8, seed=0):
    """Serving-fleet robustness sweep (SERVE_REPLICAS=N): the same
    mixed-class Poisson traffic through baseline / mid-run replica-kill
    / mid-run drain. The kill row's pass signal is failovers=1 with
    accounting_closed (every admitted request completed or left through
    a typed exit — zero drops); the drain row's is replayed=0. A
    scenario that crashes records its error and the sweep continues."""
    rows = []
    for scenario in ("baseline", "replica-kill", "drain"):
        try:
            rows.append(_record(_router_one(
                name, n_replicas, scenario, rate, n_requests, prompt_len,
                decode_tokens, chunk, block_size, max_batch, seed)))
        except Exception as e:  # noqa: BLE001 — keep sweeping
            rows.append(_record({
                "model": name, "mode": "router-traffic",
                "variant": {"fleet": n_replicas, "scenario": scenario},
                "error": f"{type(e).__name__}: {e}"[:300]}))
        write_local_report()           # partial sweep already durable
    return rows


def _disagg_one(name, fleet, scenario, rate, n_requests, short_prompt,
                long_prompt, n_interference, decode_tokens, chunk,
                block_size, max_batch, seed):
    """One disaggregated-serving run: a role-labeled fleet (shared
    weights) behind the phase-aware router, short decode-heavy klass-0
    traffic with an optional burst of long-prefill klass-1 interference
    landing mid-run. Returns one row with the router's handoff/wire
    counters and the DECODE-CLASS (klass 0) latency percentiles — the
    headline comparison is klass-0 p99 TPOT under interference:
    colocated fleets interleave the long prefill chunks into every
    decode batch, a prefill/decode split keeps the decode replicas'
    iteration time flat. ``scenario``:

      quiet              — short traffic only
      interference       — + long-prefill burst at the run's midpoint
      interference-kill  — + one armed replica_death mid-run (handoff
                           failover / colocated-degradation path under
                           real traffic; accounting must stay closed)
    """
    from deepspeed_tpu.inference.v2.replica import Replica
    model = build_model(name)
    long_prompt = min(long_prompt,
                      model.config.max_seq_len - decode_tokens)
    short_prompt = min(short_prompt, long_prompt)
    groups.reset()
    params = model.init(jax.random.key(0))
    replicas = []
    for i, role in enumerate(fleet):
        groups.reset()
        eng = InferenceEngineV2(
            model, params=params,
            config=RaggedInferenceEngineConfig(
                max_batch_size=max_batch, kv_block_size=block_size,
                prompt_bucket=min(long_prompt, 512),
                splitfuse_tokens=chunk))
        replicas.append(Replica(f"{role[:1]}{i}", eng, role=role))
    router = Router(replicas)
    r = np.random.RandomState(seed)
    V = model.config.vocab_size

    # warm every program OUTSIDE the measured traffic: each engine's
    # chunk/fused/decode programs, and for prefill/decode pairs the
    # handoff gather/scatter jits + wire codec (compiles landing inside
    # a driven request's TTFT would swamp the smoke-scale percentiles)
    for rep in replicas:
        eng = rep.engine
        w1 = eng.put(r.randint(0, V, (short_prompt,)),
                     max_new_tokens=8, eos_token_id=-1)
        for _ in range(2):
            eng.step()
        w2 = eng.put(r.randint(0, V, (long_prompt,)), max_new_tokens=2,
                     eos_token_id=-1)
        while not (eng.is_done(w1) and eng.is_done(w2)):
            eng.step()
        eng.get(w1), eng.get(w2)
    from deepspeed_tpu.inference.v2 import kv_transfer
    pre = [x for x in replicas if x.role == "prefill"]
    dec = [x for x in replicas if x.role == "decode"]
    for i, P in enumerate(pre):
        D = dec[i % len(dec)] if dec else None
        if D is None:
            break
        wu = P.engine.put(r.randint(0, V, (short_prompt,)),
                          max_new_tokens=4, eos_token_id=-1)
        P.engine.hold_decode(wu)
        while True:
            P.engine.step()
            seq = P.engine.state_mgr._seqs.get(wu)
            if seq is not None and seq.generated:
                break
        kv_transfer.import_sequence(
            D.engine, kv_transfer.export_sequence(P.engine, wu))
        P.engine.release_handoff(wu)
        while not D.engine.is_done(wu):
            D.engine.step()
        D.engine.get(wu)

    prompts = [r.randint(0, V, (short_prompt,))
               for _ in range(n_requests)]
    classes = [0] * n_requests
    arrivals = list(np.cumsum(r.exponential(1.0 / rate, n_requests)))
    if scenario != "quiet":
        # the interference burst: n_interference long prefills all
        # arriving at once at the run's midpoint
        t_burst = arrivals[n_requests // 2]
        prompts += [r.randint(0, V, (long_prompt,))
                    for _ in range(n_interference)]
        classes += [1] * n_interference
        arrivals += [t_burst] * n_interference
        order = np.argsort(np.asarray(arrivals), kind="stable")
        prompts = [prompts[i] for i in order]
        classes = [classes[i] for i in order]
        arrivals = [arrivals[i] for i in order]
    mid = max(2, len(prompts) // 2)
    try:
        wall, rejected_at_put, steps = _router_drive(
            router, prompts, np.asarray(arrivals), decode_tokens,
            classes,
            kill_at_step=mid if scenario == "interference-kill"
            else None)
    finally:
        fault_injection.reset()
    snap = router.snapshot()
    closed = (snap["completed"] + snap["expired"]
              + (snap["shed"] - rejected_at_put)) == snap["admitted"]
    k0 = snap["classes"].get(0, {})
    return {
        "model": name, "mode": "disagg-serving",
        "variant": {"fleet": "+".join(fleet), "scenario": scenario},
        "arrival_rate_qps": rate, "n_requests": len(prompts),
        "short_prompt": short_prompt, "long_prompt": long_prompt,
        "n_interference": n_interference if scenario != "quiet" else 0,
        "decode_tokens": decode_tokens, "splitfuse_tokens": chunk,
        "router_steps": steps, "wall_s": round(wall, 2),
        "admitted": snap["admitted"], "completed": snap["completed"],
        "shed": snap["shed"], "expired": snap["expired"],
        "replayed": snap["replayed"], "failovers": snap["failovers"],
        "rejected_at_put": rejected_at_put,
        "accounting_closed": closed,
        "handoffs": snap["handoffs"],
        "kv_stream_bytes": snap["kv_stream_bytes"],
        "kv_stream_ms": round(snap["kv_stream_ms"], 2),
        "kv_stream_retries": snap["kv_stream_retries"],
        "replicas": snap["replicas"],
        "roles": snap.get("roles"),
        # the headline numbers: klass-0 (short, decode-heavy) latency
        # as the router measured it — compare p99 TPOT across variants
        "decode_class": {
            "ttft_ms_p50": k0.get("ttft_ms_p50"),
            "ttft_ms_p99": k0.get("ttft_ms_p99"),
            "tpot_ms_p50": k0.get("tpot_ms_p50"),
            "tpot_ms_p99": k0.get("tpot_ms_p99"),
            "completed": k0.get("completed"),
        },
        "classes": {str(k): v for k, v in snap["classes"].items()},
        "devices": len(jax.devices()),
    }


def bench_disagg(name="gpt2-350M", rate=2.0, n_requests=24,
                 short_prompt=64, long_prompt=1024, n_interference=4,
                 decode_tokens=64, chunk=256, block_size=64,
                 max_batch=8, seed=0):
    """Disaggregated prefill/decode sweep (SERVE_DISAGG): the same
    short-request traffic through colocated vs phase-split fleets,
    quiet and under a long-prefill interference burst. The headline
    read: colocated klass-0 p99 TPOT degrades under the burst (every
    decode batch pays for the interleaved prefill chunks) while the
    1P+1D / 2P+2D fleets hold it flat, paying kv_stream_bytes over the
    wire instead. The kill variant arms one replica_death mid-run —
    its pass signal is accounting_closed with the fleet degrading to
    colocated (decode death) or failing over (prefill death). A
    variant that crashes records its error and the sweep continues."""
    variants = [
        (["colocated", "colocated"], "quiet"),
        (["colocated", "colocated"], "interference"),
        (["prefill", "decode"], "quiet"),
        (["prefill", "decode"], "interference"),
        (["prefill", "prefill", "decode", "decode"], "interference"),
        (["prefill", "decode"], "interference-kill"),
    ]
    rows = []
    for fleet, scenario in variants:
        try:
            rows.append(_record(_disagg_one(
                name, fleet, scenario, rate, n_requests, short_prompt,
                long_prompt, n_interference, decode_tokens, chunk,
                block_size, max_batch, seed)))
        except Exception as e:  # noqa: BLE001 — keep sweeping
            rows.append(_record({
                "model": name, "mode": "disagg-serving",
                "variant": {"fleet": "+".join(fleet),
                            "scenario": scenario},
                "error": f"{type(e).__name__}: {e}"[:300]}))
        write_local_report()           # partial sweep already durable
    return rows


def bench_ep_moe(decode_tokens=16, block_size=16, chunk=16,
                 expert_parallel=2):
    """EP Mixtral serving: experts sharded over the 'expert' mesh axis,
    the FFN routed through the ragged EP all_to_all path
    (moe/sharded_moe.py moe_swiglu_ragged_ep — the PR-5 fix for
    GSPMD's silent lax.ragged_dot mis-partition). Asserts greedy
    parity vs the single-shard engine and reports both decode rates;
    SplitFuse on, so the chunk program serves through EP too."""
    if len(jax.devices()) < expert_parallel:
        return _record({
            "mode": "ep-moe-serving",
            "skipped": f"needs >= {expert_parallel} devices, have "
                       f"{len(jax.devices())}"})
    from deepspeed_tpu.models.mixtral import Mixtral, MixtralConfig
    mcfg = MixtralConfig(n_layer=2, n_head=8, n_kv_heads=4, d_model=128,
                         max_seq_len=256, vocab_size=1024, remat=False,
                         num_experts=4, moe_top_k=2, dtype="float32")
    params = Mixtral(mcfg).init(jax.random.key(7))
    r = np.random.RandomState(0)
    prompts = [r.randint(0, mcfg.vocab_size, (n,))
               for n in (24, 40, 9, 33)]

    def run(ep):
        groups.reset()
        # float32 serving: the row's point is EXACT greedy parity
        # through the EP exchange; bf16 reduction reordering across the
        # all_to_all would turn rounding noise into token flips
        engine = InferenceEngineV2(
            Mixtral(mcfg), params=params,
            config=RaggedInferenceEngineConfig(
                dtype="float32", max_batch_size=4,
                kv_block_size=block_size, splitfuse_tokens=chunk,
                expert_parallel=ep))
        outs = engine.generate_all(prompts, max_new_tokens=4)  # warm
        t0 = time.perf_counter()
        outs = engine.generate_all(prompts,
                                   max_new_tokens=decode_tokens)
        dt = time.perf_counter() - t0
        produced = sum(len(o) for o in outs)
        return outs, produced / dt

    ref, rate1 = run(1)
    got, rate_ep = run(expert_parallel)
    parity = all(np.array_equal(a, b) for a, b in zip(ref, got))
    return _record({
        "mode": "ep-moe-serving", "model": "mixtral(2x128,E4)",
        "expert_parallel": expert_parallel,
        "splitfuse_tokens": chunk,
        "greedy_parity_vs_single": parity,
        "decode_tok_s_ep1": round(rate1, 1),
        "decode_tok_s_ep": round(rate_ep, 1),
        "devices": len(jax.devices()),
    })


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    models = [m for m in os.environ.get(
        "SERVE_MODELS", "gpt2-350M,llama-1b").split(",") if m]
    batches = [int(b) for b in
               os.environ.get("SERVE_BATCHES", "1,8").split(",")]
    prompt = int(os.environ.get("SERVE_PROMPT", "1024"))
    decode = int(os.environ.get("SERVE_DECODE", "128"))
    for m in models:
        for b in batches:
            bench_one(m, b, prompt, decode)
    if os.environ.get("SERVE_SPLITFUSE", "1") == "1":
        for m in models:
            bench_splitfuse(m, prompt_len=prompt,
                            chunk=int(os.environ.get("SERVE_CHUNK",
                                                     "256")),
                            decode_tokens=16)
    if os.environ.get("SERVE_MIXED", "1") == "1":
        # off-TPU the paged_kernel=on variants run interpret-mode
        # Pallas — minutes per token at 350M; default to the tiny
        # smoke model AND smoke-scale traffic there so a CPU run still
        # produces all 4 percentile rows in minutes, not hours
        on_tpu = jax.default_backend() == "tpu"
        mixed_kw = {} if on_tpu else dict(
            long_prompt=96, short_prompt=16, decode_tokens=16,
            chunk=16, block_size=8, max_batch=4, rate=8.0)
        if "SERVE_MIXED_RATE" in os.environ:
            mixed_kw["rate"] = float(os.environ["SERVE_MIXED_RATE"])
        bench_mixed_traffic(
            name=os.environ.get("SERVE_MIXED_MODEL",
                                "gpt2-350M" if on_tpu else "tiny"),
            n_requests=int(os.environ.get("SERVE_MIXED_N",
                                          "24" if on_tpu else "12")),
            **mixed_kw)
    if os.environ.get("SERVE_PREFIX", "1") == "1":
        # same CPU smoke-scale discipline as SERVE_MIXED: off-TPU the
        # tiny model + small traffic still produce both rows in minutes
        on_tpu = jax.default_backend() == "tpu"
        pf_kw = {} if on_tpu else dict(
            template_len=96, suffix_len=16, decode_tokens=16, chunk=16,
            block_size=8, max_batch=4, rate=8.0, n_templates=2)
        if "SERVE_PREFIX_SHARE" in os.environ:
            pf_kw["share_ratio"] = float(os.environ["SERVE_PREFIX_SHARE"])
        bench_shared_prefix(
            name=os.environ.get("SERVE_PREFIX_MODEL",
                                "gpt2-350M" if on_tpu else "tiny"),
            n_requests=int(os.environ.get("SERVE_PREFIX_N",
                                          "24" if on_tpu else "12")),
            **pf_kw)
    n_replicas = int(os.environ.get("SERVE_REPLICAS", "0") or "0")
    if n_replicas >= 2:
        # fleet robustness rows (baseline / replica-kill / drain); same
        # CPU smoke-scale discipline as SERVE_MIXED
        on_tpu = jax.default_backend() == "tpu"
        rt_kw = {} if on_tpu else dict(
            prompt_len=48, decode_tokens=16, chunk=16, block_size=8,
            max_batch=4, rate=8.0)
        if "SERVE_ROUTER_RATE" in os.environ:
            rt_kw["rate"] = float(os.environ["SERVE_ROUTER_RATE"])
        bench_router_traffic(
            name=os.environ.get("SERVE_ROUTER_MODEL",
                                "gpt2-350M" if on_tpu else "tiny"),
            n_replicas=n_replicas,
            n_requests=int(os.environ.get("SERVE_ROUTER_N",
                                          "24" if on_tpu else "9")),
            **rt_kw)
    if os.environ.get("SERVE_DISAGG", "1") != "0":
        # disaggregated prefill/decode rows (colocated vs 1P+1D vs
        # 2P+2D under long-prefill interference); same CPU smoke-scale
        # discipline — off-TPU the tiny model produces every row in
        # minutes
        on_tpu = jax.default_backend() == "tpu"
        dg_kw = {} if on_tpu else dict(
            short_prompt=16, long_prompt=96, n_interference=3,
            decode_tokens=24, chunk=16, block_size=8, max_batch=4,
            rate=8.0)
        if "SERVE_DISAGG_RATE" in os.environ:
            dg_kw["rate"] = float(os.environ["SERVE_DISAGG_RATE"])
        bench_disagg(
            name=os.environ.get("SERVE_DISAGG_MODEL",
                                "gpt2-350M" if on_tpu else "tiny"),
            n_requests=int(os.environ.get("SERVE_DISAGG_N",
                                          "24" if on_tpu else "10")),
            **dg_kw)
    if os.environ.get("SERVE_EP_MOE", "1") == "1":
        bench_ep_moe()
    if os.environ.get("SERVE_WQ", "1") != "0":
        # fused weight-only serving rows (off / int8 / int4); same CPU
        # smoke-scale discipline — off-TPU the tiny model produces all
        # three rows in minutes
        on_tpu = jax.default_backend() == "tpu"
        wq_kw = {} if on_tpu else dict(
            batch=4, prompt_len=64, decode_tokens=16, block_size=16)
        bench_weight_quant(
            name=os.environ.get("SERVE_WQ_MODEL",
                                "gpt2-350M" if on_tpu else "tiny-wq"),
            **wq_kw)
    if os.environ.get("SERVE_SPEC", "1") != "0":
        # speculative decoding rows (off / spec_k sweep / adversarial
        # fallback); same CPU smoke-scale discipline — off-TPU the tiny
        # model produces every row in minutes
        on_tpu = jax.default_backend() == "tpu"
        sp_kw = {} if on_tpu else dict(
            batch=4, prompt_len=64, decode_tokens=24, chunk=16,
            block_size=16)
        bench_speculative(
            name=os.environ.get("SERVE_SPEC_MODEL",
                                "gpt2-350M" if on_tpu else "tiny"),
            spec_ks=tuple(int(k) for k in os.environ.get(
                "SERVE_SPEC_KS", "2,4").split(",")),
            **sp_kw)
    if os.environ.get("SERVE_QUANT", ""):
        bench_quant(os.environ["SERVE_QUANT"])
    if os.environ.get("SERVE_KV_OFFLOAD", "") == "1":
        bench_kv_offload()
    if os.environ.get("SERVE_KV_OFFLOAD", "") == "7b":
        # the headline ZeRO-Inference capacity point: llama2-7b int8
        # weights + a KV footprint the chip cannot hold resident —
        # 6 streams x 2048 ctx = ~6 GB KV paging through a ~2 GB pool
        bench_kv_offload(name="llama2-7b-serve", batch=6,
                         prompt_len=1920, decode_tokens=64,
                         block_size=64, device_blocks=66,
                         quantize=True, splitfuse=256, max_batch=2)
    if os.environ.get("SERVE_SLA", "") == "1":
        sf = int(os.environ.get("SERVE_SLA_SPLITFUSE", "0"))
        bench_sla(splitfuse=sf)


if __name__ == "__main__":
    try:
        main()
    except BaseException as e:       # incl. KeyboardInterrupt/SystemExit
        write_local_report(error=f"{type(e).__name__}: {e}"[:300])
        raise
    else:
        write_local_report()
