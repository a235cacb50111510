#!/usr/bin/env python3
"""Chip smoke: does the system still start, and compute right, on the TPU?

    python3 chip_smoke.py              one chip: device, train, serve, kernels
    python3 chip_smoke.py --chips 4    four chips: ZeRO-2/3 at dp=4 vs dp=1

One process, no children, no network; it reads no BENCH_*/SERVE_*/
MULTICHIP_* variable — every size below is a literal. Any failed check
raises, so the exit code is non-zero and the result line is never printed.
Without a TPU it exits 1 before any phase: there is no CPU run under this
script's name.

``--rehearse`` is the one explicit exception, for the sandbox: the same
control flow at the tiny preset on whatever backend JAX has (CPU, Pallas in
interpret mode), device-only checks skipped and said so. Its last line
names the platform truthfully and carries no ``"ok"`` key.

The last line of a passing chip run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Step and compile seconds printed above it are a smoke reading, not a
benchmark.
"""

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# ---------------------------------------------------------------- sizes
# GPT-2 350M at its published widths and full depth, with the kernel set
# the headline training cell has always used: Pallas flash attention over
# the whole sequence per grid step, save_flash remat, grad-in-forward fused
# cross entropy over the Pallas unembed kernel.
FULL = dict(
    model=dict(n_layer=24, n_head=16, d_model=1024, max_seq_len=1024,
               vocab_size=50304, dtype="bfloat16"),
    flash_block=1024, loss_chunk=512, micro=8, train_steps=6,
    prompt_lens=(300, 990), new_tokens=32, splitfuse=256,
    multi_steps=3)
# --rehearse only: control flow, not numbers
TINY = dict(
    model=dict(n_layer=1, n_head=4, d_model=128, max_seq_len=128,
               vocab_size=1024, dtype="bfloat16"),
    flash_block=128, loss_chunk=64, micro=2, train_steps=6,
    prompt_lens=(20, 90), new_tokens=8, splitfuse=32,
    multi_steps=3)

SEED = 0
VOCAB_REAL = 50257
# The kernel path (flash, fused CE) and the plain path (dense attention,
# unfused CE) round bf16 in different places; measured on the v5e they part
# by 1e-4 at a loss of 11.0, and dp=4 parts from dp=1 by the same order. A
# wrong mask or scale moves the loss by tenths. Between: 0.02.
LOSS_TOL = 0.02
# An emitted token's float32 dense logit may sit below that position's
# dense maximum by at most this many standard deviations of the position's
# logits. Random-init logits are near-flat, so bf16 serving legitimately
# picks a near-tie now and then (measured worst: 0.03 std, 98-99% of tokens
# the dense argmax itself); a wrong cache row or mask picks a token several
# std down.
SERVE_GAP_TOL = 0.1
PARITY_GATES = ("flash", "flash_qkv_t", "fused_ce", "paged", "paged_chunk")


def say(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require(ok, what):
    """A failed check ends the run: no phase carries on past one."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class CompileMeter:
    """Seconds spent in XLA compile-or-fetch and persistent-cache hits,
    read per phase with :meth:`take`."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.hits = 0
        self.requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def take(self):
        out = {"compile_s": round(self.secs, 2), "cache_hits": self.hits,
               "cache_requests": self.requests}
        self.secs, self.hits, self.requests = 0.0, 0, 0
        return out


def hbm(devices):
    """Per-device (peak, now) bytes; None where the backend reports none."""
    out = []
    for d in devices:
        s = d.memory_stats() or {}
        out.append({"peak_bytes": s.get("peak_bytes_in_use"),
                    "bytes_in_use": s.get("bytes_in_use")})
    return out


def cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# --------------------------------------------------------------- device
def phase_device(devices, rehearse):
    from deepspeed_tpu.accelerator import get_accelerator
    from deepspeed_tpu.models.common import resolve_flash
    from deepspeed_tpu.monitor.telemetry import peak_flops_per_chip
    from deepspeed_tpu.ops.pallas._common import interpret_default
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_chunk_tune_defaults)

    acc = get_accelerator()
    found = {
        "accelerator": type(acc).__name__,
        "interpret_default": interpret_default(),
        "flash_auto": resolve_flash("auto"),
        "paged_chunk_mode": paged_chunk_tune_defaults()["mode"],
        "peak_flops": peak_flops_per_chip(devices[0].device_kind),
    }
    say("device", **found)
    if rehearse:
        return
    require(acc.device_name() == "tpu" and acc.is_available()
            and found["interpret_default"] is False
            and found["flash_auto"] is True
            and found["paged_chunk_mode"] == "kernel"
            and found["peak_flops"] is not None, found)


# ---------------------------------------------------------------- train
def train_model_config(size):
    from deepspeed_tpu.models import GPT2Config
    return GPT2Config(
        **size["model"], use_flash_attention=True,
        flash_block_q=size["flash_block"], flash_block_k=size["flash_block"],
        flash_block_h=1, flash_block_q_bwd=0, flash_block_k_bwd=0,
        flash_qkv_t=True, flash_bwd_qmajor=False, remat=True,
        remat_policy="save_flash", scan_unroll=1, fused_layernorm=False,
        mlp_kernel=False, loss_chunk=size["loss_chunk"], fused_loss=True,
        fused_loss_kernel=True, attention_backend="dense")


def build_trainer(size, stage, gas, devices=None):
    """``deepspeed_tpu.initialize`` on the model above: the default topology
    (every visible device) unless ``devices`` names the dp group."""
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2
    from deepspeed_tpu.utils import groups
    from deepspeed_tpu.utils.groups import TopologyConfig

    groups.reset()
    topology = None if devices is None else groups.initialize(
        TopologyConfig(data_parallel_size=len(devices)), devices=devices,
        force=True)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2(train_model_config(size)), topology=topology, seed=SEED,
        config={
            "train_micro_batch_size_per_gpu": size["micro"],
            "gradient_accumulation_steps": gas,
            "steps_per_print": 0,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 2e-4, "weight_decay": 0.01}},
            "gradient_clipping": 1.0,
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": stage},
        })
    return engine


def seeded_batch(size, rows):
    rng = np.random.RandomState(SEED)
    vocab = min(VOCAB_REAL, size["model"]["vocab_size"])
    return {"input_ids": rng.randint(
        0, vocab, (rows, size["model"]["max_seq_len"])).astype(np.int32)}


def compiled_step_hlo(engine, batch):
    """HLO text of the train-step program the engine runs on ``batch``."""
    import jax
    batch = jax.tree.map(engine._add_gas_dim, batch)
    batch = engine._shard_batch(batch, with_gas_dim=True)
    with jax.set_mesh(engine.mesh):
        return engine._train_step_jit.lower(
            engine.state, batch, engine._current_lr(), None
        ).compile().as_text()


def run_steps(engine, batch, steps):
    """``steps`` optimizer steps, each waited for. -> (losses, seconds)."""
    import jax
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(engine.train_batch(batch))
        secs.append(round(time.perf_counter() - t0, 3))
        losses.append(float(loss))
    require(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    return losses, secs


def phase_train(size, devices, meter, rehearse):
    import jax
    from deepspeed_tpu.models import GPT2
    from deepspeed_tpu.utils import groups

    engine = build_trainer(size, stage=2, gas=1)
    cfg = engine.model.config
    batch = seeded_batch(size, engine.config.train_batch_size)

    # the same parameters and batch through the plain path: dense
    # attention, unfused unchunked cross entropy — pure jax.numpy
    plain = GPT2(dataclasses.replace(
        cfg, use_flash_attention=False, fused_loss=False,
        fused_loss_kernel=False, loss_chunk=0, remat=False))
    plain_loss = float(jax.jit(
        lambda p, b: plain.loss(p, b, train=False))(
            engine.state["params"], batch))

    hlo = compiled_step_hlo(engine, batch)
    kernel_calls = hlo.count("tpu_custom_call")
    losses, secs = run_steps(engine, batch, size["train_steps"])
    gap = abs(losses[0] - plain_loss)
    say("train", zero_stage=2, micro_batch=size["micro"],
        seq_len=cfg.max_seq_len, params=cfg.num_params(), losses=losses,
        plain_first_loss=plain_loss, kernel_vs_plain_gap=gap,
        gap_tolerance=LOSS_TOL, tpu_custom_calls=kernel_calls,
        step_wall_s_smoke_not_a_benchmark=secs, **meter.take())
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    require(gap <= LOSS_TOL, f"kernel path {losses[0]} vs plain "
                             f"{plain_loss}")
    require(rehearse or kernel_calls > 0,
            "train step HLO holds no tpu_custom_call")

    del engine, hlo
    groups.reset()
    gc.collect()
    say("train_hbm", devices=hbm(devices))


# ---------------------------------------------------------------- serve
def seeded_prompts(size):
    rng = np.random.RandomState(SEED + 1)
    lo, hi = size["prompt_lens"]
    vocab = min(VOCAB_REAL, size["model"]["vocab_size"])
    return [rng.randint(0, vocab, (rng.randint(lo, hi + 1),))
            .astype(np.int32) for _ in range(4)]


def serve_once(model, prompts, new_tokens, **engine_config):
    """The verify-skill recipe: engine -> Replica -> Router, put/step
    until drained, get. -> (outputs, engine params, seconds by part)."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2, Router,
                                            RouterConfig)
    from deepspeed_tpu.inference.v2.replica import Replica
    from deepspeed_tpu.utils import groups

    groups.reset()
    t0 = time.perf_counter()
    engine = InferenceEngineV2(model, dict(
        dtype="bfloat16", seed=SEED, **engine_config))
    router = Router([Replica("r0", engine)], RouterConfig())
    t1 = time.perf_counter()
    uids = [router.put(p, max_new_tokens=new_tokens) for p in prompts]
    while router.has_work:
        router.step()
    outs = [np.asarray(router.get(u)) for u in uids]
    t2 = time.perf_counter()
    require([len(o) for o in outs] == [new_tokens] * len(prompts),
            f"token counts {[len(o) for o in outs]}")
    return outs, engine.params, {"build_s": round(t1 - t0, 2),
                                 "requests_s": round(t2 - t1, 2)}


def dense_checker(cfg):
    """-> check(params, prompts, outs): teacher-force prompt+output through
    the dense float32 model; for each emitted token, (dense max logit - its
    dense logit) / std of that position's logits, one array per request.
    One compiled program serves every request of every config."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import GPT2

    dense = GPT2(dataclasses.replace(
        cfg, dtype="float32", use_flash_attention=False, remat=False))
    T = cfg.max_seq_len

    @jax.jit
    def gaps(params, ids, pos, toks):
        rows = dense.apply(params, ids)[0][pos]            # (n_new, V)
        got = jnp.take_along_axis(rows, toks[:, None], axis=1)[:, 0]
        return (rows.max(axis=1) - got) / rows.std(axis=1)

    def check(params, prompts, outs):
        params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        out = []
        for prompt, toks in zip(prompts, outs):
            seq = np.concatenate([prompt, toks])[:-1]
            ids = np.zeros((1, T), np.int32)  # causal: the padding after
            ids[0, :len(seq)] = seq           # the sequence is never seen
            pos = len(prompt) - 1 + np.arange(len(toks))
            out.append(np.asarray(gaps(params, ids, pos.astype(np.int32),
                                       toks.astype(np.int32))))
        return out

    return check


def phase_serve(size, devices, meter):
    from deepspeed_tpu.models import GPT2, GPT2Config

    cfg = GPT2Config(**size["model"])
    model = GPT2(cfg)
    prompts = seeded_prompts(size)
    n_new = size["new_tokens"]
    dense_gaps = dense_checker(cfg)
    runs = {}
    for name, extra in (("default", {}),
                        ("splitfuse", {"splitfuse_tokens":
                                       size["splitfuse"]})):
        outs, params, wall = serve_once(model, prompts, n_new, **extra)
        t0 = time.perf_counter()
        gaps = dense_gaps(params, prompts, outs)
        wall["dense_check_s"] = round(time.perf_counter() - t0, 2)
        worst = max(float(g.max()) for g in gaps)
        runs[name] = outs
        say("serve", config=name, engine_config=extra,
            prompt_lens=[len(p) for p in prompts],
            tokens=[o.tolist() for o in outs],
            dense_argmax_share=float(np.mean(
                [np.mean(g == 0.0) for g in gaps])),
            worst_gap_in_std=worst, gap_tolerance_in_std=SERVE_GAP_TOL,
            wall_s_smoke_not_a_benchmark=wall, **meter.take())
        require(worst <= SERVE_GAP_TOL,
                f"{name}: emitted token {worst} std below dense max")
        del params
        gc.collect()

    # the two configs agree under the same rule: where their streams part,
    # both continuations were within tolerance of the dense maximum for the
    # shared prefix (checked above) — report how far they ran together
    together = [int(np.argmax(np.append(a != b, True)))
                for a, b in zip(runs["default"], runs["splitfuse"])]
    say("serve_agreement", identical_prefix_tokens=together, of=n_new)
    say("serve_hbm", devices=hbm(devices))


# -------------------------------------------------------------- kernels
def phase_kernels(meter, rehearse):
    if rehearse:
        say("kernels", skipped="parity gates compile for Mosaic only")
        return
    sys.path.insert(0, os.path.join(HERE, "benchmarks"))
    import kernel_parity
    res = kernel_parity.run(seed=SEED, only=PARITY_GATES)
    say("kernels", parity=res, **meter.take())
    require(res == dict.fromkeys(PARITY_GATES, "ok"), res)


# ----------------------------------------------------------- four chips
def held_share(tree, devices):
    """The largest share of ``tree``'s bytes that any one of ``devices``
    holds: 1/n when ZeRO partitions it, 1.0 when it is replicated."""
    import jax
    held = dict.fromkeys(devices, 0)
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.nbytes
        for s in leaf.addressable_shards:
            held[s.device] += s.data.nbytes
    return round(max(held.values()) / total, 3)


def phase_multichip(size, devices, meter, rehearse):
    from deepspeed_tpu.utils import groups

    n = len(devices)
    batch = seeded_batch(size, size["micro"] * n)
    steps = size["multi_steps"]

    def run(stage, devs, gas):
        engine = build_trainer(size, stage, gas, devices=devs)
        require(engine.config.train_batch_size == size["micro"] * n,
                "global batch differs between dp=1 and dp=n")
        hlo = compiled_step_hlo(engine, batch)
        losses, secs = run_steps(engine, batch, steps)
        state = engine.state
        info = {
            "zero_stage": stage, "dp": len(devs), "losses": losses,
            # the TPU compiler spells a reduce-scatter as a fusion that
            # calls an %all-reduce-scatter computation; count by substring
            "hlo_mentions": {k: hlo.count(k) for k in (
                "all-gather", "reduce-scatter", "tpu_custom_call")},
            "held_share": {k: held_share(state[k], devs)
                           for k in ("opt", "master", "params")},
            "hbm": hbm(devices),
            "step_wall_s_smoke_not_a_benchmark": secs, **meter.take()}
        del engine, state, hlo
        groups.reset()
        gc.collect()
        return info

    # the comparison: the same global batch on ONE of the devices, as
    # micro x n accumulation steps (ZeRO stage is moot at dp=1)
    ref = run(2, devices[:1], n)
    say("multichip_reference", **ref)
    for stage in (2, 3):
        got = run(stage, devices, 1)
        gaps = [abs(a - b) for a, b in zip(got["losses"], ref["losses"])]
        say("multichip", max_loss_gap=max(gaps), gap_tolerance=LOSS_TOL,
            **got)
        require(max(gaps) <= LOSS_TOL,
                f"dp={n} {got['losses']} vs dp=1 {ref['losses']}")
        # a few bias leaves stay whole, so a partitioned tree holds a
        # little over 1/n per device
        share = got["held_share"]
        split = 1.2 / n
        require(share["opt"] <= split and share["master"] <= split
                and (share["params"] <= split if stage == 3
                     else share["params"] == 1.0),
                f"stage {stage} state not placed as the stage implies: "
                f"{share}")
        # (XLA:CPU has no reduce-scatter: it emits all-reduce + all-to-all)
        seen = got["hlo_mentions"]
        require(rehearse or all(seen.values()),
                f"stage {stage} step HLO lacks a collective or kernel the "
                f"stage implies: {seen}")
        used = [h["bytes_in_use"] for h in got["hbm"]]
        require(rehearse or (min(used) > 0 and min(used) >= 0.5 * max(used)),
                f"bytes in use per device {used}")


# ----------------------------------------------------------------- main
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on the current backend; prints no "
                         "\"ok\"")
    args = ap.parse_args()

    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}; not running "
              f"on it (see --rehearse)", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    cache_dir = enable_compile_cache()
    entries_before = cache_entries(cache_dir)
    meter = CompileMeter()
    size = TINY if args.rehearse else FULL
    say("start", device=device, jax=jax.__version__, cache_dir=cache_dir,
        cache_entries=entries_before,
        cache="warm" if entries_before else "cold")
    t0 = time.perf_counter()

    if args.chips == 4:
        phase_multichip(size, devices, meter, args.rehearse)
    else:
        phase_device(devices, args.rehearse)
        phase_train(size, devices, meter, args.rehearse)
        phase_serve(size, devices, meter)
        phase_kernels(meter, args.rehearse)

    say("done", wall_s=round(time.perf_counter() - t0, 1),
        cache_dir=cache_dir, cache_entries_before=entries_before,
        cache_entries_after=cache_entries(cache_dir),
        memory_stats=devices[0].memory_stats())
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
