#!/usr/bin/env python3
"""A latent layer's selected read of a prompt chunk alone, the XLA read of
``models/paged.py:_latent_read`` against the Pallas kernel of
``ops/pallas/latent_attention.py``, at DeepSeek-V3.2-Exp's widths (128
heads of 128 + 64 over a 512-wide latent, ``index_topk`` 2,048) and cell
10's shapes: a 1,024-token chunk over a 264-entry table of 64-token blocks.

    python3 benchmarks/latent_read_sweep.py               # on the chip
    python3 benchmarks/latent_read_sweep.py --rehearse    # CPU, tiny

A layer's ``_attention`` is run up to the read (its real ``index_fn``,
``read_fn`` and queries), then ``_latent_read`` three ways over the same
pools: ``select`` (the index pass, the threshold search, the gather and the
mask, the read itself left out), ``xla`` and ``kernel`` — so ``xla -
select`` and ``kernel - select`` are the two reads alone. A line a
(variant, chunk start): us a layer (``CALLS`` layers a timed program) and
how far the kernel's output is from the XLA read's. ``--budgets`` sweeps the
kernel's VMEM budget, which decides the heads a grid step takes. PERF.md,
PR 44, reads them.
"""

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np              # noqa: E402

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from deepspeed_tpu.models import paged                          # noqa: E402
from deepspeed_tpu.models.deepseek_v32 import (                 # noqa: E402
    DEEPSEEK_V32, DEEPSEEK_V32_TINY, DeepseekV32)
from deepspeed_tpu.ops.pallas import latent_attention           # noqa: E402

CALLS = 5                       # a program's latent layers


def program(model, variant, C, BS, MB):
    """(layer params, x (1, C, D), lat pool, idx pool, table (MB,), start)
    -> CALLS layers' reads summed, each of a stream of its own."""
    cfg = model.config
    geom = dataclasses.replace(model.paged_geometry(),
                               kernel=variant != "xla")

    def run(p, x, lat_pool, idx_pool, table, start):
        cache = {"lat": [lat_pool], "idx": [idx_pool]}
        blocks = table[(start + jnp.arange(C)) // BS]
        step = paged.chunk_step(geom, cache, blocks, jnp.arange(C) % BS,
                                start, jnp.int32(C), table)
        read = step.latent(0)
        acc = jnp.zeros((1, C, cfg.d_model), jnp.float32)
        for i in range(CALLS):
            acc = acc + model._attention(x * (1.0 + 0.01 * i), p, read,
                                         step.q_pos)
        return acc

    return jax.jit(run)


def timed(prog, args, reps):
    times, out = [], None
    for i in range(reps + 1):
        t0 = time.perf_counter()
        out = jax.block_until_ready(prog(*args))
        if i:
            times.append((time.perf_counter() - t0) / CALLS)
    if not times:
        return 0.0, 0.0, out
    return float(np.median(times)), float(min(times)), out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--starts", default="0,4096,15360")
    ap.add_argument("--variants", default="select,xla,kernel")
    ap.add_argument("--budgets", default="",
                    help="VMEM budgets in MiB for further kernel variants")
    ap.add_argument("--out", default="chiprun_out/latent_read_sweep.jsonl")
    ap.add_argument("--rehearse", action="store_true",
                    help="the control flow on the CPU, the tiny model")
    a = ap.parse_args()
    global CALLS
    if a.rehearse:
        cfg, C, BS, MB, NB = DEEPSEEK_V32_TINY, 32, 8, 16, 20
        CALLS, a.reps, a.out, a.starts = 2, 1, os.devnull, "0,64"
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU: a time comes only from the chip")
    else:
        cfg = dataclasses.replace(DEEPSEEK_V32, n_layer=1, first_k_dense=1,
                                  vocab_size=256)
        C, BS, MB, NB = 1024, 64, 264, 4096
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    out = open(a.out, "a")

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        out.write(text + "\n")
        out.flush()

    model = DeepseekV32(cfg)
    ks = jax.random.split(jax.random.key(a.seed), 4)
    p = model.init(ks[0])["layers"][0]
    dt = jnp.dtype(cfg.dtype)
    x = jax.random.normal(ks[1], (1, C, cfg.d_model), jnp.float32)
    lat_pool = jax.random.normal(ks[2], (NB, BS, cfg.lat_row)).astype(dt)
    idx_pool = jax.random.normal(ks[3], (NB, BS, cfg.index_head_dim))
    table = jnp.asarray(np.random.RandomState(a.seed).permutation(
        NB - 1)[:MB] + 1, jnp.int32)
    real_read = paged.latent_chunk_attention

    def no_read(q, rows, sel, wk_b, wv_b, *rest, **kw):
        # everything the kernel is handed is made; nothing is read
        B, Cq, H, _ = q.shape
        return jnp.zeros((B, Cq, H, wv_b.shape[2]), jnp.float32) \
            + jnp.sum(sel) + jnp.sum(rows[:, :1, :1].astype(jnp.float32))

    @contextlib.contextmanager
    def reading(variant, budget):
        """``_latent_read``'s kernel as the variant has it: left out, or
        with a VMEM budget of its own (the jitted callee is traced anew)."""
        was = latent_attention._VMEM_BUDGET
        paged.latent_chunk_attention = no_read if variant == "select" \
            else real_read
        latent_attention._VMEM_BUDGET = budget or was
        latent_attention._latent_read_call.clear_cache()
        try:
            yield
        finally:
            paged.latent_chunk_attention = real_read
            latent_attention._VMEM_BUDGET = was
            latent_attention._latent_read_call.clear_cache()

    variants = [(v, None) for v in a.variants.split(",") if v] \
        + [("kernel", int(b) << 20) for b in a.budgets.split(",") if b]
    starts = [int(s) for s in a.starts.split(",")]
    want = {}
    for variant, budget in variants:
        with reading(variant, budget):
            prog = program(model, variant, C, BS, MB)   # compiled once
            TQ, Hg, vmem = latent_attention.read_tiles(
                C, cfg.n_head, cfg.qk_nope_head_dim,
                cfg.v_head_dim, cfg.kv_lora_rank, cfg.lat_row,
                min(512, MB * BS), dt) \
                if variant == "kernel" else (None, None, None)
            for start in starts:
                med, low, got = timed(
                    prog, (p, x, lat_pool, idx_pool, table,
                           jnp.int32(start)), a.reps)
                got = np.asarray(got)
                if variant == "xla":
                    want[start] = got
                say(what="latent_read", variant=variant, start=start,
                    frontier=start + C, C=C, heads_a_step=Hg,
                    query_tile=TQ,
                    vmem_mb=vmem and round(vmem / 2 ** 20, 1),
                    us_per_layer=round(med * 1e6, 1),
                    us_min=round(low * 1e6, 1),
                    max_rel_diff_vs_xla=float(
                        np.abs(got - want[start]).max()
                        / np.abs(want[start]).max())
                    if variant == "kernel" and start in want else None)


if __name__ == "__main__":
    main()
