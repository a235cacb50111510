#!/usr/bin/env python3
"""The paged chunk kernel alone (``ops/pallas/paged_attention.py``), at the
chunk shapes the serving cells have, by the tile a grid step takes.

    python3 benchmarks/paged_chunk_sweep.py               # on the chip

Shapes (chunk tokens, query heads, KV heads, head dim, table entries, pool
blocks): cell 12's GQA layer (solar-open2: 64 / 8 heads of 128 behind a
528-entry table), cell 9's thirty MHA heads of 128 (olmo-hybrid), cells 5
and 8's sixteen (olmoe), cell 4's thirty-two heads of 64 at its pinned
64-token tile (opt-1.3b) and cells 3 and 6's sixteen (gpt2-medium). A full
chunk ending at each context; a tile is ``block_c,heads,entries``
(:class:`paged_attention.ChunkTile`), ``rule`` what :func:`chunk_tile`
gives the shape. A line a variant: ms a call, the grid steps it took, and
the share of the bf16 peak by the causal products' operations. What it
decides is ``_CHUNK_TILE_BYTES`` and ``_CHUNK_SCORE_BYTES`` (PERF.md, PR 58).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np              # noqa: E402

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from deepspeed_tpu.ops.pallas import paged_attention as pa   # noqa: E402

PEAK_TFS = 197.0                # TPU v5e, bf16 (perfbench/peaks.json)
CALLS = 4                       # kernel calls a timed program: layers that
#                                 share one work list
BS = 64
SHAPES = {                      # C, H, KVH, d, MB, NB, block_c, contexts
    "solar-open2": (1024, 64, 8, 128, 528, 8192, 0,
                    (1024, 4096, 8192, 16384, 32768)),
    "olmo-hybrid": (1024, 30, 30, 128, 136, 1024, 0, (1024, 4096, 8192)),
    "olmoe": (1024, 16, 16, 128, 64, 512, 0, (192, 1024, 4096)),
    "opt-1.3b": (256, 32, 32, 64, 32, 128, 64, (256, 1024, 2048)),
    "gpt2-medium": (128, 16, 16, 64, 16, 320, 0, (128, 1024)),
}
TILES = {
    "solar-open2": ("128,1,8", "128,1,4", "128,2,4", "64,2,8", "64,1,8",
                    "32,8,4", "16,8,1"),
    "olmo-hybrid": ("1024,1,8", "512,2,8", "512,1,8", "256,3,8", "256,5,4",
                    "128,6,8", "64,30,1"),
    "olmoe": ("1024,1,8", "512,2,8", "256,4,8", "128,8,8", "128,16,1"),
    "opt-1.3b": ("64,32,1", "64,16,1", "64,8,1", "64,32,2", "64,16,4",
                 "256,4,1", "128,8,2"),
    "gpt2-medium": ("128,16,1", "128,8,1", "128,16,2"),
}


def chain(C, MB, window, tile):
    def run(q, kc, vc, table, start, true_len):
        work = pa.chunk_work_list(start, true_len, C, MB, BS, window, tile)
        for _ in range(CALLS):
            q = pa.paged_chunk_attention(q, kc, vc, table, start, true_len,
                                         window=window, work=work)
        return q
    return jax.jit(run)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES))
    ap.add_argument("--tiles", nargs="*", default=None,
                    help="block_c,heads,entries; default: the shape's list")
    ap.add_argument("--contexts", nargs="*", type=int, default=None,
                    help="default: the shape's list")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/paged_chunk_sweep.jsonl")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("no TPU: a time comes only from the chip")
    dt = jnp.bfloat16
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    out = open(a.out, "a")

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        out.write(text + "\n")
        out.flush()

    for name in a.shapes:
        C, H, KVH, d, MB, NB, block_c, contexts = SHAPES[name]
        G = H // KVH
        keys = jax.random.split(jax.random.key(a.seed), 3)
        q = jax.random.normal(keys[0], (C, H, d), dt)
        kc, vc = (jax.random.normal(k, (NB, KVH, BS, d), dt)
                  for k in keys[1:])
        table = jnp.arange(1, MB + 1, dtype=jnp.int32) % NB
        rule = pa.chunk_tile(C, KVH, G, d, BS, MB, dt, block_c)
        tiles = [("rule", rule)] + [
            (t, pa.ChunkTile(*map(int, t.split(","))))
            for t in (a.tiles or TILES[name])]
        for ctx in a.contexts or contexts:
            start, n = jnp.int32(ctx - C), jnp.int32(C)
            # the causal products: a query at position p multiplies p + 1
            # keys, twice (scores and values)
            flops = 4 * H * d * sum(range(ctx - C + 1, ctx + 1))
            ref = None
            for label, tile in tiles:
                prog = chain(C, MB, 0, tile)
                try:
                    got = prog(q, kc, vc, table, start, n)
                    got.block_until_ready()
                except Exception as e:  # noqa: BLE001 - a refusal is data
                    say(shape=name, context=ctx, tile=label,
                        refused=f"{type(e).__name__}: "
                                + " ".join(str(e).split())[:200])
                    continue
                got = np.asarray(got, np.float32)
                ref = got if ref is None else ref
                times = []
                for _ in range(a.reps):
                    t0 = time.perf_counter()
                    prog(q, kc, vc, table, start, n).block_until_ready()
                    times.append((time.perf_counter() - t0) / CALLS)
                ms = float(np.median(times)) * 1e3
                say(shape=name, context=ctx, tile=label,
                    block_c=tile.block_c, heads=tile.heads,
                    entries=tile.entries, ms_per_call=round(ms, 3),
                    ms_min=round(min(times) * 1e3, 3),
                    grid_steps=pa.chunk_grid_steps(
                        ctx - C, C, C, KVH, MB, BS, 0, tile),
                    steps_before=-(-C // tile.block_c) * MB,
                    peak_share=round(flops / (ms * 1e-3) / 1e12 / PEAK_TFS,
                                     4),
                    max_abs_diff_vs_first=round(
                        float(np.abs(got - ref).max()), 5))


if __name__ == "__main__":
    main()
