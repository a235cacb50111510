#!/usr/bin/env python3
"""The paged decode kernel alone (``ops/pallas/paged_attention.py``), at the
block shapes the served families have, by the table entries of a slot a
grid step takes.

    python3 benchmarks/paged_decode_sweep.py              # on the chip

Shapes (slots, KV heads, query heads a KV head, head dim, table entries):
phi-4-mini-flash's ten 128-lane head pairs at G = 4, olmoe-1b-7b's sixteen
heads of 128, gpt2-medium's sixteen and opt-1.3b's thirty-two heads of 64
(rows padded to the 128 lanes in the pools). Every slot live with 2 / 9 /
14 / 40 attended entries; N = 1, 2, 4, 8 entries a step, and N = 1 with
the kernel's two products taken out (what is left is the step, its DMA and
the softmax). A line a variant: us a call, us a block, and the share of
the HBM peak by the bytes the attended blocks hold in the pools. What it
decides is ``_STEP_BYTES`` and ``_STEP_VMEM`` of ``decode_entries_per_step``
(PERF.md, PR 39).
"""

import argparse
import contextlib
import json
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np              # noqa: E402

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from deepspeed_tpu.ops.pallas import paged_attention as pa   # noqa: E402

HBM_GBS = 819.0                 # TPU v5e (perfbench/peaks.json)
CALLS = 16                      # kernel calls a timed program: a model's
#                                 layers, one work list between them
BS = 64
SHAPES = {                      # B, KVH, G, d, MB
    "phi4-mini-flash": (64, 10, 4, 128, 64),
    "olmoe-1b-7b": (32, 16, 1, 128, 64),
    "gpt2-medium": (32, 16, 1, 64, 64),
    "opt-1.3b": (16, 32, 1, 64, 64),
}
ENTRIES = (2, 9, 14, 40)
PER_STEP = (1, 2, 4, 8)


@contextlib.contextmanager
def no_products():
    """``lax.dot_general`` as a row sum of its left operand, broadcast to
    the product's shape: no MXU work and no load of K or V, while the
    copies that bring them stay."""
    real = jax.lax.dot_general

    def fake(lhs, rhs, dims, **kw):
        out = jax.eval_shape(lambda a, b: real(a, b, dims, **kw), lhs, rhs)
        return jnp.broadcast_to(
            jnp.sum(lhs.astype(out.dtype), axis=-1, keepdims=True),
            out.shape)

    with mock.patch.object(jax.lax, "dot_general", fake):
        yield


def chain(MB, per_step):
    def run(q, kc, vc, tables, lengths):
        kc, vc = pa.as_pools((kc, vc))
        work = pa.decode_work_list(lengths, MB, BS, per_step=per_step)
        for _ in range(CALLS):
            q = pa.paged_decode_attention(q, kc, vc, tables, lengths,
                                          work=work)
        return q
    return jax.jit(run)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=sorted(SHAPES))
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/paged_decode_sweep.jsonl")
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

    rs = np.random.RandomState(a.seed)
    for name in a.shapes:
        B, KVH, G, d, MB = SHAPES[name]
        # the pools as the engine hands them to a program: the block axis
        # in the factors that keep head dim 64 row-major (pool_block_dims)
        dims = pa.pool_block_dims(1 + B * max(ENTRIES), d, True)
        keys = jax.random.split(jax.random.key(a.seed), 3)
        q = jax.random.normal(keys[0], (B, KVH * G, d), dt)
        kc, vc = (jax.random.normal(k, dims + (KVH, BS, d), dt)
                  for k in keys[1:])
        # a block's K and V as the pools hold them: rows of 128 lanes
        block_bytes = 2 * KVH * BS * max(d, 128) * 2
        rule = pa.decode_entries_per_step(KVH, BS, d, dt, MB)
        for entries in ENTRIES:
            tables = np.zeros((B, MB), np.int32)
            tables[:, :entries] = 1 + rs.permutation(B * entries).reshape(
                B, entries)
            lengths = (entries - 1) * BS + rs.randint(0, BS, B)
            args = (q, kc, vc, jnp.asarray(tables),
                    jnp.asarray(lengths, jnp.int32))
            ref = None
            for per_step, products in [(n, True) for n in PER_STEP] \
                    + [(1, False)]:
                variant = f"N={per_step}" + ("" if products
                                             else " no products")
                pa._decode_call.clear_cache()
                prog = chain(MB, per_step)
                try:
                    with contextlib.nullcontext() if products \
                            else no_products():
                        got = prog(*args).block_until_ready()
                except Exception as e:  # noqa: BLE001 - a refusal is data
                    say(shape=name, entries=entries, variant=variant,
                        refused=f"{type(e).__name__}: "
                                + " ".join(str(e).split())[:200])
                    continue
                got = np.asarray(got, np.float32)
                ref = got if ref is None else ref
                times = []
                for _ in range(a.reps):
                    t0 = time.perf_counter()
                    prog(*args).block_until_ready()
                    times.append((time.perf_counter() - t0) / CALLS)
                us = float(np.median(times)) * 1e6
                blocks = B * entries
                say(shape=name, B=B, KVH=KVH, G=G, d=d, entries=entries,
                    variant=variant, rule_N=rule,
                    us_per_call=round(us, 2),
                    us_min=round(min(times) * 1e6, 2),
                    us_per_block=round(us / blocks, 4),
                    block_mb=round(block_bytes / 1e6, 3),
                    hbm_share=round(blocks * block_bytes / (us * 1e-6)
                                    / 1e9 / HBM_GBS, 4),
                    max_abs_diff_vs_first=(
                        round(float(np.abs(got - ref).max()), 5)
                        if products else None))
    pa._decode_call.clear_cache()


if __name__ == "__main__":
    main()
