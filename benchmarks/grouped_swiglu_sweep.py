#!/usr/bin/env python3
"""The expert SwiGLU chain alone: ``lax.ragged_dot`` x 3 against the
forward grouped kernel (``ops/pallas/grouped_matmul.py``) at the tiles its
shape rule picks and at their neighbours, at a model's published widths
(default OLMoE: E 64, D 2048, F 1024, top-8, bf16).

    python3 benchmarks/grouped_swiglu_sweep.py            # on the chip
    python3 benchmarks/grouped_swiglu_sweep.py --experts 8 --d-model 4096 \\
        --d-ff 14336 --top-k 2                            # Mixtral 8x7B

Shapes: a decode step's rows (slots x top-k; every slot is multiplied and
the dead slots' rows all sit on the same k experts, as the decode program
has them) at 32 / 12 / 4 live slots, and a prefill's rows (bucket x top-k,
routed uniformly). A line a variant: us a call, GB/s of the touched
experts' weights and their share of the HBM peak. What it decides is
``forward_tiles`` and ``FORWARD_ROWS_PER_GROUP`` (PERF.md, PR 32).
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

from deepspeed_tpu.ops.pallas import grouped_matmul as gm   # noqa: E402

HBM_GBS = 819.0                 # TPU v5e (perfbench/peaks.json)
CALLS = 8                       # chain calls a timed program


def group_sizes(rows, E, k, live_slots, seed):
    """Rows a group for ``rows // k`` tokens routed uniformly; with
    ``live_slots`` the tokens past them all pick one token's experts."""
    rs = np.random.RandomState(seed)
    picks = np.stack([rs.permutation(E)[:k] for _ in range(rows // k)])
    if live_slots is not None and live_slots < len(picks):
        picks[live_slots:] = picks[live_slots]
    return np.bincount(picks.reshape(-1), minlength=E).astype(np.int32)


def chain(fn):
    def run(x, w1, w3, w2, gs):
        for _ in range(CALLS):
            x = fn(x, w1, w3, w2, gs).astype(x.dtype)
        return x
    return jax.jit(run)


def variants(rows, D, F):
    """(name, chain function): the ragged products, the rule's tiles, and
    its neighbours in the row tile and the slice of F."""
    rule = gm.forward_tiles(rows, D, F, jnp.bfloat16)
    tms = (16, 32, 64, 128, 256) if rows <= 512 else (64, 128, 256)
    tiles = [rule] + [(tm, rule[1], D) for tm in tms if tm != rule[0]]
    tiles += [(rule[0], tf, D) for tf in (rule[1] // 2, rule[1] // 4)
              if tf >= 128 and F % tf == 0]

    def kern(t):
        return lambda *a: gm._swiglu_forward(*a, tiles=t, interpret=False)
    return [("ragged", gm._ragged_swiglu)] + [
        ("kernel tm%d tf%d" % t[:2] + (" (rule)" if t == rule else ""),
         kern(t)) for t in tiles]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=2048)
    ap.add_argument("--d-ff", type=int, default=1024)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--live", type=int, nargs="*", default=[32, 12, 4])
    ap.add_argument("--prefill-rows", type=int, nargs="*",
                    default=[2048, 4096, 8192, 16384, 32768])
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--out", default="chiprun_out/grouped_swiglu_sweep.jsonl")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("no TPU: a time comes only from the chip")
    E, D, F, k = a.experts, a.d_model, a.d_ff, a.top_k
    dt = jnp.bfloat16
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    sink = open(a.out, "a")

    def say(**kw):
        line = json.dumps(kw)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    ks = jax.random.split(jax.random.key(0), 3)
    w1 = jax.random.normal(ks[0], (E, D, F), dt) * D ** -0.5
    w3 = jax.random.normal(ks[1], (E, D, F), dt) * D ** -0.5
    w2 = jax.random.normal(ks[2], (E, F, D), dt) * F ** -0.5
    shapes = [("decode", a.slots * k, live) for live in a.live]
    shapes += [("prefill", r, None) for r in a.prefill_rows]
    for kind, rows, live in shapes:
        gs_np = group_sizes(rows, E, k, live, seed=rows + (live or 0))
        touched = int((gs_np > 0).sum())
        weight_bytes = touched * 3 * D * F * 2
        x = jax.random.normal(jax.random.key(rows), (rows, D), dt)
        gs = jnp.asarray(gs_np)
        ref = None
        for name, fn in variants(rows, D, F):
            prog = chain(fn)
            try:
                prog(x, w1, w3, w2, gs).block_until_ready()
            except Exception as e:  # noqa: BLE001 - a refusal is data
                say(kind=kind, rows=rows, live=live, variant=name,
                    refused=f"{type(e).__name__}: {str(e)[:300]}")
                continue
            one = jax.jit(fn)(x, w1, w3, w2, gs).astype(jnp.float32)
            ref = one if ref is None else ref
            err = float(jnp.max(jnp.abs(one - ref)) / (jnp.std(ref) + 1e-9))
            times = []
            for _ in range(a.reps):
                t0 = time.perf_counter()
                prog(x, w1, w3, w2, gs).block_until_ready()
                times.append((time.perf_counter() - t0) / CALLS)
            us = float(np.median(times)) * 1e6
            gbs = weight_bytes / (us * 1e-6) / 1e9
            say(kind=kind, rows=rows, live=live, touched=touched,
                variant=name, us_per_call=round(us, 1),
                us_min=round(min(times) * 1e6, 1),
                weight_gb_s=round(gbs, 1),
                hbm_share=round(100 * gbs / HBM_GBS, 1),
                max_err_over_std=round(err, 4))


if __name__ == "__main__":
    main()
