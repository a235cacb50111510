#!/usr/bin/env python3
"""OLMoE on the chip against its plain reference, logit by logit, at the
configuration's published widths. Not run by the driver and outside every
timed window; run once by the builder of a PR that touches the model (PR 26:
PERF.md section 4), on the chip:

    python3 perfbench/parity_olmoe.py [--seed n] [--prompts 300,700]

The served model (``inference/utils.shard_params``: seeded weights made
unstacked, bf16, float32 router) runs the programs the engine runs —
``apply_paged_prefill`` on a prompt padded to its bucket, then 32
``apply_paged_decode`` steps through the paged cache, both prompts in one
decode batch — and every one of the 33 rows of logits per prompt is compared
with the reference's row at the same position (``references/olmoe.py``,
float32, precision highest): the largest absolute difference over the
reference row's standard deviation.

``TOL`` is set from two readings (PERF.md section 4 has both): the largest
the system gives over its seeds, and what the reference itself gives when
its weights are rounded to float8 (e5m2, which needs no scale at these
magnitudes), the nearest precision below the bf16 the configuration states,
which has to come out over it. The reference
with renormalised routing weights and the reference without QK-norm are
printed too: both must be over TOL, or the comparison cannot tell the
model from its neighbours. Exits 1 when any of this fails.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from pbench import common      # noqa: E402

# in standard deviations of a position's reference logits; see the docstring
TOL = 0.12
DECODE = 32


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-olmoe-chat")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompts", default="300,700")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    _, cell, cfg, job = common.load_cell(args.workload, args.rehearse)
    _, device = common.device_info(cell["chips"], args.rehearse)
    from deepspeed_tpu.inference.utils import shard_params
    from deepspeed_tpu.utils import groups
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from deepspeed_tpu.utils.groups import TopologyConfig
    enable_compile_cache()
    builder = common.load_module("builders", cfg["builder"])
    reference = common.load_module("references", cfg["reference"])
    model, s = builder.model(cfg), builder.sizes(cfg)
    topo = groups.initialize(TopologyConfig())
    params, _ = shard_params(model, topo.mesh, jnp.bfloat16, seed=args.seed,
                             topology=topo)

    lens = [int(x) for x in args.prompts.split(",")]
    if args.rehearse:
        lens = [min(n, 60) for n in lens]
    bucket = job["engine"]["prompt_bucket"]
    BS = 64 if not args.rehearse else 8
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, s["vocab_size"], n, dtype=np.int32)
               for n in lens]
    per_seq = -(-(max(lens) + DECODE + 1) // BS)
    cache = model.init_paged_cache(1 + len(lens) * per_seq, BS,
                                   dtype=jnp.bfloat16)
    tables = np.stack([1 + i * per_seq + np.arange(per_seq, dtype=np.int32)
                       for i in range(len(lens))])
    prefill = jax.jit(model.apply_paged_prefill, donate_argnums=(2,))
    decode = jax.jit(model.apply_paged_decode, donate_argnums=(3,))

    rows = [[] for _ in lens]           # system logits, 33 rows a prompt
    for i, p in enumerate(prompts):
        T = -(-len(p) // bucket) * bucket
        pos = np.arange(T)
        live = pos < len(p)
        ids = np.zeros((1, T), np.int32)
        ids[0, :len(p)] = p
        tb = np.where(live, tables[i][np.minimum(pos // BS, per_seq - 1)], 0)
        to = np.where(live, pos % BS, 0)
        logits, cache = prefill(params, ids, cache, tb.astype(np.int32),
                                to.astype(np.int32), np.int32(len(p)))
        rows[i].append(np.asarray(logits[0], np.float32))
    seqs = [list(p) for p in prompts]
    for _ in range(DECODE):
        toks = np.asarray([int(np.argmax(r[-1])) for r in rows], np.int32)
        lengths = np.asarray([len(q) for q in seqs], np.int32)
        for q, t in zip(seqs, toks):
            q.append(int(t))
        logits, cache = decode(params, toks, lengths, cache, tables)
        for i in range(len(lens)):
            rows[i].append(np.asarray(logits[i], np.float32))

    kw = dict(n_head=s["n_head"], activation=s["activation"])

    def ref_rows(seq, first, **variant):
        T = -(-len(seq) // bucket) * bucket
        ids = np.zeros((1, T), np.int32)
        ids[0, :len(seq)] = seq
        pos = first - 1 + np.arange(DECODE + 1)
        fn = jax.jit(lambda p, ids, pos: reference.logits_at(
            p, reference.hidden_states(p, ids, **kw, **variant)[0][pos]))
        return np.asarray(fn(params, ids, pos.astype(np.int32)))

    def worst(got, want):
        return float(np.max(np.abs(got - want).max(axis=1)
                            / want.std(axis=1)))

    out = {"device": device, "seed": args.seed, "prompts": lens,
           "decode_steps": DECODE, "tol": TOL, "per_prompt": []}
    f32 = reference._f32
    for i, p in enumerate(prompts):
        seq = np.asarray(seqs[i], np.int32)  # every token was an input
        got = np.stack(rows[i])
        want = ref_rows(seq, len(p))
        line = {"system_vs_reference": worst(got, want),
                "reference_argmax_share": float(np.mean(
                    got.argmax(axis=1) == want.argmax(axis=1)))}
        # the reference's neighbours, each against the reference itself
        reference._f32 = lambda x: f32(x.astype(jnp.float8_e5m2)) \
            if x.ndim >= 2 else f32(x)
        line["reference_fp8_weights"] = worst(ref_rows(seq, len(p)), want)
        reference._f32 = f32
        line["reference_renormalised"] = worst(
            ref_rows(seq, len(p), renormalize=True), want)
        line["reference_without_qk_norm"] = worst(
            ref_rows(seq, len(p), qk_norm=False), want)
        out["per_prompt"].append(line)
        common.say("parity", prompt=len(p), **line)
    ok = all(l["system_vs_reference"] <= TOL
             and min(l["reference_fp8_weights"], l["reference_renormalised"],
                     l["reference_without_qk_norm"]) > TOL
             for l in out["per_prompt"])
    out["ok"] = ok
    if args.rehearse:
        # a CPU rehearsal proves the control flow; its numbers are bf16 on
        # another backend at another size and decide nothing
        print(json.dumps({"rehearsal": True, "ran": True}))
        return 0
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
