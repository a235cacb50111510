#!/usr/bin/env python3
"""Phi-4-mini-flash-reasoning on the chip against its plain reference, logit
by logit, at the configuration's published widths and full depth. Not run by
the driver and outside every timed window; run once by the builder of a PR
that touches the model (PR 30: PERF.md section 4), on the chip:

    python3 perfbench/parity_phi4flash.py [--seeds 1] [--prompts 300,420,512]

The prompts go through ``InferenceEngineV2`` as the cell's requests do
(bucketed prefill into a slot, then 8-step decode dispatches, all prompts in
one batch), with a tap on the logits every token is sampled from
(``pbench/tap.py``): ``DECODE`` tokens a prompt, so positions pass the
512-token window and the window layers' rings wrap. Every row is compared
with the reference's row at the same position (``references/phi4flash.py``,
float32, precision highest): the largest absolute difference over the
reference row's standard deviation.

``TOL`` is set from two kinds of reading (PERF.md section 4 has them): the
largest the system gives over its seeds, and what the reference's nearest
neighbours give against the reference itself, each of which has to come
out over it or the comparison cannot tell the model from them: weights
rounded to float8 (e5m2), the nearest precision below the bfloat16 the
configuration states; lambda = lambda_init only (no learned term); a window
of 1,024; the memory taken after the gate; the recurrent state kept in
bfloat16. Exits 1 when the system is over ``TOL`` or a neighbour in
``MUST_DIFFER`` is under it.
"""

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from pbench import common, tap     # noqa: E402

# in standard deviations of a position's reference logits; see the docstring
TOL = 0.12
DECODE = 704
NEIGHBOURS = {
    "reference_fp8_weights": {},
    "reference_lambda_init_only": {"learned_lambda": False},
    "reference_window_1024": {"window": 1024},
    "reference_memory_after_gate": {"memory_after_gate": True},
    "reference_bf16_state": {"state_dtype": "bfloat16"},
}
# the bfloat16 state is printed and not held to TOL: PERF.md section 4
MUST_DIFFER = ("reference_fp8_weights", "reference_lambda_init_only",
               "reference_window_1024", "reference_memory_after_gate")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-phi4flash-reason")
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--prompts", default="300,420,512")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    _, cell, cfg, job = common.load_cell(args.workload, args.rehearse)
    _, device = common.device_info(cell["chips"], args.rehearse)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    builder = common.load_module("builders", cfg["builder"])
    reference = common.load_module("references", cfg["reference"])
    s = builder.sizes(cfg)
    lens = [int(x) for x in args.prompts.split(",")]
    decode = DECODE if not args.rehearse else 24
    if args.rehearse:
        lens = [min(n, 40) for n in lens]
    bucket = job["engine"]["prompt_bucket"]
    T = -(-(max(lens) + decode) // bucket) * bucket
    BS = job["engine"].get("kv_block_size", 64)
    engine_sizes = dict(
        max_batch_size=len(lens) + 1, kv_block_size=BS, prompt_bucket=bucket,
        num_kv_blocks=1 + len(lens) * -(-T // BS))
    kw = dict(n_head=s["n_head"], activation=s["activation"])
    f32 = reference._f32

    def ref_rows(params, seq, first, **variant):
        ids = np.zeros((1, T), np.int32)
        ids[0, :len(seq)] = seq
        pos = (first - 1 + np.arange(decode)).astype(np.int32)
        if "state_dtype" in variant:
            variant["state_dtype"] = jnp.dtype(variant["state_dtype"])
        fn = jax.jit(lambda p, ids, pos: reference.logits_at(
            p, reference.hidden_states(p, ids, **kw, **variant)[0][pos]))
        return np.asarray(fn(params, ids, pos))

    def worst(got, want):
        return float(np.max(np.abs(got - want).max(axis=1)
                            / want.std(axis=1)))

    out = {"device": device, "prompts": lens, "decode_steps": decode,
           "tol": TOL, "engine": engine_sizes, "runs": []}
    for seed in (int(x) for x in args.seeds.split(",")):
        model = builder.model(cfg)
        engine = tap.tap_engine()(model, dict(
            dtype="bfloat16", seed=seed, **engine_sizes))
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, s["vocab_size"], n, dtype=np.int32)
                   for n in lens]
        uids = [engine.put(p, decode) for p in prompts]
        while engine.has_work:
            engine.step()
        for p, uid in zip(prompts, uids):
            tokens = engine.get(uid)
            got = np.stack(engine.rows[uid]).astype(np.float32)
            seq = np.concatenate([p, tokens])[:-1]   # every input token
            want = ref_rows(engine.params, seq, len(p))
            line = {"seed": seed, "prompt": len(p),
                    "system_vs_reference": worst(got, want),
                    "reference_argmax_share": float(np.mean(
                        got.argmax(axis=1) == want.argmax(axis=1)))}
            # the reference's neighbours, each against the reference itself
            for name, variant in NEIGHBOURS.items():
                if name == "reference_fp8_weights":
                    reference._f32 = lambda x: f32(x.astype(
                        jnp.float8_e5m2)) if x.ndim >= 2 else f32(x)
                line[name] = worst(ref_rows(engine.params, seq, len(p),
                                            **dict(variant)), want)
                reference._f32 = f32
            out["runs"].append(line)
            common.say("parity", **line)
        # the tap's callbacks keep the engine, and so its 7.7 GB of
        # weights, alive in the programs' caches: the next seed's engine
        # does not fit beside it
        del engine
        jax.clear_caches()
        gc.collect()
    ok = all(l["system_vs_reference"] <= TOL
             and min(l[n] for n in MUST_DIFFER) > TOL for l in out["runs"])
    out["ok"] = ok
    out["system_worst"] = max(l["system_vs_reference"] for l in out["runs"])
    out["neighbour_least"] = {n: min(l[n] for l in out["runs"])
                              for n in NEIGHBOURS}
    if args.rehearse:
        # a CPU rehearsal proves the control flow; its numbers are bf16 on
        # another backend at another size and decide nothing
        print(json.dumps({"rehearsal": True, "ran": True}))
        return 0
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
