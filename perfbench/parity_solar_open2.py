#!/usr/bin/env python3
"""Solar-Open2 on the chip against its plain reference, logit by logit, at
the configuration's published widths and the cell's depth and share. Not run
by the driver and outside every timed window; run once by the builder of a
PR that touches the model (PR 54: PERF.md section 4), on the chip:

    python3 perfbench/parity_solar_open2.py [--seeds 1] [--prompts 1500,8000]

Each prompt goes through ``InferenceEngineV2`` as the cell's requests do (the
cell's own 1,024-token split-fuse chunks into a slot, then 8-step decode
dispatches), alone in the engine, with a tap on the logits every token is
sampled from (``pbench/tap.py``): ``DECODE`` tokens a prompt. Every row is
compared with the reference's row at the same position (``references/
solar_open2.py``, float32, precision highest): the largest absolute
difference over the reference row's standard deviation.

``TOL`` is set from two kinds of reading (PERF.md section 4 has them; my
chip runs, PR 54, seeds 1-3): the largest the system gives over its seeds,
**0.079** (seed 3 after a 1,500-token prompt; the other eight readings are
0.009 - 0.013: a reading is ~0.01 of rounding, or ~0.1 where one token's
eighth expert or one near-tie falls the other way), and what the
reference's nearest neighbours give against the reference itself: weights
rounded to float8 (e5m2), the nearest precision below the bfloat16 the
configuration states, **0.46 - 0.51**; one gate a head (the channels' mean)
in place of the vector over the key channels, 0.94 - 1.05; a softmax router,
0.66 - 0.91; ``beta`` without the factor 2, 0.43 - 0.45; no output gate on
the GQA layer, 0.45 - 0.74. ``TOL`` 0.15 is 1.9 x the system's largest and a
third of the nearest neighbour's least. **The matrix state kept in bfloat16
is printed and not held to it**: it reads 0.011 - 0.012 in four of six
readings and 0.110 - 0.113 in two, the system's own two levels (the state's
rounding averages away over thousands of decayed updates until it flips a
choice), as in cells 7 and 9; the float32 CPU tests tell it apart (8e-5
against 1e-7). Exits 1 when the system is over ``TOL`` or a neighbour in
``MUST_DIFFER`` is under it on any prompt. A neighbour is a whole reference
pass: they are computed for prompts of at most ``--neighbours-upto`` tokens.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from pbench import common, tap     # noqa: E402

# in standard deviations of a position's reference logits; see the docstring
TOL = 0.15
DECODE = 64
NEIGHBOURS = {
    "reference_fp8_weights": {},
    "reference_bf16_state": {"state_dtype": "bfloat16"},
    "reference_gate_a_head": {"gate_per_channel": False},
    "reference_softmax_router": {"gate_scoring": "softmax"},
    "reference_beta_without_2": {"beta_scale": 1.0},
    "reference_no_gqa_gate": {"attn_gate": False},
}
# the bfloat16 state is printed and not held to TOL: the docstring
MUST_DIFFER = tuple(n for n in NEIGHBOURS if n != "reference_bf16_state")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-solar2-longctx-sat")
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--prompts", default="1500,8000,30000")
    ap.add_argument("--neighbours", default=",".join(NEIGHBOURS),
                    help="which neighbours to compute (each is a whole "
                    "reference pass)")
    ap.add_argument("--neighbours-upto", type=int, default=8000)
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
    decode = DECODE if not args.rehearse else 12
    if args.rehearse:
        lens = [min(n, 40 + 60 * i) for i, n in enumerate(lens)]
    T, BS = s["max_seq_len"], job["engine"].get("kv_block_size", 64)
    if max(lens) + decode > T:
        raise SystemExit(f"a prompt of {max(lens)} tokens and {decode} "
                         f"decode steps pass the {T} served positions")
    engine_sizes = dict(
        max_batch_size=2, kv_block_size=BS,
        splitfuse_tokens=job["engine"]["splitfuse_tokens"],
        num_kv_blocks=1 + -(-T // BS))
    kw = dict(n_head=s["n_head"], activation=s["activation"],
              top_k=s["top_k"], experts_offset=s["experts_offset"])
    f32 = reference._f32
    wanted = [n for n in args.neighbours.split(",") if n]

    compiled = {}

    def ref_rows(params, seq, first, name="", **variant):
        # causal: the padding after the sequence is never seen, so a short
        # prompt is padded to the next 512 positions and not to T
        padded = -(-(len(seq) + 1) // 512) * 512
        ids = np.zeros((1, padded), np.int32)
        ids[0, :len(seq)] = seq
        pos = (first - 1 + np.arange(decode)).astype(np.int32)
        if "state_dtype" in variant:
            variant["state_dtype"] = jnp.dtype(variant["state_dtype"])
        if (name, padded) not in compiled:
            compiled[name, padded] = jax.jit(
                lambda p, ids, pos: reference.logits_at(
                    p, reference.hidden_states(p, ids, **kw,
                                               **variant)[0][pos]))
        return np.asarray(compiled[name, padded](params, ids, pos))

    class Tap(tap.tap_engine()):
        """The tap reads a dispatch's rows as the newest it has seen: every
        decode dispatch is read before the next goes out."""

        def _plain_decode(self, uids=None):
            toks = super()._plain_decode(uids)
            self._settle()
            return toks

    def worst(got, want):
        return float(np.max(np.abs(got - want).max(axis=1)
                            / want.std(axis=1)))

    out = {"device": device, "prompts": lens, "decode_steps": decode,
           "tol": TOL, "engine": engine_sizes, "runs": []}
    for seed in (int(x) for x in args.seeds.split(",")):
        model = builder.model(cfg)
        engine = Tap(model, dict(dtype="bfloat16", seed=seed,
                                 **engine_sizes))
        rng = np.random.default_rng(seed)
        for n in lens:
            p = rng.integers(0, s["vocab_size"], n, dtype=np.int32)
            t0 = time.perf_counter()
            uid = engine.put(p, decode)
            while engine.has_work:
                engine.step()
            tokens = engine.get(uid)
            t1 = time.perf_counter()
            got = np.stack(engine.rows[uid]).astype(np.float32)
            seq = np.concatenate([p, tokens])[:-1]   # every input token
            want = ref_rows(engine.params, seq, len(p))
            gap = (want.max(axis=1) - want[np.arange(decode), tokens]) \
                / want.std(axis=1)
            line = {"seed": seed, "prompt": len(p),
                    # compilation included, the first time a shape is met
                    "system_s": t1 - t0,
                    "reference_s": time.perf_counter() - t1,
                    "system_vs_reference": worst(got, want),
                    "worst_emitted_gap": float(gap.max()),
                    "reference_argmax_share": float(np.mean(
                        got.argmax(axis=1) == want.argmax(axis=1)))}
            # the reference's neighbours, each against the reference itself
            for name in wanted if n <= args.neighbours_upto else ():
                if name == "reference_fp8_weights":
                    reference._f32 = lambda x: f32(x.astype(
                        jnp.float8_e5m2)) if x.ndim >= 2 else f32(x)
                line[name] = worst(ref_rows(
                    engine.params, seq, len(p), name,
                    **dict(NEIGHBOURS[name])), want)
                reference._f32 = f32
            out["runs"].append(line)
            common.say("parity", **line)
        # the tap's callbacks keep the engine, and so its 6.6 GB of
        # weights, alive in the programs' caches: the next seed's engine
        # does not fit beside it
        del engine
        jax.clear_caches()
        compiled.clear()
        gc.collect()
    must = [n for n in MUST_DIFFER if n in wanted]
    ok = all(l["system_vs_reference"] <= TOL
             and all(l[n] > TOL for n in must if n in l)
             for l in out["runs"])
    out["ok"] = ok
    out["system_worst"] = max(l["system_vs_reference"] for l in out["runs"])
    out["neighbour_least"] = {
        n: min(l[n] for l in out["runs"] if n in l) for n in wanted
        if any(n in l for l in out["runs"])}
    if args.rehearse:
        # a CPU rehearsal proves the control flow; its numbers are bf16 on
        # another backend at another size and decide nothing
        print(json.dumps({"rehearsal": True, "ran": True}))
        return 0
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
