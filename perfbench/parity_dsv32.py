#!/usr/bin/env python3
"""DeepSeek-V3.2-Exp (one chip's share) on the chip against its plain
reference, logit by logit, at the configuration's published widths and the
cell's depth. Not run by the driver and outside every timed window; run once
by the builder of a PR that touches the model (PR 43: PERF.md section 4), on
the chip:

    python3 perfbench/parity_dsv32.py [--seeds 1] [--prompts 1500,6000]

Each prompt goes through ``InferenceEngineV2`` as the cell's requests do (the
cell's own 1,024-token split-fuse chunks, then 8-step decode dispatches),
alone in the engine, with a tap on the logits every token is sampled from
(``pbench/tap.py``): ``DECODE`` tokens a prompt. Every row is compared with
the reference's row at the same position (``references/deepseek_v32.py``,
float32, precision highest): the largest absolute difference over the
reference row's standard deviation. The program's own selection is read too
(``pbench.dsa.tapped_selection`` wraps ``models/paged.py``'s read from here;
the program carries no hook): of the keys the reference selects for the
prompt's queries, a layer at a time, the share the program selected as well.

``TOL`` is set from two kinds of reading (PERF.md section 4 has them): the
largest the system gives over its seeds, and what the reference's nearest
neighbours give against the reference itself, each of which has to come
out over it or the comparison cannot tell the model from them: weights
rounded to float8 (e5m2), the nearest precision below the bfloat16 the
configuration states; no selection (dense MLA over every causal key: told
apart only past ``index_topk`` keys); a selection of 1,024; the gate's
correction bias also used as weight; no group limit; softmax scores in the
gate; no shared expert; the softmax scale without m^2; rotary without YaRN.
Two neighbours are printed and not held to ``TOL``, because on the chip they
sit nearer the reference than the bfloat16 system does (PERF.md section 4;
the float32 tier-1 tests hold both): the reference with its index queries
and keys rounded to bfloat16, which says what a bfloat16 index path would do
to the selected set (the program's is float32 for that reason), and the
correction bias used as weight too, which moves only the ~0.02 of the stream
a held expert adds. Exits 1 when the system is over ``TOL`` or a neighbour
that must differ is under it.

Beside each neighbour's distance, ``<name>_emitted_gap`` is what the CELL's
own check (``runners/serve.py:check_against_reference``: every emitted token
within ``SERVE_GAP_TOL`` 0.1 deviations of the reference row's maximum) would
read of a program that computed the neighbour: the neighbour's greedy token
at each of the prompt's ``DECODE`` positions, teacher-forced along the same
sequence, against the float32 reference's row. It says which wrong models
``correct`` alone can refuse and which only ``TOL`` here can.
"""

import argparse
import contextlib
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from pbench import common, dsa, tap     # noqa: E402

# in standard deviations of a position's reference logits; see the docstring
TOL = 0.15
DECODE = 128
NEIGHBOURS = {
    "reference_fp8_weights": {},
    "reference_no_selection": {"select": False},
    "reference_topk_1024": {"index_topk": 1024},
    "reference_bias_weighs": {"bias_weighs": True},
    "reference_no_group_limit": {"group_limit": False},
    "reference_softmax_gate": {"gate_scoring": "softmax"},
    "reference_no_shared_expert": {"shared": False},
    "reference_scale_without_m2": {"mscale_squared": False},
    "reference_rope_without_yarn": {"yarn": False},
    "reference_bf16_index": {"index_dtype": "bfloat16"},
}
# printed and not held to TOL
NOT_HELD = ("reference_bf16_index", "reference_bias_weighs")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-dsv32-longctx-sat")
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--prompts", default="1500,6000,16000")
    ap.add_argument("--neighbours", default=",".join(NEIGHBOURS),
                    help="which neighbours to compute (each is a whole "
                    "reference pass)")
    ap.add_argument("--neighbour-seeds", default="1")
    ap.add_argument("--neighbour-prompts", default="1500,6000",
                    help="the prompts whose neighbours are computed")
    ap.add_argument("--selection-seeds", default="1",
                    help="the seeds whose selected sets are compared")
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
    ints = lambda text: [int(x) for x in text.split(",") if x]  # noqa: E731
    lens = ints(args.prompts)
    decode = DECODE if not args.rehearse else 12
    topk = s["index_topk"]
    base = {}
    if args.rehearse:
        # contexts under and over a selection the tiny size can show
        lens = [min(n, 40 + 60 * i) for i, n in enumerate(lens)]
        topk = 48
        base = {"index_topk": topk}
        NEIGHBOURS["reference_topk_1024"] = {"index_topk": topk // 2}
    near = [lens[i] for i, n in enumerate(ints(args.prompts))
            if n in ints(args.neighbour_prompts)]
    T, BS = s["max_seq_len"], job["engine"].get("kv_block_size", 64)
    if max(lens) + decode > T:
        raise SystemExit(f"a prompt of {max(lens)} tokens and {decode} "
                         f"decode steps pass the {T} served positions")
    engine_sizes = dict(
        max_batch_size=2, kv_block_size=BS,
        splitfuse_tokens=job["engine"]["splitfuse_tokens"],
        num_kv_blocks=1 + -(-T // BS))
    kw = dict(n_head=s["n_head"], activation=s["activation"], **base)
    f32 = reference._f32
    wanted = [n for n in args.neighbours.split(",") if n]

    compiled = {}

    def padded_ids(seq):
        # causal: the padding after the sequence is never seen, so a
        # prompt is padded to the next 512 positions and not to T
        padded = -(-(len(seq) + 1) // 512) * 512
        ids = np.zeros((1, padded), np.int32)
        ids[0, :len(seq)] = seq
        return ids

    def ref_rows(params, seq, first, name="", **variant):
        ids = padded_ids(seq)
        pos = (first - 1 + np.arange(decode)).astype(np.int32)
        key = (name, ids.shape[1])
        if key not in compiled:
            compiled[key] = jax.jit(
                lambda p, ids, pos: reference.logits_at(
                    p, reference.hidden_states(p, ids, **{**kw, **variant})
                    [0][pos]))
        return np.asarray(compiled[key](params, ids, pos))

    def ref_masks(params, prompt):
        """The reference's selected keys of the prompt's queries, a list
        of (n, n) bool a layer, on the host."""
        ids = padded_ids(prompt)[0]
        out = []
        for i in range(s["n_layer"]):
            key = ("mask", i, len(ids))
            if key not in compiled:
                compiled[key] = jax.jit(
                    lambda p, ids, i=i: reference.selection_masks(
                        p, ids, layers=(i,), **base)[0])
            n = len(prompt)
            out.append(np.asarray(compiled[key](params, ids))[:n, :n])
        return out

    class Tap(tap.tap_engine()):
        """The tap reads a dispatch's rows as the newest it has seen: every
        decode dispatch is read before the next goes out (plain decodes
        are chained since PR 35; tests/unit/test_phi4flash.py does the
        same)."""

        def _plain_decode(self, uids=None):
            toks = super()._plain_decode(uids)
            self._settle()
            return toks

    def distance(got, want):
        """(largest, median) over the rows of a row's largest |difference|
        / the reference row's deviation: what the system's rounding adds
        is a rare flipped key or expert, a few rows far out, where a wrong
        model is wrong in every row; the median tells those apart where
        the largest cannot."""
        rows = np.abs(got - want).max(axis=1) / want.std(axis=1)
        return float(rows.max()), float(np.median(rows))

    out = {"device": device, "prompts": lens, "decode_steps": decode,
           "tol": TOL, "engine": engine_sizes, "runs": []}
    for seed in ints(args.seeds):
        model = builder.model(cfg, **base)
        # [both selected, the reference selected] over the prompt's queries
        agree = {"want": None, "both": 0, "ref": 0, "extra": 0}

        def selection_tap(layer, q_pos, sel):
            want = agree["want"]
            if want is None or sel.shape[1] == 1:     # a decode step
                return
            n = want[0].shape[0]
            t = np.asarray(q_pos[0])
            t = t[t < n]
            mine = np.asarray(sel[0, :len(t), :n])
            theirs = want[int(layer)][t]
            agree["both"] += int((mine & theirs).sum())
            agree["ref"] += int(theirs.sum())
            agree["extra"] += int((mine & ~theirs).sum())

        compare_sets = seed in ints(args.selection_seeds)
        engine = Tap(model, dict(dtype="bfloat16", seed=seed,
                                 **engine_sizes))
        rng = np.random.default_rng(seed)
        for n in lens:
            p = rng.integers(0, s["vocab_size"], n, dtype=np.int32)
            agree.update(both=0, ref=0, extra=0, want=ref_masks(
                engine.params, p) if compare_sets else None)
            uid = engine.put(p, decode)
            # the programs are traced at their first call, inside the block
            with dsa.tapped_selection(s["n_layer"], selection_tap) \
                    if compare_sets else contextlib.nullcontext():
                while engine.has_work:
                    engine.step()
                jax.effects_barrier()
            tokens = engine.get(uid)
            got = np.stack(engine.rows[uid]).astype(np.float32)
            seq = np.concatenate([p, tokens])[:-1]   # every input token
            want = ref_rows(engine.params, seq, len(p))
            gap = (want.max(axis=1) - want[np.arange(decode), tokens]) \
                / want.std(axis=1)
            far, typical = distance(got, want)
            line = {"seed": seed, "prompt": len(p),
                    "system_vs_reference": far, "system_typical": typical,
                    "worst_emitted_gap": float(gap.max()),
                    "reference_argmax_share": float(np.mean(
                        got.argmax(axis=1) == want.argmax(axis=1)))}
            if agree["ref"]:
                line["reference_selected_also_selected"] = \
                    agree["both"] / agree["ref"]
                line["selected_not_in_reference"] = \
                    agree["extra"] / agree["ref"]
            agree["want"] = None
            # the reference's neighbours, each against the reference itself
            if seed in ints(args.neighbour_seeds) and n in near:
                for name in wanted:
                    if name == "reference_fp8_weights":
                        reference._f32 = lambda x: f32(x.astype(
                            jnp.float8_e5m2)) if x.ndim >= 2 else f32(x)
                    rows = ref_rows(engine.params, seq, len(p), name,
                                    **dict(NEIGHBOURS[name]))
                    reference._f32 = f32
                    line[name], line[name + "_typical"] = distance(rows, want)
                    line[name + "_emitted_gap"] = float(np.max(
                        (want.max(axis=1) - want[np.arange(decode),
                                                 rows.argmax(axis=1)])
                        / want.std(axis=1)))
            out["runs"].append(line)
            common.say("parity", **line)
        # the tap's callbacks keep the engine, and so its weights, alive in
        # the programs' caches: the next seed's engine does not fit beside it
        del engine
        jax.clear_caches()
        compiled.clear()
        gc.collect()

    def must_differ(name, line):
        # without a selection the model is itself until a query has more
        # causal keys than index_topk
        return name not in NOT_HELD and not (
            name == "reference_no_selection" and line["prompt"] <= topk)

    ok = all(l["system_vs_reference"] <= TOL
             and all(l[n] > TOL for n in wanted
                     if n in l and must_differ(n, l)) for l in out["runs"])
    out["ok"] = ok
    out["system_worst"] = max(l["system_vs_reference"] for l in out["runs"])
    out["neighbour_least"] = {
        n: min((l[n] for l in out["runs"] if n in l and must_differ(n, l)),
               default=None) for n in wanted}
    if args.rehearse:
        # a CPU rehearsal proves the control flow; its numbers are bf16 on
        # another backend at another size and decide nothing
        print(json.dumps({"rehearsal": True, "ran": True}))
        return 0
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
