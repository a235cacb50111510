#!/usr/bin/env python3
"""Kanana-2-30B-A3B (one chip's share, ``perfbench/configs/
kanana-2-30b-a3b.json``) on the chip against its plain reference, forward
AND backward, at the configuration's published widths, the cell's depth and
the cell's 8,192-token sequences. Not run by the driver and outside every
timed window; run once by the builder of a PR that touches the model (PR 47:
PERF.md section 4), on the chip:

    python3 perfbench/parity_kanana2.py [--seeds 1,2] [--neighbours all]

The program is the cell's own: the builder's model with the job file's
``model_overrides`` (the flash kernel, remat, the fused cross entropy), its
seeded weights cast as the engine casts them (bfloat16, the gate's
correction bias a float32 buffer), ``jax.value_and_grad`` of ``model.loss``
on one sequence of uniform random ids. The reference
(``references/deepseek_v3.py``, float32, precision highest) is handed the
same tree. Four comparisons. Each tolerance lies between two readings of
PR 47's chip runs (seeds 1 and 2, PERF.md section 4): the largest the
system gave, and the smallest of the float8 control and of the wrong models
that only this comparison tells:

* ``LOSS_TOL`` 4e-4: the losses' difference. System 7.7e-5 and 4.6e-5; the
  float8 control 8.0e-3 and 1.3e-3. (The runner's own check is 0.02,
  ``runners/train.py``; a mean over 8,191 tokens of nearly uniform
  predictions tells no wrong model, some read 5e-6.)
* ``LOGIT_TOL`` 0.06, in standard deviations of a position's reference
  logits: the root mean square over ``ROWS`` sampled positions of each
  row's largest difference. System 0.024 and 0.019; a softmax scale of
  128^-0.5 0.105 and 0.104, the float8 control 0.85 and 0.77. A row alone
  can sit further out (0.08, printed and not held): a token whose sixth
  expert differs between the bfloat16 program and the float32 reference, a
  near tie among 128 sigmoid scores, moves by what a held expert adds.
* ``GRAD_TOL`` 0.1: the gradient, relative L2, of every parameter group no
  choice of experts feeds directly (a group is a leaf's name, the largest
  over the layers that have it): attention, the dense and shared SwiGLUs,
  norms, embedding and head. System 0.7 - 2.5 % a group and 4.4 % on
  ``norm2``, the norm before the router (0.025 and 0.044 the worst); a
  softmax scale of 128^-0.5 0.235 and 0.240 (``wk_b``), the float8 control
  0.35 and 0.38.
* ``ROUTED_GRAD_TOL`` 0.35: the same for ``ROUTED``, the router and the held
  experts' arrays. System 0.20 and 0.12 (``moe_w3``, ``gate``) where the
  shared experts, the same SwiGLU with no choice before it, read 1 %: the
  bfloat16 stream moves the 128 scores by a few thousandths, and where the
  sixth and seventh lie closer than that the program and the reference
  send the token to different experts. Tier-1 holds the same comparison in
  float32 at 5e-7, where no pick differs. The share of picks that differ
  was not measured; a relative L2 of 0.2 is what ~2 % of a held expert's
  rows exchanged for others gives. The correction bias also weighing, which
  only this comparison tells, 0.83 and 0.61.

A gradient that is not finite counts as infinitely far. (PR 47's first run
had NaN from rows ``lax.ragged_dot`` never wrote in every gradient under
the top expert layer, and a ``max`` over layers hid it behind the top
layer's finite reading.)

The comparison has to tell the model from its neighbours, so each WRONG
MODEL (``NEIGHBOURS``: the reference computing something else) is compared
with the program the same way and has to come out over a tolerance, by at
least one of them: the correction bias also weighing, no renormalisation,
the 2.448 left out, a softmax scale of 128^-0.5, rotary on split halves, a
shared expert of width 768, absent experts' rows leaking through held ones;
and the reference on weights rounded to float8 (e5m2), the nearest
precision below the bfloat16 the configuration states. Exits 1 when the
program is over a tolerance or a neighbour is under all of them.

``--rehearse`` runs the same control flow at the configuration's
``rehearse`` sizes on whatever backend JAX has, tolerances not held.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from pbench import common     # noqa: E402

LOSS_TOL, LOGIT_TOL, GRAD_TOL, ROUTED_GRAD_TOL = 4e-4, 0.06, 0.1, 0.35
# the groups a discrete choice of experts feeds
ROUTED = ("gate", "moe_w1", "moe_w3", "moe_w2")
ROWS = 64
# the seeded correction bias is small (normal 0.02: it may not skew the held
# experts' load, ``models/deepseek_v3.py``); here it is 0.2, the scale every
# reading above was made at, so that the bias also weighing is a different
# model by more than the tolerance
BIAS_SCALE = 10.0
WORKLOAD = "train-kanana2-share-8k"
NEIGHBOURS = {
    "bias_weighs": {"bias_weighs": True},
    "no_renormalisation": {"renormalise": False},
    "scale_left_out": {"routed_scale": 1.0},
    "softmax_scale_128": {"scale_width": "qk_nope_head_dim"},
    "rope_split_halves": {"rope_interleave": False},
    "shared_width_768": {"shared_width": "moe_d_ff"},
    "absent_rows_leak": {"leak": True},
    "fp8_weights": {},
}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--neighbours", default="all",
                    help="'all', '' or a comma-separated list")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    _, cell, cfg, job = common.load_cell(WORKLOAD, args.rehearse)
    common.device_info(cell["chips"], args.rehearse)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    builder = common.load_module("builders", cfg["builder"])
    ref = common.load_module("references", cfg["reference"])
    s = builder.sizes(cfg)
    model = builder.model(cfg, **job["model_overrides"])
    T = job["seq_len"]
    kw = dict(top_k=s["top_k"], experts_offset=s["experts_offset"],
              routed_scale=cfg["routed_scaling_factor"],
              rope_theta=float(cfg["rope_theta"]), n_group=cfg["n_group"],
              topk_group=cfg["topk_group"])
    names = NEIGHBOURS if args.neighbours == "all" else {
        n: NEIGHBOURS[n] for n in args.neighbours.split(",") if n}
    buffers = model.buffer_names()

    def cast(tree):
        """As ``runtime/engine.py`` casts: a buffer leaf as it is."""
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x if getattr(path[-1], "key", None) in buffers
            else x.astype(jnp.bfloat16), tree)

    def wider_bias(tree):
        """The seeded correction bias x ``BIAS_SCALE``, for program and
        reference alike."""
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x * BIAS_SCALE
            if getattr(path[-1], "key", None) == "gate_bias" else x, tree)

    rows = np.unique(np.linspace(0, T - 2, ROWS).astype(np.int32))

    @jax.jit
    def program(params, ids):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, {"input_ids": ids}))(params)
        x = model.apply(params, ids, return_hidden=True)
        return loss, grads, model.head(params, x[:, rows])[0]

    def reference(variant):
        @jax.jit
        def run(params, ids):
            loss, grads = ref.loss_and_grads(params, ids, **variant)
            x = ref.hidden_states(params, ids, **variant)
            return loss, grads, ref.logits_at(params, x[0, rows])
        return run

    @jax.jit
    def distances(got, want):
        """(loss difference, rms and max over rows of the largest logit
        difference in deviations of the reference row, {group: relative L2
        of the gradient, the largest over layers})."""
        (l0, g0, r0), (l1, g1, r1) = got, want
        per_row = jnp.max(jnp.abs(r0 - r1), axis=-1) / jnp.std(r1, axis=-1)
        rel = jax.tree.map(
            lambda a, b: jnp.linalg.norm(a.astype(jnp.float32) - b)
            / (jnp.linalg.norm(b) + 1e-30), g0, g1)
        return (jnp.abs(l0 - l1), jnp.sqrt(jnp.mean(per_row ** 2)),
                jnp.max(per_row), rel)

    def summarise(d):
        loss, rms, worst, rel = jax.device_get(d)
        groups = {}
        for path, v in jax.tree_util.tree_flatten_with_path(rel)[0]:
            name = path[-1].key
            if name not in buffers:
                # a gradient that is not finite is as far out as can be
                v = float(v) if np.isfinite(v) else float("inf")
                groups[name] = max(groups.get(name, 0.0), v)
        return {"loss": float(loss), "logit_rms_std": float(rms),
                "logit_max_std": float(worst), "grad_rel_l2": groups,
                "grad_worst": max(v for g, v in groups.items()
                                  if g not in ROUTED),
                "routed_grad_worst": max(groups[g] for g in ROUTED)}

    def over(r):
        return [k for k, v, tol in (
            ("loss", r["loss"], LOSS_TOL),
            ("logits", r["logit_rms_std"], LOGIT_TOL),
            ("grads", r["grad_worst"], GRAD_TOL),
            ("routed_grads", r["routed_grad_worst"], ROUTED_GRAD_TOL))
            if not v <= tol]

    failed = []
    for seed in (int(x) for x in args.seeds.split(",")):
        params = jax.jit(lambda r: wider_bias(cast(model.init(r))))(
            jax.random.key(seed))
        ids = jnp.asarray(np.random.default_rng(seed).integers(
            0, s["vocab_size"], (1, T), dtype=np.int32))
        got = program(params, ids)
        want = reference(kw)(params, ids)
        r = summarise(distances(got, want))
        bias_grad = max(float(jnp.max(jnp.abs(p["gate_bias"])))
                        for p in got[1]["layers"] if "gate_bias" in p)
        common.say("parity", seed=seed, tokens=T, who="system",
                   program_loss=float(got[0]), reference_loss=float(want[0]),
                   bias_grad_max=bias_grad, over=over(r), **r)
        if over(r) or bias_grad != 0.0:
            failed.append(("system", seed, over(r)))
        del want
        for name, variant in names.items():
            variant = {k: s[v] if isinstance(v, str) else v
                       for k, v in variant.items()}
            given = params
            if name == "fp8_weights":
                # (a pair of converts through float8_e5m2 is folded away
                # by the chip's compiler: PR 47's first run read the
                # system's own numbers to the last digit)
                given = jax.jit(lambda t: jax.tree.map(
                    lambda x: jax.lax.reduce_precision(x, 5, 2)
                    if x.dtype == jnp.bfloat16 else x, t))(params)
            wrong = reference({**kw, **variant})(given, ids)
            r = summarise(distances(got, wrong))
            common.say("parity", seed=seed, tokens=T, who=name,
                       over=over(r), **r)
            if not over(r):
                failed.append((name, seed, "under every tolerance"))
            del wrong
    ok = not failed or args.rehearse
    print(json.dumps({"parity_kanana2": "ok" if not failed else "FAILED",
                      "failed": failed, "rehearsal": args.rehearse,
                      "tolerances": {"loss": LOSS_TOL, "logit_rms_std":
                                     LOGIT_TOL, "grad_rel_l2": GRAD_TOL,
                                     "routed_grad_rel_l2":
                                     ROUTED_GRAD_TOL}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
