#!/usr/bin/env python3
"""The gated delta rule alone, its XLA forms (``ops/gated_delta_rule.py``)
against its Pallas kernels (``ops/pallas/gated_delta_rule.py``), at
Olmo-Hybrid-7B's shapes: 30 heads, dk 96, dv 192, float32.

    python3 benchmarks/gated_delta_rule_sweep.py          # on the chip
    python3 benchmarks/gated_delta_rule_sweep.py --rehearse   # CPU, tiny

* ``chunk``: a 1,024-token chunk call from a state that is not zero, the
  rule alone (q, k, v as (1, T, heads, d) arrays) and ``from_qkv`` (what a
  layer pays: from the (T, 11,520) row of ``silu(conv(.))`` through the
  reshapes and L2 norms to o as (T, heads x dv), so the moves between the
  layouts are inside the time);
* ``step``: a decode step's one-token update of 16 slots of which 0, 1, 6
  or 16 are live, the XLA form with its masked write of the leaf.

A line a variant: us a call (``CALLS`` calls a timed program, the state
carried from each to the next), and how far the kernel is from the XLA form
on the chip. PERF.md, PR 42, reads them.
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

from deepspeed_tpu.models.olmo_hybrid import _l2              # noqa: E402
from deepspeed_tpu.ops.gated_delta_rule import (chunk_rule,    # noqa: E402
                                                step_rule)
from deepspeed_tpu.ops.pallas.gated_delta_rule import (        # noqa: E402
    chunk_rule_kernel, live_slot_list, step_rule_kernel)

H, DK, DV = 30, 96, 192
T, SLOTS = 1024, 16
CALLS = 12                      # a program's linear layers
HBM_GBS = 819.0                 # TPU v5e (perfbench/peaks.json)
EPS = 1e-6                      # the configuration's rms_eps


def chunk_alone(rule):
    def run(q, k, v, log_a, b, S):
        outs = []
        for _ in range(CALLS):
            o, S = rule(q, k, v, log_a, b, S)
            outs.append(jnp.sum(o))
        return jnp.stack(outs), S
    return jax.jit(run)


def chunk_from_qkv(rule):
    def run(qkv, log_a, b, S):
        acc = jnp.zeros((1, T, H * DV), jnp.float32)
        for i in range(CALLS):
            x = qkv * (1.0 + 0.01 * i)      # a fusion of its own a layer
            q = _l2(x[..., :H * DK].reshape(1, T, H, DK), EPS) * DK ** -0.5
            k = _l2(x[..., H * DK:2 * H * DK].reshape(1, T, H, DK), EPS)
            v = x[..., 2 * H * DK:].reshape(1, T, H, DV)
            o, S = rule(q, k, v, log_a, b, S)
            acc = acc + o.reshape(1, T, H * DV)
        return acc, S
    return jax.jit(run)


def step_xla(q, k, v, log_a, b, ssm, active):
    outs = []
    for _ in range(CALLS):
        o, S = step_rule(q, k, v, log_a, b, ssm)
        ssm = jnp.where(active[:, None, None, None], S, ssm)
        outs.append(o)
    return jnp.stack(outs), ssm


def step_kernel(q, k, v, log_a, b, ssm, active):
    live = live_slot_list(active)
    outs = []
    for _ in range(CALLS):
        o, ssm = step_rule_kernel(q, k, v, log_a, b, ssm, live)
        outs.append(o)
    return jnp.stack(outs), ssm


def timed(prog, args, reps, carry=None):
    """Median seconds a call of ``prog`` (compiled by a first call);
    ``carry``: the index of the argument the program's last output
    replaces (a donated state)."""
    args = list(args)
    times, out = [], None
    for i in range(reps + 1):
        t0 = time.perf_counter()
        out = jax.block_until_ready(prog(*args))
        if i:
            times.append((time.perf_counter() - t0) / CALLS)
        if carry is not None:
            args[carry] = out[-1]
    if not times:
        return 0.0, 0.0, out
    return float(np.median(times)), float(min(times)), out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/gated_delta_rule_sweep.jsonl")
    ap.add_argument("--rehearse", action="store_true",
                    help="the control flow on the CPU, 128 tokens, 2 calls")
    a = ap.parse_args()
    global T, CALLS
    if a.rehearse:
        T, CALLS, a.reps, a.out = 128, 2, 1, os.devnull
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU: a time comes only from the chip")
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    out = open(a.out, "a")

    def say(**line):
        text = json.dumps(line)
        print(text, flush=True)
        out.write(text + "\n")
        out.flush()

    ks = jax.random.split(jax.random.key(a.seed), 8)
    qkv = jax.nn.silu(jax.random.normal(ks[0], (1, T, H * (2 * DK + DV))))
    q = _l2(qkv[..., :H * DK].reshape(1, T, H, DK), EPS) * DK ** -0.5
    k = _l2(qkv[..., H * DK:2 * H * DK].reshape(1, T, H, DK), EPS)
    v = qkv[..., 2 * H * DK:].reshape(1, T, H, DV)
    log_a = -jax.random.uniform(ks[1], (1, T, H), jnp.float32, 0.0, 0.2)
    b = jax.random.uniform(ks[2], (1, T, H), jnp.float32, 0.0, 2.0)
    S0 = jax.random.normal(ks[3], (1, H, DK, DV))

    # ------------------------------------------------------------- chunk
    ref = {}
    for name, rule in (("xla", chunk_rule), ("kernel", chunk_rule_kernel)):
        for form, prog, args in (
                ("alone", chunk_alone(rule), (q, k, v, log_a, b, S0)),
                ("from_qkv", chunk_from_qkv(rule), (qkv, log_a, b, S0))):
            med, low, got = timed(prog, args, a.reps)
            got = [np.asarray(x) for x in got]
            ref.setdefault(form, got)
            say(what="chunk", form=form, rule=name, T=T,
                us_per_call=round(med * 1e6, 1),
                us_min=round(low * 1e6, 1),
                max_rel_diff_vs_xla=[
                    float(np.abs(x - y).max() / np.abs(y).max())
                    for x, y in zip(got, ref[form])])

    # -------------------------------------------------------------- step
    q1, k1, v1 = q[0, :SLOTS], k[0, :SLOTS], v[0, :SLOTS]
    la1, b1 = log_a[0, :SLOTS], b[0, :SLOTS]
    ssm = jax.random.normal(ks[4], (SLOTS, H, DK, DV))
    for n_live in (0, 1, 6, SLOTS):
        active = np.zeros(SLOTS, bool)
        active[np.random.RandomState(a.seed).permutation(SLOTS)[:n_live]] = 1
        want = None
        for name, fn in (("xla", step_xla), ("kernel", step_kernel)):
            prog = jax.jit(fn, donate_argnums=(5,))
            args = (q1, k1, v1, la1, b1, ssm + 0.0, jnp.asarray(active))
            _, _, first = timed(prog, args, 0)
            first = [np.asarray(x) for x in first]
            want = want or first
            args = (q1, k1, v1, la1, b1, ssm + 0.0, jnp.asarray(active))
            med, low, _ = timed(prog, args, a.reps, carry=5)
            moved = n_live * 2 * H * DK * DV * 4
            say(what="step", rule=name, slots=SLOTS, live=n_live,
                us_per_call=round(med * 1e6, 1),
                us_min=round(low * 1e6, 1),
                hbm_share_of_live_state=round(
                    moved / max(med, 1e-12) / 1e9 / HBM_GBS, 4),
                max_abs_diff_o_live=float(np.abs(
                    first[0][:, active] - want[0][:, active]).max(
                        initial=0.0)),
                max_abs_diff_state=float(np.abs(first[1] - want[1]).max()),
                dead_rows_untouched=bool(np.array_equal(
                    first[1][~active], np.asarray(ssm)[~active])))


if __name__ == "__main__":
    main()
