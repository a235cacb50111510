#!/usr/bin/env python3
"""Find a serving cell's knee: the highest of a few fixed rates the system
sustains. Not run by the driver; run once, on the chip, when a cell is
defined (or found again by a later benchmark PR), and its rows written into
PERF.md and the two chosen rates into the traffic files as numbers.

    python3 perfbench/sweep.py --workload <name> --rates 2,4,6,8,10,12 \\
        --seconds 20 --seed 0 [--out chiprun_out/sweep.jsonl]

One process, one engine, warmed once; each rate gets the cell's own ramp, a
window of --seconds and a full drain. A rate is sustained when the backlog
does not grow over the window (requests put and not done as the window
closes no more than as it opens, within ``--backlog-slack``) and at least
``--attain`` of the requests due in the window meet the traffic file's
``limits`` (time to first token and time per output token, from the due
time; a failed request misses). Rates are offered, never searched for.

    python3 perfbench/sweep.py --workload <name> --repeat 6 --seconds 45 \\
        --seed 2147490000 [--rates 0.8]

``--repeat N`` is the other question a cell is asked when it is defined or
measured anew: N seeds (--seed, --seed + 1, ...) at ONE rate, the traffic
file's unless --rates names one. It prints each window's ``serve_tok_s``,
their quartile spread, (q3 - q1) / median by ``statistics.quantiles(n=4)``,
and whether that is under half the metric's bound (README, "Spread").
One engine serves all N windows, so this costs a set-up once; the sets a PR
records are still made through ``run.py``, a process a seed.
"""

import argparse
import json
import os
import statistics
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from pbench import common      # noqa: E402


def quartile_spread(values):
    """(q3 - q1) / median, the quartiles as ``statistics.quantiles(n=4)``
    gives them: the spread the driver judges a bound by."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def repeat_summary(bench, workload, rows, device):
    """The last line of a ``--repeat`` call."""
    out = {"workload": workload, "rate_per_s": rows[0]["rate_per_s"],
           "seeds": [r["seed"] for r in rows],
           "backlog": [[r["live_at_open"], r["live_at_close"]]
                       for r in rows], "device": device}
    values = [r["serve_tok_s"] for r in rows if "serve_tok_s" in r]
    if len(values) >= 2:        # a rehearsal has counts and no rate
        bound = next(m["bound"] for m in common.cell_metrics(
            bench, "end_to_end", workload) if m["name"] == "serve_tok_s")
        spread = quartile_spread(values)
        out.update(serve_tok_s=values, median=statistics.median(values),
                   spread=spread, half_bound=bound / 2,
                   under_half_bound=spread <= bound / 2)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--repeat", type=int, default=0, metavar="N",
                    help="N seeds at one rate: the spread of serve_tok_s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attain", type=float, default=0.9)
    ap.add_argument("--backlog-slack", type=int, default=4)
    ap.add_argument("--out", default=None)
    ap.add_argument("--engine", action="append", default=[],
                    metavar="KEY=INT", help="try another engine size "
                    "than the traffic file's (sizing a cell)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import types
    import numpy as np
    bench, cell, cfg, job = common.load_cell(args.workload, args.rehearse)
    rates = [float(r) for r in args.rates.split(",")] if args.rates \
        else [job["arrivals"]["rate_per_s"]]
    if args.repeat:
        if len(rates) != 1:
            ap.error("--repeat takes one rate")
        rates *= args.repeat
    elif not args.rates:
        ap.error("--rates or --repeat")
    for kv in args.engine:
        key, value = kv.split("=")
        job["engine"][key] = int(value)
    devices, device = common.device_info(cell["chips"], args.rehearse)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    serve = common.load_module("runners", "serve")
    ctx = types.SimpleNamespace(
        seed=args.seed, seconds=args.seconds, trace=False,
        rehearse=args.rehearse, cell=cell, cfg=cfg, job=job,
        devices=devices, clock=common.Clock(T0),
        meter=common.CompileMeter(), checks=common.Checks(), trace_dir=None)
    router, engine, sizes = serve.build(ctx)
    serve.warm_up(ctx, router, sizes, np.random.default_rng(args.seed))
    common.say("warm", at_s=ctx.clock.now(), device=device,
               **ctx.meter.snapshot())

    rows = []
    for k, rate in enumerate(rates):
        job_r = dict(job, arrivals=dict(job["arrivals"], rate_per_s=rate),
                     # a full drain: what a rate leaves behind would eat
                     # the next rate's window uncounted
                     drain_s=max(job["drain_s"], 600))
        ctx.seed = args.seed + k
        recs, driver, marks = serve.run_window(ctx, router, sizes, job_r,
                                               args.seconds)
        s = serve.summarize(recs, args.seconds, job["limits"],
                            job_r["drain_s"])
        _, compiles_open, live_open = marks["open"]
        compiles_close, live_close, (peak, _) = marks["close"]
        row = {
            "engine": job["engine"], "rate_per_s": rate, "seed": ctx.seed,
            "seconds": args.seconds,
            "measured": s["measured"], "completed": s["completed"],
            "failed": s["failed"], "unfinished": s["unfinished"],
            "live_at_open": live_open, "live_at_close": live_close,
            "met_limits_share": s["met_limits_share"],
            "compiled_in_window": compiles_close - compiles_open,
            "memory_peak_bytes": peak,
            "sustained": bool(
                live_close <= live_open + args.backlog_slack
                and s["met_limits_share"] >= args.attain),
        }
        if not args.rehearse:       # times and rates: chip runs only
            row.update(
                serve_tok_s=serve.tokens_processed(
                    driver.steps, args.seconds) / args.seconds,
                completed_tok_s=s["tokens_in_window"] / args.seconds,
                ttft_p50_ms=common.percentile(s["ttft_ms"], 50),
                ttft_p90_ms=common.percentile(s["ttft_ms"], 90),
                tpot_p50_ms=common.percentile(s["tpot_ms"], 50),
                tpot_p90_ms=common.percentile(s["tpot_ms"], 90),
                late_p99_ms=common.percentile(s["late_ms"], 99),
                steps_per_s=len([st for st in driver.steps
                                 if 0 <= st[1] < args.seconds])
                / args.seconds)
        rows.append(row)
        common.say("rate", **row)
        if args.out:
            with open(os.path.join(ROOT, args.out), "a") as f:
                f.write(json.dumps({"workload": args.workload, **row})
                        + "\n")
    if args.repeat:
        print(json.dumps(repeat_summary(bench, args.workload, rows, device)))
        return 0
    sustained = [r["rate_per_s"] for r in rows if r["sustained"]]
    print(json.dumps({"workload": args.workload,
                      "knee_rate_per_s": max(sustained) if sustained
                      else None, "limits": job["limits"],
                      "attain": args.attain, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
