#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``). With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics. Without a TPU, or with
another number of chips than the cell asks for, it exits 1 and prints no
result: there is no CPU run under a metric's name.

``--rehearse`` (never passed by the driver) runs the same control flow at
the configuration's and the job's ``rehearse`` sizes on whatever backend JAX
has; its last line carries counts and no metric.
"""

import time
T_PROCESS_START = time.perf_counter()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402
import types             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)      # pbench/ (the yardstick)
sys.path.insert(0, ROOT)      # deepspeed_tpu (the system under test)

from pbench import common      # noqa: E402


def layer_metrics(bench, workload, ctx, result):
    """Each per-layer metric of this cell through its own reader,
    ``perfbench/layer_metrics/<name>.py``; a reader that finds nothing to
    read returns None and the metric is left out of the line."""
    view = types.SimpleNamespace(
        trace=result.get("trace"), counters=result["counters"],
        sizes=result["sizes"], job=ctx.job, cfg=ctx.cfg, peaks=ctx.peaks,
        chips=len(ctx.devices), end_to_end=result["end_to_end"],
        workload=workload,
        # a rehearsal runs the readers and prints none of their numbers
        say=(lambda *a, **k: None) if ctx.rehearse else common.say)
    out = {}
    for m in common.cell_metrics(bench, "per_layer", workload):
        reader = common.load_module("layer_metrics", m["name"])
        value = reader.read(view)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    bench, cell, cfg, job = common.load_cell(args.workload, args.rehearse)
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])

    try:
        import deepspeed_tpu  # noqa: F401 - the system under test
    except ImportError as e:
        print(f"perfbench: the system under test is not in this checkout "
              f"({e})", file=sys.stderr)
        return 1
    devices, device = common.device_info(cell["chips"], args.rehearse)

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    ctx = types.SimpleNamespace(
        seed=args.seed, seconds=seconds, trace=bool(args.trace),
        rehearse=args.rehearse, cell=cell, cfg=cfg, job=job,
        devices=devices, clock=common.Clock(T_PROCESS_START),
        meter=common.CompileMeter(), checks=common.Checks(),
        # a rehearsal borrows the v5e's peaks so that the readers run; its
        # numbers are never printed
        peaks=common.peaks_for("TPU v5 lite" if args.rehearse
                               else device["kind"]),
        trace_dir=os.path.join(ROOT, ".cache", "perfbench_trace",
                               args.workload))
    common.say("start", workload=args.workload, seed=args.seed,
               seconds=seconds, trace=args.trace, device=device,
               cache_dir=cache_dir,
               cache_entries=len(os.listdir(cache_dir))
               if os.path.isdir(cache_dir) else 0)

    runner = common.load_module("runners", job["kind"])
    result = runner.run(ctx)
    result["end_to_end"] = {
        m["name"]: result["end_to_end"][m["name"]]
        for m in common.cell_metrics(bench, "end_to_end", args.workload)}
    common.say("done", wall_s=ctx.clock.now(), **ctx.meter.snapshot(),
               memory_stats=devices[0].memory_stats())

    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    line = {"correct": ctx.checks.correct, "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.trace:
        from pbench import trace as tracing
        tr = tracing.load(ctx.trace_dir, rehearse=args.rehearse)
        if tr is None or not tr.devices or tr.busy_s() <= 0:
            print("perfbench: the trace holds no device operation",
                  file=sys.stderr)
            return 1
        result["trace"] = tr
        metrics = layer_metrics(bench, args.workload, ctx, result)
        device["busy_s"], device["window_s"] = tr.busy_s(), tr.window_s
        line["breakdown"] = {"device_ops": tr.top_ops(10),
                             "idle_gaps": tr.idle_gaps(10)}
    else:
        metrics = {
            m["name"]: {"value": float(result["end_to_end"][m["name"]]),
                        "unit": m["unit"]}
            for m in bench["end_to_end"] if m["name"] in result["end_to_end"]}
    if args.rehearse:
        # counts only: no time or rate under a metric's name
        print(json.dumps({"rehearsal": True, "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "metrics_a_chip_run_would_report": sorted(metrics),
                          "device": {k: device[k] for k in
                                     ("platform", "kind", "count")}}))
        return 0 if line["correct"] else 1
    line["metrics"] = metrics
    line["device"] = device
    # every number compared beside its limit: the line's last key, and the
    # last lines of standard error
    line["checks"] = ctx.checks.made
    for made in ctx.checks.made:
        print(json.dumps(made, default=str), file=sys.stderr, flush=True)
    print(json.dumps(line, default=str))
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
