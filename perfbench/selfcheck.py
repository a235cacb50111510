#!/usr/bin/env python3
"""The benchmark checks itself; needs no chip and no program.

    python3 perfbench/selfcheck.py

1. The trace reduction (pbench/trace.py) on the recorded four-chip trace
   ``fixtures/tiny4.xplane.pb`` gives the values written down in
   ``fixtures/tiny4.expected.json``: window, busy seconds, idle share, each
   operation's own time, exposed-collective time, idle gaps by host span.
2. ``BENCHMARK.json`` keeps the contract's limits: allowed characters of
   every name and unit, the keys of every entry, one bound per end-to-end
   metric, ``paths``, ``chips``, ``run_seconds`` and the full check's time.
3. Every cell's configuration, traffic, builder, reference and runner files
   and every per-layer metric's reader exist; at most one cell in four (and
   always one) asks for 4 chips.
4. A configuration, a traffic mix, a cell and a per-layer metric can be
   added by new files and new entries only: a dummy of each is laid over a
   copy of the benchmark in a scratch directory and found by name.
"""

import json
import os
import re
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from pbench import common, trace, traffic     # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
FAILED = []


def check(ok, what):
    print(("ok   " if ok else "FAIL ") + what)
    if not ok:
        FAILED.append(what)


def close(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def check_trace():
    path = os.path.join(HERE, "fixtures", "tiny4.xplane.pb")
    want = common.load_json("fixtures", "tiny4.expected.json")
    check(os.path.getsize(path) < 1 << 20, "fixture trace is under 1 MB")
    t = trace.Trace(path)
    every, exposed = t.collective_seconds()
    got = {
        "devices": sorted(t.devices),
        "window_s": t.window_s, "busy_s": t.busy_s(),
        "idle_share": t.idle_share(),
        "collective_s": every, "exposed_collective_s": exposed,
        "step_span_idle_s": t.span_idle_s("perfbench.fixture_step"),
        "top_ops": t.top_ops(4), "idle_gaps": t.idle_gaps(4),
        "all_gather_s": t.op_seconds(r" all-gather\(")[0],
    }
    for key, value in want["values"].items():
        if isinstance(value, float):
            check(close(got[key], value), f"trace: {key} = {value!r}")
        elif key in ("top_ops", "idle_gaps"):
            same = len(got[key]) == len(value) and all(
                a[0] == b[0] and close(a[1], b[1])
                for a, b in zip(got[key], value))
            check(same, f"trace: {key} = {value!r}")
        else:
            check(got[key] == value, f"trace: {key} = {value!r}")
    check(trace.opcode("%psum.7 = bf16[]{:T(256)} all-reduce(bf16[] %x)")
          == "all-reduce", "trace: opcode of a plain instruction")
    check(trace.opcode("%w = (s32[]{:T(128)}, bf16[2]{0:T(8)(2,1)}) "
                       "while((s32[]) %x)") == "while",
          "trace: opcode after a tuple type")
    return got


def check_contract(bench, root):
    check(set(bench) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"},
          "BENCHMARK.json has exactly the contract's keys")
    check(os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 65536,
          "BENCHMARK.json is at most 64 KiB")
    check(1 <= len(bench["paths"]) <= 16 and all(
        PATH.match(p) and not p.startswith("/") and ".." not in p
        for p in bench["paths"]), "paths are relative and well-formed")
    check(len(bench["command"]) <= 32 and all(
        1 <= len(w) <= 200 and not w.startswith("/") and ".." not in w
        for w in bench["command"]), "command is a short list of words")
    rs = bench["run_seconds"]
    check(isinstance(rs, int) and 1 <= rs <= 51, "run_seconds in 1..51")
    full = (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200
    check(full <= 43200, f"a full check of 24 cells fits: {full} s <= 43200")
    for group, keys in KEYS.items():
        names = [e["name"] for e in bench[group]]
        check(len(set(names)) == len(names), f"{group}: names are unique")
        for e in bench[group]:
            extra = set(e) - keys - ({"workloads"} if group in (
                "end_to_end", "per_layer") else set())
            check(not extra and keys <= set(e),
                  f"{group}/{e['name']}: just the contract's keys")
            check(bool(NAME.match(e["name"])), f"{group}/{e['name']}: name")
    metrics = bench["end_to_end"] + bench["per_layer"]
    all_names = [m["name"] for m in metrics]
    check(len(set(all_names)) == len(all_names), "no two metrics share a name")
    cells = {w["name"] for w in bench["workloads"]}
    for m in metrics:
        check(bool(UNIT.match(m["unit"])), f"{m['name']}: unit {m['unit']}")
        check(m["better"] in ("lower", "higher"), f"{m['name']}: better")
        check(m["source"] in SOURCES, f"{m['name']}: source")
        check(set(m.get("workloads", [])) <= cells,
              f"{m['name']}: its workloads exist")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        check(m["source"] in ("host_clock", "device_trace"),
              f"{m['name']}: an end-to-end metric is taken by the benchmark")
        check(isinstance(m["bound"], float) and 0.01 <= m["bound"] <= 0.1,
              f"{m['name']}: one bound, between 1 % and 10 %")
    check("setup_s" in e2e and "workloads" not in e2e["setup_s"],
          "setup_s is an end-to-end metric of every cell")
    for m in bench["per_layer"]:
        check(m["moves"] in e2e, f"{m['name']}: moves an end-to-end metric")
        # no "workloads" means every cell, for the driver as for run.py
        check(set(m.get("workloads", cells))
              <= set(e2e.get(m["moves"], {}).get("workloads", cells)),
              f"{m['name']}: reported only where {m['moves']} is")
        check(1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"],
              f"{m['name']}: layer on one line")
    for w in bench["workloads"]:
        check(bool(NAME.match(w["config"])) and bool(NAME.match(w["traffic"])),
              f"{w['name']}: config and traffic are names")
        check(w["chips"] in (1, 4), f"{w['name']}: chips is 1 or 4")
        check(1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
              and "\t" not in w["why"], f"{w['name']}: why fits 200")
        own = [m for m in bench["end_to_end"] if m["name"] != "setup_s"
               and ("workloads" not in m or w["name"] in m["workloads"])]
        check(len(own) >= 1, f"{w['name']}: an end-to-end metric besides "
              "setup_s")
        layer = [m for m in bench["per_layer"]
                 if "workloads" not in m or w["name"] in m["workloads"]]
        check(len(layer) >= 1, f"{w['name']}: a per-layer metric")
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    check(len(set(pairs)) == len(pairs), "each (config, traffic) pair once")
    check(2 <= len(bench["workloads"]) <= 24, "2 to 24 cells")
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    check(four <= max(1, len(bench["workloads"]) // 4),
          f"{four} cell(s) on 4 chips: at most a quarter, and always one")
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        check(c["name"] in used, f"config {c['name']} is used by a cell")
        check(1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200,
              f"config {c['name']}: source and why fit 200")
        check(len(c["reduced"]) <= 16 and all(
            NAME.match(k) for k in c["reduced"]),
            f"config {c['name']}: reduced")
        check(any(c["file"].startswith(p + "/") for p in bench["paths"]),
              f"config {c['name']}: file under paths")


def check_files(bench, root):
    pb = os.path.join(root, bench["paths"][0])

    def there(*parts):
        return os.path.isfile(os.path.join(pb, *parts))

    for c in bench["configs"]:
        path = os.path.join(root, c["file"])
        check(os.path.isfile(path), f"{c['file']} exists")
        with open(path) as f:
            cfg = json.load(f)
        check(cfg["source"] == c["source"], f"{c['name']}: one source")
        check(cfg["reduced"] == c["reduced"], f"{c['name']}: one reduced")
        check(there("builders", cfg["builder"] + ".py"),
              f"{c['name']}: builder {cfg['builder']}")
        check(there("references", cfg["reference"] + ".py"),
              f"{c['name']}: plain reference {cfg['reference']}")
    for w in bench["workloads"]:
        check(there("traffic", w["traffic"] + ".json"),
              f"{w['name']}: traffic file")
        with open(os.path.join(pb, "traffic", w["traffic"] + ".json")) as f:
            job = json.load(f)
        check(there("runners", job["kind"] + ".py"),
              f"{w['name']}: runner {job['kind']}")
    for m in bench["per_layer"]:
        check(there("layer_metrics", m["name"] + ".py"),
              f"per-layer metric {m['name']}: reader file")
    for dirpath, _, files in os.walk(pb):
        for f in files:
            if "__pycache__" in dirpath or ".cache" in dirpath:
                continue
            rel = os.path.relpath(os.path.join(dirpath, f), root)
            check(bool(PATH.match(rel)), f"file name {rel}")


def check_traffic():
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 50.0},
           "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                          "min": 8, "max": 100},
           "output_len": {"dist": "uniform", "min": 2, "max": 9}}
    a = traffic.schedule(mix, 7, -1.0, 4.0, 1000)
    b = traffic.schedule(mix, 7, -1.0, 4.0, 1000)
    c = traffic.schedule(mix, 8, -1.0, 4.0, 1000)
    check(len(a) == len(b) and all(
        x["due_s"] == y["due_s"] and (x["prompt"] == y["prompt"]).all()
        for x, y in zip(a, b)), "traffic: the same seed, the same requests")
    check([x["due_s"] for x in a] != [x["due_s"] for x in c],
          "traffic: another seed, other requests")
    check(all(-1.0 <= x["due_s"] < 4.0 and 8 <= len(x["prompt"]) <= 100
              and 2 <= x["max_new_tokens"] <= 9 for x in a),
          "traffic: due times and lengths inside their limits")
    check(150 < len(a) < 350, f"traffic: about rate x span requests "
          f"({len(a)})")
    steady = dict(mix, arrivals=dict(mix["arrivals"], count="fixed"),
                  prompt_len=dict(mix["prompt_len"], stratified=True))
    runs = [traffic.schedule(steady, seed, -1.0, 4.0, 1000)
            for seed in range(6)]
    totals = [sum(len(x["prompt"]) for x in r) for r in runs]
    check(all(len(r) == 250 for r in runs),
          "traffic: a fixed count is rate x span whatever the seed")
    check(max(totals) - min(totals) < 0.01 * min(totals),
          f"traffic: stratified lengths offer the same tokens within 1 % "
          f"({min(totals)}..{max(totals)})")
    check(runs[0][0]["due_s"] != runs[1][0]["due_s"],
          "traffic: a fixed count still places arrivals by the seed")


def check_additions(bench):
    """New files + new entries only: lay a dummy configuration, traffic
    mix, cell and per-layer metric over a copy and find them by name."""
    with tempfile.TemporaryDirectory() as tmp:
        shutil.copytree(HERE, os.path.join(tmp, "perfbench"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        pb = os.path.join(tmp, "perfbench")
        before = {os.path.relpath(os.path.join(d, f), tmp): os.path.getmtime(
            os.path.join(d, f)) for d, _, fs in os.walk(pb) for f in fs}
        cfg = common.load_json("configs", "gpt2-medium.json")
        cfg["source"] = "https://example.org/dummy/config.json"
        with open(os.path.join(pb, "configs", "dummy-model.json"), "w") as f:
            json.dump(cfg, f)
        job = common.load_json("traffic", "chat.json")
        job["arrivals"]["rate_per_s"] = 1.0
        with open(os.path.join(pb, "traffic", "dummy-mix.json"), "w") as f:
            json.dump(job, f)
        with open(os.path.join(pb, "layer_metrics", "dummy_metric.py"),
                  "w") as f:
            f.write("def read(v):\n    return None\n")
        new = json.loads(json.dumps(bench))
        new["configs"].append({
            "name": "dummy-model", "source": cfg["source"],
            "file": "perfbench/configs/dummy-model.json", "reduced": [],
            "why": "selfcheck"})
        new["workloads"].append({
            "name": "dummy-cell", "config": "dummy-model",
            "traffic": "dummy-mix", "chips": 1, "why": "selfcheck"})
        new["per_layer"].append({
            "name": "dummy_metric", "unit": "ms", "better": "lower",
            "source": "program_counter", "layer": "entry (load generator)",
            "moves": "serve_tok_s", "workloads": ["dummy-cell"]})
        for m in new["end_to_end"]:
            if m["name"] in ("serve_tok_s", "ttft_p90_ms", "tpot_p90_ms"):
                m["workloads"] = m["workloads"] + ["dummy-cell"]
        with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
            json.dump(new, f)
        n_failed = len(FAILED)
        check_contract(new, tmp)
        check_files(new, tmp)
        after = {k: os.path.getmtime(os.path.join(tmp, k)) for k in before}
        check(before == after and len(FAILED) == n_failed,
              "additions: dummy config, mix, cell and metric found by "
              "name, no existing file edited")


def main():
    bench = common.benchmark_json()
    got = check_trace()
    if "--print-trace-values" in sys.argv:
        print(json.dumps(got, indent=1))
    check_contract(bench, ROOT)
    check_files(bench, ROOT)
    check_traffic()
    check_additions(bench)
    print(f"{len(FAILED)} failed")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
