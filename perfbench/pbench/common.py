"""What every cell shares: file lookup by name, the device check, the
compile meter, earlier-line logging and the result line."""

import importlib.util
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

_LOADED = {}


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(folder, name):
    """``perfbench/<folder>/<name>.py`` as a module, found by name: a later
    PR adds a file and an entry, and edits nothing here."""
    path = os.path.join(BENCH_DIR, folder, name + ".py")
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            f"perfbench_{folder}_{name.replace('-', '_').replace('.', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench, workload):
    """-> (cell, configuration entry) of ``BENCHMARK.json``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"perfbench: no workload {workload!r}; "
                         f"have {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, config


def overlay(base, over):
    """``over`` laid on ``base``, one level into nested groups."""
    out = dict(base)
    for k, v in over.items():
        out[k] = {**base[k], **v} if isinstance(v, dict) \
            and isinstance(base.get(k), dict) else v
    return out


def load_cell(workload, rehearse=False):
    """-> (BENCHMARK.json, the cell, its configuration file, its traffic
    file), the last two at their ``rehearse`` sizes when asked."""
    bench = benchmark_json()
    cell, config = find_cell(bench, workload)
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    job = load_json("traffic", cell["traffic"] + ".json")
    if rehearse:
        cfg, job = overlay(cfg, cfg["rehearse"]), overlay(job,
                                                          job["rehearse"])
    return bench, cell, cfg, job


def cell_metrics(bench, group, workload):
    """Metrics of ``group`` ('end_to_end' | 'per_layer') this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def say(line, **fields):
    """One JSON object on an earlier line of stdout."""
    print(json.dumps({"perfbench": line, **fields}, default=str), flush=True)


class CheckFailed(RuntimeError):
    pass


class Checks:
    """Collects what decides ``correct``; every check is printed as it is
    made, and ``made`` keeps each with the numbers it compared for the
    result line's last key and the last lines of standard error."""

    def __init__(self):
        self.failed = []
        self.made = []

    def require(self, ok, what, **fields):
        ok = bool(ok)
        say("check", ok=ok, what=what, **fields)
        self.made.append({"ok": ok, "what": what, **fields})
        if not ok:
            self.failed.append(what)
        return ok

    @property
    def correct(self):
        return not self.failed


class CompileMeter:
    """Seconds and count of XLA compile-or-fetch, and persistent-cache hits
    (``jax.monitoring``; copied from chip_smoke.CompileMeter). ``count`` is
    what 'compilations inside the window' reads."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.count = 0
        self.hits = 0
        self.requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def snapshot(self):
        return {"compile_or_fetch_s": self.secs, "programs": self.count,
                "cache_hits": self.hits, "cache_requests": self.requests}


def device_info(chips, rehearse):
    """The devices this cell runs on, or exit non-zero: no CPU run carries
    a metric's name. -> (devices, info dict for the result line)."""
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if not rehearse and info["platform"] != "tpu":
        print(f"perfbench: needs a TPU, JAX found {info} (see --rehearse)",
              file=sys.stderr)
        raise SystemExit(1)
    if len(devices) != chips:
        print(f"perfbench: the cell asks for {chips} chip(s), JAX found "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(1)
    return devices, info


def memory_peak_bytes(devices):
    """Peak bytes on the fullest chip: the larger of what the allocator saw
    (``memory_stats()['peak_bytes_in_use']``, 0 where the backend reports
    none, as XLA:CPU does) and what the largest loaded program needs while
    it runs. On this TPU backend the first counter equals the resident
    arrays and misses every program's temporaries (PR 21 and PR 22 both
    measured it: 5.0 GB against a step that needs 13.1), so alone it would
    understate a full chip. -> (peak, {both counters})."""
    stats = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in devices))
    program = 0
    for ex in devices[0].client.live_executables():
        try:
            m = ex.get_compiled_memory_stats()
        except Exception:  # noqa: BLE001 - a backend without the numbers
            continue
        # per device, as Compiled.memory_analysis() counts: arguments +
        # temporaries + the outputs that are not donated arguments
        program = max(program, m.argument_size_in_bytes
                      + m.temp_size_in_bytes + m.output_size_in_bytes
                      - m.alias_size_in_bytes)
    return max(stats, program), {"peak_bytes_in_use": stats,
                                 "largest_program_bytes": int(program)}


def peaks_for(device_kind):
    """The published peaks of this exact ``device_kind``; an unknown device
    is an error, not a default."""
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise CheckFailed(f"no peaks for device_kind {device_kind!r} in "
                          f"perfbench/peaks.json")
    return table[device_kind]


def percentile(values, q):
    """q-th percentile by linear interpolation; None of nothing."""
    import numpy as np
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


class Clock:
    """Seconds since the process began (``run.py`` passes its first
    reading), on the monotonic performance counter."""

    def __init__(self, t_process_start):
        self.t0 = t_process_start

    def now(self):
        return time.perf_counter() - self.t0
