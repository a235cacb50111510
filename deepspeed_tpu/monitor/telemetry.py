"""Always-on pod telemetry: MFU/goodput step analytics, cluster
aggregation with straggler detection, and on-demand XLA profiling.

This is the layer that turns the repo's one-shot debugging tools into
production observability (ISSUE 9 tentpole):

  * **Step analytics** — per-step wall times ring-buffered on the host
    (no device sync: the tput-timer lesson from round 2 — in steady
    state dispatch-queue backpressure makes the host wall time track
    the device step time); every ``interval_steps`` the collector
    computes p50/p99 step time, tokens/s/chip, MFU (step FLOPs from
    ``Compiled.cost_analysis()`` — the engine captures them once,
    lazily, from the program that actually runs), and the
    compute-vs-exposed-comm split (the PR-3 ``overlap_report`` HLO
    parse: collectives with no async start/done pair are comm the
    schedule left exposed), and writes the lot into the MonitorMaster
    fan-out under the ``Train/Telemetry/*`` tags of
    ``monitor/tag_schema.py``.
  * **Cluster aggregation** — per-host metric dicts exchanged over one
    of two transports (the hot-tier discipline, checkpoint_engine/
    hot_tier.py): ``allgather`` rides the one-device-per-process mesh
    (comm.allgather_bytes — in-caller, because collectives must never
    interleave across threads) and ``fs`` exchanges JSON files under a
    shared dir (the virtual-mesh/bench transport — safe on the pool).
    Rank 0 reports pod-wide p50/p99 step time and the straggler delta
    (slowest host's mean minus the pod median, with the host id).
  * **Goodput** — productive wall time vs the overhead the engine
    reports (checkpoint save/restore latency, reshape, restarts), one
    ``goodput_pct`` number the elastic chaos suite can assert on.
  * **On-demand profiling** — a ``jax.profiler`` server on
    ``profile_port`` (attach xprof/tensorboard to a live pod), plus
    step-ranged trace capture armed by ``DSTPU_PROFILE_STEPS=a:b`` or
    by dropping a ``PROFILE`` trigger file into the flight-recorder
    dir mid-run — a live incident is debuggable without a relaunch.

Everything that is not a deque-append runs off the step critical path:
flushes do fixed small-array math, costs are captured once, fs gathers
and opportunistic flight dumps run on a single background worker (the
async-checkpoint pool pattern).
"""

import json
import os
import threading
import time
from collections import deque

import numpy as np

from ..utils.logging import logger
from .flight_recorder import FlightRecorder
from .tag_schema import KERNEL_SHARES, TAG_SCHEMA

# --------------------------------------------------------------- peak flops
# bf16 peak per chip by device_kind substring (first match wins; order
# matters: 'v5p' before the bare 'v5'/'v5 lite' family). A device the table
# does not know (a CPU dev container, a future TPU) has NO peak: MFU is then
# not reported, never built on another chip's denominator.
_PEAK_BF16 = (
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12), ("v5", 197e12),
    ("v4", 275e12), ("v3", 123e12),
)


def peak_flops_per_chip(device_kind):
    """bf16 peak FLOP/s of one chip of ``device_kind``, or None when the
    table does not know it. ``DSTPU_PEAK_FLOPS`` overrides (exact hardware
    the operator knows better than the table)."""
    env = os.environ.get("DSTPU_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            logger.warning(f"DSTPU_PEAK_FLOPS={env!r} is not a float; "
                           f"using the device-kind table")
    kind = str(device_kind or "").lower()
    for key, peak in _PEAK_BF16:
        if key in kind:
            return peak
    return None


def percentile(samples, p):
    """Guarded percentile: None on an empty window (never a NaN in an
    artifact)."""
    if not samples:
        return None
    return float(np.percentile(np.asarray(samples, np.float64), p))


def span(name, **stats):
    """A host span on the profiler's clock: a ``TraceAnnotation`` that
    lands on the ``/host:CPU`` plane of the same ``.xplane.pb`` as the
    device's operations. The profiler being on is the only switch — with
    no capture running this is a no-op ``TraceMe``. Stats are fixed when
    the span opens. Names live in ``tag_schema.SPAN_SCHEMA`` (linted)."""
    import jax
    return jax.profiler.TraceAnnotation(name, **stats)


def collective_breakdown(n_collectives, async_pairs):
    """(logical_collectives, exposed_comm_pct) from an
    ``overlap_report``'s entry counts. ``n_collectives`` counts HLO
    entries and an async collective is TWO entries (-start + -done) but
    ONE logical collective — so logical = n - pairs, and the exposed
    share divides the unpaired (synchronous) ops by the LOGICAL count
    (dividing by the entry count would underreport exposure: 1 sync +
    1 async must read 50%, not 33%)."""
    n = int(n_collectives)
    pairs = int(async_pairs)
    logical = n - pairs
    exposed = (100.0 * max(0, n - 2 * pairs) / logical
               if logical > 0 else 0.0)
    return logical, exposed


# ---------------------------------------------------------- cluster math
def aggregate_cluster(by_host, order=None):
    """Pod-wide stats from per-host metric dicts (each carrying
    ``mean_step_ms``): p50/p99 across hosts, and the straggler delta —
    the slowest host's mean step time minus the pod median, with the
    host's id and ring index. Pure math so the 2-host virtual-mesh
    bench and the unit tests exercise exactly what a pod runs.

    ``order`` is the ring order the ``straggler_host`` index is
    reported in (pass the aggregator's ``peers``); without it hosts
    sort lexically — fine for named hosts, WRONG for string process
    ids on pods >= 10 hosts ('10' sorts before '2'), which is why the
    production caller always passes the ring."""
    if order is not None:
        hosts = [h for h in order
                 if by_host.get(h)
                 and by_host[h].get("mean_step_ms") is not None]
    else:
        hosts = sorted(h for h, m in by_host.items()
                       if m and m.get("mean_step_ms") is not None)
    if not hosts:
        return None
    means = [float(by_host[h]["mean_step_ms"]) for h in hosts]
    med = float(np.median(means))
    worst = int(np.argmax(means))
    node = hosts[worst]
    # straggler_host is documented as the RING index — index into the
    # full order, not into the filtered list, which diverges from the
    # ring as soon as any host's metrics are missing for a round
    return {
        "hosts": len(hosts),
        "cluster_step_ms_p50": round(percentile(means, 50), 3),
        "cluster_step_ms_p99": round(percentile(means, 99), 3),
        "straggler_delta_ms": round(means[worst] - med, 3),
        "straggler_host": (order.index(node) if order is not None
                           else worst),
        "straggler_node": node,
    }


class ClusterAggregator:
    """Per-host metric exchange. Transport resolution:

      * ``fs``        — a shared dir + explicit peer ring
                        (``DSTPU_TELEM_DIR`` + ``DSTPU_TELEM_NODE`` /
                        ``DSTPU_TELEM_PEERS``, falling back to the hot
                        tier's ``DSTPU_HOT_NODE``/``DSTPU_HOT_PEERS``
                        ring): each node atomically publishes
                        ``telem-{node}.json`` and reads its peers'.
                        Pure file IO — safe on a background thread.
      * ``allgather`` — a real multi-process jax world: one
                        length-padded byte allgather over the process
                        mesh (comm.allgather_bytes). COLLECTIVE: must
                        run in-caller at a point every process reaches
                        (the flush boundary), never on a side thread.
      * ``None``      — single process, no ring: local-only telemetry.
    """

    def __init__(self, node=None, peers=None, root=None):
        import jax
        env = os.environ
        self.root = root or env.get("DSTPU_TELEM_DIR") or None
        node = node or env.get("DSTPU_TELEM_NODE") \
            or env.get("DSTPU_HOT_NODE")
        peers_s = (",".join(peers) if peers
                   else env.get("DSTPU_TELEM_PEERS")
                   or env.get("DSTPU_HOT_PEERS"))
        self.nprocs = jax.process_count()
        if self.root and peers_s:
            self.transport = "fs"
            self.peers = [p for p in peers_s.split(",") if p]
            self.node = node or str(jax.process_index())
        elif self.nprocs > 1:
            self.transport = "allgather"
            self.peers = [str(i) for i in range(self.nprocs)]
            self.node = str(jax.process_index())
        else:
            self.transport = None
            self.peers = [node or "0"]
            self.node = node or "0"

    @property
    def is_root(self):
        """Whether this node reports the pod-wide aggregates (rank 0 /
        first ring member)."""
        return not self.peers or self.node == self.peers[0]

    # ----------------------------------------------------------- exchange
    def _fs_path(self, node):
        return os.path.join(self.root, f"telem-{node}.json")

    def _fs_publish(self, metrics):
        os.makedirs(self.root, exist_ok=True)
        path = self._fs_path(self.node)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(metrics, f)
        os.replace(tmp, path)

    def _fs_read(self):
        out = {}
        for p in self.peers:
            try:
                with open(self._fs_path(p), encoding="utf-8") as f:
                    out[p] = json.load(f)
            except (OSError, ValueError):
                pass
        return out

    def gather(self, metrics, wait_s=0.0):
        """Publish this host's ``metrics`` and return ``{node: metrics}``
        across the ring (stale peer entries included — a straggling
        publisher is itself signal). ``wait_s`` > 0 (fs transport only)
        polls until every peer has published this round's step."""
        if self.transport is None:
            return {self.node: metrics}
        if self.transport == "allgather":
            from ..comm import comm
            blobs = comm.allgather_bytes(json.dumps(metrics).encode())
            if blobs is None:
                return {self.node: metrics}
            out = {}
            for i, b in enumerate(blobs):
                try:
                    out[self.peers[i]] = json.loads(b.decode())
                except (ValueError, IndexError):
                    pass
            return out
        self._fs_publish(metrics)
        step = metrics.get("step", 0)
        deadline = time.monotonic() + max(0.0, wait_s)
        while True:
            got = self._fs_read()
            fresh = [p for p in self.peers
                     if got.get(p, {}).get("step", -1) >= step]
            if len(fresh) == len(self.peers) \
                    or time.monotonic() >= deadline:
                return got
            time.sleep(0.05)


# ---------------------------------------------------------- xla profiling
_PROFILE_SERVERS = set()


def _maybe_start_server(port):
    """Start the jax profiler server once per process; attach xprof /
    tensorboard to ``localhost:{port}`` on a live pod."""
    try:
        port = int(port or 0)
    except (TypeError, ValueError):  # e.g. DSTPU_PROFILE_PORT=xprof
        logger.warning(
            f"telemetry: ignoring non-numeric profiler port {port!r}")
        return False
    if port <= 0:
        return False
    if port in _PROFILE_SERVERS:
        return True
    try:
        import jax
        jax.profiler.start_server(port)
        _PROFILE_SERVERS.add(port)
        logger.info(f"telemetry: jax profiler server on :{port}")
        return True
    except Exception as e:  # noqa: BLE001 - observability never fatal
        logger.warning(f"telemetry: profiler server on :{port} "
                       f"unavailable: {e}")
        return False


class ProfilerControl:
    """Step-ranged trace capture for live incidents.

    Armed two ways: ``DSTPU_PROFILE_STEPS=a:b`` at launch (capture
    steps [a, b)), or a ``PROFILE`` trigger file dropped into the
    flight-recorder dir mid-run (content = step count, default 5;
    checked only at flush boundaries so the step path never stats a
    file). Traces land under ``{logdir}/xprof`` for
    ``tensorboard --logdir`` / xprof.

    ``on_trace(logdir, steps, step)`` fires after a capture stops —
    the step-anatomy hook: the collector hands the finished trace to
    ``profiling.step_trace`` + the planner reconciler. Advisory: a
    callback failure warns and never re-raises into the step path."""

    def __init__(self, port=0, logdir=None, flight=None, on_trace=None):
        self.server = _maybe_start_server(
            port or os.environ.get("DSTPU_PROFILE_PORT", 0))
        self.logdir = logdir
        self.flight = flight
        self.on_trace = on_trace
        self.range = self._parse(os.environ.get("DSTPU_PROFILE_STEPS"))
        self.active = False
        self._trace_meta = None        # (logdir, start_step) while active

    @staticmethod
    def _parse(spec):
        if not spec:
            return None
        try:
            a, b = (int(v) for v in spec.split(":"))
        except ValueError:
            logger.warning(f"DSTPU_PROFILE_STEPS={spec!r} is not 'a:b'; "
                           f"ignored")
            return None
        if not 0 <= a < b:
            logger.warning(f"DSTPU_PROFILE_STEPS needs 0 <= a < b, got "
                           f"{(a, b)}; ignored")
            return None
        return (a, b)

    def _record(self, kind, **data):
        if self.flight is not None:
            self.flight.record(kind, **data)

    def on_step(self, step):
        """Hot-path hook: two int compares when disarmed."""
        r = self.range
        if r is None:
            return
        try:
            import jax
            if not self.active and r[0] <= step < r[1]:
                # resolve at start time: the flight-recorder root may
                # only be known after the first save_checkpoint
                base = self.logdir or (
                    self.flight._resolved_root()
                    if self.flight is not None else ".")
                logdir = os.path.join(base, "xprof")
                jax.profiler.start_trace(logdir)
                self.active = True
                self._trace_meta = (logdir, step)
                self._record("profile_start", step=step, logdir=logdir)
            elif self.active and step >= r[1]:
                jax.profiler.stop_trace()
                self.active = False
                self.range = None
                self._record("profile_stop", step=step)
                meta, self._trace_meta = self._trace_meta, None
                if self.on_trace is not None and meta is not None:
                    try:
                        self.on_trace(meta[0], max(1, step - meta[1]),
                                      step)
                    except Exception as e:  # noqa: BLE001 - advisory
                        logger.warning(
                            f"telemetry: trace callback failed "
                            f"({type(e).__name__}: {e})")
        except Exception as e:  # noqa: BLE001 - never break the step
            logger.warning(f"telemetry: profiler capture failed: {e}")
            self.active = False
            self.range = None
            self._trace_meta = None

    def check_trigger(self, root, step):
        """Flush-boundary check for the ``PROFILE`` trigger file."""
        if not root or self.range is not None:
            return
        path = os.path.join(root, "PROFILE")
        try:
            if not os.path.exists(path):
                return
            with open(path, encoding="utf-8") as f:
                text = f.read().strip()
            os.remove(path)
            n = int(text) if text else 5
            self.range = (step + 1, step + 1 + max(1, n))
            self._record("profile_armed", start=self.range[0],
                         stop=self.range[1])
        except (OSError, ValueError):
            pass


# ------------------------------------------------------------- training side
class TelemetryCollector:
    """The engine-facing collector. Hot path = :meth:`on_step` (deque
    appends + one modulo); everything heavier happens at
    ``interval_steps`` boundaries, with file IO on the background
    worker. ``monitor`` is the MonitorMaster fan-out (may be disabled —
    the collector still computes, so ``snapshot()`` serves benches and
    tests without any writer configured)."""

    def __init__(self, cfg, monitor=None, n_devices=1, device_kind="",
                 costs_fn=None, node=None):
        self.cfg = cfg
        self.monitor = monitor
        self.n_devices = max(1, int(n_devices))
        self.interval = max(1, int(cfg.interval_steps))
        self.flight = FlightRecorder(size=cfg.flight_recorder_size,
                                     node=node)
        self.flight.set_root(cfg.flightrec_dir
                             or os.environ.get("DSTPU_FLIGHTREC_DIR"))
        self.peak_flops = peak_flops_per_chip(device_kind)
        self.cluster = (ClusterAggregator()
                        if cfg.resolve_cluster_agg() else None)
        self.profiler = ProfilerControl(port=cfg.profile_port,
                                        flight=self.flight,
                                        on_trace=self._on_trace_ready)
        self._reconcile_fn = None
        self._reconcile_warned = False
        self._pending_reconcile_events = None
        self._costs_fn = costs_fn
        self._costs = None
        self._costs_tried = False
        # interval window (host wall times, ms) + cumulative goodput
        self._step_ms = deque(maxlen=4096)
        self._tokens = 0
        self._t0 = time.perf_counter()
        self._overhead_s = {}
        self._warned_tags = set()
        self._pending_cluster_events = None
        self._pipeline = None
        self.last = {}
        # single background worker (created lazily at the first flush
        # that needs it): fs gathers + opportunistic flight dumps ride
        # here (the async-checkpoint-pool pattern); real collectives
        # never do
        self._pool = None
        self._futs = []
        self._closed = False
        # fired fault-injection points land in the flight ring. The
        # registration is WEAK: the injector is process-global, so a
        # bound-method listener would pin every telemetry-enabled
        # engine (collector -> costs_fn -> engine) for the life of the
        # process; a dead collector's hook unregisters itself instead.
        import weakref
        from ..utils import fault_injection
        wself = weakref.ref(self)

        def _fault_hook(point, injected):
            s = wself()
            if s is None:
                fault_injection.remove_listener(_fault_hook)
                return
            s._on_fault(point, injected)

        self._fault_listener = _fault_hook
        fault_injection.add_listener(_fault_hook)

    # ------------------------------------------------------------ hot path
    def on_step(self, step, wall_s, tokens=0):
        """Called once per train_batch with the host wall time. No
        device sync, no IO."""
        self._step_ms.append(wall_s * 1e3)
        self._tokens += int(tokens)
        self.flight.record("step", step=int(step),
                           ms=round(wall_s * 1e3, 3))
        self.profiler.on_step(step)
        if step % self.interval == 0 and len(self._step_ms) > 0:
            self._flush(step)

    def reset_window(self):
        """Restart the measurement window (samples AND their token
        count — clearing one without the other would bias
        tokens_per_sec_chip). Benches call this after warmup so compile
        time never poses as a slow step."""
        self._step_ms.clear()
        self._tokens = 0

    def set_pipeline(self, info):
        """Arm the per-flush pipeline metrics (engine.pipeline_report():
        stages/microbatches/ticks, analytic bubble fraction, host
        staging payload). None disarms."""
        self._pipeline = info

    def set_reconcile(self, fn):
        """Arm modeled-vs-measured reconciliation: ``fn(trace_dir,
        steps)`` -> a ``DriftReport.summary()`` dict (or None) whenever
        ``ProfilerControl`` finishes a step-ranged capture. The engine
        wires its ``_telemetry_reconcile`` here; None disarms."""
        self._reconcile_fn = fn

    def _on_trace_ready(self, trace_dir, steps, step):
        """ProfilerControl's stop hook. Trace parsing reads gzipped
        JSON off disk — background-pool work, never the step path."""
        if self._reconcile_fn is None:
            return
        self._submit(self._reconcile_round, trace_dir, steps, step)

    def _reconcile_round(self, trace_dir, steps, step):
        """Parse + reconcile one finished capture (pool side). Emits
        nothing directly: events park for the next main-thread flush
        (monitor writers are not thread-safe) and the summary lands in
        ``self.last`` + the flight recorder's crash context."""
        try:
            summary = self._reconcile_fn(trace_dir, steps)
        except Exception as e:  # noqa: BLE001 - reconcile is advisory
            if not self._reconcile_warned:
                self._reconcile_warned = True
                logger.warning(f"telemetry: reconcile failed "
                               f"({type(e).__name__}: {e})")
            return
        if summary is None:
            if not self._reconcile_warned:
                self._reconcile_warned = True
                logger.warning(
                    "telemetry: trace produced no step decomposition; "
                    "reconcile skipped (platform may not emit XLA op "
                    "tracks)")
            return
        self.last = dict(self.last, reconcile=summary)
        self.flight.record("reconcile", step=int(step),
                           top_term=summary.get("top_term", ""),
                           top_drift_ms=summary.get("top_drift_ms", 0),
                           wall_err_pct=summary.get("wall_err_pct", 0))
        self.flight.set_context("reconcile", summary)
        self._pending_reconcile_events = [
            ("Train/Reconcile/wall_err_pct",
             summary.get("wall_err_pct", 0.0), step),
            ("Train/Reconcile/top_drift_ms",
             summary.get("top_drift_ms", 0.0), step),
            ("Train/Reconcile/top_drift_term",
             summary.get("top_term_index", -1), step),
            ("Train/Reconcile/coverage_pct",
             summary.get("coverage_pct", 0.0), step),
        ]

    # ------------------------------------------------------------ feedback
    def note_overhead(self, kind, seconds):
        """Non-productive wall time (checkpoint_save /
        checkpoint_restore / reshape / restart) for goodput
        accounting."""
        self._overhead_s[kind] = self._overhead_s.get(kind, 0.0) \
            + float(seconds)
        self.flight.record(kind, s=round(float(seconds), 4))

    def on_restore(self, tier, tag, seconds):
        """A checkpoint load completed: which tier served it is the
        fact the flight recorder must carry into the next crash."""
        self.note_overhead("checkpoint_restore", seconds)
        self.flight.record("restore", tier=str(tier), tag=str(tag))

    def record_event(self, kind, **data):
        self.flight.record(kind, **data)

    def on_crash(self, exc):
        # SystemExit is a DELIBERATE exit, not a crash: the preempt
        # drain raises it after recording 'preempted' and dumping with
        # that reason — a crash-dump here would overwrite the orderly
        # tail the elastic agent reads to classify the death
        if isinstance(exc, SystemExit):
            return
        self.flight.crash(exc)

    def _on_fault(self, point, injected):
        self.flight.record("fault_point", point=point,
                           injected=bool(injected))

    # -------------------------------------------------------------- flush
    def _emit(self, events):
        if self.monitor is None or not getattr(self.monitor, "enabled",
                                               False):
            return
        for tag, _, _ in events:
            if tag not in TAG_SCHEMA and tag not in self._warned_tags:
                self._warned_tags.add(tag)
                logger.warning(
                    f"telemetry: emitting tag {tag!r} that is missing "
                    f"from monitor/tag_schema.py TAG_SCHEMA — register "
                    f"it (the schema lint will fail until you do)")
        self.monitor.write_events(events)

    def _capture_costs(self):
        """One-time step-cost capture (flops + collective schedule) from
        the engine's compiled program. In-caller at the first flush: a
        single extra XLA compile amortized over the whole run (and the
        compile cache makes it cheap when warm)."""
        if self._costs_tried or self._costs_fn is None:
            return
        self._costs_tried = True
        try:
            self._costs = self._costs_fn()
        except Exception as e:  # noqa: BLE001 - telemetry never fatal
            logger.warning(f"telemetry: step-cost capture failed "
                           f"({type(e).__name__}: {e}); MFU/comm "
                           f"breakdown unavailable")
            self._costs = None

    def goodput_pct(self):
        elapsed = max(1e-9, time.perf_counter() - self._t0)
        overhead = sum(self._overhead_s.values())
        return max(0.0, min(100.0, 100.0 * (1.0 - overhead / elapsed)))

    def _flush(self, step):
        # cluster aggregates a background fs gather finished since the
        # last flush: emitted HERE, on the main thread — the monitor
        # writers (csv file map, wandb, TB) are not thread-safe, so
        # write_events never runs on the pool (single-slot handoff,
        # latest wins; attribute swap is atomic under the GIL)
        pending, self._pending_cluster_events = \
            self._pending_cluster_events, None
        if pending:
            self._emit(pending)
        pending, self._pending_reconcile_events = \
            self._pending_reconcile_events, None
        if pending:
            self._emit(pending)
        samples = list(self._step_ms)
        self._step_ms.clear()
        tokens, self._tokens = self._tokens, 0
        window_s = sum(samples) / 1e3
        mean_ms = window_s * 1e3 / len(samples)
        self._capture_costs()

        snap = {
            "step": int(step),
            "steps_in_window": len(samples),
            "mean_step_ms": round(mean_ms, 3),
            "step_time_ms_p50": round(percentile(samples, 50), 3),
            "step_time_ms_p99": round(percentile(samples, 99), 3),
            "goodput_pct": round(self.goodput_pct(), 3),
            "overhead_s": {k: round(v, 4)
                           for k, v in self._overhead_s.items()},
            "elastic_generation": int(
                os.environ.get("ELASTIC_GENERATION", 0) or 0),
            "peak_flops_per_chip": self.peak_flops,
        }
        if tokens and window_s > 0:
            snap["tokens_per_sec_chip"] = round(
                tokens / window_s / self.n_devices, 1)
        c = self._costs or {}
        if c.get("flops_per_chip") and self.peak_flops:
            snap["mfu_pct"] = round(
                100.0 * c["flops_per_chip"]
                / (mean_ms / 1e3) / self.peak_flops, 3)
            snap["flops_source"] = c.get("source", "hlo")
        if c.get("collectives") is not None:
            snap["collectives"] = int(c["collectives"])
            snap["exposed_comm_pct"] = round(
                float(c.get("exposed_comm_pct", 0.0)), 3)

        events = [
            ("Train/Telemetry/step_time_ms_p50",
             snap["step_time_ms_p50"], step),
            ("Train/Telemetry/step_time_ms_p99",
             snap["step_time_ms_p99"], step),
            ("Train/Telemetry/goodput_pct", snap["goodput_pct"], step),
        ]
        if "tokens_per_sec_chip" in snap:
            events.append(("Train/Telemetry/tokens_per_sec_chip",
                           snap["tokens_per_sec_chip"], step))
        if "mfu_pct" in snap:
            events.append(("Train/Telemetry/mfu_pct", snap["mfu_pct"],
                           step))
        if "collectives" in snap:
            events.append(("Train/Telemetry/collectives",
                           snap["collectives"], step))
            events.append(("Train/Telemetry/exposed_comm_pct",
                           snap["exposed_comm_pct"], step))
        if self._pipeline is not None:
            p = self._pipeline
            snap["pipeline"] = dict(
                p, steady_tick_ms=round(
                    mean_ms / max(1, p.get("ticks", 1)), 4))
            events.append(("Train/Pipeline/bubble_pct",
                           p["bubble_pct"], step))
            events.append(("Train/Pipeline/steady_tick_ms",
                           snap["pipeline"]["steady_tick_ms"], step))
            events.append(("Train/Pipeline/offload_bytes_per_step",
                           p.get("offload_bytes_per_step", 0), step))
        self._emit(events)

        if self.cluster is not None:
            metrics = {"node": self.cluster.node, "step": int(step),
                       "mean_step_ms": snap["mean_step_ms"],
                       "p99_step_ms": snap["step_time_ms_p99"],
                       "goodput_pct": snap["goodput_pct"]}
            if self.cluster.transport == "allgather":
                # collective transport: in-caller (every process flushes
                # at the same step boundary; a side thread could
                # interleave with the training collectives)
                self._cluster_round(metrics, step, emit_now=True)
            elif self.cluster.transport == "fs":
                self._submit(self._cluster_round, metrics, step, False)
        # opportunistic black-box dump: a SIGKILL'd/hung worker still
        # leaves a record at most one interval old
        if self.flight.root:
            self._submit(self.flight.dump, "interval")
            self.profiler.check_trigger(self.flight.root, step)
        # carry the most recent cluster aggregate across flushes (a
        # pool-side round attaches it asynchronously; a fresh window
        # must not blank it from snapshot())
        if "cluster" in self.last:
            snap.setdefault("cluster", self.last["cluster"])
        # ...and the latest reconcile drift summary, same discipline
        if "reconcile" in self.last:
            snap.setdefault("reconcile", self.last["reconcile"])
        self.last = snap

    def _cluster_round(self, metrics, step, emit_now):
        """Gather + aggregate one round. ``emit_now`` only when running
        in-caller (allgather transport); a pool-side round parks its
        events for the next main-thread flush instead — monitor
        writers are not thread-safe."""
        try:
            got = self.cluster.gather(metrics)
            # ring order, not lexical sort: string process ids ('10'
            # before '2') would misnumber the straggler on >=10 hosts
            agg = aggregate_cluster(got, order=self.cluster.peers)
            if agg is None:
                return
            self.last = dict(self.last, cluster=agg)
            if not self.cluster.is_root:
                return
            events = [
                ("Train/Telemetry/cluster_step_ms_p50",
                 agg["cluster_step_ms_p50"], step),
                ("Train/Telemetry/cluster_step_ms_p99",
                 agg["cluster_step_ms_p99"], step),
                ("Train/Telemetry/straggler_delta_ms",
                 agg["straggler_delta_ms"], step),
                ("Train/Telemetry/straggler_host",
                 agg["straggler_host"], step),
                ("Train/Telemetry/cluster_hosts", agg["hosts"], step),
            ]
            if emit_now:
                self._emit(events)
            else:
                self._pending_cluster_events = events
        except Exception as e:  # noqa: BLE001 - aggregation advisory
            logger.warning(f"telemetry: cluster aggregation failed: {e}")

    # ------------------------------------------------------------ plumbing
    def _submit(self, fn, *args):
        if self._closed:
            return
        if self._pool is None:
            import concurrent.futures as futures
            self._pool = futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="dstpu-telemetry")
        self._futs = [f for f in self._futs if not f.done()]
        try:
            self._futs.append(self._pool.submit(fn, *args))
        except RuntimeError:   # pool shut down under our feet
            pass

    def drain(self):
        """Block until queued background work (fs gathers, dumps) is
        done — tests and benches read ``snapshot()`` after this."""
        for f in list(self._futs):
            try:
                f.result(timeout=30)
            except Exception:  # noqa: BLE001 - advisory work
                pass
        self._futs = []

    def snapshot(self):
        """The most recent flush's metrics (plus live goodput)."""
        out = dict(self.last)
        out["goodput_pct_live"] = round(self.goodput_pct(), 3)
        return out

    def close(self):
        if self._closed:
            return
        self._closed = True
        from ..utils import fault_injection
        fault_injection.remove_listener(self._fault_listener)
        self.drain()
        if self._pool is not None:
            self._pool.shutdown(wait=False)


# -------------------------------------------------------------- serving side
class _ReqTimes:
    __slots__ = ("t_put", "waited_s", "t_first", "t_last", "pending")

    def __init__(self, t_put, waited_s=0.0):
        self.t_put = t_put
        self.waited_s = waited_s     # queued upstream (router) before put
        self.t_first = None
        self.t_last = None
        self.pending = 0


class ServingTelemetry:
    """Per-request TTFT/TPOT accounting for the v2 serving engine.

    TPOT is dispatch-amortized: the engine produces tokens in multi-step
    dispatches, so per-token deltas inside one dispatch are meaningless
    — tokens accumulate as ``pending`` and the wall time since the
    previous dispatch is split across them at :meth:`on_dispatch` (one
    call per ``engine.step()``). Sample windows are bounded deques;
    percentiles come from the window (the histogram the fan-out
    exports). With a ``monitor``, ``Serve/Telemetry/*`` events are
    written every ``interval`` completed requests, stepped by the
    completion count."""

    def __init__(self, monitor=None, interval=32, max_samples=4096):
        self.monitor = monitor
        self.interval = max(1, int(interval))
        self._live = {}
        # requests past their first token — the only ones on_dispatch
        # must visit; iterating _live would make every dispatch O(queued)
        # under an admission backlog
        self._started = {}
        self._ttft_ms = deque(maxlen=max_samples)
        self._tpot_ms = deque(maxlen=max_samples)
        # queue wait (router put -> slot), one sample per admission; the
        # two percentiles are cached every ``interval`` admissions so the
        # per-step span (dstpu.engine.step) reads two floats
        self._queue_ms = deque(maxlen=max_samples)
        self.admitted = 0
        self.queue_ms_p50 = 0.0
        self.queue_ms_p90 = 0.0
        # batch occupancy: live slots / slots, summed over decode-bearing
        # dispatches since engine construction
        self._occ_active = 0
        self._occ_slots = 0
        # the table entries the paged-decode kernel visits against the
        # block table's, and the grid steps it takes them in, summed over
        # the same dispatches
        self._grid_steps = 0
        self._table_entries = 0
        self._kernel_steps = 0
        # live rows against the rows offered to the paged KV write, summed
        # over every dispatch (chunk-only ones too)
        self._write_rows = 0
        self._write_rows_offered = 0
        # plain decode dispatches, those of them enqueued behind an
        # unread one, the decode steps x live slots they ran, and those
        # of them run for a sequence that had already ended (an EOS is
        # seen one dispatch late)
        self._plain_dispatches = 0
        self._chained_dispatches = 0
        self._slot_steps = 0
        self._late_steps = 0
        # fused dispatches: a prompt chunk beside the decode steps
        self._fused_dispatches = 0
        # mechanism -> [its calls of every program call, those of them
        # that were a Pallas kernel] (tag_schema.KERNEL_SHARES)
        self._calls = {}
        # bytes of cache the live sequences hold (blocks under their
        # tables, and whatever the model keeps a slot) against the tokens
        # they have seen, summed over the engine's steps
        self._cache_bytes = 0
        self._live_tokens = 0
        self.completed = 0
        self.rejected = 0
        self.active = 0
        self._emitted_at = 0
        # engine-attached PrefixCache (inference/v2/prefix_cache.py);
        # when set, its hit/eviction/CoW counters ride percentiles()
        # and the Serve/Telemetry fan-out
        self._prefix_cache = None
        # speculative decoding: per-round counters plus acceptance-rate
        # EMAs keyed by request class (the router's priority klass) —
        # all zero/empty and absent from percentiles() until the first
        # on_spec_round, so spec-off snapshots stay byte-identical
        self._klass = {}                 # uid -> request class
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_committed = 0
        self._spec_ema = None            # global acceptance EMA
        self._spec_class_ema = {}        # klass -> acceptance EMA
        # disaggregated serving: requests that left via a prefill->
        # decode handoff (out) or arrived through one (in). Zero and
        # absent from percentiles() on colocated engines, so
        # disagg-off snapshots stay byte-identical.
        self.handoffs_in = 0
        self.handoffs_out = 0
        self._t0 = time.perf_counter()

    def attach_prefix_cache(self, cache):
        self._prefix_cache = cache

    def on_submit(self, uid, klass=0, waited_s=0.0):
        """``waited_s``: how long the request already queued upstream (the
        router's queue) — a duration, because the router's clock is not
        this one."""
        self._live[uid] = _ReqTimes(time.perf_counter(), float(waited_s))
        self._klass[uid] = int(klass)

    def _refresh_queue(self):
        self.queue_ms_p50 = percentile(self._queue_ms, 50) or 0.0
        self.queue_ms_p90 = percentile(self._queue_ms, 90) or 0.0

    def on_admit(self, uid):
        """The request got its slot: one queue-wait sample (upstream wait
        + time in the engine's pending queue). Returns the wait in ms."""
        st = self._live.get(uid)
        if st is None:
            return 0.0
        wait_ms = (st.waited_s + time.perf_counter() - st.t_put) * 1e3
        self._queue_ms.append(wait_ms)
        self.admitted += 1
        if self.admitted % self.interval == 0:
            self._refresh_queue()
        return wait_ms

    def on_decode_batch(self, active, slots, grid_steps=0,
                        table_entries=0, kernel_steps=0):
        """One decode-bearing dispatch ran with ``active`` of ``slots``
        batch slots live, its decode kernel visiting ``grid_steps`` of
        the block table's ``table_entries`` in ``kernel_steps`` grid
        steps (a call's, over the dispatch's decode steps)."""
        self._occ_active += active
        self._occ_slots += slots
        self._grid_steps += grid_steps
        self._table_entries += table_entries
        self._kernel_steps += kernel_steps

    def on_kv_write(self, live, offered):
        """One dispatch offered ``offered`` rows to the paged KV write
        (slots x decode steps, and a chunk's C), ``live`` of them aimed
        at a block other than scratch: the grid steps the write kernel
        takes against those its one-step-a-row grid took."""
        self._write_rows += live
        self._write_rows_offered += offered

    def on_plain_decode(self, chained, slot_steps):
        """One plain decode dispatch was enqueued, ``chained`` (behind one
        whose tokens the host had not read: the device goes from that one
        to this with no host work between) or not, to run ``slot_steps``
        decode steps x live slots."""
        self._plain_dispatches += 1
        self._chained_dispatches += bool(chained)
        self._slot_steps += slot_steps

    def on_fused_dispatch(self):
        """One fused dispatch went out: a prompt chunk and, beside it, its
        own count of decode steps for the slots that decode."""
        self._fused_dispatches += 1

    def on_late_steps(self, late):
        """A dispatch that was read had run ``late`` decode steps x slots
        for sequences that the dispatch before it had ended."""
        self._late_steps += late

    def on_calls(self, counts):
        """One program call whose trace made ``counts[name][0]`` calls of
        each mechanism ``name`` (``tag_schema.KERNEL_SHARES``: an MoE
        layer's expert products, the gated delta rule, a latent cache's
        selected read; layers x the call's chunk and decode steps),
        ``counts[name][1]`` of them a Pallas kernel and the rest the XLA
        form."""
        for name, (calls, kernel) in counts.items():
            pair = self._calls.setdefault(name, [0, 0])
            pair[0] += calls
            pair[1] += kernel

    def on_cache_held(self, cache_bytes, live_tokens):
        """One engine step began with ``cache_bytes`` of cache held by
        live sequences that had seen ``live_tokens`` tokens."""
        self._cache_bytes += cache_bytes
        self._live_tokens += live_tokens

    def on_token(self, uid):
        """First token => TTFT sample; later tokens accumulate for the
        dispatch-boundary TPOT split."""
        st = self._live.get(uid)
        if st is None:
            return
        now = time.perf_counter()
        if st.t_first is None:
            st.t_first = st.t_last = now
            self._started[uid] = st
            self._ttft_ms.append((now - st.t_put) * 1e3)
        else:
            st.pending += 1

    def _flush_pending(self, st, now):
        if st.pending and st.t_last is not None:
            per_ms = (now - st.t_last) * 1e3 / st.pending
            # one sample per token, capped so a giant dispatch cannot
            # flood the window
            self._tpot_ms.extend([per_ms] * min(st.pending, 64))
        st.t_last = now
        st.pending = 0

    def on_dispatch(self, active=None):
        now = time.perf_counter()
        for st in self._started.values():
            self._flush_pending(st, now)
        if active is not None:
            self.active = int(active)

    def on_spec_round(self, uid, accepted, proposed, committed):
        """One speculative verify round for ``uid``: ``accepted`` of
        ``proposed`` draft tokens survived greedy verification and
        ``committed`` tokens (accepted + bonus) entered the stream.
        Updates the global and per-request-class acceptance EMAs the
        scheduler/router read for fallback and placement."""
        self.spec_rounds += 1
        self.spec_proposed += int(proposed)
        self.spec_accepted += int(accepted)
        self.spec_committed += int(committed)
        frac = accepted / max(1, proposed)
        a = 0.25                          # matches SPEC_EMA_ALPHA
        self._spec_ema = frac if self._spec_ema is None \
            else (1 - a) * self._spec_ema + a * frac
        k = self._klass.get(uid, 0)
        prev = self._spec_class_ema.get(k)
        self._spec_class_ema[k] = frac if prev is None \
            else (1 - a) * prev + a * frac

    def spec_acceptance_ema(self, klass=None):
        """Acceptance-rate EMA in [0, 1] — per request class when
        ``klass`` is given, global otherwise; None before the first
        verify round (spec off, or nothing speculated yet)."""
        if klass is None:
            return self._spec_ema
        return self._spec_class_ema.get(int(klass))

    def on_finish(self, uid):
        st = self._live.pop(uid, None)
        self._started.pop(uid, None)
        self._klass.pop(uid, None)
        if st is not None and st.t_first is not None:
            self._flush_pending(st, time.perf_counter())
        self.completed += 1

    def on_reject(self, uid):
        """A shed/expired/cancelled request leaves the accounting
        entirely: it has no dispatch boundary to amortize against, so
        leaving it in the maps would poison the TTFT/TPOT windows
        (zero/None samples at the next dispatch) and ``completed``
        would count requests that were never served. Percentile windows
        therefore hold ONLY requests that actually produced tokens to
        completion."""
        st = self._live.pop(uid, None)
        self._started.pop(uid, None)
        self._klass.pop(uid, None)
        if st is not None:
            self.rejected += 1

    # --------------------------- disaggregated prefill/decode handoff
    def submit_stamp(self, uid):
        """Original submit time (``time.perf_counter`` domain) of a
        live request — exported with the KV handoff payload so the
        decode side anchors its windows on the ORIGINAL submit, not
        its own admit time. Peek only; the request stays live here
        until :meth:`on_handoff_out`."""
        st = self._live.get(uid)
        return None if st is None else st.t_put

    def klass_of(self, uid):
        """Request class of a live request (0 when unknown) — carried
        across the handoff so per-class windows stay coherent."""
        return self._klass.get(uid, 0)

    def on_handoff_out(self, uid):
        """The request left THIS engine via a prefill->decode handoff:
        forget it WITHOUT counting a rejection — its TTFT sample (the
        first token was produced here) stays in the window, and the
        decode side owns the rest of its accounting."""
        self._live.pop(uid, None)
        self._started.pop(uid, None)
        self._klass.pop(uid, None)
        self.handoffs_out += 1

    def on_handoff_in(self, uid, klass=0, submit_ts=None):
        """Register a handed-off request on the DECODE side, anchored
        at the ORIGINAL submit stamp carried over the wire (decode-side
        admit time would hide the whole prefill+stream latency). The
        request arrives already STARTED — its first token was produced
        by the prefill replica, so no second TTFT sample is recorded
        here; subsequent tokens amortize TPOT from this boundary.

        Clock-domain caveat: the stamp is exact for the in-process
        transport (same ``perf_counter`` domain). Over the DCN
        transport the stamp comes from another host's clock — counters
        stay exact, latency windows are advisory there."""
        now = time.perf_counter()
        st = _ReqTimes(now if submit_ts is None else float(submit_ts))
        st.t_first = st.t_last = now
        self._live[uid] = st
        self._started[uid] = st
        self._klass[uid] = int(klass)
        self.handoffs_in += 1

    def percentiles(self):
        out = {
            "ttft_ms_p50": percentile(self._ttft_ms, 50),
            "ttft_ms_p99": percentile(self._ttft_ms, 99),
            "tpot_ms_p50": percentile(self._tpot_ms, 50),
            "tpot_ms_p99": percentile(self._tpot_ms, 99),
            "completed": self.completed,
            "active": self.active,
        }
        if self.admitted:
            self._refresh_queue()
            out["queue_ms_p50"] = self.queue_ms_p50
            out["queue_ms_p90"] = self.queue_ms_p90
        if self._occ_slots:
            out["batch_occupancy_pct"] = round(
                100.0 * self._occ_active / self._occ_slots, 2)
        if self._table_entries:
            out["decode_grid_share"] = round(
                self._grid_steps / self._table_entries, 4)
        if self._kernel_steps:
            out["decode_entries_per_step"] = round(
                self._grid_steps / self._kernel_steps, 4)
        if self._write_rows_offered:
            out["kv_write_live_share"] = round(
                self._write_rows / self._write_rows_offered, 4)
        if self._plain_dispatches:
            out["decode_chain_share"] = round(
                self._chained_dispatches / self._plain_dispatches, 4)
            out["late_stop_share"] = round(
                self._late_steps / max(1, self._slot_steps), 4)
        if self._fused_dispatches:
            out["fused_dispatches"] = self._fused_dispatches
        for name, key in KERNEL_SHARES.items():
            calls, kernel = self._calls.get(name, (0, 0))
            if calls:
                out[key] = round(kernel / calls, 4)
        if self._live_tokens:
            out["cache_bytes_per_live_token"] = round(
                self._cache_bytes / self._live_tokens)
        if self.rejected:
            # only present once a cancel/shed happened: router-off
            # engine snapshots stay byte-identical to pre-router runs
            out["rejected"] = self.rejected
        if self.handoffs_in or self.handoffs_out:
            # only present once a handoff touched this engine:
            # colocated snapshots stay byte-identical
            out["handoffs_in"] = self.handoffs_in
            out["handoffs_out"] = self.handoffs_out
        if self._prefix_cache is not None:
            s = self._prefix_cache.stats()
            elapsed = max(1e-9, time.perf_counter() - self._t0)
            out["prefix_hit_rate_pct"] = s["hit_rate_pct"]
            out["cached_tokens_per_sec"] = round(
                s["cached_tokens"] / elapsed, 1)
            out["prefix_evictions"] = s["evicted_blocks"]
            out["cow_copies"] = s["cow_copies"]
        if self.spec_rounds:
            # only present once a verify round ran: the zero-verify-step
            # guard — spec-off (and spec-on-but-idle) windows carry no
            # spec keys at all rather than NaN/zero-division rows
            out["spec_rounds"] = self.spec_rounds
            out["spec_acceptance_pct"] = round(
                100.0 * self.spec_accepted / max(1, self.spec_proposed),
                1)
            out["spec_tokens_per_verify_step"] = round(
                self.spec_committed / self.spec_rounds, 2)
            out["spec_class_acceptance_ema"] = {
                k: round(v, 3)
                for k, v in sorted(self._spec_class_ema.items())}
        return out

    def maybe_emit(self):
        if self.monitor is None \
                or not getattr(self.monitor, "enabled", False) \
                or self.completed - self._emitted_at < self.interval:
            return
        self._emitted_at = self.completed
        p = self.percentiles()
        step = self.completed
        events = [("Serve/Telemetry/completed", p["completed"], step),
                  ("Serve/Telemetry/active", p["active"], step)]
        for tag, key in (
                ("Serve/Telemetry/ttft_ms_p50", "ttft_ms_p50"),
                ("Serve/Telemetry/ttft_ms_p99", "ttft_ms_p99"),
                ("Serve/Telemetry/tpot_ms_p50", "tpot_ms_p50"),
                ("Serve/Telemetry/tpot_ms_p99", "tpot_ms_p99"),
                ("Serve/Telemetry/queue_ms_p50", "queue_ms_p50"),
                ("Serve/Telemetry/queue_ms_p90", "queue_ms_p90"),
                ("Serve/Telemetry/batch_occupancy_pct",
                 "batch_occupancy_pct"),
                # prefix-cache effectiveness (only present with an
                # attached PrefixCache — see attach_prefix_cache)
                ("Serve/Telemetry/prefix_hit_rate_pct",
                 "prefix_hit_rate_pct"),
                ("Serve/Telemetry/cached_tokens_per_sec",
                 "cached_tokens_per_sec"),
                ("Serve/Telemetry/prefix_evictions", "prefix_evictions"),
                ("Serve/Telemetry/cow_copies", "cow_copies"),
                # speculative decoding (only present once a verify
                # round ran; spec_class_acceptance_ema is a dict and
                # rides percentiles()/snapshots only, not the scalar
                # event fan-out)
                ("Serve/Telemetry/spec_rounds", "spec_rounds"),
                ("Serve/Telemetry/spec_acceptance_pct",
                 "spec_acceptance_pct"),
                ("Serve/Telemetry/spec_tokens_per_verify_step",
                 "spec_tokens_per_verify_step")):
            if p.get(key) is not None:
                events.append((tag, p[key], step))
        self.monitor.write_events(events)
