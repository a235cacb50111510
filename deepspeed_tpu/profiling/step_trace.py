"""Step-anatomy tracing: a ``jax.profiler`` Chrome trace parsed into a
:class:`StepDecomposition` — where one optimizer step's device time went,
in the planner's own cost-term vocabulary.

This promotes the parsing that was stranded in
``benchmarks/trace_summary.py`` into a library the telemetry layer and
the reconcile CLI share. The pipeline:

  1. **Track selection** — device-side tracks are processes whose name
     carries ``TPU``/``/device``/``Core`` and whose ``XLA Ops`` thread
     holds the leaf op events (Steps/Modules tracks are whole-step
     envelopes that would double count). On CPU backends there is no
     device track; the XLA CPU client's thunk-executor threads
     (``tf_XLATfrtCpuClient/*``; ``tf_XLAPjRtCpuClient/*`` from jax 0.9)
     carry the op events instead, so they serve as a fallback
     (``cpu_fallback=True`` in the result) with an HLO-op-name filter
     that drops the runtime scaffolding frames.
  2. **Self time** — per track, an event's duration minus its nested
     children (the trace_summary stack walk), so envelopes never double
     count their contents.
  3. **Classification** — every op self-time lands in exactly one
     decomposition key: a collective op kind (mapped to a planner term
     via its replica groups, see below), a host-staging copy
     (``host_offload``), a device-side layout copy (the one explicitly
     *unmodeled* key), or ``compute`` (matmul/fusion/Pallas/everything
     else). Pallas custom-call time is additionally broken out per
     kernel, by the ``dstpu.kernel.<op>`` name its call site passes
     (``kernels``; ``monitor/tag_schema.py:KERNEL_SCHEMA``).
  4. **Collective legs** — when an event's args carry the HLO
     ``replica_groups=...`` text, the PR-3 parse
     (``runtime/zero/overlap.parse_replica_groups`` + ``match_axes``)
     resolves which mesh axes the collective spans; an axis set touching
     ``data_outer`` is a DCN leg, anything else ICI.
  5. **Exposed vs hidden** — async collectives appear as
     ``*-start``/``*-done`` event pairs; the window between the start
     event's end and the done event's begin overlapped compute (hidden),
     the start/done durations themselves did not (exposed). Synchronous
     collectives are fully exposed. Planner terms accumulate EXPOSED
     time only — that is what the ``_score`` breakdown models (its
     ``_HIDDEN_FRAC`` discount plays the same role on the modeled side).

The decomposition's ``terms`` keys are exactly
``autotuning.planner.SCORE_TERMS`` and its ``unmodeled`` keys exactly
:data:`UNMODELED_KEYS` — the two-direction lint in
``tests/unit/test_reconcile.py`` keeps tracer and planner vocabularies
from silently diverging.

JSON schema: :meth:`StepDecomposition.to_dict` is versioned
(:data:`SCHEMA_VERSION`); consumers (the flight recorder, the CLI
``--json`` outputs) key off the field names below, so additions bump the version.
"""

import collections
import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field, asdict

from ..monitor.tag_schema import KERNEL_SCHEMA
from ..utils.logging import logger

SCHEMA_VERSION = 1

# the planner-aligned decomposition keys (== planner.SCORE_TERMS; the
# reconcile lint asserts the equality) ...
DECOMP_TERMS = ("compute", "grad_reduce", "tp_reduce", "pipe_handoff",
                "ring_rotate", "expert_a2a", "host_offload")
# ... plus the device time the planner deliberately does NOT model:
# device-side layout copies (transpose/bitcast/non-host copy). Keys
# here are the tracer's explicit "unmodeled" declaration — a new
# decomposition key must join one list or the other or the lint fails.
UNMODELED_KEYS = ("copy_layout",)

# collective opcode -> default planner term when no replica groups are
# available (sync CPU lowerings, stripped traces); with groups + a mesh
# the axis match refines the choice (tensor -> tp_reduce, etc.)
_COLL_RE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|all-to-all|"
    r"collective-permute|send|recv)(-start|-done)?(?:\.(\d+))?$")
_COPY_RE = re.compile(r"^copy(-start|-done)?(?:\.(\d+))?$")
# HLO-op-shaped names (lowercase opcode [+ .N]); the CPU-client
# fallback tracks interleave runtime frames (TfrtCpuExecutable::Execute,
# ParseArguments) with real op events and only the latter may count as
# device time
_HLO_NAME_RE = re.compile(r"^[a-z][a-z0-9_\-]*(?:\.\d+)?$")

# a Pallas kernel says its own name: every ``pallas_call`` site passes
# ``name="dstpu.kernel.<op>"`` (monitor/tag_schema.py:KERNEL_SCHEMA), which
# jax puts in the operation's op_name and the Mosaic call's kernel_name
_KERNEL_NAME_RE = re.compile(r"dstpu\.kernel\.([a-z0-9_]+)")


def family_of(name):
    """Coarse op family (the trace_summary table's grouping)."""
    n = name.lower()
    if _COLL_RE.match(n):
        return "collective"
    if "custom-call" in n or "pallas" in n or "flash" in n:
        return "pallas/custom-call"
    if re.search(r"convolution|dot|einsum", n):
        return "matmul"
    if "fusion" in n:
        return "fusion(elementwise/other)"
    if "copy" in n or "transpose" in n or "bitcast" in n:
        return "copy/layout"
    if "scatter" in n or "gather" in n or "dynamic" in n:
        return "gather/scatter/DUS"
    return "other"


def kernel_op_for(text):
    """``<op>`` for an event whose name or args hold ``dstpu.kernel.<op>``,
    a registered kernel name; None for anything else (a custom call that
    is not one of ours, a name nobody registered)."""
    m = _KERNEL_NAME_RE.search(text)
    return m.group(1) if m and m.group(0) in KERNEL_SCHEMA else None


# ------------------------------------------------------------- trace io

def find_trace_file(root):
    """Newest ``*.trace.json.gz`` under ``root`` (recursive — jax nests
    traces under ``plugins/profile/<timestamp>/``), or ``root`` itself
    when it already names a trace file. None when nothing is there."""
    if os.path.isfile(root):
        return root
    paths = glob.glob(os.path.join(glob.escape(root),
                                   "**", "*.trace.json.gz"),
                      recursive=True)
    return sorted(paths)[-1] if paths else None


def load_trace_events(path):
    """The ``traceEvents`` list of one Chrome trace (.json or .json.gz)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    return events if isinstance(events, list) else []


# ------------------------------------------------------- track selection

def _meta_names(events):
    pid_names, tid_names = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pid_names[e.get("pid")] = (e.get("args") or {}).get("name", "")
        elif e.get("name") == "thread_name":
            tid_names[(e.get("pid"), e.get("tid"))] = \
                (e.get("args") or {}).get("name", "")
    return pid_names, tid_names


def _op_tracks(pid_names, tid_names):
    """-> (op_tids, track_labels, cpu_fallback). Device tracks first;
    the XLA CPU client's thunk threads as the fallback so a CPU dev
    container still yields a (compute-only) decomposition."""
    dev_pids = {p for p, n in pid_names.items()
                if "TPU" in n or "/device" in n.lower() or "Core" in n}
    op_tids = {k for k, n in tid_names.items()
               if k[0] in dev_pids and n == "XLA Ops"}
    if op_tids:
        labels = sorted({pid_names[p] for p in dev_pids})
        return op_tids, labels, False
    op_tids = {k for k, n in tid_names.items()
               if "XLATfrtCpuClient" in n or "XLAPjRtCpuClient" in n}
    labels = sorted({pid_names.get(k[0], "?") for k in op_tids})
    return op_tids, labels, bool(op_tids)


# ----------------------------------------------------------- self times

def _self_times(events, op_tids, hlo_only=False):
    """[(event, self_dur_us)] per the trace_summary stack walk: sort by
    (ts, -dur), subtract each child's duration from its innermost
    enclosing parent on the same (pid, tid)."""
    by_tid = collections.defaultdict(list)
    for e in events:
        if e.get("ph") != "X" or (e.get("pid"), e.get("tid")) \
                not in op_tids:
            continue
        if hlo_only and not _HLO_NAME_RE.match(str(e.get("name", ""))):
            continue
        by_tid[(e.get("pid"), e.get("tid"))].append(e)
    out = []
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e.get("dur", 0)))
        stack = []            # (end_ts, index into selfs)
        selfs = []
        for e in evs:
            ts, dur = e["ts"], e.get("dur", 0)
            while stack and stack[-1][0] <= ts:
                stack.pop()
            if stack:
                selfs[stack[-1][1]][1] -= dur
            selfs.append([e, dur])
            stack.append((ts + dur, len(selfs) - 1))
        out.extend((e, max(0.0, s)) for e, s in selfs)
    return out


# -------------------------------------------------------- classification

def _args_text(e):
    args = e.get("args") or {}
    return " ".join(str(v) for v in args.values())


def _coll_axes(e, mesh):
    """Mesh axes of a collective event via the replica-group text its
    args carry (the HLO long name xprof attaches), or None."""
    if mesh is None:
        return None
    text = _args_text(e)
    if "replica_groups" not in text:
        return None
    try:
        from ..runtime.zero.overlap import parse_replica_groups, \
            match_axes
        groups = parse_replica_groups(text)
        axes = match_axes(groups, mesh) if groups else None
        return tuple(axes) if axes else None
    except Exception:  # noqa: BLE001 - classification is best-effort
        return None


def _term_for_collective(kind, axes, mesh):
    """Planner term for one collective: axes decide when known, the op
    kind's canonical role otherwise."""
    if axes:
        s = set(axes)
        if s <= {"tensor"}:
            return "tp_reduce"
        if kind == "all-to-all":
            return "expert_a2a"
        if s <= {"pipe"}:
            return "pipe_handoff"
        if s <= {"seq"}:
            return "ring_rotate"
        if kind in ("collective-permute", "send", "recv"):
            return "pipe_handoff" if "pipe" in s else "ring_rotate"
        return "grad_reduce"
    if kind == "all-to-all":
        return "expert_a2a"
    if kind in ("collective-permute", "send", "recv"):
        shape = dict(mesh.shape) if mesh is not None else {}
        if shape.get("seq", 1) > 1 and shape.get("pipe", 1) <= 1:
            return "ring_rotate"
        return "pipe_handoff"
    return "grad_reduce"


def _is_host_copy(e):
    text = (str(e.get("name", "")) + " " + _args_text(e)).lower()
    return "s(5)" in text or "host" in text


# ---------------------------------------------------------- decomposition

@dataclass
class StepDecomposition:
    """Per-step device-time attribution (all ``*_ms`` fields are per
    step — raw trace totals divided by ``steps``)."""
    schema: int = SCHEMA_VERSION
    steps: int = 1
    trace_path: str = ""
    device_tracks: list = field(default_factory=list)
    cpu_fallback: bool = False
    total_device_ms: float = 0.0       # sum(terms) + sum(unmodeled)
    terms: dict = field(default_factory=dict)      # DECOMP_TERMS -> ms
    unmodeled: dict = field(default_factory=dict)  # UNMODELED_KEYS -> ms
    collectives: list = field(default_factory=list)
    kernels: dict = field(default_factory=dict)    # kernel <op> -> ms
    per_op: list = field(default_factory=list)
    host_copy_ms: float = 0.0
    collective_total_ms: float = 0.0
    collective_exposed_ms: float = 0.0
    collective_hidden_ms: float = 0.0
    occupancy_pct: float = 0.0         # busy / track span (tick fill)
    span_ms: float = 0.0               # device-track span per step
    coverage_pct: float = 0.0          # 100 * sum(terms) / total

    def to_dict(self):
        return asdict(self)

    def to_json(self):
        return json.dumps(self.to_dict(), indent=2, sort_keys=True) + "\n"


def _pair_async(rows):
    """Match ``*-start``/``*-done`` rows of one collective kind: exact
    ``.N`` suffix first, then FIFO for the suffix-less leftovers.
    Returns (pairs, leftovers); each pair is (start_row, done_row)."""
    starts = [r for r in rows if r["phase"] == "start"]
    dones = [r for r in rows if r["phase"] == "done"]
    by_sfx = {r["sfx"]: r for r in dones if r["sfx"] is not None}
    pairs, used = [], set()
    rest_starts = []
    for s in starts:
        d = by_sfx.get(s["sfx"]) if s["sfx"] is not None else None
        if d is not None and id(d) not in used:
            used.add(id(d))
            pairs.append((s, d))
        else:
            rest_starts.append(s)
    rest_dones = sorted((d for d in dones if id(d) not in used),
                        key=lambda r: r["ts"])
    rest_starts.sort(key=lambda r: r["ts"])
    k = min(len(rest_starts), len(rest_dones))
    pairs.extend(zip(rest_starts[:k], rest_dones[:k]))
    leftovers = rest_starts[k:] + rest_dones[k:]
    return pairs, leftovers


def decompose(events, steps=1, mesh=None, trace_path=""):
    """Classify one trace's device op events into a
    :class:`StepDecomposition`. Returns None when the trace carries no
    recognizable op track (the caller degrades with one warning)."""
    steps = max(1, int(steps))
    pid_names, tid_names = _meta_names(events)
    op_tids, labels, cpu_fallback = _op_tracks(pid_names, tid_names)
    if not op_tids:
        return None
    selfs = _self_times(events, op_tids, hlo_only=cpu_fallback)
    if not selfs:
        return None

    terms = {k: 0.0 for k in DECOMP_TERMS}
    unmodeled = {k: 0.0 for k in UNMODELED_KEYS}
    kernels = collections.Counter()
    per_op_ms = collections.Counter()
    per_op_n = collections.Counter()
    coll_rows = collections.defaultdict(list)   # (kind, term) -> rows
    copy_async = []                             # host-copy start/done rows
    host_copy_us = 0.0

    for e, sdur in selfs:
        name = str(e.get("name", "?"))
        per_op_ms[name] += sdur / 1e3
        per_op_n[name] += 1
        m = _COLL_RE.match(name.lower())
        if m:
            kind = m.group(1)
            axes = _coll_axes(e, mesh)
            term = _term_for_collective(kind, axes, mesh)
            coll_rows[(kind, term, axes)].append({
                "phase": (m.group(2) or "").lstrip("-") or None,
                "sfx": m.group(3),
                "ts": e.get("ts", 0),
                "dur": e.get("dur", 0),
                "self": sdur,
            })
            continue
        mc = _COPY_RE.match(name.lower())
        if mc and _is_host_copy(e):
            phase = (mc.group(1) or "").lstrip("-") or None
            if phase:
                copy_async.append({"phase": phase, "sfx": mc.group(2),
                                   "ts": e.get("ts", 0),
                                   "dur": e.get("dur", 0), "self": sdur})
            else:
                host_copy_us += sdur
            continue
        fam = family_of(name)
        if fam == "copy/layout":
            unmodeled["copy_layout"] += sdur / 1e3
            continue
        text = name + " " + _args_text(e)
        kop = kernel_op_for(text)
        if kop is not None:
            kernels[kop] += sdur / 1e3
        terms["compute"] += sdur / 1e3

    # collectives: exposed/hidden per async pair, sync fully exposed
    collectives = []
    for (kind, term, axes), rows in sorted(
            coll_rows.items(), key=lambda kv: (kv[0][0], kv[0][1])):
        sync_rows = [r for r in rows if r["phase"] is None]
        pairs, leftovers = _pair_async(rows)
        total = sum(r["self"] for r in sync_rows)
        exposed = total
        hidden = 0.0
        for s, d in pairs:
            window = (d["ts"] + d["dur"]) - s["ts"]
            gap = max(0.0, d["ts"] - (s["ts"] + s["dur"]))
            hidden += gap
            exposed += max(0.0, window - gap)
            total += window
        for r in leftovers:      # unmatched start/done: count as exposed
            total += r["self"]
            exposed += r["self"]
        n = len(sync_rows) + len(pairs) + len(leftovers)
        leg = None
        if axes is not None:
            leg = "dcn" if "data_outer" in axes else "ici"
        collectives.append({
            "op": kind, "term": term,
            "axes": list(axes) if axes else None, "leg": leg,
            "count_per_step": round(n / steps, 3),
            "total_ms": round(total / 1e3 / steps, 6),
            "exposed_ms": round(exposed / 1e3 / steps, 6),
            "hidden_ms": round(hidden / 1e3 / steps, 6),
        })
        terms[term] += exposed / 1e3

    # host copies: async staging pairs + sync copies -> host_offload
    if copy_async:
        pairs, leftovers = _pair_async(copy_async)
        for s, d in pairs:
            window = (d["ts"] + d["dur"]) - s["ts"]
            gap = max(0.0, d["ts"] - (s["ts"] + s["dur"]))
            host_copy_us += max(0.0, window - gap)
        for r in leftovers:
            host_copy_us += r["self"]
    terms["host_offload"] += host_copy_us / 1e3

    # per-step scaling + occupancy
    terms = {k: round(v / steps, 6) for k, v in terms.items()}
    unmodeled = {k: round(v / steps, 6) for k, v in unmodeled.items()}
    total = sum(terms.values()) + sum(unmodeled.values())
    spans, busy = [], 0.0
    by_tid = collections.defaultdict(list)
    for e, sdur in selfs:
        by_tid[(e.get("pid"), e.get("tid"))].append((e, sdur))
        busy += sdur
    for rows in by_tid.values():
        t0 = min(e["ts"] for e, _ in rows)
        t1 = max(e["ts"] + e.get("dur", 0) for e, _ in rows)
        spans.append(max(0.0, t1 - t0))
    span = sum(spans)
    per_op = [{"op": nm, "ms": round(ms / steps, 6),
               "count": per_op_n[nm], "family": family_of(nm)}
              for nm, ms in per_op_ms.most_common()]

    d = StepDecomposition(
        steps=steps, trace_path=trace_path, device_tracks=labels,
        cpu_fallback=cpu_fallback,
        total_device_ms=round(total, 6),
        terms=terms, unmodeled=unmodeled,
        collectives=collectives,
        kernels={k: round(v / steps, 6)
                 for k, v in sorted(kernels.items())},
        per_op=per_op,
        host_copy_ms=round(host_copy_us / 1e3 / steps, 6),
        collective_total_ms=round(
            sum(c["total_ms"] for c in collectives), 6),
        collective_exposed_ms=round(
            sum(c["exposed_ms"] for c in collectives), 6),
        collective_hidden_ms=round(
            sum(c["hidden_ms"] for c in collectives), 6),
        occupancy_pct=round(
            min(100.0, 100.0 * busy / span) if span > 0 else 0.0, 3),
        span_ms=round(span / 1e3 / steps / max(1, len(spans)), 6),
        coverage_pct=round(
            100.0 * sum(terms.values()) / total if total > 0 else 0.0,
            3),
    )
    return d


def decompose_dir(root, steps=1, mesh=None):
    """Find + parse the newest trace under ``root``. Returns None (with
    ONE warning, never an exception — the step path rides on this) when
    no trace or no op track exists: CPU-only hosts and platforms
    without a profiler degrade to a no-op."""
    try:
        path = find_trace_file(root)
        if path is None:
            logger.warning(f"step_trace: no *.trace.json.gz under "
                           f"{root!r}; decomposition skipped")
            return None
        d = decompose(load_trace_events(path), steps=steps, mesh=mesh,
                      trace_path=path)
        if d is None:
            logger.warning(f"step_trace: trace {path!r} carries no "
                           f"recognizable device/op track; "
                           f"decomposition skipped")
        return d
    except Exception as e:  # noqa: BLE001 - observability never fatal
        logger.warning(f"step_trace: parsing trace under {root!r} "
                       f"failed ({type(e).__name__}: {e}); skipped")
        return None
