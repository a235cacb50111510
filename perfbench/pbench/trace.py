"""From the profiler's ``.xplane.pb`` to numbers: which planes are devices,
the device's busy time, each operation's own time, the collectives' exposed
time and what the host was doing in the idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else. Every name this
file looks for in a trace is data, in ``perfbench/trace_names.json``; a
reader of a per-layer metric calls the functions here and keeps no trace
arithmetic of its own. Checked by ``perfbench/selfcheck.py`` against the
recorded trace in ``perfbench/fixtures/``.
"""

import contextlib
import functools
import glob
import os
import re
import shutil

from . import common

WINDOW_SPAN = "perfbench.window"


@functools.lru_cache(maxsize=None)
def names():
    return common.load_json("trace_names.json")


@contextlib.contextmanager
def capture(trace_dir):
    """Profile what runs inside the ``with``; the span ``perfbench.window``
    marks, on the trace's own clock, the window the numbers refer to."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # spans are TraceAnnotations
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def span(name, **stats):
    """A host span on the trace's clock (free when no trace is on)."""
    import jax
    return jax.profiler.TraceAnnotation(name, **stats)


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def short_name(hlo_text, kernels=None):
    """An operations-line event is named by its whole HLO instruction
    (``%fusion.12 = bf16[..] fusion(...)``): the instruction's name without
    its number, or ``pallas:<kernel>`` where a kernel's pattern matches."""
    for kernel, rx in (kernels or {}).items():
        if rx.search(hlo_text):
            return "pallas:" + kernel
    head = hlo_text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def opcode(hlo_text):
    """``all-gather`` of ``%name.3 = bf16[..]{..} all-gather(...)``: the
    instruction's own name says what jax called it (``%psum.7``), the
    opcode what the device does."""
    rest = hlo_text.split(" = ", 1)[-1]
    if rest.startswith("("):                 # a tuple type: skip it whole
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.split(" ", 1)[-1]
    m = re.match(r"([\w-]+)\(", rest)
    return m.group(1) if m else ""


def kernel_patterns():
    return {k: re.compile(p["pattern"])
            for k, p in names()["kernels"].items()}


class Event:
    __slots__ = ("name", "start", "end", "stats", "self_s")

    def __init__(self, name, start, end, stats):
        self.name, self.start, self.end, self.stats = name, start, end, stats
        self.self_s = end - start

    @property
    def dur(self):
        return self.end - self.start


def _events(line):
    out = []
    for e in line.events:
        start = e.start_ns * 1e-9
        out.append(Event(e.name, start, start + e.duration_ns * 1e-9,
                         dict(e.stats)))
    out.sort(key=lambda e: (e.start, -e.end))
    return out


def _set_self_times(events):
    """Events of one line nest (a ``while`` spans the operations of its
    body): an event's own time is its duration less its children's."""
    stack = []
    for e in events:
        while stack and stack[-1].end <= e.start + 1e-12:
            stack.pop()
        if stack:
            stack[-1].self_s -= min(e.end, stack[-1].end) - e.start
        stack.append(e)
    for e in events:
        e.self_s = max(e.self_s, 0.0)


class Trace:
    """device -> operations (sorted, own times set); host spans by name."""

    def __init__(self, path, rehearse=False):
        import jax
        n = names()
        data = jax.profiler.ProfileData.from_file(path)
        self.path = path
        self.devices = {}
        self.modules = {}
        self.host = []          # (thread line name, Event)
        for plane in data.planes:
            if plane.name.startswith(n["device_plane_prefix"]):
                for line in plane.lines:
                    if line.name == n["ops_line"]:
                        ev = _events(line)
                        _set_self_times(ev)
                        self.devices[plane.name] = ev
                    elif line.name == n["modules_line"]:
                        self.modules[plane.name] = _events(line)
            elif plane.name.startswith(n["host_plane_prefix"]):
                for line in plane.lines:
                    ev = _events(line)
                    if rehearse and any("hlo_op" in e.stats for e in ev):
                        # XLA:CPU runs its operations on host threads; a
                        # rehearsal reads them as a device so that the same
                        # code runs — its numbers are never printed
                        ops = [e for e in ev if "hlo_op" in e.stats]
                        _set_self_times(ops)
                        self.devices.setdefault(
                            "rehearsal:" + line.name, []).extend(ops)
                    else:
                        self.host.extend((line.name, e) for e in ev)
        spans = [e for _, e in self.host if e.name == WINDOW_SPAN]
        if spans:
            self.t0, self.t1 = spans[0].start, spans[0].end
        else:
            every = [e for ev in self.devices.values() for e in ev]
            self.t0 = min((e.start for e in every), default=0.0)
            self.t1 = max((e.end for e in every), default=0.0)

    # ------------------------------------------------------------ windows
    @property
    def window_s(self):
        return self.t1 - self.t0

    def _clip(self, e):
        return max(e.start, self.t0), min(e.end, self.t1)

    def busy_intervals(self, device):
        """Union of the intervals in which an operation ran on ``device``,
        inside the window, as a sorted list of (start, end)."""
        out = []
        for e in self.devices[device]:
            a, b = self._clip(e)
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                if b > out[-1][1]:
                    out[-1] = (out[-1][0], b)
            else:
                out.append((a, b))
        return out

    def busy_s(self):
        """Busy seconds averaged over the devices that ran anything."""
        per = [sum(b - a for a, b in self.busy_intervals(d))
               for d in self.devices]
        return sum(per) / len(per) if per else 0.0

    def idle_share(self):
        """1 - busy / window, or None of an empty window."""
        return 1.0 - self.busy_s() / self.window_s \
            if self.window_s > 0 else None

    def in_window(self, device):
        return [e for e in self.devices[device]
                if e.end > self.t0 and e.start < self.t1]

    # ---------------------------------------------------------- operations
    def op_seconds(self, pattern):
        """Own seconds of the operations whose HLO text matches
        ``pattern``, averaged over devices, and the number of such events
        on one device."""
        rx = re.compile(pattern)
        secs, count = [], 0
        for d in self.devices:
            hit = [e for e in self.in_window(d) if rx.search(e.name)]
            secs.append(sum(e.self_s for e in hit))
            count = max(count, len(hit))
        return (sum(secs) / len(secs) if secs else 0.0), count

    def top_ops(self, k=10):
        """[[name, own seconds averaged over devices], ...], operations of
        one name summed (trailing instance numbers dropped)."""
        total = {}
        kernels = kernel_patterns()
        for d in self.devices:
            for e in self.in_window(d):
                key = short_name(e.name, kernels)
                total[key] = total.get(key, 0.0) + e.self_s
        n = max(1, len(self.devices))
        return [[name, s / n] for name, s in sorted(
            total.items(), key=lambda kv: -kv[1])[:k]]

    def collective_seconds(self):
        """(all, exposed) own seconds of collective operations, averaged
        over devices. The TensorCore runs one operation at a time, so a
        collective's own time on the operations line is time in which no
        compute ran there: an overlapped (asynchronous) collective shows as
        a short start and a short done; an exposed one as a long wait."""
        n = names()
        rx = re.compile(n["collective_pattern"])
        calls = re.compile(n["collective_calls_pattern"])
        hidden = re.compile(n["collective_async_start_pattern"])
        alls, exposed = [], []
        for d in self.devices:
            # by what the instruction is (its opcode, its own name, the
            # computation a fusion calls), never by its operands' names: a
            # fusion that reads %all-gather-done.3 is compute
            hit = []
            for e in self.in_window(d):
                what = e.name.split(" = ", 1)[0] + " " + opcode(e.name)
                if rx.search(what) or calls.search(e.name):
                    hit.append((e, what))
            alls.append(sum(e.self_s for e, _ in hit))
            exposed.append(sum(e.self_s for e, what in hit
                               if not hidden.search(what)))
        k = max(1, len(self.devices))
        return sum(alls) / k, sum(exposed) / k

    # ---------------------------------------------------------- idle gaps
    def idle_gaps(self, k=10):
        """The device's idle time inside the window, by what the host was
        doing: for every gap of the first device, the innermost
        ``perfbench.*`` span that covers its middle (or 'no span').
        -> [[what, seconds], ...], longest first."""
        if not self.devices:
            return []
        first = sorted(self.devices)[0]
        busy = self.busy_intervals(first)
        gaps, t = [], self.t0
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if self.t1 > t:
            gaps.append((t, self.t1))
        spans = [e for _, e in self.host
                 if e.name.startswith("perfbench.")
                 and e.name != WINDOW_SPAN]
        by = {}
        for a, b in gaps:
            mid = (a + b) / 2
            cover = [s for s in spans if s.start <= mid <= s.end]
            what = min(cover, key=lambda s: s.dur).name if cover \
                else "no span"
            by[what] = by.get(what, 0.0) + (b - a)
        return [[w, s] for w, s in sorted(by.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def host_spans(self, name):
        return [e for _, e in self.host if e.name == name
                and e.end > self.t0 and e.start < self.t1]

    def span_idle_s(self, name):
        """Seconds inside spans ``name`` in which no operation ran on the
        first device (the host was in that call and the chip waited)."""
        if not self.devices:
            return 0.0
        busy = self.busy_intervals(sorted(self.devices)[0])
        idle = 0.0
        for s in self.host_spans(name):
            a, b = self._clip(s)
            covered = sum(max(0.0, min(b, y) - max(a, x)) for x, y in busy)
            idle += max(0.0, (b - a) - covered)
        return idle


def load(trace_dir, rehearse=False):
    path = find_xplane(trace_dir)
    return Trace(path, rehearse=rehearse) if path else None


def describe(path, samples=6, top=25):
    """What a first look by hand wants: planes, lines, event counts, sample
    events with their statistics, and the operations that took most time.
    -> a JSON-able dict."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops_line = names()["ops_line"]
    out = {"path": path, "bytes": os.path.getsize(path), "planes": []}
    for plane in data.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            ev = list(line.events)
            by = {}
            for e in ev:
                key = e.name if line.name == ops_line \
                    else short_name(e.name)
                c = by.setdefault(key, [0, 0.0])
                c[0] += 1
                c[1] += e.duration_ns * 1e-9
            p["lines"].append({
                "name": line.name, "events": len(ev),
                "samples": [{"name": e.name, "start_ns": e.start_ns,
                             "duration_ns": e.duration_ns,
                             "stats": {k: str(v)[:160]
                                       for k, v in dict(e.stats).items()}}
                            for e in ev[:samples]],
                "top_by_duration": sorted(
                    ([k, c[0], c[1]] for k, c in by.items()),
                    key=lambda r: -r[2])[:top]})
        out["planes"].append(p)
    return out
