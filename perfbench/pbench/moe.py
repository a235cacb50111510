"""What the sparse-expert layer of a serving cell requires and what the
device trace says it took (PR 26; written out in ``perfbench/MOE.md``).

Three things live here, for the readers ``layer_metrics/moe_*.py``:

* the count: operations and bytes of the expert products of one layer for
  ``n`` rows, from shapes alone (``expert_products``), and the least time a
  traced window's dispatches and prefills need (``least_seconds``);
* the program's ``jax.named_scope`` of every device operation. The name of
  an ``XLA Ops`` event is its HLO instruction text, which carries no scope;
  the scope is in the event's *metadata* (statistic ``tf_op``, e.g.
  ``jit(decode)/dstpu.moe.route/top_k``), which ``jax.profiler.ProfileData``
  does not show. ``op_scopes`` reads just that table out of the
  ``.xplane.pb`` with a twenty-line protobuf walk and nothing else;
* which operations are the expert products and which the routing
  (``device_seconds``): by scope, and the ragged-dot fusion (whose own
  metadata loses the scope) by its name.

A program without these scopes (a dense model, a commit before PR 26)
gives (0, 0, busy): the readers then return None.
"""

import re

from . import flops

SCOPE_EXPERTS = "dstpu.moe.experts"
SCOPES_ROUTE = ("dstpu.moe.route", "dstpu.moe.combine")
# XLA:TPU lowers lax.ragged_dot to a fusion whose instruction and op_name
# say ragged-dot / ragged_dot and nothing of the scope it was traced in
RAGGED = re.compile(r"ragged[-_]dot", re.I)
DISPATCH = "dstpu.engine.dispatch"


# ------------------------------------------------------------ the count
def experts_touched(n_rows, s):
    """Expected number of a layer's experts that ``n_rows`` tokens touch
    when each picks ``top_k`` of ``n_experts`` uniformly, which routers
    with seeded random weights do. A skewed router touches fewer."""
    E, k = s["n_experts"], s["top_k"]
    return E * (1.0 - (1.0 - k / E) ** n_rows)


def expert_products(n_rows, s, itemsize=2):
    """One layer's three grouped products (gate, up, down) for ``n_rows``
    tokens -> (operations, bytes): 2 * n * k * 3 * D * F operations; each
    touched expert's 3 * D * F weights read once, the n * k routed rows
    read and their results written once."""
    D, F, k = s["d_model"], s["d_ff"], s["top_k"]
    ops = 2 * n_rows * k * 3 * D * F
    weights = 3 * D * F * itemsize * experts_touched(n_rows, s)
    rows = 2 * n_rows * k * D * itemsize
    return ops, weights + rows


def least_seconds(v):
    """Least seconds the chip could take for the expert products of the
    traced window: every decode dispatch of the program's own timeline
    (``active`` live rows x ``steps`` steps, weighted by the share of the
    span that lies inside the window) and every prompt whose prefill ended
    there, each layer its own call. -> (seconds, calls counted)."""
    s, tr = v.sizes, v.trace
    total, calls = 0.0, 0.0
    for e in tr.host_spans(DISPATCH):
        if e.stats.get("kind") not in ("decode", "fused"):
            continue
        inside = (min(e.end, tr.t1) - max(e.start, tr.t0)) / max(e.dur,
                                                                 1e-12)
        n = int(e.stats["active"])
        if n <= 0:
            continue
        least, _ = flops.roofline_s(*expert_products(n, s), v.peaks)
        steps = int(e.stats["steps"]) * inside
        total += least * steps * s["n_layer"]
        calls += steps * s["n_layer"]
    for p in v.counters.get("traced_prompts", ()):
        least, _ = flops.roofline_s(*expert_products(int(p), s), v.peaks)
        total += least * s["n_layer"]
        calls += s["n_layer"]
    return total, calls


# --------------------------------------------------- scopes from the file
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited fields; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        else:
            i += 8 if wire == 1 else 4
            continue
        yield key >> 3, val


def _map_entry(buf):
    key = val = None
    for f, x in _fields(buf):
        if f == 1:
            key = x
        elif f == 2:
            val = x
    return key, val


def op_scopes(path, plane_prefix):
    """{HLO instruction text: op_name with its scopes} for the device
    planes of ``path`` (XSpace.planes=1 -> XPlane name=2,
    event_metadata=4 {id: XEventMetadata name=2, stats=5},
    stat_metadata=5 {id: XStatMetadata name=2}; XStat metadata_id=1,
    str_value=5, ref_value=7). {} for a file without the table."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, x in _fields(plane):
            if g == 2:
                name = bytes(x).decode("utf-8", "replace")
            elif g == 4:
                events.append(_map_entry(x)[1])
            elif g == 5:
                key, meta = _map_entry(x)
                for h, y in _fields(meta):
                    if h == 2:
                        stat_names[key] = bytes(y).decode("utf-8",
                                                          "replace")
        if not name.startswith(plane_prefix):
            continue
        tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
        for meta in events:
            text, scope = None, None
            for g, x in _fields(meta):
                if g == 2:
                    text = bytes(x).decode("utf-8", "replace")
                elif g == 5:
                    st = dict(_fields(x))
                    if st.get(1) in tf_op:
                        scope = bytes(st[5]).decode("utf-8", "replace") \
                            if 5 in st else stat_names.get(st.get(7))
            if text and scope:
                out[text] = scope
    return out


# ------------------------------------------------------ the device's time
def device_seconds(v):
    """(own seconds of the expert products, own seconds of routing and
    combining, device busy seconds) in the traced window, averaged over
    devices; what was matched is said."""
    from . import trace as tracing
    tr = v.trace
    if tr is None or not getattr(tr, "path", None):
        return 0.0, 0.0, 0.0
    if getattr(tr, "moe_seconds", None) is not None:   # the three readers
        return tr.moe_seconds
    scopes = op_scopes(tr.path, tracing.names()["device_plane_prefix"])
    experts = route = 0.0
    by = {}
    for d in tr.devices:
        for e in tr.in_window(d):
            scope = scopes.get(e.name, "")
            if SCOPE_EXPERTS in scope or RAGGED.search(
                    e.name.split("(", 1)[0]) or RAGGED.search(scope):
                experts += e.self_s
                key = "experts:" + tracing.short_name(e.name)
            elif any(sc in scope for sc in SCOPES_ROUTE):
                route += e.self_s
                key = "route:" + tracing.short_name(e.name)
            else:
                continue
            by[key] = by.get(key, 0.0) + e.self_s
    n = max(1, len(tr.devices))
    if experts > 0:
        v.say("moe_device_seconds", experts_s=experts / n,
              route_s=route / n, busy_s=tr.busy_s(),
              scoped_ops=len(scopes),
              top=sorted(((k, s / n) for k, s in by.items()),
                         key=lambda kv: -kv[1])[:8])
    tr.moe_seconds = experts / n, route / n, tr.busy_s()
    return tr.moe_seconds
