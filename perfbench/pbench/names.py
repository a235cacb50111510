"""The device's time by the names the program gives its own operations (PR
57; written out in ``perfbench/NAMES.md``). For the readers
``layer_metrics/paged_attn_share.py``, ``kv_write_share.py``,
``paged_chunk_kernel_share.py``, ``chunk_phase_share.py``,
``unnamed_busy_share.py`` and ``train_unnamed_busy_share.py``, and for
whoever wants to know what a ``fusion`` is:

    cd perfbench && python3 -m pbench.names <trace dir>

An ``XLA Ops`` event's ``tf_op`` (``pbench.moe.op_scopes``) holds every
``jax.named_scope`` the operation was traced under, outermost first, with
whatever jax wraps round them: ``jit(fused)/dstpu.step.chunk/dstpu.attn.full
/dstpu.attn.paged/dstpu.kernel.paged_chunk/pallas_call``, ``jit(train_step)/
transpose(jvp(dstpu.attn.flash))/dstpu.kernel.flash_bwd_t/...``, ``.../
checkpoint/rematted_computation/dstpu.mm.mlp/dot_general``. The walk takes the
``dstpu.[a-z0-9_.]+`` components wherever they stand and sums each
operation's own seconds (nested operations count for themselves)

* under every name the ``tf_op`` holds (``by_scope``: an operation inside
  three scopes counts in each of the three, so the table does not add up to
  the busy time and is not meant to),
* under its ``dstpu.kernel.*`` name (``by_kernel``), and
* as ``unnamed`` where the ``tf_op`` holds no name but a ``dstpu.step.*``:
  those say the phase of a serving program (prefill, chunk, decode step) and
  not what the operation is. The rest's operations are listed by the names
  the trace reduction prints (``trace.short_name``).

It holds no list of names: a scope a later PR opens is read without an edit
here. One walk a trace, kept on the ``Trace`` object. A trace without the
``tf_op`` table (a CPU rehearsal, a recorded four-chip fixture) is all
unnamed; no trace gives None, and so does a name nothing in the window ran
under.
"""

import re

from . import moe

COMPONENT = re.compile(r"dstpu\.[a-z0-9_]+(?:\.[a-z0-9_]+)*")
STEP, KERNEL = "dstpu.step.", "dstpu.kernel."
ATTN_PAGED, KV_WRITE = "dstpu.attn.paged", "dstpu.kv.write"
STEP_CHUNK = STEP + "chunk"
PAGED_CHUNK_KERNEL = KERNEL + "paged_chunk"


def components(tf_op):
    """The ``dstpu.*`` names of one ``tf_op``, outermost first."""
    return COMPONENT.findall(tf_op or "")


def walk(v):
    """{busy_s, by_scope, by_kernel, unnamed_s, unnamed_ops}: own device
    seconds of the traced window by name, averaged over devices; None
    without a trace. Says the tables, once a trace."""
    from . import trace as tracing
    tr = getattr(v, "trace", None)
    if tr is None or not getattr(tr, "path", None):
        return None
    if getattr(tr, "names_walk", None) is not None:      # the six readers
        return tr.names_walk
    scopes = moe.op_scopes(tr.path, tracing.names()["device_plane_prefix"])
    parsed = {}                     # HLO text -> its names, parsed once
    by_scope, rest = {}, {}
    unnamed = 0.0
    for d in tr.devices:
        for e in tr.in_window(d):
            found = parsed.get(e.name)
            if found is None:
                found = parsed[e.name] = tuple(dict.fromkeys(
                    components(scopes.get(e.name))))
            for name in found:
                by_scope[name] = by_scope.get(name, 0.0) + e.self_s
            if all(name.startswith(STEP) for name in found):
                unnamed += e.self_s
                key = tracing.short_name(e.name)
                rest[key] = rest.get(key, 0.0) + e.self_s
    n = max(1, len(tr.devices))

    def table(by):
        return dict(sorted(((k, s / n) for k, s in by.items()),
                           key=lambda kv: -kv[1]))

    by_scope = table(by_scope)
    tr.names_walk = {"busy_s": tr.busy_s(), "by_scope": by_scope,
                     "by_kernel": {k: s for k, s in by_scope.items()
                                   if k.startswith(KERNEL)},
                     "unnamed_s": unnamed / n, "unnamed_ops": table(rest)}
    say = getattr(v, "say", None)
    if say is not None:
        say("names_device_seconds", busy_s=tr.names_walk["busy_s"],
            scoped_ops=len(scopes), unnamed_s=unnamed / n,
            by_scope=tr.names_walk["by_scope"],
            by_kernel=tr.names_walk["by_kernel"],
            unnamed_top=list(tr.names_walk["unnamed_ops"].items())[:12])
    return tr.names_walk


def share(v, name):
    """100 x own device seconds of the operations whose ``tf_op`` holds
    ``name`` / busy seconds; None where nothing in the window ran under it
    (the parent of PR 57, a cell whose program has no such layer)."""
    walked = walk(v)
    if not walked or walked["busy_s"] <= 0 \
            or not walked["by_scope"].get(name):
        return None
    return 100.0 * walked["by_scope"][name] / walked["busy_s"]


def unnamed_share(v):
    """100 x own device seconds of the operations under no ``dstpu.*`` name
    but a ``dstpu.step.*`` / busy seconds; None without a trace or where
    nothing ran in the window."""
    walked = walk(v)
    if not walked or walked["busy_s"] <= 0:
        return None
    return 100.0 * walked["unnamed_s"] / walked["busy_s"]


if __name__ == "__main__":
    # the three tables of one trace, whatever cell it is of
    import json
    import os
    import sys
    import types

    from . import trace as tracing
    given = sys.argv[1]                 # a trace directory or an .xplane.pb
    view = types.SimpleNamespace(trace=tracing.Trace(given)
                                 if os.path.isfile(given)
                                 else tracing.load(given))
    print(json.dumps(walk(view), indent=1))
