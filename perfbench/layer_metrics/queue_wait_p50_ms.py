"""Median wait from ``Router.put`` to a batch slot, as the program counts
it (``ServingTelemetry.on_admit``, a bounded window over the requests
admitted since the engine started): the ``queue_p50_us`` the last
``dstpu.engine.step`` span of the traced window carries."""

import os


def read(v):
    if v.trace is None:
        return None
    steps = v.trace.host_spans("dstpu.engine.step")
    if not steps:
        return None
    last = max(steps, key=lambda e: e.start).stats
    admits = v.trace.host_spans("dstpu.engine.admit")
    v.say("queue_wait_p50_ms", engine_steps=len(steps),
          admitted_total=last.get("admitted_total"),
          queue_p90_ms=int(last.get("queue_p90_us", 0)) / 1e3,
          pending_at_last_step=last.get("pending"),
          admits_in_trace=len(admits),
          wait_ms_in_trace=[int(e.stats.get("wait_us", 0)) / 1e3
                            for e in admits],
          trace_bytes=os.path.getsize(v.trace.path))
    return int(last["queue_p50_us"]) / 1e3
