"""Share of the traced window inside the program's ``dstpu.engine.prefill``
spans (clipped to the window): the wall in which decode stands still for a
prompt's bucketed prefill."""


def _clipped_s(trace, events):
    return sum(max(0.0, min(e.end, trace.t1) - max(e.start, trace.t0))
               for e in events)


def read(v):
    tr = v.trace
    if tr is None or tr.window_s <= 0:
        return None
    prefills = tr.host_spans("dstpu.engine.prefill")
    dispatches = tr.host_spans("dstpu.engine.dispatch")
    if not prefills and not dispatches:
        return None
    prefill_s = _clipped_s(tr, prefills)
    # how much of the benchmark's own span around Router.step the
    # program's timeline accounts for: prefills + dispatches + the builds
    # that precede a dispatch (a prefill's build lies inside it)
    builds = [b for b in tr.host_spans("dstpu.engine.build")
              if not any(p.start <= b.start and b.end <= p.end
                         for p in prefills)]
    v.say("prefill_wall_share", prefills=len(prefills),
          prefill_s=prefill_s,
          dispatch_s=_clipped_s(tr, dispatches),
          build_outside_prefill_s=_clipped_s(tr, builds),
          engine_step_s=_clipped_s(tr, tr.host_spans("dstpu.engine.step")),
          router_step_s=_clipped_s(tr, tr.host_spans("dstpu.router.step")),
          perfbench_router_step_s=_clipped_s(
              tr, tr.host_spans("perfbench.router_step")),
          window_s=tr.window_s)
    return 100.0 * prefill_s / tr.window_s
