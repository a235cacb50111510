"""Share of the traced window in which the host was inside the program's
``dstpu.engine.build`` spans (assembling a batch: ``decode_batch``, the
numpy id / table / offset arrays, the rng split) while no operation ran on
the device. One of the three parts of ``sched_host_share`` seen from inside
(build, fetch, post never overlap); what is left of it is the router's and
the admission's own bookkeeping."""


def idle_share(v, span_name, metric):
    """100 x (device-idle seconds inside spans ``span_name``) / window;
    None without a trace or on a program that opens no such span."""
    tr = v.trace
    if tr is None or tr.window_s <= 0:
        return None
    n = len(tr.host_spans(span_name))
    if not n:
        return None
    idle_s = tr.span_idle_s(span_name)
    v.say(metric, spans=n, idle_s=idle_s, window_s=tr.window_s)
    return 100.0 * idle_s / tr.window_s


def read(v):
    return idle_share(v, "dstpu.engine.build", "host_build_share")
