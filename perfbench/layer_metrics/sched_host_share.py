"""Share of the traced window in which the host was inside ``Router.step()``
(the benchmark's span) while no operation ran on the device: scheduling,
batch assembly, dispatch and the blocking read of the tokens."""


def read(v):
    if v.trace is None or v.trace.window_s <= 0:
        return None
    return 100.0 * v.trace.span_idle_s("perfbench.router_step") \
        / v.trace.window_s
