"""How late the load generator ran: put time less due time, 99th percentile
over the requests due in the window (the benchmark's own clock). One thread
plays the clients and steps the router, so a request that falls due while a
``Router.step()`` is running is put when that step returns; latencies are
taken from the due time, so this lateness is inside them, not hidden."""
from pbench import common


def read(v):
    return common.percentile(v.counters["late_ms"], 99)
