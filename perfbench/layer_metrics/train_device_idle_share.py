"""Share of the traced steps in which no operation ran on the device, in %."""


def read(v):
    share = v.trace.idle_share() if v.trace is not None else None
    return None if share is None else 100.0 * share
