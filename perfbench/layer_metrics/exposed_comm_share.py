"""Share of the traced steps that a device spent in collective operations
while no compute ran on it (pbench/trace.py, collective_seconds). None on
one chip: there is no collective to expose."""


def read(v):
    if v.trace is None or v.chips < 2 or v.trace.window_s <= 0:
        return None
    every, exposed = v.trace.collective_seconds()
    v.say("collectives", own_seconds_all=every, own_seconds_exposed=exposed,
          window_s=v.trace.window_s)
    return 100.0 * exposed / v.trace.window_s
