"""Decode tokens a ``Router.step()`` returned, mean over the steps of the
window ((uid, token) pairs; the token a prefill emits is never among them,
see README, 'First token')."""


def read(v):
    steps = v.counters["steps_in_window"]
    return v.counters["pairs_in_window"] / steps if steps else None
