"""Share of the decode batch's slots that held a live sequence, over the
decode-bearing dispatches of the traced window: 100 x sum(active) /
sum(slots) of the program's ``dstpu.engine.dispatch`` spans of kind
``decode`` or ``fused`` (names: ``monitor/tag_schema.py:SPAN_SCHEMA``)."""

from pbench import common

DISPATCH = "dstpu.engine.dispatch"


def dispatches(v, *kinds):
    """The window's dispatch spans of these kinds (of every kind if none
    is named); [] without a trace or on a program that opens none."""
    if v.trace is None:
        return []
    return [e for e in v.trace.host_spans(DISPATCH)
            if not kinds or e.stats.get("kind") in kinds]


def median_dispatch_ms(v, metric, *kinds):
    """Median duration in ms of the window's dispatch spans of ``kinds``;
    says how many it read, by kind."""
    hit = dispatches(v, *kinds)
    if not hit:
        return None
    ms = [1e3 * e.dur for e in hit]
    v.say(metric, spans=len(ms), min_ms=min(ms), max_ms=max(ms),
          by_kind={k: sum(1 for e in hit if e.stats.get("kind") == k)
                   for k in kinds})
    return common.percentile(ms, 50)


def read(v):
    every = dispatches(v)
    if not every:
        return None
    by_kind = {}
    for e in every:
        by_kind.setdefault(str(e.stats.get("kind")), []).append(1e3 * e.dur)
    hit = dispatches(v, "decode", "fused")
    active = sum(int(e.stats["active"]) for e in hit)
    slots = sum(int(e.stats["slots"]) for e in hit)
    v.say("batch_occupancy",
          dispatches_by_kind={k: len(ms) for k, ms in by_kind.items()},
          median_ms_by_kind={k: common.percentile(ms, 50)
                             for k, ms in by_kind.items()},
          active=active, slots=slots,
          decode_steps=sum(int(e.stats["steps"]) for e in hit),
          chunk_tokens=sum(int(e.stats["chunk_tokens"]) for e in every))
    return 100.0 * active / slots if slots else None
