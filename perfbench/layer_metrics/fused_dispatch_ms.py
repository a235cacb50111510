"""Median duration of the program's ``dstpu.engine.dispatch`` spans that
carry a prompt chunk, in the traced window: kind ``fused`` (one chunk and
the decode steps that ride it, from the assembled batch to the last posted
token) and kind ``chunk`` (a chunk with nothing decoding beside it, which
is what a cell under its knee mostly sends: cell 4). One median over both;
the line it says counts each kind.

What it is in cell 4 (my chip runs, PR 51, four seeds): a ``chunk`` dispatch
in mid-prompt is not read back (``engine_v2._step_splitfuse_chunk``: only a
prompt's last chunk waits for its token), so its span is the launch alone,
1.4-2.3 ms, and the prompt's last one holds the device time of all before
it (51-178 ms). Where the slice holds mostly ``chunk`` spans the median is
a launch (1.69, 1.84, 2.27 ms); where ``fused`` ones outnumber them it is a
fused dispatch (37.8 ms; ``batch_occupancy``'s line says each kind's own
median, 34-39 ms for ``fused``). A number on every seed, and in that cell
the time of a launch more often than of a chunk: PERF.md section 7. In
cells 9 and 10, saturated, every chunk rides beside decode steps and the
reading is a fused dispatch's time."""

from pbench import common


def read(v):
    return common.load_module("layer_metrics", "batch_occupancy") \
        .median_dispatch_ms(v, "fused_dispatch_ms", "fused", "chunk")
