"""Model FLOP/s utilization: tokens/s/chip x the operations the forward and
backward passes require per token (pbench/flops.train_flops_per_token) over
the chip's bf16 peak. Recomputed operations do not count. The rate is that
of the window's steps outside the profiler's capture, whose start and stop
stall the host."""
from pbench import flops


def read(v):
    rate = v.counters.get("tok_s_chip_outside_capture")
    if not rate:
        return None
    per_token = flops.train_flops_per_token(v.sizes, v.counters["seq_len"])
    v.say("mfu", flops_per_token=per_token, tok_s_chip_outside_capture=rate)
    return 100.0 * rate * per_token / v.peaks["bf16_flops_per_s"]
