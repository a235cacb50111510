"""Model FLOP/s utilization of a share of a sparse model: tokens/s/chip x
the operations the forward and backward passes require per token AT THIS
SHARE (pbench/mla_moe.train_flops_per_token: the held experts at top_k x
held / published a token, uniform routing; backward = 2 x forward,
recomputation not counted) over the chip's bf16 peak. The rate is that of
the window's steps outside the profiler's capture. None for a configuration
of another family (its sizes name no held experts). perfbench/TRAIN_MOE.md."""
from pbench import mla_moe


def read(v):
    rate = v.counters.get("tok_s_chip_outside_capture")
    if not rate or "n_experts_published" not in v.sizes \
            or "seq_len" not in v.counters:
        return None
    per_token = mla_moe.train_flops_per_token(v.sizes, v.counters["seq_len"])
    v.say("mfu_routed", flops_per_token=per_token,
          tok_s_chip_outside_capture=rate)
    return 100.0 * rate * per_token / v.peaks["bf16_flops_per_s"]
