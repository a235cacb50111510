"""A training step's held experts against their floor over the traced
steps: 3 products x (forward + dx + dW) over the expected held rows, each
held weight read twice and its gradient written once (pbench/mla_moe.
held_experts_work), a call a sparse layer and step, over the own device
time under ``dstpu.moe.experts`` WHATEVER implements it (lax.ragged_dot or
the Pallas grouped kernels): products over the absent experts' rows and
the forward's recomputation are inside this share, not excused.
perfbench/TRAIN_MOE.md."""
from pbench import mla_moe


def read(v):
    if "steps_traced" not in v.counters or "n_sparse" not in v.sizes \
            or "moe_d_ff" not in v.sizes:
        return None
    calls = v.counters["steps_traced"] * v.sizes["n_sparse"]
    tokens = v.counters["tokens_traced"] / v.counters["steps_traced"] \
        / v.chips if v.counters["steps_traced"] else 0
    ops, moved = mla_moe.held_experts_work(tokens, v.sizes)
    return mla_moe.roofline(v, mla_moe.EXPERTS, "train_moe_experts_roofline",
                            calls * ops, calls * moved)
