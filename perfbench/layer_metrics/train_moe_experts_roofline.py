"""A training step's held experts against their floor over the traced
steps: 3 products x (forward + dx + dW) over the rows THE ROUTER SENT the
held experts in those steps, the weights of the experts that got at least
one read twice, every held expert's gradient written once a sparse layer
and step (pbench/mla_moe.held_rows_work over the runner's
``held_rows_traced`` / ``held_experts_called_traced``, which the builder
counts from the model's own routing on each traced step's own parameters;
one chip: nothing is read on more), over the own device time under
``dstpu.moe.experts`` WHATEVER implements it (lax.ragged_dot, the Pallas
grouped kernels or a walk over the held rows): the forward's recomputation
and whatever else runs under the scope are inside this share, not excused.
Before PR 55 the rows were an even router's (0.75 a token, every expert
called), which this cell's router does not send. perfbench/TRAIN_MOE.md."""
from pbench import mla_moe


def read(v):
    if "held_rows_traced" not in v.counters or "moe_d_ff" not in v.sizes:
        return None
    if v.chips != 1:
        # the experts called are counted over the whole batch: which of
        # them a chip's own rows called is not counted yet, and the
        # batch's would put its floor too high
        return None
    ops, moved = mla_moe.held_rows_work(
        v.counters["held_rows_traced"],
        v.counters["held_experts_called_traced"], v.sizes,
        layer_steps=v.counters["steps_traced"] * v.sizes["n_sparse"])
    return mla_moe.roofline(v, mla_moe.EXPERTS, "train_moe_experts_roofline",
                            ops, moved)
