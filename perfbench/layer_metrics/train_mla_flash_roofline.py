"""A training step's MLA attention against its own floor over the traced
steps: forward 2 products and backward 5, causal, keys 192 and values 128
wide, operands read and results written once (pbench/mla_moe.
mla_flash_work), a call a layer and step, over the own device time under
``dstpu.attn.mla`` WHATEVER implements it: a kernel that pads V to the key
width, recomputes the forward or spends time on rotary reads low; one with
a value width of its own can claim on this. perfbench/TRAIN_MOE.md."""
from pbench import mla_moe


def read(v):
    if "steps_traced" not in v.counters or "kv_lora_rank" not in v.sizes:
        return None
    calls = v.counters["steps_traced"] * v.sizes["n_layer"]
    ops, moved = mla_moe.mla_flash_work(
        v.counters["micro_batch_per_chip"], v.sizes, v.counters["seq_len"])
    return mla_moe.roofline(v, mla_moe.MLA, "train_mla_flash_roofline",
                            calls * ops, calls * moved)
