"""GPT-2 with Mixture-of-Experts MLPs (expert parallelism flagship).

Counterpart of the reference's MoE training targets (deepspeed/moe/layer.py
MoE wrapping an expert MLP; test fixture tests/unit/simple_model.py
SimpleMoEModel). Every block's dense MLP is replaced by a top-k routed MoE;
expert weights carry a leading (L, E, ...) layout so the same ``lax.scan``
block iteration works, and the 'expert' mesh axis shards E (EP) while
'tensor' shards the FFN dim (TP) — EP x TP experts like the reference's
module_inject MoE sharding.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..moe.layer import MoE
from .gpt2 import GPT2, GPT2Config


@dataclass(frozen=True)
class GPT2MoEConfig(GPT2Config):
    num_experts: int = 8
    moe_top_k: int = 1
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    noisy_gate_policy: str = None        # None | 'RSample' | 'Jitter'
    moe_loss_coeff: float = 0.01
    moe_drop_tokens: bool = True
    # 'dense' = GShard capacity dispatch (EP-shaped); 'ragged' = dropless
    # grouped GEMM for DP/TP meshes (EP via the shard_map all_to_all)
    moe_backend: str = "dense"
    # ragged backend's expert-product engine: "auto" (ragged_dot for
    # these two-product experts: sharded_moe.resolve_grouped_params) |
    # True (Pallas grouped-GEMM kernel) | False (lax.ragged_dot)
    moe_grouped_kernel: object = "auto"


    def num_params(self):
        dense = super().num_params()
        # replace per-layer dense MLP params with E experts + gate
        mlp = 2 * self.d_model * self.d_ff + self.d_ff + self.d_model
        moe = (self.num_experts * mlp + self.d_model * self.num_experts)
        return dense + self.n_layer * (moe - mlp)


class GPT2MoE(GPT2):
    def __init__(self, config: GPT2MoEConfig):
        super().__init__(config)
        self.moe_loss_coeff = config.moe_loss_coeff
        self.moe = MoE(
            hidden_size=config.d_model, ffn_hidden_size=config.d_ff,
            num_experts=config.num_experts, k=config.moe_top_k,
            capacity_factor=config.capacity_factor,
            eval_capacity_factor=config.eval_capacity_factor,
            min_capacity=config.min_capacity,
            noisy_gate_policy=config.noisy_gate_policy,
            drop_tokens=config.moe_drop_tokens,
            dtype=jnp.dtype(config.dtype), backend=config.moe_backend,
            grouped_kernel=config.moe_grouped_kernel)

    def init(self, rng):
        import math
        params = super().init(rng)
        cfg = self.config
        blocks = dict(params["blocks"])
        for k in ("wup", "bup", "wdown", "bdown"):
            del blocks[k]
        moe_params = self.moe.init(
            jax.random.fold_in(rng, 17), stack=cfg.n_layer,
            out_std=0.02 / math.sqrt(2 * cfg.n_layer))
        blocks["moe"] = moe_params
        params["blocks"] = blocks
        return params

    def partition_specs(self, topology=None):
        specs = super().partition_specs(topology)
        blocks = dict(specs["blocks"])
        for k in ("wup", "bup", "wdown", "bdown"):
            del blocks[k]
        blocks["moe"] = self.moe.partition_specs(stacked=True)
        specs["blocks"] = blocks
        return specs

    def _requires_train_rng(self):
        cfg = self.config
        if self.moe.gate is None:  # ragged backend: deterministic routing
            return super()._requires_train_rng()
        return (super()._requires_train_rng()
                or cfg.noisy_gate_policy is not None
                or (cfg.moe_top_k == 2
                    and self.moe.gate.top2_2nd_expert_sampling))

    def _mlp(self, h, layer, rng, *, train, seq_sharded, constrain):
        # an EXPLICIT engine-config 'moe' block setting (non-"auto")
        # overrides the model-config knob; otherwise the model config
        # stands (both default "auto" — the winner cache decides)
        moe_cfg = getattr(self, "_moe_cfg", None)
        override = (moe_cfg.grouped_kernel
                    if moe_cfg is not None
                    and moe_cfg.grouped_kernel != "auto" else None)
        y, aux, _ = self.moe.apply(layer["moe"], h, rng=rng, train=train,
                                   seq_sharded=seq_sharded,
                                   grouped_kernel=override)
        return y, aux
