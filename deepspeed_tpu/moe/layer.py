"""MoE module: owns expert params + gate (reference moe/layer.py:17 MoE).

Functional style matching the rest of the model zoo: ``init(rng) ->
params``, ``apply(params, x, rng=, train=) -> (y, l_aux, exp_counts)``.
Stackable: a leading layer dim on every param works under ``lax.scan``
(init with ``stack=L``).
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .sharded_moe import (TopKGate, moe_layer, moe_layer_ragged,
                          moe_layer_ragged_ep)


class MoE:
    def __init__(self, hidden_size, ffn_hidden_size=None, num_experts=8,
                 k=1, capacity_factor=1.0, eval_capacity_factor=1.0,
                 min_capacity=4, noisy_gate_policy=None, drop_tokens=True,
                 top2_2nd_expert_sampling=True, activation=jax.nn.gelu,
                 dtype=jnp.bfloat16, backend="dense",
                 grouped_kernel="auto"):
        """backend: 'dense' = GShard static-capacity dispatch (the
        SPMD/EP-shaped path with token dropping at capacity); 'ragged' =
        DROPLESS grouped GEMM (megablox / reference cutlass moe_gemm) —
        under an expert-parallel mesh this routes through
        moe_layer_ragged_ep (shard_map + all_to_all + per-shard grouped
        product), single-shard otherwise.

        grouped_kernel: the ragged backend's expert-product engine —
        "auto" (default: lax.ragged_dot; only a SwiGLU chain has a
        forward kernel to resolve to, sharded_moe.resolve_grouped_params)
        | True (the Pallas grouped-GEMM kernel,
        ops/pallas/grouped_matmul.py) | False (ragged_dot)."""
        self.hidden_size = hidden_size
        self.ffn_hidden_size = ffn_hidden_size or 4 * hidden_size
        self.num_experts = num_experts
        self.k = k
        self.backend = backend
        if grouped_kernel not in (True, False, "auto"):
            raise ValueError(
                f"grouped_kernel must be true|false|'auto', got "
                f"{grouped_kernel!r}")
        self.grouped_kernel = grouped_kernel
        if backend == "ragged":
            # dropless routing has no capacity knobs (vacuous) but noisy
            # gating would be silently ignored — reject, don't lie
            if noisy_gate_policy is not None:
                raise ValueError(
                    "backend='ragged' uses deterministic top-k routing; "
                    f"noisy_gate_policy={noisy_gate_policy!r} is not "
                    "supported (use backend='dense')")
            if k < 1:
                raise ValueError("k must be >= 1")
            self.gate = None
        else:
            self.gate = TopKGate(k, capacity_factor, eval_capacity_factor,
                                 min_capacity, noisy_gate_policy,
                                 drop_tokens, top2_2nd_expert_sampling)
        self.activation = activation
        self.dtype = dtype

    def init(self, rng, stack=None, std=0.02, out_std=None):
        M, F, E = self.hidden_size, self.ffn_hidden_size, self.num_experts
        lead = () if stack is None else (stack,)
        ks = jax.random.split(rng, 3)
        out_std = std if out_std is None else out_std

        def nrm(key, shape, s):
            return (jax.random.normal(key, shape, jnp.float32) * s).astype(
                self.dtype)

        return {
            # gate stays fp32: routing decisions are precision-sensitive
            # (reference keeps gate weights fp32 under fp16 training)
            "gate_w": jax.random.normal(ks[0], lead + (M, E),
                                        jnp.float32) * std,
            "wi": nrm(ks[1], lead + (E, M, F), std),
            "bi": jnp.zeros(lead + (E, F), self.dtype),
            "wo": nrm(ks[2], lead + (E, F, M), out_std),
            "bo": jnp.zeros(lead + (E, M), self.dtype),
        }

    def partition_specs(self, stacked=False):
        """Experts sharded on 'expert' (EP), FFN dim on 'tensor' (TP) —
        the reference's EP x TP expert sharding (module_inject MoE)."""
        lead = (None,) if stacked else ()
        return {
            "gate_w": P(*lead, None, None),
            "wi": P(*lead, "expert", None, "tensor"),
            "bi": P(*lead, "expert", "tensor"),
            "wo": P(*lead, "expert", "tensor", None),
            "bo": P(*lead, "expert", None),
        }

    def apply(self, params, x, *, rng=None, train=True, seq_sharded=False,
              grouped_kernel=None):
        """``grouped_kernel`` overrides the construction-time knob for
        this dispatch (None = keep it) — how an engine-level ``moe``
        config block reaches a layer built before the engine existed."""
        if self.backend == "ragged":
            knob = self.grouped_kernel if grouped_kernel is None \
                else grouped_kernel
            return moe_layer_ragged_ep(
                x, params["gate_w"], params["wi"], params["bi"],
                params["wo"], params["bo"], k=self.k,
                activation=self.activation, seq_sharded=seq_sharded,
                grouped_kernel=knob)
        return moe_layer(x, params["gate_w"], params["wi"], params["bi"],
                         params["wo"], params["bo"], self.gate, rng=rng,
                         train=train, activation=self.activation,
                         seq_sharded=seq_sharded)
