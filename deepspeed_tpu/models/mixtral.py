"""Mixtral-class model: Llama attention (GQA + rope) with a sparse MoE
SwiGLU FFN per block.

Counterpart of reference ``inference/v2/model_implementations/mixtral``
(FastGen's Mixtral support over moe_gather/moe_scatter + cutlass
moe_gemm). Here the expert FFN is the dropless grouped-GEMM pattern
(``lax.ragged_dot`` — the moe_gemm role): tokens sort by routed expert,
each expert multiplies exactly its contiguous group, outputs unsort and
combine by the top-k router weights. The same ``_mlp`` serves training,
the contiguous-cache decode, and ALL THREE v2 paged serving programs
(inherited from Llama — apply_paged_prefill/apply_paged_chunk/
apply_paged_decode call ``_mlp`` per layer, so the engine's
``expert_parallel > 1`` mesh routes every serving dispatch through the
ragged EP all_to_all below; attention rides Llama's paged Pallas
kernels under the same engine ``paged_kernel`` knob).

Training note: ``_mlp`` and the grouped products are differentiable and
``Mixtral`` / ``OLMoE`` train through ``deepspeed_tpu.initialize`` like
Llama, but the router's load-balance aux loss is not threaded through
Llama's apply: use GPT2MoE for aux-loss-supervised MoE training parity
tests. The sparse family that is trained on the chip is
``models/deepseek_v3.py`` (the same ``route_topk`` / ``moe_swiglu_routed``,
forward and backward; its gate balances by a correction bias, not a loss).
"""

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .llama import Llama, LlamaConfig, _rms_norm


@dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    moe_top_k: int = 2
    # HF ``norm_topk_prob``: divide the k routing probabilities by their
    # sum (mixtral) or use them as the softmax gave them (olmoe)
    norm_topk_prob: bool = True

    def num_params(self):
        base = super().num_params()
        # replace the dense SwiGLU (3 * D * F) with E experts + router
        L, D, F, E = self.n_layer, self.d_model, self.ffn_dim, \
            self.num_experts
        return base - L * 3 * D * F + L * (D * E + E * 3 * D * F)


MIXTRAL_TINY = MixtralConfig(n_layer=2, n_head=4, n_kv_heads=2, d_model=128,
                             max_seq_len=128, vocab_size=512, remat=False,
                             num_experts=4, moe_top_k=2)
MIXTRAL_8X7B = MixtralConfig(n_layer=32, n_head=32, n_kv_heads=8,
                             d_model=4096, d_ff=14336, max_seq_len=8192,
                             vocab_size=32000, num_experts=8, moe_top_k=2)


class Mixtral(Llama):
    """Params: Llama attention tensors; blocks swap wgate/wup/wdown for
      moe_gate (L,D,E), moe_w1 (L,E,D,F), moe_w3 (L,E,D,F),
      moe_w2 (L,E,F,D)   (w1=gate, w3=up, w2=down — Mixtral naming).

    That is the TRAINING tree (``init``; ``apply`` scans the stacked
    blocks). The SERVED tree (``serving_params``; what the inference
    engines hold; ``init_served``, which ``inference/utils.shard_params``
    runs for seeded weights so that the stacked arrays never exist on
    the device) keeps the three expert arrays as
    per-layer LISTS of (E,D,F) / (E,F,D): ``lax.ragged_dot`` and the
    Pallas grouped kernel cannot read a layer's slice of a stacked
    array in place the way a dense ``dot`` can — XLA:TPU materialises
    the slice, a weight-sized temporary per layer per step (PERF.md,
    PR 26) — so each layer's experts are program operands of their
    own, as the KV pools already are."""

    # served per layer, never stacked (see the class docstring)
    _PER_LAYER = ("moe_w1", "moe_w3", "moe_w2")

    def init_dense(self, rng):
        """Everything but the experts: Llama's tensors and the router."""
        cfg = self.config
        params = super().init(rng)
        blocks = params["blocks"]
        for k in ("wgate", "wup", "wdown"):
            del blocks[k]
        # router stays fp32 (routing is precision-sensitive)
        blocks["moe_gate"] = jax.random.normal(
            jax.random.fold_in(rng, 17),
            (cfg.n_layer, cfg.d_model, cfg.num_experts), jnp.float32) * 0.02
        return params

    def init_experts(self, rng, i):
        """Layer ``i``'s experts, seeded by (rng, i): the stacked and the
        per-layer trees hold the same values, and a served model never
        needs the stacked one to exist."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        D, F, E = cfg.d_model, cfg.ffn_dim, cfg.num_experts
        ks = jax.random.split(jax.random.fold_in(
            jax.random.fold_in(rng, 18), i), 3)
        std = 0.02
        res_std = std / math.sqrt(2 * cfg.n_layer)

        def nrm(key, shape, s):
            return (jax.random.normal(key, shape, jnp.float32) * s).astype(dt)

        return {"moe_w1": nrm(ks[0], (E, D, F), std),
                "moe_w3": nrm(ks[1], (E, D, F), std),
                "moe_w2": nrm(ks[2], (E, F, D), res_std)}

    def init_served(self, rng):
        """The served tree from the seed, with no stacked array on the
        way: what ``shard_params`` runs for seeded weights, and what a
        compile of the serving programs takes its shapes from
        (``jax.eval_shape``)."""
        params = self.init_dense(rng)
        layers = [self.init_experts(rng, i)
                  for i in range(self.config.n_layer)]
        for k in self._PER_LAYER:
            params["blocks"][k] = [e[k] for e in layers]
        return params

    def init(self, rng):
        params = self.init_served(rng)
        for k in self._PER_LAYER:
            params["blocks"][k] = jnp.stack(params["blocks"][k])
        return params

    # fused weight-quant serving keeps the expert FFN weights quantized
    # (consumed by _grouped_swiglu_ffn -> grouped_swiglu_wq)
    _WQ_KEEP = ("moe_w1", "moe_w3", "moe_w2")

    def _moe_knobs(self):
        """(grouped_kernel, hierarchical, dcn_quantize, int8_matmul)
        from the engine-installed ``moe`` config block plus the
        QuantizeConfig int8-compute lever; module defaults when no
        engine installed one (direct model use)."""
        cfg = getattr(self, "_moe_cfg", None)
        q8 = getattr(self, "_moe_int8", False)
        if cfg is None:
            return "auto", "auto", False, q8
        return (cfg.grouped_kernel, cfg.hierarchical_a2a,
                cfg.dcn_quantize, q8)

    def partition_specs(self, topology=None):
        specs = super().partition_specs(topology)
        blocks = specs["blocks"]
        for k in ("wgate", "wup", "wdown"):
            del blocks[k]
        blocks["moe_gate"] = P(None, None, None)
        # experts over 'expert', FFN dim over 'tensor' (EP x TP); at pod
        # scale — a data_outer (DCN) axis and the hierarchical a2a
        # engaged — experts span the combined (outer, expert) shard grid
        # so the weight layout matches the two-stage exchange's in_specs
        # (the exchange reshards on mismatch, but then every serving
        # dispatch would pay the gather)
        eaxis = "expert"
        if topology is not None:
            from ..moe.sharded_moe import resolve_hierarchical_a2a
            _, hier_knob, _, _ = self._moe_knobs()
            if resolve_hierarchical_a2a(
                    hier_knob, topology.axis_size("data_outer"),
                    self.config.num_experts,
                    topology.axis_size("expert")):
                eaxis = ("data_outer", "expert")
        blocks["moe_w1"] = P(None, eaxis, None, "tensor")
        blocks["moe_w3"] = P(None, eaxis, None, "tensor")
        blocks["moe_w2"] = P(None, eaxis, "tensor", None)
        return specs

    def _mlp(self, x, layer):
        """Dropless top-k SwiGLU MoE over the flattened tokens.

        With an expert mesh axis > 1 the FFN routes through the explicit
        shard_map all_to_all path (moe/sharded_moe.py
        ``moe_swiglu_ragged_ep``): GSPMD silently mis-partitions
        ``lax.ragged_dot`` over expert-sharded weights (off-shard
        experts' rows come back garbage), so EP must be manual. TP-only
        ('tensor') sharding stays on the dense path — GSPMD handles it."""
        cfg = self.config
        B, T, D = x.shape
        k = cfg.moe_top_k
        with jax.named_scope("dstpu.moe.route"):
            h = _rms_norm(x, layer["rms2"], cfg.rms_eps)
        grouped, hier, dcn_q, q8 = self._moe_knobs()
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty and mesh.shape.get("expert", 1) > 1:
            from ..moe.sharded_moe import moe_swiglu_ragged_ep
            y = moe_swiglu_ragged_ep(
                h, layer["moe_gate"], layer["moe_w1"], layer["moe_w3"],
                layer["moe_w2"], k=k, hierarchical=hier,
                dcn_quantize=dcn_q, grouped_kernel=grouped,
                int8_matmul=q8, renormalize=cfg.norm_topk_prob)
            return y.astype(x.dtype)
        from ..moe.sharded_moe import moe_swiglu_routed, route_topk
        with jax.named_scope("dstpu.moe.route"):
            xs = h.reshape(-1, D)
            weights, experts = route_topk(xs, layer["moe_gate"], k,
                                          cfg.norm_topk_prob)
        y = moe_swiglu_routed(xs, weights, experts, layer["moe_w1"],
                              layer["moe_w3"], layer["moe_w2"], grouped, q8)
        return y.astype(x.dtype).reshape(B, T, D)
