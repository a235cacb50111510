"""OLMoE (allenai/OLMoE-1B-7B): a Mixtral-shaped block — Llama attention
(MHA, half-split rotary over the full head) and a dropless top-k SwiGLU
MoE — with two differences, both configuration (HF ``modeling_olmoe``):

* QK-norm: ``q = rms(x Wq; g_q)``, ``k = rms(x Wk; g_k)``, the norm taken
  over the WHOLE projection (all heads together) before the split into
  heads and before rope (``LlamaConfig.qk_norm``);
* ``norm_topk_prob: false``: float32 softmax over all 64 router logits,
  top-8, the eight probabilities used as they are — they are NOT divided
  by their sum (``MixtralConfig.norm_topk_prob``).

No shared expert, no bias anywhere, untied head, final RMSNorm. Served
like Mixtral: each layer's experts are operands of the serving programs
(``Mixtral._PER_LAYER``), the one-device path runs the forward grouped
kernel on a TPU at a serving program's few rows a group and
``lax.ragged_dot`` elsewhere (``sharded_moe.resolve_grouped_params``), an
``expert`` mesh axis > 1 the all_to_all path.
"""

from dataclasses import dataclass

from .mixtral import Mixtral, MixtralConfig


@dataclass(frozen=True)
class OLMoEConfig(MixtralConfig):
    vocab_size: int = 50304
    max_seq_len: int = 4096
    n_layer: int = 16
    n_head: int = 16
    n_kv_heads: int = 16
    d_model: int = 2048
    d_ff: int = 1024                 # one expert's width
    num_experts: int = 64
    moe_top_k: int = 8
    norm_topk_prob: bool = False
    qk_norm: bool = True
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5


# https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct config.json:
# 6,919,161,856 parameters, 1.3 B of them active per token
OLMOE_1B_7B = OLMoEConfig()
OLMOE_TINY = OLMoEConfig(n_layer=2, n_head=4, n_kv_heads=4, d_model=64,
                         d_ff=32, max_seq_len=128, vocab_size=512,
                         num_experts=16, moe_top_k=4, remat=False)

OLMOE_PRESETS = {"tiny": OLMOE_TINY, "olmoe-1b-7b": OLMOE_1B_7B}


class OLMoE(Mixtral):
    """Params: Mixtral's tree plus blocks q_norm (L,D), k_norm (L,KVH*hd).
    Everything OLMoE-specific is in ``OLMoEConfig``."""
