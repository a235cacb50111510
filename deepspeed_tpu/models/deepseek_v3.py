"""DeepSeek-V3 family (``model_type`` deepseek_v3), TRAINABLE: multi-head
latent attention (MLA), pre-norm RMSNorm blocks, a SwiGLU FFN that is dense
in the leading layers and, in the rest, the ``noaux_tc`` gate (sigmoid
scores, a correction bias that chooses and does not weigh, group-limited
top-k, renormalised and scaled) over routed experts beside shared experts
every token takes; an untied head. ``q_lora_rank`` None is the form with a
direct query projection (Kanana-2-30B-A3B); a number is V3's query latent,
the same code.

The equations are written out in ``perfbench/references/deepseek_v3.py``,
which this file has to equal. ``models/deepseek_v32.py`` is the SERVING
program of the same attention read through a learned selection (a latent
paged cache, absorbed decode, float32 stream in two bfloat16 pieces); this
file is the training form and shares its parameter layout (``wkv_a``,
``wk_b``, ``wv_b``, ``w1`` = [gate | up]) and its rotary.

**Training numerics are the engine's**: parameters and the residual stream
in the engine's dtype (bfloat16 in the benchmark), float32 norms, router
logits (the product at ``HIGHEST``), softmax and loss.

**Attention** is the expanded form: every position's latent to every head's
192-wide key (128 no-position dims + the ONE 64-wide rotary key all heads
share) and 128-wide value, then causal flash attention
(``ops/pallas/flash_attention.py``) forward and backward. That kernel
takes the values at their own width: V goes in 128 wide beside the
192-wide keys and the output comes back 128 wide, nothing padded or sliced.

**One chip's share of an expert-parallel group.** The router keeps the
published ``n_routed_experts`` outputs; the layer holds ``experts_held`` of
them from ``experts_offset`` and computes ``sum over held experts +
Shared(x)``: what the absent experts would add is left out and that partial
sum goes on (``moe/sharded_moe.py:moe_swiglu_routed``, ``held=``), forward
and backward. The layer says how many experts the router chose among, so
only the held experts' routed rows are gathered, multiplied and scattered,
a chunk at a time; the absent experts' rows (7/8 of them at an even router,
more once this share's router has trained) are never moved. Nothing here
stands in for the other chips or their exchange.

**A buffer leaf.** ``gate_bias`` (``e_score_correction_bias``) is a float32
buffer: it takes no gradient (it only chooses) and the optimizer does not
own it. ``buffer_names`` declares it; ``runtime/engine.py`` keeps such
leaves out of the cast, the decay, the moments and the update. The
aux-loss-free balance update that moves it during the published training
is a non-gradient update from the step's per-expert load and is not here
(ROADMAP.md).
"""

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from .common import (fused_linear_xent, next_token_xent, resolve_flash,
                     resolve_remat_policy)
from .deepseek_v32 import _rope
from .llama import _rms_norm


@dataclass(frozen=True)
class DeepseekV3Config:
    """Defaults: Kanana-2-30B-A3B as published, whole. Rotary is plain (no
    ``rope_scaling``): a member of the family with YaRN needs
    ``deepseek_v32.rope_frequencies`` and its softmax scale brought here."""
    vocab_size: int = 128256
    max_seq_len: int = 32768        # the longest trained sequence
    n_layer: int = 48
    first_k_dense: int = 1          # leading layers whose FFN is dense
    d_model: int = 2048
    n_head: int = 32
    q_lora_rank: Optional[int] = None   # None: q = x W_q, no query latent
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 6144
    moe_d_ff: int = 768
    n_routed_experts: int = 128     # the router's outputs: the published count
    n_shared_experts: int = 2
    moe_top_k: int = 6
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.448
    # the share this chip holds: experts offset .. offset + held - 1
    experts_offset: int = 0
    experts_held: int = 128
    rope_theta: float = 1e6
    rope_interleave: bool = True    # pairs (2i, 2i + 1); False: (i, i + dr/2)
    rms_eps: float = 1e-6
    dtype: str = "float32"          # init's; the engine casts to its own
    # "auto": the Pallas flash kernel on a TPU, the dense softmax elsewhere
    use_flash_attention: object = "auto"
    flash_block_q: int = 512
    flash_block_k: int = 512
    # every block is recomputed in the backward pass; what that keeps.
    # Nothing: the policy the benchmark's cell runs (2 x 8,192 tokens on a
    # 16 GB chip; "save_flash" keeps the flash residuals for 1.2 GB more)
    remat_policy: str = "nothing_saveable"
    loss_chunk: int = 1024          # fused chunked cross entropy; 0: dense

    def __post_init__(self):
        if not 0 <= self.first_k_dense <= self.n_layer:
            raise ValueError("first_k_dense counts leading layers")
        if self.n_routed_experts % self.n_group \
                or self.experts_offset < 0 \
                or self.experts_offset + self.experts_held \
                > self.n_routed_experts:
            raise ValueError(
                "the held experts lie inside the published count, which "
                "the groups divide")

    @property
    def d_head(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self):
        return self.d_head ** -0.5

    def layer_params(self):
        """(attention, dense FFN, sparse FFN as held here) parameters a
        layer, norms included."""
        D, H, R = self.d_model, self.n_head, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        Rq = self.q_lora_rank
        q = D * H * (dn + dr) if Rq is None \
            else D * Rq + Rq + Rq * H * (dn + dr)
        attn = q + D * (R + dr) + R + H * dn * R + H * R * dv \
            + H * dv * D + 2 * D
        Fm = self.moe_d_ff
        sparse = D * self.n_routed_experts + self.n_routed_experts \
            + (self.experts_held + self.n_shared_experts) * 3 * D * Fm
        return attn, 3 * D * self.d_ff, sparse

    def num_params(self):
        attn, dense, sparse = self.layer_params()
        return 2 * self.vocab_size * self.d_model + self.d_model \
            + self.first_k_dense * (attn + dense) \
            + (self.n_layer - self.first_k_dense) * (attn + sparse)


# the published model, whole
KANANA_2_30B_A3B = DeepseekV3Config()
# one dense and two sparse layers; 16 experts of which this "chip" holds
# experts 4 .. 7, two shared; no query latent, as Kanana-2
DEEPSEEK_V3_TINY = DeepseekV3Config(
    vocab_size=256, max_seq_len=64, n_layer=3, first_k_dense=1, d_model=64,
    n_head=4, q_lora_rank=None, kv_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, d_ff=128, moe_d_ff=32,
    n_routed_experts=16, moe_top_k=4, experts_offset=4, experts_held=4,
    loss_chunk=0)
DEEPSEEK_V3_PRESETS = {"tiny": DEEPSEEK_V3_TINY,
                       "kanana-2-30b-a3b": KANANA_2_30B_A3B}


class DeepseekV3:
    """Params: wte (V, D), lm_head (V, D), norm_f (D,), and ``layers``, a
    list of one dict a layer (dense and sparse layers differ, and an expert
    array is read in place only as an operand of its own, so nothing is
    stacked); ``perfbench/references/deepseek_v3.py`` lists the names and
    shapes."""

    def __init__(self, config: DeepseekV3Config):
        self.config = config

    # ------------------------------------------------------------- weights
    def init(self, rng):
        """Seeded weights (the checkpoint is not here). Projections normal
        0.02; the embedding's rows normal(0, 1) and every norm gain 1, so
        that the pre-norm stream the updates join is of unit size; the
        value expansion (wv_b) 0.1, the SwiGLUs' down products 0.01 (dense,
        shared) and 0.02 (a held expert, which a token takes with weight
        ~0.4): at the published widths attention then adds ~0.05 to the
        stream at the longest contexts (more at short ones), the dense FFN
        ~0.4, the shared experts ~0.2 and a chosen held expert ~0.1, each
        more than the comparison's tolerance
        (``perfbench/configs/kanana-2-30b-a3b.json``, ``assumed.weights``);
        the head D^-0.5 (0.022 at the published width), logits of unit
        deviation at every width, so that the first steps' loss has room
        to fall; the gate's correction bias normal 0.02: the published
        bias is what the balance update left, the values that EVEN the
        experts' load, and a seeded one as wide as the scores' own spread
        (0.2 was tried: PERF.md section 6, PR 47) decides by itself which
        experts are popular, so that a held share's rows, and the step's
        time, swing from seed to seed."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        D, H, Rq, R = cfg.d_model, cfg.n_head, cfg.q_lora_rank, \
            cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        std = 0.02

        def nrm(key, shape, s=std, dtype=dt):
            return (jax.random.normal(key, shape, jnp.float32)
                    * s).astype(dtype)

        def layer(i):
            ks = jax.random.split(jax.random.fold_in(rng, i + 2), 16)
            p = {"norm1": jnp.ones((D,), dt), "norm2": jnp.ones((D,), dt),
                 "wkv_a": nrm(ks[2], (D, R + dr)),
                 "kv_norm": jnp.ones((R,), dt),
                 "wk_b": nrm(ks[3], (H, dn, R)),
                 "wv_b": nrm(ks[4], (H, R, dv), 0.1),
                 "wo": nrm(ks[5], (H * dv, D))}
            if Rq is None:
                p["wq"] = nrm(ks[0], (D, H * (dn + dr)))
            else:
                p["wq_a"] = nrm(ks[0], (D, Rq))
                p["q_norm"] = jnp.ones((Rq,), dt)
                p["wq_b"] = nrm(ks[1], (Rq, H * (dn + dr)))
            if i < cfg.first_k_dense:
                p["w1"] = nrm(ks[6], (D, 2 * cfg.d_ff))
                p["w2"] = nrm(ks[7], (cfg.d_ff, D), 0.01)
                return p
            E, Fm = cfg.experts_held, cfg.moe_d_ff
            Fs = cfg.n_shared_experts * Fm
            p["gate"] = nrm(ks[8], (D, cfg.n_routed_experts))
            # a float32 BUFFER whatever the engine's dtype (buffer_names)
            p["gate_bias"] = nrm(ks[9], (cfg.n_routed_experts,), 0.02,
                                 jnp.float32)
            p["moe_w1"] = nrm(ks[10], (E, D, Fm))
            p["moe_w3"] = nrm(ks[11], (E, D, Fm))
            p["moe_w2"] = nrm(ks[12], (E, Fm, D))
            p["ws1"] = nrm(ks[13], (D, 2 * Fs))
            p["ws2"] = nrm(ks[14], (Fs, D), 0.01)
            return p

        return {"wte": nrm(jax.random.fold_in(rng, 0), (cfg.vocab_size, D),
                           1.0),
                "lm_head": nrm(jax.random.fold_in(rng, 1),
                               (cfg.vocab_size, D), D ** -0.5),
                "norm_f": jnp.ones((D,), dt),
                "layers": [layer(i) for i in range(cfg.n_layer)]}

    def partition_specs(self, topology=None):
        """Every leaf whole on every device (ZeRO then partitions what its
        stage partitions over the data axes): this family is one chip's
        share as it stands, not sharded further."""
        return jax.tree.map(lambda x: P(*(None,) * x.ndim),
                            jax.eval_shape(self.init, jax.random.key(0)))

    @staticmethod
    def buffer_names():
        """Leaves (by their key in the tree) that are buffers, not
        parameters: no gradient, and the engine neither casts, decays nor
        moves them (``runtime/engine.py``)."""
        return frozenset({"gate_bias"})

    # ----------------------------------------------------------- attention
    def _attention(self, x, p, cos, sin):
        """x (B, T, D) normed, in the stream's dtype -> (B, T, D)."""
        cfg = self.config
        B, T, _ = x.shape
        H, R = cfg.n_head, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        with jax.named_scope("dstpu.mm.qkv"):
            if "wq" in p:
                q = x @ p["wq"]
            else:
                q = _rms_norm(x @ p["wq_a"], p["q_norm"],
                              cfg.rms_eps) @ p["wq_b"]
            q = q.reshape(B, T, H, dn + dr)
            ckr = x @ p["wkv_a"]
            c = _rms_norm(ckr[..., :R], p["kv_norm"], cfg.rms_eps)
            # per head [k_nope | v] = c' W_kvb
            k_nope = jnp.einsum("btr,hdr->bthd", c, p["wk_b"])
            v = jnp.einsum("btr,hrd->bthd", c, p["wv_b"])
        with jax.named_scope("dstpu.attn.mla"):
            il = cfg.rope_interleave
            q_pe = _rope(q[..., dn:].astype(jnp.float32), cos, sin, il)
            k_pe = _rope(ckr[..., R:].astype(jnp.float32), cos, sin, il)
            q = jnp.concatenate([q[..., :dn], q_pe.astype(x.dtype)], axis=-1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_pe.astype(x.dtype)[:, :, None], (B, T, H, dr))], axis=-1)
            if resolve_flash(cfg.use_flash_attention):
                from ..ops.pallas.flash_attention import flash_attention
                o = flash_attention(
                    q, k, v, causal=True, scale=cfg.softmax_scale,
                    block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                    block_h=1)
            else:
                s = jnp.einsum("bthd,bshd->bhts", q, k,
                               preferred_element_type=jnp.float32) \
                    * cfg.softmax_scale
                s = jnp.where(jnp.tril(jnp.ones((T, T), jnp.bool_)), s,
                              -1e30)
                # (heads lead the product's output and move after it: the
                # CPU backend has no bfloat16 dot whose batch axes are not
                # the result's first)
                o = jnp.einsum("bhts,bshd->bhtd",
                               jax.nn.softmax(s, axis=-1).astype(v.dtype),
                               v).transpose(0, 2, 1, 3)
        with jax.named_scope("dstpu.mm.attn_out"):
            return o.reshape(B, T, H * dv) @ p["wo"]

    # ----------------------------------------------------------------- FFN
    @staticmethod
    def _swiglu(x, w1, w2):
        F = w2.shape[0]
        with jax.named_scope("dstpu.mm.mlp"):
            gu = x @ w1
            return (jax.nn.silu(gu[..., :F]) * gu[..., F:]) @ w2

    def _moe(self, x, p):
        """Routed experts, the held share of them, beside the shared
        experts: x (B, T, D) normed -> (B, T, D)."""
        from ..moe.sharded_moe import moe_swiglu_routed, route_topk
        cfg = self.config
        B, T, D = x.shape
        xs = x.reshape(-1, D)
        with jax.named_scope("dstpu.moe.route"):
            weights, experts = route_topk(
                xs, p["gate"], cfg.moe_top_k, True, scoring="sigmoid",
                bias=p["gate_bias"], n_group=cfg.n_group,
                topk_group=cfg.topk_group, scale=cfg.routed_scaling_factor)
        grouped = getattr(getattr(self, "_moe_cfg", None), "grouped_kernel",
                          "auto")
        y = moe_swiglu_routed(
            xs, weights, experts, p["moe_w1"], p["moe_w3"], p["moe_w2"],
            grouped, held=(cfg.experts_offset, cfg.experts_held,
                           cfg.n_routed_experts))
        return y.reshape(B, T, D) + self._swiglu(x, p["ws1"], p["ws2"])

    def _block(self, x, p, cos, sin):
        cfg = self.config
        x = x + self._attention(_rms_norm(x, p["norm1"], cfg.rms_eps), p,
                                cos, sin)
        x = checkpoint_name(x, "attn_mid")
        h = _rms_norm(x, p["norm2"], cfg.rms_eps)
        return x + (self._swiglu(h, p["w1"], p["w2"]) if "w1" in p
                    else self._moe(h, p))

    def head(self, params, x):
        x = _rms_norm(x, params["norm_f"], self.config.rms_eps)
        w = params["lm_head"]
        with jax.named_scope("dstpu.mm.unembed"):
            return jnp.einsum("btd,vd->btv", x, w,
                              preferred_element_type=jnp.float32)

    def apply(self, params, input_ids, *, rng=None, train=False,
              return_hidden=False, **_):
        """(B, T) ids -> (B, T, V) float32 logits."""
        cfg = self.config
        B, T = input_ids.shape
        dr = cfg.qk_rope_head_dim
        f = cfg.rope_theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
        ang = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.float32)[None, :, None] * f,
            (B, T, dr // 2))
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        block = jax.checkpoint(
            self._block, policy=resolve_remat_policy(cfg.remat_policy))
        x = params["wte"][input_ids]
        for p in params["layers"]:
            x = block(x, p, cos, sin)
        return x if return_hidden else self.head(params, x)

    def loss(self, params, batch, *, rng=None, train=True, **_):
        """Mean next-token cross entropy over the vocabulary slice."""
        ids = batch["input_ids"]
        chunk = self.config.loss_chunk
        if chunk and ids.shape[1] - 1 > chunk:
            x = self.apply(params, ids, return_hidden=True)
            hp = {k: params[k] for k in ("norm_f", "lm_head")}
            return fused_linear_xent(self.head, chunk, hp, x[:, :-1],
                                     ids[:, 1:])
        return next_token_xent(self.apply(params, ids), ids)
