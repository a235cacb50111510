"""Llama model family — RoPE + RMSNorm + SwiGLU + grouped-query attention.

Counterpart of the reference's llama support (inference
model_implementations/llama2, module_inject/containers/llama*.py,
csrc rms_norm/apply_rotary_pos_emb kernels) — here a first-class
trainable+servable model with the same functional surface as GPT2
(models/gpt2.py): ``init/loss/apply/partition_specs`` for the training
engine, ``init_cache/cache_specs/apply_cached`` for the v1 inference
engine, ``init_paged_cache/paged_cache_specs/apply_paged_*`` for the v2
serving engine. Same TPU-first choices: stacked layers under ``lax.scan``,
declarative Megatron TP on the 'tensor' axis, fp32 norms/logits.

GQA: ``n_kv_heads <= n_head`` — KV caches store only KV heads (the
serving memory win), queries repeat KV groups at attention time.
"""

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..utils.groups import BATCH_AXES
from . import paged
from .common import (chunked_softmax_xent, constrain_fn, fused_linear_xent,
                     next_token_xent)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    n_layer: int = 16
    n_head: int = 16
    n_kv_heads: int = 16
    d_model: int = 1024
    d_ff: int = 0               # 0 = round(8/3 * d_model) to multiple of 128
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "nothing_saveable"
    tie_embeddings: bool = False
    # chunked cross entropy (see gpt2.GPT2Config.loss_chunk); 0 = off
    loss_chunk: int = 0
    # grad-in-forward fused CE (common.fused_linear_xent); needs loss_chunk
    fused_loss: bool = False
    # "auto" (default) = pallas flash kernel on TPU, dense elsewhere
    use_flash_attention: object = "auto"
    flash_block_q: int = 512
    flash_block_k: int = 1024
    # architecture knobs covering the reference v2 model families
    # (model_implementations/{falcon,phi,qwen}): qkv projection bias
    # (qwen), rotary applied to only a fraction of each head (phi/neox
    # partial rotary), SwiGLU vs plain-gelu FFN (falcon/phi use gelu-MLP)
    qkv_bias: bool = False
    rotary_pct: float = 1.0
    mlp_gated: bool = True             # False: wup+gelu+wdown only
    # falcon/phi parallel residual: x + attn(ln1 x) + mlp(ln2 x) instead
    # of the sequential two-residual block
    parallel_block: bool = False
    # 'rms' (llama/qwen/mixtral) or 'ln' (falcon/phi LayerNorm with
    # learned bias; adds b1/b2/norm_f_b params)
    norm_type: str = "rms"
    # phi-style learned biases on the output projection, MLP and lm head
    # (adds bo/bup/bdown (+bgate) and lm_head_b params)
    proj_bias: bool = False
    # granular bias knobs for families where proj_bias is too broad
    # (reference module_inject/containers/{gptj,gptneox,internlm}.py):
    #   o_bias    — bo only (internlm: qkv+o biased, MLP not)
    #   mlp_bias  — bup/bdown (+bgate) only (gptj: fc biased, o not)
    #   head_bias — lm_head bias; "auto" follows proj_bias (gptj: biased
    #               head without o bias; gpt-neox: biased blocks, plain head)
    o_bias: bool = False
    mlp_bias: bool = False
    head_bias: object = "auto"
    # gptj rotate_every_two pairing: rotary pairs are (x0,x1),(x2,x3),...
    # instead of the llama/neox half-split (x_i, x_{i+rot/2})
    rotary_interleaved: bool = False
    # non-gated MLP activation: 'gelu_tanh' (HF gelu_new — gptj/phi) or
    # 'gelu' (exact erf gelu — gpt-neox/falcon nn.GELU default)
    mlp_act: str = "gelu_tanh"
    # mistral sliding-window attention: queries attend only the last
    # ``sliding_window`` positions (0 = full causal). Honored by every
    # path: dense training, flash kernel, v1 cached decode, v2 paged
    # prefill/decode.
    sliding_window: int = 0
    # bloom ALiBi: additive per-head linear position bias INSTEAD of
    # rotary embeddings (rope is skipped). Attention runs the dense path
    # (the flash kernel has no bias input).
    alibi: bool = False
    # falcon-rw quirk: HF falcon adds alibi BEFORE the 1/sqrt(hd) score
    # scaling (modeling_falcon.py:398/912) and quantizes the bias
    # through bf16 (:162), unlike bloom which adds it unscaled; models
    # trained that way need the same numerics
    alibi_inv_norm: bool = False
    # bloom word_embeddings_layernorm: LN applied to the embedding output
    # (adds embed_ln_s/embed_ln_b params)
    embed_norm: bool = False
    # olmoe QK-norm: RMSNorm with a learned scale over the WHOLE q and k
    # projections (all heads together), before the split into heads and
    # before rope (adds q_norm (L,D) / k_norm (L,KVH*hd) params)
    qk_norm: bool = False

    @property
    def flash_on(self):
        """Resolved use_flash_attention (see common.resolve_flash)."""
        from .common import resolve_flash
        return resolve_flash(self.use_flash_attention)

    @property
    def o_bias_on(self):
        return self.proj_bias or self.o_bias

    @property
    def mlp_bias_on(self):
        return self.proj_bias or self.mlp_bias

    @property
    def head_bias_on(self):
        return self.proj_bias if self.head_bias == "auto" \
            else bool(self.head_bias)

    @property
    def d_head(self):
        return self.d_model // self.n_head

    @property
    def ffn_dim(self):
        if self.d_ff:
            return self.d_ff
        return ((int(8 * self.d_model / 3) + 127) // 128) * 128

    def num_params(self):
        D, F, V = self.d_model, self.ffn_dim, self.vocab_size
        kvd = self.n_kv_heads * self.d_head
        block = (2 * D                      # rms scales
                 + D * D + 2 * D * kvd + D * D   # q, k, v, o
                 + (3 if self.mlp_gated else 2) * D * F)
        if self.qkv_bias:
            block += D + 2 * kvd
        if self.qk_norm:
            block += D + kvd
        if self.o_bias_on:
            block += D
        if self.mlp_bias_on:
            block += D + F * (2 if self.mlp_gated else 1)
        if self.norm_type == "ln":
            block += 2 * D                   # norm biases
        head = 0 if self.tie_embeddings else V * D
        if self.head_bias_on:
            head += V
        extra_f = D if self.norm_type == "ln" else 0
        if self.embed_norm:
            extra_f += 2 * D
        return V * D + self.n_layer * block + D + extra_f + head

    def flops_per_token(self):
        n = self.num_params() - self.vocab_size * self.d_model
        return 6 * n + 12 * self.n_layer * self.d_model * self.max_seq_len


LLAMA_TINY = LlamaConfig(n_layer=2, n_head=4, n_kv_heads=2, d_model=128,
                         max_seq_len=128, vocab_size=512, remat=False)
LLAMA2_7B = LlamaConfig(n_layer=32, n_head=32, n_kv_heads=32, d_model=4096,
                        max_seq_len=4096, vocab_size=32000)
MISTRAL_7B = LlamaConfig(n_layer=32, n_head=32, n_kv_heads=8, d_model=4096,
                         d_ff=14336, max_seq_len=8192, vocab_size=32000,
                         sliding_window=4096)

LLAMA_PRESETS = {"tiny": LLAMA_TINY, "llama2-7b": LLAMA2_7B,
                 "mistral-7b": MISTRAL_7B}


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta, interleaved=False):
    """x: (..., T, H, hd) with positions pos (..., T) -> rotated.

    ``interleaved`` (gptj rotate_every_two, HF modeling_gptj.py): pairs
    are adjacent lanes (x0,x1),(x2,x3),... instead of the llama/neox
    half-split (x_i, x_{i+hd/2}). Frequencies are identical."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = (pos.astype(jnp.float32)[..., None, None]
              * freqs[None, None, :])                  # (..., T, 1, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(x.shape)
    else:
        x1, x2 = x[..., :half], x[..., half:]
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _repeat_kv(k, n_rep):
    """(B, T, KVH, hd) -> (B, T, KVH*n_rep, hd)."""
    return k if n_rep == 1 else jnp.repeat(k, n_rep, axis=2)




class Llama:
    """Params layout (block tensors stacked on n_layer):
      wte (V,D) | norm_f (D,) | lm_head (V,D) unless tied
      blocks: rms1 (L,D), wq (L,D,D), wk (L,D,KVD), wv (L,D,KVD),
              wo (L,D,D), rms2 (L,D), wgate (L,D,F), wup (L,D,F),
              wdown (L,F,D)
    """

    moe_loss_coeff = 0.0

    def __init__(self, config: LlamaConfig):
        self.config = config

    # ------------------------------------------------------------------ init
    def init(self, rng):
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        L, D, F, V = cfg.n_layer, cfg.d_model, cfg.ffn_dim, cfg.vocab_size
        kvd = cfg.n_kv_heads * cfg.d_head
        k = iter(jax.random.split(rng, 12))
        std = 0.02
        res_std = std / math.sqrt(2 * L)

        def nrm(key, shape, s=std):
            return (jax.random.normal(key, shape, jnp.float32) * s).astype(dt)

        params = {
            "wte": nrm(next(k), (V, D)),
            "norm_f": jnp.ones((D,), dt),
            "blocks": {
                "rms1": jnp.ones((L, D), dt),
                "wq": nrm(next(k), (L, D, D)),
                "wk": nrm(next(k), (L, D, kvd)),
                "wv": nrm(next(k), (L, D, kvd)),
                "wo": nrm(next(k), (L, D, D), res_std),
                "rms2": jnp.ones((L, D), dt),
                "wup": nrm(next(k), (L, D, F)),
                "wdown": nrm(next(k), (L, F, D), res_std),
            },
        }
        if cfg.mlp_gated:
            params["blocks"]["wgate"] = nrm(next(k), (L, D, F))
        if cfg.qkv_bias:
            params["blocks"]["bq"] = jnp.zeros((L, D), dt)
            params["blocks"]["bk"] = jnp.zeros((L, kvd), dt)
            params["blocks"]["bv"] = jnp.zeros((L, kvd), dt)
        if cfg.qk_norm:
            params["blocks"]["q_norm"] = jnp.ones((L, D), dt)
            params["blocks"]["k_norm"] = jnp.ones((L, kvd), dt)
        if cfg.o_bias_on:
            params["blocks"]["bo"] = jnp.zeros((L, D), dt)
        if cfg.mlp_bias_on:
            params["blocks"]["bup"] = jnp.zeros((L, F), dt)
            params["blocks"]["bdown"] = jnp.zeros((L, D), dt)
            if cfg.mlp_gated:
                params["blocks"]["bgate"] = jnp.zeros((L, F), dt)
        if cfg.head_bias_on:
            params["lm_head_b"] = jnp.zeros((V,), dt)
        if cfg.norm_type == "ln":
            params["blocks"]["b1"] = jnp.zeros((L, D), dt)
            params["blocks"]["b2"] = jnp.zeros((L, D), dt)
            params["norm_f_b"] = jnp.zeros((D,), dt)
        if cfg.embed_norm:
            params["embed_ln_s"] = jnp.ones((D,), dt)
            params["embed_ln_b"] = jnp.zeros((D,), dt)
        if not cfg.tie_embeddings:
            params["lm_head"] = nrm(next(k), (V, D))
        return params

    # -------------------------------------------------------------- sharding
    def partition_specs(self, topology=None):
        """Column-parallel: wq/wk/wv/wgate/wup (out dim on 'tensor');
        row-parallel: wo/wdown (in dim). Embeddings/norms replicated."""
        specs = {
            "wte": P(),
            "norm_f": P(),
            "blocks": {
                "rms1": P(None, None),
                "wq": P(None, None, "tensor"),
                "wk": P(None, None, "tensor"),
                "wv": P(None, None, "tensor"),
                "wo": P(None, "tensor", None),
                "rms2": P(None, None),
                "wup": P(None, None, "tensor"),
                "wdown": P(None, "tensor", None),
            },
        }
        if self.config.mlp_gated:
            specs["blocks"]["wgate"] = P(None, None, "tensor")
        if self.config.qkv_bias:
            specs["blocks"]["bq"] = P(None, "tensor")
            specs["blocks"]["bk"] = P(None, "tensor")
            specs["blocks"]["bv"] = P(None, "tensor")
        if self.config.qk_norm:
            specs["blocks"]["q_norm"] = P(None, "tensor")
            specs["blocks"]["k_norm"] = P(None, "tensor")
        if self.config.o_bias_on:
            specs["blocks"]["bo"] = P(None, None)
        if self.config.mlp_bias_on:
            specs["blocks"]["bup"] = P(None, "tensor")
            specs["blocks"]["bdown"] = P(None, None)
            if self.config.mlp_gated:
                specs["blocks"]["bgate"] = P(None, "tensor")
        if self.config.head_bias_on:
            specs["lm_head_b"] = P()
        if self.config.norm_type == "ln":
            specs["blocks"]["b1"] = P(None, None)
            specs["blocks"]["b2"] = P(None, None)
            specs["norm_f_b"] = P()
        if self.config.embed_norm:
            specs["embed_ln_s"] = P()
            specs["embed_ln_b"] = P()
        if not self.config.tie_embeddings:
            specs["lm_head"] = P()
        return specs

    # --------------------------------------------------------------- forward
    def _constrain_fn(self):
        return constrain_fn()

    def _norm(self, x, layer, which):
        """Block norm dispatch: 'rms' (llama) or 'ln' (falcon/phi)."""
        cfg = self.config
        if cfg.norm_type == "ln":
            return _layer_norm(x, layer[f"rms{which}"], layer[f"b{which}"],
                               cfg.rms_eps)
        return _rms_norm(x, layer[f"rms{which}"], cfg.rms_eps)

    def head(self, params, x):
        if self.config.norm_type == "ln":
            x = _layer_norm(x, params["norm_f"], params["norm_f_b"],
                            self.config.rms_eps)
        else:
            x = _rms_norm(x, params["norm_f"], self.config.rms_eps)
        w = params["wte"] if self.config.tie_embeddings else \
            params["lm_head"]
        with jax.named_scope("dstpu.mm.unembed"):
            logits = jnp.einsum("btd,vd->btv", x, w,
                                preferred_element_type=jnp.float32)
        if self.config.head_bias_on:
            logits = logits + params["lm_head_b"].astype(jnp.float32)
        return logits

    def _attn_proj(self, x, layer):
        cfg = self.config
        B, T = x.shape[0], x.shape[1]
        H, KVH, hd = cfg.n_head, cfg.n_kv_heads, cfg.d_head
        h = self._norm(x, layer, 1)
        with jax.named_scope("dstpu.mm.qkv"):
            q = h @ layer["wq"]
            kk = h @ layer["wk"]
            v = h @ layer["wv"]
        if cfg.qkv_bias:                      # qwen-style attention bias
            q = q + layer["bq"]
            kk = kk + layer["bk"]
            v = v + layer["bv"]
        if cfg.qk_norm:
            q = _rms_norm(q, layer["q_norm"], cfg.rms_eps)
            kk = _rms_norm(kk, layer["k_norm"], cfg.rms_eps)
        return (q.reshape(B, T, H, hd), kk.reshape(B, T, KVH, hd),
                v.reshape(B, T, KVH, hd))

    def _rope(self, x, pos):
        """Rotary with optional partial application (phi/neox
        rotary_pct < 1: only the leading fraction of each head
        rotates). ALiBi models carry no rotary at all."""
        cfg = self.config
        if cfg.alibi:
            return x
        pct = cfg.rotary_pct
        il = cfg.rotary_interleaved
        if pct >= 1.0:
            return _rope(x, pos, cfg.rope_theta, interleaved=il)
        hd = x.shape[-1]
        rot = max(2, int(hd * pct)) // 2 * 2
        return jnp.concatenate(
            [_rope(x[..., :rot], pos, cfg.rope_theta, interleaved=il),
             x[..., rot:]],
            axis=-1)

    def _alibi_bias(self, k_pos):
        """(H, ...) additive score bias: slope_h * k_pos (softmax-shift
        equivalent to slope_h * (k_pos - q_pos); matches HF bloom).
        ``alibi_inv_norm`` (falcon-rw): bf16-quantized and divided by
        sqrt(hd), matching HF falcon's pre-scaling addition."""
        from ..ops.pallas.paged_attention import alibi_slopes
        cfg = self.config
        slopes = jnp.asarray(alibi_slopes(cfg.n_head), jnp.float32)
        bias = slopes.reshape(-1, *([1] * k_pos.ndim)) \
            * k_pos.astype(jnp.float32)[None]
        if cfg.alibi_inv_norm:
            bias = bias.astype(jnp.bfloat16).astype(jnp.float32) \
                / math.sqrt(cfg.d_head)
        return bias

    def _window_mask(self, mask, q_pos, k_pos):
        """AND a sliding-window constraint into a boolean mask
        (broadcastable q_pos/k_pos position index arrays)."""
        w = self.config.sliding_window
        if not w:
            return mask
        return mask & (q_pos - k_pos < w)

    def _wo(self, attn, layer):
        """Output projection (+ bias when proj_bias/o_bias)."""
        with jax.named_scope("dstpu.mm.attn_out"):
            out = attn @ layer["wo"]
        if self.config.o_bias_on:
            out = out + layer["bo"]
        return out

    def _mlp(self, x, layer):
        """The dense MLP after its norm; Mixtral overrides it with the
        routed experts."""
        h = self._norm(x, layer, 2)
        with jax.named_scope("dstpu.mm.mlp"):
            return self._dense_mlp(h, layer)

    def _dense_mlp(self, h, layer):
        cfg = self.config
        pb = cfg.mlp_bias_on
        from ..ops.int8_weights import _is_q
        if _is_q(layer["wup"]):
            # weight-only quantized serving FFN (engine weight_quant):
            # int8/int4 weight tiles stream HBM->VMEM with dequant fused
            # into the projection kernel's flush epilogue — no
            # dequantized weight tensor materializes
            from ..ops.pallas.mlp_matmul import wq_matmul
            if not cfg.mlp_gated:
                u = wq_matmul(h, layer["wup"])
                if pb:
                    u = u + layer["bup"]
                act = jax.nn.gelu(u, approximate=cfg.mlp_act == "gelu_tanh")
                out = wq_matmul(act, layer["wdown"])
                return out + layer["bdown"] if pb else out
            g = wq_matmul(h, layer["wgate"])
            u = wq_matmul(h, layer["wup"])
            if pb:
                g = g + layer["bgate"]
                u = u + layer["bup"]
            out = wq_matmul(jax.nn.silu(g) * u, layer["wdown"])
            return out + layer["bdown"] if pb else out
        if not cfg.mlp_gated:                 # falcon/phi plain-gelu MLP
            u = h @ layer["wup"]
            if pb:
                u = u + layer["bup"]
            act = jax.nn.gelu(u, approximate=cfg.mlp_act == "gelu_tanh")
            out = act @ layer["wdown"]
            return out + layer["bdown"] if pb else out
        g = h @ layer["wgate"]
        u = h @ layer["wup"]
        if pb:
            g = g + layer["bgate"]
            u = u + layer["bup"]
        out = (jax.nn.silu(g) * u) @ layer["wdown"]
        return out + layer["bdown"] if pb else out

    def block_forward(self, x, layer, pos, *, causal, constrain, act_spec):
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        from ..ops.int8_weights import dequant_tree
        layer = dequant_tree(layer, dt)
        q, kk, v = self._attn_proj(x, layer)
        attn = self.block_attn(q, kk, v, pos, causal=causal,
                               constrain=constrain)
        attn_out = self._wo(constrain(attn, act_spec), layer)
        if cfg.parallel_block:
            # falcon/phi: attention and MLP branch from the same input
            x = x + attn_out + self._mlp(x, layer)
        else:
            x = x + attn_out
            x = constrain(x, act_spec)
            x = x + self._mlp(x, layer)
        return constrain(x, act_spec)

    @jax.named_scope("dstpu.attn.flash")
    def block_attn(self, q, kk, v, pos, *, causal, constrain):
        """The training attention between its weight products: (B, T, H,
        hd) q and (B, T, KVH, hd) k, v -> (B, T, H * hd). Rotary, the
        heads' sharding, GQA's repeat, the flash kernel (or the dense
        softmax) and the output's layout."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        B, T = q.shape[0], q.shape[1]
        H, KVH, hd = cfg.n_head, cfg.n_kv_heads, cfg.d_head
        q = self._rope(q, pos)
        kk = self._rope(kk, pos)
        head_spec = P(BATCH_AXES, None, "tensor", None)
        q = constrain(q, head_spec)
        kk = constrain(kk, head_spec)
        v = constrain(v, head_spec)
        kk = _repeat_kv(kk, H // KVH)
        v = _repeat_kv(v, H // KVH)
        if cfg.flash_on:
            from ..ops.pallas.flash_attention import flash_attention
            alibi_arg = None
            if cfg.alibi:
                # ALiBi is computed in-kernel from the slopes (slope_h *
                # k_pos, softmax-shift equivalent to the relative form);
                # alibi_inv_norm reproduces HF falcon's pre-scaled
                # bf16-quantized variant (see _alibi_bias)
                from ..ops.pallas.paged_attention import alibi_slopes
                alibi_arg = alibi_slopes(H)
            attn = flash_attention(
                q, kk, v, causal=True,
                block_q=cfg.flash_block_q,
                block_k=cfg.flash_block_k,
                window=cfg.sliding_window,
                alibi=alibi_arg,
                alibi_scale=(1.0 / math.sqrt(hd)
                             if cfg.alibi_inv_norm else 1.0),
                alibi_bf16=cfg.alibi_inv_norm).astype(dt)
            attn = attn.reshape(B, T, H * hd)
        else:
            scores = jnp.einsum("bthd,bshd->bhts", q, kk,
                                preferred_element_type=jnp.float32)
            scores = scores / math.sqrt(hd)
            if cfg.alibi:
                scores = scores + self._alibi_bias(
                    jnp.arange(T))[None, :, None, :]
            scores = jnp.where(causal[None, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(dt)
            attn = jnp.einsum("bhts,bshd->bthd", probs,
                              v).reshape(B, T, H * hd)
        return attn

    def apply(self, params, input_ids, *, rng=None, train=False,
              seq_sharded=False, return_hidden=False):
        cfg = self.config
        T = input_ids.shape[1]
        constrain = self._constrain_fn()
        act_spec = P(BATCH_AXES, "seq" if seq_sharded else None, None)
        x = constrain(self._embed(params, input_ids), act_spec)
        pos = jnp.broadcast_to(jnp.arange(T)[None, :], input_ids.shape)
        causal = jnp.tril(jnp.ones((T, T), jnp.bool_))
        causal = self._window_mask(causal, jnp.arange(T)[:, None],
                                   jnp.arange(T)[None, :])

        def block(x, layer):
            return self.block_forward(x, layer, pos, causal=causal,
                                      constrain=constrain,
                                      act_spec=act_spec)

        block_fn = block
        if cfg.remat:
            policy = getattr(jax.checkpoint_policies, cfg.remat_policy,
                             None)
            block_fn = jax.checkpoint(block, policy=policy)

        if self._served(params):
            # the inference engines' tree: per-layer lists cannot scan
            for i in range(cfg.n_layer):
                x = block_fn(x, self._layer_tree(params, i))
        else:
            x, _ = lax.scan(lambda c, l: (block_fn(c, l), None), x,
                            params["blocks"])
        if return_hidden:
            return x
        return self.head(params, x)

    def apply_with_aux(self, params, input_ids, **kw):
        return self.apply(params, input_ids, **kw), jnp.zeros((),
                                                              jnp.float32)

    def _head_keys(self):
        """Param leaves ``head`` reads (the fused-CE d_params subset)."""
        cfg = self.config
        keys = ["norm_f"]
        if cfg.norm_type == "ln":
            keys.append("norm_f_b")
        keys.append("wte" if cfg.tie_embeddings else "lm_head")
        if cfg.head_bias_on:
            keys.append("lm_head_b")
        return keys

    def loss(self, params, batch, *, rng=None, train=True,
             seq_sharded=False):
        ids = batch["input_ids"]
        T = ids.shape[1]
        chunk = self.config.loss_chunk
        if chunk and T - 1 > chunk and not seq_sharded:
            x = self.apply(params, ids, rng=rng, train=train,
                           seq_sharded=seq_sharded, return_hidden=True)
            if self.config.fused_loss:
                hp = {k: params[k] for k in self._head_keys()}
                return fused_linear_xent(self.head, chunk, hp,
                                         x[:, :-1], ids[:, 1:])
            return chunked_softmax_xent(self.head, params, x[:, :-1],
                                        ids[:, 1:], chunk)
        logits = self.apply(params, ids, rng=rng, train=train,
                            seq_sharded=seq_sharded)
        return next_token_xent(logits, ids)

    # ------------------------------------------------- v1 KV-cache decoding
    def init_cache(self, batch_size, max_len, dtype=None):
        cfg = self.config
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(cfg.dtype)
        shape = (cfg.n_layer, batch_size, max_len, cfg.n_kv_heads,
                 cfg.d_head)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def cache_specs(self, batch_axes=BATCH_AXES):
        spec = P(None, batch_axes, None, "tensor", None)
        return {"k": spec, "v": spec}

    def _block_core(self, x, layer, pos, attn_fn):
        """Shared block scaffolding for every cache-backed inference
        path: q/k/v projection -> rotary at ``pos`` -> ``attn_fn`` ->
        output projection -> residual (or falcon/phi's parallel block)
        -> MLP. ``attn_fn((B,T,H,hd) q, (B,T,KVH,hd) k, v) -> (attn
        (B,T,H,hd), carry)`` owns masking and any cache reads/writes.
        Returns (x_out, carry)."""
        cfg = self.config
        B, T = x.shape[0], x.shape[1]
        q, kk, v = self._attn_proj(x, layer)
        # self._rope honors rotary_pct (phi partial rotary) — the
        # module-level _rope would silently diverge decode from
        # training for those families
        q = self._rope(q, pos)
        kk = self._rope(kk, pos)
        attn, carry = attn_fn(q, kk, v)
        attn_out = self._wo(attn.reshape(B, T, cfg.n_head * cfg.d_head),
                            layer)
        if cfg.parallel_block:
            # falcon/phi: attention and MLP branch from the same input
            x = x + attn_out + self._mlp(x, layer)
        else:
            x = x + attn_out
            x = x + self._mlp(x, layer)
        return x, carry

    def apply_cached(self, params, input_ids, pos_ids, cache, slot,
                     valid_mask, last_token_only=False):
        """Same contract as GPT2.apply_cached; KV cache stores KV heads
        only (GQA)."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        T = input_ids.shape[1]
        H, KVH, hd = cfg.n_head, cfg.n_kv_heads, cfg.d_head
        x = self._embed(params, input_ids)
        Tmax = cache["k"].shape[2]

        def body(x, xs):
            layer, kc0, vc0 = xs
            from ..ops.int8_weights import dequant_tree

            def attn_fn(q, kk, v):
                kc = lax.dynamic_update_slice(kc0, kk.astype(kc0.dtype),
                                              (0, slot, 0, 0))
                vc = lax.dynamic_update_slice(vc0, v.astype(vc0.dtype),
                                              (0, slot, 0, 0))
                ku = _repeat_kv(kc, H // KVH)
                vu = _repeat_kv(vc, H // KVH)
                scores = jnp.einsum("bthd,bshd->bhts", q, ku,
                                    preferred_element_type=jnp.float32)
                scores = scores / math.sqrt(hd)
                s_idx = jnp.arange(Tmax)[None, None, None, :]
                q_idx = (slot + jnp.arange(T))[None, None, :, None]
                mask = (s_idx <= q_idx) & valid_mask[:, None, None, :]
                mask = self._window_mask(mask, q_idx, s_idx)
                if cfg.alibi:
                    scores = scores + self._alibi_bias(
                        jnp.arange(Tmax))[None, :, None, :]
                scores = jnp.where(mask, scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1).astype(dt)
                return jnp.einsum("bhts,bshd->bthd", probs, vu), (kc, vc)

            return self._block_core(x, dequant_tree(layer, dt), pos_ids,
                                    attn_fn)

        if self._served(params):
            # the inference engines' tree: per-layer lists cannot scan,
            # and each layer's experts stay operands of the program
            ks, vs = [], []
            for i in range(cfg.n_layer):
                x, (kc, vc) = body(x, (self._layer_tree(params, i),
                                       cache["k"][i], cache["v"][i]))
                ks.append(kc)
                vs.append(vc)
            kc, vc = jnp.stack(ks), jnp.stack(vs)
        else:
            x, (kc, vc) = lax.scan(
                body, x, (params["blocks"], cache["k"], cache["v"]))
        if last_token_only:
            x = x[:, -1:]
        return self.head(params, x), {"k": kc, "v": vc}

    # ------------------------------------------------- v2 paged decoding
    def init_paged_cache(self, num_blocks, block_size, dtype=None):
        """LISTS of per-layer heads-major pools (NB, KVH, BS, hd) — the
        layout the Pallas paged-decode kernel consumes without
        transposes; separate per-layer buffers so the new-token scatter
        updates each donated pool IN PLACE (see GPT2.init_paged_cache)."""
        cfg = self.config
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(cfg.dtype)
        shape = (num_blocks, cfg.n_kv_heads, block_size, cfg.d_head)
        return {"k": [jnp.zeros(shape, dt) for _ in range(cfg.n_layer)],
                "v": [jnp.zeros(shape, dt) for _ in range(cfg.n_layer)]}

    def paged_cache_specs(self):
        spec = P(None, "tensor", None, None)
        L = self.config.n_layer
        return {"k": [spec] * L, "v": [spec] * L}

    # FFN weight keys the fused-dequant serving path keeps quantized
    # (engine_v2 sets _weight_quant_fused; _mlp consumes them via
    # wq_matmul / grouped_swiglu_wq)
    _WQ_KEEP = ("wgate", "wup", "wdown")

    # block keys the SERVED tree holds as per-layer lists instead of
    # stacked on n_layer (Mixtral: the experts, which a grouped product
    # cannot read in place from a stacked array)
    _PER_LAYER = ()

    def serving_params(self, params):
        """Training tree -> served tree: each ``_PER_LAYER`` key becomes a
        list of its layers (identity for the dense families and on a
        tree that is served already). Host arrays unstack as views; a
        stacked array ON THE DEVICE is copied leaf by leaf, so a model
        near the chip's size must arrive on the host or be made per
        layer (``shard_params`` does the latter for seeded weights)."""
        blocks = dict(params["blocks"])
        for k in self._PER_LAYER:
            if not isinstance(blocks[k], (list, tuple)):
                blocks[k] = [jax.tree.map(lambda a: a[i], blocks[k])
                             for i in range(self.config.n_layer)]
        return {**params, "blocks": blocks}

    def serving_specs(self, topology=None):
        """``partition_specs`` of the served tree."""
        specs = self.partition_specs(topology)
        for k in self._PER_LAYER:
            specs["blocks"][k] = [P(*specs["blocks"][k][1:])] \
                * self.config.n_layer
        return specs

    @staticmethod
    def _served(params):
        """Whether ``params`` is a served tree with per-layer lists."""
        return any(isinstance(v, (list, tuple))
                   for v in params["blocks"].values())

    def _layer_tree(self, params, i):
        """Layer ``i`` of either tree: a per-layer list gives its own
        array (a program operand), a stacked leaf its slice."""
        return {k: v[i] if isinstance(v, (list, tuple))
                else jax.tree.map(lambda a: a[i], v)
                for k, v in params["blocks"].items()}

    def _layer_slice(self, params, i):
        from ..ops.int8_weights import dequant_tree
        sl = self._layer_tree(params, i)
        # ZeRO-Inference weight-only serving: int8 block weights
        # dequantize one layer at a time (identity on bf16 trees);
        # under the fused path the FFN weights stay quantized and the
        # projection kernels dequantize in their epilogues
        keep = self._WQ_KEEP \
            if getattr(self, "_weight_quant_fused", False) else ()
        return dequant_tree(sl, jnp.dtype(self.config.dtype), keep=keep)

    def _embed(self, params, ids):
        x = params["wte"][ids].astype(jnp.dtype(self.config.dtype))
        if self.config.embed_norm:
            x = _layer_norm(x, params["embed_ln_s"], params["embed_ln_b"],
                            self.config.rms_eps)
        return x

    def _paged_layers(self, params, x, pos, step):
        """The serving layer loop (see GPT2._paged_layers); a served
        tree's per-layer experts stay operands of the program.
        Returns (x, cache)."""
        ks_out, vs_out = [], []
        for i in range(self.config.n_layer):
            x, (kc, vc) = self._block_core(
                x, self._layer_slice(params, i), pos, step.layer(i))
            ks_out.append(kc)
            vs_out.append(vc)
        return x, {"k": ks_out, "v": vs_out}

    def apply_paged_prefill(self, params, input_ids, cache, token_blocks,
                            token_offsets, length):
        """Prefill ONE sequence into the paged cache: the chunk program
        at ``start = 0`` (see GPT2.apply_paged_prefill)."""
        BS = cache["k"][0].shape[2]
        return self.apply_paged_chunk(
            params, input_ids, cache, token_blocks, token_offsets,
            jnp.int32(0), length, token_blocks[::BS])

    def apply_paged_chunk(self, params, input_ids, cache, token_blocks,
                          token_offsets, start, true_len, table):
        """Prefill ONE CHUNK of one sequence into the paged cache
        (Dynamic SplitFuse: long prompts stream through a fixed-size
        chunk program instead of one bucketed prefill per prompt —
        reference blogs/deepspeed-fastgen §3B, inference/v2/ragged/).

        input_ids: (1, C) chunk tokens (right-padded); token_blocks/
        token_offsets: (C,) destination block/slot per chunk position
        (pads point at scratch block 0); start: scalar absolute position
        of the chunk's first token; true_len: scalar number of real
        tokens in the chunk; table: (MB,) the sequence's full block
        table (scratch-padded). Queries attend the sequence's PRIOR
        cache plus the in-chunk causal prefix — K/V are scattered first,
        then read back through the table, so the attention sees one
        contiguous [0, start + true_len) key range.
        Returns (logits (1, V) at chunk position true_len - 1, cache).
        """
        pos = start + jnp.arange(input_ids.shape[1])[None, :]
        x, cache = self._paged_layers(
            params, self._embed(params, input_ids), pos, paged.chunk_step(
                paged.geometry(self), cache, token_blocks, token_offsets,
                start, true_len, table))
        last = jnp.take_along_axis(
            x, jnp.maximum(true_len - 1, 0)[None, None, None], axis=1)
        return self.head(params, last)[:, 0], cache

    def apply_paged_decode(self, params, tokens, lengths, cache,
                           block_tables):
        """One decode step: the verify program at C = 1."""
        logits, cache = self.apply_paged_verify(
            params, tokens[:, None], lengths, cache, block_tables)
        return logits[:, 0], cache

    def apply_paged_verify(self, params, tokens, lengths, cache,
                           block_tables):
        """Speculative-verify step: C tokens per slot in ONE pass (see
        GPT2.apply_paged_verify — same contract; llama families add
        RoPE at each slot's absolute positions, GQA-native kernel reads,
        and the ALiBi/sliding-window biases of the chunk path).

        tokens: (B, C); lengths: (B,) = first input token's position;
        block_tables: (B, MB). Returns (logits (B, C, V), cache)."""
        C = tokens.shape[1]
        pos = jnp.minimum(lengths[:, None] + jnp.arange(C)[None, :],
                          self.config.max_seq_len - 1)
        x, cache = self._paged_layers(
            params, self._embed(params, tokens), pos, paged.batch_step(
                paged.geometry(self), cache, lengths, block_tables, C))
        return self.head(params, x), cache
