"""DeepSeek-V3.2-Exp (``model_type`` deepseek_v32): multi-head latent
attention (MLA, DeepSeek-V2 / V3) read through a learned per-query top-k
selection (DeepSeek Sparse Attention: a "lightning indexer" scores every
causal key, the ``index_topk`` best are attended), pre-norm RMSNorm blocks,
YaRN rotary, and a SwiGLU FFN that is dense in the leading layers and, in
the rest, V3's ``noaux_tc`` gate (sigmoid scores, a correction bias that
chooses but does not weigh, group-limited top-k, renormalised and scaled)
over routed experts beside one shared expert every token takes.

The equations are written out in ``perfbench/references/deepseek_v32.py``,
which this file has to equal. What is particular to the program:

**One chip's share of an expert-parallel deployment.** The router keeps
the published ``n_routed_experts`` outputs; the layer holds
``experts_held`` of them from ``experts_offset`` and computes ``sum over
held experts + Shared(x)``: what the absent experts would add is left out
and that partial sum goes on (``moe/sharded_moe.py:moe_swiglu_routed``,
``held=``). Nothing here stands in for the other chips or their exchange.

**A latent cache** (``models/paged.py``, ``LATENT``): a token's row is its
normalised 512-wide latent and the one 64-wide rotary key all heads share
(``lat``, 576 values in a 640-wide row) and its 128-wide index key
(``idx``), under the
sequence's block table as K/V is. The model has no slot state.

**Two forms of one attention.** A prompt's chunk expands a block of latent
rows to every head's key and value (``wk_b``, ``wv_b``) and attends with
192-wide products; a decode step absorbs ``wk_b`` into the query and
``wv_b`` into the output and attends the 576-wide rows as they lie in the
cache. Both read exactly the selected keys: ``paged._latent_read`` keeps
every causal key's float32 index score, finds each query's k-th largest
and masks the rest. The expanded form's read is written twice: ``read_fn``
(XLA; ``apply``, and the serving programs off a TPU) and the Pallas kernel
of ``ops/pallas/latent_attention.py``, which a paged step runs instead
where its program runs kernels, from what ``read_fn`` is made from
(``expand``: the rounded queries, ``wk_b``, ``wv_b``); the rounding points
are the same.

**Numerics, forced by two discrete choices a layer.** The selection keeps
2,048 keys of thousands and the gate 8 experts of 256: a rounding of 2^-9
in what they are computed from moves ~2 % of a query's selected keys and a
token's eighth expert now and then, and with seeded weights (an indexer
that knows nothing of the attention it selects for) each such flip moves a
logit by a large part of what the mechanism itself contributes: a bfloat16
program read 0.7 - 0.8 standard deviations from the float32 reference on
the chip, as far as the wrong models (PR 43, PERF.md section 4). So the
residual stream is float32 and every weight product takes it as TWO
bfloat16 pieces (``common._pieces``: x to ~16 bits; one product, the
weight read once); the index queries, the cached index keys (``idx`` is a
float32 pool) and their products (three bfloat16 passes) keep that
precision, and the router, the index scores and the selection are float32.
What is averaged and not chosen stays bfloat16: the latent cache and the
products of the read itself.

Serving only: ``apply`` is the dense forward of the tests (one sequence a
row, the same two passes over a cache it makes on the spot). The
multi-token-prediction module of the checkpoint is not part of the 61
layers and is not here.
"""

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from . import paged
from .common import _pieces
from .llama import _rms_norm


@dataclass(frozen=True)
class DeepseekV32Config:
    vocab_size: int = 129280
    max_seq_len: int = 163840
    n_layer: int = 61
    first_k_dense: int = 3          # leading layers whose FFN is dense
    d_model: int = 7168
    n_head: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    d_ff: int = 18432
    moe_d_ff: int = 2048
    n_routed_experts: int = 256     # the router's outputs: the published count
    n_shared_experts: int = 1
    moe_top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    # the share this chip holds: experts offset .. offset + held - 1
    experts_offset: int = 0
    experts_held: int = 256
    rope_theta: float = 10000.0
    rope_factor: float = 40.0       # rope_scaling (YaRN)
    rope_original: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"

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
        if self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError("the index key's rotary part is its first "
                             "qk_rope_head_dim dims")

    @property
    def d_head(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def lat_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def lat_row(self):
        """A cache row of ``lat``: the latent and the rotary key, padded
        with zeros to whole 128-lane tiles. A pool whose rows are not
        (576) is given a layout with the BLOCK axis minor as a program
        argument and copied whole into and out of row-major around every
        program (compiled for a described v5e, PR 43)."""
        return -(-self.lat_width // 128) * 128

    @property
    def softmax_scale(self):
        m = 0.1 * self.mscale_all_dim * math.log(self.rope_factor) + 1.0
        return self.d_head ** -0.5 * m * m

    def num_params(self):
        D, H, Rq, R = (self.d_model, self.n_head, self.q_lora_rank,
                       self.kv_lora_rank)
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        Hi, di = self.index_n_heads, self.index_head_dim
        attn = D * Rq + Rq + Rq * H * (dn + dr) + D * (R + dr) + R \
            + H * dn * R + H * R * dv + H * dv * D \
            + Rq * Hi * di + D * di + 2 * di + D * Hi + 2 * D
        dense = 3 * D * self.d_ff
        Fm = self.moe_d_ff
        sparse = D * self.n_routed_experts + self.n_routed_experts \
            + self.experts_held * 3 * D * Fm \
            + self.n_shared_experts * 3 * D * Fm
        return 2 * self.vocab_size * D + D \
            + self.first_k_dense * (attn + dense) \
            + (self.n_layer - self.first_k_dense) * (attn + sparse)


# the published model, whole
DEEPSEEK_V32 = DeepseekV32Config()
# one dense and two sparse layers; 16 experts in 4 groups of which this
# "chip" holds experts 4 .. 7; index_topk well under the tests' contexts
DEEPSEEK_V32_TINY = DeepseekV32Config(
    vocab_size=256, max_seq_len=256, n_layer=3, first_k_dense=1, d_model=64,
    n_head=4, q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=16,
    index_topk=16, d_ff=128, moe_d_ff=32, n_routed_experts=16, moe_top_k=4,
    n_group=4, topk_group=2, experts_offset=4, experts_held=4,
    rope_original=32, dtype="float32")
DEEPSEEK_V32_PRESETS = {"tiny": DEEPSEEK_V32_TINY,
                        "deepseek-v3.2-exp": DEEPSEEK_V32}


def _mm(x, w, scope):
    """float32 ``x @ w`` for a weight kept in a narrower dtype: x goes in
    as its pieces of that dtype (the module's docstring), one product over
    all of them so that the weight is read once; float32 out. ``scope``
    names the product's device operations (``monitor/tag_schema.py:
    SCOPE_SCHEMA``)."""
    with jax.named_scope(scope):
        return jnp.dot(_pieces(x, w.dtype), w,
                       preferred_element_type=jnp.float32).sum(axis=0)


def rope_frequencies(cfg):
    """The qk_rope_head_dim / 2 inverse frequencies after YaRN: correction
    dims from beta_fast and beta_slow at the original context, a linear
    ramp between them, f / factor blended in by the ramp."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    f = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def correction_dim(rotations):
        return dim * math.log(cfg.rope_original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return f / cfg.rope_factor * ramp + f * (1.0 - ramp)


def _rope(x, cos, sin, interleaved):
    """x (B, C, ..., dr) float32, cos / sin (B, C, dr / 2): pairs (2i, 2i +
    1) (MLA) or (i, i + dr / 2) (the indexer)."""
    shape = x.shape
    cos = cos.reshape(shape[:2] + (1,) * (x.ndim - 3) + cos.shape[-1:])
    sin = sin.reshape(cos.shape)
    if interleaved:
        x = x.reshape(shape[:-1] + (shape[-1] // 2, 2))
        a, b = x[..., 0], x[..., 1]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(shape)
    half = shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


class _DenseStep:
    """``apply``'s stand-in for a ``models/paged.py`` step: T positions of
    B sequences at once, each row's cache made on the spot as one block of
    T tokens, nothing kept."""

    def __init__(self, cfg, B, T):
        self.cfg = cfg
        self.q_pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        self.frontier = jnp.full((B,), T, jnp.int32)

    def latent(self, i):
        def attn_fn(lat, idx, index_fn, read_fn, topk, dv, expand=None):
            B, T = self.q_pos.shape
            return paged._latent_read(
                lat, idx, jnp.arange(B, dtype=jnp.int32)[:, None],
                self.q_pos, self.frontier, index_fn, read_fn, topk,
                (B, T, self.cfg.n_head, dv), T)

        return attn_fn


class DeepseekV32:
    """Params: wte (V, D), lm_head (V, D), norm_f (D,), and ``layers``, a
    list of one dict a layer (dense and sparse layers differ, and an
    expert array is read in place only as an operand of its own, so
    nothing is stacked); ``perfbench/references/deepseek_v32.py`` lists the
    names and shapes."""

    def __init__(self, config: DeepseekV32Config):
        self.config = config

    # ------------------------------------------------------------- weights
    def init(self, rng):
        """Seeded weights (the checkpoint is not here). Projections normal
        0.02; the embedding's rows normal(0, 1) and every norm gain 1, so
        that the pre-norm stream the updates join is of unit size; the
        SwiGLUs' down products (w2, ws2) 0.002, the attention's output
        projection 0.007: at the published widths the dense layer then
        adds ~0.4 to the stream, a shared expert ~0.14 and an attention
        ~0.03 (random values average away over the ~200 keys a softmax of
        logits of deviation 1.5 rests on; at 0.02 the one key in a
        thousand that the program and the reference select differently
        moved a logit by 0.15 deviations, PERF.md section 4). A held expert's down product
        (moe_w2) is 0.001: chosen with weight ~0.3 it adds ~0.02, so that
        a flip of a token's eighth expert under the rounding that is left
        moves a logit by under a tenth of a deviation. The index key's
        LayerNorm is (1, 0); the gate's correction bias normal 0.2, so
        that choosing on s + b and weighing by s differ as they do in the
        trained model. PERF.md section 4 says what else was tried."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        D, H, Rq, R = cfg.d_model, cfg.n_head, cfg.q_lora_rank, \
            cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        Hi, di = cfg.index_n_heads, cfg.index_head_dim
        std, res_std = 0.02, 0.002

        def nrm(key, shape, s=std, dtype=dt):
            return (jax.random.normal(key, shape, jnp.float32)
                    * s).astype(dtype)

        def layer(i):
            ks = jax.random.split(jax.random.fold_in(rng, i + 2), 20)
            p = {"norm1": jnp.ones((D,), dt), "norm2": jnp.ones((D,), dt),
                 "wq_a": nrm(ks[0], (D, Rq)), "q_norm": jnp.ones((Rq,), dt),
                 "wq_b": nrm(ks[1], (Rq, H * (dn + dr))),
                 "wkv_a": nrm(ks[2], (D, R + dr)),
                 "kv_norm": jnp.ones((R,), dt),
                 "wk_b": nrm(ks[3], (H, dn, R)),
                 "wv_b": nrm(ks[4], (H, R, dv)),
                 "wo": nrm(ks[5], (H * dv, D), 0.007),
                 "wi_q": nrm(ks[6], (Rq, Hi * di)),
                 "wi_k": nrm(ks[7], (D, di)),
                 "ik_norm_w": jnp.ones((di,), dt),
                 "ik_norm_b": jnp.zeros((di,), dt),
                 "wi_w": nrm(ks[8], (D, Hi))}
            if i < cfg.first_k_dense:
                p["w1"] = nrm(ks[9], (D, 2 * cfg.d_ff))
                p["w2"] = nrm(ks[10], (cfg.d_ff, D), res_std)
                return p
            E, Fm = cfg.experts_held, cfg.moe_d_ff
            Fs = cfg.n_shared_experts * Fm
            # the router stays float32 (routing is precision-sensitive)
            p["gate"] = nrm(ks[11], (D, cfg.n_routed_experts),
                            dtype=jnp.float32)
            p["gate_bias"] = nrm(ks[12], (cfg.n_routed_experts,), 0.2,
                                 jnp.float32)
            p["moe_w1"] = nrm(ks[13], (E, D, Fm))
            p["moe_w3"] = nrm(ks[14], (E, D, Fm))
            p["moe_w2"] = nrm(ks[15], (E, Fm, D), 0.001)
            p["ws1"] = nrm(ks[16], (D, 2 * Fs))
            p["ws2"] = nrm(ks[17], (Fs, D), res_std)
            return p

        return {"wte": nrm(jax.random.fold_in(rng, 0), (cfg.vocab_size, D),
                           1.0),
                "lm_head": nrm(jax.random.fold_in(rng, 1),
                               (cfg.vocab_size, D)),
                "norm_f": jnp.ones((D,), dt),
                "layers": [layer(i) for i in range(cfg.n_layer)]}

    def partition_specs(self, topology=None):
        """Every leaf whole on every device: this family is one chip's
        share as it stands, not sharded further."""
        return jax.tree.map(lambda x: P(*(None,) * x.ndim),
                            jax.eval_shape(self.init, jax.random.key(0)))

    # ----------------------------------------------------------- attention
    def _attention(self, x, p, attn_fn, positions):
        """A latent layer: x (B, C, D) float32 normed input at
        ``positions`` (B, C); ``attn_fn`` (``paged._Step.latent``) owns the
        cache, the selection and the running softmax. A step of one token
        a row is the absorbed form, anything longer the expanded one."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        B, C, _ = x.shape
        H, R = cfg.n_head, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        Hi, di = cfg.index_n_heads, cfg.index_head_dim
        ang = positions.astype(jnp.float32)[..., None] * rope_frequencies(cfg)
        cos, sin = jnp.cos(ang), jnp.sin(ang)

        cq = _rms_norm(_mm(x, p["wq_a"], "dstpu.mm.qkv"), p["q_norm"],
                       cfg.rms_eps)
        q = _mm(cq, p["wq_b"], "dstpu.mm.qkv").reshape(B, C, H, dn + dr)
        ckr = _mm(x, p["wkv_a"], "dstpu.mm.qkv")
        with jax.named_scope("dstpu.attn.latent"):
            q_pe = _rope(q[..., dn:], cos, sin, True)
            ckv = _rms_norm(ckr[..., :R], p["kv_norm"], cfg.rms_eps)
            k_pe = _rope(ckr[..., R:], cos, sin, True)
            lat = jnp.pad(jnp.concatenate([ckv, k_pe], axis=-1).astype(dt),
                          ((0, 0), (0, 0), (0, cfg.lat_row - cfg.lat_width)))
            # sigma rides on the query, in float32, before it is rounded
            q_nope = q[..., :dn] * cfg.softmax_scale
            q_pe = q_pe * cfg.softmax_scale

        qi = _mm(cq, p["wi_q"], "dstpu.mm.qkv").reshape(B, C, Hi, di)
        ki = _mm(x, p["wi_k"], "dstpu.mm.qkv")
        wi = _mm(x, p["wi_w"], "dstpu.mm.qkv") * (Hi ** -0.5 * di ** -0.5)
        with jax.named_scope("dstpu.attn.index"):
            qi = jnp.concatenate([_rope(qi[..., :dr], cos, sin, False),
                                  qi[..., dr:]], axis=-1)
            mu = jnp.mean(ki, axis=-1, keepdims=True)
            var = jnp.mean((ki - mu) ** 2, axis=-1, keepdims=True)
            ki = (ki - mu) * lax.rsqrt(var + cfg.rms_eps) \
                * p["ik_norm_w"].astype(jnp.float32) \
                + p["ik_norm_b"].astype(jnp.float32)
            ki = jnp.concatenate([_rope(ki[..., :dr], cos, sin, False),
                                  ki[..., dr:]], axis=-1)

        def index_fn(keys):
            """I(t, s) of the step's queries against ``keys`` (B, n, di):
            float32 throughout (three bfloat16 passes a product), a query
            row's heads at once."""
            with jax.named_scope("dstpu.attn.index"):
                dots = jnp.einsum("bchd,bsd->bchs", qi,
                                  keys.astype(jnp.float32),
                                  precision=lax.Precision.HIGH)
                return jnp.sum(wi[..., None] * jax.nn.relu(dots), axis=2)

        if C == 1:
            # absorbed: q~_h = q_nope_h Wk_b^h, the latent row is key and
            # value both, Wv_b comes after the sum
            with jax.named_scope("dstpu.attn.latent"):
                # (the head leads each per-head product's output and is
                # moved after it: the CPU backend has no bfloat16 dot whose
                # batch axis is not the result's first)
                qa = jnp.einsum("bhd,hdr->hbr", q_nope[:, 0].astype(dt),
                                p["wk_b"],
                                preferred_element_type=jnp.float32)
                qc = jnp.concatenate([qa.transpose(1, 0, 2)[:, None], q_pe],
                                     axis=-1).astype(dt)

            def read_fn(rows):
                with jax.named_scope("dstpu.attn.latent"):
                    sc = jnp.einsum("bchw,bsw->bhcs", qc, rows[..., :R + dr],
                                    preferred_element_type=jnp.float32)
                    return sc, lambda pr: jnp.einsum(
                        "bhs,bsr->bhr", pr[:, :, 0].astype(dt),
                        rows[..., :R],
                        preferred_element_type=jnp.float32)[:, None]

            width, expand = R, None
        else:
            with jax.named_scope("dstpu.attn.latent"):
                qc = jnp.concatenate([q_nope, q_pe], axis=-1).astype(dt)

            def read_fn(rows):
                with jax.named_scope("dstpu.attn.latent"):
                    c = rows[..., :R]
                    k = jnp.einsum("bsr,hdr->bshd", c, p["wk_b"],
                                   preferred_element_type=jnp.float32)
                    k = jnp.concatenate([k.astype(dt), jnp.broadcast_to(
                        rows[:, :, None, R:R + dr], k.shape[:3] + (dr,))],
                        axis=-1)
                    v = jnp.einsum("bsr,hrd->bshd", c, p["wv_b"],
                                   preferred_element_type=jnp.float32
                                   ).astype(dt)
                    sc = jnp.einsum("bchd,bshd->bhcs", qc, k,
                                    preferred_element_type=jnp.float32)
                    return sc, lambda pr: jnp.einsum(
                        "bhcs,bshd->bchd", pr.astype(dt), v,
                        preferred_element_type=jnp.float32)

            # what ``read_fn`` is made from, for a step that reads through
            # the kernel of ``ops/pallas/latent_attention.py`` instead
            width, expand = dv, (qc, p["wk_b"], p["wv_b"])

        with jax.named_scope("dstpu.attn.latent"):
            o = attn_fn(lat, ki, index_fn, read_fn, cfg.index_topk, width,
                        expand)
            if C == 1:
                o = jnp.einsum("bhr,hrd->hbd",
                               _pieces(o[:, 0], dt).reshape(-1, H, R),
                               p["wv_b"], preferred_element_type=jnp.float32)
                o = o.reshape(H, -1, B, dv).sum(axis=1).transpose(1, 0, 2)[
                    :, None]
        return _mm(o.reshape(B, C, H * dv), p["wo"], "dstpu.mm.attn_out")

    # ----------------------------------------------------------------- FFN
    def _swiglu(self, x, w1, w2):
        F = w2.shape[0]
        gu = _mm(x, w1, "dstpu.mm.mlp")
        return _mm(jax.nn.silu(gu[..., :F]) * gu[..., F:], w2,
                   "dstpu.mm.mlp")

    def _moe(self, x, p):
        """Routed experts, the held share of them, beside the shared
        expert: x (B, C, D) float32 normed -> (B, C, D) float32. The
        two-part ``held`` keeps ``moe_swiglu_routed``'s one pass over every
        routed row: a serving chunk wants the walk over the held rows
        forward only and at a size of its own (ROADMAP.md S11(b))."""
        from ..moe.sharded_moe import moe_swiglu_routed, route_topk
        cfg = self.config
        B, C, D = x.shape
        xs = x.reshape(-1, D)
        with jax.named_scope("dstpu.moe.route"):
            weights, experts = route_topk(
                xs, p["gate"], cfg.moe_top_k, True, scoring="sigmoid",
                bias=p["gate_bias"], n_group=cfg.n_group,
                topk_group=cfg.topk_group, scale=cfg.routed_scaling_factor)
        grouped = getattr(getattr(self, "_moe_cfg", None), "grouped_kernel",
                          "auto")
        y = moe_swiglu_routed(
            xs.astype(p["moe_w1"].dtype), weights, experts, p["moe_w1"],
            p["moe_w3"], p["moe_w2"], grouped,
            held=(cfg.experts_offset, cfg.experts_held),
            out_dtype=jnp.float32)
        return y.reshape(B, C, D) + self._swiglu(x, p["ws1"], p["ws2"])

    def _layers(self, params, x, step):
        """The one layer loop: ``step`` is a ``models/paged.py`` step (or
        ``apply``'s stand-in) and owns every cache."""
        cfg = self.config
        for i, p in enumerate(params["layers"]):
            x = x + self._attention(
                _rms_norm(x, p["norm1"], cfg.rms_eps), p, step.latent(i),
                step.q_pos)
            h = _rms_norm(x, p["norm2"], cfg.rms_eps)
            x = x + (self._swiglu(h, p["w1"], p["w2"]) if "w1" in p
                     else self._moe(h, p))
        return x

    def _embed(self, params, ids):
        return params["wte"][ids].astype(jnp.float32)

    def head(self, params, x):
        x = _rms_norm(x, params["norm_f"], self.config.rms_eps)
        w = params["lm_head"]
        with jax.named_scope("dstpu.mm.unembed"):
            return jnp.einsum("btd,vd->btv", x.astype(w.dtype), w,
                              preferred_element_type=jnp.float32)

    def apply(self, params, input_ids, **_):
        """(B, T) ids -> (B, T, V) float32 logits, no cache kept."""
        B, T = input_ids.shape
        return self.head(params, self._layers(
            params, self._embed(params, input_ids),
            _DenseStep(self.config, B, T)))

    # ------------------------------------------------- v2 paged serving
    def paged_geometry(self):
        """What ``models/paged.py`` sees: every layer a latent one."""
        cfg = self.config
        return paged.geometry(
            self, n_kv_heads=1, windows=(0,) * cfg.n_layer,
            kinds=(paged.LATENT,) * cfg.n_layer)

    def init_paged_cache(self, num_blocks, block_size, dtype=None):
        """A layer's two pools under the block tables: ``lat`` (NB, BS,
        lat_row: [latent | rotary key | zeros to the lanes]), ``idx`` (NB,
        BS, index_head_dim) float32 whatever ``dtype``."""
        cfg = self.config
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(cfg.dtype)
        return {
            "lat": [jnp.zeros((num_blocks, block_size, cfg.lat_row), dt)
                    for _ in range(cfg.n_layer)],
            "idx": [jnp.zeros((num_blocks, block_size, cfg.index_head_dim),
                              jnp.float32) for _ in range(cfg.n_layer)]}

    def paged_cache_specs(self):
        return jax.tree.map(
            lambda x: P(*(None,) * x.ndim),
            jax.eval_shape(lambda: self.init_paged_cache(1, 1)))

    def apply_paged_prefill(self, params, input_ids, cache, token_blocks,
                            token_offsets, length):
        """Prefill ONE sequence, right-padded to its bucket: the chunk
        program at ``start = 0``."""
        BS = paged.block_size(cache)
        return self.apply_paged_chunk(
            params, input_ids, cache, token_blocks, token_offsets,
            jnp.int32(0), length, token_blocks[::BS])

    def apply_paged_chunk(self, params, input_ids, cache, token_blocks,
                          token_offsets, start, true_len, table):
        """``true_len`` tokens of one sequence at positions ``start ..``
        (the contract of ``Llama.apply_paged_chunk``). Returns (logits (1,
        V) at token true_len - 1, cache)."""
        step = paged.chunk_step(
            self.paged_geometry(), cache, token_blocks, token_offsets,
            jnp.asarray(start, jnp.int32), jnp.asarray(true_len, jnp.int32),
            table)
        x = self._layers(params, self._embed(params, input_ids), step)
        last = jnp.take_along_axis(
            x, jnp.maximum(true_len - 1, 0)[None, None, None], axis=1)
        return self.head(params, last)[:, 0], step.cache

    def apply_paged_decode(self, params, tokens, lengths, cache,
                           block_tables):
        """One decode step: a token a slot at position ``lengths``, the
        absorbed form."""
        step = paged.batch_step(self.paged_geometry(), cache, lengths,
                                block_tables, 1)
        x = self._layers(params, self._embed(params, tokens[:, None]), step)
        return self.head(params, x)[:, 0], step.cache
