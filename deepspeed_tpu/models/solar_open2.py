"""Solar-Open2 (``model_type`` solar_open2): delta-rule layers whose decay is
a vector over the key channels (Kimi Delta Attention, arXiv 2510.26692)
three to one with softmax GQA layers that have no positional encoding and a
sigmoid output gate, pre-norm RMSNorm blocks, and in EVERY layer a
``noaux_tc`` gate (sigmoid scores, a correction bias that chooses but does
not weigh, no groups, renormalised) over routed experts beside one shared
expert every token takes. The mixer by ``i in gqa_layers``:

* GQA: ``[q | k | v | g] = x W``, ``n_head`` query heads on ``n_kv_heads``
  K/V heads, causal softmax, ``o * sigmoid(g)``, the out-projection;
* KDA: ``[q | k | v] = silu(conv(x W))``, L2-normalised q and k a head, the
  delta rule ``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t
  v_t^T`` with ``a_t = exp(-exp(A_log) softplus(x Wf_a Wf_b + dt_bias))`` a
  (dk,) vector a head and ``b_t = 2 sigmoid(x Wb)``, an RMSNorm a head
  times ``sigmoid(x Wg_a Wg_b)``, the out-projection.

The equations are written out in ``perfbench/references/solar_open2.py``,
which this file has to equal. What is particular to the program:

**Three caches in one model** (``models/paged.py``): a GQA layer's K/V pool
under the sequence's block table, which is all the allocator's blocks pay
for; a KDA layer's recurrent state a slot: ``conv``, the last K - 1 inputs
of the three convs, (slots, K - 1, 2 H dk + H dv) in the parameters' dtype,
and ``ssm``, the matrix state S, (slots, H, dk, dv) float32; and no cache
for the experts, of which this chip holds a share. ``slot_state`` tells the
engine so; it hands the prefill and chunk programs their slot.

**One chip's share of an expert-parallel deployment**, as
``models/deepseek_v32.py`` has it: the router keeps the published
``n_routed_experts`` outputs; the layer holds ``experts_held`` of them from
``experts_offset`` and computes ``sum over held experts + Shared(x)``
(``moe/sharded_moe.py:moe_swiglu_routed``, ``held=``). What the absent
experts would add is left out and that partial sum goes on; nothing here
stands in for the other chips or their exchange.

**The rule is ``ops/gated_delta_rule.py``'s with a wider gate**: ``log_a``
(.., H, dk) where ``models/olmo_hybrid.py`` hands (.., H). A prompt's chunk
runs the chunkwise form in XLA (``chunk_rule``: the pairwise-decayed product
has no chunk kernel yet, ROADMAP R4); a decode step over the live slots is
the Pallas step kernel wherever the step's attention is a kernel
(``step.use_kernel``), the ``ssm`` leaf aliased and written in place, and
``step_rule`` elsewhere. The programs count what they took
(``note_call("rule", ...)``: ``rule_calls`` / ``rule_kernel_calls`` on the
engine's spans).

**Numerics**, what the two parents paid to learn. The residual stream is
float32. A layer makes one discrete choice, 8 experts of 320, from that
stream, so every weight product that feeds it takes its float32 rows as TWO
pieces of the weight's dtype (``common._pieces``: x to ~16 bits; one
product, the weight read once) and sums in float32; the router is float32
at HIGHEST (``route_topk``). What is averaged and not chosen stays in the
parameters' dtype: the K/V pool, the attention's products, a held expert's
rows. The convs' inputs are rounded to the parameters' dtype where they are
made, so that the K - 1 a slot keeps are the ones the next chunk would have
seen; the gates' low-rank products, the cumulated log decay and the state
are float32.

Serving only: the rule has no backward (ROADMAP R4); ``apply`` is the dense
forward of the tests.
"""

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.gated_delta_rule import chunk_rule, step_rule
from ..ops.pallas._common import note_call
from ..ops.pallas.gated_delta_rule import live_slot_list, step_rule_kernel
from . import paged
# float32 ``x @ w`` over two pieces of the weight's dtype, under a scope:
# the same product, for the same reason (the module's docstring)
from .deepseek_v32 import _mm
from .llama import _rms_norm


@dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    max_seq_len: int = 1048576
    n_layer: int = 48
    gqa_layers: tuple = tuple(range(0, 48, 4))   # the rest are KDA
    d_model: int = 4096
    n_head: int = 64
    n_kv_heads: int = 8
    d_head: int = 128
    linear_heads: int = 64
    linear_dk: int = 128
    linear_dv: int = 128
    linear_conv: int = 4            # K
    gate_rank: int = 128            # of the decay's and the output gate's
    #                                 low-rank projections
    allow_neg_eigval: bool = True   # b = 2 sigmoid(.), else sigmoid(.)
    moe_d_ff: int = 1280
    n_routed_experts: int = 320     # the router's outputs: the published count
    n_shared_experts: int = 1
    moe_top_k: int = 8
    routed_scaling_factor: float = 1.0
    # the share this chip holds: experts offset .. offset + held - 1
    experts_offset: int = 0
    experts_held: int = 320
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not self.gqa_layers or not all(
                0 <= i < self.n_layer for i in self.gqa_layers):
            raise ValueError(
                "gqa_layers names at least one of the n_layer layers (the "
                "block tables are its pool's)")
        if self.n_head % self.n_kv_heads:
            raise ValueError("n_kv_heads has to divide n_head")
        if self.experts_offset < 0 or self.experts_offset \
                + self.experts_held > self.n_routed_experts:
            raise ValueError(
                "the held experts lie inside the published count")

    @property
    def conv_channels(self):
        return self.linear_heads * (2 * self.linear_dk + self.linear_dv)

    def is_gqa(self, i):
        return i in self.gqa_layers

    def num_params(self):
        D, H, hd, Hkv = self.d_model, self.n_head, self.d_head, \
            self.n_kv_heads
        Hl, dk, dv, r = self.linear_heads, self.linear_dk, self.linear_dv, \
            self.gate_rank
        gqa = D * (2 * H + 2 * Hkv) * hd + H * hd * D
        kda = D * self.conv_channels + self.linear_conv * self.conv_channels \
            + D * r + r * Hl * dk + Hl + Hl * dk + D * Hl \
            + D * r + r * Hl * dv + dv + Hl * dv * D
        F = self.moe_d_ff
        ffn = D * self.n_routed_experts + self.n_routed_experts \
            + (self.experts_held + self.n_shared_experts) * 3 * D * F
        n_gqa = len(self.gqa_layers)
        return 2 * self.vocab_size * D + D + n_gqa * gqa \
            + (self.n_layer - n_gqa) * kda + self.n_layer * (ffn + 2 * D)


# the published model, whole
SOLAR_OPEN2_250B = SolarOpen2Config()
# two periods; 16 experts of which this "chip" holds experts 4 .. 7; dk !=
# dv, nothing a multiple of the lanes
SOLAR_OPEN2_TINY = SolarOpen2Config(
    vocab_size=256, max_seq_len=256, n_layer=8, gqa_layers=(0, 4),
    d_model=64, n_head=4, n_kv_heads=2, d_head=16, linear_heads=4,
    linear_dk=8, linear_dv=16, gate_rank=8, moe_d_ff=32,
    n_routed_experts=16, moe_top_k=4, experts_offset=4, experts_held=4,
    dtype="float32")
SOLAR_OPEN2_PRESETS = {"tiny": SOLAR_OPEN2_TINY,
                       "solar-open2-250b": SOLAR_OPEN2_250B}


def _l2(x, eps=1e-6):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class _DenseStep:
    """``apply``'s stand-in for a ``models/paged.py`` step: all T positions
    of B sequences at once, from zero state, nothing kept."""

    def __init__(self, cfg, B, T):
        self.cfg = cfg
        self.valid = jnp.ones((B, T), bool)
        self.n_valid = jnp.full((B,), T, jnp.int32)

    def state(self, i):
        cfg, B = self.cfg, self.valid.shape[0]
        return (jnp.zeros((B, cfg.linear_conv - 1, cfg.conv_channels),
                          jnp.dtype(cfg.dtype)),
                jnp.zeros((B, cfg.linear_heads, cfg.linear_dk,
                           cfg.linear_dv), jnp.float32))

    def put_state(self, i, *new, in_place=()):
        pass

    def layer(self, i):
        def attn_fn(q, k, v):
            B, T, H, hd = q.shape
            Hkv = k.shape[2]
            # (the K/V head leads each product's output: the CPU backend
            # has no bfloat16 dot whose batch axis is not the result's
            # first)
            scores = jnp.einsum(
                "btjgd,bsjd->bjgts", q.reshape(B, T, Hkv, H // Hkv, hd), k,
                preferred_element_type=jnp.float32) / math.sqrt(hd)
            mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
            probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
            out = jnp.einsum("bjgts,bsjd->bjgtd", probs.astype(q.dtype), v)
            return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd), None

        return attn_fn


class SolarOpen2:
    """Params: wte (V, D), lm_head (V, D), norm_f (D,), and ``layers``, a
    list of one dict a layer (the stack is not uniform, and an expert array
    is read in place only as an operand of its own, so nothing is stacked);
    ``perfbench/references/solar_open2.py`` lists the names and shapes."""

    # the cache holds state by batch slot, not only blocks under a table:
    # the engine gives the prefill / chunk programs their slot and refuses
    # what assumes length-masked KV (prefix cache, speculative rollback,
    # KV offload and transfer)
    slot_state = True

    def __init__(self, config: SolarOpen2Config):
        self.config = config

    # ------------------------------------------------------------- weights
    def init(self, rng):
        """Seeded weights (the checkpoint is not here). Projections normal
        0.02; the embedding's rows normal(0, 1) and every norm gain 1, so
        that the pre-norm stream the updates join is of unit size. The
        decay's own initialisation (Mamba-2's, which the delta-rule
        families keep, here a key channel): A = exp(A_log) uniform in (0,
        16) a head, dt_bias the inverse softplus of dt log-uniform in
        [1e-3, 0.1] a channel, so a channel forgets over anything from one
        token to ~10^5; with L2-normalised keys and b in (0, 2) every
        transition ``(I - b k k^T) Diag(a)`` has norm <= 1: a seeded state
        neither dies nor blows up over 32 k tokens. The out-projections
        set what a sublayer adds to the unit stream: KDA's 0.004 (a gated
        unit-rms o over 8,192 inputs: ~0.2), GQA's 0.02 (random values
        average away over thousands of keys: ~0.05 at 8 k), the shared
        expert's down product 0.004 (~0.1), a held expert's 0.006: chosen
        with weight ~1/8 it adds ~0.02, so that a flip of a token's eighth
        expert under the rounding that is left moves a logit by well
        under a tenth of a deviation while another router moves it by
        more. The gate's correction bias is normal 0.2, so that choosing
        on s + b and weighing by s differ as in a trained model."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        D, H, Hkv, hd = cfg.d_model, cfg.n_head, cfg.n_kv_heads, cfg.d_head
        Hl, dk, dv, K, r = cfg.linear_heads, cfg.linear_dk, cfg.linear_dv, \
            cfg.linear_conv, cfg.gate_rank
        E, F = cfg.experts_held, cfg.moe_d_ff
        Fs = cfg.n_shared_experts * F
        std = 0.02

        def nrm(key, shape, s=std, dtype=dt):
            return (jax.random.normal(key, shape, jnp.float32)
                    * s).astype(dtype)

        def kda(ks):
            step = jnp.exp(jax.random.uniform(
                ks[8], (Hl * dk,), jnp.float32, math.log(1e-3),
                math.log(0.1)))
            return {
                "in_proj": nrm(ks[0], (D, cfg.conv_channels)),
                "conv_w": jax.random.uniform(
                    ks[1], (K, cfg.conv_channels), jnp.float32,
                    -K ** -0.5, K ** -0.5).astype(dt),
                "f_a": nrm(ks[2], (D, r)), "f_b": nrm(ks[3], (r, Hl * dk)),
                "A_log": jnp.log(jax.random.uniform(
                    ks[4], (Hl,), jnp.float32, 1e-3, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "b_proj": nrm(ks[5], (D, Hl)),
                "g_a": nrm(ks[6], (D, r)), "g_b": nrm(ks[7], (r, Hl * dv)),
                "o_norm": jnp.ones((dv,), dt),
                "out_proj": nrm(ks[9], (Hl * dv, D), 0.004)}

        def gqa(ks):
            return {"wqkvg": nrm(ks[0], (D, (2 * H + 2 * Hkv) * hd)),
                    "wo": nrm(ks[1], (H * hd, D))}

        def layer(i):
            ks = jax.random.split(jax.random.fold_in(rng, i + 2), 20)
            return {**(gqa(ks) if cfg.is_gqa(i) else kda(ks)),
                    "norm1": jnp.ones((D,), dt), "norm2": jnp.ones((D,), dt),
                    # the router stays float32 (routing is
                    # precision-sensitive)
                    "gate": nrm(ks[10], (D, cfg.n_routed_experts),
                                dtype=jnp.float32),
                    "gate_bias": nrm(ks[11], (cfg.n_routed_experts,), 0.2,
                                     jnp.float32),
                    "moe_w1": nrm(ks[12], (E, D, F)),
                    "moe_w3": nrm(ks[13], (E, D, F)),
                    "moe_w2": nrm(ks[14], (E, F, D), 0.006),
                    "ws1": nrm(ks[15], (D, 2 * Fs)),
                    "ws2": nrm(ks[16], (Fs, D), 0.004)}

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

    # -------------------------------------------------------------- mixers
    def _kda(self, x, p, conv0, S0, valid, n_valid, kernel=False,
             live=None):
        """The delta-rule mixer: x (B, C, D) normed, from state (conv0 (B,
        K-1, channels), S0 (B, H, dk, dv) float32); pads (``~valid``) do
        not move the state, and the conv tail is that of each row's last
        ``n_valid`` token. ``kernel`` with ``live``, a decode step's
        ``live_slot_list``: the step kernel, with which S0 is the layer's
        whole leaf and comes back written in place, live rows only. ->
        (Mix (B, C, D), (conv, S) after the last real token)."""
        cfg = self.config
        B, C, _ = x.shape
        H, dk, dv = cfg.linear_heads, cfg.linear_dk, cfg.linear_dv
        K, ch, eps = cfg.linear_conv, cfg.conv_channels, cfg.rms_eps
        u = _mm(x, p["in_proj"], "dstpu.mm.in_proj").astype(conv0.dtype)
        f = _mm(_mm(x, p["f_a"], "dstpu.mm.in_proj"), p["f_b"],
                "dstpu.mm.in_proj")
        z = _mm(_mm(x, p["g_a"], "dstpu.mm.in_proj"), p["g_b"],
                "dstpu.mm.in_proj")
        bl = _mm(x, p["b_proj"], "dstpu.mm.in_proj")
        win = jnp.concatenate([conv0, u], axis=1)          # (B, K-1+C, ch)
        w = p["conv_w"].astype(jnp.float32)
        qkv = jax.nn.silu(sum(win[:, j:j + C] * w[j] for j in range(K)))
        q = _l2(qkv[..., :H * dk].reshape(B, C, H, dk)) * dk ** -0.5
        k = _l2(qkv[..., H * dk:2 * H * dk].reshape(B, C, H, dk))
        v = qkv[..., 2 * H * dk:].reshape(B, C, H, dv)
        log_a = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            f + p["dt_bias"]).reshape(B, C, H, dk)
        b = jax.nn.sigmoid(bl) * (2.0 if cfg.allow_neg_eigval else 1.0)
        log_a = jnp.where(valid[..., None, None], log_a, 0.0)
        b = jnp.where(valid[..., None], b, 0.0)
        # the step kernel's grid is a decode step's live slots; a chunk's
        # rule with a gate a channel is the XLA form
        kernel = kernel and C == 1 and live is not None
        note_call("rule", kernel)
        if C == 1:
            with jax.named_scope("dstpu.gdn.step"):
                rows = (q[:, 0], k[:, 0], v[:, 0], log_a[:, 0], b[:, 0], S0)
                o, S = step_rule_kernel(*rows, live) if kernel \
                    else step_rule(*rows)
            o, conv1 = o[:, None], win[:, 1:]
        else:
            with jax.named_scope("dstpu.gdn.chunk"):
                o, S = chunk_rule(q, k, v, log_a, b, S0)
            conv1 = jax.vmap(lambda rows, n: lax.dynamic_slice(
                rows, (n, 0), (K - 1, ch)))(win, n_valid)
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
            * p["o_norm"].astype(jnp.float32)
        o = o * jax.nn.sigmoid(z.reshape(B, C, H, dv))
        return _mm(o.reshape(B, C, H * dv), p["out_proj"],
                   "dstpu.mm.out_proj"), (conv1, S)

    def _gqa(self, x, p, attn_fn):
        """A softmax layer; ``attn_fn`` owns the cache and the mask."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        B, C, _ = x.shape
        H, Hkv, hd = cfg.n_head, cfg.n_kv_heads, cfg.d_head
        qkvg = _mm(x, p["wqkvg"], "dstpu.mm.qkv")
        q, k, v, g = jnp.split(
            qkvg, (H * hd, (H + Hkv) * hd, (H + 2 * Hkv) * hd), axis=-1)
        with jax.named_scope("dstpu.attn.full"):
            out, _ = attn_fn(q.astype(dt).reshape(B, C, H, hd),
                             k.astype(dt).reshape(B, C, Hkv, hd),
                             v.astype(dt).reshape(B, C, Hkv, hd))
            out = out.reshape(B, C, H * hd).astype(jnp.float32) \
                * jax.nn.sigmoid(g)
        return _mm(out, p["wo"], "dstpu.mm.attn_out")

    # ----------------------------------------------------------------- FFN
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
                bias=p["gate_bias"], scale=cfg.routed_scaling_factor)
        grouped = getattr(getattr(self, "_moe_cfg", None), "grouped_kernel",
                          "auto")
        y = moe_swiglu_routed(
            xs.astype(p["moe_w1"].dtype), weights, experts, p["moe_w1"],
            p["moe_w3"], p["moe_w2"], grouped,
            held=(cfg.experts_offset, cfg.experts_held),
            out_dtype=jnp.float32)
        F = p["ws2"].shape[0]
        gu = _mm(x, p["ws1"], "dstpu.mm.mlp")
        return y.reshape(B, C, D) + _mm(
            jax.nn.silu(gu[..., :F]) * gu[..., F:], p["ws2"], "dstpu.mm.mlp")

    def _layers(self, params, x, step):
        """The one layer loop: ``step`` is a ``models/paged.py`` step (or
        ``apply``'s stand-in) and owns every cache."""
        cfg = self.config
        # the rule's one-token update follows the step's attention: a
        # kernel where that is one, over the live slots alone, from one
        # list for every layer, the ``ssm`` leaf written in place
        kernel = getattr(step, "use_kernel", False)
        live = live_slot_list(step.active) \
            if kernel and x.shape[1] == 1 and hasattr(step, "active") \
            else None
        for i, p in enumerate(params["layers"]):
            h = _rms_norm(x, p["norm1"], cfg.rms_eps)
            if cfg.is_gqa(i):
                mix = self._gqa(h, p, step.layer(i))
            else:
                with jax.named_scope("dstpu.gdn.mix"):
                    mix, state = self._kda(
                        h, p, *step.state(i), step.valid, step.n_valid,
                        kernel, live)
                    step.put_state(
                        i, *state,
                        in_place=("ssm",) if live is not None else ())
            x = x + mix
            x = x + self._moe(_rms_norm(x, p["norm2"], cfg.rms_eps), p)
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
        """(B, T) ids -> (B, T, V) float32 logits, no cache."""
        B, T = input_ids.shape
        return self.head(params, self._layers(
            params, self._embed(params, input_ids),
            _DenseStep(self.config, B, T)))

    # ------------------------------------------------- v2 paged serving
    def paged_geometry(self):
        """What ``models/paged.py`` sees: each layer's kind of cache."""
        cfg = self.config
        return paged.geometry(
            self, n_kv_heads=cfg.n_kv_heads, windows=(0,) * cfg.n_layer,
            kinds=tuple(paged.KV if cfg.is_gqa(i) else paged.STATE
                        for i in range(cfg.n_layer)))

    def init_paged_cache(self, num_blocks, block_size, dtype=None, slots=1,
                         ring_blocks=None):
        """``k`` / ``v``: a pool a GQA layer, ``num_blocks`` blocks under
        the block tables, (NB, n_kv_heads, BS, d_head); ``conv`` / ``ssm``:
        a row a slot a KDA layer. ``ring_blocks`` is the engine's for a
        window layer's ring: there is none here."""
        cfg = self.config
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(cfg.dtype)
        pool = (num_blocks, cfg.n_kv_heads, block_size, cfg.d_head)
        n_gqa = len(cfg.gqa_layers)
        n_kda = cfg.n_layer - n_gqa
        return {
            "k": [jnp.zeros(pool, dt) for _ in range(n_gqa)],
            "v": [jnp.zeros(pool, dt) for _ in range(n_gqa)],
            "conv": [jnp.zeros((slots, cfg.linear_conv - 1,
                                cfg.conv_channels), dt)
                     for _ in range(n_kda)],
            "ssm": [jnp.zeros((slots, cfg.linear_heads, cfg.linear_dk,
                               cfg.linear_dv), jnp.float32)
                    for _ in range(n_kda)]}

    def paged_cache_specs(self):
        return jax.tree.map(
            lambda x: P(*(None,) * x.ndim),
            jax.eval_shape(lambda: self.init_paged_cache(1, 1)))

    def apply_paged_prefill(self, params, input_ids, cache, token_blocks,
                            token_offsets, length, slot=0):
        """Prefill ONE sequence, right-padded to its bucket, into slot
        ``slot``: the chunk program at ``start = 0``."""
        BS = paged.block_size(cache)
        return self.apply_paged_chunk(
            params, input_ids, cache, token_blocks, token_offsets,
            jnp.int32(0), length, token_blocks[::BS], slot)

    def apply_paged_chunk(self, params, input_ids, cache, token_blocks,
                          token_offsets, start, true_len, table, slot=0):
        """``true_len`` tokens of slot ``slot``'s sequence at positions
        ``start ..`` (the contract of ``Llama.apply_paged_chunk``, plus
        the slot). Returns (logits (1, V) at token true_len - 1, cache)."""
        step = paged.chunk_step(
            self.paged_geometry(), cache, token_blocks, token_offsets,
            jnp.asarray(start, jnp.int32), jnp.asarray(true_len, jnp.int32),
            table, jnp.asarray(slot, jnp.int32))
        x = self._layers(params, self._embed(params, input_ids), step)
        last = jnp.take_along_axis(
            x, jnp.maximum(true_len - 1, 0)[None, None, None], axis=1)
        return self.head(params, last)[:, 0], step.cache

    def apply_paged_decode(self, params, tokens, lengths, cache,
                           block_tables):
        """One decode step: a token a slot at position ``lengths``."""
        step = paged.batch_step(self.paged_geometry(), cache, lengths,
                                block_tables, 1)
        x = self._layers(params, self._embed(params, tokens[:, None]), step)
        return self.head(params, x)[:, 0], step.cache
