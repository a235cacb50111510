"""Olmo-Hybrid (``model_type`` olmo_hybrid): gated delta-rule layers (Gated
DeltaNet, arXiv 2412.06464) and full-attention layers, three to one. No
positional encoding; every sublayer's OUTPUT is normed (OLMo 2), none of its
inputs: ``h += rms(Mix(h)); h += rms(SwiGLU(h))``. The mixer by
``layer_types[i]``:

* ``full_attention``: QK-norm over the whole projection (OLMo 2, OLMoE),
  causal softmax attention, as many K/V heads as query heads;
* ``linear_attention``: ``[q | k | v] = silu(conv(x W))``, L2-normalised q
  and k a head, the gated delta rule ``S_t = a_t S_{t-1} (I - b_t k_t k_t^T)
  + b_t v_t k_t^T`` with ``b_t`` in (0, 2), a gated RMSNorm a head, the
  out-projection.

The equations are written out in ``perfbench/references/olmo_hybrid.py``,
which this file has to equal. What is particular to the program:

**Two kinds of cache** (``models/paged.py``): a full layer's K/V pool under
the sequence's block table, which is all the allocator's blocks pay for,
and a linear layer's recurrent state a slot: ``conv``, the last K - 1 inputs
of the conv, (slots, K - 1, 2 H dk + H dv) in the parameters' dtype, and
``ssm``, the matrix state S, (slots, H, dk, dv) float32, dk the sublanes and
dv the lanes. ``slot_state`` tells the engine so; it hands the prefill and
chunk programs their slot.

**A prompt's tokens go through the chunkwise rule** (``ops/gated_delta_rule
.py``): a prefill or chunk program of C tokens is C / 64 chunks, products
on the MXU within each and the state carried between them, from the slot's
state (zeros at ``start = 0``) to the state after token ``true_len - 1``;
the padding behind it has ``a = 1, b = 0`` and moves nothing. A decode step
is the rule's one-token update on every live slot.

**The rule is a Pallas kernel wherever the step's attention is** (``step
.use_kernel``, ``models/paged.py``'s answer: Mosaic on a TPU, the Pallas
interpreter off it; ``ops/pallas/gated_delta_rule.py``): the chunk kernel
keeps S in VMEM over a call's chunks, and the step kernel's grid is the
step's live slots (``step.active``), the ``ssm`` leaf aliased and written
in place, a dead slot's row neither read nor written. A step that runs no
kernels, and ``apply``, run the XLA forms, which are the kernels'
reference. No setting chooses; the programs count what they took
(``note_call("rule", ...)``: ``rule_calls`` / ``rule_kernel_calls`` on the
engine's spans).

**The residual stream is float32**; every weight product takes bfloat16
rows and the bfloat16 weight and accumulates in float32, as the other
families do. Sixteen layers of unit-norm updates on a stream that grows as
their root would lose a bfloat16 stream's last bits at every add; the
stream is C x D x 4 bytes a program and costs nothing beside the weights.
The conv's inputs are rounded to the parameters' dtype where they are
made, so that the K - 1 a slot keeps are the ones the next chunk would
have seen.

Serving only: there is no backward for the rule (ROADMAP), ``apply`` is the
dense forward of the tests and of the v1 engine's ``forward``.
"""

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.gated_delta_rule import chunk_rule, step_rule
from ..ops.pallas._common import note_call
from ..ops.pallas.gated_delta_rule import (chunk_rule_kernel, live_slot_list,
                                           step_rule_kernel)
from . import paged
from .llama import _rms_norm

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    max_seq_len: int = 65536
    n_head: int = 30
    d_model: int = 3840
    d_ff: int = 11008
    layer_types: tuple = (LINEAR, LINEAR, LINEAR, FULL) * 8
    linear_heads: int = 30          # key heads = value heads
    linear_dk: int = 96
    linear_dv: int = 192
    linear_conv: int = 4            # K
    allow_neg_eigval: bool = True   # b = 2 sigmoid(.), else sigmoid(.)
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        if set(self.layer_types) - {LINEAR, FULL} or FULL not in \
                self.layer_types:
            raise ValueError(
                f"layer_types are {LINEAR!r} and {FULL!r}, with at least "
                f"one {FULL!r} (the block tables are its pool's)")
        if self.d_model % self.n_head:
            raise ValueError("n_head has to divide d_model")

    @property
    def n_layer(self):
        return len(self.layer_types)

    @property
    def d_head(self):
        return self.d_model // self.n_head

    @property
    def conv_channels(self):
        return self.linear_heads * (2 * self.linear_dk + self.linear_dv)

    def num_params(self):
        D, F, H = self.d_model, self.d_ff, self.linear_heads
        dk, dv, K = self.linear_dk, self.linear_dv, self.linear_conv
        per = {LINEAR: D * (2 * H * dk + 2 * H * dv) + 2 * D * H
               + K * self.conv_channels + 2 * H + dv + H * dv * D,
               FULL: 4 * D * D + 2 * D}
        return 2 * self.vocab_size * D + D + sum(
            per[t] + 3 * D * F + 2 * D for t in self.layer_types)


# the published model
OLMO_HYBRID_7B = OlmoHybridConfig()
# two periods, dk != dv, nothing a multiple of the lanes
OLMO_HYBRID_TINY = OlmoHybridConfig(
    vocab_size=256, max_seq_len=256, n_head=4, d_model=64, d_ff=128,
    layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 2, linear_heads=4,
    linear_dk=8, linear_dv=16)
OLMO_HYBRID_PRESETS = {"tiny": OLMO_HYBRID_TINY,
                       "olmo-hybrid-7b": OLMO_HYBRID_7B}


def _mm(x, w, scope):
    """float32 ``x @ w`` for a weight kept in a narrower dtype: x is
    rounded to it, the sum is float32. ``scope`` names the product's
    device operations (``monitor/tag_schema.py:SCOPE_SCHEMA``)."""
    with jax.named_scope(scope):
        return jnp.dot(x.astype(w.dtype), w,
                       preferred_element_type=jnp.float32)


def _l2(x, eps):
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
            T = q.shape[1]
            scores = jnp.einsum("bthd,bshd->bhts", q, k,
                                preferred_element_type=jnp.float32) \
                / math.sqrt(q.shape[-1])
            mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
            probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
            return jnp.einsum("bhts,bshd->bthd", probs.astype(q.dtype),
                              v), None

        return attn_fn


class OlmoHybrid:
    """Params: wte (V, D), lm_head (V, D), norm_f (D,), and ``layers``, a
    list of one dict a layer (the stack is not uniform, so nothing is
    stacked): norm1, norm2 (D,), w1 (D, 2F) [gate | up], w2 (F, D) and the
    mixer's own (``init`` names them; ``perfbench/references/olmo_hybrid.py``
    lists the shapes)."""

    # the cache holds state by batch slot, not only blocks under a table:
    # the engine gives the prefill / chunk programs their slot and refuses
    # what assumes length-masked KV (prefix cache, speculative rollback,
    # KV offload and transfer)
    slot_state = True

    def __init__(self, config: OlmoHybridConfig):
        self.config = config

    # ------------------------------------------------------------- weights
    def init(self, rng):
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        D, F, H = cfg.d_model, cfg.d_ff, cfg.linear_heads
        dk, dv, K = cfg.linear_dk, cfg.linear_dv, cfg.linear_conv
        std = 0.02
        res_std = std / math.sqrt(2 * cfg.n_layer)
        # A norm on a sublayer's OUTPUT undoes the 1 / sqrt(2 L) of its
        # residual projection, so the depth scaling that keeps a seeded
        # stack's updates small beside its stream sits on the norm's gain,
        # and the stream starts at unit scale (embedding rows normal(0, 1)):
        # the 2 L updates together are then as large as the embedding.
        # With gains of 1 on a 0.02 embedding the stream is nothing but
        # updates, each a function of the ones before, and the stack is
        # chaotic: a rounding of 2^-9 in layer 0 was O(1) at the logits
        # (d 256, 16 layers, bfloat16 against float32: 2.3 standard
        # deviations of a logit; 0.03 with these; CPU, PR 41)
        gain = 1.0 / math.sqrt(2 * cfg.n_layer)

        def nrm(key, shape, s=std):
            return (jax.random.normal(key, shape, jnp.float32) * s).astype(dt)

        def linear(ks):
            # the gates' own initialisation (Mamba-2's, which Gated
            # DeltaNet keeps) where normal(0.02) would give a layer that
            # forgets everything or nothing: A uniform in (0, 16), dt
            # log-uniform in [1e-3, 0.1] through the inverse softplus
            step = jnp.exp(jax.random.uniform(
                ks[5], (H,), jnp.float32, math.log(1e-3), math.log(0.1)))
            return {
                "in_proj": nrm(ks[0], (D, 2 * H * dk + 2 * H * dv)),
                "ab_proj": nrm(ks[1], (D, 2 * H)),
                "conv_w": jax.random.uniform(
                    ks[2], (K, cfg.conv_channels), jnp.float32,
                    -K ** -0.5, K ** -0.5).astype(dt),
                "A_log": jnp.log(jax.random.uniform(
                    ks[4], (H,), jnp.float32, 1e-3, 16.0)),
                "dt_b": step + jnp.log(-jnp.expm1(-step)),
                "o_norm": jnp.ones((dv,), dt),
                "out_proj": nrm(ks[3], (H * dv, D), res_std)}

        def full(ks):
            return {"wqkv": nrm(ks[0], (D, 3 * D)),
                    "q_norm": jnp.ones((D,), dt),
                    "k_norm": jnp.ones((D,), dt),
                    "wo": nrm(ks[1], (D, D), res_std)}

        def layer(i, kind):
            ks = jax.random.split(jax.random.fold_in(rng, i + 2), 10)
            return {**(linear(ks) if kind == LINEAR else full(ks)),
                    "norm1": jnp.full((D,), gain, dt),
                    "norm2": jnp.full((D,), gain, dt),
                    "w1": nrm(ks[8], (D, 2 * F)),
                    "w2": nrm(ks[9], (F, D), res_std)}

        return {"wte": nrm(jax.random.fold_in(rng, 0), (cfg.vocab_size, D),
                           1.0),
                "lm_head": nrm(jax.random.fold_in(rng, 1),
                               (cfg.vocab_size, D)),
                "norm_f": jnp.ones((D,), dt),
                "layers": [layer(i, t)
                           for i, t in enumerate(cfg.layer_types)]}

    def partition_specs(self, topology=None):
        """Every leaf whole on every device: this family is not sharded."""
        return jax.tree.map(lambda x: P(*(None,) * x.ndim),
                            jax.eval_shape(self.init, jax.random.key(0)))

    # -------------------------------------------------------------- mixers
    def _delta(self, x, p, conv0, S0, valid, n_valid, kernel=False,
               live=None):
        """The gated delta-rule mixer: x (B, C, D) from state (conv0 (B,
        K-1, channels), S0 (B, H, dk, dv) float32); pads (``~valid``) do
        not move the state, and the conv tail is that of each row's last
        ``n_valid`` token. ``kernel``: the rule as a Pallas kernel;
        ``live``: a decode step's ``live_slot_list``, with which S0 is the
        layer's whole leaf and comes back written in place, live rows
        only. -> (Mix (B, C, D), (conv, S) after the last real token)."""
        cfg = self.config
        B, C, _ = x.shape
        H, dk, dv = cfg.linear_heads, cfg.linear_dk, cfg.linear_dv
        K, ch, eps = cfg.linear_conv, cfg.conv_channels, cfg.rms_eps
        uz = _mm(x, p["in_proj"], "dstpu.mm.in_proj")
        ab = _mm(x, p["ab_proj"], "dstpu.mm.in_proj")
        u, z = uz[..., :ch].astype(conv0.dtype), uz[..., ch:]
        win = jnp.concatenate([conv0, u], axis=1)          # (B, K-1+C, ch)
        w = p["conv_w"].astype(jnp.float32)
        qkv = jax.nn.silu(sum(win[:, j:j + C] * w[j] for j in range(K)))
        q = _l2(qkv[..., :H * dk].reshape(B, C, H, dk), eps) * dk ** -0.5
        k = _l2(qkv[..., H * dk:2 * H * dk].reshape(B, C, H, dk), eps)
        v = qkv[..., 2 * H * dk:].reshape(B, C, H, dv)
        log_a = -jnp.exp(p["A_log"]) * jax.nn.softplus(ab[..., :H]
                                                        + p["dt_b"])
        b = jax.nn.sigmoid(ab[..., H:]) * (2.0 if cfg.allow_neg_eigval
                                           else 1.0)
        log_a = jnp.where(valid[..., None], log_a, 0.0)
        b = jnp.where(valid[..., None], b, 0.0)
        # a one-token step that is not a decode step over the slots (a
        # dense forward of one token, a chunk program of one) has no list
        # of live slots: the XLA form
        kernel = kernel and (C > 1 or live is not None)
        note_call("rule", kernel)
        if C == 1:
            with jax.named_scope("dstpu.gdn.step"):
                rows = (q[:, 0], k[:, 0], v[:, 0], log_a[:, 0], b[:, 0], S0)
                o, S = step_rule_kernel(*rows, live) if kernel \
                    else step_rule(*rows)
            o, conv1 = o[:, None], win[:, 1:]
        else:
            with jax.named_scope("dstpu.gdn.chunk"):
                o, S = (chunk_rule_kernel if kernel else chunk_rule)(
                    q, k, v, log_a, b, S0)
            conv1 = jax.vmap(lambda rows, n: lax.dynamic_slice(
                rows, (n, 0), (K - 1, ch)))(win, n_valid)
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
            * p["o_norm"].astype(jnp.float32)
        o = o * jax.nn.silu(z.reshape(B, C, H, dv))
        return _mm(o.reshape(B, C, H * dv), p["out_proj"],
                   "dstpu.mm.out_proj"), (conv1, S)

    def _attention(self, x, p, attn_fn):
        """A full layer; ``attn_fn`` owns the cache and the mask."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        B, C, D = x.shape
        heads = (B, C, cfg.n_head, cfg.d_head)
        qkv = _mm(x, p["wqkv"], "dstpu.mm.qkv")
        q = _rms_norm(qkv[..., :D], p["q_norm"], cfg.rms_eps)
        k = _rms_norm(qkv[..., D:2 * D], p["k_norm"], cfg.rms_eps)
        with jax.named_scope("dstpu.attn.full"):
            out, _ = attn_fn(q.astype(dt).reshape(heads),
                             k.astype(dt).reshape(heads),
                             qkv[..., 2 * D:].astype(dt).reshape(heads))
        return _mm(out.reshape(B, C, D), p["wo"], "dstpu.mm.attn_out")

    def _layers(self, params, x, step):
        """The one layer loop: ``step`` is a ``models/paged.py`` step (or
        ``apply``'s stand-in) and owns every cache."""
        cfg = self.config
        # the rule follows the step's attention: a kernel where that is
        # one. A decode step's kernel takes the live slots alone, from one
        # list for every layer, and writes the ``ssm`` leaf in place
        kernel = getattr(step, "use_kernel", False)
        live = live_slot_list(step.active) \
            if kernel and x.shape[1] == 1 and hasattr(step, "active") \
            else None
        for i, (kind, p) in enumerate(zip(cfg.layer_types,
                                          params["layers"])):
            if kind == LINEAR:
                with jax.named_scope("dstpu.gdn.mix"):
                    mix, state = self._delta(
                        x, p, *step.state(i), step.valid, step.n_valid,
                        kernel, live)
                    step.put_state(
                        i, *state,
                        in_place=("ssm",) if live is not None else ())
            else:
                mix = self._attention(x, p, step.layer(i))
            x = x + _rms_norm(mix, p["norm1"], cfg.rms_eps)
            gu = _mm(x, p["w1"], "dstpu.mm.mlp")
            mlp = _mm(jax.nn.silu(gu[..., :cfg.d_ff]) * gu[..., cfg.d_ff:],
                      p["w2"], "dstpu.mm.mlp")
            x = x + _rms_norm(mlp, p["norm2"], cfg.rms_eps)
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
            self, n_kv_heads=cfg.n_head, windows=(0,) * cfg.n_layer,
            kinds=tuple(paged.STATE if t == LINEAR else paged.KV
                        for t in cfg.layer_types))

    def init_paged_cache(self, num_blocks, block_size, dtype=None, slots=1,
                         ring_blocks=None):
        """``k`` / ``v``: a pool a full layer, ``num_blocks`` blocks under
        the block tables, (NB, n_head, BS, d_head); ``conv`` / ``ssm``: a
        row a slot a linear layer. ``ring_blocks`` is the engine's for a
        window layer's ring: there is none here."""
        cfg = self.config
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(cfg.dtype)
        pool = (num_blocks, cfg.n_head, block_size, cfg.d_head)
        n_full = cfg.layer_types.count(FULL)
        n_linear = cfg.n_layer - n_full
        return {
            "k": [jnp.zeros(pool, dt) for _ in range(n_full)],
            "v": [jnp.zeros(pool, dt) for _ in range(n_full)],
            "conv": [jnp.zeros((slots, cfg.linear_conv - 1,
                                cfg.conv_channels), dt)
                     for _ in range(n_linear)],
            "ssm": [jnp.zeros((slots, cfg.linear_heads, cfg.linear_dk,
                               cfg.linear_dv), jnp.float32)
                    for _ in range(n_linear)]}

    def paged_cache_specs(self):
        return jax.tree.map(
            lambda x: P(*(None,) * x.ndim),
            jax.eval_shape(lambda: self.init_paged_cache(1, 1)))

    def apply_paged_prefill(self, params, input_ids, cache, token_blocks,
                            token_offsets, length, slot=0):
        """Prefill ONE sequence, right-padded to its bucket, into slot
        ``slot``: the chunk program at ``start = 0``."""
        BS = cache["k"][0].shape[2]
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
        """One decode step: the verify program at C = 1."""
        logits, cache = self.apply_paged_verify(
            params, tokens[:, None], lengths, cache, block_tables)
        return logits[:, 0], cache

    def apply_paged_verify(self, params, tokens, lengths, cache,
                           block_tables):
        """C tokens a slot in one pass; tokens (B, C), lengths (B,) the
        first one's position, block_tables (B, MB) with row b slot b's.
        Returns (logits (B, C, V), cache). The state it leaves is that
        after all C tokens: nothing here can take a token back."""
        step = paged.batch_step(self.paged_geometry(), cache, lengths,
                                block_tables, tokens.shape[1])
        x = self._layers(params, self._embed(params, tokens), step)
        return self.head(params, x), step.cache
