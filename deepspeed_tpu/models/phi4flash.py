"""Phi-4-mini-flash-reasoning (SambaY, arXiv 2507.06607): a decoder-hybrid-
decoder. No positional encoding; pre-LN blocks ``h += Mix(LN1 h); h +=
SwiGLU(LN2 h)`` whose mixer depends on the layer's place in the stack:

* ``mamba``  (i < L/2, even): Mamba-1 selective scan (arXiv 2312.00752);
* ``window`` (i < L/2, odd): differential attention (arXiv 2410.05258)
  under a causal window of ``sliding_window`` keys;
* ``memory`` (i = L/2): a Mamba mixer whose scan output (with the D skip,
  before the gate) is kept as the memory ``m`` of the cross-decoder;
* ``full``   (i = L/2 + 1): differential attention, causal, full: the only
  full-length K/V of the model;
* ``gmu``    (i >= L/2 + 2, even): Gated Memory Unit ``(m * silu(x W1)) W2``;
* ``cross``  (i >= L/2 + 2, odd): differential attention whose queries read
  the ``full`` layer's K/V; it has no K/V of its own.

The equations are written out in ``perfbench/references/phi4flash.py``,
which this file has to equal. What is particular to the program:

**Differential attention on the paged kernels as they are.** Adjacent heads
pair (``q1 = q[0::2]``, ...), each softmax reads ``V = [v1 | v2]``. K and V
therefore go to the pools as ``n_kv_heads / 2`` heads of ``2 * d_head``
lanes — ``[k1 | k2]`` and ``[v1 | v2]``, a plain reshape of the projection
— and a query head is zero-padded to the same width on the side of the
half it does not read: ``[q | 0]`` for a ``q1`` head, ``[0 | q]`` for a
``q2`` head. One paged read a layer (``models/paged.py``: the decode or
the chunk kernel) then gives ``A1 V`` and ``A2 V`` as alternating heads, every
K and V byte is read once, and at the published widths the pools are the
128-lane pools of the head-dim-128 families (no lane padding). The
``1 / sqrt(d_head)`` is folded into the padded query (a power of two for
d_head 64: exact in bfloat16) and the kernels run with scale 1.

**Three kinds of cache** (``models/paged.py``): the ``full`` layer's pool
under the sequence's block table, which is all the allocator's blocks pay
for; the ``window`` layers' K/V as a ring of blocks a slot; the Mamba
layers' ``conv`` (the last ``ssm_conv - 1`` inputs) and ``ssm`` ((N, Din):
the wide axis in the lanes) state a slot, both float32. ``slot_state`` tells
the engine so; it hands the prefill and chunk programs their slot.

**Activations are float32, and every projection takes them as two
bfloat16 pieces** (``_mm``: ``x = hi + lo``, both pieces against the
bfloat16 weight in one product, summed in float32). With activations
rounded to bfloat16 at every sublayer's input and inside it, this stack
at its published depth sat 0.05 standard deviations of a position's
logits (rms; 0.3 at worst) from its float32 reference, and picked another
token than the reference at one position in eight (PERF.md, PR 30): the
rounding of what a Mamba mixer and an MLP are handed comes out of them
several times larger, 64 sublayers deep. The weights still stream once,
as bfloat16, and a decode step has the rows to spare; a prefill's
products are twice the rows. Only the paged attention read is bfloat16
(q, and the K/V in the pools), which costs a tenth of that.

Serving only: there is no backward for the scan (ROADMAP), ``apply`` is the
dense forward of the tests and of the v1 engine's ``forward``.
"""

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from . import paged
from .common import _pieces
from .llama import _layer_norm

@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    max_seq_len: int = 262144
    n_layer: int = 32
    n_head: int = 40
    n_kv_heads: int = 20
    d_model: int = 2560
    d_ff: int = 10240
    sliding_window: int = 512
    ssm_state: int = 16              # N
    ssm_conv: int = 4                # K
    ssm_expand: int = 2              # Din = expand * d_model
    ln_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.n_layer < 4 or self.n_layer % 2:
            raise ValueError("n_layer must be even and >= 4: a self-decoder "
                             "half, the memory layer, the full layer")
        if self.n_head % 2 or self.n_kv_heads % 2 \
                or self.n_head % self.n_kv_heads:
            raise ValueError("differential attention pairs adjacent heads: "
                             "n_head and n_kv_heads even, n_kv_heads a "
                             "divisor of n_head")

    @property
    def d_head(self):
        return self.d_model // self.n_head

    @property
    def d_inner(self):
        return self.ssm_expand * self.d_model

    @property
    def dt_rank(self):
        return -(-self.d_model // 16)

    @property
    def mixers(self):
        """Per layer: mamba | window | memory | full | gmu | cross."""
        half = self.n_layer // 2
        return tuple(
            ("window" if i % 2 else "mamba") if i < half
            else "memory" if i == half else "full" if i == half + 1
            else ("cross" if i % 2 else "gmu")
            for i in range(self.n_layer))

    @property
    def attn_layer_windows(self):
        """The window of each paged attention read, in layer order (0 =
        the whole sequence): what the engine's telemetry averages a decode
        step's grid over, and why it keeps no prefix cache."""
        return tuple(self.sliding_window if m == "window" else 0
                     for m in self.mixers if m in ("window", "full", "cross"))

    def num_params(self):
        D, F, Din = self.d_model, self.d_ff, self.d_inner
        N, K, R = self.ssm_state, self.ssm_conv, self.dt_rank
        hd, kv = self.d_head, self.n_kv_heads * self.d_head
        lam = 4 * hd + 2 * hd
        per = {
            "mamba": D * 2 * Din + Din * K + Din + Din * (R + 2 * N)
            + R * Din + Din + N * Din + Din + Din * D,
            "window": D * (D + 2 * kv) + D + 2 * kv + D * D + D + lam,
            "gmu": 2 * D * Din,
            "cross": 2 * (D * D + D) + lam}
        per["memory"], per["full"] = per["mamba"], per["window"]
        return self.vocab_size * D + 2 * D + sum(
            per[m] + 4 * D + 3 * D * F for m in self.mixers)


# the published model
PHI4_MINI_FLASH = Phi4FlashConfig()
PHI4FLASH_TINY = Phi4FlashConfig(
    vocab_size=256, max_seq_len=128, n_layer=8, n_head=4, n_kv_heads=2,
    d_model=64, d_ff=128, sliding_window=8)
PHI4FLASH_PRESETS = {"tiny": PHI4FLASH_TINY,
                     "phi-4-mini-flash": PHI4_MINI_FLASH}


def _mm(x, w, scope):
    """float32 ``x @ w`` for a weight kept in a narrower dtype: x goes in
    as its pieces of that dtype, one product over all of them so that the
    weight is read once; float32 out. ``scope`` names the product's device
    operations (``monitor/tag_schema.py:SCOPE_SCHEMA``)."""
    with jax.named_scope(scope):
        return jnp.dot(_pieces(x, w.dtype), w,
                       preferred_element_type=x.dtype).sum(axis=0)


def _dense_diff_reads(q, k, v, window):
    """``apply``'s attention read, in the kernels' terms: q (B, T, H, w)
    padded and scaled, k / v (B, T, KV, w) -> (B, T, H, w), causal."""
    T, G = q.shape[1], q.shape[2] // k.shape[2]
    scores = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, G, axis=2),
                        preferred_element_type=jnp.float32)
    t, s = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = (s <= t) & (t - s < window) if window else s <= t
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", probs.astype(q.dtype),
                      jnp.repeat(v, G, axis=2))


class _DenseStep:
    """``apply``'s stand-in for a ``models/paged.py`` step: all T positions
    of B sequences at once, from zero state, nothing kept."""

    def __init__(self, cfg, B, T):
        self.cfg, self.kv = cfg, None
        self.valid = jnp.ones((B, T), bool)
        self.n_valid = jnp.full((B,), T, jnp.int32)

    def state(self, i):
        cfg, B = self.cfg, self.valid.shape[0]
        return (jnp.zeros((B, cfg.ssm_conv - 1, cfg.d_inner), jnp.float32),
                jnp.zeros((B, cfg.ssm_state, cfg.d_inner), jnp.float32))

    def put_state(self, i, *new):
        pass

    def layer(self, i):
        mixer = self.cfg.mixers[i]
        window = self.cfg.sliding_window if mixer == "window" else 0

        def attn_fn(q, k=None, v=None):
            if mixer == "full":
                self.kv = (k, v)
            elif mixer == "cross":
                k, v = self.kv
            return _dense_diff_reads(q, k, v, window), None

        return attn_fn


class Phi4Flash:
    """Params: wte (V, D), ln_f_s / ln_f_b, and ``layers``, a list of one
    dict a layer (the stack is not uniform, so nothing is stacked): ln1_s,
    ln1_b, ln2_s, ln2_b, w1 (D, 2F) [gate | up], w2 (F, D) and the mixer's
    own (``init`` names them; ``perfbench/references/phi4flash.py`` lists
    the shapes)."""

    # the cache holds state by batch slot, not only blocks under a table:
    # the engine gives the prefill / chunk programs their slot, sizes the
    # rings, and refuses what assumes length-masked KV (prefix cache,
    # speculative rollback, KV offload and transfer)
    slot_state = True

    def __init__(self, config: Phi4FlashConfig):
        self.config = config

    # ------------------------------------------------------------- weights
    def init(self, rng):
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        D, F, Din, hd = cfg.d_model, cfg.d_ff, cfg.d_inner, cfg.d_head
        N, K, R = cfg.ssm_state, cfg.ssm_conv, cfg.dt_rank
        kv = cfg.n_kv_heads * hd
        std = 0.02
        res_std = std / math.sqrt(2 * cfg.n_layer)

        def nrm(key, shape, s=std):
            return (jax.random.normal(key, shape, jnp.float32) * s).astype(dt)

        def uni(key, shape, bound, dtype=dt):
            return jax.random.uniform(key, shape, jnp.float32, -bound,
                                      bound).astype(dtype)

        def mamba(ks):
            # Mamba's own initialisation where normal(0.02) would give a
            # layer that forgets everything or nothing: S4D-real A, D = 1,
            # dt log-uniform in [1e-3, 0.1] through the inverse softplus
            step = jnp.exp(jax.random.uniform(
                ks[5], (Din,), jnp.float32, math.log(1e-3), math.log(0.1)))
            return {
                "in_proj": nrm(ks[0], (D, 2 * Din)),
                "conv_w": uni(ks[1], (Din, K), K ** -0.5),
                "conv_b": uni(ks[2], (Din,), K ** -0.5),
                "x_proj": nrm(ks[3], (Din, R + 2 * N)),
                "dt_w": uni(ks[4], (R, Din), R ** -0.5),
                "dt_b": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, N + 1, dtype=jnp.float32))[:, None], (N, Din)),
                "D_skip": jnp.ones((Din,), jnp.float32),
                "out_proj": nrm(ks[6], (Din, D), res_std)}

        def attention(ks, own_kv):
            qkv = {"wqkv": nrm(ks[0], (D, D + 2 * kv)),
                   "bqkv": jnp.zeros((D + 2 * kv,), dt)} if own_kv else \
                  {"wq": nrm(ks[0], (D, D)), "bq": jnp.zeros((D,), dt)}
            lam = {n: nrm(k, (hd,), 0.1) for n, k in zip(
                ("lq1", "lk1", "lq2", "lk2"), ks[2:6])}
            return {**qkv, **lam, "wo": nrm(ks[1], (D, D), res_std),
                    "bo": jnp.zeros((D,), dt),
                    "subln": jnp.ones((2 * hd,), dt)}

        def layer(i, mixer):
            ks = jax.random.split(jax.random.fold_in(rng, i + 1), 10)
            if mixer in ("mamba", "memory"):
                mix = mamba(ks)
            elif mixer == "gmu":
                mix = {"g_in": nrm(ks[0], (D, Din)),
                       "g_out": nrm(ks[1], (Din, D), res_std)}
            else:
                mix = attention(ks, mixer != "cross")
            return {**mix,
                    "ln1_s": jnp.ones((D,), dt), "ln1_b": jnp.zeros((D,), dt),
                    "ln2_s": jnp.ones((D,), dt), "ln2_b": jnp.zeros((D,), dt),
                    "w1": nrm(ks[8], (D, 2 * F)),
                    "w2": nrm(ks[9], (F, D), res_std)}

        return {"wte": nrm(jax.random.fold_in(rng, 0), (cfg.vocab_size, D)),
                "ln_f_s": jnp.ones((D,), dt), "ln_f_b": jnp.zeros((D,), dt),
                "layers": [layer(i, m) for i, m in enumerate(cfg.mixers)]}

    def partition_specs(self, topology=None):
        """Every leaf whole on every device: this family is not sharded."""
        return jax.tree.map(lambda x: P(*(None,) * x.ndim),
                            jax.eval_shape(self.init, jax.random.key(0)))

    # -------------------------------------------------------------- mixers
    def _mamba(self, x, p, conv0, ssm0, valid, n_valid):
        """x (B, C, D) from state (conv0 (B, K-1, Din), ssm0 (B, N, Din),
        both float32); pads (``~valid``) do not move the state, and the conv
        tail is that of each row's last ``n_valid`` token.
        -> (Mix (B, C, D), the scan output y (B, C, Din) float32, (conv,
        ssm) after the last real token)."""
        cfg = self.config
        B, C, _ = x.shape
        Din, N, K, R = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv, cfg.dt_rank
        uz = _mm(x, p["in_proj"], "dstpu.mm.in_proj")
        u, z = uz[..., :Din], uz[..., Din:]
        win = jnp.concatenate([conv0, u], axis=1)          # (B, K-1+C, Din)
        w = p["conv_w"].astype(x.dtype)
        conv = sum(win[:, k:k + C] * w[:, k] for k in range(K)) \
            + p["conv_b"].astype(x.dtype)
        u1 = jax.nn.silu(conv)                                # (B, C, Din)
        dbc = _mm(u1, p["x_proj"], "dstpu.mm.x_proj")
        step = jax.nn.softplus(
            _mm(dbc[..., :R], p["dt_w"], "dstpu.mm.dt") + p["dt_b"])
        step = jnp.where(valid[..., None], step, 0.0)
        Bm, Cm = dbc[..., R:R + N], dbc[..., R + N:]
        A = -jnp.exp(p["A_log"])                              # (N, Din)

        def advance(s, xs):
            d_t, du_t, b_t, c_t = xs              # (B, Din) x2, (B, N) x2
            s = jnp.exp(d_t[:, None, :] * A) * s \
                + du_t[:, None, :] * b_t[:, :, None]
            return s, jnp.sum(s * c_t[:, :, None], axis=1)

        if C == 1:
            ssm, y = advance(ssm0, (step[:, 0], step[:, 0] * u1[:, 0],
                                    Bm[:, 0], Cm[:, 0]))
            y, conv1 = y[:, None], win[:, 1:]
        else:
            ssm, y = lax.scan(advance, ssm0, tuple(
                a.swapaxes(0, 1) for a in (step, step * u1, Bm, Cm)),
                unroll=8)
            y = y.swapaxes(0, 1)
            conv1 = jax.vmap(lambda rows, n: lax.dynamic_slice(
                rows, (n, 0), (K - 1, Din)))(win, n_valid)
        y = y + p["D_skip"] * u1
        return _mm(y * jax.nn.silu(z), p["out_proj"],
                   "dstpu.mm.out_proj"), y, (conv1, ssm)

    def _attention(self, x, p, i, attn_fn):
        """Differential attention of layer i; ``attn_fn`` owns the cache
        and the mask (see the module docstring for the layout it is
        handed)."""
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        B, C, D = x.shape
        H, KV, hd = cfg.n_head, cfg.n_kv_heads, cfg.d_head
        shared = cfg.mixers[i] == "cross"
        with jax.named_scope("dstpu.attn.diff"):
            if shared:
                q = _mm(x, p["wq"], "dstpu.mm.qkv") + p["bq"]
                k = v = None
            else:
                qkv = _mm(x, p["wqkv"], "dstpu.mm.qkv") + p["bqkv"]
                q = qkv[..., :D]
                k, v = (a.reshape(B, C, KV // 2, 2 * hd).astype(dt)
                        for a in jnp.split(qkv[..., D:], 2, axis=-1))
            q = (q.reshape(B, C, H, hd) * (1.0 / math.sqrt(hd))).astype(dt)
            first = (jnp.arange(H) % 2 == 0)[:, None]        # a q1 head
            zero = jnp.zeros_like(q)
            q = jnp.concatenate([jnp.where(first, q, zero),
                                 jnp.where(first, zero, q)], axis=-1)
        with jax.named_scope("dstpu.attn.window") \
                if cfg.mixers[i] == "window" \
                else jax.named_scope("dstpu.attn.shared_kv"):
            out, _ = attn_fn(q, k, v)                         # (B, C, H, 2hd)
        with jax.named_scope("dstpu.attn.diff"):
            f32 = x.dtype
            lam0 = 0.8 - 0.6 * math.exp(-0.3 * i)
            lq1, lk1, lq2, lk2 = (p[n].astype(f32)
                                  for n in ("lq1", "lk1", "lq2", "lk2"))
            lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
                + lam0
            out = out.astype(f32)
            a = out[:, :, 0::2] - lam * out[:, :, 1::2]       # (B, C, H/2, .)
            a = a * lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                              + cfg.ln_eps) * p["subln"].astype(f32)
            return _mm((a * (1.0 - lam0)).reshape(B, C, D), p["wo"],
                       "dstpu.mm.attn_out") + p["bo"]

    def _layers(self, params, x, step):
        """The one layer loop: ``step`` is a ``models/paged.py`` step (or
        ``apply``'s stand-in) and owns every cache."""
        cfg = self.config
        memory = None
        for i, (mixer, p) in enumerate(zip(cfg.mixers, params["layers"])):
            h = _layer_norm(x, p["ln1_s"], p["ln1_b"], cfg.ln_eps)
            if mixer in ("mamba", "memory"):
                with jax.named_scope("dstpu.ssm.mix"):
                    mix, y, state = self._mamba(
                        h, p, *step.state(i), step.valid, step.n_valid)
                    step.put_state(i, *state)
                if mixer == "memory":
                    memory = y
            elif mixer == "gmu":
                with jax.named_scope("dstpu.gmu"):
                    gate = _mm(h, p["g_in"], "dstpu.mm.gmu")
                    mix = _mm(memory * jax.nn.silu(gate), p["g_out"],
                              "dstpu.mm.gmu")
            else:
                mix = self._attention(h, p, i, step.layer(i))
            x = x + mix
            gu = _mm(_layer_norm(x, p["ln2_s"], p["ln2_b"], cfg.ln_eps),
                     p["w1"], "dstpu.mm.mlp")
            x = x + _mm(jax.nn.silu(gu[..., :cfg.d_ff]) * gu[..., cfg.d_ff:],
                        p["w2"], "dstpu.mm.mlp")
        return x

    def _embed(self, params, ids):
        return params["wte"][ids].astype(jnp.float32)

    def head(self, params, x):
        x = _layer_norm(x, params["ln_f_s"], params["ln_f_b"],
                        self.config.ln_eps)
        w = params["wte"]
        with jax.named_scope("dstpu.mm.unembed"):
            return jnp.einsum("pbtd,vd->pbtv", _pieces(x, w.dtype), w,
                              preferred_element_type=x.dtype).sum(axis=0)

    def apply(self, params, input_ids, **_):
        """(B, T) ids -> (B, T, V) float32 logits, no cache."""
        B, T = input_ids.shape
        return self.head(params, self._layers(
            params, self._embed(params, input_ids),
            _DenseStep(self.config, B, T)))

    # ------------------------------------------------- v2 paged serving
    def paged_geometry(self):
        """What ``models/paged.py`` sees: head pairs of 2 * d_head lanes,
        the scale already in the query, and each layer's kind of cache."""
        cfg = self.config
        kinds = {"mamba": paged.STATE, "memory": paged.STATE,
                 "window": paged.RING, "full": paged.KV, "gmu": None,
                 "cross": (paged.SHARED, cfg.n_layer // 2 + 1)}
        return paged.geometry(
            self, n_kv_heads=cfg.n_kv_heads // 2, d_head=2 * cfg.d_head,
            scale=1.0, kinds=tuple(kinds[m] for m in cfg.mixers),
            windows=tuple(cfg.sliding_window if m == "window" else 0
                          for m in cfg.mixers))

    def init_paged_cache(self, num_blocks, block_size, dtype=None, slots=1,
                         ring_blocks=None):
        """``k`` / ``v``: the full layer's pool, ``num_blocks`` blocks under
        the block tables; ``ring_k`` / ``ring_v``: a pool a window layer,
        block 0 and ``ring_blocks`` blocks a slot (enough for a decode
        step when not given); ``conv`` / ``ssm``: a row a slot a Mamba
        layer. Pools are (NB, n_kv_heads / 2, BS, 2 * d_head)."""
        cfg = self.config
        dt = jnp.dtype(dtype) if dtype is not None else jnp.dtype(cfg.dtype)
        if ring_blocks is None:
            ring_blocks = paged.ring_blocks(cfg.sliding_window, 1,
                                            block_size)
        block = (cfg.n_kv_heads // 2, block_size, 2 * cfg.d_head)
        rings = cfg.mixers.count("window")
        states = cfg.mixers.count("mamba") + 1
        cache = {
            "k": [jnp.zeros((num_blocks,) + block, dt)],
            "v": [jnp.zeros((num_blocks,) + block, dt)],
            "conv": [jnp.zeros((slots, cfg.ssm_conv - 1, cfg.d_inner),
                               jnp.float32) for _ in range(states)],
            "ssm": [jnp.zeros((slots, cfg.ssm_state, cfg.d_inner),
                              jnp.float32) for _ in range(states)]}
        for key in ("ring_k", "ring_v"):
            cache[key] = [jnp.zeros((1 + slots * ring_blocks,) + block, dt)
                          for _ in range(rings)]
        return cache

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
