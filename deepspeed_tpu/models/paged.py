"""The paged serving forward's one seam: how a layer's new K/V rows reach
the paged pool, which implementation reads them back, and from which
cached positions each query reads.

The model families keep their own layer loop and take from here one
``attn_fn`` a layer, with the ``_block_core`` contract ``(q, k, v) ->
(attn, (k_pool, v_pool))``: write first (``paged_kv_write``), then the
kernel or the dense fallback, resolved once a program. Nothing else under
``models/`` names the paged kernels, their ``resolve_*`` questions or the
pool write; a layer kind with another cache (a latent KV, a recurrent
state) is one more ``attn_fn`` here, not a fork of the four
``apply_paged_*`` entry points. The dense fallback stays because it is
the only path that runs on a CPU at the default setting, and the
reference the kernel-on/off tests compare against.

A layer's cache is one of five kinds (``Geometry.kinds``):

* ``KV``: a pool of its own under the sequence's block table, the only
  kind the allocator's blocks pay for (cache keys ``k`` / ``v``);
* ``RING``: windowed K/V as **slot state** (``ring_k`` / ``ring_v``):
  ``ring_blocks`` blocks a slot, position p of slot s in block
  ``1 + s * ring_blocks + (p // BS) % ring_blocks`` (block 0 stays the
  pool's scratch block). The table is computed here, in the program, so
  the kernels and their work lists are called as they are: a program
  writes before it reads, the window mask hides whatever a wrapped block
  still holds of older positions, and ``ring_blocks * BS >= window +
  C - 1`` (:func:`ring_blocks`) keeps a C-token step from overwriting a
  key it still reads;
* ``(SHARED, j)``: no cache of its own; reads layer j's pools after
  layer j wrote them, and writes nothing;
* ``STATE``: a recurrent state a slot (``conv`` / ``ssm``), handed out
  and taken back by ``_Step.state`` / ``put_state``: a chunk at
  ``start = 0`` gets zeros whatever the slot held, a decode step keeps
  the state of every slot that is not live. The step says which slots
  those are (``active``) and whether its program runs kernels
  (``use_kernel``), so a model whose update is a kernel over the live
  slots alone writes the leaf in place and says so (``in_place``).

* ``LATENT``: a compressed cache under the sequence's block table, paid
  for by the allocator's blocks as ``KV`` is (cache keys ``lat`` /
  ``idx``): a token's row of ``lat`` is whatever the model attends
  through (MLA: the normalised latent and the one rotary key every head
  shares), its row of ``idx`` the key a learned indexer scores. The read
  is **chosen per query by the model**: :meth:`_Step.latent` scores every
  causal key with the model's ``index_fn``, finds each query's exact
  k-th largest score, and attends the keys that reach it — through the
  model's ``read_fn``, which turns a block of latent rows into scores
  and values in whichever form the program wants (expanded to heads for
  a prompt's chunk, absorbed into the query for a decode step): a
  flash-style pass over blocks of keys gathered through the table. The
  selection is XLA throughout; the read after it of a step of more than
  one query a row is the Pallas kernel of ``ops/pallas/latent_attention.py``
  where the program runs kernels (:func:`_latent_kernel`), given what the
  expanded form is made from (``expand``), and ``read_fn`` everywhere else.

``None`` is a layer with no cache. Everything but ``KV`` and ``LATENT`` is
indexed by the slot, which the chunk and prefill programs are therefore
told.

A serving engine names none of these. What it has to know of a model's
cache (what a live sequence holds of it, what a program call counts over
it, which serving features it cannot have) it asks :class:`Account`, whose
tables (``_KEYS``, ``_BLOCK_AXES``, ``_REFUSALS``) are where a new kind
says so.
"""

import math
from collections import Counter
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas._common import note_call
from ..ops.pallas.latent_attention import latent_chunk_attention
from ..ops.pallas.paged_attention import (
    alibi_slopes, chunk_grid_steps, chunk_tile, chunk_work_list,
    decode_entries_per_step, decode_grid_steps, decode_work_list,
    kv_write_live_rows, kv_write_row_list, paged_chunk_attention,
    paged_decode_attention, paged_decode_attention_reference, paged_kv_write,
    resolve_paged_chunk, resolve_paged_decode)


KV, RING, SHARED, STATE, LATENT = "kv", "ring", "shared", "state", "latent"
# cache keys of each kind's leaves, one list entry a layer of that kind
_KEYS = {KV: ("k", "v"), RING: ("ring_k", "ring_v"), STATE: ("conv", "ssm"),
         LATENT: ("lat", "idx")}
# the pools the paged kernels read: what a serving engine allocates in the
# kernels' layout (``pool_block_dims``)
KERNEL_POOLS = _KEYS[KV]
# keys a pass of a latent read takes at once: a chunk's scores are heads x
# C x keys in float32, a decode step's heads x slots x keys
_LATENT_KEYS = {"chunk": 512, "decode": 2048}


def block_size(cache):
    """Tokens a block of the pools under the block tables."""
    if cache.get("k"):
        return cache["k"][0].shape[2]
    return cache["lat"][0].shape[1]


def _paged_attention(geom):
    """Whether any layer reads K/V through the paged kernels' entry points
    (a model of latent layers alone asks them nothing)."""
    return any(k in (KV, RING) or isinstance(k, tuple) for k in geom.kinds)


def ring_blocks(window, chunk, block_size):
    """Blocks a slot's ring needs so that a step of ``chunk`` tokens can
    write its rows before it reads the ``window`` keys behind them."""
    return -(-(window + chunk - 1) // block_size)


@dataclass(frozen=True)
class Geometry:
    """What the seam needs to know of a model's attention."""
    n_head: int
    n_kv_heads: int
    d_head: int
    dtype: Any
    scale: Any             # the kernels' ``scale``: None = 1/sqrt(d_head);
    #                        1.0 = GPT-2 with scale_attn off
    windows: tuple         # per layer; 0 = global
    alibi: bool
    alibi_inv_norm: bool   # falcon-rw: bf16-quantized bias / sqrt(d_head)
    alibi_bias: Any        # (S,) key positions -> (H, S) score bias
    kernel: Any            # "auto" | bool: the engine's paged_kernel
    block_c: Any           # "auto" | int: the engine's paged_block_c
    kinds: tuple           # per layer: KV | RING | (SHARED, j) | STATE | None
    ring_blocks: int       # blocks a slot of a RING layer; the engine's


def geometry(model, **fields):
    """``model``'s attention geometry, read off its config and the
    trace-time settings the engine installs on it (``_paged_kernel``,
    ``_paged_block_c``, ``_paged_ring_blocks``). A field one family lacks
    reads as "not there": GPT-2 has no KV-head count, ALiBi or single
    window; Llama no ``scale_attn`` or per-layer windows. A family whose
    kernels see other heads than its config names (``paged_geometry``)
    says so itself, through ``fields``."""
    if not fields and hasattr(model, "paged_geometry"):
        return model.paged_geometry()
    cfg = model.config
    windows = getattr(cfg, "attn_layer_windows", None) \
        or (getattr(cfg, "sliding_window", 0),) * cfg.n_layer
    return Geometry(**{**dict(
        n_head=cfg.n_head,
        n_kv_heads=getattr(cfg, "n_kv_heads", cfg.n_head),
        d_head=cfg.d_head, dtype=jnp.dtype(cfg.dtype),
        scale=None if getattr(cfg, "scale_attn", True) else 1.0,
        windows=tuple(windows), alibi=getattr(cfg, "alibi", False),
        alibi_inv_norm=getattr(cfg, "alibi_inv_norm", False),
        alibi_bias=getattr(model, "_alibi_bias", None),
        kernel=getattr(model, "_paged_kernel", "auto"),
        block_c=getattr(model, "_paged_block_c", "auto"),
        kinds=(KV,) * cfg.n_layer,
        ring_blocks=getattr(model, "_paged_ring_blocks", 0)), **fields})


def _decode_kernel(geom, B, MB, BS, dtype):
    # ALiBi families keep the kernel regardless of the mode switch (the
    # dense reference lacks the falcon bf16-quantized variant)
    return _paged_attention(geom) and (geom.alibi or resolve_paged_decode(
        geom.kernel, B, MB, BS, geom.n_kv_heads,
        geom.n_head // geom.n_kv_heads, geom.d_head, dtype))


def uses_decode_kernel(model, B, MB, BS, dtype):
    """Whether ``model``'s decode step over B slots x MB table entries of
    BS-token blocks runs ``paged_decode_attention``: the answer the
    decode trace takes, and the one the engine sizes the pools by."""
    return _decode_kernel(geometry(model), B, MB, BS, dtype)


def _chunk_kernel(geom, C, MB, BS):
    """(whether a step of C queries a sequence reads through
    ``paged_chunk_attention``, the :class:`ChunkTile` its calls take): the
    answer the chunk trace takes, and the one ``Account`` counts grid
    steps by. The engine's ``paged_block_c`` (or a measured winner) pins
    the tokens a query tile; left "auto" the whole tile is read off the
    shapes (``paged_attention.chunk_tile``)."""
    # ALiBi stays dense: the chunk kernel has no per-head bias input
    # (forced off BEFORE dispatch, so no search is paid for a tile the
    # model can never use)
    if not _paged_attention(geom):
        return False, None
    G = geom.n_head // geom.n_kv_heads
    use, block_c = resolve_paged_chunk(
        False if geom.alibi else geom.kernel, geom.block_c, C, MB, BS,
        geom.n_kv_heads, G, geom.d_head, geom.dtype)
    return use, chunk_tile(C, geom.n_kv_heads, G, geom.d_head, BS, MB,
                           geom.dtype, block_c)


def _paged_windows(geom):
    """{window: the layers that read a paged table with it} (not a layer
    of recurrent state or a latent, nor one with no cache): the kernels'
    work lists are one a window."""
    return dict(Counter(
        w for w, kind in zip(geom.windows, geom.kinds)
        if kind in (KV, RING) or isinstance(kind, tuple)))


def _latent_kernel(geom, C):
    """Whether a ``LATENT`` layer's read of a step of C queries a row is
    the Pallas kernel: never a decode step's (one query a row is the
    absorbed form, which has none), else the engine's ``paged_kernel`` by
    the paged kernels' rule: "auto" is Mosaic on a TPU and the XLA form off
    it, True the kernel anywhere (the interpreter off a TPU), False the
    XLA form."""
    if C == 1:
        return False
    if geom.kernel == "auto":
        return jax.default_backend() == "tpu"
    return bool(geom.kernel)


def _dense_attention(geom, q, gk, gv, q_pos, frontier, window):
    """The dense fallback: masked attention over each slot's whole key
    range, gathered through its table.

    q: (B, C, H, hd); gk/gv: (B, MB, KVH, BS, hd), the pools' blocks a
    slot's table names, new rows included; q_pos: (B, C) absolute query
    positions; frontier: (B,) first position past each slot's written
    range. Returns (B, C, H, hd)."""
    B, _, H, hd = q.shape
    _, MB, KVH, BS, _ = gk.shape
    S = MB * BS

    def rows(g):
        # heads-major in cache: (B, MB, KVH, BS, hd) -> (B, S, H, hd)
        g = g.transpose(0, 1, 3, 2, 4).reshape(B, S, KVH, hd)
        return g if H == KVH else jnp.repeat(g, H // KVH, axis=2)

    scores = jnp.einsum("bthd,bshd->bhts", q, rows(gk),
                        preferred_element_type=jnp.float32)
    if geom.scale is None:
        scores = scores / math.sqrt(hd)
    k_pos = jnp.arange(S)
    if geom.alibi:
        scores = scores + geom.alibi_bias(k_pos)[None, :, None, :]
    q_pos = q_pos[:, :, None]
    mask = (k_pos <= q_pos) & (k_pos < frontier[:, None, None])
    if window:
        mask = mask & (q_pos - k_pos < window)
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(geom.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, rows(gv))


def _kth_largest(x, k):
    """Exact k-th largest of each row of x (..., S) float32, with no sort:
    a binary search over the 32 bits of the order-preserving integer image
    of a float, one compare and one count a bit. ``k`` >= 1 broadcasts
    against the rows; a row has to hold k values (-inf counts)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    # negative floats order backwards and below every positive one
    u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    k = jnp.broadcast_to(k, x.shape[:-1]).astype(jnp.int32)

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(x.shape[:-1], jnp.uint32))
    kth = jnp.where(kth >> 31 == 1, kth & jnp.uint32((1 << 31) - 1), ~kth)
    return jax.lax.bitcast_convert_type(kth, jnp.float32)


def _latent_read(lat_pool, idx_pool, tables, q_pos, frontier, index_fn,
                 read_fn, topk, out_shape, keys, expand=None):
    """The selected read of a latent layer, every query of the step.

    lat_pool (NB, BS, W), idx_pool (NB, BS, Wi), new rows written;
    tables (B, MB); q_pos (B, C) the queries' positions; frontier (B,) the
    first position past each row's written range. ``index_fn(idx rows (B,
    n, Wi)) -> (B, C, n)`` float32 index scores; ``read_fn(lat rows (B, n,
    W)) -> (scores (B, H, C, n) float32, pv)`` with ``pv(p (B, H, C, n))
    -> (B, C, H, dv)``. A query reads the ``min(topk, position + 1)``
    causal keys of largest index score, exactly: those that reach the
    k-th largest (ties with it included). Two passes over the table in
    blocks of ``keys`` keys, as far as the longest row's frontier: the
    index scores of every causal key, kept whole (B, C, MB x BS) float32
    because they decide a set; then scores, a running softmax and the
    value product over the selected keys. -> (B, C, H, dv) float32.

    ``expand``: ``(queries (B, C, H, d), wk_b (H, dn, R), wv_b (H, R,
    dv))``, what the model's expanded ``read_fn`` is made from, or None.
    Given, the second pass is the Pallas kernel of
    ``ops/pallas/latent_attention.py`` over the same key blocks, told the
    selection as a mask; the first pass and the threshold are the same
    code either way."""
    B, MB = tables.shape
    BS = lat_pool.shape[1]
    C = q_pos.shape[1]
    per = max(1, min(keys // BS, MB))           # table entries a pass
    KB = per * BS
    passes = -(-MB // per)
    tables = jnp.pad(tables, ((0, 0), (0, passes * per - MB)))
    n = jnp.clip((jnp.max(frontier) + KB - 1) // KB, 1, passes)
    neg = -jnp.inf

    def rows(pool, j):
        entries = jax.lax.dynamic_slice(tables, (0, j * per), (B, per))
        return pool[entries].reshape(B, KB, pool.shape[-1])

    def index_pass(j, scores):
        k_pos = j * KB + jnp.arange(KB)
        sc = index_fn(rows(idx_pool, j))
        ok = (k_pos <= q_pos[:, :, None]) \
            & (k_pos < frontier[:, None, None])
        return jax.lax.dynamic_update_slice(
            scores, jnp.where(ok, sc, neg), (0, 0, j * KB))

    scores = jax.lax.fori_loop(
        0, n, index_pass, jnp.full((B, C, passes * KB), neg, jnp.float32))
    # a query at position t < topk reads every causal key
    thr = jnp.where(q_pos < topk, neg, _kth_largest(scores, topk))
    if expand is not None:
        return latent_chunk_attention(
            expand[0], lat_pool[tables].reshape(B, passes * KB, -1),
            (scores >= thr[..., None]) & (scores > neg), *expand[1:],
            q_pos, frontier, key_tile=KB)

    def read_pass(j, carry):
        m, l, o = carry
        sc, pv = read_fn(rows(lat_pool, j))
        mine = jax.lax.dynamic_slice(scores, (0, 0, j * KB), (B, C, KB))
        sel = ((mine >= thr[..., None]) & (mine > neg))[:, None]
        sc = jnp.where(sel, sc, -1e30)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        a = jnp.exp(m - m_new)
        p = jnp.where(sel, jnp.exp(sc - m_new[..., None]), 0.0)
        return (m_new, l * a + jnp.sum(p, axis=-1),
                o * a.transpose(0, 2, 1)[..., None] + pv(p))

    H = out_shape[2]
    m, l, o = jax.lax.fori_loop(
        0, n, read_pass,
        (jnp.full((B, H, C), -1e30, jnp.float32),
         jnp.zeros((B, H, C), jnp.float32),
         jnp.zeros(out_shape, jnp.float32)))
    return o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]


class _Step:
    """One program's cache traffic: ``layer(i)`` is layer i's ``attn_fn``,
    ``state(i)`` / ``put_state(i, ...)`` its recurrent state.

    Row n of the flattened new K/V of a ``KV`` (``RING``) layer goes to
    ``dest[kind] = (blocks[n], offsets[n], row list)`` (pads and inactive
    slots aim at scratch block 0: dead rows, which the write kernel steps
    over), in place in the layer's own donated pools; then ``attend``
    reads through ``tables[kind]``. ``cache`` is the program's cache with
    every write so far in it: what the model hands back."""

    def __init__(self, geom, cache, use_kernel, attend, tables, dest,
                 take, put):
        self.geom, self.attend, self.use_kernel = geom, attend, use_kernel
        self.cache = {k: list(v) for k, v in cache.items()}
        self.tables = tables
        # the write kernel's grid: this step's live rows, one list a kind,
        # shared by every layer of it
        self.dest = {kind: (b, o, kv_write_row_list(b, o)
                            if use_kernel else None)
                     for kind, (b, o) in dest.items()}
        # STATE: ``take(leaf)`` -> the rows of it this program works on,
        # ``put(leaf, rows)`` -> the leaf with new rows in their place
        self.take, self.put = take, put
        kinds = [k[0] if isinstance(k, tuple) else k for k in geom.kinds]
        self.index = [kinds[:i].count(k) for i, k in enumerate(kinds)]

    def layer(self, i):
        kind, window = self.geom.kinds[i], self.geom.windows[i]
        if isinstance(kind, tuple):                  # (SHARED, j)
            j = self.index[kind[1]]

            def read_fn(q, k=None, v=None):
                with jax.named_scope("dstpu.attn.paged"):
                    return self.attend(q, self.cache["k"][j],
                                       self.cache["v"][j], window,
                                       self.tables[KV]), None

            return read_fn
        (kk, vk), j = _KEYS[kind], self.index[i]
        blocks, offsets, rows = self.dest[kind]

        def attn_fn(q, k, v):
            with jax.named_scope("dstpu.kv.write"):
                kc, vc = paged_kv_write(
                    (self.cache[kk][j], self.cache[vk][j]),
                    (k.reshape((-1,) + k.shape[2:]),
                     v.reshape((-1,) + v.shape[2:])),
                    blocks, offsets, rows=rows, kernel=self.use_kernel)
            self.cache[kk][j], self.cache[vk][j] = kc, vc
            with jax.named_scope("dstpu.attn.paged"):
                return self.attend(q, kc, vc, window, self.tables[kind]), \
                    (kc, vc)

        return attn_fn

    def latent(self, i):
        """Layer i's ``attn_fn`` of the ``LATENT`` kind: ``(lat (B, C, W),
        idx (B, C, Wi), index_fn, read_fn, topk, dv, expand=None) -> (B,
        C, H, dv)`` float32. Writes the step's new rows into the layer's
        two pools (pads and inactive slots aim at scratch block 0), then
        :func:`_latent_read` through the block table: through the kernel
        where the step runs it (:func:`_latent_kernel`) and the model
        handed ``expand``, and says which to whoever counts."""
        j = self.index[i]
        blocks, offsets, _ = self.dest[KV]
        tables = self.tables[KV]
        tables = tables[None] if tables.ndim == 1 else tables
        B, C = self.q_pos.shape
        keys = _LATENT_KEYS["decode" if C == 1 else "chunk"]
        kernel = _latent_kernel(self.geom, C)

        def attn_fn(lat, idx, index_fn, read_fn, topk, dv, expand=None):
            for key, new in zip(_KEYS[LATENT], (lat, idx)):
                pool = self.cache[key][j]
                self.cache[key][j] = pool.at[blocks, offsets].set(
                    new.reshape(-1, new.shape[-1]).astype(pool.dtype))
            if not kernel:
                expand = None
            note_call("latent_read", expand is not None)
            return _latent_read(
                self.cache["lat"][j], self.cache["idx"][j], tables,
                self.q_pos, self.frontier, index_fn, read_fn, topk,
                (B, C, self.geom.n_head, dv), keys, expand)

        return attn_fn

    def state(self, i):
        """Layer i's (conv, ssm) for this program's rows."""
        j = self.index[i]
        return tuple(self.take(self.cache[k][j]) for k in _KEYS[STATE])

    def put_state(self, i, *new, in_place=()):
        """Layer i's new (conv, ssm) rows back into their leaves. A key
        in ``in_place`` names a leaf that IS the new leaf already: a
        kernel took the whole of it, aliased, and wrote this program's
        live rows only."""
        j = self.index[i]
        for k, x in zip(_KEYS[STATE], new):
            leaf = self.cache[k][j]
            self.cache[k][j] = x if k in in_place \
                else self.put(leaf, x.astype(leaf.dtype))


def _ring_table(geom, slots, MB):
    """(len(slots), MB) table of a RING layer: entry j of slot s."""
    R = geom.ring_blocks
    return 1 + slots[:, None] * R + jnp.arange(MB, dtype=jnp.int32)[None] % R


def chunk_step(geom, cache, token_blocks, token_offsets, start, true_len,
               table, slot=None):
    """The step of a chunk / prefill program: q, k, v are (1, C, ., hd)
    for C tokens of one sequence at positions ``start ..``;
    ``token_blocks`` / ``token_offsets``: (C,) destinations (pads aim at
    scratch block 0); ``table``: (MB,) the sequence's block table;
    ``slot``: the batch slot the sequence holds (read only where a layer
    keeps slot state). Queries attend the prior cache plus the in-chunk
    causal prefix; recurrent state continues the slot's, from zero at
    ``start = 0``, and stops at token ``true_len - 1``."""
    C, MB = token_blocks.shape[0], table.shape[0]
    BS = block_size(cache)
    use_kernel, tile = _chunk_kernel(geom, C, MB, BS)
    # the chunk kernel's grid: the runs of live blocks a query tile, one
    # list per window size, shared by every layer that has it
    work = {w: chunk_work_list(start, true_len, C, MB, BS, w, tile)
            for w in _paged_windows(geom)} if use_kernel else {}
    tables, dest = {KV: table}, {KV: (token_blocks, token_offsets)}
    if RING in geom.kinds:
        ring = _ring_table(geom, jnp.reshape(slot, (1,)), MB)[0]
        entry = jnp.minimum((start + jnp.arange(C)) // BS, MB - 1)
        tables[RING] = ring
        dest[RING] = (jnp.where(token_blocks != 0, ring[entry], 0),
                      token_offsets)

    def attend(q, kc, vc, window, table):
        if use_kernel:
            # blocked-flash chunk kernel: each KV block streams through
            # VMEM once, located via the table; GQA-native
            return paged_chunk_attention(
                q[0], kc, vc, table, start, true_len, scale=geom.scale,
                window=window, work=work[window])[None]
        return _dense_attention(
            geom, q, kc[table][None], vc[table][None],
            (start + jnp.arange(C))[None],
            jnp.reshape(start + true_len, (1,)), window)

    def at(leaf):
        return (slot,) + (0,) * (leaf.ndim - 1)

    def take(leaf):
        row = jax.lax.dynamic_slice(leaf, at(leaf), (1,) + leaf.shape[1:])
        return jnp.where(start == 0, jnp.zeros_like(row), row)

    def put(leaf, row):
        return jax.lax.dynamic_update_slice(leaf, row, at(leaf))

    step = _Step(geom, cache, use_kernel, attend, tables, dest, take, put)
    # which of the C tokens are real: a recurrent layer steps over the pads
    step.valid = (jnp.arange(C) < true_len)[None]
    step.n_valid = jnp.reshape(true_len, (1,))
    step.q_pos = (start + jnp.arange(C))[None]
    step.frontier = jnp.reshape(start + true_len, (1,))
    return step


def batch_step(geom, cache, lengths, block_tables, C):
    """The step of a decode (C = 1) or verify (C > 1) program: q, k, v
    are (B, C, ., hd), slot b's tokens at positions ``lengths[b] ..``;
    ``block_tables``: (B, MB), inactive slots all-scratch. Row b IS slot
    b: slot state is read and written in place, and only where the slot
    is live."""
    B, MB = block_tables.shape
    BS = block_size(cache)
    active = block_tables[:, 0] != 0
    linpos = lengths[:, None] + jnp.arange(C)[None, :]           # (B, C)
    entry = jnp.minimum(linpos // BS, MB - 1)
    dst_off = (linpos % BS).reshape(-1)
    tables = {KV: block_tables}
    if RING in geom.kinds:
        tables[RING] = jnp.where(
            active[:, None],
            _ring_table(geom, jnp.arange(B, dtype=jnp.int32), MB), 0)
    dest = {kind: (jnp.take_along_axis(t, entry, axis=1).reshape(-1),
                   dst_off) for kind, t in tables.items()}

    if C == 1:
        use_kernel = _decode_kernel(geom, B, MB, BS, geom.dtype)
        # the decode kernel's grid: this step's runs of live blocks a
        # slot, one list per window size, shared by every layer that has it
        per_step = decode_entries_per_step(
            geom.n_kv_heads, BS, geom.d_head, cache["k"][0].dtype, MB) \
            if use_kernel else 1
        work = {w: decode_work_list(lengths, MB, BS, w, active=active,
                                    per_step=per_step)
                for w in set(geom.windows)} if use_kernel else {}
        alibi = dict(
            alibi_slopes=alibi_slopes(geom.n_head),
            alibi_scale=(1.0 / math.sqrt(geom.d_head)
                         if geom.alibi_inv_norm else 1.0),
            alibi_bf16=geom.alibi_inv_norm) if geom.alibi else {}

        def attend(q, kc, vc, window, tables):
            if use_kernel:
                return paged_decode_attention(
                    q[:, 0], kc, vc, tables, lengths,
                    work=work[window], scale=geom.scale, window=window,
                    **alibi)[:, None]
            return paged_decode_attention_reference(
                q[:, 0], kc, vc, tables, lengths, scale=geom.scale,
                window=window)[:, None]
    else:
        use_kernel, tile = _chunk_kernel(geom, C, MB, BS)
        # the batched split-fuse ride: each slot's span is a chunk with
        # start = lengths[b], true_len = C, and a work list of its own
        work = {(w, b): chunk_work_list(lengths[b], C, C, MB, BS, w, tile)
                for w in _paged_windows(geom) for b in range(B)} \
            if use_kernel else {}

        def attend(q, kc, vc, window, tables):
            if use_kernel:
                return jnp.stack([paged_chunk_attention(
                    q[b], kc, vc, tables[b], lengths[b],
                    jnp.int32(C), scale=geom.scale, window=window,
                    work=work[window, b]) for b in range(B)])
            return _dense_attention(
                geom, q, kc[tables], vc[tables], linpos, lengths + C,
                window)

    def put(leaf, rows):
        live = active.reshape((B,) + (1,) * (leaf.ndim - 1))
        return jnp.where(live, rows, leaf)

    step = _Step(geom, cache, use_kernel, attend, tables, dest,
                 lambda leaf: leaf, put)
    step.active = active
    step.valid = jnp.ones((B, C), bool)
    step.n_valid = jnp.full((B,), C, jnp.int32)
    step.q_pos, step.frontier = linpos, lengths + C
    return step


# ----------------------------------------------------------- the account
# the kinds whose pools lie under the block tables, paid for by the
# allocator's blocks, and the trailing axes of such a pool that are one block
_BLOCK_AXES = {KV: 3, LATENT: 2}
_BY_SLOT = ("the model keeps recurrent / window state by batch slot "
            "(slot_state), which ")
_BY_SELECTION = ("the model's blocks hold a latent cache read through a "
                 "per-query selection (models/paged.py, LATENT), which ")
# what the serving features that handle blocks as K and V pools cannot do
# for a cache of another kind, kind x feature -> the reason a serving engine
# raises with: "slot" is a model with a RING or STATE layer, "latent" one
# with a LATENT layer, "window" one whose KV layers have windows of their own
# (``attn_layer_windows``). A pair that is not here is served
_REFUSALS = {
    ("slot", "prefix_cache"):
        "prefix_cache=True: " + _BY_SLOT + "a cached block of KV does not "
        "bring back — a prefix hit would resume from a state nobody kept",
    ("slot", "spec_draft"):
        "spec_draft=True / a draft model: " + _BY_SLOT + "rollback_spec "
        "cannot take back once the rejected tokens have moved it",
    ("slot", "kv_host_offload"):
        "kv_host_offload: " + _BY_SLOT + "lives outside the block pool the "
        "offload tier pages",
    ("slot", "kv_transfer"):
        "disaggregated kv_transfer: " + _BY_SLOT + "the block payloads of a "
        "KV handoff do not carry",
    ("latent", "prefix_cache"):
        "prefix_cache=True: " + _BY_SELECTION + "the prefix cache's "
        "copy-on-write and block reuse, written for K and V pools, have not "
        "learnt",
    ("latent", "spec_draft"):
        "spec_draft=True / a draft model: " + _BY_SELECTION + "a draft "
        "model's verify pass and rollback_spec have no program for",
    ("latent", "kv_host_offload"):
        "kv_host_offload: " + _BY_SELECTION + "the offload tier, which pages "
        "K and V pools, does not page",
    ("latent", "kv_transfer"):
        "disaggregated kv_transfer: the model's blocks hold a latent cache "
        "(models/paged.py, LATENT), which the K / V payloads of a KV handoff "
        "do not carry",
    ("window", "prefix_cache"):
        "prefix_cache=True on a sliding-window model (attn_layer_windows "
        "set): a cached block's KV is position-valid only inside each "
        "layer's window, so reusing it under a shifted suffix serves wrong "
        "attention — disable prefix_cache for this model",
}


class Account:
    """What a serving engine asks of ``model``'s cache without knowing its
    kinds: what a live sequence holds of it (:meth:`size`), what a program
    call counts over it (:meth:`dispatch`, :meth:`prefill`: host arithmetic
    for the engine's spans, no device read) and which serving features it
    cannot have (:meth:`refusal`). Built once an engine, from the model's
    :class:`Geometry` and the engine's sizes: ``slots`` batch slots,
    ``table_len`` entries a block table, ``block_size`` tokens a block,
    the cache's ``dtype``."""

    def __init__(self, model, slots, table_len, block_size, dtype):
        geom = geometry(model)
        self._model = model
        self.slots, self.table_len, self.block_size = \
            slots, table_len, block_size
        # layers of each kind (a ``(SHARED, j)`` layer under ``SHARED``)
        self.layers = Counter(k[0] if isinstance(k, tuple) else k
                              for k in geom.kinds)
        # the decode kernel's calls: {window: the layers that call it with
        # that window} (layers with a paged table: not one of recurrent
        # state or a latent, nor one with no cache), and the table entries
        # of a slot one grid step of it takes
        self._windows = _paged_windows(geom)
        self._entries_per_step = decode_entries_per_step(
            geom.n_kv_heads, block_size, geom.d_head, dtype, table_len)
        # the chunk kernel's tile, by the rows of a chunk program: asked
        # at the first dispatch that has a chunk, when the engine has told
        # the model its ``paged_block_c``
        self._kv_heads, self._chunk_tiles = geom.n_kv_heads, {}
        # keys a query of a LATENT layer attends at most
        self._topk = model.config.index_topk if self.layers[LATENT] else 0
        self._kinds = [name for name, there in (
            ("latent", self.layers[LATENT]),
            ("slot", self.layers[RING] + self.layers[STATE]),
            ("window", any(getattr(model.config, "attn_layer_windows",
                                   None) or ()))) if there]
        self.block_bytes = self.slot_bytes = 0

    def size(self, cache):
        """Told the allocated ``cache``: ``block_bytes`` = bytes a block of
        the pools under the block tables, every layer's, and ``slot_bytes``
        = bytes a slot of whatever else the model keeps (rings, recurrent
        state)."""
        self.block_bytes = sum(
            math.prod(p.shape[-axes:]) * p.dtype.itemsize
            for kind, axes in _BLOCK_AXES.items() for key in _KEYS[kind]
            for p in cache.get(key, ()))
        by_block = {key for kind in _BLOCK_AXES for key in _KEYS[kind]}
        self.slot_bytes = sum(
            p.nbytes for key, sub in cache.items() if key not in by_block
            for p in jax.tree.leaves(sub)) // self.slots

    def refusal(self, feature):
        """Why the model's cache cannot have ``feature`` (``prefix_cache``
        | ``spec_draft`` | ``kv_host_offload`` | ``kv_transfer``), or None
        where it can."""
        for kind in self._kinds:
            if (kind, feature) in _REFUSALS:
                return _REFUSALS[kind, feature]
        return None

    def _selected_read(self, start, tokens):
        """(index_keys, attended_keys) of ``tokens`` consecutive real
        query tokens from position ``start`` (an array: one run a live
        slot) in every latent layer: the causal keys the indexer scores,
        position + 1 a query, and the keys attended after the selection,
        ``min(position + 1, index_topk)``. (0, 0) on a model with no such
        layer."""
        layers = self.layers[LATENT]
        if not layers or not tokens:
            return 0, 0
        ctx = np.asarray(start, np.int64)[..., None] + 1 + np.arange(tokens)
        return (int(ctx.sum()) * layers,
                int(np.minimum(ctx, self._topk).sum()) * layers)

    def _chunk_grid(self, start, tokens, rows):
        """(grid steps, query tiles x table entries) of one paged chunk
        kernel call of a chunk of ``tokens`` real tokens in ``rows`` from
        position ``start``, the layers' mean where their windows differ:
        what the call's work list gives it, and the rectangle a call
        walked before it had one. (0, 0) with no chunk or no such layer."""
        if not rows or not self._windows:
            return 0, 0
        MB, BS = self.table_len, self.block_size
        if rows not in self._chunk_tiles:
            self._chunk_tiles[rows] = _chunk_kernel(
                geometry(self._model), rows, MB, BS)[1]
        tile = self._chunk_tiles[rows]
        taken = sum(layers * chunk_grid_steps(
            start, tokens, rows, self._kv_heads, MB, BS, w, tile)
            for w, layers in self._windows.items())
        return (round(taken / sum(self._windows.values())),
                -(-rows // tile.block_c) * MB)

    def dispatch(self, lengths, tables, active, steps, chunk_start=0,
                 chunk_tokens=0, chunk_rows=0):
        """The stats of a ``dstpu.engine.dispatch`` span that are the
        cache's (``monitor/tag_schema.py``), of one program call: ``steps``
        decode steps over the batch ``lengths`` / ``tables`` with the live
        slots' mask ``active`` (where the call runs the decode programs
        over the batch; else ``lengths`` is None and ``active`` a count),
        and a chunk's ``chunk_tokens`` real tokens of ``chunk_rows`` from
        position ``chunk_start``."""
        grid_steps = kernel_steps = table_entries = 0
        chunk_grid, chunk_table = self._chunk_grid(
            chunk_start, chunk_tokens, chunk_rows)
        write_rows, write_rows_offered = chunk_tokens, chunk_rows
        index_keys, attended_keys = self._selected_read(
            chunk_start, chunk_tokens)
        if lengths is not None and self.layers[LATENT]:
            # each live slot's token a decode step, from its length on
            decode = self._selected_read(
                np.asarray(lengths)[np.asarray(active, bool)], steps)
            index_keys += decode[0]
            attended_keys += decode[1]
        if lengths is not None and self._windows:
            MB, BS = self.table_len, self.block_size

            def per_call(per_step):
                # a kernel call's, the layers' mean where their windows
                # differ, over the dispatch's decode steps
                return round(sum(layers * decode_grid_steps(
                    lengths, active, MB, BS, w, steps, per_step)
                    for w, layers in self._windows.items())
                    / sum(self._windows.values()))

            grid_steps = per_call(1)
            kernel_steps = grid_steps if self._entries_per_step == 1 \
                else per_call(self._entries_per_step)
            table_entries = steps * self.slots * MB
            write_rows += kv_write_live_rows(lengths, tables, BS, steps)
            write_rows_offered += steps * self.slots
        state = self.layers[STATE]
        return dict(
            grid_steps=grid_steps, table_entries=table_entries,
            kernel_steps=kernel_steps, chunk_grid_steps=chunk_grid,
            chunk_table_steps=chunk_table, write_rows=write_rows,
            write_rows_offered=write_rows_offered,
            state_updates=int(np.sum(active)) * steps * state if state
            else 0,
            rule_rows=chunk_rows * state, index_keys=index_keys,
            attended_keys=attended_keys)

    def prefill(self, tokens, padded):
        """The same of a ``dstpu.engine.prefill`` span: a bucketed prefill
        of ``tokens`` real tokens in ``padded`` rows."""
        index_keys, attended_keys = self._selected_read(0, tokens)
        return dict(rule_rows=padded * self.layers[STATE],
                    index_keys=index_keys, attended_keys=attended_keys)
