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
"""

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.pallas.paged_attention import (
    alibi_slopes, decode_work_list, kv_write_row_list,
    paged_chunk_attention, paged_decode_attention,
    paged_decode_attention_reference, paged_kv_write, resolve_paged_chunk,
    resolve_paged_decode)


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


def geometry(model):
    """``model``'s attention geometry, read off its config and the two
    trace-time settings the engine installs on it (``_paged_kernel``,
    ``_paged_block_c``). A field one family lacks reads as "not there":
    GPT-2 has no KV-head count, ALiBi or single window; Llama no
    ``scale_attn`` or per-layer windows."""
    cfg = model.config
    windows = getattr(cfg, "attn_layer_windows", None) \
        or (getattr(cfg, "sliding_window", 0),) * cfg.n_layer
    return Geometry(
        n_head=cfg.n_head,
        n_kv_heads=getattr(cfg, "n_kv_heads", cfg.n_head),
        d_head=cfg.d_head, dtype=jnp.dtype(cfg.dtype),
        scale=None if getattr(cfg, "scale_attn", True) else 1.0,
        windows=tuple(windows), alibi=getattr(cfg, "alibi", False),
        alibi_inv_norm=getattr(cfg, "alibi_inv_norm", False),
        alibi_bias=getattr(model, "_alibi_bias", None),
        kernel=getattr(model, "_paged_kernel", "auto"),
        block_c=getattr(model, "_paged_block_c", "auto"))


def _decode_kernel(geom, B, MB, BS, dtype):
    # ALiBi families keep the kernel regardless of the mode switch (the
    # dense reference lacks the falcon bf16-quantized variant)
    return geom.alibi or resolve_paged_decode(
        geom.kernel, B, MB, BS, geom.n_kv_heads,
        geom.n_head // geom.n_kv_heads, geom.d_head, dtype)


def uses_decode_kernel(model, B, MB, BS, dtype):
    """Whether ``model``'s decode step over B slots x MB table entries of
    BS-token blocks runs ``paged_decode_attention``: the answer the
    decode trace takes, and the one the engine sizes the pools by."""
    return _decode_kernel(geometry(model), B, MB, BS, dtype)


def _chunk_kernel(geom, C, MB, BS):
    # ALiBi stays dense: the chunk kernel has no per-head bias input
    # (forced off BEFORE dispatch, so no search is paid for a tile the
    # model can never use)
    return resolve_paged_chunk(
        False if geom.alibi else geom.kernel, geom.block_c, C, MB, BS,
        geom.n_kv_heads, geom.n_head // geom.n_kv_heads, geom.d_head,
        geom.dtype)


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


class _Step:
    """One program's attention: ``layer(i)`` is layer i's ``attn_fn``.
    Row n of the flattened new K/V goes to ``blocks[n]``, ``offsets[n]``
    (pads and inactive slots aim at scratch block 0: dead rows, which the
    write kernel steps over), in place in the layer's own donated pools;
    then ``attend`` reads through the table."""

    def __init__(self, geom, cache, blocks, offsets, use_kernel, attend):
        self.windows, self.cache, self.attend = geom.windows, cache, attend
        self.blocks, self.offsets = blocks, offsets
        self.use_kernel = use_kernel
        # the write kernel's grid: this step's live rows, one list shared
        # by every layer
        self.rows = kv_write_row_list(blocks, offsets) if use_kernel \
            else None

    def layer(self, i):
        pools = (self.cache["k"][i], self.cache["v"][i])

        def attn_fn(q, k, v):
            kc, vc = paged_kv_write(
                pools, (k.reshape((-1,) + k.shape[2:]),
                        v.reshape((-1,) + v.shape[2:])),
                self.blocks, self.offsets, rows=self.rows,
                kernel=self.use_kernel)
            return self.attend(q, kc, vc, self.windows[i]), (kc, vc)

        return attn_fn


def chunk_step(geom, cache, token_blocks, token_offsets, start, true_len,
               table):
    """The step of a chunk / prefill program: q, k, v are (1, C, ., hd)
    for C tokens of one sequence at positions ``start ..``;
    ``token_blocks`` / ``token_offsets``: (C,) destinations (pads aim at
    scratch block 0); ``table``: (MB,) the sequence's block table.
    Queries attend the prior cache plus the in-chunk causal prefix."""
    C, MB = token_blocks.shape[0], table.shape[0]
    use_kernel, block_c = _chunk_kernel(geom, C, MB,
                                        cache["k"][0].shape[2])

    def attend(q, kc, vc, window):
        if use_kernel:
            # blocked-flash chunk kernel: each KV block streams through
            # VMEM once, located via the table; GQA-native
            return paged_chunk_attention(
                q[0], kc, vc, table, start, true_len, scale=geom.scale,
                window=window, block_c=block_c)[None]
        return _dense_attention(
            geom, q, kc[table][None], vc[table][None],
            (start + jnp.arange(C))[None],
            jnp.reshape(start + true_len, (1,)), window)

    return _Step(geom, cache, token_blocks, token_offsets, use_kernel,
                 attend)


def batch_step(geom, cache, lengths, block_tables, C):
    """The step of a decode (C = 1) or verify (C > 1) program: q, k, v
    are (B, C, ., hd), slot b's tokens at positions ``lengths[b] ..``;
    ``block_tables``: (B, MB), inactive slots all-scratch."""
    B, MB = block_tables.shape
    BS = cache["k"][0].shape[2]
    linpos = lengths[:, None] + jnp.arange(C)[None, :]           # (B, C)
    dst_block = jnp.take_along_axis(
        block_tables, jnp.minimum(linpos // BS, MB - 1), axis=1).reshape(-1)
    dst_off = (linpos % BS).reshape(-1)

    if C == 1:
        use_kernel = _decode_kernel(geom, B, MB, BS, geom.dtype)
        # the decode kernel's grid: this step's live (slot, block) pairs,
        # one list per window size, shared by every layer that has it
        work = {w: decode_work_list(lengths, MB, BS, w,
                                    active=block_tables[:, 0] != 0)
                for w in set(geom.windows)} if use_kernel else {}
        alibi = dict(
            alibi_slopes=alibi_slopes(geom.n_head),
            alibi_scale=(1.0 / math.sqrt(geom.d_head)
                         if geom.alibi_inv_norm else 1.0),
            alibi_bf16=geom.alibi_inv_norm) if geom.alibi else {}

        def attend(q, kc, vc, window):
            if use_kernel:
                return paged_decode_attention(
                    q[:, 0], kc, vc, block_tables, lengths,
                    work=work[window], scale=geom.scale, window=window,
                    **alibi)[:, None]
            return paged_decode_attention_reference(
                q[:, 0], kc, vc, block_tables, lengths, scale=geom.scale,
                window=window)[:, None]
    else:
        use_kernel, block_c = _chunk_kernel(geom, C, MB, BS)

        def attend(q, kc, vc, window):
            if use_kernel:
                # the batched split-fuse ride: each slot's span is a
                # chunk with start = lengths[b], true_len = C
                return jnp.stack([paged_chunk_attention(
                    q[b], kc, vc, block_tables[b], lengths[b],
                    jnp.int32(C), scale=geom.scale, window=window,
                    block_c=block_c) for b in range(B)])
            return _dense_attention(
                geom, q, kc[block_tables], vc[block_tables], linpos,
                lengths + C, window)

    return _Step(geom, cache, dst_block, dst_off, use_kernel, attend)
