"""Paged-serving fast path (tier-1): the chunked-prefill Pallas kernel
vs the dense-gather reference (interpret mode), the decode kernel's
work-list grid vs a plain numpy reference, the aliased new-token write
vs the scatter it replaces, the compiled chunk program's
no-dense-gather guarantee, engine greedy identity with the kernels on
vs off, warm/cold winner-cache dispatch HLO identity for the serving
autotune ops, and mixtral's ragged-EP serving routing."""

import functools
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.autotuning import KernelCache, kernel_dispatch
from deepspeed_tpu.models import GPT2, GPT2Config
from deepspeed_tpu.ops.pallas._common import (paged_chunk_bucket,
                                              paged_decode_bucket)
from deepspeed_tpu.ops.pallas import paged_attention
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_chunk_attention, paged_chunk_attention_reference, paged_kv_write,
    pool_block_dims)
from deepspeed_tpu.utils import groups


@pytest.fixture(autouse=True)
def _pristine_dispatch(tmp_path, monkeypatch):
    """Private winner cache + reset process-global dispatch state."""
    monkeypatch.setenv("DSTPU_AUTOTUNE_CACHE",
                       str(tmp_path / "kernel_autotune.json"))
    monkeypatch.delenv("DSTPU_AUTOTUNE", raising=False)
    kernel_dispatch.reset()
    yield
    kernel_dispatch.reset()


def _chunk_case(C, H, KVH, d, NB, BS, MB, start, true_len, window,
                block_c, dtype=jnp.float32, seed=0, tile=None):
    """``tile``: (tokens, KV heads, table entries) a grid step, where the
    case says all three; else the shape rule's under ``block_c``."""
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (C, H, d), dtype)
    kc = jax.random.normal(ks[1], (NB, KVH, BS, d), dtype)
    vc = jax.random.normal(ks[2], (NB, KVH, BS, d), dtype)
    tbl = jax.random.randint(ks[3], (MB,), 0, NB, jnp.int32)
    work = tile and paged_attention.chunk_work_list(
        start, true_len, C, MB, BS, window, paged_attention.ChunkTile(*tile))
    out = paged_chunk_attention(q, kc, vc, tbl, jnp.int32(start),
                                jnp.int32(true_len), window=window,
                                block_c=block_c, work=work)
    ref = paged_chunk_attention_reference(
        q, kc, vc, tbl, jnp.int32(start), jnp.int32(true_len),
        window=window)
    # pad rows too: nobody reads them, and they are finite
    assert np.isfinite(np.asarray(out, np.float32)).all()
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[:true_len],
        np.asarray(ref, np.float32)[:true_len], **tol)


class TestChunkKernelParity:
    """paged_chunk_attention (interpret mode) vs the dense-gather
    reference — the ISSUE-named cases."""

    def test_chunk_mid_sequence(self):
        # chunk starts mid-sequence, not block-aligned, fully real
        _chunk_case(16, 4, 4, 32, 12, 16, 4, start=30, true_len=16,
                    window=0, block_c=8)

    def test_chunk_crossing_block_boundary(self):
        # start + true_len straddles a BS boundary; chunk partly padded
        _chunk_case(16, 4, 4, 32, 12, 16, 4, start=26, true_len=9,
                    window=0, block_c=16)

    def test_sliding_window_layer(self):
        # mistral-style trailing window smaller than the history
        _chunk_case(16, 4, 2, 32, 12, 16, 4, start=33, true_len=16,
                    window=20, block_c=8)

    def test_gqa_heads(self):
        # G = 4 query heads per kv head, bf16 (the serving dtype)
        _chunk_case(16, 8, 2, 64, 12, 16, 4, start=17, true_len=16,
                    window=0, block_c=8, dtype=jnp.bfloat16)

    def test_block_c_padding_and_prefill_start(self):
        # block_c not dividing C (pad rows), and the prefill-shaped
        # start=0 call over the chunk's own blocks
        _chunk_case(20, 8, 2, 32, 12, 16, 4, start=0, true_len=20,
                    window=0, block_c=8)
        _chunk_case(24, 4, 2, 32, 12, 16, 4, start=0, true_len=17,
                    window=0, block_c=128)


    # ISSUE 58: the grid is a work list of runs of live entries, a step is
    # tokens x KV heads x entries. C, H, KVH, d, NB, BS, MB, then the chunk
    @pytest.mark.parametrize("case", [
        # a long table behind a short context: 29 of 32 entries are tail
        dict(shape=(32, 8, 1, 128, 40, 16, 32), start=16, true_len=32,
             rule=(32, 1, 8)),
        dict(shape=(32, 8, 1, 128, 40, 16, 32), start=16, true_len=32,
             tile=(8, 1, 3)),
        # the context ends on a query tile's edge and on a block's
        dict(shape=(32, 4, 4, 64, 40, 16, 32), start=96, true_len=32,
             tile=(16, 2, 1)),
        dict(shape=(32, 4, 4, 64, 40, 16, 32), start=96, true_len=16,
             tile=(16, 4, 1)),
        # true_len < C: tiles 2 and 3 of four are pads alone
        dict(shape=(32, 16, 2, 128, 40, 16, 32), start=100, true_len=9,
             tile=(8, 1, 4)),
        dict(shape=(32, 16, 2, 128, 40, 16, 32), start=100, true_len=9,
             window=24, tile=(8, 2, 2)),
        # a window cuts a tile's first entries: 7 entries behind, 2 attended
        dict(shape=(16, 4, 2, 128, 40, 16, 16), start=120, true_len=16,
             window=20, tile=(8, 2, 2)),
        dict(shape=(16, 4, 2, 32, 40, 16, 16), start=120, true_len=13,
             window=33, block_c=8),
        # G = 8 (solar-open2's fold), bf16, at 1, 2, 4 and 5 entries a step
        *[dict(shape=(16, 16, 2, 128, 30, 16, 12), start=70, true_len=16,
               dtype=jnp.bfloat16, tile=(8, heads, n))
          for heads, n in ((2, 1), (1, 2), (2, 4), (1, 5))],
        # rows under the lanes, which the pipeline brings like any other
        dict(shape=(32, 8, 8, 64, 30, 16, 12), start=40, true_len=30,
             rule=(32, 8, 8)),
        dict(shape=(32, 8, 8, 64, 30, 16, 12), start=40, true_len=30,
             block_c=16),
        # an explicit block_c that does not divide C, under GQA
        dict(shape=(20, 8, 2, 128, 30, 16, 12), start=50, true_len=20,
             block_c=8, dtype=jnp.bfloat16),
    ], ids=lambda c: "-".join(f"{k}={v}" for k, v in c.items()
                              if k not in ("shape", "dtype")))
    def test_work_list_grid(self, case):
        case = dict(case)
        shape = case.pop("shape")
        if "rule" in case:                   # what the shapes alone give
            assert paged_attention.chunk_tile(
                shape[0], shape[2], shape[1] // shape[2], shape[3],
                shape[5], shape[6], jnp.float32) == case.pop("rule")
        _chunk_case(*shape, window=case.pop("window", 0),
                    block_c=case.pop("block_c", 0), **case)


def _chunk_items_by_hand(start, true_len, C, MB, BS, window, BC, N):
    """The chunk kernel's work list, position by position: for each query
    tile the table entries that hold a real key some real query of it
    attends, in runs of N from the first."""
    items = []
    for t in range(-(-C // BC)):
        entries = sorted({k // BS
                          for q in range(start + t * BC,
                                         min(start + (t + 1) * BC,
                                             start + true_len))
                          for k in range(max(0, q - window + 1)
                                         if window else 0, q + 1)})
        # a window's first entry is the tile's FIRST query's, and a run
        # has no gaps; a tile of pads alone keeps one item
        runs = range(entries[0], entries[-1] + 1, N) if entries else \
            [min(max(0, start + t * BC - window + 1) // BS, MB - 1)
             if window else 0]
        items += [(t, e) for e in runs]
    return items


class TestChunkWorkList:
    @pytest.mark.parametrize("N", [1, 2, 5])
    @pytest.mark.parametrize("BC", [8, 16, 24])
    @pytest.mark.parametrize("window", [0, 20, 47])
    @pytest.mark.parametrize("start, true_len", [
        (0, 48), (0, 5), (37, 48), (64, 48), (80, 17), (150, 48), (96, 1)])
    def test_items_are_the_live_runs(self, start, true_len, window, BC, N):
        """:func:`chunk_work_list` on the device and
        :func:`chunk_grid_steps` on the host against the enumeration."""
        C, MB, BS = 48, 16, 16
        tile = paged_attention.ChunkTile(BC, 2, N)
        work = paged_attention.chunk_work_list(
            jnp.int32(start), jnp.int32(true_len), C, MB, BS, window, tile)
        want = _chunk_items_by_hand(start, true_len, C, MB, BS, window, BC,
                                    N)
        n = int(work.n)
        assert list(zip(np.asarray(work.tile_of)[:n].tolist(),
                        np.asarray(work.entry_of)[:n].tolist())) == want
        # the sentinel the kernel's store looks for, inside the arrays
        assert n < work.tile_of.shape[0]
        assert (np.asarray(work.tile_of)[n:] == -(-C // BC)).all()
        assert paged_attention.chunk_grid_steps(
            start, true_len, C, 6, MB, BS, window, tile) == 3 * len(want)
        assert len(want) <= -(-C // BC) * -(-MB // N)


_CFG = GPT2Config(n_layer=2, n_head=4, d_model=64, max_seq_len=128,
                  vocab_size=256, remat=False, dtype="float32")


def _abstract_params(model):
    ab = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), ab)


def _lower_chunk(model, MB=4, BS=16, NB=9, C=16):
    cfg = model.config
    params = _abstract_params(model)
    cache = {
        "k": [jax.ShapeDtypeStruct((NB, cfg.n_head, BS, cfg.d_head),
                                   jnp.float32)] * cfg.n_layer,
        "v": [jax.ShapeDtypeStruct((NB, cfg.n_head, BS, cfg.d_head),
                                   jnp.float32)] * cfg.n_layer,
    }
    i32 = jnp.int32
    return jax.jit(model.apply_paged_chunk).lower(
        params, jax.ShapeDtypeStruct((1, C), i32), cache,
        jax.ShapeDtypeStruct((C,), i32), jax.ShapeDtypeStruct((C,), i32),
        jax.ShapeDtypeStruct((), i32), jax.ShapeDtypeStruct((), i32),
        jax.ShapeDtypeStruct((MB,), i32)).as_text()


def _lower_decode(model, B=2, MB=4, BS=16, NB=9):
    cfg = model.config
    params = _abstract_params(model)
    cache = {
        "k": [jax.ShapeDtypeStruct((NB, cfg.n_head, BS, cfg.d_head),
                                   jnp.float32)] * cfg.n_layer,
        "v": [jax.ShapeDtypeStruct((NB, cfg.n_head, BS, cfg.d_head),
                                   jnp.float32)] * cfg.n_layer,
    }
    i32 = jnp.int32
    return jax.jit(model.apply_paged_decode).lower(
        params, jax.ShapeDtypeStruct((B,), i32),
        jax.ShapeDtypeStruct((B,), i32), cache,
        jax.ShapeDtypeStruct((B, MB), i32)).as_text()


def _decode_rows(rs, NB, BS):
    """32 slots, one row each: live slots own distinct blocks, the
    inactive ones (several, scattered) all aim at scratch block 0."""
    N = 32
    blocks = rs.permutation(np.arange(1, NB))[:N].astype(np.int32)
    offsets = rs.randint(0, BS, (N,)).astype(np.int32)
    offsets[:4] = [0, 15, 16, BS - 1]
    for dead in (4, 5, 11, 19, 31):
        blocks[dead], offsets[dead] = 0, 0
    return blocks, offsets


def _chunk_rows(rs, NB, BS):
    """C = 256 rows of one sequence from a start that is not
    block-aligned, the tail past true_len padded onto scratch (0, 0) —
    what ``RaggedBatchWrapper`` hands the chunk program."""
    C, start, true_len = 256, 2 * BS + 37, 201
    table = rs.permutation(np.arange(1, NB))[:-(-(start + C) // BS)]
    pos = start + np.arange(C)
    real = np.arange(C) < true_len
    return (np.where(real, table[pos // BS], 0).astype(np.int32),
            np.where(real, pos % BS, 0).astype(np.int32))


class TestKVWriteParity:
    """paged_kv_write's aliased Pallas write (interpret mode) against
    the ``.at[blocks, :, offsets].set`` it replaces: bit-equal outside
    scratch block 0, whose rows nothing attends."""

    @pytest.mark.parametrize("rows", [_decode_rows, _chunk_rows])
    @pytest.mark.parametrize("KVH,d", [(16, 64), (32, 64), (8, 128)])
    def test_write_matches_scatter(self, rows, KVH, d):
        NB, BS = 40, 64
        blocks, offsets = rows(np.random.RandomState(KVH + d), NB, BS)
        ks = jax.random.split(jax.random.key(d), 4)
        pools = tuple(jax.random.normal(k, (NB, KVH, BS, d), jnp.bfloat16)
                      for k in ks[:2])
        new = tuple(jax.random.normal(k, (len(blocks), KVH, d),
                                      jnp.float32) for k in ks[2:])
        want = paged_kv_write(pools, new, blocks, offsets, kernel=False)
        got = jax.jit(functools.partial(paged_kv_write, interpret=True))(
            pools, new, jnp.asarray(blocks), jnp.asarray(offsets))
        for g, w, p in zip(got, want, pools):
            assert g.dtype == w.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(g[1:]).view(np.uint16),
                np.asarray(w[1:]).view(np.uint16))
            # and it wrote something: the reference differs from the pool
            assert not np.array_equal(np.asarray(w[1:]).view(np.uint16),
                                      np.asarray(p[1:]).view(np.uint16))


def _interleaved_rows(rs, NB, BS):
    """Decode: live and dead slots alternate, so every live row's
    predecessor in the old grid was a dead one."""
    N = 32
    blocks = rs.permutation(np.arange(1, NB))[:N].astype(np.int32)
    offsets = rs.randint(0, BS, (N,)).astype(np.int32)
    blocks[::2], offsets[::2] = 0, 0
    return blocks, offsets


def _all_dead_rows(rs, NB, BS):
    """A decode step (or a warm-up) with no live slot: ``n_live == 0``."""
    return np.zeros((32,), np.int32), np.zeros((32,), np.int32)


def _prefill_rows(rs, NB, BS):
    """A prompt of 75 tokens in a bucket of 128: the padded tail is
    dead, the live rows a prefix."""
    T, n = 128, 75
    table = rs.permutation(np.arange(1, NB))[:-(-T // BS)]
    real = np.arange(T) < n
    return (np.where(real, table[np.arange(T) // BS], 0).astype(np.int32),
            np.where(real, np.arange(T) % BS, 0).astype(np.int32))


def _neighbour_rows(rs, NB, BS):
    """Consecutive live rows sharing a tile (offsets 2, 3), in two tiles
    of one block (offsets R - 1, R, for R = 16 or BS), and a lone row,
    with dead rows between the groups and at both ends."""
    half = min(16, BS) - 1
    blocks = np.asarray([0, 7, 7, 0, 0, 3, 3, 0, 5, 0], np.int32)
    offsets = np.asarray([0, 2, 3, 0, 0, half, (half + 1) % BS, 0,
                          BS - 1, 0], np.int32)
    blocks[6] = 3 if half + 1 < BS else 4
    return blocks, offsets


def _verify_rows(rs, NB, BS):
    """A verify span, C = 4 positions a slot for 8 slots, slots 1, 2, 5
    and 7 inactive (all-scratch tables); slot 3's span crosses a block
    boundary."""
    B, C, MB = 8, 4, 3
    tables = rs.permutation(np.arange(1, NB))[:B * MB].reshape(B, MB)
    tables[[1, 2, 5, 7]] = 0
    lengths = rs.randint(0, (MB - 1) * BS, (B,))
    lengths[3] = BS - 2
    pos = lengths[:, None] + np.arange(C)
    blocks = np.take_along_axis(tables, pos // BS, axis=1)
    return (blocks.reshape(-1).astype(np.int32),
            np.where(blocks != 0, pos % BS, 0).reshape(-1).astype(np.int32))


class TestKVWriteLiveRows:
    """The write kernel's grid is the live rows (``blocks != 0``), in
    interpret mode: the scatter's values wherever a live row lands, and
    every other bit of both pools — scratch block 0 too, which the
    kernel no longer writes — as it came in."""

    CASES = {"interleaved": _interleaved_rows, "all_dead": _all_dead_rows,
             "prefill_tail": _prefill_rows, "neighbours": _neighbour_rows,
             "verify_span": _verify_rows, "decode": _decode_rows,
             "chunk": _chunk_rows}

    @pytest.mark.parametrize("BS,dtype", [(64, "bfloat16"), (8, "bfloat16"),
                                          (16, "float32")],
                             ids=["bs64-bf16", "bs8-bf16-wholeblock",
                                  "bs16-f32"])
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_live_rows_only(self, case, BS, dtype):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            kv_write_row_list)
        NB, KVH, d = 40, 4, 64
        blocks, offsets = self.CASES[case](np.random.RandomState(BS), NB,
                                           BS)
        offsets = offsets % BS       # ``_decode_rows`` pins 15 and 16
        live = np.flatnonzero(blocks)
        ks = jax.random.split(jax.random.key(BS), 4)
        pools = tuple(jax.random.normal(k, (NB, KVH, BS, d), dtype)
                      for k in ks[:2])
        new = tuple(jax.random.normal(k, (len(blocks), KVH, d),
                                      jnp.float32) for k in ks[2:])
        *lists, n_live = kv_write_row_list(jnp.asarray(blocks),
                                           jnp.asarray(offsets))
        assert int(n_live) == len(live)
        for got, src in zip(lists, (np.arange(len(blocks)), blocks,
                                    offsets)):
            assert got.shape == (len(blocks) + 1,)
            np.testing.assert_array_equal(np.asarray(got)[:len(live)],
                                          src[live])
            assert not np.asarray(got)[len(live):].any()
        want = paged_kv_write(pools, new, blocks, offsets, kernel=False)
        got = jax.jit(functools.partial(paged_kv_write, interpret=True))(
            pools, new, jnp.asarray(blocks), jnp.asarray(offsets))
        bits = {"bfloat16": np.uint16, "float32": np.uint32}[dtype]
        for g, w, p in zip(got, want, pools):
            assert g.dtype == w.dtype == jnp.dtype(dtype)
            g, w, p = (np.asarray(a).view(bits) for a in (g, w, p))
            np.testing.assert_array_equal(g[1:], w[1:])
            np.testing.assert_array_equal(g[0], p[0])
            assert (len(live) == 0) == np.array_equal(w[1:], p[1:])

    @pytest.mark.parametrize("steps", [1, 4, 8])
    def test_host_count_is_the_lists(self, steps):
        """``kv_write_live_rows`` (numpy, the telemetry's) counts what
        ``batch_step`` + ``kv_write_row_list`` make live on the device,
        step by step: inactive slots, a sequence running off its
        allocated blocks, and one off the table's end."""
        from deepspeed_tpu.ops.pallas.paged_attention import (
            kv_write_live_rows, kv_write_row_list)
        BS, MB = 8, 4
        tables = np.asarray([[3, 4, 0, 0], [0, 0, 0, 0], [5, 6, 7, 8],
                             [9, 0, 0, 0], [0, 0, 0, 0], [2, 1, 10, 11]],
                            np.int32)
        lengths = np.asarray([13, 0, 30, 5, 7, 0], np.int32)
        n = 0
        for s in range(steps):
            pos = lengths + s
            dst = np.take_along_axis(
                tables, np.minimum(pos // BS, MB - 1)[:, None], axis=1)
            n += int(kv_write_row_list(jnp.asarray(dst[:, 0]),
                                       jnp.asarray(pos % BS))[-1])
        assert kv_write_live_rows(lengths, tables, BS, steps) == n


def _paged_decode_numpy(q, kc, vc, tbl, lens, *, window=0, alibi=False,
                        alibi_scale=1.0, alibi_bf16=False):
    """Plain numpy paged decode attention, one slot and head at a time,
    with every knob of the kernel (the jnp reference has no bf16 ALiBi)."""
    from deepspeed_tpu.ops.pallas.paged_attention import alibi_slopes
    q, kc, vc = (np.asarray(a, np.float64) for a in (q, kc, vc))
    B, H, d = q.shape
    KVH, BS = kc.shape[1], kc.shape[2]
    G = H // KVH
    slopes = alibi_slopes(H)
    out = np.zeros((B, H, d))
    for b in range(B):
        L = int(lens[b])
        pos = np.arange(max(0, L - window + 1) if window else 0, L + 1)
        blk, off = np.asarray(tbl)[b, pos // BS], pos % BS
        for h in range(H):
            k, v = kc[blk, h // G, off], vc[blk, h // G, off]
            s = k @ q[b, h] / math.sqrt(d)
            if alibi:
                ab = np.float32(slopes[h]) * pos.astype(np.float32)
                if alibi_bf16:
                    ab = np.asarray(jnp.asarray(ab).astype(jnp.bfloat16)
                                    .astype(jnp.float32))
                s = s + ab * alibi_scale
            w = np.exp(s - s.max())
            out[b, h] = (w / w.sum()) @ v
    return out


class TestPagedDecodeWorkList:
    """The decode kernel's grid is the list of live (slot, table entry)
    pairs (ISSUE 27): same answers as the dense reference on every live
    slot whatever the mix of lengths, and nothing past a slot's length or
    in an inactive slot is touched."""

    B, KVH, BS, MB, NB, d = 6, 2, 16, 8, 50, 32
    # 0 on dead slots; exactly BS-1, BS and MB*BS-1 among the live ones
    LENGTHS = {
        "ragged": ([0, 15, 16, 0, 127, 5], [0, 1, 1, 0, 1, 1]),
        "every_slot_full": ([127] * 6, [1] * 6),
        "one_live": ([0, 0, 77, 0, 0, 0], [0, 0, 1, 0, 0, 0]),
    }
    MODES = {
        "plain": dict(G=1),
        "gqa": dict(G=4),
        "window": dict(G=1, window=20),
        "gqa_window": dict(G=2, window=40),
        "alibi": dict(G=1, alibi=True),
        "alibi_bf16": dict(G=2, alibi=True, alibi_bf16=True,
                           alibi_scale=1.0 / math.sqrt(32)),
    }

    def _setup(self, G, lengths, active, seed=0, d=None):
        rng = np.random.RandomState(seed)
        H, d = self.KVH * G, d or self.d
        q = jnp.asarray(rng.randn(self.B, H, d), jnp.float32) * 0.5
        kc, vc = (jnp.asarray(rng.randn(self.NB, self.KVH, self.BS, d),
                              jnp.float32) * 0.5 for _ in range(2))
        lens = np.asarray(lengths, np.int32)
        act = np.asarray(active, bool)
        # the engine's tables: a live slot owns blocks 1.., an inactive
        # one is all scratch block 0
        tbl = np.zeros((self.B, self.MB), np.int32)
        own = rng.permutation(np.arange(1, self.NB))
        for b in np.flatnonzero(act):
            nb = lens[b] // self.BS + 1
            tbl[b, :nb], own = own[:nb], own[nb:]
        return q, kc, vc, jnp.asarray(tbl), jnp.asarray(lens), act

    def _run(self, q, kc, vc, tbl, lens, act, *, window=0, alibi=False,
             per_step=1, **kw):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            alibi_slopes, decode_work_list, paged_decode_attention)
        work = decode_work_list(lens, self.MB, self.BS, window,
                                active=jnp.asarray(act), per_step=per_step)
        return paged_decode_attention(
            q, kc, vc, tbl, lens, work=work, window=window,
            alibi_slopes=alibi_slopes(q.shape[1]) if alibi else None,
            **kw), work

    # entries a grid step: one (the pipeline brings the block), a count
    # that divides no slot's eight entries, and a whole table's
    @pytest.mark.parametrize("per_step", [1, 3, 8])
    @pytest.mark.parametrize("lengths", sorted(LENGTHS))
    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_matches_reference(self, mode, lengths, per_step):
        kw = dict(self.MODES[mode])
        q, kc, vc, tbl, lens, act = self._setup(kw.pop("G"),
                                                *self.LENGTHS[lengths])
        out, work = self._run(q, kc, vc, tbl, lens, act, per_step=per_step,
                              **kw)
        want = _paged_decode_numpy(q, kc, vc, tbl, lens, **kw)
        np.testing.assert_allclose(np.asarray(out)[act], want[act],
                                   rtol=2e-5, atol=2e-5)
        # an inactive slot takes no grid step; its row is q's: finite
        assert set(np.asarray(work.slot_of)[:int(work.n)]) \
            == set(np.flatnonzero(act))
        np.testing.assert_array_equal(np.asarray(out)[~act],
                                      np.asarray(q)[~act])

    @pytest.mark.parametrize("per_step", [2, 4])
    @pytest.mark.parametrize("window", [0, 40])
    @pytest.mark.parametrize("G", [1, 4])
    @pytest.mark.parametrize("d", [64, 128])
    def test_runs_of_entries_at_the_served_widths(self, d, G, window,
                                                  per_step):
        """The jnp reference at the head dims and group sizes the served
        families have: runs of 2 and 4 entries over slots of 1, 2 and 8
        (one an inactive slot apart), and a window whose first attended
        entry is neither the table's first nor a run's."""
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention_reference)
        q, kc, vc, tbl, lens, act = self._setup(
            G, *self.LENGTHS["ragged"], d=d)
        out, _ = self._run(q, kc, vc, tbl, lens, act, window=window,
                           per_step=per_step)
        ref = paged_decode_attention_reference(q, kc, vc, tbl, lens,
                                               window=window)
        np.testing.assert_allclose(np.asarray(out)[act],
                                   np.asarray(ref)[act],
                                   rtol=2e-5, atol=2e-5)

    def test_every_slot_active_by_default(self):
        """Without a list of its own the kernel walks every slot, and a
        slot at length 0 attends its one position like any other."""
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention, paged_decode_attention_reference)
        q, kc, vc, tbl, lens, _ = self._setup(2, *self.LENGTHS["ragged"])
        out = paged_decode_attention(q, kc, vc, tbl, lens)
        ref = paged_decode_attention_reference(q, kc, vc, tbl, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        assert np.isfinite(np.asarray(out)).all()

    @pytest.mark.parametrize("per_step", [1, 3])
    @pytest.mark.parametrize("window", [0, 20])
    def test_table_tail_is_never_read(self, window, per_step):
        """Entries past a slot's length (and a whole inactive row) may
        name any block: scrambling them changes no bit of the output."""
        q, kc, vc, tbl, lens, act = self._setup(1, *self.LENGTHS["ragged"])
        out1, _ = self._run(q, kc, vc, tbl, lens, act, window=window,
                            per_step=per_step)
        rng = np.random.RandomState(7)
        tail = np.arange(self.MB)[None, :] > np.asarray(lens)[:, None] \
            // self.BS
        tail |= ~act[:, None]
        tbl2 = jnp.where(tail, rng.randint(0, self.NB, tail.shape), tbl)
        out2, _ = self._run(q, kc, vc, tbl2, lens, act, window=window,
                            per_step=per_step)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    @pytest.mark.parametrize("per_step", [1, 2, 3, 8])
    @pytest.mark.parametrize("window", [0, 20, 100])
    def test_list_is_the_live_pairs(self, window, per_step):
        """The list names exactly the (slot, entry) pairs that hold a
        position the new token attends, slot-major and cut into runs of
        ``per_step`` a slot, and the host's count (the telemetry's) is
        the device's."""
        from deepspeed_tpu.ops.pallas.paged_attention import (
            decode_grid_steps, decode_work_list)
        lens, act = self.LENGTHS["ragged"]
        lens, act = np.asarray(lens, np.int32), np.asarray(act, bool)
        work = decode_work_list(
            jnp.asarray(lens), self.MB, self.BS, window,
            active=jnp.asarray(act), per_step=per_step)
        want = []
        for b in np.flatnonzero(act):
            mine = [j for j in range(self.MB) if j * self.BS <= lens[b]
                    and (not window or j * self.BS + self.BS - 1
                         > lens[b] - window)]
            want += [(b, run[0], len(run)) for run in (
                mine[k:k + per_step]
                for k in range(0, len(mine), per_step))]
        n = int(work.n)
        got = [np.asarray(x) for x in work[:3]]
        assert work.per_step == per_step
        assert list(zip(*(x[:n].tolist() for x in got))) == want
        assert (got[0][n:] == self.B).all() and not got[2][n:].any()
        assert len(got[0]) == self.B * -(-self.MB // per_step) + 2

        def count(lengths, per_step):
            return int(decode_work_list(
                jnp.asarray(lengths), self.MB, self.BS, window,
                active=jnp.asarray(act), per_step=per_step).n)

        # the host's count, for every N the shape rule can return
        for N in range(1, self.MB + 1):
            assert decode_grid_steps(lens, act, self.MB, self.BS, window,
                                     per_step=N) == count(lens, N)
        # over a dispatch's steps every length grows by one a step
        assert decode_grid_steps(lens, act, self.MB, self.BS, window,
                                 steps=3, per_step=per_step) == sum(
            count(lens + t, per_step) for t in range(3))

    @pytest.mark.parametrize("shape,want", [
        # (KV heads, block size, head dim, dtype, table entries) -> N:
        # phi-4-mini-flash's ten 128-lane head pairs, 0.33 MB a block
        ((10, 64, 128, "bfloat16", 64), 4),
        ((10, 64, 128, "bfloat16", 3), 3),          # a table of three
        ((16, 64, 128, "bfloat16", 64), 2),         # olmoe-1b-7b, 0.52 MB
        ((32, 64, 128, "bfloat16", 64), 1),         # 1.05 MB: one entry
        ((16, 64, 128, "float32", 64), 1),
        ((2, 16, 128, "bfloat16", 1024), 64),       # tiny blocks: to 1 MB
        ((64, 256, 256, "bfloat16", 64), 1),        # past the VMEM cap
        # rows narrower than the lanes cannot be cut out of HBM by the
        # kernel's own copies: gpt2-medium, opt-1.3b
        ((16, 64, 64, "bfloat16", 16), 1),
        ((32, 64, 64, "bfloat16", 32), 1),
    ])
    def test_entries_a_step_come_from_the_shape(self, shape, want):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            decode_entries_per_step)
        assert decode_entries_per_step(*shape) == want


class TestPoolBoundaryShape:
    """The block axis of a pool at program boundaries: split into
    factors the TPU compiler leaves out of the lanes, only where the
    kernels run as kernels and the head dim is under the lanes."""

    @pytest.mark.parametrize("n", [8, 64, 65, 129, 131, 320, 513, 4096,
                                   4097, 10007])
    def test_factors_stay_under_the_lane_threshold(self, n):
        dims = pool_block_dims(n, 64, True)
        assert all(1 <= d <= 64 for d in dims)
        # padded up by few blocks: under a row of the split, or 2 %
        assert 0 <= np.prod(dims) - n < max(64, n // 50)
        assert np.prod(dims) == n or n in (131, 4097, 10007)
        assert pool_block_dims(n, 128, True) == (n,)     # already row-major
        assert pool_block_dims(n, 64, False) == (n,)     # no kernels

    @pytest.mark.parametrize("splitfuse", [0, 16],
                             ids=["bucketed", "splitfuse"])
    def test_engine_with_split_block_axis_is_token_identical(
            self, splitfuse, monkeypatch):
        """Every program that takes the cache merges the block axis on
        the way in and splits it on the way out: prefill / chunk /
        decode / copy-on-write / KV export and import give the tokens
        of the unsplit engine (the split forced here; off-TPU it is
        never chosen)."""
        from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                                engine_v2, kv_transfer)
        params = GPT2(_CFG).init(jax.random.key(0))
        rs = np.random.RandomState(2)
        template = rs.randint(0, 256, (21,)).astype(np.int32)
        prompts = [np.concatenate([template, rs.randint(0, 256, (n,))
                                   .astype(np.int32)]) for n in (3, 9, 20)]
        base = {"dtype": "float32", "kv_block_size": 8,
                "prompt_bucket": 16, "max_batch_size": 4,
                "splitfuse_tokens": splitfuse, "prefix_cache": True,
                "decode_steps_per_dispatch": 2}

        def run():
            groups.reset()
            eng = InferenceEngineV2(GPT2(_CFG), params=params, config=base)
            toks = [eng.generate_all([p], max_new_tokens=5)[0]
                    for p in prompts]
            uid = eng.put(prompts[0], max_new_tokens=5, eos_token_id=-1)
            eng.hold_decode(uid)
            seqs = eng.state_mgr._seqs      # admitted by the first step
            while uid not in seqs or not seqs[uid].generated:
                eng.step()
            groups.reset()
            other = InferenceEngineV2(GPT2(_CFG), params=params,
                                      config=base)
            kv_transfer.import_sequence(
                other, kv_transfer.export_sequence(eng, uid))
            while not other.is_done(uid):
                other.step()
            return toks + [other.get(uid)], eng.cache["k"][0].shape

        plain, shape = run()
        assert len(shape) == 4
        monkeypatch.setattr(
            engine_v2, "pool_block_dims",
            lambda n, hd, kernel_layout: pool_block_dims(n, hd, True))
        split, shape = run()
        assert len(shape) == 5 and shape[0] * shape[1] >= shape[0] > 1
        for a, b in zip(split, plain):
            np.testing.assert_array_equal(a, b)


class TestChunkProgramHLO:
    def test_kernel_path_never_gathers_dense_kv(self):
        """Acceptance: on the kernel path the chunk program no longer
        materializes the (MB, H, BS, hd) table-gather (the dense copy
        that became the (S, H, hd) attention operand). The dense
        variant of the SAME program contains it — proving the probe
        actually detects the gather."""
        MB, BS = 4, 16
        # the dense gather's result type in the lowered text
        sig = f"tensor<{MB}x{_CFG.n_head}x{BS}x{_CFG.d_head}xf32>"

        dense = GPT2(_CFG)
        dense._paged_kernel = False
        dense._paged_block_c = 8
        assert sig in _lower_chunk(dense, MB=MB, BS=BS)

        kern = GPT2(_CFG)
        kern._paged_kernel = True
        kern._paged_block_c = 8
        assert sig not in _lower_chunk(kern, MB=MB, BS=BS)


class TestPagedDispatchHLO:
    """Winner-cache dispatch for the serving ops, same assertion style
    as test_autotune.TestHLOIdentity: warm cache lowers byte-identical
    to the hand-set config; a cold cache is byte-identical to the
    proven defaults (dense chunk off-TPU, kernel decode)."""

    def test_warm_cache_matches_hand_set(self):
        path = os.environ["DSTPU_AUTOTUNE_CACHE"]
        dk = kernel_dispatch.device_kind()
        C, MB, BS, B = 16, 4, 16, 2
        H, hd = _CFG.n_head, _CFG.d_head
        c = KernelCache()
        c.put(dk, "paged_chunk",
              paged_chunk_bucket(C, MB, BS, H, 1, hd), "float32",
              {"mode": "kernel", "block_c": 8})
        c.put(dk, "paged_decode",
              paged_decode_bucket(B, MB, BS, H, 1, hd), "float32",
              {"mode": "kernel"})
        c.save(path)

        kernel_dispatch.configure(mode="cache_only")
        auto = GPT2(_CFG)                      # attrs default to "auto"
        t_auto = (_lower_chunk(auto, MB=MB, BS=BS, C=C),
                  _lower_decode(auto, B=B, MB=MB, BS=BS))
        assert len(kernel_dispatch._STATE["resolved"]) >= 2

        kernel_dispatch.configure(mode="off")
        hand = GPT2(_CFG)
        hand._paged_kernel = True
        hand._paged_block_c = 8
        t_hand = (_lower_chunk(hand, MB=MB, BS=BS, C=C),
                  _lower_decode(hand, B=B, MB=MB, BS=BS))
        assert t_auto == t_hand

    def test_cold_cache_matches_proven_defaults(self):
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_chunk_tune_defaults)
        kernel_dispatch.configure(mode="cache_only")   # empty cache
        auto = GPT2(_CFG)
        t_auto = (_lower_chunk(auto), _lower_decode(auto))

        kernel_dispatch.configure(mode="off")
        hand = GPT2(_CFG)
        defaults = paged_chunk_tune_defaults()
        hand._paged_kernel = defaults["mode"] == "kernel"
        hand._paged_block_c = defaults["block_c"]
        t_chunk = _lower_chunk(hand)
        # decode's proven default is the kernel on every backend
        hand_dec = GPT2(_CFG)
        hand_dec._paged_kernel = True
        hand_dec._paged_block_c = defaults["block_c"]
        assert t_auto == (t_chunk, _lower_decode(hand_dec))


class TestEngineKernelOnOff:
    def test_splitfuse_greedy_identical_kernel_on_off(self):
        """Acceptance e2e: the split-fuse engine produces IDENTICAL
        greedy tokens with the paged kernels forced on (chunk +
        prefill + decode through Pallas, interpret mode here) vs forced
        off (dense-gather parity path)."""
        from deepspeed_tpu.inference.v2 import InferenceEngineV2
        params = GPT2(_CFG).init(jax.random.key(0))
        rs = np.random.RandomState(0)
        # < 1 chunk, exactly 1 chunk, several chunks crossing blocks
        prompts = [rs.randint(0, 256, (n,)).astype(np.int32)
                   for n in (5, 16, 37)]
        base = {"dtype": "float32", "kv_block_size": 8,
                "prompt_bucket": 16, "max_batch_size": 4,
                "splitfuse_tokens": 16}

        def run(pk):
            groups.reset()
            eng = InferenceEngineV2(GPT2(_CFG), params=params,
                                    config=dict(base, paged_kernel=pk))
            return eng.generate_all(prompts, max_new_tokens=6)

        on = run(True)
        off = run(False)
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("splitfuse", [0, 16],
                             ids=["bucketed", "splitfuse"])
    def test_aliased_write_greedy_identical_to_dense(self, splitfuse,
                                                     monkeypatch):
        """Three decode dispatches of either engine with the whole
        kernel path — the aliased write too, forced into the
        interpreter here as it is never chosen off-TPU — give the
        tokens of the dense-gather path with its XLA scatter."""
        from deepspeed_tpu.inference.v2 import InferenceEngineV2
        monkeypatch.setattr(
            paged_attention, "paged_kv_write",
            functools.partial(paged_kv_write, interpret=True))
        params = GPT2(_CFG).init(jax.random.key(0))
        rs = np.random.RandomState(1)
        prompts = [rs.randint(0, 256, (n,)).astype(np.int32)
                   for n in (5, 16, 37)]
        base = {"dtype": "float32", "kv_block_size": 8,
                "prompt_bucket": 16, "max_batch_size": 4,
                "splitfuse_tokens": splitfuse,
                "decode_steps_per_dispatch": 2}

        def run(pk):
            groups.reset()
            eng = InferenceEngineV2(GPT2(_CFG), params=params,
                                    config=dict(base, paged_kernel=pk))
            return eng.generate_all(prompts, max_new_tokens=7)

        for a, b in zip(run(True), run(False)):
            np.testing.assert_array_equal(a, b)


class TestMixtralEPRouting:
    def test_serving_programs_route_ragged_ep_alltoall(self):
        """Mixtral with expert_parallel > 1 serves through the manual
        shard_map ragged-EP all_to_all (moe/sharded_moe.py) in BOTH the
        decode and the SplitFuse chunk program — and through the plain
        grouped-GEMM path at ep=1 (trace-level; the e2e greedy parity
        lives in test_inference_v2's slow tier)."""
        from deepspeed_tpu.models.mixtral import Mixtral, MixtralConfig
        from deepspeed_tpu.utils.groups import TopologyConfig
        mcfg = MixtralConfig(n_layer=2, n_head=4, n_kv_heads=2,
                             d_model=64, max_seq_len=128, vocab_size=512,
                             remat=False, num_experts=4, moe_top_k=2,
                             dtype="float32")
        NB, BS, MB, B, C = 9, 16, 4, 2, 16
        i32 = jnp.int32

        def lower(ep):
            groups.reset()
            topo = groups.initialize(TopologyConfig(
                expert_parallel_size=ep))
            model = Mixtral(mcfg)
            params = _abstract_params(model)
            cache = {
                "k": [jax.ShapeDtypeStruct(
                    (NB, mcfg.n_kv_heads, BS, mcfg.d_head),
                    jnp.float32)] * mcfg.n_layer,
                "v": [jax.ShapeDtypeStruct(
                    (NB, mcfg.n_kv_heads, BS, mcfg.d_head),
                    jnp.float32)] * mcfg.n_layer,
            }
            with jax.set_mesh(topo.mesh):
                dec = jax.jit(model.apply_paged_decode).lower(
                    params, jax.ShapeDtypeStruct((B,), i32),
                    jax.ShapeDtypeStruct((B,), i32), cache,
                    jax.ShapeDtypeStruct((B, MB), i32)).as_text()
                chk = jax.jit(model.apply_paged_chunk).lower(
                    params, jax.ShapeDtypeStruct((1, C), i32), cache,
                    jax.ShapeDtypeStruct((C,), i32),
                    jax.ShapeDtypeStruct((C,), i32),
                    jax.ShapeDtypeStruct((), i32),
                    jax.ShapeDtypeStruct((), i32),
                    jax.ShapeDtypeStruct((MB,), i32)).as_text()
            groups.reset()
            return dec, chk

        dec_ep, chk_ep = lower(2)
        assert "all_to_all" in dec_ep or "all-to-all" in dec_ep
        assert "all_to_all" in chk_ep or "all-to-all" in chk_ep
        dec_1, chk_1 = lower(1)
        assert "all_to_all" not in dec_1 and "all-to-all" not in dec_1
        assert "all_to_all" not in chk_1 and "all-to-all" not in chk_1
