"""The selected read of a latent layer for a prompt's chunk, as one Pallas
kernel: the expanded form of ``models/deepseek_v32.py`` (a block of latent
rows becomes every head's keys and values through ``wk_b`` / ``wv_b``) with
the selection of ``models/paged.py:_latent_read`` as a mask, flash style.
A tile of keys is expanded, scored, masked, exponentiated and multiplied
into its values inside one grid step; the running maximum, sum and
accumulator stay in VMEM across a head group's key tiles and leave once,
normalised. HBM sees the queries, the gathered rows, the two weights and
the packed mask in, and (rows, C, heads x dv) float32 out: nothing of shape
(heads, C, keys), and no expanded key or value, is ever an array there.

The mathematics and every rounding point are ``_latent_read``'s XLA read
through ``DeepseekV32._attention``'s expanded ``read_fn``, which stays as
this kernel's reference and as the path off a TPU: queries in the cache's
dtype (sigma already on them), expanded keys and values rounded to that
dtype from float32 accumulation, float32 scores, maximum, sum and
accumulator, probabilities rounded to the cache's dtype into the value
product. The key tile is the XLA read's pass (``key_tile``), so the two
agree to the order in which a float32 sum over a tile's keys is taken. A
key that is not read is -inf here and -1e30 with a second mask there: the
same probability, exactly 0, since the first maximum is finite.

**A row's tail rides along.** A head's key is [its expansion of the latent
| the rotary key every head shares]; the rotary key lies in the row right
after the latent, so the kernel takes the row from R to its end (a whole
lane tile at the published widths, 512 .. 640) and the queries come ending
in as many zeros as follow the rotary key there: one contraction, no slice
off the lanes, and whatever the row's padding holds meets a zero.

**The grid** is (rows, head groups, query tiles, key tiles), the key tiles
sequential and only as many as the step's longest frontier needs (a grid
bound read on the device). A (row, query tile) visits the key tiles up to
its last query's position and its row's frontier, whichever is first
(``tiles``, scalar prefetch): past that its block indices stand still, so
nothing is fetched, and the body is skipped. The selection says everything
else: a key that is not causal, lies past the frontier or under the query's
threshold is 0 in the mask, as it is -inf in the index scores the mask is
made from. Queries a tile adds to fill itself select nothing and their
rows are dropped.

**The tiles come from the shapes** (:func:`read_tiles`): a chunk of up to
1,024 queries is one query tile, so a key tile is expanded once a head (the
expansion is a third of the tile's other products at 1,024 queries and
would be two thirds at 512); a longer prompt bucket is cut into equal
tiles. A grid step takes as many heads as keep its buffers inside
``_VMEM_BUDGET`` (4 of 128 at the published widths: 32 groups x ~10 key
tiles a 1,024-token chunk at 4.75 k keys; 2 or 8 heads a step read within
3 % of 4 on a v5e), and the call raises Mosaic's VMEM limit to what that
needs.

The rows come gathered through the block table by XLA, the one gather a
layer (``lat_pool[tables]``, 21.6 MB at 264 table entries: ~0.05 ms), and
the mask packed by XLA as int8 (re-read once a head group: 4.9 MB at
4.75 k keys where the float32 scores are 19 MB; on a v5e the kernel reads
the same with and without it).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_default, round_up

_QUERY_TILE = 1024          # the most queries a grid step takes
# what a grid step's buffers may take of a v5e's 128 MiB of VMEM; Mosaic's
# default limit is 16 MiB and the call raises it to what the tiles need
_VMEM_BUDGET = 40 << 20
_NEG = -1e30


def read_tiles(C, H, dn, dv, R, W, key_tile, dtype):
    """(queries a tile, heads a grid step, VMEM bytes the step needs) of
    the kernel for C queries a row of H heads: expanded key width dn, value
    width dv, latent width R in rows of W, ``key_tile`` keys a step. The
    query tile is the chunk itself up to ``_QUERY_TILE`` (in whole int8
    tiles of 32 sublanes), else equal parts of it; the head group the
    largest divisor of H whose buffers fit the budget and whose query
    and output blocks (heads x width lanes) are whole 128-lane tiles."""
    item = jnp.dtype(dtype).itemsize
    lanes = functools.partial(round_up, m=128)
    parts = -(-C // _QUERY_TILE)
    TQ = round_up(-(-C // parts), 32)
    wide = lanes(dn + W - R)    # a query, a key: [dn | the row's tail]
    # both buffers of the pipeline: the rows and the mask; a head's
    # scores, probabilities (float32 and rounded) and the mask widened;
    # its expanded keys and values, float32 and rounded
    shared = 2 * key_tile * lanes(W) * item + 2 * TQ * lanes(key_tile) \
        + TQ * lanes(key_tile) * (4 + 4 + 4 + item) \
        + key_tile * (wide + lanes(dv)) * (4 + item)
    # a head's queries and output block (two buffers each), accumulator,
    # maximum and sum (a lane tile wide each), and its two weights
    head = TQ * (2 * wide * item + 2 * dv * 4 + lanes(dv) * 4
                 + 2 * 128 * 4) \
        + 2 * (dn * lanes(R) + R * lanes(dv)) * item
    whole = [g for g in range(1, H + 1)
             if H % g == 0 and (g == H or not (g * dv % 128
                                               or g * (dn + W - R) % 128))]
    Hg = max([g for g in whole if shared + g * head <= _VMEM_BUDGET],
             default=whole[0])
    return TQ, Hg, shared + Hg * head


def _nt(a, b):
    """a (m, k) @ b (n, k)^T, float32."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _read_kernel(tiles_ref, n_ref, q_ref, rows_ref, sel_ref, wk_ref, wv_ref,
                 o_ref, m_ref, l_ref, acc_ref):
    """One key tile of one (row, head group, query tile). q (TQ, Hg x (dn +
    W - R)), rows (KT, W), sel (TQ, KT) int8, wk (Hg, dn, R), wv (Hg, R,
    dv), o (TQ, Hg x dv); m, l (Hg, TQ, 1) and acc (Hg, TQ, dv) float32
    live from the first key tile to the last.

    The group's heads are written out one after another, not looped over:
    the compiler then runs a head's exponentials under the next head's
    products (5.7 against 7.8 ms a 1,024-query call at 5,120 keys on a
    v5e). Every operation of a head is on the whole (TQ, KT) tile: cut
    into blocks of query rows, with a block's scores kept in registers
    between the passes, the kernel was slower the smaller the block
    (PERF.md, PR 44)."""
    b, i, t = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    Hg, dn, R = wk_ref.shape
    dv = wv_ref.shape[2]
    dq = q_ref.shape[1] // Hg
    dt = rows_ref.dtype

    @pl.when(t == 0)
    def _first():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(t < tiles_ref[b * pl.num_programs(2) + i])
    def _tile():
        c = rows_ref[:, :R]
        for h in range(Hg):
            # a head's keys: its expansion of the latents, rounded, and
            # the row's tail after them, the rotary key all heads share
            # (what follows it meets the zeros the queries end in)
            k = jnp.concatenate([_nt(c, wk_ref[h]).astype(dt),
                                 rows_ref[:, R:]], axis=1)
            v = jnp.dot(c, wv_ref[h],
                        preferred_element_type=jnp.float32).astype(dt)
            # a key the query does not read is -inf, which the finite
            # first maximum turns into a probability of exactly 0
            s = jnp.where(sel_ref[...].astype(jnp.int32) != 0,
                          _nt(q_ref[:, h * dq:(h + 1) * dq], k), -jnp.inf)
            m = m_ref[h]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            a = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            m_ref[h] = m_new
            l_ref[h] = l_ref[h] * a + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * a + jnp.dot(
                p.astype(dt), v, preferred_element_type=jnp.float32)

    @pl.when(t == n_ref[0] - 1)
    def _last():
        for h in range(Hg):
            o_ref[:, h * dv:(h + 1) * dv] = acc_ref[h] / jnp.maximum(
                l_ref[h], 1e-30)


@functools.partial(jax.jit, static_argnames=("key_tile", "interpret"))
def _latent_read_call(q, rows, sel, wk_b, wv_b, q_pos, frontier, *,
                      key_tile, interpret):
    """The kernel as one jitted callee, so that a program's latent layers
    trace and lower it once (``paged_attention._decode_call``'s reason)."""
    B, C, H, d = q.shape
    _, K, W = rows.shape
    _, dn, R = wk_b.shape
    dv = wv_b.shape[2]
    KT = key_tile
    TQ, Hg, vmem = read_tiles(C, H, dn, dv, R, W, KT, rows.dtype)
    NQ = -(-C // TQ)
    pad = NQ * TQ - C
    # a head's query ends in zeros as wide as a row's tail after the
    # rotary key (the kernel contracts it with [the expanded key | the row
    # from R on]), and a group's heads lie side by side along the lanes;
    # the tile's own pads select nothing
    dq = dn + W - R
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, dq - d))).reshape(
        B, NQ * TQ, H * dq)
    sel = jnp.pad(sel.astype(jnp.int8), ((0, 0), (0, pad), (0, 0)))
    # key tiles a (row, query tile) visits: to its last query's position
    # or its row's frontier, and at least one
    n = jnp.clip((jnp.max(frontier) + KT - 1) // KT, 1, K // KT)
    last = jnp.max(jnp.pad(q_pos, ((0, 0), (0, pad))).reshape(B, NQ, TQ),
                   axis=-1)
    tiles = jnp.clip((jnp.minimum(last + 1, frontier[:, None]) + KT - 1)
                     // KT, 1, n).astype(jnp.int32).reshape(-1)

    def key_of(b, i, t, tiles):
        return jnp.minimum(t, tiles[b * NQ + i] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H // Hg, NQ, n),
        in_specs=[
            pl.BlockSpec((None, TQ, Hg * dq),
                         lambda b, g, i, t, tiles, n: (b, i, g)),
            pl.BlockSpec((None, KT, W), lambda b, g, i, t, tiles, n:
                         (b, key_of(b, i, t, tiles), 0)),
            pl.BlockSpec((None, TQ, KT), lambda b, g, i, t, tiles, n:
                         (b, i, key_of(b, i, t, tiles))),
            pl.BlockSpec((Hg, dn, R), lambda b, g, i, t, tiles, n: (g, 0, 0)),
            pl.BlockSpec((Hg, R, dv), lambda b, g, i, t, tiles, n: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, TQ, Hg * dv),
                               lambda b, g, i, t, tiles, n: (b, i, g)),
        scratch_shapes=[pltpu.VMEM((Hg, TQ, 1), jnp.float32),
                        pltpu.VMEM((Hg, TQ, 1), jnp.float32),
                        pltpu.VMEM((Hg, TQ, dv), jnp.float32)],
    )
    o = pl.pallas_call(
        _read_kernel,
        name="dstpu.kernel.latent_read",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NQ * TQ, H * dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=interpret,
    )(tiles, jnp.reshape(n, (1,)).astype(jnp.int32), q, rows, sel, wk_b,
      wv_b)
    return o[:, :C].reshape(B, C, H, dv)


def latent_chunk_attention(q, rows, sel, wk_b, wv_b, q_pos, frontier, *,
                           key_tile, interpret=None):
    """The expanded-form selected read of a step of C queries a row.

    q (B, C, H, dn + dr) the queries, sigma on them, in the cache's dtype;
    rows (B, K, W) the latent rows of each row's table in its order, [the
    R-wide latent | the dr-wide rotary key | anything]; sel (B, C, K) which
    keys each query reads (bool or int8): causal, inside the frontier and
    selected; wk_b (H, dn, R), wv_b (H, R, dv); q_pos (B, C) the queries'
    positions and frontier (B,) the first position past each row's written
    range, which only bound the key tiles visited. K is whole
    ``key_tile``s, and a tile whole lanes unless it is K. -> (B, C, H, dv)
    float32; a query that selects nothing reads 0."""
    if interpret is None:
        interpret = interpret_default()
    return _latent_read_call(q, rows, sel, wk_b, wv_b, q_pos, frontier,
                             key_tile=int(key_tile),
                             interpret=bool(interpret))
