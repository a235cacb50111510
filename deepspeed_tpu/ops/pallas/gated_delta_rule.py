"""The gated delta rule of ``ops/gated_delta_rule.py`` as two Pallas
kernels that keep the matrix state S on the chip for as long as a call
works on it. The mathematics, the precision (float32 operands, every
product ``lax.Precision.HIGHEST``, which Mosaic lowers as
``contract_precision<fp32>``) and the places the exponentials are taken are
``chunk_rule``'s and ``step_rule``'s, which stay as the reference of these
kernels and as the dense path's own form.

**The chunk kernel** (:func:`chunk_rule_kernel`; a prefill or chunk
program's T tokens). The grid is (rows, groups of heads, the call's
64-token chunks), the chunk axis sequential: a group's S, (heads, dk, dv)
float32, is the output block itself, which stays in VMEM from the group's
first chunk to its last, read from the slot's row once and written once.
All of ``chunk_rule``'s body happens inside a grid step, a head at a time:
the cumulated log decay (a product with a triangle of ones), the masked
decay matrix G, ``K K^T``, the unit-lower inverse, ``T [b e^c K | b V]``,
``Q K^T * G``, then U, o and the state's update; nothing of shape (L, L)
is ever an array in HBM. The inverse keeps the algebra of
``_unit_lower_inverse``: diagonal blocks of 16 rows by forward
substitution (a column of A times a row of X a step, all four blocks at
once), two solved halves joined as ``X - X A_21 X``, two products a level,
never a series in A.

q, k and v come as ``OlmoHybrid._delta`` makes them, (tokens, heads, d),
and are handed to the kernel as (tokens, groups, heads a group, d): the
tiled dims are then (heads a group, d) and a block takes any group. A head
of 96 lanes is cut out of a 2,880-lane row by the XLA fusion that makes
q, k and v (the reshape it had before this kernel); the kernel reads a
head's (64, d) rows out of its block by a sublane-strided load and does
no transposition of its own but the operand forms of its products. The
group is the largest divisor of the heads that fits the 8 sublanes of a
float32 tile (6 of 30: 5 x 16 = 80 grid steps a 1,024-token call, each
six heads' products).

**The step kernel** (:func:`step_rule_kernel`; a decode step's one token a
slot). Its grid is the live slots of the step: the list of them is made on
the device (:func:`live_slot_list`) and handed in by scalar prefetch, so a
dead slot's state is neither read nor written. The ``ssm`` leaf is aliased
in and out and updated in place, a slot's thirty heads a grid step.
Elementwise and sums over sublanes, float32, as ``step_rule``; every
operand comes in the layout the model has it in, so no XLA operation
stands between the mixer's fusions and the call.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..gated_delta_rule import CHUNK, EXACT, _BASE
from ._common import interpret_default, round_up

_SUBLANES = 8           # of a float32 tile: the most heads a group takes


def head_group(H):
    """Heads a grid step takes: the largest divisor of H within the 8
    sublanes that (heads a group, d) tiles to."""
    return max(g for g in range(1, _SUBLANES + 1) if H % g == 0)


def _dot(a, b, dims=((1,), (0,))):
    """float32 ``a @ b`` to the last bits; ``dims`` the contracted axes
    (NT: ((1,), (1,)); TN: ((0,), (0,)))."""
    return lax.dot_general(a, b, (dims, ((), ())), precision=EXACT,
                           preferred_element_type=jnp.float32)


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _unit_lower_inverse(A):
    """(I + A)^-1 for strictly lower triangular A (L, L), L a multiple of
    ``_BASE``: ``ops/gated_delta_rule.py``'s algebra on whole (L, L)
    tiles. The diagonal blocks are brought side by side, (L, _BASE), by a
    product with a 0 / 1 matrix (exact: every sum has one term) rather
    than by slices off the 128 lanes."""
    L = A.shape[0]
    nb = L // _BASE
    t, s = _iota((L, L), 0), _iota((L, L), 1)
    fold = (_iota((L, _BASE), 0) % _BASE
            == _iota((L, _BASE), 1)).astype(jnp.float32)
    diag = t // _BASE == s // _BASE
    Ad = _dot(jnp.where(diag, A, 0.0), fold).reshape(nb, _BASE, _BASE)
    X = (_iota((nb, _BASE, _BASE), 1)
         == _iota((nb, _BASE, _BASE), 2)).astype(jnp.float32)
    for j in range(_BASE - 1):
        # rows under j take their multiple of row j, which is final
        X = X - Ad[:, :, j:j + 1] * X[:, j:j + 1, :]
    X = jnp.where(diag, _dot(X.reshape(L, _BASE), fold, ((1,), (1,))), 0.0)
    m = _BASE
    while m < L:
        # [[X11, 0], [0, X22]] - [[0, 0], [X22 A21 X11, 0]] in every
        # block of 2 m rows at once
        low = (t // (2 * m) == s // (2 * m)) & ((t // m) % 2 == 1) \
            & ((s // m) % 2 == 0)
        X = X - _dot(_dot(X, jnp.where(low, A, 0.0)), X)
        m *= 2
    return X


def _chunk_kernel(q_ref, k_ref, v_ref, la_ref, b_ref, s0_ref, o_ref, s_ref):
    """One 64-token chunk of one group of heads. q, k (L, Hg, dk), v, o
    (L, Hg, dv), log_a, b (L, Hg), the state (Hg, dk, dv)."""
    L, Hg, dv = v_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _first():
        s_ref[...] = s0_ref[...]

    t, s = _iota((L, L), 0), _iota((L, L), 1)
    la, b = la_ref[...], b_ref[...]
    # c_t = sum_{r <= t} log a_r with t on the sublanes, and the same
    # numbers with t on the lanes (a product with the identity, exact:
    # c_t - c_t has to be 0, which two sums in two orders do not give)
    c = _dot((s <= t).astype(jnp.float32), la)                  # (L, Hg)
    c_row = _dot(c, (t == s).astype(jnp.float32), ((0,), (0,)))   # (Hg, L)
    ec = jnp.exp(c)
    e_out = jnp.exp(c[L - 1:L] - c)
    # the chunk's whole decay a head, along dv lanes: Mosaic broadcasts
    # along sublanes or lanes, not (1, 1) both ways, so the product does it
    a_chunk = jnp.exp(_dot(la, jnp.ones((L, dv), jnp.float32),
                           ((0,), (0,))))                       # (Hg, dv)
    for h in range(Hg):
        q, k, v = q_ref[:, h, :], k_ref[:, h, :], v_ref[:, h, :]
        bh = b[:, h:h + 1]
        # exp only where t >= s: above the diagonal c_t - c_s is positive
        # and may overflow
        G = jnp.where(t >= s, jnp.exp(jnp.where(
            t >= s, c[:, h:h + 1] - c_row[h:h + 1, :], 0.0)), 0.0)
        kk = _dot(k, k, ((1,), (1,)))
        Tm = _unit_lower_inverse(jnp.where(t > s, bh * kk * G, 0.0))
        W = _dot(Tm, k * (bh * ec[:, h:h + 1]))     # T b e^c K
        U0 = _dot(Tm, v * bh)                       # T b V
        qk = _dot(q, k, ((1,), (1,))) * G
        S = s_ref[h]
        U = U0 - _dot(W, S)
        o_ref[:, h, :] = _dot(q * ec[:, h:h + 1], S) + _dot(qk, U)
        s_ref[h] = a_chunk[h:h + 1, :] * S + _dot(
            k * e_out[:, h:h + 1], U, ((0,), (0,)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_call(q, k, v, log_a, b, state, *, interpret):
    """The chunk kernel as one jitted callee, so that a program's twelve
    layers trace and lower it once (``paged_attention._decode_call``'s
    reason)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    Hg = head_group(H)
    G, L = H // Hg, CHUNK
    N = -(-T // L)
    pad = N * L - T

    def rows(x):
        """(B, T, H, ...) -> (B, N L, G, Hg, ...), zeros after T."""
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape((B, N * L, G, Hg) + x.shape[3:])

    def gates(x):
        """(B, T, H) -> (B, G, N L, Hg): a head's gate a lane, a group's
        a block."""
        return jnp.moveaxis(rows(x), 2, 1)

    def tokens(d):
        return pl.BlockSpec((None, L, None, Hg, d),
                            lambda r, g, n: (r, n, g, 0, 0))

    gate = pl.BlockSpec((None, None, L, Hg), lambda r, g, n: (r, g, n, 0))
    mat = pl.BlockSpec((None, None, Hg, dk, dv),
                       lambda r, g, n: (r, g, 0, 0, 0))
    o, state = pl.pallas_call(
        _chunk_kernel,
        name="dstpu.kernel.gdn_chunk",
        grid=(B, G, N),
        in_specs=[tokens(dk), tokens(dk), tokens(dv), gate, gate, mat],
        out_specs=[tokens(dv), mat],
        out_shape=[jax.ShapeDtypeStruct((B, N * L, G, Hg, dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, G, Hg, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rows(q), rows(k), rows(v), gates(log_a), gates(b),
      state.reshape(B, G, Hg, dk, dv))
    return o.reshape(B, N * L, H, dv)[:, :T], state.reshape(B, H, dk, dv)


def chunk_rule_kernel(q, k, v, log_a, b, state, *, interpret=None):
    """``chunk_rule`` as a Pallas kernel: q, k (B, T, H, dk), v (B, T, H,
    dv), log_a (B, T, H, 1) (or (B, T, H)), b (B, T, H), state (B, H, dk,
    dv); all float32. -> (o (B, T, H, dv), the state after token T - 1). T
    is padded to whole 64-token chunks with rows that move nothing
    (``log_a`` 0, ``b`` 0). A gate a head only: a gate a key channel,
    (B, T, H, dk), has no chunk kernel yet and raises; its chunks run
    ``chunk_rule`` (ROADMAP R4)."""
    if interpret is None:
        interpret = interpret_default()
    if log_a.ndim == 4:
        if log_a.shape[-1] != 1:
            raise NotImplementedError(
                "chunk_rule_kernel takes a gate a head; a gate a key "
                "channel runs ops/gated_delta_rule.py:chunk_rule")
        log_a = log_a[..., 0]
    return _chunk_call(q, k, v, log_a, b, state, interpret=bool(interpret))


# ----------------------------------------------------------- the one token
def live_slot_list(active):
    """The step kernel's grid, as data: ``active`` (B,) bool -> (``slot_of``
    int32[B + 1], the live slots in their order and 0 from item ``n_live``
    on (the pipeline may look one item ahead), ``n_live`` on the device).
    Compares and sums over (items, slots), as
    ``paged_attention.kv_write_row_list`` and for its reason. Make it once
    a decode step and hand it to every layer's call."""
    B = active.shape[0]
    ends = jnp.cumsum(active, dtype=jnp.int32)
    item = jnp.arange(B + 1, dtype=jnp.int32)
    mine = active[None, :] & (ends[None, :] == item[:, None] + 1)
    slot_of = jnp.sum(jnp.where(mine, jnp.arange(B, dtype=jnp.int32)[None],
                                0), axis=1, dtype=jnp.int32)
    return slot_of, ends[-1]


def _step_kernel(slot_ref, q_ref, k_ref, la_ref, b_ref, v_ref, s_ref, o_ref,
                 so_ref):
    """One live slot: q, k (H, dk), v, o (H, dv) and the state (H, dk, dv)
    are the slot's; b (B, H) every slot's, the slot's row read here, and
    so log_a where it is a gate a head; a gate a key channel is the
    slot's own (H, dk) block. Everything comes as the model has it; what the sums over dk
    need on the sublanes (k, q as (dk, H)) and along dv lanes (a head's
    gates) is made by products with 0 / 1 matrices, one nonzero term a
    sum: exact."""
    H, dk, dv = s_ref.shape
    slot = slot_ref[pl.program_id(0)]
    nt = ((1,), (1,))
    eye = (_iota((dk, dk), 0) == _iota((dk, dk), 1)).astype(jnp.float32)
    kT, qT = _dot(eye, k_ref[...], nt), _dot(eye, q_ref[...], nt)
    heads = (_iota((H, H), 0) == _iota((H, H), 1)).astype(jnp.float32)
    ones = jnp.ones((dv, H), jnp.float32)
    per_channel = la_ref.shape == (H, dk)
    if per_channel:
        # the slot's (H, dk) gates as (dk, H): a head's column scales its
        # S's sublanes
        a = jnp.exp(_dot(eye, la_ref[...], nt))
    else:
        a = jnp.exp(_dot(heads * la_ref[pl.ds(slot, 1), :], ones, nt))
    b = _dot(heads * b_ref[pl.ds(slot, 1), :], ones, nt)
    for h in range(H):
        S, k = s_ref[h], kT[:, h:h + 1]
        if per_channel:
            S = a[:, h:h + 1] * S
            u = b[h:h + 1, :] * (v_ref[h:h + 1, :] - jnp.sum(
                k * S, axis=0, keepdims=True))
            S = S + k * u
        else:
            ah = a[h:h + 1, :]                                  # (1, dv)
            u = b[h:h + 1, :] * (v_ref[h:h + 1, :] - ah * jnp.sum(
                k * S, axis=0, keepdims=True))
            S = ah * S + k * u
        so_ref[h] = S
        o_ref[h:h + 1, :] = jnp.sum(qT[:, h:h + 1] * S, axis=0,
                                    keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(slot_of, n_live, q, k, v, log_a, b, ssm, *, interpret):
    """The step kernel as one jitted callee: a decode program holds
    layers x steps of it."""
    B, H, dk, dv = ssm.shape

    def row(*block):
        return pl.BlockSpec((None,) + block,
                            lambda i, slot: (slot[i],) + (0,) * len(block))

    gates = pl.BlockSpec((B, H), lambda i, slot: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_live,),
        in_specs=[row(H, dk), row(H, dk),
                  row(H, dk) if log_a.ndim == 3 else gates, gates,
                  row(H, dv),
                  row(H, dk, dv)],
        out_specs=[row(H, dv), row(H, dk, dv)],
    )
    # a slot's state in and out, two buffers each, as the chip tiles it
    tiled = H * round_up(dk, 8) * round_up(dv, 128) * 4
    return pl.pallas_call(
        _step_kernel,
        name="dstpu.kernel.gdn_step",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, jnp.float32)],
        # operand 0 is the scalar-prefetched list; v is 5, the leaf 6. A
        # dead slot's o row is its v row: finite, and read by nobody
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * tiled + (8 << 20)),
        interpret=interpret,
    )(slot_of, q, k, log_a, b, v, ssm)


def step_rule_kernel(q, k, v, log_a, b, ssm, live, *, interpret=None):
    """``step_rule`` on the live slots only, in place: q, k (B, H, dk), v
    (B, H, dv), b (B, H), log_a (B, H, 1) (or (B, H)) or, a gate a key
    channel, (B, H, dk); ``ssm`` (B, H, dk, dv) the layer's whole leaf,
    row b slot b's; all float32. ``live``: the step's
    :func:`live_slot_list`. -> (o (B, H, dv), the leaf): a live slot's row
    of both is ``step_rule``'s, a dead slot's state is not touched (the
    leaf is aliased in and out) and its o row is its v row."""
    if interpret is None:
        interpret = interpret_default()
    if log_a.ndim == 3 and log_a.shape[-1] == 1:
        log_a = log_a[..., 0]
    return _step_call(*live, q, k, v, log_a, b, ssm,
                      interpret=bool(interpret))
