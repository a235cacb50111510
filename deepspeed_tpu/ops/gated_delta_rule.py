"""The gated delta rule (Gated DeltaNet, Yang et al., arXiv 2412.06464) of
one layer, a head at a time:

    S_t = a_t S_{t-1} - a_t b_t k_t (k_t^T S_{t-1}) + b_t k_t v_t^T
    o_t = S_t^T q_t                  S (dk, dv), a_t in (0, 1], b_t in [0, 2]

``chunk_rule`` is the chunkwise-parallel form (arXiv 2406.06484, section 3,
with the gate of 2412.06464, section 3.3) for the T tokens of a prefill or
a chunk program, ``step_rule`` the one-token update of a decode step. The
two here are plain XLA: batched products over (batch, head, chunk) and one
``lax.scan`` over the chunks, in which only what depends on the state is
left. They are what a dense forward runs and the reference of the two
Pallas kernels of ``ops/pallas/gated_delta_rule.py``, which a paged step
that runs kernels takes instead (``models/olmo_hybrid.py``): the same
mathematics at the same precision with the state kept in VMEM. A token
whose ``log_a`` is 0 and ``b`` is 0 (a chunk's padding) leaves the state as
it was.

The chunkwise form is exact algebra. With ``u_t = b_t (v_t - a_t S_{t-1}^T
k_t)`` the rule is ``S_t = a_t S_{t-1} + k_t u_t^T``; inside a chunk of L
tokens that starts from S, with ``c_t = sum_{s <= t} log a_s`` and ``G_ts =
exp(c_t - c_s)`` (t >= s: never over 1, so no decay is ever divided by):

    A = tril(diag(b) (K K^T * G), -1)         T = (I + A)^-1
    U = T diag(b) V - (T diag(b exp c) K) S                    (L, dv)
    O = diag(exp c) Q S + tril(Q K^T * G) U                    (L, dv)
    S <- exp(c_L) S + (diag(exp(c_L - c)) K)^T U

**A gate a key channel** (Kimi Delta Attention, arXiv 2510.26692): ``a_t``
a (dk,) vector, ``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t
v_t^T``. ``log_a``'s trailing axis says which rule it is, at trace time: 1
(or no such axis) is the scalar gate above, dk this one; nothing else
chooses. ``c_t`` is then a (dk,) vector and the algebra holds with two
replacements: ``exp(c)`` scales K's or Q's row channel by channel
wherever it stood, and ``(X K^T) * G`` (X = K and X = Q), which no longer
factors, becomes the pairwise-decayed product

    P[X]_ts = sum_d X_td K_sd exp(c_td - c_sd)             (t >= s)

made with no decay divided by (:func:`_pairwise_decayed`): the chunk's
rows in sub-blocks of ``_BASE``; a row block i against the rows before it,
with r the block's first row, is the MXU product ``(X_i * exp(c_i - c_r))
(K_<i * exp(c_r - c_<i))^T``, every exponent <= 0; a diagonal block is
summed pair by pair, (16, 16, dk). The inverse, U, O and the state's
update are shared.

``I + A`` is unit lower triangular. Its inverse is taken as a triangular
solve would take it, never as the series ``sum_i (-A)^i``: keys that repeat
make entries of A near ``b`` and the series' terms grow as ``(b L)^i / i!``
before they cancel, which float32 does not survive. Diagonal blocks of at
most ``_BASE`` rows are solved by forward substitution, a row a step; two
solved halves join as ``[[X11, 0], [-X22 A21 X11, X22]]``, two products a
level, L = 64 in two levels.
"""

import jax.numpy as jnp
from jax import lax

CHUNK = 64
_BASE = 16          # rows of a diagonal block solved by substitution
# float32 operands on the MXU: HIGHEST is six bfloat16 passes and float32
# to the last bits; DEFAULT is one pass on operands rounded to bfloat16.
# The inverse and what carries the state keep every bit (a rounded T is a
# wrong rule for the whole chunk, a rounded S one that later chunks
# inherit); see PERF.md, PR 41, for what each costs on the chip.
EXACT = lax.Precision.HIGHEST


def _unit_lower_inverse(A):
    """(I + A)^-1 for strictly lower triangular A (..., n, n), n even
    wherever it is over ``_BASE``."""
    n = A.shape[-1]
    if n <= _BASE:
        eye = jnp.eye(n, dtype=A.dtype)
        rows = [jnp.broadcast_to(eye[0], A.shape[:-1])]
        for i in range(1, n):       # row i from the rows above it
            rows.append(eye[i] - sum(A[..., i, j, None] * rows[j]
                                     for j in range(i)))
        return jnp.stack(rows, axis=-2)
    h = n // 2
    X11, X22 = _unit_lower_inverse(
        jnp.stack([A[..., :h, :h], A[..., h:, h:]]))
    X21 = -jnp.matmul(jnp.matmul(X22, A[..., h:, :h], precision=EXACT), X11,
                      precision=EXACT)
    return jnp.concatenate(
        [jnp.concatenate([X11, jnp.zeros_like(X11)], axis=-1),
         jnp.concatenate([X21, X22], axis=-1)], axis=-2)


def _pairwise_decayed(x, k, c):
    """``P[X]_ts = sum_d X_td K_sd exp(c_td - c_sd)`` for t >= s, 0 above
    the diagonal: x, k, c (..., L, dk), L a multiple of ``_BASE``, c
    non-increasing along L. -> (..., L, L)."""
    L = x.shape[-2]
    t, s = jnp.arange(_BASE)[:, None], jnp.arange(_BASE)[None, :]
    rows = []
    for r in range(0, L, _BASE):
        xi, ci = x[..., r:r + _BASE, :], c[..., r:r + _BASE, :]
        # the diagonal block, pair by pair: exp only where t >= s
        gap = ci[..., :, None, :] - ci[..., None, :, :]
        keep = (t >= s)[..., None]
        diag = jnp.sum(jnp.where(keep, jnp.exp(jnp.where(keep, gap, 0.0))
                                 * xi[..., :, None, :]
                                 * k[..., None, r:r + _BASE, :], 0.0),
                       axis=-1)
        cr = c[..., r:r + 1, :]
        left = jnp.einsum("...td,...sd->...ts", xi * jnp.exp(ci - cr),
                          k[..., :r, :] * jnp.exp(cr - c[..., :r, :]),
                          precision=EXACT)
        rows.append(jnp.concatenate(
            [left, diag, jnp.zeros(x.shape[:-2] + (_BASE, L - r - _BASE),
                                   x.dtype)], axis=-1))
    return jnp.concatenate(rows, axis=-2)


def chunk_rule(q, k, v, log_a, b, state):
    """q, k (B, T, H, dk), v (B, T, H, dv), b (B, T, H), state (B, H, dk,
    dv); log_a (B, T, H, 1) (or (B, T, H)): a gate a head, (B, T, H, dk):
    a gate a key channel; all float32. -> (o (B, T, H, dv), the state
    after token T - 1)."""
    B, T, H, _ = q.shape
    if log_a.ndim == b.ndim:
        log_a = log_a[..., None]
    per_channel = log_a.shape[-1] != 1
    L = min(CHUNK, -(-T // _BASE) * _BASE)
    N = -(-T // L)
    pad = N * L - T

    def chunks(x):
        """(B, T, H, ...) -> (B, H, N, L, ...), zeros after T."""
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((B, N, L) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, log_a, b = (chunks(x) for x in (q, k, v, log_a, b))
    c = jnp.cumsum(log_a, axis=-2)                  # (B, H, N, L, 1 | dk)
    t, s = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    if per_channel:
        qk = _pairwise_decayed(q, k, c)
        A = jnp.where(t > s, b[..., None] * _pairwise_decayed(k, k, c), 0.0)
    else:
        # exp only where t >= s: above the diagonal c_t - c_s is positive
        # and may overflow
        G = jnp.where(t >= s, jnp.exp(jnp.where(
            t >= s, c - jnp.swapaxes(c, -1, -2), 0.0)), 0.0)
        kk = jnp.einsum("bhnld,bhnmd->bhnlm", k, k, precision=EXACT)
        A = jnp.where(t > s, b[..., None] * kk * G, 0.0)
        qk = jnp.einsum("bhnld,bhnmd->bhnlm", q, k, precision=EXACT) * G
    Tm = _unit_lower_inverse(A)
    ec = jnp.exp(c)
    rhs = jnp.concatenate([k * (b[..., None] * ec), v * b[..., None]],
                          axis=-1)
    wu = jnp.matmul(Tm, rhs, precision=EXACT)       # [T b e^c K | T b V]
    W, U0 = wu[..., :k.shape[-1]], wu[..., k.shape[-1]:]
    k_out = k * jnp.exp(c[..., -1:, :] - c)
    a_chunk = jnp.exp(c[..., -1, :])                # (B, H, N, 1 | dk)

    def advance(S, xs):
        W, U0, qk, q_in, k_out, a_chunk = xs
        U = U0 - jnp.matmul(W, S, precision=EXACT)
        o = jnp.matmul(q_in, S, precision=EXACT) \
            + jnp.matmul(qk, U, precision=EXACT)
        S = a_chunk[..., None] * S + jnp.einsum(
            "bhld,bhlv->bhdv", k_out, U, precision=EXACT)
        return S, o

    # the scan runs over N: put it first
    xs = tuple(jnp.moveaxis(x, 2, 0)
               for x in (W, U0, qk, q * ec, k_out, a_chunk))
    state, o = lax.scan(advance, state, xs)
    o = jnp.moveaxis(o, 0, 2)                               # (B, H, N, L, dv)
    o = jnp.moveaxis(o, 1, 3).reshape(B, N * L, H, -1)
    return o[:, :T], state


def step_rule(q, k, v, log_a, b, state):
    """One token a row: q, k (B, H, dk), v (B, H, dv), b (B, H), log_a (B,
    H, 1) (or (B, H)) or, a gate a key channel, (B, H, dk); state (B, H,
    dk, dv) -> (o (B, H, dv), state). Elementwise and sums, float32
    throughout: the state is read once and written once."""
    if log_a.ndim == b.ndim:
        log_a = log_a[..., None]
    a = jnp.exp(log_a)
    if a.shape[-1] != 1:
        state = a[..., None] * state
        u = b[..., None] * (v - jnp.sum(k[..., None] * state, axis=-2))
        state = state + k[..., None] * u[..., None, :]
        return jnp.sum(q[..., None] * state, axis=-2), state
    u = b[..., None] * (v - a * jnp.sum(k[..., None] * state, axis=-2))
    state = a[..., None] * state + k[..., None] * u[..., None, :]
    return jnp.sum(q[..., None] * state, axis=-2), state
