"""Shared Pallas kernel helpers (counterpart of reference
``csrc/includes/`` — the template library every CUDA kernel includes),
plus the measured-dispatch layer: kernel wrappers whose tunable
parameters are set to ``"auto"`` resolve them here against the
persistent autotune winner cache (autotuning/kernel_dispatch.py) at
TRACE time — the chosen variant is baked into the jitted program, so a
warm cache costs zero per-step host work.
"""

import contextlib
import contextvars

import jax

# sentinel a kernel tunable takes to mean "resolve via the autotune
# winner cache" (models pass their config knobs through verbatim)
AUTO = "auto"


# the tally a ``counting_calls`` block is filling, if any
_CALLS = contextvars.ContextVar("dstpu_calls", default=None)


@contextlib.contextmanager
def counting_calls():
    """Yields ``{name: [calls, kernel_calls]}``: the calls traced inside the
    block of each mechanism that has a Pallas form and another (``"expert"``:
    an MoE layer's SwiGLU chain; ``"rule"``: the gated delta rule, either
    form; ``"latent_read"``: a latent cache's selected read), and those of
    them that took the kernel (:func:`note_call` says which); and of
    ``"flash"``, a kernel either way, the calls and those whose values have
    a width of their own. Trace-time Python: a serving engine puts it round
    a program's traced body, for its dispatch span (``<name>_calls`` /
    ``<name>_kernel_calls``), the training engine round its step's, for
    its log."""
    counts = {}
    token = _CALLS.set(counts)
    try:
        yield counts
    finally:
        _CALLS.reset(token)


def note_call(name, kernel):
    """A traced body's word that it makes one call of ``name`` here, through
    a Pallas kernel or not (``"flash"``: with two head widths or one)."""
    counts = _CALLS.get()
    if counts is not None:
        pair = counts.setdefault(name, [0, 0])
        pair[0] += 1
        pair[1] += bool(kernel)


def dispatch(op, bucket, dtype, defaults):
    """Trace-time tunable resolution for kernel ``op``.

    Consults the autotune winner cache for
    (device_kind, op, shape-bucket, dtype) under the active autotune
    mode (runtime config ``autotune`` block / DSTPU_AUTOTUNE env):
    returns the cached winner's params merged over ``defaults``, runs a
    measured search first in the search modes, and falls back to
    ``defaults`` (the r05-proven hand-set values) on any miss/refusal.
    Pure Python at trace time — nothing here survives into the compiled
    program but the chosen constants."""
    from ...autotuning import kernel_dispatch
    return kernel_dispatch.resolve(op, bucket, dtype, defaults)


def dtype_name(dtype):
    """Canonical dtype string for cache keys ('bfloat16', 'float32')."""
    import jax.numpy as jnp
    return jnp.dtype(dtype).name


# ----------------------------------------------------- shape buckets
# One bucket string per op keys the winner cache: exact in the dims
# that pick kernel variants (feature/head/vocab dims — they gate block
# validity), power-of-two-rounded in the data-volume dims (tokens,
# rows) so nearby batch shapes share a winner instead of each paying a
# search.

def pow2_bucket(n):
    """Round ``n`` up to the next power of two (>= 1)."""
    return 1 << max(0, int(n) - 1).bit_length()


def flash_bucket(T, d, causal, qkv_t):
    return f"T{pow2_bucket(T)},d{int(d)},c{int(bool(causal))}," \
           f"q{int(bool(qkv_t))}"


def mlp_bucket(T, D, F):
    return f"T{pow2_bucket(T)},D{int(D)},F{int(F)}"


def ln_bucket(rows, D):
    return f"R{pow2_bucket(rows)},D{int(D)}"


def ring_bucket(T, d):
    """Ring-attention chunk-pair bucket: T is the per-step CHUNK length
    (T_global / (2 * ring) under zigzag), not the full sequence."""
    return f"T{pow2_bucket(T)},d{int(d)}"


def ce_bucket(N, D, V):
    return f"N{pow2_bucket(N)},D{int(D)},V{int(V)}"


def moe_grouped_bucket(S, E, M, F):
    """Grouped expert-FFN bucket: tokens-per-shard (rows entering the
    grouped product, incl. the k-replication) pow2-rounded; local expert
    count and model/FFN dims exact (they gate block validity and the
    kernel-vs-ragged crossover)."""
    return f"S{pow2_bucket(S)},E{int(E)},M{int(M)},F{int(F)}"


def paged_decode_bucket(B, MB, BS, KVH, G, d):
    """Serving decode-shape bucket: batch slots and blocks-per-seq
    pow2-rounded (nearby batch mixes share a winner); block size,
    kv-head count, GQA group and head dim exact (they gate kernel-block
    validity and the GQA fold)."""
    return f"B{pow2_bucket(B)},MB{pow2_bucket(MB)},BS{int(BS)}," \
           f"kh{int(KVH)},g{int(G)},d{int(d)}"


def pipe_bucket(S, B, T, D):
    """Pipeline-step bucket: stage count exact (it sets the tick count
    and the candidate microbatch grid), per-stage batch rows
    pow2-rounded, sequence pow2-rounded, model width exact (it gates
    the per-tick block cost)."""
    return f"S{int(S)},B{pow2_bucket(B)},T{pow2_bucket(T)},D{int(D)}"


def paged_chunk_bucket(C, MB, BS, KVH, G, d):
    """SplitFuse chunk-shape bucket: the chunk length C is exact (it
    gates block_c validity — one compiled chunk program per engine
    config anyway), blocks-per-seq pow2-rounded."""
    return f"C{int(C)},MB{pow2_bucket(MB)},BS{int(BS)}," \
           f"kh{int(KVH)},g{int(G)},d{int(d)}"


# ------------------------------------------- collective-op buckets
# Collective-bearing ops (autotuning/collective_ops.py) are winners per
# (device_kind, TOPOLOGY-SIGNATURE, shape-bucket): the mesh shape is
# folded into the bucket STRING itself, so the cache file format and the
# device-kind refusal rule are untouched — a winner measured on a
# dp=4,do=2 mesh can never steer a dp=8 flat mesh, exactly as a T=1024
# flash winner never steers T=128.

def topo_signature(mesh=None):
    """Compact mesh signature for collective bucket strings:
    'pp1,do1,dp4,ep1,sp1,tp1' (every axis exact — each size changes the
    collective's replica groups, so no two topologies may share a
    winner). Falls back to the all-ones signature when no topology has
    been initialized (single-chip/virtual runs)."""
    shape = {}
    if mesh is not None:
        shape = dict(mesh.shape)
    else:
        try:
            from ...utils import groups
            shape = dict(groups.get_mesh().shape)
        except Exception:  # noqa: BLE001 — pre-topology trace
            shape = {}
    g = lambda a: int(shape.get(a, 1))
    return (f"pp{g('pipe')},do{g('data_outer')},dp{g('data')},"
            f"ep{g('expert')},sp{g('seq')},tp{g('tensor')}")


def grad_comm_bucket(layer_mb, mesh=None):
    """Gradient-collective bucket (ops comm_bucket / grad_staging /
    dcn_quantize): topology signature + the per-layer gradient payload
    in MB, pow2-rounded (nearby layer sizes share a winner)."""
    return f"{topo_signature(mesh)},L{pow2_bucket(max(1, layer_mb))}"


def a2a_bucket(tokens, M, mesh=None):
    """Expert all_to_all bucket (op a2a_staging): topology signature +
    tokens-per-shard pow2-rounded + model width exact (it sets the
    payload row size the staged exchange re-buckets)."""
    return f"{topo_signature(mesh)},S{pow2_bucket(max(1, tokens))}," \
           f"M{int(M)}"


def ring_rotate_bucket(R, chunk, d, mesh=None):
    """Ring KV-rotation bucket (op ring_rotate): ring size exact (it is
    the perm), per-step chunk length pow2-rounded, head dim exact."""
    return f"{topo_signature(mesh)},R{int(R)},T{pow2_bucket(chunk)}," \
           f"d{int(d)}"


def scan_unroll_bucket(n_layer, D, mesh=None):
    """Layer-scan unroll bucket (op scan_unroll): layer count and model
    width exact — they set how much compute one unrolled body gives the
    prefetch gather to hide under."""
    return f"{topo_signature(mesh)},N{int(n_layer)},D{int(D)}"


def hot_replicas_bucket(shard_mb, mesh=None):
    """Hot-tier replication bucket (op hot_replicas): topology signature
    + per-host checkpoint shard payload in MB, pow2-rounded."""
    return f"{topo_signature(mesh)},G{pow2_bucket(max(1, shard_mb))}"


def interpret_default():
    """Kernels run in Pallas interpreter mode off-TPU (unit tests, the
    virtual CPU mesh)."""
    return jax.default_backend() != "tpu"


def sds(shape, dtype, like):
    """ShapeDtypeStruct whose varying-manual-axes match ``like`` — required
    when a kernel runs inside a shard_map region (e.g. quantized
    collectives, pipelined blocks)."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def dividing_axes(n, axes):
    """``axes`` (a mesh axis name or tuple of names) if their ambient-mesh
    size divides ``n``, else None. shard_map needs even shards where GSPMD
    would pad, so a kernel operand whose dim does not divide is replicated
    over those axes instead."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return axes
    size = 1
    for a in (axes,) if isinstance(axes, str) else axes:
        size *= mesh.shape[a]
    return axes if n % size == 0 else None


def _auto_axes():
    """(ambient mesh, its auto axes): the axes GSPMD partitions over."""
    from jax.sharding import AxisType
    mesh = jax.sharding.get_abstract_mesh()
    return mesh, () if mesh.empty else tuple(
        a for a, t in zip(mesh.axis_names, mesh.axis_types)
        if t == AxisType.Auto)


def gspmd_partitioned():
    """Whether GSPMD partitions the program being traced: an ambient mesh
    with an auto axis of size > 1. A bare Mosaic call is refused there
    (``shard_kernel``). False with no mesh, on one device, and inside a
    fully-manual region."""
    mesh, auto = _auto_axes()
    return any(mesh.shape[a] > 1 for a in auto)


def shard_kernel(fn, in_specs, out_specs):
    """``fn`` — a function that runs Pallas kernels — made legal in a
    GSPMD-partitioned program. A Mosaic custom call cannot be partitioned
    automatically (the TPU lowering refuses: "wrap the call in a
    shard_map"), which interpret mode on a CPU mesh never shows. Under an
    ambient mesh with auto axes of size > 1 the call runs per shard over
    those axes; with no mesh, one device, or inside a fully-manual region
    it is ``fn`` itself."""
    if not gspmd_partitioned():
        return fn
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         axis_names=frozenset(_auto_axes()[1]),
                         check_vma=False)


def round_up(n, m):
    return -(-n // m) * m
