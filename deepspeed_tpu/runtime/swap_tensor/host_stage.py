"""In-program host staging: the reference's ``swap_tensor`` tier for
values that live INSIDE a jitted program.

The reference's swap layer moves tensors out of device memory
imperatively (AsyncTensorSwapper -> AIO -> NVMe, swapper.py in this
package); under XLA the same capability for in-program values is a
memory-space annotation: ``jax.device_put`` onto the host memory kind
inside jit stages the value out of HBM, and XLA's host-offload pass
legalizes the dynamic-update-slice / gather traffic into async
``copy-start``/``copy-done`` pairs the latency-hiding scheduler can
overlap (the reference overlaps its D2H with compute through CUDA
streams; here the compiler owns the schedule). The pipeline executors
(runtime/pipe/spmd.py) use this to keep their activation rings — the
``activation_checkpointing`` CPU-checkpoint trade — in host RAM, and the
engine uses the same memory kind for optimizer-moment placement.

Platform contract (jax 0.9.0):

  * TPU lists ``pinned_host`` beside its default ``device`` memory and
    its compiler implements the placement: ``available()`` is True,
    ``to_host`` moves bytes, shardings take the host memory kind.
  * The CPU backend ALSO lists ``pinned_host``/``unpinned_host`` beside
    ``device``, but XLA:CPU compiles no placement: an in-program
    ``device_put`` to the host space is an identity and a host memory
    kind on ``out_shardings`` is refused ("No registered implementation
    for ... annotate_device_placement"). It is one memory space in
    practice, so there ``host_memory_kind()`` is None, ``available()`` is
    False, every transfer is an identity, callers gate structural
    assertions on it and 'auto' knobs resolve off.
"""

import functools

import jax
from jax.memory import Space

from ...utils.logging import logger


@functools.lru_cache(maxsize=None)
def memory_kinds():
    """(default_kind, host_kind): the default device memory kind and the
    host-side kind programs can be placed in, or None for the latter on
    a platform with one memory space in practice (see the module
    docstring). Cached — backend memories are fixed per process."""
    dev = jax.devices()[0]
    default = dev.default_memory().kind
    if dev.platform == "cpu":
        return default, None
    kinds = {m.kind for m in dev.addressable_memories()}
    for host in ("pinned_host", "unpinned_host"):
        if host in kinds and host != default:
            return default, host
    return default, None


def host_memory_kind():
    """The host memory kind offload targets, or None when the platform
    has a single memory space (offload degenerates to identity)."""
    return memory_kinds()[1]


def available():
    """True iff host staging actually moves bytes on this backend."""
    return host_memory_kind() is not None


def to_host(x):
    """Stage ``x`` into host memory (identity when the platform has no
    distinct host space — the CPU test mesh). Usable inside jit and
    inside shard_map manual regions (memory kinds are orthogonal to
    sharding)."""
    if host_memory_kind() is None:
        return x
    return jax.device_put(x, Space.Host)


def to_device(x):
    """Bring a host-staged value back to device memory (identity when
    staging is unavailable)."""
    if host_memory_kind() is None:
        return x
    return jax.device_put(x, Space.Device)


def with_host_memory_kind(sharding):
    """``sharding`` re-targeted at the host memory kind (for optimizer
    moments and other engine-owned state); the original sharding when
    staging is unavailable (with a one-time note, not an error — the
    knob is advisory on single-memory-space platforms)."""
    kind = host_memory_kind()
    if kind is None:
        _warn_unavailable()
        return sharding
    return sharding.with_memory_kind(kind)


_warned = False


def _warn_unavailable():
    global _warned
    if not _warned:
        _warned = True
        logger.warning(
            "host offload requested but this backend exposes a single "
            "memory space (no distinct host memory kind); offload "
            "annotations degrade to identity")
