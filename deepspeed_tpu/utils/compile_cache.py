"""JAX persistent compilation cache, placeable from outside.

Runnable entry points (``chip_smoke.py``, ``perfbench/run.py``, the
``benchmarks/`` scripts) call :func:`enable_compile_cache` before their first compile.
Nothing in the package calls it — not at import, not from an engine
constructor — so library users and the test suite keep JAX's own default
(no persistent cache).

The directory is part of the cache key's neighbourhood: a cache that moves
never hits. So the only two places it can live are the one the operator
names in ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself;
this module then sets no directory in code) and one fixed path inside the
checkout, ``<checkout>/.cache/jax`` — never a temporary name, a pid or a
time stamp.
"""

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".cache", "jax")


def enable_compile_cache():
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: the directory is left to JAX.
    Unset: ``<checkout>/.cache/jax``. Either way the size and
    compile-time thresholds are dropped to zero, so the small serving
    programs (one per prompt bucket) are cached along with the big
    training step."""
    import jax
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
