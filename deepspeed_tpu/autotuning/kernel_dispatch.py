"""Autotune dispatch state: mode + winner cache, consulted at trace time.

``ops/pallas/_common.dispatch`` routes here. The state is process-global
(kernel choice must be consistent across every trace in a process) and
is configured by the engine from the ``autotune`` config block, by
``configure()`` directly, or by env:

  DSTPU_AUTOTUNE        off | cache_only | on_first_use | search
                        (default cache_only: a shipped cache activates,
                        no cache file means the r05 defaults — zero
                        behavior change)
  DSTPU_AUTOTUNE_CACHE  cache file path (default
                        ~/.cache/deepspeed_tpu/kernel_autotune.json)

Modes:
  off          never consult the cache; every "auto" tunable takes its
               hand-set default
  cache_only   use cached winners, NEVER search (production: a cold key
               silently falls back to defaults)
  on_first_use cache hit wins; a miss triggers a measured search for
               that (op, shape-bucket, dtype) right then — once per
               process — and persists the winner
  search       re-measure every key once per process even if cached
               (cache pre-warming / re-validation after a toolchain
               bump), persisting the new winners

Resolution is memoized per process, so after the first trace each
dispatch is a dict lookup; the compiled program carries only the chosen
constants (zero per-step host work).
"""

import os

from ..utils.logging import logger
from .kernel_cache import KernelCache, default_cache_path

MODES = ("off", "cache_only", "on_first_use", "search")
MODE_ENV = "DSTPU_AUTOTUNE"

_STATE = {
    "mode": None,          # None -> env/default at use time
    "cache_path": None,    # None -> env/default at use time
    "cache": None,         # lazily loaded KernelCache
    "resolved": {},        # key -> winner params (or None for miss)
    "reports": {},         # key -> last search report
    "chain_lengths": (8, 24),
    "reps": 3,
    "searching": False,    # re-entrancy guard: a search never searches
}


def configure(mode=None, cache_path=None, chain_lengths=None, reps=None):
    """Set the process-global autotune state; None keeps env/default
    resolution for that field. Clears the memo and the loaded cache so
    new settings apply to subsequent traces."""
    if mode is not None:
        if mode not in MODES:
            raise ValueError(
                f"autotune mode must be one of {MODES}, got {mode!r}")
        _STATE["mode"] = mode
    if cache_path is not None:
        _STATE["cache_path"] = cache_path or None
    if chain_lengths is not None:
        k1, k2 = chain_lengths
        _STATE["chain_lengths"] = (int(k1), int(k2))
    if reps is not None:
        _STATE["reps"] = int(reps)
    _STATE["cache"] = None
    _STATE["resolved"] = {}


def configure_from_config(cfg):
    """Engine hook: apply the ``autotune`` config block
    (runtime/config.py AutotuneConfig) as the COMPLETE new state —
    empty-string fields revert to env/default resolution rather than
    keeping a previous engine's explicit setting (two engines in one
    process must not leak modes or cache paths into each other)."""
    if cfg.mode and cfg.mode not in MODES:
        raise ValueError(
            f"autotune mode must be one of {MODES}, got {cfg.mode!r}")
    _STATE["mode"] = cfg.mode or None
    _STATE["cache_path"] = cfg.cache_path or None
    _STATE["chain_lengths"] = tuple(int(k) for k in cfg.chain_lengths)
    _STATE["reps"] = int(cfg.reps)
    _STATE["cache"] = None
    _STATE["resolved"] = {}


def configure_serving(mode="", cache_path=""):
    """v2-engine hook: apply mode + cache path as the COMPLETE new
    state (empty string = revert that field to env/default resolution),
    preserving the search timing knobs — the serving counterpart of
    ``configure_from_config``, with the same complete-state contract:
    each engine's construction (and, for the v2 engine, each of its
    program traces) owns the process dispatch state; explicit modes or
    cache paths never leak between engines.

    No-op when the target state is already installed, so the v2
    engine's per-trace re-install keeps the resolution memo and the
    loaded cache — search mode still measures once per process, and
    the cache file is not re-read per trace."""
    if mode and mode not in MODES:
        raise ValueError(
            f"autotune mode must be one of {MODES}, got {mode!r}")
    new_mode, new_path = mode or None, cache_path or None
    if (_STATE["mode"] == new_mode
            and _STATE["cache_path"] == new_path):
        return
    _STATE["mode"] = new_mode
    _STATE["cache_path"] = new_path
    _STATE["cache"] = None
    _STATE["resolved"] = {}


def reset():
    """Back to pristine env-driven state (tests)."""
    _STATE.update(mode=None, cache_path=None, cache=None, resolved={},
                  reports={}, chain_lengths=(8, 24), reps=3,
                  searching=False)


def current_mode():
    if _STATE["mode"] is not None:
        return _STATE["mode"]
    env = os.environ.get(MODE_ENV, "cache_only")
    if env not in MODES:
        logger.warning(f"{MODE_ENV}={env!r} is not one of {MODES}; "
                       f"using cache_only")
        return "cache_only"
    return env


def cache_path():
    return _STATE["cache_path"] or default_cache_path()


def device_kind():
    """The chip the process computes on — part of every cache key, so
    interpret-mode (CPU) winners can never steer a real TPU."""
    import jax
    return jax.devices()[0].device_kind


def _cache():
    if _STATE["cache"] is None:
        _STATE["cache"] = KernelCache.load(cache_path())
    return _STATE["cache"]


def resolve(op, bucket, dtype, defaults):
    """Winner params for (device_kind, op, bucket, dtype) under the
    active mode, merged over ``defaults``; plain ``defaults`` on any
    miss/refusal. Only keys present in ``defaults`` are returned, so a
    caller tuning a subset of an op's parameters gets exactly its own
    knobs back."""
    mode = current_mode()
    defaults = dict(defaults)
    if mode == "off" or _STATE["searching"]:
        return defaults
    from .kernel_cache import entry_key
    dk = device_kind()
    key = entry_key(dk, op, bucket, str(dtype))
    if key in _STATE["resolved"]:
        winner = _STATE["resolved"][key]
    else:
        winner = None
        if mode != "search":
            winner = _cache().lookup(dk, op, bucket, str(dtype))
        if winner is None and mode in ("on_first_use", "search"):
            winner = _search_and_store(op, bucket, str(dtype), defaults,
                                       dk, key)
        _STATE["resolved"][key] = winner
    if winner is None:
        return defaults
    return {**defaults,
            **{k: v for k, v in winner.items() if k in defaults}}


def _search_and_store(op, bucket, dtype, defaults, dk, key):
    from . import kernel_autotuner, kernel_registry
    if op not in kernel_registry.REGISTRY:
        return None
    _STATE["searching"] = True
    try:
        winner, report = kernel_autotuner.search(
            op, bucket, dtype, defaults=defaults,
            chain_lengths=_STATE["chain_lengths"], reps=_STATE["reps"])
    except Exception as e:  # noqa: BLE001 — tuning must degrade, not crash
        logger.warning(f"autotune search failed for {key}: "
                       f"{type(e).__name__}: {e}; using defaults")
        return None
    finally:
        _STATE["searching"] = False
    _STATE["reports"][key] = report
    cache = _cache()
    cache.put(dk, op, bucket, dtype, winner,
              measured_ms=report["winner_ms"],
              default_ms=report["default_ms"],
              candidates=len(report["candidates"]))
    try:
        cache.save(cache_path())
    except OSError as e:
        logger.warning(f"autotune cache save to {cache_path()!r} "
                       f"failed: {e} (winner still applies in-process)")
    return winner


def table():
    """The tuned table for the CURRENT device kind, for an artifact to
    embed so winners travel with the measurements. Reads the cache FILE fresh: searches from earlier engines in this
    process have persisted there, and the in-memory view may predate
    them."""
    return KernelCache.load(cache_path()).for_device(device_kind())
