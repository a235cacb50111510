"""Measured kernel-variant search (the dispatch layer's slow path).

Counterpart in spirit of the TVM/AlphaTensor measured-schedule-search
lineage (PAPERS.md) and of this package's engine-level ``Autotuner``:
instead of trusting hand-set defaults frozen at r05, each candidate in
``kernel_registry.REGISTRY`` is TIMED ON THE CHIP and the winner cached
per (device_kind, op, shape-bucket, dtype).

Timing method: the candidate step (fwd+bwd where the kernel is
differentiable) is chained data-dependently through ``lax.scan`` inside
ONE jit, at two chain lengths; the slope between them is the per-step
time. Rationale: a host dispatch
can cost as much as a kernel step, so anything not measured inside a
single dispatch measures the dispatch. The slope additionally cancels
jit constants and scan setup.

Every winner is parity-checked against the dense reference before it is
cached; a candidate that is fastest but numerically wrong is discarded
(next-fastest wins, ultimately the defaults).
"""

import math
import threading
import time

import jax
from jax import lax

from ..utils.logging import logger
from . import kernel_registry


def time_step(step_fn, args, chain_lengths=(8, 24), reps=3):
    """Per-step milliseconds of ``step_fn`` (pytree -> same-structure
    pytree) via the two-length scan-chain slope, best-of-``reps``."""
    k1, k2 = chain_lengths
    if not (0 < k1 < k2):
        raise ValueError(f"need 0 < k1 < k2, got {chain_lengths}")
    times = []
    for k in (k1, k2):
        def chain(a, k=k):
            def body(c, _):
                return step_fn(c), None
            out, _ = lax.scan(body, a, None, length=k)
            return out

        f = jax.jit(chain)
        jax.block_until_ready(f(args))          # compile + warm
        best = float("inf")
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            jax.block_until_ready(f(args))
            best = min(best, time.perf_counter() - t0)
        times.append(best)
    return 1e3 * (times[1] - times[0]) / (k2 - k1)


def search(op, bucket, dtype, defaults=None, chain_lengths=(8, 24),
           reps=3, validate=True):
    """Measure every candidate for (op, bucket, dtype); returns
    ``(winner_params, report)`` where report carries per-candidate
    timings. ``defaults`` (if given) is always candidate 0, so the
    fallback config is measured alongside and ``default_ms`` lands in
    the cache entry. Candidates that fail to build/compile/run are
    recorded with ``ms=inf`` (invalid configs are data, like the
    engine autotuner's OOM experiments); a winner failing the parity
    check is discarded for the next-fastest."""
    spec = kernel_registry.REGISTRY.get(op)
    if spec is None:
        raise KeyError(f"no tunable registry entry for op {op!r}")
    # Dispatch fires at TRACE time, so an on_first_use search usually
    # runs while an outer jit is mid-trace — under omnistaging every
    # jax op issued here on the SAME thread would be staged into that
    # trace (tracer args, no real timings, parity concretization
    # errors). jax trace state is thread-local, so a worker thread is a
    # clean eval context: the whole measurement runs there, eagerly and
    # jit-as-usual, on any jax version. (ensure_compile_time_eval is
    # NOT equivalent: it has no eval rule for pallas interpret-mode
    # kernels — 'program_id' — so it would silently disqualify every
    # Pallas candidate.)
    result, error = [], []

    def _run():
        try:
            result.append(_search_eager(op, bucket, dtype, spec,
                                        defaults, chain_lengths, reps,
                                        validate))
        except BaseException as e:  # noqa: BLE001 — re-raised below
            error.append(e)

    t = threading.Thread(target=_run, name=f"autotune-{op}", daemon=True)
    t.start()
    t.join()
    if error:
        raise error[0]
    return result[0]


def _search_eager(op, bucket, dtype, spec, defaults, chain_lengths,
                  reps, validate):
    b = kernel_registry.parse_bucket(bucket)
    # candidate 0 is always a COMPLETE defaults dict: callers may tune a
    # subset of an op's params (the layernorm wrapper passes only
    # block_rows), so their defaults merge over the registry's — the
    # baseline must build, or default_ms would be garbage
    base = spec["defaults"](b)
    cands = [dict(base, **{k: v for k, v in (defaults or {}).items()
                           if k in base})]
    cands.extend(spec["candidates"](b))
    cands = kernel_registry._dedup(cands)

    rows = []
    for params in cands:
        try:
            step_fn, args = spec["make_step"](b, dtype, params)
            ms = time_step(step_fn, args, chain_lengths, reps)
        except Exception as e:  # noqa: BLE001 — invalid tilings are data
            rows.append({"params": params, "ms": float("inf"),
                         "error": f"{type(e).__name__}: {e}"[:200]})
            continue
        # the two chain lengths are timed independently, so host noise
        # can drive the slope through zero on very cheap steps; clamp —
        # the sort below is stable, so among all-noise ties the
        # defaults (candidate 0) win rather than a measurement artifact
        rows.append({"params": params, "ms": max(ms, 0.0),
                     "error": None})

    ok = sorted((r for r in rows if r["error"] is None),
                key=lambda r: r["ms"])
    if not ok:
        raise RuntimeError(
            f"autotune search {op}/{bucket}/{dtype}: every candidate "
            f"failed: {[r['error'] for r in rows]}")
    winner = None
    for r in ok:
        if not validate:
            winner = r
            break
        try:
            spec["parity"](b, dtype, r["params"])
            winner = r
            break
        except Exception as e:  # noqa: BLE001
            r["error"] = f"parity: {type(e).__name__}: {e}"[:200]
            logger.warning(
                f"autotune {op}/{bucket}: discarding fastest candidate "
                f"{r['params']} — failed parity ({e})")
    if winner is None:
        raise RuntimeError(
            f"autotune search {op}/{bucket}/{dtype}: no candidate "
            f"passed the parity check")
    default_ms = rows[0]["ms"]
    if not math.isfinite(default_ms):
        default_ms = None       # keeps every artifact strict JSON
    report = {"op": op, "bucket": bucket, "dtype": dtype,
              "candidates": rows, "winner": winner["params"],
              "winner_ms": winner["ms"], "default_ms": default_ms}
    logger.info(
        f"autotune {op}/{bucket}/{dtype}: winner {winner['params']} "
        f"({winner['ms']:.3f} ms/step over {len(rows)} candidates)")
    return dict(winner["params"]), report
