"""jnp-vs-Pallas microbenchmarks for the v1 serving kernel tier.

The reference ships fused CUDA kernels for rmsnorm and rotary embedding
(csrc/transformer/inference/csrc/{rms_norm,apply_rotary_pos_emb}.cu);
this repo's serving models use jnp forms and claims XLA fuses them well.
This bench MEASURES that claim on the chip: per-op device time for jnp
vs the Pallas alternative at serving shapes, using the slope method
(time K chained applications inside ONE jit for two K values; the slope
removes dispatch latency and jit constants). Prints one JSON line per
comparison.

Run: python benchmarks/kernel_microbench.py

``--from-cache [path]``: instead of the serving-tier sweep, re-time
exactly the cached autotune winners (autotuning/kernel_cache.py) for
THIS chip with the same slope harness the search used — a one-command
verification that a shipped cache's timings still hold (after a
toolchain bump, on a new chip batch, ...). Prints one JSON row per
entry with the fresh measurement next to the cached one.
"""

import json
import sys
import os
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from deepspeed_tpu.models.llama import _rms_norm, _rope  # noqa: E402
from deepspeed_tpu.ops.pallas.layernorm import fused_rmsnorm  # noqa: E402


def timed_chain(op, x, k, reps=3):
    """Wall time of K data-dependent applications inside one jit."""
    def chain(x):
        def body(c, _):
            return op(c), None
        y, _ = lax.scan(body, x, None, length=k)
        return jnp.sum(y.astype(jnp.float32))

    f = jax.jit(chain)
    np.asarray(f(x))                       # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        r = f(x)
    np.asarray(r)
    return (time.perf_counter() - t0) / reps


def per_op_ms(op, x, k1=64, k2=512):
    """Slope between two chain lengths -> per-op seconds (dispatch and
    scan constants cancel)."""
    t1 = min(timed_chain(op, x, k1) for _ in range(3))
    t2 = min(timed_chain(op, x, k2) for _ in range(3))
    return 1e3 * (t2 - t1) / (k2 - k1)


def retime_from_cache(path=None, chain_lengths=(8, 24), reps=3):
    """Re-measure every cached winner for the current device; returns
    the printed rows. A winner whose step can no longer build/run is
    reported with an error instead of aborting the sweep."""
    from deepspeed_tpu.autotuning import (KernelCache, kernel_dispatch,
                                          kernel_registry)
    from deepspeed_tpu.autotuning.kernel_autotuner import time_step
    path = path or kernel_dispatch.cache_path()
    cache = KernelCache.load(path)
    entries = cache.for_device(kernel_dispatch.device_kind())
    rows = []
    if not entries:
        rows.append({"cache": path, "note": "no cached winners for "
                     f"device {kernel_dispatch.device_kind()!r}"})
    for key, e in sorted(entries.items()):
        row = {"op": e.get("op"), "bucket": e.get("bucket"),
               "dtype": e.get("dtype"), "params": e.get("params"),
               "cached_ms": e.get("measured_ms"),
               "cached_default_ms": e.get("default_ms")}
        spec = kernel_registry.REGISTRY.get(e.get("op"))
        if spec is None:
            row["error"] = f"unknown op {e.get('op')!r}"
        else:
            try:
                step, args = spec["make_step"](
                    kernel_registry.parse_bucket(e["bucket"]),
                    e["dtype"], e["params"])
                row["retimed_ms"] = round(
                    time_step(step, args, chain_lengths, reps), 4)
            except Exception as ex:  # noqa: BLE001 — sweep must finish
                row["error"] = f"{type(ex).__name__}: {ex}"[:200]
        rows.append(row)
    for r in rows:
        print(json.dumps(r))
    return rows


def main():
    if "--from-cache" in sys.argv:
        i = sys.argv.index("--from-cache")
        path = sys.argv[i + 1] if len(sys.argv) > i + 1 \
            and not sys.argv[i + 1].startswith("-") else None
        retime_from_cache(path)
        return
    B, T, H, hd = 8, 1024, 16, 64
    D = H * hd
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, T, D), jnp.bfloat16)
    s = jnp.asarray(1 + 0.1 * rng.randn(D), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

    rows = []
    jnp_ms = per_op_ms(lambda c: _rms_norm(c, s, 1e-5), x)
    pal_ms = per_op_ms(lambda c: fused_rmsnorm(c, s), x)
    rows.append({"op": "rmsnorm", "shape": [B, T, D],
                 "jnp_ms": round(jnp_ms, 4), "pallas_ms": round(pal_ms, 4),
                 "winner": "jnp" if jnp_ms <= pal_ms else "pallas"})

    xh = x.reshape(B, T, H, hd)
    rope_ms = per_op_ms(
        lambda c: _rope(c, pos, 10000.0), xh)
    rows.append({"op": "rope", "shape": [B, T, H, hd],
                 "jnp_ms": round(rope_ms, 4), "pallas_ms": None,
                 "winner": "jnp",
                 "note": "no Pallas variant: rope is pure elementwise "
                         "(sin/cos fused by XLA into neighbors); a "
                         "custom call could only break that fusion"})
    for r in rows:
        print(json.dumps(r))


if __name__ == "__main__":
    main()
