#!/usr/bin/env python3
"""``aot.py`` for a serving cell whose model keeps state by batch slot (PR 30:
``models/phi4flash.py``, ``slot_state``): compiles the cell's programs at
real size for a described TPU v5e, with no chip attached, and prints
``memory_analysis()`` of each and the cache's leaves. Costs no chip time.

    JAX_PLATFORMS=cpu python3 perfbench/aot_slots.py --workload <name>

``aot.py`` cannot build such a cache: it hands ``init_paged_cache`` a block
count and nothing else, where this model's rings and recurrent state are
sized by the slots (the traffic file's ``max_batch_size``) and by the ring
blocks the engine derives from its own programs (``models/paged.py``
``ring_blocks``: window + the largest chunk - 1, in blocks), and its
prefill and chunk programs take the slot. Everything else is ``aot.py``'s:
its ``report``, its shapes, its answer "tpu" to ``jax.default_backend()``.
"""

import argparse
import json

import aot                      # noqa: F401 - sets TPU_LOG_DIR and sys.path
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from pbench import common

GB = 1e9


def serve(cfg, job, one):
    from deepspeed_tpu.models import paged
    builder = common.load_module("builders", cfg["builder"])
    model, s, eng = builder.model(cfg), builder.sizes(cfg), job["engine"]
    if not getattr(model, "slot_state", False):
        raise SystemExit("no slot state: perfbench/aot.py compiles this cell")
    B, BS = eng["max_batch_size"], eng.get("kv_block_size", 64)
    MB, NB = -(-s["max_seq_len"] // BS), eng["num_kv_blocks"]
    bucket = eng["prompt_bucket"]
    ring = paged.ring_blocks(model.config.sliding_window,
                             eng.get("splitfuse_tokens", 0) or bucket, BS)
    model._paged_kernel = eng.get("paged_kernel", "auto")
    model._paged_block_c = eng.get("paged_block_c", "auto")
    model._paged_ring_blocks = ring
    i32 = jnp.int32
    params = aot.shaped(jax.eval_shape(model.init, jax.random.key(0)), one)
    cache = aot.shaped(jax.eval_shape(lambda: model.init_paged_cache(
        NB, BS, dtype=jnp.bfloat16, slots=B, ring_blocks=ring)), one)
    table = {}
    for key, leaves in cache.items():
        table[key] = {"leaves": len(leaves), "shape": list(leaves[0].shape),
                      "dtype": str(leaves[0].dtype), "gb": sum(
                          x.size * x.dtype.itemsize for x in leaves) / GB}
    print(json.dumps({"cache": table, "ring_blocks": ring, "slots": B,
                      "params_gb": sum(x.size * x.dtype.itemsize for x in
                                       jax.tree.leaves(params)) / GB,
                      "cache_gb": sum(t["gb"] for t in table.values())}))

    def arr(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def decode_steps(params, cache, tokens, lengths, tables):
        toks = []
        for _ in range(eng.get("decode_steps_per_dispatch", 8)):
            logits, cache = model.apply_paged_decode(
                params, tokens, lengths, cache, tables)
            tokens = jnp.argmax(logits, axis=-1).astype(i32)
            lengths = lengths + 1
            toks.append(tokens)
        return jnp.stack(toks), cache

    def prefill(params, cache, ids, tb, to, length, slot):
        logits, cache = model.apply_paged_prefill(
            params, ids, cache, tb, to, length, slot=slot)
        return jnp.argmax(logits, axis=-1), cache

    top = -(-job["prompt_len"]["max"] // bucket) * bucket
    for T in range(bucket, top + 1, bucket):
        aot.report(f"prefill T={T}", prefill, (
            params, cache, arr((1, T)), arr((T,)), arr((T,)), arr(()),
            arr(())), donate=(1,))
    aot.report(f"decode x8, B={B}", decode_steps,
               (params, cache, arr((B,)), arr((B,)), arr((B, MB))),
               donate=(1,))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--engine", action="append", default=[],
                    metavar="KEY=INT")
    args = ap.parse_args()
    jax.default_backend = lambda: "tpu"     # aot.py's docstring
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    _, _, cfg, job = common.load_cell(args.workload)
    for kv in args.engine:
        key, value = kv.split("=")
        job["engine"][key] = int(value)
    print(json.dumps({"workload": args.workload,
                      "described": "v5e:2x2, one of its chips"}))
    serve(cfg, job, SingleDeviceSharding(topo.devices[0]))


if __name__ == "__main__":
    main()
