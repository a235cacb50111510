#!/usr/bin/env python3
"""Compile a cell's programs at real size for a described TPU v5e, with no
chip attached, and print ``memory_analysis()`` of each. Costs no chip time;
run it here before a cell's first call to the chip.

    JAX_PLATFORMS=cpu python3 perfbench/aot.py --workload <name>

What it compiles is the model-level program of each kind the cell runs
(loss + gradient at the job's batch on one chip for a training cell; each
prefill bucket, the 8-step decode dispatch, the chunk and the fused
chunk + decode dispatch for a serving cell), built from the program's own
model methods the way the engines build them. It is not the engine's whole
step: the engines place their own state on ``jax.devices()``, which is the
CPU here. Optimizer state is added by arithmetic. The code under test asks
``jax.default_backend()`` to choose kernel or interpreter; this script
answers "tpu" for it, here and nowhere else.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from pbench import common       # noqa: E402

GB = 1e9


def report(name, fn, args, donate=()):
    t0 = time.perf_counter()
    try:
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    except Exception as e:  # noqa: BLE001 - the compiler's refusal is data
        print(json.dumps({"program": name, "refused":
                          f"{type(e).__name__}: {str(e)[:400]}"}))
        return None
    m = compiled.memory_analysis()
    out = {"program": name,
           "argument_gb": m.argument_size_in_bytes / GB,
           "aliased_gb": m.alias_size_in_bytes / GB,
           "output_gb": m.output_size_in_bytes / GB,
           "temp_gb": m.temp_size_in_bytes / GB,
           "whole_gb": (m.argument_size_in_bytes + m.temp_size_in_bytes
                        + m.output_size_in_bytes
                        - m.alias_size_in_bytes) / GB,
           "mosaic_calls": compiled.as_text().count("tpu_custom_call"),
           "compile_s": round(time.perf_counter() - t0, 1)}
    print(json.dumps(out), flush=True)
    return out


def shaped(tree, sharding, dtype=None):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, dtype if dtype is not None
        and jnp.issubdtype(x.dtype, jnp.floating) else x.dtype,
        sharding=sharding), tree)


def train(cfg, job, one):
    builder = common.load_module("builders", cfg["builder"])
    model = builder.model(cfg, **job["model_overrides"])
    params = shaped(jax.eval_shape(model.init, jax.random.key(0)), one,
                    jnp.bfloat16)
    ids = jax.ShapeDtypeStruct(
        (job["micro_batch_per_chip"], job["seq_len"]), jnp.int32,
        sharding=one)
    report("loss+grad", jax.value_and_grad(
        lambda p, b: model.loss(p, {"input_ids": b}, train=True)),
        (params, ids))
    n = model.config.num_params()
    print(json.dumps({"state_by_arithmetic_gb": {
        "bf16_params": 2 * n / GB, "f32_master_and_two_moments": 12 * n / GB,
        "f32_gradients": 4 * n / GB}, "params": n}))


def serve(cfg, job, one):
    builder = common.load_module("builders", cfg["builder"])
    model = builder.model(cfg)
    eng = job["engine"]
    model._paged_kernel = eng.get("paged_kernel", "auto")
    model._paged_block_c = eng.get("paged_block_c", "auto")
    s = builder.sizes(cfg)
    B, BS = eng["max_batch_size"], eng.get("kv_block_size", 64)
    MB = -(-s["max_seq_len"] // BS)
    NB = eng["num_kv_blocks"]
    i32 = jnp.int32
    params = shaped(jax.eval_shape(model.init, jax.random.key(0)), one,
                    jnp.bfloat16)
    cache = shaped(jax.eval_shape(
        lambda: model.init_paged_cache(NB, BS, dtype=jnp.bfloat16)), one)

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

    def chunk(params, cache, ids, tb, to, start, tlen, table):
        logits, cache = model.apply_paged_chunk(
            params, ids, cache, tb, to, start, tlen, table)
        return jnp.argmax(logits, axis=-1), cache

    def fused(params, cache, ids, tb, to, start, tlen, table, tokens,
              lengths, tables):
        c_tok, cache = chunk(params, cache, ids, tb, to, start, tlen, table)
        toks, cache = decode_steps(params, cache, tokens, lengths, tables)
        return c_tok, toks, cache

    dec = (arr((B,)), arr((B,)), arr((B, MB)))
    C = eng.get("splitfuse_tokens", 0)
    if C:
        ch = (arr((1, C)), arr((C,)), arr((C,)), arr(()), arr(()),
              arr((MB,)))
        report(f"chunk C={C}", chunk, (params, cache) + ch, donate=(1,))
        report(f"chunk C={C} + decode x8, B={B}", fused,
               (params, cache) + ch + dec, donate=(1,))
    else:
        bucket = eng["prompt_bucket"]
        top = -(-job["prompt_len"]["max"] // bucket) * bucket
        for T in range(bucket, top + 1, bucket):
            def prefill(params, cache, ids, tb, to, length):
                logits, cache = model.apply_paged_prefill(
                    params, ids, cache, tb, to, length)
                return jnp.argmax(logits, axis=-1), cache
            report(f"prefill T={T}", prefill, (
                params, cache, arr((1, T)), arr((T,)), arr((T,)), arr(())),
                donate=(1,))
        report(f"decode x8, B={B}", decode_steps, (params, cache) + dec,
               donate=(1,))
    print(json.dumps({"pool_blocks": NB, "batch": B}))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--engine", action="append", default=[],
                    metavar="KEY=INT", help="try another engine size "
                    "than the traffic file's (sizing a cell)")
    args = ap.parse_args()
    jax.default_backend = lambda: "tpu"     # see the docstring
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    _, _, cfg, job = common.load_cell(args.workload)
    for kv in args.engine:
        key, value = kv.split("=")
        job["engine"][key] = int(value)
    print(json.dumps({"workload": args.workload, "described": "v5e:2x2, "
                      "one of its chips", "kind": job["kind"]}))
    {"train": train, "serve": serve}[job["kind"]](cfg, job, one)


if __name__ == "__main__":
    main()
