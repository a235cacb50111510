"""Checkpoint-stall benchmark — the fork's centerpiece metric.

The reference fork (VELOC/DataStates) exists to shrink the training stall
a checkpoint causes; its number is the wait-time logged by
``veloc_checkpoint_engine.py:158``. This benchmark measures, per engine:

  * submit_ms  — how long ``save_checkpoint`` blocks the training loop
  * durable_ms — time until the bytes are on disk (``wait()`` returns)
  * overlap    — training steps completed while the write ran

    python benchmarks/ckpt_bench.py [--preset 125M] [--engines sync async native]

NOTE: submit time includes the synchronous device->host gather.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import GPT2, PRESETS
from deepspeed_tpu.utils import groups


def bench_engine(engine_type, preset, steps_during=4, seq=256, micro=2):
    groups.reset()
    tmp = tempfile.mkdtemp(prefix=f"ckpt_bench_{engine_type}_")
    try:
        from dataclasses import replace
        cfg = replace(PRESETS[preset], max_seq_len=seq)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2(cfg),
            config={"train_micro_batch_size_per_gpu": micro,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                    "bf16": {"enabled": True}, "steps_per_print": 0,
                    "zero_optimization": {"stage": 2},
                    "checkpoint_engine": {"type": engine_type,
                                          "writer_threads": 4}})
        batch = {"input_ids": np.random.RandomState(0).randint(
            0, cfg.vocab_size,
            (engine.config.train_batch_size, seq)).astype(np.int32)}
        engine.train_batch(batch)  # compile + warm state

        # measurement 1: submit + time-to-durable, nothing overlapped
        t0 = time.perf_counter()
        engine.save_checkpoint(tmp, tag="m1")
        submit = time.perf_counter() - t0
        engine.checkpoint_engine.wait()
        durable = time.perf_counter() - t0

        # measurement 2: total wall time when training overlaps the write
        # vs the sum of its parts (overlap benefit of async engines)
        t1 = time.perf_counter()
        engine.save_checkpoint(tmp, tag="m2")
        for _ in range(steps_during):
            engine.train_batch(batch)
        engine.checkpoint_engine.wait()
        overlapped_total = time.perf_counter() - t1
        engine.save_checkpoint_terminate()
        return {"engine": engine_type,
                "submit_ms": round(submit * 1e3, 1),
                "durable_ms": round(durable * 1e3, 1),
                "overlap_total_ms": round(overlapped_total * 1e3, 1),
                "steps_overlapped": steps_during}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="125M")
    ap.add_argument("--engines", nargs="+",
                    default=["sync", "async", "native"])
    args = ap.parse_args()
    for e in args.engines:
        print(json.dumps(bench_engine(e, args.preset)))


if __name__ == "__main__":
    main()
