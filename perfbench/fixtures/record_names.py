#!/usr/bin/env python3
"""Record the small trace the readers of the program's own names are checked
against (``tests/unit/test_serving_spans.py``): the GPT-2 of
``record_dense.py`` with ONE layer (the operations' metadata is most of the
file: two layers and two decode steps a dispatch recorded 0.72 MB) served by
a SPLIT-FUSE ``InferenceEngineV2`` (chunks of 64 tokens) for three requests,
so that the trace holds chunk-only dispatches, fused dispatches (a chunk
beside two decode steps) and plain decode dispatches of one step, and in
its operations' ``tf_op`` the ``dstpu.step.*``,
``dstpu.attn.paged``, ``dstpu.kv.write`` and ``dstpu.kernel.*`` names as the
TPU compiler keeps them.

    python3 perfbench/fixtures/record_names.py <out dir>     (on the chip)

It leaves ``<out dir>/names1.xplane.pb`` (the trace less its
``/host:metadata`` plane, as ``record_dense.py`` cuts it), copied to
perfbench/fixtures/ by hand, and prints what goes into
``names1.expected.json`` (also left in ``<out dir>``; the copy under
perfbench/fixtures/ adds how it was recorded and checked by hand).
"""

import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import numpy as np              # noqa: E402

from pbench import common, names, trace        # noqa: E402
from record_dense import SIZES as DENSE, without_plane    # noqa: E402

SIZES = dict(DENSE, n_layer=1)
PROMPTS = (150, 90, 40)
NEW_TOKENS = 6
CHUNK = 64
READERS = ("paged_attn_share", "kv_write_share", "paged_chunk_kernel_share",
           "chunk_phase_share", "unnamed_busy_share",
           "train_unnamed_busy_share")


def main():
    out = sys.argv[1]
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import GPT2, GPT2Config
    s = SIZES
    model = GPT2(GPT2Config(
        n_layer=s["n_layer"], n_head=s["n_head"], d_model=s["d_model"],
        max_seq_len=s["max_seq_len"], vocab_size=s["vocab_rows"],
        dtype="bfloat16"))
    engine = InferenceEngineV2(model, dict(
        dtype="bfloat16", seed=0, max_batch_size=4, num_kv_blocks=16,
        splitfuse_tokens=CHUNK, decode_steps_per_dispatch=1))
    rng = np.random.default_rng(0)

    def serve(lengths, new):
        for n in lengths:
            engine.put(rng.integers(0, s["vocab_size"], n, dtype=np.int32),
                       max_new_tokens=new, eos_token_id=-1)
        while engine.has_work:
            engine.step()

    serve(PROMPTS, 4)                       # compile outside the capture
    with trace.capture(out):
        serve(PROMPTS, NEW_TOKENS)
    found = trace.find_xplane(out)
    path = os.path.join(out, "names1.xplane.pb")
    with open(found, "rb") as f:
        raw = f.read()
    with open(path, "wb") as f:
        f.write(without_plane(memoryview(raw), "/host:metadata"))
    said = []
    view = types.SimpleNamespace(
        trace=trace.Trace(path), sizes=s, chips=1,
        peaks=common.peaks_for("TPU v5 lite"),
        say=lambda line, **f: said.append({line: f}))
    values = {m: common.load_module("layer_metrics", m).read(view)
              for m in READERS}
    walked = names.walk(view) or {}
    dispatches = {}
    for e in view.trace.host_spans("dstpu.engine.dispatch"):
        kind = e.stats.get("kind")
        dispatches[kind] = dispatches.get(kind, 0) + 1
    expected = {"sizes": s, "values": values, "dispatches": dispatches,
                "walk": {k: walked.get(k) for k in (
                    "busy_s", "by_scope", "by_kernel", "unnamed_s")},
                "unnamed_top": list(walked.get("unnamed_ops", {}).items())[
                    :8],
                "bytes": {"recorded": len(raw),
                          "kept": os.path.getsize(path)}}
    with open(os.path.join(out, "names1.expected.json"), "w") as f:
        json.dump(expected, f, indent=1, default=str)
    print(json.dumps(expected, default=str))


if __name__ == "__main__":
    main()
