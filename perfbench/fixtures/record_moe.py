#!/usr/bin/env python3
"""Record the small trace the MoE readers are checked against
(``tests/unit/test_serving_spans.py``): a two-layer OLMoE at a reduced size
(hidden 256, 2 heads of 128, 8 experts of width 128, 2 per token) served by
``InferenceEngineV2`` for a few decode dispatches and prefills, so that the
trace holds the ``dstpu.moe.*`` scopes in its operations' ``tf_op``, the
ragged-dot fusions under the names the TPU compiler gives them, and the
``dstpu.engine.dispatch`` spans the roofline's row count reads.

    python3 perfbench/fixtures/record_moe.py <out dir>     (on the chip)

The .xplane.pb it leaves is copied to perfbench/fixtures/moe1.xplane.pb by
hand, and what it prints into moe1.expected.json.
"""

import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import numpy as np              # noqa: E402

from pbench import common, moe, trace        # noqa: E402

SIZES = dict(n_layer=2, n_head=2, n_kv_head=2, d_head=128, d_model=256,
             d_ff=128, n_experts=8, top_k=2, vocab_size=512, vocab_rows=512,
             activation="silu", max_seq_len=256)
PROMPTS = (40, 90, 17)


def main():
    out = sys.argv[1]
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import OLMoE, OLMoEConfig
    s = SIZES
    model = OLMoE(OLMoEConfig(
        n_layer=s["n_layer"], n_head=s["n_head"], n_kv_heads=s["n_kv_head"],
        d_model=s["d_model"], d_ff=s["d_ff"], num_experts=s["n_experts"],
        moe_top_k=s["top_k"], max_seq_len=s["max_seq_len"],
        vocab_size=s["vocab_rows"]))
    engine = InferenceEngineV2(model, dict(
        dtype="bfloat16", seed=0, max_batch_size=4, num_kv_blocks=16,
        prompt_bucket=128, decode_steps_per_dispatch=2))
    rng = np.random.default_rng(0)

    def serve(lengths, new):
        for n in lengths:
            engine.put(rng.integers(0, s["vocab_size"], n, dtype=np.int32),
                       max_new_tokens=new, eos_token_id=-1)
        while engine.has_work:
            engine.step()

    serve(PROMPTS, 4)                       # compile outside the capture
    with trace.capture(out):
        serve(PROMPTS, 6)
    path = trace.find_xplane(out)
    tr = trace.Trace(path)
    said = []
    view = types.SimpleNamespace(
        trace=tr, sizes=s, counters={"traced_prompts": list(PROMPTS)},
        peaks=common.peaks_for("TPU v5 lite"),
        say=lambda line, **f: said.append({line: f}))
    experts, route, busy = moe.device_seconds(view)
    least, calls = moe.least_seconds(view)
    print(json.dumps({"path": path, "bytes": os.path.getsize(path),
                      "experts_s": experts, "route_s": route,
                      "busy_s": busy, "least_s": least,
                      "layer_calls": calls, "said": said}, default=str))


if __name__ == "__main__":
    main()
