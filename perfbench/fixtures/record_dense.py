#!/usr/bin/env python3
"""Record the small trace the readers of the dense weight products are
checked against (``tests/unit/test_serving_spans.py``): a two-layer GPT-2 at
a reduced size (hidden 256, 4 heads of 64, MLP 1024, 512 rows of vocabulary)
served by ``InferenceEngineV2`` for a few prefills and decode dispatches, so
that the trace holds the ``dstpu.mm.*`` scopes in its operations' ``tf_op``
under the names the TPU compiler gives the fusions, and the
``dstpu.engine.prefill`` / ``dstpu.engine.dispatch`` spans.

    python3 perfbench/fixtures/record_dense.py <out dir>     (on the chip)

It leaves ``<out dir>/dense1.xplane.pb`` (the trace less its
``/host:metadata`` plane: the programs' HLO protos, most of the file, read by
nothing here), copied to perfbench/fixtures/ by hand, and prints what goes
into ``dense1.expected.json`` (also left in ``<out dir>``; the copy under
perfbench/fixtures/ adds how it was recorded and checked by hand).
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

SIZES = dict(n_layer=2, n_head=4, n_kv_head=4, d_head=64, d_model=256,
             d_ff=1024, vocab_size=512, vocab_rows=512, activation="gelu",
             max_seq_len=256)
PROMPTS = (40, 90, 17)
NEW_TOKENS = 6
READERS = ("weights_roofline", "weights_share", "train_weights_roofline")


def without_plane(raw, name):
    """The XSpace ``raw`` less its planes called ``name`` (XSpace.planes=1,
    XPlane.name=2), every other byte as it was."""
    out, i = bytearray(), 0
    while i < len(raw):
        start = i
        key, i = moe._varint(raw, i)
        body = None
        if key & 7 == 0:
            _, i = moe._varint(raw, i)
        elif key & 7 == 2:
            size, i = moe._varint(raw, i)
            body, i = raw[i:i + size], i + size
        else:
            i += 8 if key & 7 == 1 else 4
        if key >> 3 == 1 and body is not None and any(
                f == 2 and bytes(x).decode("utf-8", "replace") == name
                for f, x in moe._fields(body)):
            continue
        out += raw[start:i]
    return bytes(out)


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
        serve(PROMPTS, NEW_TOKENS)
    found = trace.find_xplane(out)
    path = os.path.join(out, "dense1.xplane.pb")
    with open(found, "rb") as f:
        raw = f.read()
    with open(path, "wb") as f:
        f.write(without_plane(memoryview(raw), "/host:metadata"))
    said = []
    view = types.SimpleNamespace(
        trace=trace.Trace(path), sizes=s, chips=1,
        # the tokens the capture served, for the training reader's
        # arithmetic alone: no training step is in this trace
        counters={"tokens_traced": sum(PROMPTS)
                  + len(PROMPTS) * NEW_TOKENS},
        peaks=common.peaks_for("TPU v5 lite"),
        say=lambda line, **f: said.append({line: f}))
    values = {m: common.load_module("layer_metrics", m).read(view)
              for m in READERS}
    expected = {"sizes": s, "counters": view.counters, "values": values,
                "walk": view.trace.mm_walk, "said": said,
                "bytes": {"recorded": len(raw),
                          "kept": os.path.getsize(path)}}
    with open(os.path.join(out, "dense1.expected.json"), "w") as f:
        json.dump(expected, f, indent=1, default=str)
    print(json.dumps(expected, default=str))


if __name__ == "__main__":
    main()
