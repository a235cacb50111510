"""What ``test_deepseek_v32.py`` (the model, its reference, the read's
kernel) and ``test_deepseek_v32_engine.py`` (the engine, its spans, its
refusals) share: the tiny configuration, seeded weights, the reference's rows
and an engine with the benchmark's logit tap. Two files so that neither is a
test run's wall clock under ``--dist loadfile`` (ROADMAP D12)."""

import importlib
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from deepspeed_tpu.models.deepseek_v32 import (DEEPSEEK_V32_TINY,  # noqa: E402
                                               DeepseekV32)

ref = importlib.import_module("references.deepseek_v32")
dsa = importlib.import_module("pbench.dsa")

CFG = DEEPSEEK_V32_TINY
TOPK = CFG.index_topk
# what the reference cannot read off the tree's shapes, at the tiny size
KW = dict(index_topk=TOPK, n_group=CFG.n_group, topk_group=CFG.topk_group,
          top_k=CFG.moe_top_k, experts_offset=CFG.experts_offset,
          rope_original=CFG.rope_original)
BS, SLOTS, C = 8, 3, 16
TOL = 2e-5
ENGINE = dict(dtype="float32", max_batch_size=SLOTS, kv_block_size=BS,
              splitfuse_tokens=C, num_kv_blocks=96,
              decode_steps_per_dispatch=8)


@pytest.fixture(scope="module")
def model():
    return DeepseekV32(CFG)


@pytest.fixture(scope="module")
def params(model):
    """Seeded weights; the seed's scales are chosen for the published
    widths (``DeepseekV32.init``), and at d 64 and expert width 32 the
    experts' part of the stream is a thousandth of what it is there. So
    the held experts' down products are 300 times the seed's here, the
    shared expert's 12 times and the gate's correction bias three times;
    and 24-wide heads of 0.02 weights make attention logits of deviation
    0.01, a flat softmax whose scale is nothing, so the query expansion is
    ten times the seed's and the value expansion five times: every
    neighbour then moves a logit by more than 50 x ``TOL``."""
    params = model.init(jax.random.key(0))
    for p in params["layers"]:
        p["wq_b"] = p["wq_b"] * 10.0
        p["wv_b"] = p["wv_b"] * 5.0
    for p in params["layers"][CFG.first_k_dense:]:
        p["moe_w2"] = p["moe_w2"] * 300.0
        p["ws2"] = p["ws2"] * 12.0
        p["gate_bias"] = p["gate_bias"] * 3.0
    return params


def reference_rows(params, prompt, tokens, **variant):
    """The reference's logits at the positions that emitted ``tokens``."""
    seq = np.concatenate([prompt, tokens])[None, :-1].astype(np.int32)
    rows = np.asarray(ref.logits(params, seq, **{**KW, **variant}))[0]
    return rows[len(prompt) - 1:]


class TapEngine(importlib.import_module("pbench.tap").tap_engine()):
    """The tap picks a dispatch's rows out as the NEWEST it has seen, so it
    reads every decode dispatch before the next goes out (as
    tests/unit/test_phi4flash.py does)."""

    def _plain_decode(self, uids=None):
        out = super()._plain_decode(uids)
        self._settle()
        return out


def serve(eng, prompts, max_new):
    uids = [eng.put(p, n) for p, n in zip(prompts, max_new)]
    while eng.has_work:
        eng.step()
    return [(eng.get(u), np.stack(eng.rows[u])) for u in uids]


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
            for n in lengths]
