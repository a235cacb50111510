"""What ``test_solar_open2.py`` (the model, its reference, the rule with a
gate a key channel and its kernels, the expert shares) and
``test_solar_open2_engine.py`` (the engine, its spans, its refusals) share:
the tiny configuration, seeded weights, the reference's rows and an engine
with the benchmark's logit tap. Two files so that neither is a test run's
wall clock under ``--dist loadfile`` (ROADMAP D12)."""

import dataclasses
import functools
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from deepspeed_tpu.models.solar_open2 import (SOLAR_OPEN2_TINY,  # noqa: E402
                                              SolarOpen2)

ref = importlib.import_module("references.solar_open2")

# float32 throughout: program and reference then differ by summation order
CFG = SOLAR_OPEN2_TINY
# what the engine's tests serve: a period and the next one's GQA layer (two
# K/V pools, three slot states), a third of the programs to compile
CUT = dataclasses.replace(CFG, n_layer=5)
H, DK, DV = CFG.linear_heads, CFG.linear_dk, CFG.linear_dv
BS, SLOTS, C = 4, 3, 8
TOL = 1e-5
ENGINE = dict(dtype="float32", max_batch_size=SLOTS, kv_block_size=BS,
              splitfuse_tokens=C, num_kv_blocks=96,
              decode_steps_per_dispatch=4)
# what the reference is told of the tiny size that the tree does not say
SIZES = dict(n_head=CFG.n_head, top_k=CFG.moe_top_k,
             experts_offset=CFG.experts_offset)
# the nearest wrong models, which a comparison against the reference has to
# tell from the right one
NEIGHBOURS = [{"state_dtype": jnp.bfloat16}, {"gate_per_channel": False},
              {"gate_scoring": "softmax"}, {"beta_scale": 1.0},
              {"attn_gate": False}, {"bias_weighs": True}]


@pytest.fixture(scope="module")
def model():
    return SolarOpen2(CFG)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(0))


@pytest.fixture(scope="module")
def cut():
    return SolarOpen2(CUT)


@pytest.fixture(scope="module")
def cut_params(cut):
    return cut.init(jax.random.key(0))


@functools.lru_cache(maxsize=None)
def _logits(variant):
    return jax.jit(functools.partial(ref.logits, **SIZES, **dict(variant)))


def reference_logits(params, ids, **variant):
    """``ref.logits`` of (B, T) ids at the tiny size, jitted a variant."""
    return np.asarray(_logits(tuple(sorted(variant.items())))(
        params, np.asarray(ids, np.int32)))


def reference_rows(params, prompt, tokens, **variant):
    """The reference's logits at the positions that emitted ``tokens``."""
    seq = np.concatenate([prompt, tokens])[None, :-1]
    return reference_logits(params, seq, **variant)[0][len(prompt) - 1:]


class TapEngine(importlib.import_module("pbench.tap").tap_engine()):
    """The tap picks a dispatch's rows out as the NEWEST it has seen, so it
    reads every decode dispatch before the next goes out (as
    tests/unit/test_phi4flash.py does)."""

    def _plain_decode(self, uids=None):
        out = super()._plain_decode(uids)
        self._settle()
        return out


def engine_of(model, params, **engine):
    return TapEngine(model, {**ENGINE, **engine}, params=params)


def serve(eng, prompts, max_new, order=None):
    """Run ``prompts`` through ``eng`` -> [(tokens, logits rows)] in the
    prompts' order. ``order``: lists of prompt indices put together, each
    list stepped until it is done."""
    uids = {}
    for group in order or [range(len(prompts))]:
        for i in group:
            uids[i] = eng.put(prompts[i], max_new[i])
        while eng.has_work:
            eng.step()
    return [(eng.get(uids[i]), np.stack(eng.rows[uids[i]]))
            for i in range(len(prompts))]


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
            for n in lengths]
