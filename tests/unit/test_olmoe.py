"""OLMoE (ISSUE 26): the model against its plain reference
``perfbench/references/olmoe.py`` — the same file the benchmark's runner
loads, there is no second copy — at a tiny size on the CPU in float32.

Tolerance. System and reference do the same float32 arithmetic in another
order (sorted grouped products against a weighted sum over all experts,
a scanned head loop against one einsum), so logits agree to accumulation
noise: measured 1.1e-6 of the logits' standard deviation; ``TOL`` = 1e-4
leaves room for another BLAS and is still a thousand times under what
the wrong mathematics gives (renormalised routing weights move the logits
by 0.13 std, a dropped QK-norm by 1.3 std; asserted below).
"""

import os
import sys
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import (OLMoE, OLMoEConfig, OLMOE_1B_7B,
                                  OLMOE_TINY)
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.groups import TopologyConfig

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))
from pbench import common as pb_common  # noqa: E402

reference = pb_common.load_module("references", "olmoe")

CFG = replace(OLMOE_TINY, dtype="float32")      # 2 layers, 16 experts top-4
REF = dict(n_head=CFG.n_head, activation="silu", top_k=CFG.moe_top_k)
TOL = 1e-4          # of the logits' standard deviation (module docstring)
ENGINE = {"dtype": "float32", "kv_block_size": 8, "max_batch_size": 2,
          "num_kv_blocks": 24, "decode_steps_per_dispatch": 2}
PROMPT, NEW = 21, 9


@pytest.fixture(scope="module")
def model():
    return OLMoE(CFG)


@pytest.fixture(scope="module")
def params(model):
    """Training tree with the norm scales moved off 1 so that a dropped
    norm shows."""
    p = model.init(jax.random.key(3))
    blocks = dict(p["blocks"])
    for i, k in enumerate(("q_norm", "k_norm", "rms1", "rms2")):
        blocks[k] = 1.0 + 0.3 * jax.random.normal(
            jax.random.key(10 + i), blocks[k].shape, jnp.float32)
    return {**p, "blocks": blocks}


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(0).randint(
        0, CFG.vocab_size, (2, 32)).astype(np.int32)


def _rel(got, want):
    """Largest logit difference over the reference logits' std."""
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / want.std())


def test_apply_matches_reference(model, params, ids):
    want = reference.logits(model.serving_params(params), ids, **REF)
    assert _rel(model.apply(params, ids), want) <= TOL
    # the served tree (per-layer experts) through the same apply
    assert _rel(model.apply(model.serving_params(params), ids), want) <= TOL
    # and the reference reads the stacked training tree as well
    assert _rel(reference.logits(params, ids, **REF), want) <= 1e-6


@pytest.mark.parametrize("wrong", [dict(renormalize=True),
                                   dict(qk_norm=False)])
def test_reference_tells_the_mathematics_apart(model, params, ids, wrong):
    """Renormalised top-k weights, or no QK-norm, is another model: the
    comparison above would fail by three orders of magnitude."""
    got = model.apply(params, ids)
    assert _rel(got, reference.logits(params, ids, **REF, **wrong)) \
        > 1000 * TOL


def test_mixtral_keeps_renormalising(model, params, ids):
    renorm = OLMoE(replace(CFG, norm_topk_prob=True))
    want = reference.logits(params, ids, **REF, renormalize=True)
    assert _rel(renorm.apply(params, ids), want) <= TOL


def _engine(model, params, **kw):
    groups.reset()
    return InferenceEngineV2(model, dict(ENGINE, **kw), params=params)


@pytest.mark.parametrize("mode", [dict(prompt_bucket=16),
                                  dict(splitfuse_tokens=8)],
                         ids=["bucketed", "splitfuse"])
def test_engine_matches_reference(model, params, mode):
    """Prefill (bucketed, or split-fuse chunks) + paged decode through
    ``InferenceEngineV2`` against the reference's full forward, by logits:
    every emitted token's reference logit within TOL std of the reference
    maximum at its position, and the engine's served tree keeps each
    layer's experts as arrays of their own."""
    eng = _engine(model, params, **mode)
    for k in model._PER_LAYER:
        leaf = eng.params["blocks"][k]
        assert isinstance(leaf, list) and len(leaf) == CFG.n_layer
        assert leaf[0].shape[0] == CFG.num_experts
    prompt = np.random.RandomState(1).randint(
        0, CFG.vocab_size, (PROMPT,)).astype(np.int32)
    uid = eng.put(prompt, max_new_tokens=NEW, eos_token_id=-1)
    while eng.has_work:
        eng.step()
    out = np.asarray(eng.get(uid))
    assert len(out) == NEW
    seq = np.concatenate([prompt, out])[None, :-1]
    gaps = reference.token_gaps(
        eng.params, seq, PROMPT - 1 + np.arange(NEW), out, **REF)
    assert float(np.max(gaps)) <= TOL


def test_paged_logits_match_reference(model, params):
    """The paged programs themselves, logit by logit: bucketed prefill of
    a padded prompt, then decode steps through the paged cache, against
    the reference's rows at the same positions."""
    served = model.serving_params(params)
    BS, NB, T = 8, 12, 32
    rng = np.random.RandomState(2)
    seq = rng.randint(0, CFG.vocab_size, (PROMPT + NEW,)).astype(np.int32)
    want = np.asarray(reference.logits(served, seq[None], **REF))[0]
    cache = model.init_paged_cache(NB, BS, dtype=jnp.float32)
    table = np.arange(1, 1 + -(-(PROMPT + NEW) // BS), dtype=np.int32)
    pos = np.arange(T)
    tb = np.where(pos < PROMPT, table[np.minimum(pos // BS,
                                                 len(table) - 1)], 0)
    to = np.where(pos < PROMPT, pos % BS, 0)
    padded = np.zeros((1, T), np.int32)
    padded[0, :PROMPT] = seq[:PROMPT]
    logits, cache = jax.jit(model.apply_paged_prefill)(
        served, padded, cache, tb.astype(np.int32), to.astype(np.int32),
        np.int32(PROMPT))
    assert _rel(logits[0], want[PROMPT - 1]) <= TOL
    decode = jax.jit(model.apply_paged_decode)
    tables = np.zeros((1, 6), np.int32)
    tables[0, :len(table)] = table
    for n in range(PROMPT, PROMPT + NEW):
        logits, cache = decode(served, seq[n:n + 1],
                               np.asarray([n], np.int32), cache, tables)
        assert _rel(logits[0], want[n]) <= TOL, n


def test_forward_kernel_path_matches_reference():
    """The same prefill and decode steps with the expert products through
    the forward grouped kernel (interpret mode; what "auto" takes on a TPU
    at a serving program's few rows a group) at widths that form its
    tiles: system = reference as through the ragged products, and every
    expert layer call is counted as the kernel's."""
    from types import SimpleNamespace
    from deepspeed_tpu.ops.pallas._common import counting_calls
    cfg = replace(CFG, d_model=128, d_ff=128, n_head=2, n_kv_heads=2)
    model = OLMoE(cfg)
    model._moe_cfg = SimpleNamespace(
        grouped_kernel={"backend": "forward"}, hierarchical_a2a="auto",
        dcn_quantize=False)
    served = model.init_served(jax.random.key(5))
    BS, NB, T = 8, 12, 32
    seq = np.random.RandomState(4).randint(
        0, cfg.vocab_size, (PROMPT + NEW,)).astype(np.int32)
    want = np.asarray(reference.logits(served, seq[None],
                                       **dict(REF, n_head=2)))[0]
    cache = model.init_paged_cache(NB, BS, dtype=jnp.float32)
    table = np.arange(1, 1 + -(-(PROMPT + NEW) // BS), dtype=np.int32)
    pos = np.arange(T)
    tb = np.where(pos < PROMPT, table[np.minimum(pos // BS,
                                                 len(table) - 1)], 0)
    padded = np.zeros((1, T), np.int32)
    padded[0, :PROMPT] = seq[:PROMPT]
    with counting_calls() as counts:
        logits, cache = jax.jit(model.apply_paged_prefill)(
            served, padded, cache, tb.astype(np.int32),
            np.where(pos < PROMPT, pos % BS, 0).astype(np.int32),
            np.int32(PROMPT))
        assert _rel(logits[0], want[PROMPT - 1]) <= TOL
        decode = jax.jit(model.apply_paged_decode)
        # two slots, the second dead: 8 routed rows, one row tile
        tables = np.zeros((2, 6), np.int32)
        tables[0, :len(table)] = table
        for n in range(PROMPT, PROMPT + 3):
            logits, cache = decode(served, np.asarray([seq[n], 0], np.int32),
                                   np.asarray([n, 0], np.int32), cache, tables)
            assert _rel(logits[0], want[n]) <= TOL, n
    assert counts == {"expert": [2 * cfg.n_layer] * 2}  # two programs traced


def test_expert_parallel_equals_one_device(model, params):
    """``expert_parallel=2`` (the shard_map all_to_all path, which shares
    ``route_topk`` and its ``renormalize=False``) emits what one device
    emits."""
    prompt = np.random.RandomState(4).randint(
        0, CFG.vocab_size, (PROMPT,)).astype(np.int32)

    def run(ep):
        groups.reset()
        topo = groups.initialize(TopologyConfig(expert_parallel_size=ep))
        eng = InferenceEngineV2(model, dict(ENGINE, prompt_bucket=16,
                                            expert_parallel=ep),
                                params=params, topology=topo)
        uid = eng.put(prompt, max_new_tokens=NEW, eos_token_id=-1)
        while eng.has_work:
            eng.step()
        return np.asarray(eng.get(uid)), eng

    one, _ = run(1)
    two, eng = run(2)
    np.testing.assert_array_equal(one, two)
    seq = np.concatenate([prompt, two])[None, :-1]
    groups.reset()
    gaps = reference.token_gaps(
        jax.device_get(eng.params), seq, PROMPT - 1 + np.arange(NEW), two,
        **REF)
    assert float(np.max(gaps)) <= TOL


def test_seeded_engine_weights_are_the_models_own():
    """With no params the engine makes its weights unstacked
    (``init_served``, which is also what a compile of the serving programs
    takes its shapes from); they are the values ``init`` stacks."""
    model = OLMoE(CFG)
    groups.reset()
    eng = InferenceEngineV2(model, dict(ENGINE, seed=5))
    want = model.serving_params(model.init(jax.random.key(5)))
    got = eng.params
    assert jax.tree.structure(got) == jax.tree.structure(want)
    shapes = jax.eval_shape(model.init_served, jax.random.key(5))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), got) \
        == jax.tree.map(lambda x: (x.shape, x.dtype), shapes)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        # one ulp apart: a jitted and an eager `normal * std`
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    assert got["blocks"]["moe_gate"].dtype == jnp.float32


def test_num_params_at_the_published_sizes():
    cfg = OLMOE_1B_7B
    assert cfg.num_params() == 6_919_161_856
    assert cfg.num_params() == (16 * 419_569_664 + 2 * 103_022_592 + 2_048)
    shapes = jax.eval_shape(OLMoE(cfg).init, jax.random.key(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == cfg.num_params()
    assert isinstance(cfg, OLMoEConfig) and not cfg.norm_topk_prob \
        and cfg.qk_norm and not cfg.tie_embeddings
