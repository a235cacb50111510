"""The paged serving forward's seam (tier-1): ``models/paged.py`` is the
one place under ``models/`` that knows how K/V rows reach the pool and
which implementation reads them back; bucketed prefill is the chunk
program at ``start = 0``; and the engine sizes the pools by the same
answer the decode trace takes."""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.autotuning import kernel_dispatch
from deepspeed_tpu.inference.v2 import InferenceEngineV2, engine_v2
from deepspeed_tpu.models import GPT2, GPT2Config, paged
from deepspeed_tpu.models.bloom import BLOOM_TINY, Bloom
from deepspeed_tpu.models.deepseek_v32 import DEEPSEEK_V32_TINY, DeepseekV32
from deepspeed_tpu.models.gpt_neo import GPTNEO_TINY, GPTNeo
from deepspeed_tpu.models.llama import LLAMA_TINY, Llama
from deepspeed_tpu.models.mixtral import MIXTRAL_TINY, Mixtral
from deepspeed_tpu.models.phi4flash import PHI4FLASH_TINY, Phi4Flash
from deepspeed_tpu.ops.pallas import _common as pallas_common

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GPT2_TINY = GPT2Config(n_layer=2, n_head=4, d_model=64, max_seq_len=128,
                       vocab_size=256, remat=False, dtype="float32")
MODELS = {"gpt2": lambda: GPT2(GPT2_TINY),
          "llama_gqa": lambda: Llama(LLAMA_TINY),
          "mixtral": lambda: Mixtral(MIXTRAL_TINY),
          "bloom_alibi": lambda: Bloom(BLOOM_TINY),
          "phi4flash": lambda: Phi4Flash(PHI4FLASH_TINY),
          "deepseek_v32": lambda: DeepseekV32(DEEPSEEK_V32_TINY),
          "gpt_neo": lambda: GPTNeo(GPTNEO_TINY)}


@pytest.fixture(autouse=True)
def _pristine_dispatch(tmp_path, monkeypatch):
    """Private winner cache + reset process-global dispatch state."""
    monkeypatch.setenv("DSTPU_AUTOTUNE_CACHE",
                       str(tmp_path / "kernel_autotune.json"))
    monkeypatch.delenv("DSTPU_AUTOTUNE", raising=False)
    kernel_dispatch.reset()
    yield
    kernel_dispatch.reset()


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("family", ["gpt2", "llama_gqa", "mixtral"])
def test_prefill_is_the_chunk_program_at_start_zero(family, kernel):
    """``apply_paged_prefill`` == ``apply_paged_chunk`` at ``start = 0``,
    ``true_len = length``, ``table = token_blocks[::BS]``: logits and
    pools bit for bit (scratch block 0 takes the pads' rows in no
    order, so it is left out)."""
    T, length, BS, NB = 64, 37, 16, 9
    model = MODELS[family]()
    model._paged_kernel, model._paged_block_c = kernel, 16
    params = model.init(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (1, T), 0,
                             model.config.vocab_size, jnp.int32)
    real = np.arange(T) < length
    # the sequence owns blocks 3, 5, 2; pads aim at scratch block 0
    tb = jnp.asarray(np.where(real, np.array([3, 5, 2, 7])[
        np.arange(T) // BS], 0), jnp.int32)
    to = jnp.asarray(np.where(real, np.arange(T) % BS, 0), jnp.int32)
    cache = model.init_paged_cache(NB, BS)
    n = jnp.int32(length)
    lp, cp = jax.jit(model.apply_paged_prefill)(params, ids, cache, tb, to,
                                                n)
    lc, cc = jax.jit(model.apply_paged_chunk)(params, ids, cache, tb, to,
                                              jnp.int32(0), n, tb[::BS])
    np.testing.assert_array_equal(np.asarray(lp), np.asarray(lc))
    assert np.isfinite(np.asarray(lp, np.float32)).all()
    for a, b in zip(jax.tree.leaves(cp), jax.tree.leaves(cc)):
        np.testing.assert_array_equal(np.asarray(a[1:]), np.asarray(b[1:]))
    # and the prompt's rows did land in its own blocks
    assert float(jnp.abs(cp["k"][0][3].astype(jnp.float32)).sum()) > 0


@pytest.mark.parametrize("per_step", [2, 3])
@pytest.mark.parametrize("family", ["gpt2", "llama_gqa", "bloom_alibi",
                                    "phi4flash"])
def test_decode_step_in_runs_of_entries(monkeypatch, family, per_step):
    """One decode step of each family through ``batch_step`` with the
    decode kernel taking ``per_step`` table entries of a slot a grid step
    gives the logits it gives at one (the tiny models' rows are narrower
    than the lanes, so their own N is 1: the test answers for the shape
    rule). Slots of 3, 7 and 1 attended entries, one inactive between
    them; phi-4's window layers read rings of 3 blocks a slot whose first
    attended entry lies mid-ring (position 100 of a window of 8: entry 5
    of a ring table that repeats 0, 1, 2)."""
    B, BS, MB, NB = 4, 16, 8, 24
    model = MODELS[family]()
    # float32, so that the order of a softmax's sums shows nowhere
    model = type(model)(dataclasses.replace(model.config, dtype="float32"))
    model._paged_kernel, model._paged_ring_blocks = True, 3
    params = model.init(jax.random.key(0))
    kw = dict(slots=B, ring_blocks=3) if family == "phi4flash" else {}
    cache = model.init_paged_cache(NB, BS, **kw)
    leaves, tree = jax.tree.flatten(cache)
    cache = jax.tree.unflatten(tree, [
        0.5 * jax.random.normal(k, x.shape, x.dtype) for k, x in zip(
            jax.random.split(jax.random.key(1), len(leaves)), leaves)])
    lengths = jnp.asarray([37, 0, 100, 5], jnp.int32)
    tables = np.zeros((B, MB), np.int32)
    tables[0, :3], tables[2, :7], tables[3, :1] = (1, 2, 3), range(4, 11), 11
    tokens = jnp.asarray([5, 0, 7, 9], jnp.int32)
    calls = []
    real = paged.paged_decode_attention
    monkeypatch.setattr(
        paged, "paged_decode_attention",
        lambda *a, **k: calls.append(k["work"].per_step) or real(*a, **k))

    def logits(n):
        monkeypatch.setattr(paged, "decode_entries_per_step", lambda *a: n)
        out, _ = model.apply_paged_decode(params, tokens, lengths, cache,
                                          jnp.asarray(tables))
        return np.asarray(out, np.float32)[[0, 2, 3]]

    one, runs = logits(1), logits(per_step)
    assert set(calls) == {1, per_step}
    assert np.isfinite(one).all() and one.std() > 1e-3
    np.testing.assert_allclose(runs, one, rtol=2e-5, atol=2e-5)


_SEAM_NAMES = re.compile(
    r"paged_kv_write|resolve_paged_|decode_work_list"
    r"|paged_decode_attention|paged_chunk_attention")


def test_only_the_seam_names_the_paged_kernels():
    """Source lint: under ``deepspeed_tpu/models/`` only ``paged.py``
    names the pool write, the ``resolve_*`` questions, the decode work
    list or the paged attention calls — and it does name them all."""
    pkg = os.path.join(REPO, "deepspeed_tpu", "models")
    naming = {}
    for dirpath, _, names in os.walk(pkg):
        for n in names:
            if n.endswith(".py"):
                with open(os.path.join(dirpath, n), encoding="utf-8") as f:
                    found = set(_SEAM_NAMES.findall(f.read()))
                if found:
                    naming[os.path.relpath(os.path.join(dirpath, n),
                                           pkg)] = found
    assert set(naming) == {"paged.py"}, naming
    assert naming["paged.py"] == {
        "paged_kv_write", "resolve_paged_", "decode_work_list",
        "paged_decode_attention", "paged_chunk_attention"}


def _sources(*parts):
    """{path under deepspeed_tpu/: text} of the .py files under ``parts``."""
    found = {}
    pkg = os.path.join(REPO, "deepspeed_tpu")
    for dirpath, _, names in os.walk(os.path.join(pkg, *parts)):
        for n in names:
            if n.endswith(".py"):
                path = os.path.join(dirpath, n)
                with open(path, encoding="utf-8") as f:
                    found[os.path.relpath(path, pkg)] = f.read()
    return found


def test_the_engine_names_no_cache_kind():
    """Source lint: the scheduler knows no cache key, no kind, no model
    config field of one family and no mechanism's own tally (the one
    tally, ``counting_calls``, it opens round every program); exactly one
    trace-time tally exists under ``deepspeed_tpu/``, and every mechanism
    that notes its calls there has its row in the schema, which is where
    the spans' and the snapshot's names come from (``KERNEL_SHARES``) or,
    for a choice that is no kernel-or-not and that no span carries, the
    training engine's word for it (``SHAPE_PATHS``)."""
    from deepspeed_tpu.monitor.tag_schema import KERNEL_SHARES, SHAPE_PATHS
    engine = _sources("inference", "v2")[os.path.join(
        "inference", "v2", "engine_v2.py")]
    named = re.findall(
        r"\"(?:k|v|lat|idx)\"|index_topk|LATENT|STATE|_BLOCK_KEYS"
        r"|counting_(?!calls\b)\w*|sharded_moe|gated_delta_rule", engine)
    assert named == []
    everything = _sources()
    tallies = {path: n for path, text in everything.items()
               if (n := len(re.findall(r"ContextVar\(", text)))}
    assert tallies == {os.path.join("ops", "pallas", "_common.py"): 1}
    noted = {name for text in everything.values()
             for name in re.findall(r"note_call\(\s*\"(\w+)\"", text)}
    assert set(KERNEL_SHARES) == {"expert", "rule", "latent_read"}
    assert set(SHAPE_PATHS) == {"flash"}
    assert noted == set(KERNEL_SHARES) | set(SHAPE_PATHS)


@pytest.mark.parametrize("family", ["gpt2", "phi4flash", "deepseek_v32"])
def test_the_account_counts_what_the_schema_names(family):
    """The cache's stats of a dispatch and of a prefill span are the
    account's dicts, key for key the schema's two tuples."""
    from deepspeed_tpu.monitor import tag_schema
    account = paged.Account(MODELS[family](), 3, 16, 8, jnp.float32)
    lengths, tables = np.array([9, 0, 30]), np.zeros((3, 16), np.int32)
    tables[0, :2], tables[2, :4] = (1, 2), (3, 4, 5, 6)
    for got in (account.dispatch(None, None, 0, 0, 8, 5, 16),
                account.dispatch(lengths, tables, tables[:, 0] != 0, 2)):
        assert tuple(sorted(got)) == tuple(sorted(tag_schema._ACCOUNT_STATS))
        assert all(type(v) is int and v >= 0 for v in got.values())
    assert tuple(sorted(account.prefill(5, 16))) \
        == tuple(sorted(tag_schema._ACCOUNT_PREFILL_STATS))


_BY_SLOT = ("the model keeps recurrent / window state by batch slot "
            "(slot_state), which ")
_BY_SELECTION = ("the model's blocks hold a latent cache read through a "
                 "per-query selection (models/paged.py, LATENT), which ")
# kind -> (a tiny model of it, feature -> the sentence the engine raised
# with before the kinds answered for themselves; a feature not there is
# served)
REFUSALS = {
    "slot-state": ("phi4flash", {
        "prefix_cache": "prefix_cache=True: " + _BY_SLOT + "a cached block "
        "of KV does not bring back — a prefix hit would resume from a state "
        "nobody kept",
        "spec_draft": "spec_draft=True / a draft model: " + _BY_SLOT
        + "rollback_spec cannot take back once the rejected tokens have "
        "moved it",
        "kv_host_offload": "kv_host_offload: " + _BY_SLOT + "lives outside "
        "the block pool the offload tier pages",
        "kv_transfer": "disaggregated kv_transfer: " + _BY_SLOT + "the "
        "block payloads of a KV handoff do not carry"}),
    "latent": ("deepseek_v32", {
        "prefix_cache": "prefix_cache=True: " + _BY_SELECTION + "the prefix "
        "cache's copy-on-write and block reuse, written for K and V pools, "
        "have not learnt",
        "spec_draft": "spec_draft=True / a draft model: " + _BY_SELECTION
        + "a draft model's verify pass and rollback_spec have no program "
        "for",
        "kv_host_offload": "kv_host_offload: " + _BY_SELECTION + "the "
        "offload tier, which pages K and V pools, does not page",
        "kv_transfer": "disaggregated kv_transfer: the model's blocks hold "
        "a latent cache (models/paged.py, LATENT), which the K / V payloads "
        "of a KV handoff do not carry"}),
    "windowed-kv": ("gpt_neo", {
        "prefix_cache": "prefix_cache=True on a sliding-window model "
        "(attn_layer_windows set): a cached block's KV is position-valid "
        "only inside each layer's window, so reusing it under a shifted "
        "suffix serves wrong attention — disable prefix_cache for this "
        "model"}),
}
_BUILD_KNOBS = {"prefix_cache": dict(prefix_cache=True),
                "spec_draft": dict(spec_draft=True),
                "kv_host_offload": dict(kv_host_offload=True,
                                        device_kv_blocks=8)}


@pytest.mark.parametrize("feature", ["prefix_cache", "spec_draft",
                                     "kv_host_offload", "kv_transfer"])
@pytest.mark.parametrize("kind", sorted(REFUSALS))
def test_refusals_come_from_the_kinds(kind, feature):
    """What a cache kind cannot have is one table in ``models/paged.py``,
    kind x feature; the engine keeps when it refuses and with which
    exception: ``ValueError`` at build (before anything is allocated),
    ``RuntimeError`` from a handoff call."""
    family, sentences = REFUSALS[kind]
    model = MODELS[family]()
    sentence = sentences.get(feature)
    account = paged.Account(model, 3, 16, 8, jnp.float32)
    assert account.refusal(feature) == sentence
    if sentence is None:
        return
    config = dict(dtype="float32", max_batch_size=3, kv_block_size=8,
                  num_kv_blocks=32)
    if feature == "kv_transfer":
        engine = InferenceEngineV2(model, config)
        with pytest.raises(RuntimeError) as e:
            engine.hold_decode(0)
    else:
        extra = dict(draft_model=model) if feature == "spec_draft" else {}
        with pytest.raises(ValueError) as e:
            InferenceEngineV2(model, {**config, **_BUILD_KNOBS[feature]},
                              **extra)
    assert str(e.value) == sentence


@pytest.mark.parametrize("family,setting,expect", [
    ("bloom_alibi", False, True),      # ALiBi keeps the kernel
    ("bloom_alibi", "auto", True),
    ("llama_gqa", False, False),
    ("llama_gqa", True, True),
    ("gpt2", "auto", True),            # cold cache: the decode default
])
def test_pools_are_sized_by_the_decode_traces_answer(
        monkeypatch, family, setting, expect):
    """``uses_decode_kernel`` is what ``_new_paged_cache`` hands
    ``pool_block_dims`` (off the interpreter), and what the decode trace
    does: kernel call or dense reference."""
    model = MODELS[family]()
    engine = InferenceEngineV2(model, config=dict(
        max_batch_size=4, kv_block_size=16, num_kv_blocks=9,
        paged_kernel=setting, prompt_bucket=16))
    B, BS, MB = 4, 16, engine.max_blocks_per_seq
    assert paged.uses_decode_kernel(model, B, MB, BS,
                                    engine.dtype) is expect

    asked = []
    with monkeypatch.context() as m:
        m.setattr(pallas_common, "interpret_default", lambda: False)
        m.setattr(
            engine_v2, "pool_block_dims",
            lambda n, hd, kernel_layout: asked.append(kernel_layout)
            or (n,))
        engine._new_paged_cache(model, 9)
    assert asked == [expect]

    ran = []
    for name in ("paged_decode_attention",
                 "paged_decode_attention_reference"):
        real = getattr(paged, name)
        monkeypatch.setattr(
            paged, name, lambda *a, _real=real, _name=name, **kw:
            ran.append(_name) or _real(*a, **kw))
    i32 = jnp.int32
    jax.eval_shape(
        model.apply_paged_decode, engine.params,
        jax.ShapeDtypeStruct((B,), i32), jax.ShapeDtypeStruct((B,), i32),
        model.init_paged_cache(9, BS, dtype=engine.dtype),
        jax.ShapeDtypeStruct((B, MB), i32))
    kernel_ran = "paged_decode_attention" in ran
    assert kernel_ran is expect and len(set(ran)) == 1
