"""DeepSeek-V3.2-Exp as one chip's share (ISSUE 43): the program against its
plain reference ``perfbench/references/deepseek_v32.py`` on seeded weights,
tiny (d 64, one dense and two sparse layers, 4 heads, a 24-wide latent, an
``index_topk`` of 16 well under the contexts; 16 routed experts in 4 groups
of which this "chip" holds experts 4 .. 7; BS = 8), and the engine's handling
of a cache whose blocks hold a latent and an index key and are read through
a selection the model makes for every query.

Logits are compared, not tokens. Everything runs in float32 (weights, cache,
programs), so the program and the reference differ by summation order only:
``TOL`` is 2e-5 of a logit whose standard deviation is ~0.16; the measured
differences are ~1e-6, and every wrong model of
``test_reference_tells_its_neighbours_apart`` is 50 x over it.
"""

import dataclasses
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

from deepspeed_tpu.inference.v2 import InferenceEngineV2  # noqa: E402
from deepspeed_tpu.inference.v2.replica import Replica  # noqa: E402
from deepspeed_tpu.models import paged  # noqa: E402
from deepspeed_tpu.models.deepseek_v32 import (DEEPSEEK_V32,  # noqa: E402
                                               DEEPSEEK_V32_TINY,
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


# ---------------------------------------------------------------- the model
def test_parameter_counts(model):
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == CFG.num_params()
    assert ["w1" in p for p in shapes["layers"]] == [True, False, False]
    # ISSUE 43 counts the whole model at 671.88 B and the cut at 4,635.5 M
    assert abs(DEEPSEEK_V32.num_params() - 671.88e9) < 0.02e9
    cut = dataclasses.replace(DEEPSEEK_V32, n_layer=5, first_k_dense=1,
                              experts_held=16, vocab_size=16160)
    assert abs(cut.num_params() - 4635.5e6) < 0.5e6
    assert abs(cut.softmax_scale - 0.135234) < 1e-6
    assert (cut.lat_width, cut.lat_row) == (576, 640)


def test_apply_equals_reference(model, params):
    ids = np.stack(prompts_of(96, 96, seed=1))
    got = np.asarray(model.apply(params, ids))
    want = np.asarray(ref.logits(params, ids, **KW))
    assert np.abs(got - want).max() < TOL
    assert want.std() > 0.1


def _one_chunk(model, params, p, n, cache, width=None):
    """Tokens 0 .. n - 1 of ``p`` through the chunk program at start 0, in
    a chunk of ``width`` (n rounded up to blocks) over table 1 .."""
    width = width or -(-n // BS) * BS
    table = np.arange(1, 1 + width // BS, dtype=np.int32)
    ids = np.zeros((1, width), np.int32)
    ids[0, :n] = p[:n]
    pos = np.arange(width)
    tb = np.where(pos < n, table[pos // BS], 0)
    return model.apply_paged_chunk(
        params, ids, cache, tb.astype(np.int32),
        (pos % BS * (pos < n)).astype(np.int32), np.int32(0), np.int32(n),
        table)


@pytest.mark.parametrize("paged_kernel", [None, True], ids=["apply", "kernel"])
def test_selection_is_exact_and_per_token(params, paged_kernel):
    """The program reads the set the reference reads, every query of every
    layer: min(index_topk, t + 1) causal keys (more only where keys tie
    with the k-th, which a four-head indexer's relu does make): ``apply``,
    and a 96-token chunk whose read is the Pallas kernel, which is told
    the set and has to answer as the reference does."""
    seen = {}
    ids = np.stack(prompts_of(96, seed=2))
    model = DeepseekV32(CFG)
    with dsa.tapped_selection(CFG.n_layer, lambda i, q, sel:
                              seen.__setitem__(i, np.asarray(sel)[..., :96])):
        if paged_kernel is None:
            model.apply(params, ids)
        else:
            model._paged_kernel = paged_kernel
            with paged.counting_latent_reads() as reads:
                logits, _ = _one_chunk(
                    model, params, ids[0], 96,
                    model.init_paged_cache(16, BS, dtype=jnp.float32))
            assert reads == [CFG.n_layer] * 2
            assert np.abs(np.asarray(logits)[0] - np.asarray(ref.logits(
                params, ids, **KW))[0, -1]).max() < TOL
        jax.effects_barrier()
    want = ref.selection_masks(params, ids[0], **KW)
    assert sorted(seen) == [0, 1, 2]
    t = np.arange(96)
    for i, mask in enumerate(want):
        mask = np.asarray(mask)
        assert (seen[i][0] == mask).all()
        assert not np.triu(mask, 1).any()
        count = mask.sum(axis=1)
        assert (count >= np.minimum(TOPK, t + 1)).all()
        assert (count[:TOPK] == t[:TOPK] + 1).all()
        assert np.mean(count == np.minimum(TOPK, t + 1)) > 0.9
        # the selection is not the causal prefix nor a window
        assert not mask[-1, :TOPK].all() and not mask[-1, -TOPK:].all()


def test_kth_largest_is_exact():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, 7, 300)).astype(np.float32)
    x[0, 0, :50] = -np.inf
    x[1, 1, 10:20] = 0.0
    x[1, 1, 30] = -0.0
    for k in (1, 16, 250):
        got = np.asarray(paged._kth_largest(jnp.asarray(x), k))
        assert (got == -np.sort(-x, axis=-1)[..., k - 1]).all()


@pytest.mark.parametrize("paged_kernel", [False, True], ids=["xla", "kernel"])
def test_prefill_form_equals_decode_form(params, paged_kernel):
    """Token 39's logits from a 40-token chunk (the latent expanded to
    heads: the XLA read, or the Pallas kernel) and from a decode step after
    a 39-token chunk (the projections absorbed into query and output): one
    attention, two forms."""
    p = prompts_of(40, seed=3)[0]
    model = DeepseekV32(CFG)
    model._paged_kernel = paged_kernel

    def cache():
        return model.init_paged_cache(16, BS, dtype=jnp.float32)

    def chunk(n, cache):
        return _one_chunk(model, params, p, n, cache, 48)

    expanded, _ = chunk(40, cache())
    _, held = chunk(39, cache())
    tables = np.zeros((2, 6), np.int32)
    tables[1] = np.arange(1, 7)             # slot 0 stays dead
    absorbed, _ = model.apply_paged_decode(
        params, np.array([0, p[39]], np.int32), np.array([0, 39], np.int32),
        held, tables)
    want = np.asarray(ref.logits(params, p[None], **KW))[0, 39]
    assert np.abs(np.asarray(expanded)[0] - want).max() < TOL
    assert np.abs(np.asarray(absorbed)[1] - want).max() < TOL


NEIGHBOURS = [{"select": False}, {"index_topk": TOPK // 2},
              {"bias_weighs": True}, {"group_limit": False},
              {"gate_scoring": "softmax"}, {"shared": False},
              {"mscale_squared": False}, {"yarn": False},
              {"index_dtype": "bfloat16"}, {"weights": "float8_e5m2"}]


@pytest.mark.parametrize("variant", NEIGHBOURS, ids=lambda v: next(iter(v)))
def test_reference_tells_its_neighbours_apart(params, variant, monkeypatch):
    """Each neighbour of the published model (ISSUE 43) is further from the
    reference than the comparison's tolerance, so a program that was one of
    them would fail these tests: weights in float8, the lower precision,
    among them."""
    variant = dict(variant)
    ids = np.stack(prompts_of(96, seed=1))
    want = np.asarray(ref.logits(params, ids, **KW))
    if variant.pop("weights", None):
        f32 = ref._f32
        monkeypatch.setattr(ref, "_f32", lambda x: f32(
            x.astype(jnp.float8_e5m2)) if x.ndim >= 2 else f32(x))
    near = np.asarray(ref.logits(params, ids, **{**KW, **variant}))
    assert np.abs(near - want).max() > 50 * TOL


# ------------------------------------------------ the chunk's read, a kernel
def _a_read(model, params, q_pos, seed=0):
    """What layer 0's ``_attention`` hands the step for queries at
    ``q_pos`` (B, C) of a random stream: ``index_fn``, the expanded
    ``read_fn`` and what it is made from (``expand``), beside two random
    pools of 16 blocks and each row's table over them."""
    B, C = q_pos.shape
    ks = jax.random.split(jax.random.key(seed), 3)
    got = {}

    def attn_fn(lat, idx, index_fn, read_fn, topk, dv, expand=None):
        got.update(index_fn=index_fn, read_fn=read_fn, expand=expand,
                   out_shape=(B, C, CFG.n_head, dv))
        return jnp.zeros(got["out_shape"], jnp.float32)

    model._attention(jax.random.normal(ks[0], (B, C, CFG.d_model)),
                     params["layers"][0], attn_fn, jnp.asarray(q_pos))
    assert got["expand"] is not None
    got["lat"] = jax.random.normal(ks[1], (1 + 16 * B, BS, CFG.lat_row))
    got["idx"] = jax.random.normal(ks[2], (1 + 16 * B, BS,
                                           CFG.index_head_dim))
    got["tables"] = 1 + np.arange(16 * B, dtype=np.int32).reshape(B, 16)
    return got


# name: (first position a row, queries a row, real queries of them (None:
# a decode-like step, every query real and the frontier behind the last),
# index_topk, index scores rounded to halves, the kernel's query tile)
READS = {
    "one-row": ([24], 16, 16, TOPK, False, None),
    "ragged-rows": ([0, 13, 37], 16, None, TOPK, False, None),
    "under-topk": ([0], 16, 16, 64, False, None),
    "ties-with-the-kth": ([40], 16, 16, TOPK, True, None),
    "pads-past-true-len": ([16], 16, 5, TOPK, False, None),
    "query-tiles-and-padding": ([8], 80, 80, TOPK, False, 32),
    "frontier-inside-a-key-tile": ([3, 50], 16, 7, TOPK, False, None),
}


@pytest.mark.parametrize("case", sorted(READS))
def test_kernel_read_equals_xla_read(model, params, monkeypatch, case):
    """``_latent_read`` through the Pallas kernel (interpreted) against its
    XLA read, the same selection: key blocks of 32 in a 128-key table, so
    a read crosses tiles and stops inside one."""
    from deepspeed_tpu.ops.pallas import latent_attention
    starts, C, real, topk, ties, tile = READS[case]
    if tile:
        monkeypatch.setattr(latent_attention, "_QUERY_TILE", tile)
        assert latent_attention.read_tiles(
            C, 4, 16, 16, 24, 128, 32, jnp.float32)[0] == tile < C
    starts = np.asarray(starts, np.int32)
    q_pos = starts[:, None] + np.arange(C, dtype=np.int32)[None]
    frontier = starts + (C if real is None else real)
    r = _a_read(model, params, q_pos, seed=len(case))
    index_fn = r["index_fn"]
    if ties:
        index_fn = lambda keys: jnp.round(r["index_fn"](keys) * 2.0) / 2.0
    taken = []
    real_kth = paged._kth_largest

    def kth(scores, k):
        thr = real_kth(scores, k)
        taken.append(np.asarray((scores >= thr[..., None])
                                & (scores > -jnp.inf)).sum(-1))
        return thr

    monkeypatch.setattr(paged, "_kth_largest", kth)
    args = (r["lat"], r["idx"], jnp.asarray(r["tables"]), jnp.asarray(q_pos),
            jnp.asarray(frontier), index_fn, r["read_fn"], topk,
            r["out_shape"], 32)
    want = np.asarray(paged._latent_read(*args))
    got = np.asarray(paged._latent_read(*args, r["expand"]))
    assert want.shape == got.shape == r["out_shape"]
    live = q_pos < frontier[:, None]
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-6)
    assert np.abs(want[live]).max() > 0.1
    # what the case is named for
    count = taken[0][live]
    causal = (q_pos + 1)[live]
    if case == "under-topk":
        assert (count == causal).all()
    elif ties:
        assert (count > np.minimum(causal, topk)).any()
    else:
        assert (count >= np.minimum(causal, topk)).all() \
            and (count < causal).any()



# ----------------------------------------------------------------- the share
def test_the_shares_add_up_to_the_uncut_layer(model, params):
    """The routed parts the four shares of a layer compute (experts 4 r ..
    4 r + 3 each, routing over all 16), with the shared expert every chip
    computes alike counted once, are the uncut reference layer."""
    rng = jax.random.key(5)
    x = jax.random.normal(rng, (2, 24, CFG.d_model), jnp.float32)
    p = dict(params["layers"][1])
    # at d 64 the seeded gate's scores hardly differ between tokens and the
    # bias sends every token to the same two groups: two shares would hold
    # nothing to add. A gate twenty times the seed's spreads them
    p["gate"] = p["gate"] * 20.0
    E, D, F = CFG.n_routed_experts, CFG.d_model, CFG.moe_d_ff
    ks = jax.random.split(rng, 3)
    whole = {"moe_w1": jax.random.normal(ks[0], (E, D, F)) * 0.1,
             "moe_w3": jax.random.normal(ks[1], (E, D, F)) * 0.1,
             "moe_w2": jax.random.normal(ks[2], (E, F, D)) * 0.1}
    uncut = ref._ffn(x.reshape(-1, D), {**p, **whole}, jax.nn.silu,
                     ref._constants({**KW, "experts_offset": 0}))
    shared = model._swiglu(x, p["ws1"], p["ws2"])
    total = shared
    for r in range(4):
        share = DeepseekV32(dataclasses.replace(CFG, experts_offset=4 * r))
        mine = {k: w[4 * r:4 * r + 4] for k, w in whole.items()}
        part = share._moe(x, {**p, **mine}) - shared
        assert float(jnp.abs(part).max()) > 1e-3
        total = total + part
    assert np.abs(np.asarray(total).reshape(-1, D)
                  - np.asarray(uncut)).max() < TOL


@pytest.mark.parametrize("backend", ["ragged", "forward"])
def test_rows_of_absent_experts_never_reach_the_products(backend):
    """The grouped products are told of the held experts' rows alone: the
    groups' sum is the rows routed here, the rest sort behind them and come
    back as nothing, through ``lax.ragged_dot`` and through the forward
    kernel (interpreted)."""
    from deepspeed_tpu.moe import sharded_moe
    rng = np.random.default_rng(0)
    S, k, D, F, held = 40, 4, 128, 128, (4, 4)
    xs = jnp.asarray(rng.normal(size=(S, D)), jnp.float32)
    experts = jnp.asarray(np.stack([rng.permutation(16)[:k]
                                    for _ in range(S)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(size=(S, k)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.normal(size=(4, D, F)) * 0.1, jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(4, F, D)) * 0.1, jnp.float32)
    seen = []
    real = sharded_moe._grouped_swiglu_ffn

    def spy(xr, w1, w3, w2, group_sizes, params):
        seen.append((np.asarray(group_sizes), params.get("backend")))
        return real(xr, w1, w3, w2, group_sizes, params)

    sharded_moe._grouped_swiglu_ffn = spy
    try:
        got = sharded_moe.moe_swiglu_routed(
            xs, weights, experts, w1, w3, w2,
            {"backend": backend} if backend == "forward" else False,
            held=held)
    finally:
        sharded_moe._grouped_swiglu_ffn = real
    local = np.asarray(experts) - held[0]
    mine = (local >= 0) & (local < held[1])
    (sizes, took), = seen
    assert took == backend
    assert (sizes == np.bincount(local[mine], minlength=4)).all()
    assert sizes.sum() == mine.sum() < S * k
    want = np.zeros((S, D), np.float32)
    for s in range(S):
        for j in range(k):
            if mine[s, j]:
                e = local[s, j]
                h = jax.nn.silu(xs[s] @ w1[e]) * (xs[s] @ w3[e])
                want[s] += float(weights[s, j]) * np.asarray(h @ w2[e])
    assert np.abs(np.asarray(got) - want).max() < 1e-4


# --------------------------------------------------------------- the engine
@pytest.fixture(scope="module", params=["auto", True], ids=["xla", "kernel"])
def mixed(request, params):
    """Three prompts at once through 16-token chunks: 5 tokens (one padded
    chunk, all keys selected to the end), 21 (two chunks, past index_topk
    in the second), 70 (five chunks across block boundaries), the later
    ones chunked while the earlier decode (fused dispatches), then 8-step
    decode dispatches to position 110. ``paged_kernel`` "auto" is the XLA
    read off a TPU, True the Pallas kernel (interpreted) in every chunk."""
    prompts = prompts_of(5, 21, 70)
    eng = TapEngine(DeepseekV32(CFG), {**ENGINE, "paged_kernel": request.param},
                    params=params)
    from deepspeed_tpu.inference.v2 import engine_v2
    spans, real = [], engine_v2.span

    def recording(name, **stats):
        if name == "dstpu.engine.dispatch":
            spans.append((stats["kind"], stats))
        return real(name, **stats)

    engine_v2.span = recording
    try:
        out = serve(eng, prompts, [40, 40, 40])
    finally:
        engine_v2.span = real
    return eng, prompts, out, spans


@pytest.mark.parametrize("which", [0, 1, 2],
                         ids=["one-chunk", "two-chunks", "five-chunks"])
def test_engine_equals_reference(params, mixed, which):
    _, prompts, out, spans = mixed
    tokens, rows = out[which]
    assert len(tokens) == 40 and rows.shape == (40, CFG.vocab_size)
    want = reference_rows(params, prompts[which], tokens)
    assert np.abs(rows - want).max() < TOL
    # and the comparison would have seen the nearest neighbours
    for variant in ({"select": False}, {"index_topk": TOPK // 2}):
        far = reference_rows(params, prompts[which], tokens, **variant)
        assert np.abs(far - want).max() > 50 * TOL
    assert {"chunk", "fused", "decode"} <= {kind for kind, _ in spans}


@pytest.mark.parametrize("count", [1, 8])
def test_streams_equal_whatever_the_fused_count(params, mixed, monkeypatch,
                                                count):
    """The decode steps a fused dispatch carries change no token: a single
    step, and the eight every fused dispatch once took from the config,
    give the streams of ``mixed`` (the engine's own count), here beside a
    budget that ends inside a fused dispatch: the steps left over run for
    a sequence that is gone, and nobody reads them."""
    from deepspeed_tpu.inference.v2 import engine_v2
    monkeypatch.setattr(engine_v2, "_FUSED_STEPS", count)
    base, prompts, want, spans = mixed
    eng = TapEngine(DeepseekV32(CFG),
                    {**ENGINE, "paged_kernel": base.config.paged_kernel},
                    params=params)
    steps, real = set(), eng._dispatch_span
    eng._dispatch_span = lambda kind, active, n, *a, **kw: (
        steps.add((kind, n)), real(kind, active, n, *a, **kw))[1]
    out = serve(eng, prompts, [40, 4, 40])
    for (tokens, _), (whole, _), n in zip(out, want, (40, 4, 40)):
        assert np.array_equal(tokens, whole[:n])
    mgr = eng.state_mgr
    assert mgr.allocator.free_blocks == mgr.allocator.total_blocks
    assert steps == {("chunk", 0), ("fused", count), ("decode", 8)}
    assert eng.telemetry_snapshot()["fused_dispatches"] > 4


@pytest.mark.parametrize("paged_kernel", ["auto", True], ids=["xla", "kernel"])
def test_bucketed_prefill_equals_reference(params, paged_kernel):
    """No split-fuse: a prompt goes through the bucketed prefill program,
    the chunk program at start 0 (70 tokens in a 96-token bucket)."""
    eng = TapEngine(DeepseekV32(CFG),
                    {**ENGINE, "splitfuse_tokens": 0, "prompt_bucket": 32,
                     "paged_kernel": paged_kernel}, params=params)
    prompts = prompts_of(70, 9, seed=4)
    for (tokens, rows), p in zip(serve(eng, prompts, [12, 12]), prompts):
        assert np.abs(rows - reference_rows(params, p, tokens)).max() < TOL


def test_cache_is_latent_blocks_under_the_tables(mixed):
    eng = mixed[0]
    assert set(eng.cache) == {"lat", "idx"} and eng._slot_bytes == 0
    assert [x.shape[1:] for x in eng.cache["lat"]] \
        == [(BS, CFG.lat_row)] * CFG.n_layer
    assert eng._latent_layers == CFG.n_layer and not eng._slot_state
    assert eng._block_bytes == CFG.n_layer * BS * 4 * (
        CFG.lat_row + CFG.index_head_dim)
    assert eng.telemetry_snapshot()["cache_bytes_per_live_token"] \
        >= eng._block_bytes / BS
    geom = paged.geometry(eng.model)
    assert set(geom.kinds) == {paged.LATENT}
    assert not paged.uses_decode_kernel(eng.model, SLOTS, 32, BS,
                                        jnp.float32)


@pytest.mark.parametrize("paged_kernel", ["auto", True], ids=["xla", "kernel"])
def test_dispatch_spans_count_the_selected_read(params, monkeypatch,
                                                paged_kernel):
    """``index_keys`` and ``attended_keys`` on every dispatch span, against
    a count made a query at a time: the causal keys of each real query
    token, and min(that, index_topk), x 3 latent layers; and
    ``latent_read_calls`` / ``latent_read_kernel_calls``: the selected reads
    the span's program makes, 3 a chunk or a prefill and 3 a decode step (0
    on the dispatch that traces the program), of which the chunk's are the
    Pallas kernel where the engine's ``paged_kernel`` gives one
    (``test_fused_dispatch_counts_its_reads`` has the fused dispatch)."""
    from deepspeed_tpu.inference.v2 import engine_v2
    said = []
    real = engine_v2.span

    def recording(name, **stats):
        if name in ("dstpu.engine.dispatch", "dstpu.engine.prefill"):
            said.append((name, stats))
        return real(name, **stats)

    monkeypatch.setattr(engine_v2, "span", recording)
    kernel = paged_kernel is True
    config = {**ENGINE, "paged_kernel": paged_kernel}
    eng = InferenceEngineV2(DeepseekV32(CFG), config, params=params)
    eng.put(prompts_of(37, seed=6)[0], 11)
    while eng.has_work:
        eng.step()
    L, steps = CFG.n_layer, ENGINE["decode_steps_per_dispatch"]
    assert [st["kind"] for _, st in said] == ["chunk"] * 3 + ["decode"] * 2
    # one sequence: prompt tokens 0 .. 36 in chunks, then decode steps at
    # positions 37 .. (a dispatch runs all 8 steps; the last runs past
    # the budget, and the span counts what the device does)
    contexts, traced = [], set()
    for _, st in said:
        contexts += [len(contexts) + 1 + j
                     for j in range(st["chunk_tokens"] + st["steps"])]
        mine = contexts[-(st["chunk_tokens"] + st["steps"]):]
        assert st["index_keys"] == L * sum(mine)
        assert st["attended_keys"] == L * sum(min(c, TOPK) for c in mine)
        want = {"chunk": (L, L * kernel), "decode": (L * steps, 0)}[
            st["kind"]] if st["kind"] in traced else (0, 0)
        traced.add(st["kind"])
        assert (st["latent_read_calls"],
                st["latent_read_kernel_calls"]) == want, st
    assert sum(st["chunk_tokens"] for _, st in said) == 37
    # 2 chunks' and 1 decode dispatch's reads were counted
    assert eng.telemetry_snapshot()["latent_kernel_share"] \
        == round(2 * L * kernel / (2 * L + L * steps), 4)
    bucketed = InferenceEngineV2(
        DeepseekV32(CFG), {**config, "splitfuse_tokens": 0,
                           "prompt_bucket": 32}, params=params)
    del said[:]
    for _ in range(2):                      # the second finds it traced
        bucketed.put(prompts_of(37, seed=6)[0], 2)
    while bucketed.has_work:
        bucketed.step()
    first, second = [st for name, st in said
                     if name == "dstpu.engine.prefill"]
    for st in (first, second):
        assert st["index_keys"] == L * 37 * 38 // 2
        assert st["attended_keys"] == L * sum(min(c, TOPK)
                                              for c in range(1, 38))
    assert (first["latent_read_calls"], second["latent_read_calls"],
            second["latent_read_kernel_calls"]) == (0, L, L * kernel)


def test_fused_dispatch_counts_its_reads(mixed):
    """A fused dispatch's program holds a chunk and the engine's count of
    decode steps for its company: 3 x (1 + steps) selected reads (5 x (1 +
    2) = 15 with the cell's five latent layers), of which the chunk's 3
    (5) are the kernel where the engine runs kernels; the telemetry's
    ``latent_kernel_share`` is their share of all the engine's reads."""
    from deepspeed_tpu.inference.v2.engine_v2 import _FUSED_STEPS
    eng, _, _, spans = mixed
    L = CFG.n_layer
    kernel = eng.config.paged_kernel is True
    fused = [st for kind, st in spans if kind == "fused"]
    assert len(fused) > 1
    assert (fused[0]["latent_read_calls"],
            fused[0]["latent_read_kernel_calls"]) == (0, 0)   # it traces
    for st in fused[1:]:
        assert st["steps"] == _FUSED_STEPS != ENGINE[
            "decode_steps_per_dispatch"]
        assert (st["latent_read_calls"], st["latent_read_kernel_calls"]) \
            == (L * (1 + _FUSED_STEPS), L * kernel)
    reads = sum(st["latent_read_calls"] for _, st in spans)
    mine = sum(st["latent_read_kernel_calls"] for _, st in spans)
    assert eng.telemetry_snapshot()["latent_kernel_share"] \
        == round(mine / reads, 4)
    assert (mine > 0) == kernel


# ------------------------------------------------------------- the refusals
@pytest.mark.parametrize("knobs, named", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"spec_draft": True}, "spec_draft"),
    ({"draft": True}, "draft model"),
    ({"kv_host_offload": True, "device_kv_blocks": 8}, "kv_host_offload"),
], ids=lambda x: x if isinstance(x, str) else "")
def test_engine_refuses_by_name(model, params, knobs, named):
    knobs = dict(knobs)
    extra = {"draft_model": model, "draft_params": params} \
        if knobs.pop("draft", False) or "spec_draft" in knobs else {}
    with pytest.raises(ValueError, match=named) as e:
        InferenceEngineV2(model, {**ENGINE, **knobs}, params=params,
                          **extra)
    assert "latent" in str(e.value)


def test_kv_transfer_refused_and_auto_is_off(model, params):
    eng = InferenceEngineV2(model, ENGINE, params=params)
    assert eng.prefix_cache is None and eng.draft_model is None
    for role in ("prefill", "decode"):
        with pytest.raises(RuntimeError, match="kv_transfer"):
            Replica("r", eng, role=role)
    assert Replica("r", eng).role == "colocated"


def test_a_share_outside_the_published_experts_is_refused():
    with pytest.raises(ValueError, match="held experts"):
        dataclasses.replace(CFG, experts_offset=14)
    with pytest.raises(ValueError, match="held experts"):
        dataclasses.replace(CFG, n_group=3)
