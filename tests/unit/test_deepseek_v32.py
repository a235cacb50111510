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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepseek_v32_cases import (BS, CFG, KW, TOL, TOPK, dsa, model,  # noqa: F401
                                params, prompts_of, ref)
from deepspeed_tpu.models import paged
from deepspeed_tpu.models.deepseek_v32 import DEEPSEEK_V32, DeepseekV32
from deepspeed_tpu.ops.pallas._common import counting_calls


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
            with counting_calls() as reads:
                logits, _ = _one_chunk(
                    model, params, ids[0], 96,
                    model.init_paged_cache(16, BS, dtype=jnp.float32))
            assert reads["latent_read"] == [CFG.n_layer] * 2
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
