"""Dropless MoE at pod scale: grouped-GEMM Pallas kernel + hierarchical
ICI->DCN expert all_to_all.

Tier-1 coverage for the production MoE path: grouped-kernel parity vs
``lax.ragged_dot`` (uneven/empty groups, bf16 grads, the fused SwiGLU
chain), the warm/cold autotune HLO-identity contract for the
``moe_grouped_mm`` op, the hierarchical two-stage exchange (engages only
with a data_outer axis; int8 clamp on the DCN leg only; loss parity on
the virtual mesh), the padding audit (pad rows can never skew
group_sizes or the combine), and the EP x TP / EP x ring compositions.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.autotuning import kernel_dispatch
from deepspeed_tpu.moe.sharded_moe import (moe_swiglu_ragged_ep,
                                           resolve_grouped_params,
                                           resolve_hierarchical_a2a)
from deepspeed_tpu.ops.pallas.grouped_matmul import (
    FORWARD_ROWS_PER_GROUP, FORWARD_VMEM_BYTES, TUNE_DEFAULTS,
    forward_tiles, forward_visits, grouped_matmul, grouped_swiglu)
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.groups import TopologyConfig


@pytest.fixture(autouse=True)
def _pristine_dispatch(tmp_path, monkeypatch):
    """Private winner-cache path + reset process-global dispatch state
    (the grouped-backend resolution consults it under "auto")."""
    monkeypatch.setenv("DSTPU_AUTOTUNE_CACHE",
                       str(tmp_path / "kernel_autotune.json"))
    monkeypatch.delenv("DSTPU_AUTOTUNE", raising=False)
    kernel_dispatch.reset()
    yield
    kernel_dispatch.reset()


def _swiglu_ref(x, w1, w3, w2, gs):
    g = jax.lax.ragged_dot(x, w1, gs)
    u = jax.lax.ragged_dot(x, w3, gs)
    return jax.lax.ragged_dot(jax.nn.silu(g) * u, w2, gs)


class TestGroupedKernelParity:
    """ops/pallas/grouped_matmul.py vs lax.ragged_dot (interpreter mode
    on CPU — the driver's kernel_parity.py re-proves on real Mosaic)."""

    def _data(self, dtype, S=192, K=128, N=256, E=4, seed=0):
        ks = jax.random.split(jax.random.key(seed), 2)
        x = jax.random.normal(ks[0], (S, K), dtype) * 0.3
        w = jax.random.normal(ks[1], (E, K, N), dtype) * 0.1
        return x, w

    @pytest.mark.parametrize("sizes", [
        [50, 0, 120, 22],        # uneven + an empty group
        [192, 0, 0, 0],          # everything on one expert
        [0, 0, 0, 0],            # all groups empty (zero output)
        [1, 63, 100, 28],
    ])
    def test_forward_matches_ragged_dot(self, sizes):
        x, w = self._data(jnp.float32)
        gs = jnp.asarray(sizes, jnp.int32)
        got = jax.jit(lambda x, w: grouped_matmul(x, w, gs,
                                                  block_m=64))(x, w)
        ref = jax.lax.ragged_dot(x, w, gs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_rows_beyond_groups_are_zero(self):
        """The ragged_dot tail contract the EP transport relies on:
        rows past sum(group_sizes) come out exactly zero."""
        x, w = self._data(jnp.float32)
        gs = jnp.asarray([40, 30, 0, 10], jnp.int32)
        got = np.asarray(grouped_matmul(x, w, gs, block_m=64))
        assert np.all(got[80:] == 0.0)
        assert np.abs(got[:80]).max() > 0

    def test_bf16_grads_match_ragged_dot(self):
        x, w = self._data(jnp.bfloat16)
        gs = jnp.asarray([37, 51, 3, 101], jnp.int32)

        def lk(x, w):
            return jnp.sum(grouped_matmul(x, w, gs, block_m=64)
                           .astype(jnp.float32) ** 2)

        def lr(x, w):
            return jnp.sum(jax.lax.ragged_dot(x, w, gs)
                           .astype(jnp.float32) ** 2)

        ga = jax.grad(lk, (0, 1))(x, w)
        gr = jax.grad(lr, (0, 1))(x, w)
        for a, b, n in zip(ga, gr, ("dx", "dw")):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=5e-2, atol=5e-2, err_msg=n)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_fused_swiglu_chain(self, dtype):
        """The fused w1/w3 -> silu*mul -> w2 launch: forward and all
        four cotangents against the three-ragged_dot reference."""
        S, K, F, E = 160, 128, 256, 4
        ks = jax.random.split(jax.random.key(1), 4)
        x = jax.random.normal(ks[0], (S, K), dtype) * 0.3
        w1 = jax.random.normal(ks[1], (E, K, F), dtype) * 0.1
        w3 = jax.random.normal(ks[2], (E, K, F), dtype) * 0.1
        w2 = jax.random.normal(ks[3], (E, F, K), dtype) * 0.1
        gs = jnp.asarray([60, 0, 89, 11], jnp.int32)
        tol = dict(rtol=1e-4, atol=1e-4) if dtype == jnp.float32 \
            else dict(rtol=5e-2, atol=5e-2)
        got = grouped_swiglu(x, w1, w3, w2, gs, block_m=64)
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(_swiglu_ref(x, w1, w3, w2, gs), np.float32), **tol)

        ga = jax.grad(lambda *a: jnp.sum(
            grouped_swiglu(*a, gs, block_m=64).astype(jnp.float32) ** 2),
            (0, 1, 2, 3))(x, w1, w3, w2)
        gr = jax.grad(lambda *a: jnp.sum(
            _swiglu_ref(*a, gs).astype(jnp.float32) ** 2),
            (0, 1, 2, 3))(x, w1, w3, w2)
        for a, b, n in zip(ga, gr, ("dx", "dw1", "dw3", "dw2")):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                err_msg=n, **tol)

    def test_unaligned_dims_fall_back(self):
        """Dims that cannot form tile-aligned blocks take the ragged_dot
        fallback (identical semantics, no crash) — the tiny-model path."""
        x = jax.random.normal(jax.random.key(0), (12, 16), jnp.float32)
        w = jax.random.normal(jax.random.key(1), (2, 16, 24), jnp.float32)
        gs = jnp.asarray([5, 7], jnp.int32)
        np.testing.assert_allclose(
            np.asarray(grouped_matmul(x, w, gs)),
            np.asarray(jax.lax.ragged_dot(x, w, gs)), rtol=1e-6)


def _decode_shaped(rows, sizes, E=64, D=128, F=256, dtype=jnp.float32,
                   seed=0):
    rs = np.random.RandomState(seed)
    gs = np.zeros((E,), np.int32)
    for g, n in sizes.items():
        gs[g] = n
    assert gs.sum() <= rows
    mk = lambda *shape: jnp.asarray(rs.randn(*shape) * 0.1, dtype)
    return (mk(rows, D) * 3, mk(E, D, F), mk(E, D, F), mk(E, F, D),
            jnp.asarray(gs))


def _spread(rows, E=64):
    """``rows`` rows over E groups as evenly as they go (4 a group at a
    decode step's 256 over 64)."""
    return {g: rows // E + (g < rows % E) for g in range(E)}


class TestForwardKernel:
    """``grouped_swiglu`` with no block given: the one-launch forward
    chain, tiles from the shape, non-empty groups only (interpreter mode;
    tests/unit/test_tpu_compile.py compiles it for the chip)."""

    @pytest.mark.parametrize("case, rows, sizes", [
        ("rows8", 8, {3: 5, 60: 3}),
        ("rows16_one_a_group", 16, {g: 1 for g in range(0, 64, 4)}),
        ("rows64", 64, _spread(64)),
        ("rows256_even", 256, _spread(256)),
        ("rows256_empty_at_the_start", 256,
         {g: 8 for g in range(32, 64)}),
        ("rows256_empty_in_the_middle", 256,
         {**{g: 8 for g in range(16)}, **{g: 8 for g in range(48, 64)}}),
        ("rows256_empty_at_the_end", 256, {g: 8 for g in range(32)}),
        ("rows256_one_group", 256, {17: 256}),
        ("rows256_dead_slots", 256,
         {**{g: 28 for g in range(5, 64, 8)}, **{g: 1 for g in range(32)}}),
        ("rows256_tail_past_the_groups", 256, {0: 3, 9: 120, 63: 20}),
        ("rows200_padded_to_the_tile", 200, _spread(190)),
        ("rows256_no_rows_at_all", 256, {}),
    ])
    def test_decode_shapes_match_ragged_dot(self, case, rows, sizes):
        x, w1, w3, w2, gs = _decode_shaped(rows, sizes)
        got = np.asarray(jax.jit(grouped_swiglu)(x, w1, w3, w2, gs))
        want = np.asarray(_swiglu_ref(x, w1, w3, w2, gs))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        assert not got[int(gs.sum()):].any()

    @pytest.mark.parametrize("rows", [64, 256])
    def test_bf16_matches_ragged_dot(self, rows):
        x, w1, w3, w2, gs = _decode_shaped(rows, _spread(rows - 9),
                                           dtype=jnp.bfloat16)
        got = grouped_swiglu(x, w1, w3, w2, gs)
        assert got.dtype == jnp.bfloat16 and got.shape == x.shape
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(_swiglu_ref(x, w1, w3, w2, gs), np.float32),
            rtol=5e-2, atol=5e-2)

    def test_slices_of_f_accumulate(self):
        """A VMEM limit that holds half of F a step: two slices a visit,
        summed in the float32 accumulator."""
        from deepspeed_tpu.ops.pallas import grouped_matmul as gm
        x, w1, w3, w2, gs = _decode_shaped(64, _spread(60))
        tiles = forward_tiles(64, 128, 256, jnp.float32,
                              vmem_bytes=(4 << 20) + 700_000)
        assert tiles == (64, 128, 128)
        got = gm._swiglu_forward(x, w1, w3, w2, gs, tiles=tiles,
                                 interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(_swiglu_ref(x, w1, w3, w2, gs)),
            rtol=1e-4, atol=1e-4)

    def test_a_differentiated_call_is_the_ragged_program(self):
        """No backward of the forward launch exists: under jax.grad the
        call is lax.ragged_dot's, forward and backward."""
        x, w1, w3, w2, gs = _decode_shaped(64, _spread(64, 8), E=8)
        loss = lambda f: lambda *a: jnp.sum(f(*a, gs) ** 2)
        ga = jax.grad(loss(grouped_swiglu), (0, 1, 2, 3))(x, w1, w3, w2)
        gr = jax.grad(loss(_swiglu_ref), (0, 1, 2, 3))(x, w1, w3, w2)
        for a, b in zip(ga, gr):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        text = str(jax.make_jaxpr(jax.grad(loss(grouped_swiglu)))(
            x, w1, w3, w2))
        assert "ragged_dot" in text and "pallas_call" not in text
        assert "pallas_call" in str(jax.make_jaxpr(loss(grouped_swiglu))(
            x, w1, w3, w2))

    @pytest.mark.parametrize("case, rows, D, F, dtype, want", [
        ("olmoe_decode", 256, 2048, 1024, jnp.bfloat16, (128, 1024, 2048)),
        ("olmoe_one_slot", 8, 2048, 1024, jnp.bfloat16, (16, 1024, 2048)),
        ("olmoe_prefill", 8192, 2048, 1024, jnp.bfloat16,
         (128, 1024, 2048)),
        ("mixtral_decode", 64, 4096, 14336, jnp.bfloat16, (64, 512, 4096)),
        ("float32_rows", 20, 128, 256, jnp.float32, (24, 256, 128)),
        ("f_not_a_lane_multiple", 256, 2048, 1000, jnp.bfloat16, None),
        ("d_not_a_lane_multiple", 256, 100, 1024, jnp.bfloat16, None),
        ("tiny", 12, 16, 24, jnp.float32, None),
        ("float16", 256, 2048, 1024, jnp.float16, None),
        ("no_slice_fits", 256, 2048, 1024, jnp.bfloat16, None),
    ])
    def test_tiles_from_the_shape(self, case, rows, D, F, dtype, want):
        """Whole contraction, the widest slice of F that fits the VMEM
        limit double-buffered, rows to the sublane multiple and 128 at
        most: megabytes a weight tile at published widths; None (the
        ragged products) where no tile forms."""
        limit = (5 << 20) if case == "no_slice_fits" else FORWARD_VMEM_BYTES
        tiles = forward_tiles(rows, D, F, dtype, limit)
        assert tiles == want
        if want is not None and D >= 2048:
            tm, tf, tk = tiles
            item = jnp.dtype(dtype).itemsize
            assert tk * tf * item >= 1 << 20        # a weight tile
            held = 2 * 3 * tk * tf * item + 2 * 2 * tm * D * item \
                + 4 * tm * (D + 3 * tf)
            assert held <= FORWARD_VMEM_BYTES

    @pytest.mark.parametrize("case, rows, tm, sizes, visits", [
        # groups with rows + those that straddle a row tile's edge + the
        # row tiles that hold rows past the groups
        ("even_4_a_group", 256, 128, _spread(256), 64),
        ("a_straddle", 256, 128, {0: 100, 1: 56, 2: 100}, 4),
        ("empty_groups_cost_nothing", 256, 128, {5: 3, 40: 2}, 2 + 2),
        ("one_group_two_tiles", 256, 128, {17: 256}, 2),
        ("small_tiles_more_straddles", 256, 32, {0: 100, 1: 56, 2: 100},
         4 + 2 + 4),
        ("untouched_tiles_are_zeroed", 512, 128, {3: 10}, 1 + 4),
        ("nothing_routed", 256, 128, {}, 2),
    ])
    def test_visit_list_holds_non_empty_groups_only(self, case, rows, tm,
                                                    sizes, visits):
        gs = np.zeros((64,), np.int32)
        for g, n in sizes.items():
            gs[g] = n
        wid, mtid, lo, hi, n = map(np.asarray, forward_visits(
            jnp.asarray(gs), rows, tm))
        assert n == visits and len(wid) == rows // tm + 64
        ends = np.cumsum(gs)
        kept = 0
        for i in range(n):
            if hi[i] > lo[i]:               # a group's visit of a tile
                g = wid[i]
                assert gs[g] > 0 and (lo[i], hi[i]) == (ends[g] - gs[g],
                                                        ends[g])
                a, b = max(lo[i], mtid[i] * tm), min(hi[i],
                                                     (mtid[i] + 1) * tm)
                assert b > a                # it keeps rows of that tile
                kept += b - a
            else:                   # a tile with rows past the groups
                assert (mtid[i] + 1) * tm > ends[-1]
                assert wid[i] == max([g for g in sizes if sizes[g]] or [0])
        assert kept == gs.sum()
        # visits of a row tile are consecutive, tiles in order
        assert (np.diff(mtid[:n]) >= 0).all()
        assert set(mtid[:n]) == set(range(rows // tm))


class TestGroupedDispatch:
    """The ``grouped_kernel`` knob: True / False / a dict force a path,
    "auto" decides from platform, dtype and shape."""

    def test_knob_resolution(self):
        assert resolve_grouped_params(False, 256, 4, 128, 256,
                                      jnp.float32)["backend"] == "ragged"
        p = resolve_grouped_params(True, 256, 4, 128, 256, jnp.float32)
        assert p["backend"] == "kernel"
        p = resolve_grouped_params({"backend": "kernel", "block_m": 64},
                                   256, 4, 128, 256, jnp.float32)
        assert p == dict(TUNE_DEFAULTS, backend="kernel", block_m=64)
        # "auto" off the TPU = the ragged defaults
        assert resolve_grouped_params("auto", 256, 4, 128, 256,
                                      jnp.float32) == TUNE_DEFAULTS

    def test_warm_cache_no_longer_steers_auto(self):
        """The 'moe_grouped_mm' winner cache is not asked any more: a
        cached kernel winner for the very bucket leaves "auto" where
        platform, dtype and shape put it (off the TPU: ragged)."""
        from deepspeed_tpu.autotuning import KernelCache
        from deepspeed_tpu.ops.pallas._common import moe_grouped_bucket
        path = os.environ["DSTPU_AUTOTUNE_CACHE"]
        S, E, M, F = 256, 4, 128, 256
        bucket = moe_grouped_bucket(S, E, M, F)
        c = KernelCache()
        c.put(kernel_dispatch.device_kind(), "moe_grouped_mm", bucket,
              "float32", {"backend": "kernel", "block_m": 64,
                          "block_n": 128, "block_k": 128})
        c.save(path)
        kernel_dispatch.configure(mode="cache_only")
        assert resolve_grouped_params("auto", S, E, M, F,
                                      jnp.float32) == TUNE_DEFAULTS

    @pytest.mark.parametrize("case, rows, E, D, F, dtype, want", [
        ("olmoe_decode", 256, 64, 2048, 1024, jnp.bfloat16, "forward"),
        ("olmoe_prefill_1024", 8192, 64, 2048, 1024, jnp.bfloat16,
         "forward"),
        ("mixtral_decode", 64, 8, 4096, 14336, jnp.bfloat16, "forward"),
        ("float32", 256, 64, 2048, 1024, jnp.float32, "forward"),
        ("at_the_threshold", FORWARD_ROWS_PER_GROUP * 64, 64, 2048, 1024,
         jnp.bfloat16, "forward"),
        ("past_the_threshold", FORWARD_ROWS_PER_GROUP * 64 + 8, 64, 2048,
         1024, jnp.bfloat16, "ragged"),
        ("float16", 256, 64, 2048, 1024, jnp.float16, "ragged"),
        ("no_tile", 12, 2, 16, 24, jnp.float32, "ragged"),
        ("off_tpu", 256, 64, 2048, 1024, jnp.bfloat16, "ragged"),
        ("partitioned", 256, 64, 2048, 1024, jnp.bfloat16, "ragged"),
    ])
    def test_auto_decides_from_platform_dtype_and_shape(
            self, monkeypatch, case, rows, E, D, F, dtype, want):
        """On a TPU a SwiGLU call of at most FORWARD_ROWS_PER_GROUP rows
        a group whose shape tiles takes the forward kernel (the explicit
        knob's 128 tiles stay in the dict for the quantised experts);
        another dtype, a shape that forms no tile, more rows a group, a
        program GSPMD partitions, and every call off the TPU are the
        ragged program."""
        if case != "off_tpu":
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        if case == "partitioned":
            groups.reset()
            groups.initialize(TopologyConfig(tensor_parallel_size=2),
                              devices=jax.devices()[:2])
            with jax.set_mesh(groups.get_mesh()):
                p = resolve_grouped_params("auto", rows, E, D, F, dtype)
            groups.reset()
        else:
            p = resolve_grouped_params("auto", rows, E, D, F, dtype)
        assert p == dict(TUNE_DEFAULTS, backend=want)

    def test_cold_cache_hlo_identical_to_ragged(self):
        """moe_layer_ragged with grouped_kernel="auto" off the TPU lowers
        to the byte-identical program of grouped_kernel=False, and so
        does a SwiGLU call: tier-1's "auto" is today's program."""
        from deepspeed_tpu.moe.sharded_moe import moe_layer_ragged
        kernel_dispatch.configure(mode="cache_only")
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(64, 128), jnp.float32)
        gate_w = jnp.asarray(rs.randn(128, 4) * 0.1, jnp.float32)
        wi = jnp.asarray(rs.randn(4, 128, 256) * 0.1, jnp.float32)
        bi = jnp.zeros((4, 256), jnp.float32)
        wo = jnp.asarray(rs.randn(4, 256, 128) * 0.1, jnp.float32)
        bo = jnp.zeros((4, 128), jnp.float32)

        def lower(knob):
            return jax.jit(
                lambda *a: moe_layer_ragged(*a, k=2,
                                            grouped_kernel=knob)
            ).lower(x, gate_w, wi, bi, wo, bo).as_text()

        assert lower("auto") == lower(False)
        # and the kernel knob produces a genuinely different program
        assert lower(True) != lower(False)

        from deepspeed_tpu.moe.sharded_moe import _grouped_swiglu_ffn
        xs = jnp.asarray(rs.randn(64, 128), jnp.float32)
        gs = jnp.asarray([20, 0, 30, 14], jnp.int32)

        def swiglu(knob, text=lambda f, *a: jax.jit(f).lower(*a).as_text()):
            gp = resolve_grouped_params(knob, 64, 4, 128, 256, jnp.float32)
            return text(lambda *a: _grouped_swiglu_ffn(*a, gs, gp),
                        xs, wi, wi, wo)

        jaxpr = lambda f, *a: str(jax.make_jaxpr(f)(*a))
        assert swiglu("auto") == swiglu(False)
        assert "ragged_dot" in swiglu("auto", jaxpr)
        assert "pallas_call" in swiglu({"backend": "forward"}, jaxpr)


def _swiglu_params(M=16, F=32, E=8, seed=0):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(M, E) * 0.1, jnp.float32),
            jnp.asarray(rs.randn(E, M, F) * 0.1, jnp.float32),
            jnp.asarray(rs.randn(E, M, F) * 0.1, jnp.float32),
            jnp.asarray(rs.randn(E, F, M) * 0.1, jnp.float32))


def _swiglu_dense(x, gate_w, w1, w3, w2, k=2):
    logits = x.astype(jnp.float32) @ gate_w
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(gate_w.shape[-1]):
        o = (jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]
        w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        y = y + o * w[:, None]
    return y


class TestHierarchicalA2A:
    """The two-stage ICI->DCN expert exchange (acceptance: engages only
    when the mesh has a data_outer axis; int8 clamp on the DCN leg
    only; loss parity on the virtual mesh)."""

    def _outer_mesh(self, tensor=1):
        groups.reset()
        # dp with zero_shard_size -> data_outer=2 on the 8-device world
        return groups.initialize(TopologyConfig(
            data_parallel_size=4 // tensor, zero_shard_size=2 // tensor,
            expert_parallel_size=2, tensor_parallel_size=tensor))

    def test_resolution_gating(self):
        assert resolve_hierarchical_a2a("auto", 2, 8, 2) is True
        assert resolve_hierarchical_a2a("auto", 1, 8, 2) is False
        assert resolve_hierarchical_a2a("auto", 2, 6, 2) is False
        assert resolve_hierarchical_a2a(False, 2, 8, 2) is False
        assert resolve_hierarchical_a2a(True, 1, 8, 2) is False
        with pytest.raises(ValueError, match="divisible"):
            resolve_hierarchical_a2a(True, 2, 6, 2)

    @pytest.mark.parametrize("odd_tokens", [False, True])
    def test_loss_parity_at_data_outer(self, odd_tokens):
        """y at data_outer=2 x expert=2 (experts over the combined grid,
        two-stage exchange) == the dense single-shard reference."""
        gate_w, w1, w3, w2 = _swiglu_params()
        rs = np.random.RandomState(1)
        S = 15 if odd_tokens else 16
        x = jnp.asarray(rs.randn(S, 16) * 0.3, jnp.float32)
        ref = _swiglu_dense(x, gate_w, w1, w3, w2)
        topo = self._outer_mesh()
        with jax.set_mesh(topo.mesh):
            y = jax.jit(lambda *a: moe_swiglu_ragged_ep(*a, k=2))(
                x, gate_w, w1, w3, w2)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_engages_only_with_data_outer_axis(self):
        """Acceptance: the staged exchange (an all_to_all over
        'data_outer') appears in the traced program iff the mesh has a
        data_outer axis > 1."""
        gate_w, w1, w3, w2 = _swiglu_params()
        x = jnp.zeros((16, 16), jnp.float32)
        f = lambda *a: moe_swiglu_ragged_ep(*a, k=2)   # noqa: E731
        topo = self._outer_mesh()
        with jax.set_mesh(topo.mesh):
            jaxpr_hier = str(jax.make_jaxpr(f)(x, gate_w, w1, w3, w2))
        groups.reset()
        flat = groups.initialize(TopologyConfig(expert_parallel_size=4))
        with jax.set_mesh(flat.mesh):
            jaxpr_flat = str(jax.make_jaxpr(f)(x, gate_w, w1, w3, w2))
        # the DCN hop is an all_to_all whose axis_name is data_outer —
        # present iff the staged path engaged (the mesh-shape dict in
        # the jaxpr always NAMES the axis, so probe the collective)
        probe = "axis_name=data_outer"
        assert probe in jaxpr_hier
        assert "all_to_all" in jaxpr_flat
        assert probe not in jaxpr_flat

    def test_int8_clamp_on_dcn_leg_only(self):
        """dcn_quantize perturbs the hierarchical path (bounded int8
        round-trip error on the DCN legs) but is a NO-OP on a flat mesh
        — there is no DCN leg to clamp (bitwise-identical output)."""
        gate_w, w1, w3, w2 = _swiglu_params()
        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.randn(16, 16) * 0.3, jnp.float32)
        ref = _swiglu_dense(x, gate_w, w1, w3, w2)
        topo = self._outer_mesh()
        with jax.set_mesh(topo.mesh):
            yq = jax.jit(lambda *a: moe_swiglu_ragged_ep(
                *a, k=2, dcn_quantize=True))(x, gate_w, w1, w3, w2)
        err = np.abs(np.asarray(yq) - np.asarray(ref)).max()
        assert 0 < err < 0.05, err     # clamped, not broken
        groups.reset()
        flat = groups.initialize(TopologyConfig(expert_parallel_size=4))
        with jax.set_mesh(flat.mesh):
            ya = jax.jit(lambda *a: moe_swiglu_ragged_ep(
                *a, k=2, dcn_quantize=True))(x, gate_w, w1, w3, w2)
            yb = jax.jit(lambda *a: moe_swiglu_ragged_ep(
                *a, k=2, dcn_quantize=False))(x, gate_w, w1, w3, w2)
        np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))

    def test_hier_with_tp_and_kernel_backend(self):
        """data_outer x expert x tensor with the grouped kernel forced:
        the full composition still matches the dense reference (tiny
        dims -> the kernel wrapper falls back per-call where blocks
        cannot form; the composition contract is what's under test)."""
        gate_w, w1, w3, w2 = _swiglu_params()
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(16, 16) * 0.3, jnp.float32)
        ref = _swiglu_dense(x, gate_w, w1, w3, w2)
        topo = self._outer_mesh(tensor=2)
        with jax.set_mesh(topo.mesh):
            y = jax.jit(lambda *a: moe_swiglu_ragged_ep(
                *a, k=2, grouped_kernel=True))(x, gate_w, w1, w3, w2)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


class TestPaddingAudit:
    """Pad rows added for the shard split must never skew group_sizes
    or the combine (their gate weights are masked to zero and they ride
    with the invalid expert id)."""

    @pytest.mark.parametrize("hier", [False, True])
    def test_counts_exclude_pad_rows(self, hier):
        gate_w, w1, w3, w2 = _swiglu_params()
        rs = np.random.RandomState(4)
        S, k = 13, 2                   # 13 % 4 != 0 -> 3 pad rows
        x = jnp.asarray(rs.randn(S, 16) * 0.3, jnp.float32)
        groups.reset()
        topo = groups.initialize(
            TopologyConfig(data_parallel_size=4, zero_shard_size=2,
                           expert_parallel_size=2) if hier
            else TopologyConfig(expert_parallel_size=4))
        with jax.set_mesh(topo.mesh):
            y, counts = jax.jit(lambda *a: moe_swiglu_ragged_ep(
                *a, k=k, return_counts=True))(x, gate_w, w1, w3, w2)
        # the audit observable: every real token dispatches exactly k
        # times, pad rows never enter a group
        assert int(np.asarray(counts).sum()) == S * k
        ref = _swiglu_dense(x, gate_w, w1, w3, w2)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


class TestComposition:
    """EP x TP and EP x ring are supported scenarios."""

    def test_ep_tp_with_kernel_backend(self):
        """EP x TP through the grouped kernel at kernel-aligned dims
        (M=128, F=256): interpret-mode Pallas inside the full-manual
        shard_map region matches the dense reference."""
        gate_w, w1, w3, w2 = _swiglu_params(M=128, F=256, E=4)
        rs = np.random.RandomState(5)
        x = jnp.asarray(rs.randn(24, 128) * 0.3, jnp.float32)
        ref = _swiglu_dense(x, gate_w, w1, w3, w2)
        groups.reset()
        topo = groups.initialize(TopologyConfig(
            expert_parallel_size=2, tensor_parallel_size=2))
        with jax.set_mesh(topo.mesh):
            y = jax.jit(lambda *a: moe_swiglu_ragged_ep(
                *a, k=2, grouped_kernel=True))(x, gate_w, w1, w3, w2)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_ep_ring_model_matches_unsharded(self):
        """EP x ring (long-context MoE): GPT2MoE with zigzag ring
        attention on an expert=2 x seq=2 mesh reproduces the unsharded
        model's logits."""
        from deepspeed_tpu.models import GPT2MoE, GPT2MoEConfig
        kw = dict(n_layer=2, n_head=4, d_model=32, max_seq_len=32,
                  vocab_size=128, remat=False, dtype="float32",
                  num_experts=4, moe_top_k=2, moe_backend="ragged")
        dense = GPT2MoE(GPT2MoEConfig(**kw))
        ring = GPT2MoE(GPT2MoEConfig(attention_backend="ring", **kw))
        params = dense.init(jax.random.key(0))
        # batch divisible by the batch axes (data x expert = 4 on the
        # 8-device expert=2 x seq=2 mesh)
        ids = jax.random.randint(jax.random.key(1), (4, 32), 0, 128,
                                 dtype=jnp.int32)
        ref = dense.apply(params, ids)
        groups.reset()
        topo = groups.initialize(TopologyConfig(
            expert_parallel_size=2, seq_parallel_size=2))
        with jax.set_mesh(topo.mesh):
            out = jax.jit(
                lambda p, i: ring.apply(p, i, seq_sharded=True))(
                params, ids)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-4, atol=5e-5)

    def test_engine_reports_in_scan_a2a(self):
        """engine.verify_comm_overlap on an EP mesh reports the expert
        all_to_all INSIDE the scan body (in_loop_by_op) — the dispatch
        overlaps layer compute instead of serializing after the scan."""
        import deepspeed_tpu
        from deepspeed_tpu.models import GPT2MoE, GPT2MoEConfig
        groups.reset()
        topo = groups.initialize(TopologyConfig(expert_parallel_size=2))
        cfg = GPT2MoEConfig(n_layer=2, n_head=2, d_model=32,
                            max_seq_len=16, vocab_size=128, remat=True,
                            dtype="float32", num_experts=4, moe_top_k=2,
                            moe_backend="ragged")
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=GPT2MoE(cfg), topology=topo,
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 1,
                    "steps_per_print": 0,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 2}})
        # engine installed the moe config block on the model
        assert engine.model._moe_cfg.grouped_kernel == "auto"
        rng = np.random.RandomState(0)
        batch = {"input_ids": rng.randint(
            0, cfg.vocab_size,
            (engine.config.train_batch_size, cfg.max_seq_len))
            .astype(np.int32)}
        report = engine.verify_comm_overlap(batch)
        assert report["in_loop_by_op"].get("all-to-all", 0) >= 1, \
            report["in_loop_by_op"]
