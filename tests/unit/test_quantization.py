"""Low-precision levers (tier-1): weight-only int8/int4 round trips
(channelwise scales, the two-per-byte int4 packing, the odd-dim
fallback), the dynamic W8A8 matmuls (dense + ragged grouped) with their
straight-through gradients, cold-cache byte-identity for BOTH engines
(a training step with the quantize block present-with-defaults or all
"auto" lowers the exact program the block's absence does; a v2 serving
engine with weight_quant="auto" lowers byte-identical decode/chunk
programs to weight_quant=False), and the W8A16 logit-drift bound on a
fixed tiny checkpoint (greedy token identity is NOT required — the
contract is bounded drift, gated numerics)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.autotuning import kernel_dispatch
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import GPT2, GPT2Config
from deepspeed_tpu.ops.int8_weights import (Int4Weight, Int8Weight,
                                            quantize_leaf)
from deepspeed_tpu.ops.pallas.quantization import (
    dequantize_channelwise, grouped_int8_matmul, int8_matmul, pack_int4,
    quantize_channelwise, unpack_int4)
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.groups import TopologyConfig


@pytest.fixture(autouse=True)
def _pristine_dispatch(tmp_path, monkeypatch):
    """Private empty winner cache + reset process-global dispatch state
    (cold-cache identity below depends on an actually-cold cache)."""
    monkeypatch.setenv("DSTPU_AUTOTUNE_CACHE",
                       str(tmp_path / "kernel_autotune.json"))
    monkeypatch.delenv("DSTPU_AUTOTUNE", raising=False)
    kernel_dispatch.reset()
    yield
    kernel_dispatch.reset()


# ---------------------------------------------------------------------------
# round trips: channel scales, int4 packing, host-side quantize_leaf
# ---------------------------------------------------------------------------

class TestRoundTrip:
    def test_channelwise_int8_roundtrip_bound(self):
        rng = np.random.RandomState(0)
        w = rng.randn(64, 48).astype(np.float32)
        q, s = quantize_channelwise(jnp.asarray(w), bits=8)
        assert q.dtype == jnp.int8 and s.shape == (1, 48)
        back = np.asarray(dequantize_channelwise(q, s, jnp.float32))
        # symmetric absmax/127: error <= scale/2 per element
        assert np.all(np.abs(back - w) <= np.asarray(s)[0] / 2 + 1e-7)

    def test_channelwise_int4_uses_code_range_7(self):
        w = jnp.asarray(np.random.RandomState(1).randn(32, 8),
                        jnp.float32)
        q, s = quantize_channelwise(w, bits=4)
        qn = np.asarray(q)
        assert qn.min() >= -7 and qn.max() <= 7
        # the absmax element quantizes to exactly +-7
        assert np.max(np.abs(qn), axis=0).min() == 7

    def test_zero_column_gets_unit_scale(self):
        w = jnp.zeros((16, 4), jnp.float32)
        q, s = quantize_channelwise(w, bits=8)
        assert np.all(np.asarray(s) == 1.0)
        assert np.all(np.asarray(q) == 0)

    def test_int4_pack_unpack_is_bitwise(self):
        rng = np.random.RandomState(2)
        q = rng.randint(-7, 8, (2, 64, 24)).astype(np.int8)
        p = pack_int4(jnp.asarray(q))
        assert p.shape == (2, 32, 24) and p.dtype == jnp.int8
        assert np.array_equal(np.asarray(unpack_int4(p)), q)

    def test_int4_pack_layout_pins_the_nibble_order(self):
        # byte[r, c] = (q[2r+1, c] << 4) | (q[2r, c] & 0xF) — the layout
        # the fused kernel epilogues decode; a silent swap would pass a
        # pack/unpack round trip but break every shipped checkpoint
        q = jnp.asarray([[3], [-2]], jnp.int8)
        byte = int(np.asarray(pack_int4(q))[0, 0])
        assert byte == np.int8((-2 << 4) | (3 & 0xF))

    def test_int4_pack_rejects_odd_contracted_dim(self):
        with pytest.raises(ValueError, match="even"):
            pack_int4(jnp.zeros((5, 4), jnp.int8))

    def test_quantize_leaf_int8_roundtrip(self):
        rng = np.random.RandomState(3)
        w = rng.randn(64, 32).astype(np.float32)
        qw = quantize_leaf(w, bits=8)
        assert isinstance(qw, Int8Weight)
        back = np.asarray(qw.dequant(jnp.float32))
        assert np.all(np.abs(back - w) <= qw.scale[0] / 2 + 1e-7)

    def test_quantize_leaf_int4_packs_and_roundtrips(self):
        rng = np.random.RandomState(4)
        w = rng.randn(64, 32).astype(np.float32)
        qw = quantize_leaf(w, bits=4)
        assert isinstance(qw, Int4Weight)
        assert qw.q.shape == (32, 32)          # two codes per byte
        back = np.asarray(qw.dequant(jnp.float32))
        assert np.all(np.abs(back - w) <= qw.scale[0] / 2 + 1e-7)

    def test_quantize_leaf_int4_odd_dim_falls_back_to_int8(self):
        w = np.random.RandomState(5).randn(65, 32).astype(np.float32)
        assert isinstance(quantize_leaf(w, bits=4), Int8Weight)


# ---------------------------------------------------------------------------
# dynamic W8A8 matmuls (dense + ragged) and their straight-through grads
# ---------------------------------------------------------------------------

class TestInt8Matmul:
    def test_dense_close_to_fp(self):
        rng = np.random.RandomState(6)
        x = jnp.asarray(rng.randn(4, 96, 64) * 0.5, jnp.float32)
        w = jnp.asarray(rng.randn(64, 48) / 8.0, jnp.float32)
        got = np.asarray(int8_matmul(x, w))
        ref = np.asarray(jnp.einsum("btk,km->btm", x, w))
        np.testing.assert_allclose(got, ref, rtol=5e-2, atol=5e-2)

    def test_dense_grads_are_straight_through_fp(self):
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(32, 64) * 0.5, jnp.float32)
        w = jnp.asarray(rng.randn(64, 48) / 8.0, jnp.float32)
        gx, gw = jax.grad(
            lambda a, b: jnp.sum(int8_matmul(a, b) ** 2), (0, 1))(x, w)
        rx, rw = jax.grad(
            lambda a, b: jnp.sum((a @ b) ** 2), (0, 1))(x, w)
        # backward is exact fp of the QUANTIZED forward's cotangent —
        # close to the fp/fp grads within the forward's own error
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=1e-1, atol=1e-1)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   rtol=1e-1, atol=1e-1)

    def test_grouped_close_to_ragged_dot(self):
        rng = np.random.RandomState(8)
        S, E, K, N = 128, 4, 32, 24
        x = jnp.asarray(rng.randn(S, K) * 0.5, jnp.float32)
        w = jnp.asarray(rng.randn(E, K, N) / 8.0, jnp.float32)
        sizes = jnp.asarray(np.bincount(np.arange(S) * 7919 % E,
                                        minlength=E), jnp.int32)
        got = np.asarray(grouped_int8_matmul(x, w, sizes))
        ref = np.asarray(jax.lax.ragged_dot(x, w, sizes))
        np.testing.assert_allclose(got, ref, rtol=5e-2, atol=5e-2)

    def test_grouped_grads_finite_and_close(self):
        rng = np.random.RandomState(9)
        S, E, K, N = 64, 4, 16, 8
        x = jnp.asarray(rng.randn(S, K) * 0.5, jnp.float32)
        w = jnp.asarray(rng.randn(E, K, N) / 4.0, jnp.float32)
        sizes = jnp.asarray([16, 16, 16, 16], jnp.int32)
        gx, gw = jax.grad(
            lambda a, b: jnp.sum(grouped_int8_matmul(a, b, sizes) ** 2),
            (0, 1))(x, w)
        rx, rw = jax.grad(
            lambda a, b: jnp.sum(jax.lax.ragged_dot(a, b, sizes) ** 2),
            (0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=1e-1, atol=1e-1)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   rtol=1e-1, atol=1e-1)


# ---------------------------------------------------------------------------
# cold-cache byte-identity: the training step
# ---------------------------------------------------------------------------

_TCFG = GPT2Config(n_layer=2, n_head=2, d_model=64, max_seq_len=32,
                   vocab_size=256, remat=False, dtype="float32")


def _train_engine(extra):
    groups.reset()
    topo = groups.initialize(TopologyConfig(data_parallel_size=2),
                             devices=jax.devices()[:2], force=True)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2(_TCFG), topology=topo, config={
            "train_batch_size": 4, "steps_per_print": 0,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            **extra,
        })
    return engine


def _train_text(engine):
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(
        0, _TCFG.vocab_size, (4, _TCFG.max_seq_len)).astype(np.int32)}
    batch = jax.tree.map(engine._add_gas_dim, batch)
    batch = engine._shard_batch(batch, with_gas_dim=True)
    with jax.set_mesh(engine.mesh):
        return engine._train_step_jit.lower(
            engine.state, batch, engine._current_lr(), None).as_text()


def test_quantize_block_cold_cache_is_byte_identical():
    """The block's presence with defaults, and with every knob at
    "auto" on a cold winner cache, lowers the EXACT training program
    its absence does — adopting the quantize block costs nothing until
    a knob is committed."""
    base = _train_text(_train_engine({}))
    assert base == _train_text(_train_engine({"quantize": {}}))
    assert base == _train_text(_train_engine({"quantize": {
        "grad_dcn": "auto", "moe_dcn": "auto",
        "int8_matmul": "auto", "moe_int8_matmul": "auto"}}))


def test_int8_matmul_forced_on_changes_the_program():
    """The identity test above is non-vacuous: forcing the lever
    actually lands int8 compute in the lowered step."""
    txt = _train_text(_train_engine({"quantize": {"int8_matmul": True}}))
    assert txt != _train_text(_train_engine({}))
    assert "s8" in txt or "i8" in txt


# ---------------------------------------------------------------------------
# cold-cache byte-identity: the v2 serving engine (weight_quant)
# ---------------------------------------------------------------------------

# d_model must clear quantize_tree's min_size floor (1 << 16 elements)
# or weight_quant engines silently serve fp and every test here goes
# vacuous: at d_model=128/n_layer=2 the stacked wqkv/wup/wdown leaves
# quantize, wo (32k elements) stays fp — a real mixed pool
_SCFG = GPT2Config(n_layer=2, n_head=4, d_model=128, max_seq_len=128,
                   vocab_size=256, remat=False, dtype="float32")
_SBASE = {"dtype": "float32", "kv_block_size": 8, "prompt_bucket": 16,
          "max_batch_size": 2, "splitfuse_tokens": 16,
          "decode_steps_per_dispatch": 2}
_SPARAMS = None


def _sparams():
    global _SPARAMS
    if _SPARAMS is None:
        _SPARAMS = GPT2(_SCFG).init(jax.random.key(0))
    return _SPARAMS


def _serve_engine(**kw):
    groups.reset()
    # fresh tree containers per build: quantized pool construction
    # consumes its input dict host-side (consume=True frees fp leaves)
    params = jax.tree.map(lambda x: x, _sparams())
    return InferenceEngineV2(GPT2(_SCFG), params=params,
                             config=dict(_SBASE, **kw))


def _serve_texts(eng):
    B = eng.config.max_batch_size
    MB = eng.max_blocks_per_seq
    i32, f32 = np.int32, np.float32
    z = np.zeros
    rng = jax.random.key(0)
    with jax.set_mesh(eng.mesh):
        dec = eng._get_decode().lower(
            eng.params, eng.cache, z((B,), i32), z((B,), i32),
            z((B, MB), i32), rng, z((B,), f32), z((B,), i32),
            True, eng._no_prev, z((B,), bool)).as_text()
        C = eng.config.splitfuse_tokens
        chk = eng._get_chunk_only().lower(
            eng.params, eng.cache, z((1, C), i32), z((C,), i32),
            z((C,), i32), i32(0), i32(0), z((MB,), i32), f32(0),
            i32(0), rng, True).as_text()
    return dec, chk


def test_weight_quant_auto_cold_is_byte_identical_to_off():
    """weight_quant="auto" (the shipped default) resolves OFF on a cold
    winner cache: fp params in the pool and decode/chunk programs
    byte-identical to weight_quant=False."""
    auto = _serve_engine(weight_quant="auto")
    assert not any(isinstance(x, (Int8Weight, Int4Weight))
                   for x in jax.tree.leaves(
                       auto.params,
                       is_leaf=lambda x: isinstance(
                           x, (Int8Weight, Int4Weight))))
    # and the forced engine DOES build a quantized pool (non-vacuous)
    q8 = _serve_engine(weight_quant="int8")
    assert any(isinstance(x, Int8Weight)
               for x in jax.tree.leaves(
                   q8.params,
                   is_leaf=lambda x: isinstance(x, Int8Weight)))
    t_auto = _serve_texts(auto)
    assert t_auto == _serve_texts(_serve_engine(weight_quant=False))


def test_weight_quant_int8_shrinks_the_pool():
    off = _serve_engine(weight_quant=False)
    q8 = _serve_engine(weight_quant="int8")
    nb = lambda e: sum(np.prod(x.shape) * x.dtype.itemsize  # noqa: E731
                       for x in jax.tree.leaves(e.params))
    assert nb(q8) < 0.55 * nb(off)      # fp32 pool -> ~int8 + scales


def test_weight_quant_junk_rejected():
    with pytest.raises(ValueError, match="weight_quant"):
        _serve_engine(weight_quant="int3")


# ---------------------------------------------------------------------------
# W8A16 logit drift on a fixed tiny checkpoint
# ---------------------------------------------------------------------------

def _prefill_logits(eng, ids, length):
    """Prefill ONE fixed prompt through the engine's own model + cache
    layout and return the next-token logits (1, V)."""
    BS = eng.config.kv_block_size
    T = ids.shape[1]
    tb = np.repeat(np.arange(1, T // BS + 1), BS).astype(np.int32)
    to = np.tile(np.arange(BS), T // BS).astype(np.int32)

    def f(params, cache):
        eng._install_trace_state()
        logits, _ = eng.model.apply_paged_prefill(
            params, jnp.asarray(ids), cache, jnp.asarray(tb),
            jnp.asarray(to), jnp.int32(length))
        return logits

    with jax.set_mesh(eng.mesh):
        return np.asarray(jax.jit(f)(eng.params, eng.cache),
                          np.float32)


def test_w8a16_logit_drift_is_bounded():
    """The acceptance gate for weight-only int8 serving: on a FIXED
    tiny checkpoint (seeded init), W8A16 next-token logits stay within
    a small drift envelope of the fp engine — greedy token identity is
    NOT required, bounded drift is."""
    rng = np.random.RandomState(0)
    L = 24
    ids = np.zeros((1, 32), np.int32)
    ids[0, :L] = rng.randint(0, _SCFG.vocab_size, L)
    ref = _prefill_logits(_serve_engine(weight_quant=False), ids, L)
    got = _prefill_logits(_serve_engine(weight_quant="int8"), ids, L)
    assert np.all(np.isfinite(got))
    scale = np.abs(ref).max()
    drift = np.abs(got - ref).max()
    assert drift < 0.05 * scale, (
        f"W8A16 drifted {drift:.4f} vs fp logit scale {scale:.4f}")
