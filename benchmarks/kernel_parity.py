"""On-chip Pallas kernel parity gate.

CI exercises every Pallas kernel in interpreter mode (tests/conftest.py
provisions a CPU mesh); this module is the real-Mosaic counterpart: tiny
shapes, compiled for the actual TPU, asserted against the dense
references — so every ``chip_smoke.py`` run also validates that
interpreter numerics and Mosaic numerics agree (a divergence would
otherwise ship silently). The TPU substitute for the reference's
per-kernel GPU CI (tests/unit/ops/).

``run()`` returns a dict enumerating EVERY shipped kernel path with its
status ("ok" or the failure string), so the bench JSON's
``kernels_parity`` field names each gate individually: the flash core +
its transposed-operand and q-major-backward variants, the bias family
(ALiBi, learned pair bias incl. d_bias cotangents, sliding window), the
evoformer fold, the SplitFuse fused chunk program, the paged/
block-sparse/quant/fused-CE kernels, the layout-owning MLP matmul, and
every cached autotune winner (tuned-vs-reference rows, so a stale or
wrong winner cache fails numerically instead of silently).

Budget: a few seconds of device time; tens of seconds of compiles.
Tolerances are bf16-scale — on TPU both the kernels and the dense
references run their dots on the MXU in bf16.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["run"]

_TOL = dict(rtol=2e-2, atol=2e-2)


def _close(a, b, what, tol=_TOL):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    np.testing.assert_allclose(a, b, err_msg=what, **tol)


def _flash(rng):
    from deepspeed_tpu.ops.pallas.flash_attention import (
        attention_reference, flash_attention)
    B, H, T, d = 2, 4, 256, 64
    ks = jax.random.split(rng, 4)
    q, k, v = (jax.random.normal(ks[i], (B, H, T, d), jnp.bfloat16)
               for i in range(3))
    do = jax.random.normal(ks[3], (B, H, T, d), jnp.bfloat16)

    def fl(q, k, v):
        return flash_attention(q, k, v, causal=True, heads_major=True,
                               block_q=128, block_k=128, interpret=False)

    def ref(q, k, v):
        return attention_reference(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            causal=True).swapaxes(1, 2)

    # elementwise forward parity (outputs are O(1) post-softmax values),
    # then elementwise cotangent parity through each backward
    of, pull_f = jax.vjp(fl, q, k, v)
    orf, pull_r = jax.vjp(ref, q, k, v)
    _close(of, orf, "flash fwd")
    for a, b, n in zip(pull_f(do), pull_r(do), "qkv"):
        _close(a, b, f"flash d{n}", dict(rtol=5e-2, atol=5e-2))


def _flash_t(rng, qmajor):
    """Transposed-operand (qkv_t) path — the training bench path — and
    its q-major fused backward variant."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        attention_reference, flash_attention)
    B, H, T, d = 2, 4, 256, 64
    ks = jax.random.split(rng, 4)
    q, k, v = (jax.random.normal(ks[i], (B, H, d, T), jnp.bfloat16)
               for i in range(3))
    do = jax.random.normal(ks[3], (B, H, T, d), jnp.bfloat16)

    def fl(q, k, v):
        return flash_attention(q, k, v, causal=True, qkv_t=True,
                               block_q=128, block_k=128,
                               bwd_qmajor=qmajor, interpret=False)

    def ref(q, k, v):
        qt, kt, vt = (x.transpose(0, 3, 1, 2) for x in (q, k, v))
        return attention_reference(qt, kt, vt, causal=True) \
            .transpose(0, 2, 1, 3)                 # (B, H, T, d)

    of, pull_f = jax.vjp(fl, q, k, v)
    orf, pull_r = jax.vjp(ref, q, k, v)
    tag = "qmajor" if qmajor else "qkv_t"
    _close(of, orf, f"flash[{tag}] fwd")
    for a, b, n in zip(pull_f(do), pull_r(do), "qkv"):
        _close(a, b, f"flash[{tag}] d{n}", dict(rtol=5e-2, atol=5e-2))


def _flash_alibi(rng):
    from deepspeed_tpu.ops.pallas.flash_attention import (
        attention_reference, flash_attention)
    from deepspeed_tpu.ops.pallas.paged_attention import alibi_slopes
    B, H, T, d = 2, 6, 128, 64                    # non-power-of-two heads
    ks = jax.random.split(rng, 3)
    q, k, v = (jax.random.normal(ks[i], (B, T, H, d), jnp.bfloat16)
               for i in range(3))
    sl = alibi_slopes(H)
    ab = jnp.asarray(sl, jnp.float32)[None, :, None, None] \
        * jnp.arange(T, dtype=jnp.float32)[None, None, None, :]
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, alibi=sl, block_q=128, block_k=128, interpret=False)
        .astype(jnp.float32) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(attention_reference(
        *a, bias=ab).astype(jnp.float32) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gf, gr, "qkv"):
        _close(a, b, f"flash+alibi d{n}", dict(rtol=5e-2, atol=5e-2))


def _flash_pair_bias(rng):
    """Learned pair bias: forward parity AND the in-kernel d_bias
    accumulation (the evoformer-training cotangent)."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        attention_reference, flash_attention)
    B, H, T, d = 2, 4, 128, 64
    ks = jax.random.split(rng, 4)
    q, k, v = (jax.random.normal(ks[i], (B, T, H, d), jnp.bfloat16)
               for i in range(3))
    bias = jax.random.normal(ks[3], (B, H, T, T), jnp.float32) * 0.3

    def loss_f(b):
        return jnp.sum(flash_attention(
            q, k, v, bias=b, bias_grad=True, causal=True, block_q=128,
            block_k=128, interpret=False).astype(jnp.float32) ** 2)

    def loss_r(b):
        return jnp.sum(attention_reference(
            q, k, v, bias=b, causal=True).astype(jnp.float32) ** 2)

    _close(flash_attention(q, k, v, bias=bias, causal=True, block_q=128,
                           block_k=128, interpret=False),
           attention_reference(q, k, v, bias=bias, causal=True),
           "flash pair-bias fwd")
    _close(jax.grad(loss_f)(bias), jax.grad(loss_r)(bias),
           "flash d_bias", dict(rtol=5e-2, atol=5e-2))


def _flash_window(rng):
    from deepspeed_tpu.ops.pallas.flash_attention import (
        attention_reference, flash_attention, NEG_INF)
    B, H, T, d = 2, 4, 256, 64
    ks = jax.random.split(rng, 3)
    q, k, v = (jax.random.normal(ks[i], (B, T, H, d), jnp.bfloat16)
               for i in range(3))
    win = 100
    o = flash_attention(q, k, v, causal=True, window=win, block_q=128,
                        block_k=128, interpret=False)
    pos = jnp.arange(T)
    wmask = (pos[:, None] - pos[None, :] < win)
    bias = jnp.where(wmask, 0.0, NEG_INF)[None, None]
    ref = attention_reference(q, k, v, causal=True, bias=bias)
    _close(o, ref, "flash sliding-window")


def _evoformer(rng):
    """The evoformer fold adapter over the bias-capable flash kernel vs
    its chunked-XLA twin, incl. the pair-bias gradient."""
    from deepspeed_tpu.ops.evoformer_attn import evoformer_attention
    B, S, N, H, d = 1, 2, 64, 2, 32
    ks = jax.random.split(rng, 6)
    q, k, v = (jax.random.normal(ks[i], (B, S, N, H, d), jnp.bfloat16)
               for i in range(3))
    b1 = jax.random.normal(ks[3], (B, S, 1, 1, N), jnp.float32)
    b2 = jax.random.normal(ks[4], (B, 1, H, N, N), jnp.float32) * 0.3

    def f(impl):
        def g(b2_):
            return jnp.sum(evoformer_attention(
                q, k, v, biases=(b1, b2_), impl=impl)
                .astype(jnp.float32) ** 2)
        return g

    _close(evoformer_attention(q, k, v, biases=(b1, b2), impl="kernel"),
           evoformer_attention(q, k, v, biases=(b1, b2), impl="xla"),
           "evoformer fold fwd")
    _close(jax.grad(f("kernel"))(b2), jax.grad(f("xla"))(b2),
           "evoformer d_bias2", dict(rtol=5e-2, atol=5e-2))


def _splitfuse(rng):
    """The Dynamic SplitFuse fused chunk program (chunked prefill +
    running decode in one compiled dispatch) vs the bucketed-prefill
    engine — greedy outputs must be identical."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import GPT2, GPT2Config
    from deepspeed_tpu.utils import groups
    cfg = GPT2Config(n_layer=2, n_head=4, d_model=64, max_seq_len=128,
                     vocab_size=256, remat=False, dtype="float32")
    model = GPT2(cfg)
    params = model.init(jax.random.key(0))
    base = {"dtype": "float32", "kv_block_size": 8, "prompt_bucket": 16,
            "max_batch_size": 4}
    groups.reset()
    legacy = InferenceEngineV2(model, params=params, config=dict(base))
    groups.reset()
    sf = InferenceEngineV2(model, params=params,
                           config=dict(base, splitfuse_tokens=16))
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 256, (n,)).astype(np.int32)
               for n in (5, 16, 37)]
    want = legacy.generate_all(prompts, max_new_tokens=4)
    got = sf.generate_all(prompts, max_new_tokens=4)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg="splitfuse fused program")
    groups.reset()


def _speculative(rng):
    """Draft-model speculative decoding vs plain decode: greedy
    spec-on output must be byte-identical to spec-off for BOTH model
    families (the verify program rides each family's own
    apply_paged_verify), and a mid-speculation cancel() must leave the
    target and draft allocators with zero leaked blocks."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import GPT2, GPT2Config, Llama, LlamaConfig
    from deepspeed_tpu.utils import groups
    base = {"dtype": "float32", "kv_block_size": 8, "prompt_bucket": 16,
            "max_batch_size": 4}
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, 255, (n,)).astype(np.int32)
               for n in (5, 11, 16)]
    families = (
        ("gpt2",
         GPT2(GPT2Config(n_layer=2, n_head=4, d_model=64,
                         max_seq_len=128, vocab_size=256, remat=False,
                         dtype="float32")),
         GPT2(GPT2Config(n_layer=1, n_head=2, d_model=32,
                         max_seq_len=128, vocab_size=256, remat=False,
                         dtype="float32"))),
        ("llama",
         Llama(LlamaConfig(n_layer=2, n_head=4, n_kv_heads=2,
                           d_model=64, max_seq_len=128, vocab_size=256,
                           remat=False, dtype="float32")),
         Llama(LlamaConfig(n_layer=1, n_head=2, n_kv_heads=1,
                           d_model=32, max_seq_len=128, vocab_size=256,
                           remat=False, dtype="float32"))),
    )
    for name, model, draft in families:
        params = model.init(jax.random.key(0))
        dparams = draft.init(jax.random.key(1))
        groups.reset()
        plain = InferenceEngineV2(model, params=params,
                                  config=dict(base))
        want = plain.generate_all(prompts, max_new_tokens=10)
        groups.reset()
        spec = InferenceEngineV2(
            model, params=params,
            config=dict(base, spec_draft=True, spec_k=4),
            draft_model=draft, draft_params=dparams)
        got = spec.generate_all(prompts, max_new_tokens=10)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w),
                err_msg=f"speculative decode ({name})")
        tel = spec.telemetry.percentiles()
        assert tel.get("spec_rounds", 0) > 0, \
            f"speculation never engaged ({name})"
        # mid-speculation cancel: step until the sequence is actively
        # speculating, withdraw it, and audit both pools
        uid = spec.put(prompts[1], max_new_tokens=32)
        while True:
            spec.step()
            seq = spec.state_mgr._seqs.get(uid)
            if seq is not None and seq.draft_blocks:
                break
        assert spec.cancel(uid) is True
        alloc = spec.state_mgr.allocator
        assert alloc.free_blocks == alloc.total_blocks, \
            f"leaked target blocks after mid-spec cancel ({name})"
        da = spec.state_mgr.draft_allocator
        assert da.free_blocks == da.total_blocks, \
            f"leaked draft blocks after mid-spec cancel ({name})"
    groups.reset()


def _kv_handoff(rng):
    """Disaggregated prefill/decode handoff vs colocated decode: run
    prefill on engine P with the decode hold engaged, stream the KV
    blocks + descriptor through the wire format into engine D, and the
    completed greedy output must be byte-identical to a colocated
    reference — for BOTH model families (gpt2 rides the bucketed
    prefill path, llama/GQA rides the split-fuse chunked path). The
    re-export from D before it decodes proves the scatter placed every
    block payload byte-exactly; pool audits prove both sides close
    their accounting."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2, kv_transfer
    from deepspeed_tpu.models import GPT2, GPT2Config, Llama, LlamaConfig
    from deepspeed_tpu.utils import groups
    base = {"dtype": "float32", "kv_block_size": 8, "prompt_bucket": 16,
            "max_batch_size": 4}
    rs = np.random.RandomState(2)
    prompt = rs.randint(1, 255, (21,)).astype(np.int32)
    families = (
        ("gpt2", {},
         GPT2(GPT2Config(n_layer=2, n_head=4, d_model=64,
                         max_seq_len=128, vocab_size=256, remat=False,
                         dtype="float32"))),
        ("llama", {"splitfuse_tokens": 16},
         Llama(LlamaConfig(n_layer=2, n_head=4, n_kv_heads=2,
                           d_model=64, max_seq_len=128, vocab_size=256,
                           remat=False, dtype="float32"))),
    )
    for name, extra, model in families:
        params = model.init(jax.random.key(0))
        groups.reset()
        ref = InferenceEngineV2(model, params=params,
                                config=dict(base, **extra))
        want = ref.generate_all([prompt], max_new_tokens=8)[0]
        groups.reset()
        P = InferenceEngineV2(model, params=params,
                              config=dict(base, **extra))
        groups.reset()
        D = InferenceEngineV2(model, params=params,
                              config=dict(base, **extra))
        uid = P.put(prompt, max_new_tokens=8)
        P.hold_decode(uid)
        while True:
            P.step()
            seq = P.state_mgr._seqs.get(uid)
            if seq is not None and seq.generated:
                break
        state, _ = P.export_handoff(uid)
        payload = kv_transfer.export_sequence(P, uid)
        kv_transfer.import_sequence(D, payload)
        P.release_handoff(uid)
        alloc = P.state_mgr.allocator
        assert alloc.free_blocks == alloc.total_blocks, \
            f"prefill side leaked blocks after handoff ({name})"
        # round-trip proof: what D would export is byte-identical to
        # what P exported — the scatter landed every payload exactly
        state2, kv2 = D.export_handoff(uid)
        assert state2 == state, f"handoff state drifted ({name})"
        _, flat = kv_transfer.unpack_handoff(payload)
        from deepspeed_tpu.runtime.checkpoint_engine.serialization \
            import flatten_state
        flat2, _meta = flatten_state(kv2)
        for key, arr in flat.items():
            np.testing.assert_array_equal(
                np.asarray(flat2[key]), np.asarray(arr),
                err_msg=f"KV block payload {key} not byte-identical "
                        f"after import ({name})")
        while not D.is_done(uid):
            D.step()
        got = D.get(uid)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg=f"disaggregated output != colocated ({name})")
        da = D.state_mgr.allocator
        assert da.free_blocks == da.total_blocks, \
            f"decode side leaked blocks after completion ({name})"
    groups.reset()


def _mlp_matmul(rng):
    from deepspeed_tpu.ops.pallas.mlp_matmul import _ref_proj, mlp_matmul
    B, T, K, M = 2, 256, 512, 256
    ks = jax.random.split(rng, 3)
    for x_t, out_t in ((True, False), (False, True)):
        x = jax.random.normal(ks[0], (B, K, T) if x_t else (B, T, K),
                              jnp.bfloat16)
        w = jax.random.normal(ks[1], (K, M), jnp.bfloat16)
        kw = dict(x_t=x_t, out_t=out_t, interpret=False)
        y = mlp_matmul(x, w, **kw)
        _close(y, _ref_proj(x, w, x_t, out_t), f"mlp fwd x_t={x_t}")
        dy = jax.random.normal(ks[2], y.shape, jnp.bfloat16)

        def f(x, w):
            return jnp.sum(mlp_matmul(x, w, **kw).astype(jnp.float32)
                           * dy.astype(jnp.float32))

        def fr(x, w):
            return jnp.sum(_ref_proj(x, w, x_t, out_t).astype(jnp.float32)
                           * dy.astype(jnp.float32))

        for a, b, n in zip(jax.grad(f, (0, 1))(x, w),
                           jax.grad(fr, (0, 1))(x, w), ("dx", "dw")):
            _close(a, b, f"mlp {n} x_t={x_t}",
                   dict(rtol=5e-2, atol=5e-1 if n == "dw" else 5e-2))


def _paged(rng):
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_decode_attention_reference)
    B, H, d = 4, 8, 64
    NB, BS, MB = 16, 16, 4
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, H, d), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (NB, H, BS, d), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (NB, H, BS, d), jnp.bfloat16)
    tables = jax.random.randint(ks[3], (B, MB), 0, NB, jnp.int32)
    lengths = jnp.asarray([5, 63, 17, 30], jnp.int32)
    out = jax.jit(lambda *a: paged_decode_attention(*a, interpret=False))(
        q, kc, vc, tables, lengths)
    ref = jax.jit(paged_decode_attention_reference)(
        q, kc, vc, tables, lengths)
    _close(out, ref, "paged decode")


def _paged_chunk(rng):
    """The SplitFuse chunked-prefill paged kernel vs the dense-gather
    reference on real Mosaic: a GQA chunk straddling block boundaries
    mid-sequence, plus a sliding-window case."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_chunk_attention, paged_chunk_attention_reference)
    C, H, KVH, d = 32, 8, 4, 64
    NB, BS, MB = 12, 32, 4
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (C, H, d), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (NB, KVH, BS, d), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (NB, KVH, BS, d), jnp.bfloat16)
    table = jax.random.randint(ks[3], (MB,), 0, NB, jnp.int32)
    for start, true_len, window in ((45, 32, 0), (70, 20, 48)):
        out = jax.jit(lambda *a: paged_chunk_attention(
            *a, window=window, block_c=16, interpret=False))(
            q, kc, vc, table, jnp.int32(start), jnp.int32(true_len))
        ref = jax.jit(lambda *a: paged_chunk_attention_reference(
            *a, window=window))(
            q, kc, vc, table, jnp.int32(start), jnp.int32(true_len))
        _close(out[:true_len], ref[:true_len],
               f"paged chunk w={window}")


def _paged_tuned(rng, op):
    """Tuned-winner gate for the serving autotune ops: whatever config
    dispatch resolves for this chip's decode-shape bucket (cached
    winner or the cold-cache default) must reproduce the dense
    reference — the same winner-re-proving contract as the
    autotune_winners gate, but exercised for the engine's own ops even
    when the cache is cold."""
    from deepspeed_tpu.autotuning import kernel_dispatch, kernel_registry
    spec = kernel_registry.REGISTRY[op]
    bucket = {"paged_decode": "B8,MB8,BS32,kh4,g2,d64",
              "paged_chunk": "C32,MB8,BS32,kh4,g2,d64"}[op]
    b = kernel_registry.parse_bucket(bucket)
    params = kernel_dispatch.resolve(op, bucket, "bfloat16",
                                     spec["defaults"](b))
    spec["parity"](b, "bfloat16", params)


def _block_sparse(rng):
    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention)
    from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
    B, H, T, d = 2, 4, 256, 64
    blk = 64
    layout = FixedSparsityConfig(
        num_heads=H, block=blk).make_layout(T)
    ks = jax.random.split(rng, 3)
    q, k, v = (jax.random.normal(ks[i], (B, T, H, d), jnp.bfloat16)
               for i in range(3))
    out = jax.jit(lambda q, k, v: block_sparse_attention(
        q, k, v, layout, blk, causal=True, interpret=False))(q, k, v)
    # dense reference with the same layout mask
    lay = np.asarray(jax.device_get(layout))
    if lay.ndim == 2:
        lay = np.broadcast_to(lay[None], (H,) + lay.shape)
    mask = np.kron(lay, np.ones((blk, blk), bool))[:, :T, :T]
    mask = np.tril(np.ones((T, T), bool))[None] & mask.astype(bool)
    s = jnp.einsum("bthd,bshd->bhts", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    s = jnp.where(jnp.asarray(mask)[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    ref = jnp.einsum("bhts,bshd->bthd", p, v)
    _close(out, ref, "block-sparse fwd")


def _fused_ce(rng):
    from deepspeed_tpu.ops.pallas.fused_ce import unembed_logits_stats
    N, D, V = 256, 128, 1000     # V deliberately not a block multiple
    ks = jax.random.split(rng, 3)
    h = jax.random.normal(ks[0], (N, D), jnp.bfloat16)
    w = jax.random.normal(ks[1], (V, D), jnp.bfloat16)
    t = jax.random.randint(ks[2], (N,), 0, V, jnp.int32)
    logits, logz, gold = unembed_logits_stats(h, w, t, block_m=128,
                                              block_n=256,
                                              interpret=False)
    ref = jnp.einsum("nd,vd->nv", h, w,
                     preferred_element_type=jnp.float32)
    _close(logits, ref.astype(jnp.bfloat16), "fused-ce logits")
    _close(logz, jax.nn.logsumexp(ref, axis=-1), "fused-ce logz")
    _close(gold, jnp.take_along_axis(ref, t[:, None], axis=1)[:, 0],
           "fused-ce gold")


def _ring_block(rng):
    """The carry-state blockwise flash step (ring attention's chunk-pair
    kernel): two chained pairs (diagonal-causal + full) with carried
    (m, l, acc) state vs one dense softmax over the concatenated kv —
    proving the ring's state algebra on real Mosaic, plus the per-pair
    backward path via the fused bwd kernel with a GLOBAL lse."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        flash_block_bwd, flash_block_finalize, flash_block_fwd,
        flash_block_state)
    G, T, d = 4, 128, 64
    ks = jax.random.split(rng, 5)
    q, k1, v1, k2, v2 = (jax.random.normal(k, (G, T, d), jnp.bfloat16)
                         for k in ks)
    st = flash_block_state(G, T, d)
    st = flash_block_fwd(q, k1, v1, st, causal=True, block_q=64,
                         block_k=64, interpret=False)
    st = flash_block_fwd(q, k2, v2, st, causal=False, block_q=64,
                         block_k=64, interpret=False)
    o, lse = flash_block_finalize(st)

    kc = jnp.concatenate([k1, k2], axis=1)
    vc = jnp.concatenate([v1, v2], axis=1)
    s = jnp.einsum("gtd,gsd->gts", q, kc,
                   preferred_element_type=jnp.float32)
    mask = jnp.concatenate([jnp.tril(jnp.ones((T, T), jnp.bool_)),
                            jnp.ones((T, T), jnp.bool_)], axis=1)
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("gts,gsd->gtd", p, vc.astype(jnp.float32))
    _close(o, ref, "ring_block chained fwd")
    _close(lse, jax.nn.logsumexp(s, axis=-1), "ring_block lse")

    # pair backward from the global lse/o (the ring bwd recompute) vs
    # the dense vjp restricted to pair 1's kv
    do = jax.random.normal(ks[0], (G, T, d), jnp.bfloat16)
    ob = o.astype(jnp.bfloat16)
    dq1, dk1, dv1 = flash_block_bwd(q, k1, v1, ob, lse, do, causal=True,
                                    block_q=64, block_k=64,
                                    interpret=False)

    # dense pair-1 contribution with the global lse fixed, in the
    # analytic ds = p * (dp - delta) form the flash backward computes
    pa = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((T, T), jnp.bool_))[None],
        jnp.einsum("gtd,gsd->gts", q.astype(jnp.float32),
                   k1.astype(jnp.float32)), -1e30) - lse[..., None])
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * ref, axis=-1)
    dvr = jnp.einsum("gts,gtd->gsd", pa, dof)
    dpr = jnp.einsum("gtd,gsd->gts", dof, v1.astype(jnp.float32))
    dsr = pa * (dpr - delta[..., None])
    dkr = jnp.einsum("gts,gtd->gsd", dsr, q.astype(jnp.float32))
    dqr = jnp.einsum("gts,gsd->gtd", dsr, k1.astype(jnp.float32))
    for a, b, n in ((dq1, dqr, "dq"), (dk1, dkr, "dk"), (dv1, dvr, "dv")):
        _close(a, b, f"ring_block pair {n}", dict(rtol=5e-2, atol=5e-2))


def _moe_grouped(rng):
    """The dropless-MoE grouped-GEMM kernel vs lax.ragged_dot on real
    Mosaic: uneven groups incl. an empty one, fwd + all four grads
    through the fused SwiGLU chain, plus the plain grouped product."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                         grouped_swiglu)
    S, K, F, E = 256, 128, 256, 4
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (S, K), jnp.bfloat16) * 0.3
    w1 = jax.random.normal(ks[1], (E, K, F), jnp.bfloat16) * 0.1
    w3 = jax.random.normal(ks[2], (E, K, F), jnp.bfloat16) * 0.1
    w2 = jax.random.normal(ks[3], (E, F, K), jnp.bfloat16) * 0.1
    gs = jnp.asarray([100, 0, 37, 119], jnp.int32)

    got = jax.jit(lambda x, w: grouped_matmul(
        x, w, gs, block_m=64, interpret=False))(x, w1)
    _close(got, jax.lax.ragged_dot(x, w1, gs), "moe grouped fwd")

    def lk(x, w1, w3, w2):
        return jnp.sum(grouped_swiglu(x, w1, w3, w2, gs, block_m=64,
                                      interpret=False)
                       .astype(jnp.float32) ** 2)

    def lr(x, w1, w3, w2):
        g = jax.lax.ragged_dot(x, w1, gs)
        u = jax.lax.ragged_dot(x, w3, gs)
        return jnp.sum(jax.lax.ragged_dot(jax.nn.silu(g) * u, w2, gs)
                       .astype(jnp.float32) ** 2)

    ga = jax.grad(lk, (0, 1, 2, 3))(x, w1, w3, w2)
    gr = jax.grad(lr, (0, 1, 2, 3))(x, w1, w3, w2)
    for a, b, n in zip(ga, gr, ("dx", "dw1", "dw3", "dw2")):
        _close(a, b, f"moe grouped swiglu {n}",
               dict(rtol=5e-2, atol=5e-1 if n != "dx" else 5e-2))


def _moe_grouped_tuned(rng):
    """Tuned-winner gate for the MoE grouped op: whatever dispatch
    resolves for this chip's bucket (cached winner or the cold-cache
    ragged default) must reproduce the ragged_dot reference — fwd and
    grads (the registry parity)."""
    from deepspeed_tpu.autotuning import kernel_dispatch, kernel_registry
    spec = kernel_registry.REGISTRY["moe_grouped_mm"]
    bucket = "S512,E8,M128,F256"
    b = kernel_registry.parse_bucket(bucket)
    params = kernel_dispatch.resolve("moe_grouped_mm", bucket, "bfloat16",
                                     spec["defaults"](b))
    spec["parity"](b, "bfloat16", params)


def _tuned_winners(rng):
    """Tuned-vs-reference parity for every cached autotune winner on
    THIS chip: a stale or wrong cache entry (edited file, toolchain
    bump that changed kernel numerics, foreign shapes) fails here
    numerically instead of silently steering the training step. Raises
    with a per-entry breakdown on any failure."""
    from deepspeed_tpu.autotuning import KernelCache, kernel_dispatch
    from deepspeed_tpu.autotuning import kernel_registry
    cache = KernelCache.load(kernel_dispatch.cache_path())
    entries = cache.for_device(kernel_dispatch.device_kind())
    if not entries:
        return                       # "ok": nothing cached, nothing stale
    failures = []
    for key, e in sorted(entries.items()):
        op = e.get("op")
        spec = kernel_registry.REGISTRY.get(op)
        if spec is None:
            failures.append(f"{key}: unknown op {op!r}")
            continue
        try:
            spec["parity"](kernel_registry.parse_bucket(e["bucket"]),
                           e["dtype"], e["params"])
        except Exception as ex:  # noqa: BLE001 — collect all entries
            failures.append(f"{key}: {type(ex).__name__}: {ex}"[:200])
    if failures:
        raise AssertionError(
            f"{len(failures)}/{len(entries)} cached winners failed "
            f"parity: " + "; ".join(failures))


def _quant(rng):
    from deepspeed_tpu.ops.pallas.quantization import (
        dequantize_blockwise, quantize_blockwise)
    x = jax.random.normal(rng, (512, 256), jnp.float32) * 3.0
    qp, sp, meta = quantize_blockwise(x, use_pallas=True, interpret=False)
    qr, sr, _ = quantize_blockwise(x, use_pallas=False)
    _close(qp, qr, "int8 quantize codes", dict(rtol=0, atol=1))
    yp = dequantize_blockwise(qp, sp, meta, use_pallas=True,
                              interpret=False)
    # roundtrip error bound is s/2 = blockwise absmax/254 (~0.055 for
    # |x| up to ~14 here)
    _close(yp, x, "int8 roundtrip", dict(rtol=0, atol=0.08))


def _mlp_wq(rng, bits):
    """Fused weight-only dequant projection kernel (W8A16/W4A16 serving
    FFN, ops/pallas/mlp_matmul.wq_matmul): kernel output vs the
    dequantize-then-einsum reference, every layout orientation. The
    reference uses the SAME quantized codes, so the gate isolates the
    kernel's epilogue arithmetic from quantization error itself."""
    import numpy as np
    from deepspeed_tpu.ops.int8_weights import quantize_leaf
    from deepspeed_tpu.ops.pallas.mlp_matmul import wq_matmul
    ks = jax.random.split(rng, 2)
    B, T, D, F = 2, 128, 128, 256
    x = jax.random.normal(ks[0], (B, T, D), jnp.bfloat16)
    w = np.asarray(jax.random.normal(ks[1], (D, F), jnp.float32)) * 0.1
    qw = quantize_leaf(w, bits=bits)
    wf = qw.dequant(jnp.float32)
    for x_t, out_t in ((False, False), (False, True), (True, False),
                       (True, True)):
        xi = jnp.swapaxes(x, -1, -2) if x_t else x
        got = wq_matmul(xi, qw, x_t=x_t, out_t=out_t, interpret=None)
        ref = jnp.einsum("btd,df->bft" if out_t else "btd,df->btf",
                         x.astype(jnp.float32), wf).astype(x.dtype)
        _close(got, ref, f"mlp_wq{bits} x_t={x_t} out_t={out_t}",
               dict(rtol=5e-2, atol=5e-2))


def _moe_grouped_wq8(rng):
    """Fused weight-only dequant grouped-SwiGLU chain (quantized expert
    FFN serving, grouped_matmul.grouped_swiglu_wq): kernel vs the
    dequantize-then-ragged_dot reference over uneven groups."""
    import numpy as np
    from deepspeed_tpu.ops.int8_weights import quantize_leaf
    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_swiglu_wq
    ks = jax.random.split(rng, 4)
    S, E, M, F = 512, 8, 128, 256
    x = jax.random.normal(ks[0], (S, M), jnp.bfloat16) * 0.3
    mk = lambda k, sh: np.asarray(
        jax.random.normal(k, sh, jnp.float32)) * 0.1
    q1 = quantize_leaf(mk(ks[1], (E, M, F)), bits=8)
    q3 = quantize_leaf(mk(ks[2], (E, M, F)), bits=8)
    q2 = quantize_leaf(mk(ks[3], (E, F, M)), bits=8)
    sizes = jnp.asarray(np.bincount(np.arange(S) * 7919 % E,
                                    minlength=E), jnp.int32)
    got = grouped_swiglu_wq(x, q1, q3, q2, sizes, interpret=None)
    xf = x.astype(jnp.float32)
    g = jax.lax.ragged_dot(xf, q1.dequant(jnp.float32), sizes)
    u = jax.lax.ragged_dot(xf, q3.dequant(jnp.float32), sizes)
    h = (g * jax.nn.sigmoid(g)) * u
    ref = jax.lax.ragged_dot(h, q2.dequant(jnp.float32), sizes).astype(
        x.dtype)
    _close(got, ref, "moe_grouped_wq8", dict(rtol=5e-2, atol=5e-2))


def _int8_tuned(rng, op):
    """Tuned-winner gate for the W8A8 compute levers: whatever dispatch
    resolves for this chip's bucket (cached winner or the cold-cache
    {int8: 0} exact default) must pass the registry parity — so an int8
    winner that drifted past the gate fails here, and can never have
    been cached in the first place (search runs parity before
    caching)."""
    from deepspeed_tpu.autotuning import kernel_dispatch, kernel_registry
    spec = kernel_registry.REGISTRY[op]
    bucket = ("T512,D128,F512" if op == "mlp_int8"
              else "S512,E8,M128,F256")
    b = kernel_registry.parse_bucket(bucket)
    params = kernel_dispatch.resolve(op, bucket, "bfloat16",
                                     spec["defaults"](b))
    spec["parity"](b, "bfloat16", params)


# every shipped kernel path, gated individually (acceptance: the bench
# JSON's kernels_parity enumerates each)
_GATES = (
    ("flash", _flash),
    ("flash_qkv_t", lambda r: _flash_t(r, qmajor=False)),
    ("flash_bwd_qmajor", lambda r: _flash_t(r, qmajor=True)),
    ("flash_alibi", _flash_alibi),
    ("flash_pair_bias", _flash_pair_bias),
    ("flash_window", _flash_window),
    ("evoformer", _evoformer),
    ("splitfuse", _splitfuse),
    # draft-model speculation: spec-on greedy byte-identity (gpt2 +
    # llama) and the mid-speculation cancel() zero-leak audit
    ("speculative", _speculative),
    # disaggregated prefill/decode: P->D KV-block handoff byte-identity
    # vs colocated (gpt2 + llama/GQA) + both-side pool-closure audits
    ("kv_handoff", _kv_handoff),
    ("mlp_matmul", _mlp_matmul),
    ("paged", _paged),
    # the SplitFuse chunked-prefill paged kernel + the tuned-winner
    # gates for the two serving autotune ops (cached winner — or the
    # cold-cache default — vs the dense reference)
    ("paged_chunk", _paged_chunk),
    ("paged_decode_tuned", lambda r: _paged_tuned(r, "paged_decode")),
    ("paged_chunk_tuned", lambda r: _paged_tuned(r, "paged_chunk")),
    ("block_sparse", _block_sparse),
    ("quant", _quant),
    ("fused_ce", _fused_ce),
    # the dropless-MoE grouped-GEMM kernel (fused SwiGLU chain + plain
    # grouped product, fwd + grads) and its tuned-winner re-prove
    ("moe_grouped", _moe_grouped),
    ("moe_grouped_tuned", _moe_grouped_tuned),
    # fused weight-only dequant serving kernels (W8A16/W4A16 FFN +
    # quantized expert chain) and the W8A8 compute levers' tuned-winner
    # re-prove (cold default {int8: 0} is the exact fp program)
    ("mlp_wq8", lambda r: _mlp_wq(r, 8)),
    ("mlp_wq4", lambda r: _mlp_wq(r, 4)),
    ("moe_grouped_wq8", _moe_grouped_wq8),
    ("mlp_int8_tuned", lambda r: _int8_tuned(r, "mlp_int8")),
    ("moe_grouped_int8_tuned",
     lambda r: _int8_tuned(r, "moe_grouped_int8")),
    # the ring-attention carry-state blockwise flash step (chunk-pair
    # chaining + pair backward from the global lse)
    ("ring_block", _ring_block),
    # every cached autotune winner re-proved against the dense
    # references (ok when the cache is empty)
    ("autotune_winners", _tuned_winners),
)


def run(seed=0, only=None):
    """Run every kernel parity gate (or the ``only`` named ones) on the
    default backend. Returns {gate_name: "ok" | "FAILED: ..."} — failures
    are isolated so one broken path never hides the status of the rest;
    the caller decides what a non-"ok" entry costs."""
    rng = jax.random.key(seed)
    rngs = jax.random.split(rng, len(_GATES))
    out = {}
    for (name, fn), r in zip(_GATES, rngs):
        if only is not None and name not in only:
            continue
        try:
            fn(r)
            out[name] = "ok"
        except Exception as e:
            out[name] = f"FAILED: {type(e).__name__}: {e}"[:300]
    return out


if __name__ == "__main__":
    res = run()
    print({"kernels_parity": res,
           "all_ok": all(v == "ok" for v in res.values())})
