"""Layout-owning MLP projection matmul kernel parity (interpret mode).

Counterpart of reference tests/unit/ops/ kernel parity for the fused
GEMM tier (csrc/transformer/cublas_wrappers.cu). Covers both operand
orientations (row-major and T-in-lanes), both output orientations, the
fused dx/dw backward epilogues, and the jnp fallback for untileable
shapes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.mlp_matmul import _ref_proj, mlp_matmul

_KW = dict(block_t=128, block_o=128, block_k=256, interpret=True)


def _rand(rng, shape, dt):
    return jax.random.normal(rng, shape, dt)


class TestMlpMatmulForward:
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("x_t", [False, True])
    @pytest.mark.parametrize("out_t", [False, True])
    def test_matches_reference(self, d, x_t, out_t):
        """Both layouts at head-dim-scale feature sizes (64 / 128)."""
        B, T, K = 2, 256, 256
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        x = _rand(ks[0], (B, K, T) if x_t else (B, T, K), jnp.bfloat16)
        w = _rand(ks[1], (K, d), jnp.bfloat16)
        y = mlp_matmul(x, w, x_t=x_t, out_t=out_t, **_KW)
        assert y.shape == ((B, d, T) if out_t else (B, T, d))
        # the reference runs on widened operands: XLA:CPU has no
        # bf16 x bf16 -> f32 dot over a transposed operand, and a product
        # of two bf16 values is exact in f32, so the numbers are the
        # kernel's own — f32 accumulation, one round to bf16
        ref = _ref_proj(x.astype(jnp.float32), w.astype(jnp.float32),
                        x_t, out_t).astype(jnp.bfloat16)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_fp32_exact(self):
        x = _rand(jax.random.PRNGKey(0), (1, 64, 128), jnp.float32)
        w = _rand(jax.random.PRNGKey(1), (128, 64), jnp.float32)
        y = mlp_matmul(x, w, **_KW)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(_ref_proj(x, w, False, False)),
                                   rtol=1e-5, atol=1e-5)

    def test_untileable_falls_back(self):
        # 100 is not 8/128-tileable -> jnp fallback, same math
        x = _rand(jax.random.PRNGKey(0), (2, 100, 96), jnp.float32)
        w = _rand(jax.random.PRNGKey(1), (96, 100), jnp.float32)
        y = mlp_matmul(x, w, **_KW)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(_ref_proj(x, w, False, False)),
                                   rtol=1e-5, atol=1e-5)

    def test_shape_validation(self):
        with pytest.raises(ValueError, match="mlp_matmul expects"):
            mlp_matmul(jnp.zeros((4, 4)), jnp.zeros((4, 4)))
        with pytest.raises(ValueError, match="contract dim"):
            mlp_matmul(jnp.zeros((1, 8, 16)), jnp.zeros((8, 16)))


class TestMlpMatmulBackward:
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("x_t,out_t", [(False, False), (True, False),
                                           (False, True), (True, True)])
    def test_grads_match_reference(self, d, x_t, out_t):
        B, T, K = 2, 256, 256
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        x = _rand(ks[0], (B, K, T) if x_t else (B, T, K), jnp.bfloat16)
        w = _rand(ks[1], (K, d), jnp.bfloat16)
        dy = _rand(ks[2], (B, d, T) if out_t else (B, T, d), jnp.bfloat16)

        def f(x, w):
            return jnp.sum(mlp_matmul(x, w, x_t=x_t, out_t=out_t, **_KW)
                           .astype(jnp.float32) * dy.astype(jnp.float32))

        def fr(x, w):
            return jnp.sum(_ref_proj(x, w, x_t, out_t).astype(jnp.float32)
                           * dy.astype(jnp.float32))

        gx, gw = jax.grad(f, (0, 1))(x, w)
        gxr, gwr = jax.grad(fr, (0, 1))(x, w)
        assert gx.shape == x.shape and gw.shape == w.shape
        np.testing.assert_allclose(np.asarray(gx, np.float32),
                                   np.asarray(gxr, np.float32),
                                   rtol=5e-2, atol=5e-2)
        # dw sums over B*T fp32 both sides; bf16 inputs -> looser atol
        np.testing.assert_allclose(np.asarray(gw, np.float32),
                                   np.asarray(gwr, np.float32),
                                   rtol=5e-2, atol=5e-1)

    @pytest.mark.parametrize("fuse_dw", [True, False])
    def test_gradcheck_fp32_epilogues(self, fuse_dw):
        """Analytic grads through the fused dx/dw epilogue kernels vs
        the autodiff of the jnp reference, fp32 (tight tolerance)."""
        B, T, K, d = 1, 128, 128, 64
        ks = jax.random.split(jax.random.PRNGKey(2), 2)
        x = _rand(ks[0], (B, K, T), jnp.float32)    # T-minor operand
        w = _rand(ks[1], (K, d), jnp.float32)

        def f(x, w):
            return jnp.sum(mlp_matmul(x, w, x_t=True, fuse_dw=fuse_dw,
                                      **_KW) ** 2)

        def fr(x, w):
            return jnp.sum(_ref_proj(x, w, True, False) ** 2)

        for a, b in zip(jax.grad(f, (0, 1))(x, w),
                        jax.grad(fr, (0, 1))(x, w)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


class TestMlpKernelInModel:
    """cfg.mlp_kernel wiring: loss/grad parity vs the XLA MLP path."""

    pytestmark = pytest.mark.slow

    def _setup(self):
        from dataclasses import replace
        from deepspeed_tpu.models.gpt2 import GPT2, GPT2_TINY
        cfg = replace(GPT2_TINY, remat=False)
        m = GPT2(cfg)
        params = m.init(jax.random.PRNGKey(0))
        batch = {"input_ids": np.random.RandomState(0)
                 .randint(0, 1024, (2, 128)).astype(np.int32)}
        return cfg, m, params, batch

    @pytest.mark.parametrize("mode", ["down", "both"])
    def test_loss_and_grad_parity(self, mode):
        from dataclasses import replace
        from deepspeed_tpu.models.gpt2 import GPT2
        cfg, m0, params, batch = self._setup()
        l0, g0 = jax.value_and_grad(
            lambda p: m0.loss(p, batch, train=False))(params)
        m1 = GPT2(replace(cfg, mlp_kernel=mode))
        l1, g1 = jax.value_and_grad(
            lambda p: m1.loss(p, batch, train=False))(params)
        assert abs(float(l0) - float(l1)) < 3e-2
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=5e-2, atol=5e-2)

    def test_remat_policies_compose(self):
        from dataclasses import replace
        from deepspeed_tpu.models.gpt2 import GPT2
        cfg, m0, params, batch = self._setup()
        l0 = float(m0.loss(params, batch, train=False))
        m1 = GPT2(replace(cfg, mlp_kernel="down", remat=True,
                          remat_policy="save_flash"))
        l1, _ = jax.value_and_grad(
            lambda p: m1.loss(p, batch, train=False))(params)
        assert abs(float(l1) - l0) < 3e-2

    def test_auto_defers_to_measured_dispatch(self):
        """'auto' no longer hand-guesses by platform: it defers to the
        autotune winner cache (resolved in _mlp where the activation
        shape is known), and a cache miss keeps the r05-proven XLA
        path — loss identical to mlp_kernel=False."""
        from dataclasses import replace
        from deepspeed_tpu.autotuning import kernel_dispatch
        from deepspeed_tpu.models.gpt2 import GPT2
        cfg, m0, params, batch = self._setup()
        m = GPT2(replace(cfg, mlp_kernel="auto"))
        assert m._mlp_kernel_mode() == "auto"
        kernel_dispatch.reset()
        kernel_dispatch.configure(mode="cache_only",
                                  cache_path="/nonexistent/at.json")
        try:
            l_auto = float(m.loss(params, batch, train=False))
            l_xla = float(m0.loss(params, batch, train=False))
            assert l_auto == l_xla
        finally:
            kernel_dispatch.reset()
