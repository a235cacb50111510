"""GPT-2 model family — the flagship training target.

The reference has no model zoo for training (users bring Megatron/HF
modules); its test fixtures use tiny nn.Modules (tests/unit/simple_model.py)
and the BASELINE targets are GPT-2 125M/350M/1.3B. Here the model is a
first-class citizen so the engine can be exercised end-to-end without torch.

TPU-first design decisions:
  * Layers are STACKED (leading layer dim) and iterated with ``lax.scan`` —
    one compiled block regardless of depth, fast XLA compiles at 1.3B+.
  * Tensor parallelism is *declarative*: ``partition_specs`` assigns the
    Megatron column/row split to the 'tensor' mesh axis and the forward
    inserts ``with_sharding_constraint`` on activations; GSPMD emits the
    psum/all_gathers (reference achieves this imperatively via an external
    mpu + module_inject/auto_tp.py:188).
  * Ulysses sequence parallelism is likewise declarative: inputs arrive
    sequence-sharded on the 'seq' axis, and attention constrains the heads
    dim onto 'seq' instead — XLA emits exactly the head-scatter/seq-gather
    all_to_all pair of the reference's DistributedAttention
    (deepspeed/sequence/layer.py:60).
  * Activation checkpointing = ``jax.checkpoint`` on the scanned block
    (reference runtime/activation_checkpointing/checkpointing.py:485).
  * bf16 params/activations, fp32 LayerNorm and loss, MXU-friendly dims.
"""

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..utils.groups import BATCH_AXES
from . import paged
from .common import (chunked_softmax_xent, constrain_fn, fused_linear_xent,
                     next_token_xent,
                     resolve_remat_policy)


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304          # 50257 padded to a multiple of 128 (MXU)
    max_seq_len: int = 1024
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    dropout: float = 0.0
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "nothing_saveable"
    # pallas flash kernel: "auto" (default) = on when running on TPU,
    # dense path elsewhere; True/False force. The benchmarked fast path
    # is the default — users no longer opt in via env/config.
    use_flash_attention: object = "auto"
    # pallas attention tile sizes. Each block knob (and flash_bwd_qmajor
    # below) also accepts "auto": the kernel then resolves it at trace
    # time against the persistent autotune winner cache for this
    # (device_kind, seq-bucket, head_dim, dtype) — falling back to the
    # r05-proven values below on a cache miss (ops/pallas/_common.
    # dispatch; see the README "Kernel autotuning" section)
    flash_block_q: object = 128
    flash_block_k: object = 128
    flash_block_h: object = 2          # (batch*head) instances per grid step
    flash_block_q_bwd: object = 0      # 0 = same as flash_block_q/_k; the
    flash_block_k_bwd: object = 0      # fused bwd pass may prefer smaller
    # feed the flash kernel (B, H, hd, T) operands (T in lanes) — the qkv
    # einsum's natural output layout, eliminating the relayout copies XLA
    # otherwise inserts at every kernel boundary (~46 ms/step at 350M)
    flash_qkv_t: bool = True
    # 'dense': GSPMD Ulysses resharding (all_to_all pair) when seq-sharded.
    # 'ring': ring/context-parallel attention (sequence/ring.py) — KV blocks
    #         rotate over the 'seq' axis; no head-count constraint.
    attention_backend: str = "dense"
    # pipeline parallelism (GPT2Pipe): microbatches in flight; 0 = auto
    # (2x the pipe axis size, amortizing the fill/drain bubble)
    pipe_microbatches: int = 0
    # pipeline training schedule: 'gpipe' (all-forward then autodiff
    # backward; residual memory grows with microbatch count), '1f1b'
    # (interleaved forward/backward, live activations bounded by
    # O(stages) — runtime/pipe/spmd.py pipeline_1f1b_grads), or 'zb'
    # (zero-bubble: 1F1B with the backward W/B split so weight-grad
    # work fills the drain ticks — pipeline_zb_grads; same memory
    # class, strictly lower executor bubble). The engine's pipeline
    # config block can override this when its schedule != 'auto'.
    pipe_schedule: str = "gpipe"
    # chunked cross entropy: unembed+CE computed per loss_chunk tokens
    # under remat so the full (B, T, V) fp32 logits never materialize
    # (0 = off). Big-vocab memory saver; exact same loss value.
    loss_chunk: int = 0
    # fused linear+CE with gradients computed IN FORWARD (the scalar-loss
    # custom_vjp trick — common.fused_linear_xent): removes the backward
    # logits-recompute matmul and a softmax pass vs the remat'd chunked
    # path. Requires loss_chunk > 0; same loss value.
    fused_loss: bool = False
    # + the Pallas unembed/online-stats kernel (ops/pallas/fused_ce.py):
    # fp32 logits never touch HBM; logz/gold exact, d_logits from the
    # bf16 logits (the MXU's own operand truncation)
    fused_loss_kernel: bool = False
    # lax.scan unroll over layers (1 = compact single-block program;
    # higher trades compile time/code size for cross-layer overlap)
    scan_unroll: int = 1
    # MLP activation: 'gelu' (gpt2) or 'relu' (opt)
    activation: str = "gelu"
    # gpt-neo knobs (reference module_inject/containers/gptneo.py):
    # scale_attn=False — HF GPT-Neo does NOT divide scores by sqrt(hd);
    # attn_layer_windows — per-layer sliding window from the config's
    # attention_types pattern (0 = global); non-empty forces the dense
    # attention path (the window is a per-layer scan operand)
    scale_attn: bool = True
    attn_layer_windows: tuple = ()
    # layout-owning Pallas MLP projection matmul (ops/pallas/
    # mlp_matmul.py; reference csrc/transformer/cublas_wrappers.cu —
    # the epilogue-fusing GEMM tier). Attacks the measured T-minor
    # wdown emitter penalty (~13 ms/step at 350M: XLA's
    # EmitOutputBatchInLanesKernelOutputFeatureInLanes half-rates the
    # down projection under the flash path's T-in-lanes layout
    # pressure) by giving the projection a kernel that consumes the
    # einsum's natural T-minor activation and emits the residual-add
    # layout directly, with the backward dx emitted in the activation's
    # own orientation and dw's fp32-accumulate + weight-dtype cast
    # fused. Values: False (XLA, default) | 'auto' (the autotune winner
    # cache's measured choice of path + tiles + epilogue for this
    # device/shape/dtype; r05-proven XLA einsums on a cache miss) |
    # 'down' (down projection only) | 'both' (up emits T-minor via the
    # kernel too). Not used when seq-sharded (Ulysses keeps the XLA
    # path).
    mlp_kernel: object = False
    # False leaves the weight grad to XLA (inside the layer scan it
    # fuses into the grad-stacking DUS at full MXU rate — the round-3
    # trace finding); True uses the kernel's fused fp32-accum dw
    mlp_kernel_fuse_dw: bool = True
    # q-major fused flash backward (ops/pallas/flash_attention.py
    # _bwd_kernel_t_qmajor): dq written once per grid step in the model
    # dtype (no fp32 HBM round trip + cast copy) and dk/dv accumulated
    # VMEM-resident across the sequential grid — the trick that won
    # -38 ms on dq, applied to the dkv side. qkv_t layouts only;
    # biased/ALiBi paths keep the k-major kernel. Accepts "auto"
    # (autotune winner cache, False on a miss).
    flash_bwd_qmajor: object = False
    # fused one-pass LayerNorm Pallas kernel (ops/pallas/layernorm.py;
    # reference csrc/transformer/normalize_kernels.cu). Measured SLOWER
    # than XLA's fused jnp layernorm inside the 350M training step (the
    # custom-call boundary breaks surrounding elementwise fusions and
    # pins layouts XLA wants freedom over: 727 -> 785 ms/step), so the
    # default is off; the kernel stays available for standalone use.
    # 'auto' = the autotune winner cache's measured jnp/fused/hybrid
    # choice (+ row tiling) for this device/shape/dtype, r05-proven jnp
    # on a cache miss; True forces the fused kernel.
    fused_layernorm: object = False

    @property
    def flash_on(self):
        """Resolved use_flash_attention (see common.resolve_flash)."""
        from .common import resolve_flash
        return resolve_flash(self.use_flash_attention)

    @property
    def d_head(self):
        return self.d_model // self.n_head

    @property
    def d_ff(self):
        return 4 * self.d_model

    def num_params(self):
        wte = self.vocab_size * self.d_model
        wpe = self.max_seq_len * self.d_model
        block = (4 * self.d_model  # ln scales/biases
                 + self.d_model * 3 * self.d_model + 3 * self.d_model
                 + self.d_model * self.d_model + self.d_model
                 + 2 * self.d_model * self.d_ff + self.d_ff + self.d_model)
        return wte + wpe + self.n_layer * block + 2 * self.d_model

    def flops_per_token(self):
        """6*N + attention flops per token (training fwd+bwd)."""
        n = self.num_params() - self.vocab_size * self.d_model
        return 6 * n + 12 * self.n_layer * self.d_model * self.max_seq_len


# BASELINE.md model points
GPT2_TINY = GPT2Config(n_layer=2, n_head=4, d_model=128, max_seq_len=128,
                       vocab_size=1024)
GPT2_125M = GPT2Config(n_layer=12, n_head=12, d_model=768)
GPT2_350M = GPT2Config(n_layer=24, n_head=16, d_model=1024)
GPT2_1_3B = GPT2Config(n_layer=24, n_head=32, d_model=2048)
# the GPT-3 13B shape (40 x 5120, 40 heads): the pipeline + host-offload
# target — does not fit one small-pod chip's HBM without pp>=2 and the
# offload tiers (ROADMAP item 4's measured point)
GPT2_13B = GPT2Config(n_layer=40, n_head=40, d_model=5120,
                      max_seq_len=2048)

PRESETS = {"tiny": GPT2_TINY, "125M": GPT2_125M, "350M": GPT2_350M,
           "1.3B": GPT2_1_3B, "13B": GPT2_13B}


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


class GPT2:
    """Functional model: ``init(rng) -> params``; ``loss(params, batch, rng)``.

    Params layout (all block tensors carry a leading n_layer dim):
      wte (V,D) | wpe (T,D) | lnf_{scale,bias} (D,)
      blocks: ln1_{scale,bias} (L,D), wqkv (L,D,3D), bqkv (L,3D),
              wo (L,D,D), bo (L,D), ln2_{scale,bias} (L,D),
              wup (L,D,F), bup (L,F), wdown (L,F,D), bdown (L,D)
    """

    def __init__(self, config: GPT2Config):
        self.config = config

    # --- init ---
    def init(self, rng):
        cfg = self.config
        dt = _dtype(cfg)
        k = iter(jax.random.split(rng, 16))
        std = 0.02
        # GPT-2 residual-projection scaling: std/sqrt(2L)
        res_std = std / math.sqrt(2 * cfg.n_layer)
        L, D, F, V, T = (cfg.n_layer, cfg.d_model, cfg.d_ff, cfg.vocab_size,
                         cfg.max_seq_len)

        def nrm(key, shape, s):
            return (jax.random.normal(key, shape, jnp.float32) * s).astype(dt)

        params = {
            "wte": nrm(next(k), (V, D), std),
            "wpe": nrm(next(k), (T, D), std),
            "lnf_scale": jnp.ones((D,), dt),
            "lnf_bias": jnp.zeros((D,), dt),
            "blocks": {
                "ln1_scale": jnp.ones((L, D), dt),
                "ln1_bias": jnp.zeros((L, D), dt),
                "wqkv": nrm(next(k), (L, D, 3 * D), std),
                "bqkv": jnp.zeros((L, 3 * D), dt),
                "wo": nrm(next(k), (L, D, D), res_std),
                "bo": jnp.zeros((L, D), dt),
                "ln2_scale": jnp.ones((L, D), dt),
                "ln2_bias": jnp.zeros((L, D), dt),
                "wup": nrm(next(k), (L, D, F), std),
                "bup": jnp.zeros((L, F), dt),
                "wdown": nrm(next(k), (L, F, D), res_std),
                "bdown": jnp.zeros((L, D), dt),
            },
        }
        return params

    # --- sharding rules ---
    def partition_specs(self, topology=None):
        """Megatron TP split on 'tensor' (reference module_inject/auto_tp.py
        does this by module-name heuristics; here it is the source of truth).
        Column-parallel: wqkv/wup (out dim); row-parallel: wo/wdown (in dim).
        Embeddings/LN replicated over 'tensor'."""
        return {
            "wte": P(),
            "wpe": P(),
            "lnf_scale": P(),
            "lnf_bias": P(),
            "blocks": {
                "ln1_scale": P(None, None),
                "ln1_bias": P(None, None),
                "wqkv": P(None, None, "tensor"),
                "bqkv": P(None, "tensor"),
                "wo": P(None, "tensor", None),
                "bo": P(None, None),
                "ln2_scale": P(None, None),
                "ln2_bias": P(None, None),
                "wup": P(None, None, "tensor"),
                "bup": P(None, "tensor"),
                "wdown": P(None, "tensor", None),
                "bdown": P(None, None),
            },
        }

    # --- forward ---
    moe_loss_coeff = 0.0  # overridden by GPT2MoE

    def apply(self, params, input_ids, *, rng=None, train=False,
              seq_sharded=False):
        """Return logits (B, T, V) fp32 (aux loss dropped)."""
        logits, _ = self.apply_with_aux(params, input_ids, rng=rng,
                                        train=train, seq_sharded=seq_sharded)
        return logits

    def _apply_ltd(self, params, input_ids, ltd_keep, *, rng, train,
                   constrain, act_spec):
        """Random-LTD forward (reference runtime/data_pipeline/
        data_routing + csrc/random_ltd/): first and last blocks see the
        full sequence; the middle blocks see ``ltd_keep`` random tokens
        (sorted indices preserve order/position), with dropped positions
        flowing through the skip connection. ``ltd_keep`` is static —
        distinct values are distinct programs, bounded by the schedule's
        seq_step quantization."""
        from ..runtime.data_pipeline.random_ltd import (token_drop,
                                                        token_restore)
        cfg = self.config
        if cfg.n_layer < 3:
            raise ValueError("random-LTD needs n_layer >= 3 (first and "
                             "last blocks stay full-sequence)")
        if cfg.attn_layer_windows:
            # windowed distances are undefined over LTD's gathered
            # (non-contiguous) token subsets — refuse loudly rather than
            # silently train all layers global
            raise ValueError("random-LTD is not supported with per-layer "
                             "local attention windows (attn_layer_windows)")
        T = input_ids.shape[1]
        x = self.embed(params, input_ids, rng=rng, train=train,
                       constrain=constrain, act_spec=act_spec)
        causal = jnp.tril(jnp.ones((T, T), jnp.bool_))
        base_rng = rng if rng is not None else jax.random.key(0)
        layer_rngs = jax.random.split(base_rng, cfg.n_layer)
        blocks = params["blocks"]
        first = jax.tree.map(lambda a: a[0], blocks)
        last = jax.tree.map(lambda a: a[-1], blocks)
        mid = jax.tree.map(lambda a: a[1:-1], blocks)

        x, aux0 = self.block_forward(
            x, first, layer_rngs[0], causal=causal, constrain=constrain,
            act_spec=act_spec, seq_sharded=False, train=train)
        x_keep, idx = token_drop(x, ltd_keep,
                                 jax.random.fold_in(base_rng, 0x17D))
        # gathered causal mask: kept token i attends kept token j iff
        # their ORIGINAL positions are causal
        mask = idx[:, :, None] >= idx[:, None, :]

        def mid_block(h, layer, lrng):
            return self.block_forward(
                h, layer, lrng, causal=mask, constrain=constrain,
                act_spec=act_spec, seq_sharded=False, train=train)

        block_fn = mid_block
        if cfg.remat:
            block_fn = jax.checkpoint(
                mid_block, policy=resolve_remat_policy(cfg.remat_policy))

        def scan_body(carry, xs):
            layer, lrng = xs
            h, aux = block_fn(carry, layer, lrng)
            return h, aux

        x_keep, auxs = lax.scan(scan_body, x_keep,
                                (mid, layer_rngs[1:-1]))
        x = token_restore(x_keep, idx, x)
        x, auxL = self.block_forward(
            x, last, layer_rngs[-1], causal=causal, constrain=constrain,
            act_spec=act_spec, seq_sharded=False, train=train)
        return x, aux0 + jnp.sum(auxs) + auxL

    def apply_with_aux(self, params, input_ids, *, rng=None, train=False,
                       seq_sharded=False, return_hidden=False):
        """Return (logits (B, T, V) fp32, summed aux loss) — aux is the MoE
        load-balance loss (0 for dense models). ``return_hidden`` skips the
        unembed and returns the (B, T, D) hidden states instead (the
        chunked-loss path).

        ``seq_sharded``: inputs/activations carry T on the 'seq' mesh axis
        (Ulysses). Attention re-constrains heads onto 'seq' so XLA emits the
        all_to_all pair.
        """
        cfg = self.config
        T = input_ids.shape[1]

        constrain = self._constrain_fn()
        act_spec = P(BATCH_AXES, "seq" if seq_sharded else None, None)
        x = self.embed(params, input_ids, rng=rng, train=train,
                       constrain=constrain, act_spec=act_spec)

        # causal mask built once; fp32 scores
        causal = jnp.tril(jnp.ones((T, T), jnp.bool_))

        def block(x, layer, lrng, window=None):
            return self.block_forward(x, layer, lrng, causal=causal,
                                      constrain=constrain, act_spec=act_spec,
                                      seq_sharded=seq_sharded, train=train,
                                      window=window)

        block_fn = block
        if cfg.attn_layer_windows and cfg.remat \
                and cfg.remat_policy == "split_attn":
            raise ValueError(
                "attn_layer_windows is not supported with "
                "remat_policy='split_attn' (the split block does not "
                "thread the per-layer window)")
        if cfg.remat and cfg.remat_policy == "split_attn":
            # jax NEVER stores custom_vjp residuals across a checkpoint
            # inside scan — a whole-block remat re-runs the flash forward
            # kernel in backward. Splitting the remat boundary keeps
            # attention OUTSIDE any checkpoint: its residuals (q, k, v, o,
            # lse) become ordinary scan residuals (saved), while the
            # cheap-to-recompute pre (ln1+qkv) and post (wo/ln2/MLP)
            # segments remat. Backward then runs zero extra flash kernels
            # and recomputes only matmul-light segments.
            def split_block(x, layer, lrng):
                hm = cfg.flash_on and not seq_sharded
                pre = jax.checkpoint(partial(
                    self.block_qkv, constrain=constrain, act_spec=act_spec,
                    heads_major=hm))
                q, kk, v = pre(x, layer)
                attn = self.block_attn(q, kk, v, causal=causal,
                                       constrain=constrain,
                                       seq_sharded=seq_sharded)
                post = jax.checkpoint(partial(
                    self.block_post, constrain=constrain, act_spec=act_spec,
                    seq_sharded=seq_sharded, train=train, heads_major=hm))
                return post(x, attn, layer, lrng)
            block_fn = split_block
        elif cfg.remat:
            block_fn = jax.checkpoint(
                block, policy=resolve_remat_policy(cfg.remat_policy))

        layer_rngs = jax.random.split(
            rng if rng is not None else jax.random.key(0), cfg.n_layer)

        # comm-overlap prefetch hint (engine-installed): unroll >= 2 puts
        # consecutive layers in one scan body so layer i+1's param gather
        # has layer i's matmuls to hide under (the explicit double buffer
        # XLA's ag-pipelining pass then rotates across iterations)
        unroll = max(cfg.scan_unroll,
                     getattr(self, "_scan_unroll_min", 0) or 0)

        if cfg.attn_layer_windows:
            # per-layer local windows ride the scan as an operand (not a
            # param: the optimizer never sees them)
            windows = jnp.asarray(cfg.attn_layer_windows, jnp.int32)

            def scan_body(carry, xs):
                layer, lrng, w = xs
                x, aux = block_fn(carry, layer, lrng, w)
                return x, aux

            x, auxs = lax.scan(scan_body, x,
                               (params["blocks"], layer_rngs, windows),
                               unroll=unroll)
        else:
            def scan_body(carry, xs):
                layer, lrng = xs
                x, aux = block_fn(carry, layer, lrng)
                return x, aux

            x, auxs = lax.scan(scan_body, x, (params["blocks"], layer_rngs),
                               unroll=unroll)
        if return_hidden:
            return x, jnp.sum(auxs)
        return self.head(params, x), jnp.sum(auxs)

    def _constrain_fn(self):
        return constrain_fn()

    def _ln(self, x, scale, bias):
        """LayerNorm dispatch: 'bwd' = jnp forward + one-pass Pallas
        backward (layernorm_fused_bwd); True = fully fused Pallas
        kernel; False = jnp; 'auto' = the autotune winner cache's
        measured choice for this (device, rows, D) — falling back to
        the r05-proven jnp form on a cache miss (XLA's fused layernorm
        measured faster inside real programs on v5e)."""
        use = self.config.fused_layernorm
        block_rows = "auto"
        if use == "auto":
            import math as _math
            from ..autotuning.kernel_registry import LN_DEFAULTS
            from ..ops.pallas._common import dispatch, dtype_name, \
                ln_bucket
            win = dispatch(
                "layernorm",
                ln_bucket(_math.prod(x.shape[:-1]), x.shape[-1]),
                dtype_name(x.dtype), LN_DEFAULTS)
            variant = win["variant"]
            if x.shape[-1] % 128:
                variant = "jnp"     # Pallas row-blocked kernels need
            use = {"jnp": False,    # a lane-tileable feature dim
                   "fused": True, "bwd": "bwd"}.get(variant, False)
            block_rows = int(win["block_rows"])
        if use == "bwd":
            from ..ops.pallas.layernorm import layernorm_fused_bwd
            return layernorm_fused_bwd(x, scale, bias,
                                       block_rows=block_rows)
        if use:
            from ..ops.pallas.layernorm import fused_layernorm
            return fused_layernorm(x, scale, bias,
                                   block_rows=block_rows)
        return _layernorm(x, scale, bias)

    def embed(self, params, input_ids, *, rng, train, constrain, act_spec):
        """Token + position embedding (B, T) -> (B, T, D); validates the
        train rng. Shared by the dense and pipelined paths."""
        cfg = self.config
        if train and rng is None and self._requires_train_rng():
            # without this, the key(0) fallback in apply_with_aux would
            # silently make dropout/noisy gating deterministic across steps
            raise ValueError(
                "train=True requires rng= (model uses stochastic "
                "dropout/routing)")
        T = input_ids.shape[1]
        pos = jnp.arange(T)[None, :]
        x = params["wte"][input_ids] + params["wpe"][pos]
        x = constrain(x.astype(_dtype(cfg)), act_spec)
        if train and cfg.dropout > 0 and rng is not None:
            x = _dropout(x, cfg.dropout, jax.random.fold_in(rng, 0))
        return x

    def head(self, params, x):
        """Final LN + tied-embedding unembed: (B, T, D) -> fp32 logits."""
        x = self._ln(x, params["lnf_scale"], params["lnf_bias"])
        with jax.named_scope("dstpu.mm.unembed"):
            return jnp.einsum("btd,vd->btv", x, params["wte"],
                              preferred_element_type=jnp.float32)

    def block_qkv(self, x, layer, *, constrain, act_spec,
                  heads_major=False):
        """ln1 + qkv projection: (B, T, D) -> q, k, v each (B, T, H, hd).
        With ``heads_major``: (B, H, hd, T) when cfg.flash_qkv_t (the
        default — the flash kernel's transposed-operand layout, matching
        the einsum's natural T-minor output so no relayout copy exists
        between the projection and the kernel), else (B, H, T, hd).
        Cheap to recompute in backward (one matmul whose output no grad
        rule needs — only ln1_out is, and that's VPU work)."""
        cfg = self.config
        B, T = x.shape[0], x.shape[1]
        H, hd = cfg.n_head, cfg.d_head
        h = self._ln(x, layer["ln1_scale"], layer["ln1_bias"])
        with jax.named_scope("dstpu.mm.qkv"):
            if heads_major:
                w = layer["wqkv"].reshape(x.shape[-1], 3, H, hd)
                b = layer["bqkv"].reshape(3, H, hd)
                if cfg.flash_qkv_t:
                    # (B, H, hd, T): T-minor — the layout XLA prefers for
                    # the einsum output (hd=64 fills only half a lane
                    # register), consumed by the flash kernel with no
                    # relayout copy. Three separate projections (not one
                    # (3, ...) einsum): the fused form pays ~16 ms/step of
                    # repack fusions splitting its output into q/k/v
                    return tuple(
                        jnp.einsum("btd,dhe->bhet", h, w[:, i])
                        + b[i][:, :, None]
                        for i in range(3))
                qkv = jnp.einsum("btd,dshe->sbhte", h, w) \
                    + b[:, None, :, None, :]
                return qkv[0], qkv[1], qkv[2]
            qkv = h @ layer["wqkv"] + layer["bqkv"]
        qkv = qkv.reshape(B, T, 3, H, hd)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    @jax.named_scope("dstpu.attn.flash")
    def block_attn(self, q, kk, v, *, causal, constrain, seq_sharded,
                   force_dense=False, window=None):
        """Attention backend dispatch: (B, T, H, hd) x3 -> (B, T, H, hd).
        ``causal`` may carry a batch dim (B, t, s) — the random-LTD
        middle segment attends gathered (non-contiguous) positions, which
        also forces the dense path (``force_dense``). ``window``: traced
        per-layer sliding window (gpt-neo local attention; 0 = global),
        dense path only."""
        cfg = self.config
        dt = _dtype(cfg)
        if window is not None and causal.ndim == 2:
            T_ = causal.shape[-1]
            qp, kp = jnp.arange(T_)[:, None], jnp.arange(T_)[None, :]
            causal = causal & ((window == 0) | (qp - kp < window))
        if (seq_sharded and cfg.attention_backend == "ring"
                and not jax.sharding.get_abstract_mesh().empty):
            if window is not None or not cfg.scale_attn:
                raise ValueError(
                    "ring attention supports neither per-layer local "
                    "windows nor unscaled (gpt-neo) scores")
            # context parallel: KV rotates the 'seq' ring (ppermute).
            # Layout/kernel/overlap knobs come from the engine-installed
            # runtime config 'sequence' block (zigzag + blockwise flash
            # kernel + double-buffered rotation by default)
            from ..runtime.config import SequenceConfig
            from ..sequence.ring import ring_attention_sharded
            scfg = getattr(self, "_sequence_cfg", None) or SequenceConfig()
            attn = ring_attention_sharded(
                q, kk, v, jax.sharding.get_abstract_mesh(),
                batch_spec=P(BATCH_AXES), head_axis="tensor",
                layout=scfg.layout, block_kernel=scfg.block_kernel,
                double_buffer=scfg.double_buffer,
                rotate_chunks=getattr(scfg, "rotate_chunks", "auto"))
        elif cfg.flash_on and not seq_sharded and not force_dense:
            # pallas fused attention: O(T) memory, fp32 accumulation
            # (ops/pallas/flash_attention.py). Heads shard over 'tensor'.
            # Inputs arrive from block_qkv as (B, H, hd, T) when
            # cfg.flash_qkv_t (default), else heads-major (B, H, T, hd).
            from ..ops.pallas._common import dividing_axes, shard_kernel
            from ..ops.pallas.flash_attention import flash_attention
            head_spec = P(dividing_axes(q.shape[0], BATCH_AXES),
                          dividing_axes(q.shape[1], "tensor"), None, None)
            q = constrain(q, head_spec)
            kk = constrain(kk, head_spec)
            v = constrain(v, head_spec)
            attn = shard_kernel(
                partial(flash_attention, causal=True,
                        scale=None if cfg.scale_attn else 1.0,
                        block_q=cfg.flash_block_q,
                        block_k=cfg.flash_block_k,
                        block_h=cfg.flash_block_h,
                        block_q_bwd=cfg.flash_block_q_bwd or None,
                        block_k_bwd=cfg.flash_block_k_bwd or None,
                        heads_major=not cfg.flash_qkv_t,
                        qkv_t=cfg.flash_qkv_t,
                        bwd_qmajor=cfg.flash_bwd_qmajor),
                (head_spec,) * 3, head_spec)(q, kk, v).astype(dt)
            from jax.ad_checkpoint import checkpoint_name
            attn = checkpoint_name(attn, "attn_out")
        else:
            if seq_sharded:
                # Ulysses: heads onto 'seq', sequence gathered
                head_spec = P(BATCH_AXES, None, "seq", None)
            else:
                head_spec = P(BATCH_AXES, None, "tensor", None)
            q = constrain(q, head_spec)
            kk = constrain(kk, head_spec)
            v = constrain(v, head_spec)

            scores = jnp.einsum("bthd,bshd->bhts", q, kk,
                                preferred_element_type=jnp.float32)
            if cfg.scale_attn:
                scores = scores / math.sqrt(self.config.d_head)
            mask = causal[None, None] if causal.ndim == 2 \
                else causal[:, None]
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(dt)
            attn = jnp.einsum("bhts,bshd->bthd", probs, v)
            from jax.ad_checkpoint import checkpoint_name
            attn = checkpoint_name(attn, "attn_out")
        return attn

    def block_post(self, x, attn, layer, lrng, *, constrain, act_spec,
                   seq_sharded, train, heads_major=False):
        """Output projection residual + ln2 + MLP residual. ``attn`` is
        (B, T, H, hd), or (B, H, T, hd) when ``heads_major`` (flash path
        — the wo projection contracts (h, e) directly, no transpose)."""
        cfg = self.config
        B, T = x.shape[0], x.shape[1]
        if heads_major:
            wo = layer["wo"].reshape(cfg.n_head, cfg.d_head, cfg.d_model)
            with jax.named_scope("dstpu.mm.attn_out"):
                out = jnp.einsum("bhte,hed->btd", attn, wo)
        else:
            attn = attn.reshape(B, T, cfg.n_head * cfg.d_head)
            attn = constrain(attn, act_spec)
            with jax.named_scope("dstpu.mm.attn_out"):
                out = attn @ layer["wo"]
        x = x + out + layer["bo"]
        x = constrain(x, act_spec)
        from jax.ad_checkpoint import checkpoint_name
        # named so remat policies can keep the post-attention residual
        # stream (remat_policy='save_mid'/'save_mid_up'): backward then
        # recomputes only ln2 + the MLP instead of the attention half too
        x = checkpoint_name(x, "attn_mid")

        h = self._ln(x, layer["ln2_scale"], layer["ln2_bias"])
        mlp_out, aux = self._mlp(h, layer, lrng, train=train,
                                 seq_sharded=seq_sharded,
                                 constrain=constrain)
        x = x + mlp_out
        x = constrain(x, act_spec)
        # named block output: policies saving 'block_out' make each
        # layer's INPUT directly available in backward — without it, a
        # names-policy inside lax.scan reconstructs x_in_{l+1} by
        # replaying the whole l-th MLP forward (an extra ~2.4 ms/layer
        # wdown matmul on a layout XLA emits badly)
        x = checkpoint_name(x, "block_out")
        return x, aux

    def block_forward(self, x, layer, lrng, *, causal, constrain, act_spec,
                      seq_sharded, train, window=None):
        """One transformer block: (B, T, D) -> (B, T, D), plus aux loss.
        Shared by the dense scan path and the pipelined executor
        (models/gpt2_pipe.py)."""
        # engine-installed comm-overlap annotation (runtime/zero/
        # overlap.py): explicit ZeRO-3 gather of this layer's shard in
        # forward, per-scan-iteration grad reduce-scatter in backward
        hook = getattr(self, "_layer_comm_hook", None)
        if hook is not None:
            layer = hook(layer)
        from ..ops.int8_weights import dequant_tree
        layer = dequant_tree(layer, _dtype(self.config))
        # dense path for: random-LTD gathered masks and per-layer local
        # windows (a traced scan operand cannot pick a kernel per layer);
        # unscaled gpt-neo attention keeps the flash kernel via its
        # scale input
        force_dense = causal.ndim != 2 or window is not None
        hm = self.config.flash_on and not seq_sharded and not force_dense
        q, kk, v = self.block_qkv(x, layer, constrain=constrain,
                                  act_spec=act_spec, heads_major=hm)
        attn = self.block_attn(q, kk, v, causal=causal, constrain=constrain,
                               seq_sharded=seq_sharded,
                               force_dense=force_dense, window=window)
        return self.block_post(x, attn, layer, lrng, constrain=constrain,
                               act_spec=act_spec, seq_sharded=seq_sharded,
                               train=train, heads_major=hm)

    def _requires_train_rng(self):
        """True when a training forward is stochastic (overridden by
        GPT2MoE for noisy gating / top-2 sampling)."""
        return self.config.dropout > 0

    def _mlp_kernel_mode(self):
        """Resolved cfg.mlp_kernel: None (XLA path) | 'down' | 'both' |
        'auto' (= consult the autotune winner cache in _mlp, where the
        activation shape that keys the cache bucket is known; a miss
        falls back to the r05-proven XLA path)."""
        v = self.config.mlp_kernel
        if not v:
            return None
        if v == "auto":
            return "auto"
        return "down" if v is True else v

    def _mlp(self, h, layer, rng, *, train, seq_sharded, constrain):
        """Dense MLP; overridden by GPT2MoE with an expert-parallel MoE.
        Returns (output, aux_loss)."""
        with jax.named_scope("dstpu.mm.mlp"):
            return self._dense_mlp(h, layer, rng, train=train,
                                   seq_sharded=seq_sharded,
                                   constrain=constrain)

    def _dense_mlp(self, h, layer, rng, *, train, seq_sharded, constrain):
        from jax.ad_checkpoint import checkpoint_name
        acts = {"gelu": jax.nn.gelu, "relu": jax.nn.relu}
        if self.config.activation not in acts:
            raise ValueError(
                f"unknown activation {self.config.activation!r}; "
                f"expected one of {sorted(acts)}")
        from ..ops.int8_weights import _is_q
        if _is_q(layer["wup"]):
            # weight-only quantized serving FFN (engine weight_quant):
            # dequant fused into the projection kernel's flush epilogue
            from ..ops.pallas.mlp_matmul import wq_matmul
            u = wq_matmul(h, layer["wup"]) + layer["bup"]
            up = acts[self.config.activation](u)
            out = wq_matmul(up, layer["wdown"]) + layer["bdown"]
            return out, jnp.zeros((), jnp.float32)
        q8 = getattr(self, "_int8_matmul", False)
        if q8 == "auto" and not seq_sharded:
            # measured W8A8 lever (quantize.int8_matmul="auto"): the
            # 'mlp_int8' winner for this shape bucket — winners must
            # pass the registry parity gate before caching, and a cold
            # cache keeps the exact fp program
            from ..ops.pallas._common import dispatch, dtype_name, \
                mlp_bucket
            D, F = layer["wup"].shape
            q8 = bool(dispatch("mlp_int8", mlp_bucket(h.shape[1], D, F),
                               dtype_name(h.dtype), {"int8": 0})["int8"])
        if q8 and q8 != "auto":
            # W8A8 compute: dynamic rowwise activation codes x
            # channelwise weight codes, int32 accumulate, straight-
            # through fp grads (ops/pallas/quantization.int8_matmul)
            from ..ops.pallas.quantization import int8_matmul
            u = checkpoint_name(int8_matmul(h, layer["wup"])
                                + layer["bup"], "mlp_up")
            up = acts[self.config.activation](u)
            up = constrain(up, P(BATCH_AXES,
                                 "seq" if seq_sharded else None, "tensor"))
            return (int8_matmul(up, layer["wdown"]) + layer["bdown"],
                    jnp.zeros((), jnp.float32))
        mode = self._mlp_kernel_mode() if not seq_sharded else None
        mm_kw = dict(fuse_dw=self.config.mlp_kernel_fuse_dw)
        if mode == "auto":
            # measured dispatch: the cached winner for this (device,
            # tokens, D, F) picks the projection path AND its tile/
            # epilogue knobs; a miss keeps the r05-proven XLA einsums
            from ..autotuning.kernel_registry import MLP_DEFAULTS
            from ..ops.pallas._common import dispatch, dtype_name, \
                mlp_bucket
            D, F = layer["wup"].shape
            win = dispatch(
                "mlp_matmul", mlp_bucket(h.shape[1], D, F),
                dtype_name(h.dtype),
                {**MLP_DEFAULTS,
                 "fuse_dw": self.config.mlp_kernel_fuse_dw})
            mode = None if win["mode"] == "xla" else win["mode"]
            mm_kw = dict(fuse_dw=bool(win["fuse_dw"]),
                         block_t=int(win["block_t"]),
                         block_o=int(win["block_o"]),
                         block_k=int(win["block_k"]))
        if mode:
            # layout-owning projection kernels: the pre-activation is
            # carried (B, F, T) — the up einsum's NATURAL T-minor output
            # (no transpose anywhere) — and the down kernel consumes it
            # directly, emitting the residual-add (B, T, D) layout, so
            # neither XLA's half-rate T-minor wdown emitter nor the
            # backward relayout copies exist on this path
            from ..ops.pallas.mlp_matmul import mlp_matmul
            if mode == "both":
                u = mlp_matmul(h, layer["wup"], out_t=True, **mm_kw)
            else:
                u = jnp.einsum("btd,df->bft", h, layer["wup"])
            u = checkpoint_name(u + layer["bup"][None, :, None], "mlp_up")
            up = acts[self.config.activation](u)
            up = constrain(up, P(BATCH_AXES, "tensor", None))
            out = mlp_matmul(up, layer["wdown"], x_t=True, **mm_kw)
            return out + layer["bdown"], jnp.zeros((), jnp.float32)
        # named pre-activation: saving it skips the wup matmul recompute in
        # backward (gelu' needs this tensor; gelu_out is one VPU op away)
        u = checkpoint_name(h @ layer["wup"] + layer["bup"], "mlp_up")
        up = acts[self.config.activation](u)
        up = constrain(up, P(BATCH_AXES, "seq" if seq_sharded else None,
                             "tensor"))
        return (up @ layer["wdown"] + layer["bdown"],
                jnp.zeros((), jnp.float32))

    # --- KV-cache inference path (reference ops/transformer/inference/
    #     ds_attention.py:16 + inference_context.h workspace mgmt; here the
    #     cache is an explicit pytree threaded through jitted steps) ---
    def init_cache(self, batch_size, max_len, dtype=None):
        """Allocate the KV cache: {'k','v'}: (L, B, max_len, H, hd)."""
        cfg = self.config
        dt = jnp.dtype(dtype) if dtype is not None else _dtype(cfg)
        shape = (cfg.n_layer, batch_size, max_len, cfg.n_head, cfg.d_head)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def cache_specs(self, batch_axes=BATCH_AXES):
        """Sharding for the KV cache: batch over data axes, heads over
        'tensor' (matches the attention TP split)."""
        spec = P(None, batch_axes, None, "tensor", None)
        return {"k": spec, "v": spec}

    def _block_core(self, x, layer, attn_fn):
        """Shared block scaffolding for every cache-backed inference path:
        ln1 -> qkv projection -> ``attn_fn`` -> output projection residual
        -> ln2 -> mlp residual. ``attn_fn((B,T,H,hd) q, k, v) -> (attn
        (B,T,H,hd), carry)`` owns masking and any cache reads/writes.
        Returns (x_out, carry)."""
        cfg = self.config
        from ..ops.int8_weights import dequant_tree
        keep = self._WQ_KEEP \
            if getattr(self, "_weight_quant_fused", False) else ()
        layer = dequant_tree(layer, _dtype(cfg), keep=keep)
        B, T = x.shape[0], x.shape[1]
        H, hd = cfg.n_head, cfg.d_head
        h = self._ln(x, layer["ln1_scale"], layer["ln1_bias"])
        with jax.named_scope("dstpu.mm.qkv"):
            qkv = h @ layer["wqkv"] + layer["bqkv"]
        qkv = qkv.reshape(B, T, 3, H, hd)
        attn, carry = attn_fn(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
        with jax.named_scope("dstpu.mm.attn_out"):
            out = attn.reshape(B, T, H * hd) @ layer["wo"]
        x = x + out + layer["bo"]
        h = self._ln(x, layer["ln2_scale"], layer["ln2_bias"])
        mlp_out, _ = self._mlp(h, layer, None, train=False,
                               seq_sharded=False,
                               constrain=lambda t, s: t)
        return x + mlp_out, carry

    def block_forward_cached(self, x, layer, k_cache, v_cache, slot,
                             valid_mask, window=None):
        """One block over new tokens with a KV cache.

        x: (B, T, D) new-token activations, written at cache slots
        [slot, slot+T). k_cache/v_cache: (B, Tmax, H, hd).
        valid_mask: (B, Tmax) bool — True where the cache holds a real
        token AFTER this write (left-padded prompts carry False slots).
        ``window``: traced per-layer local window (gpt-neo; 0 = global).
        Returns (x_out, k_cache, v_cache).
        """
        cfg = self.config
        dt = _dtype(cfg)
        T = x.shape[1]
        hd = cfg.d_head
        Tmax = k_cache.shape[1]

        def attn_fn(q, kk, v):
            kc = lax.dynamic_update_slice(k_cache, kk.astype(k_cache.dtype),
                                          (0, slot, 0, 0))
            vc = lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                          (0, slot, 0, 0))
            scores = jnp.einsum("bthd,bshd->bhts", q, kc,
                                preferred_element_type=jnp.float32)
            if cfg.scale_attn:
                scores = scores / math.sqrt(hd)
            # slot-causal: query at slot s_q = slot+t sees slots s <= s_q
            # that hold valid tokens (pads masked out forever)
            s_idx = jnp.arange(Tmax)[None, None, None, :]
            q_idx = (slot + jnp.arange(T))[None, None, :, None]
            mask = (s_idx <= q_idx) & valid_mask[:, None, None, :]
            if window is not None:
                mask = mask & ((window == 0) | (q_idx - s_idx < window))
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(dt)
            return jnp.einsum("bhts,bshd->bthd", probs, vc), (kc, vc)

        x, (kc, vc) = self._block_core(x, layer, attn_fn)
        return x, kc, vc

    def apply_cached(self, params, input_ids, pos_ids, cache, slot,
                     valid_mask, last_token_only=False):
        """Forward T new tokens through all layers with the KV cache.

        input_ids: (B, T); pos_ids: (B, T) absolute position-embedding
        indices (left-padded prompts offset these); slot: scalar cache
        write offset; valid_mask: (B, Tmax) validity AFTER the write.
        Returns (logits (B, T, V) fp32, new cache); ``last_token_only``
        unembeds just the final position (prefill only samples there —
        skips the (B, T, V) fp32 logits materialization).
        """
        x = (params["wte"][input_ids]
             + params["wpe"][pos_ids]).astype(_dtype(self.config))

        if self.config.attn_layer_windows:
            windows = jnp.asarray(self.config.attn_layer_windows, jnp.int32)

            def body(carry, xs):
                layer, kc, vc, w = xs
                y, kc, vc = self.block_forward_cached(carry, layer, kc, vc,
                                                      slot, valid_mask, w)
                return y, (kc, vc)

            x, (kc, vc) = lax.scan(
                body, x, (params["blocks"], cache["k"], cache["v"], windows))
        else:
            def body(carry, xs):
                layer, kc, vc = xs
                y, kc, vc = self.block_forward_cached(carry, layer, kc, vc,
                                                      slot, valid_mask)
                return y, (kc, vc)

            x, (kc, vc) = lax.scan(body, x,
                                   (params["blocks"], cache["k"], cache["v"]))
        if last_token_only:
            x = x[:, -1:]
        return self.head(params, x), {"k": kc, "v": vc}

    # --- paged (blocked) KV-cache path for the v2 serving engine
    #     (reference inference/v2/kernels/ragged_ops blocked_flash +
    #     ragged/kv_cache.py BlockedKVCache; here the cache is a pool of
    #     fixed-size blocks indexed by per-sequence block tables) ---
    def init_paged_cache(self, num_blocks, block_size, dtype=None):
        """{'k','v'}: LISTS of per-layer (num_blocks, H, block_size, hd)
        pools, heads-major (the Pallas paged-decode kernel's (H, BS, hd)
        block needs no in-VMEM transpose). Separate per-layer buffers —
        not one stacked (L, ...) array — so each layer's new-token scatter
        updates its own donated buffer IN PLACE; a stacked array carried
        through lax.scan gets defensively copied every layer (custom-call
        operand + carry), ~the whole pool per layer. Block 0 is the
        scratch block (pad/inactive writes land there)."""
        cfg = self.config
        dt = jnp.dtype(dtype) if dtype is not None else _dtype(cfg)
        shape = (num_blocks, cfg.n_head, block_size, cfg.d_head)
        return {"k": [jnp.zeros(shape, dt) for _ in range(cfg.n_layer)],
                "v": [jnp.zeros(shape, dt) for _ in range(cfg.n_layer)]}

    def paged_cache_specs(self):
        spec = P(None, "tensor", None, None)
        L = self.config.n_layer
        return {"k": [spec] * L, "v": [spec] * L}

    # FFN weight keys the fused-dequant serving path keeps quantized
    # (engine_v2 sets _weight_quant_fused; _mlp routes them through
    # wq_matmul's fused epilogue)
    _WQ_KEEP = ("wup", "wdown")

    def _paged_layers(self, params, x, step):
        """The serving layer loop: every block through ``_block_core``
        with the ``attn_fn`` the paged ``step`` (models/paged.py) hands
        out for it. Python-unrolled: each layer's pools are buffers of
        their own (see init_paged_cache), and ``_block_core`` dequantizes
        int8 serving weights one layer's slice at a time. Returns
        (x, cache)."""
        ks_out, vs_out = [], []
        for i in range(self.config.n_layer):
            layer = jax.tree.map(lambda a: a[i], params["blocks"])
            x, (kc, vc) = self._block_core(x, layer, step.layer(i))
            ks_out.append(kc)
            vs_out.append(vc)
        return x, {"k": ks_out, "v": vs_out}

    def apply_paged_prefill(self, params, input_ids, cache, token_blocks,
                            token_offsets, length):
        """Prefill ONE sequence into the paged cache.

        input_ids: (1, T_pad) right-padded prompt; token_blocks/
        token_offsets: (T_pad,) destination block / in-block slot per
        position (pads point at scratch block 0); length: scalar true
        prompt length. Returns (logits (1, V) at position length-1, cache).

        This is the chunk program at ``start = 0``: tokens are laid
        contiguously from position 0, so position m*BS's destination
        block IS table entry m (pads are scratch 0) and the prompt's
        table is read off its per-token placement.
        """
        BS = cache["k"][0].shape[2]
        return self.apply_paged_chunk(
            params, input_ids, cache, token_blocks, token_offsets,
            jnp.int32(0), length, token_blocks[::BS])

    def apply_paged_chunk(self, params, input_ids, cache, token_blocks,
                          token_offsets, start, true_len, table):
        """Prefill ONE CHUNK of one sequence into the paged cache (the
        Dynamic SplitFuse chunk program; see Llama.apply_paged_chunk —
        same contract, GPT-2's learned positions and full-head cache;
        kernel or dense gather is models/paged.py ``chunk_step``'s)."""
        cfg = self.config
        C = input_ids.shape[1]
        pos = jnp.minimum(start + jnp.arange(C), cfg.max_seq_len - 1)
        x = (params["wte"][input_ids]
             + params["wpe"][pos][None]).astype(_dtype(cfg))
        x, cache = self._paged_layers(params, x, paged.chunk_step(
            paged.geometry(self), cache, token_blocks, token_offsets,
            start, true_len, table))
        last = jnp.take_along_axis(
            x, jnp.maximum(true_len - 1, 0)[None, None, None], axis=1)
        return self.head(params, last)[:, 0], cache

    def apply_paged_decode(self, params, tokens, lengths, cache,
                           block_tables):
        """One decode step for a fixed-size batch over the paged cache.

        tokens: (B,) next input token per slot; lengths: (B,) tokens
        already in cache (the new token's position); block_tables:
        (B, MB) int32 block ids (inactive slots point at scratch block 0).
        Returns (logits (B, V), cache).
        """
        logits, cache = self.apply_paged_verify(
            params, tokens[:, None], lengths, cache, block_tables)
        return logits[:, 0], cache

    def apply_paged_verify(self, params, tokens, lengths, cache,
                           block_tables):
        """Speculative-verify step: C tokens per slot in ONE pass.

        tokens: (B, C) — per slot, the last committed token followed by
        the draft proposals; lengths: (B,) tokens already in cache (the
        first input token's position, i.e. ``seen_tokens - 1``);
        block_tables: (B, MB) as in decode (inactive slots all-scratch
        with lengths 0). Returns (logits (B, C, V), cache) — logits at
        EVERY position, so the host can take the longest accepted
        prefix plus the bonus token.

        Each slot's C-token span is a chunk with ``start=lengths[b]``/
        ``true_len=C`` (models/paged.py ``batch_step``; C = 1 is the
        decode step). Writes beyond a slot's committed frontier land in
        its already-allocated blocks and are either committed (accepted)
        or harmlessly overwritten next step (rejected) — callers
        guarantee every slot has k tokens of block budget left (the
        engine never speculates inside the tail).
        """
        cfg = self.config
        C = tokens.shape[1]
        pos = jnp.minimum(lengths[:, None] + jnp.arange(C)[None, :],
                          cfg.max_seq_len - 1)
        x = (params["wte"][tokens] + params["wpe"][pos]).astype(_dtype(cfg))
        x, cache = self._paged_layers(params, x, paged.batch_step(
            paged.geometry(self), cache, lengths, block_tables, C))
        return self.head(params, x), cache

    # --- loss ---
    def loss(self, params, batch, *, rng=None, train=True, seq_sharded=False,
             ltd_keep=None):
        """Next-token cross entropy. batch: {"input_ids": (B, T) int32}.
        ``ltd_keep``: random-LTD kept-token count for the middle layers
        (static; engine-scheduled — see runtime/engine.py)."""
        ids = batch["input_ids"]
        cfg = self.config
        T = ids.shape[1]
        chunk = cfg.loss_chunk
        if ltd_keep and train and not seq_sharded and ltd_keep < T:
            constrain = self._constrain_fn()
            act_spec = P(BATCH_AXES, None, None)
            x, aux = self._apply_ltd(params, ids, int(ltd_keep), rng=rng,
                                     train=train, constrain=constrain,
                                     act_spec=act_spec)
            if chunk and T - 1 > chunk:
                return self._chunked_head_loss(params, x[:, :-1],
                                               ids[:, 1:], chunk) \
                    + self.moe_loss_coeff * aux
            return next_token_xent(self.head(params, x), ids) \
                + self.moe_loss_coeff * aux
        if chunk and T - 1 > chunk and not seq_sharded:
            # chunked CE: never materialize the full (B, T, V) fp32 logits
            # (3.3 GB at B=16, T=1024, V=50k) — unembed + CE per sequence
            # chunk under remat, recomputed in backward
            x, aux = self.apply_with_aux(params, ids, rng=rng, train=train,
                                         seq_sharded=seq_sharded,
                                         return_hidden=True)
            return self._chunked_head_loss(params, x[:, :-1], ids[:, 1:],
                                           chunk) \
                + self.moe_loss_coeff * aux
        logits, aux = self.apply_with_aux(params, ids, rng=rng, train=train,
                                          seq_sharded=seq_sharded)
        return next_token_xent(logits, ids) + self.moe_loss_coeff * aux

    # head leaves the fused-CE d_params accumulator tracks (the subset
    # ``head`` reads; see common.fused_linear_xent)
    _head_keys = ("wte", "lnf_scale", "lnf_bias")

    def _chunked_head_loss(self, params, hidden, targets, chunk):
        """Dispatch the big-vocab head: fused grad-in-forward CE when
        cfg.fused_loss (optionally over the Pallas unembed/stats
        kernel), else the remat'd chunked path."""
        if self.config.fused_loss and self.config.fused_loss_kernel:
            from .common import fused_linear_xent_kernel

            def norm(np_, x):
                return self._ln(x, np_["lnf_scale"], np_["lnf_bias"])

            np_ = {k: params[k] for k in ("lnf_scale", "lnf_bias")}
            with jax.named_scope("dstpu.mm.unembed"):
                return fused_linear_xent_kernel(norm, chunk, np_,
                                                params["wte"], hidden,
                                                targets)
        if self.config.fused_loss:
            hp = {k: params[k] for k in self._head_keys}
            return fused_linear_xent(self.head, chunk, hp, hidden, targets)
        return chunked_softmax_xent(self.head, params, hidden, targets,
                                    chunk)



def _layernorm(x, scale, bias, eps=1e-5):
    from ..ops.pallas.layernorm import _ln_jnp
    return _ln_jnp(x, scale, bias, eps)


def _dropout(x, rate, rng):
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)
