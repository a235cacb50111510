"""GPT2Pipe — GPT-2 with pipeline parallelism over the 'pipe' mesh axis.

The reference expresses pipelined GPT-style models as a PipelineModule of
LayerSpecs interpreted by PipelineEngine (runtime/pipe/module.py:87,
engine.py:56). Here the pipeline is *inside* the model's forward: the
stacked block params shard over 'pipe' (each stage owns n_layer/S layers)
and spmd_pipeline (runtime/pipe/spmd.py) rotates microbatch activations
through the stages with ppermute. Embedding and the LM head run outside the
pipelined region, replicated over 'pipe' — their grads psum across stages
automatically, which is exactly the reference's tied-weight allreduce
(pipe/engine.py:260 _exec_reduce_tied_grads) in declarative form.

Composes with the rest of the mesh: batch stays sharded over data/expert,
Megatron TP over 'tensor', and ZeRO partitioning applies on top of the
'pipe'-sharded layer dim (the reference needs a dedicated PipelineEngine +
grid for this; here it is the same DeepSpeedEngine).
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..runtime.pipe.spmd import (spmd_pipeline, split_microbatches,
                                 merge_microbatches)
from ..utils.groups import BATCH_AXES
from .gpt2 import GPT2


class GPT2Pipe(GPT2):
    """Same params / math / init as GPT2; pipelined forward when the active
    mesh has pipe > 1 (falls back to the dense scan otherwise, so one model
    object serves any topology)."""

    def __init__(self, config):
        if config.attn_layer_windows:
            # the pipelined executors do not thread the per-layer window
            # operand; refuse loudly rather than silently attend globally
            raise ValueError(
                "attn_layer_windows (gpt-neo local attention) is not "
                "supported by the pipelined executor")
        super().__init__(config)

    def partition_specs(self, topology=None):
        specs = super().partition_specs(topology)
        pipe = 1
        if topology is not None:
            pipe = topology.get_pipe_parallel_world_size()
        if pipe <= 1:
            return specs
        if self.config.n_layer % pipe:
            raise ValueError(
                f"n_layer {self.config.n_layer} not divisible by pipeline "
                f"stages {pipe}")
        blocks = {k: P(*(("pipe",) + tuple(s)[1:]))
                  for k, s in specs["blocks"].items()}
        specs = dict(specs)
        specs["blocks"] = blocks
        return specs

    def _pipe_size(self):
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty or "pipe" not in mesh.shape:
            return 1
        return mesh.shape["pipe"]

    def _resolved_pipe(self, S):
        """(schedule, microbatches, offload) for this trace: the
        engine-installed ``_pipe_cfg`` (runtime/config.py
        PipelineConfig, resolved) wins where set; the model-config
        knobs are the no-engine fallback."""
        from ..runtime.pipe.spmd import PipeOffload
        cfg = self.config
        pc = getattr(self, "_pipe_cfg", None)
        schedule = (getattr(pc, "schedule", None)
                    or cfg.pipe_schedule)
        M = (getattr(pc, "micro_batches", 0)
             or cfg.pipe_microbatches or 2 * S)
        offload = PipeOffload(
            activations=bool(getattr(pc, "offload_activations", False)),
            double_buffer=bool(getattr(pc, "offload_double_buffer",
                                       True)))
        return schedule, M, offload

    def apply_with_aux(self, params, input_ids, *, rng=None, train=False,
                       seq_sharded=False, return_hidden=False):
        S = self._pipe_size()
        if S == 1:
            return super().apply_with_aux(params, input_ids, rng=rng,
                                          train=train,
                                          seq_sharded=seq_sharded,
                                          return_hidden=return_hidden)
        cfg = self.config
        if cfg.attention_backend == "ring":
            raise NotImplementedError(
                "ring attention inside the pipelined region (nested "
                "shard_map) is not supported; use Ulysses (dense) with pipe")
        if cfg.use_flash_attention is True:
            # explicit force only: "auto" resolves to the dense path
            # inside the pipelined region (pallas_call under a
            # partial-manual shard_map is not supported)
            raise NotImplementedError(
                "flash attention inside the pipelined region is not "
                "supported yet (pallas_call under a partial-manual "
                "shard_map); use the dense backend with pipe")
        B, T = input_ids.shape
        _, M, offload = self._resolved_pipe(S)
        if B % M:
            raise ValueError(f"batch {B} not divisible by "
                             f"pipe_microbatches {M}")

        act_spec = P(BATCH_AXES, "seq" if seq_sharded else None, None)
        mb_act_spec = P(None, BATCH_AXES, "seq" if seq_sharded else None,
                        None)
        constrain = lax.with_sharding_constraint

        # --- embedding (outside the pipe; replicated over 'pipe') ---
        x = self.embed(params, input_ids, rng=rng, train=train,
                       constrain=constrain, act_spec=act_spec)

        # --- pipelined blocks ---
        causal = jnp.tril(jnp.ones((T, T), jnp.bool_))

        if cfg.remat and cfg.remat_policy == "split_attn":
            # same split-boundary structure as GPT2.apply_with_aux: the
            # pre (ln1+qkv) and post (wo/ln2/MLP) segments remat, the
            # attention custom_vjp sits OUTSIDE any checkpoint so its
            # forward kernel is never re-run in backward
            from functools import partial

            def block_fn(x, layer_and_rng):
                layer, lrng = layer_and_rng
                pre = jax.checkpoint(partial(
                    self.block_qkv, constrain=constrain, act_spec=act_spec))
                q, kk, v = pre(x, layer)
                attn = self.block_attn(q, kk, v, causal=causal,
                                       constrain=constrain,
                                       seq_sharded=seq_sharded)
                post = jax.checkpoint(partial(
                    self.block_post, constrain=constrain,
                    act_spec=act_spec, seq_sharded=seq_sharded,
                    train=train))
                y, _aux = post(x, attn, layer, lrng)
                return y
        else:
            def block_fn(x, layer_and_rng):
                layer, lrng = layer_and_rng
                y, _aux = self.block_forward(
                    x, layer, lrng, causal=causal, constrain=constrain,
                    act_spec=act_spec, seq_sharded=seq_sharded, train=train)
                return y

            if cfg.remat:
                from .common import resolve_remat_policy
                policy = resolve_remat_policy(cfg.remat_policy)
                if offload.activations:
                    # GPipe keeps every in-flight microbatch's residuals
                    # live for autodiff — with offload on, save them
                    # into host memory instead of recomputing (the
                    # reference's cpu_checkpointing; swap_tensor tier)
                    from ..runtime.activation_checkpointing import (
                        checkpointing as ckpt)
                    policy = ckpt.offload_policy() or policy
                block_fn = jax.checkpoint(block_fn, policy=policy)

        layer_rngs = jax.random.split(
            rng if rng is not None else jax.random.key(0), cfg.n_layer)

        x_mb = split_microbatches(x, M)
        x_mb = constrain(x_mb, mb_act_spec)
        out_mb = spmd_pipeline(block_fn, (params["blocks"], layer_rngs),
                               x_mb)
        x = merge_microbatches(out_mb)
        x = constrain(x, act_spec)

        # --- head (outside the pipe) ---
        if return_hidden:
            return x, jnp.zeros((), jnp.float32)
        return self.head(params, x), jnp.zeros((), jnp.float32)

    def loss(self, params, batch, *, rng=None, train=True,
             seq_sharded=False):
        """Steady-state pipelined training loss when the resolved
        schedule is '1f1b' or 'zb' and the mesh pipelines: the
        interleaved executor computes loss AND grads in one pass with
        O(stages) live activations (pipeline_1f1b_grads /
        pipeline_zb_grads — the latter splits each backward into B/W
        passes so weight-grad work fills the drain ticks, optionally
        with the activation rings host-offloaded). Identical loss value
        to the GPipe path — parity-tested."""
        cfg = self.config
        S = self._pipe_size()
        schedule, M, offload = self._resolved_pipe(S)
        if S == 1 or schedule not in ("1f1b", "zb"):
            return super().loss(params, batch, rng=rng, train=train,
                                seq_sharded=seq_sharded)
        if cfg.use_flash_attention is True \
                or cfg.attention_backend == "ring":
            raise NotImplementedError(
                "flash/ring attention inside the pipelined region is not "
                "supported; use the dense backend with pipe")
        if getattr(self, "moe_loss_coeff", 0.0):
            # the 1F1B executor's block_fn drops per-block aux outputs —
            # silently losing the MoE load-balance loss; mirror the
            # explicit flash/ring errors rather than training wrong
            raise NotImplementedError(
                "MoE aux (load-balance) losses are not threaded through "
                "the 1f1b/zb schedules; use the GPipe schedule for MoE "
                "pipeline models")
        from ..runtime.pipe.spmd import pipeline_loss
        from .common import (chunked_softmax_xent, next_token_xent,
                             resolve_remat_policy)

        ids = batch["input_ids"]
        B, T = ids.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by "
                             f"pipe_microbatches {M}")
        act_spec = P(BATCH_AXES, "seq" if seq_sharded else None, None)
        constrain = lax.with_sharding_constraint
        x = self.embed(params, ids, rng=rng, train=train,
                       constrain=constrain, act_spec=act_spec)
        causal = jnp.tril(jnp.ones((T, T), jnp.bool_))

        if cfg.remat and cfg.remat_policy == "split_attn":
            # same split-boundary structure as apply_with_aux: pre/post
            # segments remat, attention sits outside any checkpoint
            from functools import partial

            def block_fn(x, layer, key_data):
                lrng = jax.random.wrap_key_data(key_data)
                pre = jax.checkpoint(partial(
                    self.block_qkv, constrain=constrain,
                    act_spec=act_spec))
                q, kk, v = pre(x, layer)
                attn = self.block_attn(q, kk, v, causal=causal,
                                       constrain=constrain,
                                       seq_sharded=seq_sharded)
                post = jax.checkpoint(partial(
                    self.block_post, constrain=constrain,
                    act_spec=act_spec, seq_sharded=seq_sharded,
                    train=train))
                y, _aux = post(x, attn, layer, lrng)
                return y
        else:
            def block_fn(x, layer, key_data):
                lrng = jax.random.wrap_key_data(key_data)
                y, _aux = self.block_forward(
                    x, layer, lrng, causal=causal, constrain=constrain,
                    act_spec=act_spec, seq_sharded=seq_sharded,
                    train=train)
                return y

            if cfg.remat:
                block_fn = jax.checkpoint(
                    block_fn,
                    policy=resolve_remat_policy(cfg.remat_policy))

        layer_rngs = jax.random.key_data(jax.random.split(
            rng if rng is not None else jax.random.key(0), cfg.n_layer))

        def head_loss(hp, y, tgt):
            # honors loss_chunk like the dense/GPipe path: never
            # materialize the full per-microbatch (b, T, V) fp32 logits
            if cfg.loss_chunk and y.shape[1] - 1 > cfg.loss_chunk:
                return chunked_softmax_xent(
                    self.head, hp, y[:, :-1], tgt[:, 1:], cfg.loss_chunk)
            return next_token_xent(self.head(hp, y), tgt)

        head_params = {"wte": params["wte"],
                       "lnf_scale": params["lnf_scale"],
                       "lnf_bias": params["lnf_bias"]}
        x_mb = split_microbatches(x, M)
        ids_mb = split_microbatches(ids, M)
        return pipeline_loss(
            block_fn, head_loss, "pipe", schedule,
            offload if schedule == "zb" else None,
            params["blocks"], layer_rngs, head_params, x_mb, ids_mb)
