"""SPMD pipeline executor: collective-permute over the 'pipe' mesh axis.

The reference's pipeline engine (runtime/pipe/engine.py:56) is an imperative
instruction interpreter: per-rank processes walk a 1F1B instruction stream
(runtime/pipe/schedule.py:189) exchanging activations over NCCL p2p
(runtime/pipe/p2p.py:50,71). On TPU the same dataflow is ONE jitted SPMD
program:

  * the stacked layer dim of the model params is sharded over the 'pipe'
    mesh axis — each pipe shard owns L/S contiguous layers (the
    PipelineModule partitioning, reference runtime/pipe/module.py:372);
  * a ``shard_map`` manual only over 'pipe' (data/tensor/seq stay
    GSPMD-automatic, so the block's internal sharding constraints keep
    working) runs the rotation loop: at tick t, stage s computes microbatch
    t-s and ``ppermute``s its activation to stage s+1 — the p2p send/recv
    of the reference, but expressed as a collective XLA can schedule;
  * reverse-mode AD through the scan yields the backward pipeline (reverse
    ppermutes) automatically — the schedule the reference hand-codes.

The forward fills the pipe GPipe-style (all M microbatches in flight);
memory is bounded by rematerializing each block (``jax.checkpoint``), the
same trade the reference makes with activation checkpointing. The 1F1B
instruction stream in schedule.py documents/verifies the logical order for
parity tests; this executor is the compute path.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

def spmd_pipeline(block_fn, layers, x_mb, *, pipe_axis="pipe",
                  unroll_local=False):
    """Run ``x`` through all L layers, pipelined over the pipe axis.

    Args:
      block_fn: ``(x, layer_slice) -> x`` — one layer's forward. ``x`` is a
        single microbatch activation; ``layer_slice`` is the layers pytree
        with the leading layer dim removed (bundle rngs etc. into it).
      layers: pytree whose leaves have leading dim L (== S * layers_per_
        stage); sharded P(pipe_axis) on that dim by the caller's param specs.
      x_mb: microbatch-stacked input, leaves (M, ...) — replicated over the
        pipe axis, sharded however the caller likes on auto axes.
      pipe_axis: manual mesh axis name.
      unroll_local: unroll the per-stage layer scan (faster for tiny depth).

    Returns outputs with the same (M, ...) structure as ``x_mb``, replicated
    over the pipe axis.

    Must be called under an active mesh (``jax.set_mesh``) that has
    ``pipe_axis``. Total ticks = M + S - 1; per-stage bubble fraction
    (S-1)/(M+S-1) — choose M >= S (reference guidance for 1F1B too).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or pipe_axis not in mesh.shape:
        raise ValueError(f"spmd_pipeline needs an active mesh with a "
                         f"'{pipe_axis}' axis; got {mesh}")
    S = mesh.shape[pipe_axis]
    if S == 1:
        # degenerate: plain scan over layers, no collectives
        def body(c, layer):
            return block_fn(c, layer), None

        def run(x):
            y, _ = lax.scan(body, x, layers, unroll=unroll_local)
            return y
        return jax.vmap(run)(x_mb) if _leading(x_mb) else run(x_mb)

    M = _leading(x_mb)
    if M is None:
        raise ValueError("x_mb must have a leading microbatch dim")

    # XLA-CPU (the virtual test mesh) check-fails promoting partial-manual
    # sub-f32 all-reduces, so THERE activations cross the shard_map
    # boundary in f32. On TPU bf16 ppermute/psum are legal and halve the
    # boundary bytes — the workaround is scoped to the CPU interpreter.
    f32_boundary = jax.default_backend() == "cpu"

    def _is_lowp(x):
        return (jnp.issubdtype(x.dtype, jnp.floating)
                and jnp.finfo(x.dtype).bits < 32)
    in_dtypes = jax.tree.map(lambda x: x.dtype, x_mb)
    if f32_boundary:
        x_mb = jax.tree.map(
            lambda x: x.astype(jnp.float32) if _is_lowp(x) else x, x_mb)

    def stage_fn(layers_local, x_local):
        sid = lax.axis_index(pipe_axis)

        def run_local(state):
            def body(c, layer):
                return block_fn(c, layer), None
            y, _ = lax.scan(body, state, layers_local, unroll=unroll_local)
            return y

        def varying_zeros(x):
            # CPU: pcast in f32, cast after — the transpose of
            # pcast(to='varying') is a psum over 'pipe', and XLA-CPU
            # check-fails promoting a sub-f32 partial-manual all-reduce.
            # TPU: pcast in the native dtype (bf16 collectives are legal).
            if not f32_boundary:
                return lax.pcast(jnp.zeros(x.shape, x.dtype), (pipe_axis,),
                                 to="varying")
            z = lax.pcast(jnp.zeros(x.shape, jnp.float32), (pipe_axis,),
                          to="varying")
            return z.astype(x.dtype)

        state = jax.tree.map(lambda x: varying_zeros(x[0]), x_local)
        outputs = jax.tree.map(varying_zeros, x_local)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (clamped index; garbage ticks at
            # t >= M never reach the output buffer). The pipe-invariant
            # slice is promoted to pipe-varying EXPLICITLY, in f32, before
            # the dtype cast — otherwise shard_map's vma machinery inserts
            # the promotion inside the where in the compute dtype, and that
            # lowers to a sub-f32 all-reduce XLA-CPU cannot promote.
            inject = jax.tree.map(
                lambda x, dt: lax.pcast(
                    x[jnp.minimum(t, M - 1)], (pipe_axis,),
                    to="varying").astype(dt),
                x_local, in_dtypes)
            state = jax.tree.map(
                lambda i, s: jnp.where(sid == 0, i, s), inject, state)
            out = run_local(state)
            # last stage owns microbatch t-(S-1) at tick t
            idx = t - (S - 1)
            safe = jnp.clip(idx, 0, M - 1)
            valid = (sid == S - 1) & (idx >= 0)

            def write(buf, o):
                cur = lax.dynamic_index_in_dim(buf, safe, 0, keepdims=False)
                return lax.dynamic_update_index_in_dim(
                    buf, jnp.where(valid, o, cur), safe, 0)
            outputs = jax.tree.map(write, outputs, out)
            nxt = jax.tree.map(lambda o: lax.ppermute(o, pipe_axis, perm),
                               out)
            return (nxt, outputs), None

        (_, outputs), _ = lax.scan(tick, (state, outputs),
                                   jnp.arange(M + S - 1))

        # non-last stages hold zeros: psum broadcasts the result pipe-wide.
        # On the CPU test mesh sub-f32 floats go through f32 (XLA-CPU
        # check-fails promoting a partial-manual bf16 all-reduce); on TPU
        # the psum runs in the native dtype — half the boundary bytes.
        def bcast(o):
            if f32_boundary and jnp.issubdtype(o.dtype, jnp.floating) \
                    and jnp.finfo(o.dtype).bits < 32:
                return lax.psum(o.astype(jnp.float32),
                                pipe_axis).astype(o.dtype)
            return lax.psum(o, pipe_axis)
        return jax.tree.map(bcast, outputs)

    return jax.shard_map(
        stage_fn,
        in_specs=(P(pipe_axis), P()),
        out_specs=P(),
        axis_names={pipe_axis},
    )(layers, x_mb)


def _leading(tree):
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return None
    n = leaves[0].shape[0] if leaves[0].ndim else None
    return n


def split_microbatches(x, num_microbatches, batch_dim=0):
    """(B, ...) -> (M, B//M, ...) with stride-M row sampling so each
    microbatch draws evenly from every data-parallel shard of the batch dim
    (a contiguous split would put whole microbatches on single DP shards).
    Inverse: merge_microbatches."""
    M = num_microbatches
    B = x.shape[batch_dim]
    assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
    x = jnp.moveaxis(x, batch_dim, 0)
    x = x.reshape((B // M, M) + x.shape[1:])
    x = jnp.swapaxes(x, 0, 1)           # (M, B//M, ...)
    return x


def merge_microbatches(x, batch_dim=0):
    """Inverse of split_microbatches: (M, B//M, ...) -> (B, ...)."""
    x = jnp.swapaxes(x, 0, 1)
    x = x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
    return jnp.moveaxis(x, 0, batch_dim) if batch_dim else x


# ------------------------------------------------------------- 1F1B executor
def _ring_capacity(S):
    """Saved-input slots per stage under interleaved 1F1B: stage s holds a
    microbatch's input from its forward (tick m + s) until its backward
    (tick m + 2(S-1) - s) — at most 2(S-1) in flight, capacity 2S with
    slack. Independent of the microbatch count M: the memory property the
    whole schedule exists for."""
    return 2 * S


def pipeline_1f1b_grads(block_fn, head_loss_fn, layers_params, layers_aux,
                        head_params, x_mb, tgt_mb, *, pipe_axis="pipe"):
    """Interleaved-1F1B pipelined training pass: mean loss over M
    microbatches AND all gradients, in ONE jitted SPMD program.

    The reference executes 1F1B imperatively (_exec_schedule,
    runtime/pipe/engine.py:1382 walking schedule.py:189's TrainSchedule);
    here the same interleave is a lax.scan over ticks inside a shard_map
    manual on the pipe axis. Per tick every stage does one FORWARD step
    (microbatch t - s) and one BACKWARD step (microbatch t - 2(S-1) + s):
    the backward wave chases the forward wave S-1 ticks behind, so saved
    block inputs live in a fixed-size ring (``_ring_capacity``) rather
    than growing with M — unlike autodiff-of-the-GPipe-scan, which keeps
    every tick's residuals.

    Per-block backward recomputes the forward under ``jax.vjp`` from the
    ring-saved input (activation checkpointing, the reference's trade).
    The last stage seeds each microbatch's cotangent from
    ``head_loss_fn(head_params, y, tgt)`` the same tick it computes y.

    Args:
      block_fn: ``(x, layer_params_slice, layer_aux_slice) -> x``.
      head_loss_fn: ``(head_params, y_mb, tgt_mb) -> scalar`` per-mb loss.
      layers_params: differentiable stacked layers, leading dim L,
        sharded P(pipe_axis).
      layers_aux: non-differentiable per-layer inputs (rng key DATA,
        uint32 — wrap back with jax.random.wrap_key_data in block_fn),
        leading dim L, sharded P(pipe_axis).
      head_params / x_mb / tgt_mb: replicated over the pipe axis
        (x/tgt leaves lead with M).

    Returns (loss, (dlayers_params, dhead_params, dx_mb)).
    """
    mesh = jax.sharding.get_abstract_mesh()
    S = mesh.shape[pipe_axis]
    M = _leading(x_mb)
    R = _ring_capacity(S)
    n_ticks = M + 2 * (S - 1)
    f32_boundary = jax.default_backend() == "cpu"

    def _b(x):
        """Boundary-safe collective dtype (see spmd_pipeline)."""
        if f32_boundary and jnp.issubdtype(x.dtype, jnp.floating) \
                and jnp.finfo(x.dtype).bits < 32:
            return jnp.float32
        return x.dtype

    def stage_fn(lp, la, hp, x_mb, tgt_mb):
        sid = lax.axis_index(pipe_axis)
        # Promote head params to pipe-varying BEFORE any vjp against
        # them: differentiating w.r.t. a pipe-INVARIANT value inside
        # shard_map makes the transpose insert an implicit cross-stage
        # psum (the adjoint of the invariant->varying promotion), which
        # would multiply the masked-accumulate-then-psum pattern by S.
        hp = jax.tree.map(
            lambda p: lax.pcast(p, (pipe_axis,), to="varying"), hp)
        perm_f = [(i, (i + 1) % S) for i in range(S)]
        perm_b = [(i, (i - 1) % S) for i in range(S)]

        def fwd_local(x, lp):
            def body(c, sl):
                p, a = sl
                return block_fn(c, p, a), None
            y, _ = lax.scan(body, x, (lp, la))
            return y

        def vz(x, dt=None):
            z = lax.pcast(
                jnp.zeros(x.shape, _b(x)), (pipe_axis,), to="varying")
            return z.astype(dt or x.dtype)

        x0 = jax.tree.map(lambda x: x[0], x_mb)
        act0 = jax.tree.map(vz, x0)
        dy0 = jax.tree.map(vz, x0)
        ring0 = jax.tree.map(
            lambda x: jnp.tile(vz(x)[None], (R,) + (1,) * x.ndim), x0)
        gacc0 = jax.tree.map(lambda p: vz(p, jnp.float32), lp)
        hacc0 = jax.tree.map(
            lambda p: lax.pcast(jnp.zeros(p.shape, jnp.float32),
                                (pipe_axis,), to="varying"), hp)
        dx0 = jax.tree.map(
            lambda x: jnp.zeros((M,) + x.shape[1:], _b(x)), x_mb)
        dx0 = jax.tree.map(
            lambda x: lax.pcast(x, (pipe_axis,), to="varying"), dx0)
        loss0 = lax.pcast(jnp.zeros((), jnp.float32), (pipe_axis,),
                          to="varying")

        def tick(carry, t):
            act_in, dy_in, ring, gacc, hacc, dx_out, loss_acc = carry
            # ---------- forward half: stage s runs microbatch t - s
            f_idx = t - sid
            f_valid = (f_idx >= 0) & (f_idx < M)
            f_safe = jnp.clip(f_idx, 0, M - 1)
            # f_safe is pipe-varying (depends on sid), so indexing the
            # replicated x_mb already yields a varying value — no pcast
            inject = jax.tree.map(
                lambda x, a: x[f_safe].astype(a.dtype), x_mb, act_in)
            x_in = jax.tree.map(
                lambda i, a: jnp.where(sid == 0, i, a), inject, act_in)
            y = fwd_local(x_in, lp)
            slot = f_safe % R
            ring = jax.tree.map(
                lambda r, x: r.at[slot].set(
                    jnp.where(f_valid, x, r[slot])), ring, x_in)

            # last stage: per-microbatch loss + cotangent seed (cotangent
            # of the MEAN over M, hence the 1/M seed). Guarded by
            # lax.cond on the pipe-varying stage id — legal inside the
            # fully-manual shard_map (per-shard control flow, no
            # collectives in either branch) — so non-last stages skip
            # the d_model x vocab unembed fwd+vjp at runtime instead of
            # computing and masking it (S-fold redundant MXU work that
            # grows with vocab size).
            tgt = jax.tree.map(lambda x: x[f_safe], tgt_mb)
            seed = lax.pcast(jnp.float32(1.0 / M), (pipe_axis,),
                             to="varying")

            def head_branch(hp_, y_, tgt_, seed_):
                l_mb_, vjp_h = jax.vjp(
                    lambda h, yy: head_loss_fn(h, yy, tgt_), hp_, y_)
                dhp_, dy_ = vjp_h(seed_)
                return l_mb_, dhp_, dy_

            def skip_branch(hp_, y_, tgt_, seed_):
                # zeros must carry the same varying-over-pipe type as the
                # head branch's vjp outputs or cond rejects the branches
                zv = lambda a: lax.pcast(jnp.zeros(a.shape, a.dtype),
                                         (pipe_axis,), to="varying")
                return (zv(jnp.zeros((), jnp.float32)),
                        jax.tree.map(zv, hp_), jax.tree.map(zv, y_))

            l_mb, dhp, dy_seed = lax.cond(sid == S - 1, head_branch,
                                          skip_branch, hp, y, tgt, seed)
            seed_valid = f_valid & (sid == S - 1)
            loss_acc = loss_acc + jnp.where(seed_valid, l_mb, 0.0)
            hacc = jax.tree.map(
                lambda a, g: a + jnp.where(seed_valid,
                                           g.astype(jnp.float32), 0.0),
                hacc, dhp)

            # ---------- backward half: stage s runs microbatch
            # t - 2(S-1) + s; the last stage consumes its own seed
            b_idx = t - 2 * (S - 1) + sid
            b_valid = (b_idx >= 0) & (b_idx < M)
            b_safe = jnp.clip(b_idx, 0, M - 1)
            dy = jax.tree.map(
                lambda s_, d: jnp.where(sid == S - 1,
                                        s_.astype(d.dtype), d),
                dy_seed, dy_in)
            x_saved = jax.tree.map(lambda r: r[b_safe % R], ring)
            _, vjp_blk = jax.vjp(fwd_local, x_saved, lp)
            dx, dlp = vjp_blk(dy)
            gacc = jax.tree.map(
                lambda a, g: a + jnp.where(b_valid,
                                           g.astype(jnp.float32), 0.0),
                gacc, dlp)
            write_dx = (sid == 0) & b_valid
            dx_out = jax.tree.map(
                lambda buf, d: buf.at[b_safe].set(
                    jnp.where(write_dx, d.astype(buf.dtype),
                              buf[b_safe])),
                dx_out, dx)

            # rotations: activations forward, cotangents backward
            act_nxt = jax.tree.map(
                lambda o: lax.ppermute(
                    o.astype(_b(o)), pipe_axis, perm_f).astype(o.dtype), y)
            dy_nxt = jax.tree.map(
                lambda o: lax.ppermute(
                    o.astype(_b(o)), pipe_axis, perm_b).astype(o.dtype),
                dx)
            return (act_nxt, dy_nxt, ring, gacc, hacc, dx_out,
                    loss_acc), None

        carry = (act0, dy0, ring0, gacc0, hacc0, dx0, loss0)
        (act, dy, ring, gacc, hacc, dx_out, loss_acc), _ = lax.scan(
            tick, carry, jnp.arange(n_ticks))

        loss = lax.psum(loss_acc, pipe_axis) / M
        # layer grads stay stage-local (P(pipe) like the params); head/dx
        # live only on their owning stage -> psum broadcasts
        hgrads = jax.tree.map(lambda a: lax.psum(a, pipe_axis), hacc)
        dx_mb = jax.tree.map(lambda a: lax.psum(a, pipe_axis), dx_out)
        return loss, gacc, hgrads, dx_mb

    loss, gacc, hgrads, dx_mb = jax.shard_map(
        stage_fn,
        in_specs=(P(pipe_axis), P(pipe_axis), P(), P(), P()),
        out_specs=(P(), P(pipe_axis), P(), P()),
        axis_names={pipe_axis},
    )(layers_params, layers_aux, head_params, x_mb, tgt_mb)
    dlayers = jax.tree.map(lambda g, p: g.astype(p.dtype),
                           gacc, layers_params)
    dhead = jax.tree.map(lambda g, p: g.astype(p.dtype),
                         hgrads, head_params)
    dx_mb = jax.tree.map(lambda g, x: g.astype(x.dtype), dx_mb, x_mb)
    return loss, (dlayers, dhead, dx_mb)


# --------------------------------------------------- zero-bubble executor
#
# ZB-H1 (the W/B backward split) on top of the 1F1B rotation loop. Each
# block's backward splits into the activation-grad pass B (dx from dy —
# the only piece the previous stage is waiting on) and the weight-grad
# pass W (dW from the ring-saved input and dy — nothing downstream
# consumes it until the optimizer). 1F1B runs B and W fused on the
# backward wave, so every drain tick costs B+W while the forward slot
# idles; here each stage DEFERS its trailing ``zb_deferred_window``
# microbatches' W passes into exactly those forward-drain ticks. The
# index maps (shared with schedule.py's ZeroBubbleSchedule — the
# tick-parity test pins the two together):
#
#     F(m) on stage s  at tick m + s                       (fill wave)
#     B(m) on stage s  at tick m + 2(S-1) - s              (drain wave)
#     W(m) fused with B(m)          for m <  M - K_s
#     W(m) deferred    at tick m + 2(S-1)  (all stages!)   for m >= M - K_s
#
# with K_s = min(2(S-1) - s, M): stage s has exactly 2(S-1) - s ticks
# after its last F and the deferred W(m) wave lands s ticks after B(m) —
# always causally after its own B. Invalid slots are lax.cond no-ops
# (the 1F1B executor computes garbage forwards during the drain instead),
# so the lock-step wall — every tick costs the busiest stage, the
# ppermute is the barrier — drops below the GPipe figure:
# ``schedule.executor_bubble_fraction`` is the model, asserted by tests.
#
# Memory: the 1F1B input ring plus a dy ring of ``S`` slots (a deferred
# microbatch's cotangent lives the s ticks between its B and W) — still
# O(stages), never O(M). Cost of the split: B and W each rematerialize
# the block forward (two recomputes per microbatch instead of the fused
# pass's one) — the standard ZB trade under full activation
# checkpointing, bought back by the drain ticks it fills.
#
# Host offload (``offload=``): the input/dy rings are the activation
# carries the reference's ``swap_tensor`` + ``activation_checkpointing``
# layers spill; with offload on they are INITIALIZED in host memory
# (swap_tensor/host_stage.py) so the in-scan dynamic-update-slice
# writes stage D2H and the reads stage H2D (copy-start/copy-done pairs
# under the latency-hiding scheduler; overlap_report counts them). The
# next tick's B input is prefetched one tick early (``x_pref`` carry, a
# real double buffer); the last stage consumes its own same-tick
# forward input from registers, never through the host.


def zb_deferred_window(stage_id, micro_batches, stages):
    """K_s: how many trailing microbatches' W passes stage s defers into
    its forward-drain ticks. Polymorphic over python ints and traced
    values (the executor and the schedule spec share it)."""
    lo = 2 * (stages - 1) - stage_id
    if isinstance(stage_id, int):
        return min(lo, micro_batches)
    return jnp.minimum(lo, micro_batches)


def zb_f_index(t, stage_id, micro_batches, stages):
    """Microbatch whose FORWARD stage ``stage_id`` runs at tick t
    (valid iff in [0, M))."""
    return t - stage_id


def zb_b_index(t, stage_id, micro_batches, stages):
    """Microbatch whose activation-grad (B) pass runs at tick t."""
    return t - 2 * (stages - 1) + stage_id


def zb_w_deferred_index(t, stage_id, micro_batches, stages):
    """Microbatch whose DEFERRED weight-grad (W) pass runs at tick t —
    a uniform wave (independent of the stage: the per-stage deferral
    window exactly cancels the backward skew). Valid iff in
    [max(M - K_s, 0), M)."""
    return t - 2 * (stages - 1)


def zb_num_ticks(micro_batches, stages):
    """Same tick count as 1F1B: the last deferred W (microbatch M-1)
    lands on tick M - 1 + 2(S-1), the final tick."""
    return micro_batches + 2 * (stages - 1)


def pipeline_zb_grads(block_fn, head_loss_fn, layers_params, layers_aux,
                      head_params, x_mb, tgt_mb, *, pipe_axis="pipe",
                      offload=None):
    """Zero-bubble (ZB-H1) pipelined training pass: mean loss over M
    microbatches AND all gradients in ONE jitted SPMD program, with the
    backward W/B split filling the drain bubble (see the module-level
    schedule notes above). Signature and return match
    :func:`pipeline_1f1b_grads`; ``offload`` is an optional
    ``PipeOffload`` (host placement of the activation rings)."""
    mesh = jax.sharding.get_abstract_mesh()
    S = mesh.shape[pipe_axis]
    M = _leading(x_mb)
    R = _ring_capacity(S)
    n_ticks = zb_num_ticks(M, S)
    f32_boundary = jax.default_backend() == "cpu"

    off = offload if offload is not None else PipeOffload()
    if off.activations:
        from ..swap_tensor import host_stage
        to_host = host_stage.to_host
        to_device = host_stage.to_device
    else:
        to_host = to_device = lambda x: x

    def _b(x):
        if f32_boundary and jnp.issubdtype(x.dtype, jnp.floating) \
                and jnp.finfo(x.dtype).bits < 32:
            return jnp.float32
        return x.dtype

    def stage_fn(lp, la, hp, x_mb, tgt_mb):
        sid = lax.axis_index(pipe_axis)
        K = zb_deferred_window(sid, M, S)
        # see pipeline_1f1b_grads: differentiate only pipe-varying head
        # params or the transpose inserts a cross-stage psum per tick
        hp = jax.tree.map(
            lambda p: lax.pcast(p, (pipe_axis,), to="varying"), hp)
        perm_f = [(i, (i + 1) % S) for i in range(S)]
        perm_b = [(i, (i - 1) % S) for i in range(S)]

        def fwd_local(x, lp):
            def body(c, sl):
                p, a = sl
                return block_fn(c, p, a), None
            y, _ = lax.scan(body, x, (lp, la))
            return y

        def vz(x, dt=None):
            z = lax.pcast(
                jnp.zeros(x.shape, _b(x)), (pipe_axis,), to="varying")
            return z.astype(dt or x.dtype)

        x0 = jax.tree.map(lambda x: x[0], x_mb)
        act0 = jax.tree.map(vz, x0)
        dy0 = jax.tree.map(vz, x0)
        ring0 = jax.tree.map(
            lambda x: to_host(
                jnp.tile(vz(x)[None], (R,) + (1,) * x.ndim)), x0)
        # deferred cotangents live the s ticks between B(m) and W(m):
        # an S-slot ring (slot m % S) bounds them by stages, not M
        dyring0 = jax.tree.map(
            lambda x: to_host(
                jnp.tile(vz(x)[None], (S,) + (1,) * x.ndim)), x0)
        # prefetch buffer lives WITH the ring (host when offloading) so
        # the scan carry keeps one consistent memory space
        xpref0 = jax.tree.map(lambda x: to_host(vz(x)), x0)
        gacc0 = jax.tree.map(lambda p: vz(p, jnp.float32), lp)
        hacc0 = jax.tree.map(
            lambda p: lax.pcast(jnp.zeros(p.shape, jnp.float32),
                                (pipe_axis,), to="varying"), hp)
        dx0 = jax.tree.map(
            lambda x: jnp.zeros((M,) + x.shape[1:], _b(x)), x_mb)
        dx0 = jax.tree.map(
            lambda x: lax.pcast(x, (pipe_axis,), to="varying"), dx0)
        loss0 = lax.pcast(jnp.zeros((), jnp.float32), (pipe_axis,),
                          to="varying")

        def tick(carry, t):
            (act_in, dy_in, ring, dy_ring, x_pref, gacc, hacc, dx_out,
             loss_acc) = carry
            # ---------- F phase: stage s runs microbatch t - s; invalid
            # slots are cond no-ops (the drain tick's forward lane is
            # freed for the deferred W below, not burned on garbage)
            f_idx = zb_f_index(t, sid, M, S)
            f_valid = (f_idx >= 0) & (f_idx < M)
            f_safe = jnp.clip(f_idx, 0, M - 1)
            inject = jax.tree.map(
                lambda x, a: x[f_safe].astype(a.dtype), x_mb, act_in)
            x_in = jax.tree.map(
                lambda i, a: jnp.where(sid == 0, i, a), inject, act_in)

            def f_branch(x_, lp_):
                return fwd_local(x_, lp_)

            def f_skip(x_, lp_):
                return jax.tree.map(vz, x_)

            y = lax.cond(f_valid, f_branch, f_skip, x_in, lp)
            slot = f_safe % R
            ring = jax.tree.map(
                lambda r, x: r.at[slot].set(
                    jnp.where(f_valid, to_host(x), r[slot])), ring, x_in)

            # head: per-microbatch loss + 1/M cotangent seed, last stage
            # only AND only while it still has forwards (its B wave ends
            # with its F wave, so drain ticks skip the unembed entirely)
            tgt = jax.tree.map(lambda x: x[f_safe], tgt_mb)
            seed = lax.pcast(jnp.float32(1.0 / M), (pipe_axis,),
                             to="varying")

            def head_branch(hp_, y_, tgt_, seed_):
                l_mb_, vjp_h = jax.vjp(
                    lambda h, yy: head_loss_fn(h, yy, tgt_), hp_, y_)
                dhp_, dy_ = vjp_h(seed_)
                return l_mb_, dhp_, dy_

            def skip_branch(hp_, y_, tgt_, seed_):
                zv = lambda a: lax.pcast(jnp.zeros(a.shape, a.dtype),
                                         (pipe_axis,), to="varying")
                return (zv(jnp.zeros((), jnp.float32)),
                        jax.tree.map(zv, hp_), jax.tree.map(zv, y_))

            head_on = (sid == S - 1) & f_valid
            l_mb, dhp, dy_seed = lax.cond(head_on, head_branch,
                                          skip_branch, hp, y, tgt, seed)
            seed_valid = head_on
            loss_acc = loss_acc + jnp.where(seed_valid, l_mb, 0.0)
            hacc = jax.tree.map(
                lambda a, g: a + jnp.where(seed_valid,
                                           g.astype(jnp.float32), 0.0),
                hacc, dhp)

            # ---------- B phase: activation-grad only (dx via the
            # x-closure vjp — XLA's cone for dx alone, no dW work on the
            # wave the next stage is waiting on)
            b_idx = zb_b_index(t, sid, M, S)
            b_valid = (b_idx >= 0) & (b_idx < M)
            b_safe = jnp.clip(b_idx, 0, M - 1)
            dy = jax.tree.map(
                lambda s_, d: jnp.where(sid == S - 1,
                                        s_.astype(d.dtype), d),
                dy_seed, dy_in)
            # last stage: B(m) == F(m) same tick — its input is still in
            # registers; other stages use the one-tick-early prefetch
            # (double_buffer, the default) or fetch at use (A/B lever)
            if off.double_buffer:
                x_fetch = x_pref
            else:
                x_fetch = jax.tree.map(lambda r: r[b_safe % R], ring)
            x_for_b = jax.tree.map(
                lambda xi, xp: jnp.where(sid == S - 1, xi,
                                         to_device(xp).astype(xi.dtype)),
                x_in, x_fetch)

            def b_branch(x_, lp_, dy_):
                _, vjp_x = jax.vjp(lambda xx: fwd_local(xx, lp_), x_)
                (dx_,) = vjp_x(dy_)
                return dx_

            def b_skip(x_, lp_, dy_):
                return jax.tree.map(vz, x_)

            dx = lax.cond(b_valid, b_branch, b_skip, x_for_b, lp, dy)
            # stash the cotangent a DEFERRED microbatch's W will need
            defer_class = b_valid & (b_idx >= M - K)
            dslot = b_safe % S
            dy_ring = jax.tree.map(
                lambda r, d: r.at[dslot].set(
                    jnp.where(defer_class, to_host(d), r[dslot])),
                dy_ring, dy)
            write_dx = (sid == 0) & b_valid
            dx_out = jax.tree.map(
                lambda buf, d: buf.at[b_safe].set(
                    jnp.where(write_dx, d.astype(buf.dtype),
                              buf[b_safe])),
                dx_out, dx)

            # ---------- W phase: weight-grad pass — fused with B for
            # the early microbatches, the deferred wave for the last K_s
            w_idx = zb_w_deferred_index(t, sid, M, S)
            w_safe = jnp.clip(w_idx, 0, M - 1)
            w_def = (w_idx >= jnp.maximum(M - K, 0)) & (w_idx < M)
            w_fused = b_valid & (b_idx < M - K)
            x_w = jax.tree.map(
                lambda fb, r: jnp.where(
                    w_def, to_device(r[w_safe % R]).astype(fb.dtype), fb),
                x_for_b, ring)
            dy_w = jax.tree.map(
                lambda d, r: jnp.where(
                    w_def, to_device(r[w_safe % S]).astype(d.dtype), d),
                dy, dy_ring)

            def w_branch(x_, lp_, dy_):
                _, vjp_p = jax.vjp(lambda pp: fwd_local(x_, pp), lp_)
                (dlp_,) = vjp_p(dy_)
                return jax.tree.map(
                    lambda g: g.astype(jnp.float32), dlp_)

            def w_skip(x_, lp_, dy_):
                return jax.tree.map(lambda p: vz(p, jnp.float32), lp_)

            dlp = lax.cond(w_def | w_fused, w_branch, w_skip,
                           x_w, lp, dy_w)
            gacc = jax.tree.map(lambda a, g: a + g, gacc, dlp)

            # prefetch NEXT tick's B input out of the (host) ring — the
            # H2D copy gets a full tick of compute to hide under
            nb_safe = jnp.clip(zb_b_index(t + 1, sid, M, S), 0, M - 1)
            x_pref = jax.tree.map(lambda r: r[nb_safe % R], ring)

            act_nxt = jax.tree.map(
                lambda o: lax.ppermute(
                    o.astype(_b(o)), pipe_axis, perm_f).astype(o.dtype), y)
            dy_nxt = jax.tree.map(
                lambda o: lax.ppermute(
                    o.astype(_b(o)), pipe_axis, perm_b).astype(o.dtype),
                dx)
            return (act_nxt, dy_nxt, ring, dy_ring, x_pref, gacc, hacc,
                    dx_out, loss_acc), None

        carry = (act0, dy0, ring0, dyring0, xpref0, gacc0, hacc0, dx0,
                 loss0)
        (_, _, _, _, _, gacc, hacc, dx_out, loss_acc), _ = lax.scan(
            tick, carry, jnp.arange(n_ticks))

        loss = lax.psum(loss_acc, pipe_axis) / M
        hgrads = jax.tree.map(lambda a: lax.psum(a, pipe_axis), hacc)
        dx_mb = jax.tree.map(lambda a: lax.psum(a, pipe_axis), dx_out)
        return loss, gacc, hgrads, dx_mb

    loss, gacc, hgrads, dx_mb = jax.shard_map(
        stage_fn,
        in_specs=(P(pipe_axis), P(pipe_axis), P(), P(), P()),
        out_specs=(P(), P(pipe_axis), P(), P()),
        axis_names={pipe_axis},
    )(layers_params, layers_aux, head_params, x_mb, tgt_mb)
    dlayers = jax.tree.map(lambda g, p: g.astype(p.dtype),
                           gacc, layers_params)
    dhead = jax.tree.map(lambda g, p: g.astype(p.dtype),
                         hgrads, head_params)
    dx_mb = jax.tree.map(lambda g, x: g.astype(x.dtype), dx_mb, x_mb)
    return loss, (dlayers, dhead, dx_mb)


import functools as _functools
from typing import NamedTuple as _NamedTuple

import numpy as _np


class PipeOffload(_NamedTuple):
    """Host-offload knobs threaded through the custom_vjp wrappers
    (hashable — nondiff custom_vjp args must be). ``activations`` puts
    the executor's input/dy rings in host memory
    (swap_tensor/host_stage.py resolves the platform's memory kind;
    identity when the platform has a single memory space)."""
    activations: bool = False
    double_buffer: bool = True


def _grads_fn(schedule):
    if schedule == "zb":
        return pipeline_zb_grads
    if schedule == "1f1b":
        return pipeline_1f1b_grads
    raise ValueError(f"unknown steady-state pipeline schedule "
                     f"{schedule!r} (want '1f1b' or 'zb')")


@_functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def pipeline_loss(block_fn, head_loss_fn, pipe_axis, schedule, offload,
                  layers_params, layers_aux, head_params, x_mb, tgt_mb):
    """Differentiable wrapper over the steady-state executors: returns
    the mean microbatch loss; ``jax.grad`` through it yields the grads
    the pipelined pass already computed (stored as vjp residuals), so
    the engine's ordinary value_and_grad drives the schedule unchanged.
    ``schedule``: '1f1b' | 'zb'; ``offload``: PipeOffload or None."""
    kw = {"offload": offload} if schedule == "zb" else {}
    loss, _ = _grads_fn(schedule)(
        block_fn, head_loss_fn, layers_params, layers_aux, head_params,
        x_mb, tgt_mb, pipe_axis=pipe_axis, **kw)
    return loss


def _pl_fwd(block_fn, head_loss_fn, pipe_axis, schedule, offload,
            layers_params, layers_aux, head_params, x_mb, tgt_mb):
    kw = {"offload": offload} if schedule == "zb" else {}
    loss, (dl, dh, dx) = _grads_fn(schedule)(
        block_fn, head_loss_fn, layers_params, layers_aux, head_params,
        x_mb, tgt_mb, pipe_axis=pipe_axis, **kw)
    # the int-dtype primals ride along so the bwd rule can shape their
    # float0 cotangents
    return loss, (dl, dh, dx, layers_aux, tgt_mb)


def _pl_bwd(block_fn, head_loss_fn, pipe_axis, schedule, offload, res, g):
    dl, dh, dx, layers_aux, tgt_mb = res
    scale = lambda tr: jax.tree.map(lambda a: (a * g).astype(a.dtype), tr)
    f0 = lambda tr: jax.tree.map(
        lambda a: _np.zeros(a.shape, jax.dtypes.float0), tr)
    return (scale(dl), f0(layers_aux), scale(dh), scale(dx), f0(tgt_mb))


pipeline_loss.defvjp(_pl_fwd, _pl_bwd)


def pipeline_1f1b_loss(block_fn, head_loss_fn, pipe_axis, layers_params,
                       layers_aux, head_params, x_mb, tgt_mb):
    """Back-compat alias: the 1F1B schedule through the generic
    :func:`pipeline_loss` wrapper."""
    return pipeline_loss(block_fn, head_loss_fn, pipe_axis, "1f1b", None,
                         layers_params, layers_aux, head_params, x_mb,
                         tgt_mb)
