"""Runner of the cells whose job file says ``"kind": "train"``.

``deepspeed_tpu.initialize`` on the configuration's model, the job file's
engine config passed through as it stands; a new batch of uniform random
token ids from a seeded host generator every step; steps counted over a
window that a ``block_until_ready`` closes.
"""

import collections
import copy
import time

import numpy as np

from pbench import common, trace as tracing

# The kernel path (flash, fused CE) and the plain float32 reference round in
# different places: measured on the v5e they part by ~1e-4 at a loss of 11
# (PERF.md, PR 21); a wrong mask or scale moves the loss by tenths.
LOSS_TOL = 0.02


def held_share(tree, devices):
    """Largest share of ``tree``'s bytes that one device holds: 1/n when
    ZeRO partitions it, 1.0 when replicated (copied from chip_smoke)."""
    import jax
    held = dict.fromkeys(devices, 0)
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.nbytes
        for s in leaf.addressable_shards:
            held[s.device] += s.data.nbytes
    return max(held.values()) / total


def run(ctx):
    import jax
    import deepspeed_tpu

    job, cfg, clock, checks = ctx.job, ctx.cfg, ctx.clock, ctx.checks
    builder = common.load_module("builders", cfg["builder"])
    reference = common.load_module("references", cfg["reference"])
    s = builder.sizes(cfg)
    n = len(ctx.devices)
    T = job["seq_len"]
    if T != s["max_seq_len"]:
        raise common.CheckFailed(
            f"job seq_len {T} is not the configuration's context "
            f"{s['max_seq_len']}")
    model = builder.model(cfg, **job["model_overrides"])

    engine_config = dict(job["engine_config"])
    engine_config["train_micro_batch_size_per_gpu"] = \
        job["micro_batch_per_chip"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, seed=ctx.seed, config=engine_config)
    rows = engine.config.train_batch_size
    if rows != job["micro_batch_per_chip"] * n \
            * engine_config.get("gradient_accumulation_steps", 1):
        raise common.CheckFailed(f"global batch {rows} is not micro x chips")
    tokens_per_step = rows * T
    common.say("engine", params=model.config.num_params(), global_batch=rows,
               seq_len=T, tokens_per_step=tokens_per_step, chips=n,
               built_at_s=clock.now(), **ctx.meter.snapshot())

    rng = np.random.default_rng(ctx.seed)

    def draw(generator):
        return {"input_ids": generator.integers(
            0, s["vocab_size"], (rows, T), dtype=np.int32)}

    def new_batch():
        with tracing.span("perfbench.batch"):
            return draw(rng)

    # ---- correct, part 1: the untrained weights through both paths
    first = new_batch()
    sample = {"input_ids": first["input_ids"][:n]}
    with jax.set_mesh(engine.mesh):
        kernel_loss = float(jax.jit(
            lambda p, b: model.loss(p, b, train=False))(
                engine.state["params"], sample))
    plain = jax.jit(lambda p, ids: reference.loss(
        p, ids, n_head=s["n_head"], activation=s["activation"]))
    plain_loss = float(np.mean([
        float(plain(engine.state["params"], sample["input_ids"][i:i + 1]))
        for i in range(n)]))
    checks.require(abs(kernel_loss - plain_loss) <= LOSS_TOL,
                   "untrained loss: kernel path within 0.02 of the plain "
                   "float32 reference", kernel=kernel_loss, plain=plain_loss,
                   rows=n)
    if n > 1:
        stage = engine_config["zero_optimization"]["stage"]
        share = {k: held_share(engine.state[k], ctx.devices)
                 for k in ("opt", "master", "params")}
        split = 1.2 / n     # a few bias leaves stay whole
        checks.require(
            share["opt"] <= split and share["master"] <= split
            and (share["params"] <= split) == (stage == 3),
            "every device holds 1/chips of the state its ZeRO stage "
            "partitions", **share)

    # ---- warm-up: the one step program (and whatever else the first
    # steps compile), counted as set-up
    losses = []
    batch = first
    for _ in range(job["warmup_steps"]):
        losses.append(engine.train_batch(batch))
        batch = new_batch()
    jax.block_until_ready(losses)
    # what of the traced steps only the model can say (a router's choices):
    # a builder that has such counters is shown every traced step's
    # parameters and batch before the step. Its programs compile here
    tally = None
    if ctx.trace and hasattr(builder, "traced_counters"):
        tally = builder.traced_counters(model, s)
        with jax.set_mesh(engine.mesh):
            tally.warm(engine.state["params"], batch)
    common.say("warm", at_s=clock.now(), **ctx.meter.snapshot())

    # ---- the window
    run_ahead = job["steps_in_flight"]
    trace_at = ctx.seconds * job["trace_after_share"]
    inflight = collections.deque()
    traced, traced_counters = None, {}
    compiles_before = ctx.meter.count
    setup_s = clock.now()
    t_open = time.perf_counter()

    def one_step():
        with tracing.span("perfbench.train_batch"):
            loss = engine.train_batch(new_batch())
        losses.append(loss)
        inflight.append(loss)
        if len(inflight) > run_ahead:
            with tracing.span("perfbench.wait_step"):
                jax.block_until_ready(inflight.popleft())

    steps = 0
    while time.perf_counter() - t_open < ctx.seconds:
        if ctx.trace and traced is None \
                and time.perf_counter() - t_open >= trace_at:
            jax.block_until_ready(losses[-1])
            inflight.clear()
            t_capture = time.perf_counter()
            if tally:
                # a step's routing is counted on the parameters it is about
                # to use, which it donates away: the first's now, before
                # the profiler opens; a later one's from a copy made on the
                # device between the traced steps, once the profiler has
                # closed. The batches are drawn ahead from a copy of the
                # generator: the window's own draws stay where they were.
                # All inside the capture's span, which the rate MFU reads
                # leaves out
                ahead = copy.deepcopy(rng)
                batches = [draw(ahead) for _ in range(job["trace_steps"])]
                with jax.set_mesh(engine.mesh):
                    tally.count(engine.state["params"], batches[0])
            with tracing.capture(ctx.trace_dir):
                for i in range(job["trace_steps"]):
                    if tally and i:
                        with jax.set_mesh(engine.mesh):
                            tally.keep(engine.state["params"], batches[i])
                    one_step()
                with tracing.span("perfbench.block_until_ready"):
                    jax.block_until_ready(losses[-1])
            if tally:
                with jax.set_mesh(engine.mesh):
                    traced_counters = tally.counters("the traced steps")
            capture_s = time.perf_counter() - t_capture
            steps += job["trace_steps"]
            traced = job["trace_steps"]
            continue
        one_step()
        steps += 1
    with tracing.span("perfbench.block_until_ready"):
        jax.block_until_ready(losses[-1])
    window_s = time.perf_counter() - t_open
    compiled_in_window = ctx.meter.count - compiles_before
    peak, memory = common.memory_peak_bytes(ctx.devices)
    common.say("memory", **memory)

    # ---- correct, part 2
    losses = [float(x) for x in losses]
    checks.require(np.isfinite(losses).all(), "every loss finite")
    checks.require(np.mean(losses[-5:]) <= losses[0],
                   "mean of the last five losses <= the first",
                   first=losses[0], last5=float(np.mean(losses[-5:])))
    checks.require(compiled_in_window == 0,
                   "no compilation inside the window",
                   programs=compiled_in_window)
    checks.require(steps >= job["min_steps"] or ctx.rehearse,
                   f"at least {job['min_steps']} steps in the window",
                   steps=steps)

    tok_s_chip = steps * tokens_per_step / window_s / n
    common.say("window", steps=steps, window_s=window_s, setup_s=setup_s,
               losses_first=losses[:3], losses_last=losses[-3:],
               train_tok_s_chip=None if ctx.rehearse else tok_s_chip)
    counters = {"steps": steps, "tokens_per_step": tokens_per_step,
                "tokens_traced": (traced or 0) * tokens_per_step,
                "steps_traced": traced or 0, "seq_len": T,
                "micro_batch_per_chip": job["micro_batch_per_chip"],
                "window_s": window_s, **memory}
    if traced:
        # starting and stopping the profiler stalls the host: the rate of
        # the steps outside the capture is what MFU is taken from
        counters["tok_s_chip_outside_capture"] = (
            (steps - traced) * tokens_per_step / (window_s - capture_s) / n)
        counters.update(traced_counters)
    return {"attempted": steps, "failed": 0,
            "end_to_end": {"train_tok_s_chip": tok_s_chip,
                           "setup_s": setup_s},
            "memory_peak_bytes": peak, "sizes": s, "counters": counters}
