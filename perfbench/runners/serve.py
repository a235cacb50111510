"""Runner of the cells whose traffic file says ``"kind": "serve"``.

``InferenceEngineV2`` -> ``Replica`` -> ``Router``, the traffic file's
``engine`` and ``router`` groups passed through as they stand. One thread
plays the clients and turns the crank: it puts every request that is due,
calls ``Router.step()``, and reads what a client could read. The loop is
open: a request is due when the schedule says so, whatever the server is
doing, and every latency is taken from that due time.

Where a request's first token surfaces (README, "First token"): the pairs
``Router.step()`` returns never hold the token the prefill emits, and
``Router.get(uid, flush=False)`` is empty until the request is done. The
first moment a client can hold a token of the request is the return of the
``Router.step()`` after which its replica's ``engine.get(uid, flush=False)``
is non-empty; that return is ``t_first``.
"""

import time

import numpy as np

from pbench import common, traffic, trace as tracing

# An emitted token's float32 reference logit may sit below that position's
# reference maximum by at most this many standard deviations of the
# position's logits (chip_smoke.SERVE_GAP_TOL: random-init logits are
# near-flat, bf16 serving picks a near-tie now and then, measured worst
# 0.03 std on the v5e; a wrong cache row or mask lands several std down).
SERVE_GAP_TOL = 0.1


class Rec:
    __slots__ = ("due", "prompt", "max_new", "uid", "t_put", "t_first",
                 "t_done", "n_out", "tokens", "failed", "counted")

    def __init__(self, r):
        self.due, self.prompt = r["due_s"], r["prompt"]
        self.max_new = r["max_new_tokens"]
        self.uid = self.t_put = self.t_first = self.t_done = None
        self.tokens = None
        self.n_out = 0
        self.counted = 0        # prompt tokens a step has brought so far
        self.failed = None


def build(ctx):
    """-> (router, engine, sizes): the served model with seeded weights made
    on the device, the engine at its defaults except the sizes the traffic
    file fixes."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2, Router,
                                            RouterConfig)
    from deepspeed_tpu.inference.v2.replica import Replica

    builder = common.load_module("builders", ctx.cfg["builder"])
    model = builder.model(ctx.cfg)
    engine = InferenceEngineV2(model, dict(
        dtype="bfloat16", seed=ctx.seed, **ctx.job["engine"]))
    router = Router([Replica("r0", engine)],
                    RouterConfig(**ctx.job.get("router", {})))
    return router, engine, builder.sizes(ctx.cfg)


def progress(router, uid):
    """-> (prompt tokens the program has taken in, tokens a client could
    hold) of an unfinished request. The first is the host-side
    ``prefill_offset`` of the request's sequence (``inference/v2/ragged.py``),
    which a split-fuse chunk dispatch advances by its chunk and a bucketed
    prefill not before its token is readable; 0 while the request waits
    for a slot."""
    for rep in router.replicas:
        if uid in rep.inflight:
            try:
                seq = rep.engine.state_mgr.get_sequence(uid)
            except KeyError:        # queued in the engine, or just retired
                return 0, len(rep.engine.get(uid, flush=False))
            return seq.prefill_offset, len(seq.generated)
    return 0, 0


class Driver:
    """The client side of put/step/get, on a clock whose zero is the
    opening of the measured window."""

    def __init__(self, router):
        self.router = router
        self.live = {}          # uid -> Rec, put and not done
        # (t_start, t_end, pairs, kv_tokens_read, prompt tokens this step
        # brought: see count_prompt)
        self.steps = []
        # the PLAIN decode dispatch's steps (8). A FUSED dispatch, a chunk
        # beside running decodes, carries engine_v2._FUSED_STEPS = 2 since
        # PR 45, so kv_tokens_read over-counts where chunks fuse (cells 4,
        # 9, 10); only cell 3 reads it (paged_decode_roofline), and its
        # engine is bucketed and never fuses
        self.decode_steps = router.replicas[0].engine.config \
            .decode_steps_per_dispatch

    def put(self, rec, now):
        try:
            rec.uid = self.router.put(rec.prompt,
                                      max_new_tokens=rec.max_new,
                                      eos_token_id=-1)
        except Exception as e:  # noqa: BLE001 - refused: a failed request
            rec.failed = f"{type(e).__name__}: {e}"
            return
        rec.t_put = now
        self.live[rec.uid] = rec

    def step(self, clock):
        """One ``Router.step()`` and what a client reads after it."""
        # positions the decode kernel has to read in this dispatch, from
        # the client's own count of each request's tokens
        n = self.decode_steps
        kv = sum(n * (len(r.prompt) + r.n_out) + n * (n - 1) // 2
                 for r in self.live.values() if r.n_out)
        t0 = clock()
        with tracing.span("perfbench.router_step"):
            pairs = self.router.step()
        t = clock()
        brought = 0
        with tracing.span("perfbench.client_read"):
            for uid, _tok in pairs:
                rec = self.live.get(uid)
                if rec is not None:
                    rec.n_out += 1
            for uid, rec in list(self.live.items()):
                if self.router.is_done(uid):
                    try:
                        rec.tokens = np.asarray(self.router.get(uid))
                    except Exception as e:  # noqa: BLE001 - shed/expired
                        rec.failed = f"{type(e).__name__}: {e}"
                        rec.tokens = np.zeros((0,), np.int32)
                    rec.n_out = len(rec.tokens)
                    rec.t_done = t
                    if rec.t_first is None:
                        rec.t_first = t
                        brought += self.count_prompt(
                            rec, len(rec.prompt) + 1)
                    del self.live[uid]
                elif rec.t_first is None:
                    taken_in, seen = progress(self.router, uid)
                    if seen:
                        rec.t_first = t
                        rec.n_out = max(rec.n_out, seen)
                    brought += self.count_prompt(
                        rec, len(rec.prompt) + 1 if seen else taken_in)
        self.steps.append((t0, t, len(pairs), kv, brought))
        return t

    @staticmethod
    def count_prompt(rec, upto):
        """Prompt tokens of ``rec`` this step brought, its count now
        standing at ``upto``. A prompt's tokens count as the program takes
        them in: each chunk with the step that advanced the prefill past it
        (``upto`` = the sequence's ``prefill_offset``), and whatever is not
        yet counted when the first token becomes readable with that step,
        beside the +1 for the token the prefill emits (``upto`` =
        ``len(prompt) + 1``). So a request counts ``len(prompt) + 1`` over
        its life: a chunk at a time where its prefill is streamed
        (``splitfuse_tokens``), whole with its first token where it is one
        bucketed program."""
        new = max(0, upto - rec.counted)
        rec.counted += new
        return new

    def run_until(self, recs, clock, stop_s, on_tick=None):
        """Put each of ``recs`` when it is due and step the router until
        every one is done or the clock passes ``stop_s``."""
        i = 0
        while True:
            now = clock()
            while i < len(recs) and recs[i].due <= now:
                self.put(recs[i], clock())
                i += 1
            if on_tick is not None:
                on_tick(now)
            if now >= stop_s or (i >= len(recs) and not self.live):
                return
            if not self.router.has_work:
                nxt = recs[i].due if i < len(recs) else stop_s
                time.sleep(max(0.0, min(0.002, nxt - now)))
                continue
            self.step(clock)


def tokens_processed(steps, seconds):
    """Tokens the system processed inside the window [0, seconds): the
    decode pairs and the prompt tokens (``Driver.count_prompt``) of the
    steps that returned inside it. ``serve_tok_s`` is this over
    ``seconds``."""
    return sum(st[2] + st[4] for st in steps if 0.0 <= st[1] < seconds)


def warm_up(ctx, router, sizes, rng):
    """Every program the cell's traffic will use, and no other: the
    traffic file's ``warmup`` groups, each put together and stepped until
    its ``until`` ('first_token' or 'done')."""
    driver = Driver(router)
    clock = time.perf_counter
    for group in ctx.job["warmup"]:
        recs = [Rec({"due_s": 0.0, "max_new_tokens": group["max_new_tokens"],
                     "prompt": rng.integers(0, sizes["vocab_size"], n,
                                            dtype=np.int32)})
                for n in group["prompts"]]
        for r in recs:
            driver.put(r, clock())
        want = (lambda r: r.t_first is not None) \
            if group["until"] == "first_token" \
            else (lambda r: r.t_done is not None)
        while not all(want(r) or r.failed for r in recs):
            driver.step(clock)
    while driver.live:
        driver.step(clock)


def summarize(recs, seconds, limits, drain_s):
    """The client's numbers of one window. ``recs`` are all requests of
    the schedule; the measured ones were due in [0, seconds)."""
    measured = [r for r in recs if 0.0 <= r.due < seconds]
    done = [r for r in measured if r.t_done is not None and not r.failed
            and len(r.tokens) == r.max_new]
    failed = [r for r in measured if r.failed
              or (r.t_done is not None and len(r.tokens) != r.max_new)]
    unfinished = [r for r in measured if r.t_done is None and not r.failed]
    in_window = [r for r in recs if r.t_done is not None and not r.failed
                 and 0.0 <= r.t_done < seconds]
    miss_ms = 1e3 * (seconds + drain_s)
    ttft = [1e3 * (r.t_first - r.due) if r.t_first is not None else miss_ms
            for r in measured]
    tpot = [1e3 * (r.t_done - r.t_first) / (r.n_out - 1)
            for r in done if r.n_out > 1]
    met = sum(1 for r in done
              if 1e3 * (r.t_first - r.due) <= limits["ttft_ms"]
              and (r.n_out < 2 or 1e3 * (r.t_done - r.t_first)
                   / (r.n_out - 1) <= limits["tpot_ms"]))
    late = [1e3 * (r.t_put - r.due) for r in measured if r.t_put is not None]
    return {
        "measured": len(measured), "completed": len(done),
        "failed": len(failed), "unfinished": len(unfinished),
        "completed_in_window": len(in_window),
        "tokens_in_window": int(sum(len(r.prompt) + len(r.tokens)
                                    for r in in_window)),
        "generated_in_window": int(sum(len(r.tokens) for r in in_window)),
        "ttft_ms": ttft, "tpot_ms": tpot, "late_ms": late,
        "met_limits_share": met / max(1, len(measured)),
        "done": done,
    }


def check_against_reference(ctx, engine, sizes, done, rng):
    """A seeded sample of completed requests, teacher-forced through the
    plain float32 reference: every emitted token within SERVE_GAP_TOL."""
    import jax
    reference = common.load_module("references", ctx.cfg["reference"])
    T = sizes["max_seq_len"]
    n_pos = max(r.max_new for r in done)
    gaps = jax.jit(lambda p, ids, pos, toks: reference.token_gaps(
        p, ids, pos, toks, n_head=sizes["n_head"],
        activation=sizes["activation"]))
    pick = rng.choice(len(done), min(ctx.job["reference_sample"], len(done)),
                      replace=False)
    worst, argmax_share = 0.0, []
    for i in pick:
        r = done[int(i)]
        seq = np.concatenate([r.prompt, r.tokens])[:-1]
        ids = np.zeros((1, T), np.int32)     # causal: the padding after the
        ids[0, :len(seq)] = seq              # sequence is never seen
        pos = np.zeros((n_pos,), np.int32)
        toks = np.zeros((n_pos,), np.int32)
        pos[:r.max_new] = len(r.prompt) - 1 + np.arange(r.max_new)
        toks[:r.max_new] = r.tokens
        g = np.asarray(gaps(engine.params, ids, pos, toks))[:r.max_new]
        worst = max(worst, float(g.max()))
        argmax_share.append(float(np.mean(g == 0.0)))
    ctx.checks.require(
        worst <= SERVE_GAP_TOL,
        "sampled requests: every emitted token within 0.1 std of the "
        "float32 reference maximum", worst_gap_in_std=worst,
        limit_in_std=SERVE_GAP_TOL,
        reference_argmax_share=float(np.mean(argmax_share)),
        requests=len(pick))


def run_window(ctx, router, sizes, job, seconds):
    """One ramp, one measured window of ``seconds`` and the drain after it,
    under traffic ``job``. -> (requests, driver, marks); ``marks`` holds
    what was read as the window opened and closed, and the traced slice."""
    ramp_s, drain_s = job["ramp_s"], job["drain_s"]
    recs = [Rec(r) for r in traffic.schedule(
        job, ctx.seed, -ramp_s, seconds, sizes["vocab_size"])]
    driver = Driver(router)
    t_base = time.perf_counter() + ramp_s

    def wclock():
        return time.perf_counter() - t_base

    marks = {}
    capture = None

    def read_close():
        return (ctx.meter.count, len(driver.live),
                common.memory_peak_bytes(ctx.devices))

    def on_tick(now):
        nonlocal capture
        if "open" not in marks and now >= 0.0:
            marks["open"] = (ctx.clock.now(), ctx.meter.count,
                             len(driver.live))
        if "close" not in marks and now >= seconds:
            marks["close"] = read_close()
        if ctx.trace and "t0" not in marks \
                and now >= seconds * job["trace_after_share"]:
            capture = tracing.capture(ctx.trace_dir)
            capture.__enter__()
            marks["t0"] = wclock()
        if capture is not None and "t1" not in marks \
                and now >= marks["t0"] + job["trace_seconds"]:
            marks["t1"] = wclock()
            capture.__exit__(None, None, None)

    driver.run_until(recs, wclock, seconds + drain_s, on_tick)
    on_tick(wclock())
    if capture is not None and "t1" not in marks:
        marks["t1"] = wclock()
        capture.__exit__(None, None, None)
    marks.setdefault("close", read_close())  # drained before the end
    return recs, driver, marks


def run(ctx):
    job, clock, checks = ctx.job, ctx.clock, ctx.checks
    router, engine, sizes = build(ctx)
    common.say("engine", built_at_s=clock.now(), **ctx.meter.snapshot())
    warm_up(ctx, router, sizes, np.random.default_rng(ctx.seed))
    common.say("warm", at_s=clock.now(), **ctx.meter.snapshot())

    seconds, drain_s = ctx.seconds, job["drain_s"]
    recs, driver, marks = run_window(ctx, router, sizes, job, seconds)
    setup_s, compiles_open, live_open = marks["open"]
    compiles_close, live_close, (peak, memory) = marks["close"]
    common.say("memory", **memory)

    s = summarize(recs, seconds, job["limits"], drain_s)
    # starting and stopping the profiler stalls this thread for seconds:
    # what a traced run reads off the client's clock, it reads from the part
    # of the window before the capture began
    clean_s = marks.get("t0", seconds)
    clean = summarize(recs, clean_s, job["limits"], drain_s) \
        if ctx.trace else s
    if job["unfinished_counts_as"] == "failed":
        s["failed"] += s["unfinished"]
    common.say("window", **{k: v for k, v in s.items() if k not in (
        "ttft_ms", "tpot_ms", "late_ms", "done")},
        live_at_open=live_open, live_at_close=live_close, setup_s=setup_s,
        router_steps=len(driver.steps), offered_per_s=len(
            [r for r in recs if 0 <= r.due < seconds]) / seconds,
        unfinished_counts_as=job["unfinished_counts_as"])

    checks.require(compiles_close == compiles_open,
                   "no compilation inside the window",
                   programs=compiles_close - compiles_open)
    checks.require(s["failed"] == 0, "no request failed or was refused",
                   failed=s["failed"])
    checks.require(all(len(r.tokens) == r.max_new for r in recs
                       if r.t_done is not None and not r.failed),
                   "token counts exact")
    if checks.require(len(s["done"]) >= 1, "some request completed"):
        check_against_reference(ctx, engine, sizes, s["done"],
                                np.random.default_rng(ctx.seed + 1))

    processed = tokens_processed(driver.steps, seconds)
    in_trace = [st for st in driver.steps
                if "t1" in marks and st[0] >= marks["t0"]
                and st[1] <= marks["t1"]]
    window_steps = [st for st in driver.steps if 0.0 <= st[1] < clean_s]
    counters = {
        "window_s": seconds, "steps_in_window": len(window_steps),
        "pairs_in_window": sum(st[2] for st in window_steps),
        "late_ms": clean["late_ms"], "ttft_ms": clean["ttft_ms"],
        "tpot_ms": clean["tpot_ms"],
        "tokens_processed_in_window": processed,
        "traced_steps": len(in_trace),
        "traced_pairs": sum(st[2] for st in in_trace),
        "traced_prompt_tokens": sum(st[4] for st in in_trace),
        "traced_kv_tokens_read": sum(st[3] for st in in_trace),
        # requests whose prefill ended inside the traced slice: the chunk
        # and prefill kernels' work (edge effects at both ends cancel).
        # Whole prompts, unlike traced_prompt_tokens, which counts chunks
        "traced_prompts": [len(r.prompt) for r in recs
                           if r.t_first is not None and "t1" in marks
                           and marks["t0"] <= r.t_first <= marks["t1"]],
        **memory,
    }
    end_to_end = {
        "serve_tok_s": processed / seconds,
        "tpot_p90_ms": common.percentile(s["tpot_ms"], 90),
        "setup_s": setup_s,
    }
    if not ctx.rehearse:
        # the machine stands still now and then (~0.12 s, PERF.md section 5,
        # cell 3): a Router.step over five times the window's median one
        durations = sorted(st[1] - st[0] for st in window_steps)
        stalls = [d for d in durations
                  if d > 5.0 * durations[len(durations) // 2]]  # [] of []
        common.say("client", **end_to_end, router_step_stalls=len(stalls),
                   router_step_stalled_s=sum(stalls),
                   ttft_p50_ms=common.percentile(s["ttft_ms"], 50),
                   ttft_p90_ms=common.percentile(s["ttft_ms"], 90),
                   completed_tok_s=s["tokens_in_window"] / seconds,
                   tpot_p50_ms=common.percentile(s["tpot_ms"], 50),
                   late_p99_ms=common.percentile(s["late_ms"], 99),
                   met_limits_share=s["met_limits_share"])
    return {"attempted": s["completed"] + s["failed"], "failed": s["failed"],
            "end_to_end": end_to_end, "memory_peak_bytes": peak,
            "sizes": sizes, "counters": counters}
