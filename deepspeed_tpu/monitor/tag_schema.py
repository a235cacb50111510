"""Metric tag schema — the documented contract for every event the
production code writes into the MonitorMaster fan-out.

Every ``(tag, value, step)`` event emitted from ``deepspeed_tpu/``
must name a tag registered here, and every registered tag must be
emitted by production code — both directions are linted by
``tests/unit/test_telemetry.py`` (the test_fault_points_lint.py
discipline applied to metrics: a renamed emission site or a stale
registry entry cannot silently rot the schema dashboards are built on).

``SPAN_SCHEMA`` below is the same contract for the host spans the
serving path opens on the profiler's clock, ``SCOPE_SCHEMA`` for the
``jax.named_scope``s that name device operations, ``KERNEL_SCHEMA`` for the
names of the Pallas kernels among them.

This module deliberately holds NOTHING but the registry: the lint
collects emitted-tag literals by grepping the package with this file
excluded, so the registry's own keys never count as "emissions".

Tag grammar: ``<Domain>/<Group>/<name>`` with domain ``Train`` or
``Serve``; values are floats (host ids / tiers are reported as numeric
indices). Steps are the engine's global step (Train) or the completed-
request count (Serve).
"""

# tag -> one-line meaning (the README "Observability" table is
# generated from the same entries)
TAG_SCHEMA = {
    # --- per-step training samples (engine._write_monitor_events) ---
    "Train/Samples/lr":
        "learning rate applied at this step",
    "Train/Samples/train_loss":
        "loss of the most recent train_batch",
    "Train/Samples/loss_scale":
        "dynamic loss scale (fp16 runs only)",

    # --- checkpoint health (engine._write_ckpt_monitor_events) ---
    "Train/Checkpoint/save_latency_ms":
        "wall time of the most recent save_checkpoint",
    "Train/Checkpoint/load_latency_ms":
        "wall time of the most recent load_checkpoint",
    "Train/Checkpoint/retries":
        "cumulative shard-write retries (retry/degrade policy)",
    "Train/Checkpoint/fallbacks":
        "cumulative writer degradations (native->python, async->sync)",
    "Train/Checkpoint/save_errors":
        "cumulative saves that failed after retry+degrade",
    "Train/Checkpoint/load_fallbacks":
        "cumulative corrupt-generation fallbacks on load",
    "Train/Checkpoint/gc_removed":
        "cumulative tags removed by retention GC",
    "Train/Checkpoint/hot_pushes":
        "cumulative hot-tier replica pushes completed",
    "Train/Checkpoint/hot_push_errors":
        "cumulative advisory hot-tier push failures",
    "Train/Checkpoint/hot_restores":
        "cumulative loads served from in-memory replicas",
    "Train/Checkpoint/hot_fallbacks":
        "hot tier present but load degraded to durable",
    "Train/Checkpoint/durable_restores":
        "cumulative loads that read persistent storage",
    "Train/Checkpoint/replica_pushes":
        "cumulative cross-slice replica pushes (DCN peer writes + "
        "MiCS zero-replica registrations)",
    "Train/Checkpoint/replica_restores":
        "cumulative loads served by the cross-slice replica tier",
    "Train/Checkpoint/replica_fallbacks":
        "replica tier present but load degraded to durable",
    "Train/Checkpoint/reshape":
        "1 when this resume re-partitioned onto a new topology",

    # --- step analytics (monitor/telemetry.py, every interval_steps) ---
    "Train/Telemetry/step_time_ms_p50":
        "median per-step wall time over the interval (this host)",
    "Train/Telemetry/step_time_ms_p99":
        "p99 per-step wall time over the interval (this host)",
    "Train/Telemetry/tokens_per_sec_chip":
        "interval token throughput / participating chips",
    "Train/Telemetry/mfu_pct":
        "model-flops utilization: step FLOPs (XLA cost_analysis) "
        "/ step time / per-chip peak",
    "Train/Telemetry/collectives":
        "logical collectives in the compiled step program (an async "
        "start/done pair counts once; HLO parse)",
    "Train/Telemetry/exposed_comm_pct":
        "share of step collectives with no async start/done pair "
        "(comm the schedule left exposed)",
    "Train/Telemetry/goodput_pct":
        "productive share of wall time: 100 * (1 - ckpt/restore/"
        "reshape/restart overhead / elapsed)",

    # --- pipeline parallelism (telemetry._flush when a pipelined
    #     engine armed set_pipeline; engine.pipeline_report is the
    #     source) ---
    "Train/Pipeline/bubble_pct":
        "analytic executor bubble fraction of the active schedule "
        "(lock-step wall model, runtime/pipe/schedule.py)",
    "Train/Pipeline/steady_tick_ms":
        "mean step wall time / schedule tick count — the microbatch "
        "steady-state tick wall",
    "Train/Pipeline/offload_bytes_per_step":
        "D2H+H2D activation-ring payload host offload stages per step "
        "(0 = offload off) — the copy overhead the schedule must hide",

    # --- modeled-vs-measured reconciliation (telemetry._flush after a
    #     ProfilerControl capture; autotuning/reconcile.py is the
    #     source) ---
    "Train/Reconcile/wall_err_pct":
        "abs(modeled - measured) step wall / measured, pct — how far "
        "off-model the pod is running",
    "Train/Reconcile/top_drift_ms":
        "largest absolute modeled-vs-measured drift across planner "
        "_score terms (per step, ms)",
    "Train/Reconcile/top_drift_term":
        "index of the worst-drift term in planner.SCORE_TERMS "
        "(-1 = none)",
    "Train/Reconcile/coverage_pct":
        "share of measured device time the step decomposition "
        "attributed to a term",

    # --- pod-wide aggregation (rank 0 only; cluster_agg transports) ---
    "Train/Telemetry/cluster_step_ms_p50":
        "p50 of per-host mean step time across the pod",
    "Train/Telemetry/cluster_step_ms_p99":
        "p99 of per-host mean step time across the pod",
    "Train/Telemetry/straggler_delta_ms":
        "slowest host's mean step time minus the pod median",
    "Train/Telemetry/straggler_host":
        "index (ring order) of the slowest host",
    "Train/Telemetry/cluster_hosts":
        "hosts whose metrics reached this aggregation round",

    # --- serving (inference/v2 engine; step = completed requests) ---
    "Serve/Telemetry/ttft_ms_p50":
        "median time-to-first-token over the sample window",
    "Serve/Telemetry/ttft_ms_p99":
        "p99 time-to-first-token over the sample window",
    "Serve/Telemetry/tpot_ms_p50":
        "median time-per-output-token (dispatch-amortized)",
    "Serve/Telemetry/tpot_ms_p99":
        "p99 time-per-output-token (dispatch-amortized)",
    "Serve/Telemetry/completed":
        "requests completed since engine construction",
    "Serve/Telemetry/active":
        "sequences decoding when the window was emitted",
    "Serve/Telemetry/queue_ms_p50":
        "median wait from Router.put (or engine.put) to a batch slot, "
        "over the admissions of the sample window",
    "Serve/Telemetry/queue_ms_p90":
        "p90 of the same wait",
    "Serve/Telemetry/batch_occupancy_pct":
        "live slots / batch slots over the decode-bearing dispatches "
        "since engine construction",

    # --- prefix cache (inference/v2/prefix_cache.py radix tree;
    #     emitted only when the engine runs with prefix_cache on) ---
    "Serve/Telemetry/prefix_hit_rate_pct":
        "admissions whose prompt matched a cached prefix, pct of all "
        "admissions since engine construction",
    "Serve/Telemetry/cached_tokens_per_sec":
        "prompt tokens served from cached KV blocks (prefill skipped) "
        "per wall second since engine construction",
    "Serve/Telemetry/prefix_evictions":
        "cumulative cold tree blocks reclaimed by LRU eviction",
    "Serve/Telemetry/cow_copies":
        "cumulative copy-on-write block copies (partial-tail prefix "
        "hits that diverge inside a shared block)",

    # --- speculative decoding (inference/v2/speculative.py; emitted
    #     only once the engine has run a verify round) ---
    "Serve/Telemetry/spec_rounds":
        "cumulative speculative verify rounds since engine construction",
    "Serve/Telemetry/spec_acceptance_pct":
        "draft tokens accepted by greedy verification, pct of all "
        "proposed since engine construction",
    "Serve/Telemetry/spec_tokens_per_verify_step":
        "tokens committed per verify round (accepted prefix + bonus "
        "token; 1.0 would mean speculation is pure overhead)",

    # --- serving fleet router (inference/v2/router.py; step = completed
    #     router requests) ---
    "Serve/Router/shed":
        "cumulative requests rejected at admission or shed under "
        "overload (typed Overloaded, surfaced through get())",
    "Serve/Router/expired":
        "cumulative requests flushed at a deadline boundary (typed "
        "DeadlineExceeded; unref-without-insert, never served late)",
    "Serve/Router/replayed":
        "cumulative in-flight requests re-enqueued and replayed on a "
        "survivor after a replica death",
    "Serve/Router/failovers":
        "cumulative replica deaths the router recovered from",
    "Serve/Router/queue_depth":
        "router queue depth when the window was emitted",
    "Serve/Router/draining":
        "replicas in the draining state when the window was emitted",

    # --- disaggregated prefill/decode serving (router handoff path;
    #     emitted only when the fleet runs phase-specialized roles) ---
    "Serve/Router/handoffs":
        "cumulative prefill->decode KV handoffs completed",
    "Serve/Router/kv_stream_bytes":
        "cumulative KV wire bytes streamed across completed handoffs",
    "Serve/Router/kv_stream_ms":
        "cumulative wall time spent exporting/streaming/importing KV "
        "across completed handoffs",
    "Serve/Router/prefill_inflight":
        "requests in flight on prefill-role replicas when the window "
        "was emitted (per-role queue depth)",
    "Serve/Router/decode_inflight":
        "requests in flight on decode-role replicas when the window "
        "was emitted (per-role queue depth)",
}

# a mechanism that has a Pallas form and another, by the name its traced
# body notes its calls under (``ops/pallas/_common.py`` ``note_call``) -> the
# ``telemetry_snapshot()`` key of the share of its calls that took the kernel
KERNEL_SHARES = {"expert": "moe_kernel_share", "rule": "rule_kernel_share",
                 "latent_read": "latent_kernel_share"}
# a mechanism that is a Pallas kernel either way and whose pair counts a
# choice its operands' shapes make, by the name it notes its calls under in
# the same tally -> the word for the second count where the training engine
# says a traced step's tally (``runtime/engine.py`` ``_noting_calls``:
# "flash: 2 calls, 2 two-width", the calls whose values have a width of
# their own, ``ops/pallas/flash_attention.py``). No span or tag carries it.
SHAPE_PATHS = {"flash": "two-width"}
# what a program call's trace noted of them (``counting_calls``), as the
# dispatch and prefill spans say it
_TALLY_STATS = tuple(f"{name}{stat}" for name in KERNEL_SHARES
                     for stat in ("_calls", "_kernel_calls"))
# what ``models/paged.py``'s ``Account`` counts of a program call over the
# model's cache: of a bucketed prefill, and of a dispatch
_ACCOUNT_PREFILL_STATS = ("rule_rows", "index_keys", "attended_keys")
_ACCOUNT_STATS = ("grid_steps", "table_entries", "kernel_steps",
                  "chunk_grid_steps", "chunk_table_steps", "write_rows",
                  "write_rows_offered", "state_updates") \
    + _ACCOUNT_PREFILL_STATS

# span name -> the stats it carries and what it covers. Spans are
# ``monitor.telemetry.span(name, **stats)`` (a jax TraceAnnotation on the
# profiler's clock, free when no capture runs); both directions linted by
# tests/unit/test_serving_spans.py like the tags above. build / fetch /
# post are leaves that never nest in or overlap each other, so every idle
# gap of the device falls in at most one of them.
SPAN_SCHEMA = {
    "dstpu.router.step": {
        "stats": ("queued", "inflight"),
        "meaning": "one Router.step round; its own bookkeeping is this "
                   "span less the engine.step spans inside it"},
    "dstpu.engine.step": {
        "stats": ("pending", "active", "slots", "queue_p50_us",
                  "queue_p90_us", "admitted_total", "cache_bytes",
                  "live_tokens"),
        "meaning": "one InferenceEngineV2.step; carries the queue-wait "
                   "counters of ServingTelemetry to the trace's reader, "
                   "and cache_bytes = what the sequences in a slot hold "
                   "of the cache as the step begins (their blocks under "
                   "the block tables, every layer's, plus a slot's share "
                   "of whatever the model keeps by slot: window rings, "
                   "recurrent state) against live_tokens = the tokens "
                   "they have seen"},
    "dstpu.engine.admit": {
        "stats": ("uid", "prompt_tokens", "wait_us"),
        "meaning": "one request admitted: pool check passed -> queued "
                   "for chunks or through its bucketed prefill"},
    "dstpu.engine.prefill": {
        "stats": ("uid", "tokens", "padded") + _ACCOUNT_PREFILL_STATS
        + _TALLY_STATS,
        "meaning": "bucketed prefill of one request: arrays, program "
                   "call, blocking read of its token; "
                   + " / ".join(_ACCOUNT_PREFILL_STATS + _TALLY_STATS)
                   + " as on dstpu.engine.dispatch"},
    "dstpu.engine.dispatch": {
        "stats": ("kind", "active", "slots", "steps", "chunk_tokens",
                  "chunk_start", "chained", "late_steps") + _ACCOUNT_STATS
        + _TALLY_STATS,
        "meaning": "one decode-bearing or chunk program call (kind "
                   "decode | fused | chunk | spec | offload) from the "
                   "assembled batch to the last posted token; steps = the "
                   "decode steps it ran: decode_steps_per_dispatch of kind "
                   "decode, and of kind fused the engine's fixed count "
                   "beside a chunk (engine_v2._FUSED_STEPS; "
                   "ServingTelemetry's fused_dispatches counts those "
                   "dispatches) — of kind "
                   "decode, where plain decodes follow one another, the "
                   "tokens read and posted inside the span are those of "
                   "the dispatch BEFORE the one it enqueues (chained = 1: "
                   "the call went out while that one was unread, so the "
                   "device passes from one to the other with no host work "
                   "between; none are read under the first of a run, so "
                   "that span is a launch alone, and the last of a run is "
                   "read under dstpu.engine.settle); "
                   "late_steps = decode steps x slots which the dispatch "
                   "read under the span ran for sequences that an EOS in "
                   "the one before it had ended (an EOS is seen one "
                   "dispatch late; a budget never is); grid_steps "
                   "of table_entries = how much of the block table one "
                   "paged-decode kernel call visits, over the dispatch's "
                   "decode steps, and kernel_steps = the grid steps it "
                   "takes them in, several entries of a slot a step (a "
                   "grid step took one entry when grid_steps was named); "
                   "chunk_grid_steps of chunk_table_steps = the grid steps "
                   "one paged-chunk kernel call of the dispatch's chunk "
                   "takes (its work list's items x the blocks of KV heads: "
                   "runs of table entries that hold a key some query of "
                   "the tile attends) against query tiles x table entries, "
                   "the rectangle a call walked before it had a work list "
                   "(0 / 0 with no chunk); "
                   "write_rows of write_rows_offered = the "
                   "live rows (destination not scratch block 0) among "
                   "those one layer's KV writes are handed: slots x "
                   "steps, and a chunk's chunk_tokens of its C (0 / 0 on "
                   "a spec round); expert_kernel_calls of expert_calls = "
                   "the expert layer calls (MoE layers x steps) of the "
                   "dispatch's program whose products are a Pallas "
                   "grouped kernel, of all: noted when the program is "
                   "traced, so 0 / 0 on a dense model and on the dispatch "
                   "that traces it; state_updates = live slots x decode "
                   "steps x the layers that keep a recurrent state a slot "
                   "(a Mamba scan's, a delta rule's matrix): the one-token "
                   "updates the dispatch makes, and rule_rows = the rows "
                   "its chunk runs the scan or the chunkwise rule on, "
                   "padding included, x those layers (both 0 on a model "
                   "without such a layer); "
                   "rule_calls = the calls of the gated delta rule the "
                   "dispatch's program makes, chunkwise or one-token, "
                   "with a gate a head or a key channel "
                   "(linear layers x its chunk call and decode steps), "
                   "noted when the program is traced as the expert calls "
                   "are, so 0 on a model without the rule and on the "
                   "dispatch that traces it; "
                   "rule_kernel_calls = those of rule_calls that are a "
                   "Pallas kernel (ops/pallas/gated_delta_rule.py), the "
                   "rest the XLA form (a chunk whose gate is one a key "
                   "channel always is: solar_open2); "
                   "index_keys = the causal keys a learned indexer scores "
                   "for the span's real query tokens (a chunk's "
                   "chunk_tokens from position chunk_start, the "
                   "sequence's tokens before the chunk, 0 with no chunk; "
                   "a live slot's token a decode step), "
                   "position + 1 a query, x the layers that cache a "
                   "latent read through a per-query selection "
                   "(models/paged.py, LATENT), and attended_keys = the "
                   "keys those queries attend after the selection, "
                   "min(position + 1, index_topk) a query, x those layers: "
                   "the model's work whatever implements the read (both "
                   "0 on a model without such a layer); "
                   "latent_read_calls = the selected reads the dispatch's "
                   "program makes (those layers x its chunk and its "
                   "decode steps: 5 x (1 + 2) in a fused dispatch of five "
                   "such layers and two steps), noted when the program is "
                   "traced as the expert calls are, so 0 on a model "
                   "without such a layer and on the dispatch that traces "
                   "it, and "
                   "latent_read_kernel_calls = those of them whose read "
                   "after the selection is the Pallas kernel "
                   "(ops/pallas/latent_attention.py: a chunk's, where the "
                   "program runs kernels), the rest the XLA form (a "
                   "decode step's always)"},
    "dstpu.engine.build": {
        "stats": (),
        "meaning": "leaf: host work before a program call (decode "
                   "batch, numpy id/table/offset arrays, rng split)"},
    "dstpu.engine.fetch": {
        "stats": (),
        "meaning": "leaf: the program call and the blocking read of "
                   "tokens: its own, or under a chained decode dispatch "
                   "those of the dispatch before; under "
                   "dstpu.engine.settle the read alone"},
    "dstpu.engine.post": {
        "stats": (),
        "meaning": "leaf: the Python loop feeding the fetched tokens "
                   "to their sequences"},
    "dstpu.engine.settle": {
        "stats": (),
        "meaning": "the read (fetch) and posting (post) of a decode "
                   "dispatch that was enqueued and left unread, once "
                   "something other than a plain decode comes next or "
                   "nothing does: the last of a run of chained "
                   "dispatches. Inside dstpu.engine.step, except from "
                   "cancel, hold_decode and the KV handoff calls"},
}

# jax.named_scope name -> meaning. Scopes name the DEVICE operations of a
# part of a jitted program (they end up in each operation's op_name, which
# the profiler keeps as the event's ``tf_op``); they cost nothing at run
# time and add no host sync. Linted both ways like the spans above.
SCOPE_SCHEMA = {
    "dstpu.moe.route":
        "MoE layer: pre-FFN norm, router product, float32 softmax (or "
        "sigmoid scores and a correction bias: deepseek_v32, deepseek_v3, "
        "solar_open2), top-k, "
        "sort by expert, gather of the routed rows (and, expert-parallel, "
        "the all_to_all out)",
    "dstpu.moe.experts":
        "MoE layer: the three grouped expert products (gate, up, down) — "
        "lax.ragged_dot or the Pallas grouped kernel",
    "dstpu.moe.combine":
        "MoE layer: unsort, weight by the routing probabilities, sum over "
        "the k picks (and, expert-parallel, the all_to_all back)",
    "dstpu.moe.spill":
        "MoE layer, a held share walked a chunk at a time (moe/sharded_moe."
        "py:_held_walk): every chunk after the layer's first, forward and "
        "backward, with dstpu.moe.route / experts / combine inside it — "
        "device time here is held rows past the first chunk",
    "dstpu.ssm.mix":
        "state-space (Mamba) mixer: in-projection, causal conv, the "
        "x / dt projections, the selective scan (prefill) or its one-step "
        "update (decode), gate, out-projection, and the slot state's "
        "read and write",
    "dstpu.gmu":
        "Gated Memory Unit: gate projection, product with the memory "
        "layer's scan output, out-projection",
    "dstpu.gdn.mix":
        "delta-rule mixer of the two families that have one (olmo_hybrid: "
        "a gate a head; solar_open2, KDA: a gate a key channel, low-rank "
        "gates): q / k / v (/ z) and gate projections, causal conv, L2 "
        "norms, gates, the rule, the gated norm a head, out-projection, "
        "and the slot state's read and write",
    "dstpu.gdn.chunk":
        "inside dstpu.gdn.mix: the chunkwise-parallel rule of a prefill "
        "or chunk program, from the slot's state to the state after the "
        "last real token: the Pallas kernel that keeps the state in VMEM "
        "over the call's chunks (ops/pallas/gated_delta_rule.py) where the "
        "step runs kernels and the gate is one a head (olmo_hybrid), else "
        "ops/gated_delta_rule.py:chunk_rule, which is all a gate a key "
        "channel has (solar_open2): one scope, both families",
    "dstpu.gdn.step":
        "inside dstpu.gdn.mix: the rule's one-token update of a decode "
        "step: the Pallas kernel over the step's live slots, their state "
        "read once and written once in place, where the step runs "
        "kernels, else step_rule over every slot; either gate (a head, "
        "olmo_hybrid; a key channel, solar_open2)",
    "dstpu.attn.full":
        "a full-attention layer of olmo_hybrid or a GQA layer of "
        "solar_open2: the K/V write into its pool under the block table "
        "and the paged read (solar_open2: and the sigmoid output gate)",
    "dstpu.attn.latent":
        "a latent (MLA) layer's attention outside its weight products: "
        "rotary, the latent's norm, the write of the new rows into the "
        "latent pool, the read of the selected keys through the block "
        "table, scores, the running softmax and the value product, in "
        "the expanded form of a prompt's chunk (a block of latent rows "
        "to every head's key and value first) and the absorbed form of a "
        "decode step (the two per-head products round the read)",
    "dstpu.attn.index":
        "a latent layer's learned selection (DeepSeek sparse attention): "
        "rotary and LayerNorm of the index queries and key, the index "
        "scores of every causal key and, where the trace shows them "
        "under it, the search for each query's k-th largest",
    "dstpu.attn.mla":
        "a TRAINING latent (MLA) layer's attention outside its weight "
        "products (models/deepseek_v3.py): rotary on the queries and the "
        "one shared key, the assembly of 192-wide keys, the flash forward "
        "and backward (or the dense softmax off a TPU) and the padding of "
        "V to the key width and the slicing of the output around them; a "
        "backward or recomputed operation's tf_op carries the scope too",
    "dstpu.attn.diff":
        "differential attention outside the paged read: q/k/v "
        "projection, the query's padding to the head pair's width, "
        "A1 V - lambda A2 V, sub-norm, out-projection",
    "dstpu.attn.window":
        "a windowed layer's K/V write into its slot's ring and the "
        "paged read of at most the window's keys",
    "dstpu.attn.shared_kv":
        "the one full-length K/V: the full layer's write into its pool "
        "under the block table and its read, and every cross-decoder "
        "layer's read of that same pool",
    # --- the weight products: a dense weight against the step's rows. A
    #     fusion carries one tf_op, so what the compiler fuses in (a bias,
    #     a residual add, the next norm's statistics) rides under the
    #     name; the benchmark's metrics sum every dstpu.mm.* (pbench/
    #     weights.py). They nest inside dstpu.ssm.mix / attn.diff / gmu.
    "dstpu.mm.qkv":
        "the q / k / v projection of an attention layer (gpt2, llama, "
        "phi4flash; a cross-decoder layer's q alone), forward and, in a "
        "training step, its backward and recomputation",
    "dstpu.mm.attn_out":
        "an attention layer's output projection",
    "dstpu.mm.mlp":
        "a dense MLP's products (up / gate and down) and the activation "
        "between them; not the routed experts (dstpu.moe.experts)",
    "dstpu.mm.unembed":
        "the logits: hidden states against the (tied) embedding, in a "
        "training step the fused cross-entropy kernel with it",
    "dstpu.mm.in_proj":
        "Mamba mixer: the input projection to u and the gate z; gated "
        "delta-rule mixer: the q / k / v / z projection and the a / b "
        "gates' beside it (olmo_hybrid); the q / k / v projection, beta's "
        "and the two low-rank gates' pairs (solar_open2)",
    "dstpu.mm.x_proj":
        "Mamba mixer: the projection to dt's rank and B, C",
    "dstpu.mm.dt":
        "Mamba mixer: dt's rank up to the inner width",
    "dstpu.mm.out_proj":
        "Mamba or gated delta-rule mixer: the gated output back to the "
        "model width",
    "dstpu.mm.gmu":
        "Gated Memory Unit: its gate and its output projection",
    # --- what every family shares (PR 57): read by name, with no list of
    #     names, by perfbench/pbench/names.py
    "dstpu.attn.paged":
        "models/paged.py:_Step: the read of a KV / RING / SHARED layer's "
        "pools through the block table, whatever implements it (the paged "
        "decode or chunk kernel, or the dense gather off a TPU); a sibling "
        "of dstpu.kv.write inside a family's own dstpu.attn.* scope",
    "dstpu.kv.write":
        "models/paged.py:_Step: the write of a step's new K and V rows "
        "into the layer's donated pools (the write kernel, or the scatter)",
    "dstpu.step.prefill":
        "a serving program's bucketed prefill: the model's "
        "apply_paged_prefill and the sampling of its first token; the "
        "dstpu.step.* are the outermost scope of a serving program's "
        "operations and say the phase, not the layer",
    "dstpu.step.chunk":
        "a serving program's prompt chunk (the chunk of a fused or "
        "chunk-only dispatch, a draft model's catch-up chunk): "
        "apply_paged_chunk and the sampling after it",
    "dstpu.step.decode":
        "ONE decode step of a serving program (a plain dispatch has "
        "decode_steps_per_dispatch of them, a fused one _FUSED_STEPS, a "
        "draft's propose program spec_k + 1): apply_paged_decode and the "
        "sampling of its tokens",
    "dstpu.attn.flash":
        "a TRAINING attention layer of gpt2 / llama outside its weight "
        "products: rotary, the q / k / v layout, the flash forward and "
        "backward (or the ring, or the dense softmax off a TPU) and the "
        "output's layout; what dstpu.attn.mla is to deepseek_v3",
    "dstpu.optim.update":
        "the training step after its gradients (runtime/engine.py "
        "apply_update / finish_grads): unscale, the overflow check, the "
        "global norm and clip, the optimizer's update, the cast of the "
        "master weights back to the parameters' dtype",
}

# the name every ``pallas_call`` site under ``deepspeed_tpu/`` passes as
# ``name=`` -> what the kernel is. In jax 0.9.0 that is a ``named_scope``
# round the bind (so the name is in the operation's ``tf_op`` like any of
# SCOPE_SCHEMA) and the Mosaic call's ``kernel_name``. Linted three ways by
# tests/unit/test_serving_spans.py: every site carries a registered name,
# no two sites share one, every registered name is at a site.
_K = "dstpu.kernel."
KERNEL_SCHEMA = {
    _K + "flash_fwd": "flash attention forward, heads-major or standard",
    _K + "flash_fwd_t": "flash attention forward on (B, H, hd, T) operands",
    _K + "flash_bwd": "flash attention backward: dq, dk, dv (k-major)",
    _K + "flash_bwd_t": "flash backward on transposed operands (k-major)",
    _K + "flash_bwd_t_qmajor":
        "flash backward on transposed operands, one q-major pass",
    _K + "flash_block_fwd":
        "one KV block of ring attention: the running max / sum / "
        "accumulator carried over the rotation",
    _K + "fused_ce": "unembed + cross-entropy statistics of a training step",
    _K + "paged_decode": "paged decode attention over a step's work list",
    _K + "paged_chunk":
        "paged attention of a prompt chunk (or a bucketed prefill, or a "
        "verify pass) against the cache and its own causal prefix",
    _K + "kv_write": "a step's new K / V rows into the donated pools",
    _K + "swiglu_forward":
        "the routed experts' gate, up and down products in one forward call",
    _K + "gmm": "grouped matmul of sorted rows against per-expert weights",
    _K + "tgmm": "the grouped matmul's weight gradient",
    _K + "swiglu_up": "grouped gate and up products with the SwiGLU between",
    _K + "gmm_wq": "grouped matmul against int8 / int4 weights",
    _K + "swiglu_up_wq": "grouped gate / up against int8 / int4 weights",
    _K + "gdn_chunk":
        "the gated delta rule over a call's chunks, state kept in VMEM",
    _K + "gdn_step":
        "the gated delta rule's one-token update over the live slots",
    _K + "latent_read":
        "a chunk's read of the selected latent rows (MLA, expanded form)",
    _K + "ln_fwd": "LayerNorm forward",
    _K + "ln_bwd": "LayerNorm backward: dx, dscale, dbias",
    _K + "rms_fwd": "RMSNorm forward",
    _K + "mlp_mm": "the dense MLP's tiled matmul (any operand layout)",
    _K + "mlp_dw": "the dense MLP's weight gradient",
    _K + "mlp_mm_wq": "the dense MLP's matmul against int8 / int4 weights",
    _K + "quantize": "blockwise quantization to int8 with scales",
    _K + "dequantize": "blockwise dequantization",
    _K + "bsa_fwd": "block-sparse attention forward",
    _K + "bsa_bwd_dq": "block-sparse attention backward: dq",
    _K + "bsa_bwd_dkv": "block-sparse attention backward: dk, dv",
}
# the trace-time tally's word (KERNEL_SHARES, SHAPE_PATHS above: what
# ``note_call`` counts a call under) -> the kernels such a call runs. The
# tally counts calls of a traced body, the names label device operations.
KERNEL_TALLY = {
    "flash": tuple(_K + n for n in (
        "flash_fwd", "flash_fwd_t", "flash_bwd", "flash_bwd_t",
        "flash_bwd_t_qmajor")),
    "expert": tuple(_K + n for n in (
        "swiglu_forward", "gmm", "tgmm", "swiglu_up", "gmm_wq",
        "swiglu_up_wq")),
    "rule": (_K + "gdn_chunk", _K + "gdn_step"),
    "latent_read": (_K + "latent_read",),
}


def check_tag(tag):
    """Raise on a tag the schema does not document. This is the
    TEST-SIDE enforcement (the schema lint and unit tests call it);
    the production emit path (``TelemetryCollector._emit``) only
    warns on an undocumented tag — telemetry must never kill a run
    over a dashboard label."""
    if tag not in TAG_SCHEMA:
        raise KeyError(
            f"metric tag {tag!r} is not registered in "
            f"monitor/tag_schema.py TAG_SCHEMA — document it there "
            f"(and the lint in tests/unit/test_telemetry.py will hold "
            f"both directions)")
    return tag
