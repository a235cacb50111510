"""InferenceEngineV2 — continuous-batching serving over a paged KV cache.

Counterpart of reference ``inference/v2/engine_v2.py:30 InferenceEngineV2``
(FastGen). TPU redesign:
  * The blocked KV cache is ONE device pytree {'k','v'}:
    (L, num_blocks, H_kv, block_size, hd), heads-major; per-sequence block
    tables index it (reference BlockedKVCache, kv_cache.py:40). Heads
    shard over 'tensor'.
  * Two compiled programs replace most of the ragged kernel zoo: a
    per-bucket prefill (one sequence, causal over its prompt, KV scattered
    into its blocks) and a fixed-shape decode (whole batch, one token
    each) whose attention is the Pallas paged kernel
    (ops/pallas/paged_attention.py) reading K/V straight through the
    block table — the blocked_flash role. Fixed shapes mean exactly two
    XLA compilations per bucket — the CUDA-graph-like property FastGen
    gets from its kernel design.
  * Scheduling (reference DSStateManager + the put/schedule loop in
    mii/ragged batching): admit pending requests while slots+blocks allow,
    prefill them, then batched decode steps; sequences retire on EOS or
    max_new_tokens and their blocks return to the free list immediately —
    the continuous-batching property. Plain decode dispatches are
    chained: the next one is enqueued before the host reads the last
    (``_plain_decode``), so the host's work between two of them is not on
    the device's path.
"""

import functools
import math
from collections import deque
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...utils import groups
from ...utils.groups import TopologyConfig
from ...utils.logging import log_dist
from ...monitor.tag_schema import KERNEL_SHARES
from ...monitor.telemetry import span
from ...ops.pallas.paged_attention import (as_pools, like_boundary,
                                           pool_block_dims)
from ..utils import shard_params
from .ragged import DSStateManager, RaggedBatchWrapper

# the programs a ``dstpu.engine.dispatch`` span of each kind calls, by the
# names ``_noting_calls`` keeps their counts under
_PROGRAMS_OF_KIND = {"decode": ("decode",), "offload": ("decode",),
                     "fused": ("fused",), "chunk": ("chunk",),
                     "spec": ("propose", "verify")}

# the decode steps a FUSED dispatch carries beside its chunk. Not the
# plain decode's ``decode_steps_per_dispatch``: that count amortises a
# launch and a read, and a fused dispatch is read at once either way, so
# here each further step is one more pass over every weight for the few
# rows that decode, and the count only sets the ratio in which the
# waiting prompt and the running answers share the chip. Timed at 1 / 2 /
# 4 / 8 and 2-or-4 by the live slots in the long-prompt cells (PERF.md
# section 6, PR 45)
_FUSED_STEPS = 2


@dataclass
class RaggedInferenceEngineConfig:
    """Reference config_v2.py RaggedInferenceEngineConfig (condensed)."""
    dtype: str = "bfloat16"
    tensor_parallel: int = 1
    # EP-sharded MoE serving (reference module_inject/layers.py EP+TP
    # inference MoE): experts shard over the 'expert' mesh axis in the
    # decode/prefill programs (Mixtral partition_specs put moe_w* on
    # ('expert', 'tensor'))
    expert_parallel: int = 1
    max_batch_size: int = 8          # concurrent sequences
    kv_block_size: int = 64
    num_kv_blocks: int = 0           # 0 = auto from max_seq_len * max_batch
    prompt_bucket: int = 64
    temperature: float = 0.0         # 0 = greedy
    top_k: int = 0
    seed: int = 0
    # decode steps of one plain decode dispatch: one launch and one
    # read of tokens for this many a sequence. Scheduling granularity
    # coarsens with it: a prompt's prefill waits behind a dispatch. A
    # FUSED dispatch (a prompt chunk beside the running decodes) carries
    # ``_FUSED_STEPS`` instead, whatever this is
    decode_steps_per_dispatch: int = 8
    # Dynamic SplitFuse (reference blogs/deepspeed-fastgen §3B): > 0 =
    # prompts stream through fixed-size chunks of this many tokens,
    # FUSED with the running decodes in one program per dispatch — long
    # prompts neither stall running decodes (no head-of-line blocking)
    # nor compile per-length bucket programs. 0 = legacy bucketed
    # whole-prompt prefill.
    splitfuse_tokens: int = 0
    # ZeRO-Inference weight-only int8 (reference README.md:30,
    # inference/quantization/): block weights live in HBM as int8 +
    # per-channel scales, dequantized one layer at a time in-program —
    # ~2x weight-capacity over bf16, serving models bf16 cannot fit
    quantize_weights: bool = False
    # Fused weight-only low-precision serving (W8A16 / W4A16): the
    # param pool is quantized ONCE at engine build (per-output-channel
    # scales; int4 packs two codes per byte along the contracted dim)
    # and the FFN weights stay quantized through the paged programs —
    # dequant happens inside the matmul kernels' flush epilogue
    # (ops/pallas/mlp_matmul.wq_matmul, grouped_matmul.grouped_swiglu_wq)
    # so HLO never materializes a dequantized weight tensor.
    #   "auto" (default): resolves OFF on a cold cache — every compiled
    #     program stays byte-identical to weight_quant=False. (Reserved
    #     for a measured HBM-pressure heuristic; today auto == off.)
    #   "int8" / "int4" force W8A16 / W4A16. False forces off.
    # Distinct from quantize_weights (ZeRO-Inference capacity mode):
    # that path dequantizes whole layers in-program; this one keeps the
    # FFN weights quantized end-to-end for bandwidth. When both are
    # set, weight_quant wins.
    weight_quant: object = "auto"
    # ZeRO-Inference KV host offload (reference README.md:30 "and
    # KV-cache offload"): the logical block space lives in host RAM,
    # the device holds an LRU-cached pool of device_kv_blocks slots;
    # decode dispatches run in groups whose working set fits the pool,
    # with the next group's H2D uploads prefetched under the current
    # group's compute (inference/v2/kv_offload.py)
    kv_host_offload: bool = False
    device_kv_blocks: int = 0        # required > 1 when kv_host_offload
    # Pallas paged-attention kernels on the serving hot path (the
    # reference's ragged_ops blocked_flash role): governs BOTH the
    # decode step and the SplitFuse chunk/prefill programs.
    #   "auto" (default): the autotune winner cache's measured choice
    #     per decode-shape bucket; a cold cache keeps the proven
    #     defaults (decode kernel everywhere; chunk kernel on TPU,
    #     dense-gather elsewhere).
    #   True/False force the kernel / the dense-gather parity fallback.
    # ALiBi model families keep the decode kernel regardless (the dense
    # fallback lacks the falcon bf16-quantized bias variant).
    paged_kernel: object = "auto"
    # chunk-kernel q-tile (tokens per grid step): "auto" = the winner
    # cache's tile for this (chunk, blocks, kv-heads, dtype) bucket,
    # int forces
    paged_block_c: object = "auto"
    # Radix-tree prefix cache (inference/v2/prefix_cache.py): finished
    # prompt+generation prefixes keep their KV blocks in a token-keyed
    # tree; later requests sharing a prefix skip its prefill entirely
    # (refcounted blocks, copy-on-write at the divergence point, LRU
    # eviction of cold leaves under admission pressure).
    #   "auto" (default): the winner cache's measured choice for this
    #     pool-shape bucket; a COLD cache keeps the hand-set default —
    #     DISABLED — so the admission path and every compiled program
    #     stay byte-identical to prefix_cache=False.
    #   True/False force. True raises on model/config combinations the
    #   cache cannot serve correctly (sliding-window attention, KV host
    #   offload); "auto" resolves them off silently.
    prefix_cache: object = "auto"
    # cap on tree-held blocks (0 = bounded only by the pool)
    prefix_cache_blocks: int = 0
    # minimum matched FULL blocks for a hit to be taken ("auto" = the
    # winner cache's measured knee; below it, scheduling + CoW overhead
    # beats the skipped prefill). Cold default: 1 block.
    prefix_cache_min_match: object = "auto"
    # Draft-model speculative decoding (ROADMAP 1(b)): a narrow draft
    # model proposes ``spec_k`` tokens per greedy sequence per round
    # and the target verifies all k+1 positions in ONE batched pass
    # riding the split-fuse chunk kernel; greedy acceptance keeps the
    # output streams byte-identical to plain decode. The OPT-IN is the
    # ``draft_model`` argument to the engine constructor — with no
    # draft model, scheduling and every compiled program are unchanged
    # whatever these knobs say (the PR 13 cold-cache discipline).
    #   spec_draft: "auto" (the winner cache's choice for this pool
    #     bucket; cold default ON once a draft model is present) |
    #     True (raises without a draft model, or under kv_host_offload
    #     — the draft pool has no offload tier) | False
    #   spec_k: "auto" (winner cache; cold default 4) | int >= 1
    spec_draft: object = "auto"
    spec_k: object = "auto"
    # serving-side autotune dispatch state, applied COMPLETE at engine
    # construction and at this engine's program traces ("" = env/default
    # resolution — DSTPU_AUTOTUNE, default cache_only; an earlier
    # engine's explicit setting never leaks in): off | cache_only |
    # on_first_use | search, and the winner-cache file path
    # ("" = DSTPU_AUTOTUNE_CACHE / default path). Same convention as
    # the training engine's ``autotune`` config block: dispatch state
    # is process-global and the last engine to construct (or, for v2,
    # to trace) owns it — a process mixing engines with DIFFERENT
    # explicit autotune settings should give each its own process.
    autotune_mode: str = ""
    autotune_cache: str = ""
    # per-request TTFT/TPOT accounting (monitor/telemetry.py
    # ServingTelemetry): bounded sample windows, dispatch-amortized
    # TPOT; with a monitor passed to the engine, Serve/Telemetry/*
    # events flow through the same MonitorMaster fan-out as training,
    # every telemetry_interval completed requests
    telemetry: bool = True
    telemetry_interval: int = 32

    def __post_init__(self):
        if self.paged_kernel not in (True, False, "auto"):
            raise ValueError(
                f"paged_kernel must be true|false|'auto', got "
                f"{self.paged_kernel!r}")
        if self.paged_block_c != "auto" and (
                not isinstance(self.paged_block_c, int)
                or self.paged_block_c < 1):
            raise ValueError(
                f"paged_block_c must be 'auto' or a positive int, got "
                f"{self.paged_block_c!r}")
        if self.weight_quant not in (False, "auto", "int8", "int4"):
            raise ValueError(
                f"weight_quant must be false|'auto'|'int8'|'int4', got "
                f"{self.weight_quant!r}")
        if self.prefix_cache not in (True, False, "auto"):
            raise ValueError(
                f"prefix_cache must be true|false|'auto', got "
                f"{self.prefix_cache!r}")
        if self.prefix_cache_min_match != "auto" and (
                not isinstance(self.prefix_cache_min_match, int)
                or isinstance(self.prefix_cache_min_match, bool)
                or self.prefix_cache_min_match < 1):
            raise ValueError(
                f"prefix_cache_min_match must be 'auto' or an int >= 1, "
                f"got {self.prefix_cache_min_match!r}")
        if not isinstance(self.prefix_cache_blocks, int) \
                or isinstance(self.prefix_cache_blocks, bool) \
                or self.prefix_cache_blocks < 0:
            raise ValueError(
                f"prefix_cache_blocks must be an int >= 0, got "
                f"{self.prefix_cache_blocks!r}")
        if self.spec_draft not in (True, False, "auto"):
            raise ValueError(
                f"spec_draft must be true|false|'auto', got "
                f"{self.spec_draft!r}")
        if self.spec_k != "auto" and (
                not isinstance(self.spec_k, int)
                or isinstance(self.spec_k, bool)
                or self.spec_k < 1):
            raise ValueError(
                f"spec_k must be 'auto' or an int >= 1, got "
                f"{self.spec_k!r}")
        if self.prefix_cache is True and self.kv_host_offload:
            raise ValueError(
                "prefix_cache=True is incompatible with kv_host_offload: "
                "tree-held blocks would pin host/device residency the "
                "offload pool cannot track — use one or the other")
        if self.autotune_mode not in ("", "off", "cache_only",
                                      "on_first_use", "search"):
            raise ValueError(
                f"autotune_mode must be ''|off|cache_only|on_first_use|"
                f"search, got {self.autotune_mode!r}")
        if self.splitfuse_tokens < 0:
            raise ValueError(
                f"splitfuse_tokens must be >= 0, got "
                f"{self.splitfuse_tokens}")
        if not isinstance(self.telemetry_interval, int) \
                or self.telemetry_interval < 1:
            raise ValueError(
                f"telemetry_interval must be an int >= 1, got "
                f"{self.telemetry_interval!r}")


@dataclass
class _Request:
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_token_id: int = -1
    temperature: float = 0.0
    top_k: int = 0


class InferenceEngineV2:
    """``put(uid, prompt)`` then ``step()`` until ``is_done(uid)``;
    ``get(uid)`` returns the generated tokens."""

    def __init__(self, model, config=None, params=None, topology=None,
                 monitor=None, draft_model=None, draft_params=None,
                 **kwargs):
        if isinstance(config, dict):
            config = RaggedInferenceEngineConfig(**{**config, **kwargs})
        elif config is None:
            config = RaggedInferenceEngineConfig(**kwargs)
        self.config = config
        self.model = model
        # serving-side telemetry: TTFT/TPOT histograms exported through
        # the same MonitorMaster fan-out as training when ``monitor``
        # (a monitor.Monitor / MonitorMaster) is given; always readable
        # via telemetry_snapshot()
        self.telemetry = None
        if config.telemetry:
            from ...monitor.telemetry import ServingTelemetry
            self.telemetry = ServingTelemetry(
                monitor=monitor, interval=config.telemetry_interval)
        mcfg = model.config
        self.max_seq_len = mcfg.max_seq_len
        BS = config.kv_block_size
        self.max_blocks_per_seq = -(-self.max_seq_len // BS)
        dtype = jnp.dtype(config.dtype)
        self.dtype = dtype
        # a model that keeps state by batch slot (a recurrent state, a
        # window's ring: models/paged.py) is told the slot of every
        # prefill and chunk
        self._slot_state = bool(getattr(model, "slot_state", False))
        # what the model's cache holds, counts and cannot have, whatever
        # its kinds
        from ...models.paged import Account
        self._account = Account(model, config.max_batch_size,
                                self.max_blocks_per_seq, BS, dtype)
        # refused by name before anything is built; every "auto" resolves
        # to off
        if config.prefix_cache is True:
            self._refuse("prefix_cache")
        if config.spec_draft is True or (
                draft_model is not None and config.spec_draft is not False):
            # a draft model IS the opt-in to speculation
            self._refuse("spec_draft")
        if config.kv_host_offload:
            self._refuse("kv_host_offload")
        if self._account.refusal("spec_draft"):
            draft_model = None            # spec_draft=False: off
        # blocks a slot of a window layer's ring: enough for the largest
        # step this engine's programs take past position 0 (a chunk; a
        # decode step is 1), so also what a one-shot prefill may hold
        self._ring_blocks = 0
        if self._slot_state and getattr(mcfg, "sliding_window", 0):
            from ...models.paged import ring_blocks
            self._ring_blocks = ring_blocks(
                mcfg.sliding_window,
                config.splitfuse_tokens or config.prompt_bucket,
                config.kv_block_size)

        # fused weight-only quant mode for this engine ("auto" resolves
        # OFF — cold-cache programs byte-identical to weight_quant=False;
        # reserved for a measured HBM-pressure heuristic)
        self._weight_quant = (
            config.weight_quant if config.weight_quant in ("int8", "int4")
            else False)

        # serving-side measured dispatch: apply the engine's autotune
        # fields + paged-kernel knobs once now, and again at the top of
        # every program TRACE (_install_trace_state) — the knobs live
        # on the (possibly shared) model object and in process-global
        # dispatch state, and traces are lazy, so without the re-install
        # a later-constructed engine sharing this model would silently
        # steer this engine's (re-)traces
        self._install_trace_state()

        if topology is None:
            topology = groups.initialize(TopologyConfig(
                tensor_parallel_size=config.tensor_parallel,
                expert_parallel_size=config.expert_parallel))
        self.topology = topology
        self.mesh = topology.mesh

        num_blocks = config.num_kv_blocks or (
            1 + config.max_batch_size * self.max_blocks_per_seq)
        self.state_mgr = DSStateManager(
            num_blocks=num_blocks, block_size=BS,
            max_batch=config.max_batch_size,
            max_blocks_per_seq=self.max_blocks_per_seq)

        # radix-tree prefix cache over the block pool (host-side
        # scheduling policy: the compiled programs never change, so
        # disabled == byte-identical to the pre-cache engine)
        self.prefix_cache = None
        pc_on, pc_min_match, pc_watermark = self._resolve_prefix_cache(
            num_blocks)
        if pc_on:
            from .prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(
                self.state_mgr.allocator, BS,
                min_match_blocks=pc_min_match,
                max_blocks=config.prefix_cache_blocks,
                evict_watermark_pct=pc_watermark)
            self.state_mgr.prefix_cache = self.prefix_cache
            if self.telemetry is not None:
                self.telemetry.attach_prefix_cache(self.prefix_cache)

        self.params, self.param_shardings = shard_params(
            model, self.mesh, dtype, params=params, seed=config.seed,
            topology=topology,
            quantize=self._weight_quant or config.quantize_weights)
        self.kv_pool = None
        device_blocks = num_blocks
        if config.kv_host_offload:
            if config.device_kv_blocks < 2:
                raise ValueError(
                    "kv_host_offload requires device_kv_blocks >= 2")
            device_blocks = config.device_kv_blocks
        self.cache, self._cache_sh = self._new_paged_cache(
            model, device_blocks)
        self._account.size(self.cache)
        if config.kv_host_offload:
            from .kv_offload import OffloadKVPool
            self.kv_pool = OffloadKVPool(
                model, num_blocks, device_blocks, BS, dtype,
                self._cache_sh, self.mesh)

        # --- draft-model speculative decoding (ROADMAP 1(b)) ---
        # own allocator + cache pool over the same block geometry; the
        # draft is narrow, so the pool is a small fraction of the
        # target's. With no draft model nothing below exists and the
        # engine is byte-identical to the pre-speculation engine.
        self.draft_model = None
        self._spec_k = 0
        self._spec_floor = 0.0
        if config.spec_draft is True and draft_model is None:
            raise ValueError(
                "spec_draft=True requires a draft model (pass "
                "draft_model= to the engine)")
        if draft_model is not None and config.kv_host_offload:
            if config.spec_draft is True:
                raise ValueError(
                    "spec_draft=True is incompatible with "
                    "kv_host_offload: the draft pool has no offload "
                    "tier to keep residency honest — use one or the "
                    "other")
            draft_model = None            # "auto"/False resolve off
        if draft_model is not None:
            from .speculative import resolve_spec
            spec_on, spec_k, spec_floor = resolve_spec(
                config.spec_draft, config.spec_k,
                B=config.max_batch_size, NB=num_blocks, BS=BS,
                dtype=config.dtype)
            if spec_on:
                if draft_model.config.vocab_size != mcfg.vocab_size:
                    raise ValueError(
                        f"draft/target vocab mismatch: "
                        f"{draft_model.config.vocab_size} vs "
                        f"{mcfg.vocab_size} — speculation verifies "
                        f"draft token ids against target argmax, the "
                        f"vocabularies must be the same")
                from .blocked_allocator import BlockedAllocator
                self.draft_model = draft_model
                self._spec_k = spec_k
                self._spec_floor = spec_floor
                self.state_mgr.draft_allocator = BlockedAllocator(
                    num_blocks)
                self.draft_params, self._draft_param_sh = shard_params(
                    draft_model, self.mesh, dtype, params=draft_params,
                    seed=config.seed + 1, topology=topology)
                # now covers the draft, whose pools are sized by the
                # kernel setting it carries
                self._install_trace_state()
                self.draft_cache, self._draft_cache_sh = \
                    self._new_paged_cache(draft_model, num_blocks)
                self._propose_jit = None
                self._verify_jit = None
                self._draft_chunk_jit = None

        self._pending = deque()
        self._results = {}            # uid -> generated tokens (finished)
        self._rng = jax.random.key(config.seed + 23)
        self._prefill_jit = None
        self._decode_jit = None
        # program -> {mechanism: (its calls, those of them through a Pallas
        # kernel)}, noted when the program is traced
        self._calls = {}
        self._splitfuse_jit = None
        self._chunk_jit = None        # chunk-only (no decoders running)
        self._cow_jit = None          # prefix-cache partial-tail copy
        self._prefill_q = deque()     # uids mid-chunked-prefill (SplitFuse)
        # disaggregated prefill/decode handoff (kv_transfer.py): uids
        # parked out of every decode dispatch until their KV streams to
        # a decode replica, plus the export gather / donated import
        # scatter programs (lazy, the _get_cow_copy idiom)
        self._decode_hold = set()
        # the plain decode dispatch that is enqueued and whose tokens the
        # host has not read, as (batch, device tokens); the (uid, token)
        # pairs of one that _settle read outside _plain_decode, until
        # step() returns them
        self._unread = None
        self._settled = []
        self._kv_export_jit = None
        self._kv_import_jit = None
        self._uid_next = 0
        log_dist(
            f"v2 engine ready: tp={config.tensor_parallel} blocks="
            f"{num_blocks}x{BS} max_batch={config.max_batch_size}",
            ranks=[0])

    # ------------------------------------------------------------- requests
    def put(self, prompt, max_new_tokens=32, eos_token_id=-1, uid=None,
            temperature=None, top_k=None, klass=0, waited_s=0.0):
        """Queue a generation request (sampling params per request, like
        FastGen; None = the engine-config defaults; ``klass`` = the
        router's request class, keying the per-class acceptance EMAs in
        serving telemetry; ``waited_s`` = how long it already queued in
        the router, for the queue-wait window). Returns its uid."""
        if uid is None:
            uid = self._uid_next
            self._uid_next += 1
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = len(prompt) + max_new_tokens
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt+max_new={total} exceeds "
                f"model max_seq_len={self.max_seq_len}")
        mgr = self.state_mgr
        if mgr.blocks_needed(total) > mgr.allocator.total_blocks:
            raise ValueError(
                f"request needs {mgr.blocks_needed(total)} KV blocks but "
                f"the pool only has {mgr.allocator.total_blocks}; raise "
                "num_kv_blocks")
        if self.kv_pool is not None \
                and mgr.blocks_needed(total) > self.kv_pool.D - 1:
            raise ValueError(
                f"request needs {mgr.blocks_needed(total)} KV blocks but "
                f"the device pool holds {self.kv_pool.D - 1} (+scratch); "
                "a single sequence's working set must fit on device — "
                "raise device_kv_blocks")
        self._pending.append(_Request(
            uid, prompt, max_new_tokens, eos_token_id,
            temperature=(self.config.temperature if temperature is None
                         else float(temperature)),
            top_k=(self.config.top_k if top_k is None else int(top_k))))
        if self.telemetry is not None:
            # TTFT clock starts at submit; the class keys acceptance EMAs
            self.telemetry.on_submit(uid, klass=klass, waited_s=waited_s)
        return uid

    def is_done(self, uid):
        if uid in self._results:
            return True
        if any(r.uid == uid for r in self._pending):
            return False
        if uid in self.state_mgr._seqs:
            return False
        raise KeyError(f"unknown uid {uid} (never submitted or already "
                       "fetched with get())")

    def get(self, uid, flush=True):
        """Generated tokens for a finished request (``flush`` forgets the
        result afterwards; in-flight requests return their tokens so far)."""
        if uid in self._results:
            return self._results.pop(uid) if flush else self._results[uid]
        if any(r.uid == uid for r in self._pending):
            return np.zeros((0,), np.int32)  # queued, nothing yet
        try:
            seq = self.state_mgr.get_sequence(uid)
        except KeyError:
            raise KeyError(
                f"unknown uid {uid} (never submitted, or already fetched "
                f"with get(flush=True))") from None
        return np.asarray(seq.generated, np.int32)

    def cancel(self, uid):
        """Withdraw a request (the router's deadline/shed path): queued
        requests are dropped; in-flight sequences are flushed through
        the prefix-cache-safe unref path — NO tree insert, because
        cache contents past the prefill frontier are unverified — so
        the pool accounting closes; a finished-but-unfetched result is
        forgotten. Serving telemetry excludes the request from the
        TTFT/TPOT windows (``on_reject``): a cancelled request has no
        dispatch boundary to amortize against and would poison the
        percentiles. Returns True when the uid was known."""
        self._settle()
        # read, but no step() has returned them: they never surface
        self._settled = [pair for pair in self._settled if pair[0] != uid]
        self._decode_hold.discard(uid)
        for i, r in enumerate(self._pending):
            if r.uid == uid:
                del self._pending[i]
                if self.telemetry is not None:
                    self.telemetry.on_reject(uid)
                return True
        if uid in self._results:
            # finished before the cancel landed: telemetry already
            # counted the completion; just forget the result
            del self._results[uid]
            return True
        if uid not in self.state_mgr._seqs:
            return False
        try:
            self._prefill_q.remove(uid)
        except ValueError:
            pass
        seq = self.state_mgr.get_sequence(uid)
        if seq.cow is not None:
            # admitted but the CoW slice copy never ran: drop the
            # claim's temporary source ref before the unref sweep
            self.state_mgr.cow_complete(seq)
        if self.kv_pool is not None:
            self.kv_pool.release(seq.blocks)
        self.state_mgr.flush(uid)
        if self.telemetry is not None:
            self.telemetry.on_reject(uid)
        return True

    @property
    def has_work(self):
        # an unread dispatch, or pairs no step() has returned yet, are
        # work: whoever steps while this is true strands no token
        return bool(self._pending) or self.state_mgr.n_active > 0 \
            or self._unread is not None or bool(self._settled)

    # ------------------------------------------------------------- programs
    def _refuse(self, feature, error=ValueError):
        """Raise where the model's cache cannot have ``feature``, with
        the reason its kinds give (models/paged.py): ``ValueError`` at
        build, ``RuntimeError`` from a handoff call."""
        why = self._account.refusal(feature)
        if why is not None:
            raise error(why)

    def _resolve_prefix_cache(self, num_blocks):
        """Resolve (enabled, min_match_blocks, evict_watermark_pct) for
        the prefix cache. Model/config combinations the cache cannot
        serve correctly refuse LOUDLY when forced on and resolve off
        under "auto"; the "auto" spelling consults the winner cache for
        this pool-shape bucket with cold-cache defaults equal to the
        hand-set values (disabled, min-match 1, on-demand eviction), so
        a cold-cache engine is byte-identical to prefix_cache=False."""
        cfg = self.config
        if self._account.refusal("prefix_cache"):
            return False, 1, 0        # True was refused at build
        if cfg.prefix_cache is False or cfg.kv_host_offload:
            # explicit off, or offload (True+offload raised in config
            # validation; "auto" resolves off)
            return False, 1, 0
        from .prefix_cache import resolve_prefix_cache
        return resolve_prefix_cache(
            cfg.prefix_cache, cfg.prefix_cache_min_match,
            B=cfg.max_batch_size, NB=num_blocks,
            BS=cfg.kv_block_size, dtype=cfg.dtype)

    def _install_trace_state(self):
        """(Re)apply THIS engine's kernel/autotune knobs: the model
        attributes the paged paths read and the process dispatch state
        ("" = env/default; an earlier engine's explicit mode or cache
        path never leaks in). Called in __init__ and — because jax
        re-traces lazily per shape bucket — at trace time inside every
        program, so engines sharing one model object each trace under
        their own config (pure python side effect; nothing lands in
        the compiled program)."""
        from ...autotuning import kernel_dispatch
        kernel_dispatch.configure_serving(
            mode=self.config.autotune_mode,
            cache_path=self.config.autotune_cache)
        self.model._paged_kernel = self.config.paged_kernel
        self.model._paged_block_c = self.config.paged_block_c
        self.model._paged_ring_blocks = self._ring_blocks
        # fused W8A16/W4A16: _layer_slice keeps the FFN weights
        # quantized (model._WQ_KEEP) and _mlp routes them through the
        # fused-dequant kernels; False = every path dequantizes whole
        # slices as before
        self.model._weight_quant_fused = self._weight_quant
        draft = getattr(self, "draft_model", None)
        if draft is not None:
            # the draft traces under the same kernel knobs but never
            # under fused weight-quant (its params shard unquantized)
            draft._paged_kernel = self.config.paged_kernel
            draft._paged_block_c = self.config.paged_block_c
            draft._weight_quant_fused = False

    def _noting_calls(self, body, key=None):
        """``body`` — a program's traced function — noting the calls its
        trace makes of each mechanism that has a Pallas form and another,
        and how many of them took the kernel (ops/pallas/_common.py
        ``counting_calls``: expert layer calls, MoE layers x steps; the
        gated delta rule's, linear layers x calls; the selected reads of
        a latent cache, latent layers x steps; a model with none of them
        notes nothing), under its name or ``key(*args)``. Known once the
        program is traced: the dispatch that traces it still reads 0 of
        0."""
        from ...ops.pallas._common import counting_calls

        @functools.wraps(body)
        def program(*args):
            with counting_calls() as counts:
                out = body(*args)
            self._calls[body.__name__ if key is None else key(*args)] = \
                counts
            return out
        return program

    def _slot_arg(self, uid):
        """The slot ``uid``'s prefill / chunk program serves, as that
        program's last argument, for a model that keeps state by slot;
        the other families' programs have no such argument."""
        return (np.int32(self.state_mgr._slots.index(uid)),) \
            if self._slot_state else ()

    def _new_paged_cache(self, model, num_blocks):
        """Allocate ``model``'s paged cache on this engine's mesh ->
        (cache, the shardings its programs declare for it). Where the
        model's decode step will run the paged kernel
        (``models/paged.uses_decode_kernel``, the question its trace
        asks, of the same shapes; off-TPU the kernels are interpreted)
        the pools those kernels read (``models/paged.KERNEL_POOLS``) are
        born in the shape that keeps them in the kernels' layout
        (:func:`pool_block_dims`). A model with slot state sizes the rest
        of its cache from the slots and ring blocks it is given, and
        those leaves keep the shape it gives them."""
        from ...models.paged import KERNEL_POOLS, uses_decode_kernel
        from ...ops.pallas._common import interpret_default
        cfg = self.config
        extra = dict(slots=cfg.max_batch_size,
                     ring_blocks=self._ring_blocks) \
            if model is self.model and self._slot_state else {}

        def init(n):
            return model.init_paged_cache(n, cfg.kv_block_size,
                                          dtype=self.dtype, **extra)

        pools = jax.eval_shape(lambda: init(1)).get(KERNEL_POOLS[0])
        # a cache with no such pool is read by XLA and keeps the shape
        # the model gives it
        _, _, BS, hd = pools[0].shape if pools \
            else (0, 0, cfg.kv_block_size, 128)
        kernel = not interpret_default() and uses_decode_kernel(
            model, cfg.max_batch_size, self.max_blocks_per_seq, BS,
            self.dtype)
        dims = pool_block_dims(num_blocks, hd, kernel)
        lead = dict.fromkeys(KERNEL_POOLS, len(dims) - 1)

        def by_key(fn, tree, **kw):
            return {key: jax.tree.map(
                lambda x: fn(x, lead.get(key, 0)), sub, **kw)
                for key, sub in tree.items()}

        # the model's own specs, behind the block axis's extra dimensions
        shardings = by_key(
            lambda spec, n: NamedSharding(self.mesh,
                                          P(*(None,) * n, *spec)),
            model.paged_cache_specs(), is_leaf=lambda x: isinstance(x, P))
        with jax.set_mesh(self.mesh):
            cache = jax.jit(
                lambda: by_key(
                    lambda p, n: p.reshape(dims + p.shape[1:]) if n else p,
                    init(math.prod(dims))),
                out_shardings=shardings)()
        return cache, shardings

    @staticmethod
    def _sample_per_slot(logits, rng, temps, top_ks, all_greedy=False):
        """Vectorized per-request sampling (FastGen carries sampling
        params per sequence): logits (B, V), temps (B,) f32 (0 = greedy),
        top_ks (B,) int32 (0 = off). Traced — one program serves any mix
        of greedy and sampled requests."""
        B, V = logits.shape
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if all_greedy:
            # static fast path: no full-vocab sort/categorical in the
            # compiled program when every live request is greedy
            return greedy
        lt = logits / jnp.maximum(temps, 1e-6)[:, None]
        # per-row top-k: mask everything below each row's k-th largest
        sorted_desc = -jnp.sort(-lt, axis=-1)
        kth_idx = jnp.clip(top_ks - 1, 0, V - 1)[:, None]
        kth_val = jnp.take_along_axis(sorted_desc, kth_idx, axis=1)
        masked = jnp.where((top_ks[:, None] > 0) & (lt < kth_val),
                           -1e30, lt)
        sampled = jax.random.categorical(rng, masked, axis=-1).astype(
            jnp.int32)
        return jnp.where(temps > 0, sampled, greedy)

    def _get_prefill(self):
        # one jit object; jax specializes per T_pad bucket shape itself
        if self._prefill_jit is None:
            model = self.model

            def prefill(params, cache, ids, tb, to, length, rng, temp,
                        top_k, all_greedy, *slot):
                self._install_trace_state()
                with jax.named_scope("dstpu.step.prefill"):
                    logits, pools = model.apply_paged_prefill(
                        params, ids, as_pools(cache), tb, to, length, *slot)
                    tok = self._sample_per_slot(logits, rng, temp, top_k,
                                                all_greedy)
                return tok, like_boundary(pools, cache)

            # a bucket's program is a trace of its own: noted by its length
            self._prefill_jit = jax.jit(
                self._noting_calls(
                    prefill, key=lambda *a: ("prefill", a[2].shape[1])),
                donate_argnums=(1,), static_argnums=(9,),
                in_shardings=(self.param_shardings, self._cache_sh)
                + (None,) * (7 + self._slot_state),
                out_shardings=(None, self._cache_sh))
        return self._prefill_jit

    def _get_decode(self):
        if self._decode_jit is None:
            model = self.model
            n = max(1, self.config.decode_steps_per_dispatch)

            def decode(params, cache, tokens, lengths, tables, rng,
                       temps, top_ks, all_greedy, prev, from_host):
                self._install_trace_state()
                # n decode steps in ONE program: the sampled token feeds
                # the next step in-trace, and the last of them feeds the
                # next dispatch the same way — ``prev`` is the (n, B)
                # tokens of the dispatch before, still on the device, and
                # a slot takes the host's token only where ``from_host``
                # (its first token came from a prefill since, or no
                # dispatch came before). So the host's round trip (token
                # sync, batch upload, launch) is off the device's path
                # wherever _plain_decode can enqueue this call before it
                # reads the last one, and costs one launch where it
                # cannot. Unrolled (not lax.scan): the cache pools
                # must stay per-layer donated buffers updated in place —
                # carrying them through a scan defensively copies them.
                tokens = jnp.where(from_host, tokens, prev[-1])
                all_toks = []
                pools = as_pools(cache)
                for t in range(n):
                    with jax.named_scope("dstpu.step.decode"):
                        logits, pools = model.apply_paged_decode(
                            params, tokens, lengths, pools, tables)
                        tokens = self._sample_per_slot(
                            logits, jax.random.fold_in(rng, t), temps,
                            top_ks, all_greedy)
                        lengths = lengths + 1
                    all_toks.append(tokens)
                return jnp.stack(all_toks), like_boundary(pools, cache)

            # the tokens go out as they come back in as ``prev``: one
            # sharding for both, and the first call's zeros an array on
            # the device like them (jax keys a trace on that too), so
            # that every call shares one executable
            whole = NamedSharding(self.mesh, P())
            self._decode_jit = jax.jit(
                self._noting_calls(decode), donate_argnums=(1,),
                static_argnums=(8,),
                in_shardings=(self.param_shardings, self._cache_sh,
                              None, None, None, None, None, None, whole,
                              None),
                out_shardings=(whole, self._cache_sh))
            self._no_prev = jax.device_put(
                np.zeros((n, self.config.max_batch_size), np.int32), whole)
        return self._decode_jit

    def _get_splitfuse(self):
        """ONE fused fixed-shape program per dispatch: a C-token prompt
        chunk for the head-of-queue prefilling sequence PLUS
        ``_FUSED_STEPS`` decode steps for every running sequence — the
        Dynamic SplitFuse composition (reference blogs/deepspeed-fastgen
        §3B; the ragged kernels' role). Shapes are static (C, B, MB), so
        exactly one compilation serves every prompt length and batch
        mix."""
        if self._splitfuse_jit is None:
            model = self.model
            n = _FUSED_STEPS

            def fused(params, cache, c_ids, c_tb, c_to, c_start, c_len,
                      c_table, c_temp, c_topk, d_tokens, d_lengths,
                      d_tables, rng, d_temps, d_topks, all_greedy, *c_slot):
                self._install_trace_state()
                with jax.named_scope("dstpu.step.chunk"):
                    c_logits, pools = model.apply_paged_chunk(
                        params, c_ids, as_pools(cache), c_tb, c_to, c_start,
                        c_len, c_table, *c_slot)
                    c_tok = self._sample_per_slot(
                        c_logits, jax.random.fold_in(rng, 7919), c_temp,
                        c_topk, all_greedy)
                toks = []
                for t in range(n):
                    with jax.named_scope("dstpu.step.decode"):
                        logits, pools = model.apply_paged_decode(
                            params, d_tokens, d_lengths, pools, d_tables)
                        d_tokens = self._sample_per_slot(
                            logits, jax.random.fold_in(rng, t), d_temps,
                            d_topks, all_greedy)
                        d_lengths = d_lengths + 1
                    toks.append(d_tokens)
                return c_tok, jnp.stack(toks), like_boundary(pools, cache)

            self._splitfuse_jit = jax.jit(
                self._noting_calls(fused), donate_argnums=(1,),
                static_argnums=(16,),
                in_shardings=(self.param_shardings, self._cache_sh)
                + (None,) * (14 + self._slot_state),
                out_shardings=(None, None, self._cache_sh))
        return self._splitfuse_jit

    def _get_chunk_only(self):
        """Chunk program WITHOUT the fused decode steps — used when no
        sequence is decoding (e.g. a long prompt arriving at an idle
        engine), so prefill never pays scratch-write decode forwards."""
        if self._chunk_jit is None:
            model = self.model

            def chunk(params, cache, c_ids, c_tb, c_to, c_start, c_len,
                      c_table, c_temp, c_topk, rng, all_greedy, *c_slot):
                self._install_trace_state()
                with jax.named_scope("dstpu.step.chunk"):
                    c_logits, pools = model.apply_paged_chunk(
                        params, c_ids, as_pools(cache), c_tb, c_to, c_start,
                        c_len, c_table, *c_slot)
                    c_tok = self._sample_per_slot(
                        c_logits, jax.random.fold_in(rng, 7919), c_temp,
                        c_topk, all_greedy)
                return c_tok, like_boundary(pools, cache)

            self._chunk_jit = jax.jit(
                self._noting_calls(chunk), donate_argnums=(1,),
                static_argnums=(11,),
                in_shardings=(self.param_shardings, self._cache_sh)
                + (None,) * (9 + self._slot_state),
                out_shardings=(None, self._cache_sh))
        return self._chunk_jit

    def _get_cow_copy(self):
        """Prefix-cache copy-on-write: copy the first ``plen`` token
        rows of block ``src`` into block ``dst`` across every layer's
        K and V pools. A shared (refcount > 1) block is never written in
        place — the sequence diverging inside it gets its matched slice
        copied into a fresh block, then prefill resumes there. Block ids
        and the slice length are traced operands, so every divergence
        point shares ONE compiled program."""
        if self._cow_jit is None:
            BS = self.config.kv_block_size

            def cow(cache, src, dst, plen):
                keep = (jnp.arange(BS) < plen)[None, :, None]
                return like_boundary(jax.tree.map(
                    lambda p: p.at[dst].set(
                        jnp.where(keep, p[src], p[dst])),
                    as_pools(cache)), cache)

            self._cow_jit = jax.jit(
                cow, donate_argnums=(0,),
                in_shardings=(self._cache_sh, None, None, None),
                out_shardings=self._cache_sh)
        return self._cow_jit

    def _get_draft_chunk(self):
        """Draft-side catch-up chunk: ingest a span of COMMITTED tokens
        into the draft cache — the draft's prefill. It replays the real
        token history from the descriptor, so prefix-cache-served
        prompt tokens (which the target never recomputed) and any
        plain-decoded stretch before speculation engaged are covered by
        the same program. Logits are discarded — proposals only come
        from the propose program."""
        if self._draft_chunk_jit is None:
            draft = self.draft_model

            def dchunk(params, cache, ids, tb, to, start, tlen, table):
                self._install_trace_state()
                with jax.named_scope("dstpu.step.chunk"):
                    _logits, pools = draft.apply_paged_chunk(
                        params, ids, as_pools(cache), tb, to, start, tlen,
                        table)
                return like_boundary(pools, cache)

            self._draft_chunk_jit = jax.jit(
                dchunk, donate_argnums=(1,),
                in_shardings=(self._draft_param_sh, self._draft_cache_sh)
                + (None,) * 6,
                out_shardings=self._draft_cache_sh)
        return self._draft_chunk_jit

    def _get_propose(self):
        """ONE program: a re-ingest step + ``spec_k`` greedy draft
        decode steps, each proposal feeding the next in-trace (the
        draft-side analogue of the fused decode dispatch). The
        re-ingest writes the second-to-last committed token's KV at its
        own position: after a fully-accepted round that position holds
        nothing (the draft never saw its own last proposal fed back),
        and after a partial round the rewrite is byte-idempotent — so
        the draft needs no per-round gap bookkeeping."""
        if self._propose_jit is None:
            draft = self.draft_model
            k = self._spec_k

            def propose(params, cache, tokens2, lengths, tables):
                self._install_trace_state()
                with jax.named_scope("dstpu.step.decode"):
                    _lg, pools = draft.apply_paged_decode(
                        params, tokens2[:, 0], lengths, as_pools(cache),
                        tables)
                cur = tokens2[:, 1]
                lengths = lengths + 1
                props = []
                for _ in range(k):
                    with jax.named_scope("dstpu.step.decode"):
                        logits, pools = draft.apply_paged_decode(
                            params, cur, lengths, pools, tables)
                        # only greedy sequences speculate, so the draft
                        # is always greedy too
                        cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        lengths = lengths + 1
                    props.append(cur)
                return jnp.stack(props, axis=1), like_boundary(pools, cache)

            self._propose_jit = jax.jit(
                self._noting_calls(propose), donate_argnums=(1,),
                in_shardings=(self._draft_param_sh, self._draft_cache_sh,
                              None, None, None),
                out_shardings=(None, self._draft_cache_sh))
        return self._propose_jit

    def _get_verify(self):
        """Batched verify: all k+1 positions of every speculating slot
        in ONE pass through the split-fuse chunk kernel
        (apply_paged_verify), returning the target's greedy next token
        at every position — the host takes the longest accepted prefix
        plus the bonus token."""
        if self._verify_jit is None:
            model = self.model

            def verify(params, cache, tokens, lengths, tables):
                self._install_trace_state()
                logits, pools = model.apply_paged_verify(
                    params, tokens, lengths, as_pools(cache), tables)
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                        like_boundary(pools, cache))

            self._verify_jit = jax.jit(
                self._noting_calls(verify), donate_argnums=(1,),
                in_shardings=(self.param_shardings, self._cache_sh,
                              None, None, None),
                out_shardings=(None, self._cache_sh))
        return self._verify_jit

    def _apply_cow(self, seq):
        self._settle()
        fn = self._get_cow_copy()
        src, dst, plen = seq.cow
        with jax.set_mesh(self.mesh):
            self.cache = fn(self.cache, np.int32(src), np.int32(dst),
                            np.int32(plen))
        self.state_mgr.cow_complete(seq)   # drops the claim ref on src

    # -------------------------------- disaggregated prefill/decode handoff
    def hold_decode(self, uid):
        """Park ``uid`` out of every decode dispatch. A prefill-role
        replica holds each sequence here once submitted: it runs
        chunked prefill to the last prompt token, posts the first
        generated token, and then waits for its KV handoff to a decode
        replica instead of decoding locally."""
        self._refuse("kv_transfer", RuntimeError)
        self._settle()
        self._decode_hold.add(uid)

    def release_decode_hold(self, uid=None):
        """Release one park (or all of them with ``uid=None`` — the
        router flips a fleet back to colocated when its last decode
        replica dies, and every held sequence must resume decoding
        HERE rather than deadlock)."""
        if uid is None:
            self._decode_hold.clear()
        else:
            self._decode_hold.discard(uid)

    def _get_kv_export(self):
        """Handoff export: gather one sequence's KV block payloads out
        of the paged cache in ONE compiled program. The block-id vector
        is a traced operand padded to the per-sequence table shape, so
        every handoff shares the program. NOT donated — the prefill
        replica keeps serving from its cache, and export must be
        repeatable for stream-failure retries."""
        if self._kv_export_jit is None:
            def gather(cache, src):
                return jax.tree.map(lambda p: p[src], as_pools(cache))

            self._kv_export_jit = jax.jit(
                gather, in_shardings=(self._cache_sh, None))
        return self._kv_export_jit

    def _get_kv_import(self):
        """Handoff import: scatter received block payloads into freshly
        allocated block ids in place — the donated ``_get_cow_copy``
        idiom, so the import never copies the whole cache. Pad rows of
        the destination vector map to block 0, the scratch block, which
        every dispatch overwrites by design."""
        if self._kv_import_jit is None:
            def scatter(cache, kv, dst):
                return like_boundary(jax.tree.map(
                    lambda p, s: p.at[dst].set(s), as_pools(cache), kv),
                    cache)

            self._kv_import_jit = jax.jit(
                scatter, donate_argnums=(0,),
                in_shardings=(self._cache_sh, None, None),
                out_shardings=self._cache_sh)
        return self._kv_import_jit

    def export_handoff(self, uid):
        """Export half of the handoff: -> (descriptor state dict, host
        KV tree sliced to the blocks the sequence wrote). The sequence
        is NOT removed — :meth:`release_handoff` runs only after the
        decode side confirms the import, so a failed stream retries
        from unchanged state.

        Byte-identity is by construction: the gathered blocks hold
        positions ``0..seen_tokens-2`` — exactly the cache state a
        colocated decode dispatch would attend, because the last
        generated token's KV is written by the decode step that
        consumes it."""
        self._refuse("kv_transfer", RuntimeError)
        if self.kv_pool is not None:
            raise RuntimeError(
                "KV handoff is incompatible with kv_host_offload: "
                "block payloads live in the host pool, not the device "
                "cache — run prefill-role replicas without offload")
        self._settle()
        mgr = self.state_mgr
        seq = mgr.get_sequence(uid)
        if not seq.generated:
            raise RuntimeError(
                f"uid {uid} has no first token yet — only "
                f"prefill-complete sequences hand off")
        n = mgr.blocks_needed(seq.seen_tokens - 1)
        src = np.zeros((self.max_blocks_per_seq,), np.int32)
        src[:len(seq.blocks)] = seq.blocks
        with jax.set_mesh(self.mesh):
            kv = self._get_kv_export()(self.cache, src)
        kv_host = jax.tree.map(lambda a: np.asarray(a)[:n], kv)
        t_submit = None
        klass = 0
        if self.telemetry is not None:
            t_submit = self.telemetry.submit_stamp(uid)
            klass = self.telemetry.klass_of(uid)
        state = {
            "uid": int(uid),
            "prompt": [int(t) for t in seq.prompt],
            "generated": [int(t) for t in seq.generated],
            "cached_len": int(seq.cached_len),
            "max_new_tokens": int(seq.max_new_tokens),
            "eos_token_id": int(seq.eos_token_id),
            "temperature": float(seq.temperature),
            "top_k": int(seq.top_k),
            "klass": int(klass),
            "t_submit": t_submit,
        }
        return state, kv_host

    def import_handoff(self, state, kv_flat):
        """Import half of the handoff: rebuild the wire's KV tree
        against this engine's cache template, validate the layout,
        allocate the sequence's full budget from THIS pool, scatter the
        received payloads in one donated program, and bind the
        descriptor straight into the decode batch
        (``prefill_offset = len(prompt)`` — every prompt position's KV
        just arrived). Serving telemetry registers the request anchored
        at the ORIGINAL submit stamp. Returns the uid."""
        from ...runtime.checkpoint_engine import serialization as ser
        from .kv_transfer import KVWireError
        self._refuse("kv_transfer", RuntimeError)
        if self.kv_pool is not None:
            raise RuntimeError(
                "KV handoff is incompatible with kv_host_offload: "
                "imported blocks would bypass residency tracking — run "
                "decode-role replicas without offload")
        self._settle()
        mgr = self.state_mgr
        uid = int(state["uid"])
        if uid in mgr._seqs or uid in self._results:
            raise RuntimeError(f"handoff uid {uid} already live here")
        prompt = np.asarray(state["prompt"], np.int32)
        generated = [int(t) for t in state["generated"]]
        max_new = int(state["max_new_tokens"])
        kv = ser.unflatten_into(
            jax.tree.map(lambda _p: 0, self.cache), kv_flat)
        # layout guard: a gpt2-shaped payload must never scatter into a
        # llama (GQA) cache — per-block shapes and dtypes must match
        # the local cache exactly, and every leaf must carry the same
        # block count
        n_blocks = set()

        def _check(p, s):
            if not hasattr(s, "shape") or s.shape[1:] != p.shape[-3:] \
                    or s.dtype != p.dtype:
                raise KVWireError(
                    f"handoff KV layout mismatch: payload block shape "
                    f"{getattr(s, 'shape', None)}/"
                    f"{getattr(s, 'dtype', None)} vs local cache "
                    f"{p.shape}/{p.dtype}")
            n_blocks.add(int(s.shape[0]))
            return p

        jax.tree.map(_check, self.cache, kv)
        if len(n_blocks) != 1:
            raise KVWireError(
                f"handoff KV payload has inconsistent block counts "
                f"across layers: {sorted(n_blocks)}")
        n = n_blocks.pop()
        total = len(prompt) + max_new
        need = mgr.blocks_needed(total)
        if need > self.max_blocks_per_seq or n > need \
                or total > self.max_seq_len:
            raise KVWireError(
                f"handoff sequence needs {need} blocks / {total} "
                f"tokens — beyond this engine's per-sequence capacity")
        if mgr.free_slot() is None or \
                mgr.allocator.available_blocks < need:
            raise RuntimeError(
                "decode replica cannot admit handoff (no free "
                "slot/blocks) — the router must back-pressure "
                "(can_accept) before streaming")
        blocks = mgr.allocator.allocate(need)
        MB = self.max_blocks_per_seq
        dst = np.zeros((MB,), np.int32)     # pads scatter into scratch
        dst[:n] = blocks[:n]

        def _pad(s):
            buf = np.zeros((MB,) + s.shape[1:], s.dtype)
            buf[:n] = s
            return buf

        kv_pad = jax.tree.map(_pad, kv)
        with jax.set_mesh(self.mesh):
            self.cache = self._get_kv_import()(self.cache, kv_pad, dst)
        mgr.admit_imported(
            uid, prompt, generated, max_new, blocks,
            eos_token_id=int(state["eos_token_id"]),
            temperature=float(state["temperature"]),
            top_k=int(state["top_k"]))
        if self.telemetry is not None:
            self.telemetry.on_handoff_in(
                uid, klass=int(state.get("klass", 0)),
                submit_ts=state.get("t_submit"))
        return uid

    def release_handoff(self, uid):
        """The decode side confirmed the import: drop the sequence
        HERE (the prefill side). ``retire`` inserts the verified
        prompt+first-token prefix into the local prefix cache — its KV
        was fully written by this replica's prefill — and releases
        blocks/slot; ``flush`` drops the descriptor without surfacing
        a result; telemetry forgets the request WITHOUT counting a
        rejection, keeping its TTFT sample (the first token was
        produced here) in the window."""
        self._decode_hold.discard(uid)
        self.state_mgr.retire(uid)
        self.state_mgr.flush(uid)
        if self.telemetry is not None:
            self.telemetry.on_handoff_out(uid)

    def _dispatch_span(self, kind, active, steps, chunk_tokens=0,
                       chunk_rows=0, batch=None, chained=0, late_steps=0,
                       chunk_start=0):
        """The ``dstpu.engine.dispatch`` span of one program call, opened
        once the batch is assembled (its stats are fixed here); a
        decode-bearing dispatch also feeds the occupancy counter.
        ``chained`` / ``late_steps``: see :meth:`_plain_decode`.
        ``active``: a count, or — with ``batch`` = (lengths, block
        tables), where the dispatch runs the decode programs over the
        batch — the live slots' mask. What the call does to the cache
        (the decode kernel's grid, the KV write's rows, state updates,
        a selection's keys) is the account's to count
        (models/paged.py ``Account.dispatch``)."""
        slots = self.config.max_batch_size
        cache = self._account.dispatch(
            *(batch or (None, None)), active, steps, chunk_start,
            chunk_tokens, chunk_rows)
        active = int(np.sum(active))
        calls = self._calls_of(*_PROGRAMS_OF_KIND[kind])
        if self.telemetry is not None:
            if steps:
                self.telemetry.on_decode_batch(
                    active, slots, cache["grid_steps"],
                    cache["table_entries"], cache["kernel_steps"])
            self.telemetry.on_kv_write(cache["write_rows"],
                                       cache["write_rows_offered"])
            if kind == "decode":
                self.telemetry.on_plain_decode(chained, steps * active)
            elif kind == "fused":
                self.telemetry.on_fused_dispatch()
        return span("dstpu.engine.dispatch", kind=kind, active=active,
                    slots=slots, steps=steps, chunk_tokens=chunk_tokens,
                    chunk_start=chunk_start, chained=chained,
                    late_steps=late_steps, **cache, **calls)

    def _calls_of(self, *programs):
        """What one call of each of ``programs`` makes, as their traces
        noted it, under the names the dispatch and prefill spans say it
        by: ``<mechanism>_calls`` / ``<mechanism>_kernel_calls``, of each
        mechanism of ``monitor/tag_schema.py`` ``KERNEL_SHARES`` (0 / 0
        where no trace noted it), which the telemetry's
        ``*_kernel_share`` keys are fed from. What else a trace noted (a
        prefill's flash calls) is not the spans'."""
        counts = {name: [0, 0] for name in KERNEL_SHARES}
        for program in programs:
            noted = self._calls.get(program, {})
            for name, pair in counts.items():
                calls, kernel = noted.get(name, (0, 0))
                pair[0] += calls
                pair[1] += kernel
        if self.telemetry is not None:
            self.telemetry.on_calls(counts)
        return {f"{name}{stat}": n for name, pair in counts.items()
                for stat, n in zip(("_calls", "_kernel_calls"), pair)}

    def _step_splitfuse_chunk(self):
        """Run one fused dispatch: the next chunk of the oldest
        prefilling sequence + ``_FUSED_STEPS`` decode steps (not the
        plain decode's ``decode_steps_per_dispatch``; chunk-only when
        nothing is decoding). Returns decode (uid, token) pairs.
        Prefix-cache hits ride this path even with SplitFuse off (chunk
        accounting already handles a nonzero start offset); the chunk
        size then falls back to the prompt bucket."""
        self._settle()      # the decode slots' tokens come from the host
        mgr = self.state_mgr
        C = self.config.splitfuse_tokens or self.config.prompt_bucket
        with span("dstpu.engine.build"):
            uid = self._prefill_q[0]
            seq = mgr.get_sequence(uid)
            slot = self._slot_arg(uid)
            off = seq.prefill_offset
            true_len = min(C, len(seq.prompt) - off)
            last = off + true_len >= len(seq.prompt)
            ids = np.zeros((1, C), np.int32)
            ids[0, :true_len] = seq.prompt[off:off + true_len]
            tb = np.zeros((C,), np.int32)
            to = np.zeros((C,), np.int32)
            fb, fo = mgr.token_placement(seq)
            tb[:true_len] = fb[off:off + true_len]
            to[:true_len] = fo[off:off + true_len]
            table = np.zeros((self.max_blocks_per_seq,), np.int32)
            table[:len(seq.blocks)] = seq.blocks
            c_temp = np.asarray([seq.temperature], np.float32)
            c_topk = np.asarray([seq.top_k], np.int32)
            if self.kv_pool is not None:
                # offload: chunk-only dispatch over the resident history
                # + destination blocks, then the grouped decode path keeps
                # the running sequences fed (the fused program would need
                # the union working set resident)
                live = seq.blocks[:mgr.blocks_needed(off + true_len)]
                # blocks starting at/after the chunk's first position
                # hold no prior tokens — this dispatch writes them from
                # scratch, so they need slots but no host upload
                first_fresh = -(-off // mgr.block_size)
                self.cache = self.kv_pool.ensure(
                    self.cache, live, skip_upload=live[first_fresh:])
                dest = sorted({int(b) for b in tb[:true_len]})
                tb = self.kv_pool.translate(tb)
                table = self.kv_pool.translate(table)
                batch = None
            else:
                batch = mgr.decode_batch(exclude=self._decode_hold)
            self._rng, sub = jax.random.split(self._rng)
            fused = batch is not None and bool(batch.active.any())
            fn = self._get_splitfuse() if fused else self._get_chunk_only()

        if fused:
            dispatch = self._dispatch_span(
                "fused", batch.active, _FUSED_STEPS, true_len, C,
                batch=(batch.lengths, batch.block_tables), chunk_start=off)
        else:
            dispatch = self._dispatch_span("chunk", 0, 0, true_len, C,
                                           chunk_start=off)
        with dispatch:
            with span("dstpu.engine.fetch"):
                with jax.set_mesh(self.mesh):
                    if fused:
                        all_greedy = (seq.temperature == 0.0
                                      and not bool(batch.temps.any()))
                        c_tok, toks, self.cache = fn(
                            self.params, self.cache, ids, tb, to,
                            np.int32(off), np.int32(true_len), table,
                            c_temp, c_topk, batch.tokens, batch.lengths,
                            batch.block_tables, sub, batch.temps,
                            batch.top_ks, all_greedy, *slot)
                    else:
                        c_tok, self.cache = fn(
                            self.params, self.cache, ids, tb, to,
                            np.int32(off), np.int32(true_len), table,
                            c_temp, c_topk, sub, seq.temperature == 0.0,
                            *slot)
                        toks = np.zeros((0, self.config.max_batch_size),
                                        np.int32)
                toks = np.asarray(toks)
                # the chunk's token is read only when the prompt ends: a
                # chunk-only dispatch in mid-prompt stays asynchronous
                first = int(np.asarray(c_tok)[0]) if last else None
            with span("dstpu.engine.post"):
                if self.kv_pool is not None:
                    self.kv_pool.mark_dirty(dest)
                seq.prefill_offset = off + true_len
                if last:
                    self._prefill_q.popleft()
                    self._post_token(seq, first)
                out = [] if batch is None \
                    else self._post_decode_tokens(batch, toks)
        if self.kv_pool is not None:
            return self._step_offload_decode()
        return out

    # ----------------------------------------------------------------- step
    def _admit_pending(self):
        mgr = self.state_mgr
        tel = self.telemetry
        while self._pending:
            req = self._pending[0]
            if not mgr.can_admit(len(req.prompt), req.max_new_tokens,
                                 prompt=req.prompt):
                break
            wait_ms = tel.on_admit(req.uid) if tel is not None else 0.0
            with span("dstpu.engine.admit", uid=req.uid,
                      prompt_tokens=len(req.prompt),
                      wait_us=int(wait_ms * 1e3)):
                self._pending.popleft()
                slot, seq = mgr.admit(req.uid, req.prompt,
                                      req.max_new_tokens, req.eos_token_id,
                                      temperature=req.temperature,
                                      top_k=req.top_k)
                if seq.cow is not None:
                    # partial-tail prefix hit: device-copy the matched
                    # slice into the fresh block before any prefill
                    # touches it
                    self._apply_cow(seq)
                if self.config.splitfuse_tokens or seq.cached_len \
                        or self._past_ring(len(req.prompt)):
                    # SplitFuse: the prompt streams through chunk
                    # dispatches interleaved with decodes — no bucketed
                    # prefill here. Prefix-cache hits take the same path
                    # regardless: the chunk program's start/true_len
                    # accounting is what skips the cached prefix (the
                    # bucketed prefill always starts at 0). So does a
                    # prompt whose bucket a window layer's ring cannot
                    # hold at once
                    self._prefill_q.append(req.uid)
                else:
                    self._prefill_bucketed(req, seq)

    def _past_ring(self, prompt_tokens):
        """Whether a prompt's bucket is more than the rings hold."""
        bucket = self.config.prompt_bucket
        padded = -(-max(prompt_tokens, 1) // bucket) * bucket
        return bool(self._ring_blocks) \
            and padded > self._ring_blocks * self.config.kv_block_size

    def _prefill_bucketed(self, req, seq):
        """The whole prompt in one program call, padded to the bucket;
        blocks on the read of its token."""
        mgr = self.state_mgr
        bucket = self.config.prompt_bucket
        T = len(req.prompt)
        T_pad = -(-max(T, 1) // bucket) * bucket
        with span("dstpu.engine.prefill", uid=req.uid, tokens=T,
                  padded=T_pad, **self._account.prefill(T, T_pad),
                  **self._calls_of(("prefill", T_pad))):
            with span("dstpu.engine.build"):
                ids = np.zeros((1, T_pad), np.int32)
                ids[0, :T] = req.prompt
                tb = np.zeros((T_pad,), np.int32)       # scratch for pads
                to = np.zeros((T_pad,), np.int32)
                tb[:T], to[:T] = mgr.token_placement(seq)
                prompt_blocks = seq.blocks[:mgr.blocks_needed(T)]
                if self.kv_pool is not None:
                    # every prompt block is fully written by this
                    # dispatch: slots only, no garbage H2D (code-review
                    # finding)
                    self.cache = self.kv_pool.ensure(
                        self.cache, prompt_blocks,
                        skip_upload=prompt_blocks)
                    tb = self.kv_pool.translate(tb)
                self._rng, sub = jax.random.split(self._rng)
                fn = self._get_prefill()
            with span("dstpu.engine.fetch"):
                with jax.set_mesh(self.mesh):
                    tok, self.cache = fn(
                        self.params, self.cache, ids, tb, to, np.int32(T),
                        sub, np.asarray([seq.temperature], np.float32),
                        np.asarray([seq.top_k], np.int32),
                        seq.temperature == 0.0,
                        *self._slot_arg(req.uid))
                tok = int(np.asarray(tok)[0])
            with span("dstpu.engine.post"):
                if self.kv_pool is not None:
                    self.kv_pool.mark_dirty(prompt_blocks)
                self._post_token(seq, tok)

    def _post_token(self, seq, token):
        seq.generated.append(token)
        if self.telemetry is not None:
            self.telemetry.on_token(seq.uid)
        if ((seq.eos_token_id >= 0 and token == seq.eos_token_id)
                or len(seq.generated) >= seq.max_new_tokens):
            # a held sequence that finishes AT its first token (EOS or
            # max_new_tokens=1) never needs the handoff — drop the park
            self._decode_hold.discard(seq.uid)
            self._results[seq.uid] = np.asarray(seq.generated, np.int32)
            if self.telemetry is not None:
                self.telemetry.on_finish(seq.uid)
            if self.kv_pool is not None:
                # drop residency before the allocator recycles the ids
                self.kv_pool.release(seq.blocks)
            # an EOS is seen one dispatch late: the dispatch enqueued
            # behind the one that held it still writes this sequence's
            # tail blocks (and its slot's ring / state) after they are
            # given back here. Safe because the device runs its queue in
            # order: whatever the next owner runs (prefill, chunk, CoW
            # copy, KV import) is enqueued after that dispatch. The
            # prefix cache takes prompt + generated[:-1], and every such
            # late write lands past it
            self.state_mgr.retire(seq.uid)
            self.state_mgr.flush(seq.uid)

    # ------------------------------------------------- KV host offload path
    def _seq_live_blocks(self, seq, n_steps=0):
        """Logical blocks a decode dispatch touches for ``seq``: the
        history it attends plus the tail blocks the next ``n_steps``
        writes land in."""
        last = seq.seen_tokens - 1 + max(0, n_steps - 1)
        hi = min(last // self.state_mgr.block_size, len(seq.blocks) - 1)
        return seq.blocks[:hi + 1]

    def _offload_decode_groups(self, batch, n_steps):
        """Greedy-pack active slots into dispatch groups whose combined
        working set fits the device pool."""
        mgr = self.state_mgr
        cap = self.kv_pool.D - 1
        groups = []
        cur, cur_blocks = [], set()
        for slot in np.nonzero(batch.active)[0]:
            seq = mgr.get_sequence(mgr._slots[slot])
            nb = set(self._seq_live_blocks(seq, n_steps))
            if cur and len(cur_blocks | nb) > cap:
                groups.append((cur, cur_blocks))
                cur, cur_blocks = [], set()
            cur.append(int(slot))
            cur_blocks |= nb
        if cur:
            groups.append((cur, cur_blocks))
        return groups

    def _step_offload_decode(self):
        """Grouped decode under KV host offload: each group's blocks are
        made device-resident (next group's H2D prefetched under the
        current group's compute), tables are translated to device slots,
        and tail blocks are marked dirty."""
        self._settle()
        mgr = self.state_mgr
        pool = self.kv_pool
        n = max(1, self.config.decode_steps_per_dispatch)
        with span("dstpu.engine.build"):
            batch = mgr.decode_batch(exclude=self._decode_hold)
            if not batch.active.any():
                return []
            groups = self._offload_decode_groups(batch, n)
            fn = self._get_decode()
            prepared = pool.prepare(sorted(groups[0][1])) if groups \
                else None
        out = []
        for gi, (slots_g, blocks_g) in enumerate(groups):
            with span("dstpu.engine.build"):
                self.cache = pool.ensure(self.cache, sorted(blocks_g),
                                         prepared)
                prepared = (pool.prepare(sorted(groups[gi + 1][1]))
                            if gi + 1 < len(groups) else None)
                sub_active = np.zeros_like(batch.active)
                sub_active[slots_g] = batch.active[slots_g]
                tables = np.zeros_like(batch.block_tables)
                tokens = np.where(sub_active, batch.tokens, 0)
                lengths = np.where(sub_active, batch.lengths, 0)
                for s in slots_g:
                    tables[s] = pool.translate(batch.block_tables[s])
                self._rng, sub = jax.random.split(self._rng)
            with self._dispatch_span("offload", sub_active, n,
                                     batch=(lengths, tables)):
                with span("dstpu.engine.fetch"):
                    with jax.set_mesh(self.mesh):
                        toks, self.cache = fn(
                            self.params, self.cache, tokens,
                            lengths, tables, sub, batch.temps,
                            batch.top_ks,
                            not bool(batch.temps[sub_active].any()),
                            self._no_prev, batch.from_host)
                    toks = np.asarray(toks)
                with span("dstpu.engine.post"):
                    for s in slots_g:
                        seq = mgr.get_sequence(mgr._slots[s])
                        pool.mark_dirty(self._seq_live_blocks(seq, n)[
                            (batch.lengths[s]) // mgr.block_size:])
                    sub_batch = RaggedBatchWrapper(
                        tokens=tokens, lengths=lengths,
                        block_tables=tables, active=sub_active,
                        temps=batch.temps, top_ks=batch.top_ks,
                        seqs=[q if on else None for q, on
                              in zip(batch.seqs, sub_active)])
                    out.extend(self._post_decode_tokens(sub_batch, toks))
        return out

    def step(self):
        """One scheduler iteration (see :meth:`_step_inner`). The
        dispatch boundary is where serving telemetry amortizes this
        dispatch's wall time across the tokens it produced (per-token
        deltas inside one multi-step dispatch are meaningless)."""
        tel = self.telemetry
        live, blocks, tokens = self.state_mgr.held()
        cache_bytes = blocks * self._account.block_bytes \
            + live * self._account.slot_bytes
        # the counters ride the span so that whoever reads the trace has
        # them on the profiler's clock (cached floats; 0 with telemetry
        # off)
        with span("dstpu.engine.step", pending=len(self._pending),
                  active=live, slots=self.config.max_batch_size,
                  queue_p50_us=int(tel.queue_ms_p50 * 1e3) if tel else 0,
                  queue_p90_us=int(tel.queue_ms_p90 * 1e3) if tel else 0,
                  admitted_total=tel.admitted if tel else 0,
                  cache_bytes=cache_bytes, live_tokens=tokens):
            out = self._step_inner()
            if self._settled:
                # read outside _plain_decode, before whatever else this
                # step (or a call between two steps) went on to run
                out, self._settled = self._settled + out, []
            if tel is not None:
                tel.on_cache_held(cache_bytes, tokens)
                tel.on_dispatch(active=self.state_mgr.n_active)
                tel.maybe_emit()
        return out

    def telemetry_snapshot(self):
        """Current TTFT/TPOT percentiles + counters (None when serving
        telemetry is disabled)."""
        return None if self.telemetry is None else \
            self.telemetry.percentiles()

    def _step_inner(self):
        """One scheduler iteration: admit+prefill pending, then one
        device program: while a prompt is streaming in chunks its next
        chunk, fused with ``_FUSED_STEPS`` decode steps for every active
        sequence (:meth:`_step_splitfuse_chunk`); else
        ``decode_steps_per_dispatch`` decode steps for every active
        sequence. Returns the (uid, token) pairs the host read this
        step: where plain decodes follow one another
        (:meth:`_plain_decode`) those of the dispatch BEFORE the one
        this step enqueued, so [] on the step that enqueues the first.

        A sequence that hits EOS or its budget mid-dispatch keeps
        decoding until the dispatch ends (its extra tokens are discarded
        and its over-writes land in its own tail slots / the scratch
        block) — the FastGen trade of scheduling granularity for
        amortized launch overhead. A budget is host arithmetic, so such
        a sequence is in no later dispatch; an EOS is seen only when its
        dispatch is read, one dispatch late: the sequence rides the next
        one too, and that one's tokens for it are discarded the same way.
        """
        self._admit_pending()
        mgr = self.state_mgr
        if self._prefill_q:
            return self._step_splitfuse_chunk()
        if mgr.n_active == 0:
            # the last live sequence may have ended by an EOS read while
            # the dispatch behind it, which carried it too, went out
            self._settle()
            return []
        if self.kv_pool is not None:
            return self._step_offload_decode()
        if self.draft_model is not None:
            return self._step_spec_decode()
        return self._plain_decode()

    def _plain_decode(self, uids=None):
        """The plain decode dispatch: n fused decode steps over the given
        slots (all active slots when ``uids`` is None), chained. The
        program call of this dispatch is enqueued BEFORE the host reads
        the tokens of the one before (``self._unread``): the batch is
        built without them (:meth:`DSStateManager.decode_batch`), the
        program takes them device to device, and the device goes from
        one dispatch to the next with nothing between. Returns the pairs
        of the dispatch it read — the one before — and leaves its own
        unread for the next call, or for :meth:`_settle` where anything
        else runs next. The span says ``chained`` (1: enqueued behind an
        unread one) and ``late_steps`` (decode steps x slots which the
        dispatch READ under it ran for sequences that an EOS in the one
        before had ended: known when the span opens).

        The speculative scheduler's plain set (``uids``) is read at once:
        a speculative round follows it, which takes tokens from the host."""
        n = max(1, self.config.decode_steps_per_dispatch)
        prev = self._unread
        with span("dstpu.engine.build"):
            batch = self.state_mgr.decode_batch(
                uids, exclude=self._decode_hold,
                unread=prev and prev[0], ahead=n)
            live = bool(batch.active.any())
            if live:
                self._rng, sub = jax.random.split(self._rng)
                fn = self._get_decode()
        if not live:
            self._settle()      # what is left ends in the unread one
            return []
        with self._dispatch_span(
                "decode", batch.active, n,
                batch=(batch.lengths, batch.block_tables),
                chained=int(prev is not None),
                late_steps=self._late_steps(prev)):
            with span("dstpu.engine.fetch"):
                with jax.set_mesh(self.mesh):
                    toks, self.cache = fn(
                        self.params, self.cache, batch.tokens,
                        batch.lengths, batch.block_tables, sub,
                        batch.temps, batch.top_ks,
                        not bool(batch.temps.any()),
                        self._no_prev if prev is None else prev[1],
                        batch.from_host)
                self._unread = (batch, toks)
                if prev is None:
                    if uids is None:
                        return []   # the first of a run: none to read yet
                    prev, self._unread = self._unread, None
                toks = np.asarray(prev[1])
            with span("dstpu.engine.post"):
                return self._post_decode_tokens(prev[0], toks)

    def _late_steps(self, unread):
        """Decode steps x slots a dispatch ((batch, tokens); None: 0) runs
        for sequences that have ended since its batch was built."""
        if unread is None:
            return 0
        batch, toks = unread
        return toks.shape[0] * sum(
            q is not None and not self._is_live(q) for q in batch.seqs)

    def _is_live(self, seq):
        """Whether this descriptor is still the one its uid names: not
        once retired or cancelled, whoever took its slot or its uid."""
        return self.state_mgr._seqs.get(seq.uid) is seq

    def _settle(self):
        """Read and post the decode dispatch that is enqueued and unread,
        if there is one. Whatever is not the next plain decode calls this
        before it builds anything: it takes the decode slots' tokens from
        the host, or frees, parks, copies or moves what that dispatch
        holds. The pairs go out with the next return of :meth:`step`."""
        unread, self._unread = self._unread, None
        if unread is None:
            return
        batch, toks = unread
        with span("dstpu.engine.settle"):
            with span("dstpu.engine.fetch"):
                toks = np.asarray(toks)
            with span("dstpu.engine.post"):
                self._settled.extend(self._post_decode_tokens(batch, toks))

    # ------------------------------------------------- speculative decoding
    def _spec_candidate(self, seq):
        """Greedy, not floor-latched, and far enough from its budget
        tail that a full k-token span stays inside the blocks allocated
        up-front — tail sequences ride plain decode (at most k extra
        plain steps), so speculation never writes past a block table."""
        return (self.draft_model is not None and seq.spec_on
                and seq.temperature == 0.0
                and len(seq.prompt) + seq.max_new_tokens
                - seq.seen_tokens >= self._spec_k)

    @property
    def spec_pending(self):
        """True when the next step() would run a verify dispatch — the
        replica boundary gates its ``serve_verify`` chaos point on
        this, so chaos tests can target mid-speculation state."""
        if self.draft_model is None or self._prefill_q:
            return False
        mgr = self.state_mgr
        for uid in mgr._slots:
            if uid is None or uid in self._decode_hold:
                continue
            seq = mgr.get_sequence(uid)
            if seq.generated and self._spec_candidate(seq):
                return True
        return False

    def _step_spec_decode(self):
        """Acceptance-aware scheduling: partition the decoding slots
        into a SPEC set (greedy, latched on, draft pool has room) and a
        PLAIN set. The spec set runs propose -> batched verify -> host
        acceptance; the plain set runs the UNCHANGED decode program in
        its own dispatch — adversarial (low-acceptance) traffic latches
        off per sequence and pays exactly the plain-decode cost."""
        self._settle()
        mgr = self.state_mgr
        spec, plain = [], []
        for uid in list(mgr._slots):
            if uid is None or uid in self._decode_hold:
                continue
            seq = mgr.get_sequence(uid)
            if not seq.generated:
                continue
            if not self._spec_candidate(seq):
                plain.append(uid)
                continue
            if not seq.draft_blocks and not mgr.alloc_draft(seq):
                plain.append(uid)     # draft pool full: plain decode
                continue
            while seq.draft_len < seq.seen_tokens - 2:
                self._draft_catchup(seq)
            spec.append(uid)
        out = []
        if spec:
            out.extend(self._spec_round(spec))
        if plain:
            out.extend(self._plain_decode(set(plain)))
        return out

    def _draft_catchup(self, seq):
        """Ingest one chunk of committed history into the draft cache
        (the draft's prefill, riding its own chunk program): tokens
        [draft_len, seen-1) from prompt+generated, written at their
        absolute positions in the sequence's draft blocks."""
        mgr = self.state_mgr
        BS = mgr.block_size
        C = self.config.splitfuse_tokens or self.config.prompt_bucket
        hist = (seq.prompt if not seq.generated else np.concatenate(
            [seq.prompt, np.asarray(seq.generated, np.int32)]))
        off = seq.draft_len
        true_len = min(C, seq.seen_tokens - 1 - off)
        ids = np.zeros((1, C), np.int32)
        ids[0, :true_len] = hist[off:off + true_len]
        idx = np.arange(off, off + true_len)
        tb = np.zeros((C,), np.int32)
        to = np.zeros((C,), np.int32)
        tb[:true_len] = np.asarray(seq.draft_blocks, np.int32)[idx // BS]
        to[:true_len] = (idx % BS).astype(np.int32)
        table = np.zeros((self.max_blocks_per_seq,), np.int32)
        table[:len(seq.draft_blocks)] = seq.draft_blocks
        fn = self._get_draft_chunk()
        with jax.set_mesh(self.mesh):
            self.draft_cache = fn(
                self.draft_params, self.draft_cache, ids, tb, to,
                np.int32(off), np.int32(true_len), table)
        seq.draft_len = off + true_len

    def _spec_round(self, uids):
        """One propose/verify round for the spec set. Each sequence
        commits its accepted prefix plus the target's bonus token —
        every committed token is a target-argmax output, which is what
        keeps greedy streams byte-identical to plain decode."""
        mgr, k = self.state_mgr, self._spec_k
        uid_set = set(uids)
        with span("dstpu.engine.build"):
            pb = mgr.propose_batch(uid_set)
        # one span for the round: its fetch holds both program calls
        # (propose, verify) and the host work between them
        with self._dispatch_span("spec", len(uids), k):
            with span("dstpu.engine.fetch"):
                proposals, nxt = self._spec_propose_verify(uid_set, pb)
            with span("dstpu.engine.post"):
                return self._spec_commit(uid_set, proposals, nxt)

    def _spec_propose_verify(self, uid_set, pb):
        """Draft proposals and the target's verdict on them, both read
        back: -> ({uid: (k,) proposals}, (B, k+1) target tokens)."""
        mgr, k = self.state_mgr, self._spec_k
        with jax.set_mesh(self.mesh):
            props, self.draft_cache = self._get_propose()(
                self.draft_params, self.draft_cache, pb.tokens,
                pb.lengths, pb.block_tables)
        props = np.asarray(props)                           # (B, k)
        proposals = {uid: props[slot]
                     for slot, uid in enumerate(mgr._slots)
                     if uid in uid_set}
        vb = mgr.verify_batch(proposals, k)
        for uid in uid_set:
            mgr.begin_spec(mgr.get_sequence(uid), proposals[uid])
        try:
            with jax.set_mesh(self.mesh):
                nxt, self.cache = self._get_verify()(
                    self.params, self.cache, vb.tokens, vb.lengths,
                    vb.block_tables)
            nxt = np.asarray(nxt)                           # (B, k+1)
        except BaseException:
            # an interrupted verify must not leave speculative tokens
            # in ``generated`` — unwind before the failure propagates,
            # or the replica/router retry would replay corrupt state
            for uid in uid_set:
                mgr.rollback_spec(mgr.get_sequence(uid))
            raise
        return proposals, nxt

    def _spec_commit(self, uid_set, proposals, nxt):
        """Host acceptance: each sequence commits its accepted prefix
        plus the bonus token. Returns the (uid, token) pairs."""
        mgr, k = self.state_mgr, self._spec_k
        from .speculative import (SPEC_EMA_ALPHA, SPEC_MIN_ROUNDS,
                                  longest_accept)
        out = []
        for slot, uid in enumerate(list(mgr._slots)):
            if uid is None or uid not in uid_set:
                continue
            seq = mgr.get_sequence(uid)
            mgr.rollback_spec(seq)
            pre_seen = seq.seen_tokens
            d, t = proposals[uid], nxt[slot]
            a = longest_accept(d, t)
            commit = [int(x) for x in d[:a]] + [int(t[a])]
            seq.spec_rounds += 1
            seq.spec_accepted += a
            frac = a / k
            seq.spec_ema = frac if seq.spec_ema is None else \
                (1 - SPEC_EMA_ALPHA) * seq.spec_ema \
                + SPEC_EMA_ALPHA * frac
            if self.telemetry is not None:
                self.telemetry.on_spec_round(
                    uid, accepted=a, proposed=k, committed=len(commit))
            out.extend(self._post_tokens(seq, commit))
            if uid in self._results or uid not in mgr._seqs:
                continue                        # retired mid-span
            # the draft holds the committed history through seen-1 on
            # a partial round, seen-2 on a full one (its own last
            # proposal was never fed back; re-ingest covers the gap)
            seq.draft_len = pre_seen + (a if a < k else k - 1)
            if seq.spec_rounds >= SPEC_MIN_ROUNDS \
                    and seq.spec_ema < self._spec_floor:
                # acceptance floor: latch plain decode for this
                # sequence and return its over-allocated draft blocks
                seq.spec_on = False
                mgr.drop_draft(seq)
        return out

    def _post_tokens(self, seq, tokens):
        """Feed a committed multi-token span (accepted proposals +
        bonus) one at a time: EOS or budget retires mid-span and the
        tail is discarded, exactly like _post_decode_tokens discards
        post-finish dispatch tokens. Returns the accepted (uid, token)
        pairs."""
        out = []
        uid = seq.uid
        for tok in tokens:
            if uid in self._results:
                break
            self._post_token(seq, tok)
            out.append((uid, tok))
        return out

    def _post_decode_tokens(self, batch, toks):
        """Feed (n, B) decode outputs to their sequences; returns the
        accepted (uid, token) pairs. The sequences are those the batch
        was built over (``batch.seqs``), whoever holds their slots now: a
        dispatch may be read after a later one was built, and a slot may
        have changed hands between."""
        late = self._late_steps((batch, toks))
        if late and self.telemetry is not None:
            self.telemetry.on_late_steps(late)
        out = []
        for slot, seq in enumerate(batch.seqs):
            if seq is None:
                continue
            for t in range(toks.shape[0]):
                if not self._is_live(seq):
                    # finished mid-dispatch, or in the dispatch before
                    break
                tok = int(toks[t, slot])
                self._post_token(seq, tok)
                out.append((seq.uid, tok))
        return out

    def generate_all(self, prompts, max_new_tokens=32, eos_token_id=-1):
        """Convenience: run the scheduler to completion over a request
        list; returns generated-token arrays in submission order."""
        uids = [self.put(p, max_new_tokens, eos_token_id) for p in prompts]
        while self.has_work:
            self.step()
        return [self.get(u) for u in uids]
