"""Paged-KV host offload: a device-resident block cache over host RAM.

The other half of ZeRO-Inference (reference README.md:30 — "weight
quantization and KV-cache offload"; the async-tier pattern is the
reference's swap machinery,
runtime/swap_tensor/partitioned_param_swapper.py:40). The logical block
space — what the BlockedAllocator hands out, what sequences' block
tables reference — lives in HOST memory; the device holds a fixed pool
of ``device_blocks`` physical slots managed as an LRU cache. Context
length x concurrent streams is then bounded by host RAM, not HBM.

Mechanics:
  * ``ensure(cache, logical_ids)`` makes a set of logical blocks
    device-resident: LRU-evicts victims (dirty ones are fetched back to
    host first), uploads the missing blocks for EVERY layer in one
    stacked H2D transfer + one jitted donated scatter, and returns the
    logical -> device slot translation for building dispatch tables.
  * Dispatches reference DEVICE slots; the engine translates each
    step's block tables through ``translate``.
  * Blocks a dispatch writes (prefill scatter positions, decode tail
    blocks) are marked ``dirty``; their device copy is authoritative
    until eviction writes them back.
  * Prefetch: ``prepare(logical_ids)`` host-gathers and device_puts the
    upload payload WITHOUT the scatter — JAX transfers are async, so
    issuing the next dispatch group's prepare before the current
    group's compute overlaps H2D with the decode (the reference
    overlaps its swap-in the same way, via aio + compute streams).
  * Device slot 0 is pinned to logical block 0 (the scratch block every
    padded table position points at) and is never evicted.
"""

import numpy as np
import jax
import jax.numpy as jnp

from ...ops.pallas.paged_attention import as_pools, like_boundary

__all__ = ["OffloadKVPool"]


class OffloadKVPool:
    def __init__(self, model, num_logical, device_blocks, block_size,
                 dtype, cache_shardings, mesh):
        if device_blocks < 2:
            raise ValueError("device_kv_blocks must be >= 2 (slot 0 is "
                             "the pinned scratch block)")
        self.model = model
        self.NL = int(num_logical)
        self.D = int(device_blocks)
        self.block_size = block_size
        self.dtype = jnp.dtype(dtype)
        self.mesh = mesh
        self._cache_sh = cache_shardings

        mcfg = model.config
        L = mcfg.n_layer
        self.n_layer = L
        # host store mirrors the per-layer device pool layout
        # (NL, KVH, BS, hd); one numpy array per layer per k/v
        probe = jax.eval_shape(
            lambda: model.init_paged_cache(1, block_size, dtype=dtype))
        self._blk_shape = tuple(probe["k"][0].shape[1:])
        np_dt = np.dtype(self.dtype)
        self.host = {
            kv: [np.zeros((self.NL,) + self._blk_shape, np_dt)
                 for _ in range(L)]
            for kv in ("k", "v")}

        # slot maps: device slot -> logical block (or -1), and inverse
        self.logical_of = np.full((self.D,), -1, np.int64)
        self.slot_of = np.full((self.NL,), -1, np.int64)
        self.dirty = np.zeros((self.D,), bool)
        self.last_used = np.zeros((self.D,), np.int64)
        self._tick = 0
        # pin scratch
        self.logical_of[0] = 0
        self.slot_of[0] = 0

        self._scatter_jit = None
        self._gather_jit = None
        self.swapped_in = 0           # blocks uploaded (stats)
        self.swapped_out = 0          # dirty blocks written back

    # ---------------------------------------------------------- jitted ops
    def _get_scatter(self):
        if self._scatter_jit is None:
            def scatter(cache, slots, blk_k, blk_v):
                # blk_k/blk_v: (L, n, KVH, BS, hd) stacked uploads
                pools = as_pools(cache)
                k = [c.at[slots].set(blk_k[i])
                     for i, c in enumerate(pools["k"])]
                v = [c.at[slots].set(blk_v[i])
                     for i, c in enumerate(pools["v"])]
                return like_boundary({"k": k, "v": v}, cache)
            self._scatter_jit = jax.jit(
                scatter, donate_argnums=(0,),
                in_shardings=(self._cache_sh, None, None, None),
                out_shardings=self._cache_sh)
        return self._scatter_jit

    def _get_gather(self):
        if self._gather_jit is None:
            def gather(cache, slots):
                pools = as_pools(cache)
                k = jnp.stack([c[slots] for c in pools["k"]])
                v = jnp.stack([c[slots] for c in pools["v"]])
                return k, v
            self._gather_jit = jax.jit(
                gather,
                in_shardings=(self._cache_sh, None),
                out_shardings=(None, None))
        return self._gather_jit

    # ------------------------------------------------------------ prefetch
    def prepare(self, logical_ids, skip_upload=()):
        """Host-gather + async device_put of the upload payload for the
        blocks in ``logical_ids`` that are NOT yet resident. Returns an
        opaque handle ``ensure`` accepts (None when nothing to upload).
        Does not touch the slot maps — call ``ensure`` with the handle
        to commit. ``skip_upload``: blocks the coming dispatch fully
        overwrites (never-written prefill/chunk destinations) — they
        are excluded here and get bare slot assignments in ``ensure``,
        skipping the pointless H2D of garbage host contents."""
        skip = {int(b) for b in skip_upload}
        missing = [b for b in dict.fromkeys(int(b) for b in logical_ids)
                   if self.slot_of[b] < 0 and b not in skip]
        if not missing:
            return None
        # pad the upload to a power-of-two bucket so the scatter program
        # compiles once per bucket, not once per distinct miss count;
        # pad rows land in the scratch slot (contents never attended)
        n = len(missing)
        n_pad = 1 << (n - 1).bit_length()
        midx = np.asarray(missing + [0] * (n_pad - n), np.int64)
        blk_k = np.stack([h[midx] for h in self.host["k"]])
        blk_v = np.stack([h[midx] for h in self.host["v"]])
        # async H2D: returns immediately, overlaps in-flight compute
        return (missing, jax.device_put(blk_k), jax.device_put(blk_v))

    # -------------------------------------------------------------- ensure
    def ensure(self, cache, logical_ids, prepared=None, skip_upload=()):
        """Make every block in ``logical_ids`` device-resident.
        Returns the updated cache. ``prepared``: a matching
        ``prepare`` handle (uploads already in flight). ``skip_upload``:
        see ``prepare`` — such blocks get slots but no data transfer
        (the dispatch fully overwrites them / never attends their
        stale positions)."""
        need = list(dict.fromkeys(int(b) for b in logical_ids))
        self._tick += 1
        if prepared is None:
            prepared = self.prepare(need, skip_upload)
        skip = [b for b in dict.fromkeys(int(b) for b in skip_upload)
                if self.slot_of[b] < 0 and b in set(need)]
        missing, blk_k, blk_v = prepared if prepared is not None \
            else ([], None, None)
        if not missing and not skip:
            self._check_resident(need)
            for b in need:
                self.last_used[self.slot_of[b]] = self._tick
            return cache
        if len(need) > self.D - 1:
            raise ValueError(
                f"dispatch references {len(need)} KV blocks but the "
                f"device pool holds only {self.D - 1} (+scratch); raise "
                f"device_kv_blocks or lower concurrency/context")

        # victims: LRU over slots not referenced by this ensure, slot 0
        # excluded
        needed_slots = {int(self.slot_of[b]) for b in need
                        if self.slot_of[b] >= 0}
        free = [s for s in range(1, self.D)
                if self.logical_of[s] < 0 and s not in needed_slots]
        evictable = sorted(
            (s for s in range(1, self.D)
             if self.logical_of[s] >= 0 and s not in needed_slots),
            key=lambda s: self.last_used[s])

        def take_slot():
            if free:
                return free.pop()
            if evictable:
                return evictable.pop(0)
            raise ValueError(
                "KV device pool exhausted mid-ensure (should be "
                "unreachable given the size check above)")

        slots = [take_slot() for _ in missing]
        skip_slots = [take_slot() for _ in skip]

        # write back dirty victims before their slots are overwritten
        dirty_slots = [s for s in slots + skip_slots
                       if self.logical_of[s] >= 0 and self.dirty[s]]
        if dirty_slots:
            cache = self._writeback(cache, dirty_slots)
        for s in slots + skip_slots:
            old = self.logical_of[s]
            if old >= 0:
                self.slot_of[old] = -1
            self.logical_of[s] = -1
            self.dirty[s] = False

        if missing:
            # the upload was padded to a power-of-two bucket: route the
            # pad rows at the scratch slot (never attended)
            pad_slots = [0] * (blk_k.shape[1] - len(slots))
            sl = jnp.asarray(np.asarray(slots + pad_slots, np.int32))
            with jax.set_mesh(self.mesh):
                cache = self._get_scatter()(cache, sl, blk_k, blk_v)
        for b, s in zip(list(missing) + skip, slots + skip_slots):
            self.logical_of[s] = b
            self.slot_of[b] = s
        # a stale ``prepared`` handle (built for a different block list)
        # can leave a needed block without a slot — translate() would
        # then silently route its reads to the scratch slot and the
        # dispatch would attend garbage; fail loudly instead
        self._check_resident(need)
        for b in need:
            self.last_used[self.slot_of[b]] = self._tick
        self.swapped_in += len(missing)
        return cache

    def _check_resident(self, need):
        stale = [b for b in need if self.slot_of[b] < 0]
        if stale:
            raise RuntimeError(
                f"ensure() commit left blocks {stale} without device "
                "slots — the prepared handle was built for a different "
                "block list (stale prepare()); re-prepare with the "
                "dispatch's actual blocks")

    def _writeback(self, cache, slots):
        # pad to the same power-of-two buckets as the upload path so the
        # gather program compiles once per bucket, not per victim count
        # (pad rows re-read slot 0 and are discarded below)
        n = len(slots)
        n_pad = 1 << (n - 1).bit_length()
        padded = list(slots) + [0] * (n_pad - n)
        with jax.set_mesh(self.mesh):
            k, v = self._get_gather()(cache,
                                      jnp.asarray(padded, jnp.int32))
        k = np.asarray(k)
        v = np.asarray(v)
        for j, s in enumerate(slots):
            b = int(self.logical_of[s])
            for li in range(self.n_layer):
                self.host["k"][li][b] = k[li, j]
                self.host["v"][li][b] = v[li, j]
            self.dirty[s] = False
        self.swapped_out += len(slots)
        return cache

    # ------------------------------------------------------------- helpers
    def translate(self, logical_ids):
        """logical block ids (any numpy shape) -> device slot ids.
        Unresident blocks map to scratch 0 — callers must ``ensure``
        everything a dispatch actually reads/writes first."""
        ids = np.asarray(logical_ids, np.int64)
        out = self.slot_of[ids]
        return np.where(out < 0, 0, out).astype(np.int32)

    def mark_dirty(self, logical_ids):
        for b in dict.fromkeys(int(b) for b in np.asarray(
                logical_ids).reshape(-1)):
            s = self.slot_of[b]
            if s > 0:
                self.dirty[s] = True

    def release(self, logical_ids):
        """A retired sequence's blocks: drop residency, nothing to keep."""
        for b in dict.fromkeys(int(b) for b in logical_ids):
            s = self.slot_of[b]
            if s > 0:
                self.logical_of[s] = -1
                self.slot_of[b] = -1
                self.dirty[s] = False
