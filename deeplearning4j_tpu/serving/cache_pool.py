"""KV-slot pool: slot recycling over ONE pre-allocated decode cache.

The batch axis of ``_decode_builder.init_caches`` IS the slot pool: the
buffers — (n_layers, 2, n_slots, Tpad, Hkv*K), plus the f32 scale
planes in int8 mode — are allocated once at engine start and never
re-allocated. Admitting a request into a freed slot overwrites that
slot's rows (the prefill insert copies a full Tpad slab, zeros beyond
the prompt, so no stale rows from the previous occupant survive);
releasing a slot is pure free-list bookkeeping, no device work. This is
the fixed-slot special case of vLLM's paged pool: one page per request,
sized to the engine's token budget.

Slots are handed out lowest-index-first so admission order is
deterministic — tests (and trace replays) rely on it.

Under tensor-parallel serving the pool carries a ``sharding`` pytree
(:func:`~deeplearning4j_tpu.models.transformer.serving_tp_cache_sharding`):
every allocation this pool hands out — the decode cache, crash-recovery
re-creations, and the prefix-cache segment region from
:meth:`alloc_region` — is placed with it, so pool slabs and region
slabs stay interchangeable under the same dynamic-slice programs.
"""

from __future__ import annotations

import heapq
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.analysis.sanitizers import note_access, wrap_lock

from deeplearning4j_tpu.models.transformer import (
    TransformerConfig,
    _decode_builder,
    full_cache_leaf,
)


class KVSlotPool:
    """Free-list of decode-cache slots over one device allocation.

    ``caches`` is the live pytree (an array, or ``{"kv", "scale"}`` in
    int8-cache mode). The engine's jitted steps consume and return it
    functionally; with buffer donation the update is in place.
    """

    is_paged = False  # layout flag consumers branch on (PrefixCache)

    def __init__(self, cfg: TransformerConfig, n_slots: int, max_total: int,
                 sharding=None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        _, init_caches, _, _ = _decode_builder(cfg)
        self._init_caches = init_caches
        self._max_total = max_total
        self._sharding = sharding
        # shape-only pass first: the slab geometry (Tpad row count) is
        # needed before allocation so subclasses can size their own
        # layout from it in ``_alloc_caches`` (PagedKVPool carves the
        # same rows into blocks)
        shapes = jax.eval_shape(
            lambda: init_caches(n_slots, max_total)
        )
        self.n_slots = n_slots
        # rounded-up row count per slot, of the leaf that runs to
        # max_total (a ring leaf is shorter)
        self.tpad = full_cache_leaf(shapes).shape[3]
        self.caches = self._alloc_caches()
        # acquire/release/generation run on the engine thread while
        # n_free/n_active/occupancy feed metrics gauges scraped from
        # the sidecar thread — free-list bookkeeping moves under the
        # lock so a scrape never sees the heap mid-rebalance
        self._lock = wrap_lock(threading.Lock(), "pool._lock")
        self._free = list(range(n_slots))  # already a heap; guarded-by: _lock
        self._in_use: set[int] = set()  # guarded-by: _lock
        # per-slot generation, bumped on acquire: with pipelined
        # readback a token block can arrive for a slot that was retired
        # and re-acquired after its dispatch — the generation lets the
        # engine tell the block belongs to the previous occupant
        self._gen = [0] * n_slots  # guarded-by: _lock
        # byte sizes captured ONCE at allocation time (shape/dtype are
        # host metadata): metrics scrapes must never walk the live
        # device pytree (under donation a buffer can be
        # mid-invalidation, and under TP the per-scrape answer must not
        # depend on which shard you ask) — zero device interaction per
        # scrape
        self._nbytes = sum(
            math.prod(x.shape) * x.dtype.itemsize
            for x in jax.tree.leaves(self.caches)
        )
        self._nbytes_per_slot = self._nbytes // n_slots

    def _place(self, caches):
        """Place a fresh allocation with the pool's sharding (identity
        when unsharded)."""
        if self._sharding is None:
            return caches
        return jax.tree.map(jax.device_put, caches, self._sharding)

    def _alloc_caches(self):
        """Allocate the pool's device cache, zeroed and placed — the
        layout hook ``reinit`` and ``__init__`` share (subclasses
        override it to change the layout without touching the slot
        bookkeeping)."""
        return self._place(
            self._init_caches(self.n_slots, self._max_total)
        )

    @property
    def n_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def n_active(self) -> int:
        with self._lock:
            return len(self._in_use)

    @property
    def occupancy(self) -> float:
        """Active fraction of the slot batch this instant, in [0, 1]."""
        with self._lock:
            return len(self._in_use) / self.n_slots

    def acquire(self) -> int:
        """Claim the lowest free slot index."""
        with self._lock:
            note_access("pool.freelist", write=True)
            if not self._free:
                raise RuntimeError("no free KV slots")
            slot = heapq.heappop(self._free)
            self._in_use.add(slot)
            self._gen[slot] += 1
            return slot

    def generation(self, slot: int) -> int:
        """Acquire count for ``slot`` — identifies the current occupant
        across release/re-acquire (see ``_gen`` above)."""
        with self._lock:
            return self._gen[slot]

    def release(self, slot: int) -> None:
        with self._lock:
            note_access("pool.freelist", write=True)
            if slot not in self._in_use:
                raise ValueError(f"slot {slot} is not in use")
            self._in_use.remove(slot)
            heapq.heappush(self._free, slot)

    def alloc_region(self, n_slots: int):
        """A second bounded cache region with the SAME per-slot layout
        as the pool — Tpad row count, dtype, int8 scale planes, and
        (under TP) the same head-axis sharding — so a region slab and a
        pool slab are interchangeable under plain dynamic slices. This
        is how the prefix cache gets its segment store: the pool owns
        the layout, the cache owns the slots."""
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        return self._place(self._init_caches(n_slots, self._max_total))

    def region_nbytes(self, n_slots: int) -> int:
        """Host-metadata byte size of an ``alloc_region(n_slots)``
        allocation (the prefix cache reports this instead of walking
        its live device pytree on metrics scrapes)."""
        return self._nbytes_per_slot * n_slots

    def reinit(self) -> None:
        """Re-create the pooled cache buffers, zeroed (crash recovery:
        after an engine-loop crash the old buffers must be assumed
        corrupt — and with donation they may already be invalidated
        mid-step). Free-list/occupancy bookkeeping is preserved; the
        engine re-prefills every live slot afterwards (see
        ``ServingEngine.recover``)."""
        self.caches = self._alloc_caches()

    def nbytes(self) -> int:
        """Device bytes of the pooled cache (all slots; global logical
        bytes under TP). Precomputed host metadata — never touches the
        live device arrays, so metrics scrapes cost no device sync."""
        return self._nbytes


class PagedKVPool(KVSlotPool):
    """Block-paged KV pool: one shared device pool of fixed-size blocks
    plus a host-side per-slot int32 block table (vLLM-style paged
    attention). The slot free-list/generation machinery is inherited
    unchanged; what changes is the storage behind a slot:

    - ``caches`` leaves are ``(n_layers, 2, n_blocks, block_size, Hkv*K)``
      (plus the ``(..., 1)`` f32 scale planes in int8 mode) instead of
      per-slot Tpad slabs;
    - slot ``s`` owns the rows named by ``tables()[s]`` — a
      ``blocks_per_slot``-long int32 row where entry ``j`` maps token
      rows ``[j*block_size, (j+1)*block_size)``; unallocated entries
      hold 0, the permanently-zero SENTINEL block (block ids are
      therefore 1-based);
    - admission allocates only ``ceil((prompt+max_new)/block_size)``
      blocks instead of a whole Tpad slab, which is where the capacity
      lift at fixed HBM comes from;
    - blocks are reference-counted: a cached prefix is byte-SHARED by
      aliasing its block ids into a hitting slot's table and bumping
      refcounts (no copy); a block returns to the free heap only when
      its refcount reaches zero.

    Block ids are handed out lowest-id-first (a heap, like the slot
    free list) so allocation order is deterministic — the paged
    extensions of the free-list determinism tests rely on it.

    ``block_size`` must be a power of two dividing Tpad; keeping it a
    multiple of the engine's admission grain (8 rows) makes every
    grain-aligned partial-prefix hit block-aligned, so hits are pure
    aliasing. On TPU the natural size is the flash-decode kernel's time
    tile (512 for the >=1k-context Tpad grain).
    """

    is_paged = True

    def __init__(self, cfg: TransformerConfig, n_slots: int,
                 max_total: int, sharding=None, *, block_size: int = 8,
                 n_blocks: int | None = None):
        bs = int(block_size)
        if bs < 1 or bs & (bs - 1):
            raise ValueError(
                f"block_size must be a power of two, got {block_size}"
            )
        self.block_size = bs
        self._requested_blocks = n_blocks
        super().__init__(cfg, n_slots, max_total, sharding)
        # host-side paging state (same lock as the slot free list —
        # metrics gauges scrape block occupancy from a sidecar thread)
        self._tables = np.zeros(
            (n_slots, self.blocks_per_slot), np.int32
        )  # guarded-by: _lock
        self._refs = np.zeros((self.n_blocks,), np.int32)  # guarded-by: _lock
        self._refs[0] = 1  # zero sentinel: permanently pinned
        self._free_blocks = list(range(1, self.n_blocks))  # heap; guarded-by: _lock

    def _alloc_caches(self):
        if self.block_size > self.tpad or self.tpad % self.block_size:
            raise ValueError(
                f"block_size {self.block_size} does not divide the "
                f"slab row count Tpad={self.tpad}"
            )
        self.blocks_per_slot = self.tpad // self.block_size
        # default capacity matches the slab pool exactly (plus the
        # sentinel), so a paged pool can always hold what the slab pool
        # held; callers oversubscribe by passing a smaller n_blocks or
        # raise n_slots at the same n_blocks
        self.n_blocks = (
            self._requested_blocks if self._requested_blocks is not None
            else self.n_slots * self.blocks_per_slot + 1
        )
        if self.n_blocks < 2:
            raise ValueError(
                f"n_blocks must be >= 2 (sentinel + one allocatable "
                f"block), got {self.n_blocks}"
            )
        shapes = jax.eval_shape(
            lambda: self._init_caches(1, self._max_total)
        )
        return self._place(jax.tree.map(
            lambda s: jnp.zeros(
                (s.shape[0], s.shape[1], self.n_blocks,
                 self.block_size, s.shape[4]),
                s.dtype,
            ),
            shapes,
        ))

    # -- block accounting --------------------------------------------------

    def block_nbytes(self) -> int:
        """Host-metadata byte size of ONE block across all cache leaves
        (the prefix cache reports its footprint from block counts
        instead of walking live device arrays)."""
        return self._nbytes // self.n_blocks

    def blocks_needed(self, n_tokens: int) -> int:
        """Blocks covering ``n_tokens`` rows (every row a request can
        ever write — admission sizes this as prompt + max_new)."""
        return -(-max(0, int(n_tokens)) // self.block_size)

    @property
    def n_free_blocks(self) -> int:
        with self._lock:
            return len(self._free_blocks)

    @property
    def n_blocks_in_use(self) -> int:
        """Allocated blocks (sentinel excluded)."""
        with self._lock:
            return self.n_blocks - 1 - len(self._free_blocks)

    def can_admit(self, n_tokens: int) -> bool:
        """Whether the free heap covers a fresh ``n_tokens``-row
        allocation (the paged admission gate)."""
        return self.blocks_needed(n_tokens) <= self.n_free_blocks

    def table(self, slot: int) -> np.ndarray:
        """Snapshot of one slot's block-table row."""
        with self._lock:
            return self._tables[slot].copy()

    def tables(self) -> np.ndarray:
        """Snapshot of the whole (n_slots, blocks_per_slot) table."""
        with self._lock:
            return self._tables.copy()

    def slot_blocks(self, slot: int) -> list[int]:
        """The non-sentinel block ids a slot's table names, in table
        order."""
        with self._lock:
            return [int(b) for b in self._tables[slot] if b]

    def refcount(self, block_id: int) -> int:
        with self._lock:
            return int(self._refs[block_id])

    # -- allocation / sharing ----------------------------------------------

    def alloc_slot_blocks(self, slot: int, n_tokens: int,
                          start: int = 0) -> list[int]:
        """Allocate private blocks for table entries
        ``[start, blocks_needed(n_tokens))`` of ``slot`` (lowest block
        id first) and return them. ``start`` > 0 is the partial-hit
        path: the first ``start`` entries were aliased from a cached
        segment and stay untouched. Raises ``RuntimeError`` when the
        free heap cannot cover the allocation (callers gate admission
        on :meth:`can_admit`)."""
        k = self.blocks_needed(n_tokens)
        if k > self.blocks_per_slot:
            raise RuntimeError(
                f"{n_tokens} rows need {k} blocks, slot tables hold "
                f"{self.blocks_per_slot}"
            )
        with self._lock:
            note_access("pool.blockmap", write=True)
            need = max(0, k - start)
            if need > len(self._free_blocks):
                raise RuntimeError("no free KV blocks")
            out = []
            for j in range(start, k):
                bid = heapq.heappop(self._free_blocks)
                self._refs[bid] = 1
                self._tables[slot, j] = bid
                out.append(bid)
            return out

    def alias_into_slot(self, slot: int, block_ids, start: int = 0
                        ) -> None:
        """Byte-share existing blocks into ``slot``'s table entries
        ``[start, start+len(block_ids))``: a refcount bump, zero device
        work. This is how a prefix-cache hit lands its cached rows."""
        with self._lock:
            note_access("pool.blockmap", write=True)
            for j, bid in enumerate(block_ids):
                self._refs[bid] += 1
                self._tables[slot, start + j] = bid

    def alloc_blocks(self, k: int) -> list[int]:
        """Allocate ``k`` blocks owned by no slot (refcount 1) — the
        prefix cache's segment storage. Freed via :meth:`decref`."""
        with self._lock:
            note_access("pool.blockmap", write=True)
            if k > len(self._free_blocks):
                raise RuntimeError("no free KV blocks")
            out = [heapq.heappop(self._free_blocks) for _ in range(k)]
            for bid in out:
                self._refs[bid] = 1
            return out

    def incref(self, block_ids) -> None:
        with self._lock:
            note_access("pool.blockmap", write=True)
            for bid in block_ids:
                self._refs[bid] += 1

    def decref(self, block_ids) -> None:
        """Drop one reference per id; blocks reaching zero return to
        the free heap (eviction frees blocks, not slabs)."""
        with self._lock:
            note_access("pool.blockmap", write=True)
            for bid in block_ids:
                self._refs[bid] -= 1
                if self._refs[bid] == 0:
                    heapq.heappush(self._free_blocks, int(bid))

    def release(self, slot: int) -> None:
        """Slot free-list release plus block teardown: every non-
        sentinel table entry drops one reference (shared prefix blocks
        survive under their other holders; private blocks return to the
        heap) and the table row resets to the sentinel."""
        super().release(slot)
        with self._lock:
            note_access("pool.blockmap", write=True)
            for bid in self._tables[slot]:
                if bid:
                    self._refs[bid] -= 1
                    if self._refs[bid] == 0:
                        heapq.heappush(self._free_blocks, int(bid))
            self._tables[slot] = 0

    def reinit(self) -> None:
        """Crash recovery: re-create the block pool zeroed and reset
        ALL paging state — tables, refcounts, free heap. Slot
        free-list/occupancy bookkeeping is preserved (the engine
        re-allocates blocks while re-prefilling each live slot)."""
        super().reinit()
        with self._lock:
            self._tables[:] = 0
            self._refs[:] = 0
            self._refs[0] = 1
            self._free_blocks = list(range(1, self.n_blocks))
