"""Block (paged) KV cache for the continuous-batching engine: the host-side
``BlockAllocator`` plus the physical ``PagedKVStore``.

vLLM-style paging: cache HBM is divided into fixed-size blocks, each
admitted request owns a per-slot block table that grows one block at a time
as it decodes, and every block is reclaimed when the request finishes (EOS
or max-tokens).  The allocator is what makes admission control and the
cache-pressure telemetry real: the scheduler refuses to admit a request
whose worst case cannot fit, and ``ServeTelemetry`` reports
``blocks_in_use / n_blocks`` (and, with a physical store attached, resident
HBM bytes) to the scheduling assistants (paper §3) as serving memory
pressure.

A model's layers are partitioned into *cache groups* (``CacheLayout``,
built by the engine from ``models.lm.serve_groups``):

* **global** — global-attention K/V (and MLA latents): per-slot block
  tables that grow with the context, the original paging regime.
* **window** — sliding-window attention: a per-slot *block ring* indexed by
  logical block; blocks that fall fully behind ``pos - window`` are freed
  back to the pool and the published table entry becomes the null page, so
  a window lane pins O(window) blocks regardless of generated length.
* **recurrent** — ssd/rglru scan state: O(1) per-slot state slabs, no
  blocks at all; the allocator accounts these slots (and their bytes)
  separately from paged blocks.
* **cross** — enc-dec cross-attention K/V: a per-slot *static block set*
  sized for exactly ``frontend_tokens`` rows, allocated in full at
  admission (priced by ``can_allocate`` alongside the decoder groups, so
  admission can never deadlock on it), written once by the
  encode-at-admission step, never extended, and freed at retirement.
  Cross residency is therefore flat for the lifetime of a request.

A modality frontend (VLM) needs no group of its own: its projected rows
prepend the decoder sequence, so the layout's ``frontend_extra`` simply
widens the global/window price of every admission by ``frontend_tokens``
physical rows.

**Prefix cache** (``CacheLayout.sharable``): global-group blocks are
*content-addressed* — every full prompt block is identified by a hash
chain ``h_i = H(h_{i-1}, token_ids_i)`` (``models.lm.prompt_block_hashes``)
and refcounted.  At admission the allocator matches the longest cached
chain prefix and hands those physical blocks to the new slot read-only
(prefill then starts at the first uncached block); when a prefill
completes, ``commit_slot`` publishes the slot's full prompt blocks into
the index.  ``free_slot`` decrements refcounts instead of freeing:
refcount-zero committed blocks park in an LRU *cached* pool that still
counts as allocatable capacity — ``_claim`` evicts LRU cached blocks
(and their index entries) only when the free list runs dry, and never a
block with a live reference.  A write into a shared or indexed block
must copy-on-write first (``ensure_private``); partial tail blocks are
always private, so the only CoW site is the recompute of the last
prompt position on a full-prompt-aligned hit.  Sharability is per
group: global (and MLA-latent) blocks are sharable; window rings,
recurrent state slabs, and enc-dec cross sets are not (their content is
not a pure function of the token prefix).

**Failure taxonomy**: expected capacity backpressure raises
``CacheExhausted`` (a ``MemoryError`` subclass — schedulers catch it and
wait or preempt), while contract violations raise
``AllocatorInvariantError`` (an ``AssertionError`` subclass — a real bug,
never caught by admission control).

Two layers:

* ``BlockAllocator`` — pure host bookkeeping (free list + per-slot group
  tables); runs between device steps, no jax in the hot path.
* ``PagedKVStore`` — a pair of physical page pools of shape
  ``[n_layers, n_blocks + 1, block_size, width]`` the tables index into
  (the extra trailing page is the *null block*: inactive decode lanes,
  padded table tails, and freed-behind-window ring entries point at it, so
  their writes land harmlessly and their reads are masked).  Attention
  leaves pair K/V pools; MLA leaves pair ckv/krope latent pools.  The
  engine threads the pools through its jitted steps and rebinds the store
  afterwards; ``write_token``/``gather_slot`` are the standalone host-side
  APIs (tests, debugging, residency accounting).
"""

from __future__ import annotations

import math
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional


class CacheError(Exception):
    """Base class for the allocator's typed failures."""


class CacheExhausted(CacheError, MemoryError):
    """Expected capacity backpressure: the pool cannot satisfy this claim
    right now.  Admission control treats this as "wait for blocks" (break
    out of the admit loop) and the engine's decode path as "preempt the
    youngest slot and requeue it" — it is never a bug.  Subclasses
    ``MemoryError`` so pre-existing ``except MemoryError`` call sites keep
    working."""


class AllocatorInvariantError(CacheError, AssertionError):
    """A broken allocator invariant (double allocate, double free, shrink,
    refcount corruption, leaked blocks): a real bug in the caller or the
    allocator itself.  Deliberately *not* a ``MemoryError`` subclass so the
    scheduler's break-on-full path can never swallow corruption."""


@dataclass(frozen=True)
class CacheConfig:
    """Block pool geometry: ``n_blocks`` blocks of ``block_size`` tokens."""

    block_size: int = 16
    n_blocks: int = 256

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cache entries."""
        return max(0, -(-n_tokens // self.block_size))

    @property
    def null_block(self) -> int:
        """Physical id of the scratch page (one past the allocatable pool)."""
        return self.n_blocks


@dataclass(frozen=True)
class CacheLayout:
    """Which cache groups a model's layers need, in allocator terms.

    Built by the engine from the per-layer capability report
    (``models.lm.serve_groups``) and installed with
    ``BlockAllocator.set_layout``; the default describes the original
    global-only regime, which is also what the dense (accounting-only)
    engine uses.  ``window_cap_blocks`` is the admission price of one
    window ring: the most blocks a lane can pin simultaneously
    (``blocks_for(window) + 1``, plus the in-flight chunk during chunked
    prefill).  ``state_slots``/``state_bytes_per_slot`` describe the
    recurrent lanes, accounted separately from paged blocks.
    ``cross_tokens``/``cross_cap_blocks`` describe the enc-dec static
    cross block set (allocated whole at admission, never extended);
    ``frontend_extra`` widens every admission's global/window price by the
    VLM frontend rows that share the decoder cache.  ``sharable`` enables
    the content-addressed prefix cache over the *global* group only —
    the engine sets it when every layer's cache content is a pure
    function of the token prefix (``models.lm.prefix_sharable_reason``
    is None): window rings, recurrent slabs, and cross sets are never
    shared, and frontend rows disqualify the whole arch."""

    has_global: bool = True
    window: int = 0                  # sliding-window width (0 = no group)
    window_cap_blocks: int = 0
    state_slots: int = 0             # recurrent lanes (0 = no group)
    state_bytes_per_slot: int = 0
    prefill_chunk: int = 0           # chunked prefill (window rings start
                                     # at block 0 and slide with the chunks)
    cross_tokens: int = 0            # enc-dec cross-KV rows (0 = no group)
    cross_cap_blocks: int = 0        # static per-slot cross block set size
    frontend_extra: int = 0          # VLM frontend rows resident in the
                                     # decoder cache on top of every
                                     # admission's logical token count
    sharable: bool = False           # content-addressed prefix reuse over
                                     # the global group (see class doc)


class PagedKVStore:
    """Physical paged storage for a stack of layers of one cache group.

    Owns a pair of page pools ``k_pages``/``v_pages`` of shape
    ``[n_layers, n_blocks + 1, block_size, width]`` — attention leaves pair
    lane-dense K/V rows (``width = n_kv_heads * head_dim``), MLA leaves
    pair ckv/krope latent rows (the two pools may have different widths;
    a krope row is zero-padded to whole 128-lane rows).
    Page ``n_blocks`` is the null block (see module docstring).  All
    updates are functional — methods replace ``self.k_pages``/``self.v_pages``
    with the updated arrays, so a store can also be *rebound* to pool
    arrays produced inside a jitted engine step (``from_pools`` /
    ``rebind``).
    """

    def __init__(self, config: CacheConfig, n_layers: int, n_kv_heads: int,
                 head_dim: int, dtype=None):
        import jax.numpy as jnp
        dtype = dtype or jnp.float32
        shape = (n_layers, config.n_blocks + 1, config.block_size,
                 n_kv_heads * head_dim)
        self.config = config
        self.k_pages = jnp.zeros(shape, dtype)
        self.v_pages = jnp.zeros(shape, dtype)

    @classmethod
    def from_pools(cls, config: CacheConfig, k_pages, v_pages) -> "PagedKVStore":
        """Wrap existing pool arrays (e.g. a leaf of the engine's cache tree)."""
        store = cls.__new__(cls)
        store.config = config
        store.rebind(k_pages, v_pages)
        return store

    def rebind(self, k_pages, v_pages) -> None:
        assert k_pages.shape[:3] == v_pages.shape[:3], (k_pages.shape,
                                                        v_pages.shape)
        assert k_pages.shape[1] == self.config.n_blocks + 1, k_pages.shape
        assert k_pages.shape[2] == self.config.block_size, k_pages.shape
        self.k_pages = k_pages
        self.v_pages = v_pages

    # -- geometry ---------------------------------------------------------------
    @property
    def n_layers(self) -> int:
        return self.k_pages.shape[0]

    @property
    def block_bytes(self) -> int:
        """HBM bytes one block id pins across all layers (both pools),
        from the pools' shapes and types alone: indexing a pool would run
        a program on the device."""
        return sum(math.prod(p.shape) // p.shape[1] * p.dtype.itemsize
                   for p in (self.k_pages, self.v_pages))

    @property
    def capacity_bytes(self) -> int:
        return self.config.n_blocks * self.block_bytes

    # -- physical access ---------------------------------------------------------
    def write_token(self, table: list, pos: int, k, v) -> None:
        """Write one token's rows (``[n_layers, *row]``, each row
        flattening to at most its pool's width — ``[n_layers, KV, hd]`` for
        K/V; a narrower row fills the leading columns) at logical position
        ``pos`` of the lane backed by ``table``."""
        block = table[pos // self.config.block_size]
        off = pos % self.config.block_size
        L = self.n_layers
        self.k_pages = self.k_pages.at[:, block, off, :k[0].size].set(
            k.reshape(L, -1))
        self.v_pages = self.v_pages.at[:, block, off, :v[0].size].set(
            v.reshape(L, -1))

    def gather_slot(self, table: list, context_len: int):
        """Reconstruct the lane's logical rows: ``[n_layers, context_len,
        width]`` each, gathered through ``table``."""
        import jax.numpy as jnp
        idx = jnp.asarray(table, jnp.int32)
        L = self.n_layers
        k = self.k_pages[:, idx].reshape(
            L, -1, self.k_pages.shape[3])[:, :context_len]
        v = self.v_pages[:, idx].reshape(
            L, -1, self.v_pages.shape[3])[:, :context_len]
        return k, v


class BlockAllocator:
    """Free-list block allocator with per-slot, per-group block tables.

    The installed ``CacheLayout`` decides what an admission claims: a
    growing **global** table (``tables``), a sliding **window** block ring
    (``window_tables``: logical block -> physical block), a static
    **cross** block set (``cross_tables``: enc-dec cross-KV, fixed length
    for the request's lifetime), and/or a **recurrent state slot** — all
    drawn from (and accounted against) the same pool, so admission
    control and the cache-pressure telemetry see every group.  The
    default layout is global-only (the original regime).

    Every global-table entry is *refcounted* — with a ``sharable`` layout
    one physical block may back several slots' tables (prefix reuse) and
    may outlive all of them in the LRU cached pool (see module
    docstring).  Three block states: **free** (on ``_free``), **cached**
    (committed content, refcount 0, LRU-evictable — still allocatable
    capacity), **live** (refcount >= 1).  Capacity failures raise
    ``CacheExhausted``; caller bugs raise ``AllocatorInvariantError``.

    Admissions may carry a *worst-case reservation*
    (``reserve_tokens=prompt + max_new``): the outstanding (reserved but
    not yet claimed) blocks of every live slot are subtracted from what
    ``can_allocate`` will promise to the next admission, and a slot's own
    ``extend``s draw down its reservation — so a reserving scheduler can
    never see a mid-decode ``CacheExhausted``.

    Optionally carries attached ``PagedKVStore``s tagged with their group
    (the engine attaches one per pool leaf); the allocator then reports
    physical residency in bytes — per group via ``resident_bytes_by_group``
    — and ``write_token``/``gather_slot`` resolve a slot's global table
    against the first store.
    """

    def __init__(self, config: CacheConfig,
                 store: Optional[PagedKVStore] = None):
        self.config = config
        self.layout = CacheLayout()
        # LIFO free list: reclaimed blocks are reused first (cache-friendly)
        self._free: list[int] = list(range(config.n_blocks - 1, -1, -1))
        # slot -> ordered block ids backing that slot's global cache lane
        self.tables: dict[int, list[int]] = {}
        # slot -> tokens currently resident (drives the growth math)
        self._tokens: dict[int, int] = {}
        # slot -> {logical block index: physical block} window ring
        self.window_tables: dict[int, dict[int, int]] = {}
        # slot -> static cross-KV block set (fixed length, never extended)
        self.cross_tables: dict[int, list[int]] = {}
        self._state_slots: set[int] = set()
        self._group_in_use: dict[str, int] = {"global": 0, "window": 0,
                                              "cross": 0}
        # -- prefix cache / refcount state (global group only) ------------
        self._ref: dict[int, int] = {}           # live block -> refcount
        self._hash_of: dict[int, str] = {}       # committed block -> hash
        self._index: dict[str, int] = {}         # content hash -> block
        self._cached: OrderedDict[int, int] = OrderedDict()  # LRU ref-0
        self._tick = 0                           # LRU recency counter
        self._slot_hashes: dict[int, tuple] = {}  # slot -> prompt chain
        # slot -> tokens served from the index at admission (engine reads
        # this to start prefill at the first uncached position)
        self.matched_tokens: dict[int, int] = {}
        self._reserve: dict[int, int] = {}       # slot -> reserved blocks
        self.stats: dict[str, int] = {
            "admissions": 0, "hit_admissions": 0, "lookup_tokens": 0,
            "hit_tokens": 0, "commits": 0, "evictions": 0, "cow_forks": 0}
        self.stores: list[PagedKVStore] = []
        self.store_groups: list[str] = []
        if store is not None:
            self.attach_store(store)

    def set_layout(self, layout: CacheLayout) -> None:
        """Install the engine's cache-group layout (before any admission)."""
        if self.tables or self.window_tables or self.cross_tables or \
                self._state_slots or self._cached:
            raise ValueError("cannot change layout with live allocations "
                             "or cached prefix blocks")
        self.layout = layout

    # -- queries ----------------------------------------------------------------
    @property
    def n_blocks(self) -> int:
        return self.config.n_blocks

    @property
    def n_free(self) -> int:
        """Allocatable blocks: truly free plus refcount-0 cached blocks
        (the prefix cache is reclaimable capacity, not pressure)."""
        return len(self._free) + len(self._cached)

    @property
    def n_in_use(self) -> int:
        return self.config.n_blocks - self.n_free

    def pressure(self) -> float:
        """Fraction of the block pool currently allocated, in [0, 1]."""
        return self.n_in_use / self.config.n_blocks if self.config.n_blocks else 0.0

    def blocks_needed(self, n_tokens: int,
                      reserve_tokens: Optional[int] = None) -> int:
        """Admission price of ``n_tokens`` logical tokens across block
        groups: global tables grow with the context (plus the layout's
        ``frontend_extra`` physical rows a VLM admission brings along); a
        window ring is capped at ``layout.window_cap_blocks`` regardless
        of length; an enc-dec cross block set costs its full static size
        up front — pricing it here is what keeps admission deadlock-free
        (a request can never be admitted without room for its whole
        cross KV).  ``reserve_tokens`` prices the request's *worst case*
        (prompt + max_new_tokens) instead of its prefill footprint."""
        phys = max(n_tokens, reserve_tokens or 0) + self.layout.frontend_extra
        need = 0
        if self.layout.has_global:
            need += self.config.blocks_for(phys)
        if self.layout.window:
            need += min(self.config.blocks_for(phys),
                        self.layout.window_cap_blocks)
        if self.layout.cross_tokens:
            need += self.layout.cross_cap_blocks
        return need

    def outstanding_blocks(self) -> int:
        """Blocks promised to live reserving slots but not yet claimed:
        the remaining global-table growth of each reservation, plus the
        window-ring headroom up to the cap for reserving slots."""
        out = 0
        for slot, reserved in self._reserve.items():
            out += max(0, reserved - len(self.tables.get(slot, ())))
            if self.layout.window and slot in self.window_tables:
                out += max(0, self.layout.window_cap_blocks
                           - len(self.window_tables[slot]))
        return out

    def n_available(self) -> int:
        """Blocks the next admission may be promised: allocatable minus
        every live reservation's outstanding growth."""
        return self.n_free - self.outstanding_blocks()

    def can_allocate(self, n_tokens: int,
                     reserve_tokens: Optional[int] = None) -> bool:
        if self.layout.state_slots and \
                len(self._state_slots) >= self.layout.state_slots:
            return False
        return self.blocks_needed(n_tokens, reserve_tokens) \
            <= self.n_available()

    def state_slots_in_use(self) -> int:
        return len(self._state_slots)

    # -- lifecycle ---------------------------------------------------------------
    def _claim(self, n: int, what: str) -> list[int]:
        """Pop ``n`` blocks: the free list first, then LRU eviction of
        refcount-0 cached blocks (dropping their index entries)."""
        if n > self.n_free:
            raise CacheExhausted(
                f"need {n} blocks for {what}, {self.n_free} allocatable "
                f"({len(self._free)} free + {len(self._cached)} cached)")
        got = []
        for _ in range(max(0, n)):
            got.append(self._free.pop() if self._free else self._evict_lru())
        return got

    def _evict_lru(self) -> int:
        """Evict the least-recently-used cached block from the prefix
        index.  Children of the evicted block's chain may stay indexed —
        they become unreachable for matching (a chain lookup stops at the
        first miss) and age out of the LRU on their own."""
        block, _ = self._cached.popitem(last=False)
        if self._ref.get(block):
            raise AllocatorInvariantError(
                f"cached block {block} has refcount {self._ref[block]}")
        h = self._hash_of.pop(block)
        if self._index.get(h) == block:
            del self._index[h]
        self.stats["evictions"] += 1
        return block

    def _retain(self, block: int) -> None:
        """Add one live reference to a global-group block (revives it out
        of the cached pool on the 0 -> 1 transition)."""
        r = self._ref.get(block, 0)
        if r == 0:
            self._cached.pop(block, None)
            self._group_in_use["global"] += 1
        self._ref[block] = r + 1

    def _release(self, block: int) -> None:
        """Drop one live reference; at refcount 0 a committed block parks
        in the LRU cached pool, an uncommitted one returns to the free
        list."""
        r = self._ref.get(block)
        if r is None:
            raise AllocatorInvariantError(
                f"block {block} released with no live reference "
                "(double free?)")
        if r > 1:
            self._ref[block] = r - 1
            return
        del self._ref[block]
        self._group_in_use["global"] -= 1
        if block in self._hash_of:
            self._tick += 1
            self._cached[block] = self._tick
        else:
            self._free.append(block)

    def allocate(self, slot: int, n_tokens: int, *,
                 reserve_tokens: Optional[int] = None,
                 block_hashes=None) -> list[int]:
        """Claim every group's resources for a newly admitted request
        occupying ``slot``; returns the global block ids (empty when the
        layout has no global layers).  ``n_tokens`` is the request's
        logical count (prompt + first generated token); the per-slot token
        ledger is kept in *physical* rows, i.e. with ``frontend_extra``
        folded in, so the engine's later ``extend`` calls (which pass
        physical resident rows) line up.

        ``reserve_tokens`` (worst-case pricing) records a reservation of
        ``blocks_for(reserve + frontend_extra)`` global blocks plus the
        window cap, guaranteeing the slot's own ``extend``s up to that
        total can never raise ``CacheExhausted``.

        ``block_hashes`` (sharable layouts) is the prompt's content hash
        chain: the longest indexed prefix is mapped read-only into the
        head of the slot's table, ``matched_tokens[slot]`` records how
        many tokens that covers, and only the remaining blocks are
        claimed fresh.  The prompt always needs at least one block past
        its full-block chain (for position ``prompt_len`` onward), so the
        tail is private by construction."""
        if slot in self.tables:
            raise AllocatorInvariantError(
                f"slot {slot} already has an allocation")
        if not self.can_allocate(n_tokens, reserve_tokens):
            raise CacheExhausted(
                f"need {self.blocks_needed(n_tokens, reserve_tokens)} blocks "
                f"for {n_tokens} tokens, {self.n_available()} available "
                f"({self.n_free} allocatable, "
                f"{self.outstanding_blocks()} reserved)")
        phys = n_tokens + self.layout.frontend_extra
        need = self.config.blocks_for(phys) if self.layout.has_global else 0
        self.stats["admissions"] += 1
        table: list[int] = []
        if block_hashes and self.layout.sharable and self.layout.has_global:
            for h in block_hashes:
                block = self._index.get(h)
                if block is None or len(table) >= need:
                    break
                table.append(block)
            self.stats["lookup_tokens"] += \
                len(block_hashes) * self.config.block_size
            self.stats["hit_tokens"] += len(table) * self.config.block_size
            if table:
                self.stats["hit_admissions"] += 1
            for block in table:
                self._retain(block)
        matched = len(table)
        fresh = self._claim(need - matched, f"slot {slot}")
        for block in fresh:
            self._retain(block)
        table.extend(fresh)
        self.tables[slot] = table
        self._tokens[slot] = phys
        self.matched_tokens[slot] = matched * self.config.block_size
        self._slot_hashes[slot] = tuple(block_hashes or ())
        if reserve_tokens is not None and self.layout.has_global:
            self._reserve[slot] = self.config.blocks_for(
                reserve_tokens + self.layout.frontend_extra)
        if self.layout.window:
            self._allocate_window(slot, phys)
            if reserve_tokens is not None:
                self._reserve.setdefault(slot, 0)
        if self.layout.cross_tokens:
            cross = self._claim(self.layout.cross_cap_blocks,
                                f"slot {slot} cross block set")
            self.cross_tables[slot] = cross
            self._group_in_use["cross"] += len(cross)
        if self.layout.state_slots:
            self._state_slots.add(slot)
        return list(self.tables[slot])

    def _allocate_window(self, slot: int, n_tokens: int) -> None:
        """Initial window ring: whole-prompt prefill lands only the last
        ``window`` positions in the ring, so cover the blocks holding
        ``[max(0, p - window + 1), p]``; chunked prefill starts at block 0
        and slides forward with the chunks (``extend_window``)."""
        bs, W = self.config.block_size, self.layout.window
        if self.layout.prefill_chunk:
            p = min(self.layout.prefill_chunk, n_tokens) - 1
            lo = 0
        else:
            p = n_tokens - 1
            lo = max(0, p - W + 1) // bs
        blocks = self._claim(p // bs - lo + 1, f"slot {slot} window ring")
        self.window_tables[slot] = {lo + i: b for i, b in enumerate(blocks)}
        self._group_in_use["window"] += len(blocks)

    def extend(self, slot: int, n_tokens_total: int) -> list[int]:
        """Grow ``slot``'s global table to cover ``n_tokens_total`` resident
        tokens.

        Returns the newly claimed block ids (usually empty — a new block is
        only needed every ``block_size`` decode steps).  Growth within the
        slot's own reservation always succeeds; growth beyond it (lazy
        pricing) must fit in the unreserved headroom, else
        ``CacheExhausted`` — the engine's cue to preempt a slot."""
        if slot not in self.tables:
            raise AllocatorInvariantError(f"slot {slot} has no allocation")
        if n_tokens_total < self._tokens[slot]:
            raise AllocatorInvariantError(
                f"slot {slot}: cannot shrink {self._tokens[slot]} -> {n_tokens_total}")
        need = self.config.blocks_for(n_tokens_total) - len(self.tables[slot])
        if not self.layout.has_global:
            need = 0
        if need > 0:
            own = max(0, self._reserve.get(slot, 0) - len(self.tables[slot]))
            extra = max(0, need - own)
            if extra > self.n_available():
                raise CacheExhausted(
                    f"slot {slot}: needs {need} more blocks ({extra} beyond "
                    f"its reservation), {self.n_available()} available "
                    f"({self.n_free} allocatable, "
                    f"{self.outstanding_blocks()} reserved)")
        fresh = self._claim(max(0, need), f"slot {slot}")
        for block in fresh:
            self._retain(block)
        self.tables[slot].extend(fresh)
        self._tokens[slot] = n_tokens_total
        return fresh

    def extend_window(self, slot: int, n_tokens_total: int,
                      first_query_pos: Optional[int] = None) -> tuple:
        """Slide ``slot``'s window ring forward to cover position
        ``n_tokens_total - 1``: claim blocks up to its logical block, free
        every block that has fallen fully behind
        ``first_query_pos - window`` (default: the covered position itself —
        the decode case; chunked prefill passes the chunk's first row so
        earlier in-chunk queries keep their window).  Returns
        ``(fresh, freed)`` physical block id lists; a non-empty either means
        the published table row must be rebuilt."""
        if slot not in self.window_tables:
            raise AllocatorInvariantError(f"slot {slot} has no window ring")
        bs, W = self.config.block_size, self.layout.window
        ring = self.window_tables[slot]
        p = n_tokens_total - 1
        fq = p if first_query_pos is None else first_query_pos
        lo = max(0, fq - W + 1) // bs
        freed = [ring.pop(i) for i in sorted(ring) if i < lo]
        self._free.extend(reversed(freed))
        self._group_in_use["window"] -= len(freed)
        hi = p // bs
        cur_hi = max(ring, default=lo - 1)
        n_claim = max(0, hi - cur_hi)
        if n_claim and slot not in self._reserve \
                and n_claim > self.n_available():
            # a reserving slot's ring headroom is pre-counted in
            # outstanding_blocks(); an unreserved (lazy) slot must not eat
            # into other slots' reservations
            raise CacheExhausted(
                f"slot {slot}: window ring needs {n_claim} more blocks, "
                f"{self.n_available()} available")
        fresh = self._claim(n_claim, f"slot {slot} window ring")
        for i, b in enumerate(fresh):
            ring[cur_hi + 1 + i] = b
        self._group_in_use["window"] += len(fresh)
        return fresh, freed

    def truncate(self, slot: int, n_tokens_total: int) -> list[int]:
        """Shrink ``slot``'s global table to cover ``n_tokens_total``
        resident tokens — the speculative-decode rewind path for rejected
        draft tokens.  Frees whole tail blocks only; a partially-vacated
        tail block stays claimed (its stale rows sit beyond the slot's
        position, so the attention mask never reads them and the next
        accepted token overwrites them).  Returns the freed physical ids.

        Rewinding must never touch content visible beyond the slot: a
        shared or prefix-indexed block in the dropped tail is an
        ``AllocatorInvariantError`` (decode tails are always private —
        admission CoW forks the boundary block before the first decode
        write, and rewind never reaches back into the committed prompt)."""
        if slot not in self.tables:
            raise AllocatorInvariantError(f"slot {slot} has no allocation")
        if n_tokens_total > self._tokens[slot]:
            raise AllocatorInvariantError(
                f"slot {slot}: truncate cannot grow "
                f"{self._tokens[slot]} -> {n_tokens_total}")
        table = self.tables[slot]
        keep = self.config.blocks_for(n_tokens_total) if self.layout.has_global \
            else len(table)
        for idx in range(keep, len(table)):
            if self.is_block_shared(slot, idx):
                raise AllocatorInvariantError(
                    f"slot {slot}: rewind would drop shared/indexed block "
                    f"{table[idx]} (table entry {idx})")
        freed = table[keep:]
        del table[keep:]
        # reversed: freed tail blocks re-enter the LIFO free list so the
        # next growth reclaims them first, in table order
        for block in reversed(freed):
            self._release(block)
        self._tokens[slot] = n_tokens_total
        return freed

    def truncate_window(self, slot: int, n_tokens_total: int) -> list[int]:
        """Rewind ``slot``'s window ring: free ring blocks whose logical
        index lies wholly beyond position ``n_tokens_total - 1``.  The low
        edge is untouched — the speculative round slides it with
        ``first_query_pos`` pinned at the pre-draft position, so every
        block a post-rewind query can attend is still resident.  Returns
        the freed physical ids."""
        if slot not in self.window_tables:
            raise AllocatorInvariantError(f"slot {slot} has no window ring")
        ring = self.window_tables[slot]
        hi = (n_tokens_total - 1) // self.config.block_size
        freed = [ring.pop(i) for i in sorted(ring, reverse=True) if i > hi]
        self._free.extend(freed)
        self._group_in_use["window"] -= len(freed)
        return freed

    def free_slot(self, slot: int) -> int:
        """Reclaim every group's resources owned by ``slot`` (EOS /
        max-tokens).  Global-table entries are *released* (refcount
        decrement): a block still referenced by another slot stays live,
        and a committed refcount-0 block parks in the LRU cached pool
        instead of the free list.  Returns the number of table entries the
        slot relinquished across all groups."""
        if slot not in self.tables:
            raise AllocatorInvariantError(f"slot {slot} has no allocation")
        blocks = self.tables.pop(slot)
        self._tokens.pop(slot)
        self._reserve.pop(slot, None)
        self._slot_hashes.pop(slot, None)
        self.matched_tokens.pop(slot, None)
        # reversed so blocks re-enter the LIFO free list in table order
        # (the next allocation reuses them first, in the same order)
        for block in reversed(blocks):
            self._release(block)
        ring = self.window_tables.pop(slot, None)
        if ring:
            ring_blocks = [ring[i] for i in sorted(ring, reverse=True)]
            self._free.extend(ring_blocks)
            self._group_in_use["window"] -= len(ring_blocks)
            blocks = blocks + ring_blocks
        cross = self.cross_tables.pop(slot, None)
        if cross:
            self._free.extend(reversed(cross))
            self._group_in_use["cross"] -= len(cross)
            blocks = blocks + cross
        self._state_slots.discard(slot)
        return len(blocks)

    # -- prefix cache -----------------------------------------------------------
    def match_tokens(self, block_hashes) -> int:
        """Read-only peek: tokens the longest *indexed* prefix of
        ``block_hashes`` covers right now — no allocation, no refcount
        change, no LRU touch.  This is the router's affinity signal (how
        much of a prompt this replica's pool already holds); 0 on
        non-sharable layouts."""
        if not (self.layout.sharable and self.layout.has_global):
            return 0
        n = 0
        for h in block_hashes or ():
            if h not in self._index:
                break
            n += 1
        return n * self.config.block_size

    def lookup_block(self, block_hash: str) -> Optional[int]:
        """Physical block currently committed under ``block_hash`` (None
        when the content is not resident) — the export side of a
        prefill -> decode block handoff reads pool pages through this."""
        return self._index.get(block_hash)

    def inject_cached(self, block_hashes) -> list[tuple]:
        """Install externally produced committed content into the prefix
        index: for each hash in chain order, claim one block and park it
        directly in the refcount-0 *cached* pool with its hash registered.
        Returns the ``(hash, block)`` pairs newly claimed — the caller
        must copy the physical content into those blocks' pages before
        any admission can match them.

        Hashes already indexed are skipped (their content is resident);
        injection stops at the first hash the pool cannot take
        (``CacheExhausted`` swallowed — a shorter injected chain is
        graceful degradation: the decode replica recomputes the rest).
        Chain-prefix structure is preserved either way, so ``allocate``'s
        longest-prefix matching stays sound.  Requires a sharable layout."""
        if not (self.layout.sharable and self.layout.has_global):
            raise AllocatorInvariantError(
                "inject_cached requires a sharable global layout")
        injected: list[tuple] = []
        own = set()
        for h in block_hashes or ():
            if h in self._index:
                continue
            if not self._free and self._cached and \
                    next(iter(self._cached)) in own:
                # claiming would LRU-evict the head of the chain injected
                # by this very call — a self-cannibalizing injection can
                # never extend the matchable prefix, so stop here
                break
            try:
                block = self._claim(1, f"injected prefix block {h[:12]}")[0]
            except CacheExhausted:
                break
            self._index[h] = block
            self._hash_of[block] = h
            self._tick += 1
            self._cached[block] = self._tick
            injected.append((h, block))
            own.add(block)
        return injected

    def commit_slot(self, slot: int) -> int:
        """Publish ``slot``'s full prompt blocks into the prefix index
        (call once the prompt's K/V is physically resident, i.e. when its
        prefill completes).  Blocks already indexed — the slot's matched
        prefix, or content another slot committed first — are skipped, so
        a hash maps to exactly one physical block.  Returns the number of
        newly indexed blocks.  No-op on non-sharable layouts."""
        if not (self.layout.sharable and self.layout.has_global):
            return 0
        if slot not in self.tables:
            raise AllocatorInvariantError(f"slot {slot} has no allocation")
        fresh = 0
        for h, block in zip(self._slot_hashes.get(slot, ()),
                            self.tables[slot]):
            if self._hash_of.get(block) == h:
                continue                      # already carries this content
            if h in self._index or block in self._hash_of:
                continue                      # content owned elsewhere
            self._index[h] = block
            self._hash_of[block] = h
            fresh += 1
        self.stats["commits"] += fresh
        return fresh

    def is_block_shared(self, slot: int, block_idx: int) -> bool:
        """True when writing ``slot``'s table entry ``block_idx`` would be
        visible beyond the slot: another slot references the block, or the
        prefix index expects its content to stay intact."""
        block = self.tables[slot][block_idx]
        return self._ref.get(block, 0) > 1 or block in self._hash_of

    def ensure_private(self, slot: int, block_idx: int) -> Optional[tuple]:
        """Copy-on-write: give ``slot`` a private block at table entry
        ``block_idx`` if the current one is shared or indexed.  Returns
        ``(src, dst)`` physical ids when forked — the *caller* must copy
        the physical content src -> dst (the allocator's stores may be
        stale while the engine is mid-run) — or None when the entry is
        already private.  The source keeps its index entry, so the cached
        prefix survives the fork."""
        table = self.tables[slot]
        src = table[block_idx]
        if not self.is_block_shared(slot, block_idx):
            return None
        dst = self._claim(1, f"slot {slot} CoW fork")[0]
        self._retain(dst)
        table[block_idx] = dst
        self._release(src)
        self.stats["cow_forks"] += 1
        return src, dst

    def copy_block(self, src: int, dst: int, group: str = "global") -> None:
        """Copy one block's physical content across all of ``group``'s
        attached stores (host-side CoW for tests/debugging; the engine
        copies inside its jitted step instead)."""
        for store, g in zip(self.stores, self.store_groups):
            if g != group:
                continue
            store.k_pages = store.k_pages.at[:, dst].set(store.k_pages[:, src])
            store.v_pages = store.v_pages.at[:, dst].set(store.v_pages[:, src])

    def drop_cached(self) -> int:
        """Evict every refcount-0 cached block back to the free list
        (returns how many) — empties the prefix index of anything not
        currently live."""
        n = 0
        while self._cached:
            self._free.append(self._evict_lru())
            n += 1
        return n

    def cached_blocks(self) -> int:
        return len(self._cached)

    def prefix_stats(self) -> dict:
        """Cumulative prefix-cache counters plus an instantaneous view of
        the pool's sharing state."""
        shared = sum(1 for r in self._ref.values() if r > 1)
        saved = sum(r - 1 for r in self._ref.values() if r > 1)
        return dict(self.stats, cached_blocks=len(self._cached),
                    shared_blocks=shared, saved_blocks=saved,
                    indexed_blocks=len(self._index))

    def shared_saved_bytes(self) -> int:
        """Physical HBM bytes deduplicated right now by prefix sharing:
        each extra reference to a live global block saves one block's
        bytes (0 with no global store attached)."""
        bb = sum(s.block_bytes for s, g in zip(self.stores,
                                               self.store_groups)
                 if g == "global")
        return sum(r - 1 for r in self._ref.values() if r > 1) * bb

    # -- invariants --------------------------------------------------------------
    def check(self) -> None:
        """Full structural invariant check: refcounts equal table
        references, every block is in exactly one of free / cached / live
        / window / cross, the hash index is a bijection onto committed
        blocks, cached blocks have refcount 0, and reservations never
        exceed the allocatable pool."""
        refs: dict[int, int] = {}
        for table in self.tables.values():
            for block in table:
                refs[block] = refs.get(block, 0) + 1
        if refs != self._ref:
            diff = {b: (refs.get(b), self._ref.get(b))
                    for b in set(refs) | set(self._ref)
                    if refs.get(b) != self._ref.get(b)}
            raise AllocatorInvariantError(
                f"refcount ledger disagrees with tables "
                f"(block: tables vs ledger): {diff}")
        window = [b for ring in self.window_tables.values()
                  for b in ring.values()]
        cross = [b for t in self.cross_tables.values() for b in t]
        everything = (self._free + list(self._cached) + list(self._ref)
                      + window + cross)
        if len(set(everything)) != len(everything):
            raise AllocatorInvariantError(
                "a block is owned twice across free/cached/live/window/cross")
        if len(everything) != self.config.n_blocks:
            raise AllocatorInvariantError(
                f"{self.config.n_blocks - len(everything)} blocks "
                "unaccounted for")
        for h, block in self._index.items():
            if self._hash_of.get(block) != h:
                raise AllocatorInvariantError(
                    f"index maps {h!r} to block {block} whose committed "
                    f"hash is {self._hash_of.get(block)!r}")
        free_set = set(self._free)
        for block in self._hash_of:
            if block in free_set:
                raise AllocatorInvariantError(
                    f"committed block {block} is on the free list")
        for block in self._cached:
            if block not in self._hash_of:
                raise AllocatorInvariantError(
                    f"cached block {block} has no committed hash")
            if self._ref.get(block):
                raise AllocatorInvariantError(
                    f"cached block {block} has live references")
        if self._group_in_use["global"] != len(self._ref):
            raise AllocatorInvariantError(
                f"global in-use ledger {self._group_in_use['global']} != "
                f"{len(self._ref)} live blocks")
        if self._group_in_use["window"] != len(window):
            raise AllocatorInvariantError("window in-use ledger mismatch")
        if self._group_in_use["cross"] != len(cross):
            raise AllocatorInvariantError("cross in-use ledger mismatch")
        if self.outstanding_blocks() > self.n_free:
            raise AllocatorInvariantError(
                f"reservations outstanding ({self.outstanding_blocks()}) "
                f"exceed allocatable blocks ({self.n_free})")

    def check_no_leaks(self) -> None:
        """Invariant check: with no live slots, every block is either free
        or parked (refcount 0) in the prefix cache."""
        if self.tables:
            raise AllocatorInvariantError(
                f"live tables remain: {sorted(self.tables)}")
        if self.window_tables:
            raise AllocatorInvariantError(
                f"live window rings remain: {sorted(self.window_tables)}")
        if self.cross_tables:
            raise AllocatorInvariantError(
                f"live cross block sets remain: {sorted(self.cross_tables)}")
        if self._state_slots:
            raise AllocatorInvariantError(
                f"live state slots remain: {sorted(self._state_slots)}")
        if len(self._free) + len(self._cached) != self.config.n_blocks:
            leaked = self.config.n_blocks - len(self._free) \
                - len(self._cached)
            raise AllocatorInvariantError(f"{leaked} blocks leaked")
        self.check()

    # -- physical store ----------------------------------------------------------
    def attach_store(self, store: PagedKVStore, group: str = "global") -> None:
        if store.config.block_size != self.config.block_size or \
                store.config.n_blocks != self.config.n_blocks:
            raise ValueError("store geometry does not match allocator config")
        self.stores.append(store)
        self.store_groups.append(group)

    def padded_table(self, slot: int, width: int) -> list[int]:
        """``slot``'s global block table padded to ``width`` entries with
        the null block id (unallocated logical blocks resolve to the
        scratch page)."""
        table = self.tables[slot]
        if len(table) > width:
            raise ValueError(f"table of {len(table)} blocks exceeds width {width}")
        return table + [self.config.null_block] * (width - len(table))

    def padded_window_table(self, slot: int, width: int) -> list[int]:
        """``slot``'s window ring as a full-width logical table: entry i is
        the physical block of logical block i, or the null page when i is
        behind the window (freed) or not yet written."""
        ring = self.window_tables[slot]
        if ring and max(ring) >= width:
            raise ValueError(
                f"window ring reaches block {max(ring)}, width {width}")
        null = self.config.null_block
        return [ring.get(i, null) for i in range(width)]

    def padded_cross_table(self, slot: int, width: int) -> list[int]:
        """``slot``'s static cross block set padded to ``width`` entries
        with the null block id.  The set never grows, so this row is
        published exactly once per admission."""
        table = self.cross_tables[slot]
        if len(table) > width:
            raise ValueError(
                f"cross table of {len(table)} blocks exceeds width {width}")
        return table + [self.config.null_block] * (width - len(table))

    def write_token(self, slot: int, pos: int, k, v) -> None:
        """Write one token's K/V into ``slot``'s lane via the first store."""
        self.stores[0].write_token(self.tables[slot], pos, k, v)

    def gather_slot(self, slot: int, context_len: Optional[int] = None):
        """Gather ``slot``'s logical K/V view from the first store."""
        if context_len is None:
            context_len = self._tokens[slot]
        return self.stores[0].gather_slot(self.tables[slot], context_len)

    def resident_bytes(self) -> int:
        """Physical HBM bytes pinned by allocated blocks and recurrent
        state slots (0 with no store attached and no state group)."""
        return sum(self.resident_bytes_by_group().values())

    def resident_bytes_by_group(self) -> dict[str, int]:
        """Physical residency split by cache group — what the per-group
        telemetry reports.  Block groups multiply blocks-in-use by their
        own stores' per-block bytes; the recurrent group is state slots
        times the layout's per-slot state bytes."""
        out: dict[str, int] = {}
        for group in ("global", "window", "cross"):
            bb = sum(s.block_bytes for s, g in zip(self.stores,
                                                   self.store_groups)
                     if g == group)
            if bb or self._group_in_use[group]:
                out[group] = self._group_in_use[group] * bb
        if self.layout.state_slots:
            out["recurrent"] = len(self._state_slots) * \
                self.layout.state_bytes_per_slot
        return out

    def capacity_bytes(self) -> int:
        total = self.config.n_blocks * sum(s.block_bytes for s in self.stores)
        if self.layout.state_slots:
            total += self.layout.state_slots * \
                self.layout.state_bytes_per_slot
        return total


class BlockTransferBuffer:
    """Staging buffer for prefill -> decode block handoff between engine
    replicas (the disaggregated-serving transfer protocol).

    A prefill replica finishes a prompt, commits its full blocks into its
    own prefix index, and *exports* their physical content here keyed by
    content hash (``ContinuousEngine.export_prefix_blocks``); the router
    then *delivers* the chain to a decode replica, whose allocator claims
    fresh blocks for the payloads and parks them refcount-0 committed in
    its own index (``inject_cached`` + ``import_prefix_blocks``) — after
    which the decode replica admits the request as an ordinary full
    prefix-cache hit.  The buffer itself is pure host-side staging: it
    owns no pool blocks on either side, so allocator refcounts never pass
    through it (``check()`` holds on both allocators at every stage of a
    handoff, which the transfer tests assert).

    Failure semantics are graceful degradation, never corruption: a
    payload evicted here (capacity FIFO), or a chain the importing pool
    cannot fully take, just means the decode replica recomputes those
    positions — ``take_chain`` only ever returns a *prefix* of the
    requested chain, preserving the chain-match structure.

    ``capacity_blocks`` bounds staged entries (0 = unbounded); when full,
    the oldest staged entries are dropped FIFO.
    """

    def __init__(self, capacity_blocks: int = 0):
        if capacity_blocks < 0:
            raise ValueError("capacity_blocks must be >= 0")
        self.capacity_blocks = capacity_blocks
        self._entries: OrderedDict[str, object] = OrderedDict()
        self.stats: dict[str, int] = {"staged": 0, "delivered": 0,
                                      "dropped": 0}

    def __len__(self) -> int:
        return len(self._entries)

    def put(self, block_hash: str, payload) -> None:
        """Stage one block's physical content under its hash; re-staging
        a held hash refreshes its recency instead of duplicating."""
        if block_hash in self._entries:
            self._entries.move_to_end(block_hash)
            self._entries[block_hash] = payload
            return
        while self.capacity_blocks and \
                len(self._entries) >= self.capacity_blocks:
            self._entries.popitem(last=False)
            self.stats["dropped"] += 1
        self._entries[block_hash] = payload
        self.stats["staged"] += 1

    def put_chain(self, entries) -> None:
        """Stage an exported ``(hash, payload)`` chain, head first."""
        for h, payload in entries:
            self.put(h, payload)

    def take_chain(self, block_hashes) -> list[tuple]:
        """Remove and return the longest staged *prefix* of
        ``block_hashes`` as ``(hash, payload)`` pairs.  Stops at the
        first hash not held so the receiver always imports a well-formed
        chain prefix (later stragglers would be unmatchable anyway)."""
        out: list[tuple] = []
        for h in block_hashes or ():
            payload = self._entries.pop(h, None)
            if payload is None:
                break
            out.append((h, payload))
        self.stats["delivered"] += len(out)
        return out
