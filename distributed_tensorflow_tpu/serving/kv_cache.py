"""Block-allocated KV cache for incremental decode.

The serving engine keeps every running sequence's attention keys/values
on device between decode steps. A naive per-slot ``(max_seq,)`` buffer
wastes HBM proportional to the LONGEST request; instead the cache is a
pool of fixed-size *blocks* (PagedAttention, Kwon et al. SOSP'23 —
vLLM's core idea): a sequence of length ``L`` holds exactly
``ceil(L / block_size)`` blocks, mixed-length requests share one batch,
and a finished sequence's blocks return to the pool immediately.

Two layers:

- **Host side** — :class:`BlockAllocator` (the free list; physical
  block 0 is reserved as the *trash block*: padded positions of every
  sequence write there and reads from it are always masked),
  :class:`BlockTable` (a sequence's logical-position → physical-block
  map plus the flat pool indices the device gather/scatter consume) and
  :class:`PrefixCache` (cross-request block SHARING: committed prompt
  prefixes indexed by content so later requests with the same prefix
  adopt the blocks instead of recomputing prefill — see below).
- **Device side** — the pool itself, ``(n_layers, num_blocks *
  block_size, n_heads, head_dim)`` per K and V (:func:`init_pool`),
  flat over the block dimension so position ``p`` of a sequence maps to
  row ``table[p // block_size] * block_size + p % block_size``. On a
  serving mesh the head axis is sharded over ``tp`` (the same axis
  training shards heads on) and the pool is replicated over ``dp`` —
  ``dp`` shards the decode batch's slots, and every slot's gather may
  touch any block (:func:`pool_shardings`).

**Sharing & refcounts.** Blocks are reference-counted:
:meth:`BlockAllocator.alloc` hands out blocks at refcount 1,
:meth:`BlockAllocator.incref` adds an owner, and
:meth:`BlockAllocator.free` DECREFS — a block only returns to the free
list when its last owner lets go, so freeing a shared block is safe by
construction (and freeing an unowned block still raises). The
:class:`PrefixCache` holds one reference per cached block; sequences
that hash-match a prefix hold their own. A shared block is never
written in place: :meth:`BlockTable.ensure_writable` copies it first
(copy-on-write), so a request diverging after a shared prefix cannot
corrupt its siblings' cache.

**Quantized pools.** ``CacheConfig(kv_dtype=)`` selects the pool's
storage dtype: ``"f32"`` (reference), ``"bf16"`` (plain cast, 2x the
slots) or ``"int8"`` (quantize-on-write with one f32 scale per
quantisation block — a block here is one head's ``head_dim`` vector of
one pool row — dequantize-on-gather; 2-3.8x the slots depending on
``head_dim``, see :meth:`CacheConfig.bytes_per_token`).
"""

from __future__ import annotations

import dataclasses
import heapq
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu import telemetry

#: Physical block every allocator reserves: padded/inactive positions
#: scatter here and masked attention never reads it.
TRASH_BLOCK = 0

#: CacheConfig(kv_dtype=) spellings -> storage dtype.
KV_DTYPES = {
    "f32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "int8": jnp.int8,
}


class OutOfBlocksError(RuntimeError):
    """The pool cannot satisfy an allocation (admission must wait or a
    running sequence must be preempted)."""


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Shape of the device-side KV pool.

    ``kv_dtype`` (``"f32"``/``"bf16"``/``"int8"``) overrides ``dtype``
    by name; ``"int8"`` switches the pool to quantized storage with
    per-(row, head) f32 scales (:func:`init_pool` adds ``k_scale`` /
    ``v_scale`` arrays).

    ``n_layers`` counts CACHE layers: one per layer of the model and per
    pass of a looped stack (every pass attends to the keys and values
    that pass produced), pass-major: cache layer ``pass * model layers
    + layer``. A block of the table stands for that many layer slices,
    so sharing, copy-on-write and eviction never see the passes.

    What a cache layer keeps of a token is one of two kinds of row:
    per-head K and V (``n_heads`` x ``head_dim`` each: the pool arrays
    ``k`` and ``v``), or, with ``latent_dim`` > 0, ONE latent row of
    that many values shared by all heads (latent attention: the normed
    compressed key/value vector and the rotary key; the pool array
    ``latent``; ``n_heads`` and ``head_dim`` are then 0). The pool's rows
    are padded with zeros to whole 128-value tiles (576 values lie in
    640): a TPU keeps an array whose minor dimension is no multiple of
    128 with its rows on the lanes instead, and every program that wants
    them row-major then turns the whole pool round (compiled for a
    described v5e, PERF.md section 6, PR 37). A block is
    ``block_size`` rows of every cache layer either way, so the
    allocator, the tables, the prefix cache and eviction see no
    difference."""

    n_layers: int
    n_heads: int
    head_dim: int
    num_blocks: int
    block_size: int = 16
    dtype: object = jnp.float32
    kv_dtype: str | None = None
    latent_dim: int = 0

    def __post_init__(self):
        if self.num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is the "
                             "reserved trash block)")
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        if self.kv_dtype is not None:
            if self.kv_dtype not in KV_DTYPES:
                raise ValueError(
                    f"kv_dtype={self.kv_dtype!r}; expected one of "
                    f"{sorted(KV_DTYPES)}")
            object.__setattr__(self, "dtype", KV_DTYPES[self.kv_dtype])
        if self.latent_dim and self.quantized:
            raise NotImplementedError(
                "an int8 pool quantizes per (row, head); a latent row "
                "has no heads and no quantized form here: use a "
                "floating-point kv_dtype")

    @property
    def quantized(self) -> bool:
        return jnp.dtype(self.dtype) == jnp.dtype(jnp.int8)

    @property
    def row_shape(self) -> tuple[int, ...]:
        """One cached token in one pool array of one cache layer."""
        return ((-(-self.latent_dim // 128) * 128,) if self.latent_dim
                else (self.n_heads, self.head_dim))

    @property
    def pool_names(self) -> tuple[str, ...]:
        """The pool's value arrays (an int8 pool adds their scales)."""
        return ("latent",) if self.latent_dim else ("k", "v")

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1          # minus the trash block

    @property
    def max_tokens(self) -> int:
        """Cache capacity in tokens (across all sequences)."""
        return self.usable_blocks * self.block_size

    @property
    def bytes_per_token(self) -> int:
        """Pool bytes one cached token costs (K + V over every cache
        layer, scales included for quantized dtypes) — the
        slots-per-chip arithmetic behind the README's KV-dtype table."""
        per = len(self.pool_names) * math.prod(self.row_shape) \
            * jnp.dtype(self.dtype).itemsize
        if self.quantized:
            per += 2 * self.n_heads * 4          # f32 scale per head
        return per * self.n_layers

    def blocks_for_budget(self, pool_bytes: int) -> int:
        """Usable blocks (+1 trash) a device-memory budget affords at
        this dtype — how ``kv_dtype="int8"`` turns into 2x+ servable
        slots at an equal byte budget."""
        per_block = self.block_size * self.bytes_per_token
        return max(0, pool_bytes // per_block)

    def blocks_for(self, n_tokens: int) -> int:
        return max(1, math.ceil(n_tokens / self.block_size))

    @classmethod
    def for_model(cls, model_cfg, *, num_blocks: int,
                  block_size: int = 16, dtype=None,
                  kv_dtype: str | None = None) -> "CacheConfig":
        """Pool sized for a TransformerConfig-shaped model config: one
        cache layer per layer, pass and attention sub-block, of the kind
        of row the model's attention keeps."""
        latent = getattr(model_cfg, "latent", None)
        return cls(n_layers=model_cfg.n_layers
                   * getattr(model_cfg, "passes", 1)
                   * getattr(model_cfg, "sub_blocks", 1),
                   n_heads=0 if latent else model_cfg.n_heads,
                   head_dim=0 if latent else model_cfg.head_dim,
                   num_blocks=num_blocks, block_size=block_size,
                   dtype=dtype if dtype is not None else model_cfg.dtype,
                   kv_dtype=kv_dtype,
                   latent_dim=latent.row_dim if latent else 0)


class BlockAllocator:
    """Refcounted free-list over the physical blocks of one pool.

    Blocks are interchangeable fixed-size units, so there is no external
    fragmentation by construction — any free block satisfies any
    request; the only waste is internal (the tail of a sequence's last
    block), bounded by ``block_size - 1`` tokens per sequence.
    Allocation is lowest-id-first so reuse is deterministic
    (test- and replay-friendly).

    Every owner of a block — the sequence that allocated it, each later
    sequence sharing it, the prefix cache — holds one reference:
    :meth:`free` decrefs and only the LAST owner's free returns the
    block to the pool. Freeing a block nobody owns is still a
    programming error and raises.

    References are dropped from many places (a finished, preempted or
    re-queued sequence, a match handed back, copy-on-write, migration),
    and all of them pass :meth:`free`. Whoever has to know installs
    :attr:`observer`: the :class:`PrefixCache` does, to learn that a
    block it indexes has lost its last foreign owner."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved)")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, TRASH_BLOCK, -1))
        self._refs: dict[int, int] = {}
        #: optional ``callable(block, refs_left)``: :meth:`free` calls
        #: it for every reference it drops from a block that stays
        #: allocated (``refs_left >= 1``).
        self.observer = None

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocated(self) -> int:
        return len(self._refs)

    @property
    def total_refs(self) -> int:
        """Sum of live references across all allocated blocks — the
        conservation quantity the disaggregated chaos gate audits:
        every ref must be owned by a running sequence's table or a
        prefix-cache entry, so ``total_refs - cache_entries -
        Σ len(table.blocks) == 0`` or blocks leaked."""
        return sum(self._refs.values())

    def refcount(self, block: int) -> int:
        """Live references on ``block`` (0 = free). Refcount > 1 means
        SHARED: writers must copy first (BlockTable.ensure_writable)."""
        return self._refs.get(block, 0)

    def alloc(self, n: int) -> list[int]:
        """``n`` blocks at refcount 1, lowest ids first; raises
        :class:`OutOfBlocksError` (allocating nothing) when fewer than
        ``n`` are free."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise OutOfBlocksError(
                f"need {n} blocks, {len(self._free)} free "
                f"(of {self.num_blocks - 1} usable)")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        return out

    def incref(self, block: int) -> None:
        """Add an owner to an allocated block (prefix-cache sharing)."""
        if block not in self._refs:
            raise ValueError(f"incref of unallocated block {block}")
        self._refs[block] += 1

    def free(self, blocks) -> None:
        """Drop one reference per block; a block whose LAST reference
        is dropped returns to the pool. Freeing an unowned block (true
        double-free) and freeing the trash block raise."""
        blocks = list(blocks)
        for b in blocks:
            if b == TRASH_BLOCK:
                raise ValueError("cannot free the reserved trash block")
            if b not in self._refs:
                raise ValueError(f"double free of block {b}")
        released = []
        observer = self.observer
        for b in blocks:
            left = self._refs[b] - 1
            if left:
                self._refs[b] = left
                if observer is not None:
                    observer(b, left)
            else:
                del self._refs[b]
                released.append(b)
        if released:
            self._free.extend(released)
            # keep lowest-id-first allocation order deterministic
            self._free.sort(reverse=True)


class BlockTable:
    """One sequence's logical-position → physical-row mapping.

    ``max_blocks`` fixes the table's device-visible width (every slot's
    table has the same shape so the decode step compiles once); unused
    entries point at the trash block."""

    def __init__(self, cache_cfg: CacheConfig, max_blocks: int):
        self.cfg = cache_cfg
        self.max_blocks = max_blocks
        self.blocks: list[int] = []
        self.length = 0                     # tokens written

    @property
    def capacity(self) -> int:
        return len(self.blocks) * self.cfg.block_size

    def ensure_room(self, n_tokens: int, allocator: BlockAllocator):
        """Grow the table so ``length + n_tokens`` fits; raises
        :class:`OutOfBlocksError` (allocating nothing) when the pool or
        the table width cannot hold it."""
        need = self.cfg.blocks_for(self.length + n_tokens)
        grow = need - len(self.blocks)
        if grow <= 0:
            return
        if need > self.max_blocks:
            raise OutOfBlocksError(
                f"sequence needs {need} blocks > max_blocks_per_seq="
                f"{self.max_blocks}")
        self.blocks.extend(allocator.alloc(grow))

    def ensure_writable(self, start: int, end: int,
                        allocator: BlockAllocator) -> list[tuple]:
        """Copy-on-write: every block covering logical positions
        ``[start, end)`` that is SHARED (refcount > 1 — a prefix-cache
        entry or a sibling sequence also owns it) is swapped for a
        private fresh block. Returns ``(src_row0, dst_row0, n_rows)``
        device copy instructions the engine must apply to the pool
        BEFORE writing — the copy preserves the shared prefix content
        that precedes the divergent write inside the block."""
        if end <= start or not self.blocks:
            return []
        bs = self.cfg.block_size
        lo = start // bs
        hi = min(len(self.blocks) - 1, (end - 1) // bs)
        copies = []
        for bi in range(lo, hi + 1):
            b = self.blocks[bi]
            if allocator.refcount(b) > 1:
                new = allocator.alloc(1)[0]
                copies.append((b * bs, new * bs, bs))
                self.blocks[bi] = new
                allocator.free([b])          # drop OUR ref; others keep it
        return copies

    def row_of(self, position: int) -> int:
        """Flat pool row of logical ``position``."""
        bs = self.cfg.block_size
        return self.blocks[position // bs] * bs + position % bs

    def rows(self, positions) -> np.ndarray:
        """Flat pool rows for an array of logical positions; positions
        at/past the written blocks map into the trash block."""
        bs = self.cfg.block_size
        table = np.full(self.max_blocks, TRASH_BLOCK, np.int32)
        table[:len(self.blocks)] = self.blocks
        positions = np.asarray(positions, np.int64)
        return (table[np.minimum(positions // bs, self.max_blocks - 1)]
                * bs + positions % bs).astype(np.int32)

    def window_rows(self) -> np.ndarray:
        """Rows of the full ``max_blocks * block_size`` attention window
        (the decode step's gather index): logical positions 0.. in
        order, trash rows past the allocated blocks."""
        return self.rows(np.arange(self.max_blocks * self.cfg.block_size))

    def release(self, allocator: BlockAllocator):
        if self.blocks:
            allocator.free(self.blocks)
        self.blocks = []
        self.length = 0


class _CacheEntry:
    __slots__ = ("key", "parent", "block", "tokens", "last_used")

    def __init__(self, key, parent, block, tokens, last_used):
        self.key = key
        self.parent = parent
        self.block = block
        self.tokens = tokens
        self.last_used = last_used


class _SpillEntry:
    __slots__ = ("key", "parent", "tokens", "arrays", "epoch")

    def __init__(self, key, parent, tokens, arrays, epoch):
        self.key = key
        self.parent = parent
        self.tokens = tokens
        self.arrays = arrays          # host copies of the block's rows
        self.epoch = epoch            # pool epoch of the spilling engine

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self.arrays.values())


class HostTier:
    """Host-memory spill tier for cold :class:`PrefixCache` blocks.

    When the prefix cache must evict a block (pool pressure), the
    block's K/V rows — quantisation scales included — are copied to
    host RAM instead of being dropped; a later prompt that walks the
    same chain re-adopts the block into a fresh pool slot bit-exactly
    (tests/test_migrate.py pins the round-trip per ``kv_dtype``). The
    tier holds NO allocator references — its entries are plain host
    bytes keyed by the same chain key the cache indexes by.

    **Epoch fencing.** Every entry records the spilling engine's
    ``pool_epoch``. A restarted engine has a NEW epoch, so a stale
    spill (possibly from different weights or a different pool layout)
    is rejected at re-adoption rather than served — the cache then just
    prefill-recomputes, which is always correct.

    Capacity is bounded (``capacity_blocks``); insertion past it drops
    the least-recently-touched spilled block."""

    def __init__(self, capacity_blocks: int = 256):
        if capacity_blocks < 1:
            raise ValueError("capacity_blocks must be >= 1")
        self.capacity_blocks = capacity_blocks
        import collections
        self._entries: "collections.OrderedDict[tuple, _SpillEntry]" = \
            collections.OrderedDict()
        self.spilled = 0
        self.readopted = 0
        self.rejected = 0
        self.dropped = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def nbytes(self) -> int:
        return sum(e.nbytes for e in self._entries.values())

    def put(self, key, parent, tokens, arrays, epoch):
        if key in self._entries:
            self._entries.pop(key)
        while len(self._entries) >= self.capacity_blocks:
            self._entries.popitem(last=False)
            self.dropped += 1
        self._entries[key] = _SpillEntry(key, parent, tokens, arrays,
                                         epoch)
        self.spilled += 1

    def get(self, key) -> "_SpillEntry | None":
        e = self._entries.get(key)
        if e is not None:
            self._entries.move_to_end(key)
        return e

    def drop(self, key):
        self._entries.pop(key, None)

    def stats(self) -> dict:
        return {"entries": len(self._entries), "nbytes": self.nbytes,
                "spilled": self.spilled, "readopted": self.readopted,
                "rejected": self.rejected, "dropped": self.dropped}


class PrefixCache:
    """Content index over committed prompt-prefix blocks (cross-request
    KV reuse — the vLLM "automatic prefix caching" idea on this pool).

    **Granularity.** The index key of a block is the CHAIN
    ``(parent_key, block_tokens)``: a hit certifies the entire prefix
    up to and including that block, not just the block's own
    ``block_size`` tokens, so matching is a plain walk down the chain.
    The last hop may be a *partial* match — a cached block whose tokens
    merely START with the remaining prompt — which is what makes
    copy-on-write real: the matching sequence will later write its own
    tokens into that block's tail, and ``BlockTable.ensure_writable``
    copies the block first.

    **References.** The cache holds ONE allocator reference per entry;
    :meth:`match` bumps each returned block once more (the caller —
    the admitting sequence — owns those refs and drops them via the
    normal ``BlockTable.release``). Eviction (:meth:`evict`) is LRU
    over entries with NO references beyond the cache's own
    (refcount == 1) and only over chain LEAVES, so an entry a running
    sequence shares — or one a cached longer chain still hangs off —
    is never reclaimed out from under its users.

    **How the order is kept.** ``evict`` does not look for its victim
    among all entries: the evictable ones are filed in a min-heap on
    ``last_used`` as they BECOME evictable — the last foreign reference
    on the block is dropped (the allocator tells the cache through
    :attr:`BlockAllocator.observer`; the cache is handed no call at the
    many places that release), the last cached child is evicted, a
    spilled block is re-adopted — and filed again whenever ``last_used``
    is written on an entry that stays evictable (:meth:`_touch`, the
    one place that writes it). Nothing is taken out when an entry stops
    being evictable (a match shares it, a child hangs off it): an item
    is checked when it is popped (the entry still indexed, under that
    ``last_used``, a leaf, refcount 1) and dropped if stale, and the
    heap is rebuilt from the entries before stale items outnumber them
    several times. So ``evict(n)`` costs ``n`` pops and a few stale
    ones, whatever the cache holds. The victim is the one a scan of all
    entries would choose, call for call: the eligible entry of least
    ``last_used``. Ties need no rule: ``_clock`` advances once per
    :meth:`match` / :meth:`register` call, a call touches entries of
    one root path, and of those only the deepest cached one can be a
    leaf, so among equal ``last_used`` at most one entry is eligible at
    a time.

    At most ``len(prompt) - 1`` tokens ever match: prefill must compute
    at least the final prompt position to produce the first generated
    token's logits."""

    def __init__(self, allocator: BlockAllocator, block_size: int):
        self._alloc = allocator
        self.block_size = block_size
        self._entries: dict[tuple, _CacheEntry] = {}
        self._children: dict[object, set] = {}
        self._by_block: dict[int, _CacheEntry] = {}
        #: lazy min-heap of ``(last_used, push number, entry)``; the
        #: push number keeps a comparison from ever reaching the entry
        self._lru: list[tuple] = []
        self._pushes = 0
        allocator.observer = self._on_free
        self._clock = 0
        self.hit_tokens = 0
        self.lookup_tokens = 0
        self.hit_requests = 0
        self.lookups = 0
        self.evictions = 0
        self.evict_examined = 0
        self._spill: HostTier | None = None
        self._spill_extract = None
        self._spill_insert = None
        self._spill_epoch = None
        self.spill_hits = 0
        self.spill_rejects = 0
        self.fences = 0
        self.fence_dropped = 0

    def __len__(self) -> int:
        return len(self._entries)

    def attach_spill(self, tier: HostTier, *, extract, insert, epoch):
        """Wire a :class:`HostTier` behind this cache. ``extract(block)
        -> {name: np.ndarray}`` copies one block's pool rows (plus
        scales) to host; ``insert(block, arrays)`` writes them back
        into a freshly allocated block; ``epoch`` is the engine's
        ``pool_epoch`` fence (stale entries from a previous engine
        incarnation are rejected on re-adoption). The engine provides
        all three — the cache stays device-agnostic."""
        self._spill = tier
        self._spill_extract = extract
        self._spill_insert = insert
        self._spill_epoch = epoch

    def match(self, tokens) -> tuple[int, list[int]]:
        """``(n_cached_tokens, blocks)`` — the longest cached chain
        over ``tokens[:-1]``. Full blocks match by chain key; one final
        partial hop may match a cached block whose tokens extend the
        prompt's sub-block tail. Every returned block's refcount is
        bumped; the caller owns (and must eventually free) those refs.
        A match counts nothing: the caller reports the one that led to
        an admission through :meth:`count_admission`.
        """
        tokens = tuple(int(t) for t in tokens)
        limit = len(tokens) - 1
        self._clock += 1
        bs = self.block_size
        key = None
        blocks: list[int] = []
        n = 0
        while n + bs <= limit:
            k = (key, tokens[n:n + bs])
            e = self._entries.get(k)
            if e is None:
                # Chain miss on device — maybe the block was spilled to
                # the host tier. Re-adoption is full-block only: the
                # partial-hop heuristic below stays device-resident.
                e = self._readopt(k, key)
            if e is None:
                break
            self._alloc.incref(e.block)
            self._touch(e)
            blocks.append(e.block)
            key = k
            n += bs
        if 0 < limit - n < bs:
            rest = tokens[n:limit]
            best = None
            for ck in self._children.get(key, ()):
                e = self._entries[ck]
                if e.tokens[:len(rest)] == rest and (
                        best is None or e.last_used > best.last_used):
                    best = e
            if best is not None:
                self._alloc.incref(best.block)
                self._touch(best)
                blocks.append(best.block)
                n += len(rest)
        return n, blocks

    def count_admission(self, n_tokens: int, n_cached: int) -> None:
        """Count one admitted request's lookup (``n_tokens`` prompt
        tokens, ``n_cached`` of them matched). Counted here and not in
        :meth:`match` because a deferred head request is matched again
        at every step until it fits, and would count each time."""
        self.lookups += 1
        self.lookup_tokens += max(0, n_tokens - 1)
        if n_cached:
            self.hit_tokens += n_cached
            self.hit_requests += 1

    def register(self, tokens, blocks) -> int:
        """Index every FULL block of a just-prefilled prompt
        (``blocks`` = the sequence's BlockTable blocks, which hold
        exactly these tokens' K/V — shared hits included, and
        post-copy-on-write for a partially-matched tail). Newly
        inserted entries gain one cache-owned reference. Returns the
        number of new entries."""
        tokens = tuple(int(t) for t in tokens)
        self._clock += 1
        bs = self.block_size
        key = None
        added = 0
        for i in range(len(tokens) // bs):
            btoks = tokens[i * bs:(i + 1) * bs]
            k = (key, btoks)
            e = self._entries.get(k)
            if e is None:
                self._alloc.incref(blocks[i])
                e = _CacheEntry(k, key, blocks[i], btoks, self._clock)
                self._index(e)
                added += 1
            else:
                # the prompt may sit in OTHER blocks than the entry's
                # (an ask that ran cold beside its document's first):
                # then nobody holds e.block and e stays evictable
                self._touch(e)
            key = k
        return added

    def _index(self, e: _CacheEntry) -> None:
        self._entries[e.key] = e
        self._children.setdefault(e.parent, set()).add(e.key)
        self._by_block[e.block] = e

    def _evictable(self, e: _CacheEntry) -> bool:
        return (not self._children.get(e.key)
                and self._alloc.refcount(e.block) == 1)

    def _file(self, e: _CacheEntry) -> None:
        """Put ``e`` in the eviction order under its present
        ``last_used``. Stale items are bounded: past a few times the
        number of entries the heap is rebuilt from the entries."""
        self._pushes += 1
        heapq.heappush(self._lru, (e.last_used, self._pushes, e))
        if len(self._lru) > 4 * len(self._entries) + 64:
            self._lru = [(x.last_used, i, x) for i, x in enumerate(
                x for x in self._entries.values() if self._evictable(x))]
            heapq.heapify(self._lru)
            self._pushes = len(self._lru)

    def _touch(self, e: _CacheEntry) -> None:
        """Every write of ``last_used``. An entry that is evictable now
        sees no other event before it is wanted, so it is filed again
        here; the item under its old ``last_used`` has gone stale."""
        e.last_used = self._clock
        if self._evictable(e):
            self._file(e)

    def _on_free(self, block: int, refs_left: int) -> None:
        """The allocator's observer: the last foreign reference on an
        indexed leaf's block has gone."""
        if refs_left == 1:
            e = self._by_block.get(block)
            if e is not None and not self._children.get(e.key):
                self._file(e)

    def evict(self, n_blocks: int) -> int:
        """Free up to ``n_blocks`` pool blocks by dropping
        least-recently-used UNREFERENCED leaf entries (allocator
        refcount 1 — only the cache's own reference — and no cached
        children). Entries referenced by running sequences are never
        evicted. Returns how many blocks actually went back to the
        pool. The ``kv.evict`` span counts them (``blocks``) beside the
        candidates popped to find them (``examined``)."""
        if n_blocks <= 0:
            return 0
        with telemetry.span("kv.evict") as sp:
            freed = examined = 0
            while freed < n_blocks and self._lru:
                last_used, _, victim = heapq.heappop(self._lru)
                examined += 1
                if (self._entries.get(victim.key) is not victim
                        or victim.last_used != last_used
                        or not self._evictable(victim)):
                    continue                 # stale: see the class docstring
                if self._spill is not None:
                    # Only truly cold cache-private blocks are spilled:
                    # the victim's refcount is 1 (the cache's own ref),
                    # so no sequence shares it.
                    self._spill.put(victim.key, victim.parent,
                                    victim.tokens,
                                    self._spill_extract(victim.block),
                                    self._spill_epoch)
                del self._entries[victim.key]
                del self._by_block[victim.block]
                kids = self._children[victim.parent]
                kids.discard(victim.key)
                if not kids:
                    del self._children[victim.parent]
                    parent = self._entries.get(victim.parent)
                    if parent is not None and self._evictable(parent):
                        self._file(parent)   # its last child went
                self._alloc.free([victim.block])
                self.evictions += 1
                freed += 1
            self.evict_examined += examined
            sp["blocks"] = freed
            sp["examined"] = examined
        return freed

    def fence(self, epoch) -> int:
        """Invalidate the whole cache in one step and rotate the spill
        epoch — the weights-version fence a hot-swap relies on: a block
        committed under weights N must never match a request served
        under weights N+1 (same tokens, different K/V). Device entries
        are dropped eagerly (the cache's own allocator reference per
        entry returns to the pool; blocks a running sequence still
        shares survive through the sequence's refs). Host-tier spilled
        entries are NOT scanned: the epoch rotation makes
        :meth:`_readopt` drop-and-count each one lazily on its next
        lookup, exactly like a stale entry from a dead engine
        incarnation. Returns the number of device entries dropped."""
        dropped = len(self._entries)
        blocks = [e.block for e in self._entries.values()]
        self._entries.clear()
        self._children.clear()
        self._by_block.clear()
        self._lru.clear()
        self._alloc.free(blocks)
        self._spill_epoch = epoch
        self.fences += 1
        self.fence_dropped += dropped
        return dropped

    def _readopt(self, key, chain_key) -> "_CacheEntry | None":
        """Try to pull a spilled block back into the pool on a chain
        miss. Needs one free block; a stale entry (pool-epoch mismatch
        — the engine restarted since the spill) is dropped and counted
        in ``spill_rejects`` instead of being served."""
        if self._spill is None:
            return None
        se = self._spill.get(key)
        if se is None:
            return None
        if se.epoch != self._spill_epoch:
            self._spill.drop(key)
            self._spill.rejected += 1
            self.spill_rejects += 1
            return None
        if self._alloc.num_free < 1:
            return None
        block = self._alloc.alloc(1)[0]       # cache-owned reference
        self._spill_insert(block, se.arrays)
        self._spill.drop(key)
        self._spill.readopted += 1
        self.spill_hits += 1
        e = _CacheEntry(key, chain_key, block, se.tokens, self._clock)
        self._index(e)
        self._file(e)              # a leaf nobody else holds
        return e

    def stats(self) -> dict:
        return {
            "entries": len(self._entries),
            "lookups": self.lookups,
            "hit_requests": self.hit_requests,
            "hit_tokens": self.hit_tokens,
            "lookup_tokens": self.lookup_tokens,
            "hit_rate": (self.hit_tokens / self.lookup_tokens
                         if self.lookup_tokens else 0.0),
            "evictions": self.evictions,
            "evict_examined": self.evict_examined,
            "spill_hits": self.spill_hits,
            "spill_rejects": self.spill_rejects,
            "fences": self.fences,
            "fence_dropped": self.fence_dropped,
        }


def init_pool(cache_cfg: CacheConfig, mesh=None):
    """Zero-initialized ``{"k", "v"}`` pools (``{"latent"}`` for latent
    rows; plus ``k_scale`` /
    ``v_scale`` per-(row, head) f32 scales when the config is int8-
    quantized), placed with :func:`pool_shardings` when a mesh is
    given."""
    rows = cache_cfg.num_blocks * cache_cfg.block_size
    shape = (cache_cfg.n_layers, rows) + cache_cfg.row_shape
    pool = {n: jnp.zeros(shape, cache_cfg.dtype)
            for n in cache_cfg.pool_names}
    if cache_cfg.quantized:
        sshape = (cache_cfg.n_layers, rows, cache_cfg.n_heads)
        pool["k_scale"] = jnp.zeros(sshape, jnp.float32)
        pool["v_scale"] = jnp.zeros(sshape, jnp.float32)
    if mesh is not None:
        sh = pool_shardings(mesh, cache_cfg)
        pool = {n: jax.device_put(a, sh[n]) for n, a in pool.items()}
    return pool


def pool_shardings(mesh, cache_cfg: CacheConfig | None = None) -> dict:
    """Cache layout on a serving mesh, one NamedSharding per pool
    array: heads over ``tp`` (matching the training-side head
    sharding), rows replicated — ``dp`` shards the decode batch's
    SLOTS, and any slot's block gather may touch any physical row, so
    the row axis stays unsharded. Quantisation scales follow their
    pool's head axis."""
    if cache_cfg is not None and cache_cfg.latent_dim:
        raise NotImplementedError(
            "a latent pool has no head axis to shard over tp: no mesh "
            "layout is defined for it (serve it on one device)")
    head_axis = "tp" if "tp" in mesh.shape else None
    kv = NamedSharding(mesh, P(None, None, head_axis, None))
    out = {"k": kv, "v": kv}
    if cache_cfg is not None and cache_cfg.quantized:
        sc = NamedSharding(mesh, P(None, None, head_axis))
        out["k_scale"] = sc
        out["v_scale"] = sc
    return out
