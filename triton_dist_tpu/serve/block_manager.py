"""Paged-KV block allocator: free list, per-request block tables, and a
content-addressed prefix cache with copy-on-write sharing.

The physical cache is a pool of ``num_blocks`` pages of ``page_size``
token rows each (per layer, per K/V — the pools live in the engine; this
class owns only the *index* arithmetic, so it is trivially unit-testable
and the engine's device arrays follow it).

Block 0 is RESERVED as the null block: retired/inactive batch rows
redirect their dummy K/V writes there, and dead block-table entries
(logical pages past a request's allocation) point at it — so a pool row
freed and re-allocated to another request can never be corrupted by a
stale writer, and every table entry always indexes a valid pool row (the
paged kernel DMAs dead entries too; see kernels/flash_decode.py).

Contract with `kernels/flash_decode.gqa_decode_paged_shard`: logical page
``i`` of a request lives at pool row ``table(rid)[i]``; entries past the
allocation hold the null block and are masked by the sequence length.

**Prefix sharing (docs/serving.md "Prefix caching").**  Every block is
ref-counted, and a FULL block whose token contents are known can be
*committed* to a content-addressed index keyed by ``(parent block,
token ids in block)`` — the parent link makes the key a chain, so a hit
at logical page ``i`` certifies the ENTIRE prefix up to ``i``, not just
this page's tokens at some position.  ``match_prefix`` walks the chain
to find the longest cached block-aligned prefix of a prompt, and
``allocate(..., shared=...)`` maps those blocks read-only into a new
request's table (refcount++).  Writes into a block with refcount > 1 go
through :meth:`cow` first (copy-on-write — the caller copies the page
on device and the table entry swaps to the fresh block).  Freed blocks
whose contents are committed don't die: they enter an LRU-evictable
cache tier, reclaimed only under allocation pressure — so
``num_free``/``num_allocatable`` semantics (and the ``BlockExhausted``
→ preemption path above them) are unchanged, the cache just keeps warm
KV alive in pages nobody is using yet.

Hash-collision safety: the index buckets on :func:`_block_hash` but a
lookup only matches after a FULL ``(parent, token ids)`` compare — a
colliding hash can never alias two different prefixes (pinned by
tests/test_serve_prefix.py with a deliberately degenerate hash).

**Window and global layers (docs/serving.md).**  A model whose layers
differ in reach has one allocator a GROUP of layers (:class:`KvGroups`):
each group its own block-id space, its own pool geometry and one table a
request.  A group whose layers see only the last ``window`` positions
(``BlockManager(window=W)``) keeps a POSITIONAL table — entry ``i`` is
logical page ``i`` — in which the pages no query can see any more hold the
null block: they are never allocated at admission (``allocate`` skips
them) and :meth:`BlockManager.release_unseen` gives them back while the
request runs.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

_ROOT = 0  # parent sentinel for a request's first block (the null block
           # can never be committed, so the id is free to mean "no parent")


def _block_hash(parent: int, tokens: tuple) -> int:
    """Bucket key for the content index.  Collisions are SAFE (lookup
    compares the full (parent, tokens) pair) — tests monkeypatch this to
    a constant to prove it."""
    return hash((parent, tokens))


class BlockExhausted(Exception):
    """Raised by :meth:`BlockManager.allocate` /
    :meth:`BlockManager.ensure` when the free list plus the evictable
    cache tier cannot cover the request (the scheduler turns this into
    queueing or preemption)."""


class KvGroupsUnsupported(NotImplementedError):
    """A serving feature that has not been carried over to a cache of
    several layer GROUPS (window and global layers, :class:`KvGroups`) was
    asked for: raised where the engine is built, or where the entry point
    is called — never a quiet fallback."""


class StateCacheUnsupported(KvGroupsUnsupported):
    """What has not been carried over to a cache with a STATE group (a
    fixed slot a running request beside its pages: models/ssm_yoco.py)
    refuses by this name where the engine is built — never a quiet
    fallback."""


class StateSlots:
    """The allocator of a STATE group: one fixed slot a running request,
    behind the part of :class:`BlockManager`'s interface that
    :class:`KvGroups` uses.  A state-space layer's cache does not grow — a
    request holds the same bytes at token 10 and at token 5,000 — so a slot
    is taken at admission, kept through every chunk and decode step, and
    given back at finish or preemption; ``ensure`` never allocates, and the
    group is never the one that refuses growth.  Slot 0 is the null slot
    (inactive rows of a decode step read and write it).  A request's
    "table" is its slot in column 0 of a row of nulls, so the engine's
    table and block-id operands carry it like any group's."""

    window = 0
    released = 0
    state = True

    def __init__(self, slots: int, page_size: int):
        self.num_blocks = slots + 1
        self.page_size = page_size
        self.null_block = 0
        self._free = list(range(slots, 0, -1))      # pop() gives slot 1 first
        self._slot: dict[str, int] = {}

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocatable(self) -> int:
        return self.num_blocks - 1

    @property
    def utilization(self) -> float:
        return len(self._slot) / self.num_allocatable

    def blocks_for(self, n_tokens: int) -> int:
        return 1

    def pages_held(self, n_tokens: int) -> int:
        return 1

    def can_allocate(self, n_tokens: int, shared: Sequence[int] = ()) -> bool:
        return bool(self._free)

    def allocate(self, rid: str, n_tokens: int,
                 shared: Sequence[int] = ()) -> list[int]:
        if not self._free:
            raise BlockExhausted(f"{rid}: no state slot is free")
        self._slot[rid] = self._free.pop()
        return [self._slot[rid]]

    def ensure(self, rid: str, n_tokens: int) -> list[int]:
        return [self._slot[rid]]                    # a state never grows

    def release_unseen(self, rid: str, kv_len: int) -> int:
        return 0

    def free(self, rid: str) -> None:
        self._free.append(self._slot.pop(rid))

    def table(self, rid: str) -> list[int]:
        return [self._slot[rid]]

    def padded_table(self, rid: str, width: int) -> list[int]:
        return [self._slot[rid]] + [self.null_block] * (width - 1)

    def capacity_tokens(self, rid: str) -> int:
        return 1 << 62                              # never the binding group

    def page_ids(self, rid: str, lo: int, hi: int, width: int):
        ids = np.full((width,), self.null_block, np.int32)
        ids[0] = self._slot[rid]
        return ids


class BlockManager:
    state = False               # a group of pages (StateSlots: of slots)

    def __init__(self, num_blocks: int, page_size: int, *, faults=None,
                 prefix_cache: bool = False, shards: int = 1,
                 pages_per_shard: Optional[int] = None, window: int = 0):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the reserved null "
                f"block), got {num_blocks}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        # Sequence-sharded serving (docs/serving.md "Sharded serving"):
        # with shards=W the block-id space splits into W equal
        # partitions — rank r's pool holds global blocks
        # [r*NB/W, (r+1)*NB/W) — and logical page ``i`` of ANY request
        # must be allocated from partition ``i // pages_per_shard``
        # (contiguous sequence-span ownership, the
        # sp_gqa_decode_paged_shard contract).  Each partition reserves
        # its own null block (its first id): per-rank dummy writes
        # redirect to LOCAL row 0, so one global null cannot serve
        # every rank.  shards=1 is the world-1 engine, bit-identical to
        # the pre-mesh allocator.
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if num_blocks % shards:
            raise ValueError(f"num_blocks {num_blocks} must divide by "
                             f"shards {shards}")
        if shards > 1 and num_blocks // shards < 2:
            raise ValueError(
                f"num_blocks//shards = {num_blocks // shards}: every "
                f"partition reserves a null block and still needs an "
                f"allocatable page")
        if shards > 1 and not pages_per_shard:
            raise ValueError("shards > 1 needs pages_per_shard (the "
                             "logical-page span each rank owns)")
        if window and (prefix_cache or shards > 1):
            raise KvGroupsUnsupported(
                "a window group's table has released pages: neither a "
                "prefix chain nor a sequence-sharded placement runs "
                "through it")
        self.num_blocks = num_blocks
        self.page_size = page_size
        # Layers that see only the last ``window`` positions (0: all of
        # them): pages wholly behind every query's window are not held.
        self.window = int(window)
        self.released = 0         # pages given back while requests ran
        self._first_held: dict[str, int] = {}   # rid -> first page it holds
        self.shards = shards
        self.pages_per_shard = pages_per_shard or num_blocks
        self._nb_loc = num_blocks // shards
        self.null_block = 0
        self._nulls = frozenset(r * self._nb_loc for r in range(shards))
        self.prefix_cache = bool(prefix_cache)
        # runtime.faults.FaultInjector (optional): the mid-grow alloc is
        # a fault point — an injected failure exercises the engine's
        # quarantine path without a genuinely exhausted pool.
        self._faults = faults
        # LIFO free list: recently-freed (cache-warm) blocks are reused
        # first.  Null blocks (block 0; one per partition when sharded)
        # never enter it.
        self._free: list[int] = [b for b in range(num_blocks - 1, 0, -1)
                                 if b not in self._nulls]
        self._tables: dict[str, list[int]] = {}
        # -- sharing / content cache state --------------------------------
        self._ref: dict[int, int] = {}          # block -> refcount (> 0)
        # committed blocks: block -> (parent block, token-id tuple);
        # present while the block is live-shared OR in the cache tier
        self._meta: dict[int, tuple[int, tuple]] = {}
        self._index: dict[int, list[int]] = {}  # _block_hash -> blocks
        self._children: dict[int, set[int]] = {}
        # LRU cache tier: committed refcount-0 blocks, insertion-ordered
        # (dict iteration order = admission order = eviction order)
        self._cached: dict[int, None] = {}
        # observability (engine surfaces these via metrics.summary())
        # on_evict(block): optional hook fired as a cache-tier block is
        # reclaimed — the engine points it at the flight recorder so
        # eviction storms land on the request timeline
        # (docs/observability.md); must never raise (called on the
        # allocation hot path).
        self.on_evict = None
        self.lookups = 0          # match_prefix calls
        self.lookup_hits = 0      # match_prefix calls matching > 0 blocks
        self.hit_blocks = 0       # blocks mapped read-only into tables
        self.committed_blocks = 0  # commit_block registrations
        self.cow_copies = 0       # copy-on-write block splits
        self.evictions = 0        # cache-tier blocks reclaimed
        # bumped on every index mutation (_register/_unregister): a
        # match_prefix result is valid for exactly as long as this is
        # unchanged, so a blocked head-of-line request can reuse its
        # match instead of re-walking the chain every engine step
        self.index_gen = 0

    # -- accounting -------------------------------------------------------

    @property
    def num_free(self) -> int:
        """Blocks an allocation can claim: the free list PLUS the
        evictable cache tier (cached blocks hold warm KV but belong to
        nobody — allocation pressure reclaims them LRU-first)."""
        return len(self._free) + len(self._cached)

    @property
    def num_cached(self) -> int:
        """Blocks in the evictable warm-KV cache tier (refcount 0)."""
        return len(self._cached)

    @property
    def num_shared(self) -> int:
        """Blocks currently mapped into more than one table."""
        return sum(1 for r in self._ref.values() if r > 1)

    @property
    def num_allocatable(self) -> int:
        return self.num_blocks - self.shards

    # -- partition arithmetic (shards > 1: kv_shard="seq") ---------------

    def part_of_block(self, block: int) -> int:
        """Partition owning physical block ``block``."""
        return block // self._nb_loc

    def part_of_page(self, logical: int) -> int:
        """Partition that must hold logical page ``logical`` of any
        request (contiguous sequence-span ownership)."""
        return min(logical // self.pages_per_shard, self.shards - 1)

    def placement_ok(self, blocks: Sequence[int]) -> bool:
        """True when a position-ordered block table satisfies the
        partition constraint (trivially true unsharded).  The restore
        path gates in-place adoption on this — a table snapshotted
        under a different mesh shape re-queues through exact recompute
        instead of serving junk pages."""
        if self.shards == 1:
            return True
        return all(self.part_of_block(b) == self.part_of_page(i)
                   and b not in self._nulls
                   for i, b in enumerate(blocks))

    def _part_free(self, part: int, *, skip_cached: int = 0) -> int:
        """Free + evictable blocks available in one partition."""
        lo, hi = part * self._nb_loc, (part + 1) * self._nb_loc
        return (sum(1 for b in self._free if lo <= b < hi)
                + sum(1 for b in self._cached if lo <= b < hi)
                - skip_cached)

    def fit_error(self, n_tokens: int) -> Optional[str]:
        """Can ``n_tokens`` EVER fit this pool (all blocks free)?
        Returns None when yes, else the rejection message — per
        partition when sharded: a long request needs its span's pages
        in specific partitions, so a global block count is not enough."""
        need = self.blocks_for(n_tokens)
        if need > self.num_allocatable:
            return (f"needs {need} blocks, pool has "
                    f"{self.num_allocatable}")
        if self.shards > 1:
            for p in range(self.shards):
                in_p = sum(1 for i in range(need)
                           if self.part_of_page(i) == p)
                if in_p > self._nb_loc - 1:
                    return (f"needs {in_p} blocks in partition {p} "
                            f"(kv_shard='seq' sequence-span "
                            f"ownership), partition holds "
                            f"{self._nb_loc - 1}")
        return None

    @property
    def utilization(self) -> float:
        """Fraction of allocatable blocks currently held by requests."""
        used = self.num_allocatable - self.num_free
        return used / self.num_allocatable

    def group_stats(self) -> dict:
        """Blocks in use by layer group, where the cache has several
        (:meth:`KvGroups.group_stats`); one group has none to tell apart:
        ``utilization`` says it all."""
        return {}

    def blocks_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` cache rows."""
        return -(-n_tokens // self.page_size)

    def first_seen_page(self, kv_len: int) -> int:
        """The first logical page a query at position ``kv_len`` or later
        can still see: key ``j`` is seen by query ``i`` iff ``i - j <
        window``, so the earliest seen key is ``kv_len - window + 1`` (the
        rule of ``flash_decode._live_pages``, whose walk starts there).
        0 without a window."""
        if not self.window:
            return 0
        return max(0, kv_len - self.window + 1) // self.page_size

    def pages_held(self, n_tokens: int) -> int:
        """Blocks an allocation for ``n_tokens`` rows takes (its LAST row
        is the earliest query's position): all its pages, less those a
        window leaves behind."""
        return self.blocks_for(n_tokens) - self.first_seen_page(n_tokens - 1)

    def can_allocate(self, n_tokens: int,
                     shared: Sequence[int] = ()) -> bool:
        """Would :meth:`allocate` succeed?  ``shared`` is the
        :meth:`match_prefix` hit the allocation will map in: those
        blocks don't need the free list — but the ones currently
        sitting in the cache tier must NOT also be counted as
        evictable supply (they're about to be claimed), so they are
        subtracted from both sides."""
        in_cache = sum(1 for b in shared if b in self._cached)
        avail = len(self._free) + len(self._cached) - in_cache
        if self.pages_held(n_tokens) - len(shared) > avail:
            return False
        if self.shards > 1:
            need = self.blocks_for(n_tokens)
            for p in range(self.shards):
                need_p = sum(1 for i in range(len(shared), need)
                             if self.part_of_page(i) == p)
                skip = sum(1 for b in shared if b in self._cached
                           and self.part_of_block(b) == p)
                if need_p > self._part_free(p, skip_cached=skip):
                    return False
        return True

    def ref_of(self, block: int) -> int:
        return self._ref.get(block, 0)

    def block_key(self, block: int) -> Optional[tuple]:
        """The content-index key ``(parent block, token ids)`` of a
        committed block, or ``None``.  The engine's draft-side prefix
        cache tags its draft pool pages with this key and re-validates
        the tag at read time — a freed-and-reused block's key changes or
        vanishes, so a stale draft page can never be served (the
        draft-pool twin of the chain's id-reuse safety)."""
        return self._meta.get(block)

    def prefix_stats(self) -> dict:
        """The prefix-cache counters + gauges as one dict (the engine's
        ``metrics.summary()["prefix_cache"]``)."""
        return {
            "lookups": self.lookups,
            "lookup_hits": self.lookup_hits,
            "hit_rate": (self.lookup_hits / self.lookups
                         if self.lookups else 0.0),
            "hit_blocks": self.hit_blocks,
            "hit_tokens": self.hit_blocks * self.page_size,
            "committed_blocks": self.committed_blocks,
            "cow_copies": self.cow_copies,
            "evictions": self.evictions,
            "cached_blocks": self.num_cached,
            "shared_blocks": self.num_shared,
        }

    # -- the content-addressed index --------------------------------------

    def _find(self, parent: int, tokens: tuple) -> Optional[int]:
        """Committed block for (parent, tokens) — FULL compare, never the
        hash alone (collision safety)."""
        for b in self._index.get(_block_hash(parent, tokens), ()):
            if self._meta.get(b) == (parent, tokens):
                return b
        return None

    def _register(self, block: int, parent: int, tokens: tuple) -> bool:
        """Enter ``block`` into the content index (idempotent; refuses a
        duplicate (parent, tokens) key — first committer wins)."""
        if block in self._meta:
            return True
        if self._find(parent, tokens) is not None:
            return False  # identical content already cached elsewhere
        self._meta[block] = (parent, tokens)
        self._index.setdefault(_block_hash(parent, tokens), []).append(block)
        if parent != _ROOT:
            self._children.setdefault(parent, set()).add(block)
        self.committed_blocks += 1
        self.index_gen += 1
        return True

    def _unregister(self, block: int) -> None:
        self.index_gen += 1
        parent, tokens = self._meta.pop(block)
        h = _block_hash(parent, tokens)
        bucket = self._index.get(h)
        if bucket is not None:
            bucket.remove(block)
            if not bucket:
                del self._index[h]
        if parent != _ROOT:
            kids = self._children.get(parent)
            if kids is not None:
                kids.discard(block)
                if not kids:
                    del self._children[parent]

    def match_prefix(self, tokens: Sequence[int], *,
                     count: bool = True) -> list[int]:
        """Longest cached block-aligned prefix of ``tokens``: the chain
        of committed blocks matching full pages of the prompt, capped at
        ``len(tokens) - 1`` so at least one token always prefills (the
        request needs the last prompt token's logits).  Returns the
        physical blocks, in logical order — pass them to
        :meth:`allocate`'s ``shared=``.

        ``count=False`` leaves the ``lookups``/``lookup_hits`` gauges
        alone: a blocked head-of-line request re-matches every engine
        step until it admits, and counting each retry would deflate
        ``hit_rate`` into a queue-pressure artifact."""
        if not self.prefix_cache or len(tokens) < 2:
            return []
        if count:
            self.lookups += 1
        page = self.page_size
        limit = (len(tokens) - 1) // page
        out: list[int] = []
        parent = _ROOT
        for i in range(limit):
            key = tuple(int(t) for t in tokens[i * page:(i + 1) * page])
            blk = self._find(parent, key)
            if blk is None:
                break
            if (self.shards > 1
                    and self.part_of_block(blk) != self.part_of_page(i)):
                # Sharded pools: a cached block is only usable at the
                # logical position whose partition physically holds it
                # (re-admitted warm blocks from a different mesh shape
                # land here and simply never match).
                break
            out.append(blk)
            parent = blk
        if out and count:
            self.lookup_hits += 1
        return out

    def commit_block(self, rid: str, logical: int,
                     tokens: Sequence[int]) -> None:
        """Register ``rid``'s full logical page ``logical`` (its
        ``page_size`` token ids are ``tokens``) in the content index so
        later prompts sharing the prefix can map it read-only.  The
        parent link is the table's previous entry — by induction the
        whole chain up to this page is certified by the commit.
        Idempotent; a no-op when the cache is disabled or identical
        content is already indexed under another block."""
        if not self.prefix_cache:
            return
        if len(tokens) != self.page_size:
            raise ValueError(
                f"{rid}: commit_block needs exactly page_size="
                f"{self.page_size} tokens, got {len(tokens)}")
        table = self._tables[rid]
        block = table[logical]
        parent = table[logical - 1] if logical > 0 else _ROOT
        self._register(block, parent,
                       tuple(int(t) for t in tokens))

    # -- allocate / extend / free ----------------------------------------

    def _pop_free(self, part: Optional[int] = None) -> int:
        """One writable block off the free list, evicting the LRU cached
        block (plus its now-unreachable cached descendants — a committed
        child whose parent is gone can never be matched again, and its
        stale chain link must not survive the parent id's reuse) when
        the list is empty.  ``part`` (sharded pools) restricts the pop
        — and any eviction — to one partition."""
        if part is None or self.shards == 1:
            if not self._free:
                if not self._cached:
                    raise BlockExhausted("no free or evictable blocks")
                self._evict(next(iter(self._cached)))
            return self._free.pop()
        lo, hi = part * self._nb_loc, (part + 1) * self._nb_loc
        for i in range(len(self._free) - 1, -1, -1):
            if lo <= self._free[i] < hi:
                return self._free.pop(i)
        victim = next((b for b in self._cached if lo <= b < hi), None)
        if victim is None:
            raise BlockExhausted(
                f"no free or evictable blocks in partition {part}")
        self._evict(victim)
        for i in range(len(self._free) - 1, -1, -1):
            if lo <= self._free[i] < hi:
                return self._free.pop(i)
        raise BlockExhausted(       # pragma: no cover — _evict freed one
            f"no free or evictable blocks in partition {part}")

    def _evict(self, block: int) -> None:
        """Reclaim a cache-tier block into the free list.  Its committed
        descendants are orphaned first: the block's id is about to be
        reusable with different contents, and a child keyed on it could
        otherwise falsely certify its chain once the id comes back."""
        if block not in self._cached:
            return
        del self._cached[block]
        self._unregister(block)
        self._orphan_children(block)
        self._free.append(block)
        self.evictions += 1
        if self.on_evict is not None:
            self.on_evict(block)

    def _orphan_children(self, block: int) -> None:
        """``block`` is returning to the free list: its id can be
        reallocated with different contents, so no committed child keyed
        on it may survive — a match walking through the REUSED id would
        certify a chain the child's KV was never computed under (the
        block-id-reuse twin of hash-collision safety).  Cached children
        are reclaimed outright; live-shared children only lose their
        index entry (their holders' KV stays valid, the chain is just no
        longer matchable — their own children stay registered and are
        orphaned in turn when the live child is eventually freed)."""
        for child in list(self._children.get(block, ())):
            if child in self._cached:
                self._evict(child)
            else:
                self._unregister(child)

    def _claim_shared(self, block: int) -> None:
        """Map an existing block into one more table: refcount++ (pulling
        it out of the cache tier when it sat at refcount 0)."""
        if block in self._cached:
            del self._cached[block]
        self._ref[block] = self._ref.get(block, 0) + 1

    def allocate(self, rid: str, n_tokens: int,
                 shared: Sequence[int] = ()) -> list[int]:
        """Allocate blocks covering ``n_tokens`` for a NEW request.
        ``shared`` (from :meth:`match_prefix`) maps those blocks
        read-only as the table's head — only the remainder comes off the
        free list."""
        if rid in self._tables:
            raise ValueError(f"request {rid!r} already has blocks")
        need = self.blocks_for(n_tokens)
        shared = list(shared)
        if len(shared) > need:
            raise ValueError(
                f"{rid}: {len(shared)} shared blocks exceed the "
                f"{need}-block allocation for {n_tokens} tokens")
        # A window group: the pages behind the first query's window hold
        # the null block from the start (``shared`` is empty there: no
        # prefix chain runs through a window group).
        behind = self.first_seen_page(n_tokens - 1)
        # Same availability math as can_allocate: shared blocks sitting
        # in the cache tier are about to be CLAIMED, so they cannot also
        # count as evictable supply for the fresh remainder.
        avail = self.num_free - sum(1 for b in shared if b in self._cached)
        if need - behind - len(shared) > avail:
            raise BlockExhausted(
                f"{rid}: need {need - behind - len(shared)} blocks for "
                f"{n_tokens} tokens ({len(shared)} shared), only {avail} "
                f"free")
        if self.shards > 1:
            # Partitioned placement: every fresh page must come from its
            # logical position's partition, and the availability check
            # must hold PER PARTITION (the global count above can pass
            # while the one partition this span needs is empty).
            if not self.placement_ok(shared):
                raise ValueError(
                    f"{rid}: shared prefix blocks {list(shared)} violate "
                    f"the partition placement (kv_shard='seq')")
            for p in range(self.shards):
                need_p = sum(1 for i in range(len(shared), need)
                             if self.part_of_page(i) == p)
                skip = sum(1 for b in shared if b in self._cached
                           and self.part_of_block(b) == p)
                if need_p > self._part_free(p, skip_cached=skip):
                    raise BlockExhausted(
                        f"{rid}: need {need_p} blocks in partition {p} "
                        f"for {n_tokens} tokens, only "
                        f"{self._part_free(p, skip_cached=skip)} free")
        table = [self.null_block] * behind
        if self.window:
            self._first_held[rid] = behind  # release_unseen's watermark
        for b in shared:
            self._claim_shared(b)
            table.append(b)
        for i in range(behind + len(shared), need):
            b = self._pop_free(self.part_of_page(i)
                               if self.shards > 1 else None)
            self._ref[b] = 1
            table.append(b)
        self._tables[rid] = table
        self.hit_blocks += len(shared)
        return list(table)

    def ensure(self, rid: str, n_tokens: int) -> list[int]:
        """Extend ``rid``'s allocation to cover ``n_tokens`` (no-op when
        it already does).  Returns the blocks appended."""
        table = self._tables[rid]
        need = self.blocks_for(n_tokens) - len(table)
        if need <= 0:
            return []
        if self._faults is not None:
            # Fires BEFORE the free list is touched: an injected alloc
            # failure (InjectedFault, not BlockExhausted) leaves the pool
            # intact and bypasses the preemption machinery, so it lands
            # on the engine's quarantine path.
            self._faults.fire("block_alloc", rid=rid)
        if need > self.num_free:
            raise BlockExhausted(
                f"{rid}: extension to {n_tokens} tokens needs {need} more "
                f"blocks, only {self.num_free} free")
        base = len(table)
        if self.shards > 1:
            for p in range(self.shards):
                need_p = sum(1 for i in range(base, base + need)
                             if self.part_of_page(i) == p)
                if need_p > self._part_free(p):
                    raise BlockExhausted(
                        f"{rid}: extension to {n_tokens} tokens needs "
                        f"{need_p} blocks in partition {p}, only "
                        f"{self._part_free(p)} free")
        fresh = []
        for i in range(base, base + need):
            b = self._pop_free(self.part_of_page(i)
                               if self.shards > 1 else None)
            self._ref[b] = 1
            fresh.append(b)
        table.extend(fresh)
        return fresh

    def cow(self, rid: str, logical: int) -> tuple[int, int]:
        """Copy-on-write split of ``rid``'s logical page ``logical``: the
        shared block's refcount drops, a fresh block takes its table
        slot, and ``(old, new)`` returns so the caller can copy the page
        on device BEFORE any write lands.  Raises ``BlockExhausted``
        when no block (free or evictable) remains."""
        table = self._tables[rid]
        old = table[logical]
        if self._ref.get(old, 0) <= 1:
            raise ValueError(
                f"{rid}: block {old} (logical {logical}) is not shared")
        # The split stays in the logical page's partition (sharded
        # pools): the device copy is rank-local by construction.
        new = self._pop_free(self.part_of_page(logical)
                             if self.shards > 1 else None)
        self._ref[old] -= 1
        self._ref[new] = 1
        table[logical] = new
        self.cow_copies += 1
        return old, new

    def share(self, rid: str, blocks: Sequence[int]) -> None:
        """Impose a table for ``rid`` that references ``blocks`` in
        order, sharing any block another table already owns
        (refcount++), claiming cache-tier blocks, and taking free-list
        blocks.  The sharing twin of :meth:`adopt` — beam search maps
        every beam onto one prefix this way, and restore rebuilds
        snapshot tables that legitimately overlap."""
        if rid in self._tables:
            raise ValueError(f"request {rid!r} already has blocks")
        blocks = [int(b) for b in blocks]
        bad = [b for b in blocks
               if b in self._nulls or not 0 <= b < self.num_blocks]
        if bad:
            raise ValueError(f"{rid}: cannot claim blocks {bad} "
                             f"(null or outside pool {self.num_blocks})")
        if len(set(blocks)) != len(blocks):
            raise ValueError(f"{rid}: duplicate blocks in {blocks}")
        if not self.placement_ok(blocks):
            raise ValueError(
                f"{rid}: blocks {blocks} violate the partition "
                f"placement (kv_shard='seq': logical page i lives in "
                f"partition i // {self.pages_per_shard})")
        free = set(self._free)
        for b in blocks:
            if b in free:
                self._free.remove(b)
                free.discard(b)
                self._ref[b] = 1
            else:
                self._claim_shared(b)
        self._tables[rid] = blocks

    def adopt(self, rid: str, blocks: list[int], *,
              shared_ok: bool = False) -> None:
        """Impose a block table restored from a snapshot: claim exactly
        ``blocks`` (in order) for ``rid``, removing them from the free
        list.  The restore-time twin of :meth:`allocate` — the snapshot
        already decided WHICH physical pages hold the request's KV, so
        the allocator must adopt that mapping rather than hand out fresh
        pages the restored pools never wrote.  ``shared_ok=True`` lets
        blocks another restored table already claimed ride along as
        shared (refcount++) — snapshot tables legitimately overlap when
        the snapshotted engine served a shared prefix."""
        if rid in self._tables:
            raise ValueError(f"request {rid!r} already has blocks")
        blocks = [int(b) for b in blocks]
        bad = [b for b in blocks
               if b in self._nulls or not 0 <= b < self.num_blocks]
        if bad:
            raise ValueError(f"{rid}: cannot adopt blocks {bad} "
                             f"(null or outside pool {self.num_blocks})")
        if len(set(blocks)) != len(blocks):
            raise ValueError(f"{rid}: duplicate blocks in {blocks}")
        if not shared_ok:
            missing = set(blocks) - set(self._free)
            if missing:
                raise ValueError(
                    f"{rid}: blocks {sorted(missing)} already owned — the "
                    f"snapshot tables overlap")
        self.share(rid, blocks)

    def restore_index(self, entries: Sequence) -> None:
        """Re-register committed ``(block, parent, tokens)`` entries for
        LIVE blocks (refcount > 0) — the restore-time twin of
        :meth:`commit_block`, run after the snapshot's tables were
        re-adopted.  Entries whose block nobody re-adopted are skipped
        here; :meth:`admit_cached` is the path for ownerless warm
        blocks."""
        if not self.prefix_cache:
            return
        for block, parent, tokens in entries:
            if self._ref.get(int(block), 0) > 0:
                self._register(int(block), int(parent),
                               tuple(int(t) for t in tokens))

    def admit_cached(self, block: int, parent: int,
                     tokens: Sequence[int]) -> bool:
        """Restore-time cache admission: move a FREE block into the
        warm-KV cache tier under (parent, tokens) — the
        ``BlockManager.adopt`` counterpart for blocks nobody owns but
        whose pool pages still hold committed prefix KV (snapshots carry
        the warm cache across restarts).  Returns False (no-op) when the
        block is not free or the key is already indexed."""
        if block not in self._free or not self.prefix_cache:
            return False
        key = tuple(int(t) for t in tokens)
        if not self._register(block, int(parent), key):
            return False
        self._free.remove(block)
        self._cached[block] = None
        return True

    def release_unseen(self, rid: str, kv_len: int) -> int:
        """Give back the pages of ``rid`` that no query at position
        ``kv_len`` or later can see (a window group; a no-op without a
        window): each goes to the free list and its entry to the null
        block — the table stays positional.  ``kv_len`` is the position of
        the EARLIEST query still to be issued for the row, so the caller
        counts what is in flight (the engine calls between chains, with
        nothing in flight: its committed length).  Returns the pages
        released."""
        if not self.window:
            return 0
        table = self._tables[rid]
        lo = self._first_held[rid]      # everything below went already
        hi = min(self.first_seen_page(kv_len), len(table))
        for i in range(lo, hi):
            b = table[i]
            table[i] = self.null_block
            del self._ref[b]
            self._free.append(b)
        if hi > lo:
            self._first_held[rid] = hi
            self.released += hi - lo
        return max(hi - lo, 0)

    def free(self, rid: str) -> None:
        """Drop ``rid``'s claim on its blocks.  A block whose refcount
        reaches 0 returns to the free list — unless its contents are
        committed in the prefix index, in which case it enters the LRU
        cache tier instead (still counted by ``num_free``; reclaimed
        under allocation pressure)."""
        first = self._first_held.pop(rid, 0)    # a window left those behind
        for b in reversed(self._tables.pop(rid)[first:]):
            self._ref[b] -= 1
            if self._ref[b] > 0:
                continue
            del self._ref[b]
            if b in self._meta:
                self._cached[b] = None   # warm-KV tier, LRU order
            else:
                self._orphan_children(b)
                self._free.append(b)

    # -- tables -----------------------------------------------------------

    def table(self, rid: str) -> list[int]:
        return list(self._tables[rid])

    def padded_table(self, rid: str, width: int) -> list[int]:
        """The request's block table padded to ``width`` logical pages
        with the null block (the engine's fixed-width device row)."""
        t = self._tables[rid]
        if len(t) > width:
            raise ValueError(
                f"{rid}: {len(t)} blocks exceed table width {width}")
        return t + [self.null_block] * (width - len(t))

    def capacity_tokens(self, rid: str) -> int:
        """Cache rows the request's current allocation can hold."""
        return len(self._tables[rid]) * self.page_size

    def page_ids(self, rid: str, lo: int, hi: int, width: int):
        """``rid``'s table entries ``lo .. hi - 1`` at their positions in a
        ``width``-wide row of null blocks: the block ids a scratch's pages
        scatter to (the engine's ``fill_pages`` operand)."""
        ids = np.full((width,), self.null_block, np.int32)
        ids[lo:hi] = self._tables[rid][lo:hi]
        return ids


class KvGroups:
    """One :class:`BlockManager` a GROUP of layers, behind the interface
    the scheduler and the engine use of one: layers whose reach differs
    (full attention beside a sliding window) keep their pages in pools of
    their own geometry, and a request has one table a group.

    Admission, growth, preemption and release hold for every group or for
    none: ``can_allocate`` asks all, ``allocate`` / ``ensure`` / ``free``
    act on all.  ``num_free`` / ``num_allocatable`` are sums over the
    groups (whole free lists: equal).  ``utilization`` is LOAD, as the
    brownout ladder, the fleet's router and the benchmark read it: the
    fullest share among the groups that grow with the context.  A window
    group is left out — what it holds follows the rows in the batch, not
    their contexts, and the engine sizes it to ``max_batch`` rows' worst
    case, so it reads near full on every full batch and is never the one
    that refuses; its numbers are in :meth:`group_stats`.  A STATE group
    (:class:`StateSlots`: one fixed slot a running request) is left out
    for the same reason and reported the same way.  Planes differ by
    group — pages of K and V rows, or a slot of state — and a layer may
    own a pool in none (it reads another layer's, or nothing): that is the
    generator's to say (``kv_groups[..]["layers"]``), the tables here are a
    request's whatever reads them.  Tables and block ids come back with a
    leading group axis (``padded_table``, ``page_ids``).  No prefix chain
    and no sequence sharding run through it
    (:class:`KvGroupsUnsupported`)."""

    prefix_cache = False        # the engine's warm-up toggles it: inert
    on_evict = None             # no cache tier, so nothing evicts
    index_gen = 0

    def __init__(self, managers: dict):
        self.groups = dict(managers)        # name -> BlockManager, in order
        self._all = list(self.groups.values())
        pages = {m.page_size for m in self._all}
        if len(pages) != 1:
            raise ValueError(f"one page size for every group, got {pages}")
        self.page_size = pages.pop()
        self.null_block = 0
        # most blocks requests ever held at once, by group
        self._peak = dict.fromkeys(self.groups, 0)

    # -- accounting ---------------------------------------------------------

    @property
    def num_blocks(self) -> int:
        return sum(m.num_blocks for m in self._all)

    @property
    def num_free(self) -> int:
        return sum(m.num_free for m in self._all)

    @property
    def num_allocatable(self) -> int:
        return sum(m.num_allocatable for m in self._all)

    @property
    def utilization(self) -> float:
        grows = [m for m in self._all
                 if not m.window and not m.state] or self._all
        return max(m.utilization for m in grows)

    def group_stats(self) -> dict:
        """Blocks in use, by group (``summary()["kv"]["groups"]``)."""
        return {name: {"blocks": m.num_allocatable,
                       "in_use": m.num_allocatable - m.num_free,
                       "peak": self._peak[name],
                       "window": m.window, "released": m.released,
                       **({"state": True} if m.state else {})}
                for name, m in self.groups.items()}

    def note_peak(self) -> None:
        """Fold the blocks (and slots) held right now into each group's
        peak: after every allocation here, and by the engine where requests
        came and went since (``serve.decode.plan.state``)."""
        for name, m in self.groups.items():
            self._peak[name] = max(self._peak[name],
                                   m.num_allocatable - m.num_free)

    def blocks_for(self, n_tokens: int) -> int:
        return self._all[0].blocks_for(n_tokens)

    def fit_error(self, n_tokens: int) -> Optional[str]:
        for name, m in self.groups.items():
            need = m.pages_held(n_tokens)
            if need > m.num_allocatable:
                return (f"needs {need} blocks of the {name} group, its "
                        f"pool has {m.num_allocatable}")
        return None

    def match_prefix(self, tokens, *, count: bool = True) -> list:
        return []

    def prefix_stats(self) -> dict:
        return self._all[0].prefix_stats()

    # -- allocate / extend / release / free -----------------------------------

    def can_allocate(self, n_tokens: int, shared: Sequence[int] = ()) -> bool:
        return all(m.can_allocate(n_tokens) for m in self._all)

    def allocate(self, rid: str, n_tokens: int,
                 shared: Sequence[int] = ()) -> None:
        if not self.can_allocate(n_tokens):
            raise BlockExhausted(f"{rid}: a group cannot hold {n_tokens} "
                                 f"tokens")
        for m in self._all:
            m.allocate(rid, n_tokens)
        self.note_peak()

    def ensure(self, rid: str, n_tokens: int) -> None:
        """Grow every group's table to ``n_tokens`` rows.  A group that
        runs out raises ``BlockExhausted`` with the others' growth kept:
        those pages stay the request's own, and the retry after a
        preemption finds them there."""
        try:
            for m in self._all:
                m.ensure(rid, n_tokens)
        finally:
            self.note_peak()

    def release_unseen(self, rid: str, kv_len: int) -> int:
        return sum(m.release_unseen(rid, kv_len) for m in self._all)

    def free(self, rid: str) -> None:
        for m in self._all:
            m.free(rid)

    # -- tables ---------------------------------------------------------------

    def padded_table(self, rid: str, width: int):
        """[groups, width]: the request's table in each group."""
        return [m.padded_table(rid, width) for m in self._all]

    def page_ids(self, rid: str, lo: int, hi: int, width: int):
        """[groups, width] (:meth:`BlockManager.page_ids` a group: a
        window group's released pages hold the null block already)."""
        return np.stack([m.page_ids(rid, lo, hi, width) for m in self._all])

    def capacity_tokens(self, rid: str) -> int:
        return min(m.capacity_tokens(rid) for m in self._all)
