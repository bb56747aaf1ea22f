"""paddle_tpu.serving.block_pool — paged-KV block accounting + prefix radix tree.

Host-side bookkeeping for the serving engine's paged KV cache (ISSUE 10).
The device side is a fixed-shape pool per layer — ``[num_blocks,
block_size, heads * head_dim]`` (``ops/kv_pool.py``) — addressed through
per-slot block tables;
nothing here ever touches a device array. Two pieces:

* :class:`BlockPool` — a refcounted free list over physical block ids.
  Block 0 is RESERVED as the garbage block: padded block-table entries and
  masked-out lanes write/read it, so a stray lane can never corrupt a
  block that belongs to someone else. A block is held by every slot whose
  table contains it plus (for shared prefix blocks) by the radix tree;
  it returns to the free list when the last reference drops. ``audit()``
  cross-checks the free list against the refcounts so leak/double-free
  bugs fail tests instead of slowly eating the pool.

* :class:`RadixPrefixCache` — a radix tree over block-aligned token
  chunks (RadixAttention-style, Zheng et al. 2023): one node per
  ``block_size``-token chunk, keyed by the chunk's token tuple, holding
  the physical block where that chunk's KV rows live. A new request walks
  the tree with its prompt's chunks; every matched node hands its
  IMMUTABLE block to the request by refcount instead of recomputing the
  prefill — thousands of requests sharing a system prompt share its KV
  bytes and skip its FLOPs. Sharing is full-block granularity only: the
  partial tail block of a prompt is always freshly allocated, so shared
  blocks are never written after insertion.

  Entries are keyed by the engine's **weight generation**: a weight
  hot-swap (or ``reprime()``) bumps the generation and flushes the tree,
  because KV computed under the old weights is garbage under the new ones
  (the satellite-1 regression in tests/test_paged_kv.py pins this).
  Eviction is leaf-first LRU over a deterministic logical clock (no wall
  time — replays stay bitwise): under pool pressure the coldest leaf
  whose block nobody but the tree holds is freed, one at a time, and a
  parent left a leaf joins the order at its own clock. The tree keeps
  what eviction needs as it changes instead of walking for it: the
  evictable count, and the evictable leaves in a heap by clock. The pool
  tells it when a block it holds goes from refcount 1 to 2 and back
  (:meth:`BlockPool.watch`), so ``evictable_count()`` is O(1) and
  ``evict(n)`` is O(n log nodes); ``serving.prefix_evict_visits`` counts
  the heap entries eviction examines.
"""
from __future__ import annotations

import heapq
import itertools

import numpy as np

from ..profiler import registry as _registry

_counters = _registry.scoped_counters("serving", {"prefix_evict_visits": 0})


class PagePoolExhausted(RuntimeError):
    """The KV block pool cannot cover a request even after evicting every
    cold prefix block. The scheduler answers this with admission
    backpressure (the request stays queued; ``submit()`` fast-fails with
    ``QueueFullError`` once the queue is full) — never a crash and never
    a silently truncated generation."""


class BlockPool:
    """Refcounted allocator over ``num_blocks`` physical KV blocks.

    Block 0 is reserved (the garbage block) and is never handed out:
    zero-padded block-table entries point at it by construction, so the
    decode step's masked lanes scribble there instead of into live data.
    """

    def __init__(self, num_blocks, name=""):
        self.num_blocks = int(num_blocks)
        # `name` labels multi-pool engines' errors (the spec-decode
        # drafter runs its own pool: "draft KV block pool exhausted"
        # must not read like the target pool backpressuring)
        self.name = str(name)
        if self.num_blocks < 2:
            raise ValueError(
                f"BlockPool needs >= 2 blocks (1 reserved + 1 usable), "
                f"got {num_blocks}")
        # LIFO free list: recently-freed blocks are reused first, which
        # keeps the hot working set small and allocation order (hence
        # every downstream table/token stream) deterministic
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._ref = np.zeros(self.num_blocks, np.int32)
        self._watched = {}
        self._on_cross = None

    def watch(self, watched, on_cross):
        """Call ``on_cross(blocks)`` once an ``incref`` / ``decref`` with
        the blocks keyed in ``watched`` whose refcount crossed between 1
        and 2, in call order: the radix tree's hook, since a block only
        the tree holds is evictable and one a slot holds too is not."""
        self._watched = watched
        self._on_cross = on_cross

    @property
    def usable_blocks(self):
        return self.num_blocks - 1

    def free_count(self):
        return len(self._free)

    def in_use(self):
        return self.usable_blocks - len(self._free)

    def alloc(self, n, evict=None):
        """Allocate ``n`` blocks (refcount 1 each). When the free list is
        short and ``evict`` is given, it is asked to free the shortfall
        (the radix cache's LRU eviction) before giving up."""
        n = int(n)
        if n > len(self._free) and evict is not None:
            evict(n - len(self._free))
        if n > len(self._free):
            label = f"{self.name} KV" if self.name else "KV"
            raise PagePoolExhausted(
                f"{label} block pool exhausted: need {n} blocks, "
                f"{len(self._free)}/{self.usable_blocks} free and nothing "
                "left to evict")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def incref(self, block_ids):
        ref, watched, crossed = self._ref, self._watched, []
        for b in block_ids:
            if ref[b] <= 0:
                raise RuntimeError(
                    f"incref on free block {b} — stale block table or "
                    "radix node holding a freed block")
            ref[b] += 1
            if ref[b] == 2 and b in watched:
                crossed.append(b)
        if crossed:
            self._on_cross(crossed)

    def decref(self, block_ids):
        """Drop one reference per id; blocks reaching zero return to the
        free list. Double-frees raise instead of corrupting the pool."""
        ref, watched, crossed = self._ref, self._watched, []
        for b in block_ids:
            if ref[b] <= 0:
                raise RuntimeError(
                    f"decref on free block {b} — double free")
            ref[b] -= 1
            if ref[b] == 0:
                self._free.append(b)
            elif ref[b] == 1 and b in watched:
                crossed.append(b)
        if crossed:
            self._on_cross(crossed)

    def refcount(self, block_id):
        return int(self._ref[block_id])

    def audit(self):
        """Invariant check: every usable block is either on the free list
        with refcount 0 or off it with refcount > 0, exactly once.
        Returns the accounting summary; raises on any violation."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise AssertionError("free list contains duplicates")
        if 0 in free:
            raise AssertionError("reserved garbage block 0 was freed into "
                                 "the pool")
        for b in range(1, self.num_blocks):
            ref = int(self._ref[b])
            if b in free and ref != 0:
                raise AssertionError(
                    f"block {b} is free but has refcount {ref}")
            if b not in free and ref <= 0:
                raise AssertionError(
                    f"block {b} is in use but has refcount {ref} (leak)")
        return {"total": self.usable_blocks, "free": len(self._free),
                "in_use": self.in_use(),
                "ref_total": int(self._ref[1:].sum())}


class _Node:
    __slots__ = ("chunk", "block", "children", "parent", "last_used",
                 "bad", "ok", "queued")

    def __init__(self, chunk, block, parent):
        self.chunk = chunk          # tuple of block_size token ids
        self.block = block          # physical block id holding its KV
        self.children = {}          # chunk tuple -> _Node
        self.parent = parent        # None once dropped
        self.last_used = 0
        self.bad = 0                # children that are not evictable
        self.ok = False             # evictable: refcount 1, bad == 0
        self.queued = None          # last_used of its live LRU heap entry


class RadixPrefixCache:
    """Block-granular prefix tree handing immutable KV blocks to new
    requests by refcount. One tree per engine; single-threaded (the
    engine's driver thread owns it, like every other slot structure).

    A node is evictable when its block's refcount is 1 (the tree's own)
    and all its children are evictable; ``evictable_count()`` is their
    number and ``evict`` frees them leaf first. Both are kept as the tree
    and the refcounts change: each node counts its children that are not
    evictable, a change carries up only as far as it flips a node, and
    the evictable leaves sit in a heap keyed by ``last_used`` (unique:
    one clock tick a node) whose stale entries are skipped when popped.
    """

    def __init__(self, pool, block_size):
        self.pool = pool
        self.block_size = int(block_size)
        self._root = _Node((), 0, None)
        self._clock = itertools.count(1)
        self._nodes = 0
        self.generation = 0
        self._by_block = {}         # block id -> its node
        self._n_evictable = 0
        self._lru = []              # (last_used, node), evictable leaves
        pool.watch(self._by_block, self._cross)

    def __len__(self):
        return self._nodes

    def _chunks(self, tokens):
        bs = self.block_size
        n = len(tokens) // bs
        return [tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
                for i in range(n)]

    def _touch(self, node):
        node.last_used = next(self._clock)
        if node.ok and not node.children:
            self._offer(node)

    def match(self, tokens):
        """Longest cached block-aligned prefix of ``tokens``. Returns the
        matched physical block ids, root-first (prefix length is
        ``len(ids) * block_size``); matched nodes' LRU clocks refresh."""
        node, out = self._root, []
        for chunk in self._chunks(tokens):
            child = node.children.get(chunk)
            if child is None:
                break
            self._touch(child)
            out.append(child.block)
            node = child
        return out

    def insert(self, tokens, block_ids):
        """Record ``tokens`` (block-aligned; ``len == len(block_ids) *
        block_size``) as a shareable prefix. Walks the tree; existing
        nodes win (their block is the canonical copy — the caller's
        duplicate block stays private to its slot), new nodes take one
        tree reference on the caller's block. Returns how many new
        blocks became shared."""
        node, created = self._root, 0
        for chunk, block in zip(self._chunks(tokens), block_ids):
            child = node.children.get(chunk)
            if child is None:
                if block in self._by_block:
                    raise RuntimeError(
                        f"block {block} is already a radix node's")
                self.pool.incref([block])  # not yet watched: no hook
                child = _Node(chunk, block, node)  # the caller holds it too
                node.children[chunk] = child
                self._by_block[block] = child
                self._nodes += 1
                created += 1
                node.bad += 1
                self._settle(node)
            self._touch(child)
            node = child
        return created

    def _cross(self, blocks):
        """The pool's hook: these blocks' refcounts crossed 1 <-> 2. A
        slot's release hands its whole root-first path in one call; taken
        leaf first, each node settles against children already settled,
        so the path costs O(path)."""
        for b in reversed(blocks):
            self._settle(self._by_block[b])

    def _settle(self, node):
        """Recompute whether ``node`` is evictable and carry a flip up
        through the parents' counts of children that are not."""
        root, ref = self._root, self.pool.refcount
        while node is not root:
            ok = not node.bad and ref(node.block) == 1
            if ok == node.ok:
                return
            node.ok = ok
            if ok:
                self._n_evictable += 1
                if not node.children:
                    self._offer(node)
            else:
                self._n_evictable -= 1
            node = node.parent
            node.bad += -1 if ok else 1  # (the root's is never read)

    def _offer(self, node):
        """An evictable leaf joins the LRU order at its ``last_used``."""
        if node.queued == node.last_used:
            return                  # its entry is in the heap already
        node.queued = node.last_used
        heapq.heappush(self._lru, (node.last_used, node))
        if len(self._lru) > 2 * self._nodes + 64:
            self._compact()

    def _compact(self):
        """Drop the heap's stale entries (refreshed, pinned, no longer a
        leaf, dropped); at most half the heap once it outgrows the tree."""
        live = []
        for lu, node in self._lru:
            if node.parent is not None and lu == node.last_used \
                    and node.ok and not node.children:
                live.append((lu, node))
            elif node.queued == lu:
                node.queued = None
        self._lru[:] = live
        heapq.heapify(self._lru)

    def evictable_count(self):
        return self._n_evictable

    def evict(self, n):
        """Free up to ``n`` cold blocks, the coldest evictable leaf first.
        Cascades: a parent left an evictable leaf joins the order at its
        own ``last_used`` (older than its children's, so an unshared
        chain empties before the next). Returns the number freed."""
        freed, lru, root, visits = 0, self._lru, self._root, 0
        while freed < n and lru:
            lu, node = heapq.heappop(lru)
            visits += 1
            if node.parent is None or lu != node.last_used:
                continue            # dropped, or refreshed since
            node.queued = None
            if not node.ok or node.children:
                continue            # re-offered when it is a leaf again
            parent = node.parent
            self._drop(node)
            freed += 1
            if parent is not root and parent.ok and not parent.children:
                self._offer(parent)
        _counters["prefix_evict_visits"] += visits
        return freed

    def _drop(self, node):
        """Free an evictable leaf (its parent's count is unchanged)."""
        del node.parent.children[node.chunk]
        del self._by_block[node.block]
        node.parent = None
        self._nodes -= 1
        self._n_evictable -= 1
        self.pool.decref([node.block])

    def flush(self):
        """Drop every entry (weight swap / reprime: KV from the old
        weight generation must never serve the new one). Blocks shared
        with in-flight slots stay alive through the slots' own refs."""

        def _free(node):
            for child in list(node.children.values()):
                _free(child)
            if node is not self._root:
                node.parent = None
                self.pool.decref([node.block])
        self._by_block.clear()      # unwatched first: no hook fires
        self._lru.clear()
        self._n_evictable = 0
        _free(self._root)
        self._root.children.clear()
        self._root.bad = 0
        self._nodes = 0
        return self

    def new_generation(self):
        """Bump the weight-generation key and flush — the swap/reprime
        invalidation hook (satellite 1)."""
        self.generation += 1
        return self.flush()
