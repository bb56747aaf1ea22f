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
  time — replays stay bitwise): under pool pressure the coldest leaves
  whose blocks nobody but the tree holds are freed, cascading upward.
"""
from __future__ import annotations

import itertools

import numpy as np


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
        for b in block_ids:
            if self._ref[b] <= 0:
                raise RuntimeError(
                    f"incref on free block {b} — stale block table or "
                    "radix node holding a freed block")
            self._ref[b] += 1

    def decref(self, block_ids):
        """Drop one reference per id; blocks reaching zero return to the
        free list. Double-frees raise instead of corrupting the pool."""
        for b in block_ids:
            if self._ref[b] <= 0:
                raise RuntimeError(
                    f"decref on free block {b} — double free")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(b)

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
    __slots__ = ("chunk", "block", "children", "parent", "last_used")

    def __init__(self, chunk, block, parent):
        self.chunk = chunk          # tuple of block_size token ids
        self.block = block          # physical block id holding its KV
        self.children = {}          # chunk tuple -> _Node
        self.parent = parent
        self.last_used = 0


class RadixPrefixCache:
    """Block-granular prefix tree handing immutable KV blocks to new
    requests by refcount. One tree per engine; single-threaded (the
    engine's driver thread owns it, like every other slot structure)."""

    def __init__(self, pool, block_size):
        self.pool = pool
        self.block_size = int(block_size)
        self._root = _Node((), 0, None)
        self._clock = itertools.count(1)
        self._nodes = 0
        self.generation = 0

    def __len__(self):
        return self._nodes

    def _chunks(self, tokens):
        bs = self.block_size
        n = len(tokens) // bs
        return [tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
                for i in range(n)]

    def match(self, tokens):
        """Longest cached block-aligned prefix of ``tokens``. Returns the
        matched physical block ids, root-first (prefix length is
        ``len(ids) * block_size``); matched nodes' LRU clocks refresh."""
        node, out = self._root, []
        for chunk in self._chunks(tokens):
            child = node.children.get(chunk)
            if child is None:
                break
            child.last_used = next(self._clock)
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
                child = _Node(chunk, block, node)
                node.children[chunk] = child
                self.pool.incref([block])
                self._nodes += 1
                created += 1
            child.last_used = next(self._clock)
            node = child
        return created

    def _evictable(self, node, out):
        """Depth-first collect of fully-evictable subtrees: a node whose
        block only the tree holds (refcount 1) and whose children are all
        evictable too can be freed leaf-first."""
        ok = self.pool.refcount(node.block) == 1
        for child in node.children.values():
            ok = self._evictable(child, out) and ok
        if ok:
            out.append(node)
        return ok

    def evictable_count(self):
        out = []
        for child in self._root.children.values():
            self._evictable(child, out)
        return len(out)

    def evict(self, n):
        """Free up to ``n`` cold blocks, coldest leaves first. Cascades:
        a parent becomes a leaf once its children are gone. Returns the
        number of blocks actually freed."""
        freed = 0
        while freed < n:
            leaves = []
            self._walk_leaves(self._root, leaves)
            victims = [lf for lf in leaves
                       if self.pool.refcount(lf.block) == 1]
            if not victims:
                break
            victims.sort(key=lambda nd: nd.last_used)
            for nd in victims:
                if freed >= n:
                    break
                self._drop(nd)
                freed += 1
        return freed

    def _walk_leaves(self, node, out):
        for child in node.children.values():
            if child.children:
                self._walk_leaves(child, out)
            else:
                out.append(child)

    def _drop(self, node):
        del node.parent.children[node.chunk]
        self.pool.decref([node.block])
        self._nodes -= 1

    def flush(self):
        """Drop every entry (weight swap / reprime: KV from the old
        weight generation must never serve the new one). Blocks shared
        with in-flight slots stay alive through the slots' own refs."""

        def _free(node):
            for child in list(node.children.values()):
                _free(child)
            if node is not self._root:
                self.pool.decref([node.block])
        _free(self._root)
        self._root.children.clear()
        self._nodes = 0
        return self

    def new_generation(self):
        """Bump the weight-generation key and flush — the swap/reprime
        invalidation hook (satellite 1)."""
        self.generation += 1
        return self.flush()
