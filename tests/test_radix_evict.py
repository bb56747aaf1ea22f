"""The radix prefix cache's kept eviction order against a whole-tree walk.

``RadixPrefixCache`` keeps its evictable count and its evictable leaves (a
heap by ``last_used``) as the tree and the pool's refcounts change, instead
of walking the tree for them. The oracle here is the walk it replaced: a
node counts when its block's refcount is 1 and every child counts, and
eviction frees the coldest such leaf, one at a time. Seeded random traces
of admission (match, pin, allocate with eviction), installation (insert),
release, direct eviction, clock refreshes and flushes run through both and
compare at every step; a cost test counts the nodes eviction examines.
"""
import importlib.util
import os

import numpy as np
import pytest

from paddle_tpu.profiler import registry
from paddle_tpu.serving import BlockPool, PagePoolExhausted, RadixPrefixCache


def _nodes(cache):
    out, stack = [], list(cache._root.children.values())
    while stack:
        node = stack.pop()
        out.append(node)
        stack.extend(node.children.values())
    return out


def _oracle_evictable(cache):
    """The walk ``evictable_count`` used to make: fully evictable subtrees."""
    out = []

    def walk(node):
        ok = cache.pool.refcount(node.block) == 1
        for child in node.children.values():
            ok = walk(child) and ok
        if ok:
            out.append(node.block)
        return ok
    for child in cache._root.children.values():
        walk(child)
    return out


def _oracle_order(cache, n):
    """The blocks ``evict(n)`` must free, in order: each time the coldest
    leaf whose block only the tree holds (refcounts and clocks do not
    change while it runs)."""
    kids = {nd.block: len(nd.children) for nd in _nodes(cache)}
    parent = {nd.block: nd.parent.block for nd in _nodes(cache)}
    clock = {nd.block: nd.last_used for nd in _nodes(cache)}
    out = []
    while len(out) < n:
        leaves = [b for b, k in kids.items()
                  if not k and cache.pool.refcount(b) == 1]
        if not leaves:
            break
        b = min(leaves, key=clock.__getitem__)
        out.append(b)
        del kids[b]
        if parent[b] in kids:
            kids[parent[b]] -= 1
    return out


class _Checked:
    """A tree whose every eviction and state is held to the oracle."""

    def __init__(self, num_blocks, block_size):
        self.pool = BlockPool(num_blocks)
        self.cache = RadixPrefixCache(self.pool, block_size)
        self.dropped = []
        drop = self.cache._drop
        self.cache._drop = lambda nd: (self.dropped.append(nd.block),
                                       drop(nd))

    def evict(self, n):
        want = _oracle_order(self.cache, n)
        count = len(_oracle_evictable(self.cache))
        self.dropped.clear()
        freed = self.cache.evict(n)
        assert freed == min(n, count) == len(want)
        assert self.dropped == want  # each the coldest evictable leaf
        return freed

    def check(self):
        assert self.cache.evictable_count() \
            == len(_oracle_evictable(self.cache))
        assert len(self.cache) == len(_nodes(self.cache))
        self.pool.audit()


def _prompt(rng, bs, shared):
    if shared and rng.random() < 0.7:
        base = shared[rng.integers(len(shared))]
        cut = int(rng.integers(0, len(base) + 1))
        tail = list(rng.integers(1, 4, int(rng.integers(0, 3 * bs))))
        p = base[:cut] + tail
    else:
        p = list(rng.integers(1, 4, int(rng.integers(1, 6 * bs))))
    return p or [1]


@pytest.mark.parametrize("seed", range(8))
def test_kept_order_matches_the_walk_on_random_traces(seed):
    rng = np.random.default_rng(seed)
    bs, slots = 2, 4
    t = _Checked(num_blocks=int(rng.integers(24, 48)), block_size=bs)
    cache, pool = t.cache, t.pool
    shared = [list(rng.integers(1, 4, 4 * bs)) for _ in range(3)]
    begun, held = {}, {}   # slot -> (prompt, table) before / after insert
    for _ in range(300):
        op = rng.choice(["begin", "install", "release", "evict", "match",
                         "flush"], p=[.32, .25, .25, .1, .07, .01])
        free = [s for s in range(slots) if s not in begun and s not in held]
        if op == "begin" and free:
            prompt = _prompt(rng, bs, shared)
            full = len(prompt) // bs
            matched = cache.match(prompt)[:(len(prompt) - 1) // bs]
            pool.incref(matched)  # pinned before eviction runs
            try:
                fresh = pool.alloc(full - len(matched)
                                   + int(rng.integers(1, 3)),
                                   evict=t.evict)
            except PagePoolExhausted:
                pool.decref(matched)
            else:
                begun[free[0]] = (prompt, matched + fresh)
        elif op == "install" and begun:
            s = list(begun)[int(rng.integers(len(begun)))]
            prompt, table = begun.pop(s)
            full = len(prompt) // bs
            cache.insert(prompt[:full * bs], table[:full])
            held[s] = (prompt, table)
        elif op == "release" and (begun or held):
            pick = begun if begun and (not held or rng.random() < 0.3) \
                else held
            s = list(pick)[int(rng.integers(len(pick)))]
            pool.decref(pick.pop(s)[1])  # the whole path, one call
        elif op == "evict":
            t.evict(int(rng.integers(0, 12)))
        elif op == "match":
            cache.match(_prompt(rng, bs, shared))
        elif op == "flush":
            cache.flush()
            assert cache.evictable_count() == 0 and not cache._lru
        t.check()
    for pick in (begun, held):
        for _, table in pick.values():
            pool.decref(table)
    t.check()
    assert cache.evictable_count() == len(cache)
    t.evict(len(cache))
    assert pool.audit()["in_use"] == 0


def test_pinned_node_under_an_unpinned_parent():
    """Two slots bring one prompt before either installs it: the first
    insert makes the nodes, the second's duplicates stay private and its
    deeper chunks hang below them pinned by the second slot alone. When
    the first releases, the shared nodes are held by the tree only but
    still do not count: a pinned node lies below them."""
    t = _Checked(num_blocks=32, block_size=2)
    cache, pool = t.cache, t.pool
    p = [1, 2, 3, 1, 2, 3, 1, 2]
    a = pool.alloc(2)
    b = pool.alloc(4)
    assert cache.insert(p[:4], a) == 2
    assert cache.insert(p, b) == 2  # b[0], b[1] stay private
    pool.decref(a)
    t.check()
    assert cache.evictable_count() == 0 and t.evict(5) == 0
    pool.decref(b[:2])  # the private duplicates go back to the pool
    t.check()
    assert cache.evictable_count() == 0
    pool.decref(b[2:])
    t.check()
    assert cache.evictable_count() == 4
    assert t.evict(3) == 3
    assert cache.match(p) == a[:1]
    t.check()


def test_a_leaf_pinned_while_evicting_is_evicted_once_released():
    """Eviction pops the entry of a leaf a slot has pinned since it was
    queued; released, the leaf must join the order again."""
    t = _Checked(num_blocks=16, block_size=2)
    cache, pool = t.cache, t.pool
    for p in ([1, 1, 2, 2], [3, 3]):
        blocks = pool.alloc(len(p) // 2)
        cache.insert(p, blocks)
        pool.decref(blocks)
    hit = cache.match([1, 1, 2, 2, 5])  # the leaf queued at a new clock
    pool.incref(hit)                     # then pinned by a slot
    t.check()
    assert t.evict(8) == 1               # only [3, 3] is free to go
    pool.decref(hit)
    t.check()
    assert t.evict(8) == 2 and len(cache) == 0


def test_refreshes_leave_a_bounded_heap_and_the_same_order():
    """Every clock refresh of an evictable leaf queues it again; the stale
    entries are compacted away once the heap outgrows the tree, pinned
    leaves' included, and the order is still the walk's."""
    t = _Checked(num_blocks=32, block_size=2)
    cache, pool = t.cache, t.pool
    prompts = [[1, 1, 2, 2], [1, 1, 3, 3], [2, 2, 2, 2, 1, 1], [3, 3]]
    for p in prompts:
        blocks = pool.alloc(len(p) // 2)
        cache.insert(p, blocks)
        pool.decref(blocks)
    pinned = cache.match([3, 3, 1])
    pool.incref(pinned)
    for i in range(300):
        cache.match(prompts[i % 3] + [9])
        assert len(cache._lru) <= 2 * len(cache) + 64
    t.check()
    assert t.evict(4) == 4
    pool.decref(pinned)
    t.check()
    assert t.evict(len(cache)) == 3 and len(cache) == 0


def test_flush_and_new_generation_clear_the_kept_state():
    t = _Checked(num_blocks=16, block_size=2)
    cache, pool = t.cache, t.pool
    blocks = pool.alloc(3)
    cache.insert([1, 1, 2, 2, 3, 3], blocks)
    pool.decref(blocks[1:])  # the first stays held by its slot
    t.check()
    cache.new_generation()
    assert cache.generation == 1
    assert len(cache) == 0 and cache.evictable_count() == 0
    assert not cache._lru and not cache._by_block
    pool.decref(blocks[:1])  # no longer the tree's: no hook, freed
    assert pool.audit()["in_use"] == 0
    again = pool.alloc(2)
    cache.insert([1, 1, 2, 2], again)
    pool.decref(again)
    t.check()
    assert cache.evictable_count() == 2 and t.evict(2) == 2


def test_evict_examines_a_constant_per_block_on_the_long_prefill_shape():
    """A full pool of unshared prompts, the long-prefill cell's shape
    (1 + 8 x 128 blocks of 16 tokens, chains of 64-121 blocks): freeing 89
    blocks and counting what is left examine a few nodes a freed block,
    where the walk examined the whole tree every round."""
    rng = np.random.default_rng(0)
    bs = 16
    pool = BlockPool(1 + 8 * 128)
    cache = RadixPrefixCache(pool, bs)
    while True:
        n = int(rng.integers(64, 122))
        if n > pool.free_count():
            break
        blocks = pool.alloc(n)
        cache.insert(list(rng.integers(0, 50000, n * bs)), blocks)
        pool.decref(blocks)  # released: the tree holds the chain alone
    assert len(cache) >= 900 and cache.evictable_count() == len(cache)
    nodes = len(cache)
    v0 = registry.counters("serving")["prefix_evict_visits"]
    assert cache.evict(89) == 89
    assert cache.evictable_count() == nodes - 89
    visits = registry.counters("serving")["prefix_evict_visits"] - v0
    assert 89 <= visits <= 2 * 89
    pool.audit()


def test_engine_counts_visits_beside_evicted_blocks():
    """Through the engine: admissions into a full pool evict, and
    ``serving.prefix_evict_visits`` stands beside
    ``serving.prefix_evicted_blocks`` in the registry and in ``stats()``,
    one visit a freed block on unshared prompts; the benchmark's reader
    gives their ratio, and nothing where no block was evicted or the
    program has no such counter."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining, GPTModel
    from paddle_tpu.serving import GenerationEngine

    paddle.seed(3)
    model = GPTForPretraining(GPTModel(GPTConfig(
        vocab_size=64, n_layer=1, n_head=2, d_model=32, seq_len=64)))
    eng = GenerationEngine(model, max_batch_size=2, buckets=(16,),
                           rng_seed=1, block_size=4, num_blocks=13)
    rng = np.random.default_rng(7)
    c0 = registry.counters("serving")
    for i in range(6):
        prompt = [int(x) for x in rng.integers(1, 64, 12)]
        assert eng.can_admit(prompt, max_new_tokens=4)
        eng.prefill(i % 2, prompt, max_new_tokens=4)
        eng.release(i % 2)
        eng.pool.audit()
    c1 = registry.counters("serving")
    evicted = c1["prefix_evicted_blocks"] - c0["prefix_evicted_blocks"]
    visits = c1["prefix_evict_visits"] - c0["prefix_evict_visits"]
    assert evicted > 0 and visits == evicted
    assert eng.stats()["prefix_evict_visits"] == c1["prefix_evict_visits"]
    assert eng.prefix_cache.evictable_count() == len(eng.prefix_cache)

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "benchmark", "layer_metrics",
        "kv.evict_visits_per_block.serve.py")
    spec = importlib.util.spec_from_file_location("evict_visits", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    window = {"serving." + k: c1[k] - c0.get(k, 0) for k in c1}
    assert reader.read({"counters": window}) == 1.0
    window["serving.prefix_evicted_blocks"] = 0
    assert reader.read({"counters": window}) is None
    assert reader.read({"counters": {
        "serving.prefix_evicted_blocks": evicted}}) is None
