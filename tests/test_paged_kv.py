"""Paged KV cache + radix prefix reuse + mesh-sharded decode (ISSUE 10).

Covers the acceptance gates:
  * shared-system-prompt traffic is token-BITWISE identical to the cold
    path (prefix-hit tokens vs recomputed tokens), greedy AND sampled;
  * refcounted block release leaves no leaked or double-freed blocks
    (``BlockPool.audit`` invariants after churn, eviction and flush);
  * ``page_pool_exhausted`` answers with admission backpressure +
    ``QueueFullError`` + the ``serving.pool_exhausted`` counter — never a
    crash or a silently truncated generation (fault-injected AND with a
    genuinely tiny pool);
  * ``swap_weights`` / ``reprime`` invalidate the prefix cache (satellite
    1 regression: a post-swap request with a cached prefix gets
    freshly-computed blocks);
  * mesh-sharded decode (mp=2 over the forced-host-device mesh) is
    token-bitwise vs the single-chip engine for a gpt2-tiny-shaped model;
  * the pool's device form (PR 28: heads merged, ``ops/kv_pool.py``): the
    row write is bit-equal to the element scatter it replaced, padding
    and dead lanes land only in garbage block 0, token streams are the
    parent commit's, and the handoff payload keeps its 4-D blocks.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import registry
from paddle_tpu.serving import (BlockPool, GenerationEngine,
                                GenerationServer, PagePoolExhausted,
                                QueueFullError, RadixPrefixCache,
                                RequestStatus)

VOCAB = 96


def _build_model(seed=11):
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTModel)

    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=VOCAB, n_layer=2, n_head=2, d_model=48,
                    seq_len=64, initializer_range=0.35)
    return GPTForPretraining(GPTModel(cfg))


def _greedy_straightline(model, prompt, n):
    ids = list(prompt)
    out = []
    with paddle.no_grad():
        for _ in range(n):
            logits = model(paddle.to_tensor(np.asarray([ids], np.int64)))
            t = int(np.asarray(logits.numpy())[0, -1].argmax())
            out.append(t)
            ids.append(t)
    return out


def _run_one(eng, prompt, n, seed=0, **kw):
    tok = eng.prefill(0, prompt, seed=seed, **kw)
    out = [tok]
    for _ in range(n - 1):
        out.append(int(eng.decode_step()[0]))
    eng.release(0)
    return out


class TestBlockPoolUnit:
    def test_alloc_free_audit_roundtrip(self):
        pool = BlockPool(8)
        a = pool.alloc(3)
        b = pool.alloc(2)
        assert len(set(a) | set(b)) == 5 and 0 not in a + b
        pool.incref(a)          # a second holder (a prefix tree, say)
        pool.decref(a)
        assert pool.in_use() == 5  # still held once each
        pool.decref(a + b)
        assert pool.in_use() == 0
        assert pool.audit()["free"] == 7

    def test_double_free_and_stale_incref_raise(self):
        pool = BlockPool(4)
        (blk,) = pool.alloc(1)
        pool.decref([blk])
        with pytest.raises(RuntimeError, match="double free"):
            pool.decref([blk])
        with pytest.raises(RuntimeError, match="free block"):
            pool.incref([blk])

    def test_exhaustion_raises_after_eviction_hook(self):
        pool = BlockPool(4)
        pool.alloc(3)
        calls = []
        with pytest.raises(PagePoolExhausted):
            pool.alloc(1, evict=lambda n: calls.append(n))
        assert calls == [1]  # the hook was consulted for the shortfall

    def test_radix_match_insert_evict(self):
        pool = BlockPool(16)
        cache = RadixPrefixCache(pool, block_size=4)
        toks = list(range(1, 13))  # 3 full blocks
        blocks = pool.alloc(3)
        assert cache.insert(toks, blocks) == 3
        assert cache.match(toks) == blocks
        assert cache.match(toks[:8]) == blocks[:2]
        assert cache.match([9] + toks[1:]) == []
        # while the caller (a slot) still holds refs nothing is evictable
        assert cache.evictable_count() == 0
        pool.decref(blocks)  # caller's refs gone; tree still holds them
        assert cache.evictable_count() == 3
        assert cache.evict(2) == 2
        assert cache.match(toks) == blocks[:1]
        cache.flush()
        assert len(cache) == 0
        assert pool.audit()["in_use"] == 0


class TestPrefixReuseBitwise:
    @pytest.fixture(scope="class")
    def rig(self):
        model = _build_model(seed=41)
        eng = GenerationEngine(model, max_batch_size=2, buckets=(8, 16),
                               rng_seed=9, block_size=4)
        return model, eng

    def test_greedy_hit_matches_straightline_oracle(self, rig):
        model, eng = rig
        rng = np.random.default_rng(1)
        sys_prompt = list(rng.integers(1, VOCAB, 8))  # 2 full blocks
        p1 = sys_prompt + list(rng.integers(1, VOCAB, 3))
        p2 = sys_prompt + list(rng.integers(1, VOCAB, 4))
        c0 = dict(registry.counters("serving"))
        got1 = _run_one(eng, p1, 6, seed=0)
        got2 = _run_one(eng, p2, 6, seed=1)  # hits p1's prefix blocks
        c1 = dict(registry.counters("serving"))
        assert c1["prefix_hits"] - c0["prefix_hits"] == 1
        assert c1["prefix_hit_tokens"] - c0["prefix_hit_tokens"] == 8
        assert got1 == _greedy_straightline(model, p1, 6)
        assert got2 == _greedy_straightline(model, p2, 6)

    def test_sampled_hit_bitwise_vs_cold_engine(self, rig):
        """The hit path must reproduce the COLD path token for token
        under sampling too: a fresh engine (empty prefix cache) with the
        same rng_seed is the recompute oracle."""
        model, eng = rig
        rng = np.random.default_rng(2)
        sys_prompt = list(rng.integers(1, VOCAB, 8))
        p = sys_prompt + list(rng.integers(1, VOCAB, 3))
        kw = dict(seed=77, temperature=0.9, top_k=30)
        _run_one(eng, sys_prompt + [5, 6, 7], 4, seed=3)  # primes cache
        c0 = dict(registry.counters("serving"))
        hit = _run_one(eng, p, 8, **kw)
        assert registry.counters("serving")["prefix_hits"] \
            == c0["prefix_hits"] + 1
        cold_eng = GenerationEngine(model, max_batch_size=2,
                                    buckets=(8, 16), rng_seed=9,
                                    block_size=4)
        cold = _run_one(cold_eng, p, 8, **kw)
        assert hit == cold

    def test_shared_prefix_server_traffic_matches_cold(self):
        """8 requests sharing a system prompt through the full server
        stack: > 0.5 hit rate and every response equals its straight-line
        truth."""
        model = _build_model(seed=43)
        srv = GenerationServer(model, max_batch_size=3, buckets=(8, 16),
                               max_queue_size=32, block_size=4)
        srv.start()
        try:
            rng = np.random.default_rng(5)
            sys_prompt = list(rng.integers(1, VOCAB, 8))
            prompts = [sys_prompt + list(rng.integers(1, VOCAB, 3))
                       for _ in range(8)]
            c0 = dict(registry.counters("serving"))
            reqs = [srv.submit(p, max_new_tokens=5) for p in prompts]
            got = [list(r.result(120).tokens) for r in reqs]
            c1 = dict(registry.counters("serving"))
            hits = c1["prefix_hits"] - c0["prefix_hits"]
            misses = c1["prefix_misses"] - c0["prefix_misses"]
            assert hits / (hits + misses) > 0.5
            for p, g in zip(prompts, got):
                assert g == _greedy_straightline(model, p, 5)
        finally:
            srv.shutdown(timeout=30)


class TestPoolAccounting:
    def test_no_leak_no_double_free_after_churn(self):
        eng = GenerationEngine(_build_model(seed=45), max_batch_size=2,
                               buckets=(8, 16), rng_seed=1, block_size=4)
        rng = np.random.default_rng(3)
        shared = list(rng.integers(1, VOCAB, 8))
        for i in range(6):  # overlapping admissions + releases
            p = shared + list(rng.integers(1, VOCAB, 1 + i % 3))
            eng.prefill(i % 2, p, seed=i, max_new_tokens=4)
            eng.decode_step()
            eng.release(i % 2)
            eng.pool.audit()  # invariants hold at every boundary
        # all slots free: only the radix tree holds blocks
        audit = eng.pool.audit()
        assert audit["in_use"] == len(eng.prefix_cache)
        assert eng.prefix_cache.evictable_count() == audit["in_use"]
        eng.prefix_cache.flush()
        assert eng.pool.audit()["in_use"] == 0

    def test_eviction_under_pressure_keeps_accounting(self):
        # pool too small for two disjoint working sets: admitting the
        # second prompt family must evict the first's cold prefix
        eng = GenerationEngine(_build_model(seed=46), max_batch_size=1,
                               buckets=(8, 16), rng_seed=1, block_size=4,
                               num_blocks=5)  # 4 usable
        rng = np.random.default_rng(4)
        p1 = list(rng.integers(1, VOCAB, 8))
        p2 = list(rng.integers(1, VOCAB, 8))
        c0 = dict(registry.counters("serving"))
        _run_one(eng, p1, 3, seed=0, max_new_tokens=2)
        assert len(eng.prefix_cache) == 2  # p1's blocks cached
        _run_one(eng, p2, 3, seed=1, max_new_tokens=2)
        c1 = dict(registry.counters("serving"))
        assert c1["prefix_evicted_blocks"] - c0["prefix_evicted_blocks"] > 0
        eng.pool.audit()
        eng.prefix_cache.flush()
        assert eng.pool.audit()["in_use"] == 0


class TestPoolExhaustionBackpressure:
    @pytest.fixture(autouse=True)
    def _disarm(self):
        yield
        from paddle_tpu.testing import faults
        faults.reset()

    def test_fault_injected_exhaustion_backpressures_then_recovers(self):
        from paddle_tpu.testing import faults

        eng = GenerationEngine(_build_model(seed=47), max_batch_size=2,
                               buckets=(8,), rng_seed=1, block_size=4)
        from paddle_tpu.serving import ContinuousBatchScheduler, \
            GenerationRequest

        sched = ContinuousBatchScheduler(eng, max_queue_size=2)
        c0 = dict(registry.counters("serving"))
        faults.configure("page_pool_exhausted:times=3")
        reqs = [sched.submit(GenerationRequest([1 + i, 2, 3],
                                               max_new_tokens=3))
                for i in range(2)]
        sched.step()  # admission blocked: both stay queued
        assert all(r.status == RequestStatus.QUEUED for r in reqs)
        assert registry.counters("serving")["pool_exhausted"] \
            > c0["pool_exhausted"]
        # the queue is full while the pool is "exhausted": submit()
        # turns pool pressure into QueueFullError backpressure
        with pytest.raises(QueueFullError):
            sched.submit(GenerationRequest([9, 9], max_new_tokens=2))
        # fault budget (3) exhausted: traffic drains completely — no
        # crash, and NO truncation (every request gets its full budget)
        while sched.has_work():
            sched.step()
        assert all(r.status == RequestStatus.DONE for r in reqs)
        assert all(len(r.tokens) == 3 for r in reqs)
        eng.pool.audit()

    def test_prefill_exhaustion_requeues_without_spinning_step(self):
        """Belt-and-braces path: if prefill raises PagePoolExhausted
        despite can_admit saying yes (over-commit policies, drift), the
        request requeues at the head and step() RETURNS — it must not
        spin the admission loop forever."""
        eng = GenerationEngine(_build_model(seed=49), max_batch_size=2,
                               buckets=(8,), rng_seed=1, block_size=4,
                               num_blocks=4)  # 3 usable
        eng.can_admit = lambda *a, **kw: True  # lie: force the raise path
        from paddle_tpu.serving import ContinuousBatchScheduler, \
            GenerationRequest

        sched = ContinuousBatchScheduler(eng, max_queue_size=8)
        a = sched.submit(GenerationRequest([1, 2, 3, 4, 5],
                                           max_new_tokens=6))  # 3 blocks
        b = sched.submit(GenerationRequest([6, 7, 8, 9, 10],
                                           max_new_tokens=6))
        c0 = registry.counters("serving")["pool_exhausted"]
        sched.step()  # a admitted; b's prefill raises, requeues, returns
        assert a.status == RequestStatus.RUNNING
        assert b.status == RequestStatus.QUEUED
        assert registry.counters("serving")["pool_exhausted"] == c0 + 1
        while sched.has_work():
            sched.step()  # a finishes, frees blocks, b then admits
        assert a.status == b.status == RequestStatus.DONE
        assert len(a.tokens) == len(b.tokens) == 6
        eng.pool.audit()

    def test_real_tiny_pool_serializes_requests_without_truncation(self):
        # 3 usable blocks, each request needs 3 → strictly one at a time
        # even though TWO slots are free: admission budgets blocks, not
        # slots
        eng = GenerationEngine(_build_model(seed=48), max_batch_size=2,
                               buckets=(8,), rng_seed=1, block_size=4,
                               num_blocks=4)
        from paddle_tpu.serving import ContinuousBatchScheduler, \
            GenerationRequest

        sched = ContinuousBatchScheduler(eng, max_queue_size=8)
        c0 = dict(registry.counters("serving"))
        reqs = [sched.submit(GenerationRequest(
                    [1 + i, 2, 3, 4, 5], max_new_tokens=6))
                for i in range(3)]
        sched.step()
        assert sum(r.status == RequestStatus.RUNNING for r in reqs) == 1
        assert registry.counters("serving")["pool_exhausted"] \
            > c0["pool_exhausted"]
        while sched.has_work():
            sched.step()
        assert all(r.status == RequestStatus.DONE for r in reqs)
        assert all(len(r.tokens) == 6 for r in reqs)
        audit = eng.pool.audit()
        assert audit["in_use"] == len(eng.prefix_cache)


class TestSwapInvalidatesPrefixCache:
    def test_post_swap_request_recomputes_cached_prefix(self):
        """Satellite 1 regression: prefix blocks computed under old
        weights must never serve after a hot-swap — the post-swap request
        MISSES the cache, recomputes, and its tokens match the NEW
        model's straight-line truth."""
        m_a = _build_model(seed=51)
        m_b = _build_model(seed=52)
        b_sd = {k: np.asarray(v.numpy()).copy()
                for k, v in m_b.gpt.state_dict().items()}
        eng = GenerationEngine(m_a, max_batch_size=2, buckets=(8, 16),
                               rng_seed=2, block_size=4)
        rng = np.random.default_rng(6)
        sys_prompt = list(rng.integers(1, VOCAB, 8))
        p = sys_prompt + [3, 4, 5]
        _run_one(eng, p, 4, seed=0)           # caches the prefix
        c0 = dict(registry.counters("serving"))
        got = _run_one(eng, p, 4, seed=1)     # hit, old weights
        assert registry.counters("serving")["prefix_hits"] \
            == c0["prefix_hits"] + 1
        assert got == _greedy_straightline(m_a, p, 4)
        gen0 = eng.prefix_cache.generation
        eng.swap_weights(b_sd, source="test")
        assert eng.prefix_cache.generation == gen0 + 1
        assert len(eng.prefix_cache) == 0     # flushed, nothing matchable
        c1 = dict(registry.counters("serving"))
        got_b = _run_one(eng, p, 4, seed=2)
        c2 = dict(registry.counters("serving"))
        assert c2["prefix_hits"] == c1["prefix_hits"]      # no stale hit
        assert c2["prefix_misses"] == c1["prefix_misses"] + 1
        assert got_b == _greedy_straightline(m_b, p, 4)
        eng.pool.audit()

    def test_reprime_flushes_prefix_cache(self):
        eng = GenerationEngine(_build_model(seed=53), max_batch_size=1,
                               buckets=(8, 16), rng_seed=2, block_size=4)
        p = list(np.random.default_rng(7).integers(1, VOCAB, 9))
        _run_one(eng, p, 3, seed=0)
        assert len(eng.prefix_cache) == 2
        gen0 = eng.prefix_cache.generation
        eng.reprime()
        assert eng.prefix_cache.generation == gen0 + 1
        assert len(eng.prefix_cache) == 0
        assert eng.pool.audit()["in_use"] == 0

    def test_inflight_shared_blocks_survive_swap_flush(self):
        """A swap mid-flight flushes the tree, but blocks shared with an
        ACTIVE slot stay alive through the slot's own reference (the
        in-flight request keeps decoding on its pre-swap prefix KV, per
        the hot-swap contract)."""
        m_a = _build_model(seed=54)
        b_sd = {k: np.asarray(v.numpy()).copy()
                for k, v in _build_model(seed=55).gpt.state_dict().items()}
        eng = GenerationEngine(m_a, max_batch_size=2, buckets=(8, 16),
                               rng_seed=2, block_size=4)
        p = list(np.random.default_rng(8).integers(1, VOCAB, 9))
        eng.prefill(0, p, seed=0, max_new_tokens=8)
        held = list(eng._slot_blocks[0])
        eng.swap_weights(b_sd, source="midflight")
        eng.pool.audit()   # tree refs dropped, slot refs intact
        assert all(eng.pool.refcount(b) == 1 for b in held)
        eng.decode_step()  # still serves without error
        eng.release(0)
        assert eng.pool.audit()["in_use"] == 0


class TestMeshShardedDecode:
    """mp=2 decode over the forced-host-device CPU mesh must be
    token-bitwise vs the single-chip engine (the plain-GSPMD jit it uses
    is the same machinery test_spmd exercises); guarded on device count
    like the other multi-chip suites."""

    @pytest.mark.skipif(
        __import__("jax").device_count() < 2,
        reason="needs >= 2 (forced host) devices for mp=2")
    def test_mp2_decode_bitwise_vs_single_chip(self):
        from paddle_tpu.distributed import spmd

        def build():
            return _build_model(seed=61)

        rng = np.random.default_rng(9)
        prompts = [list(rng.integers(1, VOCAB, n)) for n in (5, 9)]
        kws = [dict(seed=11, temperature=0.0),
               dict(seed=12, temperature=0.9, top_k=25)]

        single = GenerationEngine(build(), max_batch_size=2,
                                  buckets=(8, 16), rng_seed=13,
                                  block_size=4)
        want = [_run_one(single, p, 7, **kw)
                for p, kw in zip(prompts, kws)]

        mesh = spmd.serving_mesh(2)
        sharded = GenerationEngine(build(), max_batch_size=2,
                                   buckets=(8, 16), rng_seed=13,
                                   block_size=4, mesh=mesh)
        # weights and KV pools really live on 2 devices
        qkv = sharded._state[
            "blocks.0.attn.qkv_proj.weight"]._data
        assert len(qkv.devices()) == 2
        assert len(sharded._k[0].devices()) == 2
        got = [_run_one(sharded, p, 7, **kw)
               for p, kw in zip(prompts, kws)]
        assert got == want
        # prefix reuse works identically on the mesh
        c0 = dict(registry.counters("serving"))
        p = prompts[1][:8] + [2, 3]
        got_hit = _run_one(sharded, p, 5, seed=14)
        assert registry.counters("serving")["prefix_hits"] \
            == c0["prefix_hits"] + 1
        cold = GenerationEngine(build(), max_batch_size=2,
                                buckets=(8, 16), rng_seed=13,
                                block_size=4)
        assert got_hit == _run_one(cold, p, 5, seed=14)


def _element_scatter(pool4, new, bt, off, sl):
    """The write as it was before PR 28, on a 4-D pool: the row index
    broadcast over heads and head_dim, one element at a time."""
    Nb, bs, H, Dh = pool4.shape
    B, T = new.shape[:2]
    M = bt.shape[1]
    rows = off[:, None] + np.arange(T, dtype=np.int32)[None]
    phys = np.take_along_axis(bt, np.minimum(rows // bs, M - 1), axis=1)
    flat_rows = np.where(rows < sl[:, None], phys * bs + rows % bs, 0)
    flat = np.array(pool4).reshape(Nb * bs, H, Dh)
    for n, r in enumerate(flat_rows.reshape(-1)):  # last write wins
        flat[r] = new.reshape(B * T, H, Dh)[n]
    return flat.reshape(pool4.shape)


class TestPoolDeviceForm:
    """PR 28: the pool is ``[num_blocks, block_size, H*Dh]`` on the
    device and the step's rows are written into it by rows."""

    # (B, T, offsets, seq_lens, zeroed table rows): bs = 4, M = 4
    CASES = {
        "decode": (3, 1, [5, 0, 7], [6, 1, 8], [1]),  # lane 1 is dead
        "verify_span_crosses_a_block_edge": (2, 5, [2, 6], [7, 11], []),
        "prefill_at_a_prefix": (1, 8, [4], [9], []),  # 3 rows are padding
        "span_past_the_table": (1, 4, [14], [16], []),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_row_write_bit_equal_to_the_element_scatter(self, case):
        import jax.numpy as jnp

        from paddle_tpu.nn import functional as F
        from paddle_tpu.ops import kv_pool

        B, T, off, sl, dead = self.CASES[case]
        Nb, bs, H, Dh, M = 14, 4, 2, 8, 4
        rng = np.random.default_rng(5)
        pools4 = [rng.standard_normal((Nb, bs, H, Dh)).astype(np.float32)
                  for _ in range(2)]
        news = [rng.standard_normal((B, T, H, Dh)).astype(np.float32)
                for _ in range(2)]
        bt = rng.permutation(np.arange(1, Nb))[:B * M].reshape(B, M) \
            .astype(np.int32)
        bt[dead] = 0
        off, sl = np.asarray(off, np.int32), np.asarray(sl, np.int32)
        want = [_element_scatter(p, n, bt, off, sl)
                for p, n in zip(pools4, news)]
        # the public op: a span of a block or more goes by whole blocks,
        # which leave the garbage block garbage of their own
        public = F.paged_kv_write(
            *(paddle.to_tensor(np.asarray(kv_pool.merged(jnp.asarray(p))))
              for p in pools4),
            *(paddle.to_tensor(n) for n in news),
            paddle.to_tensor(bt), paddle.to_tensor(off),
            paddle.to_tensor(sl))
        for g, w in zip(public, want):
            g = np.asarray(g.numpy())
            assert g.shape == (Nb, bs, H * Dh)  # no 4-D pool comes back
            np.testing.assert_array_equal(g.reshape(Nb, bs, H, Dh)[1:],
                                          w[1:])
        blk, row = kv_pool.span_rows(bt, off, sl, T, bs)
        got = [kv_pool.write_rows(kv_pool.merged(jnp.asarray(p)),
                                  n.reshape(B * T, -1), blk, row)
               for p, n in zip(pools4, news)]
        for g, w, before in zip(got, want, pools4):
            g = np.asarray(g).reshape(Nb, bs, H, Dh)
            np.testing.assert_array_equal(g, w)
            # rows outside [0, seq_len) and dead lanes: garbage row only
            live = sum(int(min(o + T, s) - o) for b, (o, s) in
                       enumerate(zip(off, sl)) if b not in dead)
            changed = np.argwhere((g != before).any(axis=(2, 3)))
            assert len(changed) <= live + 1
            assert all(tuple(c) == (0, 0) for c in changed
                       if c[0] == 0), changed
            np.testing.assert_array_equal(g[0, 1:], before[0, 1:])

    def test_token_streams_are_the_parent_commits(self):
        """Greedy and sampled streams of a toy engine, recorded on the
        parent of PR 28 (4-D pools, element scatter) for this model and
        seed: the pool's form changes no served token, on the gather
        path and through the kernel's interpreter."""
        want = [[74, 52, 52, 27, 85, 1, 1, 74, 82, 52],
                [17, 58, 48, 51, 17, 76, 74, 48, 58, 58],
                [74, 74, 51, 91, 82, 52, 85, 85, 85, 2]]
        for kern in ("xla", "pallas"):
            eng = GenerationEngine(_build_model(), max_batch_size=2,
                                   buckets=(8, 16), rng_seed=5,
                                   block_size=4, paged_kernel=kern)
            rng = np.random.default_rng(3)
            got = []
            for n, kw in ((5, dict(seed=1)),
                          (11, dict(seed=2, temperature=0.9, top_k=20)),
                          (13, dict(seed=3))):
                prompt = [int(x) for x in rng.integers(1, VOCAB, n)]
                got.append(_run_one(eng, prompt, 10, **kw))
            assert got == want, kern
            eng.pool.audit()

    def test_engine_pools_are_merged_and_the_gauge_says_row_major(self):
        from paddle_tpu.profiler import explainer

        eng = GenerationEngine(_build_model(), max_batch_size=2,
                               buckets=(8,), block_size=4)
        H, Dh = 2, 24
        assert all(a.shape == (eng.pool.num_blocks, 4, H * Dh)
                   for a in eng._k + eng._v)
        assert eng.stats()["kv_pool_row_major"] == 1
        assert registry.gauges()["serving.kv_pool_row_major"] == 1
        ev = explainer.events(kind="kv_pool_layout")[-1]
        assert ev["row_major"] and "(0, 1, 2)" in ev["why"]

    @pytest.mark.parametrize("kern,keys", [("xla", 0), ("pallas", 64)])
    def test_gauge_says_the_keys_a_program_of_the_kernel_folds(self, kern,
                                                              keys):
        # 2 heads x 24, block 4, 16 table columns: the plan would take 256
        # keys, the table has 64; the gather path has no program
        from paddle_tpu.profiler import explainer

        eng = GenerationEngine(_build_model(), max_batch_size=2,
                               buckets=(8,), block_size=4,
                               paged_kernel=kern)
        assert eng.blocks_per_slot == 16
        assert eng.stats()["paged_keys_per_program"] == keys
        assert registry.gauges()["serving.paged_keys_per_program"] == keys
        ev = explainer.events(kind="kv_pool_layout")[-1]
        assert ev["paged_keys_per_program"] == keys
        assert f"folds {keys} keys a program" in ev["why"]

    def test_gauge_reads_zero_on_another_layout(self, monkeypatch):
        from paddle_tpu.ops import kv_pool
        from paddle_tpu.profiler import explainer

        monkeypatch.setattr(kv_pool, "device_layout",
                            lambda pool: (1, 2, 0))
        eng = GenerationEngine(_build_model(), max_batch_size=1,
                               buckets=(8,), block_size=4)
        assert eng.stats()["kv_pool_row_major"] == 0
        assert registry.gauges()["serving.kv_pool_row_major"] == 0
        ev = explainer.events(kind="kv_pool_layout")[-1]
        assert not ev["row_major"] and "(1, 2, 0)" in ev["why"]

    def test_handoff_payload_keeps_its_4d_blocks(self):
        """Export -> import between two engines: the wire's block arrays
        stay [n, block_size, H, Dh] whatever the pool's device form, the
        adopted blocks are bit-equal, and decoding goes on bitwise."""
        prompt = [3, 5, 7, 9, 11, 2]
        mono = GenerationEngine(_build_model(), max_batch_size=2,
                                buckets=(8,), block_size=4, rng_seed=7)
        want = _run_one(mono, prompt, 6, seed=4, temperature=0.8)
        a = GenerationEngine(_build_model(), max_batch_size=2,
                             buckets=(8,), block_size=4, rng_seed=7)
        b = GenerationEngine(_build_model(), max_batch_size=2,
                             buckets=(8,), block_size=4, rng_seed=99)
        a.prefill(0, prompt, seed=4, temperature=0.8, max_new_tokens=6)
        payload = a.export_request_kv(0)
        n = payload["n_blocks"]
        assert len(payload["kv_k"]) == 2  # layers
        for blocks in payload["kv_k"] + payload["kv_v"]:
            assert isinstance(blocks, np.ndarray)
            assert blocks.shape == (n, 4, 2, 24)
        got = [b.import_request_kv(1, payload, prompt_ids=prompt)]
        again = b.export_request_kv(1)
        for field in ("kv_k", "kv_v"):
            for x, y in zip(payload[field], again[field]):
                np.testing.assert_array_equal(x, y)
        for _ in range(5):
            got.append(int(b.decode_step()[1]))
        assert got == want
        # a block of another geometry is refused by name
        payload["kv_k"][0] = payload["kv_k"][0].reshape(n, 4, 4, 12)
        b.release(1)
        with pytest.raises(ValueError, match="block shape"):
            b.import_request_kv(1, payload)
        b.pool.audit()


class TestPromptBlockWrite:
    """A span of at least a block a slot lands in a full layer's pools as
    whole blocks (``kv_pool.span_blocks`` + ``write_blocks``): bit-equal to
    the row write on every row outside the garbage block, whatever the
    offset and however few the rows."""

    # (B, T, offsets, seq_lens): bs = 4, M = 8 table columns (32 rows)
    CASES = {
        "block_aligned": (1, 16, [8], [21]),  # 3 rows of bucket padding
        "unaligned_offset": (1, 16, [5], [21]),
        # rows 4-5 of the first block are a shared prefix's tail
        "shared_prefix_last_block": (1, 16, [6], [19]),
        "bucket_padding": (1, 32, [0], [3]),  # 8 of 9 blocks dead
        "span_past_the_table": (1, 32, [16], [32]),
        "batch_of_three": (3, 16, [0, 9, 20], [16, 12, 32]),
        "nothing_live": (2, 16, [4, 8], [4, 8]),
        # spans shorter than a block, the row scatter's in write_span
        "decode_row": (3, 1, [0, 9, 31], [1, 10, 32]),
        "short_span": (2, 3, [8, 13], [11, 16]),
        "span_over_a_block": (2, 6, [3, 12], [9, 16]),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_block_write_bit_equal_to_the_row_write(self, case):
        import jax.numpy as jnp

        from paddle_tpu.ops import kv_pool

        B, T, off, sl = self.CASES[case]
        Nb, bs, H, Dh, M = 1 + 3 * 8 + 3, 4, 2, 8, 8
        rng = np.random.default_rng(7)
        pools = [jnp.asarray(rng.standard_normal((Nb, bs, H * Dh)),
                             jnp.float32) for _ in range(2)]
        new = [jnp.asarray(rng.standard_normal((B, T, H, Dh)), jnp.float32)
               for _ in range(2)]
        bt = rng.permutation(np.arange(1, Nb))[:B * M].reshape(B, M) \
            .astype(np.int32)
        off, sl = np.asarray(off, np.int32), np.asarray(sl, np.int32)
        if kv_pool.writes_blocks(T, bs):
            got = kv_pool.write_span(*pools, *new, bt, off, sl)
        else:
            where = kv_pool.span_blocks(bt, off, sl, T, bs)
            got = [kv_pool.write_blocks(p, r.reshape(B, T, -1), *where)
                   for p, r in zip(pools, new)]
        blk, row = kv_pool.span_rows(bt, off, sl, T, bs)
        live = {(int(bt[b, p // bs]), p % bs) for b in range(B)
                for p in range(off[b], min(off[b] + T, sl[b]))}
        for g, pool, rows in zip(got, pools, new):
            want = np.asarray(kv_pool.write_rows(
                pool, rows.reshape(B * T, -1), blk, row))
            g, before = np.asarray(g), np.asarray(pool)
            assert g.shape == before.shape
            # live rows bit-equal, every other row outside block 0 as it was
            np.testing.assert_array_equal(g[1:], want[1:])
            changed = {(int(b), int(r)) for b, r in
                       np.argwhere((g != before).any(axis=-1)) if b != 0}
            assert changed == live
        # the scatter's indices: the span's blocks, dead ones on block 0
        ids, shift, rows_live, edges = kv_pool.span_blocks(bt, off, sl, T,
                                                           bs)
        ids = np.asarray(ids)
        assert ids.shape == (B, -(-(T + bs - 1) // bs))
        held = ids[ids != 0]
        assert len(held) == len(set(held.tolist()))
        assert set(held.tolist()) == {b for b, _ in live}
        np.testing.assert_array_equal(np.asarray(shift), off % bs)

    @pytest.mark.parametrize("T,window,blocks", [
        (1, None, False), (4, None, False), (5, None, False),
        (15, None, False), (16, None, True), (2048, None, True),
        (2048, 4096, False)])
    def test_which_spans_land_as_blocks(self, T, window, blocks):
        # at block 16: decode's row, a block step's 4, a verify span's K + 1
        # under a block and every span of a window layer's ring keep the
        # row scatter
        from paddle_tpu.ops import kv_pool

        assert kv_pool.writes_blocks(T, 16, window) is blocks

    def test_the_engine_counts_what_the_layers_write(self):
        # the engine's counter asks the cache the same question: some full
        # layer of a heads cache (a latent row is the decoder's own write)
        from paddle_tpu.ops.kv_pool import CacheSpec

        full = CacheSpec("heads", [(2, 8)] * 2)
        mixed = CacheSpec("heads", [(2, 8)] * 2, windows=[64, None])
        rings = CacheSpec("heads", [(2, 8)] * 2, windows=[64, 64])
        latent = CacheSpec("latent", [(512, 64)])
        assert full.writes_blocks(16, 16) and mixed.writes_blocks(2048, 16)
        assert not full.writes_blocks(4, 16)
        assert not rings.writes_blocks(2048, 16)
        assert not latent.writes_blocks(2048, 16)

    def test_token_streams_are_the_parent_commits(self):
        """Greedy and sampled streams of a toy engine whose buckets (16,
        32) are prompt spans, one of them after a prefix hit (the span
        starts past the matched blocks), recorded on the tree before the
        block write: the same on the gather path and through the kernel's
        interpreter, and every prefill counted as a block write."""
        want = [[74, 74, 51, 51, 74, 51, 0, 27, 58, 88],
                [24, 85, 51, 85, 74, 5, 52, 51, 52, 52],
                [48, 52, 58, 1, 74, 60, 82, 82, 74, 74]]
        for kern in ("xla", "pallas"):
            eng = GenerationEngine(_build_model(), max_batch_size=2,
                                   buckets=(16, 32), rng_seed=7,
                                   block_size=4, paged_kernel=kern)
            rng = np.random.default_rng(4)
            a = [int(x) for x in rng.integers(1, VOCAB, 13)]
            b = [int(x) for x in rng.integers(1, VOCAB, 27)]
            c0 = dict(registry.counters("serving"))
            got = [_run_one(eng, a, 10, seed=1),
                   _run_one(eng, b, 10, seed=2, temperature=0.9, top_k=20),
                   _run_one(eng, b[:20] + [5, 6, 7], 10, seed=3)]
            c1 = registry.counters("serving")
            assert got == want, kern
            assert c1["prefix_hits"] - c0["prefix_hits"] == 1
            assert c1["prefill_block_writes"] \
                - c0["prefill_block_writes"] == 3
            # (a span's counters exist from its first call in the process)
            assert c1["prefill_n"] - c0.get("prefill_n", 0) == 3
            eng.pool.audit()


class TestPagedSchedulingEdges:
    def test_max_seq_len_budget_and_length_stop(self):
        # prompt + budget crosses max_seq_len: the budget caps at the
        # ceiling and the request stops with "length", exactly like the
        # contiguous cache did
        eng = GenerationEngine(_build_model(seed=63), max_batch_size=1,
                               buckets=(8, 24), rng_seed=3,
                               max_seq_len=24, block_size=4)
        from paddle_tpu.serving import ContinuousBatchScheduler, \
            GenerationRequest

        sched = ContinuousBatchScheduler(eng, max_queue_size=4)
        req = sched.submit(GenerationRequest(list(range(1, 21)),
                                             max_new_tokens=500))
        while sched.has_work():
            sched.step()
        assert req.status == RequestStatus.DONE
        assert req.stop_reason == "length"
        eng.pool.audit()
        assert eng.pool.in_use() == len(eng.prefix_cache)

    def test_blocks_needed_is_request_proportional(self):
        eng = GenerationEngine(_build_model(seed=64), max_batch_size=1,
                               buckets=(8, 16), rng_seed=3, block_size=4)
        assert eng.blocks_needed(5, 4) == 3       # ceil(9/4)
        assert eng.blocks_needed(5, 500) == 16    # capped at max_seq 64
        assert eng.blocks_needed(5, None) == 16   # unknown budget: worst


# ------------------------------ two kinds of cache state side by side (PR 35)
class TestWindowAndFullLayers:
    """`CacheSpec(windows=...)`: a window layer's state is a ring of the
    last `window` rows a slot, sized by the window; a full layer's pools
    stay as they are. The engine allocates both, budgets admission by the
    full layers, gives each layer its own table, and refuses — counted,
    with the reason — what cannot work over a ring."""

    @pytest.fixture(scope="class")
    def tiny(self):
        from paddle_tpu.models import Cohere2MoeConfig, Cohere2MoeModel

        paddle.seed(3)
        model = Cohere2MoeModel(Cohere2MoeConfig.preset("tiny"))
        model.eval()
        return model

    def test_spec_sizes_rings_by_the_window(self):
        import jax.numpy as jnp
        from paddle_tpu.ops import kv_pool

        # the cell's geometry: 4 full-length layers would be 4.70 GB
        spec = kv_pool.CacheSpec("heads", [(8, 128)] * 4,
                                 windows=[4096] * 3 + [None])
        assert spec.window == 4096 and spec.window_layers() == [0, 1, 2]
        assert spec.ring_blocks(16) == 257  # one block over the window
        nb = 1 + 32 * 560
        blocks = [spec.layer_blocks(i, nb, 16, 32) for i in range(4)]
        assert blocks == [1 + 32 * 257] * 3 + [nb]
        row = 16 * 1024 * 2 * 2  # a block of K and of V, bf16
        assert round(sum(blocks) * row / 1e9, 2) == 2.79
        assert round(4 * nb * row / 1e9, 2) == 4.70
        assert "3 x heads" in spec.describe() \
            and "the last 4096 keys in a ring" in spec.describe() \
            and "1 x heads" in spec.describe()
        # one kind of layer: said as before, and the table is the table
        plain = kv_pool.CacheSpec("heads", [(32, 64)] * 2)
        assert plain.describe() == \
            "heads: a K and a V row of 32 x 64 = 2048 a layer"
        bt = jnp.zeros((2, 5), jnp.int32)
        assert plain.layer_tables(bt, 16) is bt and plain.ring_blocks(16) == 0
        with pytest.raises(ValueError, match="one window"):
            kv_pool.CacheSpec("heads", [(2, 16)] * 2, windows=[8, 16])
        with pytest.raises(ValueError, match="one window"):
            kv_pool.CacheSpec("latent", [(32, 8)], windows=[8])

    def test_ring_addressing(self):
        """Position p lands in ring column (p // bs) % ring of the slot's
        own blocks; of a prompt longer than the window only the last
        `window` rows land, so no two rows of a call meet; padding and the
        head go to the garbage row."""
        import jax.numpy as jnp
        from paddle_tpu.ops import kv_pool

        bs, window = 4, 8
        ring = kv_pool.ring_blocks(window, bs)
        assert ring == 3
        table = kv_pool.ring_table(2, ring)
        assert table.tolist() == [[1, 2, 3], [4, 5, 6]]
        blk, row = kv_pool.ring_span_rows(
            jnp.asarray(table[1:]), jnp.asarray([0]), jnp.asarray([22]), 24,
            bs, window)
        blk, row = np.asarray(blk), np.asarray(row)
        live = np.arange(24)[(np.arange(24) >= 14) & (np.arange(24) < 22)]
        assert (blk[:14] == 0).all() and (blk[22:] == 0).all()
        assert blk[live].tolist() == [4 + (p // bs) % ring for p in live]
        assert row[live].tolist() == [p % bs for p in live]
        assert len({(b, r) for b, r in zip(blk[live], row[live])}) == 8
        # a decode step's one row, whatever the length
        blk, row = kv_pool.ring_span_rows(
            jnp.asarray(table), jnp.asarray([37, 5]), jnp.asarray([38, 6]),
            1, bs, window)
        assert np.asarray(blk).tolist() == [1 + (37 // bs) % ring,
                                            4 + (5 // bs) % ring]
        # where a slot of 22 rows keeps each position
        pos = np.asarray(kv_pool.ring_positions(jnp.asarray([22]), ring, bs))
        assert pos[0].tolist() == [12, 13, 14, 15, 16, 17, 18, 19,
                                   20, 21, 22, 23]

    def test_engine_allocates_both_and_tells_every_layer(self, tiny):
        from paddle_tpu.profiler import explainer

        explainer.clear()
        eng = GenerationEngine(tiny, max_batch_size=2, buckets=(32, 64),
                               max_seq_len=128, block_size=8, rng_seed=0)
        st = eng.stats()
        assert st["kv_cache_kind"] == "heads" and st["kv_window"] == 24
        assert st["kv_window_blocks"] == 4 and not st["prefix_sharing"]
        assert [(r["heads"], r["window"], r["blocks"])
                for r in st["kv_layers"]] == [(2, 24, 9)] * 3 + [(2, None, 33)]
        assert st["kv_pool_bytes"] == sum(
            int(k.nbytes) + int(v.nbytes) for k, v in zip(eng._k, eng._v))
        assert eng._block_tables.shape == (2, 16 + 4)
        assert registry.gauges().get("serving.kv_layers_window") == 3 \
            and registry.gauges().get("serving.kv_layers_full") == 1 \
            and registry.gauges().get("serving.kv_window_blocks") == 4
        said = [e for e in explainer.events()
                if e.get("kind") == "kv_pool_layout"]
        assert said and "3 x heads" in said[-1]["why"] \
            and "ring is 4 blocks a slot" in said[-1]["why"]
        # serve two requests past the window, then everything comes back
        before = registry.counters("serving")
        rng = np.random.default_rng(0)
        for slot, n in enumerate((50, 9)):
            eng.prefill(slot, rng.integers(1, 512, n).tolist(),
                        max_new_tokens=30)
        assert eng._block_tables[1, 16:].tolist() == [5, 6, 7, 8]
        for _ in range(20):
            eng.decode_step()
        after = registry.counters("serving")
        full = after["kv_tokens_read"] - before["kv_tokens_read"]
        win = after["kv_window_rows_read"] - before["kv_window_rows_read"]
        assert full == sum(50 + t + 9 + t for t in range(1, 21))
        assert win == sum(24 + min(9 + t, 24) for t in range(1, 21))
        assert after["prefix_hits"] == before["prefix_hits"] \
            and len(eng.prefix_cache) == 0
        eng.release(0), eng.release(1)
        audit = eng.pool.audit()
        assert audit["in_use"] == 0 and audit["free"] == audit["total"]
        assert (eng._block_tables == 0).all()

    def test_what_a_ring_cannot_hold_is_refused_and_counted(self, tiny):
        from paddle_tpu.profiler import explainer
        from paddle_tpu.serving.spec_decode import DraftVerifyEngine

        explainer.clear()
        c0 = registry.counters("serving")["cache_refusals"]
        eng = GenerationEngine(tiny, max_batch_size=2, buckets=(32,),
                               max_seq_len=64, block_size=8, rng_seed=0)
        # prefix sharing is a default, not a call: switched off, and said
        assert registry.counters("serving")["cache_refusals"] == c0 + 1
        eng.prefill(0, list(range(1, 20)), max_new_tokens=4)
        for feature, call in (
                ("handoff", lambda: eng.export_request_kv(0)),
                ("handoff", lambda: eng.import_request_kv(1, {})),
                ("chunked prefill", lambda: eng.begin_prefill(
                    1, list(range(1, 20)), chunk_tokens=8)),
                ("chunked prefill", lambda: GenerationServer(
                    engine=eng, prefill_chunk_tokens=8)),
                ("mesh", lambda: GenerationEngine(
                    tiny, max_batch_size=2, mesh=object())),
                ("spec_decode", lambda: DraftVerifyEngine(
                    tiny, tiny, max_batch_size=2, buckets=(32,),
                    max_seq_len=64, block_size=8))):
            with pytest.raises(TypeError, match=f"{feature}.*window "
                               r"layers.*layers \[0, 1, 2\].*ring"):
                call()
        # six raised (+ the two engines built inside said prefix sharing)
        assert registry.counters("serving")["cache_refusals"] >= c0 + 7
        why = [e["why"] for e in explainer.events()
               if e.get("kind") == "cache_feature_refused"]
        assert any("prefix sharing" in w for w in why) \
            and any("handoff" in w for w in why)
        eng.release(0)
