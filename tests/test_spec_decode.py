"""Speculative decoding + chunked prefill (ISSUE 12).

Covers the acceptance gates:
  * draft-verify output is token-BITWISE identical to plain decode —
    greedy AND sampled, engine-level and through the continuous-batching
    server, whatever the drafter proposes (incl. the fault-injected
    worst-case-wrong drafter, whose rounds must all reject);
  * the exact acceptance rule: a twin drafter (identical weights) is
    accepted in full (acceptance rate 1.0, K+1 tokens per round);
  * ONE verify executable per engine — mixed traffic after warmup adds
    zero ``serving.verify_compiles`` / ``serving.draft_compiles``;
  * rejected speculation never leaks blocks: ``BlockPool.audit()`` clean
    on BOTH pools at every lifecycle boundary;
  * prefill→decode handoff into a spec engine re-ingests the prompt on
    the drafter and continues bitwise;
  * chunked prefill: block-aligned chunks are token-bitwise with the
    one-shot prefill, in-flight decode streams emit tokens BETWEEN
    chunks, and a mid-prefill deadline/cancel releases every block.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import registry
from paddle_tpu.serving import (ContinuousBatchScheduler, DraftVerifyEngine,
                                GenerationEngine, GenerationRequest,
                                GenerationServer)
from paddle_tpu.testing import faults

VOCAB = 96


def _build(seed, n_layer=2, d_model=48):
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTModel)

    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=VOCAB, n_layer=n_layer, n_head=2,
                    d_model=d_model, seq_len=64, initializer_range=0.35)
    return GPTForPretraining(GPTModel(cfg))


def _run_plain(eng, prompt, n, seed=0, **kw):
    tok = eng.prefill(0, prompt, seed=seed, **kw)
    out = [tok]
    while len(out) < n:
        out.append(int(eng.decode_step()[0]))
    eng.release(0)
    return out[:n]


def _run_spec(eng, prompt, n, seed=0, slot=0, **kw):
    tok = eng.prefill(slot, prompt, seed=seed, **kw)
    out = [tok]
    while len(out) < n:
        out.extend(eng.decode_step_spec()[slot])
    eng.release(slot)
    return out[:n]


@pytest.fixture(scope="module")
def rig():
    """One plain engine and one spec engine over the SAME target
    weights (fresh builds, same seed), plus the drafter (different
    arch + seed — a genuinely wrong-by-default drafter)."""
    plain = GenerationEngine(_build(11), max_batch_size=2,
                             buckets=(8, 16), rng_seed=9, block_size=4)
    spec = DraftVerifyEngine(_build(11), _build(5, n_layer=1, d_model=32),
                             draft_k=3, max_batch_size=2,
                             buckets=(8, 16), rng_seed=9, block_size=4)
    return plain, spec


class TestSpecBitwise:
    def test_greedy_bitwise_vs_plain(self, rig):
        plain, spec = rig
        rng = np.random.default_rng(0)
        for ln in (5, 11):  # one per bucket
            prompt = list(rng.integers(1, VOCAB, ln))
            assert _run_spec(spec, prompt, 12) \
                == _run_plain(plain, prompt, 12)
        spec.pool.audit()
        spec.draft_pool.audit()

    def test_sampled_bitwise_vs_plain(self, rig):
        plain, spec = rig
        rng = np.random.default_rng(1)
        prompt = list(rng.integers(1, VOCAB, 6))
        kw = dict(temperature=0.9, top_k=30, seed=42)
        assert _run_spec(spec, prompt, 12, **kw) \
            == _run_plain(plain, prompt, 12, **kw)
        # rejected suffixes rolled back without leaking a block
        spec.pool.audit()
        spec.draft_pool.audit()

    def test_twin_drafter_accepts_everything(self):
        """Identical drafter weights = the exact-acceptance upper bound:
        every proposal matches the target's replayed Gumbel-max sample,
        every round emits K+1 tokens, even under sampling."""
        plain = GenerationEngine(_build(11), max_batch_size=2,
                                 buckets=(8,), rng_seed=9, block_size=4)
        spec = DraftVerifyEngine(_build(11), _build(11), draft_k=3,
                                 max_batch_size=2, buckets=(8,),
                                 rng_seed=9, block_size=4)
        rng = np.random.default_rng(2)
        prompt = list(rng.integers(1, VOCAB, 5))
        c0 = dict(registry.counters("serving"))
        kw = dict(temperature=0.8, seed=7)
        assert _run_spec(spec, prompt, 13, **kw) \
            == _run_plain(plain, prompt, 13, **kw)
        c1 = dict(registry.counters("serving"))
        proposed = c1["spec_proposed"] - c0["spec_proposed"]
        accepted = c1["spec_accepted"] - c0["spec_accepted"]
        assert proposed > 0 and accepted == proposed
        rounds = c1["spec_slot_rounds"] - c0["spec_slot_rounds"]
        emitted = c1["spec_emitted"] - c0["spec_emitted"]
        assert emitted == rounds * (spec.draft_k + 1)

    def test_draft_garbage_still_bitwise(self, rig):
        """Worst-case-wrong drafter: every proposal replaced with a
        constant. Throughput collapses to ~1 token/round but the output
        must not change by a single token, and nothing leaks."""
        plain, spec = rig
        rng = np.random.default_rng(3)
        prompt = list(rng.integers(1, VOCAB, 7))
        want = _run_plain(plain, prompt, 12, temperature=0.7, seed=5)
        c0 = dict(registry.counters("serving"))
        faults.configure("draft_garbage")
        try:
            got = _run_spec(spec, prompt, 12, temperature=0.7, seed=5)
        finally:
            faults.reset()
        assert got == want
        c1 = dict(registry.counters("serving"))
        proposed = c1["spec_proposed"] - c0["spec_proposed"]
        accepted = c1["spec_accepted"] - c0["spec_accepted"]
        # garbage token 0 can collide with a true sample occasionally;
        # anywhere near real acceptance means the fault didn't bite
        assert accepted <= proposed * 0.5
        assert registry.counters("fault")["injected.draft_garbage"] >= 1
        spec.pool.audit()
        spec.draft_pool.audit()

    def test_one_verify_executable_across_mixed_traffic(self, rig):
        """After the first round, greedy/sampled mixes, different slots
        and different acceptance patterns must all replay the same
        verify + draft executables (ISSUE 12 gate: one executable per
        (K, bucket))."""
        plain, spec = rig
        rng = np.random.default_rng(4)
        # warm BOTH prompt buckets here: the drafter's prefill is one
        # executable per bucket too (radar phase "draft"), and the window
        # below admits a 12-token prompt — relying on an earlier test of
        # this module to have touched bucket 16 made this test fail when
        # run alone
        for ln in (5, 11):
            _run_spec(spec, list(rng.integers(1, VOCAB, ln)), 8)
        c0 = dict(registry.counters("serving"))
        with paddle.profiler.CompileWatch() as window:
            # two co-resident slots, mixed configs, staggered lifecycles
            spec.prefill(0, list(rng.integers(1, VOCAB, 6)), seed=1)
            spec.prefill(1, list(rng.integers(1, VOCAB, 12)),
                         temperature=1.2, top_k=20, seed=2)
            for _ in range(6):
                spec.decode_step_spec()
            spec.pool.audit()
            spec.draft_pool.audit()
            spec.release(0)
            spec.release(1)
        c1 = dict(registry.counters("serving"))
        # JAX's own count, under the radar's: nothing compiled at all
        assert window.compiles == 0
        assert c1["verify_compiles"] == c0["verify_compiles"]
        assert c1["draft_compiles"] == c0["draft_compiles"]
        assert c1["decode_compiles"] == c0["decode_compiles"]
        spec.pool.audit()
        spec.draft_pool.audit()

    def test_handoff_into_spec_engine_bitwise(self, rig):
        """A plain (prefill-pod) engine exports a fresh slot; the spec
        engine adopts it, re-ingests the prompt on the drafter, and
        continues bitwise with plain decode."""
        plain, spec = rig
        rng = np.random.default_rng(5)
        prompt = list(rng.integers(1, VOCAB, 6))
        want = _run_plain(plain, prompt, 10, seed=3, temperature=0.6)

        first = plain.prefill(0, prompt, seed=3, temperature=0.6)
        payload = plain.export_request_kv(0)
        plain.release(0)
        with pytest.raises(ValueError, match="prompt_ids"):
            spec.import_request_kv(0, payload)
        got = [spec.import_request_kv(0, payload, prompt_ids=prompt)]
        assert got[0] == first
        while len(got) < 10:
            got.extend(spec.decode_step_spec()[0])
        spec.release(0)
        assert got[:10] == want
        spec.pool.audit()
        spec.draft_pool.audit()


class TestSpecServer:
    def test_interleaved_server_matches_plain_server(self):
        """The whole stack: a spec server under staggered continuous-
        batching traffic reproduces a plain server's outputs bitwise,
        zero failed, zero post-warmup verify compiles."""
        plain_srv = GenerationServer(
            engine=GenerationEngine(_build(21), max_batch_size=3,
                                    buckets=(8, 16), rng_seed=4,
                                    block_size=4)).start()
        spec_srv = GenerationServer(
            engine=DraftVerifyEngine(_build(21),
                                     _build(6, n_layer=1, d_model=32),
                                     draft_k=3, max_batch_size=3,
                                     buckets=(8, 16), rng_seed=4,
                                     block_size=4)).start()
        rng = np.random.default_rng(6)
        prompts = [list(rng.integers(1, VOCAB, n))
                   for n in (5, 11, 7, 13, 6)]
        budgets = [6, 9, 4, 7, 11]
        opts = [dict(temperature=0.9 if i % 2 else 0.0, seed=200 + i)
                for i in range(len(prompts))]
        want = [plain_srv.generate(p, max_new_tokens=b, **o)
                for p, b, o in zip(prompts, budgets, opts)]
        # warmup pass on the spec server (compiles both buckets + round)
        solo = [spec_srv.generate(p, max_new_tokens=b, **o)
                for p, b, o in zip(prompts, budgets, opts)]
        assert solo == want
        c0 = dict(registry.counters("serving"))
        reqs = []
        for p, b, o in zip(prompts, budgets, opts):
            reqs.append(spec_srv.submit(p, max_new_tokens=b, **o))
            time.sleep(0.003)  # staggered: admissions land mid-flight
        inter = [list(r.result(120).tokens) for r in reqs]
        assert inter == want
        c1 = dict(registry.counters("serving"))
        assert c1["verify_compiles"] == c0["verify_compiles"]
        assert c1["prefill_compiles"] == c0["prefill_compiles"]
        assert all(r.status == "done" for r in reqs)
        spec_srv.engine.pool.audit()
        spec_srv.engine.draft_pool.audit()
        plain_srv.shutdown(timeout=30)
        spec_srv.shutdown(timeout=30)


class TestChunkedPrefill:
    @pytest.fixture(scope="class")
    def engine(self):
        return GenerationEngine(_build(31), max_batch_size=2,
                                buckets=(8, 16, 32), rng_seed=2,
                                block_size=4)

    def test_chunked_equals_one_shot(self, engine):
        rng = np.random.default_rng(7)
        prompt = list(rng.integers(1, VOCAB, 27))
        # chunked admission runs FIRST (cold prefix cache — afterwards
        # the published prompt blocks would legitimately shrink the
        # chunk count; chunking composes with prefix reuse)
        c0 = dict(registry.counters("serving"))
        chunks = engine.begin_prefill(0, prompt, seed=1, temperature=0.8,
                                      chunk_tokens=8)
        assert chunks == 4  # ceil(27/8) block-aligned chunks
        assert engine.free_slots() == [1]  # slot 0 reserved, not free
        first = None
        while first is None:
            first = engine.prefill_chunk(0)
        got = [first]
        while len(got) < 8:
            got.append(int(engine.decode_step()[0]))
        engine.release(0)
        want = _run_plain(engine, prompt, 8, seed=1, temperature=0.8)
        assert got == want
        c1 = dict(registry.counters("serving"))
        assert c1["chunked_prefills"] - c0["chunked_prefills"] == 1
        assert c1["prefill_chunks"] - c0["prefill_chunks"] == 4
        engine.pool.audit()

    def test_decode_interleaves_between_chunks(self, engine):
        """The latency point of chunked prefill: a scheduler step
        advances ONE chunk then runs a decode iteration, so an in-flight
        stream keeps emitting while a long prompt prefills."""
        sched = ContinuousBatchScheduler(engine,
                                         prefill_chunk_tokens=8)
        rng = np.random.default_rng(8)
        stream = GenerationRequest(list(rng.integers(1, VOCAB, 5)),
                                   max_new_tokens=20, seed=1)
        sched.submit(stream)
        sched.step()  # admits + first decode
        tokens_before = len(stream.tokens)
        long_req = GenerationRequest(list(rng.integers(1, VOCAB, 27)),
                                     max_new_tokens=4, seed=2)
        sched.submit(long_req)
        sched.step()  # begin_prefill + chunk 1 + decode
        assert long_req.status == "running" and not long_req.tokens
        assert sched.prefilling() == 1
        assert len(stream.tokens) > tokens_before  # stream not stalled
        mid_stream = len(stream.tokens)
        while sched.prefilling():
            sched.step()
        assert len(stream.tokens) > mid_stream
        assert len(long_req.tokens) >= 1  # first token landed
        while not (stream.done and long_req.done):
            sched.step()
        assert stream.status == "done" and long_req.status == "done"
        engine.pool.audit()

    def test_mid_prefill_deadline_releases_blocks(self, engine):
        sched = ContinuousBatchScheduler(engine, prefill_chunk_tokens=8)
        rng = np.random.default_rng(9)
        in_use0 = engine.pool.in_use()
        req = GenerationRequest(list(rng.integers(1, VOCAB, 27)),
                                max_new_tokens=4, seed=3,
                                timeout_s=0.001)
        sched.submit(req)
        sched.step()   # chunk-admitted
        time.sleep(0.01)
        sched.step()   # deadline scan fires mid-prefill
        assert req.done and req.status == "timeout"
        engine.pool.audit()
        # every staged block came back: the admission never completed,
        # so no prefix blocks were published to the radix tree either
        assert engine.pool.in_use() == in_use0

    def test_chunked_spec_reserves_draft_blocks_up_front(self):
        """Review finding (ISSUE 12): a chunked admission on a spec
        engine must hold the DRAFTER's block budget from begin_prefill
        on — drafter-pool pressure is admission backpressure (request
        stays queued), never a mid-flight failure at the final chunk."""
        eng = DraftVerifyEngine(_build(51), _build(9, n_layer=1,
                                                   d_model=32),
                                draft_k=2, max_batch_size=2,
                                buckets=(8, 32), rng_seed=3,
                                block_size=4, draft_num_blocks=9)
        sched = ContinuousBatchScheduler(eng, prefill_chunk_tokens=8)
        rng = np.random.default_rng(11)
        # 7 of the 8 usable draft blocks go to the first request
        r1 = GenerationRequest(list(rng.integers(1, VOCAB, 5)),
                               max_new_tokens=20, seed=1)
        sched.submit(r1)
        sched.step()
        assert r1.status == "running"
        assert eng.draft_pool.in_use() == 7
        # the long prompt needs 8 draft blocks: backpressure, not error
        r2 = GenerationRequest(list(rng.integers(1, VOCAB, 25)),
                               max_new_tokens=4, seed=2)
        sched.submit(r2)
        sched.step()
        assert r2.status == "queued"
        assert registry.counters("serving")["pool_exhausted"] >= 1
        while not r1.done:
            sched.step()
        sched.step()  # chunk-admits r2: draft budget reserved AT BEGIN
        assert r2.status == "running"
        assert sched.prefilling() == 1
        assert eng.draft_pool.in_use() == 8
        while not r2.done:
            sched.step()
        assert r2.status == "done" and len(r2.tokens) == 4
        eng.pool.audit()
        eng.draft_pool.audit()
        assert eng.draft_pool.in_use() == 0

    def test_server_chunked_spec_bitwise(self):
        """Chunked prefill + speculative decode composed through the
        server: long and short prompts, outputs bitwise with a plain
        unchunked server."""
        plain_srv = GenerationServer(
            engine=GenerationEngine(_build(41), max_batch_size=2,
                                    buckets=(8, 32), rng_seed=6,
                                    block_size=4)).start()
        spec_srv = GenerationServer(
            engine=DraftVerifyEngine(_build(41),
                                     _build(8, n_layer=1, d_model=32),
                                     draft_k=2, max_batch_size=2,
                                     buckets=(8, 32), rng_seed=6,
                                     block_size=4),
            prefill_chunk_tokens=8).start()
        rng = np.random.default_rng(10)
        prompts = [list(rng.integers(1, VOCAB, n)) for n in (26, 5, 21)]
        kw = [dict(max_new_tokens=6, seed=300 + i,
                   temperature=0.5 if i == 1 else 0.0)
              for i in range(3)]
        want = [plain_srv.generate(p, **o) for p, o in zip(prompts, kw)]
        reqs = [spec_srv.submit(p, **o) for p, o in zip(prompts, kw)]
        got = [list(r.result(120).tokens) for r in reqs]
        assert got == want
        assert all(r.status == "done" for r in reqs)
        c = registry.counters("serving")
        assert c["prefill_chunks"] >= 3  # the 26/21-token prompts chunked
        spec_srv.engine.pool.audit()
        spec_srv.engine.draft_pool.audit()
        plain_srv.shutdown(timeout=30)
        spec_srv.shutdown(timeout=30)


class TestPodPrefillPipelining:
    def test_prefill_requests_overlap_per_connection(self):
        """ISSUE 12 satellite (PR 10 residual): the pod's prefill op
        must not hold the connection's handler loop for its whole
        engine turn — two submitted prefills overlap (second handler
        returns before the first reply arrives), replies mid-matched."""
        from paddle_tpu.serving.pod_worker import PodWorker

        spec = {"model": {"kind": "gpt", "seed": 3,
                          "config": dict(vocab_size=VOCAB, n_layer=1,
                                         n_head=2, d_model=32,
                                         seq_len=64,
                                         initializer_range=0.3)},
                "role": "prefill",
                "engine": {"max_batch_size": 2, "buckets": [8],
                           "block_size": 4, "rng_seed": 0}}
        worker = PodWorker(spec)
        replies, got_two = [], threading.Event()

        def send(obj):
            replies.append(obj)
            if len(replies) >= 2:
                got_two.set()

        t0 = time.monotonic()
        worker._op_prefill({"op": "prefill", "mid": 1,
                            "prompt": [1, 2, 3], "options": {"seed": 0}},
                           send)
        worker._op_prefill({"op": "prefill", "mid": 2,
                            "prompt": [4, 5, 6], "options": {"seed": 1}},
                           send)
        dispatch_s = time.monotonic() - t0
        assert got_two.wait(120), f"replies: {replies}"
        # both handler calls returned without waiting for the engine
        # (the actual prefills take much longer than the dispatch did)
        assert dispatch_s < 0.5
        assert sorted(r["mid"] for r in replies) == [1, 2]
        assert all(r["op"] == "prefill_done" for r in replies)
