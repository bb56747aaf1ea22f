"""paddle_tpu.serving — continuous-batching generation engine (ISSUE 5).

Covers the acceptance gates:
  * greedy decode through the engine == a straight-line full-forward
    argmax loop (token-id exact);
  * interleaved continuous batching == each request run solo (token-id
    exact), across >= 2 prompt buckets with different token budgets and
    staggered arrivals;
  * ZERO decode-step recompiles after warmup, asserted via the profiler
    explainer ring + serving counters;
  * queue-full fast-fail backpressure and deadline timeouts;
  * the legacy growing-concat KV-cache path still works and warns once.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import explainer, registry
from paddle_tpu.serving import (ContinuousBatchScheduler, GenerationRequest,
                                GenerationServer, QueueFullError,
                                RequestStatus, sampling)

VOCAB = 96


def _build_model(seed=11):
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                      GPTModel)

    paddle.seed(seed)
    # initializer_range is cranked up so greedy continuations are varied
    # (a near-uniform tiny model collapses to one repeated token, which
    # would make the equality tests vacuous)
    cfg = GPTConfig(vocab_size=VOCAB, n_layer=2, n_head=2, d_model=48,
                    seq_len=64, initializer_range=0.35)
    return GPTForPretraining(GPTModel(cfg))


@pytest.fixture(scope="module")
def server():
    srv = GenerationServer(_build_model(), max_batch_size=3,
                           buckets=(8, 16), max_queue_size=16)
    srv.start()
    yield srv
    srv.shutdown(timeout=30)


def _prompts(rng, sizes):
    return [list(rng.integers(1, VOCAB, n)) for n in sizes]


class TestEngineCorrectness:
    def test_greedy_matches_straightline_forward(self, server):
        m = server.engine._model
        rng = np.random.default_rng(0)
        for prompt in _prompts(rng, (5, 12)):  # one per bucket
            got = server.generate(prompt, max_new_tokens=6)
            ids = list(prompt)
            want = []
            with paddle.no_grad():
                for _ in range(6):
                    logits = m(paddle.to_tensor(
                        np.asarray([ids], np.int64)))
                    t = int(np.asarray(logits.numpy())[0, -1].argmax())
                    want.append(t)
                    ids.append(t)
            assert got == want

    def test_interleaved_equals_solo_and_zero_decode_recompiles(
            self, server):
        rng = np.random.default_rng(3)
        # spans both buckets, different budgets, greedy AND sampled
        prompts = _prompts(rng, (5, 11, 7, 14, 6, 9))
        budgets = [6, 9, 4, 7, 11, 5]
        opts = [dict(temperature=0.9 if i % 2 else 0.0, seed=100 + i)
                for i in range(len(prompts))]

        solo = [server.generate(p, max_new_tokens=b, **o)
                for p, b, o in zip(prompts, budgets, opts)]

        # the solo pass doubled as warmup: every signature is compiled now
        c0 = registry.counters("serving")
        e0 = len(explainer.events(kind="serving_decode_compile"))
        reqs = []
        for p, b, o in zip(prompts, budgets, opts):
            reqs.append(server.submit(p, max_new_tokens=b, **o))
            time.sleep(0.003)  # staggered arrivals: admissions mid-flight
        inter = [list(r.result(120).tokens) for r in reqs]

        assert inter == solo
        c1 = registry.counters("serving")
        assert c1["decode_compiles"] == c0["decode_compiles"]
        assert c1["prefill_compiles"] == c0["prefill_compiles"]
        assert len(explainer.events(kind="serving_decode_compile")) == e0
        # continuous batching actually batched: slots were co-resident
        assert c1["active_slot_steps"] > c1["decode_steps"]

    def test_seed_determinism(self, server):
        rng = np.random.default_rng(5)
        prompt = list(rng.integers(1, VOCAB, 6))
        kw = dict(max_new_tokens=10, temperature=5.0, top_k=50, seed=42)
        a = server.generate(prompt, **kw)
        b = server.generate(prompt, **kw)
        assert a == b
        c = server.generate(prompt, **{**kw, "seed": 43})
        assert c != a  # 10 tokens at temperature 5: collision ~ V**-10

    def test_eos_stop(self, server):
        rng = np.random.default_rng(7)
        prompt = list(rng.integers(1, VOCAB, 5))
        free = server.generate(prompt, max_new_tokens=6)
        req = server.submit(prompt, max_new_tokens=6,
                            eos_id=free[1]).result(60)
        assert req.status == RequestStatus.DONE
        assert req.stop_reason == "eos"
        assert list(req.tokens) == free[:2]

    def test_prompt_overflow_fails_request(self, server):
        # longest bucket is 16: a 30-token prompt must fail cleanly, not
        # wedge the loop
        req = server.submit(list(range(1, 31)), max_new_tokens=4)
        req.finished.wait(60)
        assert req.status == RequestStatus.ERROR
        assert "bucket" in req.error

    def test_serving_telemetry_populated(self, server):
        counters = registry.counters("serving")
        assert counters["tokens_generated"] > 0
        assert counters["requests_completed"] > 0
        timings = registry.timings("serving")
        assert timings["serving.ttft"]["count"] > 0
        assert timings["serving.decode_step"]["count"] > 0
        assert registry.gauge("serving.batch_occupancy") is not None
        assert 0.0 < server.engine.mean_occupancy() <= 1.0

    def test_create_generation_engine_entry(self, server):
        from paddle_tpu.inference import create_generation_engine

        eng = create_generation_engine(server.engine._model,
                                       max_batch_size=2, buckets=(8,))
        assert eng.buckets == (8,)
        assert eng.free_slots() == [0, 1]


class _FakeEngine:
    """Engine stand-in for scheduler-logic tests: no compiles, emits
    deterministic tokens, honors the slot protocol."""

    def __init__(self, max_batch_size=2, max_seq_len=32):
        self.max_batch_size = max_batch_size
        self.max_seq_len = max_seq_len
        self._active = [False] * max_batch_size
        self._lens = [0] * max_batch_size
        self.prefills = 0

    def free_slots(self):
        return [i for i, a in enumerate(self._active) if not a]

    def prefill(self, slot, prompt_ids, **kw):
        if len(prompt_ids) > self.max_seq_len:
            raise ValueError("prompt exceeds largest bucket")
        self._active[slot] = True
        self._lens[slot] = len(prompt_ids)
        self.prefills += 1
        return 1

    def decode_step(self):
        for i, a in enumerate(self._active):
            if a:
                self._lens[i] += 1
        return np.arange(2, 2 + self.max_batch_size, dtype=np.int32)

    def release(self, slot):
        self._active[slot] = False
        self._lens[slot] = 0

    def slot_len(self, slot):
        return self._lens[slot]


class TestSchedulerPolicies:
    def test_queue_full_fast_fail(self):
        sched = ContinuousBatchScheduler(_FakeEngine(), max_queue_size=2)
        r0 = registry.counters("serving")["requests_rejected"]
        sched.submit(GenerationRequest([1, 2]))
        sched.submit(GenerationRequest([1, 2]))
        t0 = time.monotonic()
        with pytest.raises(QueueFullError):
            sched.submit(GenerationRequest([1, 2]))
        assert time.monotonic() - t0 < 0.5  # fast-fail, no blocking
        assert registry.counters("serving")["requests_rejected"] == r0 + 1

    def test_deadline_expires_in_queue(self):
        sched = ContinuousBatchScheduler(_FakeEngine(max_batch_size=1),
                                         max_queue_size=8)
        blocker = sched.submit(GenerationRequest([1], max_new_tokens=50))
        doomed = sched.submit(GenerationRequest([1], timeout_s=0.0))
        sched.step()  # blocker takes the only slot; doomed expires queued
        assert doomed.done
        assert doomed.status == RequestStatus.TIMEOUT
        assert doomed.tokens == []
        assert blocker.status == RequestStatus.RUNNING

    def test_deadline_expires_mid_flight(self):
        sched = ContinuousBatchScheduler(_FakeEngine(), max_queue_size=8)
        req = sched.submit(GenerationRequest([1, 2], max_new_tokens=500,
                                             timeout_s=10.0))
        sched.step()
        assert req.status == RequestStatus.RUNNING
        req.deadline = time.monotonic() - 1.0  # deadline passes mid-run
        sched.step()
        assert req.status == RequestStatus.TIMEOUT
        assert req.stop_reason == "deadline"
        assert len(req.tokens) >= 1  # partial output survives

    def test_capacity_stop_and_slot_reuse(self):
        eng = _FakeEngine(max_batch_size=1, max_seq_len=6)
        sched = ContinuousBatchScheduler(eng, max_queue_size=8)
        a = sched.submit(GenerationRequest([1, 2, 3], max_new_tokens=500))
        b = sched.submit(GenerationRequest([1], max_new_tokens=2))
        while sched.has_work():
            sched.step()
        assert a.status == RequestStatus.DONE
        assert a.stop_reason == "length"  # hit the cache, not the budget
        assert b.status == RequestStatus.DONE  # refilled the freed slot
        assert eng.prefills == 2

    def test_drain_and_closed_submit(self):
        sched = ContinuousBatchScheduler(_FakeEngine(), max_queue_size=8)
        req = sched.submit(GenerationRequest([1], max_new_tokens=3))
        assert sched.drain(timeout=30)
        assert req.status == RequestStatus.DONE
        with pytest.raises(RuntimeError, match="not accepting"):
            sched.submit(GenerationRequest([1]))


class TestServerFrontend:
    def test_graceful_drain_on_shutdown(self):
        srv = GenerationServer(engine=_FakeEngine(), max_queue_size=8)
        srv.start()
        reqs = [srv.submit([1, 2], max_new_tokens=4) for _ in range(5)]
        assert srv.shutdown(drain=True, timeout=30)
        assert all(r.status == RequestStatus.DONE for r in reqs)
        with pytest.raises(RuntimeError, match="shutting down"):
            srv.submit([1])

    def test_hard_shutdown_fails_pending(self):
        srv = GenerationServer(engine=_FakeEngine(), max_queue_size=8)
        # never started: queued work can't run, hard shutdown must fail it
        req = srv.scheduler.submit(GenerationRequest([1, 2]))
        srv.shutdown(drain=False, timeout=5)
        assert req.status == RequestStatus.ERROR

    def test_sigterm_style_drain_flag(self):
        srv = GenerationServer(engine=_FakeEngine(), max_queue_size=8)
        srv.start()
        req = srv.submit([1, 2], max_new_tokens=3)
        srv.request_drain()  # what the SIGTERM handler does: flags only
        assert req.result(30).status == RequestStatus.DONE
        srv._thread.join(30)
        assert not srv._thread.is_alive()

    def test_result_wait_timeout_is_not_request_deadline(self):
        srv = GenerationServer(engine=_FakeEngine(), max_queue_size=8)
        # not started: the request can never finish, so result() times out
        req = srv.scheduler.submit(GenerationRequest([1]))
        with pytest.raises(TimeoutError):
            req.result(0.05)
        assert req.status == RequestStatus.QUEUED  # still alive


# --- the sort-based forms the selection replaced (the parent of PR 31, line
# for line), kept here as the oracle of what a filter keeps ----------------
def _sorted_top_k(logits, top_k):
    import jax.numpy as jnp

    V = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        sorted_desc, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=-1)
    keep = (top_k[:, None] <= 0) | (logits >= kth)
    return jnp.where(keep, logits, -jnp.inf)


def _sorted_top_p(logits, top_p):
    import jax
    import jax.numpy as jnp

    p = jnp.clip(top_p, 1e-6, 1.0)[:, None]
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    before = jnp.cumsum(probs, axis=-1) - probs
    kept = jnp.where(before < p, sorted_desc, jnp.inf)
    threshold = jnp.min(kept, axis=-1, keepdims=True)
    keep = (top_p[:, None] >= 1.0) | (logits >= threshold)
    return jnp.where(keep, logits, -jnp.inf)


def _sorted_sample_tokens(logits, temperature, top_k, top_p, gumbel):
    import jax.numpy as jnp

    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_t[:, None]
    filtered = _sorted_top_p(_sorted_top_k(scaled, top_k), top_p)
    sampled = jnp.argmax(filtered + gumbel, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


_HEAD = np.asarray([0.3, 0.2, 0.15, 0.1, 0.08, 0.06, 0.05, 0.03, 0.02, 0.01])


def _tied_rows(rng, B, V):
    """bf16-rounded logits (so the k-th value is tied with its neighbours at
    the cells' vocabularies), with the rows a filter can trip over: one
    holding -inf, one holding both zeros, one of nothing but zeros."""
    import jax.numpy as jnp

    x = np.array(jnp.asarray(rng.normal(size=(B, V)) * 3, jnp.float32)
                 .astype(jnp.bfloat16).astype(jnp.float32))
    x[0, :5] = -np.inf
    x[1, 3], x[1, 4] = -0.0, 0.0
    x[2, 0::2], x[2, 1::2] = -0.0, 0.0
    return x


def _lumpy_rows(rng, B, V):
    """Rows whose mass sits in ten ids (`_HEAD`, placed at random) over a
    tail of < 1e-6 in all: the cumulative mass moves in steps of >= 0.01, so
    a `top_p` between two steps is far from any id's boundary."""
    x = (rng.normal(size=(B, V)) - 30.0).astype(np.float32)
    for b in range(B):
        ids = rng.choice(V, len(_HEAD), replace=False)
        x[b, ids] = np.log(_HEAD).astype(np.float32)
    return x


def _boundary_margin(filtered, top_p):
    """How far, in mass, each nucleus row's `top_p` lies from the nearest
    id's preceding mass (float64 over the filtered, scaled logits)."""
    out = []
    for row, p in zip(np.asarray(filtered, np.float64), top_p):
        if p >= 1.0:
            continue
        row = np.sort(row[row > -np.inf])[::-1]
        probs = np.exp(row - row[0])
        before = np.cumsum(probs / probs.sum()) - probs / probs.sum()
        out.append(np.abs(before - p).min())
    return min(out) if out else 1.0


def _kept_cases():
    cases = []
    for V in (32, 50304, 131072):
        for k in (1, 2, 40, "V", "V+8", 0, -1):
            cases.append(pytest.param("top_k", V, k, id=f"top_k-V{V}-k{k}"))
        cases.append(pytest.param("top_p", V, None, id=f"top_p-V{V}"))
        cases.append(pytest.param("mixed", V, None, id=f"mixed-V{V}"))
    for p in (0.75, 0.5, 0.8):
        cases.append(pytest.param("handmade", 5, p, id=f"handmade-p{p}"))
    cases.append(pytest.param("all_greedy", 50304, None, id="all-greedy"))
    cases.append(pytest.param("all_disabled", 50304, None,
                              id="all-disabled"))
    return cases


class TestSampling:
    def _logits(self):
        rng = np.random.default_rng(0)
        return rng.normal(size=(4, 32)).astype(np.float32)

    def test_top_k_one_is_greedy(self):
        import jax.numpy as jnp

        logits = self._logits()
        gum = np.asarray(np.random.default_rng(1).gumbel(
            size=logits.shape), np.float32)
        toks = sampling.sample_tokens(
            jnp.asarray(logits), jnp.full((4,), 1.0, np.float32),
            jnp.full((4,), 1, np.int32), jnp.ones((4,), np.float32),
            jnp.asarray(gum))
        np.testing.assert_array_equal(np.asarray(toks),
                                      logits.argmax(-1))

    def test_tiny_top_p_is_greedy(self):
        import jax.numpy as jnp

        logits = self._logits()
        gum = np.asarray(np.random.default_rng(2).gumbel(
            size=logits.shape), np.float32)
        toks = sampling.sample_tokens(
            jnp.asarray(logits), jnp.full((4,), 1.0, np.float32),
            jnp.zeros((4,), np.int32), jnp.full((4,), 1e-6, np.float32),
            jnp.asarray(gum))
        np.testing.assert_array_equal(np.asarray(toks),
                                      logits.argmax(-1))

    def test_top_k_filter_masks_tail(self):
        import jax.numpy as jnp

        logits = jnp.asarray(self._logits())
        out = np.asarray(sampling.filter_top_k(
            logits, jnp.full((4,), 5, np.int32)))
        assert ((out > -np.inf).sum(-1) == 5).all()

    def test_top_p_keeps_nucleus_only(self):
        import jax.numpy as jnp

        row = np.log(np.asarray(
            [[0.5, 0.3, 0.1, 0.06, 0.04]], np.float32))
        out = np.asarray(sampling.filter_top_p(
            jnp.asarray(row), jnp.asarray([0.75], np.float32)))
        # 0.5 + 0.3 covers 0.75 ⇒ exactly {0.5, 0.3} survive
        assert (out[0, :2] > -np.inf).all() and (out[0, 2:] == -np.inf).all()

    def test_mixed_batch_greedy_rows_ignore_noise(self):
        import jax.numpy as jnp

        logits = self._logits()
        gum = np.asarray(np.random.default_rng(3).gumbel(
            size=logits.shape), np.float32)
        temps = np.asarray([0.0, 1.0, 0.0, 2.0], np.float32)
        toks = np.asarray(sampling.sample_tokens(
            jnp.asarray(logits), jnp.asarray(temps),
            jnp.zeros((4,), np.int32), jnp.ones((4,), np.float32),
            jnp.asarray(gum)))
        np.testing.assert_array_equal(toks[[0, 2]],
                                      logits.argmax(-1)[[0, 2]])


    @pytest.mark.parametrize("kind,V,arg", _kept_cases())
    def test_kept_set_equals_the_sorted_form(self, kind, V, arg):
        """PR 31: the thresholds come from selection (32 compare-and-reduce
        passes over the row's order-preserving integer image), never from
        ordering the vocabulary. What is kept is the sorted form's, bit for
        bit for top-k; for top-p wherever `top_p` is not within float32
        summation error of an id's preceding mass."""
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(31)
        B = 8
        if kind == "top_k":
            k = {"V": V, "V+8": V + 8}.get(arg, arg)
            x = jnp.asarray(_tied_rows(rng, B, V))
            ks = jnp.full((B,), k, jnp.int32)
            got = np.asarray(jax.jit(sampling.filter_top_k)(x, ks))
            want = np.asarray(jax.jit(_sorted_top_k)(x, ks))
            np.testing.assert_array_equal(got, want)
            if k > 0:  # ties at the k-th value keep every tied id
                assert ((got == np.asarray(x)).sum(-1) >= min(k, V)).all()
        elif kind == "top_p":
            x = _lumpy_rows(rng, B, V)
            ps = np.asarray([0.25, 0.4, 0.58, 0.7, 0.79, 0.86, 0.925, 0.965],
                            np.float32)
            assert _boundary_margin(x, ps) >= 1e-4
            got = np.asarray(jax.jit(sampling.filter_top_p)(
                jnp.asarray(x), jnp.asarray(ps)))
            want = np.asarray(jax.jit(_sorted_top_p)(
                jnp.asarray(x), jnp.asarray(ps)))
            np.testing.assert_array_equal(got, want)
            np.testing.assert_array_equal(
                (got > -np.inf).sum(-1), [1, 2, 3, 4, 5, 6, 7, 8])
        elif kind == "handmade":
            row = jnp.asarray(np.log(np.asarray(
                [[0.5, 0.3, 0.1, 0.06, 0.04]], np.float32)))
            p = jnp.asarray([arg], np.float32)
            got = np.asarray(sampling.filter_top_p(row, p))[0] > -np.inf
            want = np.asarray(_sorted_top_p(row, p))[0] > -np.inf
            if arg == 0.8:
                # 0.5 + 0.3 IS top_p: whether id 2's preceding mass reads
                # under 0.8 is the summation order's to say (the one place
                # the two forms may part); the ids around it are not
                assert got[:2].all() and not got[3:].any()
                assert want[:2].all() and not want[3:].any()
            else:
                np.testing.assert_array_equal(got, want)
                assert got.sum() == {0.75: 2, 0.5: 1}[arg]
        else:
            # whole batches through sample_tokens: the tokens are the
            # sorted form's (same gumbel, same kept set)
            x = _tied_rows(rng, B, V)
            x[5:] = _lumpy_rows(rng, 3, V)
            temps = np.asarray([0, .8, 1., 0, .5, 1., 1., 1.], np.float32)
            ks = np.asarray([0, 40, 0, 5, -1, V + 8, 6, 0], np.int32)
            ps = np.asarray([1, 1, 1, .5, 1, .7, .58, .925], np.float32)
            if kind == "all_greedy":  # neither branch of either cond runs
                temps = np.zeros(B, np.float32)
            elif kind == "all_disabled":
                ks, ps = np.zeros(B, np.int32), np.ones(B, np.float32)
                temps = np.full(B, 0.7, np.float32)
            scaled = x / np.where(temps > 0, temps, 1.0)[:, None]
            on = temps > 0
            filtered = np.asarray(_sorted_top_k(
                jnp.asarray(scaled), jnp.asarray(ks)))
            assert _boundary_margin(filtered[on], ps[on]) >= 1e-4
            gum = jnp.asarray(rng.gumbel(size=(B, V)), jnp.float32)
            args = (jnp.asarray(x), jnp.asarray(temps), jnp.asarray(ks),
                    jnp.asarray(ps), gum)
            got = np.asarray(jax.jit(sampling.sample_tokens)(*args))
            want = np.asarray(jax.jit(_sorted_sample_tokens)(*args))
            np.testing.assert_array_equal(got, want)
            np.testing.assert_array_equal(got[~on], x.argmax(-1)[~on])
            if kind == "all_disabled":
                np.testing.assert_array_equal(
                    got, np.asarray(jnp.asarray(scaled) + gum).argmax(-1))


    def test_filter_step_counters_follow_the_active_slots_knobs(self):
        """`serving.sample_topk_steps` / `sample_topp_steps` count the decode
        steps whose sampling ran that filter's passes: some ACTIVE slot
        samples (temperature > 0) with the filter on. A greedy slot, and the
        knobs a released slot leaves behind, count nothing; one decode
        executable serves all of it."""
        from paddle_tpu.serving import GenerationEngine

        eng = GenerationEngine(_build_model(), max_batch_size=2,
                               buckets=(8,), rng_seed=3)

        def steps(n):
            before = eng.stats()
            for _ in range(n):
                eng.decode_step()
            after = eng.stats()
            return tuple(after[k] - before[k] for k in (
                "decode_steps", "sample_topk_steps", "sample_topp_steps",
                "decode_compiles"))

        eng.prefill(0, [5, 6, 7], top_k=4, top_p=0.5)  # greedy: knobs idle
        assert steps(3)[:3] == (3, 0, 0)
        eng.prefill(1, [8, 9], temperature=0.8, top_k=4, seed=1)
        assert steps(2) == (2, 2, 0, 0)
        eng.release(1)
        assert steps(2) == (2, 0, 0, 0)
        eng.prefill(1, [8, 9], temperature=0.8, top_p=0.9, seed=2)
        assert steps(2) == (2, 0, 2, 0)
        eng.release(1)
        eng.prefill(1, [8, 9], temperature=0.8, top_k=3, top_p=0.9, seed=2)
        assert steps(2) == (2, 2, 2, 0)


class TestLegacyCachePath:
    def test_caches_without_block_tables_is_a_type_error(self):
        """The paged pools are the model's one cache form: a call that
        hands it a growing or a per-slot contiguous cache is told where
        generation lives."""
        m = _build_model(seed=3)
        toks = paddle.to_tensor(
            np.random.default_rng(0).integers(
                1, VOCAB, (1, 4)).astype(np.int64))
        blk = m.gpt.blocks[0].attn
        grown = [(paddle.zeros([1, 0, blk.n_head, blk.head_dim]),) * 2
                 for _ in m.gpt.blocks]
        slots = [(paddle.zeros([1, 8, blk.n_head, blk.head_dim]),) * 2
                 for _ in m.gpt.blocks]
        zero = paddle.to_tensor(np.zeros([1], np.int32))
        with paddle.no_grad():
            for kw in (dict(caches=grown),
                       dict(caches=slots, cache_offsets=zero,
                            seq_lens=zero + 1)):
                with pytest.raises(TypeError) as e:
                    m.gpt(toks[:, :1], **kw)
                assert "block_tables=" in str(e.value)
                assert "GenerationEngine" in str(e.value)


# =========================================================================
# Train→serve resilience loop (ISSUE 7): drain-free weight hot-swap,
# transient-step retry, checkpoint watcher, elastic replica supervision.
# =========================================================================

def _greedy_straightline(model, prompt, n):
    """Ground-truth greedy continuation via the full forward path."""
    ids = list(prompt)
    out = []
    with paddle.no_grad():
        for _ in range(n):
            logits = model(paddle.to_tensor(np.asarray([ids], np.int64)))
            t = int(np.asarray(logits.numpy())[0, -1].argmax())
            out.append(t)
            ids.append(t)
    return out


def _np_state(model):
    """gpt-level state dict as plain numpy (a frozen weight snapshot —
    engines alias live tensors, so tests swap from copies)."""
    return {k: np.asarray(v.numpy()).copy()
            for k, v in model.gpt.state_dict().items()}


class TestWeightHotSwap:
    @pytest.fixture(autouse=True)
    def _disarm(self):
        yield
        from paddle_tpu.testing import faults
        faults.reset()

    @pytest.fixture(scope="class")
    def swap_rig(self):
        m_a = _build_model(seed=21)
        m_b = _build_model(seed=22)  # same arch, different weights
        a_sd, b_sd = _np_state(m_a), _np_state(m_b)
        srv = GenerationServer(m_a, max_batch_size=3, buckets=(8, 16),
                               max_queue_size=32)
        srv.start()
        prompt = list(np.random.default_rng(7).integers(1, VOCAB, 5))
        exp_a = _greedy_straightline(m_a, prompt, 6)
        exp_b = _greedy_straightline(m_b, prompt, 6)
        assert exp_a != exp_b  # the swap must be observable
        yield srv, prompt, a_sd, b_sd, exp_a, exp_b
        srv.shutdown(timeout=30)

    def _install(self, srv, sd):
        """Put the rig in a known weight state through the swap path."""
        srv.swap_weights(sd, source="test-install")
        srv.generate([1, 2, 3], max_new_tokens=1)  # drives a step boundary

    def test_mid_flight_swap_zero_failed_zero_recompiles(self, swap_rig):
        srv, prompt, a_sd, b_sd, exp_a, exp_b = swap_rig
        self._install(srv, a_sd)
        assert srv.generate(prompt, max_new_tokens=6) == exp_a
        c0 = dict(registry.counters("serving"))
        reqs = [srv.submit(list(np.random.default_rng(i).integers(
                    1, VOCAB, 5)), max_new_tokens=20) for i in range(4)]
        time.sleep(0.03)  # requests are mid-decode now
        # swap from the WRAPPER model's prefixed state dict ("gpt.<name>")
        srv.swap_weights({f"gpt.{k}": v for k, v in b_sd.items()},
                         source="unit-test")
        for r in reqs:
            assert r.result(120).status == RequestStatus.DONE
        # swap_weights STAGES the swap; the scheduler thread applies it at
        # its next boundary, which on a fast host may come after the last
        # of these short requests was answered
        deadline = time.monotonic() + 30
        while registry.counters("serving")["weight_swaps"] \
                == c0["weight_swaps"] and time.monotonic() < deadline:
            time.sleep(0.01)
        c1 = dict(registry.counters("serving"))
        assert c1["weight_swaps"] == c0["weight_swaps"] + 1
        assert c1["swap_failures"] == c0["swap_failures"]
        assert c1["requests_failed"] == c0["requests_failed"]
        assert c1["decode_compiles"] == c0["decode_compiles"]
        # the new weights actually serve: post-swap greedy == model-B truth
        assert srv.generate(prompt, max_new_tokens=6) == exp_b
        c2 = registry.counters("serving")
        assert c2["decode_compiles"] == c0["decode_compiles"]
        assert c2["prefill_compiles"] == c0["prefill_compiles"]

    def test_swap_refuses_aval_and_name_mismatch(self, swap_rig):
        srv, prompt, a_sd, b_sd, exp_a, exp_b = swap_rig
        from paddle_tpu.serving import WeightSwapError

        self._install(srv, a_sd)
        eng = srv.engine
        with pytest.raises(WeightSwapError, match="missing"):
            eng.swap_weights({k: b_sd[k] for k in list(b_sd)[:3]})
        bad = dict(b_sd)
        name = next(k for k in bad if bad[k].ndim == 2)
        bad[name] = bad[name][:-1]  # truncated: a different model
        with pytest.raises(WeightSwapError, match="aval mismatch"):
            eng.swap_weights(bad)
        # staged through the server: refusal is counted, old weights serve
        c0 = dict(registry.counters("serving"))
        srv.swap_weights(bad, source="bad-swap")
        assert srv.generate(prompt, max_new_tokens=6) == exp_a
        c1 = dict(registry.counters("serving"))
        assert c1["swap_failures"] == c0["swap_failures"] + 1
        assert c1["weight_swaps"] == c0["weight_swaps"]
        assert isinstance(srv.scheduler.last_swap_error, WeightSwapError)

    def test_kill_during_swap_leaves_server_healthy(self, swap_rig):
        srv, prompt, a_sd, b_sd, exp_a, exp_b = swap_rig
        from paddle_tpu.testing import faults

        self._install(srv, a_sd)
        c0 = dict(registry.counters("serving"))
        faults.configure("kill_during_swap")
        srv.swap_weights(b_sd, source="doomed-swap")
        # the swap dies between validation and commit; requests keep
        # flowing on the COMPLETE pre-swap weights
        assert srv.generate(prompt, max_new_tokens=6) == exp_a
        faults.reset()
        c1 = dict(registry.counters("serving"))
        assert c1["swap_failures"] == c0["swap_failures"] + 1
        assert c1["weight_swaps"] == c0["weight_swaps"]
        assert c1["requests_failed"] == c0["requests_failed"]
        assert registry.counters("fault").get(
            "injected.kill_during_swap", 0) >= 1

    def test_watcher_follows_checkpoints_skips_torn_merges_shards(
            self, swap_rig, tmp_path):
        srv, prompt, a_sd, b_sd, exp_a, exp_b = swap_rig
        from paddle_tpu.incubate import checkpoint as ckpt
        from paddle_tpu.testing import faults

        self._install(srv, a_sd)
        srv.last_swap_step = -1
        srv.watch_checkpoints(str(tmp_path), interval=0.05)
        try:
            # (1) a fresh training checkpoint lands -> serving follows
            ckpt.save_checkpoint(str(tmp_path), {"model": b_sd}, step=1)
            deadline = time.monotonic() + 20
            while srv.last_swap_step < 1 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert srv.last_swap_step == 1
            assert srv.generate(prompt, max_new_tokens=6) == exp_b
            # (2) torn checkpoint under the watcher: skipped, no crash,
            # no swap, server keeps serving
            faults.configure("truncate_checkpoint:nth=1,bytes=7")
            ckpt.save_checkpoint(str(tmp_path), {"model": a_sd}, step=2)
            faults.reset()
            time.sleep(0.3)
            assert srv.last_swap_step == 1
            assert srv.generate(prompt, max_new_tokens=6) == exp_b
            # (3) a SHARDED world-2 checkpoint merges through the manifest
            for r in range(2):
                ckpt.save_checkpoint(str(tmp_path), {"model": a_sd},
                                     step=3, rank=r, world_size=2,
                                     shard=True)
            deadline = time.monotonic() + 20
            while srv.last_swap_step < 3 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert srv.last_swap_step == 3
            assert srv.generate(prompt, max_new_tokens=6) == exp_a
        finally:
            srv.stop_watcher()


class TestDecodeFastPath:
    """ISSUE 9: the steady decode iteration runs on prebuilt device-side
    slot state (one fingerprint check + one executable call); rebuilds
    happen only at batch boundaries (admission/evict/swap/reprime) and a
    periodic audit cross-checks device copies against the host mirrors.
    The bitwise-parity tests above already prove tokens are unchanged —
    these pin the fast/rebuild/audit accounting."""

    def test_steady_window_runs_fast_and_audits_clean(self):
        srv = GenerationServer(_build_model(seed=31), max_batch_size=2,
                               buckets=(8,), max_queue_size=16)
        srv.engine._audit_every = 5
        srv.start()
        try:
            srv.generate([1, 2, 3], max_new_tokens=2)  # warm both steps
            f0 = dict(registry.counters("fastpath"))
            reqs = [srv.submit([3 + i, 4, 5], max_new_tokens=24, seed=i)
                    for i in range(2)]
            for r in reqs:
                assert r.result(120).status == RequestStatus.DONE
            f1 = dict(registry.counters("fastpath"))
            fast = f1["decode_fast_steps"] - f0["decode_fast_steps"]
            rebuilds = f1["decode_rebuilds"] - f0["decode_rebuilds"]
            audits = f1["decode_audit_runs"] - f0["decode_audit_runs"]
            assert fast > rebuilds, (fast, rebuilds)
            assert audits >= 1  # the 5-step cadence fired in the window
            assert f1["decode_demotions"] == f0["decode_demotions"]
        finally:
            srv.shutdown(timeout=30)

    def test_mutations_invalidate_and_mirrors_track_device(self):
        from paddle_tpu.serving.engine import GenerationEngine

        eng = GenerationEngine(_build_model(seed=32), max_batch_size=2,
                               buckets=(8,), rng_seed=5)
        eng.prefill(0, [1, 2, 3], seed=0)
        eng.prefill(1, [4, 5, 6], seed=1)
        assert eng._fast is None  # admission invalidated it
        f0 = dict(registry.counters("fastpath"))
        eng.decode_step()  # rebuild + re-arm
        for _ in range(5):
            eng.decode_step()  # steady: fast
        f1 = dict(registry.counters("fastpath"))
        assert f1["decode_rebuilds"] - f0["decode_rebuilds"] == 1
        assert f1["decode_fast_steps"] - f0["decode_fast_steps"] == 5
        fast = eng._fast
        assert fast is not None
        # host mirrors advance in lockstep with the device copies
        assert np.array_equal(np.asarray(fast[1]), eng._cur_lens)
        assert np.array_equal(np.asarray(fast[3]), eng._gen_idx)
        assert np.array_equal(np.asarray(fast[0]), eng._last_tokens)
        # eviction is a batch-boundary event: next decode rebuilds
        eng.release(1)
        assert eng._fast is None
        eng.decode_step()
        f2 = dict(registry.counters("fastpath"))
        assert f2["decode_rebuilds"] - f1["decode_rebuilds"] == 1
        # a weight swap drops the cached weight tuple AND the fast
        # state: the first post-swap decode rebuilds through the radar
        eng.swap_weights(_np_state(_build_model(seed=33)),
                         source="fastpath-test")
        assert eng._state_tuple is None and eng._fast is None
        eng.decode_step()
        f3 = dict(registry.counters("fastpath"))
        assert f3["decode_rebuilds"] - f2["decode_rebuilds"] == 1
        assert eng._state_tuple is not None  # rebuilt on demand


class TestStepRetry:
    @pytest.fixture(autouse=True)
    def _disarm(self):
        yield
        from paddle_tpu.testing import faults
        faults.reset()

    def test_transient_decode_error_retries_once(self, server):
        from paddle_tpu.testing import faults

        prompt = [3, 5, 7]
        want = server.generate(prompt, max_new_tokens=4)  # pre-fault truth
        c0 = dict(registry.counters("serving"))
        faults.configure("decode_error:fails=1")
        got = server.generate(prompt, max_new_tokens=4)
        faults.reset()
        assert got == want  # retried step produced the same tokens
        c1 = dict(registry.counters("serving"))
        assert c1["step_retries"] == c0["step_retries"] + 1
        assert c1["reprimes"] == c0["reprimes"] + 1
        assert c1["requests_failed"] == c0["requests_failed"]
        assert len(explainer.events(kind="serving_step_retry")) >= 1

    def test_second_consecutive_error_fails_batch_then_recovers(
            self, server):
        from paddle_tpu.testing import faults

        c0 = dict(registry.counters("serving"))
        faults.configure("decode_error:fails=2")
        req = server.submit([2, 4, 6], max_new_tokens=4)
        req.result(60)
        assert req.status == RequestStatus.ERROR
        assert "decode failure" in req.error
        c1 = dict(registry.counters("serving"))
        assert c1["step_retries"] == c0["step_retries"] + 1
        assert c1["requests_failed"] == c0["requests_failed"] + 1
        # the injected budget is exhausted: the server recovered and the
        # next request sails through
        got = server.generate([2, 4, 6], max_new_tokens=4)
        faults.reset()
        assert len(got) == 4


class _SlowFakeEngine(_FakeEngine):
    """Fake engine whose decode is slow enough to pile up a queue (drives
    the supervisor's scale-up) and which honors reset()."""

    def decode_step(self):
        time.sleep(0.03)
        return super().decode_step()

    def reset(self):
        for i in range(self.max_batch_size):
            self.release(i)


class TestReplicaSupervision:
    @pytest.fixture(autouse=True)
    def _disarm(self):
        yield
        from paddle_tpu.testing import faults
        faults.reset()

    def test_replica_kill_restarts_and_replays_bitwise(self):
        from paddle_tpu.serving import GenerationEngine, ReplicaSupervisor
        from paddle_tpu.testing import faults

        model = _build_model(seed=31)
        factory = lambda: GenerationEngine(  # noqa: E731
            model, max_batch_size=2, buckets=(8,), rng_seed=7)
        rng = np.random.default_rng(11)
        prompts = [list(rng.integers(1, VOCAB, 5)) for _ in range(3)]
        opts = dict(max_new_tokens=6, temperature=0.8)

        sup = ReplicaSupervisor(factory, replicas=1, restart_backoff=0.05,
                                monitor_interval=0.02)
        expected = [sup.submit(p, **opts) for p in prompts]
        expected = [list(r.result(120).tokens) for r in expected]
        sup.shutdown()

        c0 = dict(registry.counters("serving"))
        faults.configure("replica_kill:nth=4")
        sup2 = ReplicaSupervisor(factory, replicas=1, restart_backoff=0.05,
                                 monitor_interval=0.02)
        reqs = [sup2.submit(p, **opts) for p in prompts]
        got = [list(r.result(180).tokens) for r in reqs]
        faults.reset()
        c1 = dict(registry.counters("serving"))
        sup2.shutdown()
        # the replica died mid-flight, was restarted, and REPLAYED its
        # requests: same seeds + same engine rng_seed -> bitwise tokens
        assert got == expected
        assert all(r.status == RequestStatus.DONE for r in reqs)
        assert c1["replica_restarts"] == c0["replica_restarts"] + 1
        assert c1["requeued_requests"] > c0["requeued_requests"]

    def test_autoscale_up_on_queue_depth_then_down_when_idle(self):
        from paddle_tpu.serving import ReplicaSupervisor

        sup = ReplicaSupervisor(
            lambda: _SlowFakeEngine(max_batch_size=1), replicas=1,
            max_replicas=3, min_replicas=1, scale_up_queue_depth=2,
            scale_interval=0.05, monitor_interval=0.02, max_queue_size=64)
        c0 = dict(registry.counters("serving"))
        reqs = [sup.submit([1, 2], max_new_tokens=3) for _ in range(10)]
        deadline = time.monotonic() + 10
        while sup.replicas() < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert sup.replicas() >= 2, "queue depth never triggered scale-up"
        for r in reqs:
            assert r.result(60).status == RequestStatus.DONE
        deadline = time.monotonic() + 10
        while sup.replicas() > 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert sup.replicas() == 1, "idle fleet never scaled back down"
        c1 = dict(registry.counters("serving"))
        assert c1["scale_ups"] >= c0["scale_ups"] + 1
        assert c1["scale_downs"] >= c0["scale_downs"] + 1
        sup.shutdown()
