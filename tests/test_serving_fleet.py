"""Cross-process serving fleet (ISSUE 11).

Covers the acceptance gates:
  * SIGKILL/fatal death of a serving pod mid-flight → ZERO failed
    requests, orphans replayed BITWISE on the respawned/surviving pod;
  * fleet-wide ``swap_weights`` lands on every pod at its decode-step
    boundary: 0 failed requests, 0 new decode compiles, post-swap
    tokens equal the new weights' reference;
  * prefix-affinity routing measurably raises the aggregate
    ``prefix_hit_rate`` over round-robin on shared-prompt traffic;
  * router backpressure (``QueueFullError``) engages ONLY when every
    eligible pod's admission budget is exhausted (unit-tested against
    fake pod clients for determinism);
  * disaggregated prefill→decode KV handoff is token-bitwise vs a
    monolithic pod (engine-level unit + real two-role fleet);
  * ``watch_checkpoints`` per-pod interval jitter is deterministic and
    the fleet swap path shares the watcher's file-set-change dedup.

Real-fleet tests spawn pod SUBPROCESSES (the point of the issue); they
share one model/engine config so reference tokens are computed once.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import registry
from paddle_tpu.serving import (GenerationEngine, GenerationServer,
                                QueueFullError)
from paddle_tpu.serving.fleet import ServingFleet
from paddle_tpu.serving.router import (FleetRouter, pack_payload,
                                       unpack_payload)
from paddle_tpu.serving.server import pod_jitter_fraction
from paddle_tpu.testing import faults

VOCAB = 96
CONFIG = dict(vocab_size=VOCAB, n_layer=2, n_head=2, d_model=48,
              seq_len=64, initializer_range=0.35)
MODEL_SPEC = {"kind": "gpt", "seed": 21, "config": CONFIG}
ENGINE_KW = dict(max_batch_size=2, buckets=[16], block_size=4, rng_seed=0)


def _build_model(seed=21):
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTModel)

    paddle.seed(seed)
    return GPTForPretraining(GPTModel(GPTConfig(**CONFIG)))


def _timeout(base):
    from proc_utils import proc_timeout

    return proc_timeout(base)


def _reference_tokens(requests, seed=21):
    """What a single healthy pod would generate: same model seed, same
    engine rng_seed, seeds assigned in submission order (the router pins
    0, 1, 2, ... exactly like this)."""
    srv = GenerationServer(
        engine=GenerationEngine(_build_model(seed), max_batch_size=2,
                                buckets=(16,), block_size=4, rng_seed=0))
    srv.start()
    out = []
    for i, (prompt, opts) in enumerate(requests):
        out.append(srv.generate(prompt, seed=opts.get("seed", i),
                                **{k: v for k, v in opts.items()
                                   if k != "seed"}))
    srv.shutdown(timeout=30)
    return out


@pytest.fixture
def fleet_factory():
    fleets = []

    def make(**kw):
        kw.setdefault("engine", ENGINE_KW)
        kw.setdefault("restart_backoff", 0.05)
        kw.setdefault("connect_timeout", _timeout(120))
        fl = ServingFleet(MODEL_SPEC, **kw)
        fleets.append(fl)
        return fl.start()

    yield make
    for fl in fleets:
        try:
            fl.shutdown(drain=False, timeout=_timeout(30))
        except Exception:
            pass


# ---------------------------------------------------------------- units --
class TestHandoffUnit:
    def test_export_import_bitwise_and_accounted(self):
        prompt = [3, 5, 7, 9, 11]
        ref = GenerationEngine(_build_model(), max_batch_size=2,
                               buckets=(8,), block_size=4, rng_seed=7)
        want = [ref.prefill(0, prompt, temperature=0.8, seed=0,
                            max_new_tokens=6)]
        for _ in range(5):
            want.append(int(ref.decode_step()[0]))

        eng_a = GenerationEngine(_build_model(), max_batch_size=2,
                                 buckets=(8,), block_size=4, rng_seed=7)
        # decode-side base seed differs on purpose: the EXPORTED request
        # key must rule, or replays would depend on which pod decodes
        eng_b = GenerationEngine(_build_model(), max_batch_size=2,
                                 buckets=(8,), block_size=4, rng_seed=99)
        eng_a.prefill(0, prompt, temperature=0.8, seed=0,
                      max_new_tokens=6)
        payload = eng_a.export_request_kv(0)
        eng_a.release(0)
        eng_a.pool.audit()
        assert eng_b.can_import(payload)
        got = [eng_b.import_request_kv(1, payload, prompt_ids=prompt)]
        for _ in range(5):
            got.append(int(eng_b.decode_step()[1]))
        assert got == want
        # the adopted prompt's full blocks joined B's prefix cache
        assert len(eng_b.prefix_cache) == len(prompt) // 4
        eng_b.release(1)
        eng_b.pool.audit()

    def test_stale_handoff_refused_and_reprefilled(self):
        """A weight swap landing between export and import must not let
        old-weight KV decode under new weights (or leak into the prefix
        cache): the engine refuses, and the scheduler falls back to a
        fresh local prefill under the current weights — exactly what a
        monolithic pod that swapped first would have produced."""
        from paddle_tpu.serving import ContinuousBatchScheduler
        from paddle_tpu.serving.engine import StaleHandoffError
        from paddle_tpu.serving.scheduler import GenerationRequest

        prompt = [3, 5, 7, 9, 11]
        b_sd = {k: np.asarray(v.numpy()).copy()
                for k, v in _build_model(22).gpt.state_dict().items()}
        # monolithic truth: model B prefills + decodes the request
        want = _reference_tokens([(prompt, dict(max_new_tokens=6,
                                                seed=0))], seed=22)[0]
        eng_a = GenerationEngine(_build_model(), max_batch_size=2,
                                 buckets=(16,), block_size=4, rng_seed=0)
        eng_b = GenerationEngine(_build_model(), max_batch_size=2,
                                 buckets=(16,), block_size=4, rng_seed=0)
        eng_a.prefill(0, prompt, seed=0, max_new_tokens=6)
        payload = eng_a.export_request_kv(0)  # generation 0
        eng_a.release(0)
        eng_b.swap_weights(b_sd)              # generation bump on B
        with pytest.raises(StaleHandoffError):
            eng_b.import_request_kv(0, payload, prompt_ids=prompt)
        eng_b.pool.audit()  # refusal leaks nothing
        assert len(eng_b.prefix_cache) == 0  # no stale blocks published
        # scheduler path: the request still completes, on B's weights
        sched = ContinuousBatchScheduler(eng_b)
        req = GenerationRequest(prompt, max_new_tokens=6, seed=0)
        req.kv_payload = payload
        sched.submit(req)
        while sched.step():
            pass
        assert req.status == "done"
        assert list(req.tokens) == want
        assert registry.counters("serving")["handoff_stale"] >= 1

    def test_import_refuses_geometry_mismatch(self):
        prompt = [3, 5, 7, 9, 11]
        eng_a = GenerationEngine(_build_model(), max_batch_size=1,
                                 buckets=(8,), block_size=4, rng_seed=7)
        eng_b = GenerationEngine(_build_model(), max_batch_size=1,
                                 buckets=(8,), block_size=8, rng_seed=7)
        eng_a.prefill(0, prompt, max_new_tokens=4)
        payload = eng_a.export_request_kv(0)
        with pytest.raises(ValueError, match="block_size"):
            eng_b.import_request_kv(0, payload)
        eng_b.pool.audit()  # refused import leaks nothing

    def test_payload_wire_roundtrip_bitwise(self):
        import json

        eng = GenerationEngine(_build_model(), max_batch_size=1,
                               buckets=(8,), block_size=4, rng_seed=7)
        eng.prefill(0, [1, 2, 3, 4, 5], temperature=0.9, seed=3,
                    max_new_tokens=4)
        payload = eng.export_request_kv(0)
        back = unpack_payload(json.loads(json.dumps(
            pack_payload(payload))))
        for field in ("kv_k", "kv_v"):
            for a, b in zip(payload[field], back[field]):
                assert np.array_equal(a, b)
        assert np.array_equal(payload["key"], back["key"])
        assert back["cur_len"] == payload["cur_len"]
        assert back["last_token"] == payload["last_token"]


class _FakeClient:
    """In-process stand-in for PodClient: scripted ack/reject/silence so
    router semantics are tested deterministically."""

    def __init__(self, behavior="ack"):
        self.behavior = behavior  # "ack" | "reject" | "silent"
        self.alive = True
        self.sent = []

    def call(self, msg, timeout=None):
        self.sent.append(msg)
        if not self.alive or self.behavior == "silent":
            return None
        if self.behavior == "reject":
            return {"op": "reject", "mid": msg.get("mid"),
                    "reason": "queue_full"}
        return {"op": "ack", "mid": msg.get("mid"), "queued": 0,
                "active": 0}

    def close(self):
        self.alive = False


class TestRouterUnit:
    def _router(self, behaviors, policy="prefix"):
        r = FleetRouter(policy=policy, block_size=4, ack_timeout=0.2)
        clients = []
        for i, b in enumerate(behaviors):
            c = _FakeClient(b)
            clients.append(c)
            r.register_pod(i, c, role="serve")
        return r, clients

    def test_queue_full_only_at_fleet_wide_exhaustion(self):
        # one pod rejecting is NOT backpressure — the sibling absorbs it
        r, clients = self._router(["reject", "ack"])
        req = r.submit([1, 2, 3], max_new_tokens=4)
        assert req.pod == 1
        # ALL pods rejecting IS: QueueFullError reaches the caller
        r, clients = self._router(["reject", "reject"])
        with pytest.raises(QueueFullError):
            r.submit([1, 2, 3], max_new_tokens=4)
        assert registry.counters("fleet")["router_rejects"] >= 3

    def test_down_pod_is_not_backpressure(self):
        # a dead/mid-restart pod must hold traffic for replay, never
        # surface QueueFullError
        r, clients = self._router(["silent", "silent"])
        req = r.submit([1, 2, 3], max_new_tokens=4)
        assert not req.done and r.held() == 1
        # pod 1 comes back: redistribute places the held request
        clients[1].behavior = "ack"
        r.redistribute()
        assert r.held() == 0 and req.pod == 1

    def test_router_drop_resubmits_idempotently(self):
        r, clients = self._router(["ack", "ack"])
        faults.configure("router_drop:nth=1")
        try:
            req = r.submit([1, 2, 3, 4, 5], max_new_tokens=4)
        finally:
            faults.reset()
        # first send was lost in transit; the SAME rid landed elsewhere
        assert req.pod is not None
        sent = clients[0].sent + clients[1].sent
        assert len(sent) == 1 and sent[0]["rid"] == req.rid
        assert req.attempts == 2

    def test_affinity_sticks_and_spills(self):
        r, clients = self._router(["ack", "ack"])
        shared = [9, 9, 9, 9]  # one full block at block_size=4
        first = r.submit(shared + [1], max_new_tokens=4)
        home = first.pod
        for _ in range(3):
            assert r.submit(shared + [2], max_new_tokens=4).pod == home
        c = registry.counters("fleet")
        assert c["affinity_hits"] >= 3
        # the sticky pod running out of budget spills AND remaps
        clients[home].behavior = "reject"
        spilled = r.submit(shared + [3], max_new_tokens=4)
        assert spilled.pod != home
        clients[home].behavior = "ack"
        assert r.submit(shared + [4], max_new_tokens=4).pod == spilled.pod

    def test_pod_down_replays_orphans(self):
        r, clients = self._router(["ack", "silent"])
        req = r.submit([1, 2, 3], max_new_tokens=4)
        assert req.pod == 0
        clients[0].alive = False
        n = r.pod_down(0)
        assert n == 1 and req.pod is None
        clients[1].behavior = "ack"
        r.redistribute()
        assert req.pod == 1
        # late duplicate completion from the dead pod is dropped first-
        # wins once the live pod reports
        r.on_pod_message(1, {"op": "done", "rid": req.rid,
                             "status": "done", "tokens": [5, 6]})
        r.on_pod_message(0, {"op": "done", "rid": req.rid,
                             "status": "done", "tokens": [7, 8]})
        assert req.tokens == [5, 6] and req.status == "done"


class TestWatcherJitter:
    def test_jitter_fraction_deterministic_per_pod(self):
        a1 = pod_jitter_fraction("3")
        a2 = pod_jitter_fraction("3")
        b = pod_jitter_fraction("4")
        assert a1 == a2 and 0.0 <= a1 < 1.0
        assert a1 != b  # neighboring pods de-phase

    def test_follower_dedups_file_set_and_is_shared(self, tmp_path,
                                                    monkeypatch):
        from paddle_tpu.incubate import checkpoint as ckpt

        srv = GenerationServer(
            engine=GenerationEngine(_build_model(), max_batch_size=1,
                                    buckets=(8,), rng_seed=0))
        srv.start()
        try:
            f1 = srv.checkpoint_follower(tmp_path)
            assert srv.checkpoint_follower(tmp_path) is f1  # shared
            b_sd = {k: np.asarray(v.numpy()).copy()
                    for k, v in _build_model(22).gpt.state_dict().items()}
            # rank 0's shard of a world-2 checkpoint lands FIRST (the
            # late-arriving-shard window): the merge fails until rank
            # 1's shard exists
            ckpt.save_checkpoint(str(tmp_path), {"model": b_sd}, step=1,
                                 rank=0, world_size=2, shard=True)
            calls = []
            real = ckpt.load_resharded

            def counting(*a, **kw):
                calls.append(1)
                return real(*a, **kw)

            monkeypatch.setattr(ckpt, "load_resharded", counting)
            assert f1.poll(wait_applied=5) is None  # incomplete: tried
            assert len(calls) == 1
            assert f1.poll(wait_applied=5) is None  # same file set:
            assert len(calls) == 1                  # NOT re-read
            # the missing shard landing (file-set change) re-attempts
            # and the swap applies
            ckpt.save_checkpoint(str(tmp_path), {"model": b_sd}, step=1,
                                 rank=1, world_size=2, shard=True)
            assert f1.poll(wait_applied=_timeout(30)) == 1
            assert len(calls) == 2
            assert srv.last_swap_step == 1
        finally:
            srv.shutdown(timeout=30)


# ----------------------------------------------------- real-fleet (subproc) --
class TestFleetIntegration:
    def test_pod_kill_zero_failed_bitwise_replay(self, fleet_factory):
        """SIGKILL-style pod death mid-flight: the fleet supervisor
        respawns with backoff, the router replays every orphan, tokens
        are bitwise what an unkilled pod would have produced."""
        traffic = [([3, 5, 7, 9, 11], dict(max_new_tokens=8,
                                           temperature=0.8)),
                   ([2, 4, 6], dict(max_new_tokens=8, temperature=0.8)),
                   ([1, 2, 3, 4, 5, 6, 7], dict(max_new_tokens=8,
                                                temperature=0.8))]
        want = _reference_tokens(traffic)
        f0 = dict(registry.counters("fleet"))
        fleet = fleet_factory(pods=1,
                              pod_faults={0: "replica_kill:nth=4"})
        reqs = [fleet.submit(p, **o) for p, o in traffic]
        got = [list(r.result(_timeout(180)).tokens) for r in reqs]
        assert [r.status for r in reqs] == ["done"] * 3
        assert got == want
        st = fleet.stats()
        assert st["pods"][0]["restarts"] >= 1
        c = registry.counters("fleet")
        assert c["requests_failed"] == f0.get("requests_failed", 0)
        assert c["orphans_replayed"] > f0.get("orphans_replayed", 0)

    def test_fleet_swap_all_pods_zero_failed_zero_recompiles(
            self, fleet_factory, tmp_path):
        from paddle_tpu.incubate import checkpoint as ckpt

        b_sd = {k: np.asarray(v.numpy()).copy()
                for k, v in _build_model(22).gpt.state_dict().items()}
        probe = [3, 5, 7, 9, 11]
        want_b = _reference_tokens([(probe, dict(max_new_tokens=6,
                                                 seed=50))], seed=22)[0]
        fleet = fleet_factory(pods=2)
        # warm both pods' executables (distinct prompts spread by load)
        fleet.generate(probe, max_new_tokens=4, result_timeout=_timeout(120))
        fleet.generate([9, 8, 7], max_new_tokens=4,
                       result_timeout=_timeout(120))
        compiles0 = {p: d.get("decode_compiles")
                     for p, d in fleet.stats()["pods"].items()}
        ckpt.save_checkpoint(str(tmp_path), {"model": b_sd}, step=1)
        # swap lands while requests are in flight
        reqs = [fleet.submit([2, 4, 6, 8], max_new_tokens=12,
                             temperature=0.5) for _ in range(4)]
        replies = fleet.swap_weights(tmp_path, timeout=_timeout(60))
        for r in reqs:
            r.result(_timeout(120))
        assert [r.status for r in reqs] == ["done"] * 4
        assert all(rep is not None and rep["applied_step"] == 1
                   and rep["swap_error"] is None
                   for rep in replies.values()), replies
        st = fleet.stats()
        compiles1 = {p: d.get("decode_compiles")
                     for p, d in st["pods"].items()}
        assert compiles1 == compiles0, "fleet swap recompiled decode"
        assert st["router"]["requests_failed"] == 0
        # post-swap traffic decodes on the NEW weights
        got = fleet.generate(probe, max_new_tokens=6, seed=50,
                             result_timeout=_timeout(120))
        assert got == want_b

    def test_prefix_affinity_beats_round_robin(self, fleet_factory):
        shared = [11, 12, 13, 14, 15, 16, 17, 18]  # 2 full blocks @ 4
        rng = np.random.default_rng(3)
        suffixes = [[int(t) for t in rng.integers(1, VOCAB, 3)]
                    for _ in range(8)]

        def run(policy):
            fl = fleet_factory(pods=2, policy=policy)
            reqs = [fl.submit(shared + sfx, max_new_tokens=4)
                    for sfx in suffixes]
            for r in reqs:
                r.result(_timeout(120))
            assert all(r.status == "done" for r in reqs)
            st = fl.stats()
            fl.shutdown(drain=False, timeout=_timeout(30))
            return st

        st_aff = run("prefix")
        st_rr = run("round_robin")
        assert st_aff["prefix_hit_rate"] > st_rr["prefix_hit_rate"], (
            st_aff["prefix_hit_rate"], st_rr["prefix_hit_rate"])
        # shared-prompt traffic all landed on one pod under affinity
        assert st_aff["router"]["affinity_hits"] >= 6

    def test_disaggregated_handoff_bitwise_vs_monolithic(
            self, fleet_factory):
        traffic = [([3, 5, 7, 9, 11], dict(max_new_tokens=8,
                                           temperature=0.8)),
                   ([2, 4, 6], dict(max_new_tokens=8)),
                   ([1, 2, 3, 4, 5, 6, 7], dict(max_new_tokens=8,
                                                temperature=0.6))]
        want = _reference_tokens(traffic)
        fleet = fleet_factory(roles=["prefill", "decode"])
        got = [fleet.generate(p, result_timeout=_timeout(180), **o)
               for p, o in traffic]
        assert got == want
        st = fleet.stats()
        assert st["router"]["handoffs"] >= 3
        assert st["pods"][0]["handoff_exports"] >= 3
        assert st["pods"][1]["handoff_imports"] >= 3

    def test_binary_plane_carries_every_handoff(self, fleet_factory):
        """The default data plane streams EVERY prefill->decode KV bundle
        pod to pod: no handoff rides back inline as JSON, nothing fails,
        and the traffic adds no decode compile after the warm-up."""
        fleet = fleet_factory(roles=["prefill", "decode"])
        assert fleet.data_plane == "binary"
        fleet.generate([9, 8, 7], max_new_tokens=4,
                       result_timeout=_timeout(180))  # warm both pods
        f0 = dict(registry.counters("fleet"))
        compiles0 = {p: d.get("decode_compiles")
                     for p, d in fleet.stats()["pods"].items()}
        rng = np.random.default_rng(9)
        reqs = [fleet.submit([int(t) for t in rng.integers(1, VOCAB, 3 + i)],
                             max_new_tokens=6) for i in range(6)]
        for r in reqs:
            r.result(_timeout(180))
        assert [r.status for r in reqs] == ["done"] * 6
        st = fleet.stats()
        f1 = registry.counters("fleet")
        assert f1["handoffs_binary"] - f0["handoffs_binary"] == 6
        assert f1["handoffs_fallback"] == f0["handoffs_fallback"]
        assert f1["handoff_bytes"] > f0["handoff_bytes"]
        assert f1["requests_failed"] == f0["requests_failed"]
        assert st["data_plane"]["tx_bytes"] > 0
        assert {p: d.get("decode_compiles")
                for p, d in st["pods"].items()} == compiles0
