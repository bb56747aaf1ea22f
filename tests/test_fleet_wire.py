"""Fleet binary data plane (ISSUE 19).

Unit coverage for the framed KV transport and its chaos layer:

  * frame codec round-trip (every kind, zero-length payloads) and the
    full malformed-stream taxonomy — truncation at EVERY byte boundary
    of header and payload, CRC corruption, version mismatch, bad magic
    — each surfacing as a FrameError (transport loss), never as data;
  * payload codec: ``export_request_kv``-shaped dicts survive bitwise,
    zero-length tensors included;
  * ``testing/netfaults.py`` grammar + the tx/rx fault seams;
  * FrameSender ↔ DataPlaneListener loopback under every injected
    fault: delivery always succeeds (within budget) with the payload
    intact, or raises DataPlaneError past the budget — no third
    outcome;
  * store endpoint publication: generation-monotone publish, stale-
    generation rejection on resolve;
  * router circuit breaker: a flapping pod degrades to held-and-
    replayed, never to a caller-visible error.
"""
import io
import socket
import threading
import time

import numpy as np
import pytest

from paddle_tpu.profiler import registry
from paddle_tpu.serving import wire
from paddle_tpu.serving.router import FleetRouter
from paddle_tpu.testing import faults, netfaults


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.reset()


def _frame_of(kind=wire.TENSOR, fid=7, body=b"abcdef"):
    return wire.pack_frame(kind, fid, body)


def _read(data):
    return wire.read_frame(io.BytesIO(data).read)


class TestFrameCodec:
    def test_roundtrip_every_kind(self):
        for kind in (wire.OPEN, wire.TENSOR, wire.COMMIT, wire.ACK,
                     wire.NACK, wire.PING, wire.PONG):
            for body in (b"", b"x", b"payload" * 500):
                k, flags, fid, payload = _read(
                    wire.pack_frame(kind, 123456789, body))
                assert (k, fid, payload) == (kind, 123456789, body)

    def test_clean_eof_returns_none(self):
        assert _read(b"") is None

    def test_truncation_at_every_byte_boundary(self):
        # a stream cut anywhere inside a frame is FrameTruncatedError
        # (connection loss), except a cut at offset 0 (clean EOF)
        fb = _frame_of(body=b"abc")
        assert len(fb) == wire.HEADER.size + 3
        for cut in range(1, len(fb)):
            with pytest.raises(wire.FrameTruncatedError):
                _read(fb[:cut])

    def test_crc_corruption_every_payload_byte(self):
        fb = _frame_of(body=b"abcdef")
        for off in range(wire.HEADER.size, len(fb)):
            bad = bytearray(fb)
            bad[off] ^= 0xFF
            with pytest.raises(wire.FrameCRCError) as ei:
                _read(bytes(bad))
            assert ei.value.frame_id == 7

    def test_version_mismatch(self):
        bad = bytearray(_frame_of())
        bad[2] = wire.VERSION + 1
        with pytest.raises(wire.FrameVersionError):
            _read(bytes(bad))

    def test_bad_magic_is_desync(self):
        bad = b"XX" + _frame_of()[2:]
        with pytest.raises(wire.FrameProtocolError):
            _read(bad)

    def test_crc32c_reference_vector(self):
        # the iSCSI Castagnoli check value
        assert wire.crc32c_sw(b"123456789") == 0xE3069283

    def test_checksum_flags_agree(self):
        data = b"the payload"
        crc, flags = wire.checksum(data)
        assert wire.verify_checksum(data, crc, flags)
        assert not wire.verify_checksum(data + b"!", crc, flags)
        # the software CRC32C verifier accepts what any accelerated
        # implementation would produce for FLAG_CRC32C frames
        assert wire.verify_checksum(
            b"123456789", 0xE3069283, wire.FLAG_CRC32C)


class TestPayloadCodec:
    def _payload(self):
        rng = np.random.default_rng(3)
        return {
            "n_blocks": 3, "block_size": 4, "cur_len": 11,
            "last_token": 42, "gen_idx": 2, "temperature": 0.5,
            "top_k": 0, "top_p": 1.0, "weight_generation": 1,
            "trace": "t-1",
            "key": np.array([123, 456], np.uint32),
            "kv_k": [rng.standard_normal((2, 4, 8)).astype(np.float32),
                     np.zeros((0, 4, 8), np.float32)],
            "kv_v": [rng.standard_normal((2, 4, 8)).astype(np.float32),
                     np.zeros((0, 4, 8), np.float32)],
        }

    def test_bitwise_roundtrip_with_zero_length_tensors(self):
        payload = self._payload()
        doc, tensors = wire.encode_payload(payload)
        back = wire.decode_payload(doc,
                                   [t.tobytes() for t in tensors])
        for k, v in payload.items():
            if isinstance(v, np.ndarray):
                assert back[k].dtype == v.dtype
                assert (back[k] == v).all()
            elif isinstance(v, list):
                for a, b in zip(v, back[k]):
                    assert b.dtype == a.dtype and b.shape == a.shape
                    assert (a == b).all()
            else:
                assert back[k] == v

    def test_tensor_count_mismatch_rejected(self):
        doc, tensors = wire.encode_payload(self._payload())
        bodies = [t.tobytes() for t in tensors]
        with pytest.raises(wire.FrameProtocolError):
            wire.decode_payload(doc, bodies + [b"extra"])

    def test_payload_nbytes(self):
        payload = self._payload()
        n = wire.payload_nbytes(payload)
        assert n == sum(a.nbytes for a in payload["kv_k"]
                        + payload["kv_v"]) + payload["key"].nbytes


class TestNetFaults:
    def test_armed_through_shared_grammar(self):
        # one FLAGS_fault_inject spec arms both surfaces
        faults.configure("net_corrupt:nth=1;pod_slow:delay=0.01")
        assert netfaults.ACTIVE and "net_corrupt" in netfaults.spec()
        assert "pod_slow" in faults.spec()
        assert "net_corrupt" not in faults.spec()
        faults.reset()
        assert not netfaults.ACTIVE

    def test_tx_plan_windows(self):
        faults.configure("net_drop:nth=2")
        fb = _frame_of()
        assert netfaults.tx_plan(fb)[0] == [fb]      # 1st passes
        chunks, close, _ = netfaults.tx_plan(fb)     # 2nd dropped
        assert chunks == [] and close
        assert netfaults.tx_plan(fb)[0] == [fb]      # 3rd passes

    def test_tx_corrupt_is_crc_detectable(self):
        faults.configure("net_corrupt:nth=1")
        chunks, close, _ = netfaults.tx_plan(_frame_of(body=b"Z" * 64))
        assert not close and len(chunks) == 1
        with pytest.raises(wire.FrameCRCError):
            _read(chunks[0])

    def test_tx_truncate_cuts_mid_frame(self):
        faults.configure("net_truncate:nth=1,bytes=9")
        fb = _frame_of(body=b"Z" * 64)
        chunks, close, _ = netfaults.tx_plan(fb)
        assert close and chunks == [fb[:9]]
        with pytest.raises(wire.FrameTruncatedError):
            _read(chunks[0])

    def test_rx_hold_window(self):
        faults.configure("net_half_open:nth=2")
        assert not netfaults.rx_hold()
        assert netfaults.rx_hold()
        assert not netfaults.rx_hold()


class TestLoopback:
    def _pair(self, **kw):
        got = {}
        ev = threading.Event()

        def deliver(rid, payload, meta):
            got[rid] = payload
            ev.set()

        lis = wire.DataPlaneListener(deliver)
        kw.setdefault("attempt_timeout", 2.0)
        kw.setdefault("retries", 4)
        kw.setdefault("backoff", 0.02)
        snd = wire.FrameSender(lis.host, lis.port, link="t", **kw)
        return snd, lis, got, ev

    def _payload(self):
        return {"kv_k": [np.arange(64, dtype=np.float32).reshape(4, 16)],
                "key": np.array([9, 9], np.uint32), "cur_len": 5}

    @pytest.mark.parametrize("spec", [
        "", "net_corrupt:nth=2", "net_drop:nth=1", "net_truncate:nth=2",
        "net_dup:nth=1", "net_delay:delay=0.02,times=2",
        "net_half_open:nth=1"])
    def test_delivery_survives_every_fault(self, spec):
        snd, lis, got, ev = self._pair()
        try:
            if spec:
                faults.configure(spec)
            payload = self._payload()
            nbytes, attempts = snd.send_payload("r1", payload)
            assert ev.wait(10.0), spec
            assert nbytes > 0
            back = got["r1"]
            assert (back["kv_k"][0] == payload["kv_k"][0]).all()
            assert (back["key"] == payload["key"]).all()
            assert back["cur_len"] == 5
        finally:
            faults.reset()
            snd.close()
            lis.close()

    def test_budget_exhaustion_raises_not_fakes(self):
        # a dead destination: every attempt fails, DataPlaneError after
        # the bounded budget — the caller owns the fallback. The port is
        # HELD for the whole test by a socket that is bound and never
        # listens: a listener's port, once given back, goes to the next
        # port-0 bind of any process on the machine (another test
        # worker's pod listener took the payload and acknowledged it)
        dead = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        dead.bind(("127.0.0.1", 0))
        host, port = dead.getsockname()
        snd = wire.FrameSender(host, port, connect_timeout=0.2,
                               attempt_timeout=0.3, retries=1,
                               backoff=0.01)
        try:
            with pytest.raises(wire.DataPlaneError):
                snd.send_payload("r2", self._payload(), deadline=1.5)
        finally:
            snd.close()
            dead.close()

    def test_corrupt_frames_counted_never_decoded(self):
        before = dict(wire.stats())
        snd, lis, got, ev = self._pair()
        try:
            faults.configure("net_corrupt:nth=2")
            snd.send_payload("r3", self._payload())
            assert ev.wait(10.0)
            after = wire.stats()
            assert after["crc_errors"] > before.get("crc_errors", 0)
            assert after["nacks_sent"] > before.get("nacks_sent", 0)
            # the delivered payload is the RETRY's, bitwise intact
            assert (got["r3"]["kv_k"][0]
                    == self._payload()["kv_k"][0]).all()
        finally:
            faults.reset()
            snd.close()
            lis.close()


class TestStoreEndpoints:
    def _store(self):
        from paddle_tpu.distributed.store import TCPStore

        return TCPStore("127.0.0.1", 0, is_master=True)

    def test_publish_resolve_and_stale_rejection(self):
        from paddle_tpu.distributed.fleet.elastic import (
            publish_endpoint, resolve_endpoint)

        store = self._store()
        assert publish_endpoint(store, "3", "127.0.0.1", 5001,
                                generation=0, role="decode",
                                data_port=5002)
        doc = resolve_endpoint(store, "3")
        assert doc["port"] == 5001 and doc["data_port"] == 5002
        assert doc["generation"] == 0 and doc["role"] == "decode"
        # a reader demanding the NEXT generation refuses the stale record
        assert resolve_endpoint(store, "3", min_gen=1) is None
        # the respawned incarnation publishes gen 1 on a fresh port
        assert publish_endpoint(store, "3", "127.0.0.1", 6001,
                                generation=1, role="decode",
                                data_port=6002)
        doc = resolve_endpoint(store, "3", min_gen=1)
        assert doc["port"] == 6001 and doc["generation"] == 1
        # a zombie's late gen-0 publish must NOT clobber gen 1
        assert not publish_endpoint(store, "3", "127.0.0.1", 5001,
                                    generation=0)
        assert resolve_endpoint(store, "3")["port"] == 6001

    def test_resolve_missing_pod_times_out_none(self):
        from paddle_tpu.distributed.fleet.elastic import resolve_endpoint

        store = self._store()
        t0 = time.monotonic()
        assert resolve_endpoint(store, "99", timeout=0.2) is None
        assert time.monotonic() - t0 < 5.0


class TestAccelPinning:
    """ISSUE 19 satellite: accelerator fleets default to one pod per
    chip; explicit pinnings that collide on a device warn loudly."""

    def _fleet(self, **kw):
        from paddle_tpu.serving.fleet import ServingFleet

        kw.setdefault("pods", 3)
        return ServingFleet({"kind": "gpt", "seed": 0, "config": {}},
                            **kw)

    def test_tpu_fleet_defaults_one_pod_per_chip(self):
        fleet = self._fleet(platform="tpu")
        assert fleet.pod_env == {0: {"TPU_VISIBLE_DEVICES": "0"},
                                 1: {"TPU_VISIBLE_DEVICES": "1"},
                                 2: {"TPU_VISIBLE_DEVICES": "2"}}

    def test_cpu_fleet_untouched(self):
        assert not self._fleet(platform="cpu").pod_env

    def test_explicit_pinning_respected(self):
        env = {0: {"TPU_VISIBLE_DEVICES": "2"},
               1: {"TPU_VISIBLE_DEVICES": "1"},
               2: {"TPU_VISIBLE_DEVICES": "0"}}
        fleet = self._fleet(platform="tpu", pod_env=dict(env))
        assert fleet.pod_env == env

    def test_chip_contention_warns(self):
        with pytest.warns(RuntimeWarning, match="fight"):
            self._fleet(platform="gpu", pods=2,
                        pod_env={0: {"CUDA_VISIBLE_DEVICES": "0"},
                                 1: {"CUDA_VISIBLE_DEVICES": "0"}})

    def test_unpinned_pod_warns(self):
        with pytest.warns(RuntimeWarning, match="every chip"):
            self._fleet(platform="tpu", pods=2,
                        pod_env={0: {"TPU_VISIBLE_DEVICES": "0"}})


class _FlakyClient:
    """alive-but-lossy pod: the breaker's target. `losses` calls return
    None (lost reply), then it acks."""

    def __init__(self, losses=0):
        self.losses = losses
        self.alive = True
        self.calls = 0

    def call(self, msg, timeout=None):
        self.calls += 1
        if self.losses > 0:
            self.losses -= 1
            return None
        return {"op": "ack", "mid": msg.get("mid"), "queued": 0,
                "active": 0}

    def close(self):
        self.alive = False


class TestCircuitBreaker:
    def test_flapping_pod_degrades_to_held_never_errors(self):
        r = FleetRouter(policy="least_loaded", ack_timeout=0.2,
                        breaker_threshold=3, breaker_cooldown=0.2)
        flaky = _FlakyClient(losses=100)
        r.register_pod(0, flaky, role="serve")
        # three straight losses trip the breaker; every request is HELD
        # (zero caller-visible failures), and the open breaker stops
        # the router from even dialing the zombie
        reqs = [r.submit([1, 2, 3, 4], max_new_tokens=4)
                for _ in range(4)]
        assert r.held() == 4
        assert all(not q.done for q in reqs)
        assert r.stats()["pods"][0]["breaker_open"]
        calls_when_open = flaky.calls
        r.redistribute()   # breaker open: candidate set is empty
        assert flaky.calls == calls_when_open and r.held() == 4
        # pod recovers; after the cooldown the half-open probe succeeds
        # and the backlog replays
        flaky.losses = 0
        time.sleep(0.25)
        r.redistribute()
        assert r.held() == 0
        assert all(q.pod == 0 for q in reqs)
        assert not r.stats()["pods"][0]["breaker_open"]
        assert registry.counters("fleet")["breaker_trips"] >= 1

    def test_success_resets_streak(self):
        r = FleetRouter(policy="least_loaded", ack_timeout=0.2,
                        breaker_threshold=3, breaker_cooldown=5.0)
        flaky = _FlakyClient(losses=2)   # two losses, then ack
        r.register_pod(0, flaky, role="serve")
        req = r.submit([1, 2, 3, 4], max_new_tokens=4)
        r.redistribute()
        r.redistribute()
        assert req.pod == 0
        assert not r.stats()["pods"][0]["breaker_open"]
