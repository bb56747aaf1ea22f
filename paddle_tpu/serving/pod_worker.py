"""paddle_tpu.serving.pod_worker — one serving pod process.

Entry point for the serving-fleet pods (`ISSUE 11`): ``ServingFleet``
spawns ``python -m paddle_tpu.serving.pod_worker <spec.json>`` under the
launch stack's ``Pod`` supervision and talks to it over a line-JSON TCP
socket (``serving/router.PodClient`` is the other end). The spec carries
everything needed to rebuild the pod deterministically on a respawn:

.. code-block:: json

    {"model":  {"kind": "gpt", "seed": 21, "config": {"n_layer": 2}},
     "role":   "serve",              // or "prefill" / "decode"
     "engine": {"max_batch_size": 4, "rng_seed": 0, "block_size": 16},
     "server": {"max_queue_size": 16, "prefill_chunk_tokens": 64},
     "watch":  {"dir": "/ckpts/run0", "interval": 0.5},
     "draft":  {"kind": "gpt", "seed": 5, "config": {"n_layer": 1}},
     "draft_k": 4,
     "platform": "cpu",
     "env": {"TPU_VISIBLE_DEVICES": "0"}}

``draft`` (optional) builds a second model and promotes the engine to a
``DraftVerifyEngine`` (ISSUE 12 speculative decoding); ``env`` entries
land in ``os.environ`` before any jax import, so accelerator fleets run
one pod per chip by pinning per-pod visible devices.

``model`` is either the built-in ``gpt`` kind (seeded ``GPTConfig``
build — what tests/bench/smoke use) or ``{"factory": "pkg.mod:fn",
"kwargs": {...}}`` for arbitrary models. The engine's ``rng_seed``
defaults to 0 so a respawned pod — or a DIFFERENT pod replaying a dead
sibling's requests — regenerates bitwise-identical tokens (the
supervisor replay contract from ISSUE 7, now across processes).

Roles: ``serve`` (monolithic: scheduler + decode loop), ``decode``
(same engine, additionally adopts handed-off KV payloads), ``prefill``
(no decode loop: runs prompt prefills and exports the KV blocks +
first token for a decode pod to adopt).

Death protocol: a ``FatalEngineError`` (device loss, ``replica_kill``
injection) exits the process with rc 17; ``pod_kill`` injection
SIGKILL-exits with rc 137 mid-handler. Either way the fleet supervisor
respawns the pod with backoff and the router replays its orphans. The
socket is bound only AFTER the engine is built, so the router's
connect-retry doubles as the readiness probe.

Endpoints + data plane (ISSUE 19): when the fleet hands the pod a
rendezvous store (``PADDLE_STORE_HOST``/``PADDLE_STORE_PORT``), the pod
PUBLISHES its control endpoint — and, for adopting roles, its binary
data-plane listener port — through ``elastic.publish_endpoint`` under
generation = ``PADDLE_RESTART_COUNT``, instead of relying on a shared
filesystem; the port file is still written when asked (debugging, the
storeless fallback). Prefill pods receiving a ``handoff`` target
resolve the decode pod's data endpoint through the store
(stale generations rejected) and stream the KV bundle DIRECTLY to it
over ``serving/wire.py`` frames; the decode pod stashes delivered
bundles by rid until the router's ``adopt {remote: true}`` claims them.
"""
from __future__ import annotations

import json
import os
import socket
import sys
import threading


def _build_model(spec):
    kind = spec.get("kind", "gpt")
    if "factory" in spec:
        import importlib

        mod, _, fn = spec["factory"].partition(":")
        return getattr(importlib.import_module(mod), fn)(
            **(spec.get("kwargs") or {}))
    if kind != "gpt":
        raise ValueError(
            f"pod model spec kind {kind!r}: the built-in builder makes GPT "
            "models only ({'kind': 'gpt', 'seed': s, 'config': {GPTConfig "
            "keys}}); give any other decoder the engine serves (one that "
            "answers kv_cache_spec / serving_head / max_positions) as "
            "{'factory': 'module:function', 'kwargs': {...}}")
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTModel)

    paddle.seed(int(spec.get("seed", 0)))
    cfg = GPTConfig(**(spec.get("config") or {}))
    return GPTForPretraining(GPTModel(cfg))


class _PrefillSwapShim:
    """Duck-typed ``GenerationServer`` stand-in so ``CheckpointFollower``
    can drive a scheduler-less prefill pod: swaps apply immediately
    between prefills (the op handler holds the engine lock)."""

    class _Sched:
        def __init__(self):
            self.swap_count = 0
            self.last_swap_error = None

    def __init__(self, engine, lock):
        self.engine = engine
        self._lock = lock
        self.scheduler = self._Sched()
        self.last_swap_step = -1

    def swap_weights(self, state, source=None):
        with self._lock:
            try:
                self.engine.swap_weights(state, source=source)
                self.scheduler.swap_count += 1
                self.scheduler.last_swap_error = None
            except Exception as e:
                self.scheduler.last_swap_error = e


class PodWorker:
    def __init__(self, spec):
        from paddle_tpu.profiler import registry as _registry
        from paddle_tpu.profiler import tracing as _tracing
        from paddle_tpu.serving.engine import GenerationEngine
        from paddle_tpu.serving.server import (CheckpointFollower,
                                               GenerationServer)
        from paddle_tpu.testing import faults as _faults

        self._registry = _registry
        self._tracing = _tracing
        self._faults = _faults
        self.spec = spec
        self.role = spec.get("role", "serve")
        self.pod_id = os.environ.get("PADDLE_POD_ID", "0")
        # a respawned pod disarms its LETHAL one-shot faults: the env
        # spec re-arms with a reset count on every restart, so a pod
        # that already died once would re-kill itself on the replayed
        # requests and crash-loop through its whole restart budget.
        # (Arm "persist=1" on the point to opt out — e.g. a scenario
        # that wants to exhaust max_restarts.)
        if int(os.environ.get("PADDLE_RESTART_COUNT", "0") or 0) > 0:
            table = _faults.spec()
            lethal = [p for p in ("pod_kill", "replica_kill")
                      if p in table and not table[p].get("persist")]
            if lethal:
                for p in lethal:
                    del table[p]
                _faults.configure(table)
        model = _build_model(spec.get("model") or {})
        ekw = dict(spec.get("engine") or {})
        ekw.setdefault("rng_seed", 0)
        draft_spec = spec.get("draft")
        if draft_spec:
            # speculative-decode pod (ISSUE 12): a second, smaller model
            # spec builds the drafter; the engine becomes draft-verify.
            # Built AFTER the target so the target's seeded init draws
            # are identical with or without a drafter.
            from paddle_tpu.serving.spec_decode import DraftVerifyEngine

            draft_model = _build_model(draft_spec)
            self.engine = DraftVerifyEngine(
                model, draft_model,
                draft_k=int(spec.get("draft_k", 4)), **ekw)
        else:
            self.engine = GenerationEngine(model, **ekw)
        self.lock = threading.Lock()  # engine ops for scheduler-less roles
        self._reqs: dict = {}         # wire rid -> GenerationRequest
        self._rlock = threading.Lock()
        if self.role == "prefill":
            self.server = None
            self._swap_owner = _PrefillSwapShim(self.engine, self.lock)
        else:
            self.server = GenerationServer(
                engine=self.engine, fail_fast_on_fatal=False,
                **(spec.get("server") or {})).start()
            self._swap_owner = self.server
            watch = spec.get("watch")
            if watch:
                self.server.watch_checkpoints(
                    watch["dir"], interval=float(watch.get("interval",
                                                           0.5)))
        self._followers: dict = {}
        self._CheckpointFollower = CheckpointFollower
        # ---- fleet data plane (ISSUE 19) --------------------------------
        from paddle_tpu.serving import wire as _wire

        self._wire = _wire
        self.generation = int(os.environ.get("PADDLE_RESTART_COUNT",
                                             "0") or 0)
        self.host = os.environ.get("PADDLE_POD_HOST", "127.0.0.1")
        self.wire_kwargs = dict(spec.get("wire") or {})
        self.store = None
        sh = os.environ.get("PADDLE_STORE_HOST")
        sp = os.environ.get("PADDLE_STORE_PORT")
        if sh and sp:
            try:
                from paddle_tpu.distributed.store import TCPStore

                self.store = TCPStore(sh, int(sp), is_master=False)
            except Exception as e:
                # store down at boot: the pod still serves (port-file /
                # direct-connect fallback); endpoint publication and the
                # binary handoff degrade, requests do not
                print(f"pod {self.pod_id}: store unreachable ({e}); "
                      "serving without endpoint publication",
                      file=sys.stderr)
        # adopting roles run a data-plane listener: prefill pods stream
        # KV bundles straight at it, `adopt {remote: true}` claims them
        self._stash: dict = {}       # rid -> delivered payload dict
        self._stash_lock = threading.Lock()
        self._senders: dict = {}     # target pod id -> FrameSender
        self._senders_lock = threading.Lock()
        self.data_plane = None
        if self.role != "prefill":
            self.data_plane = _wire.DataPlaneListener(
                self._stash_payload, host=self.host)

    def _stash_payload(self, rid, payload, meta):
        """DataPlaneListener delivery callback (connection thread):
        park the verified bundle until the router's adopt claims it.
        Idempotent by rid — a resent bundle overwrites its twin. The
        stash is bounded: under a router that never adopts (died between
        handoff and adopt), oldest-first eviction keeps the pod's memory
        flat and the re-routed request simply re-prefills."""
        with self._stash_lock:
            while len(self._stash) >= 64:
                self._stash.pop(next(iter(self._stash)))
            self._stash[str(rid)] = payload

    # ------------------------------------------------------------ serving --
    def run(self):
        # bind port 0 and PUBLISH the kernel-assigned port through the
        # port file (tmp+rename, atomic): a parent-preallocated "free"
        # port races the whole world between probe and bind — under a
        # loaded test suite the kernel handed the probed port to another
        # socket and the pod died EADDRINUSE while the router connected
        # to the impostor. An explicit PADDLE_POD_PORT > 0 still wins
        # (manual runs).
        port = int(os.environ.get("PADDLE_POD_PORT", "0") or 0)
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((self.host, port))
        srv.listen(4)
        port_file = os.environ.get("PADDLE_POD_PORT_FILE")
        if port_file:
            tmp = f"{port_file}.tmp"
            with open(tmp, "w") as f:
                f.write(str(srv.getsockname()[1]))
            os.replace(tmp, port_file)
        # publish the endpoint through the store (ISSUE 19): the router
        # resolves host:port from here — no shared filesystem needed —
        # and the generation (= restart count) lets it reject this
        # pod's DEAD incarnations after a respawn
        if self.store is not None:
            from paddle_tpu.distributed.fleet.elastic import \
                publish_endpoint

            publish_endpoint(
                self.store, self.pod_id, host=self.host,
                port=srv.getsockname()[1], generation=self.generation,
                role=self.role,
                data_port=self.data_plane.port if self.data_plane
                else 0)
        threading.Thread(target=self._fatal_watchdog, daemon=True,
                         name="paddle-tpu-pod-fatal").start()
        while True:
            conn, _ = srv.accept()
            # acks/dones are small JSON lines; without NODELAY Nagle +
            # delayed-ACK adds ~40ms to every router round trip
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            try:
                self._serve_conn(conn)
            except (OSError, ValueError):
                pass
            finally:
                try:
                    conn.close()
                except OSError:
                    pass

    def _fatal_watchdog(self):
        """A fatally-dead engine means this POD is dead: exit so the
        fleet supervisor respawns the process and the router replays the
        orphans (the cross-process analogue of ReplicaSupervisor's
        fatal_error poll)."""
        import time

        while True:
            if self.server is not None \
                    and self.server.fatal_error is not None:
                self._tracing.dump_flight_recorder(
                    reason=f"pod fatal: {self.server.fatal_error}")
                os._exit(17)
            time.sleep(0.02)

    def _serve_conn(self, conn):
        wlock = threading.Lock()

        def send(obj):
            data = (json.dumps(obj) + "\n").encode("utf-8")
            try:
                with wlock:
                    conn.sendall(data)
            except OSError:
                pass  # router went away; the fleet will reconnect or die

        f = conn.makefile("r", encoding="utf-8")
        for line in f:
            try:
                msg = json.loads(line)
            except ValueError:
                continue
            op = msg.get("op")
            handler = getattr(self, f"_op_{op}", None)
            if handler is None:
                send({"op": "error", "mid": msg.get("mid"),
                      "error": f"unknown op {op!r}"})
                continue
            try:
                handler(msg, send)
            except SystemExit:
                raise
            except Exception as e:
                from paddle_tpu.serving.engine import FatalEngineError

                if isinstance(e, FatalEngineError):
                    self._tracing.dump_flight_recorder(
                        reason=f"fatal in op {op!r}: {e}")
                    os._exit(17)
                send({"op": "error", "mid": msg.get("mid"),
                      "error": f"{type(e).__name__}: {e}"})

    # ----------------------------------------------------------- handlers --
    @staticmethod
    def _options(msg):
        allowed = ("max_new_tokens", "eos_id", "temperature", "top_k",
                   "top_p", "seed", "timeout_s")
        return {k: v for k, v in (msg.get("options") or {}).items()
                if k in allowed}

    def _op_ping(self, msg, send):
        send({"op": "pong", "mid": msg["mid"], "role": self.role,
              "pod": self.pod_id})

    def _op_submit(self, msg, send):
        from paddle_tpu.serving.scheduler import (GenerationRequest,
                                                  QueueFullError)

        if self._faults.ACTIVE:
            self._faults.fire("pod_kill")
        if self.server is None:
            send({"op": "reject", "mid": msg["mid"],
                  "reason": f"role {self.role} does not serve requests"})
            return
        rid = msg["rid"]
        with self._rlock:
            known = self._reqs.get(rid)
        if known is not None:
            # duplicate submit (the ack was lost, not the message):
            # idempotent re-ack instead of double-enqueueing
            send(self._ack(msg["mid"]))
            return
        req = GenerationRequest(msg["prompt"], **self._options(msg))
        req.trace_id = msg.get("trace")
        try:
            self.server.submit_request(req)
        except (QueueFullError, RuntimeError) as e:
            send({"op": "reject", "mid": msg["mid"], "reason": str(e)})
            return
        with self._rlock:
            self._reqs[rid] = req
        send(self._ack(msg["mid"]))
        threading.Thread(target=self._report, args=(send, rid, req),
                         daemon=True).start()

    def _op_adopt(self, msg, send):
        """Disaggregated decode side: admit a request whose prompt KV a
        prefill pod already computed — the payload rides the scheduler's
        admission queue and is imported at the slot instead of
        prefilled."""
        from paddle_tpu.serving.router import unpack_payload
        from paddle_tpu.serving.scheduler import (GenerationRequest,
                                                  QueueFullError)

        if self._faults.ACTIVE:
            self._faults.fire("pod_kill")
        if self.server is None:
            send({"op": "reject", "mid": msg["mid"],
                  "reason": f"role {self.role} cannot adopt"})
            return
        rid = msg["rid"]
        with self._rlock:
            known = self._reqs.get(rid)
        if known is not None:
            send(self._ack(msg["mid"]))
            return
        req = GenerationRequest(msg["prompt"], **self._options(msg))
        req.trace_id = msg.get("trace")
        if msg.get("remote"):
            # binary transport: the payload arrived pod-to-pod over the
            # data plane and is waiting in the stash. Missing means the
            # delivered incarnation died (stash is process memory) — an
            # explicit nak, which the router treats as loss (re-runs the
            # pipeline), NOT as backpressure.
            with self._stash_lock:
                payload = self._stash.pop(str(msg["rid"]), None)
            if payload is None:
                send({"op": "nak", "mid": msg["mid"],
                      "reason": "no stashed payload for rid "
                                f"{msg['rid']} (delivered bundle lost "
                                "across a respawn?)"})
                return
            req.kv_payload = payload
        else:
            req.kv_payload = unpack_payload(msg["payload"])
        try:
            self.server.submit_request(req)
        except (QueueFullError, RuntimeError) as e:
            send({"op": "reject", "mid": msg["mid"], "reason": str(e)})
            return
        with self._rlock:
            self._reqs[rid] = req
        send(self._ack(msg["mid"]))
        threading.Thread(target=self._report, args=(send, rid, req),
                         daemon=True).start()

    def _op_prefill(self, msg, send):
        """Disaggregated prefill side: run the prompt, export the KV
        blocks + first token, release the slot (the prefix cache keeps
        the full prompt blocks for the next shared-prefix request).

        The engine work runs on a SIDE thread (serialized by the engine
        lock) so the connection's handler loop keeps reading: a router
        can keep many prefill requests in flight on ONE connection —
        mid-matched replies land whenever each prefill finishes — instead
        of one request per round-trip (the PR 10 residual)."""
        if self._faults.ACTIVE:
            self._faults.fire("pod_kill")
        threading.Thread(target=self._do_prefill, args=(msg, send),
                         daemon=True,
                         name="paddle-tpu-pod-prefill").start()

    def _do_prefill(self, msg, send):
        from paddle_tpu.serving.block_pool import PagePoolExhausted
        from paddle_tpu.serving.engine import FatalEngineError
        from paddle_tpu.serving.router import pack_payload

        opts = self._options(msg)
        try:
            with self.lock:
                free = self.engine.free_slots()
                if not free:
                    raise PagePoolExhausted("no free prefill slot")
                slot = free[0]
                first = self.engine.prefill(
                    slot, msg["prompt"],
                    temperature=float(opts.get("temperature", 0.0)),
                    top_k=int(opts.get("top_k", 0)),
                    top_p=float(opts.get("top_p", 1.0)),
                    seed=opts.get("seed"),
                    max_new_tokens=opts.get("max_new_tokens"))
                payload = self.engine.export_request_kv(slot)
                self.engine.release(slot)
        except PagePoolExhausted as e:
            send({"op": "reject", "mid": msg["mid"], "reason": str(e)})
            return
        except FatalEngineError as e:
            self._tracing.dump_flight_recorder(
                reason=f"fatal in prefill: {e}")
            os._exit(17)
        except Exception as e:
            # off the handler loop now: this thread owns its own error
            # reply (the _serve_conn catch-all can't see it)
            send({"op": "error", "mid": msg["mid"],
                  "error": f"{type(e).__name__}: {e}"})
            return
        handoff = msg.get("handoff")
        if handoff and self.store is not None:
            try:
                nbytes, attempts = self._push_payload(
                    msg["rid"], payload, handoff, msg.get("trace"))
                send({"op": "prefill_done", "mid": msg["mid"],
                      "first": first, "delivered": True,
                      "bytes": nbytes, "attempts": attempts})
                return
            except Exception as e:
                # data plane exhausted its retry budget (or the target
                # endpoint never resolved): DEGRADE to the inline JSON
                # payload — delivery falls back, the request never fails
                self._registry.inc("fallbacks", scope="wire")
                from paddle_tpu.profiler import explainer as _explain

                _explain.record(
                    "handoff_fallback", op="data_plane",
                    why=f"binary handoff for rid {msg['rid']} failed "
                        f"({type(e).__name__}: {e}); payload riding the "
                        "control plane inline instead",
                    rid=msg["rid"])
        send({"op": "prefill_done", "mid": msg["mid"], "first": first,
              "payload": pack_payload(payload), "delivered": False})

    def _push_payload(self, rid, payload, handoff, trace):
        """Stream one KV bundle straight to the decode pod named in
        ``handoff``: resolve its data-plane endpoint through the store
        (generations below ``min_gen`` — dead incarnations — rejected),
        then frame it over the pooled per-target FrameSender. Returns
        (bytes, attempts); raises DataPlaneError past the retry
        budget."""
        from paddle_tpu.distributed.fleet.elastic import resolve_endpoint

        target = str(handoff["pod"])
        min_gen = int(handoff.get("min_gen", 0))
        doc = resolve_endpoint(self.store, target, min_gen=min_gen,
                               timeout=5.0)
        if not doc or not doc.get("data_port"):
            raise self._wire.DataPlaneError(
                f"no data-plane endpoint for pod {target} at gen >= "
                f"{min_gen}")
        host, dport = doc.get("host", "127.0.0.1"), int(doc["data_port"])
        with self._senders_lock:
            snd = self._senders.get(target)
            if snd is None:
                snd = self._senders[target] = self._wire.FrameSender(
                    host, dport, link=f"pod{self.pod_id}->pod{target}",
                    **self.wire_kwargs)
            else:
                # a respawned target published a fresh port: retarget
                snd.retarget(host, dport)
        return snd.send_payload(str(rid), payload, trace=trace)

    def _op_swap(self, msg, send):
        """Fleet-wide weight swap: reuse the checkpoint watcher's
        follower (file-set-change dedup — a torn checkpoint is attempted
        once, not per retry) to load + stage; the scheduler applies at
        its decode-step boundary. The load + wait-applied runs on a side
        thread: blocking the pod's ONE request-handler thread for the
        swap timeout would stall submit acks past the router's
        ack_timeout and double-run traffic on another pod."""
        d = msg["dir"]
        if self.server is not None:
            follower = self.server.checkpoint_follower(d)
        else:
            follower = self._followers.get(d)
            if follower is None:
                follower = self._followers[d] = \
                    self._CheckpointFollower(self._swap_owner, d)

        def _swap():
            try:
                follower.poll(wait_applied=float(msg.get("timeout",
                                                         30.0)))
            except Exception as e:
                send({"op": "error", "mid": msg["mid"],
                      "error": f"{type(e).__name__}: {e}"})
                return
            owner = self._swap_owner
            err = owner.scheduler.last_swap_error
            c = self._registry.counters("serving")
            send({"op": "swap_done", "mid": msg["mid"],
                  "applied_step": owner.last_swap_step,
                  "swap_count": owner.scheduler.swap_count,
                  "swap_error": repr(err) if err is not None else None,
                  "decode_compiles": c["decode_compiles"]})

        threading.Thread(target=_swap, daemon=True,
                         name="paddle-tpu-pod-swap").start()

    def _op_stats(self, msg, send):
        c = self._registry.counters("serving")
        fatal = self.server is not None \
            and self.server.fatal_error is not None
        send({"op": "stats_reply", "mid": msg["mid"], "role": self.role,
              "pod": self.pod_id,
              "restarts": int(os.environ.get("PADDLE_RESTART_COUNT",
                                             "0") or 0),
              "queued": self.server.scheduler.queued()
              if self.server else 0,
              "active": self.server.scheduler.active()
              if self.server else 0,
              "fatal": bool(fatal),
              "occupancy": self.engine.mean_occupancy(),
              "prefix_hits": c["prefix_hits"],
              "prefix_misses": c["prefix_misses"],
              "prefix_hit_tokens": c["prefix_hit_tokens"],
              "decode_compiles": c["decode_compiles"],
              "prefill_compiles": c["prefill_compiles"],
              "requests_failed": c["requests_failed"],
              "weight_swaps": c["weight_swaps"],
              "handoff_exports": c["handoff_exports"],
              "handoff_imports": c["handoff_imports"],
              "kv_blocks_in_use": self.engine.pool.in_use(),
              # the host's part of an iteration and of an admission (ms,
              # means since start), and the collector's pauses
              **self.engine.host_means(),
              "host": self._registry.counters("host"),
              "swap_count": self._swap_owner.scheduler.swap_count,
              "generation": self.generation,
              # data-plane wire counters + per-link byte/retry table:
              # fleet.stats() sums these across pods
              "data_plane": self._wire.stats(),
              "links": self._wire.link_stats(),
              "timings": {k: {"count": v.get("count"),
                              "mean_ms": v.get("mean_ms")}
                          for k, v in
                          self._registry.timings("serving").items()},
              "hists": self._registry.histograms("serving"),
              "spans": self._tracing.drain_spans(),
              "spans_dropped": self._tracing.spans_dropped(),
              "clock_anchor": self._tracing.clock_anchor(),
              # sampled as late as possible: the router midpoints its
              # send/recv stamps against this for the clock offset
              "mono_now": self._tracing.clock()})

    def _op_logs(self, msg, send):
        """Ship the tail of this pod's log OVER THE WIRE: with
        store-published endpoints a pod may live on a host the router
        has no filesystem view of, so log collection rides the control
        socket like everything else."""
        path = os.environ.get("PADDLE_POD_LOG")
        text = ""
        if path and os.path.exists(path):
            try:
                with open(path, "rb") as f:
                    f.seek(0, os.SEEK_END)
                    size = f.tell()
                    f.seek(max(0, size - 65536))
                    text = f.read().decode("utf-8", "replace")
            except OSError:
                text = ""
        lines = text.splitlines()[-int(msg.get("tail", 200)):]
        send({"op": "logs_reply", "mid": msg["mid"], "pod": self.pod_id,
              "generation": self.generation, "path": path,
              "lines": lines})

    def _op_flight(self, msg, send):
        """On-demand flight-recorder dump from a LIVE pod: write the
        ring to the fleet log dir (the same place a dying pod leaves
        it) and reply with the path — chaos drills get a parseable
        post-mortem without having to kill anything."""
        path = self._tracing.dump_flight_recorder(
            reason=str(msg.get("reason") or "requested"))
        send({"op": "flight_done", "mid": msg["mid"],
              "pod": self.pod_id, "path": path})

    def _op_drain(self, msg, send):
        """Graceful retirement: finish every queued + in-flight request,
        confirm, exit 0 (the fleet supervisor treats rc 0 as a clean
        exit, not a death)."""
        if self.server is not None:
            self.server.shutdown(drain=True,
                                 timeout=float(msg.get("timeout", 60.0)))
        send({"op": "drain_done", "mid": msg["mid"],
              "spans": self._tracing.drain_spans(),
              "clock_anchor": self._tracing.clock_anchor(),
              "mono_now": self._tracing.clock()})
        os._exit(0)

    # ------------------------------------------------------------ helpers --
    def _ack(self, mid):
        return {"op": "ack", "mid": mid,
                "queued": self.server.scheduler.queued(),
                "active": self.server.scheduler.active()}

    def _report(self, send, rid, req):
        req.finished.wait()
        send({"op": "done", "rid": rid, "status": req.status,
              "tokens": [int(t) for t in req.tokens],
              "stop_reason": req.stop_reason, "error": req.error,
              "queued": self.server.scheduler.queued(),
              "active": self.server.scheduler.active()})
        # the dedup entry has done its job (ack-loss resends arrive
        # before completion); dropping it bounds the map — a duplicate
        # arriving AFTER the done would re-run, and the router's
        # first-wins completion makes that harmless
        with self._rlock:
            self._reqs.pop(rid, None)


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if len(argv) != 1:
        print("usage: python -m paddle_tpu.serving.pod_worker spec.json",
              file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        spec = json.load(f)
    # per-pod env overrides (ISSUE 12 satellite): applied BEFORE any
    # jax import so accelerator fleets can pin one pod per chip
    # (JAX_PLATFORMS, TPU_VISIBLE_DEVICES / CUDA_VISIBLE_DEVICES, ...).
    # Spec env wins over inherited env; `platform` is the shorthand for
    # JAX_PLATFORMS and loses to an explicit env entry.
    if spec.get("platform"):
        os.environ.setdefault("JAX_PLATFORMS", spec["platform"])
    for k, v in (spec.get("env") or {}).items():
        os.environ[str(k)] = str(v)
    worker = PodWorker(spec)
    worker.run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
