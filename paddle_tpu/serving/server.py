"""paddle_tpu.serving.server — threaded frontend over the batch scheduler.

``GenerationServer`` owns the single thread that drives
``ContinuousBatchScheduler.step()`` (the engine is not thread-safe; the
server is the one consumer). Frontends interact only through thread-safe
surfaces:

* ``submit()`` — enqueue and return a ``GenerationRequest`` handle
  immediately; raises ``QueueFullError`` the instant the admission queue
  is at capacity (fast-fail backpressure, nothing blocks the decode loop);
* ``result(req)`` / ``req.result()`` — block until the request is
  terminal;
* ``generate()`` — submit + wait, returning the token ids;
* per-request ``timeout_s`` deadlines cover queue wait AND generation.

Shutdown follows the fault-tolerance stack's SIGTERM convention
(incubate/checkpoint.py): a signal handler only sets a flag; the worker
loop observes it at the next iteration boundary and drains — stops
admitting, finishes every queued and in-flight request, then exits. The
same drain runs on ``shutdown()`` (graceful default) so a preempted
serving task hands back complete responses instead of torn ones;
``shutdown(drain=False)`` fails pending work fast instead.
"""
from __future__ import annotations

import os
import signal
import threading
import time
import zlib

from ..profiler import explainer as _explain
from ..profiler import span as _span
from ..profiler import spans as _spans
from ..profiler import tracing as _tracing
from .engine import FatalEngineError, GenerationEngine
from .scheduler import (ContinuousBatchScheduler, GenerationRequest,
                        QueueFullError, RequestStatus)


def pod_jitter_fraction(ident=None):
    """Deterministic per-pod fraction in [0, 1) for de-phasing periodic
    work (checkpoint-dir polling) across a serving fleet: N pods tailing
    ONE checkpoint directory must not hit the manifest read in lockstep
    every interval. Derived from the pod's identity env
    (``PADDLE_POD_ID``, falling back to ``PADDLE_TRAINER_ID``) so the
    schedule is reproducible run to run — a thundering herd fixed by
    random jitter would come back in every bug report replay."""
    if ident is None:
        ident = os.environ.get("PADDLE_POD_ID") \
            or os.environ.get("PADDLE_TRAINER_ID") or "0"
    return (zlib.crc32(str(ident).encode()) % 1000) / 1000.0


class CheckpointFollower:
    """One checkpoint-directory tail: the poll step of
    ``GenerationServer.watch_checkpoints``, factored out so the fleet
    swap path (``pod_worker``'s ``swap`` op) reuses the SAME
    file-set-change dedup — a torn or late-arriving multi-rank
    checkpoint is attempted once per distinct (step, file set), never
    re-unpickled in a hot loop per pod, while a new shard landing
    (file-set change) re-attempts automatically.

    ``owner`` duck-types ``GenerationServer``: ``swap_weights(state,
    source)`` staging, ``scheduler.swap_count`` / ``last_swap_error``,
    and a mutable ``last_swap_step`` (advanced HERE only once a swap is
    APPLIED — a refused swap must not report success, and stays
    re-attemptable when the checkpoint dir changes)."""

    def __init__(self, owner, ckpt_dir):
        self.owner = owner
        self.ckpt_dir = str(ckpt_dir)
        # (step, file set) of the newest attempted checkpoint — the
        # watcher dedup that keeps a torn payload from being re-read
        # every tick while a late-arriving shard still re-attempts
        self._attempted = (-1, ())
        # the follower is deliberately SHARED (watcher thread + fleet
        # swap ops): serialize polls, or two concurrent callers would
        # both pass the dedup and both re-unpickle the checkpoint —
        # the exact work the dedup exists to prevent
        self._lock = threading.Lock()

    def poll(self, wait_applied=30.0, stop_event=None):
        """Check the directory once; when a newer VALID checkpoint has
        committed, stage a weight swap and wait (bounded) for the
        scheduler to apply it. Returns the applied step, or None (no
        news, torn payload, refused swap, or still pending). Thread-
        safe: concurrent polls serialize, the loser re-checks the dedup
        and returns without re-reading."""
        with self._lock:
            return self._poll(wait_applied, stop_event)

    def _poll(self, wait_applied, stop_event):
        from ..incubate import checkpoint as _ckpt

        step = _ckpt.latest_step(self.ckpt_dir)
        if step is None or step <= self.owner.last_swap_step:
            return None
        d = os.path.join(self.ckpt_dir, f"ckpt-{step:08d}")
        try:
            probe = (step, tuple(sorted(os.listdir(d))))
        except OSError:
            probe = (step, ())
        if probe == self._attempted:
            return None
        self._attempted = probe
        state, man = _ckpt.load_resharded(self.ckpt_dir, world_size=1)
        if state is None or int(man["step"]) <= self.owner.last_swap_step:
            return None
        model_state = state.get("model", state) \
            if isinstance(state, dict) else state
        got = int(man["step"])
        c0 = self.owner.scheduler.swap_count
        e0 = self.owner.scheduler.last_swap_error
        self.owner.swap_weights(
            model_state, source=f"{self.ckpt_dir}/ckpt-{got:08d}")
        waited = 0.0
        while waited < float(wait_applied) \
                and not (stop_event is not None and stop_event.is_set()):
            if self.owner.scheduler.swap_count > c0:
                self.owner.last_swap_step = got
                return got
            err = self.owner.scheduler.last_swap_error
            if err is not None and err is not e0:
                return None  # refused; the explainer ring has why
            time.sleep(0.02)
            waited += 0.02
        return None


class GenerationServer:
    def __init__(self, model=None, engine=None, max_batch_size=4,
                 buckets=None, max_seq_len=None, max_queue_size=16,
                 idle_wait_s=0.005, fail_fast_on_fatal=True,
                 block_size=16, num_blocks=None, mesh=None,
                 draft_model=None, draft_k=4, prefill_chunk_tokens=None,
                 paged_kernel=None):
        if engine is None:
            if model is None:
                raise ValueError("GenerationServer needs a model or an "
                                 "engine")
            ekw = dict(max_batch_size=max_batch_size, buckets=buckets,
                       max_seq_len=max_seq_len, block_size=block_size,
                       num_blocks=num_blocks, mesh=mesh,
                       paged_kernel=paged_kernel)
            if draft_model is not None:
                # speculative decoding (ISSUE 12): a small drafter
                # proposes draft_k tokens per iteration, the target
                # verifies them in one fixed-shape forward — bitwise-
                # equal tokens, fewer target forwards per token
                from .spec_decode import DraftVerifyEngine

                engine = DraftVerifyEngine(model, draft_model,
                                           draft_k=draft_k, **ekw)
            else:
                engine = GenerationEngine(model, **ekw)
        self.engine = engine
        self.scheduler = ContinuousBatchScheduler(
            engine, max_queue_size=max_queue_size,
            prefill_chunk_tokens=prefill_chunk_tokens)
        self._idle_wait_s = float(idle_wait_s)
        self._work = threading.Condition()
        self._stop = threading.Event()      # hard stop at next boundary
        self._draining = threading.Event()  # graceful: finish, then stop
        self._thread = None
        self._old_sigterm = None
        # FatalEngineError handling: standalone servers fail pending work
        # fast (callers must not wedge); a ReplicaSupervisor sets
        # fail_fast_on_fatal=False so it can take over the UN-finished
        # requests and replay them on a restarted replica
        self._fail_fast_on_fatal = bool(fail_fast_on_fatal)
        self._fatal = None
        # checkpoint watcher (train→serve loop); followers are cached
        # per directory so the watcher loop AND the fleet swap path
        # share one file-set-change dedup state per checkpoint dir
        self._watcher = None
        self._watch_stop = None
        self._followers: dict = {}
        self.last_swap_step = -1

    # ----------------------------------------------------------- control --
    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return self
        if self._stop.is_set() or self._draining.is_set():
            raise RuntimeError("server was shut down; build a new one")
        self._thread = threading.Thread(
            target=self._loop, name="paddle-tpu-serving", daemon=True)
        self._thread.start()
        return self

    def _loop(self):
        # the collector's pauses hold this thread whichever thread
        # collects: `host.gc` spans and counters while a worker runs
        _spans.watch_gc()
        try:
            self._serve()
        finally:
            _spans.unwatch_gc()

    def _serve(self):
        while not self._stop.is_set():
            if self.scheduler.has_work():
                try:
                    self.scheduler.step()
                except FatalEngineError as e:
                    # replica death: stop driving the engine. Requests
                    # stay UN-finished when a supervisor owns this server
                    # (it takes them over and replays them); standalone,
                    # fail them so result() callers don't wedge.
                    self._fatal = e
                    self.scheduler.close()
                    _explain.record(
                        "serving_replica_fatal", op="serve_loop",
                        why=f"engine died fatally ({e}); worker loop "
                            "exiting — supervisor restart / takeover "
                            "required",
                        error=str(e))
                    # flight recorder: the last N request lifecycle
                    # events, dumped next to whatever kills the process
                    # (post-mortem: what was this replica serving?)
                    _tracing.flight("fatal", error=str(e))
                    _tracing.dump_flight_recorder(
                        reason=f"fatal_engine_error: {e}")
                    if self._fail_fast_on_fatal:
                        self.scheduler.cancel_pending(
                            reason=f"fatal engine error: {e}")
                    break
                except Exception as e:  # fail loudly, don't wedge callers
                    self.scheduler.fail_all(e)
                continue
            if self._draining.is_set():
                break
            # idle = no decode in flight: a staged swap applies here too,
            # so following a checkpoint dir doesn't wait for traffic
            self.scheduler._apply_pending_swap()
            with _span("serving.loop_idle"), self._work:
                self._work.wait(self._idle_wait_s)

    @property
    def fatal_error(self):
        """The FatalEngineError that killed this server's worker, or
        None while healthy. Supervisors poll this."""
        return self._fatal

    def request_drain(self):
        """Signal-safe graceful-drain trigger: sets flags only (the
        CheckpointHook SIGTERM convention); the worker loop notices at its
        next iteration boundary, finishes all queued + in-flight requests,
        and exits."""
        self.scheduler.close()
        self._draining.set()

    def install_sigterm_handler(self):
        """Route SIGTERM (TPU preemption grace) to request_drain(). Call
        from the main thread; restored by shutdown()."""
        self._old_sigterm = signal.signal(
            signal.SIGTERM, lambda signum, frame: self.request_drain())
        return self

    # ------------------------------------------------- train→serve loop --
    def swap_weights(self, state, source=None):
        """Stage a drain-free weight hot-swap: thread-safe, returns
        immediately. The scheduler applies it between decode steps —
        in-flight requests keep their KV cache and finish on consistent
        weights (old until the boundary, new after); an aval/placement
        mismatch is refused loudly (``serving.swap_failures`` +
        ``serving_swap_failed`` explainer event) and the old weights keep
        serving. Zero requests fail or stall across a swap."""
        self.scheduler.request_swap(state, source=source)
        with self._work:
            self._work.notify()

    def checkpoint_follower(self, ckpt_dir):
        """The (cached) ``CheckpointFollower`` for ``ckpt_dir``. One
        follower per directory per server, shared by ``watch_checkpoints``
        and the fleet swap path, so both reuse one file-set-change dedup
        state — a fleet-wide swap retry against a torn checkpoint is a
        no-op until the directory actually changes."""
        key = str(ckpt_dir)
        f = self._followers.get(key)
        if f is None:
            f = self._followers[key] = CheckpointFollower(self, key)
        return f

    def watch_checkpoints(self, ckpt_dir, interval=0.5, jitter=None):
        """Tail a training checkpoint directory: whenever a newer VALID
        checkpoint commits, merge its per-rank shards (any world size —
        incubate.checkpoint.load_resharded) and stage a weight swap, so
        serving follows training automatically. Torn or partial
        checkpoints are skipped by the checksummed-manifest loader — the
        watcher never crashes the server, it just waits for the next
        commit. Stops with shutdown().

        ``jitter`` de-phases a FLEET of watchers tailing one directory
        (thundering-herd on the manifest read): each pod stretches its
        poll period by up to 50% of ``interval`` and offsets its first
        poll, both by a deterministic per-pod fraction
        (``pod_jitter_fraction``, derived from ``PADDLE_POD_ID``).
        Pass an explicit fraction in [0, 1) to override, or 0 to
        disable."""
        if self._watcher is not None and self._watcher.is_alive():
            return self
        follower = self.checkpoint_follower(ckpt_dir)
        frac = pod_jitter_fraction() if jitter is None else float(jitter)
        eff_interval = float(interval) * (1.0 + 0.5 * frac)
        self._watch_stop = threading.Event()

        def _tail():
            # first poll offset: even identical effective periods start
            # de-phased across the fleet
            self._watch_stop.wait(frac * float(interval))
            while not self._watch_stop.is_set():
                try:
                    follower.poll(stop_event=self._watch_stop)
                except Exception as e:
                    _explain.record(
                        "serving_watcher_error", op="watch_checkpoints",
                        why=f"checkpoint watcher poll failed "
                            f"({type(e).__name__}: {e}); retrying next "
                            "interval", error=str(e))
                self._watch_stop.wait(eff_interval)

        self._watcher = threading.Thread(target=_tail, daemon=True,
                                         name="paddle-tpu-ckpt-watcher")
        self._watcher.start()
        return self

    def stop_watcher(self):
        if self._watch_stop is not None:
            self._watch_stop.set()
        if self._watcher is not None:
            self._watcher.join(timeout=5)
            self._watcher = None

    def shutdown(self, drain=True, timeout=None):
        """Stop the server. drain=True (default) finishes every queued and
        in-flight request first; drain=False fails them fast with
        status="error". Returns True if the worker exited in time."""
        self.stop_watcher()
        if drain:
            self.request_drain()
        else:
            self._stop.set()
            self.scheduler.close()
        with self._work:
            self._work.notify_all()
        ok = True
        if self._thread is not None:
            self._thread.join(timeout)
            ok = not self._thread.is_alive()
        self._stop.set()
        if not drain:
            # only after the worker has exited: cancel_pending _finish()es
            # active requests and releases engine slots, which must not
            # race a decode_step still in flight (single-thread engine
            # contract). If the join timed out the worker is wedged
            # mid-step; unwedging callers blocked on result() beats
            # strict isolation from a thread that will never return.
            self.scheduler.cancel_pending()
        if self._old_sigterm is not None:
            signal.signal(signal.SIGTERM, self._old_sigterm)
            self._old_sigterm = None
        return ok

    # ---------------------------------------------------------- frontend --
    def submit(self, prompt_ids, **options):
        """Enqueue a generation job; returns its GenerationRequest handle.
        Raises QueueFullError immediately under backpressure and
        RuntimeError once shutdown/drain has begun."""
        return self.submit_request(GenerationRequest(prompt_ids, **options))

    def submit_request(self, request):
        """Enqueue an existing GenerationRequest handle (the supervisor's
        replay path re-submits a dead replica's requests — same object,
        same seed — to a healthy server)."""
        if self._draining.is_set() or self._stop.is_set():
            raise RuntimeError("server is shutting down; not accepting "
                               "requests")
        if self._thread is None:
            self.start()
        self.scheduler.submit(request)
        with self._work:
            self._work.notify()
        return request

    def result(self, request, timeout=None):
        return request.result(timeout)

    def generate(self, prompt_ids, result_timeout=None, **options):
        """Blocking convenience: submit + wait; returns the generated token
        ids. Raises TimeoutError when the request's own deadline expired
        (partial tokens are on the exception's .tokens) and RuntimeError on
        failure."""
        req = self.submit(prompt_ids, **options).result(result_timeout)
        if req.status == RequestStatus.DONE:
            return list(req.tokens)
        if req.status == RequestStatus.TIMEOUT:
            err = TimeoutError(
                f"request {req.rid} hit its deadline after "
                f"{len(req.tokens)} tokens")
            err.tokens = list(req.tokens)
            raise err
        raise RuntimeError(f"request {req.rid} failed: {req.error}")
