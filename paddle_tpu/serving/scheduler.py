"""paddle_tpu.serving.scheduler — iteration-level continuous batching.

Orca-style (Yu et al., OSDI'22) scheduling: the unit of work is one engine
ITERATION. Each ``step()`` (1) fails queued requests whose deadline passed,
(2) admits queued requests into free slots — one compiled prefill each,
which also yields the request's first token, so TTFT is prefill latency
plus queue wait — then (3) runs one compiled decode iteration over every
active slot and applies per-request stop conditions (EOS, max tokens,
cache capacity, deadline). A finished request's slot frees THIS iteration
and can be refilled the next — no other slot notices.

Admission is a bounded deque: ``submit()`` on a full queue raises
``QueueFullError`` immediately (fast-fail backpressure — the caller sheds
load or retries; nothing blocks the decode loop). All request-visible
transitions set a ``threading.Event`` so a frontend can block on
``request.result()`` from another thread, but ``step()`` itself must be
driven from a single thread (``serving.GenerationServer`` owns that loop).

Telemetry: ``serving.requests_*`` counters, ``serving.queue_wait`` /
``serving.ttft`` timings, log2 latency histograms (``ttft``,
``inter_token``, ``queue_wait``), per-request trace spans
(queue_wait → prefill/admit → decode) and a running
``serving.tokens_per_sec`` gauge.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time

from ..profiler import explainer as _explain
from ..profiler import registry as _registry
from ..profiler import span as _span
from ..profiler import tracing as _tracing
from .block_pool import PagePoolExhausted
from .engine import FatalEngineError, StaleHandoffError

_counters = _registry.scoped_counters("serving", {
    "requests_submitted": 0, "requests_completed": 0,
    "requests_rejected": 0, "requests_timeout": 0, "requests_failed": 0,
    "step_retries": 0, "swap_failures": 0, "requeued_requests": 0,
    "pool_exhausted": 0, "sched_steps": 0, "queue_wait_ns": 0,
    "admitted": 0})


def _note_queue_wait(wait):
    """One request left the queue for a slot after `wait` seconds."""
    _counters["queue_wait_ns"] += int(wait * 1e9)
    _counters["admitted"] += 1
    _registry.timing("queue_wait", wait, scope="serving")
    _registry.hist_record("queue_wait", wait)


class QueueFullError(RuntimeError):
    """Admission queue at capacity — backpressure, retry later."""


class RequestStatus:
    QUEUED = "queued"
    RUNNING = "running"
    DONE = "done"
    TIMEOUT = "timeout"
    ERROR = "error"


class GenerationRequest:
    """One generation job: prompt in, token ids out.

    ``timeout_s`` is a wall-clock deadline measured from submission; it
    covers queue wait AND generation, so an expired request fails fast in
    the queue or finishes early mid-flight with whatever tokens it has
    (``status == "timeout"``, partial ``tokens`` kept).
    ``seed`` pins the request's sampling stream regardless of which slot
    or batch composition serves it; None draws a deterministic per-engine
    sequence number, so a whole workload is reproducible under
    ``paddle_tpu.seed``.
    """

    def __init__(self, prompt_ids, max_new_tokens=32, eos_id=None,
                 temperature=0.0, top_k=0, top_p=1.0, seed=None,
                 timeout_s=None):
        self.prompt_ids = [int(t) for t in prompt_ids]
        if not self.prompt_ids:
            raise ValueError("prompt_ids must not be empty")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.eos_id = None if eos_id is None else int(eos_id)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = seed
        self.timeout_s = timeout_s

        self.rid = None
        self.slot = None
        # disaggregated serving (ISSUE 11): a decode pod receives a
        # request whose prompt KV was already computed by a prefill pod;
        # the exported slot payload rides here and admission adopts it
        # (engine.import_request_kv) instead of running a local prefill
        self.kv_payload = None
        self.tokens: list = []
        self.status = RequestStatus.QUEUED
        self.stop_reason = None
        self.error = None
        self.finished = threading.Event()
        self.submit_ts = None
        self.deadline = None
        self.ttft_s = None
        # fleet tracing (ISSUE 18): the router ships an explicit trace
        # id with handed-off requests; locally submitted requests derive
        # one from the pinned seed at submit() — both hash the same seed
        # so an orphan replay joins the original trace
        self.trace_id = None
        self.first_tok_ts = None
        self.last_tok_ts = None
        # time.monotonic stamp of every token, in order: its ends are
        # first_tok_ts / last_tok_ts, its single gaps what a client sees
        # between two tokens (another request's prefill included)
        self.tok_ts = []

    @property
    def done(self):
        return self.finished.is_set()

    def result(self, timeout=None):
        """Block until the request reaches a terminal state; returns self.
        Raises TimeoutError if the WAIT times out (the request itself keeps
        running — this is the caller giving up, not the deadline)."""
        if not self.finished.wait(timeout):
            raise TimeoutError(
                f"request {self.rid} still {self.status} after waiting "
                f"{timeout}s")
        return self

    def __repr__(self):
        return (f"GenerationRequest(rid={self.rid}, status={self.status}, "
                f"tokens={len(self.tokens)}, stop={self.stop_reason})")


class ContinuousBatchScheduler:
    """Bounded admission queue feeding an engine's free slots each step."""

    def __init__(self, engine, max_queue_size=16,
                 prefill_chunk_tokens=None):
        self.engine = engine
        self.max_queue_size = int(max_queue_size)
        # chunked prefill (ISSUE 12): prompts LONGER than this many
        # tokens admit via engine.begin_prefill and process one
        # block-aligned chunk per step(), interleaved with decode
        # iterations — one 8k-token prompt can no longer stall every
        # in-flight stream for its whole prefill. None disables.
        self.prefill_chunk_tokens = None if prefill_chunk_tokens is None \
            else int(prefill_chunk_tokens)
        if self.prefill_chunk_tokens is not None \
                and hasattr(engine, "require_full_layers"):
            # a ring of window rows does not hold what an earlier chunk
            # wrote beyond the window: refused at build, not per request
            engine.require_full_layers(
                "chunked prefill (prefill_chunk_tokens)")
            engine.require_autoregressive(
                "chunked prefill (prefill_chunk_tokens)",
                "its prefill attends to the call's own rows only, not to "
                "what an earlier chunk wrote")
        self._queue: collections.deque = collections.deque()
        self._active: dict = {}  # slot -> request
        self._prefilling: dict = {}  # slot -> request (chunked admission)
        self._lock = threading.Lock()
        self._rid = itertools.count(1)
        self._closed = False
        self._t0 = None
        self._tok_base = _counters["tokens_generated"] \
            if "tokens_generated" in _counters else 0
        self._pending_swap = None  # (state, source), newest staged wins
        self.swap_count = 0
        self.last_swap_error = None

    # ---------------------------------------------------------- frontend --
    def submit(self, request):
        """Enqueue; O(1), thread-safe, fast-fails on backpressure."""
        with self._lock:
            if self._closed:
                raise RuntimeError(
                    "scheduler is draining/closed; not accepting requests")
            if len(self._queue) >= self.max_queue_size:
                _counters["requests_rejected"] += 1
                raise QueueFullError(
                    f"admission queue full ({self.max_queue_size} "
                    "requests); retry later")
            request.rid = next(self._rid)
            request.submit_ts = time.monotonic()
            if request.timeout_s is not None:
                request.deadline = request.submit_ts + request.timeout_s
            request.status = RequestStatus.QUEUED
            if request.trace_id is None and request.seed is not None:
                request.trace_id = _tracing.trace_id_for_seed(request.seed)
            self._queue.append(request)
            _counters["requests_submitted"] += 1
        _tracing.flight("submit", rid=request.rid,
                        trace_id=request.trace_id,
                        prompt_len=len(request.prompt_ids))
        return request

    def has_work(self):
        return bool(self._queue or self._active or self._prefilling)

    def prefilling(self):
        return len(self._prefilling)

    def queued(self):
        return len(self._queue)

    def active(self):
        return len(self._active)

    def close(self):
        """Stop accepting; already-queued and in-flight requests drain.

        Deliberately lock-free: the server's SIGTERM handler calls this
        on whatever thread the signal lands on, possibly one already
        inside submit() holding _lock — taking the non-reentrant lock
        here would deadlock the drain. A plain bool store is atomic in
        CPython and submit() reads it under _lock, so at worst one
        concurrent submit wins the race and drains normally."""
        self._closed = True

    def cancel_pending(self, reason="server shutdown"):
        """Hard shutdown path: fail everything that hasn't finished."""
        self.close()
        with self._lock:
            pending = list(self._queue)
            self._queue.clear()
        for req in pending:
            self._finish(req, RequestStatus.ERROR, error=reason)
        for slot, req in list(self._active.items()) \
                + list(self._prefilling.items()):
            self._finish(req, RequestStatus.ERROR, error=reason)

    def fail_all(self, exc):
        """Engine fault escape hatch: fail in-flight work loudly instead of
        wedging callers blocked on result()."""
        for slot, req in list(self._active.items()) \
                + list(self._prefilling.items()):
            self._finish(req, RequestStatus.ERROR, error=repr(exc))

    def takeover_requests(self):
        """Replica-death path (supervisor): hand back every queued AND
        in-flight request UN-finished — events stay unset so callers
        blocked on result() keep waiting for the replay, token prefixes
        are cleared so the replay regenerates them. Because sampling
        depends only on (engine base key, request seed, token index), a
        restarted replica built with the same ``rng_seed`` reproduces
        each request's tokens bitwise — resubmission is idempotent by
        request seed. Call only after the driving worker has stopped
        (the dead replica's engine is not touched beyond slot releases)."""
        self.close()
        with self._lock:
            queued = list(self._queue)
            self._queue.clear()
        inflight = list(self._active.values()) \
            + list(self._prefilling.values())
        self._active.clear()
        self._prefilling.clear()
        try:
            self.engine.reset()
        except Exception:
            pass  # dead engines don't need their slots back
        out = []
        for req in inflight + queued:
            if req.done:
                continue
            req.slot = None
            req.tokens = []
            req.status = RequestStatus.QUEUED
            req.stop_reason = None
            req.error = None
            out.append(req)
        _counters["requeued_requests"] += len(out)
        return out

    # ----------------------------------------------------- weight swaps --
    def request_swap(self, state, source=None, draft_state=None):
        """Stage a weight swap; thread-safe, O(1). The swap is applied by
        the driving thread at the NEXT step boundary — between decode
        steps, so no request ever observes a half-swapped model. Staging
        twice before a step replaces the earlier stage (newest weights
        win). ``draft_state`` (spec-decode engines only, ISSUE 16) swaps
        the drafter in the same commit so acceptance recovers instead of
        decaying against stale draft weights."""
        with self._lock:
            self._pending_swap = (state, source, draft_state)

    def _apply_pending_swap(self):
        with self._lock:
            pending, self._pending_swap = self._pending_swap, None
        if pending is None:
            return
        state, source, draft_state = pending
        try:
            if draft_state is not None:
                self.engine.swap_weights(state, source=source,
                                         draft_state=draft_state)
            else:
                self.engine.swap_weights(state, source=source)
            self.swap_count += 1
            self.last_swap_error = None
        except Exception as e:
            # refused or died mid-validation: the engine guarantees no
            # partial assignment, so the pre-swap weights keep serving
            _counters["swap_failures"] += 1
            self.last_swap_error = e
            _explain.record(
                "serving_swap_failed", op="swap_weights",
                why=f"weight swap{f' from {source}' if source else ''} "
                    f"failed ({type(e).__name__}: {e}); serving continues "
                    "on the pre-swap weights",
                source=source, error=str(e))

    # ---------------------------------------------------------- the loop --
    def step(self):
        """One continuous-batching iteration; returns True while any work
        remains. Single-threaded with respect to itself and the engine.

        The steady decode window (no queued work, no staged swap) skips
        straight to the decode call: admission, queued-deadline scans and
        swap application are batch-boundary bookkeeping that only runs
        when their cheap preconditions fire (attribute reads are atomic
        in CPython, so the gates take no lock; the locked slow paths
        re-check under the lock as before). Combined with the engine's
        prebuilt decode args this makes the scheduler->engine hop one
        fingerprint check + one executable call per steady iteration."""
        with _span("serving.sched_step"):
            _counters["sched_steps"] += 1
            return self._step()

    def _step(self):
        now = time.monotonic()
        if self._t0 is None:
            self._t0 = now

        # (0) staged weight swap lands HERE — between decode steps, so
        # every token of every request is computed on one consistent set
        # of weights (old until this boundary, new after)
        if self._pending_swap is not None:
            self._apply_pending_swap()

        if self._queue:
            # (1) deadline-expired while queued: fail fast, never occupy
            # a slot
            with self._lock:
                queued = list(self._queue)
            for req in queued:
                if req.deadline is not None and now > req.deadline:
                    with self._lock:
                        try:
                            self._queue.remove(req)
                        except ValueError:
                            continue
                    self._finish(req, RequestStatus.TIMEOUT)

            # (2) admission: fill free slots from the queue, one
            # compiled prefill each. Admission budgets KV BLOCKS, not
            # just slots (ISSUE 10): a request only leaves the queue when
            # the paged pool can cover its worst case (prompt + token
            # budget, prefix-evictable blocks counted), so generation can
            # never run out of cache mid-flight. A pool-exhausted head
            # request simply stays queued — FIFO order is preserved, the
            # queue backs up, and submit() turns the pressure into
            # QueueFullError backpressure at the edge.
            while True:
                free = self.engine.free_slots()
                if not free:
                    break
                with self._lock:
                    head = self._queue[0] if self._queue else None
                if head is None or not self._admit(head, free[0]):
                    # no request, or pool pressure (at the budget check, or
                    # in the prefill despite it: the request is at the
                    # queue's head again): stop admitting THIS step
                    # (retrying in this loop would spin forever) and let
                    # decode progress free blocks
                    break

        # (2b) chunked prefill (ISSUE 12): advance ONE block-aligned
        # chunk per mid-prefill slot, then fall through to the decode
        # iteration — every in-flight stream emits a token between
        # chunks, so a long prompt bounds inter-token latency at one
        # chunk's latency instead of its whole prefill
        if self._prefilling:
            now = time.monotonic()
            for slot, req in list(self._prefilling.items()):
                if req.deadline is not None and now > req.deadline:
                    self._finish(req, RequestStatus.TIMEOUT)
                    continue
                try:
                    first = self.engine.prefill_chunk(slot)
                except Exception as e:
                    # the engine dropped the chunk state and its blocks;
                    # same terminal split as _admit
                    self._finish(req, RequestStatus.ERROR, error=str(e))
                    if not isinstance(e, (ValueError, TypeError)):
                        raise
                    continue
                if first is None:
                    continue
                self._prefilling.pop(slot, None)
                self._active[slot] = req
                now = time.monotonic()
                self._note_ttft(req, now)
                self._append_token(req, first, now)

        # (3) one decode iteration over every active slot; per-request
        # stop-condition bookkeeping happens once per iteration at this
        # batch boundary (one shared timestamp, no per-token clock reads).
        # A speculative engine (decode_step_spec) emits 1..K+1 tokens per
        # slot per iteration — each bitwise-equal to plain decode's — and
        # stop conditions are applied per token in emission order. A
        # block-diffusion decoder (engine.generation) yields nothing for a
        # slot that denoised and a block's tokens at once, with one
        # timestamp, for a slot that committed: a request's first token
        # comes with its first commit (prefill hands none over).
        if self._active:
            # the engine's serving.decode_step span times the iteration;
            # serving.emit the bookkeeping after it (one span, never per
            # slot / per token)
            spec = getattr(self.engine, "decode_step_spec", None)
            blocks = getattr(self.engine, "generation", None) is not None
            out = self._decode_with_retry(spec or self.engine.decode_step)
            now = time.monotonic()
            with _span("serving.emit"):
                for slot, req in list(self._active.items()):
                    if blocks:
                        if out[slot] is None:
                            continue
                        toks, base = out[slot]  # its first token's position
                        if req.ttft_s is None:
                            self._note_ttft(req, now)
                    elif spec is None:
                        self._append_token(req, int(out[slot]), now)
                        continue
                    else:
                        toks = out[slot]
                        base = self.engine.slot_len(slot) - len(toks)
                    for i, t in enumerate(toks):
                        self._append_token(req, int(t), now,
                                           slot_len=base + i + 1)
                        if req.done:
                            break

        self._update_throughput()
        return self.has_work()

    def _decode_with_retry(self, step_fn):
        """One decode iteration with single-retry fault tolerance: a
        transient engine exception re-primes the decode executable and
        retries once; only the SECOND consecutive error propagates (the
        server loop then fails the batch). Fatal errors (replica death)
        are never retried — they must reach the supervisor."""
        try:
            return step_fn()
        except FatalEngineError:
            raise
        except Exception as e:
            _counters["step_retries"] += 1
            _explain.record(
                "serving_step_retry", op="decode_step",
                why=f"transient decode failure ({type(e).__name__}: {e}); "
                    "re-priming the decode executable and retrying once "
                    "before failing the batch",
                error=str(e))
            reprime = getattr(self.engine, "reprime", None)
            if reprime is not None:
                reprime()
            return step_fn()

    def drain(self, timeout=None):
        """Run step() until idle (graceful drain); True if fully drained."""
        self.close()
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.has_work():
            if deadline is not None and time.monotonic() > deadline:
                return False
            self.step()
        return True

    # ----------------------------------------------------------- helpers --
    def _admit(self, head, slot):
        """The queue's head into `slot`, if the pool can cover it: the
        budget check, then the prefill. Returns False when admission hit
        pool pressure and the request stayed at (or went back to) the
        queue's head (the caller must stop admitting this step — retrying
        immediately would spin); True for every terminal outcome
        (admitted, chunk-admitted or failed)."""
        with _span("serving.admit", head.trace_id):
            with _span("serving.admit_check"):
                can_import = getattr(self.engine, "can_import", None)
                can_admit = getattr(self.engine, "can_admit", None)
                if head.kv_payload is not None and can_import is not None:
                    fits = can_import(head.kv_payload)
                else:
                    fits = can_admit is None or can_admit(
                        head.prompt_ids, head.max_new_tokens)
            if not fits:
                _counters["pool_exhausted"] += 1
                _explain.record(
                    "serving_pool_exhausted", op="admission",
                    why="KV block pool cannot cover the next queued "
                        "request even after prefix eviction; leaving "
                        "it queued (admission backpressure) until "
                        "running requests release blocks",
                    queued=len(self._queue))
                return False
            with self._lock:
                # step() is the only consumer and the deadline scan
                # already ran, so the head we budgeted is still the head
                # we pop
                req = self._queue.popleft() if self._queue else None
            return req is not None and self._admit_into(req, slot)

    def _admit_into(self, req, slot):
        t_start = time.monotonic()
        begin = getattr(self.engine, "begin_prefill", None)
        if (self.prefill_chunk_tokens is not None and begin is not None
                and req.kv_payload is None
                and len(req.prompt_ids) > self.prefill_chunk_tokens):
            # long prompt: chunked admission — blocks budgeted up front
            # (identical to prefill), chunks land in step()'s phase (2b)
            try:
                begin(slot, req.prompt_ids, temperature=req.temperature,
                      top_k=req.top_k, top_p=req.top_p, seed=req.seed,
                      max_new_tokens=req.max_new_tokens,
                      chunk_tokens=self.prefill_chunk_tokens)
            except PagePoolExhausted:
                _counters["pool_exhausted"] += 1
                with self._lock:
                    self._queue.appendleft(req)
                return False
            except Exception as e:
                self._finish(req, RequestStatus.ERROR, error=str(e))
                if not isinstance(e, (ValueError, TypeError)):
                    raise
                return True
            req.slot = slot
            req.status = RequestStatus.RUNNING
            self._prefilling[slot] = req
            _note_queue_wait(t_start - req.submit_ts)
            _tracing.add_span(req.trace_id, "queue_wait",
                              req.submit_ts, t_start)
            _tracing.flight("admit_chunked", rid=req.rid,
                            trace_id=req.trace_id, slot=slot)
            return True
        handoff = req.kv_payload is not None
        try:
            first = None
            if req.kv_payload is not None:
                # handed-off request (disaggregated serving): the prompt
                # KV and first token already exist — adopt the exported
                # slot instead of prefilling
                try:
                    first = self.engine.import_request_kv(
                        slot, req.kv_payload, prompt_ids=req.prompt_ids)
                except StaleHandoffError as e:
                    # a weight swap landed between the prefill pod's
                    # export and this admission: adopting would decode
                    # new weights over old-weight KV. Re-prefill the
                    # prompt locally under the CURRENT weights — exactly
                    # what a monolithic pod that swapped before this
                    # request would have produced; the block budget is
                    # identical (same prompt + token-budget formula), so
                    # the can_import approval still covers it.
                    _explain.record(
                        "serving_handoff_stale", op="admission",
                        why=f"{e}; falling back to a fresh local "
                            "prefill on the current weights",
                        rid=req.rid)
                req.kv_payload = None  # adopted or discarded
            if first is None:
                first = self.engine.prefill(
                    slot, req.prompt_ids, temperature=req.temperature,
                    top_k=req.top_k, top_p=req.top_p, seed=req.seed,
                    max_new_tokens=req.max_new_tokens)
        except PagePoolExhausted:
            # can_admit's conservative budget makes this unreachable in
            # normal operation (belt and braces for fault injection /
            # future over-commit policies): the request goes BACK to the
            # queue head un-finished — backpressure, never a truncated
            # or failed generation
            _counters["pool_exhausted"] += 1
            with self._lock:
                self._queue.appendleft(req)
            return False
        except Exception as e:
            # the request left the queue but never reached _active, so
            # fail it HERE — nothing else (fail_all iterates _active) can
            # ever set its finished event. Bad-request errors stop there;
            # anything else (compile failure, OOM) is an engine fault and
            # re-raises so the server loop fails the in-flight batch too.
            self._finish(req, RequestStatus.ERROR, error=str(e))
            if not isinstance(e, (ValueError, TypeError)):
                raise
            return True
        # the scheduler's half of the installation (the engine's is
        # `_install_slot`, under the same name)
        with _span("serving.admit_install"):
            req.slot = slot
            req.status = RequestStatus.RUNNING
            self._active[slot] = req
            _note_queue_wait(t_start - req.submit_ts)
            now = time.monotonic()
            if first is not None:
                self._note_ttft(req, now)
            _tracing.add_span(req.trace_id, "queue_wait", req.submit_ts,
                              t_start)
            if handoff:  # a local admission is the serving.admit span itself
                _tracing.add_span(req.trace_id, "kv_adopt", t_start, now)
            _tracing.flight("admit", rid=req.rid, trace_id=req.trace_id,
                            slot=slot, handoff=handoff)
            if first is not None:  # (a block decoder's prefill hands none)
                self._append_token(req, first, now)
        return True

    @staticmethod
    def _note_ttft(req, now):
        req.ttft_s = now - req.submit_ts
        _registry.timing("ttft", req.ttft_s, scope="serving")
        _registry.hist_record("ttft", req.ttft_s)

    def _append_token(self, req, token, now, slot_len=None):
        # slot_len: the sequence length AS OF this token (the spec path
        # appends a whole round at once, so the engine's cursor is past
        # the intermediate tokens — the length stop must see each
        # token's own position, exactly as plain decode would have)
        req.tokens.append(token)
        # inter-token latency histogram: one frexp + two list stores per
        # token — rides the per-token bookkeeping that already runs here
        if req.last_tok_ts is not None:
            _registry.hist_record("inter_token", now - req.last_tok_ts)
        else:
            req.first_tok_ts = now
        req.last_tok_ts = now
        req.tok_ts.append(now)
        if slot_len is None and req.slot is not None:
            slot_len = self.engine.slot_len(req.slot)
        if req.eos_id is not None and token == req.eos_id:
            self._finish(req, RequestStatus.DONE, stop_reason="eos")
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish(req, RequestStatus.DONE, stop_reason="max_tokens")
        elif req.slot is not None and \
                slot_len >= self.engine.max_seq_len:
            self._finish(req, RequestStatus.DONE, stop_reason="length")
        elif req.deadline is not None and now > req.deadline:
            self._finish(req, RequestStatus.TIMEOUT)

    def _finish(self, req, status, stop_reason=None, error=None):
        if req.slot is not None:
            self.engine.release(req.slot)
            self._active.pop(req.slot, None)
            self._prefilling.pop(req.slot, None)
            req.slot = None
        req.status = status
        req.stop_reason = stop_reason
        req.error = error
        if status == RequestStatus.DONE:
            _counters["requests_completed"] += 1
        elif status == RequestStatus.TIMEOUT:
            req.stop_reason = "deadline"
            _counters["requests_timeout"] += 1
        else:
            _counters["requests_failed"] += 1
        if req.first_tok_ts is not None and req.last_tok_ts is not None \
                and req.last_tok_ts > req.first_tok_ts:
            _tracing.add_span(req.trace_id, "decode",
                              req.first_tok_ts, req.last_tok_ts)
        _tracing.flight("finish", rid=req.rid, trace_id=req.trace_id,
                        status=status, stop=req.stop_reason,
                        tokens=len(req.tokens))
        req.finished.set()

    def _update_throughput(self):
        if self._t0 is None:
            return
        dt = time.monotonic() - self._t0
        if dt <= 0:
            return
        _registry.gauge_set(
            "serving.tokens_per_sec",
            (_counters["tokens_generated"] - self._tok_base) / dt)
