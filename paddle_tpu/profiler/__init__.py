"""Profiler — the unified runtime observability layer.

Reference: `python/paddle/profiler/profiler.py:344` (Profiler with
scheduler states, chrome-trace export) over the C++ unified profiler
(`fluid/platform/profiler/profiler.h:47`: HostTracer + CudaTracer/CUPTI
+ CustomTracer).

TPU re-design, three pillars (ISSUE 3):

1. **Metrics registry** (`registry.py`): process-wide counters / gauges
   / timings with named scopes. The lazy capture engine, the eager
   jit cache, collectives, and the dataloader all publish here;
   `stats()` is the one query point.
2. **Recompile/fallback explainer** (`explainer.py`): every lazy
   capture fallback, segment recompile, capture promotion, and eager
   jit-cache miss records a structured cause event into a ring buffer —
   `explain()` reads it back; `FLAGS_log_compiles` logs live.
3. **Host spans** (`spans.py`): one `span(name)` for the program's own
   call sites, with one table of the names it may carry (`spans.SPANS`;
   the names of the kernels, executables and scopes on the device trace
   sit beside it). Every span is a `jax.profiler.TraceAnnotation` (so it
   lies under the device ops in a `jax.profiler` trace), moves its
   `<name>_ns` / `<name>_n` counters, and goes to the `tracing` ring and
   the `timeline` when those are on. `RecordEvent` is the Paddle-named
   wrapper over the same code for a user's own event names.
   `timeline.py` buffers the spans of a Profiler window and
   `export_chrome_tracing` writes them as chrome-trace JSON with no
   libtpu.

`Profiler` keeps the reference's state machine
(CLOSED/READY/RECORD/RECORD_AND_RETURN).
"""
from __future__ import annotations

import enum
import os
import time

import jax

from . import explainer, registry, timeline
from .spans import HostSpan, span

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "ProfilerResult",
           "RecordEvent", "span", "make_scheduler", "export_chrome_tracing",
           "load_profiler_result", "stats", "explain", "reset_stats",
           "set_step_metrics", "CompileWatch"]


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Reference profiler.py:79 scheduler factory."""

    def sched(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = (step - skip_first) % max(closed + ready + record, 1)
        if repeat and (step - skip_first) // max(closed + ready + record, 1) \
                >= repeat:
            return ProfilerState.CLOSED
        if s < closed:
            return ProfilerState.CLOSED
        if s < closed + ready:
            return ProfilerState.READY
        if s == closed + ready + record - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return sched


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready handler: write the host-span chrome trace (plus
    the telemetry snapshot) into `dir_name` when a record window closes.
    A jax/xprof device trace, when one ran, is written by jax into the
    same directory — TensorBoard merges the two views."""

    def handler(prof):
        prof._export_dir = dir_name
        prof._worker_name = worker_name
        prof._export_host_trace()

    # attributes let Profiler.__init__ route the jax trace into the same
    # directory from the very first record window (the handler itself
    # only runs when the window closes)
    handler._export_dir = dir_name
    handler._worker_name = worker_name
    return handler


class RecordEvent:
    """Host-side event annotation (reference event_tracing.h RecordEvent):
    Paddle's name for a span, open to any event name. A thin wrapper over
    `spans.HostSpan` — the same `TraceAnnotation`, ring and timeline sinks
    as the program's own `span`, but no name check and no counter.

    begin/end form a STACK: re-entrant begin() calls each open a span
    and end() closes the innermost one; end() without a matching begin
    is a no-op."""

    __slots__ = ("name", "_stack")

    def __init__(self, name, event_type=None):
        self.name = name
        self._stack = []

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def begin(self):
        self._stack.append(HostSpan(self.name).__enter__())

    def end(self):
        if self._stack:
            self._stack.pop().__exit__(None, None, None)


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False):
        self._scheduler = scheduler if callable(scheduler) else (
            make_scheduler(record=scheduler[1] - scheduler[0],
                           skip_first=scheduler[0])
            if isinstance(scheduler, (tuple, list)) else (lambda s:
                                                          ProfilerState.RECORD))
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._export_dir = getattr(on_trace_ready, "_export_dir", None)
        self._worker_name = getattr(on_trace_ready, "_worker_name", None)
        self._step = 0
        self._host_tracing = False
        self._jax_running = False
        self._host_spans = []
        self._last_export = None
        self._export_count = 0
        self._pending_export = False  # closed window not yet delivered
        self._delivered = 0           # on_trace_ready invocations
        self._step_times = []  # fixed-size reservoir (registry.RESERVOIR_CAP)
        self._step_count = 0
        self._step_total = 0.0
        self._last_t = None

    def start(self):
        self._state = self._scheduler(self._step)
        if self._state in (ProfilerState.RECORD,
                           ProfilerState.RECORD_AND_RETURN) \
                and not self._timer_only:
            self._begin_trace()
        self._last_t = time.perf_counter()

    def _begin_trace(self):
        if not self._host_tracing:
            timeline.start()
            self._host_tracing = True
        if not self._jax_running:
            d = self._export_dir or os.environ.get(
                "PADDLE_TPU_PROFILE_DIR", "/tmp/paddle_tpu_profile")
            os.makedirs(d, exist_ok=True)
            try:
                jax.profiler.start_trace(d)
                self._jax_running = True
            except Exception:
                pass  # no device tracer — the host timeline still records

    def _end_trace(self):
        if self._host_tracing:
            self._host_spans = timeline.stop()
            self._host_tracing = False
            self._pending_export = True
        if self._jax_running:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_running = False

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last_t is not None:
            dt = now - self._last_t
            # bounded: running count/total + a fixed reservoir for the
            # summary percentiles (long profiled runs used to grow this
            # list forever), plus the mergeable log2 histogram that the
            # fleet metrics plane aggregates
            self._step_count += 1
            self._step_total += dt
            registry.reservoir_add(self._step_times, self._step_count, dt)
            registry.hist_record("step_host", dt, scope="profiler")
        self._last_t = now
        self._step += 1
        prev = getattr(self, "_state", ProfilerState.CLOSED)
        self._state = self._scheduler(self._step)
        if self._state in (ProfilerState.RECORD,
                           ProfilerState.RECORD_AND_RETURN):
            if not self._timer_only:
                self._begin_trace()
        elif prev in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            if not self._timer_only:
                self._end_trace()
            if self._on_trace_ready:
                self._on_trace_ready(self)
                self._delivered += 1
            self._pending_export = False

    def stop(self):
        self._end_trace()
        # skip the handler when step() already delivered every closed
        # window (a second call would re-deliver the last window's stale
        # spans — true for custom handlers too, hence the delivery
        # counter, not the export counter); a profiler that never
        # recorded still gets one callback (timer_only use)
        if self._on_trace_ready and (self._pending_export
                                     or self._delivered == 0):
            self._on_trace_ready(self)
            self._delivered += 1
        self._pending_export = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def _export_host_trace(self):
        """Write the last record window's host spans as chrome-trace
        JSON (with the telemetry snapshot embedded); returns the path."""
        d = self._export_dir or os.environ.get(
            "PADDLE_TPU_PROFILE_DIR", "/tmp/paddle_tpu_profile")
        os.makedirs(d, exist_ok=True)
        name = self._worker_name or f"paddle_tpu_host_{os.getpid()}"
        if self._export_count:  # later record windows get their own file
            name = f"{name}.{self._export_count}"
        self._export_count += 1
        meta = registry.snapshot()
        meta["step_times_ms"] = [t * 1e3 for t in self._step_times]
        meta["step_count"] = self._step_count
        self._last_export = timeline.write_chrome_trace(
            os.path.join(d, name + ".json"), self._host_spans, meta)
        return self._last_export

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        if not self._step_times:
            return "no steps recorded"
        import numpy as np

        # percentiles from the reservoir (a uniform sample of every step
        # when the run outgrew it); count/avg from the exact running sums
        ts = np.asarray(self._step_times) * 1e3
        avg_s = self._step_total / max(self._step_count, 1)
        line = (f"steps={self._step_count} avg={avg_s * 1e3:.3f}ms p50="
                f"{np.percentile(ts, 50):.3f}ms p99="
                f"{np.percentile(ts, 99):.3f}ms")
        tokens = registry.gauge("step.tokens")
        flops = registry.gauge("step.flops")
        if tokens:
            line += f" tokens/s={tokens / avg_s:,.1f}"
        if flops:
            from ..cost_model import device_peak_flops

            try:
                line += f" MFU={flops / avg_s / device_peak_flops():.2%}"
            except LookupError:
                pass  # no peak on record for this device (CPU): no MFU
        return line


def set_step_metrics(flops_per_step=None, tokens_per_step=None):
    """Declare per-step model FLOPs / token counts (cost-model output)
    so `Profiler.summary()` and bench telemetry can report MFU and
    tokens/sec alongside step-time percentiles."""
    if flops_per_step is not None:
        registry.gauge_set("step.flops", float(flops_per_step))
    if tokens_per_step is not None:
        registry.gauge_set("step.tokens", float(tokens_per_step))


class CompileWatch:
    """Counts what JAX itself compiles while the block runs, from JAX's own
    monitoring events — the ground truth under the registry's per-layer
    signature radars (``serving.decode_compiles``, ``spmd.step_compiles``),
    which count first-seen signatures rather than executables.

        with profiler.CompileWatch() as warm:
            step(batch)            # traces + compiles
        with profiler.CompileWatch() as steady:
            step(batch)
        assert steady.compiles == 0

    ``compiles``: executables XLA built or loaded from the persistent
    cache; ``cache_hits``: how many of them the persistent cache served;
    ``seconds``: trace + lowering + backend-compile time (set-up time)."""

    _TIMED = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0

    def _on_duration(self, event, seconds, **_):
        if event in self._TIMED:
            self.seconds += seconds
            self.compiles += event == self._TIMED[2]

    def _on_event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False


def stats(scope=None):
    """Telemetry snapshot: {"counters", "gauges", "timings"} — flat
    "<scope>.<name>" keys. With `scope`, just that scope's counters.
    Includes the lazy engine (promotions, fallbacks, cache hits), the
    dispatch jit cache, collective call/byte counters, and dataloader
    waits; see DESIGN_DECISIONS.md for each counter's meaning."""
    if scope is not None:
        return registry.counters(scope)
    return registry.snapshot()


def explain(n=None, kind=None):
    """Recent structured cause events (capture fallbacks, segment
    recompiles, promotions, jit-cache misses), oldest first."""
    return explainer.events(n, kind)


def reset_stats():
    """Zero all counters/timings/gauges and clear the explainer ring."""
    registry.reset()
    explainer.clear()


class ProfilerResult:
    """Parsed chrome trace: host spans + the embedded telemetry
    snapshot (`load_profiler_result` return type)."""

    def __init__(self, doc):
        self.events = [e for e in doc.get("traceEvents", ())
                       if e.get("ph") == "X"]
        self.telemetry = doc.get("paddle_tpu", {})

    def span_totals(self):
        """name -> {"count", "total_ms"} aggregated over all spans."""
        out = {}
        for e in self.events:
            rec = out.setdefault(e.get("name", "?"),
                                 {"count": 0, "total_ms": 0.0})
            rec["count"] += 1
            rec["total_ms"] += float(e.get("dur", 0.0)) / 1e3
        return out

    def summary(self):
        tot = self.span_totals()
        rows = sorted(tot.items(), key=lambda kv: -kv[1]["total_ms"])
        lines = [f"{'name':<40} {'count':>8} {'total_ms':>12} {'avg_ms':>10}"]
        for name, rec in rows:
            lines.append(f"{name:<40} {rec['count']:>8} "
                         f"{rec['total_ms']:>12.3f} "
                         f"{rec['total_ms'] / rec['count']:>10.3f}")
        return "\n".join(lines)


def load_profiler_result(filename):
    """Parse an exported chrome-trace JSON back into a ProfilerResult
    with per-name span totals (reference load_profiler_result)."""
    import json

    with open(filename) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError(
            f"{filename} is not a chrome-trace JSON (no traceEvents key)")
    return ProfilerResult(doc)
