"""paddle_tpu.profiler.spans — the one span API inside the program, and the
one table of the names it (and the device trace) may carry.

A host span (`span`) is, on the thread that did the work:

* a `jax.profiler.TraceAnnotation(name)` — always; a `TraceMe` is a flag
  check while no profiler trace runs — so under `jax.profiler.start_trace`
  it lies on the host plane on the same clock as the device ops (host and
  device `start_ns` share an origin);
* two integer counters in the span's registry scope, `<name>_ns` and
  `<name>_n` (`serving.decode_step` -> scope `serving`, `decode_step_ns`
  and `decode_step_n`), which `registry.counters()` returns, so a window
  delta of the counters holds durations from inside the program; a span
  whose table entry says so also feeds the `registry.timing` reservoir of
  its name, which `stats_dump` and the pod's `stats` reply read;
* one entry of the ring of `profiler/tracing.py` while `tracing.enabled()`
  (with its `trace_id`), and one of `profiler/timeline.py` while a
  `Profiler` window records.

A span that is in both the ring and the profiler's trace (same name, same
order) gives the offset between `time.monotonic` and the trace's clock.

Rule for call sites: step and phase frequency only — never per op, per slot
or per token. The spans of the server thread TILE its iteration, so that a
reader of the device's idle time (`benchmark/step_timeline.py`) finds a named
phase under every piece of a gap and no catch-all:

    steady iteration: six spans         an admission adds: seven
    serving.sched_step                  serving.admit
      serving.decode_prepare              serving.admit_check
      serving.decode_step                 serving.admit_blocks
        serving.decode_sync               serving.admit_stage
      serving.decode_finish               serving.prefill
      serving.emit                        serving.admit_install (the engine's)
                                          serving.admit_install (the scheduler's)
                                        and the step after it rebuilds: the
                                        same six spans, no more

`serving.sched_step`'s self time is then the scheduler's own checks and
nothing of the engine's; a wait for work is `serving.loop_idle`, between
iterations. A training step has two. `host.gc` is no call site: the
collector's own callback (`watch_gc`), on whichever thread collects. The
program's own call sites use `span` and only names from `SPANS`;
`RecordEvent` (Paddle's name, a user's own event names) runs the same code,
takes any name and feeds no counter. `tracing.span` / `tracing.add_span`
stay for what only they can do: spans closed after the fact from two
timestamps and spans shipped between processes.

The device side of the table (`KERNELS`, `EXECUTABLES`, `SCOPES`) holds the
names the compiled programs carry: metadata only, the programs do not
change.
"""
from __future__ import annotations

import gc
import threading
import time

import jax

from . import registry, timeline, tracing

# Host spans: name -> what it covers. PERF.md section 3 mirrors this table.
SPANS = {
    "serving.loop_idle": "server.py `_loop`, around `_work.wait`: the "
                         "server thread had no work",
    "serving.sched_step": "scheduler.py `step`, whole body: one scheduler "
                          "iteration",
    "serving.admit": "scheduler.py `_admit`: one admission (or the "
                     "attempt the pool refused); its self time is the "
                     "scheduler's own around the engine's phases",
    "serving.admit_check": "scheduler.py `_admit`, around the engine's "
                           "budget check of the queue's head (`can_admit` "
                           "/ `can_import`: the pool's free blocks and a "
                           "walk of the radix tree for the evictable ones)",
    "serving.admit_blocks": "engine.py `_admit_prompt`: the prompt checked, "
                            "the radix match, the blocks allocated "
                            "(eviction inside)",
    "serving.admit_stage": "engine.py `prefill` / `prefill_chunk`: from the "
                           "blocks to the executable's call: the request's "
                           "key (`_request_key`: a device op and a read of "
                           "it), padding, the arguments' `_put`s, the "
                           "signature radar",
    "serving.prefill": "engine.py `_prefill_run`: dispatch + wait of one "
                       "prefill executable (whole prompts, prefix-hit "
                       "remainders and chunks)",
    "serving.admit_install": "engine.py around `_install_slot` (slot state, "
                             "the radix insert, a drafter's ingestion), and "
                             "again in scheduler.py `_admit_into` around "
                             "the request's own bookkeeping after it "
                             "(`_note_ttft`, `_append_token`)",
    "serving.decode_prepare": "engine.py `decode_step`, from its first line "
                              "to the call: fault hooks, the audit, the "
                              "rebuild's `_put`s after an admission, the "
                              "argument tuple",
    "serving.decode_step": "engine.py `_decode_call`, fast and rebuild "
                           "path: dispatch + wait of one decode (or "
                           "speculative round) executable",
    "serving.decode_sync": "inside serving.decode_step, around "
                           "`np.asarray(toks_d)`: the host waiting for "
                           "the device; the parent's self time is dispatch",
    "serving.decode_finish": "engine.py `decode_step`, from the call's "
                             "return to its own: the step's counters, the "
                             "pools and the fast tuple, the host mirrors "
                             "(`_finish_decode` / `_finish_block`)",
    "serving.emit": "scheduler.py, the `_append_token` loop after a "
                    "decode: per-request bookkeeping of one iteration",
    "train.step": "jit `TrainStep.__call__`: lifting the arguments, the "
                  "executable call, writing state back",
    "host.gc": "`watch_gc`: one collection of Python's garbage collector, "
               "from `gc.callbacks`' \"start\" to its \"stop\", on the "
               "thread that collects (it holds the interpreter lock, so "
               "every thread waits); on while a `GenerationServer`'s "
               "worker runs",
}

# Spans that also feed the `registry.timing` reservoir of the same name.
_TIMED = frozenset({"serving.prefill", "serving.decode_step"})

# Counters kept at the same boundaries as the spans (scope.name -> what).
COUNTERS = {
    "host.gc_gen2_n": "of host.gc_n the collections of the oldest "
                      "generation (the long ones)",
    "serving.sched_steps": "one a scheduler `step()`",
    "serving.queue_wait_ns": "submit to admission start, summed over "
                             "admitted requests",
    "serving.admitted": "requests that left the queue for a slot",
    "serving.kv_tokens_read": "sum of the active slots' lengths at each "
                              "decode step: one full layer's rows, the KV "
                              "rows that step's attention had to read in a "
                              "layer that keeps every row (plain decode; a "
                              "speculative round does not count them); "
                              "a row is whatever the cache keeps a token",
    "serving.kv_window_rows_read": "sum over the active slots of min(length, "
                                   "window) at each decode step: the rows "
                                   "ONE window layer's attention read from "
                                   "its ring (host; 0 for a cache without "
                                   "window layers)",
    "serving.cache_refusals": "features this engine's cache cannot give "
                              "(a mesh, the handoff, chunked prefill, spec "
                              "decode over a latent cache or a ring; prefix "
                              "sharing switched off for a ring), each with "
                              "a `cache_feature_refused` explainer event",
    "serving.diffusion.slot_forwards": "active slots x forwards of a "
                                       "block-diffusion decoder (host, "
                                       "engine.py `_finish_block`)",
    "serving.diffusion.tokens_committed": "tokens requests were given by "
                                          "committed blocks: a block "
                                          "without a prompt's tail and "
                                          "without what lies past "
                                          "max_new_tokens",
    "serving.diffusion.blocks_committed": "slots x commits",
    "serving.diffusion.commit_forwards": "forwards in which at least one "
                                         "slot committed",
    "serving.moe_layer_steps": "expert layers x decode steps (host)",
    "serving.moe_routed_rows": "active slots x experts per token, a "
                               "layer-step (host)",
    "serving.moe_kernel_layer_steps": "of serving.moe_layer_steps those "
                                      "whose grouped matmuls ran through "
                                      "the Pallas kernel `grouped_matmul` "
                                      "(host: all of a step's or none, by "
                                      "the gauge serving.moe_grouped_kernel)",
    "serving.prefix_evict_visits": "radix tree nodes eviction examined "
                                   "(entries popped off its LRU heap: "
                                   "`block_pool.RadixPrefixCache.evict`); "
                                   "beside serving.prefix_evicted_blocks, "
                                   "the visits a freed block",
    "serving.prefill_block_writes": "prefill calls whose full layers wrote "
                                    "the prompt's K/V rows into the pools "
                                    "as whole blocks (host, from the "
                                    "bucket: `CacheSpec.writes_blocks`)",
    "serving.linear_layer_steps": "state layers (linear attention: a "
                                  "recurrent state a slot) x decode steps "
                                  "(host)",
    "serving.linear_kernel_layer_steps": "of serving.linear_layer_steps "
                                         "those whose state update ran "
                                         "through the Pallas kernel "
                                         "`gated_delta_step` (host: all of "
                                         "a step's or none, by the gauge "
                                         "serving.linear_state_kernel)",
    "serving.moe_experts_hit": "distinct held experts given >= 1 row, "
                               "summed over the expert layers of each "
                               "decode step: counted in the executable, "
                               "read in the transfer that brings the tokens",
}

# Gauges set once at engine build, beside `serving.kv_pool_row_major` and
# `serving.paged_keys_per_program` (engine.py `_note_pool_layout`).
GAUGES = {
    "serving.kv_layers_full": "layers whose pools keep every row of a slot",
    "serving.kv_layers_window": "layers whose pools keep a ring of the last "
                                "`window` rows a slot",
    "serving.kv_window_blocks": "blocks of one slot's ring in a window "
                                "layer: window / block_size + 1 (0: none)",
    "serving.moe_grouped_kernel": "what the dropless expert layers' grouped "
                                  "matmuls run through at a decode step: "
                                  "pallas / interpret / xla "
                                  "(`pallas_ops.select_grouped_kernel`, "
                                  "following the paged kernel; the reason "
                                  "in `engine.stats()`)",
    "serving.linear_state_kernel": "what the state layers' decode update "
                                   "runs through: pallas / interpret / xla "
                                   "(`pallas_ops.select_gated_delta_kernel`"
                                   ", following the paged kernel; the "
                                   "reason in `engine.stats()`)",
    "serving.kv_state_bytes": "bytes of the state layers' pools: every "
                              "slot's recurrent state and convolution tail "
                              "(0: no state layer)",
}

# Mosaic kernels (`pl.pallas_call(name=...)`): the custom call's HLO
# instruction is named from it, whoever calls the kernel.
KERNELS = {
    "flash_fwd": "flash attention forward -> (out, lse)",
    "flash_bwd_dq": "flash attention backward -> dQ",
    "flash_bwd_dkv": "flash attention backward -> (dK, dV)",
    "paged_attention": "paged decode/verify attention over the block pool "
                       "(every earlier key; grouped queries or not)",
    "paged_attention_window": "the same kernel over a window layer's ring: "
                              "only the blocks that hold the last `window` "
                              "keys",
    "mla_paged_attention": "absorbed latent (MLA) decode attention over "
                           "the latent block pool, all heads a block",
    "grouped_matmul": "the served expert layer's matmuls at a decode step "
                      "(nn/moe/dropless.py): each hit expert's weight "
                      "tiles once, its few sorted rows against them",
    "flash_prefill": "the prompt span's attention over the slot's rows in "
                     "the block pool (models/gpt.py's prefill): online "
                     "softmax a block of query rows, all heads",
    "gated_delta_step": "a state layer's decode update (models/qwen3_next.py"
                        "): each slot's gated delta rule state read once "
                        "and written once in place, a group of value heads "
                        "a program",
}

# Jitted steps: the function's name, so the `XLA Modules` event and the
# host's `PjitFunction(...)` read `jit_<name>` / `<name>`.
EXECUTABLES = {
    "train_step": "jit.TrainStep: forward, backward, optimizer update",
    "serving_prefill": "GenerationEngine: one prompt window at a bucket",
    "serving_decode": "GenerationEngine: one token for every slot",
}

# `jax.named_scope` regions inside those executables: in every op's
# `op_name` in the HLO, so xprof shows them when the trace holds the HLO
# proto; an op event of a trace without it carries no `op_name`.
SCOPES = {
    "kv_write": "models/gpt.py: the new K/V rows scattered into the pool",
    "attn": "models/gpt.py: LayerNorm, QKV, attention, output projection",
    "mlp": "models/gpt.py: LayerNorm, FFN",
    "lm_head": "models/gpt.py, serving/engine.py: the tied LM head's "
               "logits, and the cross-entropy in training",
    "sampling": "serving/sampling.py: top-k/top-p filter and Gumbel argmax",
    "optimizer": "optimizer.step(): clip, decay and the update rule",
    "hc_mix": "models/xing4.py: a sublayer's hyper-connection coefficients "
              "(stream norm, maps, Sinkhorn) and the stream mixing",
    "mla_absorb": "models/xing4.py: decode's absorbed projections, "
                  "q_nope W_uk before and o_lat W_uv after the kernel",
    "moe_router": "nn/moe/dropless.py: sigmoid scores and biased top-k (or "
                  "softmax probabilities and top-k), the sort of the routed "
                  "rows by expert",
    "moe_experts": "nn/moe/dropless.py: the grouped matmuls of the held "
                   "experts and the weighted sum back to tokens (and the "
                   "shared expert where its output is summed)",
    "moe_shared": "nn/moe/dropless.py: the shared experts where their "
                  "outputs are averaged (models/cohere2_moe.py)",
    "attn_window": "models/cohere2_moe.py: a sliding-window layer's "
                   "attention: QKV, rotary, the ring write, the kernel or "
                   "the prefill walk bounded by the window, output "
                   "projection",
    "attn_full": "models/cohere2_moe.py: a full (no-position) layer's "
                 "attention: QKV, the pool write, the kernel or the prefill "
                 "walk over every earlier key, output projection",
    "block_attention": "models/sdar_moe.py: a block-causal layer's "
                       "attention: RMSNorm, QKV, query/key norm, rotary, "
                       "the pool write, the kernel over a block span or "
                       "the prefill walk, output projection",
    "unmask_select": "serving/sampling.py: which masked positions of a "
                     "block a denoise forward unmasks, from the sampled "
                     "ids' confidences",
    "gated_delta": "models/qwen3_next.py: a linear-attention layer: its "
                   "projections, the short convolution, the gated delta "
                   "rule (the kernel at a decode step, the chunks at a "
                   "prompt), the gated norm, output projection",
    "gated_delta_chunks": "models/qwen3_next.py: a prompt's gated delta "
                          "rule by chunks of 64 (the intra-chunk triangular "
                          "solve, the scan of the state over chunks)",
    "short_conv": "models/qwen3_next.py: the causal depthwise convolution "
                  "over q, k, v and its tail's shift",
    "attn_gate": "models/qwen3_next.py: a gated full-attention layer: "
                 "projections, q/k norms, partial rotary, the pool write, "
                 "the kernel or the prefill walk, the sigmoid output gate, "
                 "output projection",
    "moe_shared_gate": "nn/moe/dropless.py: the sigmoid gate a token over "
                       "the shared expert's output",
}


def device_name(name):
    """`name`, checked against the device side of the table."""
    if name not in KERNELS and name not in EXECUTABLES \
            and name not in SCOPES:
        raise ValueError(f"{name!r} is not a device name of "
                         "paddle_tpu.profiler.spans' table")
    return name


def scope(name):
    """`jax.named_scope` with a name from `SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not in paddle_tpu.profiler.spans."
                         "SCOPES")
    return jax.named_scope(name)


def named(fn, name):
    """A plain function called `name` (one of `EXECUTABLES`) around `fn`,
    for `jax.jit` to name the executable after."""
    device_name(name)

    def call(*args):
        return fn(*args)

    call.__name__ = call.__qualname__ = name
    return call


class HostSpan:
    """What every span does; `span` adds the name check and the counters,
    `RecordEvent` the begin/end stack."""

    __slots__ = ("name", "trace_id", "_ann", "_t0", "_r0")

    def __init__(self, name, trace_id=None):
        self.name = name
        self.trace_id = trace_id

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._r0 = tracing.clock() if tracing.enabled() else 0.0
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(None, None, None)
        if self._r0:
            tracing.add_span(self.trace_id, self.name, self._r0,
                             tracing.clock())
        if timeline.active():
            t0 = self._t0 / 1e9
            timeline.add_span(self.name, t0, t0 + ns / 1e9)
        self._count(ns)
        return False

    def _count(self, ns):
        pass


class span(HostSpan):
    """``with span("serving.decode_step"):`` — see the module docstring.
    Refuses a name that is not in `SPANS`."""

    __slots__ = ("_keys",)
    _table: dict = {}

    def __init__(self, name, trace_id=None):
        keys = self._table.get(name)
        if keys is None:
            if name not in SPANS:
                raise ValueError(f"{name!r} is not in paddle_tpu.profiler."
                                 "spans.SPANS")
            sc, _, short = name.partition(".")
            keys = self._table[name] = (
                registry.scoped_counters(sc, {short + "_ns": 0,
                                              short + "_n": 0}),
                short + "_ns", short + "_n",
                (short, sc) if name in _TIMED else None)
        self._keys = keys
        self.name = name
        self.trace_id = trace_id

    def _count(self, ns):
        d, k_ns, k_n, timed = self._keys
        d[k_ns] += ns
        d[k_n] += 1
        if timed:
            registry.timing(timed[0], ns / 1e9, scope=timed[1])


class _GcWatch:
    """`host.gc`: Python's collector says when it starts and stops
    (`gc.callbacks`), on the thread it runs on; collections never nest, so
    one open span is all there is. Counted by its users, so that two
    servers of one process are one callback."""

    def __init__(self):
        self._lock = threading.Lock()
        self._users = 0
        self._open = None
        self._gen2 = registry.scoped_counters("host", {"gc_gen2_n": 0})

    def __call__(self, phase, info):
        if phase == "start":
            self._open = span("host.gc")
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
            self._gen2["gc_gen2_n"] += info["generation"] == 2

    def acquire(self):
        with self._lock:
            self._users += 1
            if self._users == 1:
                gc.callbacks.append(self)

    def release(self):
        with self._lock:
            self._users -= 1
            if self._users == 0:
                gc.callbacks.remove(self)
                self._open = None


_gc_watch = _GcWatch()
watch_gc = _gc_watch.acquire      # pair every call with one of unwatch_gc
unwatch_gc = _gc_watch.release
