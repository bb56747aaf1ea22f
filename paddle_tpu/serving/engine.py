"""paddle_tpu.serving.engine — paged-KV generation engine for decoders.

The continuous-batching design follows Orca (Yu et al., OSDI'22): the unit
of scheduling is one decode ITERATION, not one request, so finished slots
are evicted and refilled mid-flight without touching their neighbors. The
cache is vLLM-style paged (Kwon et al., SOSP'23), adapted to XLA's
static-shape world: per layer ONE fixed-shape block pool
``[num_blocks, block_size, heads * head_dim]`` (heads merged: the form a
TPU stores row by row, ``ops/kv_pool.py``) for K and for V — or, for a
decoder whose layers cache one latent row a token (MLA), one pool
``[num_blocks, block_size, W]`` — addressed through per-slot
int32 block tables — an indirection gather per attention read buys
(a) per-request memory proportional to ``prompt + max_new_tokens`` instead
of a full ``max_seq_len`` slab, and (b) prefix sharing: a radix tree over
block-aligned prompt chunks (RadixAttention-style) hands immutable prefix
blocks to new requests by refcount, so a system prompt shared by thousands
of requests is prefilled ONCE (``serving.prefix_hits`` /
``serving.prefix_hit_tokens`` count the saved work).

Compile discipline (the whole point on a TPU):

* prefill compiles once per bucket — the input is the ``[1, L]``
  bucket-padded SUFFIX of the prompt (the part after the cached prefix);
  prompt length, prefix length and the block table are data, never shapes,
  so cold prefills and prefix hits share one executable per bucket;
* the decode step compiles exactly once — fixed ``[max_batch, 1]`` query,
  in-place row writes into the donated pool at block-table-derived
  (block, row) pairs, valid-length masking instead of shape changes;
* every per-request difference (current length, sampling config, RNG key,
  activity, block table) is an ARRAY argument, so no workload mix can
  retrace.

Sharded decode (ISSUE 10): pass ``mesh=`` (see
``distributed.spmd.serving_mesh``) and the engine places weights by their
``sharding_spec`` annotations (``param_pspec``, same derivation as the
SPMD train step) and the KV pools head-sharded over the ``'mp'`` axis —
GSPMD partitions the compiled steps, so models larger than one chip serve
with zero code changes elsewhere. All host-built step inputs are placed
mesh-replicated; the replay fast path below is layout-agnostic.

The engine tracks call signatures itself, mirroring ``jax.jit``'s aval
cache: any signature first-seen bumps ``serving.prefill_compiles`` /
``serving.decode_compiles`` and lands a ``serving_prefill_compile`` /
``serving_decode_compile`` event in the profiler explainer ring — a decode
retrace storm is loud (``profiler.explain()``) instead of a silent 100x
slowdown. Host spans (``serving.prefill`` / ``serving.decode_step`` /
``serving.decode_sync``, names from ``profiler.spans.SPANS``) and
``serving.*`` counters/timings ride the same observability stack as the
training runtime.

What the engine asks of a decoder (``model`` or ``model.gpt``), and nothing
else: ``kv_cache_spec()`` — the rows its layers cache a token
(``ops.kv_pool.CacheSpec``); ``serving_head()`` — the weight the logits
come from (tied or not) and ``logits(hidden, weight)``; ``max_positions``;
a ``forward`` that takes the paged-cache arguments and returns the
final-normed hidden states with the written pools; optionally
``step_counters()`` — small device-side counts of the last forward (the
decode step hands them over in the same transfer as the tokens) and
``host_step_counts(n_active)``. What a cache kind does not support (a
latent cache: a ``mesh``, the KV handoff, spec decode; a cache with window
layers, whose state is a ring of the last ``window`` rows a slot: a
``mesh``, prefix sharing, the KV handoff, chunked prefill, spec decode) is
refused with a ``TypeError`` naming the feature and the kind, counted in
``serving.cache_refusals`` and explained (``cache_feature_refused``); prefix
sharing, which is no call but a default, is switched off at engine build
and said so the same way.

Two kinds of cache state side by side (``CacheSpec.windows``): a full
layer's pools are the above; a window layer's hold ``1 + max_batch *
ring`` blocks, slot s owning blocks ``1 + s * ring ..`` for good. A slot's
table row is the full layers' columns followed by its ring columns; each
layer's forward gets its own part (``CacheSpec.layer_tables``). Admission
budgets the full layers' blocks; the rings need no budget.

Slot lifecycle: free → (admission: blocks allocated/shared, suffix
prefill, first token sampled) → active (each decode step appends one row
at the slot's own cursor, always inside its OWN blocks — shared prefix
blocks are never written after insertion) → released (blocks decref'd
back to the pool; the block table row is zeroed so the lane's masked
garbage writes land in reserved block 0). Inactive slots still flow
through the decode step — their lane computes garbage that nothing reads —
because a data-dependent batch size would be a shape change.
"""
from __future__ import annotations

import itertools

import numpy as np
import jax
import jax.numpy as jnp

from ..core import autograd as _ag
from ..core import lazy as _lazy
from ..core import random as _random
from ..core.tensor import Tensor
from ..ops import kv_pool as _kv_pool
from ..profiler import span as _span
from ..profiler import explainer as _explain
from ..profiler import registry as _registry
from ..profiler import spans as _spans
from ..profiler import tracing as _tracing
from ..testing import faults as _faults
from . import sampling as _sampling
from .block_pool import BlockPool, PagePoolExhausted, RadixPrefixCache

_counters = _registry.scoped_counters("serving", {
    "prefills": 0, "decode_steps": 0, "tokens_generated": 0,
    "active_slot_steps": 0, "prefill_compiles": 0, "decode_compiles": 0,
    "bucket_promotions": 0, "weight_swaps": 0, "reprimes": 0,
    "prefix_hits": 0, "prefix_misses": 0, "prefix_hit_tokens": 0,
    "prefix_inserted_blocks": 0, "prefix_evicted_blocks": 0,
    "kv_blocks_hwm": 0, "handoff_exports": 0, "handoff_imports": 0,
    "handoff_stale": 0, "chunked_prefills": 0, "prefill_chunks": 0,
    "kv_tokens_read": 0, "kv_window_rows_read": 0,
    "cache_refusals": 0, "moe_layer_steps": 0, "moe_routed_rows": 0,
    "moe_experts_hit": 0, "moe_kernel_layer_steps": 0, "sample_topk_steps": 0, "sample_topp_steps": 0,
    "prefill_flash_calls": 0, "diffusion.slot_forwards": 0,
    "diffusion.tokens_committed": 0, "diffusion.blocks_committed": 0,
    "diffusion.commit_forwards": 0})

# Decode replay fast path (ISSUE 9, same machinery as lazy.ReplayStep):
# in the steady window a decode iteration is one fingerprint check (the
# prebuilt device-side arg tuple IS the fingerprint — every slot/weight/
# executable mutation clears it) plus one executable call; the per-slot
# state advances ON DEVICE inside the step instead of being re-uploaded
# from host numpy every iteration. Block tables ride the same tuple as
# device-resident step inputs (they only change at batch boundaries,
# which rebuild anyway). A periodic audit cross-checks the device copies
# against the host mirrors.
_fp_counters = _registry.scoped_counters("fastpath", {
    "decode_fast_steps": 0, "decode_rebuilds": 0, "decode_audit_runs": 0,
    "decode_demotions": 0})


class WeightSwapError(RuntimeError):
    """A proposed weight swap does not fit the running engine (missing or
    extra names, shape mismatch, incompatible device placement). Raised
    BEFORE any weight is replaced — the engine keeps serving the old
    weights, and the KV cache is never touched."""


class StaleHandoffError(RuntimeError):
    """A handed-off KV payload was exported under a different weight
    generation than this engine is serving — adopting it would decode
    new weights over old-weight prompt KV (and publish stale blocks
    into the prefix cache). The scheduler answers this by re-prefilling
    the prompt locally under the CURRENT weights, which is exactly what
    a monolithic pod that swapped before the request would have done."""


class FatalEngineError(RuntimeError):
    """Non-transient engine death (device lost, injected replica kill).
    The scheduler's transient-retry path does NOT swallow this: it
    propagates to the server loop, which marks the replica dead so a
    supervisor can restart it and re-queue its requests."""


def _note_pool_layout(pools, cache, keys_per_program, ring, pool_bytes):
    """Set the gauge ``serving.kv_pool_row_major`` from the layout the
    device gave a freshly built pool: 1 when it is stored row by row (the
    form the in-place row write and the paged kernel take as it is), 0
    when a jax / libtpu upgrade chose another — then every step pays
    whole-pool relayouts again, and the explainer event says which layout
    it was. None (no gauge) where jax does not expose the layout. Beside
    it the gauge ``serving.paged_keys_per_program``: the keys one program
    of the engine's paged kernel folds (0: the gather path, no kernel)."""
    _registry.gauge_set("serving.paged_keys_per_program", keys_per_program)
    windows = cache.window_layers()
    _registry.gauge_set("serving.kv_layers_window", len(windows))
    _registry.gauge_set("serving.kv_layers_full",
                        len(cache.layers) - len(windows))
    _registry.gauge_set("serving.kv_window_blocks", ring)
    # one pool of each kind of layer says it for its kind
    kinds = {}
    for i, pool in enumerate(pools):
        kinds.setdefault(cache.windows[i], pool)
    orders = {w: _kv_pool.device_layout(p) for w, p in kinds.items()}
    if any(o is None for o in orders.values()):
        return None
    row_major = int(all(o == tuple(range(kinds[w].ndim))
                        for w, o in orders.items()))
    _registry.gauge_set("serving.kv_pool_row_major", row_major)
    said = "; ".join(
        ("" if len(kinds) == 1 else
         f"{'a full' if w is None else 'a window'} layer's ")
        + f"pool {tuple(p.shape)} {p.dtype} is stored major-to-minor "
        f"{orders[w]}" for w, p in kinds.items())
    _explain.record(
        "kv_pool_layout", op="kv_pool", row_major=bool(row_major),
        cache_kind=cache.kind, row_width=cache.row_width(),
        paged_keys_per_program=keys_per_program,
        window=cache.window, ring_blocks=ring, pool_bytes=pool_bytes,
        why=(f"{cache.describe()}; {pool_bytes / 1e9:.2f} GB of pools"
             + (f" (a window layer's ring is {ring} blocks a slot)"
                if ring else "")
             + f"; {said}: "
             + ("row-major, rows are written in place" if row_major else
                "NOT row-major — row writes and the paged kernel will "
                "relayout the whole pool every step")
             + f"; the paged kernel folds {keys_per_program} keys a "
             "program"))
    return row_major


def _pool_record(layer, name, pool, heads, mesh):
    """One KV pool in ``describe_sharding()``: its device shape
    ``[num_blocks, block_size, H*Dh]``, the H that the merged axis holds
    (what tools/sharding_lint.py divides by 'mp') and its placement."""
    from ..core.lazy import _spec_repr

    return {"layer": layer, "pool": name,
            "shape": [int(d) for d in pool.shape], "heads": int(heads),
            "dtype": str(pool.dtype), "bytes": int(pool.nbytes),
            "spec": _spec_repr(pool.sharding) if mesh is not None else None}


def _default_buckets(max_seq_len):
    """Powers-of-two ladder up to max_seq_len (always included): few enough
    that prefill compiles stay cheap, dense enough that short prompts don't
    pay full-length attention."""
    out = []
    b = 16
    while b < max_seq_len:
        out.append(b)
        b *= 2
    out.append(max_seq_len)
    return tuple(out)


class GenerationEngine:
    """Wraps a decoder LM (GPT first) with a paged block-pool KV cache and
    compiled prefill/decode steps. The engine owns device compute,
    per-slot state and the block/prefix bookkeeping; request lifecycle
    (stop conditions, queueing, block-budget admission) lives in
    ``serving.scheduler``. Not thread-safe — drive it from one thread
    (``serving.GenerationServer`` does).
    """

    def __init__(self, model, max_batch_size=4, buckets=None,
                 max_seq_len=None, rng_seed=None, block_size=16,
                 num_blocks=None, mesh=None, paged_kernel=None):
        gpt = getattr(model, "gpt", model)
        lacks = [a for a in ("kv_cache_spec", "serving_head",
                             "max_positions") if not hasattr(gpt, a)]
        if lacks:
            raise TypeError(
                "GenerationEngine serves a decoder that answers "
                "kv_cache_spec() (the rows its layers cache a token), "
                "serving_head() (the head's weight and its logits) and "
                "max_positions, and whose forward takes the paged-cache "
                "arguments (models.GPTModel, models.Xing4Model, "
                "models.Cohere2MoeModel); "
                f"{type(model).__name__} lacks {lacks}")
        self._model = model
        self._gpt = gpt
        self._cache = gpt.kv_cache_spec()
        # how the decoder generates is asked of the decoder, as its cache
        # is: None (no such answer) is left to right, a token a step; a
        # block-diffusion decoder (models/sdar_moe.py) answers its block
        # length, denoise forwards, strategy, threshold and mask id
        self._gen = getattr(gpt, "generation_spec", lambda: None)()
        if mesh is not None:
            self.require_autoregressive(
                "GenerationEngine(mesh=...)",
                "a block step's kernel call has no per-shard route")
        if mesh is not None and self._cache.kind != "heads":
            self._refuse(
                f"GenerationEngine(mesh=...) is not supported for a "
                f"{self._cache.kind!r} cache: a latent row is shared by "
                "every head, so there is no head axis to place over 'mp'")
        if mesh is not None:
            self.require_full_layers("GenerationEngine(mesh=...)")
        self.max_seq_len = int(max_seq_len or gpt.max_positions)
        if self.max_seq_len > gpt.max_positions:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the model's "
                f"position-embedding range {gpt.max_positions}")
        self.max_batch_size = int(max_batch_size)
        if self.max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        if buckets is None:
            buckets = _default_buckets(self.max_seq_len)
        self.buckets = tuple(sorted(
            {int(b) for b in buckets if 0 < int(b) <= self.max_seq_len}))
        if not self.buckets:
            raise ValueError(
                f"no usable prompt buckets in {buckets!r} "
                f"(need 0 < bucket <= max_seq_len={self.max_seq_len})")

        # paged-KV geometry: each slot addresses at most blocks_per_slot
        # blocks through its table row; the pool defaults to capacity
        # parity with the old contiguous layout (every slot CAN fill to
        # max_seq_len) plus the reserved garbage block — shrink
        # num_blocks to oversubscribe and lean on prefix sharing +
        # admission backpressure
        self.block_size = int(block_size)
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.blocks_per_slot = -(-self.max_seq_len // self.block_size)
        if self._gen is not None:
            L = self._gen.block_length
            if self._cache.kind != "heads" or self._cache.window is not None \
                    or self.block_size % L or self.max_seq_len % L:
                raise ValueError(
                    f"a decoder that generates blocks of {L} needs a "
                    "'heads' cache with no window layers, and a block_size "
                    f"({self.block_size}) and a max_seq_len "
                    f"({self.max_seq_len}) that are whole blocks of it (a "
                    "block's rows never straddle a slot's last pool block)")
        if num_blocks is None:
            num_blocks = 1 + self.max_batch_size * self.blocks_per_slot
        self.pool = BlockPool(num_blocks)
        self.prefix_cache = RadixPrefixCache(self.pool, self.block_size)
        # window layers keep a ring of their last rows a slot: the blocks
        # of a slot's ring are its own for good (ops/kv_pool.py), and a
        # prefix block shared by refcount has no ring to go with it —
        # such a decoder shares no prefixes
        self._ring = self._cache.ring_blocks(self.block_size)
        self._ring_table = _kv_pool.ring_table(self.max_batch_size,
                                               self._ring)
        self._prefix_sharing = not self._ring and self._gen is None
        if self._gen is not None:
            self._note_refusal(
                "prefix sharing (the radix prefix cache) is off for a "
                "decoder that generates by diffusion over blocks: its "
                "prefill attends to the call's own rows only, so a prompt "
                "is prefilled whole by the call that brings it")
        if self._ring:
            self._note_refusal(
                "prefix sharing (the radix prefix cache) is off for a "
                f"cache with window layers (layers "
                f"{self._cache.window_layers()} keep a ring of the last "
                f"{self._cache.window} rows a slot): a shared prefix "
                "block has no ring rows to go with it, and prefill "
                "attends to the call's own rows only")

        # generation is inference: dropout off, or padded lanes would
        # perturb nothing but sampled RNG streams would diverge
        if hasattr(model, "eval"):
            model.eval()

        # params/buffers bound by name once; the pure step fns take the
        # arrays as arguments (StaticFunction's state-swap idiom), so a
        # weight update never needs an engine rebuild — same avals, same
        # compiled steps
        self._state = dict(gpt.state_dict())
        self._names = list(self._state)
        wt, self._head_logits = gpt.serving_head()
        self._head_idx = next(
            i for i, n in enumerate(self._names) if self._state[n] is wt)
        self._dtype = wt._data.dtype
        # small device-side counts a decode step hands over with its
        # tokens (an expert layer's experts hit), by name
        self._step_counter_names = tuple(
            getattr(gpt, "step_counter_names", ()))

        # mesh-sharded decode: weights placed by their sharding_spec
        # annotations (same param_pspec derivation as the SPMD train
        # step), KV pools head-sharded over 'mp', every host-built step
        # input replicated — GSPMD partitions the compiled steps
        self._mesh = mesh
        self._repl = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..distributed import spmd as _spmd

            self._repl = NamedSharding(mesh, PartitionSpec())
            for n in self._names:
                t = self._state[n]
                arr = _lazy.force(t._data)
                pspec = _spmd.param_pspec(
                    getattr(t, "sharding_spec", None), mesh,
                    tuple(arr.shape))
                t._data = jax.device_put(arr, NamedSharding(mesh, pspec))

        # paged-attention kernel choice (ISSUE 14): resolved ONCE here —
        # "pallas" (compiled TPU kernel), "interpret" (same kernel body
        # through the Pallas interpreter: CPU CI's parity route) or
        # "xla" (PR 9 gather path). A static per-engine constant closed
        # over by the jitted steps, so the replay fast path sees ONE
        # stable executable per (bucket, kernel) and a mid-flight kernel
        # flip is impossible by construction. Decode + spec verify ride
        # it; so does the prompt span of a decoder whose cache path reads
        # the pools for it (`prefill_reads_pools`, ISSUE 36: the gather
        # path's float32 [H, L, S] scores were 88 of a 147 ms prefill) —
        # `_prefill_kernel` below, the same kind unless a bucket or the
        # geometry refuses, and then "xla" loudly.
        from ..ops import pallas_ops as _pallas_ops

        if self._cache.kind == "latent":
            self._paged_kernel, self._paged_kernel_reason = \
                _pallas_ops.select_mla_paged_kernel(
                    paged_kernel, row_width=self._cache.row_width(),
                    block_size=self.block_size, dtype=self._dtype)
        else:
            heads, head_dim = self._cache.layers[0]
            self._paged_kernel, self._paged_kernel_reason = \
                _pallas_ops.select_paged_kernel(
                    paged_kernel, head_dim=head_dim,
                    block_size=self.block_size, dtype=self._dtype,
                    mesh=mesh, num_heads=heads)
        # per-shard fused route (ISSUE 16): when the fused kernel
        # survived mesh resolution, decode calls it through shard_map
        # with head-sharded q/pools — a static closure constant like the
        # kernel kind itself, so the (bucket, kernel, mesh) executable
        # set stays exactly one deep. xla (or indivisible heads, which
        # select demotes to xla) leaves this None and GSPMD partitions
        # the gather path as before.
        self._paged_mesh = mesh if (
            mesh is not None
            and self._paged_kernel in ("pallas", "interpret")) else None
        # the prompt span's read, resolved once like the kernel it follows
        if self._cache.kind == "heads" and getattr(
                gpt, "prefill_reads_pools", False):
            self._prefill_kernel, self._prefill_kernel_reason = \
                _pallas_ops.select_prefill_kernel(
                    self._paged_kernel, spans=self.buckets,
                    head_dim=head_dim, block_size=self.block_size,
                    dtype=self._dtype, num_heads=heads,
                    table_cols=self.blocks_per_slot, mesh=mesh)
        else:
            self._prefill_kernel, self._prefill_kernel_reason = (
                "xla", "the decoder's prefill is its own forward's")
        _registry.gauge_set("serving.prefill_kernel", self._prefill_kernel)
        # the dropless expert layers' grouped matmuls, resolved the same
        # way from the decode step's shapes (a block decoder's step brings
        # a block of rows a slot); the forward hands the kind down to the
        # layers at trace time, where each call's own shape decides
        moe_layers = [l for l in gpt.sublayers()
                      if hasattr(l, "grouped_shapes")]
        step_tokens = self.max_batch_size * (
            self._gen.block_length if self._gen is not None else 1)
        self._moe_kernel, self._moe_kernel_reason = \
            _pallas_ops.select_grouped_kernel(
                self._paged_kernel, dtype=self._dtype,
                shapes=[s for l in moe_layers
                        for s in l.grouped_shapes(step_tokens)])
        self._moe_kernel_arg = {"moe_kernel": self._moe_kernel} \
            if moe_layers else {}
        _registry.gauge_set("serving.moe_grouped_kernel", self._moe_kernel)
        if mesh is not None:
            # telemetry for the stats_dump "mesh serving" section
            _registry.gauge_set("serving.mesh.mp",
                                _pallas_ops._mesh_mp_degree(mesh))
            _registry.gauge_set("serving.mesh.paged_kernel",
                                self._paged_kernel)
            _registry.gauge_set("serving.mesh.paged_kernel_sharded",
                                int(self._paged_mesh is not None))

        # the pools, in the device form ops/kv_pool.py owns:
        # [num_blocks, block_size, H*Dh] per layer, for K and for V
        # (heads over 'mp' on a mesh they divide); a latent cache has
        # one pool a layer, in _k, and _v stays empty
        self._kv_heads = [self._cache.heads(i)
                          for i in range(len(self._cache.layers))]
        self._k, self._v = self._cache.allocate(
            self.pool.num_blocks, self.block_size, self._dtype, mesh,
            slots=self.max_batch_size)
        if self._paged_kernel == "xla":
            self._paged_keys_per_program = 0
        elif self._cache.kind == "latent":
            self._paged_keys_per_program = _pallas_ops.mla_keys_per_program(
                self.block_size, self.blocks_per_slot)
        else:
            shards = _pallas_ops._mesh_mp_degree(self._paged_mesh)
            self._paged_keys_per_program = \
                _pallas_ops.paged_keys_per_program(
                    self.block_size, heads // shards, head_dim, self._dtype,
                    self.blocks_per_slot, self._cache.q_per_kv)
        self._kv_pool_bytes = sum(int(p.nbytes) for p in self._k + self._v)
        self._kv_row_major = _note_pool_layout(
            self._k, self._cache, self._paged_keys_per_program, self._ring,
            self._kv_pool_bytes)

        # host-side slot state, mirrored into the decode step as arrays
        B = self.max_batch_size
        self._active = np.zeros(B, bool)
        self._cur_lens = np.zeros(B, np.int32)
        self._last_tokens = np.zeros(B, np.int32)
        self._gen_idx = np.zeros(B, np.int32)
        self._temps = np.zeros(B, np.float32)
        self._top_ks = np.zeros(B, np.int32)
        self._top_ps = np.ones(B, np.float32)
        self._keys = np.zeros((B, 2), np.uint32)
        if self._gen is not None:
            # a block decoder's slot: the block at its cursor — its tokens,
            # which of them are still masked (STATE, never a comparison
            # with the mask id: a prompt may hold that id), the denoise
            # forwards it has had — and, for the host alone, the prompt's
            # tail that opened the slot's first block and the tokens the
            # request may still be given
            L = self._gen.block_length
            self._blk_tokens = np.full((B, L), self._gen.mask_token_id,
                                       np.int32)
            self._blk_masked = np.ones((B, L), bool)
            self._blk_steps = np.zeros(B, np.int32)
            self._blk_skip = np.zeros(B, np.int32)
            self._blk_budget = np.zeros(B, np.int64)
        # a decode iteration's per-slot arguments after the weights and the
        # pools, by the name of their host mirror (`_decode_rebuild` puts
        # them on the device, `_audit_fast` compares), and which of them
        # the pure step hands back advanced
        if self._gen is None:
            self._slot_state = (
                "_last_tokens", "_cur_lens", "_keys", "_gen_idx", "_temps",
                "_top_ks", "_top_ps", "_active", "_block_tables")
            self._slot_stepped = (0, 1, 3)
        else:
            self._slot_state = (
                "_blk_tokens", "_blk_masked", "_blk_steps", "_cur_lens",
                "_keys", "_temps", "_top_ks", "_top_ps", "_active",
                "_block_tables")
            self._slot_stepped = (0, 1, 2, 3)
        # per-slot block tables: row of physical block ids, zero-padded
        # (block 0 = reserved garbage block); _slot_blocks holds the ids
        # each slot has a pool reference on
        # a cache with window layers: the slot's ring columns behind them
        self._block_tables = np.zeros((B, self.blocks_per_slot + self._ring),
                                      np.int32)
        self._slot_blocks = [[] for _ in range(B)]
        # chunked prefill (ISSUE 12): slot -> in-progress admission state.
        # A mid-prefill slot is RESERVED — neither free (its blocks are
        # allocated, chunks are landing) nor active (it must not join the
        # decode batch until its first token is sampled).
        self._mid_prefill: dict = {}
        # fleet tracing (ISSUE 18): slot -> trace id, derived from the
        # request seed at admission (or carried inside a KV-handoff
        # payload) so engine-level spans tag the request they serve
        self._slot_trace: dict = {}

        # seed-determinism root: one split of the global generator, so
        # paddle_tpu.seed(s) pins every sampled token this engine produces.
        # An explicit rng_seed pins the base key independently of global
        # generator history — two engines built with the same rng_seed
        # sample identically, which is what lets a supervisor's restarted
        # replica REPLAY a dead replica's requests bitwise (idempotent by
        # request seed)
        if rng_seed is None:
            self._base_key = _random.split_key()
        else:
            self._base_key = jax.random.PRNGKey(int(rng_seed))
        self._seed_counter = itertools.count()

        # donate the KV pools (args 1, 2) so the per-step row write is
        # in place on device: with the donation AND the pool's row-major
        # form the step holds no pool-sized copy at all (without the
        # donation XLA copies each pool once a step; with a 4-D pool it
        # relaid each one out twice, PERF.md PR 28). Accelerator only:
        # XLA-CPU intermittently SIGABRTs with many donated executables
        # co-resident in one process (hybrid_engine._compile has the
        # same gate for the same reason).
        self._donate = (1, 2) if jax.devices()[0].platform != "cpu" else ()
        self._prefill_jit = jax.jit(
            _spans.named(self._prefill_pure, "serving_prefill"),
            donate_argnums=self._donate)
        self._decode_jit = self._jit_decode()
        self._seen_sigs: set = set()

        # decode fast path state: cached weight-array tuple (invalidated
        # by swap_weights) and the prebuilt device-side slot-state args
        # (invalidated by ANY prefill/release/swap/reprime — those are
        # the batch-boundary events, so the steady decode loop between
        # them runs with zero host->device uploads and no radar walk)
        self._state_tuple = None
        self._fast = None
        self._decode_since_audit = 0
        self._audit_every = _lazy.AUDIT_EVERY
        # what a decode step adds to the host's counters besides its own
        # (an expert layer's layer-steps and routed rows)
        self._host_step_counts = getattr(gpt, "host_step_counts",
                                         lambda n_active: {})

    def _put(self, x):
        """Host → device for step inputs: plain asarray single-chip,
        mesh-replicated placement when sharded (a single-device-committed
        input cannot join mesh-committed weights in one jit)."""
        if self._repl is None:
            return jnp.asarray(x)
        return jax.device_put(jnp.asarray(x), self._repl)

    # ------------------------------------------------------------- slots --
    def free_slots(self):
        return [i for i in range(self.max_batch_size)
                if not self._active[i] and i not in self._mid_prefill]

    def active_slots(self):
        return [i for i in range(self.max_batch_size) if self._active[i]]

    def release(self, slot):
        """Evict a finished request: drop the slot's pool references and
        zero its table row (its lane now scribbles into the reserved
        garbage block). Shared prefix blocks stay alive through the radix
        tree's own reference — only truly dead blocks return to the free
        list. A mid-chunked-prefill slot releases its staged blocks the
        same way (deadline/cancel before the first token)."""
        st = self._mid_prefill.pop(slot, None)
        if st is not None:
            self.pool.decref(st["table_ids"])
            self._note_pool()
        if self._slot_blocks[slot]:
            self.pool.decref(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
            self._note_pool()
        self._block_tables[slot] = 0
        self._active[slot] = False
        self._cur_lens[slot] = 0
        self._gen_idx[slot] = 0
        if self._gen is not None:
            self._blk_tokens[slot] = self._gen.mask_token_id
            self._blk_masked[slot] = True
            self._blk_steps[slot] = 0
        self._slot_trace.pop(slot, None)
        self._fast = None  # slot membership changed: rebuild + re-radar

    def slot_len(self, slot):
        return int(self._cur_lens[slot])

    def reset(self):
        for i in range(self.max_batch_size):
            self.release(i)

    def bucket_for(self, prompt_len):
        """Smallest bucket holding the prompt; counts a promotion whenever
        the smallest bucket didn't fit (bucket-ladder health signal)."""
        if prompt_len < 1:
            raise ValueError("prompt must contain at least one token")
        for b in self.buckets:
            if prompt_len <= b:
                if b != self.buckets[0]:
                    _counters["bucket_promotions"] += 1
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest bucket "
            f"{self.buckets[-1]} (buckets={self.buckets})")

    # --------------------------------------------------- block budgeting --
    def _budget_rows(self, prompt_len, max_new_tokens):
        """Worst-case KV rows a request can ever write: its prompt plus
        its token budget, capped by the cache ceiling. Allocating this up
        front at admission means generation can NEVER run out of blocks
        mid-flight — pool pressure is answered with admission
        backpressure, not a truncated response."""
        if max_new_tokens is None:
            return self.max_seq_len
        return min(prompt_len + int(max_new_tokens), self.max_seq_len)

    def blocks_needed(self, prompt_len, max_new_tokens=None):
        b = self._budget_rows(prompt_len, max_new_tokens)
        return -(-b // self.block_size)

    def can_admit(self, prompt_ids, max_new_tokens=None):
        """Admission budget check for the scheduler: can the pool cover
        this request's worst case, counting cold prefix blocks as
        evictable? Conservative on purpose — it ignores the prefix-hit
        discount, so a True here guarantees ``prefill`` cannot raise
        ``PagePoolExhausted`` (a matched block either still stands, which
        only lowers the real need, or was evicted into the free count)."""
        if _faults.ACTIVE and _faults.fire("page_pool_exhausted"):
            return False
        need = self.blocks_needed(len(prompt_ids), max_new_tokens)
        return need <= (self.pool.free_count()
                        + self.prefix_cache.evictable_count())

    def _evict(self, n):
        freed = self.prefix_cache.evict(n)
        if freed:
            _counters["prefix_evicted_blocks"] += freed
        return freed

    def _note_pool(self):
        used = self.pool.in_use()
        _registry.gauge_set("serving.kv_blocks_in_use", used)
        if used > _counters["kv_blocks_hwm"]:
            _counters["kv_blocks_hwm"] = used

    # ----------------------------------------------------- pure step fns --
    def _jit_decode(self):
        step = self._decode_pure if self._gen is None else self._block_pure
        return jax.jit(_spans.named(step, "serving_decode"),
                       donate_argnums=self._donate)

    def _state_arrays(self):
        # cached between weight swaps: walking hundreds of Tensor
        # attribute loads per decode step was a measurable slice of the
        # scheduler->engine hop (_forward_slot's trace-time rebinding
        # restores the same array objects, so the cache stays valid)
        cached = self._state_tuple
        if cached is None:
            cached = self._state_tuple = tuple(
                self._state[n]._data for n in self._names)
        return cached

    def _forward_slot(self, state_arrays, ids, positions, ks, vs, offsets,
                      seq_lens, block_tables, kernel=None, counts=None):
        """Run the model's paged-cache forward path on traced arrays by
        temporarily binding them into the layer parameters (the
        jit.StaticFunction state-swap idiom). Trace-time only — the jitted
        executables never re-enter Python. ``kernel`` selects the paged-
        attention read path (None = XLA gather): a static string, fixed
        per compiled step. The fused kinds additionally close over the
        engine's per-shard mesh (ISSUE 16) so a mesh engine runs the
        kernel body per head-shard through shard_map. ``counts``, a dict,
        receives the decoder's device-side step counters (taken from the
        decoder inside this trace either way, so none outlives it)."""
        paged_mesh = self._paged_mesh \
            if kernel in ("pallas", "interpret") else None
        old = {n: self._state[n]._data for n in self._names}
        for n, arr in zip(self._names, state_arrays):
            self._state[n]._data = arr
        try:
            with _ag.no_grad(), _lazy.lazy_guard(False):
                caches = [(Tensor(k), Tensor(v)) for k, v in zip(ks, vs)] \
                    if vs else [(Tensor(k),) for k in ks]
                # one kind of layer: the table as it is; full and window
                # layers: each layer its own part
                tables = self._cache.layer_tables(block_tables,
                                                  self.block_size)
                hidden, new_caches = self._gpt(
                    Tensor(ids), position_ids=Tensor(positions),
                    caches=caches, cache_offsets=Tensor(offsets),
                    seq_lens=Tensor(seq_lens),
                    block_tables=[Tensor(t) for t in tables]
                    if isinstance(tables, list) else Tensor(tables),
                    paged_kernel=kernel, paged_mesh=paged_mesh,
                    **self._moe_kernel_arg)
                if self._step_counter_names:
                    got = self._gpt.step_counters()
                    if counts is not None:
                        counts.update(got)
            return (hidden._data,
                    tuple(c[0]._data for c in new_caches),
                    tuple(c[1]._data for c in new_caches) if vs else ())
        finally:
            for n in self._names:
                self._state[n]._data = old[n]

    def _prefill_pure(self, state_arrays, ks, vs, ids, prompt_len,
                      prefix_len, block_table, key, temp, top_k, top_p):
        """One request's prompt-SUFFIX pass at bucket shape [1, L]: the
        tokens after the cached prefix are embedded at absolute positions
        prefix_len.., their KV rows scatter through the block table into
        the pool, attention reads the slot's rows back (cached prefix
        blocks included) — through the engine's prefill kernel where one
        resolved (`flash_prefill`: the live keys only, a block of query
        rows at a time), else the gathered view of the whole table under
        a mask — and the first token is sampled at the prompt's true last
        position. A cold prefill is the SAME program with prefix_len == 0
        — prefix length is data, never a shape, so hits, misses and chunks
        share one executable per bucket (and stay token-bitwise: same
        program, same order of keys)."""
        L = ids.shape[1]
        positions = jnp.minimum(
            prefix_len[:, None] + jnp.arange(L, dtype=jnp.int32)[None],
            self.max_seq_len - 1)
        hidden, nk, nv = self._forward_slot(
            state_arrays, ids, positions, ks, vs, prefix_len, prompt_len,
            block_table,
            kernel=None if self._prefill_kernel == "xla"
            else self._prefill_kernel)
        if self._gen is not None:
            # a block decoder samples nothing from a prompt: its first
            # block is generated like every other
            return jnp.zeros((1,), jnp.int32), nk, nv
        last_local = prompt_len - 1 - prefix_len
        last = jnp.take_along_axis(
            hidden,
            jnp.broadcast_to(last_local[:, None, None],
                             (1, 1, hidden.shape[2])).astype(jnp.int32),
            axis=1)[:, 0]
        w = state_arrays[self._head_idx]
        with _spans.scope("lm_head"):
            logits = self._head_logits(last, w)
        gum = _sampling.gumbel_rows(key[None], jnp.zeros((1,), jnp.int32),
                                    logits.shape[-1])
        tok = _sampling.sample_tokens(logits, temp, top_k, top_p, gum)
        return tok, nk, nv

    def _decode_pure(self, state_arrays, ks, vs, last_tokens, cur_lens,
                     keys, gen_idx, temps, top_ks, top_ps, active,
                     block_tables):
        """One decode iteration for EVERY slot at fixed [B, 1] shape: feed
        each slot's last token at its own position, scatter its KV row
        through its block table, sample its next token. Inactive lanes
        compute garbage that the host discards — their zeroed table rows
        aim every write at the reserved garbage block, so batch
        membership is data, not shape, and a dead lane can never corrupt
        a live slot's blocks. The per-slot cursors advance IN the step
        (masked by ``active``) so the steady fast path keeps them on
        device instead of re-uploading host mirrors every iteration."""
        ids = last_tokens[:, None]
        positions = jnp.minimum(cur_lens, self.max_seq_len - 1)[:, None]
        counts = {}
        hidden, nk, nv = self._forward_slot(
            state_arrays, ids, positions, ks, vs,
            positions[:, 0], cur_lens + 1, block_tables,
            kernel=self._paged_kernel, counts=counts)
        w = state_arrays[self._head_idx]
        with _spans.scope("lm_head"):
            logits = self._head_logits(hidden[:, 0], w)
        gum = _sampling.gumbel_rows(keys, gen_idx, logits.shape[-1])
        # a released slot keeps its last knobs: as a greedy lane it asks
        # for no filter pass (its token is discarded either way)
        toks = _sampling.sample_tokens(
            logits, jnp.where(active, temps, 0.0), top_ks, top_ps, gum)
        adv = active.astype(cur_lens.dtype)
        new_last = jnp.where(active, toks, last_tokens)
        if self._step_counter_names:
            # behind the B tokens, in the one array the host reads
            toks = jnp.concatenate([toks, jnp.stack(
                [counts[n] for n in self._step_counter_names]
            ).astype(toks.dtype)])
        return (toks, nk, nv, new_last, cur_lens + adv,
                gen_idx + adv.astype(gen_idx.dtype))

    def _block_pure(self, state_arrays, ks, vs, blk_tokens, blk_masked,
                    blk_steps, cur_lens, keys, temps, top_ks, top_ps,
                    active, block_tables):
        """One forward of a block-diffusion decoder for EVERY slot at fixed
        ``[B, L]`` shape (L the block length), denoise and commit alike —
        the phase is data, a slot's own: each slot's block (the mask id
        where its state says masked) at positions ``cur .. cur + L - 1``
        against its rows in the pools and itself, its rows written at the
        cursor (a denoise forward's are overwritten by the next forward:
        the cursor moves only on a commit). A slot with a masked position
        DENOISES: every row samples its own position's token (no shift)
        with its probability, and `sampling.unmask_select` says which
        masked positions take theirs. A slot with none COMMITS: the rows
        just written are the clean block's, the cursor moves on by L and a
        new block of masks opens. The host reads one array: the blocks
        (after this forward's unmasking; a committing slot's is the block
        it committed), their mask bits, who committed, then the step
        counters. Inactive lanes compute garbage into the garbage block."""
        g = self._gen
        L, B = g.block_length, self.max_batch_size
        i32 = jnp.int32
        ids = jnp.where(blk_masked, i32(g.mask_token_id), blk_tokens)
        rows = cur_lens[:, None] + jnp.arange(L, dtype=i32)[None]
        counts = {}
        hidden, nk, nv = self._forward_slot(
            state_arrays, ids, jnp.minimum(rows, self.max_seq_len - 1), ks,
            vs, cur_lens, cur_lens + L, block_tables,
            kernel=self._paged_kernel, counts=counts)
        w = state_arrays[self._head_idx]
        with _spans.scope("lm_head"):
            logits = self._head_logits(
                hidden.reshape(B * L, hidden.shape[-1]), w)

        def a_row(x):  # a slot's knob, once a row of its block
            return jnp.repeat(x, L, axis=0)
        # a position's noise is its own: the request's key folded with the
        # position and the block's denoise forwards so far
        gum = _sampling.gumbel_rows(
            a_row(keys), (rows * L + blk_steps[:, None]).reshape(B * L),
            logits.shape[-1])
        tok, conf = _sampling.sample_block(
            logits, a_row(jnp.where(active, temps, 0.0)), a_row(top_ks),
            a_row(top_ps), gum, g.mask_token_id)
        commit = active & ~blk_masked.any(-1)
        unmask = _sampling.unmask_select(
            conf.reshape(B, L), blk_masked & active[:, None], blk_steps,
            g.denoising_steps, g.strategy, g.confidence_threshold)
        tokens = jnp.where(unmask, tok.reshape(B, L), blk_tokens)
        masked = blk_masked & ~unmask
        out = jnp.concatenate(
            [tokens.reshape(-1), masked.reshape(-1).astype(i32),
             commit.astype(i32)]
            + [counts[n].astype(i32)[None]
               for n in self._step_counter_names])
        return (out, nk, nv,
                jnp.where(commit[:, None], i32(g.mask_token_id), tokens),
                masked | commit[:, None],
                jnp.where(commit, 0, blk_steps + (active & ~commit)),
                cur_lens + commit.astype(cur_lens.dtype) * L)

    # ------------------------------------------------------- weight swap --
    def _resolve_swap_state(self, state, names=None):
        """Map an incoming state nest onto this engine's bound weight
        names (or an explicit ``names`` list — the spec-decode drafter
        reuses the resolver against its own name set). Accepts the
        decoder's own state_dict, a wrapper model's (uniform name
        prefix, e.g. ``gpt.``), or a full checkpoint nest
        (``{"model": ..., "optimizer": ...}`` from
        capture_training_state — the optimizer part is ignored)."""
        names = self._names if names is None else names
        if not isinstance(state, dict):
            raise WeightSwapError(
                f"swap state must be a dict of name -> array, got "
                f"{type(state).__name__}")
        if "model" in state and isinstance(state["model"], dict) \
                and "model" not in names:
            state = state["model"]
        if all(n in state for n in names):
            return {n: state[n] for n in names}
        # wrapper prefix: every engine name appears under one common
        # prefix (GPTForPretraining saves "gpt.<name>" while the engine
        # binds the inner GPTModel's names)
        probe = names[0]
        for key in state:
            if key.endswith(probe) and key != probe:
                pre = key[:-len(probe)]
                if all(pre + n in state for n in names):
                    return {n: state[pre + n] for n in names}
        missing = [n for n in names if n not in state]
        raise WeightSwapError(
            f"swap state is missing {len(missing)}/{len(names)} "
            f"weights (first: {missing[:3]}); a partial swap would serve "
            "inconsistent weights, refusing")

    def _stage_swap(self, resolved, names, bound):
        """Validate and stage a resolved swap map against the ``bound``
        Tensor dict (the engine's target state, or the spec-decode
        drafter's): aval/sharding checks happen for EVERY array before
        the first assignment, so staging either returns a complete array
        list or raises with nothing mutated."""
        staged = []
        for n in names:
            cur = bound[n]._data
            v = resolved[n]
            if isinstance(v, Tensor):
                v = v._data
            if isinstance(v, jax.Array):
                if v.shape != cur.shape:
                    raise WeightSwapError(
                        f"aval mismatch for {n!r}: engine holds "
                        f"{tuple(cur.shape)}, swap offers "
                        f"{tuple(v.shape)} — this is a different model")
                try:
                    v_placed = len(v.devices()) > 1
                    mesh_mismatch = v_placed and v.sharding != cur.sharding
                except Exception:
                    v_placed, mesh_mismatch = True, False
                if mesh_mismatch:
                    raise WeightSwapError(
                        f"sharding mismatch for {n!r}: engine weight is "
                        f"placed as {cur.sharding}, swap offers "
                        f"{v.sharding} — re-place the arrays on the "
                        "serving mesh before swapping")
                arr = v if v.dtype == cur.dtype else v.astype(cur.dtype)
                if self._mesh is not None and not v_placed:
                    # single-device/host array onto a mesh engine: place
                    # it like the numpy path does — a checkpoint load
                    # should not have to know the serving layout
                    arr = jax.device_put(arr, cur.sharding)
            else:
                a = np.asarray(v.numpy() if hasattr(v, "numpy") else v)
                if tuple(a.shape) != tuple(cur.shape):
                    raise WeightSwapError(
                        f"aval mismatch for {n!r}: engine holds "
                        f"{tuple(cur.shape)}, swap offers "
                        f"{tuple(a.shape)} — this is a different model")
                arr = jnp.asarray(a, cur.dtype)
                if self._mesh is not None:
                    arr = jax.device_put(arr, cur.sharding)
            staged.append(arr)
        return staged

    def swap_weights(self, state, source=None):
        """Atomically replace every bound weight. Must be called between
        steps on the engine's driver thread (the scheduler applies staged
        swaps at its step boundary — ``scheduler.request_swap`` /
        ``server.swap_weights`` are the thread-safe frontends).

        All-or-nothing: every array is validated and staged on host
        BEFORE the first assignment, so any refusal (missing name, shape
        mismatch, foreign device placement) — or a crash mid-swap — leaves
        the engine serving the complete pre-swap weights. The KV cache is
        untouched: in-flight requests keep their prefix state and simply
        decode their next token under the new weights, and because the
        new arrays have the same avals the compiled decode step replays
        with ZERO recompiles. The PREFIX cache, however, is flushed: its
        blocks hold KV computed under the old weights, and reusing them
        would serve a franken-model (prefix under old weights, suffix
        under new) — the weight-generation bump makes every cached prefix
        unmatchable, so post-swap requests recompute their prefixes."""
        t0 = _tracing.clock() if _tracing.enabled() else 0.0
        resolved = self._resolve_swap_state(state)
        staged = self._stage_swap(resolved, self._names, self._state)
        if _faults.ACTIVE:
            _faults.fire("kill_during_swap")
        for n, arr in zip(self._names, staged):
            self._state[n]._data = arr
        # drop the cached weight tuple AND the decode fast path: the
        # first post-swap decode rebuilds + re-runs the signature radar
        # (an audited first step, same contract as lazy drop_plans).
        # The prefix cache is invalidated by generation bump (satellite
        # 1): old-weight KV blocks must never serve the new weights.
        self._state_tuple = None
        self._fast = None
        self.prefix_cache.new_generation()
        self._note_pool()
        _counters["weight_swaps"] += 1
        if t0:
            # swap-boundary span: process-level (no single request owns
            # it), marks the wall every in-flight stream decoded across
            _tracing.add_span(None, "swap_weights", t0, _tracing.clock())
        _tracing.flight("swap_weights", weights=len(staged), source=source,
                        generation=self.prefix_cache.generation)
        _explain.record(
            "serving_weight_swap", op="swap_weights",
            why=f"swapped {len(staged)} weights"
                + (f" from {source}" if source else "")
                + "; in-flight requests keep their KV cache and decode "
                  "the next token on the new weights; the prefix cache "
                  "is flushed (old-weight KV is unreusable)",
            weights=len(staged), source=source)

    def reprime(self):
        """Rebuild the compiled decode step (drops the executable and its
        cache). Transient-fault recovery: the scheduler re-primes then
        retries one decode after a step error before failing the batch.
        The compile radar mirrors jax.jit's aval cache, so the decode
        signatures are forgotten with it — the retry's recompile must
        count in ``decode_compiles``, not hide behind a stale entry. The
        prefix cache is flushed too: a fault mid-step may have left
        cached prefix blocks in an unknown state, and recomputing a
        prefix is cheap next to serving a corrupt one."""
        self._decode_jit = self._jit_decode()
        self._seen_sigs = {s for s in self._seen_sigs
                           if s[0] != "decode"}
        self._fast = None  # fresh executable: audited rebuild first
        self.prefix_cache.new_generation()
        self._note_pool()
        _counters["reprimes"] += 1

    # ----------------------------------------------------- compile radar --
    def _note_signature(self, phase, args, detail):
        """Mirror jax.jit's aval cache: a first-seen (shape, dtype)
        signature IS a trace+compile. Counted and pushed into the explainer
        ring so decode retraces are loud."""
        leaves = jax.tree_util.tree_leaves(args)
        sig = (phase,) + tuple(
            (tuple(a.shape), str(a.dtype)) for a in leaves)
        if sig in self._seen_sigs:
            return
        self._seen_sigs.add(sig)
        _counters[f"{phase}_compiles"] += 1
        _explain.record(
            f"serving_{phase}_compile", op=f"serving.{phase}",
            why=f"first {phase} trace for this signature ({detail}); "
                "recurring events of this kind after warmup are a retrace "
                "storm — check for shape or dtype drift in engine inputs",
            **{"detail": detail})

    # ------------------------------------------------------------ prefill --
    def _check_prompt(self, slot, prompt_ids):
        if self._active[slot]:
            raise RuntimeError(f"slot {slot} is still active")
        if slot in self._mid_prefill:
            raise RuntimeError(f"slot {slot} has a prefill in progress")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("prompt must contain at least one token")
        if len(prompt) > self.buckets[-1]:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the largest bucket "
                f"{self.buckets[-1]} (buckets={self.buckets})")
        if len(prompt) >= self.max_seq_len:
            raise ValueError(
                f"prompt length {len(prompt)} leaves no room to generate "
                f"(max_seq_len={self.max_seq_len})")
        return prompt

    def _table_row(self, slot, table_ids):
        """A slot's table row: its full-layer blocks in order, zero-padded,
        then (window layers) the ring blocks the slot owns."""
        row = np.zeros(self._block_tables.shape[1], np.int32)
        row[:len(table_ids)] = table_ids
        if self._ring:
            row[self.blocks_per_slot:] = self._ring_table[slot]
        return row

    def _admit_blocks(self, slot, prompt, max_new_tokens):
        """Match + pin the longest cached block-aligned prefix (capped so
        the prompt's last token is always recomputed — its hidden state
        feeds the first sample) and allocate the rest of the worst-case
        budget. Returns (table_ids, bt_row, matched_prefix_len)."""
        bs = self.block_size
        matched = self.prefix_cache.match(prompt) \
            if self._prefix_sharing else []
        max_full = (len(prompt) - 1) // bs
        matched = matched[:max_full]
        P = len(matched) * bs
        need = self.blocks_needed(len(prompt), max_new_tokens) \
            - len(matched)
        self.pool.incref(matched)  # pin before eviction can run
        try:
            fresh = self.pool.alloc(need, evict=self._evict)
        except PagePoolExhausted:
            self.pool.decref(matched)
            raise
        table_ids = matched + fresh
        return table_ids, self._table_row(slot, table_ids), P

    def _admit_prompt(self, slot, prompt_ids, max_new_tokens):
        """An admission's first phase (`serving.admit_blocks`): the prompt
        checked, its cached prefix matched and pinned, the rest of its
        blocks allocated. Returns (prompt, table_ids, bt_row, matched)."""
        with _span("serving.admit_blocks"):
            prompt = self._check_prompt(slot, prompt_ids)
            return (prompt,) + self._admit_blocks(slot, prompt,
                                                  max_new_tokens)

    def _prefill_stage(self, window, end, start, bt_row, key, temperature,
                       top_k, top_p):
        """The arguments of one prefill pass over prompt[start:end] at the
        window's bucket, on the device. ``end`` doubles as the write mask
        (only the window's rows land) and positions the sample at
        ``end - 1``."""
        L = self.bucket_for(len(window))
        ids = np.zeros((1, L), np.int32)
        ids[0, :len(window)] = window
        args = (self._state_arrays(), tuple(self._k), tuple(self._v),
                self._put(ids),
                self._put(np.asarray([end], np.int32)),
                self._put(np.asarray([start], np.int32)),
                self._put(bt_row[None]), self._put(key),
                self._put(np.asarray([temperature], np.float32)),
                self._put(np.asarray([top_k], np.int32)),
                self._put(np.asarray([top_p], np.float32)))
        self._note_signature(
            "prefill", args,
            f"bucket_len={L}, max_batch={self.max_batch_size}")
        return args

    def _prefill_run(self, args):
        """One compiled prefill pass: intermediate chunks discard its
        sample, the final window's IS the request's first token. Same
        executable per bucket whether the window is a whole suffix, a
        prefix-hit remainder or one chunk."""
        with _span("serving.prefill"):
            tok, nk, nv = self._prefill_jit(*args)
            tok = int(np.asarray(tok)[0])
        self._k, self._v = list(nk), list(nv)
        _counters["prefill_flash_calls"] += self._prefill_kernel != "xla"
        return tok

    def _reserve_extra(self, slot, prompt, max_new_tokens):
        """Subclass hook (spec decode): reserve any EXTRA per-slot
        resources (the drafter's block budget) at admission time.
        Called by ``begin_prefill`` so chunked admissions hold their
        whole footprint up front — a shortage surfaces HERE as
        ``PagePoolExhausted`` (admission backpressure), never as a
        mid-flight failure at the final chunk."""

    def _chunk_extra(self, slot, prompt, start, end):
        """Subclass hook (spec decode): extra work per prefill chunk —
        the drafter ingests the same window, so its catch-up cost is
        bounded by one chunk too, not deferred into one whole-prompt
        stall at installation."""

    def _install_extra(self, slot, prompt, max_new_tokens):
        """Subclass hook (spec decode): extra per-slot admission work —
        drafter blocks + drafter prompt ingestion — run BEFORE the slot
        state is installed. Raising here unwinds the admission."""

    def _install_slot(self, slot, prompt, table_ids, bt_row, tok, key,
                      temperature, top_k, top_p, matched_prefix,
                      max_new_tokens):
        try:
            self._install_extra(slot, prompt, max_new_tokens)
        except Exception:
            self.pool.decref(table_ids)  # failed admission leaks nothing
            self._note_pool()
            raise
        if matched_prefix:
            _counters["prefix_hits"] += 1
            _counters["prefix_hit_tokens"] += matched_prefix
        else:
            _counters["prefix_misses"] += 1
        bs = self.block_size
        full = len(prompt) // bs if self._prefix_sharing else 0
        if full:
            created = self.prefix_cache.insert(prompt[:full * bs],
                                               table_ids[:full])
            _counters["prefix_inserted_blocks"] += created
        self._slot_blocks[slot] = table_ids
        self._block_tables[slot] = bt_row
        self._active[slot] = True
        self._cur_lens[slot] = len(prompt)
        self._last_tokens[slot] = tok or 0  # (a block decoder has none)
        self._gen_idx[slot] = 1
        self._temps[slot] = temperature
        self._top_ks[slot] = top_k
        self._top_ps[slot] = top_p
        self._keys[slot] = key
        self._fast = None  # admission is a batch-boundary event: rebuild
        self._note_pool()
        _counters["prefills"] += 1
        if self._gen is None:
            _counters["tokens_generated"] += 1
            return
        # a block decoder: the cursor stands at the prompt's last whole
        # block, and the prompt's tail opens the first generated block
        # already unmasked
        tail = len(prompt) % self._gen.block_length
        self._cur_lens[slot] = len(prompt) - tail
        self._blk_skip[slot] = tail
        self._blk_tokens[slot, :tail] = prompt[len(prompt) - tail:]
        self._blk_masked[slot, :tail] = False
        self._blk_budget[slot] = np.iinfo(np.int64).max \
            if max_new_tokens is None else int(max_new_tokens)

    def _request_key(self, seed):
        if seed is None:
            seed = next(self._seed_counter)
        return np.asarray(_sampling.request_key(self._base_key, seed),
                          np.uint32)

    def prefill(self, slot, prompt_ids, temperature=0.0, top_k=0,
                top_p=1.0, seed=None, max_new_tokens=None):
        """Admit a prompt into `slot`: match its longest cached block
        prefix (shared blocks join the slot's table by refcount, their
        prefill FLOPs skipped), allocate fresh blocks for the suffix +
        generation budget, run the compiled suffix prefill, install the
        slot state and publish the prompt's full blocks into the prefix
        cache. Returns the first generated token (TTFT == prefill
        latency). Raises ``PagePoolExhausted`` when the pool cannot cover
        the request even after evicting cold prefixes (the scheduler's
        ``can_admit`` pre-check makes that unreachable in normal
        operation)."""
        prompt, table_ids, bt_row, P = self._admit_prompt(
            slot, prompt_ids, max_new_tokens)
        trace = _tracing.trace_id_for_seed(seed) if seed is not None \
            else None
        end = len(prompt)
        if self._gen is not None:
            # block-causally over the prompt's whole blocks, no token
            # sampled: the tail is generated with the first block
            end -= end % self._gen.block_length
        try:
            with _tracing.span(trace, "prefill"):
                # everything between the blocks and the executable's call
                with _span("serving.admit_stage"):
                    key = self._request_key(seed)
                    args = self._prefill_stage(
                        prompt[P:end], end, P, bt_row, key, temperature,
                        top_k, top_p) if end else None
                tok = self._prefill_run(args) if end else None
        except Exception:
            self.pool.decref(table_ids)  # failed admission leaks nothing
            self._note_pool()
            raise
        with _span("serving.admit_install"):
            self._install_slot(slot, prompt, table_ids, bt_row, tok, key,
                               temperature, top_k, top_p, P, max_new_tokens)
            self._slot_trace[slot] = trace
        return tok if self._gen is None else None

    # -------------------------------------------------- chunked prefill --
    def begin_prefill(self, slot, prompt_ids, temperature=0.0, top_k=0,
                      top_p=1.0, seed=None, max_new_tokens=None,
                      chunk_tokens=None):
        """Start a CHUNKED admission (ISSUE 12): allocate the request's
        worst-case blocks up front (identical admission budget to
        ``prefill`` — chunking bounds LATENCY, never memory), match the
        prefix cache, then leave the prompt to be processed in
        block-aligned chunks by :meth:`prefill_chunk`. The slot is
        reserved (not free, not active) until the final chunk samples the
        first token, so decode iterations for in-flight streams
        interleave between chunks instead of stalling behind one long
        prompt. Returns the number of pending chunks."""
        self.require_full_layers("chunked prefill (begin_prefill)")
        self.require_autoregressive(
            "chunked prefill (begin_prefill)",
            "its prefill attends to the call's own rows only, not to what "
            "an earlier chunk wrote")
        prompt, table_ids, bt_row, P = self._admit_prompt(
            slot, prompt_ids, max_new_tokens)
        bs = self.block_size
        chunk = max(bs, (int(chunk_tokens or bs) // bs) * bs)
        try:
            self._reserve_extra(slot, prompt, max_new_tokens)
        except Exception:
            self.pool.decref(table_ids)  # failed admission leaks nothing
            self._note_pool()
            raise
        with _span("serving.admit_stage"):
            key = self._request_key(seed)
        self._mid_prefill[slot] = {
            "prompt": prompt, "done": P, "chunk": chunk,
            "table_ids": table_ids, "bt_row": bt_row,
            "key": key, "temperature": temperature,
            "top_k": top_k, "top_p": top_p, "matched": P,
            "max_new_tokens": max_new_tokens,
            "trace": _tracing.trace_id_for_seed(seed)
            if seed is not None else None,
        }
        self._note_pool()
        _counters["chunked_prefills"] += 1
        return -(-(len(prompt) - P) // chunk)

    def prefill_chunk(self, slot):
        """Process the next chunk of a :meth:`begin_prefill` admission.
        Returns ``None`` while chunks remain; the FINAL chunk samples the
        request's first token, installs the slot (it joins the next
        decode batch) and returns that token. Chunks reuse the ordinary
        per-bucket prefill executable — earlier chunks are just a longer
        'prefix' whose length is data, so a chunked prompt is token-
        bitwise with an unchunked one."""
        st = self._mid_prefill.get(slot)
        if st is None:
            raise RuntimeError(f"slot {slot} has no prefill in progress")
        prompt, start = st["prompt"], st["done"]
        end = min(start + st["chunk"], len(prompt))
        try:
            with _tracing.span(st.get("trace"), "prefill_chunk"):
                with _span("serving.admit_stage"):
                    args = self._prefill_stage(
                        prompt[start:end], end, start, st["bt_row"],
                        st["key"], st["temperature"], st["top_k"],
                        st["top_p"])
                tok = self._prefill_run(args)
                self._chunk_extra(slot, prompt, start, end)
        except Exception:
            # drop the chunk state; reserved extras (drafter blocks)
            # come back when the scheduler releases the slot
            del self._mid_prefill[slot]
            self.pool.decref(st["table_ids"])
            self._note_pool()
            raise
        st["done"] = end
        _counters["prefill_chunks"] += 1
        if end < len(prompt):
            return None
        del self._mid_prefill[slot]
        with _span("serving.admit_install"):
            self._install_slot(
                slot, prompt, st["table_ids"], st["bt_row"], tok, st["key"],
                st["temperature"], st["top_k"], st["top_p"], st["matched"],
                st["max_new_tokens"])
            self._slot_trace[slot] = st.get("trace")
        return tok

    # --------------------------------------------- prefill→decode handoff --
    def _note_refusal(self, why):
        """A feature this engine's cache cannot give: counted
        (``serving.cache_refusals``) and explained
        (``cache_feature_refused``), whether it then raises or, like
        prefix sharing, is a default switched off."""
        _counters["cache_refusals"] += 1
        _explain.record("cache_feature_refused", op="serving.engine",
                        cache_kind=self._cache.kind,
                        window_layers=self._cache.window_layers(), why=why)

    def _refuse(self, why):
        self._note_refusal(why)
        raise TypeError(why)

    def require_full_layers(self, feature):
        """Refuse what a ring cannot hold: window layers keep the last
        ``window`` rows of a slot in blocks that are the slot's own."""
        if self._cache.window is not None:
            self._refuse(
                f"{feature} is not supported for a cache with window "
                f"layers yet: layers {self._cache.window_layers()} keep a "
                f"ring of the last {self._cache.window} rows a slot, "
                "which has no head-sharded placement, no payload form, "
                "and holds neither a verify span nor the rows an earlier "
                "call wrote beyond the window")

    def require_autoregressive(self, feature, why):
        """Refuse what is written for a decoder that yields one token a
        sequence a step, left to right."""
        if self._gen is not None:
            self._refuse(
                f"{feature} is not supported for a decoder that generates "
                f"by diffusion over blocks of {self._gen.block_length} "
                f"yet: {why}")

    def _heads_cache_only(self, feature):
        self.require_autoregressive(
            feature, "a slot's state is a block's tokens, mask bits and "
            "phase, not a last token; a verify span is causal and its "
            "acceptance rule is of a token a step")
        if self._cache.kind != "heads":
            self._refuse(
                f"{feature} is not supported for a {self._cache.kind!r} "
                "cache yet: its payload and its verify span are written "
                "for K and V rows of heads")
        self.require_full_layers(feature)

    def export_request_kv(self, slot):
        """Serialize an active slot's paged-KV state for a cross-pod
        handoff (disaggregated serving, ISSUE 11): the slot's physical
        blocks are gathered out of every layer's pool in block-table
        order, together with the per-slot decode state (cursor, last
        token, RNG key, sampling knobs). ``import_request_kv`` on ANY
        engine with the same model + block geometry reproduces the slot
        exactly, and because sampling depends only on (request key,
        token index) and the KV bytes are carried verbatim, decoding
        there is token-BITWISE with decoding here — a prefill pod can
        hand its finished prompt KV to a decode pod and the stream is
        indistinguishable from a monolithic pod's."""
        self._heads_cache_only("the KV handoff (export_request_kv)")
        if not self._active[slot]:
            raise RuntimeError(f"slot {slot} is not active; nothing to "
                               "export")
        trace = self._slot_trace.get(slot)
        t0 = _tracing.clock() if _tracing.enabled() else 0.0
        ids = list(self._slot_blocks[slot])
        # the wire keeps [n, block_size, H, Dh] blocks, whatever the
        # pool's device form: pods need no format change
        ks = [_kv_pool.export_blocks(a, ids, h)
              for a, h in zip(self._k, self._kv_heads)]
        vs = [_kv_pool.export_blocks(a, ids, h)
              for a, h in zip(self._v, self._kv_heads)]
        _counters["handoff_exports"] += 1
        if t0:
            _tracing.add_span(
                trace, "kv_export", t0, _tracing.clock(),
                meta={"bytes": sum(a.nbytes for a in ks + vs)})
        _tracing.flight("kv_export", trace_id=trace, slot=slot,
                        blocks=len(ids))
        return {
            "n_blocks": len(ids),
            "block_size": self.block_size,
            "kv_k": ks, "kv_v": vs,
            "cur_len": int(self._cur_lens[slot]),
            "last_token": int(self._last_tokens[slot]),
            "gen_idx": int(self._gen_idx[slot]),
            "key": np.asarray(self._keys[slot]).copy(),
            "temperature": float(self._temps[slot]),
            "top_k": int(self._top_ks[slot]),
            "top_p": float(self._top_ps[slot]),
            "weight_generation": self.prefix_cache.generation,
            # trace context rides the handoff payload: the decode pod's
            # import span lands in the SAME trace without any extra wire
            # field between pods
            "trace": trace,
        }

    def can_import(self, payload):
        """Admission budget check for a handed-off slot: the pool must
        cover the payload's block count (prefill already allocated the
        request's WORST CASE — prompt + token budget — so an import can
        never run out of blocks mid-flight either). Same conservative
        contract as ``can_admit``: True guarantees ``import_request_kv``
        cannot raise ``PagePoolExhausted``."""
        if _faults.ACTIVE and _faults.fire("page_pool_exhausted"):
            return False
        return int(payload["n_blocks"]) <= (
            self.pool.free_count() + self.prefix_cache.evictable_count())

    def import_request_kv(self, slot, payload, prompt_ids=None):
        """Adopt a slot exported by :meth:`export_request_kv` on another
        engine: allocate fresh blocks, scatter the handed-off KV rows
        into this engine's pools, install the slot state. Returns the
        request's first generated token (sampled by the exporting
        engine) so the scheduler's admission path can append it exactly
        as it would a local prefill's. Passing ``prompt_ids`` publishes
        the prompt's full blocks into THIS engine's prefix cache too, so
        a handed-off shared prefix keeps earning hits on the decode
        pod."""
        self._heads_cache_only("the KV handoff (import_request_kv)")
        if self._active[slot]:
            raise RuntimeError(f"slot {slot} is still active")
        t0 = _tracing.clock() if _tracing.enabled() else 0.0
        gen = payload.get("weight_generation")
        if gen is not None and int(gen) != self.prefix_cache.generation:
            # a weight swap landed between the export and this import:
            # the payload's KV belongs to another weight generation
            # (same invalidation rule the prefix cache enforces locally)
            _counters["handoff_stale"] += 1
            raise StaleHandoffError(
                f"handoff exported under weight generation {gen}, this "
                f"engine serves generation "
                f"{self.prefix_cache.generation}; re-prefill under the "
                "current weights instead of adopting stale KV")
        n = int(payload["n_blocks"])
        if int(payload["block_size"]) != self.block_size:
            raise ValueError(
                f"handoff block_size {payload['block_size']} != engine "
                f"block_size {self.block_size} — pods must share one KV "
                "geometry")
        if n > self.blocks_per_slot:
            raise ValueError(
                f"handoff carries {n} blocks but a slot holds at most "
                f"{self.blocks_per_slot}")
        if len(payload["kv_k"]) != len(self._k):
            raise ValueError(
                f"handoff has {len(payload['kv_k'])} layers, engine has "
                f"{len(self._k)} — different model")
        for li, kb in enumerate(payload["kv_k"]):
            want = _kv_pool.block_shape(self._k[li], self._kv_heads[li])
            if tuple(np.shape(kb))[1:] != tuple(want):
                raise ValueError(
                    f"handoff layer {li} block shape "
                    f"{tuple(np.shape(kb))[1:]} != engine {tuple(want)}")
        fresh = self.pool.alloc(n, evict=self._evict)
        try:
            for li in range(len(self._k)):
                self._k[li] = _kv_pool.import_blocks(
                    self._k[li], fresh, payload["kv_k"][li], self._put)
                self._v[li] = _kv_pool.import_blocks(
                    self._v[li], fresh, payload["kv_v"][li], self._put)
        except Exception:
            self.pool.decref(fresh)  # failed adoption leaks nothing
            raise
        bt_row = self._table_row(slot, fresh)
        if prompt_ids is not None:
            prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
            full = min(len(prompt) // self.block_size, n)
            if full:
                created = self.prefix_cache.insert(
                    prompt[:full * self.block_size], fresh[:full])
                _counters["prefix_inserted_blocks"] += created
        self._slot_trace[slot] = payload.get("trace")
        self._slot_blocks[slot] = fresh
        self._block_tables[slot] = bt_row
        self._active[slot] = True
        self._cur_lens[slot] = int(payload["cur_len"])
        self._last_tokens[slot] = int(payload["last_token"])
        self._gen_idx[slot] = int(payload["gen_idx"])
        self._temps[slot] = float(payload["temperature"])
        self._top_ks[slot] = int(payload["top_k"])
        self._top_ps[slot] = float(payload["top_p"])
        self._keys[slot] = np.asarray(payload["key"], np.uint32)
        self._fast = None  # admission is a batch-boundary event: rebuild
        self._note_pool()
        _counters["handoff_imports"] += 1
        _counters["tokens_generated"] += 1  # the adopted first token
        if t0:
            _tracing.add_span(
                payload.get("trace"), "kv_import", t0, _tracing.clock(),
                meta={"bytes": sum(np.asarray(a).nbytes for a in
                                   payload["kv_k"] + payload["kv_v"])})
        _tracing.flight("kv_import", trace_id=payload.get("trace"),
                        slot=slot, blocks=n)
        return int(payload["last_token"])

    # ------------------------------------------------------------- decode --
    def decode_step(self):
        """One continuous-batching iteration over all slots. A decoder that
        generates left to right returns the np.int32[B] token block (junk on
        inactive lanes) and advances every active slot's cursor and
        per-request RNG index; a block-diffusion decoder (`generation`) runs
        one forward of `_block_pure` and returns what `_finish_block` does.

        Steady fast path: when nothing mutated the batch since the last
        iteration (no admission, eviction, weight swap or reprime), the
        prebuilt device-side arg tuple is still valid — the iteration is
        one fingerprint check plus one executable call, with the host
        mirrors advanced by cheap numpy stores. Every
        ``PADDLE_TPU_AUDIT_EVERY`` fast steps an audit cross-checks the
        device copies against the host mirrors and demotes on mismatch."""
        with _span("serving.decode_prepare"):
            active = self._active
            n_active = int(active.sum())
            if n_active == 0:
                raise RuntimeError("decode_step with no active slots")
            if _faults.ACTIVE:
                _faults.fire("slow_decode")
                _faults.fire("pod_slow")
                _faults.fire("replica_kill")
                _faults.fire("decode_error")
            fast = self._fast
            if fast is not None \
                    and self._decode_since_audit + 1 >= self._audit_every:
                self._audit_fast(fast)
                fast = self._fast  # a failed audit demoted it
            rebuilt = fast is None
            if rebuilt:
                fast = self._decode_rebuild()
            args = (self._state_arrays(), tuple(self._k),
                    tuple(self._v)) + fast
        toks, nk, nv, *stepped = self._decode_call(args)
        with _span("serving.decode_finish"):
            # the step's own part, then its counters
            B = len(toks) - len(self._step_counter_names)
            for name, n in zip(self._step_counter_names, toks[B:]):
                _counters[name] += int(n)
            self._k, self._v = list(nk), list(nv)
            fast = list(fast)
            for i, x in zip(self._slot_stepped, stepped):
                fast[i] = x
            self._fast = tuple(fast)
            given = (self._finish_decode if self._gen is None
                     else self._finish_block)(active, n_active, toks[:B])
            if not rebuilt:
                self._decode_since_audit += 1
                _fp_counters["decode_fast_steps"] += 1
        return given

    def _decode_call(self, args):
        """The one timed site of a decode iteration, fast path and rebuild
        path alike: dispatch of the executable, then the wait for its
        tokens (the span's self time is the dispatch). The call's begin,
        the dispatch's return and the wait's end are the three instants
        `benchmark/step_timeline.py` splits the device's idle time at."""
        with _span("serving.decode_step"):
            toks_d, *rest = self._decode_jit(*args)
            with _span("serving.decode_sync"):
                toks = np.asarray(toks_d)
        return (toks, *rest)

    def _decode_rebuild(self):
        """Off-steady decode: rebuild the device-side slot state from the
        host mirrors (a batch-boundary event — admission, eviction,
        weight swap, reprime — invalidated it) and run the signature
        radar; the iteration that follows re-arms the fast path."""
        tail = tuple(self._put(getattr(self, name))
                     for name in self._slot_state)
        self._note_signature(
            "decode",
            (self._state_arrays(), tuple(self._k), tuple(self._v)) + tail,
            f"max_batch={self.max_batch_size}, "
            f"max_seq_len={self.max_seq_len}")
        _fp_counters["decode_rebuilds"] += 1
        self._decode_since_audit = 0
        return tail

    def _count_model_step(self, n_active):
        """What the decoder says a step adds to the host's counters, and of
        its expert layer-steps those whose grouped matmuls ran through the
        kernel (all, or none: the kind is the engine's)."""
        for name, n in self._host_step_counts(n_active).items():
            _counters[name] += n
            if name == "moe_layer_steps" and self._moe_kernel != "xla":
                _counters["moe_kernel_layer_steps"] += n

    def _finish_decode(self, active, n_active, toks):
        # host mirrors advance in lockstep with the device copies (numpy
        # stores over B elements; the audit cross-checks the two)
        c = _counters
        # the KV rows this step's attention read, whatever kernel read
        # them: each active slot's length with its new row
        self._cur_lens[active] += 1
        c["kv_tokens_read"] += int(self._cur_lens[active].sum())
        if self._ring:  # what ONE window layer read: its ring's live rows
            c["kv_window_rows_read"] += int(np.minimum(
                self._cur_lens[active], self._cache.window).sum())
        self._gen_idx[active] += 1
        self._last_tokens[active] = toks[active]
        c["decode_steps"] += 1
        self._count_filter_steps(active)
        self._count_model_step(n_active)
        c["active_slot_steps"] += n_active
        c["tokens_generated"] += n_active
        _registry.gauge_set("serving.batch_occupancy",
                            n_active / self.max_batch_size)
        return toks

    # ------------------------------------------- block-diffusion decoding --
    @property
    def generation(self):
        """How the decoder generates: None, left to right a token a step,
        or its block-diffusion settings (`decode_step` then runs
        `_block_pure`, and `_finish_block` says what it returns)."""
        return self._gen

    def _finish_block(self, active, n_active, out):
        """The host mirrors after a forward of a block-diffusion decoder,
        the counters, and what `decode_step` returns for one: a slot, None
        or — where the slot committed a block — ``(tokens, position)``: the
        tokens the request is given (the block without the prompt's tail
        that opened a first block and without what lies past the request's
        ``max_new_tokens``) and the position of the first of them."""
        g, c = self._gen, _counters
        B, L = self.max_batch_size, g.block_length
        tokens = out[:B * L].reshape(B, L)
        masked = out[B * L:2 * B * L].reshape(B, L).astype(bool)
        commit = out[2 * B * L:].astype(bool)
        # the rows this forward's attention read: each active slot's
        # committed rows and its block
        c["kv_tokens_read"] += int((self._cur_lens[active] + L).sum())
        given, n_tok = [None] * B, 0
        for slot in np.nonzero(commit)[0]:
            skip = int(self._blk_skip[slot])
            new = [int(t) for t in
                   tokens[slot, skip:skip + int(self._blk_budget[slot])]]
            given[slot] = (new, int(self._cur_lens[slot]) + skip)
            self._blk_budget[slot] -= len(new)
            self._blk_skip[slot] = 0
            n_tok += len(new)
        denoised = active & ~commit
        self._blk_tokens[denoised] = tokens[denoised]
        self._blk_masked[denoised] = masked[denoised]
        self._blk_steps[denoised] += 1
        self._blk_tokens[commit] = g.mask_token_id
        self._blk_masked[commit] = True
        self._blk_steps[commit] = 0
        self._cur_lens[commit] += L
        c["decode_steps"] += 1
        self._count_filter_steps(denoised)
        self._count_model_step(n_active)
        c["active_slot_steps"] += n_active
        c["tokens_generated"] += n_tok
        c["diffusion.slot_forwards"] += n_active
        c["diffusion.tokens_committed"] += n_tok
        c["diffusion.blocks_committed"] += int(commit.sum())
        c["diffusion.commit_forwards"] += int(commit.any())
        _registry.gauge_set("serving.batch_occupancy",
                            n_active / self.max_batch_size)
        return given

    def _count_filter_steps(self, active):
        """Whether this step's sampling ran its top-k / top-p passes:
        ``sampling.sample_tokens`` gates each on the same test of the same
        knobs (``_decode_pure`` hands it inactive lanes as greedy)."""
        on = active & (self._temps > 0)
        _counters["sample_topk_steps"] += int((on & (self._top_ks > 0)).any())
        _counters["sample_topp_steps"] += int(
            (on & (self._top_ps < 1.0)).any())

    def _audit_fast(self, fast):
        """Periodic decode audit: the device-side slot state must equal
        the host mirrors bit for bit. A mismatch demotes the fast path
        (next step rebuilds from the host mirrors, which stay
        authoritative) with a structured explainer cause."""
        _fp_counters["decode_audit_runs"] += 1
        self._decode_since_audit = 0
        # what a step advances, then who is active and where their
        # blocks lie (the last two of the tuple, whatever the decoder)
        names = self._slot_state
        ok = all(np.array_equal(np.asarray(fast[i]), getattr(self, names[i]))
                 for i in self._slot_stepped
                 + (len(names) - 2, len(names) - 1))
        if not ok:
            _fp_counters["decode_demotions"] += 1
            self._fast = None
            _explain.record(
                "fastpath_demoted", op="serving.decode",
                reason="decode_audit",
                why="decode audit: device-side slot state diverged from "
                    "the host mirrors; rebuilding from host state")

    # -------------------------------------------------------------- stats --
    @property
    def paged_kernel(self):
        """The resolved paged-attention kernel for decode/verify:
        "pallas" | "interpret" | "xla". Fixed at engine build."""
        return self._paged_kernel

    def mean_occupancy(self):
        steps = _counters["decode_steps"]
        if not steps:
            return 0.0
        return _counters["active_slot_steps"] / (
            steps * self.max_batch_size)

    def prefix_hit_rate(self):
        hits = _counters["prefix_hits"]
        total = hits + _counters["prefix_misses"]
        return hits / total if total else 0.0

    @staticmethod
    def host_means():
        """The host's time, in ms, means since the process began: an
        iteration's outside its decode call and its admissions
        (``step_host_ms``), and an admission's outside its prefill
        executable (``admit_host_ms``); from the spans' counters, as the
        benchmark's `serve.step_host_ms` / `serve.admit_host_ms` read them
        over a window. Beside them the iterations that took the fast path:
        what `stats()` and a pod's `stats` reply show an operator."""
        ns = {k: _counters.get(k + "_ns", 0) for k in
              ("sched_step", "decode_step", "admit", "prefill")}
        steps = _counters.get("sched_steps", 0)
        admitted = _counters.get("admitted", 0)
        step = ns["sched_step"] - ns["decode_step"] - ns["admit"]
        admit = ns["admit"] - ns["prefill"]
        return {"step_host_ms": step / steps / 1e6 if steps else 0.0,
                "admit_host_ms": admit / admitted / 1e6 if admitted else 0.0,
                "decode_fast_steps": _fp_counters["decode_fast_steps"]}

    def stats(self):
        out = {**_registry.counters("serving"),
               **self.host_means(),
               "paged_kernel": self._paged_kernel,
               "paged_kernel_reason": self._paged_kernel_reason,
               "mean_occupancy": self.mean_occupancy(),
               "prefix_hit_rate": self.prefix_hit_rate(),
               "kv_blocks_total": self.pool.usable_blocks,
               "kv_blocks_in_use": self.pool.in_use(),
               "kv_blocks_free": self.pool.free_count(),
               "prefix_cache_nodes": len(self.prefix_cache),
               "weight_generation": self.prefix_cache.generation,
               "kv_pool_row_major": self._kv_row_major,
               "paged_keys_per_program": self._paged_keys_per_program,
               "prefill_kernel": self._prefill_kernel,
               "prefill_kernel_reason": self._prefill_kernel_reason,
               "moe_grouped_kernel": self._moe_kernel,
               "moe_grouped_kernel_reason": self._moe_kernel_reason,
               "kv_cache_kind": self._cache.kind,
               "kv_row_width": self._cache.row_width(),
               "kv_cache": self._cache.describe(),
               "kv_window": self._cache.window,
               "kv_window_blocks": self._ring,
               "kv_layers": self._layer_records(),
               "kv_pool_bytes": self._kv_pool_bytes,
               "prefix_sharing": self._prefix_sharing}
        if self._mesh is not None:
            out["mesh_axes"] = dict(zip(
                self._mesh.axis_names,
                (int(s) for s in self._mesh.devices.shape)))
            out["paged_kernel_sharded"] = self._paged_mesh is not None
        return out

    def _layer_records(self):
        """Every layer's cache state: the heads (or latent rank) and width
        of its row, its window, its pools' blocks and bytes."""
        return [{"layer": i, "heads": self._kv_heads[i],
                 "row_width": self._cache.row_width(i),
                 "window": self._cache.windows[i],
                 "blocks": int(k.shape[0]),
                 "pool_bytes": int(k.nbytes) + (
                     int(self._v[i].nbytes) if self._v else 0)}
                for i, k in enumerate(self._k)]

    def describe_sharding(self):
        """JSON-able placement description of the engine's hot buffers —
        consumed by tools/sharding_lint.py ``lint_engine`` (the serving
        analogue of spmd.describe_plans): mesh axes, the resolved paged
        kernel, and one record per per-layer KV pool with its partition
        spec, so the lint can flag a mesh engine whose pools stayed
        replicated (the exact demotion ISSUE 16 removes)."""
        mesh = None
        if self._mesh is not None:
            mesh = {"axes": dict(zip(
                self._mesh.axis_names,
                (int(s) for s in self._mesh.devices.shape)))}
        names = ("k", "v") if self._v else ("latent",)
        layers = zip(self._k, self._v) if self._v else zip(self._k)
        pools = [_pool_record(i, name, a, self._kv_heads[i], self._mesh)
                 for i, layer in enumerate(layers)
                 for name, a in zip(names, layer)]
        return {"mesh": mesh,
                "paged_kernel": self._paged_kernel,
                "paged_kernel_sharded": self._paged_mesh is not None,
                "kv_pools": pools}
