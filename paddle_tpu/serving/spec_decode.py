"""paddle_tpu.serving.spec_decode — draft-verify speculative decoding.

The tentpole of ISSUE 12: cut per-output-token latency by letting a small
DRAFTER model propose ``K`` tokens per iteration and having the target
model check all of them in ONE fixed-shape ``[B, K+1]`` forward, instead
of paying one full target forward per token.

Why the acceptance rule is EXACT here (not the approximate
accept/reject of Leviathan et al. 2023): this serving stack's sampler is
the seeded Gumbel-max (``serving.sampling``) — the token a request emits
at generated-token index ``i`` is a DETERMINISTIC function of (target
logits at that position, request key, ``i``).  The verify step therefore
replays the exact per-(key, index) Gumbel draw on the target's own
logits at every drafted position and compares: a draft token is accepted
iff it EQUALS what plain decode would have sampled there, at any
temperature.  Accepted tokens are bitwise-identical to plain decode by
construction; the first mismatch position yields the target's own sample
as a free correction token, and an all-accept round yields a bonus
(K+1)-th token.  A worst-case-wrong drafter (the ``draft_garbage`` fault)
degrades THROUGHPUT to plain decode (one token per round) but can never
change a single emitted token.

Shapes and executables (the compile discipline):

* drafter round — ONE executable: a fixed-trip ``lax.scan`` of K+1
  ``[B, 1]`` drafter steps (cursors are data).  Scan steps 0..K-1
  propose ``d_1..d_K`` (sampling with the SAME seeded Gumbel noise the
  target will use at those indices, which is what makes acceptance
  high at temperature > 0), and step K ingests ``d_K`` into the
  drafter's KV so the drafter never falls behind the accepted sequence
  — the round feeds the drafter exactly the token window
  ``[last, d_1..d_K]`` that the verify step consumes.
* target verify — ONE ``[B, K+1]`` executable per engine (per K): ids,
  cursors, block tables, sampling knobs and the accept arithmetic are
  all arrays inside the jit, so no acceptance pattern can retrace.  PR
  8's replay fast path survives: the steady round is exactly TWO
  executable calls (draft scan + verify) on a prebuilt device-side arg
  tuple with zero per-op Python — host overhead independent of K.

Rollback without bookkeeping: the verify step writes K+1 KV rows but a
rejection only advances the cursors by the accepted count.  Rows past
the new cursor hold rejected-draft garbage — they are masked out of
every attention read (``jpos <= row`` caps at the query's own position)
and the NEXT round's writes cover exactly that span (``new_len ..
new_len+K`` ⊇ ``old_len+m .. old_len+K``), so stale rows are overwritten
before any query can reach them.  No block is ever allocated for
speculation (writes past the slot's budgeted blocks redirect to the
reserved garbage block), so ``BlockPool.audit()`` stays clean at every
boundary and rejected speculation can't leak memory by construction.

The drafter's KV rides its OWN ``BlockPool`` + block tables (same block
geometry, separate device pools — the drafter's head count differs),
budgeted at admission exactly like the target's, so drafter memory obeys
the same never-exhausts-mid-flight contract.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..core import autograd as _ag
from ..core import lazy as _lazy
from ..core.tensor import Tensor
from ..profiler import explainer as _explain
from ..profiler import registry as _registry
from ..profiler import span as _span
from ..testing import faults as _faults
from . import sampling as _sampling
from .block_pool import BlockPool, PagePoolExhausted
from .engine import GenerationEngine
from .engine import _counters as _serving_counters
from .engine import _fp_counters
from .engine import _pool_record

__all__ = ["DraftVerifyEngine"]

# speculative-decode counters live in the shared "serving" scope so
# stats_dump/bench read one table; verify_compiles/draft_compiles feed
# the engine's signature radar (phases "verify" / "draft")
# gauge-retention bound for serving.spec_acceptance.gen<N> (ISSUE 18
# satellite): generations older than the last 4 fold into .historic
SPEC_ACCEPT_KEEP_GENERATIONS = 4

_counters = _registry.scoped_counters("serving", {
    "spec_rounds": 0, "spec_slot_rounds": 0, "spec_proposed": 0,
    "spec_accepted": 0, "spec_emitted": 0, "draft_prefills": 0,
    "verify_compiles": 0, "draft_compiles": 0,
    "draft_kv_blocks_hwm": 0, "spec_mesh_refused": 0,
    "draft_swaps": 0})


def _refuse_mesh(reason, why, **detail):
    """Structured mesh refusal (ISSUE 16 satellite): the tentpole lifts
    the blanket mesh ban, but residual topologies the spec engine cannot
    serve still refuse — with a ``spec_mesh_refused`` explainer event +
    counter naming the reason, so a refusal in a serving fleet is
    diagnosable from the ring instead of a bare traceback."""
    _counters["spec_mesh_refused"] += 1
    _explain.record("spec_mesh_refused", op="DraftVerifyEngine",
                    reason=reason, why=why, **detail)
    raise ValueError(why)


class DraftVerifyEngine(GenerationEngine):
    """A :class:`GenerationEngine` whose decode loop is draft-verify
    speculative decoding.  Drop-in for the scheduler/server: admission,
    paged-KV budgeting, prefix reuse, weight swaps and the handoff
    protocol are inherited; only the per-iteration decode differs — the
    scheduler discovers :meth:`decode_step_spec` and consumes a variable
    number of tokens per slot per iteration.

    ``draft_model`` must share the target's vocabulary (token ids are
    compared for acceptance) and block geometry is shared by
    construction; everything else (depth, width, heads) is free — the
    canonical pairing is gpt2-tiny drafting for gpt2-medium.  A target
    ``swap_weights`` keeps serving bitwise-correct (acceptance is
    re-checked against the NEW target every round); pass the matching
    ``draft_state`` to the swap and the drafter's weights AND its KV
    (recomputed from each slot's token history) swap too, so acceptance
    recovers instead of decaying against stale draft weights.

    Mesh-sharded serving (ISSUE 16): an ``('mp',)`` serving mesh shards
    the TARGET's weights/KV per head and the verify executable runs
    per-shard through the same fused route as plain decode; the drafter
    stays effectively single-shard (it is tiny) — its weights and KV
    ride the mesh replicated unless its own head count divides mp, in
    which case its kernel shards too. Meshes with non-'mp' axes of
    degree > 1 are refused with a structured ``spec_mesh_refused``
    event (spec decode has no batch/pipeline axis to map them to).
    """

    def __init__(self, model, draft_model, draft_k=4,
                 draft_num_blocks=None, **kw):
        mesh = kw.get("mesh")
        if mesh is not None:
            extra = {a: int(s)
                     for a, s in zip(mesh.axis_names, mesh.devices.shape)
                     if a != "mp" and int(s) > 1}
            if extra:
                _refuse_mesh(
                    "non_mp_axes",
                    "DraftVerifyEngine supports only the one-axis "
                    f"('mp',) serving mesh; got extra axes {extra} — "
                    "spec decode has no batch or pipeline dimension to "
                    "map them to", axes=extra)
        super().__init__(model, **kw)
        self._heads_cache_only("spec_decode (DraftVerifyEngine)")
        self.draft_k = int(draft_k)
        if self.draft_k < 1:
            raise ValueError("draft_k must be >= 1")
        dgpt = getattr(draft_model, "gpt", draft_model)
        if not hasattr(dgpt, "blocks") or not hasattr(dgpt, "embeddings"):
            raise TypeError(
                "draft_model needs a GPTModel-shaped decoder; got "
                f"{type(draft_model).__name__}")
        if dgpt.cfg.vocab_size != self._gpt.cfg.vocab_size:
            raise ValueError(
                f"drafter vocab {dgpt.cfg.vocab_size} != target vocab "
                f"{self._gpt.cfg.vocab_size} — acceptance compares token "
                "ids, the vocabularies must match")
        if dgpt.cfg.seq_len < self.max_seq_len:
            raise ValueError(
                f"drafter position range {dgpt.cfg.seq_len} < engine "
                f"max_seq_len {self.max_seq_len}")
        if hasattr(draft_model, "eval"):
            draft_model.eval()
        self._draft_model = draft_model
        self._dgpt = dgpt
        self._dstate = dict(dgpt.state_dict())
        self._dnames = list(self._dstate)
        dwt = dgpt.embeddings.word_embeddings.weight
        self._demb_idx = next(
            i for i, n in enumerate(self._dnames)
            if self._dstate[n] is dwt)
        self._ddtype = dwt._data.dtype

        # mesh-sharded target (ISSUE 16): the drafter's weights ride the
        # mesh REPLICATED — it is tiny, and replicated placement lets
        # its arrays join the mesh-committed verify/draft executables
        # without resharding
        if self._mesh is not None:
            for n in self._dnames:
                t = self._dstate[n]
                t._data = jax.device_put(_lazy.force(t._data), self._repl)

        # the drafter's paged kernel resolves SEPARATELY against its own
        # shapes (head_dim/dtype/heads may differ from the target's);
        # same requested policy, same build-time-only contract. The
        # verify span rides the target's kernel resolved by
        # super().__init__. Under a mesh the drafter's head count rarely
        # divides mp — select demotes it to the GSPMD gather path loudly
        # (kernel_fallback, family paged_attention.draft) while the
        # target keeps its per-shard fused route.
        from ..ops import pallas_ops as _pallas_ops

        self._draft_kernel, self._draft_kernel_reason = \
            _pallas_ops.select_paged_kernel(
                kw.get("paged_kernel"),
                head_dim=dgpt.blocks[0].attn.head_dim,
                block_size=self.block_size, dtype=self._ddtype,
                mesh=self._mesh,
                num_heads=dgpt.blocks[0].attn.n_head,
                family="paged_attention.draft")
        self._draft_mesh = self._mesh if (
            self._mesh is not None
            and self._draft_kernel in ("pallas", "interpret")) else None
        if self._mesh is not None:
            _registry.gauge_set("serving.mesh.draft_kernel",
                                self._draft_kernel)
            _registry.gauge_set("serving.mesh.draft_kernel_sharded",
                                int(self._draft_mesh is not None))

        # drafter paged KV: same block geometry as the target (tables
        # share the row math), its own pool arrays (drafter head count
        # differs) and its own host-side accounting
        B = self.max_batch_size
        if draft_num_blocks is None:
            draft_num_blocks = 1 + B * self.blocks_per_slot
        self.draft_pool = BlockPool(draft_num_blocks, name="draft")
        self._dk, self._dv = dgpt.kv_cache_spec().allocate(
            self.draft_pool.num_blocks, self.block_size, self._ddtype,
            self._mesh)
        self._draft_tables = np.zeros((B, self.blocks_per_slot), np.int32)
        self._draft_blocks = [[] for _ in range(B)]
        # acceptance per weight generation (stats_dump "mesh serving"
        # section): generation -> [accepted, proposed], so a hot-swap's
        # acceptance recovery (or decay, if the drafter was not swapped)
        # is readable from stats. Only the last
        # SPEC_ACCEPT_KEEP_GENERATIONS generations keep live gauges —
        # older ones fold into one ".historic" rollup so a long-lived
        # server with frequent hot-swaps never leaks registry keys
        self._gen_accept = {}
        self._accept_historic = [0, 0]
        # per-slot token history (prompt + every emitted token, the
        # pending last token included): len == cur_len + 1 for installed
        # slots, and rows 0..cur_len-1 of the drafter's KV always hold
        # exactly history[:cur_len] — which is what lets swap_weights
        # REBUILD the drafter KV under new drafter weights (acceptance
        # recovery after a hot-swap) instead of serving stale context
        self._slot_tokens = [[] for _ in range(B)]
        # drafter ingest cursor per slot: how many prompt rows the
        # drafter's KV holds (trails the target's chunk cursor when the
        # target prefix-hits; advanced window by window)
        self._draft_ingested = [0] * B
        self._dstate_tuple = None

        self._draft_prefill_jit = jax.jit(self._draft_prefill_pure,
                                          donate_argnums=self._donate)
        self._draft_round_jit = jax.jit(self._draft_round_pure,
                                        donate_argnums=self._donate)
        self._verify_jit = jax.jit(self._verify_pure,
                                   donate_argnums=self._donate)
        # draft_garbage fault: a constant worst-case-wrong proposal block
        self._garbage_drafts = self._put(
            np.zeros((self.draft_k, B), np.int32))

    # ---------------------------------------------------- drafter state --
    def _draft_arrays(self):
        cached = self._dstate_tuple
        if cached is None:
            cached = self._dstate_tuple = tuple(
                self._dstate[n]._data for n in self._dnames)
        return cached

    def _forward_draft(self, dstate_arrays, ids, positions, ks, vs,
                       offsets, seq_lens, block_tables, kernel=None):
        """The drafter's trace-time parameter rebinding — same
        StaticFunction state-swap idiom as the target's
        ``_forward_slot``, against the drafter's own module tree."""
        paged_mesh = self._draft_mesh \
            if kernel in ("pallas", "interpret") else None
        old = {n: self._dstate[n]._data for n in self._dnames}
        for n, arr in zip(self._dnames, dstate_arrays):
            self._dstate[n]._data = arr
        try:
            with _ag.no_grad(), _lazy.lazy_guard(False):
                caches = [(Tensor(k), Tensor(v))
                          for k, v in zip(ks, vs)]
                hidden, new_caches = self._dgpt(
                    Tensor(ids), position_ids=Tensor(positions),
                    caches=caches, cache_offsets=Tensor(offsets),
                    seq_lens=Tensor(seq_lens),
                    block_tables=Tensor(block_tables),
                    paged_kernel=kernel, paged_mesh=paged_mesh)
            return (hidden._data,
                    tuple(c[0]._data for c in new_caches),
                    tuple(c[1]._data for c in new_caches))
        finally:
            for n in self._dnames:
                self._dstate[n]._data = old[n]

    # ----------------------------------------------------- pure step fns --
    def _draft_prefill_pure(self, dstate, ks, vs, ids, start, end,
                            block_table):
        """Drafter prompt ingestion at bucket shape [1, L]: fills the
        drafter's KV rows start..end-1 (start/end are data, so a full
        prompt and a chunk window share one executable per bucket).  No
        sampling — the target's prefill sample is the authoritative
        first token; the drafter only needs the context."""
        L = ids.shape[1]
        positions = jnp.minimum(
            start[:, None] + jnp.arange(L, dtype=jnp.int32)[None],
            self.max_seq_len - 1)
        _, nk, nv = self._forward_draft(
            dstate, ids, positions, ks, vs, start, end, block_table)
        return nk, nv

    def _draft_round_pure(self, dstate, ks, vs, last_tokens, cur_lens,
                          keys, gen_idx, temps, top_ks, top_ps,
                          block_tables):
        """The WHOLE drafting round as one executable: a fixed-trip
        ``lax.scan`` of K+1 drafter [B, 1] steps.  Step j feeds each
        slot's chained token at row cur_len+j, scatters its drafter-KV
        row, and samples the proposal with the SAME seeded Gumbel draw
        the target will replay at generated-token index gen_idx+j — at
        temperature 0 this is greedy drafting, above it the drafter
        mimics the exact noise realization, which is what keeps
        acceptance high for sampled requests.  The final step ingests
        d_K (proposal discarded) so the drafter's KV never trails the
        accepted sequence after an all-accept round.  One scan = one
        dispatch per round instead of K+1 — the drafter's host overhead
        does not scale with K."""
        w = dstate[self._demb_idx]

        def step(carry, j):
            feed, ks, vs = carry
            rows = cur_lens + j
            positions = jnp.minimum(rows, self.max_seq_len - 1)[:, None]
            hidden, nk, nv = self._forward_draft(
                dstate, feed[:, None], positions, ks, vs,
                positions[:, 0], rows + 1, block_tables,
                kernel=self._draft_kernel)
            logits = (hidden[:, 0].astype(jnp.float32)
                      @ w.T.astype(jnp.float32))
            gum = _sampling.gumbel_rows(keys, gen_idx + j,
                                        logits.shape[-1])
            toks = _sampling.sample_tokens(logits, temps, top_ks,
                                           top_ps, gum)
            return (toks, nk, nv), toks

        (_, nk, nv), props = jax.lax.scan(
            step, (last_tokens, ks, vs),
            jnp.arange(self.draft_k + 1, dtype=jnp.int32))
        return props[:self.draft_k], nk, nv

    def _verify_pure(self, state, ks, vs, last_tokens, drafts, cur_lens,
                     keys, gen_idx, temps, top_ks, top_ps, active,
                     block_tables):
        """THE verify step: one [B, K+1] target forward over
        [last, d_1..d_K] (``drafts`` is the draft round's [K, B]
        proposal block), then an exact replay of the seeded Gumbel-max
        draw at every position.  ``accepts[b]`` = number of leading
        drafts equal to the target's own samples; ``emitted`` = accepts
        + 1 (the correction/bonus token), capped at the sequence
        ceiling.  Cursor state advances IN the step (masked by
        ``active``) so the steady fast path keeps it on device."""
        K = self.draft_k
        ids = jnp.concatenate([last_tokens[:, None], drafts.T], axis=1)
        offs = jnp.arange(K + 1, dtype=jnp.int32)
        positions = jnp.minimum(cur_lens[:, None] + offs[None],
                                self.max_seq_len - 1)
        # verify-span variant of the fused kernel (ISSUE 14): the [B,
        # K+1] span reads its slot's blocks through the same kernel —
        # the causal intra-span mask falls out of the position mask
        hidden, nk, nv = self._forward_slot(
            state, ids, positions, ks, vs, cur_lens,
            cur_lens + K + 1, block_tables,
            kernel=self._paged_kernel)
        w = state[self._head_idx]
        B = ids.shape[0]
        flat = hidden.astype(jnp.float32).reshape(B * (K + 1), -1)
        logits = flat @ w.T.astype(jnp.float32)
        rep = lambda a: jnp.repeat(a, K + 1, axis=0)  # noqa: E731
        idxs = (gen_idx[:, None] + offs[None]).reshape(-1)
        gum = _sampling.gumbel_rows(rep(keys), idxs, logits.shape[-1])
        toks = _sampling.sample_tokens(
            logits, rep(temps), rep(top_ks), rep(top_ps), gum)
        sampled = toks.reshape(B, K + 1)
        matches = (sampled[:, :K] == ids[:, 1:]).astype(jnp.int32)
        accepts = jnp.cumprod(matches, axis=1).sum(axis=1)
        emitted = jnp.where(
            active,
            jnp.minimum(accepts + 1, self.max_seq_len - cur_lens),
            0).astype(cur_lens.dtype)
        last_idx = jnp.maximum(emitted - 1, 0)
        new_last = jnp.where(
            active & (emitted > 0),
            jnp.take_along_axis(sampled, last_idx[:, None], axis=1)[:, 0],
            last_tokens)
        return (sampled, accepts, emitted, nk, nv, new_last,
                cur_lens + emitted,
                gen_idx + emitted.astype(gen_idx.dtype))

    # --------------------------------------------------------- admission --
    def can_admit(self, prompt_ids, max_new_tokens=None):
        """Both pools must cover the worst case: the target's (prefix
        discount counted, as before) AND the drafter's (no prefix
        sharing — the drafter always ingests the full prompt)."""
        if not super().can_admit(prompt_ids, max_new_tokens):
            return False
        return self.blocks_needed(len(prompt_ids), max_new_tokens) \
            <= self.draft_pool.free_count()

    def can_import(self, payload):
        if not super().can_import(payload):
            return False
        # adopted slots budget the drafter's worst case (max_new unknown
        # on this side → full ceiling), mirroring the conservative
        # contract: True ⇒ the import cannot raise
        return self.blocks_per_slot <= self.draft_pool.free_count()

    def _reserve_extra(self, slot, prompt, max_new_tokens):
        """Reserve the drafter's worst-case block budget at ADMISSION
        time (``begin_prefill`` calls this before any chunk lands, so a
        drafter-pool shortage is admission backpressure, never a
        mid-flight failure; the scheduler's ``can_admit`` pre-check
        makes it unreachable in normal operation).  The drafter skips
        the prefix cache — it is cheap by design and shared blocks
        would pin two pools together."""
        if self._draft_blocks[slot]:
            return  # already reserved (chunked admission)
        need = self.blocks_needed(len(prompt), max_new_tokens)
        fresh = self.draft_pool.alloc(need)
        dt_row = np.zeros(self.blocks_per_slot, np.int32)
        dt_row[:need] = fresh
        self._draft_blocks[slot] = fresh
        self._draft_tables[slot] = dt_row
        self._draft_ingested[slot] = 0
        used = self.draft_pool.in_use()
        if used > _counters["draft_kv_blocks_hwm"]:
            _counters["draft_kv_blocks_hwm"] = used

    def _draft_ingest(self, slot, prompt, end):
        """Feed drafter KV rows up to ``end``: one [1, L] window from
        the drafter's own progress cursor (the drafter has no prefix
        cache, so its cursor can trail the target's chunk start)."""
        start = self._draft_ingested[slot]
        if end <= start:
            return
        window = prompt[start:end]
        L = self.bucket_for(len(window))
        ids = np.zeros((1, L), np.int32)
        ids[0, :len(window)] = window
        args = (self._draft_arrays(), tuple(self._dk), tuple(self._dv),
                self._put(ids),
                self._put(np.asarray([start], np.int32)),
                self._put(np.asarray([end], np.int32)),
                self._put(self._draft_tables[slot][None]))
        self._note_signature(
            "draft", args[3:],
            f"draft_prefill bucket_len={L}")
        nk, nv = self._draft_prefill_jit(*args)
        self._dk, self._dv = list(nk), list(nv)
        self._draft_ingested[slot] = end
        _counters["draft_prefills"] += 1

    def _chunk_extra(self, slot, prompt, start, end):
        """Per-chunk hook: the drafter ingests (at least) the same
        window, so a chunked admission's drafter catch-up is bounded by
        ~one chunk per step too — no whole-prompt drafter stall at
        installation (the first chunk additionally covers the target's
        prefix-cache hit span, which the drafter must compute)."""
        self._draft_ingest(slot, prompt, end)

    def _install_extra(self, slot, prompt, max_new_tokens):
        """Admission hook: reserve (if the chunked path hasn't already)
        and finish the drafter's prompt ingestion."""
        self._reserve_extra(slot, prompt, max_new_tokens)
        try:
            self._draft_ingest(slot, prompt, len(prompt))
        except Exception:
            self.draft_pool.decref(self._draft_blocks[slot])
            self._draft_blocks[slot] = []
            self._draft_tables[slot] = 0
            self._draft_ingested[slot] = 0
            raise

    def _install_slot(self, slot, prompt, table_ids, bt_row, tok, key,
                      temperature, top_k, top_p, matched_prefix,
                      max_new_tokens):
        super()._install_slot(slot, prompt, table_ids, bt_row, tok, key,
                              temperature, top_k, top_p, matched_prefix,
                              max_new_tokens)
        # token history starts as prompt + pending first token
        # (len == cur_len + 1, the standing invariant)
        self._slot_tokens[slot] = [int(t) for t in prompt] + [int(tok)]

    def _finish_decode(self, active, n_active, toks):
        # plain decode_step on a spec engine (scheduler fallback) must
        # keep the history invariant too — each step appends its one
        # emitted token
        super()._finish_decode(active, n_active, toks)
        for b in np.nonzero(active)[0]:
            self._slot_tokens[b].append(int(toks[b]))
        return toks

    def release(self, slot):
        if self._draft_blocks[slot]:
            self.draft_pool.decref(self._draft_blocks[slot])
            self._draft_blocks[slot] = []
        self._draft_tables[slot] = 0
        self._draft_ingested[slot] = 0
        self._slot_tokens[slot] = []
        super().release(slot)

    def import_request_kv(self, slot, payload, prompt_ids=None):
        """Adopt a prefill-pod handoff: the target KV arrives verbatim
        (bitwise), the DRAFTER re-ingests the prompt locally — its KV
        never crosses the wire (drafter geometries may differ pod to
        pod, and drafter state is a throughput hint, never correctness).
        Only fresh handoffs (cur_len == prompt length) are adoptable:
        past that the drafter would be missing generated context."""
        if prompt_ids is None:
            raise ValueError(
                "DraftVerifyEngine.import_request_kv needs prompt_ids "
                "(the drafter re-ingests the prompt)")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if int(payload["cur_len"]) != len(prompt):
            raise ValueError(
                "DraftVerifyEngine only adopts fresh prefill handoffs "
                f"(payload cur_len {payload['cur_len']} != prompt length "
                f"{len(prompt)}) — the drafter cannot reconstruct "
                "mid-generation context")
        first = super().import_request_kv(slot, payload,
                                          prompt_ids=prompt_ids)
        try:
            self._install_extra(slot, prompt, None)
        except Exception:
            super().release(slot)
            raise
        self._slot_tokens[slot] = [int(t) for t in prompt] \
            + [int(self._last_tokens[slot])]
        return first

    # ------------------------------------------------------- weight swap --
    def swap_weights(self, state, source=None, draft_state=None):
        """Target hot-swap, optionally with a matching drafter swap.

        Without ``draft_state`` this is the inherited target swap:
        emitted tokens stay bitwise-correct (acceptance is re-checked
        against the new target every round) but the drafter now guesses
        from stale weights, so acceptance decays. With ``draft_state``
        the drafter's weights swap in the SAME all-or-nothing commit
        (both states validate before either engine mutates), and every
        in-flight slot's drafter KV is REBUILT from its token history
        under the new drafter weights — acceptance recovers immediately
        instead of paying a stale-context penalty for the rest of each
        stream."""
        dstaged = None
        if draft_state is not None:
            dresolved = self._resolve_swap_state(draft_state,
                                                 names=self._dnames)
            dstaged = self._stage_swap(dresolved, self._dnames,
                                       self._dstate)
        super().swap_weights(state, source=source)
        if dstaged is None:
            return
        for n, arr in zip(self._dnames, dstaged):
            self._dstate[n]._data = arr
        self._dstate_tuple = None
        self._rebuild_draft_kv()
        _counters["draft_swaps"] += 1
        _explain.record(
            "serving_draft_swap", op="swap_weights",
            why=f"swapped {len(dstaged)} drafter weights"
                + (f" from {source}" if source else "")
                + "; every in-flight slot's drafter KV was rebuilt from "
                  "its token history, so acceptance recovers immediately "
                  "instead of decaying against stale draft context",
            weights=len(dstaged), source=source)

    def _rebuild_draft_kv(self):
        """Recompute every in-flight slot's drafter KV under the CURRENT
        drafter weights by re-ingesting its token history (prompt +
        emitted tokens) window by window — the same ``_draft_ingest``
        path chunked admission uses, so window lengths stay inside the
        bucket ladder and no new executable shapes appear. Rows past the
        re-ingested span hold stale garbage, exactly like rejected
        speculation rows: masked out of every read and overwritten by
        the next round's writes."""
        maxw = self.buckets[-1]
        for slot in range(self.max_batch_size):
            if self._active[slot]:
                hist = self._slot_tokens[slot]
                end = int(self._cur_lens[slot])
            elif slot in self._mid_prefill:
                # mid-chunked-admission: the drafter had ingested the
                # prompt up to its cursor; redo that span under the new
                # weights (remaining chunks continue from there)
                hist = list(self._mid_prefill[slot]["prompt"])
                end = self._draft_ingested[slot]
            else:
                continue
            if end <= 0:
                continue
            if len(hist) < end:  # history can't cover the KV: refuse
                raise RuntimeError(
                    f"slot {slot}: token history ({len(hist)}) shorter "
                    f"than cur_len ({end}) — drafter KV cannot be "
                    "rebuilt; this is a bookkeeping bug")
            self._draft_ingested[slot] = 0
            while self._draft_ingested[slot] < end:
                self._draft_ingest(
                    slot, hist,
                    min(self._draft_ingested[slot] + maxw, end))

    # ------------------------------------------------------------ decode --
    def reprime(self):
        """Transient-fault recovery: rebuild the verify + drafter
        executables alongside the base decode path and forget their
        radar signatures (the retry's recompiles must count)."""
        super().reprime()
        self._verify_jit = jax.jit(self._verify_pure,
                                   donate_argnums=self._donate)
        self._draft_round_jit = jax.jit(self._draft_round_pure,
                                        donate_argnums=self._donate)
        self._seen_sigs = {s for s in self._seen_sigs
                           if s[0] not in ("verify", "draft")}

    def decode_step_spec(self):
        """One speculative iteration over all slots: K+1 drafter steps,
        one [B, K+1] target verify, exact acceptance.  Returns a list of
        per-slot emitted-token lists (empty for inactive lanes) — 1 to
        K+1 tokens per active slot, each bitwise-equal to what
        ``decode_step`` would have produced one at a time.

        Steady fast path (PR 8 contract): between batch-boundary events
        the round runs on a prebuilt device-side arg tuple — no host
        uploads, no radar walk; a periodic audit cross-checks device
        cursors against the host mirrors and demotes on mismatch."""
        active = self._active
        n_active = int(active.sum())
        if n_active == 0:
            raise RuntimeError("decode_step_spec with no active slots")
        if _faults.ACTIVE:
            _faults.fire("slow_decode")
            _faults.fire("pod_slow")
            _faults.fire("replica_kill")
            _faults.fire("decode_error")
        fast = self._fast
        if fast is not None \
                and self._decode_since_audit + 1 >= self._audit_every:
            self._audit_fast(fast)
            fast = self._fast
        if fast is None:
            fast = (self._put(self._last_tokens),
                    self._put(self._cur_lens), self._put(self._keys),
                    self._put(self._gen_idx), self._put(self._temps),
                    self._put(self._top_ks), self._put(self._top_ps),
                    self._put(active), self._put(self._block_tables),
                    self._put(self._draft_tables))
            # radar probe with the real call's avals (the proposal block
            # is i32[K, B] like the garbage const) so a verify retrace
            # is loud
            probe = (self._state_arrays(), tuple(self._k),
                     tuple(self._v), fast[0],
                     self._garbage_drafts) + fast[1:9]
            self._note_signature(
                "verify", probe,
                f"K={self.draft_k}, max_batch={self.max_batch_size}")
            self._note_signature(
                "draft", (fast[0], fast[1], fast[9]),
                f"draft round K={self.draft_k}")
            self._decode_since_audit = 0
            _fp_counters["decode_rebuilds"] += 1
        else:
            self._decode_since_audit += 1
            _fp_counters["decode_fast_steps"] += 1
        return self._spec_round(fast, active, n_active)

    def _spec_round(self, fast, active, n_active):
        (last, lens, keys, gen, temps, tks, tps, act, bt, dbt) = fast
        K = self.draft_k
        dstate = self._draft_arrays()
        # the decode-step span sits AROUND the two executable calls (PR 8
        # contract: no span work inside the replayed round)
        with _span("serving.decode_step"):
            drafts, ndk, ndv = self._draft_round_jit(
                dstate, tuple(self._dk), tuple(self._dv), last, lens,
                keys, gen, temps, tks, tps, dbt)
            self._dk, self._dv = list(ndk), list(ndv)
            if _faults.ACTIVE and _faults.fire("draft_garbage"):
                # worst-case-wrong drafter: every proposal replaced by a
                # constant.  Acceptance must reject them all and the
                # emitted stream must stay bitwise-identical — the
                # drafter's own (correct) KV ingests above are stale
                # rows the next round overwrites either way.
                drafts = self._garbage_drafts
            (sampled_d, accepts_d, emitted_d, nk, nv, nlast, nlens,
             ngen) = self._verify_jit(
                self._state_arrays(), tuple(self._k), tuple(self._v),
                last, drafts, lens, keys, gen, temps, tks, tps,
                act, bt)
            with _span("serving.decode_sync"):
                sampled = np.asarray(sampled_d)
                accepts = np.asarray(accepts_d)
                emitted = np.asarray(emitted_d)
        self._k, self._v = list(nk), list(nv)
        self._fast = (nlast, nlens, keys, ngen, temps, tks, tps, act,
                      bt, dbt)
        out = [[] for _ in range(self.max_batch_size)]
        total = 0
        c = _counters
        gen_acc = self._gen_accept.setdefault(
            self.prefix_cache.generation, [0, 0])
        for b in np.nonzero(active)[0]:
            m = int(emitted[b])
            toks = [int(t) for t in sampled[b, :m]]
            out[b] = toks
            total += m
            self._cur_lens[b] += m
            self._gen_idx[b] += m
            if m:
                self._last_tokens[b] = toks[-1]
                self._slot_tokens[b].extend(toks)
            c["spec_accepted"] += int(accepts[b])
            c["spec_proposed"] += K
            c["spec_emitted"] += m
            gen_acc[0] += int(accepts[b])
            gen_acc[1] += K
        c["spec_rounds"] += 1
        c["spec_slot_rounds"] += n_active
        if gen_acc[1]:
            # per-weight-generation acceptance (stats_dump "mesh
            # serving" section reads these gauges)
            _registry.gauge_set(
                f"serving.spec_acceptance.gen{self.prefix_cache.generation}",
                round(gen_acc[0] / gen_acc[1], 4))
            if len(self._gen_accept) > SPEC_ACCEPT_KEEP_GENERATIONS:
                self._retire_old_generations()
        sc = _serving_counters
        sc["decode_steps"] += 1
        self._count_filter_steps(active)
        sc["active_slot_steps"] += n_active
        sc["tokens_generated"] += total
        _registry.gauge_set("serving.batch_occupancy",
                            n_active / self.max_batch_size)
        return out

    def _retire_old_generations(self):
        """Fold generations beyond the last
        ``SPEC_ACCEPT_KEEP_GENERATIONS`` into the ``.historic`` rollup
        and retire their gauges — bounded registry keys no matter how
        many hot-swaps a server lives through."""
        while len(self._gen_accept) > SPEC_ACCEPT_KEEP_GENERATIONS:
            g = min(self._gen_accept)
            acc, prop = self._gen_accept.pop(g)
            self._accept_historic[0] += acc
            self._accept_historic[1] += prop
            _registry.gauge_drop(f"serving.spec_acceptance.gen{g}")
        if self._accept_historic[1]:
            _registry.gauge_set(
                "serving.spec_acceptance.historic",
                round(self._accept_historic[0]
                      / self._accept_historic[1], 4))

    def _audit_fast(self, fast):
        """Spec-round audit: base cursor checks plus the drafter's block
        tables (index 9 of the spec fast tuple)."""
        _fp_counters["decode_audit_runs"] += 1
        self._decode_since_audit = 0
        ok = (np.array_equal(np.asarray(fast[0]), self._last_tokens)
              and np.array_equal(np.asarray(fast[1]), self._cur_lens)
              and np.array_equal(np.asarray(fast[3]), self._gen_idx)
              and np.array_equal(np.asarray(fast[7]), self._active)
              and np.array_equal(np.asarray(fast[8]), self._block_tables)
              and np.array_equal(np.asarray(fast[9]),
                                 self._draft_tables))
        if not ok:
            _fp_counters["decode_demotions"] += 1
            self._fast = None
            _explain.record(
                "fastpath_demoted", op="serving.spec_decode",
                reason="decode_audit",
                why="spec-decode audit: device-side slot state diverged "
                    "from the host mirrors; rebuilding from host state")

    # -------------------------------------------------------------- stats --
    def acceptance_rate(self):
        p = _counters["spec_proposed"]
        return _counters["spec_accepted"] / p if p else 0.0

    def accepted_len_mean(self):
        """Mean tokens emitted per slot per speculative round (1.0 =
        plain-decode speed, K+1 = perfect drafter)."""
        r = _counters["spec_slot_rounds"]
        return _counters["spec_emitted"] / r if r else 0.0

    def acceptance_by_generation(self):
        """Acceptance rate per weight generation (the prefix-cache
        generation a round ran under): a hot-swap that also swapped the
        drafter shows recovery here; a target-only swap shows decay."""
        return {int(g): (a / p if p else 0.0)
                for g, (a, p) in sorted(self._gen_accept.items())}

    def describe_sharding(self):
        desc = super().describe_sharding()
        for i, (k, v) in enumerate(zip(self._dk, self._dv)):
            heads = self._dgpt.blocks[i].attn.n_head
            for name, a in (("k", k), ("v", v)):
                desc["kv_pools"].append({
                    **_pool_record(i, f"draft_{name}", a, heads,
                                   self._mesh), "draft": True})
        desc["draft_paged_kernel"] = self._draft_kernel
        desc["draft_kernel_sharded"] = self._draft_mesh is not None
        return desc

    def stats(self):
        out = {**super().stats(),
               "draft_paged_kernel": self._draft_kernel,
               "draft_paged_kernel_reason": self._draft_kernel_reason,
               "draft_k": self.draft_k,
               "acceptance_rate": self.acceptance_rate(),
               "accepted_len_mean": self.accepted_len_mean(),
               "acceptance_by_generation":
                   self.acceptance_by_generation(),
               "acceptance_historic":
                   (self._accept_historic[0] / self._accept_historic[1]
                    if self._accept_historic[1] else 0.0),
               "draft_kv_blocks_total": self.draft_pool.usable_blocks,
               "draft_kv_blocks_in_use": self.draft_pool.in_use()}
        if self._mesh is not None:
            out["draft_kernel_sharded"] = self._draft_mesh is not None
        return out
