"""Pipeline parallelism through the one-compilation SPMD path (ISSUE 15).

PR 6 made dp x mp a property of ONE captured executable (lazy step capture
+ NamedSharding specs, distributed/spmd.py); pp > 1 still fell back to the
per-op `HybridParallelEngine`, which can never ride the PR 8 zero-dispatch
`ReplayStep` fast path. This module makes pp a first-class citizen of the
captured step:

  * the uniform block trunk is STACKED into `[L, ...]` parameters sharded
    over the folded mesh's 'pp' axis (spmd.mesh_from_hcg builds
    ('dp', 'pp', 'mp') when pp_degree > 1) — each stage owns L/pp layers
    of every trunk weight, the t5x axis-rules idiom generalized
    (SNIPPETS [2]);
  * the microbatch schedule is expressed INSIDE one op: a `lax.scan` over
    M + pp - 1 lockstep ticks carrying a `[pp, mb, ...]` stage-activation
    buffer. Each tick ingests the next microbatch's embedding into slot
    0, runs every stage's layer slice (a scan over L/pp layers of a
    stage-vmapped block), reads the last slot into the masked loss, and
    SHIFTS the buffer one stage with `jnp.roll` on the pp-sharded dim —
    GSPMD lowers that roll to the inter-stage collective-permute
    (SNIPPETS [3]; verified: the compiled HLO carries the
    collective-permutes, no Python issues any). Backward is
    `jax.value_and_grad` THROUGH the schedule (GPipe: the transposed
    rolls carry the cotangents backward stage-to-stage).
  * the whole thing — pipeline fwd+bwd, then the optimizer update ops —
    is ONE lazy-captured segment: `forward(_PipelineKernel, ...)` records
    a single multi-output op (loss + one grad per param), the optimizer
    consumes those grads through the normal dispatch path, and the
    captured plan compiles ONCE with the live pp/dp/mp shardings pinned
    as in/out specs and donation on the stacked stage params + slots
    (exactly as PR 6 pinned params/slots). Steady state replays through
    `core/lazy.ReplayStep`: zero dispatched ops, zero per-step Python
    collectives.

Schedule choice (see DESIGN_DECISIONS.md "Pipeline in one executable"):
GPipe-via-autodiff rather than the engine's hand-scheduled 1F1B. The
engine keeps 1F1B for its O(pp) activation memory; here the priority is
riding capture/replay unchanged, and autodiff through the tick scan
keeps the schedule ~80 lines and provably grad-exact against the dense
oracle. Activation residuals are O(M) per stage (scan stashes each
tick's carry); `recompute=True` wraps the per-block body in
`jax.checkpoint` for the usual trade.

jaxlib note: no `shard_map` and no `with_sharding_constraint` on the
loop carry — on the jaxlib this step was first written against,
manual-'pp'-plus-auto-axes regions failed to lower and a constraint on
the scanned activation buffer miscompiled its gradient (bisected; the
executable-boundary in_shardings the capture engine pins are sufficient
to drive propagation). Not re-examined on the installed jaxlib
(ROADMAP D9).
"""
from __future__ import annotations

import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import dispatch as _dispatch
from ..core import lazy as _lazy
from ..core import autograd as _autograd
from ..core.tensor import Parameter, Tensor
from ..profiler import explainer as _explain
from ..profiler import registry as _registry
from . import spmd
from .meta_parallel.pp_layers import PipelineLayer, PipelineStageError

__all__ = ["PipelineSpmdStep", "PipelineStageError"]

# static pipeline facts for tools/stats_dump.py's "pipeline" section;
# gauges (not counters): they describe the CURRENT step's topology
_counters = _registry.scoped_counters("pp", {"steps_built": 0})


def _refuse(reason, why, **detail):
    _explain.record("spmd_pp_refused", op="pp_spmd", reason=reason,
                    why=why, **detail)
    return PipelineStageError(why)


def _model_parts(model, pp, criterion):
    """(embed, trunk_blocks, head, criterion) stage slicing.

    Three protocols, most specific first:
      * `model.pipeline_parts(pp)` — models that know their own slicing
        (GPTForPretraining: embeddings / block trunk / ln_f + tied head);
      * `PipelineLayer.segment_for_pipeline(pp)` — explicit LayerDesc
        lists (pre entries -> stage 0, post entries -> last stage);
      * generic uniform-trunk discovery (hybrid_engine._find_block_stack)
        for gpt-shaped models exposing .embeddings / .ln_f.
    """
    if hasattr(model, "pipeline_parts"):
        embed, trunk, head = model.pipeline_parts(pp)
        return embed, trunk, head, criterion
    if isinstance(model, PipelineLayer):
        pre, trunk, post = model.segment_for_pipeline(pp)

        def embed(toks):
            x = toks
            for e in pre:
                x = model._apply(e, x)
            return x

        def head(x):
            for e in post:
                x = model._apply(e, x)
            return x

        return embed, trunk, head, criterion or model._loss_fn
    from .fleet.hybrid_engine import _find_block_stack

    stack = _find_block_stack(model)
    gpt = getattr(model, "gpt", model)
    if stack is None or not hasattr(gpt, "embeddings"):
        raise _refuse(
            "no_uniform_trunk",
            "PipelineSpmdStep needs a model with a uniform block trunk "
            "and known embed/head slicing: implement pipeline_parts(pp) "
            "(models/gpt.py does), build a PipelineLayer from LayerDescs, "
            "or keep pp on the HybridParallelEngine path")
    _, blocks = stack

    def embed(toks):
        return gpt.embeddings(toks)

    def head(x):
        x = gpt.ln_f(x)
        w = gpt.embeddings.word_embeddings.weight
        from .. import ops

        return ops.matmul(x, w, transpose_y=True)

    return embed, list(blocks), head, criterion


class _PipelineKernel:
    """The single recorded op: (trunk stacks..., other params..., tokens,
    labels) -> (loss, d_stack..., d_other...).

    A callable OBJECT on purpose: `lazy.fn_key` keys kernels without
    `__code__` by pinned identity, so the op stays cache-stable across
    steps (a per-step closure would defeat the segment cache and capture
    promotion). All schedule/topology facts are static attributes of the
    owning step; only arrays flow through the call.
    """

    def __init__(self, step):
        self._step = step

    def __call__(self, *arrays):
        s = self._step
        nk = len(s.block_keys)
        no = len(s.other_tensors)
        stacks = arrays[:nk]
        other = arrays[nk:nk + no]
        toks, labels = arrays[nk + no], arrays[nk + no + 1]
        # model code dispatches through forward(); inside this kernel the
        # inputs are tracers of the ENCLOSING executable, so ops must run
        # plain-eager (lazy recording of a tracer leaf would wedge the
        # segment) and tape-free (jax.value_and_grad is the
        # differentiator, as in the engine)
        with _lazy.lazy_guard(False), _autograd._scoped(False):
            loss, d_stacks, d_other = s._loss_and_grads(
                stacks, other, toks, labels)
        return (loss,) + tuple(d_stacks) + tuple(d_other)


class PipelineSpmdStep:
    """dp x mp x pp train step as ONE captured executable.

    Usage (mirrors the engine's flow; fleet.init must have installed the
    pp-folded SPMD mesh — hybrid_configs use_spmd with pp_degree > 1):

        step = PipelineSpmdStep(model, opt, criterion=crit,
                                accumulate_steps=M)
        for _ in range(n):
            loss = step.train_batch([tokens, labels])   # Tensor

    The constructor RESTRUCTURES training state: the trunk's per-layer
    params are stacked into `[L, ...]` Parameters sharded over 'pp' and
    swapped into the optimizer's parameter list (pass a freshly-built
    optimizer — existing accumulator slots keyed to the per-layer params
    would be orphaned). `sync_params_to_model()` writes the trained
    stacks back into the per-layer tensors for save/eval.
    """

    def __init__(self, model, optimizer, criterion=None, hcg=None,
                 accumulate_steps=None, mesh=None, recompute=None,
                 unroll_ticks=None):
        self.model = model
        # a fleet.distributed_optimizer wrapper delegates attribute READS
        # to the inner optimizer but would absorb the parameter-list
        # WRITE below on the wrapper instance — the inner step() would
        # keep updating the stale per-layer list (no grads, silent
        # plateau); always restructure the real optimizer
        optimizer = getattr(optimizer, "inner_opt", optimizer)
        self.optimizer = optimizer
        mesh = mesh or spmd.current_mesh()
        if mesh is None or "pp" not in mesh.axis_names:
            raise RuntimeError(
                "PipelineSpmdStep: no pp-folded SPMD mesh installed — "
                "fleet.init with hybrid_configs use_spmd and pp_degree>1 "
                "(or spmd.enable a ('dp','pp','mp') mesh) first")
        self.mesh = mesh
        axes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.pp = int(axes["pp"])
        if hcg is None:
            from . import fleet as _fleet

            hcg = _fleet._fleet_state.get("hcg")
        if accumulate_steps is None and hcg is not None:
            from . import fleet as _fleet

            strat = _fleet._fleet_state.get("strategy")
            if strat is not None:
                accumulate_steps = strat.pipeline_configs.get(
                    "accumulate_steps")
        # honor an EXPLICIT accumulate_steps exactly — the lockstep
        # schedule is correct for M < pp too (every microbatch's loss
        # tick lands inside the M+pp-1 scan), just bubblier; only the
        # unset default scales with pp
        if accumulate_steps is None:
            self.M = max(self.pp, 1)
        else:
            self.M = int(accumulate_steps)
            if self.M < 1:
                raise _refuse(
                    "bad_accumulate_steps",
                    f"accumulate_steps={accumulate_steps} must be >= 1",
                    accumulate_steps=self.M)

        self.embed, blocks, self.head, self.criterion = _model_parts(
            model, self.pp, criterion)
        self.n_layers = len(blocks)
        if self.n_layers % self.pp != 0:
            raise _refuse(
                "stage_indivisible",
                f"trunk has {self.n_layers} layers, not divisible by "
                f"pp={self.pp}: every stage must own an equal layer "
                f"slice of the stacked trunk",
                n_layers=self.n_layers, pp=self.pp)
        self.Ls = self.n_layers // self.pp
        self.template = blocks[0]
        self.template_state = self.template.state_dict()
        self.block_keys = list(self.template_state.keys())
        if recompute is None:
            cfg = getattr(getattr(model, "gpt", model), "cfg", None)
            recompute = bool(getattr(cfg, "use_recompute", False))
        self.recompute = bool(recompute)
        # schedule form: unroll short tick counts (static indices/masks,
        # and the M=1 jaxlib workaround — see _pipeline_loss), scan long
        # ones (compile time O(1) in M)
        self.unroll_ticks = int(
            unroll_ticks if unroll_ticks is not None
            else os.environ.get("PADDLE_TPU_PP_UNROLL_TICKS", "8"))

        # ---- stacked stage-sharded trunk params -------------------------
        per_layer = [b.state_dict() for b in blocks]
        self._per_layer_tensors = per_layer
        trunk_ids = {id(t) for sd in per_layer for t in sd.values()}
        self.trunk_params = []
        for k in self.block_keys:
            t0 = per_layer[0][k]
            arr = jnp.stack([_lazy.force(sd[k]._data) for sd in per_layer])
            spec0 = getattr(t0, "sharding_spec", None)
            inner = spmd.param_pspec(spec0, mesh, tuple(arr.shape[1:]))
            pspec = P("pp", *inner)
            p = Parameter(jax.device_put(arr, NamedSharding(mesh, pspec)),
                          name=f"pp_stack.{k}",
                          trainable=not t0.stop_gradient)
            p.sharding_spec = ("pp",) + tuple(
                spec0 if spec0 is not None else (None,) * (arr.ndim - 1))
            p._donatable = True
            self.trunk_params.append(p)

        # ---- everything else (embeddings, final norm, tied head) -------
        self.other_names, self.other_tensors = [], []
        for name, t in model.state_dict().items():
            if id(t) not in trunk_ids:
                self.other_names.append(name)
                self.other_tensors.append(t)
        for t in self.other_tensors:
            arr = _lazy.force(t._data)
            pspec = spmd.param_pspec(getattr(t, "sharding_spec", None),
                                     mesh, tuple(arr.shape))
            target = NamedSharding(mesh, pspec)
            if getattr(arr, "sharding", None) != target:
                t._data = jax.device_put(arr, target)
            t._donatable = True

        # the optimizer updates the RESTRUCTURED state: stacked trunk +
        # non-trunk params (one logical step == the engine's update over
        # the same values — elementwise rules are stacking-transparent)
        self._grad_params = self.trunk_params + self.other_tensors
        optimizer._parameter_list = [
            p for p in self._grad_params if not p.stop_gradient]

        # stacked slots from per-layer ones: a mid-session restructure
        # (the optimizer already stepped on the per-layer params, or a
        # checkpoint restored their slots) must not silently zero the
        # Adam moments — stack them exactly like the params
        self._adopt_per_layer_slots(per_layer, mesh)

        self._kernel = _PipelineKernel(self)
        self._replay = _lazy.ReplayStep(self._body, optimizers=optimizer)
        self._batch_checked = False
        self._steps = 0
        self._synced_steps = 0

        # static pipeline facts for stats_dump's "pipeline" section
        trunk_bytes = sum(
            int(np.prod(p._data.shape)) * np.dtype(p._data.dtype).itemsize
            for p in self.trunk_params)
        _counters["steps_built"] += 1
        _registry.gauge_set("pp.stages", self.pp)
        _registry.gauge_set("pp.microbatches", self.M)
        _registry.gauge_set("pp.trunk_layers", self.n_layers)
        _registry.gauge_set("pp.trunk_params", len(self.trunk_params))
        _registry.gauge_set("pp.trunk_param_bytes", trunk_bytes)
        _registry.gauge_set("pp.stage_param_bytes",
                            trunk_bytes // self.pp)
        _explain.record(
            "spmd_pp_selected", op="pp_spmd",
            why=(f"pipeline step built on the one-compilation SPMD path: "
                 f"{self.pp} stages x {self.Ls} layers, {self.M} "
                 f"microbatches inside one captured executable"),
            stages=self.pp, layers_per_stage=self.Ls,
            microbatches=self.M,
            schedule=("unrolled" if self.M + self.pp - 1
                      <= self.unroll_ticks else "scan"),
            mesh_axes={k: int(v) for k, v in axes.items()})

    def _adopt_per_layer_slots(self, per_layer, mesh):
        """Stack existing per-layer accumulator slots onto the stacked
        trunk params (and drop the per-layer entries). No-op for a fresh
        optimizer; for a stepped/restored one this carries the Adam
        moments through the restructure instead of zeroing them. Also
        evicts slots keyed to params no longer in the parameter list —
        without this, every restructure (mesh change, checkpoint
        reload) would leak the PREVIOUS step's stacked m/v buffers, and
        a stale id could even collide with a future object's id."""
        opt = self.optimizer
        for name, store in list(opt._accumulators.items()):
            for k, p_new in zip(self.block_keys, self.trunk_params):
                olds = [store.get(id(sd[k])) for sd in per_layer]
                if any(o is None for o in olds):
                    continue
                arr = jnp.stack([_lazy.force(o._data) for o in olds])
                inner = spmd.param_pspec(
                    getattr(per_layer[0][k], "sharding_spec", None),
                    mesh, tuple(arr.shape[1:]))
                t = Tensor(jax.device_put(
                    arr, NamedSharding(mesh, P("pp", *inner))))
                t._donatable = True
                store[id(p_new)] = t
                for sd in per_layer:
                    store.pop(id(sd[k]), None)
            live = {id(p) for p in self._grad_params}
            for key in [k for k in store if k not in live]:
                del store[key]

    # ------------------------------------------------------------- step --
    def _body(self, toks, labels):
        from .. import incubate

        with incubate.lazy_eval():
            outs = _dispatch.forward(
                self._kernel,
                [*self.trunk_params, *self.other_tensors, toks, labels],
                name="pp_pipeline_step", nondiff=True)
            loss = outs[0]
            for p, g in zip(self._grad_params, outs[1:]):
                if not p.stop_gradient:
                    p.grad = g
            self.optimizer.step()
            self.optimizer.clear_grad()
            return loss

    def train_batch(self, data, optimizer=None, lr_scheduler=None,
                    scaler=None):
        """One pipelined train step over [tokens, labels]; returns the
        loss Tensor (detached on replayed steps). Engine-compatible
        signature so the two paths swap in tests/benches."""
        toks, labels = data[0], data[1]
        tt = spmd.shard_batch(toks, self.mesh)
        lt = spmd.shard_batch(labels, self.mesh)
        B = int(tt._data.shape[0])
        # every batch, not just the first: a ragged final batch must get
        # the structured refusal, not a raw reshape error from inside
        # the trace (one shape read — cheap on the hot path)
        if B % self.M != 0:
            raise _refuse(
                "batch_indivisible",
                f"batch size {B} is not divisible by "
                f"accumulate_steps={self.M}: the microbatch reshape "
                f"inside the captured schedule needs B % M == 0",
                batch=B, microbatches=self.M)
        if not self._batch_checked:
            self._batch_checked = True
            # static permute-traffic estimate, now that mb is known: the
            # stage shift moves the whole [pp, mb, ...] buffer one slot
            # per tick, forward and (transposed) backward
            _registry.gauge_set(
                "pp.permute_bytes_per_step",
                self._permute_bytes_estimate(B))
        self._steps += 1
        return self._replay(tt, lt)

    __call__ = train_batch

    def _permute_bytes_estimate(self, B):
        """Bytes crossing stage boundaries per step (fwd + bwd), from the
        embedding aval: (pp-1)/pp of the activation buffer per tick."""
        mb = B // self.M
        d = getattr(getattr(self.model, "gpt", self.model), "cfg", None)
        width = getattr(d, "d_model", None)
        seq = getattr(d, "seq_len", None)
        if width is None:
            return 0
        act = mb * (seq or 1) * width * 4
        ticks = self.M + self.pp - 1
        return int(2 * ticks * act * (self.pp - 1))

    @property
    def armed(self):
        """True once steady steps replay with zero dispatched ops."""
        return self._replay.armed

    # --------------------------------------------------- pipeline math --
    def _loss_and_grads(self, stacks, other, toks, labels):
        def lossf(stacks_t, other_t):
            return self._pipeline_loss(stacks_t, other_t, toks, labels)

        loss, (d_s, d_o) = jax.value_and_grad(lossf, argnums=(0, 1))(
            tuple(stacks), tuple(other))
        return loss, d_s, d_o

    def _pipeline_loss(self, stacks, other, toks, labels):
        pp, M, Ls = self.pp, self.M, self.Ls
        B = toks.shape[0]
        mb = B // M
        tok_mb = toks.reshape((M, mb) + tuple(toks.shape[1:]))
        lab_mb = labels.reshape((M, mb) + tuple(labels.shape[1:]))
        # [L, ...] -> [Ls, pp, ...]: the scan walks each stage's layer
        # slice in lockstep; pp-sharding flows in from the stacked
        # input's executable-boundary spec (no inner constraints — see
        # the module docstring's jaxlib note)
        xs = [jnp.swapaxes(s.reshape((pp, Ls) + tuple(s.shape[1:])), 0, 1)
              for s in stacks]
        saved_o = [t._data for t in self.other_tensors]
        block_tensors = [self.template_state[k] for k in self.block_keys]
        saved_b = [t._data for t in block_tensors]
        for t, a in zip(self.other_tensors, other):
            t._data = a
        try:
            def run_block(x, layer_arrays):
                for t, a in zip(block_tensors, layer_arrays):
                    t._data = a
                fwd = getattr(self.template, "_forward", None) or \
                    self.template.forward
                out = fwd(Tensor(x))
                return out._data if isinstance(out, Tensor) else out

            if self.recompute:
                run_block = jax.checkpoint(run_block)
            vblock = jax.vmap(run_block, in_axes=(0, 0))

            def run_stage(act):
                def body(a, wl):
                    return vblock(a, wl), None

                out, _ = jax.lax.scan(body, act, xs)
                return out

            def embed_arr(toks_a):
                out = self.embed(Tensor(toks_a))
                return out._data if isinstance(out, Tensor) else out

            def head_loss_arr(x_a, lab_a):
                logits = self.head(Tensor(x_a))
                if self.criterion is not None:
                    lt = self.criterion(logits, Tensor(lab_a))
                    return lt._data if isinstance(lt, Tensor) else lt
                lp = jax.nn.log_softmax(
                    logits._data.astype(jnp.float32), axis=-1)
                ll = jnp.take_along_axis(
                    lp, lab_a[..., None].astype(jnp.int32), axis=-1)
                return -ll.mean()

            x_sds = jax.eval_shape(embed_arr, tok_mb[0])
            act0 = jnp.zeros((pp,) + tuple(x_sds.shape), x_sds.dtype)
            ticks = M + pp - 1

            # lockstep GPipe ticks: microbatch i enters stage 0 at tick
            # i, exits stage pp-1 (-> masked loss) at tick i + pp - 1;
            # ticks past M re-ingest microbatch M-1 whose outputs never
            # reach a valid loss slot (zero cotangent — grad-exact, the
            # unsharded schedule matches dense grads to 1e-7)
            if ticks <= self.unroll_ticks:
                # unrolled form (the ISSUE's sanctioned alternative):
                # static microbatch indices and ingest/loss masks. Also
                # a workaround from the jaxlib this was written against
                # — differentiating the tick scan under jax_enable_x64
                # hit an s64/s32 partitioned-dynamic-update-slice
                # verifier bug at M=1 (bisected; the unrolled form never
                # builds the jvp while loop). Kept until a cell can
                # judge its removal (ROADMAP D9/D4)
                act, acc = act0, jnp.float32(0.0)
                for t in range(ticks):
                    if t < M:
                        act = act.at[0].set(
                            embed_arr(tok_mb[t]).astype(act.dtype))
                    act = run_stage(act)
                    li = t - (pp - 1)
                    if 0 <= li < M:
                        acc = acc + head_loss_arr(
                            act[pp - 1], lab_mb[li]).astype(jnp.float32)
                    act = jnp.roll(act, 1, axis=0)
                return acc / M

            def tick(carry, t):
                act, acc = carry
                fic = jnp.clip(t, 0, M - 1)
                x_in = embed_arr(tok_mb[fic])
                act = act.at[0].set(x_in.astype(act.dtype))
                act = run_stage(act)
                li = t - (pp - 1)
                lic = jnp.clip(li, 0, M - 1)
                loss_t = head_loss_arr(act[pp - 1], lab_mb[lic])
                acc = acc + jnp.where(li >= 0,
                                      loss_t.astype(jnp.float32), 0.0)
                act = jnp.roll(act, 1, axis=0)
                return (act, acc), None

            (_, acc), _ = jax.lax.scan(
                tick, (act0, jnp.float32(0.0)), jnp.arange(ticks))
            return acc / M
        finally:
            for t, a in zip(self.other_tensors, saved_o):
                t._data = a
            for t, a in zip(block_tensors, saved_b):
                t._data = a

    # ------------------------------------------------------ state sync --
    def sync_params_to_model(self):
        """Write the trained stacks back into the model's per-layer
        tensors (save/eval; the engine's contract), and mirror the
        stacked optimizer slots onto the per-layer params so a later
        restructure (mesh change -> fresh PipelineSpmdStep) re-adopts
        the Adam moments via _adopt_per_layer_slots instead of zeroing
        them. No-op when no step ran since the last sync, so per-batch
        eval callers don't pay a device round trip each time."""
        if self._synced_steps == self._steps:
            return
        self._synced_steps = self._steps
        for k, p in zip(self.block_keys, self.trunk_params):
            stacked = np.asarray(_lazy.force(p._data))
            for li, sd in enumerate(self._per_layer_tensors):
                sd[k]._data = jnp.asarray(stacked[li])
        for name, store in self.optimizer._accumulators.items():
            for k, p in zip(self.block_keys, self.trunk_params):
                slot = store.get(id(p))
                if slot is None:
                    continue
                stacked = np.asarray(_lazy.force(slot._data))
                for li, sd in enumerate(self._per_layer_tensors):
                    t = Tensor(jnp.asarray(stacked[li]))
                    t._donatable = True
                    store[id(sd[k])] = t

    def release(self):
        """Retire the step: sync the trained stacks (params + slot
        mirrors) back to the per-layer tensors, return the optimizer to
        the model's original parameter list, and evict the stacked slot
        entries — so a follow-on dense/engine/spmd path updates the real
        params (not orphaned stacks with no grads) and the trunk-scale
        stacked m/v buffers don't pin device memory for the session.
        Called by hapi on mesh change and checkpoint reload."""
        self.sync_params_to_model()
        opt = self.optimizer
        opt._parameter_list = list(self.model.parameters())
        for p in opt._parameter_list:
            if p is not None:
                p._donatable = True
        stale = {id(p) for p in self.trunk_params}
        for store in opt._accumulators.values():
            for key in [k for k in store if k in stale]:
                del store[key]

    def export_optimizer_state(self):
        """Optimizer state_dict in the CANONICAL per-layer layout (the
        same keys a dense/engine run writes), so a pp checkpoint's
        .pdopt restores on every path. Syncs first (mirrors the stacked
        slots onto the per-layer params), then serializes against the
        model's original parameter list instead of the restructured
        stacked one."""
        self.sync_params_to_model()
        opt = self.optimizer
        saved = opt._parameter_list
        # the FULL original list, not just trainables: unnamed params
        # serialize by POSITION in the list, and the dense construction
        # convention is parameters=model.parameters()
        opt._parameter_list = list(self.model.parameters())
        try:
            return opt.state_dict()
        finally:
            opt._parameter_list = saved

    def refresh_pipeline_stats(self):
        """Update the donation gauges from the live captured plan (for
        stats_dump's per-stage donation line)."""
        donated = carried = 0
        for plan in _lazy.describe_plans():
            if plan.get("first_op") != "pp_pipeline_step":
                continue
            for lf in plan.get("leaves", ()):
                if not spmd._spec_has_axis(lf.get("spec"), "pp"):
                    continue
                carried += 1 if lf.get("carried") else 0
                donated += 1 if lf.get("donated") else 0
        _registry.gauge_set("pp.stage_classes_carried", carried)
        _registry.gauge_set("pp.stage_classes_donated", donated)
        return {"carried": carried, "donated": donated}
