"""Optimizer base + SGD family.

Reference: `python/paddle/optimizer/optimizer.py` (Optimizer base),
`sgd.py`, `momentum.py`. Kernels (`phi/kernels/gpu/sgd_kernel.cu`,
`momentum_kernel`) become pure jnp update functions; under a jitted train
step XLA fuses all parameter updates into a handful of kernels (the
reference needed multi_tensor/fused_* ops for that — on TPU it's free).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core.dispatch import forward
from ..core.tensor import Parameter, Tensor
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Lars"]


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        if parameters is None:
            from ..core import dispatch

            if dispatch.static_recorder is None:
                raise ValueError(
                    "parameters is required in dygraph mode (pass "
                    "model.parameters()); static mode uses minimize().")
            parameters = []
        self._parameter_list = list(parameters)
        # donation-awareness (step capture, core/lazy.py): parameters this
        # optimizer updates are loop-carried slots — each step's input
        # buffer is the previous step's update output and the Tensor
        # rebinds past it in _apply_one. Flagging them lets the captured
        # whole-step executable donate the old buffer (in-place update)
        # once the Tensor no longer owns it; the flag alone never donates.
        for p in self._parameter_list:
            if p is not None:
                p._donatable = True
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        if isinstance(weight_decay, float):
            from .regularizer import L2Decay

            self._weight_decay = L2Decay(weight_decay)
        else:
            self._weight_decay = weight_decay
        self._accumulators: dict[str, dict[int, Tensor]] = {}
        self._opt_step = 0

    # -- lr -------------------------------------------------------------------
    def get_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate()
        return self._learning_rate

    def set_lr(self, value):
        self._learning_rate = value

    def _lr_for(self, p):
        return self.get_lr() * p.optimize_attr.get("learning_rate", 1.0) \
            if isinstance(p, Parameter) else self.get_lr()

    def _scalar_input(self, name, value):
        """f32 scalar Tensor for a dynamic hyperparameter (lr, step),
        cached by value for PYTHON scalars only: the step count and lr
        are shared by every parameter in one step, and rebuilding a
        device scalar per parameter per step is measurable overhead in
        eager/lazy loops. Traced/array values — and ANY call made while a
        trace is active — wrap fresh: a cached committed array entering a
        later sharded jit gets lifted into a hidden executable argument
        (buffer-count mismatch at dispatch), and a cached tracer poisons
        every later compile."""
        from ..core.dispatch import trace_state_clean

        if hasattr(value, "dtype") or not trace_state_clean():
            return Tensor(jnp.asarray(value, jnp.float32))
        cache = getattr(self, "_scalar_cache", None)
        if cache is None:
            cache = self._scalar_cache = {}
        # one small value->tensor map PER NAME (the step count changes
        # monotonically; lr takes a handful of values — scheduler steps
        # and per-param optimize_attr multipliers). The old flat
        # (name, value)-keyed LRU accumulated one step-count entry per
        # iteration and its size-triggered clear could fire between two
        # parameters of the SAME step, handing them different scalar
        # objects — which broke the step-capture leaf identity classes
        # once every cache-lifetime. A per-name map keeps hits for
        # per-param lr multipliers too, and a per-name clear can only
        # land before a value's FIRST use in a step (identity within the
        # step is preserved: the re-created entry serves the rest).
        by_name = cache.get(name)
        if by_name is None:
            by_name = cache[name] = {}
        hit = by_name.get(value)
        if hit is not None:
            return hit
        # 0-d NUMPY payload, not jnp.asarray: the step count changes
        # every iteration, and minting a device scalar per step costs a
        # full jax eager dispatch (~0.5 ms/step on CPU, measured) on the
        # captured hot path. jit/XLA converts the numpy scalar at the
        # executable boundary for free, and its aval is identical.
        if len(by_name) > 64:
            by_name.clear()
        t = Tensor.__new__(Tensor)
        t._data = np.asarray(value, np.float32)
        t.stop_gradient = True
        t.grad = None
        t._grad_node = None
        t._out_idx = 0
        t.name = None
        t.persistable = False
        t._hooks = []
        by_name[value] = t
        return t

    # -- accumulators (reference Optimizer._add_accumulator) ------------------
    def _acc(self, name, p, init=0.0, dtype=None):
        store = self._accumulators.setdefault(name, {})
        key = id(p)
        if key not in store:
            t = Tensor(jnp.full(p._data.shape, init,
                                dtype or p._data.dtype))
            # accumulator slots are loop-carried like the params they
            # track: donation-eligible under step capture (see __init__)
            t._donatable = True
            store[key] = t
        return store[key]

    # -- step -----------------------------------------------------------------
    def _params_grads(self):
        pg = []
        for p in self._parameter_list:
            if p is None or p.stop_gradient or p.grad is None:
                continue
            pg.append((p, p.grad))
        return pg

    def step(self):
        from ..core.selected_rows import SelectedRows
        from ..core.tensor import Tensor
        from ..profiler import RecordEvent
        from ..profiler.spans import scope

        with RecordEvent("optimizer-step"), scope("optimizer"):
            self._step_impl(SelectedRows, Tensor)

    def _fastpath_tick(self):
        """Advance the per-step Python state exactly as step() would —
        called once per zero-dispatch replayed step (core/lazy.ReplayStep)
        in place of the full step() body, so the step counter (Adam bias
        correction, scheduler reads, checkpointed ``_opt_step``) stays
        true while no op is dispatched. The replay recomputes the 't' /
        uniform-'lr' scalar leaves from this state every step."""
        self._opt_step += 1
        return self._opt_step

    def _step_impl(self, SelectedRows, Tensor):
        pg = self._params_grads()
        # SelectedRows grads (sparse embedding, eager): row-capable
        # optimizers apply row-wise updates; anything that needs the
        # whole gradient (weight decay, clipping) or an optimizer
        # without a sparse rule densifies first — the reference's
        # MergeAdd-then-dense fallback.
        densify = (self._weight_decay is not None
                   or self._grad_clip is not None
                   or not self._supports_sparse_grad())
        pg = [(p, Tensor(g.to_dense(), stop_gradient=True)
               if densify and isinstance(g, SelectedRows) else g)
              for p, g in pg]
        if self._weight_decay is not None:
            pg = [(p, self._weight_decay(p, g)) for p, g in pg]
        if self._grad_clip is not None:
            pg = self._grad_clip(pg)
        self._opt_step += 1
        for p, g in pg:
            if isinstance(g, SelectedRows):
                self._apply_one_sparse(p, g)
            else:
                self._apply_one(p, g)

    def _apply_one(self, p, g):
        raise NotImplementedError

    def _supports_sparse_grad(self):
        """Override (with _apply_one_sparse) for row-wise update rules."""
        return False

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..core import dispatch

        if dispatch.static_recorder is not None:
            # declarative mode: record backward+update into the Program
            return dispatch.static_recorder.minimize(self, loss)
        loss.backward()
        self.step()
        return None, self._params_grads()

    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list:
            if p is not None:
                p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    # -- state ----------------------------------------------------------------
    def _slot_key(self, name, p, i):
        """Serialized key for one accumulator slot. Unnamed parameters
        key by POSITION in the parameter list (`p<i>`), not `id(p)`:
        object ids are meaningless in another process, and a checkpoint
        written by one run must restore the slots of a freshly-built
        model in the next (fault-tolerant resume, ISSUE 4). Construction
        order is deterministic, so position is a stable identity."""
        return f"{name}/{p.name or f'p{i}'}"

    def state_dict(self):
        sd = {}
        for name, store in self._accumulators.items():
            for i, p in enumerate(self._parameter_list):
                if p is not None and id(p) in store:
                    sd[self._slot_key(name, p, i)] = store[id(p)]
        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        sd["_opt_step"] = self._opt_step
        return sd

    def set_state_dict(self, state_dict):
        self._opt_step = int(state_dict.get("_opt_step", 0))
        if "LR_Scheduler" in state_dict and isinstance(self._learning_rate,
                                                       LRScheduler):
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])
        for name, store in self._accumulators.items():
            for i, p in enumerate(self._parameter_list):
                key = self._slot_key(name, p, i)
                if p is not None and key in state_dict:
                    v = state_dict[key]
                    existing = store.get(id(p))
                    arr = v._data if isinstance(v, Tensor) else v
                    if existing is not None and \
                            tuple(existing._data.shape) == \
                            tuple(np.shape(arr)):
                        # in-place: live captured-step plans key leaves by
                        # Tensor identity — replacing the slot object would
                        # force a re-capture after every resume
                        existing.set_value(np.asarray(arr))
                    else:
                        t = v if isinstance(v, Tensor) else Tensor(arr)
                        t._donatable = True  # restored slot stays loop-carried
                        store[id(p)] = t

    # -- static (declarative) mode hooks --------------------------------------
    _STATIC_ACCS: list[str] = []

    def _static_acc_names(self):
        return type(self)._STATIC_ACCS

    def _static_apply(self, oi, step_arr, pairs, state, grad_clip=None):
        """Apply updates inside an Executor trace (static/executor.py).

        pairs: [(Variable, traced param Tensor with .grad set)]. Accumulators
        are seeded from / written back to `state` (the Scope-backed dict), so
        the whole optimizer step compiles into the program's XLA executable —
        the reference needed per-op optimizer kernels + a program rewrite pass
        (fleet/meta_optimizers) for the same effect. `grad_clip` overrides
        self._grad_clip for program-level clip (auto_parallel_grad_clip
        pass) without mutating this shared optimizer object.
        """
        prev_step = self._opt_step
        self._opt_step = step_arr
        clip = grad_clip if grad_clip is not None else self._grad_clip
        try:
            pg = [(pt, pt.grad) for _, pt in pairs if pt.grad is not None]
            if self._weight_decay is not None:
                pg = [(p, self._weight_decay(p, g)) for p, g in pg]
            if clip is not None:
                pg = clip(pg)
            grads = {id(p): g for p, g in pg}
            for pv, pt in pairs:
                g = grads.get(id(pt))
                if g is None:
                    continue
                for acc in self._static_acc_names():
                    key = f"@opt{oi}@{acc}@{pv.name}"
                    self._accumulators.setdefault(acc, {})[id(pt)] = \
                        Tensor(state[key])
                self._apply_one(pt, g)
                for acc in self._static_acc_names():
                    key = f"@opt{oi}@{acc}@{pv.name}"
                    state[key] = self._accumulators[acc][id(pt)]._data
        finally:
            self._opt_step = prev_step
            # the per-trace accumulator Tensors wrap TRACED arrays keyed by
            # transient ids: drop them so the optimizer object holds no
            # tracer after the trace (they'd leak memory and poison
            # static.save's program serialization)
            for acc in self._static_acc_names():
                store = self._accumulators.get(acc)
                if store is not None:
                    for _, pt in pairs:
                        store.pop(id(pt), None)

    def _ensure_accumulators(self):
        """Materialize all state now (used by ZeRO sharding wrappers)."""
        for p in self._parameter_list:
            if p is not None and not p.stop_gradient:
                self._create_accumulators(p)

    def _create_accumulators(self, p):
        pass


def _sgd_rows_update(w, rows, vals, lr):
    return w.at[rows].add((-lr * vals).astype(w.dtype))


def _sgd_update(w, gg, lr):
    return w - (lr * gg.astype(jnp.float32)).astype(w.dtype)


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)

    def _apply_one(self, p, g):
        # dynamic lr as an input (not a closure cell) keeps the lazy grad
        # path's segment signature stable across steps — see Adam. The
        # kernel is MODULE-LEVEL: a closure-free per-call lambda would
        # get its own jit cache entry every step (compile storm).
        lr_t = self._scalar_input("lr", self._lr_for(p))
        new_p = forward(_sgd_update, (p, g, lr_t), name="sgd",
                        nondiff=True)
        p._data = new_p._data

    def _supports_sparse_grad(self):
        return True

    def _apply_one_sparse(self, p, g):
        # row-wise SGD over a SelectedRows grad (reference
        # phi/kernels/selected_rows/ sgd kernel): only looked-up rows
        # move. No merged() here — at[rows].add sums duplicate rows
        # itself, and merged()'s np.unique would force a host sync
        # every step (Adam's read-modify-write of moments DOES need it)
        lr_t = self._scalar_input("lr", self._lr_for(p))
        new_p = forward(_sgd_rows_update, (p, g.rows, g.values, lr_t),
                        name="sgd_rows", nondiff=True)
        p._data = new_p._data


class Momentum(Optimizer):
    _STATIC_ACCS = ["velocity"]

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _create_accumulators(self, p):
        self._acc("velocity", p)

    def _apply_one(self, p, g):
        mu = self._momentum
        vel = self._acc("velocity", p)
        lr_t = self._scalar_input("lr", self._lr_for(p))

        def f(w, gg, v, lr):
            gg = gg.astype(w.dtype)
            lr = lr.astype(w.dtype)
            v_new = mu * v + gg
            if self._nesterov:
                w_new = w - lr * (gg + mu * v_new)
            else:
                w_new = w - lr * v_new
            return w_new, v_new

        new_p, new_v = forward(f, (p, g, vel, lr_t), name="momentum",
                               nondiff=True)
        p._data = new_p._data
        vel._data = new_v._data


class Lars(Momentum):
    """LARS momentum: layer-wise adaptive rate scaling for large-batch SGD
    (reference `python/paddle/fluid/optimizer.py` LarsMomentumOptimizer +
    `phi/kernels/gpu/lars_momentum_kernel.cu`):

        local_lr = lr * lars_coeff * ||w|| / (||g|| + wd * ||w|| + eps)
        v_new    = mu * v + local_lr * (g + wd * w)
        w_new    = w - v_new

    Norms accumulate in fp32 regardless of param dtype (the CUDA kernel's
    MT=float master-type path)."""

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, epsilon=1e-9,
                 exclude_from_weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, momentum, parameters,
                         use_nesterov=False, weight_decay=None,
                         grad_clip=grad_clip, name=name)
        self._lars_coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        self._eps = epsilon
        self._exclude = list(exclude_from_weight_decay or [])

    def _apply_one(self, p, g):
        mu, coeff, eps = self._momentum, self._lars_coeff, self._eps
        wd = self._lars_wd
        pname = getattr(p, "name", "") or ""
        if any(k in pname for k in self._exclude):
            wd = 0.0
        vel = self._acc("velocity", p)
        lr_t = self._scalar_input("lr", self._lr_for(p))

        def f(w, gg, v, lr):
            wf = w.astype(jnp.float32)
            gf = gg.astype(jnp.float32)
            w_norm = jnp.sqrt(jnp.sum(jnp.square(wf)))
            g_norm = jnp.sqrt(jnp.sum(jnp.square(gf)))
            local_lr = jnp.where(
                (w_norm > 0) & (g_norm > 0),
                lr * coeff * w_norm / (g_norm + wd * w_norm + eps), lr)
            v_new = mu * v.astype(jnp.float32) + local_lr * (gf + wd * wf)
            return (wf - v_new).astype(w.dtype), v_new.astype(v.dtype)

        new_p, new_v = forward(f, (p, g, vel, lr_t), name="lars_momentum",
                               nondiff=True)
        p._data = new_p._data
        vel._data = new_v._data
