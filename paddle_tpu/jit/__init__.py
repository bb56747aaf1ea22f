"""paddle_tpu.jit — dygraph-to-static + whole-step compilation.

Reference: `python/paddle/jit/` — dy2static AST transpilation
(`jit/dy2static/program_translator.py:299`), `paddle.jit.save/load` →
inference programs (`jit/api.py`, `translated_layer.py`).

TPU re-design: `to_static` functionalizes a Layer/function over its
parameter/buffer/RNG state and hands it to `jax.jit`. Data-INdependent
Python control flow is unrolled at trace time; data-DEPENDENT `if`/`while`
over tensor values is AST-rewritten first by `jit.dy2static.ast_transform`
into `lax.cond`/`lax.while_loop` conversion calls (runtime-dispatched, so
eager/python semantics are untouched; unconvertible functions fall back
unchanged). `TrainStep` compiles forward+backward+optimizer into ONE XLA
executable — the TPU answer to the reference's per-op executor overhead and
the engine under the benchmark's training cell.

`paddle.jit.save` exports StableHLO via `jax.export` + a params archive —
the inference-deployment artifact (reference: inference program + params,
consumed by AnalysisPredictor).
"""
from __future__ import annotations

import functools
import os
import pickle

import numpy as np
import jax
import jax.numpy as jnp

from ..core import random as prandom
from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from ..profiler import span as _span

__all__ = ["to_static", "TrainStep", "save", "load", "not_to_static",
           "ignore_module", "enable_to_static"]

_to_static_enabled = True


def enable_to_static(flag=True):
    global _to_static_enabled
    _to_static_enabled = bool(flag)


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


def _collect_state(layers):
    """name → Tensor for all params+buffers of the given layers."""
    state = {}
    for i, layer in enumerate(layers):
        for k, t in layer.state_dict().items():
            state[f"m{i}.{k}"] = t
    return state


class StaticFunction:
    """Compiled wrapper (reference StaticFunction, program_translator.py:299)."""

    def __init__(self, fn, layer=None, input_spec=None):
        # dy2static: rewrite pythonic tensor control flow (if/while on
        # tensor values) into lax.cond/while_loop conversion calls before
        # tracing (reference program_translator applies the AST
        # transformers here); functions the transformer can't handle run
        # unchanged
        if not getattr(fn, "_not_to_static", False):
            from .dy2static import ast_transform

            fn = ast_transform(fn)
        self._fn = fn
        self._layer = layer
        self._input_spec = input_spec
        self._compiled = None
        self._state = None

    def _build(self):
        layers = [self._layer] if self._layer is not None else []
        self._state = _collect_state(layers)
        names = list(self._state)
        fn = self._fn

        def pure(state_arrays, key, arg_arrays):
            tensors = {n: self._state[n] for n in names}
            old = {n: t._data for n, t in tensors.items()}
            old_key = prandom.get_rng_state()
            for n, arr in zip(names, state_arrays):
                tensors[n]._data = arr
            prandom.set_rng_state(key)
            try:
                args = [Tensor(a) if isinstance(a, jax.Array) or
                        isinstance(a, jnp.ndarray) else a for a in arg_arrays]
                out = fn(*args)
                outs = out if isinstance(out, (tuple, list)) else (out,)
                out_arrays = tuple(o._data if isinstance(o, Tensor) else o
                                   for o in outs)
                new_state = tuple(tensors[n]._data for n in names)
                return out_arrays, new_state, prandom.get_rng_state()
            finally:
                for n, t in tensors.items():
                    t._data = old[n]
                # a FAILED trace must not leave a traced key in the global
                # RNG state (it would poison every later unrelated op)
                prandom.set_rng_state(old_key)
        self._pure = pure
        self._compiled = jax.jit(pure)

    def __call__(self, *args):
        if not _to_static_enabled:
            return self._fn(*args)
        # already inside an enclosing trace (TrainStep / an outer jit):
        # INLINE instead of dispatching a nested compiled executable —
        # the nested jit would return bare arrays that silently sever the
        # autograd tape (zero grads for every upstream param) and thread
        # traced state through host-side globals. One cheap global check;
        # no per-call state walk.
        from ..core.dispatch import trace_state_clean

        if not trace_state_clean():
            return self._fn(*args)
        if self._compiled is None:
            self._build()
        arg_arrays = tuple(a._data if isinstance(a, Tensor) else jnp.asarray(a)
                           for a in args)
        state_arrays = tuple(self._state[n]._data for n in self._state)
        outs, new_state, new_key = self._compiled(state_arrays,
                                                  prandom.get_rng_state(),
                                                  arg_arrays)
        for n, arr in zip(self._state, new_state):
            self._state[n]._data = arr
        prandom.set_rng_state(new_key)
        res = tuple(Tensor(o) for o in outs)
        return res[0] if len(res) == 1 else res

    @property
    def forward(self):
        return self


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None):
    """`paddle.jit.to_static` decorator."""

    def decorate(fn):
        if isinstance(fn, Layer):
            sf = StaticFunction(fn.forward, layer=fn, input_spec=input_spec)
            fn.forward = sf
            return fn
        # bound method of a Layer?
        layer = getattr(fn, "__self__", None)
        if isinstance(layer, Layer):
            return StaticFunction(fn, layer=layer, input_spec=input_spec)
        return StaticFunction(fn, input_spec=input_spec)

    if function is not None:
        return decorate(function)
    return decorate


class TrainStep:
    """Compile a full training step (fwd+bwd+optimizer) into one XLA program.

    Usage:
        step = paddle_tpu.jit.TrainStep(step_fn, model, optimizer)
        loss = step(batch_x, batch_y)   # each call = one compiled step

    step_fn runs ordinary dygraph code: forward, loss.backward(),
    opt.step(), opt.clear_grad(), return loss. The wrapper functionalizes
    parameters, optimizer accumulators, the step counter, and the PRNG key —
    so dropout and Adam bias-correction stay correct across steps.
    """

    def __init__(self, fn, models, optimizers, donate=True):
        self._fn = fn
        self._models = models if isinstance(models, (list, tuple)) else [models]
        self._opts = optimizers if isinstance(optimizers, (list, tuple)) \
            else [optimizers]
        self._compiled = None
        self._donate = donate

    def _build(self):
        self._state = _collect_state(self._models)
        # materialize optimizer accumulators so they're part of the state
        for opt in self._opts:
            for p in opt._parameter_list:
                if p is not None and not p.stop_gradient:
                    opt._create_accumulators(p)
        self._acc_refs = []  # (opt_idx, acc_name, param_idx, Tensor)
        plists = []
        for oi, opt in enumerate(self._opts):
            plists.append(list(opt._parameter_list))
            for acc_name, store in sorted(opt._accumulators.items()):
                for pi, p in enumerate(opt._parameter_list):
                    if p is not None and id(p) in store:
                        acc = store[id(p)]
                        # optimizer state follows its parameter's placement
                        # (a planner/apply_plan may have sharded the param
                        # after the accumulator was created; jit refuses
                        # mixed committed placements)
                        p_sh = getattr(p._data, "sharding", None)
                        a_sh = getattr(acc._data, "sharding", None)
                        if p_sh is not None and a_sh is not None and \
                                p_sh != a_sh and \
                                acc._data.shape == p._data.shape:
                            acc._data = jax.device_put(acc._data, p_sh)
                        self._acc_refs.append((oi, acc_name, pi, acc))
        names = list(self._state)
        fn = self._fn
        opts = self._opts

        # named from profiler.spans.EXECUTABLES: the device trace's
        # module event reads `jit_train_step`
        def train_step(state_arrays, acc_arrays, steps, key, arg_arrays):
            tensors = [self._state[n] for n in names]
            saved_p = [t._data for t in tensors]
            saved_a = [r[3]._data for r in self._acc_refs]
            saved_steps = [o._opt_step for o in opts]
            saved_key = prandom.get_rng_state()
            for t, arr in zip(tensors, state_arrays):
                t._data = arr
            for (oi, an, pi, t), arr in zip(self._acc_refs, acc_arrays):
                t._data = arr
            for o, s in zip(opts, steps):
                o._opt_step = s  # fn's own opt.step() advances it
            prandom.set_rng_state(key)
            try:
                out = fn(*[Tensor(a) for a in arg_arrays])
                outs = out if isinstance(out, (tuple, list)) else (out,)
                out_arrays = tuple(o._data if isinstance(o, Tensor) else o
                                   for o in outs)
                return (out_arrays,
                        tuple(t._data for t in tensors),
                        tuple(r[3]._data for r in self._acc_refs),
                        tuple(o._opt_step for o in opts),
                        prandom.get_rng_state())
            finally:
                for t, arr in zip(tensors, saved_p):
                    t._data = arr
                for r, arr in zip(self._acc_refs, saved_a):
                    r[3]._data = arr
                for o, s in zip(opts, saved_steps):
                    o._opt_step = s
                prandom.set_rng_state(saved_key)

        # donation is accelerator-only: XLA-CPU's transfer manager can
        # abort the process when many donated executables coexist (see
        # hybrid_engine._compile note); CPU runs are tests, where the
        # memory win is irrelevant
        donate = (0, 1) if self._donate and \
            jax.devices()[0].platform != "cpu" else ()
        self._compiled = jax.jit(train_step, donate_argnums=donate)
        # planner-sharded params span a mesh: scalars (step counters, rng
        # key) and single-device batches must be lifted onto it, or jit
        # rejects the mixed committed placements
        self._lift_sh = None
        for n in self._state:
            sh = getattr(self._state[n]._data, "sharding", None)
            if sh is not None and len(sh.device_set) > 1 and \
                    hasattr(sh, "mesh"):
                from jax.sharding import NamedSharding, PartitionSpec

                self._lift_sh = NamedSharding(sh.mesh, PartitionSpec())
                break

    def _lift(self, arr):
        if self._lift_sh is None:
            return arr
        sh = getattr(arr, "sharding", None)
        if sh is None or len(getattr(sh, "device_set", [1, 2])) > 1:
            return arr
        return jax.device_put(arr, self._lift_sh)

    def __call__(self, *args):
        with _span("train.step"):
            return self._call(args)

    def _call(self, args):
        if self._compiled is None:
            self._build()
        arg_arrays = tuple(
            self._lift(a._data if isinstance(a, Tensor) else jnp.asarray(a))
            for a in args)
        state_arrays = tuple(self._state[n]._data for n in self._state)
        acc_arrays = tuple(r[3]._data for r in self._acc_refs)
        steps = tuple(self._lift(jnp.asarray(o._opt_step, jnp.float32))
                      for o in self._opts)
        outs, new_state, new_accs, new_steps, new_key = self._compiled(
            state_arrays, acc_arrays, steps,
            self._lift(prandom.get_rng_state()), arg_arrays)
        for n, arr in zip(self._state, new_state):
            self._state[n]._data = arr
        for r, arr in zip(self._acc_refs, new_accs):
            r[3]._data = arr
        for o, s in zip(self._opts, new_steps):
            o._opt_step = s
        if self._lift_sh is not None:
            # the key came back committed to the whole mesh; the global RNG
            # state must stay single-device or every later unrelated jit
            # sees mixed committed placements
            new_key = jax.device_put(new_key, jax.devices()[0])
        prandom.set_rng_state(new_key)
        res = tuple(Tensor(o) for o in outs)
        return res[0] if len(res) == 1 else res


# ======================= save / load (inference artifact) ====================

def save(layer, path, input_spec=None, **configs):
    """`paddle.jit.save`: StableHLO (via jax.export) + params.

    Produces `path.pdmodel` (serialized StableHLO bytes) and
    `path.pdiparams` (state dict) — the deployment pair mirroring the
    reference's inference program + params files."""
    from jax import export as jax_export

    if isinstance(layer, StaticFunction):
        fn, lay = layer._fn, layer._layer
    elif isinstance(layer, Layer):
        fn, lay = layer.forward, layer
        if isinstance(fn, StaticFunction):
            fn, lay = fn._fn, fn._layer or layer
    else:
        fn, lay = layer, None

    if input_spec is None:
        raise ValueError("jit.save requires input_spec on this framework")

    state = _collect_state([lay] if lay is not None else [])
    names = list(state)

    def pure(state_arrays, *arg_arrays):
        old = {n: state[n]._data for n in names}
        for n, arr in zip(names, state_arrays):
            state[n]._data = arr
        try:
            out = fn(*[Tensor(a) for a in arg_arrays])
            outs = out if isinstance(out, (tuple, list)) else (out,)
            return tuple(o._data if isinstance(o, Tensor) else o for o in outs)
        finally:
            for n in names:
                state[n]._data = old[n]

    # None/-1 dims export symbolically (jax.export shape polymorphism) so
    # ONE artifact serves any batch size (see core/export_utils — same
    # helper as save_inference_model; independent symbols first, shared
    # leading symbol when the program combines feeds)
    from ..core import dtype as dtypes
    from ..core.export_utils import export_with_symbolic_feeds

    spec_sd = [(list(spec.shape),
                dtypes.convert_dtype(getattr(spec, "dtype", "float32")))
               for spec in input_spec]
    state_shapes = tuple(jax.ShapeDtypeStruct(state[n]._data.shape,
                                              state[n]._data.dtype)
                         for n in names)

    exported = export_with_symbolic_feeds(
        lambda arg_shapes: jax_export.export(jax.jit(pure))(state_shapes,
                                                            *arg_shapes),
        spec_sd)
    blob = exported.serialize()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + ".pdmodel", "wb") as f:
        f.write(blob)
    with open(path + ".pdiparams", "wb") as f:
        pickle.dump({"names": names,
                     "arrays": [np.asarray(state[n]._data) for n in names],
                     "feed_names": [getattr(s, "name", None) or f"x{i}"
                                    for i, s in enumerate(input_spec)],
                     "kind": "jit_save"},
                    f, protocol=4)


class TranslatedLayer(Layer):
    """`paddle.jit.load` result (reference translated_layer.py)."""

    def __init__(self, exported, names, arrays):
        super().__init__()
        self._exported = exported
        self._names = names
        self._arrays = [jnp.asarray(a) for a in arrays]

    def forward(self, *args):
        arg_arrays = tuple(a._data if isinstance(a, Tensor) else jnp.asarray(a)
                           for a in args)
        outs = self._exported.call(tuple(self._arrays), *arg_arrays)
        res = tuple(Tensor(o) for o in outs)
        return res[0] if len(res) == 1 else res


def load(path, **configs):
    from jax import export as jax_export

    with open(path + ".pdmodel", "rb") as f:
        exported = jax_export.deserialize(f.read())
    with open(path + ".pdiparams", "rb") as f:
        d = pickle.load(f)
    return TranslatedLayer(exported, d["names"], d["arrays"])
