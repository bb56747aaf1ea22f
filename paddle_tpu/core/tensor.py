"""Eager Tensor.

TPU-native equivalent of the reference's `paddle::Tensor` + `AutogradMeta`
(`/root/reference/paddle/phi/api/include/tensor.h:86`,
`fluid/eager/autograd_meta.h:61`) and the Python-side monkey-patched VarBase
methods. The payload is a `jax.Array` (PJRT buffer on TPU HBM, or an XLA
tracer inside a compiled region — which is what makes whole-step `jax.jit`
compilation of eager code possible). Autograd metadata is carried directly on
the tensor: `_grad_node` + `_out_idx` mirror AutogradMeta's GradNode/slot pair.

Most math methods are attached by `paddle_tpu.ops.methods` (the analog of the
reference's monkey_patch_varbase), keeping this module import-light.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtypes
from . import lazy as _lazy
from .place import current_place, jax_device, place_of, Place


def _to_array(data, dtype=None, place=None):
    # hot path: every lazy op output wraps a LazyArray in a Tensor — skip
    # the jax.Array ABC __instancecheck__ walk for it
    if type(data) is _lazy.LazyArray and dtype is None:
        return data
    if isinstance(data, Tensor):
        data = data._data
    if isinstance(data, (jax.Array,)) or hasattr(data, "aval"):
        arr = data
        if dtype is not None:
            arr = arr.astype(dtypes.convert_dtype(dtype))
        return arr
    npd = np.asarray(data)
    if npd.dtype == np.float64 and dtype is None:
        # Match paddle's default: python floats / float64 numpy become the
        # framework default dtype (float32) unless explicitly requested.
        if not isinstance(data, np.ndarray):
            npd = npd.astype(dtypes.default_dtype().np_dtype)
    if dtype is not None:
        npd = npd.astype(dtypes.convert_dtype(dtype))
    dev = jax_device(place)
    return jax.device_put(npd, dev)


class Tensor:
    __slots__ = (
        "_payload", "stop_gradient", "grad", "_grad_node", "_out_idx",
        "name", "persistable", "_hooks", "__weakref__", "__dict__",
    )

    def __init__(self, data=None, dtype=None, place=None, stop_gradient=True,
                 name=None):
        self._data = None if data is None else _to_array(data, dtype, place)
        self.stop_gradient = stop_gradient
        self.grad = None
        self._grad_node = None
        self._out_idx = 0
        self.name = name
        self.persistable = False
        self._hooks = []

    # donation eligibility: optimizers flip this to True on parameters and
    # accumulator slots they manage. Step capture (core/lazy.py) may then
    # donate the buffer to the captured whole-step executable once it is
    # loop-carried and this Tensor has rebound past it — updates happen in
    # place instead of allocating fresh HBM. Class attribute, not a slot:
    # the default costs nothing per instance.
    _donatable = False

    @property
    def _data(self):
        return self._payload

    @_data.setter
    def _data(self, value):
        # lazy keep-mask: registering every holding Tensor here (not just
        # dispatch outputs) is what lets `p._data = new_lazy` in an
        # optimizer mark the update node as live — without it the segment
        # never records the node's values and every later iteration
        # re-executes the whole history (round-4 lazy-grad lesson).
        # Rebinding DISOWNS the previous payload from its CURRENT-holder
        # set only (the sticky keep-mask owner set is untouched: an
        # optimizer rebinds p._data past the update placeholder before
        # the step materializes, and that update must still be an
        # executable output). An empty current-holder set on the old
        # placeholder is what proves no Tensor can read the buffer after
        # the captured step donates it.
        old = getattr(self, "_payload", None)
        if old is not None and isinstance(old, _lazy.LazyArray) \
                and old is not value:
            old.disown(self)
        self._payload = value
        if isinstance(value, _lazy.LazyArray):
            value.own(self, self._donatable)

    # -- basic introspection --------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def dtype(self):
        return dtypes.to_paddle_dtype(self._data.dtype)

    @property
    def ndim(self):
        return self._data.ndim

    def dim(self):
        return self._data.ndim

    def rank(self):
        return self._data.ndim

    ndimension = dim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    def numel(self):
        return self.size

    @property
    def place(self) -> Place:
        return place_of(self._data)

    @property
    def is_leaf(self):
        return self._grad_node is None

    @property
    def T(self):
        from .. import ops

        return ops.transpose(self, list(range(self.ndim))[::-1])

    # -- conversions ----------------------------------------------------------
    def numpy(self):
        return np.asarray(self._data)

    def __array__(self, dtype=None):
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    def __jax_array__(self):
        return self._data

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        return bool(self.numpy())

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- device / dtype movement ---------------------------------------------
    def astype(self, dtype):
        from .. import ops

        return ops.cast(self, dtype)

    cast = astype

    def cpu(self):
        from .dispatch import note as _note
        _note('cpu')
        return Tensor(jax.device_put(self._data, jax.devices("cpu")[0]),
                      stop_gradient=self.stop_gradient)

    def tpu(self, device_id=0):
        return Tensor(jax.device_put(self._data, jax_device(Place("tpu", device_id))),
                      stop_gradient=self.stop_gradient)

    cuda = tpu  # reference-API parity

    def to(self, *args, **kwargs):
        t = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and a in ("cpu",) or isinstance(a, Place):
                p = a if isinstance(a, Place) else Place("cpu", 0)
                t = Tensor(jax.device_put(t._data, jax_device(p)),
                           stop_gradient=t.stop_gradient)
            elif isinstance(a, str) and (a.startswith(("tpu", "gpu", "cuda"))):
                t = t.tpu()
            else:
                t = t.astype(a)
        return t

    # -- autograd -------------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        from . import autograd

        autograd.backward([self], [grad_tensor] if grad_tensor is not None else None,
                          retain_graph=retain_graph)

    def detach(self):
        t = Tensor.__new__(Tensor)
        t._data = self._data
        t.stop_gradient = True
        t.grad = None
        t._grad_node = None
        t._out_idx = 0
        t.name = self.name
        t.persistable = False
        t._hooks = []
        return t

    def detach_(self):
        self._grad_node = None
        self._out_idx = 0
        self.stop_gradient = True
        return self

    def clone(self):
        from .. import ops

        return ops.assign(self)

    def gradient(self):
        return None if self.grad is None else self.grad.numpy()

    def clear_gradient(self, set_to_zero=False):
        if set_to_zero and self.grad is not None:
            from .selected_rows import SelectedRows

            if isinstance(self.grad, SelectedRows):
                self.grad = Tensor(jnp.zeros(tuple(self.grad.shape),
                                             self.grad.dtype))
            else:
                self.grad = Tensor(jnp.zeros_like(self.grad._data))
        else:
            self.grad = None

    clear_grad = clear_gradient

    def register_hook(self, hook):
        if self._grad_node is not None:
            self._grad_node.add_hook(self._out_idx, hook)
        else:
            self._hooks.append(hook)
        return _HookHandle(self, hook)

    def zero_(self):
        self._data = jnp.zeros_like(self._data)
        return self

    # -- value assignment (mutating; reference Tensor::copy_ / set_value) -----
    def set_value(self, value):
        arr = _to_array(value, place=self.place)
        if tuple(arr.shape) != tuple(self._data.shape):
            raise ValueError(
                f"set_value shape mismatch: {arr.shape} vs {self._data.shape}")
        self._data = arr.astype(self._data.dtype)
        # in-place restore contract (checkpoint restore_training_state,
        # optimizer set_state_dict, Model.load all land here): an armed
        # zero-dispatch ReplayStep feeds loop-carried leaves from its own
        # outputs and would silently clobber this write on its next
        # rebind — the epoch bump demotes it to an audited slow step that
        # records from the restored buffer instead
        _lazy.note_external_mutation()
        return self

    copy_ = set_value

    def _rebind(self, result):
        """Adopt another tensor's payload+autograd identity (inplace-op core).

        The reference tracks inplace versions on TensorWrapper
        (`eager/tensor_wrapper.h`); functionally-rebinding to a fresh value
        gives the same autograd semantics without version hazards.
        """
        self._data = result._data
        self._grad_node = result._grad_node
        self._out_idx = result._out_idx
        self.stop_gradient = result.stop_gradient
        return self

    # -- indexing -------------------------------------------------------------
    def __getitem__(self, idx):
        from .. import ops

        return ops.getitem(self, idx)

    def __setitem__(self, idx, value):
        from .. import ops

        self._rebind(ops.setitem(self, idx, value))

    def __repr__(self):
        sg = self.stop_gradient
        try:
            vals = np.array2string(self.numpy(), precision=6, threshold=40)
        except Exception:
            vals = "<traced>"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                f"place={self.place}, stop_gradient={sg},\n       {vals})")

    def __hash__(self):
        return id(self)


class _HookHandle:
    def __init__(self, tensor, hook):
        self._tensor = tensor
        self._hook = hook

    def remove(self):
        t = self._tensor
        if self._hook in t._hooks:
            t._hooks.remove(self._hook)
        node = t._grad_node
        if node is not None and node.hooks:
            for fns in node.hooks.values():
                if self._hook in fns:
                    fns.remove(self._hook)


class Parameter(Tensor):
    """Trainable tensor (`python/paddle/fluid/framework.py` Parameter)."""

    def __init__(self, data=None, dtype=None, place=None, name=None,
                 trainable=True):
        super().__init__(data, dtype=dtype, place=place,
                         stop_gradient=not trainable, name=name)
        self.persistable = True
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_distributed = False
        # sharding annotation consumed by the distributed engine
        # (jax.sharding.PartitionSpec or None)
        self.sharding_spec = None

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()
