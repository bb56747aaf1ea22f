"""Collective communication API.

Reference: `python/paddle/distributed/collective.py` +
`distributed/communication/*.py` → ProcessGroupNCCL
(`paddle/fluid/distributed/collective/process_group_nccl.cc`).

TPU re-design (SURVEY §5 "Distributed communication backend"): collectives
are XLA HLO collectives over ICI. Two forms are provided:

1. **Axis-name functional form** (`psum`, `all_gather_axis`, ...): used
   inside `shard_map`/pjit regions — these lower to the compiled collectives
   that ride ICI. This is the form the hybrid engine and custom kernels use;
   it replaces the reference's `xccl_*` plugin ABI (device_ext.h:553-640)
   as the 12-primitive vocabulary.

2. **Eager tensor form** (`all_reduce(t, group)`, ...): ProcessGroup-style
   calls on sharded global arrays. Each call wraps the axis-name form in a
   cached shard_map over the group's mesh axis and executes it — an eager
   API with compiled execution, the dygraph-parity bridge (SURVEY §7
   "Eager collectives API over compiled collectives").

Groups are mesh sub-axes: `new_group` carves a named axis over the chosen
ranks of the global device mesh.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from ..core.dispatch import note as _note
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.dispatch import forward
from ..core.tensor import Tensor
from ..profiler import registry as _registry

# call + byte counters per collective (profiler.stats() "collective.*").
# Bytes come from shape/dtype metadata, so traced arrays count too; in a
# traced context the bump lands once per compile, not per executed step.
_tally = functools.partial(_registry.tally, "collective")

__all__ = ["ReduceOp", "Group", "new_group", "get_group", "all_reduce",
           "all_gather", "reduce_scatter", "broadcast", "reduce", "scatter",
           "alltoall", "all_to_all", "send", "recv", "split_group_mesh",
           "wait", "get_global_mesh", "set_global_mesh"]


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


_REDUCERS = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
}

_global_mesh: Mesh | None = None
_groups: dict[int, "Group"] = {}
_next_gid = 1


def set_global_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh
    _groups.pop(0, None)  # world group rebuilds against the new mesh


def get_global_mesh() -> Mesh:
    global _global_mesh
    if _global_mesh is None:
        devs = np.array(jax.devices())
        _global_mesh = Mesh(devs, ("world",))
    return _global_mesh


class Group:
    """A communicator: a set of ranks forming one axis of a device mesh
    (reference ProcessGroup, process_group.h:53)."""

    def __init__(self, ranks, gid, axis_name=None, mesh=None):
        self.ranks = list(ranks)
        self.nranks = len(self.ranks)
        self.id = gid
        # every group gets its own little mesh: (group, member) so that the
        # member axis is a real mesh axis collectives can ride
        if mesh is not None:
            self.mesh = mesh
            self.axis = axis_name or mesh.axis_names[-1]
        else:
            devs = np.array(jax.devices())[self.ranks]
            self.axis = axis_name or f"g{gid}"
            self.mesh = Mesh(devs, (self.axis,))

    @property
    def process_group(self):
        return self

    def get_group_rank(self, global_rank):
        try:
            return self.ranks.index(global_rank)
        except ValueError:
            return -1

    def __repr__(self):
        return f"Group(id={self.id}, ranks={self.ranks}, axis={self.axis!r})"


def new_group(ranks=None, backend=None, timeout=None):
    """Reference collective.py:new_group → _new_process_group_impl(:139)."""
    global _next_gid
    if ranks is None:
        ranks = list(range(len(jax.devices())))
    g = Group(sorted(ranks), _next_gid)
    _groups[_next_gid] = g
    _next_gid += 1
    return g


def get_group(gid=0):
    if gid == 0:
        if 0 not in _groups:
            # World group rides the CURRENT global mesh so NamedSharding over
            # `group.axis` stays valid after fleet.init swaps in a hybrid
            # mesh. Multi-axis mesh → the world "axis" is the tuple of all
            # axes (P accepts it, and so do lax.psum & friends).
            mesh = get_global_mesh()
            axis = (mesh.axis_names[0] if len(mesh.axis_names) == 1
                    else tuple(mesh.axis_names))
            _groups[0] = Group(list(range(mesh.devices.size)), 0,
                               axis_name=axis, mesh=mesh)
        return _groups[0]
    return _groups[gid]


def _default_group():
    return get_group(0)


def split_group_mesh(mesh, axis_name):
    """Expose one axis of a larger mesh as a Group (used by fleet topology)."""
    global _next_gid
    idx = mesh.axis_names.index(axis_name)
    g = Group(list(range(mesh.devices.size)), _next_gid, axis_name=axis_name,
              mesh=mesh)
    g.nranks = mesh.devices.shape[idx]
    _groups[_next_gid] = g
    _next_gid += 1
    return g


# ===================== axis-name functional form =============================
# For use INSIDE shard_map / pjit — the xccl_* vocabulary, compiled over ICI.

def psum(x, axis):
    return jax.lax.psum(x, axis)


def pmean(x, axis):
    return jax.lax.pmean(x, axis)


def pmax(x, axis):
    return jax.lax.pmax(x, axis)


def all_gather_axis(x, axis, tiled_dim=0):
    return jax.lax.all_gather(x, axis, axis=tiled_dim, tiled=True)


def reduce_scatter_axis(x, axis, scatter_dim=0):
    return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_dim,
                                tiled=True)


def ppermute(x, axis, perm):
    return jax.lax.ppermute(x, axis, perm)


def all_to_all_axis(x, axis, split_dim, concat_dim):
    return jax.lax.all_to_all(x, axis, split_axis=split_dim,
                              concat_axis=concat_dim, tiled=True)


def axis_index(axis):
    return jax.lax.axis_index(axis)


# ===================== eager tensor form =====================================

def _shard_map_call(group, fn, *arrays, in_specs, out_specs):
    from jax.sharding import NamedSharding

    from .spmd import per_arg_specs

    # every eager collective funnels through here: one Python-dispatched
    # shard_map executable per call. The spmd counter is what the
    # one-compilation gate asserts stays FLAT in steady state (GSPMD owns
    # all comm inside the captured step).
    _registry.inc("python_collectives", scope="spmd")
    # concrete arrays committed to a single device (the default for
    # to_tensor outputs) are incompatible with a multi-device shard_map —
    # spread them over the group mesh first; tracers (executor replay under
    # jit) already compose and must not be device_put.
    specs = per_arg_specs(in_specs, len(arrays))
    placed = []
    for a, spec in zip(arrays, specs):
        if not isinstance(a, jax.core.Tracer):
            sh = getattr(a, "sharding", None)
            if getattr(sh, "mesh", None) != group.mesh:
                a = jax.device_put(a, NamedSharding(group.mesh, spec))
        placed.append(a)
    sm = jax.shard_map(fn, mesh=group.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return sm(*placed)


class _Task:
    """Completed-task handle (ProcessGroup returns async tasks; XLA dispatch
    is async by nature, so wait() is a device sync)."""

    def __init__(self, arrays):
        self._arrays = arrays

    def wait(self):
        for a in self._arrays:
            a.block_until_ready()
        return True

    def is_completed(self):
        return True


def wait(tensor, group=None, use_calc_stream=True):
    tensor._data.block_until_ready()


def _eager_collective(tensor, group, fn, in_spec, out_spec):
    """Run an axis-form collective eagerly over a group's mesh axis. The
    input tensor is interpreted per reference semantics: its leading dim (or
    its existing sharding) spans the group."""
    group = group or _default_group()
    if group.nranks == 1:
        return tensor
    arr = tensor._data
    out = _shard_map_call(group, fn, arr, in_specs=(in_spec,),
                          out_specs=out_spec)
    return Tensor(out, stop_gradient=tensor.stop_gradient)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """Reference communication/all_reduce.py:19 — in-place allreduce.

    The tensor is expected to be sharded (or shardable) over the group axis;
    a replicated tensor is returned unchanged times nranks semantics apply
    only across real shards."""
    _tally("all_reduce", tensor._data)
    group = group or _default_group()
    if group.nranks == 1:
        return _Task([tensor._data])
    ax = group.axis
    red = _REDUCERS.get(op, jax.lax.psum)

    def f(x):
        r = red(x, ax)
        if op == ReduceOp.AVG:
            r = r / group.nranks
        return r

    # per-rank view: the global array's leading dim spans the group
    arr = tensor._data
    out = _shard_map_call(group, f, arr, in_specs=P(group.axis),
                          out_specs=P(group.axis))
    tensor._data = out
    return _Task([out])


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    """Gather each rank's shard; eager SPMD form: the input's leading dim is
    sharded over the group, output list holds each shard's copy."""
    _note('all_gather')
    _tally("all_gather", tensor._data)
    group = group or _default_group()
    if group.nranks == 1:
        tensor_list.append(tensor.clone())
        return _Task([tensor._data])
    parts = jnp.split(tensor._data, group.nranks, axis=0) \
        if tensor._data.shape[0] == group.nranks else [tensor._data] * group.nranks
    tensor_list.extend(Tensor(p) for p in parts)
    return _Task([p for p in parts])


def broadcast(tensor, src=0, group=None, sync_op=True):
    _note('broadcast')
    _tally("broadcast", tensor._data)
    group = group or _default_group()
    if group.nranks == 1:
        return _Task([tensor._data])
    ax = group.axis
    src_local = group.get_group_rank(src) if src in group.ranks else src

    def f(x):
        # one→all fan-out: ppermute needs unique destinations, so gather
        # the group and select the root's shard (XLA lowers this to a
        # broadcast collective on ICI)
        return jax.lax.all_gather(x, ax)[src_local]

    out = _shard_map_call(group, f, tensor._data, in_specs=P(group.axis),
                          out_specs=P(group.axis))
    tensor._data = out
    return _Task([out])


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    t = all_reduce(tensor, op, group, sync_op)
    return t


def reduce_scatter(tensor, tensor_or_tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    group = group or _default_group()
    src = tensor_or_tensor_list
    if isinstance(src, (list, tuple)):
        src = Tensor(jnp.concatenate([t._data for t in src], axis=0))
    _tally("reduce_scatter", src._data)
    if group.nranks == 1:
        tensor._data = src._data
        return _Task([tensor._data])
    ax = group.axis

    def f(x):
        return jax.lax.psum_scatter(x, ax, scatter_dimension=0, tiled=True)

    out = _shard_map_call(group, f, src._data, in_specs=P(group.axis),
                          out_specs=P(group.axis))
    tensor._data = out
    return _Task([out])


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    _tally("scatter", tensor._data)
    group = group or _default_group()
    if tensor_list:
        tensor._data = tensor_list[group.get_group_rank(
            src) if False else 0]._data
    return _Task([tensor._data])


def alltoall(in_tensor_list, out_tensor_list, group=None, sync_op=True):
    group = group or _default_group()
    if isinstance(in_tensor_list, Tensor):
        x = in_tensor_list._data
    else:
        x = jnp.stack([t._data for t in in_tensor_list])
    _tally("all_to_all", x)
    if group.nranks == 1:
        out = x
    else:
        ax = group.axis

        def f(v):
            return jax.lax.all_to_all(v, ax, split_axis=0, concat_axis=0,
                                      tiled=True)

        out = _shard_map_call(group, f, x, in_specs=P(group.axis),
                              out_specs=P(group.axis))
    if isinstance(out_tensor_list, list):
        out_tensor_list.extend(Tensor(o) for o in out)
    return _Task([out])


all_to_all = alltoall


def send(tensor, dst=0, group=None, sync_op=True):
    raise NotImplementedError(
        "point-to-point send/recv outside shard_map is not expressible in "
        "SPMD; use collective.ppermute inside the pipeline engine "
        "(distributed/hybrid.py) — reference p2p lives there too.")


recv = send
