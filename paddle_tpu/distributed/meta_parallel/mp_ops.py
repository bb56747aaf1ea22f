"""Tensor-parallel communication primitives.

Reference: `python/paddle/distributed/fleet/layers/mpu/mp_ops.py:27`
(_c_identity), `:83` (_c_concat), `:145` (_c_split), `:211` (_mp_allreduce),
plus the collective kernels they bind
(`fluid/operators/collective/c_embedding_op.cc`,
`c_softmax_with_cross_entropy_op.cu`).

TPU re-design — every primitive has two execution contexts:

1. **Inside a `shard_map` region where the 'mp' axis is manual** (custom
   kernels, hand-scheduled engines): arrays are per-device shards and the
   primitives issue real XLA collectives (`psum`, `all_gather`) over ICI,
   with the reference's forward/backward split encoded via jax.custom_vjp.
2. **Outside (eager per-op jit or pjit/GSPMD)**: arrays are global and the
   mp layout lives in their NamedSharding; the primitives reduce to
   identity/layout annotations and GSPMD inserts the same collectives the
   reference issues by hand. (Eager ops on mp-sharded weights already
   execute distributed — per-op jit partitions them.)

`axis_in_scope('mp')` picks the context at trace time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...profiler import registry as _registry

# call + byte counters per mp primitive (profiler.stats() "mp.*"). These
# ops run inside traces, so a bump lands once per COMPILE of the
# enclosing region, not once per executed step — a usage/topology
# signal, same trace-time semantics as jax.log_compiles.
_tally = functools.partial(_registry.tally, "mp")

__all__ = ["axis_in_scope", "mp_axis_size", "mp_rank",
           "_c_identity", "_c_concat", "_c_split", "_mp_allreduce",
           "_c_lookup_table", "_c_softmax_with_cross_entropy",
           "shard_parameter", "current_mp_mesh"]

MP_AXIS = "mp"


def axis_in_scope(name: str = MP_AXIS) -> bool:
    """True when `name` is a manual (shard_map) axis in the current trace."""
    try:
        jax.lax.axis_size(name)
        return True
    except Exception:
        return False


def mp_axis_size(axis: str = MP_AXIS) -> int:
    return jax.lax.axis_size(axis)


def mp_rank(axis: str = MP_AXIS):
    return jax.lax.axis_index(axis)


def current_mp_mesh():
    """The fleet hybrid mesh, when fleet.init ran with mp_degree > 1."""
    from .. import fleet

    hcg = fleet._fleet_state.get("hcg")
    if hcg is None or hcg.get_model_parallel_world_size() <= 1:
        return None
    return hcg.mesh


def _layout_mesh():
    """Mesh for GSPMD layout annotations: the global SPMD mesh when the
    one-compilation path is enabled (distributed.spmd), else the fleet
    hybrid mesh. Both carry an 'mp' axis, so the P specs below work on
    either."""
    from .. import spmd

    m = spmd.current_mesh()
    return m if m is not None else current_mp_mesh()


def shard_parameter(param, spec=None):
    """Place a parameter onto the mesh per its `sharding_spec` — this
    is what makes the mpu layers REAL outside the engine: eager per-op jit
    partitions every op that touches a sharded weight, inserting the same
    collectives the reference's mp_ops issue manually. Under the SPMD
    path the spec folds through spmd.param_pspec ('sharding' → 'dp')."""
    mesh = _layout_mesh()
    if mesh is None:
        return param
    spec = spec or getattr(param, "sharding_spec", None)
    if spec is None:
        return param
    from .. import spmd
    from ...core import lazy as _lazy

    arr = _lazy.force(param._data)
    pspec = spmd.param_pspec(spec, mesh, tuple(arr.shape))
    param._data = jax.device_put(arr, NamedSharding(mesh, pspec))
    return param


def ensure_on_mesh(tensor):
    """Replicate an off-mesh eager tensor onto the mesh (layout-only,
    value and autograd tape untouched) so per-op jit can combine it with
    mesh-sharded weights — eager jax refuses mixed commitments otherwise.
    Pending LazyArrays pass through: they are not committed anywhere yet
    and materialize inside the (mesh-aware) segment executable."""
    mesh = _layout_mesh()
    if mesh is None or not hasattr(tensor, "_data"):
        return tensor
    arr = tensor._data
    if isinstance(arr, jax.Array) and arr.sharding.device_set != set(
            mesh.devices.flat):
        tensor._data = jax.device_put(
            arr, NamedSharding(mesh, P(*([None] * arr.ndim))))
    return tensor


def _wsc(x, sharding=None):
    """with_sharding_constraint as a recordable op kernel (module-level:
    stable fn_key; the NamedSharding rides in attrs, which hash)."""
    return jax.lax.with_sharding_constraint(x, sharding)


def _constrain(x, pspec):
    """Annotation-form layout constraint, skipped inside manual regions
    (where GSPMD specs would clash with the enclosing shard_map).

    A pending LazyArray is RECORDED (one `sharding_constraint` op in the
    accumulated segment) instead of forced: under the lazy train loop a
    mid-forward force would split the step into multiple executables and
    permanently diverge the capture cursor (observed: 2 materializations
    + a fallback per step for gather_output ColumnParallelLinear). The
    recorded op lowers to with_sharding_constraint inside the captured
    whole-step jit, where it is GSPMD's layout hint — the ISSUE-6
    one-compilation contract."""
    mesh = _layout_mesh()
    if mesh is None or axis_in_scope(MP_AXIS):
        return x
    ns = NamedSharding(mesh, pspec)
    from ...core import lazy as _lazy

    if isinstance(x, _lazy.LazyArray):
        return _lazy.build(_wsc, "sharding_constraint", [x],
                           {"sharding": ns}, _lazy.fn_key(_wsc),
                           _lazy.attrs_key({"sharding": ns}))
    return jax.lax.with_sharding_constraint(x, ns)


# ------------------------- in-region (manual) forms --------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _identity_manual(x, axis):
    return x


def _identity_manual_fwd(x, axis):
    return x, None


def _identity_manual_bwd(axis, _, g):
    return (jax.lax.psum(g, axis),)


_identity_manual.defvjp(_identity_manual_fwd, _identity_manual_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _allreduce_manual(x, axis):
    return jax.lax.psum(x, axis)


def _allreduce_manual_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _allreduce_manual_bwd(axis, _, g):
    return (g,)


_allreduce_manual.defvjp(_allreduce_manual_fwd, _allreduce_manual_bwd)


# ------------------------------- public ops ----------------------------------

def _c_identity(x, group=None, axis: str = MP_AXIS):
    """Forward identity / backward allreduce (mp_ops.py:27) — marks the
    replicated input of a ColumnParallelLinear."""
    _tally("_c_identity", x)
    if axis_in_scope(axis):
        return _identity_manual(x, axis)
    return x  # GSPMD: backward partial-sums reduce automatically


def _mp_allreduce(x, group=None, axis: str = MP_AXIS):
    """Forward allreduce / backward identity (mp_ops.py:211) — reduces the
    partial outputs of a RowParallelLinear."""
    _tally("_mp_allreduce", x)
    if axis_in_scope(axis):
        return _allreduce_manual(x, axis)
    return x  # GSPMD inserts the reduce where the contraction is sharded


def _c_split(x, group=None, axis: str = MP_AXIS):
    """Keep this rank's chunk of the last dim (mp_ops.py:145)."""
    _tally("_c_split", x)
    if axis_in_scope(axis):
        n = jax.lax.axis_size(axis)
        rank = jax.lax.axis_index(axis)
        chunk = x.shape[-1] // n
        return jax.lax.dynamic_slice_in_dim(x, rank * chunk, chunk, -1)
    return _constrain(x, P(*([None] * (x.ndim - 1) + [MP_AXIS])))


def _c_concat(x, group=None, axis: str = MP_AXIS):
    """All-gather chunks along the last dim (mp_ops.py:83)."""
    _tally("_c_concat", x)
    if axis_in_scope(axis):
        return jax.lax.all_gather(x, axis, axis=x.ndim - 1, tiled=True)
    return _constrain(x, P(*([None] * x.ndim)))


def _c_lookup_table(table, ids, start_index=0, axis: str = MP_AXIS):
    """Vocab-sharded embedding lookup (c_embedding_op.cc semantics): each
    rank owns rows [start, start + V_local); out-of-range ids contribute
    zeros and the psum over mp assembles the full lookup."""
    _tally("_c_lookup_table", table)
    if axis_in_scope(axis):
        v_local = table.shape[0]
        rank = jax.lax.axis_index(axis)
        start = start_index + rank * v_local
        local = ids - start
        valid = (local >= 0) & (local < v_local)
        rows = jnp.take(table, jnp.clip(local, 0, v_local - 1), axis=0)
        rows = jnp.where(valid[..., None], rows, 0)
        return jax.lax.psum(rows, axis)
    return jnp.take(table, ids, axis=0)


def _c_softmax_with_cross_entropy(logits, label, axis: str = MP_AXIS,
                                  ignore_index=-100):
    """Vocab-sharded softmax cross-entropy
    (c_softmax_with_cross_entropy_op.cu): sharded logsumexp = pmax of the
    local max + psum of local exp-sums; the label logit is a masked local
    gather psum'd across ranks. Returns per-token loss [..., ] (f32).

    Works on both shard-local logits (inside an mp shard_map region) and
    global logits (GSPMD partitions the same reductions)."""
    _tally("_c_softmax_with_cross_entropy", logits)
    lg = logits.astype(jnp.float32)
    if axis_in_scope(axis):
        v_local = lg.shape[-1]
        rank = jax.lax.axis_index(axis)
        start = rank * v_local
        # the max shift cancels in the loss gradient; stop_gradient BEFORE
        # pmax so differentiation never reaches it (pmax has no JVP rule)
        m = jax.lax.pmax(jax.lax.stop_gradient(jnp.max(lg, -1)), axis)
        shifted = lg - m[..., None]
        sumexp = jax.lax.psum(jnp.sum(jnp.exp(shifted), -1), axis)
        local = label - start
        valid = (local >= 0) & (local < v_local)
        picked = jnp.take_along_axis(
            shifted, jnp.clip(local, 0, v_local - 1)[..., None], -1)[..., 0]
        label_logit = jax.lax.psum(jnp.where(valid, picked, 0.0), axis)
        loss = jnp.log(sumexp) - label_logit
    else:
        m = jax.lax.stop_gradient(jnp.max(lg, -1, keepdims=True))
        shifted = lg - m
        lse = jnp.log(jnp.sum(jnp.exp(shifted), -1))
        picked = jnp.take_along_axis(shifted, label[..., None], -1)[..., 0]
        loss = lse - picked
    if ignore_index >= 0:
        loss = jnp.where(label == ignore_index, 0.0, loss)
    return loss
