"""Pallas TPU kernels for the hot fused ops.

These replace the reference's hand-written CUDA fusion layer:
  - flash attention  ← `phi/kernels/gpu/flash_attn_kernel.cu` (dynloaded
    libflashattn) and `fluid/operators/fused/fused_attention_op.cu`
  - fused softmax-mask ← `phi/kernels/fusion/fused_softmax_mask_kernel`

Kernel design follows the TPU playbook (/opt/skills/guides/pallas_guide.md):
fp32 accumulators in VMEM, MXU matmuls via jnp.dot with
preferred_element_type=f32, online-softmax streaming over K/V blocks so the
full [T, T] score matrix never materializes in HBM.

Every public entry point falls back to a pure-XLA implementation when the
platform is not TPU or shapes don't tile (CPU tests, odd seq lens), so
numerics are always available — the same role the reference's CPU reference
kernels play for its CUDA ops.

Kernel bodies are x64-proof where they are written: the package turns
``jax_enable_x64`` on, under which a bare python float or int becomes an
f64/i64 constant that Mosaic has no type for. Every in-kernel constant is
therefore a typed 32-bit scalar (``_f32`` / ``jnp.int32``), and every MXU dot
names its precision (``_dot_precision``) instead of inheriting the global
``jax_default_matmul_precision``.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..core import lazy as _lazy
from ..profiler import explainer as _explain
from ..profiler import registry as _registry
from ..profiler.spans import device_name as _kernel_name
from . import kv_pool as _kv_pool

# Kernel-selection telemetry (ISSUE 14): every resolution of a hot-path
# kernel family bumps exactly one counter, so an operator can read which
# implementation actually serves from one table. The paged family's
# selection happens ONCE per engine build (serving.kernel.*); the flash
# family's happens per trace of the attention op (kernel.flash.*) —
# trace-time only, the replay fast path never re-enters these bodies.
_paged_counters = _registry.scoped_counters("serving", {
    "kernel.pallas": 0, "kernel.xla": 0, "kernel.interpret": 0,
    "kernel.fallbacks": 0})
_flash_counters = _registry.scoped_counters("kernel", {
    "flash.pallas": 0, "flash.xla": 0, "flash.fallbacks": 0})


def _note_kernel_fallback(family, reason, **detail):
    """A Pallas-eligible call resolved to the XLA path: name the shape or
    platform reason in the explainer ring so the slowdown is loud. Each
    family bumps its OWN fallback counter — serving.kernel.fallbacks is
    the serving kernels' health signal (the paged decode/verify family and
    the prompt span's `flash_prefill`) and must not be inflated by
    training flash traces."""
    if family == "flash_attention":
        _flash_counters["flash.fallbacks"] += 1
    else:
        _paged_counters["kernel.fallbacks"] += 1
    _explain.record(
        "kernel_fallback", op=family, why=reason, **detail)


def _env_flag(name: str) -> bool:
    """Truthy env flag: unset, empty, or \"0\" mean OFF (consistent with
    PADDLE_TPU_X64 parsing in paddle_tpu/__init__.py)."""
    return os.environ.get(name, "0") not in ("", "0")


def _on_tpu() -> bool:
    if _env_flag("PADDLE_TPU_DISABLE_PALLAS"):  # perf A/B escape hatch
        return False
    return jax.default_backend() == "tpu"


def _i0():
    """int32 zero for BlockSpec index maps: under jax_enable_x64 a bare
    python 0 lowers as an i64 constant, which Mosaic rejects."""
    return jnp.int32(0)


# typed in-kernel float constants (numpy scalars: strongly typed f32 in a
# trace, and building them touches no JAX backend at import)
_f32 = np.float32
_NEG_INF = _f32(-np.inf)
_TINY = _f32(1e-30)


def _dot_precision(dtype):
    """MXU precision for a kernel's dots, chosen from the operand dtype the
    caller handed in. bf16 inputs are exact in one bf16 MXU pass (DEFAULT)
    — the same arithmetic XLA gives the model's own bf16 matmuls; f32
    inputs keep the package's full-f32 contract (HIGHEST, the multi-pass
    decomposition) so f32 parity bounds hold on the chip as in the
    interpreter. Pinned per dot because the kernels upcast their operands
    to f32 in VMEM: left to the global ``jax_default_matmul_precision``
    ("highest") a bf16 model would pay the multi-pass rate for bits its
    inputs never had."""
    if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16):
        return jax.lax.Precision.DEFAULT
    return jax.lax.Precision.HIGHEST


# =========================== flash attention =================================
#
# Forward + backward both run as Pallas kernels wired together with
# jax.custom_vjp (FlashAttention-2 style): the forward emits the row
# logsumexp, the backward recomputes score blocks from (q, k, lse) so the
# full [T, T] matrix never exists in HBM in either pass. Replaces the
# reference's dynloaded libflashattn fwd/bwd pair
# (`phi/kernels/gpu/flash_attn_kernel.cu`, `flash_attn_grad_kernel.cu`).

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                      block_q, block_k, seq_len, precision):
    head_dim = q_ref.shape[-1]
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=precision)
    q = q_ref[:].astype(jnp.float32) * _f32(scale)
    q_blk = pl.program_id(1)

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, head_dim), jnp.float32)

    # All index arithmetic pinned to int32: under jax_enable_x64, bare python
    # ints lower as i64 constants, which Mosaic rejects next to i32
    # program_ids.
    bq, bk = jnp.int32(block_q), jnp.int32(block_k)
    if causal:
        hi = (q_blk * bq + bq + bk - jnp.int32(1)) // bk
        hi = jnp.minimum(hi, jnp.int32(seq_len // block_k))
    else:
        hi = jnp.int32(seq_len // block_k)

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(i * bk, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(i * bk, block_k), :].astype(jnp.float32)
        s = dot(q, k.T)
        if causal:
            qpos = q_blk * bq + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = i * bk + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_new = acc * corr + dot(p, v)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(jnp.int32(0), hi, body, (m0, l0, acc0))
    l = jnp.maximum(l, _TINY)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[:] = m + jnp.log(l)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, scale, causal, block_q, block_k, seq_len,
                         precision):
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=precision)
    scale = _f32(scale)
    q = q_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:]
    delta = delta_ref[:]
    q_blk = pl.program_id(1)

    bq, bk = jnp.int32(block_q), jnp.int32(block_k)
    if causal:
        hi = (q_blk * bq + bq + bk - jnp.int32(1)) // bk
        hi = jnp.minimum(hi, jnp.int32(seq_len // block_k))
    else:
        hi = jnp.int32(seq_len // block_k)

    def body(i, dq):
        k = k_ref[pl.ds(i * bk, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(i * bk, block_k), :].astype(jnp.float32)
        s = dot(q, k.T) * scale
        if causal:
            qpos = q_blk * bq + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = i * bk + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = dot(do, v.T)
        ds = p * (dp - delta)
        return dq + dot(ds, k)

    dq0 = jnp.zeros_like(q)
    dq = jax.lax.fori_loop(jnp.int32(0), hi, body, dq0)
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, scale, causal, block_q, block_k,
                          seq_len, precision):
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=precision)
    scale = _f32(scale)
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    k_blk = pl.program_id(1)

    bq, bk = jnp.int32(block_q), jnp.int32(block_k)
    lo = (k_blk * bk) // bq if causal else jnp.int32(0)
    n_q = jnp.int32(seq_len // block_q)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * bq, block_q), :].astype(jnp.float32)
        do = do_ref[pl.ds(i * bq, block_q), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(i * bq, block_q), :]
        delta = delta_ref[pl.ds(i * bq, block_q), :]
        s = dot(q, k.T) * scale
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_blk * bk + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dv_new = dv + dot(p.T, do)
        dp = dot(do, v.T)
        ds = p * (dp - delta)
        dk_new = dk + dot(ds.T, q)
        return dk_new, dv_new

    dk0 = jnp.zeros_like(k)
    dv0 = jnp.zeros_like(v)
    dk, dv = jax.lax.fori_loop(lo, n_q, body, (dk0, dv0))
    dk_ref[:] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _flash_fwd_call(q, k, v, causal, scale, block_q, block_k):
    """q,k,v: [BN, T, H] flattened batch*heads. Returns (out, lse[BN,T,1])."""
    BN, T, H = q.shape
    grid = (BN, T // block_q)
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=T,
                          precision=_dot_precision(q.dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, H), lambda b, i: (b, i, _i0())),
            pl.BlockSpec((None, T, H), lambda b, i: (b, _i0(), _i0())),
            pl.BlockSpec((None, T, H), lambda b, i: (b, _i0(), _i0())),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, H), lambda b, i: (b, i, _i0())),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, _i0())),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BN, T, H), q.dtype),
            jax.ShapeDtypeStruct((BN, T, 1), jnp.float32),
        ],
        name=_kernel_name("flash_fwd"),
    )(q, k, v)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_flat(q, k, v, causal, scale, block_q, block_k):
    return _flash_fwd_call(q, k, v, causal, scale, block_q, block_k)[0]


def _flash_flat_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _flash_fwd_call(q, k, v, causal, scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_flat_bwd(causal, scale, block_q, block_k, res, do):
    q, k, v, out, lse = res
    BN, T, H = q.shape
    # delta_i = rowsum(do * o): cheap elementwise-reduce, XLA fuses it.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, seq_len=T,
                  precision=_dot_precision(q.dtype))
    full = lambda b, i: (b, _i0(), _i0())  # noqa: E731
    row = lambda b, i: (b, i, _i0())  # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(BN, T // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, H), row),
            pl.BlockSpec((None, T, H), full),
            pl.BlockSpec((None, T, H), full),
            pl.BlockSpec((None, block_q, H), row),
            pl.BlockSpec((None, block_q, 1), row),
            pl.BlockSpec((None, block_q, 1), row),
        ],
        out_specs=pl.BlockSpec((None, block_q, H), row),
        out_shape=jax.ShapeDtypeStruct((BN, T, H), q.dtype),
        name=_kernel_name("flash_bwd_dq"),
    )(q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(BN, T // block_k),
        in_specs=[
            pl.BlockSpec((None, T, H), full),
            pl.BlockSpec((None, block_k, H), row),
            pl.BlockSpec((None, block_k, H), row),
            pl.BlockSpec((None, T, H), full),
            pl.BlockSpec((None, T, 1), full),
            pl.BlockSpec((None, T, 1), full),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, H), row),
            pl.BlockSpec((None, block_k, H), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BN, T, H), k.dtype),
            jax.ShapeDtypeStruct((BN, T, H), v.dtype),
        ],
        name=_kernel_name("flash_bwd_dkv"),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


_flash_flat.defvjp(_flash_flat_fwd, _flash_flat_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k"))
def _flash_attention_tpu(q, k, v, causal=False, scale=None, block_q=256,
                         block_k=256):
    """q,k,v: [B, T, N, H] (reference flash_attn layout). Pallas grid:
    (batch*heads, T/block_q); K/V streamed in block_k chunks."""
    B, T, N, H = q.shape
    scale = float(scale) if scale is not None else H ** -0.5
    block_q = min(block_q, T)
    block_k = min(block_k, T)

    def reshape_in(x):
        return x.transpose(0, 2, 1, 3).reshape(B * N, x.shape[1], H)

    qf, kf, vf = reshape_in(q), reshape_in(k), reshape_in(v)
    out = _flash_flat(qf, kf, vf, causal, scale, block_q, block_k)
    return out.reshape(B, N, T, H).transpose(0, 2, 1, 3)


def _attention_xla(q, k, v, mask=None, causal=False, scale=None):
    """Reference semantics of fmha_ref.h, fused by XLA."""
    H = q.shape[-1]
    scale = scale if scale is not None else H ** -0.5
    logits = jnp.einsum("bqnh,bknh->bnqk", q, k).astype(jnp.float32) * scale
    if causal:
        T, S = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((T, S), bool))
        logits = jnp.where(cm, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bknh->bqnh", probs, v)


def _flash_shape_refusal(q, k, mask):
    """Why the Pallas flash kernel cannot take this call (None = it can)."""
    T, H = q.shape[1], q.shape[3]
    if mask is not None:
        return "explicit attn_mask (flash kernel is mask-free)"
    if k.shape[1] != T:
        return f"cross-length kv (T={T}, S={k.shape[1]})"
    if T % 128:
        return f"seq_len {T} not a multiple of 128"
    if H not in (64, 96, 128, 256):
        return f"head_dim {H} not in (64, 96, 128, 256)"
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return f"dtype {q.dtype} not in (float32, bfloat16)"
    return None


def _flash_mesh_spec(mesh, batch, heads):
    """How the flash call splits over the installed SPMD mesh. A Mosaic
    custom call has no partitioning rule — GSPMD refuses it outright
    ("Mosaic kernels cannot be automatically partitioned") — so under a
    mesh the kernel runs per shard through ``jax.shard_map``: batch over
    the data axes ('dp', and 'ep' whose ranks are data-parallel for the
    dense trunk), heads over 'mp', each shard the unmodified kernel on
    its local [B/dp, T, N/mp, H] slice (attention never mixes batch rows
    or heads, so no collective is needed). Returns ``(spec, None)``,
    ``(None, None)`` when no split is needed, or ``(None, why)`` when
    this mesh has no per-shard plan and the call must take the XLA path
    (which GSPMD partitions by itself)."""
    from ..distributed.meta_parallel.mp_ops import axis_in_scope

    axes = {n: int(s) for n, s in zip(mesh.axis_names, mesh.devices.shape)
            if int(s) > 1}
    # an axis already manual in this trace: the caller runs per shard
    if not axes or any(axis_in_scope(a) for a in axes):
        return None, None
    unplanned = sorted(set(axes) - {"dp", "ep", "mp"})
    if unplanned:
        return None, (f"mesh axes {unplanned} have no per-shard flash "
                      "plan")
    data = tuple(a for a in ("dp", "ep") if a in axes)
    n_data = math.prod(axes[a] for a in data)
    if batch % n_data:
        return None, (f"batch {batch} does not divide over mesh axes "
                      f"{data} = {n_data}")
    mp = axes.get("mp", 1)
    if heads % mp:
        return None, f"{heads} heads do not divide over mesh axis mp={mp}"
    return P(data or None, None, "mp" if mp > 1 else None, None), None


def flash_attention(q, k, v, mask=None, causal=False, scale=None):
    """[B, T, N, H] attention; Pallas on TPU when tileable, XLA otherwise.
    Under an installed SPMD mesh the kernel runs per shard (see
    :func:`_flash_mesh_spec`)."""
    B, T, N, _ = q.shape
    on_tpu = _on_tpu()
    why = _flash_shape_refusal(q, k, mask) if on_tpu else "not on tpu"
    spec = None
    mesh = _lazy.spmd_mesh() if why is None else None
    if mesh is not None:
        spec, why = _flash_mesh_spec(mesh, B, N)
    if why is None:
        _flash_counters["flash.pallas"] += 1
        blk = 256 if T % 256 == 0 else 128
        kernel = functools.partial(_flash_attention_tpu, causal=causal,
                                   scale=scale, block_q=blk, block_k=blk)
        if spec is not None:
            kernel = jax.shard_map(kernel, mesh=mesh, in_specs=(spec,) * 3,
                                   out_specs=spec, check_vma=False)
        out = kernel(q, k, v)
    else:
        # record the fallback REASON when the platform was eligible but a
        # shape/dtype/mesh constraint forced the XLA path (the flash
        # selection rides the same counters/explainer as the paged family)
        _flash_counters["flash.xla"] += 1
        if on_tpu:
            _note_kernel_fallback("flash_attention", why,
                                  shape=str(tuple(q.shape)))
        out = _attention_xla(q, k, v, mask=mask, causal=causal, scale=scale)
    # tag for remat policies: attention is the most expensive op to
    # rematerialize, so the "attn"/"dots_attn" recompute policies pin this
    # output in HBM by name
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(out, "attn_out")


# =========================== paged attention =================================
#
# Decode-path fused paged attention (ISSUE 14; the walk and the body of
# PR 33). The serving engine's paged KV cache (PR 9) stores every slot's KV
# in a shared fixed-shape block pool [num_blocks, block_size, H*Dh] (heads
# merged into the last axis: the form the chip stores row by row,
# ops/kv_pool.py) addressed through per-slot int32 block tables. The XLA
# path materializes a gathered [B, M*bs, H, Dh] view of the pool and runs
# masked attention over it — two HBM round-trips XLA cannot fuse. The Pallas
# kernel below walks the block table INSIDE the kernel: the tables, lengths
# and query offsets ride scalar prefetch (pltpu.PrefetchScalarGridSpec), the
# pools stay in HBM (pl.ANY), and program (b, j) copies the live blocks of
# its span of G consecutive table columns into VMEM itself, one DMA a
# block, one live span ahead of the fold (`_paged_attn_kernel`). No gathered
# view ever exists, a dead table column costs neither a DMA nor a program's
# bookkeeping for an operand, and all heads of a span are folded by two
# dots (`_paged_plan` sizes the span and the head groups from the geometry).
# Measured on the v5e (PERF.md, PR 33): a BlockSpec operand costs ~0.07 us
# of the pipeline's scalar bookkeeping a grid step whether its block moves
# or not, so B x M x 2 block operands were 0.66 ms a call at the chat
# cell's shapes however many keys a program folded; with its own copies the
# kernel runs at 84-92 % of the HBM time of the rows it reads.
#
# One kernel serves both consumers:
#   * decode:      q is a [B, 1, H, Dh] span (T=1), q_offsets = cursors;
#   * spec verify: q is the [B, K+1, H, Dh] verify span — the causal
#     intra-span mask falls out of the position mask (row t admits key
#     positions <= q_offsets+t, and span row u>t lives at position
#     q_offsets+u), so no extra mask plumbing exists to get wrong.
#
# Semantics are pinned to the PR 9 gather path: key position j is valid for
# query row t iff  j <= q_offsets[b] + t  AND  j < seq_lens[b].  Inactive
# lanes (zeroed table rows, seq_lens=1) read the reserved garbage block 0
# and produce finite garbage the host discards — masked lanes contribute
# zero and can never corrupt live blocks, exactly like the gather path.
#
# Numerics: fp32 online-softmax accumulation in VMEM scratch. The XLA
# oracle reduces in a different order (full-softmax over the gathered
# view, probabilities cast back to the compute dtype before the PV
# matmul), so fused-vs-XLA parity is a TOLERANCE contract, not bitwise:
# PAGED_PARITY_TOL pins the per-dtype bounds the tests and the bench
# parity gate use. Greedy token streams ARE required to be identical
# across kernels at the served model sizes (the argmax margin dwarfs the
# accumulation-order delta).

# per-dtype |fused - xla| bounds (atol, rtol): fp32 differs only by
# f32 reduction order; bf16 additionally keeps 16 bits of a probability
# where the XLA path rounds it to bf16 before the PV matmul
PAGED_PARITY_TOL = {"float32": (3e-5, 3e-5), "bfloat16": (0.05, 0.05)}


def _paged_attn_kernel(bt_ref, sl_ref, qo_ref, q_ref, k_hbm, v_hbm, o_ref,
                       k_buf, v_buf, sem, turn, qbd_scr, m_scr, l_scr,
                       acc_scr, *, scale, head_dim, lanes, precision,
                       q_per_kv=1, window=None, block_span=False):
    """Grid (B, ceil(M / G)): program (b, j) folds the G consecutive
    logical blocks ``j*G .. j*G+G-1`` of slot b (``G * block_size`` keys)
    into the slot's online-softmax state, for all heads at once. Scratch
    persists across the grid; the output block is written once, at the
    last j.

    The walk. The pools stay in HBM; a program copies exactly the live
    blocks of its span into one half of ``k_buf`` / ``v_buf``
    ``[2, G, block_size, H*Dh]``, one DMA a block, and the copies run one
    live program ahead: before it waits for its own blocks a program
    starts the copies of the next span that has any (the next of its
    slot, else the first of the next slot), into the other half. A
    program past its slot's last live block starts nothing, waits for
    nothing and folds nothing. ``turn`` holds which half the next live
    program reads.

    The body. The merged axis is taken in groups of ``lanes`` lanes (whole
    128-lane tiles holding whole heads; all of it in the serving cells). A
    group's heads meet the span in ONE dot: its query block holds head h's
    query on row h, on head h's own lanes and zeros on the rest
    (``qbd_scr``, built once a slot), so ``qbd @ K.T`` is every head's
    scores ``[heads, span]`` lane-dense, the max and the sum run along
    the span, and ``p @ V`` holds head h's output on row h under head h's
    lanes (the finalizer keeps those). No head is sliced, rotated or
    addressed. A verify span's T rows are T such row blocks (M of the
    dots), each masked to its own position — or, a ``block_span`` (a
    block-diffusion decoder's block of T rows, models/sdar_moe.py), all to
    the span's end: every row admits ``j < q_offsets + T``, its own block
    whole, in both directions.

    Grouped queries (``q_per_kv`` > 1: the pool's row holds Hkv heads and
    ``q_per_kv`` query heads read each). The query arrives ``[T * q_per_kv,
    Hkv * Dh]``, row ``t * q_per_kv + r`` holding query head ``h * q_per_kv
    + r`` on key/value head h's lanes; a group is the fewest key/value
    heads that fill whole lane tiles (one, at 128-wide heads), and its row
    block t is those heads' ``q_per_kv`` queries each, one under the
    other: a key/value head's queries meet the span in one dot with no
    other head's lanes in it.

    A window (``window`` keys, the query's own among them; the table is
    then the slot's RING, ``ops/kv_pool.py``): the walk starts at the
    logical block that holds the oldest key the span's first row may see
    and ends at the newest, logical block lb sits in table column ``lb %
    ring``, and the rows of the oldest block that fell out of the window
    are masked like the rows past the slot's length."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    _, G, block_size, HD = k_buf.shape
    T = q_ref.shape[0] // q_per_kv
    n_groups = HD // lanes
    Hp = qbd_scr.shape[1] // T  # a group's heads, padded to whole sublanes
    span = G * block_size
    i32 = jnp.int32
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=precision)
    dot_nt = functools.partial(  # q @ k.T without forming k.T
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)
    # own[h, c]: lane c of the group belongs to head h of the group (rows
    # past the group's heads own nothing: zero queries, dropped outputs)
    head = jax.lax.broadcasted_iota(i32, (Hp, lanes), 0)
    lane = jax.lax.broadcasted_iota(i32, (Hp, lanes), 1)
    if q_per_kv > 1:  # row hl * q_per_kv + r: a query of the group's head hl
        head = head // i32(q_per_kv)
    own = ((lane >= head * i32(head_dim))
           & (lane < (head + i32(1)) * i32(head_dim)))

    def limit_of(slot):  # highest key position a row of `slot` reads, excl.
        return jnp.minimum(qo_ref[slot] + i32(T), sl_ref[slot])

    def base_of(slot):  # the logical block the slot's walk starts at
        return jnp.maximum(qo_ref[slot] - i32(window - 1),
                           _i0()) // i32(block_size)

    def blocks_of(slot):
        # table columns the slot's rows read; its first span always walks
        # one (an inactive lane: the reserved block 0, finite garbage)
        if window is not None:
            return jnp.maximum(pl.cdiv(limit_of(slot), i32(block_size))
                               - base_of(slot), i32(1))
        return jnp.maximum(pl.cdiv(limit_of(slot), i32(block_size)), i32(1))

    def copies(half, g, blk=_i0()):
        return (pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[half, g],
                                      sem.at[half, _i0()]),
                pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[half, g],
                                      sem.at[half, i32(1)]))

    def fetch(slot, first, half):  # start: blocks first.. of slot's span
        def start(g, _):
            col = first + g
            if window is not None:  # logical block -> its ring column
                col = (base_of(slot) + col) % i32(bt_ref.shape[1])
            for c in copies(half, g, bt_ref[slot, col]):
                c.start()
            return _
        jax.lax.fori_loop(
            _i0(), jnp.minimum(blocks_of(slot) - first, i32(G)), start, _i0())

    n_blk = blocks_of(b)
    limit = limit_of(b)
    # position of the walk's first key (a window's: its oldest live block)
    origin = None if window is None else base_of(b) * i32(block_size)

    @pl.when((b == 0) & (j == 0))
    def _first():
        # rows of a half that no copy has reached yet meet p = 0 in the
        # fold: they must be finite
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        turn[0] = _i0()
        fetch(b, _i0(), _i0())

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        for g in range(n_groups):  # static unrolls
            for t in range(T):
                qt = q_ref[t * q_per_kv:(t + 1) * q_per_kv,
                           g * lanes:(g + 1) * lanes].astype(
                    jnp.float32)  # the select runs on 32-bit lanes
                if q_per_kv > 1:
                    # the q_per_kv queries once under each head of the
                    # group, rows up to whole sublanes zero
                    n_kv = lanes // head_dim
                    qt = jnp.concatenate(
                        [qt] * n_kv + [jnp.zeros(
                            (Hp - n_kv * q_per_kv, lanes), jnp.float32)
                        ] * (Hp > n_kv * q_per_kv), axis=0)
                qbd_scr[g, t * Hp:(t + 1) * Hp, :] = jnp.where(
                    own, qt, _f32(0)).astype(qbd_scr.dtype)

    @pl.when(j * i32(G) < n_blk)
    def _walk():
        half = turn[0]
        more = (j + i32(1)) * i32(G) < n_blk

        @pl.when(more)
        def _ahead_in_slot():
            fetch(b, (j + i32(1)) * i32(G), i32(1) - half)

        @pl.when(jnp.logical_not(more) & (b + i32(1) < pl.num_programs(0)))
        def _ahead_next_slot():
            fetch(b + i32(1), _i0(), i32(1) - half)

        def wait(g, _):
            for c in copies(half, g):
                c.wait()
            return _
        jax.lax.fori_loop(
            _i0(), jnp.minimum(n_blk - j * i32(G), i32(G)), wait, _i0())
        turn[0] = i32(1) - half

        @pl.when(j * i32(span) < limit if window is None
                 else origin + j * i32(span) < limit)
        def _fold():
            k = k_buf[half].reshape(span, HD)
            v = v_buf[half].reshape(span, HD)
            pos = j * i32(span) + jax.lax.broadcasted_iota(
                i32, (T * Hp, span), 1)
            if window is not None:
                pos = origin + pos
            # row block t reads key positions < min(qo + t + 1, sl)
            row = jax.lax.broadcasted_iota(i32, (T * Hp, 1), 0)
            t_of = sum(((row >= i32(t * Hp)).astype(i32)
                        for t in range(1, T)), jnp.zeros_like(row))
            # (a block span: every row reads the span whole)
            mask = pos < (limit if block_span else jnp.minimum(
                qo_ref[b] + t_of + i32(1), sl_ref[b]))
            if window is not None:
                mask = mask & (pos > qo_ref[b] + t_of - i32(window))
            for g in range(n_groups):
                kg = k[:, g * lanes:(g + 1) * lanes]
                vg = v[:, g * lanes:(g + 1) * lanes]
                # the pool's dtype goes to the MXU as it is: the query
                # unscaled (exact), the scale on the float32 scores
                s = jnp.where(mask, dot_nt(qbd_scr[g], kg) * _f32(scale),
                              _NEG_INF)
                m_new = jnp.maximum(m_scr[g],
                                    s.max(axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_scr[g] - m_new)
                l_scr[g] = l_scr[g] * corr + p.sum(axis=-1, keepdims=True)
                if vg.dtype == jnp.float32:
                    pv = dot(p, vg)
                else:
                    # p as two pool-dtype terms, as many more rows of the
                    # same dot: a bf16 pool's probabilities keep 16 bits
                    hi = p.astype(vg.dtype)
                    lo = (p - hi.astype(jnp.float32)).astype(vg.dtype)
                    pv = dot(jnp.concatenate([hi, lo], axis=0), vg)
                    pv = pv[:T * Hp] + pv[T * Hp:]
                acc_scr[g] = acc_scr[g] * corr + pv
                m_scr[g] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        for g in range(n_groups):
            out = acc_scr[g] / jnp.maximum(l_scr[g], _TINY)
            for t in range(T):
                mine = jnp.where(own, out[t * Hp:(t + 1) * Hp], _f32(0))
                if q_per_kv > 1:  # query r of every head of the group
                    o_ref[t * q_per_kv:(t + 1) * q_per_kv,
                          g * lanes:(g + 1) * lanes] = sum(
                        mine[h * q_per_kv:(h + 1) * q_per_kv]
                        for h in range(lanes // head_dim)
                    ).astype(o_ref.dtype)
                    continue
                o_ref[t:t + 1, g * lanes:(g + 1) * lanes] = mine.sum(
                    axis=0, keepdims=True).astype(o_ref.dtype)


# What one program of the heads kernel may hold in VMEM: both halves of the
# span's K and V blocks, the span relaid out for the MXU, and the float32
# working set (block-diagonal queries, accumulator, one dot's result).
# Mosaic scopes 16 MiB to a kernel; compiled for the v5e, a 2 MiB block
# (8 MiB of copy buffers, G = 1) fits and a 4 MiB block ends in
# RESOURCE_EXHAUSTED (tests/test_tpu_lowering.py).
_PAGED_VMEM_BUDGET = 12 << 20
# keys one program folds, lanes one dot contracts and query rows it takes,
# at most: measured on the v5e at the serving cells' shapes (PERF.md, PR 33:
# 256 keys beat 128 by 14 % and tie 512; all 2048 lanes in one dot beat
# four dots of 512 by 5 % at T = 1 and lose 13 % at T = 5's 160 rows)
_PAGED_MAX_SPAN_KEYS = 256
# ... and with grouped queries (PERF.md, PR 35: a row of 8 x 128 lanes is
# half the bytes of the cells' 32 x 64, and 512 keys beat 256 by 17-25 % at
# every group width; the multi-head cells stay at 256)
_PAGED_MAX_SPAN_KEYS_GROUPED = 512
_PAGED_MAX_GROUP_LANES = 2048
_PAGED_MAX_DOT_ROWS = 64


def _group_rows(lanes, head_dim, span_rows, q_per_kv=1):
    """Rows of a head group's query block: ``span_rows`` row blocks of the
    group's heads (``q_per_kv`` queries each), each padded to whole
    sublanes (8)."""
    return span_rows * (-(-(lanes // head_dim * q_per_kv) // 8) * 8)


def _paged_group_lanes(num_heads, head_dim, span_rows=1, q_per_kv=1):
    """Lanes of the merged axis one dot of the heads kernel contracts:
    whole 128-lane tiles holding whole heads, the largest such divisor of
    ``H * Dh`` within ``_PAGED_MAX_GROUP_LANES`` whose query block
    (:func:`_group_rows`) stays within ``_PAGED_MAX_DOT_ROWS``; all of the
    axis where heads and tiles
    never line up (12 x 80: the interpreter only, `paged_tileable`). With
    grouped queries a head brings ``q_per_kv`` rows, so the same bound on
    the rows gives narrower groups (8 x 128 with 16 queries a head: 4 heads,
    64 rows against 512 lanes; PERF.md, PR 35: one head a group, 16 rows
    against 128 lanes, is 1.9 x slower, all 8 heads 1.3 x)."""
    width = num_heads * head_dim
    unit = math.lcm(head_dim, 128)
    if width % unit:
        return width
    units = width // unit
    fits = [k for k in range(1, units + 1)
            if units % k == 0 and k * unit <= _PAGED_MAX_GROUP_LANES
            and _group_rows(k * unit, head_dim, span_rows, q_per_kv)
            <= _PAGED_MAX_DOT_ROWS]
    return max(fits, default=1) * unit


def _paged_plan(block_size, num_heads, head_dim, dtype, span_rows=1,
                table_cols=None, q_per_kv=1):
    """(G, lanes, rows) for one pool geometry: G blocks a program, the
    lanes of a head group and the rows ``T * Hp`` of its query block. G is
    what ``_PAGED_VMEM_BUDGET`` holds beside the float32 working set, at
    most ``_PAGED_MAX_SPAN_KEYS`` keys and the table's columns; 0 when not
    even one block fits."""
    item = jnp.dtype(dtype).itemsize
    lanes = _paged_group_lanes(num_heads, head_dim, span_rows, q_per_kv)
    rows = _group_rows(lanes, head_dim, span_rows, q_per_kv)
    groups = num_heads * head_dim // lanes
    block = block_size * num_heads * head_dim * item
    # every group's queries (pool dtype) and accumulator, one group's dot
    # result and rescaled accumulator
    work = rows * lanes * (groups * (item + 4) + 8)
    room = _PAGED_VMEM_BUDGET - work
    if room < 4 * block:
        return 0, lanes, rows
    span = _PAGED_MAX_SPAN_KEYS if q_per_kv == 1 \
        else _PAGED_MAX_SPAN_KEYS_GROUPED
    G = max(1, min(span // block_size, room // (6 * block)))
    G = 1 << (G.bit_length() - 1)  # whole MXU tiles of keys at block 16
    if table_cols is not None:
        G = min(G, table_cols)
    return int(G), lanes, rows


def paged_keys_per_program(block_size, num_heads, head_dim, dtype,
                           table_cols, q_per_kv=1):
    """Keys one program of the heads kernel folds at decode (T = 1) for
    this geometry (``num_heads``: the heads of a pool's row one shard
    holds, ``q_per_kv`` query heads reading each): the gauge
    ``serving.paged_keys_per_program``."""
    return block_size * max(1, _paged_plan(
        block_size, num_heads, head_dim, dtype, 1, table_cols, q_per_kv)[0])


def _paged_attention_fused(q, k_pool, v_pool, block_tables, seq_lens,
                           q_offsets, scale, interpret, window=None,
                           block_span=False):
    B, T, Hq, Dh = q.shape
    bs = int(k_pool.shape[1])
    M = int(block_tables.shape[1])
    H = int(k_pool.shape[2]) // Dh  # the heads of a pool's row
    qpk = Hq // H
    G, lanes, rows = _paged_plan(bs, H, Dh, k_pool.dtype, T, M, qpk)
    G = max(G, 1)  # a geometry paged_tileable refuses: Mosaic says why
    groups = H * Dh // lanes
    if qpk > 1:  # query r of every key/value head side by side on row r
        q = q.reshape(B, T, H, qpk, Dh).transpose(0, 1, 3, 2, 4)

    def q_map(b, j, bt, sl, qo):
        return (b, _i0(), _i0())

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, -(-M // G)),
        in_specs=[pl.BlockSpec((None, T * qpk, H * Dh), q_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, T * qpk, H * Dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, G, bs, H * Dh), k_pool.dtype),  # K, two halves
            pltpu.VMEM((2, G, bs, H * Dh), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # [half, K | V]
            pltpu.SMEM((1,), jnp.int32),  # the half the next span reads
            pltpu.VMEM((groups, rows, lanes), k_pool.dtype),  # queries
            pltpu.VMEM((groups, rows, 1), jnp.float32),  # running max
            pltpu.VMEM((groups, rows, 1), jnp.float32),  # running denom
            pltpu.VMEM((groups, rows, lanes), jnp.float32),  # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, scale=scale, head_dim=Dh,
                          lanes=lanes,
                          precision=_dot_precision(k_pool.dtype),
                          q_per_kv=qpk, window=window,
                          block_span=block_span),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T * qpk, H * Dh), q.dtype),
        interpret=interpret,
        name=_kernel_name("paged_attention" if window is None
                          else "paged_attention_window"),
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q_offsets.astype(jnp.int32), q.reshape(B, T * qpk, H * Dh), k_pool,
      v_pool)
    if qpk > 1:
        return out.reshape(B, T, qpk, H, Dh).transpose(
            0, 1, 3, 2, 4).reshape(B, T, Hq, Dh)
    return out.reshape(B, T, H, Dh)


def _mesh_mp_degree(mesh):
    """Size of the mesh's 'mp' axis (1 when absent or mesh is None)."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get("mp", 1))


def _per_head_shard(body, mesh, num_heads):
    """``body(q, k_pool, v_pool, block_tables, seq_lens, q_offsets)`` per
    shard under ``jax.shard_map``: pools and q are head-sharded over the
    mesh's 'mp' axis, block tables / seq_lens / q_offsets ride in
    replicated, and each shard runs the UNMODIFIED kernel body over its
    local heads. The kernels compute every head independently (per-head
    scratch rows, no cross-head reduction), so the sharded result is
    bitwise the single-chip result. check_vma is off because pallas_call
    carries no replication rule."""
    mp = _mesh_mp_degree(mesh)
    if num_heads % mp:  # select_paged_kernel prevents this; defensive
        raise ValueError(
            f"paged_attention: {num_heads} heads do not divide over mesh "
            f"axis mp={mp}; resolve the kernel with select_paged_kernel("
            "num_heads=...) so indivisible head counts demote to xla")
    head = P(None, None, "mp", None)
    pool = _kv_pool.pspec(True)  # a shard's merged axis is its own heads
    repl = P()
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(head, pool, pool, repl, repl, repl),
        out_specs=head, check_vma=False)


def paged_attention_xla(q, k_pool, v_pool, block_tables, seq_lens,
                        q_offsets, scale=None, window=None,
                        block_span=False):
    """The gather-path reference: materialize each slot's logical
    [M*bs] view of the pool and run masked attention over it. Same
    semantics as GPTAttention's PR 9 paged branch; serves as the parity
    oracle for the fused kernel and as the ``kernel="xla"`` route. A pool
    whose row holds fewer heads than ``q`` has is read grouped (query head
    h reads head ``h // (Hq / Hkv)``); with a ``window`` the table is the
    slot's ring and a view row's position comes from
    ``kv_pool.ring_positions``; a ``block_span``'s rows all read up to the
    span's end."""
    B, T, H, Dh = q.shape
    scale = float(scale) if scale is not None else Dh ** -0.5
    k_pool, v_pool = _kv_pool.merged(k_pool), _kv_pool.merged(v_pool)
    Hkv = int(k_pool.shape[2]) // Dh
    k_view = _kv_pool.gather_view(k_pool, block_tables, Hkv)
    v_view = _kv_pool.gather_view(v_pool, block_tables, Hkv)
    if Hkv != H:
        k_view = jnp.repeat(k_view, H // Hkv, axis=2)
        v_view = jnp.repeat(v_view, H // Hkv, axis=2)
    S = k_view.shape[1]
    jpos = jnp.arange(S, dtype=jnp.int32)[None, None, :]
    qrow = q_offsets.astype(jnp.int32)[:, None] + (
        jnp.full((1, T), T - 1, jnp.int32) if block_span
        else jnp.arange(T, dtype=jnp.int32)[None])
    if window is not None:
        jpos = _kv_pool.ring_positions(
            seq_lens, int(block_tables.shape[1]),
            int(k_pool.shape[1]))[:, None, :]
    mask = ((jpos <= qrow[:, :, None])
            & (jpos < seq_lens.astype(jnp.int32)[:, None, None]))
    if window is not None:
        mask = mask & (jpos > qrow[:, :, None] - jnp.int32(window)) \
            & (jpos >= 0)
    return _attention_xla(q, k_view, v_view, mask=mask[:, None],
                          scale=scale)


def paged_attention(q, k_pool, v_pool, block_tables, seq_lens, q_offsets,
                    kernel="xla", scale=None, mesh=None, window=None,
                    block_span=False):
    """Paged-KV attention: ``q`` [B, T, H, Dh] over pools
    [num_blocks, block_size, H*Dh] (ops/kv_pool.py; a 4-D
    [num_blocks, block_size, H, Dh] pool is merged on entry, which is a
    whole-pool relayout on the chip: the engine never passes one)
    addressed by ``block_tables`` [B, M].
    ``seq_lens`` [B] counts each slot's valid rows INCLUDING the span's
    own freshly-scattered rows; ``q_offsets`` [B] is the absolute
    position of span row 0. ``kernel``: "pallas" (compiled TPU),
    "interpret" (the same kernel body through the Pallas interpreter —
    the CPU-CI parity route) or "xla" (gather reference). A ``mesh``
    with an 'mp' axis of > 1 devices routes the fused kinds per-shard
    through :func:`jax.shard_map` with head-sharded q/pools — the
    kernel body is unchanged, each shard just sees H/mp heads. Resolve
    the choice ONCE per engine with :func:`select_paged_kernel` — it
    must never vary per step or the serving replay fast path retraces.

    Grouped queries need no argument: a pool whose row holds ``Hkv < H``
    heads is read by ``H / Hkv`` query heads a head. ``window`` (keys a
    query may see, its own among them) says ``block_tables`` holds each
    slot's ring (``ops/kv_pool.py``); the kernel is then called
    ``paged_attention_window`` in the trace. A ring has no mesh route and
    holds no verify span (its newest rows would overwrite keys its oldest
    still sees): T must be 1. ``block_span``: the T rows are one block of a
    block-diffusion decoder and each reads key positions ``< q_offsets + T``
    (the verify span's row t reads ``<= q_offsets + t``); same kernel, same
    name in a trace, no ring."""
    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    if block_span and window is not None:
        raise TypeError("paged_attention(block_span=True): a ring holds no "
                        "span of rows")
    if window is not None and (q.shape[1] != 1
                               or _mesh_mp_degree(mesh) > 1):
        raise TypeError("paged_attention(window=...): a ring takes one "
                        "query row a slot and no mesh")
    if kernel == "xla":
        return paged_attention_xla(q, k_pool, v_pool, block_tables,
                                   seq_lens, q_offsets, scale=scale,
                                   window=window, block_span=block_span)
    if kernel not in ("pallas", "interpret"):
        raise ValueError(
            f"unknown paged-attention kernel {kernel!r} "
            "(expected pallas | interpret | xla)")
    k_pool, v_pool = _kv_pool.merged(k_pool), _kv_pool.merged(v_pool)
    body = functools.partial(_paged_attention_fused, scale=scale,
                             interpret=(kernel == "interpret"),
                             window=window, block_span=block_span)
    if _mesh_mp_degree(mesh) > 1:
        body = _per_head_shard(body, mesh, int(q.shape[2]))
    out = body(q, k_pool, v_pool, block_tables, seq_lens, q_offsets)
    # kernel_mismatch fault (testing/faults.py): perturb ONE element of
    # the fused output so parity gates provably trip. Trace-time firing:
    # the perturbation is baked into whichever executable traces while
    # the point is armed (tests build throwaway engines/calls).
    from ..testing import faults as _faults

    if _faults.ACTIVE and _faults.fire("kernel_mismatch"):
        out = out.at[(0,) * out.ndim].add(jnp.asarray(1.0, jnp.float32)
                                          .astype(out.dtype))
    return out


def paged_tileable(head_dim, block_size, dtype, num_heads=None):
    """Will Mosaic compile the kernel for this pool geometry? (The
    interpreter route has no such constraint.) Returns (ok, reason).

    A program copies whole ``(block_size, H*Dh)`` pool blocks, so no
    head_dim or block_size fails to tile as long as the merged row is whole
    128-lane tiles; how many blocks a program folds and how the heads are
    grouped for its dots come from :func:`_paged_plan`. What is refused is
    a dtype the body has no arithmetic for and, judged when ``num_heads`` —
    the heads one shard holds — is given, a merged row that is not whole
    lane tiles (12 x 80 = 960: Mosaic cannot slice a block of it for the
    copy) and a geometry of which not one block fits ``_PAGED_VMEM_BUDGET``
    beside the working set."""
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False, f"pool dtype {dt.name} not in (float32, bfloat16)"
    if num_heads is not None and num_heads * head_dim % 128:
        return False, (
            f"a merged row of {num_heads} x {head_dim} = "
            f"{num_heads * head_dim} lanes is not whole 128-lane tiles: "
            "the kernel's block copies need them")
    if num_heads is not None \
            and not _paged_plan(block_size, num_heads, head_dim, dt)[0]:
        block = block_size * num_heads * head_dim * dt.itemsize
        return False, (
            f"one KV block [{block_size}, {num_heads}, {head_dim}] "
            f"{dt.name} is {block / 2 ** 20:.1f} MiB; K and V blocks, "
            "double-buffered, would not fit the kernel's VMEM beside its "
            f"working set (budget {_PAGED_VMEM_BUDGET >> 20} MiB)")
    return True, "tileable"


def select_paged_kernel(requested=None, *, head_dim, block_size, dtype,
                        mesh=None, num_heads=None,
                        family="paged_attention"):
    """Resolve the paged-attention kernel for one engine build.

    ``requested``: "pallas" | "xla" | "auto" | None (None reads env
    ``PADDLE_TPU_PAGED_KERNEL``, default "auto"). Resolution:

      * auto   -> "pallas" on TPU when :func:`paged_tileable` passes,
                  else "xla" (with a ``kernel_fallback`` explainer event
                  naming the reason when a TPU was eligible);
      * pallas -> "pallas" on TPU, "interpret" off-chip (the kernel BODY
                  still runs — CPU CI's parity route); untileable shapes
                  fall back to "xla" loudly;
      * xla    -> "xla", always.

    A ``mesh`` whose 'mp' axis has > 1 devices resolves PER SHARD: the
    kernel is head-parallel, so when ``num_heads`` divides mp each
    shard runs the unmodified body over its local num_heads/mp heads
    (a shard's KV block holds only its local heads). Indivisible or unknown head counts
    demote to the GSPMD gather path with a loud fallback naming both
    numbers. Returns ``(kind, reason)`` and bumps
    ``serving.kernel.<kind>`` — call once at engine build, never per
    step; the resolved kind is a static closure constant, so each
    (bucket, kernel, mesh) pair keeps exactly one executable."""
    env = os.environ.get("PADDLE_TPU_PAGED_KERNEL", "")
    req = (requested or env or "auto").strip().lower()
    if req not in ("pallas", "xla", "auto"):
        source = ("paged_kernel argument" if requested
                  else "env PADDLE_TPU_PAGED_KERNEL")
        raise ValueError(
            f"{source} = {req!r} (expected pallas | xla | auto; "
            "\"interpret\" is a RESOLVED kind, not a request — ask for "
            "pallas and off-chip engines run the interpreter)")
    on_tpu = _on_tpu()
    mp = _mesh_mp_degree(mesh)
    local_heads = num_heads // mp if num_heads and not num_heads % mp \
        else None
    ok, why = paged_tileable(head_dim, block_size, dtype, local_heads)
    if req == "xla":
        kind, reason = "xla", "requested"
    elif mp > 1 and (num_heads is None or num_heads % mp):
        if num_heads is None:
            reason = (f"mesh-sharded decode (mp={mp}) needs num_heads "
                      "to plan the per-shard kernel; demoting to the "
                      "GSPMD gather path")
        else:
            reason = (f"model has {num_heads} heads, not divisible by "
                      f"mesh axis mp={mp}: no per-shard kernel; "
                      "demoting to the GSPMD gather path")
        kind = "xla"
        if req == "pallas" or on_tpu:
            _note_kernel_fallback(family, reason, num_heads=num_heads,
                                  mp=mp)
    elif req == "pallas":
        if on_tpu and not ok:
            kind, reason = "xla", why
            _note_kernel_fallback(family, reason,
                                  head_dim=head_dim,
                                  block_size=block_size)
        elif on_tpu:
            kind, reason = "pallas", "requested"
        else:
            kind = "interpret"
            reason = ("requested pallas off-chip: kernel body runs "
                      "through the Pallas interpreter")
    else:  # auto
        if on_tpu and ok:
            kind, reason = "pallas", "auto: tpu + tileable shapes"
        elif on_tpu:
            kind, reason = "xla", why
            _note_kernel_fallback(family, reason,
                                  head_dim=head_dim,
                                  block_size=block_size)
        else:
            kind, reason = "xla", "auto: platform is not tpu"
    if mp > 1 and kind in ("pallas", "interpret"):
        reason += (f"; per-shard over mesh mp={mp} "
                   f"(local heads {num_heads // mp})")
    _paged_counters[f"kernel.{kind}"] += 1
    return kind, reason


# ============================ flash prefill ==================================
#
# The prompt span's read (ISSUE 36): one slot's T new rows (a whole prompt, a
# prefix hit's remainder or one chunk, T a bucket) attend to the slot's rows
# in the pools, their own among them (the call wrote them just before).
# Semantics are the gather path's and the paged kernel's: key position j is
# valid for span row t iff  j <= q_offsets[b] + t  AND  j < seq_lens[b]; the
# offset and the length are DATA (scalar prefetch), so a cold prefill, a
# prefix hit and a chunk share one executable a bucket.
#
# The gather path formed [H, T, S] float32 scores in HBM, a [T, S] mask and a
# per-head relayout of the slot's gathered view, at the table's whole width
# whatever the prompt (PERF.md, PR 36: 88 + 19 of a 147 ms prefill). Here the
# pools stay in HBM (pl.ANY) as in the paged kernel; the first program of a
# slot starts one DMA a LIVE block of the slot into a VMEM copy of the
# slot's rows as they lie ([spans, span, H*Dh]: no head is transposed) and
# every program waits only for the spans it is the first to read, so the
# copies run behind the first query blocks' dots. A program is one block of
# ``block_q`` query rows, all heads: the merged axis is taken in groups of
# whole lane tiles holding whole heads (128 lanes: two 64-wide heads), a
# group's heads meet a span block-diagonally as in the paged kernel (head h's
# queries on rows h*block_q.., its own lanes, zeros on the rest), so no head
# is sliced, rotated or addressed, and the online softmax state of a group
# lives in the loop's carry. Spans are at ABSOLUTE key positions (span k =
# keys k*span .. (k+1)*span-1 whatever the offset) and fold in rising order,
# so a row folds the same keys in the same order in a one-shot, a chunked
# and a prefix-hit prefill. What is dead is skipped, not masked: spans past
# the block's last visible key are never read, spans wholly visible take the
# loop without the mask, and a query block wholly past the prompt (the
# bucket's padding) writes zeros.

# rows of one query block and keys of one span (whole blocks of the pool),
# at most: measured on the v5e at the two gpt cells' shapes (PERF.md, PR 36:
# 128 x 512 beat 256 x 256 by 22 % at the 2048 bucket and 13-30 % at the 512
# and 1024 buckets and tied it at 256 — the row statistics are columns of
# `heads * block_q` rows whatever the span, so a longer span amortises them,
# and a shorter query block wastes less above the diagonal; 1024 keys lost
# 10-15 %). A bucket takes the largest power of two <= the first that
# divides it.
_PREFILL_MAX_BLOCK_Q = 128
_PREFILL_MAX_SPAN_KEYS = 512
# the slot's K and V rows held in VMEM, at most, and what the body needs
# beside them (query and output blocks, one group's scores and accumulator);
# their sum is the kernel's `vmem_limit_bytes` (a v5e core has 128 MiB)
_PREFILL_MAX_RESIDENT = 40 << 20
_PREFILL_WORK_BYTES = 16 << 20
# a span of at least this many rows, in whole 16-row tiles, is a PROMPT span
# (decode's T = 1 and a verify span's T = K + 1 stay with the paged kernel)
_PREFILL_MIN_ROWS = 16


def prefill_span(rows):
    """Is a span of ``rows`` new rows a slot a prompt span, the flash
    prefill kernel's (True), or a decode / verify span, the paged kernel's
    (False)? Static: read from the shape."""
    return rows >= _PREFILL_MIN_ROWS and rows % _PREFILL_MIN_ROWS == 0


def _prefill_group_lanes(num_heads, head_dim):
    """Lanes of one head group: the fewest whole 128-lane tiles holding
    whole heads (64-wide heads: two a group, so the block-diagonal dot
    contracts the MXU's full depth and wastes no pass); all of the merged
    axis where heads and tiles never line up (the interpreter only,
    `prefill_tileable`)."""
    width = num_heads * head_dim
    unit = math.lcm(head_dim, 128)
    return width if width % unit else unit


def _prefill_plan(rows, block_size, table_cols, block_q=None, span=None):
    """(block_q, blocks a span) for a span of ``rows`` query rows over a
    table of ``table_cols`` blocks. The span depends on the pool's geometry
    only, never on ``rows``: every bucket folds a row's keys in the same
    order."""
    if block_q is None:
        block_q = _PREFILL_MAX_BLOCK_Q
        while block_q > _PREFILL_MIN_ROWS and rows % block_q:
            block_q //= 2
        if rows % block_q:  # the interpreter only: one block of all rows
            block_q = rows
    G = max(1, min((span or _PREFILL_MAX_SPAN_KEYS) // block_size,
                   table_cols))
    return int(block_q), int(G)


def _prefill_resident_bytes(table_cols, G, block_size, width, dtype):
    """Bytes of VMEM the kernel's copy of one slot's K and V rows takes:
    the table's columns rounded up to whole spans of ``G`` blocks."""
    return (2 * -(-table_cols // G) * G * block_size * width
            * jnp.dtype(dtype).itemsize)


def _flash_prefill_kernel(bt_ref, sl_ref, qo_ref, q_ref, k_hbm, v_hbm, o_ref,
                          k_scr, v_scr, sem, ready, *, scale, head_dim,
                          lanes, precision):
    """Grid (B, T / block_q): program (b, i) is query rows ``i*block_q ..``
    of slot b against the slot's keys. ``k_scr`` / ``v_scr`` ``[spans,
    span, H*Dh]`` hold the slot's rows, copied by program (b, 0) one DMA a
    live block; ``sem[k]`` counts span k's copies and ``ready`` how many
    spans this slot's programs have waited for."""
    b = pl.program_id(0)
    i = pl.program_id(1)
    n_spans, span, HD = k_scr.shape
    block_q = q_ref.shape[0]
    bs = k_hbm.shape[1]
    G = span // bs
    heads = lanes // head_dim  # heads of a group
    n_groups = HD // lanes
    rows = heads * block_q
    i32 = jnp.int32
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=precision)
    dot_nt = functools.partial(  # q @ k.T without forming k.T
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)
    # a power-of-two scale (64-wide heads: 1/8) is exact on the query in
    # any dtype; any other goes on the float32 scores
    fold_scale = math.frexp(scale)[0] == 0.5

    off, sl = qo_ref[b], sl_ref[b]
    total = jnp.minimum(off + pl.num_programs(1) * i32(block_q), sl)
    n_blk = pl.cdiv(total, i32(bs))  # blocks any row of the call reads
    row0 = off + i * i32(block_q)  # position of the block's first row
    live = row0 < sl  # the block holds a row of the prompt
    limit = jnp.minimum(row0 + i32(block_q), sl)  # keys it reads: < limit

    def copies(col, blk=_i0()):
        k, r = col // i32(G), pl.multiple_of((col % i32(G)) * i32(bs), bs)
        return (pltpu.make_async_copy(
                    k_hbm.at[blk], k_scr.at[k, pl.ds(r, bs)],
                    sem.at[k, _i0()]),
                pltpu.make_async_copy(
                    v_hbm.at[blk], v_scr.at[k, pl.ds(r, bs)],
                    sem.at[k, i32(1)]))

    @pl.when((b == 0) & (i == 0))
    def _first():
        # rows no copy reaches meet p = 0 in the fold: they must be finite
        k_scr[...] = jnp.zeros_like(k_scr)
        v_scr[...] = jnp.zeros_like(v_scr)

    @pl.when(i == 0)
    def _fetch():
        def start(col, _):
            for c in copies(col, bt_ref[b, col]):
                c.start()
            return _
        jax.lax.fori_loop(_i0(), n_blk, start, _i0())
        ready[0] = _i0()

    # wait for the spans this block is the first to read (the last block
    # of a slot for whatever is left: no copy outlives the slot's programs)
    need = jnp.where(live, pl.cdiv(limit, i32(span)), _i0())
    need = jnp.where(i == pl.num_programs(1) - 1,
                     pl.cdiv(n_blk, i32(G)), need)

    def wait_span(k, _):
        def wait(g, _):
            for c in copies(k * i32(G) + g):
                c.wait()
            return _
        return jax.lax.fori_loop(
            _i0(), jnp.minimum(n_blk - k * i32(G), i32(G)), wait, _)
    jax.lax.fori_loop(ready[0], need, wait_span, _i0())
    ready[0] = jnp.maximum(ready[0], need)

    @pl.when(jnp.logical_not(live))
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _fold():
        # rows h*block_q.. of a group's block are head h's queries
        lane = jax.lax.broadcasted_iota(i32, (block_q, lanes), 1)
        qpos = jnp.concatenate(
            [row0 + jax.lax.broadcasted_iota(i32, (block_q, 1), 0)] * heads,
            axis=0)
        # spans wholly visible to every row of the block and wholly live
        n_full = jnp.minimum((row0 + i32(1)) // i32(span), sl // i32(span))
        hi = pl.cdiv(limit, i32(span))

        def group(g, done):
            cols = pl.ds(pl.multiple_of(g * i32(lanes), lanes), lanes)
            qg = q_ref[:, cols].astype(jnp.float32)  # selects on 32 bits
            if fold_scale:
                qg = qg * _f32(scale)
            qbd = jnp.concatenate(
                [jnp.where((lane >= i32(h * head_dim))
                           & (lane < i32((h + 1) * head_dim)), qg, _f32(0))
                 for h in range(heads)], axis=0).astype(k_scr.dtype)

            def fold(k, carry, masked):
                m, l, acc = carry
                s = dot_nt(qbd, k_scr[k, :, cols])
                if not fold_scale:
                    s = s * _f32(scale)
                if masked:
                    kpos = k * i32(span) + jax.lax.broadcasted_iota(
                        i32, (1, span), 1)
                    s = jnp.where((kpos <= qpos) & (kpos < sl), s, _NEG_INF)
                m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m - m_new)
                l = l * corr + p.sum(axis=-1, keepdims=True)
                # p in the pool's dtype, as the gather path rounds its
                # probabilities before the value matmul
                acc = acc * corr + dot(p.astype(v_scr.dtype),
                                       v_scr[k, :, cols])
                return m_new, l, acc

            carry = (jnp.full((rows, 1), _NEG_INF, jnp.float32),
                     jnp.zeros((rows, 1), jnp.float32),
                     jnp.zeros((rows, lanes), jnp.float32))
            carry = jax.lax.fori_loop(
                _i0(), n_full, functools.partial(fold, masked=False), carry)
            _, l, acc = jax.lax.fori_loop(
                n_full, hi, functools.partial(fold, masked=True), carry)
            out = acc * (_f32(1) / jnp.maximum(l, _TINY))
            mine = out[:block_q]  # head h's output: rows h.., its lanes
            for h in range(1, heads):
                mine = jnp.where(lane >= i32(h * head_dim),
                                 out[h * block_q:(h + 1) * block_q], mine)
            o_ref[:, cols] = mine.astype(o_ref.dtype)
            return done

        # a loop, not an unroll: one copy of the body whatever the width
        jax.lax.fori_loop(_i0(), i32(n_groups), group, _i0())


# jitted: the layers of a decoder call it with one signature, so a step
# traces and lowers the body once, not once a layer (PERF.md, PR 36: with
# the body unrolled over the head groups and traced a layer, 24 layers x
# the 2-3 traces an engine's warm-up makes of a bucket were 35 s of the
# long-prefill cell's `setup_s`; the body loops over the groups now)
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "block_q",
                                             "span"))
def _flash_prefill_fused(q, k_pool, v_pool, block_tables, seq_lens,
                         q_offsets, scale, interpret, block_q=None,
                         span=None):
    B, T, H, Dh = q.shape
    bs = int(k_pool.shape[1])
    M = int(block_tables.shape[1])
    HD = H * Dh
    block_q, G = _prefill_plan(T, bs, M, block_q, span)
    n_spans = -(-M // G)
    lanes = _prefill_group_lanes(H, Dh)
    resident = _prefill_resident_bytes(M, G, bs, HD, k_pool.dtype)

    def q_map(b, i, bt, sl, qo):
        return (b, i, _i0())

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, T // block_q),
        in_specs=[pl.BlockSpec((None, block_q, HD), q_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, block_q, HD), q_map),
        scratch_shapes=[
            pltpu.VMEM((n_spans, G * bs, HD), k_pool.dtype),  # slot's K
            pltpu.VMEM((n_spans, G * bs, HD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((n_spans, 2)),  # [span, K | V]
            pltpu.SMEM((1,), jnp.int32),  # spans waited for
        ],
    )
    out = pl.pallas_call(
        functools.partial(_flash_prefill_kernel, scale=scale, head_dim=Dh,
                          lanes=lanes,
                          precision=_dot_precision(k_pool.dtype)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, HD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=resident + _PREFILL_WORK_BYTES),
        interpret=interpret,
        name=_kernel_name("flash_prefill"),
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q_offsets.astype(jnp.int32), q.reshape(B, T, HD), k_pool, v_pool)
    return out.reshape(B, T, H, Dh)


def flash_prefill(q, k_pool, v_pool, block_tables, seq_lens, q_offsets,
                  kernel="xla", scale=None, mesh=None):
    """The prompt span's attention: ``q`` [B, T, H, Dh], T new rows a slot
    already written to the pools [num_blocks, block_size, H*Dh], read back
    through ``block_tables`` [B, M] under the paged family's mask
    (``seq_lens`` counts the span's own rows, ``q_offsets`` is the position
    of span row 0). ``kernel`` as :func:`paged_attention`'s, resolved once
    an engine by :func:`select_prefill_kernel`; "xla" is the gather route
    (:func:`paged_attention_xla`), the parity oracle. A ``mesh`` whose 'mp'
    axis is > 1 runs the kernel per head shard through ``shard_map``."""
    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    if kernel == "xla":
        return paged_attention_xla(q, k_pool, v_pool, block_tables,
                                   seq_lens, q_offsets, scale=scale)
    if kernel not in ("pallas", "interpret"):
        raise ValueError(
            f"unknown flash-prefill kernel {kernel!r} "
            "(expected pallas | interpret | xla)")
    k_pool, v_pool = _kv_pool.merged(k_pool), _kv_pool.merged(v_pool)
    body = functools.partial(_flash_prefill_fused, scale=scale,
                             interpret=(kernel == "interpret"))
    if _mesh_mp_degree(mesh) > 1:
        body = _per_head_shard(body, mesh, int(q.shape[2]))
    return body(q, k_pool, v_pool, block_tables, seq_lens, q_offsets)


def prefill_tileable(head_dim, block_size, dtype, num_heads, table_cols):
    """Will Mosaic compile the flash prefill kernel over this pool geometry
    (``num_heads``: the heads one shard holds; ``table_cols``: a slot's
    blocks)? Returns (ok, reason)."""
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False, f"pool dtype {dt.name} not in (float32, bfloat16)"
    width = num_heads * head_dim
    if width % math.lcm(head_dim, 128):
        return False, (
            f"a merged row of {num_heads} x {head_dim} = {width} lanes "
            "does not split into whole 128-lane tiles holding whole heads")
    tile = 32 // dt.itemsize  # rows of one packed sublane tile
    if block_size % tile:
        return False, (
            f"a block of {block_size} {dt.name} rows is not whole "
            f"{tile}-row tiles: its copy lands mid-tile in the slot's rows")
    G = _prefill_plan(_PREFILL_MIN_ROWS, block_size, table_cols)[1]
    resident = _prefill_resident_bytes(table_cols, G, block_size, width, dt)
    if resident > _PREFILL_MAX_RESIDENT:
        return False, (
            f"a slot's K and V rows ({table_cols} blocks of {block_size} x "
            f"{width} {dt.name}) are {resident / 2 ** 20:.0f} MiB; the "
            "kernel holds them in VMEM (budget "
            f"{_PREFILL_MAX_RESIDENT >> 20} MiB)")
    return True, "tileable"


def select_prefill_kernel(paged_kind, *, spans, head_dim, block_size, dtype,
                          num_heads, table_cols, mesh=None):
    """Resolve the prompt span's read for one engine build, from what the
    engine's paged kernel resolved to (``paged_kernel=`` is the one request
    both follow): "xla" stays the gather path; a fused kind stays itself
    when every prompt span (``spans``: the buckets) is one the model will
    route (:func:`prefill_span`, or short enough for the paged kernel) and,
    compiled, :func:`prefill_tileable`; a refusal is "xla", loudly
    (``serving.kernel.fallbacks``, a ``kernel_fallback`` event). Returns
    ``(kind, reason)``; sets no counter of its own kind."""
    if paged_kind == "xla":
        return "xla", "the engine's paged kernel resolved to xla"
    odd = [s for s in spans if s >= _PREFILL_MIN_ROWS and not prefill_span(s)]
    why = None
    if odd:
        why = (f"prompt spans {odd} are not whole {_PREFILL_MIN_ROWS}-row "
               "tiles")
    elif paged_kind == "pallas":  # the interpreter tiles anything
        ok, reason = prefill_tileable(
            head_dim, block_size, dtype,
            num_heads // _mesh_mp_degree(mesh), table_cols)
        why = None if ok else reason
    if why is None:
        return paged_kind, "follows the paged kernel: " + (
            "compiled" if paged_kind == "pallas" else "the interpreter")
    _note_kernel_fallback("flash_prefill", why, head_dim=head_dim,
                          block_size=block_size, spans=list(spans))
    return "xla", why


# ====================== latent (MLA) paged attention =========================
#
# Absorbed decode over a pool of one latent row a token (ops/kv_pool.py,
# kind "latent"): the row is [c_kv | k_rope | 0] on W lanes, every head's
# query arrives as [q_nope W_uk | q_rope | 0] on the same lanes, so one dot
# over the row scores a block for ALL heads (an MQA shape: M = heads for the
# MXU) and p @ rows gives every head's latent output on the first `rank`
# lanes (the rest is dropped by the caller, who applies W_uv). A latent
# block is read once for all heads. One query row a slot (T = 1): the
# engine refuses spec decode for this cache kind.
#
# Grid (B, ceil(M / G)): program (b, j) folds G consecutive logical blocks
# of slot b (G x block_size keys, 128 at block 16) into the slot's online-
# softmax state; the pool comes in G times, each operand's index map
# picking one of the program's blocks through the table, so a program is
# G small DMAs and two dots and the per-program cost is paid once for 128
# keys. Dead tail blocks clamp to the slot's last live block (no DMA) and
# the fold is `pl.when`-ed off. (The heads kernel's walk no longer takes its
# blocks as operands: ROADMAP S1 says what that is worth here.)

_MLA_KEYS_PER_PROGRAM = 128


def mla_keys_per_program(block_size, table_cols):
    """Keys one program of the latent kernel folds: the gauge
    ``serving.paged_keys_per_program`` for a latent cache."""
    return block_size * max(1, min(_MLA_KEYS_PER_PROGRAM // block_size,
                                   table_cols))


def _mla_paged_kernel(bt_ref, sl_ref, q_ref, *refs, scale, block_size,
                      blocks, precision):
    k_refs, o_ref = refs[:blocks], refs[blocks]
    m_scr, l_scr, acc_scr = refs[blocks + 1:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    H = q_ref.shape[0]
    span = blocks * block_size
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=precision)
    dot_nt = functools.partial(  # q @ rows.T without forming rows.T
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    sl = sl_ref[b]

    @pl.when(j * jnp.int32(span) < sl)
    def _fold():
        rows = jnp.concatenate([r[...] for r in k_refs],
                               axis=0).astype(jnp.float32)  # [span, W]
        q = q_ref[...].astype(jnp.float32) * _f32(scale)
        s = dot_nt(q, rows)  # [H, span]: every head against the blocks
        pos = j * jnp.int32(span) + jax.lax.broadcasted_iota(
            jnp.int32, (H, span), 1)
        s = jnp.where(pos < sl, s, _NEG_INF)
        m_new = jnp.maximum(m_scr[...], s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_scr[...] - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + dot(p, rows)
        m_scr[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], _TINY)
                      ).astype(o_ref.dtype)


def _mla_paged_fused(q, pool, block_tables, seq_lens, scale, interpret):
    B, H, W = q.shape
    bs = int(pool.shape[1])
    M = int(block_tables.shape[1])
    G = mla_keys_per_program(bs, M) // bs

    def q_map(b, j, bt, sl):
        return (b, _i0(), _i0())

    def row_map(g):
        def index(b, j, bt, sl):
            last = jnp.maximum(pl.cdiv(sl[b], jnp.int32(bs)) - 1, _i0())
            return (bt[b, jnp.minimum(j * jnp.int32(G) + jnp.int32(g),
                                      jnp.minimum(last, jnp.int32(M - 1)))],
                    _i0(), _i0())
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, -(-M // G)),
        in_specs=[pl.BlockSpec((None, H, W), q_map)]
        + [pl.BlockSpec((None, bs, W), row_map(g)) for g in range(G)],
        out_specs=pl.BlockSpec((None, H, W), q_map),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),  # running max
            pltpu.VMEM((H, 1), jnp.float32),  # running denom
            pltpu.VMEM((H, W), jnp.float32),  # fp32 accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_paged_kernel, scale=scale, block_size=bs,
                          blocks=G, precision=_dot_precision(q.dtype)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, W), q.dtype),
        interpret=interpret,
        name=_kernel_name("mla_paged_attention"),
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), q,
      *([pool] * G))


def mla_paged_attention_xla(q, pool, block_tables, seq_lens, scale):
    """The gather-path reference and the ``kernel="xla"`` route: each
    slot's logical view of the latent pool, masked to its length."""
    view = _kv_pool.latent_view(pool, block_tables)  # [B, S, W]
    s = jnp.einsum("bhw,bsw->bhs", q, view,
                   preferred_element_type=jnp.float32) * _f32(scale)
    live = (jnp.arange(view.shape[1], dtype=jnp.int32)[None, None]
            < seq_lens.astype(jnp.int32)[:, None, None])
    p = jax.nn.softmax(jnp.where(live, s, _NEG_INF), axis=-1)
    p = jnp.where(live, p, _f32(0))  # a lane of length 0 reads nothing
    return jnp.einsum("bhs,bsw->bhw", p.astype(q.dtype), view,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def mla_paged_attention(q, pool, block_tables, seq_lens, scale,
                        kernel="xla"):
    """Absorbed MLA decode attention: ``q`` [B, H, W] (one query row a
    slot, every head on the latent row's lanes) over a latent pool
    [num_blocks, block_size, W] addressed by ``block_tables`` [B, M];
    ``seq_lens`` [B] counts each slot's valid rows including the one just
    written. Returns [B, H, W]: p @ rows, of which the caller keeps the
    latent lanes. ``kernel`` as in :func:`paged_attention`; resolve it once
    per engine with :func:`select_mla_paged_kernel`."""
    if kernel == "xla":
        return mla_paged_attention_xla(q, pool, block_tables, seq_lens,
                                       scale)
    if kernel not in ("pallas", "interpret"):
        raise ValueError(
            f"unknown paged-attention kernel {kernel!r} "
            "(expected pallas | interpret | xla)")
    return _mla_paged_fused(q, pool, block_tables, seq_lens, float(scale),
                            interpret=(kernel == "interpret"))


def select_mla_paged_kernel(requested=None, *, row_width, block_size,
                            dtype):
    """:func:`select_paged_kernel` for the latent family: the same
    requests, environment variable, counters and loud fallbacks. What is
    refused on a TPU is a pool dtype without arithmetic in the body and a
    block over the VMEM limit (a row is whole 128-lane tiles by
    ``CacheSpec``'s padding). There is no mesh route: the engine refuses a
    mesh for this cache kind."""
    return select_paged_kernel(
        requested, head_dim=row_width, block_size=block_size, dtype=dtype,
        num_heads=1, family="mla_paged_attention")


# ============================ grouped matmul =================================
#
# The served expert layer's matmuls (nn/moe/dropless.py, ISSUE 38): ``rows``
# [M, K] sorted by expert, ``weights`` [G, K, N] as the layer keeps them,
# ``group_sizes`` [G] (data) -> float32 [M, N], row r times the weights of the
# group that owns it — `lax.ragged_dot`'s contract, for the regime a decode
# step is in: a few rows a group (2-8 in the serving cells), two orders under
# the chip's flops a byte, so the work is reading each HIT expert's weights
# once. XLA's grouped matmul took 1.3-2.8 x that time (PERF.md, PR 37).
#
# Grid (N / tn, V, K / tk), V = min(G, M) visits: visit v is the v-th expert
# that has a row (a scalar-prefetched list, the hit experts first), so an
# expert without a row is never named and its weights never move; the visits
# past the last hit expert re-name the block the last one had (no copy) and
# do nothing. The weights are a BlockSpec operand ``[tk, tn]`` cut out of the
# bank as it lies (``[tk, N]`` in the serving cells: whole rows of an expert,
# one contiguous piece; measured 1-2 % ahead of ``[K, tn]`` columns on the
# v5e, PERF.md PR 38): Pallas copies the tile of step i+1 while step i
# computes, whatever expert either belongs to. The rows stay whole in VMEM
# (one copy a call) and so does the ``[M, tn]`` column block of the result
# for a whole walk over the experts, accumulating over k in float32. A visit's rows are a window of the sorted rows at
# its group's offset: the start aligned down to a packed sublane tile (16),
# ``tm`` rows a chunk, as many chunks as the group needs, each row of a chunk
# kept only where the group owns it (the rest of the window belongs to the
# neighbours and is left as it was). Rows no group owns are never written:
# the caller zeroes them (`DroplessMoE._compute`).

# what the kernel's buffers may take of a core's 128 MiB of VMEM (the rows
# and a column block of the result, each twice as BlockSpec operands, and two
# weight tiles), and one weight tile, at most
_GROUPED_VMEM_BUDGET = 40 << 20
_GROUPED_TILE_BYTES = 4 << 20
_GROUPED_WORK_BYTES = 8 << 20
# rows of one chunk of a group's window: a whole number of packed sublane
# tiles; a group of 8 rows that starts mid-tile still fits one chunk
_GROUPED_ROW_TILE = 32
# the kernel's regime: at most this many rows a held group on average
# (M / G). Above it (a prompt's rows: 64-512 a group in the serving cells) a
# group is several chunks, each a full pass of the weights through the MXU,
# and XLA's grouped matmul is not bound by the bytes any more
_GROUPED_MAX_ROWS_A_GROUP = 32
_GROUPED_ALIGN = 16


def _grouped_vmem_bytes(M, K, N, tiles, dtype):
    """Bytes of VMEM the kernel's operands take at these tiles."""
    _, tk, tn = tiles
    item = jnp.dtype(dtype).itemsize
    return 2 * (M * K * item + tk * tn * item + M * tn * 4)


def _lane_tiles(n, most):
    """The largest divisor of ``n`` that is whole 128-lane tiles and at
    most ``most`` (0: none)."""
    return max((t for t in range(128, min(n, most) + 1, 128) if n % t == 0),
               default=0)


def _grouped_plan(M, K, N, G, dtype, compiled=True):
    """(route, (tm, tk, tn), why) for ``rows [M, K] x weights [G, K, N]``,
    from the static shapes alone. ``route``: "kernel"; "xla" — not the
    kernel's regime (more than ``_GROUPED_MAX_ROWS_A_GROUP`` rows a group):
    `lax.ragged_dot`, by design; "refused" — the regime is the kernel's but
    Mosaic could not tile the shape (``compiled`` only: the interpreter tiles
    anything), the caller falls back loudly. A weight tile is ``[tk, N]``,
    ``tk`` whole rows of an expert as they lie (one contiguous piece of the
    bank), the most within ``_GROUPED_TILE_BYTES``; N is cut too only where
    128 rows of it are over that."""
    dt = jnp.dtype(dtype)
    tm = min(_GROUPED_ROW_TILE, M)
    tn = _lane_tiles(N, _GROUPED_TILE_BYTES // (128 * dt.itemsize)) or N
    tk = _lane_tiles(K, _GROUPED_TILE_BYTES // (tn * dt.itemsize)) or K
    tiles = (tm, tk, tn)
    if M > _GROUPED_MAX_ROWS_A_GROUP * G:
        return "xla", tiles, (
            f"{M} rows over {G} groups is more than "
            f"{_GROUPED_MAX_ROWS_A_GROUP} a group: lax.ragged_dot's regime")
    if not compiled:
        return "kernel", tiles, "the interpreter tiles anything"
    why = None
    need = _grouped_vmem_bytes(M, K, N, tiles, dt)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        why = f"operand dtype {dt.name} not in (float32, bfloat16)"
    elif M % _GROUPED_ALIGN:
        why = (f"{M} sorted rows are not whole {_GROUPED_ALIGN}-row tiles: "
               "a group's window could not be aligned")
    elif K % 128 or N % 128:
        why = f"weights [{K}, {N}] are not whole 128-lane tiles both ways"
    elif need > _GROUPED_VMEM_BUDGET:
        why = (f"rows [{M}, {K}] {dt.name}, a float32 [{M}, {tn}] block of "
               f"the result and [{tk}, {tn}] weight tiles, each twice, are "
               f"{need / 2 ** 20:.0f} MiB of VMEM (budget "
               f"{_GROUPED_VMEM_BUDGET >> 20} MiB)")
    if why:
        return "refused", tiles, why
    return "kernel", tiles, "tileable"


def _grouped_matmul_kernel(eid_ref, off_ref, size_ref, hit_ref, x_ref, w_ref,
                           o_ref, *, tm, align, precision):
    """Program (n, v, k): the v-th hit expert's rows against tile (k, n) of
    its weights. See the section's header."""
    v = pl.program_id(1)
    k = pl.program_id(2)
    M = x_ref.shape[0]
    tk = w_ref.shape[0]
    whole_k = tk == x_ref.shape[1]  # static: all of K in one tile
    i32 = jnp.int32

    @pl.when(v < hit_ref[0])
    def _visit():
        e = eid_ref[v]
        start, size = off_ref[e], size_ref[e]
        first = start // i32(align) * i32(align)

        def chunk(c, _):
            lo = first + c * i32(tm)  # the chunk's own rows: lo .. lo+tm-1
            a = jnp.minimum(lo, i32(M - tm))  # the window stays inside M
            if align > 1:
                a = pl.multiple_of(a, align)
            if whole_k:
                x = x_ref[pl.ds(a, tm), :]
            else:
                x = x_ref[pl.ds(a, tm),
                          pl.ds(pl.multiple_of(k * i32(tk), 128), tk)]
            acc = jnp.dot(x, w_ref[...], precision=precision,
                          preferred_element_type=jnp.float32)
            row = a + jax.lax.broadcasted_iota(i32, (tm, 1), 0)
            mine = (row >= jnp.maximum(start, lo)) & (row < start + size)
            old = o_ref[pl.ds(a, tm), :]
            if not whole_k:  # K in tiles: the block accumulates
                acc = jnp.where(k == 0, acc, old + acc)
            o_ref[pl.ds(a, tm), :] = jnp.where(mine, acc, old)
            return _
        jax.lax.fori_loop(_i0(), pl.cdiv(start - first + size, i32(tm)),
                          chunk, _i0())


@functools.partial(jax.jit, static_argnames=("interpret", "tiles"))
def _grouped_matmul_fused(rows, weights, group_sizes, interpret, tiles):
    M, K = rows.shape
    G, _, N = weights.shape
    tm, tk, tn = tiles
    n_k, V = K // tk, min(G, M)
    i32 = jnp.int32
    sizes = group_sizes.astype(i32)
    hit = sizes > 0
    n_hit = hit.sum().astype(i32)
    # the hit experts first, in their order; past them the last one again
    order = jnp.argsort(jnp.logical_not(hit), stable=True).astype(i32)
    eid = order[jnp.minimum(jnp.arange(V, dtype=i32),
                            jnp.maximum(n_hit - 1, 0))]

    def w_map(n, v, k, eid, off, size, hit):
        # a visit past the last hit expert re-names that one's last tile
        return (eid[v], jnp.where(v < hit[0], k, i32(n_k - 1)), n)

    align = _GROUPED_ALIGN \
        if M % _GROUPED_ALIGN == 0 and tm % _GROUPED_ALIGN == 0 else 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(N // tn, V, n_k),
        in_specs=[pl.BlockSpec((M, K), lambda n, v, k, *_: (_i0(), _i0())),
                  pl.BlockSpec((None, tk, tn), w_map)],
        out_specs=pl.BlockSpec((M, tn), lambda n, v, k, *_: (_i0(), n)),
    )
    return pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, tm=tm, align=align,
                          precision=_dot_precision(rows.dtype)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_grouped_vmem_bytes(M, K, N, tiles, rows.dtype)
            + _GROUPED_WORK_BYTES),
        interpret=interpret,
        name=_kernel_name("grouped_matmul"),
    )(eid, jnp.cumsum(sizes) - sizes, sizes, n_hit[None], rows, weights)


def grouped_matmul(rows, weights, group_sizes, kernel="xla"):
    """``rows`` [M, K] sorted by group times ``weights`` [G, K, N], group g
    owning the ``group_sizes[g]`` rows after its predecessors' -> float32
    [M, N]; rows past the last group hold anything (`lax.ragged_dot` zeroes
    them, the kernel leaves them unwritten). ``kernel`` is what the engine's
    :func:`select_grouped_kernel` resolved to ("xla" / None: always
    `lax.ragged_dot`); under a fused kind the static shapes decide
    (:func:`_grouped_plan`): a decode step's few rows a group take the
    kernel, a prompt's rows stay with `lax.ragged_dot`."""
    if kernel not in (None, "xla", "pallas", "interpret"):
        raise ValueError(
            f"unknown grouped-matmul kernel {kernel!r} "
            "(expected pallas | interpret | xla)")
    if kernel in ("pallas", "interpret"):
        (M, K), (G, _, N) = rows.shape, weights.shape
        route, tiles, _ = _grouped_plan(M, K, N, G, rows.dtype,
                                        compiled=kernel == "pallas")
        if route == "kernel":
            return _grouped_matmul_fused(
                rows, weights, group_sizes,
                interpret=kernel == "interpret", tiles=tiles)
    # the precision is named: XLA:TPU's grouped matmul has no float32-
    # contract form for bf16 operands, which the package's global "highest"
    # would ask of it
    return jax.lax.ragged_dot(
        rows, weights, group_sizes=group_sizes.astype(jnp.int32),
        precision=_dot_precision(rows.dtype),
        preferred_element_type=jnp.float32)


def select_grouped_kernel(paged_kind, *, shapes, dtype):
    """Resolve the expert layers' grouped matmuls for one engine build, from
    what the engine's paged kernel resolved to (``paged_kernel=`` is the one
    request all three follow) and the decode step's shapes (``shapes``:
    ``(M, K, N, G)`` of every grouped matmul of a step). A fused kind stays
    itself when :func:`_grouped_plan` gives every shape to the kernel; a
    shape in `lax.ragged_dot`'s regime is "xla" by design; a shape Mosaic
    could not tile is "xla" loudly (``serving.kernel.fallbacks``, a
    ``kernel_fallback`` event). Returns ``(kind, reason)``."""
    if paged_kind == "xla":
        return "xla", "the engine's paged kernel resolved to xla"
    if not shapes:
        return "xla", "the decoder has no dropless expert layer"
    for M, K, N, G in shapes:
        route, _, why = _grouped_plan(M, K, N, G, dtype,
                                      compiled=paged_kind == "pallas")
        if route == "refused":
            _note_kernel_fallback("grouped_matmul", why, rows=M,
                                  weights=[G, K, N])
        if route != "kernel":
            return "xla", why
    return paged_kind, "follows the paged kernel: " + (
        "compiled" if paged_kind == "pallas" else "the interpreter")


# =========================== gated delta rule ================================
#
# A state layer's decode step (models/qwen3_next.py: gated DeltaNet): every
# value head h of a slot keeps S [dk, dv] float32 and, given its token's
# normalised key k and query q (of key head h // (Hv / Hk)), value v, decay
# exp(g) and strength beta,
#
#     S <- exp(g) S;  S <- S + k (beta (v - S^T k))^T;  o = S^T q.
#
# XLA makes several passes over the states for that (the decay, the read
# S^T k, the update, the read S^T q). `gated_delta_step` is one program per
# slot and group of value heads: each head's 64 KiB state is copied into
# VMEM once, updated there on the VPU (k and q arrive as columns, so every
# product is a lane broadcast and every read a sublane sum) and written back
# once, in place (the pool is aliased to the result). The key heads are
# picked by the index map: no repeat is formed. The decay and beta of each
# head ride scalar prefetch.

_DELTA_GROUP_HEADS = 8  # value heads a program: 8 x 64 KiB of state


def _delta_plan(Hk, Hv, dk, dv, compiled=True):
    """(route, value heads a program, why) for a step over states
    ``[.., Hv, dk, dv]`` with ``Hk`` key heads, from the shapes alone:
    "kernel", or "refused" where Mosaic could not tile it (``compiled``
    only: the interpreter tiles anything)."""
    r = Hv // Hk if Hk and Hv % Hk == 0 else 0
    hg = math.gcd(Hv, _DELTA_GROUP_HEADS)
    why = None
    if not r:
        why = f"{Hv} value heads are not a whole number a key head of {Hk}"
    elif hg % r:
        hg = r * max(1, _DELTA_GROUP_HEADS // r)
        if Hv % hg:
            why = (f"{Hv} value heads do not divide into groups of whole "
                   f"key heads ({r} value heads a key head)")
    if why is None and compiled and (dk % 8 or dv % 128):
        why = (f"a head's state [{dk}, {dv}] is not whole (8, 128) tiles")
    return ("refused" if why else "kernel"), hg, why or "tileable"


def _gated_delta_kernel(decay_ref, beta_ref, qk_ref, v_ref, s_ref, o_ref,
                        so_ref, *, hg, r):
    """Program (b, j): slot b's value heads j * hg .. (j + 1) * hg - 1. The
    group's key heads come as rows, queries then keys; one transpose makes
    them columns."""
    base = (pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)) * hg
    kh = hg // r
    cols = qk_ref[0, 0].T                              # [dk, rows]
    for h in range(hg):  # static: the group's heads one after another
        S = s_ref[0, h] * decay_ref[base + h]          # [dk, dv]
        qc = cols[:, h // r:h // r + 1]                # [dk, 1]
        kc = cols[:, kh + h // r:kh + h // r + 1]
        mem = jnp.sum(S * kc, axis=0, keepdims=True)   # [1, dv]: S^T k
        delta = (v_ref[0, h:h + 1, :] - mem) * beta_ref[base + h]
        S = S + kc * delta
        so_ref[0, h] = S
        o_ref[0, h:h + 1, :] = jnp.sum(S * qc, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gated_delta_fused(state, q, k, v, g, beta, interpret):
    B, Hv, dk, dv = state.shape
    Hk = q.shape[1]
    _, hg, _ = _delta_plan(Hk, Hv, dk, dv, compiled=not interpret)
    r = Hv // Hk
    n, kh = Hv // hg, hg // r
    rows = -(-2 * kh // 8) * 8  # a group's queries then keys, whole sublanes
    f32 = jnp.float32
    qk = jnp.concatenate([q.astype(f32).reshape(B, n, kh, dk),
                          k.astype(f32).reshape(B, n, kh, dk)], axis=2)
    qk = jnp.pad(qk, ((0, 0), (0, 0), (0, rows - 2 * kh), (0, 0)))

    def at(b, j, *_):
        return (b, j, _i0(), _i0())

    def heads(b, j, *_):
        return (b, j, _i0())

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n),
        in_specs=[pl.BlockSpec((1, 1, rows, dk), at),
                  pl.BlockSpec((1, hg, dv), heads),
                  pl.BlockSpec((1, hg, dk, dv), at)],
        out_specs=[pl.BlockSpec((1, hg, dv), heads),
                   pl.BlockSpec((1, hg, dk, dv), at)],
    )
    return pl.pallas_call(
        functools.partial(_gated_delta_kernel, hg=hg, r=r),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, Hv, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)),
        # the flat inputs are (decay, beta, qk, v, state): the state is
        # updated where it lies
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=_kernel_name("gated_delta_step"),
    )(jnp.exp(g.astype(f32)).reshape(-1), beta.astype(f32).reshape(-1), qk,
      v.astype(f32), state)


def gated_delta_step_xla(state, q, k, v, g, beta):
    """The same step in plain XLA (what `gated_delta_step` computes): value
    head j reads key head j // (Hv / Hk), every product and sum float32
    elementwise."""
    r = state.shape[1] // q.shape[1]
    f32 = jnp.float32
    qh = jnp.repeat(q.astype(f32), r, axis=1)[..., None]   # [B, Hv, dk, 1]
    kh = jnp.repeat(k.astype(f32), r, axis=1)[..., None]
    S = state * jnp.exp(g.astype(f32))[..., None, None]
    mem = jnp.sum(S * kh, axis=-2)                          # [B, Hv, dv]
    delta = (v.astype(f32) - mem) * beta.astype(f32)[..., None]
    S = S + kh * delta[..., None, :]
    return jnp.sum(S * qh, axis=-2), S


def gated_delta_step(state, q, k, v, g, beta, kernel="xla"):
    """One decode step of the gated delta rule for every slot: ``state``
    [B, Hv, dk, dv] float32 (the layer's state pool, updated in place on
    the kernel route), ``q``, ``k`` [B, Hk, dk] normalised (q scaled),
    ``v`` [B, Hv, dv], ``g`` and ``beta`` [B, Hv]. Returns (o [B, Hv, dv]
    float32, the new state). ``kernel`` is what the engine's
    :func:`select_gated_delta_kernel` resolved to ("xla" / None: plain
    XLA)."""
    if kernel not in (None, "xla", "pallas", "interpret"):
        raise ValueError(
            f"unknown gated-delta kernel {kernel!r} "
            "(expected pallas | interpret | xla)")
    if kernel in ("pallas", "interpret"):
        return _gated_delta_fused(state, q, k, v, g, beta,
                                  interpret=kernel == "interpret")
    return gated_delta_step_xla(state, q, k, v, g, beta)


def select_gated_delta_kernel(paged_kind, *, shapes):
    """Resolve the state layers' decode update for one engine build, from
    what the engine's paged kernel resolved to (the platform: "pallas" on
    the chip) and the decode step's shapes (``shapes``: ``(B, Hk, Hv, dk,
    dv)`` of every state layer). A fused kind stays itself when
    :func:`_delta_plan` tiles every shape; a shape Mosaic could not tile is
    "xla" loudly (``serving.kernel.fallbacks``, a ``kernel_fallback``
    event). Returns ``(kind, reason)``."""
    if not shapes:
        return "xla", "the decoder has no state layer"
    if paged_kind == "xla":
        return "xla", "the engine's paged kernel resolved to xla"
    for B, Hk, Hv, dk, dv in shapes:
        route, _, why = _delta_plan(Hk, Hv, dk, dv,
                                    compiled=paged_kind == "pallas")
        if route == "refused":
            _note_kernel_fallback("gated_delta_step", why,
                                  state=[B, Hv, dk, dv], key_heads=Hk)
            return "xla", why
    return paged_kind, "follows the paged kernel: " + (
        "compiled" if paged_kind == "pallas" else "the interpreter")


# =========================== fused softmax mask ==============================

def fused_softmax_mask(x, mask):
    """softmax(x + mask) fused (reference fused_softmax_mask_kernel.h)."""
    return jax.nn.softmax(x + mask, axis=-1)


def fused_softmax_mask_upper_triangle(x):
    """Causal softmax (reference fused_softmax_mask_upper_triangle_op.cu)."""
    T, S = x.shape[-2], x.shape[-1]
    cm = jnp.tril(jnp.ones((T, S), bool))
    return jax.nn.softmax(jnp.where(cm, x.astype(jnp.float32), -jnp.inf),
                          axis=-1).astype(x.dtype)
