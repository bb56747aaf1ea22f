"""The pieces the served decoders (`xing4`, `cohere2_moe`, `sdar_moe`,
`qwen3_next`) share: a bare weight, RMSNorm, the float32-accumulating
matmul, rotary positions on a head's halves, the walk over key blocks that
prefills grouped-query attention without its ``[H, T, S]`` scores, and the
head's logits against a weight as it is stored.

All of them run on the parameters' arrays (`Tensor._data`): the autograd
tape does not see them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn

_F32 = jnp.float32
# queries and keys a block of the walk: float32 scores of every head for one
# pair of blocks are heads x 512 x 512 x 4 B (134 MB at 128 heads)
_WALK_BLOCK = 512


def _param(layer, shape, init, dtype):
    return layer.create_parameter(list(shape), dtype=dtype,
                                  default_initializer=init)


class _Weight(nn.Layer):
    """A bare ``weight`` of shape [in, out] (or [n] for a norm's gain), so
    that names read `q_a_proj.weight` as the published checkpoints' do."""

    def __init__(self, shape, init, dtype):
        super().__init__()
        self.weight = _param(self, shape, init, dtype)


def _rms(x, w, eps):
    """RMSNorm with float32 statistics; returns float32."""
    x = x.astype(_F32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                             + _F32(eps)) * w.astype(_F32)


def _mm(x, w):
    return jnp.dot(x, w, preferred_element_type=_F32)


def _rope_halves(x, positions, theta):
    """x [B, T, H, D] rotated on the halves (i, i + D/2) at positions
    [B, T], in float32: inv_freq_i = theta^(-2i/D)."""
    D = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, D, 2, dtype=_F32) / D))
    ang = positions.astype(_F32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(_F32)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _walk_attention(q, k, v, q_pos, k_pos, k_valid, window, block):
    """Grouped-query attention of q [B, T, Hq, D] over k, v [B, S, Hkv, D]
    without the ``[Hq, T, S]`` scores: queries a block at a time, for each a
    walk over the key blocks it may see with a running softmax (float32
    maximum, sum and accumulator). ``q_pos`` [B, T] and ``k_pos`` [B, S]
    are positions, ascending alike (row i of q and of k is the same
    token's); a query sees keys with ``k_pos <= q_pos``, ``k_valid`` [B, S]
    and, with a ``window``, ``k_pos > q_pos - window`` — and the walk of a
    window layer starts at the block that holds the oldest of them.
    Returns [B, T, Hq, D] in q's dtype."""
    B, T, Hq, D = q.shape
    S, G = k.shape[1], k.shape[2]
    R, dt = Hq // G, q.dtype
    if T % block or S % block or T != S:
        block_q, block_k = T, S
    else:
        block_q = block_k = block
    n_k = S // block_k
    scale = _F32(D ** -0.5)
    q = q.reshape(B, T // block_q, block_q, G, R, D)
    i32 = jnp.int32

    def kblock(x, j):  # [B, S, ...] -> block j [B, block_k, ...]
        return jax.lax.dynamic_slice_in_dim(x, j * block_k, block_k, axis=1)

    def rows(args):
        i, qb, qp = args  # block index, [B, Q, G, R, D], [B, Q]
        # rows of q and k are the same tokens: query block i may see key
        # blocks up to its own, from the one `window` rows back
        hi = jnp.minimum(((i + 1) * block_q + block_k - 1) // block_k, n_k)
        lo = i32(0) if window is None else jnp.maximum(
            (i * block_q - i32(window)) // block_k, i32(0))

        def fold(j, carry):
            m, l, acc = carry
            kb, vb = kblock(k, j), kblock(v, j)
            kp, kv = kblock(k_pos, j), kblock(k_valid, j)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, kb,
                           preferred_element_type=_F32) * scale
            keep = (kp[:, None, :] <= qp[:, :, None]) & kv[:, None, :]
            if window is not None:
                keep = keep & (kp[:, None, :] > qp[:, :, None] - i32(window))
            s = jnp.where(keep[:, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            # a row that has seen no key yet keeps exp(-inf - -inf) out
            safe = jnp.where(jnp.isfinite(m_new), m_new, _F32(0))
            p = jnp.exp(s - safe[..., None])
            corr = jnp.exp(jnp.where(jnp.isfinite(m), m - safe, -jnp.inf))
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p.astype(dt), vb,
                preferred_element_type=_F32)
            return m_new, l, acc

        shape = (B, G, R, block_q)
        m, l, acc = jax.lax.fori_loop(
            lo, hi, fold, (jnp.full(shape, -jnp.inf, _F32),
                           jnp.zeros(shape, _F32),
                           jnp.zeros(shape + (D,), _F32)))
        out = acc / jnp.maximum(l, _F32(1e-30))[..., None]
        return jnp.moveaxis(out, 3, 1).astype(dt)  # [B, Q, G, R, D]

    n_q = T // block_q
    out = jax.lax.map(rows, (jnp.arange(n_q, dtype=i32),
                             jnp.moveaxis(q, 1, 0),
                             jnp.moveaxis(q_pos.reshape(B, n_q, block_q),
                                          1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, Hq, D)


def _head_logits(scale=1):
    """The head's logits: bf16 x bf16 with float32 accumulation against the
    weight as it is stored, ``[V, d]`` (a tied embedding table or an untied
    head: no float32 copy, no transpose), times ``scale`` (`logit_scale`)."""
    def logits(hidden, w):
        out = jax.lax.dot_general(hidden, w, (((1,), (1,)), ((), ())),
                                  preferred_element_type=_F32)
        return out if scale == 1 else out * _F32(scale)
    return logits
