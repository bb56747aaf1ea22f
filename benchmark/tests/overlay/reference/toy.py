"""The toy family's own plain reference: a pre-LayerNorm decoder with learned
positions, tanh GELU and a tied head, float32, one sequence, no cache."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _norm(x, p, w):
    x = x - x.mean(-1, keepdims=True)
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-5)
    return x * w[p + ".weight"].astype(jnp.float32) \
        + w[p + ".bias"].astype(jnp.float32)


def _dense(x, p, w):
    return jnp.matmul(x, w[p + ".weight"].astype(jnp.float32),
                      precision=HI) + w[p + ".bias"].astype(jnp.float32)


def logits(w, layers, heads, ids, at):
    """float32 [K, vocab]: next-token logits after the positions `at`."""
    T = ids.shape[0]
    emb = w["embeddings.word_embeddings.weight"].astype(jnp.float32)
    x = emb[ids] + w["embeddings.position_embeddings.weight"].astype(
        jnp.float32)[:T]
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    for i in range(layers):
        b = f"blocks.{i}"
        q, k, v = jnp.split(_dense(_norm(x, b + ".ln1", w),
                                   b + ".attn.qkv_proj", w)
                            .reshape(T, 3, heads, -1), 3, axis=1)
        q, k, v = q[:, 0], k[:, 0], v[:, 0]
        s = jnp.einsum("thd,shd->hts", q, k, precision=HI) \
            / jnp.sqrt(jnp.float32(q.shape[-1]))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        a = jnp.einsum("hts,shd->thd", p, v, precision=HI).reshape(T, -1)
        x = x + _dense(a, b + ".attn.out_proj", w)
        h = _dense(_norm(x, b + ".ln2", w), b + ".mlp.fc1", w)
        h = 0.5 * h * (1 + jnp.tanh(0.7978845608028654
                                    * (h + 0.044715 * h ** 3)))
        x = x + _dense(h, b + ".mlp.fc2", w)
    return jnp.matmul(_norm(x, "ln_f", w)[at], emb.T, precision=HI)
