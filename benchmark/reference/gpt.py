"""Plain reference for the GPT-2/GPT-3 decoder the configurations share:
the forward pass in straightforward jax.numpy, float32, matmuls at
"highest" precision, no kernel, no cache, no batching tricks. It follows
Radford et al. 2019 (GPT-2: pre-LayerNorm blocks, learned positions, tanh
GELU, head tied to the token embedding); Brown et al. 2020 use the same
block. Weights come in under the program's state_dict names, in whatever
dtype they are served in, and are upcast here.

    logits = forward(weights, n_layer, n_head, ids, at)

ids [T] token ids; `at` [K] positions; returns float32 [K, vocab]: the
next-token logits after each of those positions."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_EPS = 1e-5


def _f32(x):
    return x.astype(jnp.float32)


def _ln(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + _EPS) * _f32(w) + _f32(b)


def _lin(x, w, b):
    return jnp.matmul(x, _f32(w), precision=_HI) + _f32(b)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def hidden(weights, n_layer, n_head, ids):
    """Final-LayerNorm hidden states [T, d] of one sequence."""
    T = ids.shape[0]
    x = _f32(weights["embeddings.word_embeddings.weight"])[ids] \
        + _f32(weights["embeddings.position_embeddings.weight"])[:T]
    d = x.shape[-1]
    dh = d // n_head
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(n_layer):
        p = f"blocks.{i}."
        h = _ln(x, weights[p + "ln1.weight"], weights[p + "ln1.bias"])
        qkv = _lin(h, weights[p + "attn.qkv_proj.weight"],
                   weights[p + "attn.qkv_proj.bias"])
        q, k, v = (qkv.reshape(T, 3, n_head, dh)[:, j] for j in range(3))
        s = jnp.einsum("thd,shd->hts", q, k, precision=_HI) / jnp.sqrt(
            jnp.float32(dh))
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v,
                       precision=_HI).reshape(T, d)
        x = x + _lin(a, weights[p + "attn.out_proj.weight"],
                     weights[p + "attn.out_proj.bias"])
        h = _ln(x, weights[p + "ln2.weight"], weights[p + "ln2.bias"])
        h = _gelu_tanh(_lin(h, weights[p + "mlp.fc1.weight"],
                            weights[p + "mlp.fc1.bias"]))
        x = x + _lin(h, weights[p + "mlp.fc2.weight"],
                     weights[p + "mlp.fc2.bias"])
    return _ln(x, weights["ln_f.weight"], weights["ln_f.bias"])


def forward(weights, n_layer, n_head, ids, at):
    h = hidden(weights, n_layer, n_head, ids)[at]
    return jnp.matmul(
        h, _f32(weights["embeddings.word_embeddings.weight"]).T,
        precision=_HI)
