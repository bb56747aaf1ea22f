"""Plain reference for the Cohere2-MoE decoder
(configs/command-a-plus-05-2026.json): the forward pass in straightforward
jax.numpy, float32, matmuls at "highest" precision, no kernel, no cache, no
batching. Written from the published config's keys and the equations of the
layers they name; it imports nothing of the program.

With d = hidden_size, Hq / Hkv query / key-value heads of head_dim, W =
sliding_window, E experts, k a token:

* LN(h) = (h - mean h) / sqrt(var h + layer_norm_eps) * g: a weight, no bias;
* block (`use_parallel_block`): u = LN_l(h); h' = h + Attn_l(u) + Routed_l(u)
  + Shared_l(u); one norm a layer;
* attention: q = u Wq (Hq heads), k = u Wk, v = u Wv (Hkv heads), no bias, no
  q/k norm; query head h reads key/value head h // (Hq / Hkv); scores
  q.k / sqrt(head_dim), softmax in float32. `sliding_attention` layers:
  rotary over all head_dim dims on adjacent pairs (2i, 2i+1)
  (`position_embedding_type` rope_gptj, `rotary_pct` 1), inv_freq_i =
  rope_theta^(-2i/head_dim), no scaling; position t sees keys t - W + 1 .. t.
  `full_attention` layers: no positional signal, causal over every earlier
  key;
* routed experts: s = sigmoid(u Wr) over all E, T = the k largest, w_e = s_e /
  sum_{j in T} s_j (`norm_topk_prob`), each expert silu(u Wg) * (u Wu) -> Wd
  of width intermediate_size;
* shared experts: `num_shared_experts` experts of the same form, every token
  through each, their outputs averaged
  (`shared_expert_combination_strategy` average) and added;
* embedding E[id], no multiplier; a last LN; logits = logit_scale LN(h) E^T,
  the embedding tied.

Departures from the published description, and what the config does not
settle (`assumed` in the configuration file): the width of one expert is
`intermediate_size` (the config has no key of its own for it) and a shared
expert is as wide; the four shared experts are separate experts whose mean
is added to the routed sum; no selection bias and no scaling factor (no key
for either); the window holds W keys with the query's own;
`first_k_dense_replace` 0, so the `prefix_dense_*` keys describe layers this
model does not have; the checkpoint's vision tower has no key in this config
and is not here.

Weights come in under the program's state_dict names, in whatever dtype they
are served in, and are upcast here, an expert at a time. The program keeps
the shared experts side by side in one matrix (`shared.gate_proj.weight` [d,
n F]: expert j is columns j F .. (j + 1) F, and rows of `down_proj`); here
they are taken apart again. `experts_held = (lo, hi)`: the weights hold those
routed experts only, the router still scores all E, and the result is their
part of the layer plus the shared experts — one chip's share.

    logits = forward(weights, cfg, ids, at)

ids [T] token ids; `at` [K] positions; returns float32 [K, vocab]: the
next-token logits after each of those positions. `layer`, `embed` and `head`
are the same computation a layer at a time, for a caller that cannot hold it
all at once (families/cohere2_moe.py at the published widths); `q_block`
computes attention over that many queries at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _f32(x):
    return x.astype(jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=_HI)


def layer_norm(x, w, eps):
    x = x - x.mean(-1, keepdims=True)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def rope(x, pos, theta):
    """x [T, H, D], adjacent pairs rotated by pos * theta^(-2i/D)."""
    D = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = _f32(pos)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(w, p, cfg, u, pos, sliding, q_block=None):
    """Grouped-query attention of layer prefix `p` over u [T, d]; with
    `q_block` the queries are projected, scored and projected back that many
    at a time (the keys and values of all T stay)."""
    T = u.shape[0]
    Hq, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    D, W = int(cfg["head_dim"]), int(cfg["sliding_window"])
    k = _mm(u, w[p + "k_proj.weight"]).reshape(T, Hkv, D)
    v = _mm(u, w[p + "v_proj.weight"]).reshape(T, Hkv, D)
    if sliding:
        k = rope(k, pos, cfg["rope_theta"])

    def rows(ub, pb):  # [Q, d], [Q]
        q = _mm(ub, w[p + "q_proj.weight"]).reshape(-1, Hq, D)
        if sliding:
            q = rope(q, pb, cfg["rope_theta"])
        # query head h reads key/value head h // (Hq / Hkv)
        q = q.reshape(-1, Hkv, Hq // Hkv, D)
        s = jnp.einsum("qgrd,kgd->grqk", q, k, precision=_HI) * D ** -0.5
        keep = pos[None, :] <= pb[:, None]
        if sliding:
            keep = keep & (pos[None, :] > pb[:, None] - W)
        a = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("grqk,kgd->qgrd", a, v, precision=_HI)
        return _mm(o.reshape(-1, Hq * D), w[p + "o_proj.weight"])

    if not q_block or T % q_block:
        return rows(u, pos)
    return jax.lax.map(lambda a: rows(*a), (
        u.reshape(T // q_block, q_block, -1),
        pos.reshape(T // q_block, q_block))).reshape(T, -1)


def ffn(u, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(u, w_gate)) * _mm(u, w_up), w_down)


def route(w, p, cfg, u, experts_held=None):
    """(chosen [T, k] expert ids, their weights [T, k], margin [T]) of every
    token. The margin is how far the choice is from another one THAT
    INVOLVES A HELD EXPERT: the smallest score difference between a chosen
    and an unchosen expert of which one at least is held. (A swap between
    two experts held elsewhere moves this chip's result through the
    normalisation alone, continuously.) The choice is a discontinuous
    function of `u`; a caller that compares a lower-precision computation
    with this one reads from the margin where the two may rightly choose
    differently."""
    E, k = int(cfg["num_experts"]), int(cfg["num_experts_per_tok"])
    lo, hi = experts_held or (0, E)
    s = jax.nn.sigmoid(_mm(u, w[p + "router.weight"]))
    top, chosen = jax.lax.top_k(s, k)
    held = (jnp.arange(E) >= lo) & (jnp.arange(E) < hi)
    is_in = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(True)
    inf = jnp.inf
    best_out = jnp.where(~is_in, s, -inf).max(-1)
    best_out_held = jnp.where(~is_in & held, s, -inf).max(-1)
    worst_in_held = jnp.where(is_in & held, s, inf).min(-1)
    margin = jnp.minimum(worst_in_held - best_out, top[:, -1] - best_out_held)
    weight = top
    if cfg["norm_topk_prob"]:
        weight = top / top.sum(-1, keepdims=True)
    return chosen, weight, margin


def moe(w, p, cfg, u, experts_held=None, margins=None):
    """The held routed experts by a plain loop with a 0/1 mask, plus the
    mean of the shared experts: no capacity, no drop. A list given as
    `margins` receives the tokens' routing margins (see `route`)."""
    E, F = int(cfg["num_experts"]), int(cfg["intermediate_size"])
    n = int(cfg["num_shared_experts"])
    lo, hi = experts_held or (0, E)
    chosen, weight, margin = route(w, p, cfg, u, experts_held)
    if margins is not None:
        margins.append(margin)
    gate_up, down = w[p + "experts.gate_up"], w[p + "experts.down"]

    def one(e, y):  # expert lo + e is row e of the held stack
        mask = (chosen == lo + e).astype(jnp.float32)  # [T, k] of 0/1
        g = jax.lax.dynamic_index_in_dim(gate_up, e, keepdims=False)
        d = jax.lax.dynamic_index_in_dim(down, e, keepdims=False)
        return y + (mask * weight).sum(-1, keepdims=True) \
            * ffn(u, g[:, :F], g[:, F:], d)

    y = jax.lax.fori_loop(0, hi - lo, one, jnp.zeros_like(u))
    sg, su, sd = (w[p + f"shared.{m}_proj.weight"]
                  for m in ("gate", "up", "down"))
    shared = sum(ffn(u, sg[:, j * F:(j + 1) * F], su[:, j * F:(j + 1) * F],
                     sd[j * F:(j + 1) * F]) for j in range(n))
    return y + shared / n


def embed(w, cfg, ids):
    return _f32(w["embed_tokens.weight"][ids])


def layer(w, i, cfg, h, pos, q_block=None, experts_held=None, margins=None):
    """Decoder layer i over h [T, d]; `w` needs only the names under
    ``layers.<i>.``. `margins`: see `moe`."""
    p = f"layers.{i}."
    sliding = cfg["layer_types"][i] == "sliding_attention"
    u = layer_norm(h, w[p + "input_layernorm.weight"],
                   float(cfg["layer_norm_eps"]))
    return h + attention(w, p + "self_attn.", cfg, u, pos, sliding, q_block) \
        + moe(w, p + "mlp.", cfg, u, experts_held, margins)


def head(w, cfg, h, at, v_block=None):
    """The last LayerNorm and the tied head at positions `at` (over
    `v_block` rows of the vocabulary at a time when given)."""
    x = layer_norm(h[at], w["norm.weight"], float(cfg["layer_norm_eps"]))
    W = w["embed_tokens.weight"]  # [V, d], tied
    V, scale = W.shape[0], float(cfg["logit_scale"])
    if not v_block or V % v_block:
        return scale * jnp.matmul(x, _f32(W).T, precision=_HI)
    out = jax.lax.map(lambda wb: jnp.matmul(x, _f32(wb).T, precision=_HI),
                      W.reshape(V // v_block, v_block, -1))
    return scale * jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


def forward(w, cfg, ids, at, experts_held=None):
    h = embed(w, cfg, ids)
    pos = jnp.arange(ids.shape[0])
    for i in range(int(cfg["num_hidden_layers"])):
        h = layer(w, i, cfg, h, pos, experts_held=experts_held)
    return head(w, cfg, h, at)
