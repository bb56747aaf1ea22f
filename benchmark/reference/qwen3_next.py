"""Plain reference for the Qwen3-Next decoder
(configs/qwen3-next-80b-a3b-instruct.json): the forward pass in
straightforward jax.numpy, float32, matmuls at "highest" precision, no
kernel, no cache, no batching. Written from the published config's keys and
the equations of the layers they name; it imports nothing of the program.

With d = hidden_size, N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w) (the
family's RMSNorm, eps rms_norm_eps):

* layer i is `full_attention` when (i + 1) % full_attention_interval == 0,
  else `linear_attention`; h' = h + Mixer_i(N(h)); h'' = h' + MoE_i(N(h'));
* linear attention (Gated DeltaNet; Hk = linear_num_key_heads, Hv =
  linear_num_value_heads, dk, dv their head dims, r = Hv / Hk): x =
  u W_qkvz read per key head g as [q_g (dk) | k_g (dk) | v (r dv) | z (r
  dv)] and u W_ba per key head as [b (r) | a (r)] (the family's
  `fix_query_key_value_ordering`); the channels [q_0 .. q_Hk-1 | k_0 .. |
  v_0 .. v_Hv-1] through a causal depthwise convolution, y_t = sum_j
  w[c, j] x_{t - K + 1 + j} (K = linear_conv_kernel_dim, no bias, zeros
  before the first token), then SiLU; q, k L2-normalised per head (eps
  1e-6), q times dk^-1/2; value head j reads key head j // r; beta =
  sigmoid(b), g = -exp(A_log) softplus(a + dt_bias); per value head, TOKEN
  BY TOKEN from S = 0: S <- exp(g_t) S; S <- S + k_t (beta_t (v_t - S^T
  k_t))^T; o_t = S^T q_t; then o / sqrt(mean(o^2) + eps) * w * silu(z) a
  head (the gated norm: its own weight, not 1 + w) and W_out;
* full attention (Hq heads over Hkv of head_dim D): u W_q read per head as
  [q (D) | gate (D)]; q, k through N over a head's D dims (one weight each,
  shared by the heads); rotary on the first R = D partial_rotary_factor
  dims on the halves (i, i + R/2), inv_freq_i = rope_theta^(-2i/R), the
  other dims as they are; query head h reads key/value head h // (Hq /
  Hkv); scores q.k / sqrt(D), causal softmax; the output times
  sigmoid(gate), then W_o;
* experts: p = softmax(u W_r) over all E = num_experts, T = the k largest,
  w_e = p_e / sum_{j in T} p_j (`norm_topk_prob`), each expert silu(u Wg) *
  (u Wu) -> Wd of width moe_intermediate_size; one shared expert of
  shared_expert_intermediate_size times sigmoid(u w_sg), added;
* embedding E[id]; a last N; logits = N(h) W_head^T, untied.

What the config does not settle (`assumed` in the configuration file): the
layouts above; the norms' (1 + w) form with w drawn as 0 and the gated
norm's weight drawn as 1; A_log = log U(0, 16) and dt_bias = 1, both kept in
float32; the checkpoint's multi-token prediction module, which has no key in
the config, is not here.

Weights come in under the program's state_dict names, in whatever dtype they
are served in, and are upcast here, an expert at a time. `experts_held =
(lo, hi)`: the weights hold those routed experts only, the router still
scores all E, and the result is their part of the layer plus the shared
expert — one chip's share.

    logits = forward(weights, cfg, ids, at)

ids [T] token ids; `at` [K] positions; returns float32 [K, vocab]: the
next-token logits after each of those positions. `layer`, `embed` and `head`
are the same computation a layer at a time, for a caller that cannot hold it
all at once (families/qwen3_next.py at the published widths); `q_block`
computes full attention over that many queries at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _f32(x):
    return x.astype(jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=_HI)


def norm(x, w, eps):
    """The family's RMSNorm: x / rms(x) * (1 + w)."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * (1.0 + _f32(w))


def l2norm(x, eps=1e-6):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + eps)


def layer_kinds(cfg):
    n = int(cfg["full_attention_interval"])
    return ["full_attention" if (i + 1) % n == 0 else "linear_attention"
            for i in range(int(cfg["num_hidden_layers"]))]


def rope_partial(x, pos, cfg):
    """x [T, H, D]: the first R dims rotated on their halves."""
    D = x.shape[-1]
    R = int(D * float(cfg["partial_rotary_factor"]))
    inv = 1.0 / (float(cfg["rope_theta"])
                 ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R))
    ang = _f32(pos)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :R // 2], x[..., R // 2:R]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., R:]],
                           axis=-1)


def value_heads(x, r):
    """[T, Hk, d] -> [T, Hk r, d]: value head j reads key head j // r
    (repeat_interleave)."""
    return jnp.repeat(x, r, axis=1)


def delta_token(S, q, k, v, g, beta):
    """One token of the gated delta rule, every value head: S [Hv, dk, dv]
    -> (S', o [Hv, dv])."""
    S = S * jnp.exp(g)[:, None, None]
    mem = (S * k[:, :, None]).sum(1)
    S = S + k[:, :, None] * (beta[:, None] * (v - mem))[:, None, :]
    return S, (S * q[:, :, None]).sum(1)


def output_gate(o, gate):
    """A full layer's attention output times sigmoid(gate)."""
    return o * jax.nn.sigmoid(gate)


def shared_gate(u, w_sg):
    """The shared expert's factor a token: sigmoid(u w_sg) [T, 1]."""
    return jax.nn.sigmoid(_mm(u, w_sg))


def linear_attention(w, p, cfg, u):
    """Gated DeltaNet of layer prefix `p` over u [T, d], the recurrence a
    token at a time."""
    T = u.shape[0]
    Hk, Hv = int(cfg["linear_num_key_heads"]), int(
        cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(
        cfg["linear_value_head_dim"])
    K, r = int(cfg["linear_conv_kernel_dim"]), Hv // Hk
    eps = float(cfg["rms_norm_eps"])
    x = _mm(u, w[p + "in_proj_qkvz.weight"]).reshape(T, Hk,
                                                    2 * dk + 2 * r * dv)
    q, k = x[..., :dk], x[..., dk:2 * dk]
    v = x[..., 2 * dk:2 * dk + r * dv]
    z = x[..., 2 * dk + r * dv:].reshape(T, Hv, dv)
    ba = _mm(u, w[p + "in_proj_ba.weight"]).reshape(T, Hk, 2 * r)
    b, a = ba[..., :r].reshape(T, Hv), ba[..., r:].reshape(T, Hv)
    mixed = jnp.concatenate([q.reshape(T, -1), k.reshape(T, -1),
                             v.reshape(T, -1)], axis=-1)
    cw = _f32(w[p + "conv1d.weight"])  # [channels, K]
    xp = jnp.pad(mixed, ((K - 1, 0), (0, 0)))
    y = jax.nn.silu(sum(xp[j:j + T] * cw[:, j] for j in range(K)))
    q = l2norm(y[:, :Hk * dk].reshape(T, Hk, dk)) * dk ** -0.5
    k = l2norm(y[:, Hk * dk:2 * Hk * dk].reshape(T, Hk, dk))
    v = y[:, 2 * Hk * dk:].reshape(T, Hv, dv)
    q, k = value_heads(q, r), value_heads(k, r)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(_f32(w[p + "A_log"])) * jax.nn.softplus(
        a + _f32(w[p + "dt_bias"]))
    _, o = jax.lax.scan(lambda S, xs: delta_token(S, *xs),
                        jnp.zeros((Hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) \
        * _f32(w[p + "norm.weight"]) * jax.nn.silu(z)
    return _mm(o.reshape(T, -1), w[p + "out_proj.weight"])


def full_attention(w, p, cfg, u, pos, q_block=None):
    """Gated grouped-query attention of layer prefix `p` over u [T, d];
    with `q_block` the queries are projected, scored and projected back
    that many at a time (the keys and values of all T stay)."""
    T = u.shape[0]
    Hq, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    D, eps = int(cfg["head_dim"]), float(cfg["rms_norm_eps"])
    k = norm(_mm(u, w[p + "k_proj.weight"]).reshape(T, Hkv, D),
             w[p + "k_norm.weight"], eps)
    k = rope_partial(k, pos, cfg)
    v = _mm(u, w[p + "v_proj.weight"]).reshape(T, Hkv, D)

    def rows(ub, pb):  # [Q, d], [Q]
        qg = _mm(ub, w[p + "q_proj.weight"]).reshape(-1, Hq, 2 * D)
        q = rope_partial(norm(qg[..., :D], w[p + "q_norm.weight"], eps), pb,
                         cfg)
        # query head h reads key/value head h // (Hq / Hkv)
        q = q.reshape(-1, Hkv, Hq // Hkv, D)
        s = jnp.einsum("qgrd,kgd->grqk", q, k, precision=_HI) * D ** -0.5
        keep = pos[None, :] <= pb[:, None]
        a = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("grqk,kgd->qgrd", a, v, precision=_HI)
        o = output_gate(o.reshape(-1, Hq * D),
                        qg[..., D:].reshape(-1, Hq * D))
        return _mm(o, w[p + "o_proj.weight"])

    if not q_block or T % q_block:
        return rows(u, pos)
    return jax.lax.map(lambda a: rows(*a), (
        u.reshape(T // q_block, q_block, -1),
        pos.reshape(T // q_block, q_block))).reshape(T, -1)


def ffn(u, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(u, w_gate)) * _mm(u, w_up), w_down)


def route(w, p, cfg, u, experts_held=None):
    """(chosen [T, k] expert ids, their weights [T, k], margin [T]) of every
    token. The margin is how far the choice is, in probability, from
    another one THAT INVOLVES A HELD EXPERT: the smallest difference between
    a chosen and an unchosen expert of which one at least is held. The
    choice is a discontinuous function of `u`; a caller that compares a
    lower-precision computation with this one reads from the margin where
    the two may rightly choose differently."""
    E, k = int(cfg["num_experts"]), int(cfg["num_experts_per_tok"])
    lo, hi = experts_held or (0, E)
    s = jax.nn.softmax(_mm(u, w[p + "router.weight"]), axis=-1)
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
    gated shared expert: no capacity, no drop. A list given as `margins`
    receives the tokens' routing margins (see `route`)."""
    E, F = int(cfg["num_experts"]), int(cfg["moe_intermediate_size"])
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
    shared = ffn(u, w[p + "shared.gate_proj.weight"],
                 w[p + "shared.up_proj.weight"],
                 w[p + "shared.down_proj.weight"])
    return y + shared * shared_gate(u, w[p + "shared_gate.weight"])


def embed(w, cfg, ids):
    return _f32(w["embed_tokens.weight"][ids])


def layer(w, i, cfg, h, pos, q_block=None, experts_held=None, margins=None):
    """Decoder layer i over h [T, d]; `w` needs only the names under
    ``layers.<i>.``. `margins`: see `moe`."""
    p = f"layers.{i}."
    eps = float(cfg["rms_norm_eps"])
    u = norm(h, w[p + "input_layernorm.weight"], eps)
    if layer_kinds(cfg)[i] == "linear_attention":
        h = h + linear_attention(w, p + "linear_attn.", cfg, u)
    else:
        h = h + full_attention(w, p + "self_attn.", cfg, u, pos, q_block)
    u = norm(h, w[p + "post_attention_layernorm.weight"], eps)
    return h + moe(w, p + "mlp.", cfg, u, experts_held, margins)


def head(w, cfg, h, at, v_block=None):
    """The last norm and the untied head at positions `at` (over `v_block`
    rows of the vocabulary at a time when given)."""
    x = norm(h[at], w["norm.weight"], float(cfg["rms_norm_eps"]))
    W = w["lm_head.weight"]  # [V, d]
    V = W.shape[0]
    if not v_block or V % v_block:
        return jnp.matmul(x, _f32(W).T, precision=_HI)
    out = jax.lax.map(lambda wb: jnp.matmul(x, _f32(wb).T, precision=_HI),
                      W.reshape(V // v_block, v_block, -1))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)


def forward(w, cfg, ids, at, experts_held=None):
    h = embed(w, cfg, ids)
    pos = jnp.arange(ids.shape[0])
    for i in range(int(cfg["num_hidden_layers"])):
        h = layer(w, i, cfg, h, pos, experts_held=experts_held)
    return head(w, cfg, h, at)
