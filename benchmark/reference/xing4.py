"""Plain reference for the Xing4.0 decoder (configs/xing4.0-29b-a4b.json):
the forward pass in straightforward jax.numpy, float32, matmuls at "highest"
precision, no kernel, no cache. Written from the published config's keys and
the equations of the layers they name; it imports nothing of the program.

* attention: multi-head latent attention as DeepSeek-V2/V3 publish it
  (arXiv:2405.04434 section 2.1): a low-rank query (`q_lora_rank`), one
  latent row a token (`kv_lora_rank`) from which every head's no-position
  key and value are expanded, one rotated key (`qk_rope_head_dim`) shared by
  all heads, YaRN-scaled rotary positions (arXiv:2309.00071) with the
  softmax scale multiplied by mscale^2;
* expert layers: DeepSeek-V3's router (arXiv:2412.19437 section 2.1.2):
  sigmoid scores, a selection bias used for choosing only, the chosen
  scores normalised and multiplied by `routed_scaling_factor`; SwiGLU
  experts, one shared expert, no capacity and no drop; `n_group` =
  `topk_group` = 1, so there is no group limit to apply;
* residual: manifold-constrained hyper-connections (arXiv:2512.24880):
  `hc_mult` residual streams, mixed by a matrix made doubly stochastic by
  `hc_sinkhorn_iters` Sinkhorn-Knopp rounds.

What the config does not settle is `assumed` in the configuration file:
hyper-connections on both sublayers of every layer; streams made by copying
the embedding and read out by summing; the clamp before `exp`, `hc_eps` in
the stream norm and in both Sinkhorn denominators, columns before rows;
RoPE on adjacent pairs; the shared expert's width = `moe_intermediate_size`
x `n_shared_experts`. Multi-token prediction is not part of a served
forward pass and is not here.

Weights come in under the program's state_dict names, in whatever dtype they
are served in, and are upcast here, an expert at a time. `experts_held =
(lo, hi)` computes those routed experts' part of an expert layer (the
router still scores all of them) plus the shared expert.

    logits = forward(weights, cfg, ids, at)

ids [T] token ids; `at` [K] positions; returns float32 [K, vocab]: the
next-token logits after each of those positions. `layer`, `embed` and
`head` are the same computation a layer at a time, for a caller that cannot
hold it all at once (families/xing4.py at the published widths);
`q_block` computes attention over that many queries at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _f32(x):
    return x.astype(jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=_HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


# ------------------------------------------------------------------ rotary --
def yarn_inv_freq(cfg):
    """The YaRN frequencies of the rotated dims: as they are (extrapolated)
    for the fast dims below the correction range, divided by `factor`
    (interpolated) for the slow ones above it, a linear ramp between."""
    rs = cfg["rope_scaling"]
    dim, base = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / float(rs["factor"])
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    m = _yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return (int(cfg["qk_nope_head_dim"])
            + int(cfg["qk_rope_head_dim"])) ** -0.5 * m * m


def rope(x, pos, cfg):
    """x [..., T, (H,) dr] rotated by adjacent pairs at positions pos [T];
    the cos/sin scale mscale / mscale_all_dim is applied as published."""
    rs = cfg["rope_scaling"]
    f = float(rs["factor"])
    amp = _yarn_mscale(f, float(rs["mscale"])) \
        / _yarn_mscale(f, float(rs["mscale_all_dim"]))
    ang = _f32(pos)[:, None] * yarn_inv_freq(cfg)[None]  # [T, dr/2]
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    if x.ndim == 3:  # [T, H, dr]
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


# --------------------------------------------------------------- attention --
def mla(w, p, cfg, u, pos, q_block=None):
    """Latent attention of one sequence, expanded form: u [T, d] -> [T, d],
    causal."""
    T = u.shape[0]
    H = int(cfg["num_attention_heads"])
    dn, dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv, rkv = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    eps = float(cfg["rms_norm_eps"])
    c_q = rms_norm(_mm(u, w[p + "q_a_proj.weight"]),
                   w[p + "q_a_layernorm.weight"], eps)
    q = _mm(c_q, w[p + "q_b_proj.weight"]).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, cfg)
    kv = _mm(u, w[p + "kv_a_proj.weight"])
    c_kv = rms_norm(kv[:, :rkv], w[p + "kv_a_layernorm.weight"], eps)
    k_rope = rope(kv[:, rkv:], pos, cfg)  # one for all heads
    kvb = _mm(c_kv, w[p + "kv_b_proj.weight"]).reshape(T, H, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    scale = softmax_scale(cfg)

    def rows(args):
        qn, qr, first = args  # [Q, H, dn], [Q, H, dr], first query's index
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=_HI)
             + jnp.einsum("qhd,kd->hqk", qr, k_rope, precision=_HI)) * scale
        qi = first + jnp.arange(qn.shape[0])
        s = jnp.where(jnp.arange(T)[None, None] <= qi[None, :, None], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=_HI)

    Q = q_block if q_block and T % q_block == 0 else T
    o = jax.lax.map(rows, (q_nope.reshape(T // Q, Q, H, dn),
                           q_rope.reshape(T // Q, Q, H, dr),
                           jnp.arange(0, T, Q)))
    return _mm(o.reshape(T, H * dv), w[p + "o_proj.weight"])


# --------------------------------------------------------------------- FFN --
def ffn(u, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(u, w_gate)) * _mm(u, w_up), w_down)


def route(w, p, cfg, u):
    """(chosen [T, k] expert ids, their weights [T, k], margin [T]) of
    every token. The margin is how far the choice is from another one: the
    last chosen expert's biased score less the best one's left out. The
    choice is a discontinuous function of `u`; a caller that compares a
    lower-precision computation with this one reads from the margin where
    the two may rightly choose differently."""
    k = int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(_mm(u, w[p + "router.weight"]))
    top, chosen = jax.lax.top_k(s + _f32(w[p + "router.bias"]), k + 1)
    margin, chosen = top[:, k - 1] - top[:, k], chosen[:, :k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen, picked * float(cfg["routed_scaling_factor"]), margin


def moe(w, p, cfg, u, experts_held=None, margins=None):
    """Routed experts by a plain loop with a 0/1 mask, plus the shared
    expert: no capacity, no drop. A list given as `margins` receives the
    tokens' routing margins (see `route`)."""
    E = int(cfg["n_routed_experts"])
    F = int(cfg["moe_intermediate_size"])
    lo, hi = experts_held or (0, E)
    chosen, weight, margin = route(w, p, cfg, u)
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
    return y + ffn(u, w[p + "shared.gate_proj.weight"],
                   w[p + "shared.up_proj.weight"],
                   w[p + "shared.down_proj.weight"])


# ------------------------------------------------------- hyper-connections --
def hc_coeffs(w, p, cfg, X):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) from the streams
    X [T, n, d]."""
    n = int(cfg["hc_mult"])
    eps = float(cfg["hc_eps"])
    T = X.shape[0]
    xt = rms_norm(X.reshape(T, -1), w[p + "norm.weight"], eps)
    z = _mm(xt, w[p + "phi"])  # [T, n + n + n*n]: pre, post, res
    alpha, b = _f32(w[p + "alpha"]), _f32(w[p + "bias"])
    h_pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + b[n:2 * n])
    raw = (alpha[2] * z[:, 2 * n:] + b[2 * n:]).reshape(T, n, n)
    m = jnp.exp(jnp.clip(raw, float(cfg["mhc_h_res_clamp_min"]),
                         float(cfg["mhc_h_res_clamp_max"])))
    for _ in range(int(cfg["hc_sinkhorn_iters"])):
        m = m / (m.sum(-2, keepdims=True) + eps)  # columns by their sums
        m = m / (m.sum(-1, keepdims=True) + eps)  # rows by theirs
    return h_pre, h_post, m


def sublayer(w, p_hc, norm_w, cfg, X, fn):
    """X' = H_res X + H_post^T F(RMSNorm(H_pre X))."""
    h_pre, h_post, h_res = hc_coeffs(w, p_hc, cfg, X)
    u = jnp.einsum("tn,tnd->td", h_pre, X, precision=_HI)
    y = fn(rms_norm(u, norm_w, float(cfg["rms_norm_eps"])))
    return jnp.einsum("tij,tjd->tid", h_res, X, precision=_HI) \
        + h_post[:, :, None] * y[:, None, :]


# ------------------------------------------------------------------- model --
def embed(w, cfg, ids):
    x = _f32(w["embed_tokens.weight"][ids])
    return jnp.broadcast_to(x[:, None], (x.shape[0], int(cfg["hc_mult"]),
                                         x.shape[1]))


def layer(w, i, cfg, X, pos, q_block=None, experts_held=None, margins=None):
    """Decoder layer i over streams X [T, n, d]; `w` needs only the names
    under ``layers.<i>.``. `margins`: see `moe`."""
    p = f"layers.{i}."
    X = sublayer(w, p + "attn_hc.", w[p + "input_layernorm.weight"], cfg, X,
                 lambda u: mla(w, p + "self_attn.", cfg, u, pos, q_block))
    if i < int(cfg["first_k_dense_replace"]):
        def mlp(u):
            return ffn(u, w[p + "mlp.gate_proj.weight"],
                       w[p + "mlp.up_proj.weight"],
                       w[p + "mlp.down_proj.weight"])
    else:
        def mlp(u):
            return moe(w, p + "mlp.", cfg, u, experts_held, margins)
    return sublayer(w, p + "ffn_hc.", w[p + "post_attention_layernorm.weight"],
                    cfg, X, mlp)


def head(w, cfg, X, at, v_block=None):
    """Streams summed, final RMSNorm, the untied head at positions `at`
    (over `v_block` rows of the vocabulary at a time when given)."""
    h = rms_norm(X.sum(1)[at], w["norm.weight"], float(cfg["rms_norm_eps"]))
    W = w["lm_head.weight"]  # [V, d]
    V = W.shape[0]
    if not v_block or V % v_block:
        return jnp.matmul(h, _f32(W).T, precision=_HI)
    out = jax.lax.map(lambda wb: jnp.matmul(h, _f32(wb).T, precision=_HI),
                      W.reshape(V // v_block, v_block, -1))
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V)


def forward(w, cfg, ids, at, experts_held=None):
    X = embed(w, cfg, ids)
    pos = jnp.arange(ids.shape[0])
    for i in range(int(cfg["num_hidden_layers"])):
        X = layer(w, i, cfg, X, pos, experts_held=experts_held)
    return head(w, cfg, X, at)
