"""Plain reference for the SDAR-MoE decoder (configs/sdar-30b-a3b-chat.json):
the forward pass and the generation of one block in straightforward
jax.numpy, float32, matmuls at "highest" precision, no kernel, no cache, no
batching. Written from the published config's keys and the equations of the
layers they name; it imports nothing of the program.

With d = hidden_size, Hq / Hkv query / key-value heads of head_dim D, E
experts of width F = moe_intermediate_size, k a token, B = block_length:

* RMS(x) = x / sqrt(mean x^2 + rms_norm_eps) * g: a weight, no bias;
* block: h = x + Attn(RMS_in x); y = h + Experts(RMS_post h);
* attention: q = u Wq (Hq heads), k = u Wk, v = u Wv (Hkv heads), no bias;
  q and k each through an RMS over the D dims of a head (one weight of D,
  shared by the heads); rotary over all D dims on the halves (i, i + D/2),
  inv_freq_i = rope_theta^(-2i/D), no scaling; query head h reads key/value
  head h // (Hq / Hkv); scores q.k / sqrt(D), softmax in float32. The mask is
  BLOCK-CAUSAL: the query at position i sees the key at j iff j // B <=
  i // B — its own block whole, in both directions, and every earlier block;
* experts: p = softmax(u Wr) over all E, T = the k largest, w_e = p_e /
  sum_{j in T} p_j (`norm_topk_prob`), each expert silu(u Wg) * (u Wu) -> Wd
  of width F; no shared expert, no selection bias, no scaling;
* embedding E[id]; a last RMS; logits = RMS(h) H^T with an untied head H.

Generation (`generate_block`): the sequence grows a block at a time. A block
starts as B mask tokens (the last `P mod B` tokens of a prompt open the first
one already unmasked); a denoise forward runs prefix + block, reads the
logits AT each masked position for that position's own token (no shift;
the mask id can never be sampled), and unmasks some of them
(`unmask_choice`): `low_confidence_static` the ceil(masked / steps_left)
most confident, so that `denoising_steps` forwards leave nothing masked;
`low_confidence_dynamic` every one whose confidence is over
`confidence_threshold`, at least the most confident one. The confidence of a
position is the probability of the id it sampled, under the softmax of its
logits at temperature 1.

Departures from the published description, and what the config does not
settle (`assumed` in the configuration file): the block is the Qwen3-MoE
family's (`model_type` sdar_moe has no key for the query/key norm or the
order of the norms); rotary on the halves; `block_length`, `mask_token_id`,
`denoising_steps`, the strategy and its threshold are the family's published
generation settings, not keys of config.json; logits are read unshifted; the
noise schedule is a training matter and nothing here reads it;
`intermediate_size`, `max_window_layers` and the sliding-window keys describe
layers this model does not have (`mlp_only_layers` is empty,
`use_sliding_window` false).

Weights come in under the program's state_dict names, in whatever dtype they
are served in, and are upcast here, an expert at a time (the program keeps
an expert's gate and up side by side in `experts.gate_up` [E, d, 2 F]).

    logits = forward(weights, ids, masked, cfg)

ids [T] token ids, masked [T] bool (None: nothing): returns float32 [T,
vocab], the logits at every position. `layer`, `embed` and `head` are the
same computation a layer at a time, for a caller that cannot hold it all at
once (families/sdar_moe.py at the published widths); `q_block` computes
attention over that many queries at a time, `past` hands a layer the keys
and values of earlier rows (what a clean prefix's layer gave: block-causality
makes them what every later block sees).
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _f32(x):
    return x.astype(jnp.float32)


def _weight(w):
    """A weight as the reference computes with it: upcast from whatever it
    is served in (the one place: a control that rounds the weights lower
    replaces this)."""
    return _f32(w)


def _mm(x, w):
    return jnp.matmul(x, _weight(w), precision=_HI)


def rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _weight(w)


def qk_norm(x, w, eps):
    """The RMS over the D dims of each head of x [T, H, D], one weight of D
    for all heads."""
    return rms_norm(x, w, eps)


def visible(k_pos, q_pos, B):
    """[Q, K] bool: the block-causal mask, the key's block not after the
    query's."""
    return k_pos[None, :] // B <= q_pos[:, None] // B


def rope(x, pos, theta):
    """x [T, H, D], the halves (i, i + D/2) rotated by pos *
    theta^(-2i/D)."""
    D = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = _f32(pos)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(w, p, cfg, u, pos, q_block=None, past=None, kv_out=None):
    """Grouped-query block-causal attention of layer prefix `p` over u
    [T, d] at positions `pos`. `past` = (K [S, Hkv, D], V, their positions
    [S], valid [S]): earlier rows' keys and values (normed and rotated), seen
    under the same mask where valid. `kv_out`, a list, receives this call's
    own (K, V). With `q_block` the queries are taken that many at a time."""
    T = u.shape[0]
    Hq, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    D, B = int(cfg["head_dim"]), int(cfg["block_length"])
    eps, theta = float(cfg["rms_norm_eps"]), cfg["rope_theta"]
    k = _mm(u, w[p + "k_proj.weight"]).reshape(T, Hkv, D)
    k = rope(qk_norm(k, w[p + "k_norm.weight"], eps), pos, theta)
    v = _mm(u, w[p + "v_proj.weight"]).reshape(T, Hkv, D)
    if kv_out is not None:
        kv_out.append((k, v))
    k_pos, k_ok = pos, jnp.ones((T,), bool)
    if past is not None:
        k = jnp.concatenate([past[0], k])
        v = jnp.concatenate([past[1], v])
        k_pos = jnp.concatenate([past[2], pos])
        k_ok = jnp.concatenate([past[3], k_ok])

    def rows(ub, pb):  # [Q, d], [Q]
        q = _mm(ub, w[p + "q_proj.weight"]).reshape(-1, Hq, D)
        q = rope(qk_norm(q, w[p + "q_norm.weight"], eps), pb, theta)
        # query head h reads key/value head h // (Hq / Hkv)
        q = q.reshape(-1, Hkv, Hq // Hkv, D)
        s = jnp.einsum("qgrd,kgd->grqk", q, k, precision=_HI) * D ** -0.5
        keep = visible(k_pos, pb, B) & k_ok[None, :]
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


def route(w, p, cfg, u):
    """(chosen [T, k] expert ids, their weights [T, k], margin [T]) of every
    token. The margin is the k-th largest probability less the (k+1)-th:
    how far the choice is from another one. The choice is a discontinuous
    function of `u`; a caller that compares a lower-precision computation
    with this one reads from the margin where the two may rightly choose
    differently."""
    k = int(cfg["num_experts_per_tok"])
    s = jax.nn.softmax(_mm(u, w[p + "router.weight"]), axis=-1)
    top, chosen = jax.lax.top_k(s, k + 1)
    margin = top[:, k - 1] - top[:, k]
    top, chosen = top[:, :k], chosen[:, :k]
    weight = top / top.sum(-1, keepdims=True) if cfg["norm_topk_prob"] \
        else top
    return chosen, weight, margin


def moe(w, p, cfg, u, margins=None):
    """The experts by a plain loop with a 0/1 mask: no capacity, no drop.
    A list given as `margins` receives the tokens' routing margins."""
    E, F = int(cfg["num_experts"]), int(cfg["moe_intermediate_size"])
    chosen, weight, margin = route(w, p, cfg, u)
    if margins is not None:
        margins.append(margin)
    gate_up, down = w[p + "experts.gate_up"], w[p + "experts.down"]

    def one(e, y):
        mask = (chosen == e).astype(jnp.float32)  # [T, k] of 0/1
        g = jax.lax.dynamic_index_in_dim(gate_up, e, keepdims=False)
        d = jax.lax.dynamic_index_in_dim(down, e, keepdims=False)
        return y + (mask * weight).sum(-1, keepdims=True) \
            * ffn(u, g[:, :F], g[:, F:], d)

    return jax.lax.fori_loop(0, E, one, jnp.zeros_like(u))


def embed(w, cfg, ids):
    return _weight(w["embed_tokens.weight"][ids])


def layer(w, i, cfg, h, pos, q_block=None, past=None, kv_out=None,
          margins=None):
    """Decoder layer i over h [T, d]; `w` needs only the names under
    ``layers.<i>.``. `past`, `kv_out`: see `attention`; `margins`: `moe`."""
    p = f"layers.{i}."
    eps = float(cfg["rms_norm_eps"])
    h = h + attention(w, p + "self_attn.", cfg,
                      rms_norm(h, w[p + "input_layernorm.weight"], eps), pos,
                      q_block, past, kv_out)
    return h + moe(w, p + "mlp.", cfg, rms_norm(
        h, w[p + "post_attention_layernorm.weight"], eps), margins)


def head(w, cfg, h, v_block=None):
    """The last RMS and the untied head over every row of h (over `v_block`
    rows of the vocabulary at a time when given). The mask id can never be
    sampled: its logit is -inf."""
    x = rms_norm(h, w["norm.weight"], float(cfg["rms_norm_eps"]))
    W = w["lm_head.weight"]  # [V, d]
    V = W.shape[0]
    if not v_block or V % v_block:
        out = jnp.matmul(x, _weight(W).T, precision=_HI)
    else:
        out = jax.lax.map(
            lambda wb: jnp.matmul(x, _weight(wb).T, precision=_HI),
            W.reshape(V // v_block, v_block, -1))
        out = jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)
    return out.at[:, int(cfg["mask_token_id"])].set(-jnp.inf)


def forward(w, ids, masked, cfg, margins=None):
    """Logits [T, V] at every position for that position's own token:
    `ids` with the mask id where `masked` [T] (None: nowhere), under the
    block-causal mask. `margins`, a list, receives each layer's routing
    margins [T]."""
    ids = jnp.asarray(ids)
    if masked is not None:
        ids = jnp.where(jnp.asarray(masked), int(cfg["mask_token_id"]), ids)
    h = embed(w, cfg, ids)
    pos = jnp.arange(ids.shape[0])
    for i in range(int(cfg["num_hidden_layers"])):
        h = layer(w, i, cfg, h, pos, margins=margins)
    return head(w, cfg, h)


def unmask_choice(conf, masked, steps_left, cfg):
    """(unmask bool [B], tie): which masked positions a denoise forward
    unmasks, from their confidences `conf` [B], and how near the choice is
    to another one: the least relative difference in confidence between a
    position taken and one left (inf where nothing is left, or the rule
    compares with the threshold alone and no confidence is near it). Equal
    confidences rank by position, the earlier first."""
    conf = np.asarray(conf, np.float64)
    masked = np.asarray(masked, bool)
    order = [j for j in np.argsort(-conf, kind="stable") if masked[j]]
    m = len(order)
    take = np.zeros(conf.shape, bool)
    if not m:
        return take, math.inf
    if cfg["strategy"] == "low_confidence_static":
        n = -(-m // max(int(steps_left), 1))
        near = []
    elif cfg["strategy"] == "low_confidence_dynamic":
        thr = float(cfg["confidence_threshold"])
        n = max(1, int((conf[order] > thr).sum()))
        # a confidence near the threshold may fall on its other side
        near = [abs(conf[j] - thr) / thr for j in order[1:]] if thr > 0 \
            else []
    else:
        raise ValueError(f"strategy {cfg['strategy']!r}")
    take[order[:n]] = True
    if n < m and (cfg["strategy"] == "low_confidence_static"
                  or n == 1):  # ranks decide: the last taken, the first left
        a, b = conf[order[n - 1]], conf[order[n]]
        near.append((a - b) / a if a > 0 else 0.0)
    return take, min(near, default=math.inf)


def generate_block(w, cfg, prefix, block, masked, forced=None,
                   block_forward=None):
    """The denoise forwards of ONE block from a clean prefix, under the
    strategy of `cfg`: `prefix` [S] clean ids (S a multiple of B), `block`
    [B] ids (read where not `masked` [B]). Returns the list of the forwards,
    each a dict: `logits` [B, V] (of prefix + block as that forward saw it),
    `unmask` bool [B] (what it unmasked), `margin` [B] (the least routing
    margin over the layers, a position), `tie` (`unmask_choice`'s), `ids`
    [B] (what it put there). Greedy: an unmasked position takes its argmax —
    or, teacher-forced, the id `forced` [B] gives it (-1: none, the argmax),
    so that a served block can be replayed through the reference's own
    choices of positions. `block_forward(ids [B], masked [B]) -> (logits
    [B, V], margin [B])` replaces the plain full forward (a caller that
    keeps the prefix's keys and values)."""
    B = int(cfg["block_length"])
    block = np.asarray(block, np.int64).copy()
    masked = np.asarray(masked, bool).copy()
    S = len(prefix)
    if block_forward is None:
        def block_forward(ids, m):
            margins = []
            lg = forward(w, np.concatenate([np.asarray(prefix), ids]),
                         np.concatenate([np.zeros(S, bool), m]), cfg,
                         margins)
            return lg[S:], jnp.stack(margins).min(0)[S:]
    steps, done = [], 0
    while masked.any():
        logits, margin = block_forward(block, masked)
        logits = np.asarray(logits, np.float32)
        lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
            + logits.max(-1)
        ids = logits.argmax(-1)
        conf = np.exp(logits[np.arange(B), ids] - lse)
        take, tie = unmask_choice(
            conf, masked, int(cfg["denoising_steps"]) - done, cfg)
        if forced is not None:
            f = np.asarray(forced)
            ids = np.where(f >= 0, f, ids)
        block = np.where(take, ids, block)
        masked &= ~take
        done += 1
        steps.append({"logits": logits, "unmask": take,
                      "margin": np.asarray(margin), "tie": tie,
                      "ids": block.copy()})
    return steps
