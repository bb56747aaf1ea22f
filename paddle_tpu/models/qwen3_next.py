"""Qwen3-Next decoder (Qwen/Qwen3-Next-80B-A3B-Instruct `config.json`,
`model_type` `qwen3_next`), the served forward pass: layers of two kinds of
mixer in a period of `full_attention_interval` — layer i is a gated
full-attention layer when ``(i + 1) % full_attention_interval == 0`` and a
Gated DeltaNet (linear attention) layer otherwise — each ``h = x +
Mixer(RMSNorm x)``, ``y = h + Experts(RMSNorm h)``; `num_experts`
softmax-routed experts of width `moe_intermediate_size`,
`num_experts_per_tok` a token, beside one shared expert whose output a
sigmoid gate scales a token (`nn.moe.DroplessMoE` with ``router="softmax"``,
``shared_combine="sigmoid_gate"``); a last RMSNorm and an untied head. The
family's RMSNorm is ``x / rms(x) * (1 + w)``.

* Gated DeltaNet: `in_proj_qkvz` laid out per key head as [q | k | v | z]
  (v and z of the key head's `linear_num_value_heads / linear_num_key_heads`
  value heads), `in_proj_ba` per key head as [b | a]; [q | k | v] through a
  causal depthwise convolution of `linear_conv_kernel_dim` taps, no bias,
  then SiLU; q and k L2-normalised per head, q scaled by dk^-1/2; value
  head j reads key head j // (Hv / Hk); beta = sigmoid(b), g = -exp(A_log)
  softplus(a + dt_bias); a head's state S [dk, dv] follows ``S <- exp(g) S;
  S <- S + k (beta (v - S^T k))^T; o = S^T q``; o through an RMSNorm per
  head (weight w) times silu(z), then `out_proj`.
* Gated attention: `q_proj` gives a head its query and an output gate side
  by side; q and k through the (1 + w) RMSNorm of a head; rotary over the
  first `partial_rotary_factor` of a head's dims on their halves; the
  attention's output times sigmoid(gate), then `o_proj`.

The equations are those of `benchmark/reference/qwen3_next.py`, which is
written from the config alone; here they run in the weights' dtype (bf16
when served) with float32 where the reference's result depends on it: the
residual stream, the norms, the rotation, the convolution, the linear
layers' states and the router's probabilities, softmax and every matmul's
accumulation. What the config does not settle is listed there and in the
benchmark's configuration file (`assumed`).

The two kinds of layer keep two kinds of cache state (`ops.kv_pool.CacheSpec`
with ``states``): a full layer's K and V rows in the paged pools, a linear
layer's one recurrent state [Hv, dk, dv] float32 and its convolution's last
``conv_k - 1`` input rows a slot. Three paths, one set of weights:

* no cache: every full layer by the walk over key blocks
  (`_blocks._walk_attention`), every linear layer by the chunkwise form of
  the gated delta rule from a zero state — the path tests compare with the
  reference;
* paged prefill (``paged_kernel=None``): a full layer writes the prompt's
  rows into its pools (by whole blocks, `kv_pool.write_span`) and walks the
  call's own keys; a linear layer runs the chunks (`_chunk_gated_delta`:
  chunks of 64, the intra-chunk UT form by one triangular solve, a
  `lax.scan` of the state over chunks) and writes the state it leaves and
  the convolution's tail into the slot's entry of its pools. Bucket padding
  carries beta = 0 and g = 0, so the state after the bucket's tail is the
  state at the prompt's last token. It attends to nothing the call did not
  bring, so the engine refuses what would need that of such a decoder;
* paged decode (``paged_kernel`` "xla" | "interpret" | "pallas"): a full
  layer through `ops.pallas_ops.paged_attention`, a linear layer shifts its
  convolution's tail in XLA and updates every slot's state through
  `ops.pallas_ops.gated_delta_step` (``state_kernel``, the engine's
  `select_gated_delta_kernel`), lane b being slot b.

``moe_kernel`` (the engine's `pallas_ops.select_grouped_kernel`) goes to
every expert layer: each call's shape decides there between the
`grouped_matmul` kernel (a decode step's rows) and `lax.ragged_dot` (a
prompt's). The chip may hold a share of the experts (`experts_held`) and of
the vocabulary (a smaller `vocab_size`): what one chip of an expert-parallel
deployment computes before the exchange; nothing here stands in for the
others. The multi-token prediction module of the checkpoint has no key in
this config and is not here.

The forward is written on the parameters' arrays (`Tensor._data`): the
autograd tape does not see it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn.initializer import Constant, Normal, Uniform
from ..nn.moe.dropless import DroplessMoE
from ..ops import kv_pool as _kv_pool
from ..ops import pallas_ops as _pallas_ops
from ..profiler.spans import scope as _scope
from ._blocks import (_head_logits, _mm, _param, _rms, _rope_halves,
                      _walk_attention, _WALK_BLOCK, _Weight)

_F32 = jnp.float32
# a prompt's tokens the expert layer takes at a time (DroplessMoE
# `rows_at_a_time`): 1024 x 10 routed rows x 2048 float32 are 84 MB
_MOE_ROWS = 1024
# positions of one chunk of the gated delta rule at a prompt
_CHUNK = 64
_L2_EPS = 1e-6


class Qwen3NextConfig:
    """The published keys under their published names (the published sizes
    are the defaults, `PRESETS["tiny"]` is the CPU tests' size).
    `experts_held` = (lo, hi): the routed experts this chip holds of
    `num_experts`."""

    PUBLISHED = dict(
        decoder_sparse_step=1, full_attention_interval=4, head_dim=256,
        hidden_act="silu", hidden_size=2048, intermediate_size=5120,
        linear_conv_kernel_dim=4, linear_key_head_dim=128,
        linear_num_key_heads=16, linear_num_value_heads=32,
        linear_value_head_dim=128, max_position_embeddings=262144,
        mlp_only_layers=[], model_type="qwen3_next",
        moe_intermediate_size=512, norm_topk_prob=True,
        num_attention_heads=16, num_experts=512, num_experts_per_tok=10,
        num_hidden_layers=48, num_key_value_heads=2,
        partial_rotary_factor=0.25, rms_norm_eps=1e-6, rope_scaling=None,
        rope_theta=10000000, shared_expert_intermediate_size=512,
        tie_word_embeddings=False, use_sliding_window=False,
        vocab_size=151936)
    PRESETS = {
        "tiny": dict(
            head_dim=32, hidden_size=64, intermediate_size=160,
            linear_key_head_dim=16, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_value_head_dim=16,
            max_position_embeddings=4096, moe_intermediate_size=24,
            num_attention_heads=4, num_experts=16, num_experts_per_tok=2,
            num_hidden_layers=4, num_key_value_heads=2,
            shared_expert_intermediate_size=24, vocab_size=512),
    }

    def __init__(self, dtype="float32", initializer_range=0.02,
                 experts_held=None, **keys):
        unknown = sorted(set(keys) - set(self.PUBLISHED))
        if unknown:
            raise ValueError(f"Qwen3NextConfig: not keys of the published "
                             f"config: {unknown}")
        for k, v in {**self.PUBLISHED, **keys}.items():
            setattr(self, k, v)
        self.dtype = dtype
        self.initializer_range = initializer_range
        self.experts_held = tuple(experts_held) if experts_held \
            else (0, self.num_experts)
        refused = [f"{k}={getattr(self, k)!r}" for k, want in (
            ("decoder_sparse_step", 1), ("hidden_act", "silu"),
            ("mlp_only_layers", []), ("rope_scaling", None),
            ("tie_word_embeddings", False), ("use_sliding_window", False))
            if getattr(self, k) != want]
        if self.shared_expert_intermediate_size \
                != self.moe_intermediate_size:
            refused.append(f"a shared expert of width "
                           f"{self.shared_expert_intermediate_size} beside "
                           f"routed experts of {self.moe_intermediate_size}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.linear_num_value_heads % self.linear_num_key_heads:
            refused.append("query (value) heads that are not a whole number "
                           "a key/value (key) head")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            refused.append(f"partial_rotary_factor="
                           f"{self.partial_rotary_factor} of head_dim "
                           f"{self.head_dim} (rotary halves)")
        if refused:
            raise ValueError("Qwen3NextConfig: no code for "
                             + ", ".join(refused))
        self.layer_kinds = [
            "full_attention" if (i + 1) % self.full_attention_interval == 0
            else "linear_attention" for i in range(self.num_hidden_layers)]

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_channels(self):
        """[q | k | v] of every head: the convolution's channels."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @classmethod
    def preset(cls, name, **overrides):
        return cls(**{**cls.PRESETS[name], **overrides})

    def as_dict(self):
        return {k: getattr(self, k) for k in self.PUBLISHED}


class _LogUniform(Uniform):
    """log of a draw of U(low, high)."""

    def __call__(self, shape, dtype):
        return jnp.log(super().__call__(shape, dtype))


def _rms1p(x, w, eps):
    """The family's RMSNorm: x / rms(x) * (1 + w), float32."""
    return _rms(x, w.astype(_F32) + _F32(1), eps)


def _l2norm(x):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + _F32(_L2_EPS))


def _chunk_gated_delta(q, k, v, g, beta, chunk=_CHUNK):
    """The gated delta rule over a prompt from a zero state, a chunk of
    ``chunk`` positions at a time: q, k [B, T, Hk, dk] (normalised, q
    scaled), v [B, T, Hv, dv], g and beta [B, T, Hv], all float32 -> (o [B,
    T, Hv, dv], the state [B, Hv, dk, dv] the last position leaves).

    Inside a chunk (cumulative decay c_i = g_1 + .. + g_i, Gamma_ij =
    exp(c_i - c_j)), the UT form: T = (I + strict_lower(beta_i k_i.k_j
    Gamma_ij))^-1, U = T (beta v), W = T (beta exp(c) k) — one triangular
    solve for both. Over chunks, a scan of the state: v' = U - W S, o =
    (exp(c) q) S + lower(q_i.k_j Gamma_ij) v', S <- exp(c_C) S + (exp(c_C -
    c) k)^T v'. A position with beta = 0 and g = 0 (bucket padding) leaves
    the state as it was."""
    B, T, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    r = Hv // Hk
    pad = -T % chunk
    n = (T + pad) // chunk

    def chunks(x):  # [B, T, Hk or Hv, ...] -> [n, B, Hv, chunk, ...]
        if x.shape[2] != Hv:
            x = jnp.repeat(x, r, axis=2)  # value head j reads key head j // r
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((B, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, (1, 2), (0, 3))

    q, k, v = chunks(q), chunks(k), chunks(v)         # [n, B, Hv, C, d]
    g, beta = chunks(g), chunks(beta)                 # [n, B, Hv, C]
    c = jnp.cumsum(g, axis=-1)
    i = jnp.arange(chunk)
    lower = i[:, None] >= i[None, :]
    diff = c[..., :, None] - c[..., None, :]
    gamma = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb = k * beta[..., None]
    a = jnp.where(i[:, None] > i[None, :],
                  jnp.einsum("...id,...jd->...ij", kb, k) * gamma, 0.0)
    rhs = jnp.concatenate([v * beta[..., None],
                           kb * jnp.exp(c)[..., None]], axis=-1)
    uw = jax.lax.linalg.triangular_solve(a, rhs, left_side=True, lower=True,
                                         unit_diagonal=True)
    u, w = uw[..., :dv], uw[..., dv:]
    intra = jnp.einsum("...id,...jd->...ij", q, k) * gamma
    qc = q * jnp.exp(c)[..., None]
    kc = k * jnp.exp(c[..., -1:] - c)[..., None]
    last = jnp.exp(c[..., -1])[..., None, None]

    def step(S, xs):
        qc_i, kc_i, u_i, w_i, intra_i, last_i = xs
        v_new = u_i - w_i @ S
        o = qc_i @ S + intra_i @ v_new
        S = S * last_i + jnp.swapaxes(kc_i, -1, -2) @ v_new
        return S, o

    S0 = jnp.zeros((B, Hv, dk, dv), _F32)
    S, o = jax.lax.scan(step, S0, (qc, kc, u, w, intra, last))
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(B, n * chunk, Hv, dv)
    return o[:, :T], S


def _conv_tail(xp, n, K):
    """The convolution's tail a prompt leaves: of xp [B, K - 1 + T, C] (the
    call's rows behind K - 1 zero rows) the K - 1 rows before row n[b] of
    the call, the last it brought before its padding (zeros where the
    prompt is shorter)."""
    return jax.vmap(lambda x, m: jax.lax.dynamic_slice_in_dim(
        x, m, K - 1, axis=0))(xp, n)


class GatedDeltaNet(nn.Layer):
    """A linear-attention layer (its parameters under the checkpoint's
    names: `in_proj_qkvz`, `in_proj_ba`, `conv1d` [channels, taps],
    `dt_bias`, `A_log`, `norm`, `out_proj`)."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        d, dt = cfg.hidden_size, cfg.dtype
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        init = Normal(0.0, cfg.initializer_range)
        self.in_proj_qkvz = _Weight((d, 2 * Hk * dk + 2 * Hv * dv), init, dt)
        self.in_proj_ba = _Weight((d, 2 * Hv), init, dt)
        self.conv1d = _Weight((cfg.conv_channels, cfg.linear_conv_kernel_dim),
                              init, dt)
        # the family's initialisers: dt_bias 1, A = exp(A_log) ~ U(0, 16);
        # both float32 (the family's code reads them so)
        self.dt_bias = _param(self, (Hv,), Constant(1.0), "float32")
        self.A_log = _param(self, (Hv,), _LogUniform(0.0, 16.0), "float32")
        self.norm = _Weight((dv,), Constant(1.0), dt)
        self.out_proj = _Weight((Hv * dv, d), init, dt)

    def delta_shapes(self, batch):
        """``(B, Hk, Hv, dk, dv)`` of this layer's decode update over
        ``batch`` slots: what `pallas_ops._delta_plan` reads."""
        cfg = self.cfg
        return (int(batch), cfg.linear_num_key_heads,
                cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                cfg.linear_value_head_dim)

    def _project(self, u):
        """u [B, T, d] -> (the convolution's input [B, T, channels] in u's
        dtype, z [B, T, Hv, dv], b and a [B, T, Hv] float32)."""
        cfg = self.cfg
        B, T, _ = u.shape
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv, r = cfg.linear_key_head_dim, cfg.linear_value_head_dim, \
            Hv // Hk
        x = _mm(u, self.in_proj_qkvz.weight._data).reshape(
            B, T, Hk, 2 * dk + 2 * r * dv)
        q, k = x[..., :dk], x[..., dk:2 * dk]
        v = x[..., 2 * dk:2 * dk + r * dv]
        z = x[..., 2 * dk + r * dv:].reshape(B, T, Hv, dv)
        ba = _mm(u, self.in_proj_ba.weight._data).reshape(B, T, Hk, 2 * r)
        mixed = jnp.concatenate([q.reshape(B, T, -1), k.reshape(B, T, -1),
                                 v.reshape(B, T, -1)], axis=-1)
        return (mixed.astype(u.dtype), z,
                ba[..., :r].reshape(B, T, Hv), ba[..., r:].reshape(B, T, Hv))

    def _heads(self, y, b, a):
        """The convolution's output [B, T, channels] -> q, k [B, T, Hk, dk]
        normalised (q scaled), v [B, T, Hv, dv], g and beta [B, T, Hv]."""
        cfg = self.cfg
        B, T, _ = y.shape
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        q = _l2norm(y[..., :Hk * dk].reshape(B, T, Hk, dk)) \
            * _F32(dk ** -0.5)
        k = _l2norm(y[..., Hk * dk:2 * Hk * dk].reshape(B, T, Hk, dk))
        v = y[..., 2 * Hk * dk:].reshape(B, T, Hv, dv)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(self.A_log._data) * jax.nn.softplus(
            a + self.dt_bias._data)
        return q, k, v, g, beta

    def _out(self, o, z, dt):
        """The gated RMSNorm of a head (weight w, times silu(z)), then
        `out_proj`."""
        B, T = o.shape[:2]
        o = _rms(o, self.norm.weight._data, self.cfg.rms_norm_eps) \
            * jax.nn.silu(z)
        return _mm(o.reshape(B, T, -1).astype(dt),
                   self.out_proj.weight._data)

    def forward(self, u, positions, cache=None, cache_offset=None,
                seq_lens=None, block_tables=None, paged_kernel=None,
                state_kernel=None):
        """u [B, T, d] (normed) -> (y [B, T, d] float32, new (state, tail)
        pools)."""
        cfg = self.cfg
        B, T, _ = u.shape
        K = cfg.linear_conv_kernel_dim
        dt = u.dtype
        w = self.conv1d.weight._data.astype(_F32)  # [channels, K]
        mixed, z, b, a = self._project(u)
        if cache is not None and paged_kernel is not None:
            # a decode step: lane b is slot b of both pools
            states, tails = cache
            if T != 1 or B != states.shape[0]:
                raise TypeError(f"GatedDeltaNet: a decode step brings one "
                                f"row for each of {states.shape[0]} slots, "
                                f"not [{B}, {T}]")
            with _scope("short_conv"):
                window = jnp.concatenate([tails, mixed], axis=1)  # [B, K, C]
                y = jax.nn.silu(jnp.einsum("bkc,ck->bc",
                                           window.astype(_F32), w))[:, None]
                tails = window[:, 1:]
            q, k, v, g, beta = self._heads(y, b, a)
            o, states = _pallas_ops.gated_delta_step(
                states, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                kernel=state_kernel)
            return self._out(o[:, None], z, dt), (states, tails)
        with _scope("short_conv"):
            xp = jnp.pad(mixed, ((0, 0), (K - 1, 0), (0, 0)))
            y = jax.nn.silu(sum(xp[:, j:j + T].astype(_F32) * w[:, j]
                                for j in range(K)))
        q, k, v, g, beta = self._heads(y, b, a)
        if seq_lens is not None:
            # bucket padding: no decay and no update, so the state after the
            # bucket's tail is the state at the prompt's last token
            valid = positions < seq_lens.astype(jnp.int32)[:, None]
            g = jnp.where(valid[..., None], g, _F32(0))
            beta = jnp.where(valid[..., None], beta, _F32(0))
        with _scope("gated_delta_chunks"):
            o, S = _chunk_gated_delta(q, k, v, g, beta)
        if cache is not None:
            states, tails = cache
            slot = block_tables[:, 0].astype(jnp.int32)
            tail = _conv_tail(xp, (seq_lens - cache_offset).astype(jnp.int32),
                              K)
            cache = (states.at[slot].set(S),
                     tails.at[slot].set(tail.astype(tails.dtype)))
        return self._out(o, z, dt), cache


def _rope_partial(x, positions, cfg):
    """Rotary over a head's first `rotary_dim` dims, on their halves; the
    rest pass through (float32)."""
    R = cfg.rotary_dim
    x = x.astype(_F32)
    return jnp.concatenate(
        [_rope_halves(x[..., :R], positions, cfg.rope_theta), x[..., R:]],
        axis=-1)


class GatedAttention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        d, dt = cfg.hidden_size, cfg.dtype
        Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        init = Normal(0.0, cfg.initializer_range)
        # a head's query and its output gate side by side: [q D | gate D]
        self.q_proj = _Weight((d, Hq * 2 * D), init, dt)
        self.k_proj = _Weight((d, Hkv * D), init, dt)
        self.v_proj = _Weight((d, Hkv * D), init, dt)
        self.o_proj = _Weight((Hq * D, d), init, dt)
        # the (1 + w) norm of head_dim, shared by the heads; w drawn as 0
        self.q_norm = _Weight((D,), Constant(0.0), dt)
        self.k_norm = _Weight((D,), Constant(0.0), dt)

    def forward(self, u, positions, cache=None, cache_offset=None,
                seq_lens=None, block_tables=None, paged_kernel=None):
        """u [B, T, d] (normed) -> (y [B, T, d] float32, new cache)."""
        cfg = self.cfg
        B, T, _ = u.shape
        Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        eps, dt = cfg.rms_norm_eps, u.dtype
        qg = _mm(u, self.q_proj.weight._data).reshape(B, T, Hq, 2 * D)
        gate = qg[..., D:].reshape(B, T, Hq * D)
        k = _mm(u, self.k_proj.weight._data).reshape(B, T, Hkv, D)
        v = _mm(u, self.v_proj.weight._data).reshape(B, T, Hkv, D).astype(dt)
        q = _rope_partial(_rms1p(qg[..., :D], self.q_norm.weight._data, eps),
                          positions, cfg).astype(dt)
        k = _rope_partial(_rms1p(k, self.k_norm.weight._data, eps),
                          positions, cfg).astype(dt)
        if cache is not None:
            with _scope("kv_write"):
                cache = _kv_pool.write_span(cache[0], cache[1], k, v,
                                            block_tables, cache_offset,
                                            seq_lens)
        if cache is not None and paged_kernel is not None:
            o = _pallas_ops.paged_attention(
                q, cache[0], cache[1], block_tables, seq_lens, cache_offset,
                kernel=paged_kernel)
        else:
            valid = jnp.ones((B, T), bool) if seq_lens is None else \
                positions < seq_lens.astype(jnp.int32)[:, None]
            o = _walk_attention(q, k, v, positions, positions, valid, None,
                                _WALK_BLOCK)
        o = o.reshape(B, T, Hq * D).astype(_F32) * jax.nn.sigmoid(gate)
        return _mm(o.astype(dt), self.o_proj.weight._data), cache


class Qwen3NextLayer(nn.Layer):
    def __init__(self, cfg, idx):
        super().__init__()
        self.cfg = cfg
        self.linear = cfg.layer_kinds[idx] == "linear_attention"
        zero = Constant(0.0)
        self.input_layernorm = _Weight((cfg.hidden_size,), zero, cfg.dtype)
        if self.linear:
            self.linear_attn = GatedDeltaNet(cfg)
        else:
            self.self_attn = GatedAttention(cfg)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,), zero,
                                                cfg.dtype)
        self.mlp = DroplessMoE(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, n_shared=1,
            norm_topk_prob=cfg.norm_topk_prob,
            experts_held=cfg.experts_held, init_std=cfg.initializer_range,
            dtype=cfg.dtype, select_bias=False, router="softmax",
            shared_combine="sigmoid_gate", rows_at_a_time=_MOE_ROWS)

    def forward(self, h, positions, valid=None, moe_kernel=None,
                state_kernel=None, **cache_args):
        """h [B, T, d] float32 -> (h + Mixer(RMS h), then + Experts(RMS .),
        cache)."""
        eps = self.cfg.rms_norm_eps
        w = self.input_layernorm.weight._data
        u = _rms1p(h, w, eps).astype(w.dtype)
        if self.linear:
            with _scope("gated_delta"):
                a, cache = self.linear_attn(u, positions,
                                            state_kernel=state_kernel,
                                            **cache_args)
        else:
            with _scope("attn_gate"):
                a, cache = self.self_attn(u, positions, **cache_args)
        h = h + a
        # the router reads the float32 normed input
        u = _rms1p(h, self.post_attention_layernorm.weight._data, eps)
        return h + self.mlp(u, valid=valid, kernel=moe_kernel)._data, cache


class Qwen3NextModel(nn.Layer):
    """forward(input_ids [B, T]) -> logits [B, T, V] float32, or with the
    paged-cache arguments (what `serving.GenerationEngine` passes;
    ``block_tables`` a list, each layer's own part) -> (final-normed hidden
    [B, T, d], the written pools)."""

    step_counter_names = ("moe_experts_hit",)

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        init = Normal(0.0, cfg.initializer_range)
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size), init,
                                    cfg.dtype)
        self.layers = nn.LayerList([Qwen3NextLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), Constant(0.0), cfg.dtype)
        # untied, [V, d] as the checkpoints keep it
        self.lm_head = _Weight((cfg.vocab_size, cfg.hidden_size), init,
                               cfg.dtype)
        self._logits = _head_logits()

    # -- what serving.GenerationEngine asks of a decoder -------------------
    @property
    def max_positions(self):
        return self.cfg.max_position_embeddings

    def kv_cache_spec(self):
        """A full layer keeps a K and a V row of its key/value heads a token;
        a linear layer keeps a recurrent state and its convolution's tail a
        slot."""
        cfg = self.cfg
        state = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim, cfg.conv_channels,
                 cfg.linear_conv_kernel_dim)
        return _kv_pool.CacheSpec(
            "heads", [None if l.linear else (cfg.num_key_value_heads,
                                             cfg.head_dim)
                      for l in self.layers],
            q_per_kv=cfg.num_attention_heads // cfg.num_key_value_heads,
            states=[state if l.linear else None for l in self.layers])

    def serving_head(self):
        return self.lm_head.weight, self._logits

    def step_counters(self):
        """Device-side counts of the last forward, by name (taken once: the
        arrays belong to the trace that made them)."""
        hit = sum(l.mlp.last_experts_hit for l in self.layers)
        for l in self.layers:
            l.mlp.last_experts_hit = None
        return {"moe_experts_hit": hit}

    def host_step_counts(self, n_active):
        n = len(self.layers)
        return {"moe_layer_steps": n,
                "moe_routed_rows": n * n_active
                * self.cfg.num_experts_per_tok,
                "linear_layer_steps": sum(l.linear for l in self.layers)}

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_offsets=None, seq_lens=None, block_tables=None,
                paged_kernel=None, paged_mesh=None, moe_kernel=None,
                state_kernel=None):
        if paged_mesh is not None:
            raise TypeError("Qwen3NextModel: a cache with state layers has "
                            "no mesh route")

        def arr(t):
            return t._data if isinstance(t, Tensor) else t

        ids = arr(input_ids)
        B, T = ids.shape
        positions = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None], (B, T)) \
            if position_ids is None else arr(position_ids)
        h = self.embed_tokens.weight._data[ids].astype(_F32)
        valid, new_caches = None, []
        if caches is not None:
            offs, sl = arr(cache_offsets), arr(seq_lens)
            valid = (offs.astype(jnp.int32)[:, None]
                     + jnp.arange(T, dtype=jnp.int32)[None]
                     < sl.astype(jnp.int32)[:, None])
        for i, layer in enumerate(self.layers):
            cache_args = {} if caches is None else dict(
                cache=tuple(arr(p) for p in caches[i]), cache_offset=offs,
                seq_lens=sl, block_tables=arr(block_tables[i]),
                paged_kernel=paged_kernel)
            h, nc = layer(h, positions, valid=valid, moe_kernel=moe_kernel,
                          state_kernel=state_kernel, **cache_args)
            if nc is not None:
                new_caches.append(tuple(Tensor(p) for p in nc))
        w = self.norm.weight._data
        h = _rms1p(h, w, self.cfg.rms_norm_eps).astype(w.dtype)
        if caches is not None:
            return Tensor(h), new_caches
        return Tensor(self._logits(
            h.reshape(B * T, -1), self.lm_head.weight._data
        ).reshape(B, T, -1))
