"""Xing4.0 decoder (XingChen-AGI/Xing4.0-29B-A4B `config.json`), the served
forward pass: latent (MLA) attention over a paged pool of one latent row a
token, a dropless sigmoid-routed expert layer with a shared expert
(`nn.moe.DroplessMoE`), manifold-constrained hyper-connections
(arXiv:2512.24880) — `hc_mult` residual streams mixed by a doubly stochastic
matrix — RMSNorm, SwiGLU, YaRN rotary positions computed from the position
ids (no table of `max_position_embeddings` rows) and an untied head.

The equations are those of `benchmark/reference/xing4.py`, which is written
from the config alone; here they run in the weights' dtype (bf16 when
served) with float32 where the reference's result depends on it: the
residual streams and every hyper-connection coefficient, every norm's
statistics, the router's scores, softmax, and every matmul's accumulation.
What the config does not settle is listed there and in the benchmark's
configuration file (`assumed`).

Three attention paths, one set of weights:
* no cache: the expanded form, causal — the path tests compare with the
  reference;
* paged prefill (``paged_kernel=None``): the window's latent rows are
  written into the pool, then the slot's logical view is expanded
  (`kv_b_proj`) to per-head keys and values, queries a block at a time;
* paged decode (``paged_kernel`` "xla" | "interpret" | "pallas"): absorbed —
  ``q_nope W_uk`` scores against the latent rows themselves
  (`ops.pallas_ops.mla_paged_attention`, every head against a block read
  once) and ``W_uv`` is applied to the latent output.

``moe_kernel`` (the engine's `pallas_ops.select_grouped_kernel`) goes to every
expert layer: each call's shape decides there between the `grouped_matmul`
kernel (a decode step's rows) and `lax.ragged_dot` (a prompt's).

The forward is written on the parameters' arrays (`Tensor._data`): the
autograd tape does not see it. Training through MLA and the dropless layer
is open (ROADMAP). Multi-token prediction is not part of a served forward
pass and is not here.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn.initializer import Constant, Normal
from ..nn.moe.dropless import DroplessMoE, swiglu
from ..ops import kv_pool as _kv_pool
from ..ops import pallas_ops as _pallas_ops
from ..profiler.spans import scope as _scope
from ._blocks import _head_logits, _mm, _param, _rms, _Weight

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
# queries a block in paged prefill: float32 scores of every head over the
# slot's whole view are heads x block x view x 4 B (92 MB at 32 x 256 x 2816)
_PREFILL_Q_BLOCK = 256


class Xing4Config:
    """The published keys under their published names. `PRESETS["tiny"]` is
    the CPU tests' size; the published sizes are the defaults."""

    PUBLISHED = dict(
        attention_bias=False, ep_size=1, first_k_dense_replace=2,
        hidden_act="silu", hidden_size=3584, intermediate_size=9216,
        kv_lora_rank=512, max_position_embeddings=262144,
        moe_intermediate_size=1024, moe_layer_freq=1, n_group=1,
        n_routed_experts=64, n_shared_experts=1, norm_topk_prob=True,
        num_attention_heads=32, num_experts_per_tok=4, num_hidden_layers=40,
        num_key_value_heads=32, hc_mult=4, hc_sinkhorn_iters=20,
        hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
        q_lora_rank=768, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rms_norm_eps=1e-6, rope_theta=10000,
        rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                          mscale_all_dim=1,
                          original_max_position_embeddings=4096,
                          type="yarn"),
        routed_scaling_factor=2, scoring_func="sigmoid",
        tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc",
        v_head_dim=128, vocab_size=131072)
    PRESETS = {
        "tiny": dict(
            hidden_size=64, intermediate_size=160, kv_lora_rank=32,
            max_position_embeddings=4096, moe_intermediate_size=32,
            n_routed_experts=8, num_attention_heads=4,
            num_experts_per_tok=2, num_hidden_layers=3,
            first_k_dense_replace=1, num_key_value_heads=4, q_lora_rank=48,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            vocab_size=512, hc_sinkhorn_iters=4,  # fewer rounds: CPU compiles
            rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64,
                              mscale=1, mscale_all_dim=1,
                              original_max_position_embeddings=64,
                              type="yarn")),
    }

    def __init__(self, dtype="float32", initializer_range=0.02,
                 experts_held=None, **keys):
        unknown = sorted(set(keys) - set(self.PUBLISHED))
        if unknown:
            raise ValueError(f"Xing4Config: not keys of the published "
                             f"config: {unknown}")
        for k, v in {**self.PUBLISHED, **keys}.items():
            setattr(self, k, v)
        self.dtype = dtype
        self.initializer_range = initializer_range
        # which routed experts this chip holds (all of them by default)
        self.experts_held = tuple(experts_held) if experts_held \
            else (0, self.n_routed_experts)
        refused = [f"{k}={getattr(self, k)!r}" for k, want in (
            ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
            ("n_group", 1), ("topk_group", 1), ("moe_layer_freq", 1),
            ("hidden_act", "silu"), ("attention_bias", False),
            ("tie_word_embeddings", False)) if getattr(self, k) != want]
        if self.rope_scaling.get("type") != "yarn":
            refused.append(f"rope_scaling.type="
                           f"{self.rope_scaling.get('type')!r}")
        if refused:
            raise ValueError("Xing4Config: no code for " + ", ".join(refused))

    @classmethod
    def preset(cls, name, **overrides):
        return cls(**{**cls.PRESETS[name], **overrides})

    def as_dict(self):
        return {k: getattr(self, k) for k in self.PUBLISHED}


# ------------------------------------------------------------------ rotary --
def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg):
    """Constants of the config: the YaRN frequencies of the rotated dims
    (as they are for the fast dims below the correction range, divided by
    `factor` for the slow ones above it, a linear ramp between)."""
    rs = cfg.rope_scaling
    dim, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        extra = 1.0 / base ** (2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(extra / float(rs["factor"]) * ramp + extra * (1 - ramp))
    return jnp.asarray(out, _F32)


def _rope(x, positions, cfg):
    """x [B, T, (H,) dr] rotated by adjacent pairs at positions [B, T], in
    float32."""
    rs = cfg.rope_scaling
    f = float(rs["factor"])
    amp = _F32(_yarn_mscale(f, float(rs["mscale"]))
               / _yarn_mscale(f, float(rs["mscale_all_dim"])))
    ang = positions.astype(_F32)[..., None] * yarn_inv_freq(cfg)
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    if x.ndim == 4:
        cos, sin = cos[:, :, None], sin[:, :, None]
    x = x.astype(_F32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def softmax_scale(cfg):
    rs = cfg.rope_scaling
    m = _yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


# --------------------------------------------------------------- attention --
class Xing4Attention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        d, H, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        init, one = Normal(0.0, cfg.initializer_range), Constant(1.0)
        self.q_a_proj = _Weight((d, cfg.q_lora_rank), init, dt)
        self.q_a_layernorm = _Weight((cfg.q_lora_rank,), one, dt)
        self.q_b_proj = _Weight((cfg.q_lora_rank, H * (dn + dr)), init, dt)
        self.kv_a_proj = _Weight((d, cfg.kv_lora_rank + dr), init, dt)
        self.kv_a_layernorm = _Weight((cfg.kv_lora_rank,), one, dt)
        self.kv_b_proj = _Weight((cfg.kv_lora_rank, H * (dn + dv)), init, dt)
        self.o_proj = _Weight((H * dv, d), init, dt)

    def _project(self, u, positions):
        """(q_nope [B,T,H,dn], q_rope [B,T,H,dr] rotated, c_kv [B,T,rkv]
        normalised, k_rope [B,T,dr] rotated), in u's dtype."""
        cfg = self.cfg
        B, T, _ = u.shape
        H, dn, rkv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                      cfg.kv_lora_rank)
        dt, eps = u.dtype, cfg.rms_norm_eps
        c_q = _rms(_mm(u, self.q_a_proj.weight._data),
                   self.q_a_layernorm.weight._data, eps).astype(dt)
        q = _mm(c_q, self.q_b_proj.weight._data).reshape(B, T, H, -1)
        kv = _mm(u, self.kv_a_proj.weight._data)
        c_kv = _rms(kv[..., :rkv], self.kv_a_layernorm.weight._data, eps)
        return (q[..., :dn].astype(dt),
                _rope(q[..., dn:], positions, cfg).astype(dt),
                c_kv.astype(dt),
                _rope(kv[..., rkv:], positions, cfg).astype(dt))

    def _expanded(self, q_nope, q_rope, c_kv, k_rope, keep):
        """Attention with keys and values expanded from the latent rows
        c_kv [B,S,rkv], k_rope [B,S,dr]; ``keep(first, n)`` -> bool
        [B, n, S], the keys queries first..first+n-1 may read. Float32
        scores and softmax; queries a block at a time when they are many."""
        cfg = self.cfg
        B, T, H, dn = q_nope.shape
        S, dt = c_kv.shape[1], q_nope.dtype
        kvb = _mm(c_kv, self.kv_b_proj.weight._data).astype(dt).reshape(
            B, S, H, dn + cfg.v_head_dim)
        k_nope, v = kvb[..., :dn], kvb[..., dn:]
        scale = _F32(softmax_scale(cfg))

        def rows(first, qn, qr):
            s = (jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope,
                            preferred_element_type=_F32)
                 + jnp.einsum("bqhd,bkd->bhqk", qr, k_rope,
                              preferred_element_type=_F32)) * scale
            s = jnp.where(keep(first, qn.shape[1])[:, None], s, -jnp.inf)
            return jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(s, axis=-1).astype(dt), v,
                              preferred_element_type=_F32).astype(dt)

        Q = _PREFILL_Q_BLOCK
        if T <= Q or T % Q:
            o = rows(jnp.int32(0), q_nope, q_rope)
        else:
            def blocked(x):  # [B,T,H,D] -> [T/Q, B, Q, H, D]
                return jnp.moveaxis(x.reshape(B, T // Q, Q, H, -1), 1, 0)
            o = jax.lax.map(lambda a: rows(*a), (
                jnp.arange(0, T, Q, dtype=jnp.int32), blocked(q_nope),
                blocked(q_rope)))
            o = jnp.moveaxis(o, 0, 1)
        return _mm(o.reshape(B, T, -1), self.o_proj.weight._data)

    def forward(self, u, positions, cache=None, cache_offset=None,
                seq_lens=None, block_tables=None, paged_kernel=None):
        """u [B, T, d] (normed) -> (y [B, T, d] float32, new cache)."""
        cfg = self.cfg
        B, T, _ = u.shape
        q_nope, q_rope, c_kv, k_rope = self._project(u, positions)
        if cache is None:
            causal = jnp.tril(jnp.ones((T, T), bool))

            def keep(first, n):
                return jax.lax.dynamic_slice_in_dim(causal, first, n)[None]
            return self._expanded(q_nope, q_rope, c_kv, k_rope, keep), None
        (pool,) = cache
        rkv, dr, W = cfg.kv_lora_rank, cfg.qk_rope_head_dim, pool.shape[2]
        with _scope("kv_write"):
            row = jnp.concatenate(
                [c_kv, k_rope, jnp.zeros((B, T, W - rkv - dr), c_kv.dtype)],
                axis=-1).reshape(B * T, W)
            blk, at = _kv_pool.span_rows(block_tables, cache_offset,
                                         seq_lens, T, pool.shape[1])
            pool = _kv_pool.write_rows(pool, row, blk, at)
        if paged_kernel is None:
            view = _kv_pool.latent_view(pool, block_tables)  # [B, S, W]
            jpos = jnp.arange(view.shape[1], dtype=jnp.int32)[None, None]
            off = cache_offset.astype(jnp.int32)[:, None, None]
            sl = seq_lens.astype(jnp.int32)[:, None, None]

            def keep(first, n):
                qpos = off + first + jnp.arange(n, dtype=jnp.int32)[None, :,
                                                                    None]
                return (jpos <= qpos) & (jpos < sl)
            y = self._expanded(q_nope, q_rope, view[..., :rkv],
                               view[..., rkv:rkv + dr], keep)
            return y, (pool,)
        if T != 1:
            raise TypeError("Xing4Attention: the absorbed decode path "
                            "takes one query row a slot; a span of "
                            f"{T} (spec-decode verify) has no kernel for a "
                            "'latent' cache")
        H, dn, dt = cfg.num_attention_heads, cfg.qk_nope_head_dim, u.dtype
        w_kvb = self.kv_b_proj.weight._data.reshape(rkv, H, -1)
        with _scope("mla_absorb"):
            q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0],
                               w_kvb[..., :dn],
                               preferred_element_type=_F32).astype(dt)
            q = jnp.concatenate(
                [q_lat, q_rope[:, 0],
                 jnp.zeros((B, H, W - rkv - dr), dt)], axis=-1)
        o = _pallas_ops.mla_paged_attention(
            q, pool, block_tables, seq_lens, softmax_scale(cfg),
            kernel=paged_kernel)
        with _scope("mla_absorb"):
            o = jnp.einsum("bhr,rhv->bhv", o[..., :rkv], w_kvb[..., dn:],
                           preferred_element_type=_F32).astype(dt)
        return _mm(o.reshape(B, 1, -1), self.o_proj.weight._data), (pool,)


# ------------------------------------------------------- hyper-connections --
class HyperConnection(nn.Layer):
    """One sublayer's maps: ``coeffs(X)`` -> (H_pre [n, B, T], H_post
    [n, B, T], H_res [n, n, B, T]) in float32, the coefficient axes first
    so that the Sinkhorn rounds are elementwise over the tokens."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        n, d = cfg.hc_mult, cfg.hidden_size
        f32 = "float32"  # coefficient maths is float32 whatever is served
        self.norm = _Weight((n * d,), Constant(1.0), f32)
        self.phi = _param(self, (n * d, 2 * n + n * n),
                          Normal(0.0, cfg.initializer_range), f32)
        # the data-dependent part moves the coefficients by ~0.1 (a logit
        # by ~0.4): z has a deviation of initializer_range * sqrt(n d)
        self.alpha = _param(
            self, (3,), Constant(0.4 / (cfg.initializer_range
                                        * math.sqrt(n * d))), f32)
        self.bias = _param(self, (2 * n + n * n,), Normal(0.0, 0.5), f32)

    def coeffs(self, streams):
        cfg = self.cfg
        n, eps = cfg.hc_mult, _F32(cfg.hc_eps)
        xt = _rms(jnp.concatenate(streams, axis=-1), self.norm.weight._data,
                  cfg.hc_eps)
        # coefficient axis first: [2n + n*n, B, T], tokens on the lanes
        z = jnp.moveaxis(jnp.dot(xt, self.phi._data, precision=_HI), -1, 0)
        alpha = self.alpha._data
        b = self.bias._data[:, None, None]
        pre = jax.nn.sigmoid(alpha[0] * z[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * z[n:2 * n] + b[n:2 * n])
        m = jnp.exp(jnp.clip(alpha[2] * z[2 * n:] + b[2 * n:],
                             _F32(cfg.mhc_h_res_clamp_min),
                             _F32(cfg.mhc_h_res_clamp_max))
                    ).reshape((n, n) + z.shape[1:])
        # sums over the 4 rows / columns as adds of slices: every op of a
        # round is elementwise over the tokens, so the rounds fuse
        for _ in range(cfg.hc_sinkhorn_iters):
            m = m / (sum(m[i] for i in range(n)) + eps)[None]
            m = m / (sum(m[:, j] for j in range(n)) + eps)[:, None]
        return pre, post, m


def _hc_sublayer(hc, norm_w, eps, streams, fn):
    """X' = H_res X + H_post^T F(RMSNorm(H_pre X)) over the list of n
    float32 streams [B, T, d]; ``fn`` maps the float32 normed input to
    float32 (casting it to its weights' dtype where it multiplies)."""
    n = len(streams)
    with _scope("hc_mix"):
        pre, post, res = hc.coeffs(streams)
        u = sum(pre[i][..., None] * streams[i] for i in range(n))
        u = _rms(u, norm_w._data, eps)
    y = fn(u)
    with _scope("hc_mix"):
        return [sum(res[i][j][..., None] * streams[j] for j in range(n))
                + post[i][..., None] * y for i in range(n)]


# ------------------------------------------------------------------ layers --
class Xing4MLP(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        init = Normal(0.0, cfg.initializer_range)
        d, F, dt = cfg.hidden_size, cfg.intermediate_size, cfg.dtype
        self.gate_proj = _Weight((d, F), init, dt)
        self.up_proj = _Weight((d, F), init, dt)
        self.down_proj = _Weight((F, d), init, dt)

    def forward(self, u):
        return swiglu(u, self.gate_proj.weight._data,
                      self.up_proj.weight._data, self.down_proj.weight._data)


class Xing4Layer(nn.Layer):
    def __init__(self, cfg, idx):
        super().__init__()
        self.cfg = cfg
        one, dt = Constant(1.0), cfg.dtype
        self.attn_hc = HyperConnection(cfg)
        self.input_layernorm = _Weight((cfg.hidden_size,), one, dt)
        self.self_attn = Xing4Attention(cfg)
        self.ffn_hc = HyperConnection(cfg)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,), one, dt)
        self.is_moe = idx >= cfg.first_k_dense_replace
        if self.is_moe:
            self.mlp = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                n_shared=cfg.n_shared_experts,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob,
                experts_held=cfg.experts_held,
                init_std=cfg.initializer_range, dtype=dt)
        else:
            self.mlp = Xing4MLP(cfg)

    def forward(self, streams, positions, valid=None, moe_kernel=None,
                **cache_args):
        eps = self.cfg.rms_norm_eps
        new_cache = []

        dt = self.input_layernorm.weight._data.dtype

        def attn(u):
            y, nc = self.self_attn(u.astype(dt), positions, **cache_args)
            new_cache.append(nc)
            return y

        def mlp(u):
            if self.is_moe:  # the router reads the float32 input
                return self.mlp(u, valid=valid, kernel=moe_kernel)._data
            return self.mlp(u.astype(dt))

        streams = _hc_sublayer(self.attn_hc, self.input_layernorm.weight,
                               eps, streams, attn)
        streams = _hc_sublayer(self.ffn_hc,
                               self.post_attention_layernorm.weight, eps,
                               streams, mlp)
        return streams, new_cache[0]


class Xing4Model(nn.Layer):
    """forward(input_ids [B, T]) -> logits [B, T, V] float32, or with the
    paged-cache arguments (what `serving.GenerationEngine` passes) ->
    (final-normed hidden [B, T, d], the written pools)."""

    step_counter_names = ("moe_experts_hit",)

    def __init__(self, cfg: Xing4Config):
        super().__init__()
        self.cfg = cfg
        init, dt = Normal(0.0, cfg.initializer_range), cfg.dtype
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size), init,
                                    dt)
        self.layers = nn.LayerList([Xing4Layer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), Constant(1.0), dt)
        self.lm_head = _Weight((cfg.vocab_size, cfg.hidden_size), init, dt)
        self._moe_layers = [l.mlp for l in self.layers if l.is_moe]

    # -- what serving.GenerationEngine asks of a decoder -------------------
    @property
    def max_positions(self):
        return self.cfg.max_position_embeddings

    def kv_cache_spec(self):
        """One latent row a token a layer: c_kv and the rotated key."""
        return _kv_pool.CacheSpec(
            "latent", [(self.cfg.kv_lora_rank, self.cfg.qk_rope_head_dim)]
            * len(self.layers))

    def serving_head(self):
        return self.lm_head.weight, _head_logits()

    def step_counters(self):
        """Device-side counts of the last forward, by name (taken once: the
        arrays belong to the trace that made them)."""
        hit = sum(m.last_experts_hit for m in self._moe_layers)
        for m in self._moe_layers:
            m.last_experts_hit = None
        return {"moe_experts_hit": hit}

    def host_step_counts(self, n_active):
        n = len(self._moe_layers)
        return {"moe_layer_steps": n,
                "moe_routed_rows": n * n_active * self.cfg.num_experts_per_tok}

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_offsets=None, seq_lens=None, block_tables=None,
                paged_kernel=None, paged_mesh=None, moe_kernel=None):
        if paged_mesh is not None:
            raise TypeError("Xing4Model: a 'latent' cache has no mesh route")
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        B, T = ids.shape

        def arr(t):
            return t._data if isinstance(t, Tensor) else t

        positions = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None], (B, T)) \
            if position_ids is None else arr(position_ids)
        x = self.embed_tokens.weight._data[ids].astype(_F32)
        streams = [x] * self.cfg.hc_mult
        valid, new_caches = None, []
        if caches is not None:
            offs, sl = arr(cache_offsets), arr(seq_lens)
            valid = (offs.astype(jnp.int32)[:, None]
                     + jnp.arange(T, dtype=jnp.int32)[None]
                     < sl.astype(jnp.int32)[:, None])
        for i, layer in enumerate(self.layers):
            cache_args = {} if caches is None else dict(
                cache=tuple(arr(p) for p in caches[i]), cache_offset=offs,
                seq_lens=sl, block_tables=arr(block_tables),
                paged_kernel=paged_kernel)
            streams, nc = layer(streams, positions, valid=valid,
                                moe_kernel=moe_kernel, **cache_args)
            if nc is not None:
                new_caches.append(tuple(Tensor(p) for p in nc))
        h = _rms(sum(streams), self.norm.weight._data,
                 self.cfg.rms_norm_eps).astype(self.norm.weight._data.dtype)
        if caches is not None:
            return Tensor(h), new_caches
        return Tensor(_head_logits()(
            h.reshape(B * T, -1), self.lm_head.weight._data
        ).reshape(B, T, -1))
