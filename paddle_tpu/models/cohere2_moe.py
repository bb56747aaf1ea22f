"""Cohere2-MoE decoder (CohereLabs/command-a-plus-05-2026 `config.json`,
`model_type` `cohere2_moe`), the served forward pass: grouped-query
attention (`num_attention_heads` query heads over `num_key_value_heads`
key/value heads) in layers of two kinds, `sliding_attention` (rotary
positions on adjacent pairs, each query sees the last `sliding_window` keys,
its own among them) and `full_attention` (no positional signal, every
earlier key); a PARALLEL block, ``h + Attn(LN h) + Experts(LN h)`` with one
bias-free LayerNorm a layer; an expert layer of `num_experts` sigmoid-routed
experts, `num_experts_per_tok` a token, beside `num_shared_experts` shared
experts whose outputs are averaged (`nn.moe.DroplessMoE`); a tied head
scaled by `logit_scale`.

The equations are those of `benchmark/reference/cohere2_moe.py`, which is
written from the config alone; here they run in the weights' dtype (bf16
when served) with float32 where the reference's result depends on it: the
residual stream, the norm, the rotation, the router's scores, softmax and
every matmul's accumulation. What the config does not settle is listed
there and in the benchmark's configuration file (`assumed`).

The two kinds of layer keep two kinds of cache state
(`ops.kv_pool.CacheSpec` with `windows`): a full layer's pools hold every
row of a sequence, a window layer's a ring of the last `sliding_window`
rows a slot. Three attention paths, one set of weights:

* no cache: a walk over key blocks with a running softmax, the window
  layers' walk bounded by the window — the path tests compare with the
  reference;
* paged prefill (``paged_kernel=None``): the window's rows are written into
  both kinds of pool (a prompt longer than the window lands only its last
  `sliding_window` rows in the ring), and attention runs the same walk over
  the call's own keys: scores ``[heads, L, S]`` are never formed. It
  attends to nothing the call did not bring, so the engine refuses what
  would need that of such a decoder (prefix sharing, chunked prefill);
* paged decode (``paged_kernel`` "xla" | "interpret" | "pallas"):
  `ops.pallas_ops.paged_attention`, a key/value head's queries against a
  span of its keys in one dot; the window layers' call walks their ring
  (`paged_attention_window` in a trace).

``moe_kernel`` (the engine's `pallas_ops.select_grouped_kernel`) goes to every
expert layer: each call's shape decides there between the `grouped_matmul`
kernel (a decode step's rows) and `lax.ragged_dot` (a prompt's chunk).

The chip may hold a share of the experts (`experts_held`) and of the
vocabulary (a smaller `vocab_size`): what an expert-parallel deployment's
chip computes before the exchange; nothing here stands in for the others.
The forward is written on the parameters' arrays (`Tensor._data`): the
autograd tape does not see it. The checkpoint's vision tower has no key in
the language model's config and is not here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn.initializer import Constant, Normal
from ..nn.moe.dropless import DroplessMoE
from ..ops import kv_pool as _kv_pool
from ..ops import pallas_ops as _pallas_ops
from ..profiler.spans import scope as _scope
from .xing4 import _mm, _Weight

_F32 = jnp.float32
# queries and keys a block of the walk: float32 scores of every head for one
# pair of blocks are heads x 512 x 512 x 4 B (134 MB at 128 heads)
_WALK_BLOCK = 512
# tokens of a prompt the expert layer takes at a time (DroplessMoE
# `rows_at_a_time`): 1024 x 8 routed rows x 2 x 4096 float32 are 268 MB
_MOE_ROWS = 1024


class Cohere2MoeConfig:
    """The published keys under their published names; the published sizes
    are the defaults, `PRESETS["tiny"]` is the CPU tests' size. `layer_types`
    may be longer than `num_hidden_layers`: the first that many are the
    model's (a cut in depth keeps the published list). `experts_held` =
    (lo, hi): the routed experts this chip holds of `num_experts`."""

    PUBLISHED = dict(
        attention_bias=False, expert_selection_fn="sigmoid",
        first_k_dense_replace=0, head_dim=128, hidden_act="silu",
        hidden_size=4096, intermediate_size=4096, layer_norm_eps=1e-5,
        layer_switch=4,
        layer_types=["sliding_attention", "sliding_attention",
                     "sliding_attention", "full_attention"] * 8,
        logit_scale=1, max_position_embeddings=200000,
        norm_topk_prob=True, num_attention_heads=128, num_experts=128,
        num_experts_per_tok=8, num_hidden_layers=32, num_key_value_heads=8,
        num_shared_experts=4,
        order_of_interleaved_layers="local_attn_first",
        position_embedding_type="rope_gptj",
        prefix_dense_intermediate_size=16384,
        prefix_dense_sliding_window_pattern=1, rms_norm_eps=None,
        rope_parameters=dict(rope_theta=50000, rope_type="default"),
        rope_theta=50000, rotary_pct=1,
        shared_expert_combination_strategy="average", sliding_window=4096,
        tf_legacy_loss=False, tie_word_embeddings=True,
        use_embedding_sharing=True, use_gated_activation=True,
        use_parallel_block=True, use_parallel_embedding=False,
        use_qk_norm=False, vocab_size=262144)
    PRESETS = {
        "tiny": dict(
            head_dim=16, hidden_size=64, intermediate_size=32,
            max_position_embeddings=4096, num_attention_heads=8,
            num_experts=16, num_experts_per_tok=2, num_hidden_layers=4,
            num_key_value_heads=2, num_shared_experts=2, sliding_window=24,
            vocab_size=512),
    }

    def __init__(self, dtype="float32", initializer_range=0.02,
                 experts_held=None, **keys):
        unknown = sorted(set(keys) - set(self.PUBLISHED))
        if unknown:
            raise ValueError(f"Cohere2MoeConfig: not keys of the published "
                             f"config: {unknown}")
        for k, v in {**self.PUBLISHED, **keys}.items():
            setattr(self, k, v)
        self.dtype = dtype
        self.initializer_range = initializer_range
        self.experts_held = tuple(experts_held) if experts_held \
            else (0, self.num_experts)
        refused = [f"{k}={getattr(self, k)!r}" for k, want in (
            ("expert_selection_fn", "sigmoid"),
            ("shared_expert_combination_strategy", "average"),
            ("use_parallel_block", True), ("first_k_dense_replace", 0),
            ("position_embedding_type", "rope_gptj"), ("rotary_pct", 1),
            ("hidden_act", "silu"), ("use_gated_activation", True),
            ("tie_word_embeddings", True), ("attention_bias", False),
            ("use_qk_norm", False)) if getattr(self, k) != want]
        if self.rope_parameters.get("rope_type") != "default":
            refused.append(f"rope_parameters.rope_type="
                           f"{self.rope_parameters.get('rope_type')!r}")
        kinds = list(self.layer_types)[:self.num_hidden_layers]
        if len(kinds) < self.num_hidden_layers or set(kinds) - {
                "sliding_attention", "full_attention"}:
            refused.append(f"layer_types={kinds!r} for "
                           f"{self.num_hidden_layers} layers")
        if self.num_attention_heads % self.num_key_value_heads:
            refused.append(f"num_attention_heads={self.num_attention_heads}"
                           f" over {self.num_key_value_heads} key/value "
                           "heads")
        if refused:
            raise ValueError("Cohere2MoeConfig: no code for "
                             + ", ".join(refused))
        self.layer_kinds = kinds

    @classmethod
    def preset(cls, name, **overrides):
        return cls(**{**cls.PRESETS[name], **overrides})

    def as_dict(self):
        return {k: getattr(self, k) for k in self.PUBLISHED}


def _layer_norm(x, w, eps):
    """LayerNorm with a weight and no bias, float32 statistics; returns
    float32."""
    x = x.astype(_F32)
    x = x - x.mean(-1, keepdims=True)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                             + _F32(eps)) * w.astype(_F32)


def _rope(x, positions, theta):
    """x [B, T, H, D] rotated by adjacent pairs (2i, 2i+1) over all D dims
    at positions [B, T], in float32: inv_freq_i = theta^(-2i/D)."""
    D = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, D, 2, dtype=_F32) / D))
    ang = positions.astype(_F32)[:, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(_F32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


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


class Cohere2MoeAttention(nn.Layer):
    def __init__(self, cfg, sliding):
        super().__init__()
        self.cfg, self.sliding = cfg, sliding
        self.window = int(cfg.sliding_window) if sliding else None
        d, dt = cfg.hidden_size, cfg.dtype
        Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        init = Normal(0.0, cfg.initializer_range)
        self.q_proj = _Weight((d, Hq * D), init, dt)
        self.k_proj = _Weight((d, Hkv * D), init, dt)
        self.v_proj = _Weight((d, Hkv * D), init, dt)
        self.o_proj = _Weight((Hq * D, d), init, dt)

    def forward(self, u, positions, cache=None, cache_offset=None,
                seq_lens=None, block_tables=None, paged_kernel=None):
        """u [B, T, d] (normed) -> (y [B, T, d] float32, new cache)."""
        cfg = self.cfg
        B, T, _ = u.shape
        Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        dt = u.dtype
        q = _mm(u, self.q_proj.weight._data).reshape(B, T, Hq, D)
        k = _mm(u, self.k_proj.weight._data).reshape(B, T, Hkv, D)
        v = _mm(u, self.v_proj.weight._data).reshape(B, T, Hkv, D).astype(dt)
        if self.sliding:  # a full layer has no positional signal at all
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        q, k = q.astype(dt), k.astype(dt)
        if cache is not None:
            k_pool, v_pool = cache
            with _scope("kv_write"):
                cache = _kv_pool.write_span(
                    k_pool, v_pool, k, v, block_tables, cache_offset,
                    seq_lens, window=self.window)
        if cache is not None and paged_kernel is not None:
            o = _pallas_ops.paged_attention(
                q, cache[0], cache[1], block_tables, seq_lens, cache_offset,
                kernel=paged_kernel, window=self.window)
        else:
            valid = jnp.ones((B, T), bool) if seq_lens is None else \
                positions < seq_lens.astype(jnp.int32)[:, None]
            o = _walk_attention(q, k, v, positions, positions, valid,
                                self.window, _WALK_BLOCK)
        return _mm(o.reshape(B, T, Hq * D), self.o_proj.weight._data), cache


class Cohere2MoeLayer(nn.Layer):
    def __init__(self, cfg, idx):
        super().__init__()
        self.cfg = cfg
        self.sliding = cfg.layer_kinds[idx] == "sliding_attention"
        self.input_layernorm = _Weight((cfg.hidden_size,), Constant(1.0),
                                       cfg.dtype)
        self.self_attn = Cohere2MoeAttention(cfg, self.sliding)
        # every shared expert as wide as a routed one, no selection bias
        # (the config has no key for either), scaling 1
        self.mlp = DroplessMoE(
            cfg.hidden_size, cfg.intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, n_shared=cfg.num_shared_experts,
            routed_scaling_factor=1.0, norm_topk_prob=cfg.norm_topk_prob,
            experts_held=cfg.experts_held, init_std=cfg.initializer_range,
            dtype=cfg.dtype, select_bias=False, shared_combine="average",
            rows_at_a_time=_MOE_ROWS)

    def forward(self, h, positions, valid=None, moe_kernel=None,
                **cache_args):
        """h [B, T, d] float32 -> (h + Attn(LN h) + Experts(LN h), cache)."""
        w = self.input_layernorm.weight._data
        u = _layer_norm(h, w, self.cfg.layer_norm_eps)
        with _scope("attn_window" if self.sliding else "attn_full"):
            a, cache = self.self_attn(u.astype(w.dtype), positions,
                                      **cache_args)
        # the router reads the float32 input
        return h + a + self.mlp(u, valid=valid,
                                kernel=moe_kernel)._data, cache


def _tied_logits(scale):
    """bf16 x bf16 with float32 accumulation against the embedding table as
    it is stored (no float32 copy, no transpose), times `logit_scale`."""
    def logits(hidden, w):
        out = jax.lax.dot_general(hidden, w, (((1,), (1,)), ((), ())),
                                  preferred_element_type=_F32)
        return out if scale == 1 else out * _F32(scale)
    return logits


class Cohere2MoeModel(nn.Layer):
    """forward(input_ids [B, T]) -> logits [B, T, V] float32, or with the
    paged-cache arguments (what `serving.GenerationEngine` passes;
    ``block_tables`` a list, each layer's own table) -> (final-normed hidden
    [B, T, d], the written pools)."""

    step_counter_names = ("moe_experts_hit",)

    def __init__(self, cfg: Cohere2MoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _Weight(
            (cfg.vocab_size, cfg.hidden_size),
            Normal(0.0, cfg.initializer_range), cfg.dtype)
        self.layers = nn.LayerList([Cohere2MoeLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), Constant(1.0), cfg.dtype)
        self._logits = _tied_logits(cfg.logit_scale)

    # -- what serving.GenerationEngine asks of a decoder -------------------
    @property
    def max_positions(self):
        return self.cfg.max_position_embeddings

    def kv_cache_spec(self):
        """A K and a V row of the key/value heads a token a layer; the
        sliding layers keep the last `sliding_window` of them in a ring."""
        cfg = self.cfg
        return _kv_pool.CacheSpec(
            "heads", [(cfg.num_key_value_heads, cfg.head_dim)]
            * len(self.layers),
            windows=[l.self_attn.window for l in self.layers],
            q_per_kv=cfg.num_attention_heads // cfg.num_key_value_heads)

    def serving_head(self):
        return self.embed_tokens.weight, self._logits

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
                * self.cfg.num_experts_per_tok}

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_offsets=None, seq_lens=None, block_tables=None,
                paged_kernel=None, paged_mesh=None, moe_kernel=None):
        if paged_mesh is not None:
            raise TypeError("Cohere2MoeModel: a cache with window layers "
                            "has no mesh route")

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
                          **cache_args)
            if nc is not None:
                new_caches.append(tuple(Tensor(p) for p in nc))
        w = self.norm.weight._data
        h = _layer_norm(h, w, self.cfg.layer_norm_eps).astype(w.dtype)
        if caches is not None:
            return Tensor(h), new_caches
        return Tensor(self._logits(
            h.reshape(B * T, -1), self.embed_tokens.weight._data
        ).reshape(B, T, -1))
