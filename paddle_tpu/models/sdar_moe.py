"""SDAR-MoE decoder (JetLM/SDAR-30B-A3B-Chat `config.json`, `model_type`
`sdar_moe`), the served forward pass: a Qwen3-MoE block — ``h = x +
Attn(RMSNorm x)``, ``y = h + Experts(RMSNorm h)`` — with grouped-query
attention (`num_attention_heads` query heads over `num_key_value_heads`
key/value heads, an RMSNorm over each head's queries and keys before the
rotation, rotary on the halves ``(i, i + head_dim / 2)``), `num_experts`
softmax-routed experts of width `moe_intermediate_size`, `num_experts_per_tok`
a token, no shared expert (`nn.moe.DroplessMoE` with ``router="softmax"``),
a last RMSNorm and an untied head.

What sets it apart is how it generates (`generation_spec()`): by diffusion
over blocks of `block_length` B tokens. The attention mask is BLOCK-CAUSAL:
the query at position i sees the key at j iff ``j // B <= i // B`` — its own
block whole, in both directions, and every earlier block. A block starts as
B mask tokens; a denoise forward runs the block's B positions against the
cache of the earlier blocks, reads the logits at each masked position for
that position's OWN token (no shift) and unmasks some; when nothing is
masked a commit forward over the clean block writes its keys and values for
good (`serving.GenerationEngine._block_pure` drives it; the rows a denoise
forward writes at the cursor are overwritten by the next forward).

The equations are those of `benchmark/reference/sdar_moe.py`, which is
written from the config alone; here they run in the weights' dtype (bf16
when served) with float32 where the reference's result depends on it: the
residual stream, the norms, the rotation, the router's probabilities,
softmax and every matmul's accumulation. What the config does not settle is
listed there and in the benchmark's configuration file (`assumed`).

Three attention paths, one set of weights:

* no cache: `_blocks._walk_attention` (a walk over key blocks with a
  running softmax), every query standing at its block's last position —
  the path tests compare with the reference;
* paged prefill (``paged_kernel=None``): the call's rows are written into
  the pools and attention runs the same walk over the call's own keys. It
  attends to nothing the call did not bring, so the engine refuses what
  would need that of such a decoder (prefix sharing, chunked prefill);
* a block step (``paged_kernel`` "xla" | "interpret" | "pallas"):
  `ops.pallas_ops.paged_attention(block_span=True)`, a key/value head's
  ``q_per_kv x B`` query rows against a span of its keys in one dot.

``moe_kernel`` (the engine's `pallas_ops.select_grouped_kernel`) goes to
every expert layer beside it: there each call's shape decides between the
`grouped_matmul` kernel (a block step's rows) and `lax.ragged_dot` (a
prompt's).

The forward is written on the parameters' arrays (`Tensor._data`): the
autograd tape does not see it.
"""
from __future__ import annotations

import typing

import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn.initializer import Constant, Normal
from ..nn.moe.dropless import DroplessMoE
from ..ops import kv_pool as _kv_pool
from ..ops import pallas_ops as _pallas_ops
from ..profiler.spans import scope as _scope
from ._blocks import (_head_logits, _mm, _rms, _rope_halves,
                      _walk_attention, _WALK_BLOCK, _Weight)

_F32 = jnp.float32


class BlockDiffusion(typing.NamedTuple):
    """How a decoder generates when it is not left to right, a token a
    step: what `generation_spec()` answers `serving.GenerationEngine`."""

    block_length: int
    denoising_steps: int
    strategy: str  # a name serving.sampling.unmask_select knows
    confidence_threshold: float
    mask_token_id: int


class SdarMoeConfig:
    """The published keys under their published names (the published sizes
    are the defaults, `PRESETS["tiny"]` is the CPU tests' size), and beside
    them the generation settings, which `config.json` does not hold
    (``generation``: the family's published defaults, `assumed` in the
    benchmark's file)."""

    PUBLISHED = dict(
        attention_bias=False, decoder_sparse_step=1, head_dim=128,
        hidden_act="silu", hidden_size=2048, intermediate_size=6144,
        max_position_embeddings=32768, max_window_layers=48,
        mlp_only_layers=[], model_type="sdar_moe", moe_intermediate_size=768,
        norm_topk_prob=True, num_attention_heads=32, num_experts=128,
        num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4,
        rms_norm_eps=1e-6, rope_scaling=None, rope_theta=1000000,
        sliding_window=None, tie_word_embeddings=False,
        use_sliding_window=False, vocab_size=151936)
    GENERATION = BlockDiffusion(
        block_length=4, denoising_steps=2, strategy="low_confidence_static",
        confidence_threshold=0.9, mask_token_id=151669)
    PRESETS = {
        "tiny": dict(
            head_dim=16, hidden_size=64, intermediate_size=192,
            max_position_embeddings=512, max_window_layers=3,
            moe_intermediate_size=24, num_attention_heads=8, num_experts=16,
            num_experts_per_tok=2, num_hidden_layers=3,
            num_key_value_heads=2, vocab_size=512,
            generation=dict(mask_token_id=509)),
    }

    def __init__(self, dtype="float32", initializer_range=0.02,
                 generation=None, **keys):
        unknown = sorted(set(keys) - set(self.PUBLISHED))
        if unknown:
            raise ValueError(f"SdarMoeConfig: not keys of the published "
                             f"config: {unknown}")
        for k, v in {**self.PUBLISHED, **keys}.items():
            setattr(self, k, v)
        self.dtype = dtype
        self.initializer_range = initializer_range
        self.generation = self.GENERATION._replace(**(generation or {}))
        refused = [f"{k}={getattr(self, k)!r}" for k, want in (
            ("attention_bias", False), ("decoder_sparse_step", 1),
            ("hidden_act", "silu"), ("mlp_only_layers", []),
            ("rope_scaling", None), ("sliding_window", None),
            ("use_sliding_window", False), ("tie_word_embeddings", False))
            if getattr(self, k) != want]
        if self.num_attention_heads % self.num_key_value_heads:
            refused.append(f"num_attention_heads={self.num_attention_heads}"
                           f" over {self.num_key_value_heads} key/value "
                           "heads")
        if self.head_dim % 2:
            refused.append(f"head_dim={self.head_dim} (rotary halves)")
        g = self.generation
        # (the strategy's name is checked where it is read:
        # serving.sampling.unmask_select)
        if not 1 <= g.denoising_steps <= g.block_length \
                or not 0 <= g.mask_token_id < self.vocab_size:
            refused.append(f"generation={g!r} (1 <= denoising_steps <= "
                           "block_length, the mask id in the vocabulary)")
        if refused:
            raise ValueError("SdarMoeConfig: no code for "
                             + ", ".join(refused))

    @classmethod
    def preset(cls, name, **overrides):
        return cls(**{**cls.PRESETS[name], **overrides})

    def as_dict(self):
        return {k: getattr(self, k) for k in self.PUBLISHED}


class SdarMoeAttention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        d, dt = cfg.hidden_size, cfg.dtype
        Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        init = Normal(0.0, cfg.initializer_range)
        self.q_proj = _Weight((d, Hq * D), init, dt)
        self.k_proj = _Weight((d, Hkv * D), init, dt)
        self.v_proj = _Weight((d, Hkv * D), init, dt)
        self.o_proj = _Weight((Hq * D, d), init, dt)
        # one weight of head_dim, shared by the heads
        self.q_norm = _Weight((D,), Constant(1.0), dt)
        self.k_norm = _Weight((D,), Constant(1.0), dt)

    def forward(self, u, positions, cache=None, cache_offset=None,
                seq_lens=None, block_tables=None, paged_kernel=None):
        """u [B, T, d] (normed) -> (y [B, T, d] float32, new cache)."""
        cfg = self.cfg
        B, T, _ = u.shape
        Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        L, eps, dt = cfg.generation.block_length, cfg.rms_norm_eps, u.dtype
        q = _mm(u, self.q_proj.weight._data).reshape(B, T, Hq, D)
        k = _mm(u, self.k_proj.weight._data).reshape(B, T, Hkv, D)
        v = _mm(u, self.v_proj.weight._data).reshape(B, T, Hkv, D).astype(dt)
        q = _rope_halves(_rms(q, self.q_norm.weight._data, eps), positions,
                         cfg.rope_theta).astype(dt)
        k = _rope_halves(_rms(k, self.k_norm.weight._data, eps), positions,
                         cfg.rope_theta).astype(dt)
        if cache is not None:
            with _scope("kv_write"):
                cache = _kv_pool.write_span(cache[0], cache[1], k, v,
                                            block_tables, cache_offset,
                                            seq_lens)
        if cache is not None and paged_kernel is not None:
            if T != L:
                raise TypeError(f"SdarMoeAttention: a block step brings "
                                f"{L} rows a slot, not {T}")
            o = _pallas_ops.paged_attention(
                q, cache[0], cache[1], block_tables, seq_lens, cache_offset,
                kernel=paged_kernel, block_span=True)
        else:
            valid = jnp.ones((B, T), bool) if seq_lens is None else \
                positions < seq_lens.astype(jnp.int32)[:, None]
            # block-causal: a query sees what its block's last position
            # sees causally (the walk's blocks are whole blocks of L)
            o = _walk_attention(q, k, v, positions // L * L + (L - 1),
                                positions, valid, None, _WALK_BLOCK)
        return _mm(o.reshape(B, T, Hq * D), self.o_proj.weight._data), cache


class SdarMoeLayer(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        one = Constant(1.0)
        self.input_layernorm = _Weight((cfg.hidden_size,), one, cfg.dtype)
        self.self_attn = SdarMoeAttention(cfg)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,), one,
                                                cfg.dtype)
        self.mlp = DroplessMoE(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, n_shared=0,
            norm_topk_prob=cfg.norm_topk_prob,
            init_std=cfg.initializer_range, dtype=cfg.dtype,
            select_bias=False, router="softmax")

    def forward(self, h, positions, valid=None, moe_kernel=None,
                **cache_args):
        """h [B, T, d] float32 -> (h + Attn(RMS h), then + Experts(RMS .),
        cache)."""
        eps = self.cfg.rms_norm_eps
        w = self.input_layernorm.weight._data
        with _scope("block_attention"):
            a, cache = self.self_attn(_rms(h, w, eps).astype(w.dtype),
                                      positions, **cache_args)
        h = h + a
        # the router reads the float32 normed input
        u = _rms(h, self.post_attention_layernorm.weight._data, eps)
        return h + self.mlp(u, valid=valid, kernel=moe_kernel)._data, cache


class SdarMoeModel(nn.Layer):
    """forward(input_ids [B, T]) -> logits [B, T, V] float32 at every
    position for that position's own token under the block-causal mask (the
    caller puts the mask id where a position is masked), or with the
    paged-cache arguments (what `serving.GenerationEngine` passes) ->
    (final-normed hidden [B, T, d], the written pools)."""

    step_counter_names = ("moe_experts_hit",)

    def __init__(self, cfg: SdarMoeConfig):
        super().__init__()
        self.cfg = cfg
        init = Normal(0.0, cfg.initializer_range)
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size), init,
                                    cfg.dtype)
        self.layers = nn.LayerList([SdarMoeLayer(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = _Weight((cfg.hidden_size,), Constant(1.0), cfg.dtype)
        # untied, [V, d] as the checkpoints keep it
        self.lm_head = _Weight((cfg.vocab_size, cfg.hidden_size), init,
                               cfg.dtype)
        self._logits = _head_logits()

    # -- what serving.GenerationEngine asks of a decoder -------------------
    @property
    def max_positions(self):
        return self.cfg.max_position_embeddings

    def kv_cache_spec(self):
        """A K and a V row of the key/value heads a token a layer, every
        layer keeping every row."""
        cfg = self.cfg
        return _kv_pool.CacheSpec(
            "heads", [(cfg.num_key_value_heads, cfg.head_dim)]
            * len(self.layers),
            q_per_kv=cfg.num_attention_heads // cfg.num_key_value_heads)

    def serving_head(self):
        return self.lm_head.weight, self._logits

    def generation_spec(self):
        """How this decoder generates: by diffusion over blocks."""
        return self.cfg.generation

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
                * self.cfg.generation.block_length
                * self.cfg.num_experts_per_tok}

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_offsets=None, seq_lens=None, block_tables=None,
                paged_kernel=None, paged_mesh=None, moe_kernel=None):
        if paged_mesh is not None:
            raise TypeError("SdarMoeModel: a block step has no mesh route")

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
                seq_lens=sl, block_tables=arr(block_tables),
                paged_kernel=paged_kernel)
            h, nc = layer(h, positions, valid=valid, moe_kernel=moe_kernel,
                          **cache_args)
            if nc is not None:
                new_caches.append(tuple(Tensor(p) for p in nc))
        w = self.norm.weight._data
        h = _rms(h, w, self.cfg.rms_norm_eps).astype(w.dtype)
        if caches is not None:
            return Tensor(h), new_caches
        return Tensor(self._logits(
            h.reshape(B * T, -1), self.lm_head.weight._data
        ).reshape(B, T, -1))
