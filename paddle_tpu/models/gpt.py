"""GPT model family — the flagship benchmark model.

Reference: the GPT test fixture `python/paddle/fluid/tests/unittests/
auto_parallel_gpt_model.py:625` (GPTModel/GPTForPretraining/
GPTPretrainingCriterion) which is the model behind the north-star Fleet
configs (BASELINE configs 3 & 4).

TPU-first design decisions:
  - attention runs through `scaled_dot_product_attention(is_causal=True)` →
    Pallas flash kernel on TPU; no [T, T] mask materialization.
  - hidden compute in bf16 (set dtype="bfloat16"), LN/softmax accumulate in
    fp32 inside the kernels.
  - TP/PP-ready: `mesh_axes` metadata on parameters lets the Fleet hybrid
    engine shard QKV/FFN weights over the 'model'(='mp') axis and stack
    blocks over 'pipe' (SURVEY §7 step 7).
  - `use_recompute` wraps each block in `jax.checkpoint` (the reference's
    fleet recompute, `fleet/recompute/recompute.py:69`).
"""
from __future__ import annotations

import math

import numpy as np

from .. import nn, ops
from ..nn import functional as F
from ..nn.initializer import Normal
from ..profiler.spans import scope as _scope

class GPTConfig:
    PRESETS = {
        "gpt2-tiny": dict(n_layer=2, n_head=4, d_model=128, seq_len=128),
        "gpt2-tiny-moe": dict(n_layer=2, n_head=4, d_model=128,
                              seq_len=128, moe_num_experts=4),
        "gpt2-small": dict(n_layer=12, n_head=12, d_model=768, seq_len=1024),
        "gpt2-medium": dict(n_layer=24, n_head=16, d_model=1024, seq_len=1024),
        "gpt2-large": dict(n_layer=36, n_head=20, d_model=1280, seq_len=1024),
        "gpt3-1.3B": dict(n_layer=24, n_head=32, d_model=2048, seq_len=2048),
        "gpt3-2.7B": dict(n_layer=32, n_head=32, d_model=2560, seq_len=2048),
        "gpt3-6.7B": dict(n_layer=32, n_head=32, d_model=4096, seq_len=2048),
    }

    def __init__(self, vocab_size=50304, n_layer=12, n_head=12, d_model=768,
                 seq_len=1024, d_ff=None, dropout=0.0, attn_dropout=0.0,
                 dtype="float32", use_recompute=False, recompute_policy=None,
                 initializer_range=0.02, moe_num_experts=0, moe_top_k=2,
                 moe_capacity_factor=1.25, moe_every=1,
                 moe_aux_weight=0.01):
        self.vocab_size = vocab_size
        self.n_layer = n_layer
        self.n_head = n_head
        self.d_model = d_model
        self.seq_len = seq_len
        self.d_ff = d_ff or 4 * d_model
        self.dropout = dropout
        self.attn_dropout = attn_dropout
        self.dtype = dtype
        self.use_recompute = use_recompute
        # None = save nothing (full remat); "dots" = keep MXU matmul
        # outputs and rematerialize only the cheap elementwise tail —
        # ~25-30% less recompute FLOPs for a modest activation-memory cost
        self.recompute_policy = recompute_policy
        self.initializer_range = initializer_range
        # MoE trunk (ISSUE 20): moe_num_experts=0 keeps the dense MLP;
        # >0 swaps every `moe_every`-th block's MLP for nn.moe.MoEMLP.
        # Hyperparameters are validated HERE (structured
        # moe_config_refused + MoEConfigError), not inside a trace —
        # the ep-divisibility half re-checks at layer construction when
        # the mesh is known.
        self.moe_num_experts = int(moe_num_experts)
        self.moe_top_k = int(moe_top_k)
        self.moe_capacity_factor = float(moe_capacity_factor)
        self.moe_every = int(moe_every)
        self.moe_aux_weight = float(moe_aux_weight)
        if self.moe_num_experts > 0:
            from ..nn.moe import validate_moe_config

            validate_moe_config(self.moe_num_experts, self.moe_top_k,
                                self.moe_capacity_factor, op="GPTConfig")

    @classmethod
    def preset(cls, name, **overrides):
        cfg = dict(cls.PRESETS[name])
        cfg.update(overrides)
        return cls(**cfg)

    def num_params(self):
        d, L, V = self.d_model, self.n_layer, self.vocab_size
        return V * d + self.seq_len * d + L * (12 * d * d + 13 * d) + 2 * d

    def flops_per_token(self):
        """Training (fwd+bwd) model FLOPs per token, standard accounting.

        Matches the convention shared by Megatron-LM's formula
        96*B*s*L*h^2*(1 + s/(6h) + V/(16Lh)) — whose V/(16Lh) term IS the
        vocab projection — and PaLM appendix B / nanoGPT `estimate_mfu`
        (6 FLOPs per parameter participating in a matmul, + the O(T)
        attention score/value term). Concretely:
          * transformer blocks + final LN: 6 FLOPs/param,
          * tied LM head: 6*V*d — the [*,d]x[d,V] logits matmul and its
            two backward matmuls are real MXU work (the tied embedding
            weight participates; its forward *lookup* is a gather and
            contributes nothing),
          * position embeddings: excluded (pure lookup),
          * attention scores+values: 12*L*d*T fwd+bwd.
        """
        d, L, V = self.d_model, self.n_layer, self.vocab_size
        block_params = L * (12 * d * d + 13 * d) + 2 * d
        return 6 * (block_params + V * d) + 12 * L * d * self.seq_len


class GPTAttention(nn.Layer):
    """Causal self-attention; fused QKV projection (single MXU matmul)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        d, h = cfg.d_model, cfg.n_head
        self.n_head = h
        self.head_dim = d // h
        init = Normal(0.0, cfg.initializer_range)
        out_init = Normal(0.0, cfg.initializer_range / math.sqrt(2 * cfg.n_layer))
        self.qkv_proj = nn.Linear(d, 3 * d,
                                  weight_attr=nn.ParamAttr(initializer=init))
        self.out_proj = nn.Linear(d, d,
                                  weight_attr=nn.ParamAttr(initializer=out_init))
        self.dropout_p = cfg.attn_dropout
        # TP metadata: qkv column-sharded, out row-sharded over 'mp'
        self.qkv_proj.weight.sharding_spec = (None, "mp")
        self.out_proj.weight.sharding_spec = ("mp", None)

    def forward(self, x, cache=None, cache_offset=None, seq_lens=None,
                block_tables=None, paged_kernel=None, paged_mesh=None):
        B, T, D = x.shape
        qkv = self.qkv_proj(x).reshape([B, T, 3, self.n_head, self.head_dim])
        q, k, v = ops.unbind(qkv, axis=2)
        if cache is not None:
            # Paged-cache path (paddle_tpu.serving, ISSUE 10): `cache` is
            # the SHARED fixed-shape block pool in its device form
            # [num_blocks, block_size, H*Dh] (heads merged: the form the
            # chip stores row by row, ops/kv_pool.py); `block_tables`
            # [B, M] maps each slot's logical block j to a physical pool
            # block, so slots of wildly different lengths (and slots
            # SHARING immutable prefix blocks) live in one buffer with
            # zero copies. The B*T new rows of H*Dh are written into the
            # pool through the table (a prompt span as whole blocks) — in
            # place when the step donates the pools; no view of the whole
            # pool is formed, on the chip that would be a relayout.
            # Attention reads each slot's logical view back out under a
            # causal-by-absolute-position AND valid-length mask, so
            # neither stale rows nor bucket padding leak in. Block 0 is the
            # reserved garbage block: writes for rows outside
            # [0, seq_len) (bucket padding, inactive decode lanes)
            # redirect there so they can never clobber live blocks.
            k_pool, v_pool = cache
            with _scope("kv_write"):
                new_k, new_v = F.paged_kv_write(
                    k_pool, v_pool, k, v, block_tables, cache_offset,
                    seq_lens)
            if paged_kernel in ("pallas", "interpret"):
                # Fused read path (ISSUE 14): the Pallas kernel walks the
                # block table inside the kernel, so the gathered
                # [B, M*bs, H, Dh] view below never materializes. The
                # write above is the same (T rows, garbage-block-0
                # redirect intact); only the O(M*bs) gather is fused.
                # `paged_kernel` is a static per-engine choice
                # (pallas_ops.select_paged_kernel) — never data. Which
                # kernel reads is the span's static T: a prompt span
                # (a bucket of rows, ISSUE 36) folds the slot's keys a
                # block of query rows at a time in `flash_prefill`;
                # decode's one row and a verify span's K + 1 stay with
                # `paged_attention`. Same mask, same operands.
                from ..ops.pallas_ops import prefill_span

                read = F.flash_prefill if prefill_span(T) \
                    else F.paged_attention
                out = read(q, new_k, new_v, block_tables, seq_lens,
                           cache_offset, kernel=paged_kernel,
                           mesh=paged_mesh)
                out = self.out_proj(out.reshape([B, T, D]))
                return out, (new_k, new_v)
            k_view = F.paged_kv_view(new_k, block_tables, self.n_head)
            v_view = F.paged_kv_view(new_v, block_tables, self.n_head)
            S = k_view.shape[1]
            rows = cache_offset.unsqueeze(1) + ops.arange(0, T,
                                                          dtype="int32")
            jpos = ops.arange(0, S, dtype="int32")
            mask = ops.logical_and(
                jpos.unsqueeze(0).unsqueeze(0) <= rows.unsqueeze(-1),
                jpos.unsqueeze(0).unsqueeze(0)
                < seq_lens.unsqueeze(-1).unsqueeze(-1))
            out = F.scaled_dot_product_attention(
                q, k_view, v_view, attn_mask=mask.unsqueeze(1),
                is_causal=False, dropout_p=self.dropout_p,
                training=self.training)
            out = self.out_proj(out.reshape([B, T, D]))
            return out, (new_k, new_v)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, dropout_p=self.dropout_p,
            training=self.training)
        return self.out_proj(out.reshape([B, T, D]))


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = Normal(0.0, cfg.initializer_range)
        out_init = Normal(0.0, cfg.initializer_range / math.sqrt(2 * cfg.n_layer))
        self.fc1 = nn.Linear(cfg.d_model, cfg.d_ff,
                             weight_attr=nn.ParamAttr(initializer=init))
        self.fc2 = nn.Linear(cfg.d_ff, cfg.d_model,
                             weight_attr=nn.ParamAttr(initializer=out_init))
        self.dropout = nn.Dropout(cfg.dropout)
        self.fc1.weight.sharding_spec = (None, "mp")
        self.fc2.weight.sharding_spec = ("mp", None)

    def forward(self, x):
        return self.dropout(self.fc2(F.gelu(self.fc1(x), approximate=True)))


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig, layer_idx=0):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.d_model)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.d_model)
        if cfg.moe_num_experts > 0 and layer_idx % cfg.moe_every == 0:
            from ..nn.moe import MoEMLP

            self.mlp = MoEMLP(
                cfg.d_model, cfg.d_ff, cfg.moe_num_experts,
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                dropout=cfg.dropout, init_std=cfg.initializer_range,
                out_init_std=cfg.initializer_range
                / math.sqrt(2 * cfg.n_layer))
        else:
            self.mlp = GPTMLP(cfg)
        self.dropout = nn.Dropout(cfg.dropout)
        self._recompute = cfg.use_recompute
        self._recompute_policy = getattr(cfg, "recompute_policy", None)

    def _forward(self, x):
        with _scope("attn"):
            x = x + self.dropout(self.attn(self.ln1(x)))
        with _scope("mlp"):
            return x + self.mlp(self.ln2(x))

    def forward(self, x, cache=None, cache_offset=None, seq_lens=None,
                block_tables=None, paged_kernel=None, paged_mesh=None):
        if cache is not None:
            with _scope("attn"):
                a, new_cache = self.attn(self.ln1(x), cache=cache,
                                         cache_offset=cache_offset,
                                         seq_lens=seq_lens,
                                         block_tables=block_tables,
                                         paged_kernel=paged_kernel,
                                         paged_mesh=paged_mesh)
                x = x + self.dropout(a)
            with _scope("mlp"):
                return x + self.mlp(self.ln2(x)), new_cache
        if self._recompute and self.training:
            from ..distributed.fleet.utils import recompute

            return recompute(self._forward, x, layer=self,
                             policy=self._recompute_policy)
        return self._forward(x)


class GPTEmbeddings(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = Normal(0.0, cfg.initializer_range)
        self.word_embeddings = nn.Embedding(
            cfg.vocab_size, cfg.d_model,
            weight_attr=nn.ParamAttr(initializer=init))
        self.position_embeddings = nn.Embedding(
            cfg.seq_len, cfg.d_model,
            weight_attr=nn.ParamAttr(initializer=init))
        self.dropout = nn.Dropout(cfg.dropout)
        self.word_embeddings.weight.sharding_spec = ("mp", None)

    def forward(self, input_ids, position_ids=None):
        T = input_ids.shape[1]
        if position_ids is None:
            position_ids = ops.arange(0, T, dtype="int64").unsqueeze(0)
        return self.dropout(self.word_embeddings(input_ids) +
                            self.position_embeddings(position_ids))


def _tied_logits(hidden, w):
    import jax.numpy as jnp

    return hidden.astype(jnp.float32) @ w.T.astype(jnp.float32)


class GPTModel(nn.Layer):
    """Reference auto_parallel_gpt_model.py GPTModel equivalent."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = GPTEmbeddings(cfg)
        self.blocks = nn.LayerList([GPTBlock(cfg, layer_idx=i)
                                    for i in range(cfg.n_layer)])
        self.ln_f = nn.LayerNorm(cfg.d_model)
        if cfg.dtype != "float32":
            self.to(dtype=cfg.dtype)

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_offsets=None, seq_lens=None, block_tables=None,
                paged_kernel=None, paged_mesh=None):
        if caches is not None and block_tables is None:
            raise TypeError(
                "GPTModel: caches= are the paged KV pools of "
                "paddle_tpu.serving.GenerationEngine and need "
                "block_tables= (with cache_offsets= and seq_lens=); there "
                "is no other cache form. Generate through GenerationEngine "
                "or GenerationServer")
        x = self.embeddings(input_ids, position_ids)
        if caches is not None:
            new_caches = []
            for blk, c in zip(self.blocks, caches):
                x, nc = blk(x, cache=c, cache_offset=cache_offsets,
                            seq_lens=seq_lens, block_tables=block_tables,
                            paged_kernel=paged_kernel,
                            paged_mesh=paged_mesh)
                new_caches.append(nc)
            return self.ln_f(x), new_caches
        for blk in self.blocks:
            x = blk(x)
        return self.ln_f(x)

    # -- what serving.GenerationEngine asks of a decoder -------------------
    # the cache path's prompt span reads the slot's rows through whatever
    # kernel the engine passes (GPTAttention.forward): the engine resolves
    # its prefill kernel for such a decoder and leaves another's prefill to
    # its own forward
    prefill_reads_pools = True

    @property
    def max_positions(self):
        return self.cfg.seq_len  # the learned position table

    def kv_cache_spec(self):
        """A K and a V row of n_head x head_dim a token a layer."""
        from ..ops.kv_pool import CacheSpec

        return CacheSpec("heads", [(blk.attn.n_head, blk.attn.head_dim)
                                   for blk in self.blocks])

    def serving_head(self):
        """The tied head: the token embedding, and its logits in float32."""
        return self.embeddings.word_embeddings.weight, _tied_logits

    def moe_aux_loss(self):
        """Weighted sum of every MoE block's load-balancing loss from
        the MOST RECENT forward (each MoEMLP re-assigns its aux_loss per
        step, so this must be read inside the same train step). None
        for a dense trunk — callers add it to the loss only when set."""
        total = None
        for blk in self.blocks:
            aux = getattr(blk.mlp, "aux_loss", None)
            if aux is None:
                continue
            total = aux if total is None else total + aux
        if total is None:
            return None
        return total * self.cfg.moe_aux_weight


class GPTForPretraining(nn.Layer):
    """LM head tied to word embeddings (reference GPTForPretraining)."""

    def __init__(self, model: GPTModel):
        super().__init__()
        self.gpt = model

    def _lm_logits(self, x):
        """Tied LM head over final hidden states — the ONE definition
        shared by forward() and the pipeline head, so a head change
        (untying, scaling) cannot diverge the two paths."""
        w = self.gpt.embeddings.word_embeddings.weight
        with _scope("lm_head"):
            return ops.matmul(x, w, transpose_y=True)

    def forward(self, input_ids, position_ids=None):
        return self._lm_logits(self.gpt(input_ids, position_ids))

    def moe_aux_loss(self):
        return self.gpt.moe_aux_loss()

    def pipeline_parts(self, pp):
        """Stage slicing for the one-compilation SPMD pipeline
        (`distributed.pp_spmd.PipelineSpmdStep`): embeddings ride stage
        0, the uniform block trunk layer-shards over the 'pp' mesh axis,
        and final LN + tied LM head ride the last stage. Returns
        (embed, blocks, head) where embed/head are Tensor->Tensor
        callables producing the stage-boundary activation / the logits.
        Raises PipelineStageError (with a structured spmd_pp_refused
        explainer event) when n_layer does not divide into pp equal
        stage slices."""
        L = len(self.gpt.blocks)
        if self.gpt.cfg.moe_num_experts > 0:
            from ..distributed.meta_parallel.pp_layers import \
                PipelineStageError
            from ..profiler import explainer as _explain

            _explain.record(
                "spmd_pp_refused", op="gpt.pipeline_parts",
                reason="moe_trunk",
                why=("MoE blocks cannot ride the pp trunk: the pipeline "
                     "step stacks blocks into one scanned bank, but "
                     "each MoE block carries its own routing state and "
                     "aux loss — train MoE with dp/ep/mp instead"),
                n_layers=L, pp=pp,
                moe_num_experts=self.gpt.cfg.moe_num_experts)
            raise PipelineStageError(
                "MoE-bearing GPT configs do not support pipeline "
                "parallelism (pp>1): use dp/ep/mp degrees instead")
        if pp < 1 or L % pp != 0:
            from ..distributed.meta_parallel.pp_layers import \
                PipelineStageError
            from ..profiler import explainer as _explain

            _explain.record(
                "spmd_pp_refused", op="gpt.pipeline_parts",
                reason="stage_indivisible",
                why=(f"GPT n_layer={L} is not divisible by pp={pp}: "
                     f"each pipeline stage must own an equal slice of "
                     f"the block trunk"),
                n_layers=L, pp=pp)
            raise PipelineStageError(
                f"GPT n_layer={L} is not divisible by pp={pp}: each "
                f"pipeline stage must own an equal slice of the block "
                f"trunk (choose n_layer a multiple of pp_degree)")

        def head(x):
            # the trunk output is pre-ln_f (GPTModel applies ln_f after
            # the blocks); the stage head finishes norm + tied logits
            return self._lm_logits(self.gpt.ln_f(x))

        return self.gpt.embeddings, list(self.gpt.blocks), head


class GPTPretrainingCriterion(nn.Layer):
    def __init__(self):
        super().__init__()

    def forward(self, logits, labels, loss_mask=None):
        with _scope("lm_head"):
            loss = F.cross_entropy(logits.reshape([-1, logits.shape[-1]]),
                                   labels.reshape([-1]), reduction="none")
            if loss_mask is not None:
                m = loss_mask.reshape([-1])
                return (loss * m).sum() / ops.clip(m.sum(), min=1.0)
            return loss.mean()


def gpt_tiny(**kw):
    return GPTForPretraining(GPTModel(GPTConfig.preset("gpt2-tiny", **kw)))


def gpt_tiny_moe(**kw):
    return GPTForPretraining(
        GPTModel(GPTConfig.preset("gpt2-tiny-moe", **kw)))


def gpt2_small(**kw):
    return GPTForPretraining(GPTModel(GPTConfig.preset("gpt2-small", **kw)))


def gpt3_1p3b(**kw):
    return GPTForPretraining(GPTModel(GPTConfig.preset("gpt3-1.3B", **kw)))


def gpt3_6p7b(**kw):
    return GPTForPretraining(GPTModel(GPTConfig.preset("gpt3-6.7B", **kw)))
