"""Dropless expert layer for the served path: sigmoid router with a
selection bias (DeepSeek-V3, arXiv:2412.19437 section 2.1.2), top-k without
capacity, SwiGLU experts computed as grouped matmuls over rows sorted by
expert, a shared expert every token passes through.

`MoEMLP` (layer.py) pads every expert to a capacity and drops what does not
fit — the price of its dense ``[G, S, E, C]`` masks. Here the shapes stay
fixed another way: the ``N * k`` routed rows are sorted by expert and the
group sizes are DATA (`ops.pallas_ops.grouped_matmul`: `lax.ragged_dot` —
on a TPU XLA lowers it to a grouped matmul that walks the groups, on the CPU
to its reference form — or, where a serving engine hands the layer a fused
kernel kind and the step brings a few rows a group, the Pallas kernel that
copies each hit expert's weight tiles once), so no token is dropped however
uneven the routing, one executable serves ``[32, 1]`` decode and
``[1, 2048]`` prefill, and an expert nobody chose costs no weight read.

The layer is TOLD which experts it holds: ``experts_held=(lo, hi)`` keeps
the weights of experts ``lo..hi-1`` only, the router still scores all
``num_experts``, and the result is those experts' part plus the shared
expert — what one chip of an expert-parallel deployment computes before the
exchange. On one chip that holds every expert this is the whole layer;
nothing here stands in for absent chips.

The arithmetic is written on the parameters' arrays (`Tensor._data`), so
the autograd tape does not see it: this is the served path; training
through it is open (ROADMAP).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...ops.pallas_ops import grouped_matmul
from ...profiler import spans as _spans
from ..initializer import Normal
from ..layer.layers import Layer

__all__ = ["DroplessMoE", "route_sigmoid_topk", "route_softmax_topk",
           "swiglu"]


def swiglu(x, w_gate, w_up, w_down):
    """``W_down(silu(W_gate x) * W_up x)`` with float32 accumulation."""
    f32 = jnp.float32
    g = jnp.dot(x, w_gate, preferred_element_type=f32)
    u = jnp.dot(x, w_up, preferred_element_type=f32)
    return jnp.dot((jax.nn.silu(g) * u).astype(x.dtype), w_down,
                   preferred_element_type=f32)


def route_sigmoid_topk(x, w_router, b_select, top_k, scaling, norm=True):
    """(chosen [N, k] int32, weights [N, k] float32): float32 sigmoid
    scores, the bias (None: the router has none) added for CHOOSING only,
    the chosen scores normalised to sum 1 (``norm``) and multiplied by
    ``scaling``."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               w_router.astype(jnp.float32)))
    _, chosen = jax.lax.top_k(
        s if b_select is None else s + b_select.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if norm:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), picked * jnp.float32(scaling)


def route_softmax_topk(x, w_router, top_k, norm=True):
    """(chosen [N, k] int32, weights [N, k] float32) of a softmax router
    (the Qwen3-MoE family's, models/sdar_moe.py): float32 probabilities over
    ALL experts, the k largest, divided by their sum (``norm``); no bias, no
    scaling."""
    p = jax.nn.softmax(jnp.dot(x.astype(jnp.float32),
                               w_router.astype(jnp.float32)), axis=-1)
    picked, chosen = jax.lax.top_k(p, top_k)
    if norm:
        picked = picked / picked.sum(-1, keepdims=True)
    return chosen.astype(jnp.int32), picked


class DroplessMoE(Layer):
    """forward(x [B, T, d], valid=None) -> y [B, T, d] float32: the held
    routed experts' part of the layer plus the shared experts (``n_shared``
    of them side by side in one matmul; their outputs summed, or with
    ``shared_combine="average"`` their mean; ``n_shared=0``: none;
    ``select_bias=False``: a router that chooses by its scores alone;
    ``router="softmax"``: probabilities over all experts where the sigmoid
    scores are, `route_softmax_topk`). The router
    scores ``x`` in the precision it comes in (hand it the float32 normed
    input: a choice between near-tied experts is discontinuous, and a bf16
    rounding of the input flips it); the experts read it in their weights'
    dtype. ``valid``
    [B, T] bool marks the rows that are tokens (bucket padding is routed
    nowhere and costs no expert row). After a forward
    ``last_experts_hit`` holds the number of held experts that were given
    at least one row (an int32 scalar of that trace). ``kernel``: what a
    serving engine's `select_grouped_kernel` resolved to, handed down by the
    model's forward at trace time (None, a layer called outside an engine:
    `lax.ragged_dot`)."""

    def __init__(self, d_model, d_ff, num_experts, top_k, n_shared=1,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 experts_held=None, init_std=0.02, bias_std=0.02,
                 dtype=None, select_bias=True, shared_combine="sum",
                 rows_at_a_time=None, router="sigmoid"):
        super().__init__()
        lo, hi = experts_held or (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(f"experts_held {experts_held!r} is not a "
                             f"range of the {num_experts} experts")
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        if router not in ("sigmoid", "softmax") or (
                router == "softmax" and (select_bias
                                         or routed_scaling_factor != 1.0)):
            raise ValueError(f"router {router!r}: 'sigmoid', or 'softmax' "
                             "with no selection bias and no scaling")
        self.router_kind = router
        if shared_combine not in ("sum", "average"):
            raise ValueError(f"shared_combine {shared_combine!r} is not "
                             "'sum' or 'average'")
        # n experts side by side give their sum: the mean is that over n
        self.shared_scale = 1.0 / n_shared \
            if n_shared and shared_combine == "average" else None
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.experts_held = (int(lo), int(hi))
        self.scaling = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.d_ff = int(d_ff)
        # a long prompt's tokens this many at a time (None: all at once):
        # the sorted rows and both matmuls' float32 results are N * k rows
        # whatever share of the experts is held here
        self.rows_at_a_time = rows_at_a_time
        held = hi - lo
        init = Normal(0.0, init_std)

        def param(shape, initializer=init, dt=dtype):
            return self.create_parameter(list(shape), dtype=dt,
                                         default_initializer=initializer)

        # the router and its selection bias stay float32: scores decide
        # discontinuously, and the two are 0.23 M numbers. A seeded bias
        # of 0.02 is the spacing of the scores around the k-th of 64: it
        # changes some choices and leaves the experts' load even (at 0.1
        # a few experts take most rows: 60 % of 64 hit by 128 rows on the
        # chip, not the 87 % of an even load)
        self.router = Layer()
        self.router.weight = param((d_model, num_experts), dt="float32")
        if select_bias:
            self.router.bias = param((num_experts,), Normal(0.0, bias_std),
                                     dt="float32")
        # the held experts, stacked: gate and up side by side, so a row
        # meets its expert's first matmul once
        self.experts = Layer()
        self.experts.gate_up = param((held, d_model, 2 * d_ff))
        self.experts.down = param((held, d_ff, d_model))
        self.shared = None
        if n_shared:
            self.shared = Layer()
            for name, shape in (("gate_proj", (d_model, n_shared * d_ff)),
                                ("up_proj", (d_model, n_shared * d_ff)),
                                ("down_proj", (n_shared * d_ff, d_model))):
                lin = Layer()
                lin.weight = param(shape)
                setattr(self.shared, name, lin)
        self.last_experts_hit = None

    def grouped_shapes(self, tokens):
        """``(M, K, N, G)`` of the layer's two grouped matmuls in a forward
        over ``tokens`` tokens: what `pallas_ops._grouped_plan` reads."""
        C = self.rows_at_a_time
        if C and tokens > C and tokens % C == 0:
            tokens = C
        held, d, F2 = self.experts.gate_up.shape
        M = tokens * self.top_k
        return [(M, d, F2, held), (M, F2 // 2, d, held)]

    def forward(self, x, valid=None, kernel=None):
        xa = x._data if isinstance(x, Tensor) else x
        B, T, d = xa.shape
        x2 = xa.reshape(B * T, d)
        v = None if valid is None else (
            valid._data if isinstance(valid, Tensor) else valid
        ).reshape(B * T)
        C = self.rows_at_a_time
        if C and B * T > C and B * T % C == 0:
            if v is None:
                v = jnp.ones((B * T,), bool)
            y, hit = jax.lax.map(lambda a: self._compute(*a, kernel),
                                 (x2.reshape(-1, C, d), v.reshape(-1, C)))
            # of the chunks' counts the largest: a count of experts, not
            # of rows (only decode, one chunk, reads it)
            self.last_experts_hit = hit.max()
        else:
            y, self.last_experts_hit = self._compute(x2, v, kernel)
        return Tensor(y.reshape(B, T, d))

    def _compute(self, x, valid, kernel=None):
        N, d = x.shape
        k, F = self.top_k, self.d_ff
        lo, hi = self.experts_held
        held = hi - lo
        bias = getattr(self.router, "bias", None)
        with _spans.scope("moe_router"):
            routed_from, x = x, x.astype(self.experts.gate_up._data.dtype)
            if self.router_kind == "softmax":
                chosen, weight = route_softmax_topk(
                    routed_from, self.router.weight._data, k,
                    self.norm_topk_prob)
            else:
                chosen, weight = route_sigmoid_topk(
                    routed_from, self.router.weight._data,
                    None if bias is None else bias._data, k,
                    self.scaling, self.norm_topk_prob)
            mine = (chosen >= lo) & (chosen < hi)
            if valid is not None:
                mine = mine & valid[:, None]
            # rows of experts held elsewhere (and padding) sort behind the
            # last group: no group owns them, no expert computes them
            group = jnp.where(mine, chosen - lo, held).reshape(N * k)
            order = jnp.argsort(group, stable=True).astype(jnp.int32)
            sizes = jnp.bincount(group, length=held + 1)[:held].astype(
                jnp.int32)
            back = jnp.zeros((N * k,), jnp.int32).at[order].set(
                jnp.arange(N * k, dtype=jnp.int32))
        with _spans.scope("moe_experts"):
            rows = x[order // k]  # [N*k, d], sorted by expert
            h = grouped_matmul(rows, self.experts.gate_up._data, sizes,
                               kernel=kernel)
            h = (jax.nn.silu(h[:, :F]) * h[:, F:]).astype(x.dtype)
            out = grouped_matmul(h, self.experts.down._data, sizes,
                                 kernel=kernel)
            # back to token order; a row no held expert owns adds nothing
            # (whatever the grouped matmul left in it: the kernel never
            # writes such a row)
            out = jnp.where(mine.reshape(N * k, 1), out[back], 0.0)
            y = (out.reshape(N, k, d) * weight[:, :, None]).sum(1)
        if self.shared is not None:
            with _spans.scope("moe_experts" if self.shared_scale is None
                              else "moe_shared"):
                shared = swiglu(x, self.shared.gate_proj.weight._data,
                                self.shared.up_proj.weight._data,
                                self.shared.down_proj.weight._data)
                if self.shared_scale is not None:
                    shared = shared * jnp.float32(self.shared_scale)
                y = y + shared
        return y, (sizes > 0).sum().astype(jnp.int32)
