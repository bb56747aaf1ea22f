"""Operations and bytes of the named kernels and steps, beside flops.py and
kept with the benchmark so that no later PR can move the yardstick.

Every count is the LEAST work the cell's shapes demand, computed from the
cell's sizes (configs/<config>.json, traffic/<traffic>.json) and from the
program's counters — never from a kernel's block sizes or grid — so no
honest kernel can read over 100 % of its roofline. Convention throughout:
a multiply-add is 2 operations; a matmul [m,k]x[k,n] is 2*m*k*n;
recomputation, masked-out positions, padding and lookups count nothing;
bytes are bf16 (2 B) tensors read or written once from HBM.
"""
from __future__ import annotations


def flash_fwd_flops(sizes, batch):
    """Causal flash-attention forward, one layer: the two matmuls QK^T and
    PV are each 2*B*T*T*d over the full square (d = heads x head_dim), and
    a causal mask needs half of it: 2*B*T^2*d. The ceiling it is held to
    is the chip's bf16 peak, though a head_dim of 64 fills only half of a
    128-wide MXU pass: the share says how far the kernel is from the chip,
    not from the best a 64-wide head can do."""
    T, d = sizes["seq_len"], sizes["d_model"]
    return 2 * batch * T * T * d


def flash_bwd_flops(sizes, batch):
    """Causal flash-attention backward, one layer: five matmuls of the
    forward's size (S = QK^T again, dV = P^T dO, dP = dO V^T, dQ = dS K,
    dK = dS^T Q) against the forward's two: 2.5 x the forward's count. A
    backward split into a dQ and a dK/dV kernel computes S and dP in both;
    the second time is recomputation and counts nothing."""
    return 5 * flash_fwd_flops(sizes, batch) // 2


def flash_fwd_bytes(sizes, batch):
    """Q, K, V read and O written once, one layer (the log-sum-exp row is
    1/d of one of them and is left out)."""
    return 4 * batch * sizes["seq_len"] * sizes["d_model"] * 2


def flash_bwd_bytes(sizes, batch):
    """Q, K, V, O, dO read and dQ, dK, dV written once, one layer."""
    return 8 * batch * sizes["seq_len"] * sizes["d_model"] * 2


def gpt_matmul_params(sizes):
    """Weights that take part in a matmul for every token: per block the
    QKV and output projections (4*d*d) and the two FFN matrices (2*d*f),
    and the tied LM head (V*d). Biases, LayerNorm and the embedding
    lookups count nothing."""
    d, L = sizes["d_model"], sizes["n_layer"]
    return L * (4 * d * d + 2 * d * sizes["d_ff"]) + sizes["vocab_size"] * d


def decode_step_flops(sizes, slots, kv_rows):
    """One decode step: every active slot's token through every matmul
    weight (2 per weight), and its attention over the KV rows it holds
    (QK^T and PV, 2*d each a row a layer: 4*d*L a row). `slots` and
    `kv_rows` are the step's means, from the program's counters."""
    return 2 * gpt_matmul_params(sizes) * slots \
        + 4 * sizes["d_model"] * sizes["n_layer"] * kv_rows


def kv_row_bytes(sizes):
    """One KV row of one layer: K and V, d wide, bf16."""
    return 2 * sizes["d_model"] * 2


def decode_step_bytes(sizes, kv_rows):
    """One decode step: the bf16 matmul weights once, and every KV row the
    step's attention reads, in every layer. Activations, the new rows'
    writes and the logits are thousands of times smaller and count
    nothing."""
    return 2 * gpt_matmul_params(sizes) \
        + kv_rows * sizes["n_layer"] * kv_row_bytes(sizes)


def least_seconds(flops, nbytes, peaks_of_device):
    """(seconds, which) — the roofline: the larger of operations over the
    peak rate and bytes over the peak bandwidth, and which of the two it
    was ("flops" or "bytes")."""
    tf = flops / peaks_of_device["bf16_flops_per_s"]
    tb = nbytes / peaks_of_device["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")


def decode_step_means(counters):
    """(active slots, KV rows read) of a mean decode step of the window,
    from the deltas of the program's counters; None where the program does
    not keep them."""
    steps = counters.get("serving.decode_steps")
    rows = counters.get("serving.kv_tokens_read")
    if not steps or not rows:
        return None
    return counters["serving.active_slot_steps"] / steps, rows / steps
