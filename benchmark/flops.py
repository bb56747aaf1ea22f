"""Operation counts, kept with the benchmark so that no later PR can move
the yardstick. Copied from `GPTConfig.flops_per_token` (paddle_tpu/models/
gpt.py), which stays the program's own."""
from __future__ import annotations


def gpt_train_flops_per_token(sizes):
    """Model FLOPs per trained token, forward + backward, the Megatron /
    PaLM appendix B convention: 6 per parameter that takes part in a matmul
    (blocks, final LayerNorm, the tied head's V*d), plus 12*L*d*T for the
    attention scores and values. Lookups and recomputation count nothing."""
    d, L = sizes["d_model"], sizes["n_layer"]
    V, T, f = sizes["vocab_size"], sizes["seq_len"], sizes["d_ff"]
    block = 4 * d * d + 2 * d * f + 9 * d + f  # qkv, out, fc1, fc2, 2 LN
    return 6 * (L * block + 2 * d + V * d) + 12 * L * d * T


def peak(peaks, device_kind, key):
    """A peak of the device from peaks.json; an unknown device is an error."""
    if device_kind not in peaks["devices"]:
        raise SystemExit(f"peaks.json has no entry for device kind "
                         f"{device_kind!r}")
    return peaks["devices"][device_kind][key]
