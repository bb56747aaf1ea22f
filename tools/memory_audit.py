"""Analytic HBM budget audit for the bench ladder configs.

Round-5 (VERDICT r4 item 3): gpt2-large ran at 37.2% MFU in round 2 and
hit RESOURCE_EXHAUSTED in round 4 under the same jaxlib. This audit
computes each config's first-order device-memory requirement — params,
fp32 master copies, Adam moments, grads, and a per-policy activation
estimate — against the v5e's 16 GiB HBM, so an on-chip bisection starts
from the dominant terms instead of guessing.
Pure arithmetic: runs anywhere, no device needed.

Usage: python tools/memory_audit.py [preset batch seq policy]...
(defaults to the bench ladder + the gpt2-large rungs that OOMed)
"""
from __future__ import annotations

import sys

GIB = 1024 ** 3
HBM = 16 * GIB  # v5e

PRESETS = {
    "gpt2-medium": dict(L=24, H=16, D=1024, V=50304),
    "gpt2-large": dict(L=36, H=20, D=1280, V=50304),
    "gpt2-small": dict(L=12, H=12, D=768, V=50304),
    "gpt3-6.7B": dict(L=32, H=32, D=4096, V=50304),
}


def params(preset):
    p = PRESETS[preset]
    L, D, V = p["L"], p["D"], p["V"]
    block = 12 * D * D + 13 * D        # qkv/proj/mlp + ln scales/biases
    return L * block + V * D + 1024 * D + 2 * D  # + wpe + ln_f


def activation_bytes(preset, B, T, policy):
    """bf16 live-activation estimate for ONE step's backward.

    none: every block's intermediates live — per block per token:
      ln1/ln2 (2D) + qkv (3D) + attn-out pre/post proj (2D) + mlp hidden
      (4D) + mlp out (D) + residuals (2D) ≈ 14D, plus attention
      [B,H,T,T] scores fwd-saved (flash avoids it; dots policies save
      output only ≈ D).
    dots_attn: matmul outputs + attention outputs live ≈ 5D per block.
    full: only block inputs live ≈ D per block.
    """
    p = PRESETS[preset]
    L, D = p["L"], p["D"]
    per_tok = {"none": 14 * D, "dots_attn": 5 * D, "attn": 6 * D,
               "full": 1 * D}[policy]
    return 2 * B * T * L * per_tok


def audit(preset, B, T, policy):
    n = params(preset)
    weights = 2 * n                  # bf16
    master = 4 * n                   # fp32 master (multi_precision)
    moments = 2 * 4 * n              # Adam m+v, fp32
    grads = 4 * n                    # fp32 grads at the update boundary
    acts = activation_bytes(preset, B, T, policy)
    logits = 4 * B * T * PRESETS[preset]["V"]  # fp32 head out + softmax
    total = weights + master + moments + grads + acts + logits
    print(f"{preset:12s} bs{B:<3d} seq{T:<5d} {policy:9s} "
          f"params {n/1e6:7.1f}M  w+m+opt {(weights+master+moments)/GIB:5.2f}G "
          f"grads {grads/GIB:5.2f}G  acts {acts/GIB:5.2f}G "
          f"logits {logits/GIB:5.2f}G  TOTAL {total/GIB:6.2f}G "
          f"{'FITS' if total < HBM * 0.9 else 'OVER' if total > HBM else 'TIGHT'}")
    return total


if __name__ == "__main__":
    args = sys.argv[1:]
    if args:
        configs = [tuple(args[i:i + 4]) for i in range(0, len(args), 4)]
        configs = [(p, int(b), int(t), pol) for p, b, t, pol in configs]
    else:
        configs = [
            ("gpt2-medium", 8, 1024, "none"),
            ("gpt2-medium", 12, 1024, "none"),
            ("gpt2-medium", 16, 1024, "none"),
            ("gpt2-medium", 16, 1024, "dots_attn"),
            ("gpt2-medium", 8, 2048, "dots_attn"),
            ("gpt2-large", 8, 1024, "none"),
            ("gpt2-large", 8, 1024, "dots_attn"),
            ("gpt2-large", 8, 1024, "full"),
            ("gpt2-large", 4, 1024, "dots_attn"),
            ("gpt3-6.7B", 8, 2048, "full"),
        ]
    print(f"v5e HBM budget: {HBM/GIB:.0f} GiB "
          "(FITS < 90%, TIGHT 90-100%, OVER > 100%)")
    for cfg in configs:
        audit(*cfg)
