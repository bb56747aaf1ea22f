"""Family `gpt`: the GPT-2/GPT-3 decoder of paddle_tpu/models/gpt.py. Every
answer is a call into the code the benchmark already had — model.py (the
constructor and the size keys), flops.py and kernel_counts.py (the counts),
reference/gpt.py (the plain forward) — so a reading of a GPT cell is what it
was before the drivers and readers asked here."""
from __future__ import annotations

import flops
import kernel_counts as kc
import model as bench_model
from reference import gpt as reference

build = bench_model.build
sizes = bench_model.sizes


def vocab_size(cfg_json):
    return sizes(cfg_json)["vocab_size"]


def criterion():
    from paddle_tpu.models import GPTPretrainingCriterion

    return GPTPretrainingCriterion()


def reference_scorer(cfg_json, cfg, model, padded_len, positions):
    """reference.forward over the model's own state_dict (upcast there),
    one executable for ids of `padded_len` and `positions` places, run once
    on zeros so that the check after the window only runs it."""
    import jax
    import jax.numpy as jnp

    weights = {n: t._data for n, t in model.gpt.state_dict().items()}
    score = jax.jit(lambda w, ids, at: reference.forward(
        w, cfg.n_layer, cfg.n_head, ids, at))
    score(weights, jnp.zeros((padded_len,), jnp.int32),
          jnp.zeros((positions,), jnp.int32)).block_until_ready()
    return lambda ids, at: score(weights, ids, at)


def train_flops_per_token(run):
    return flops.gpt_train_flops_per_token(sizes(run["cfg"]))


def decode_step_work(run):
    """Every active slot's token through every matmul weight and over the
    KV rows it holds; the weights once and those rows, in bytes."""
    means = kc.decode_step_means(run["counters"])
    if means is None:
        return None
    slots, rows = means
    s = sizes(run["cfg"])
    return kc.decode_step_flops(s, slots, rows), kc.decode_step_bytes(s, rows)


def kernel_work(run, kernel):
    s = sizes(run["cfg"])
    if kernel == "paged_attention":
        # QK^T and PV over the KV rows a mean step reads in one layer
        means = kc.decode_step_means(run["counters"])
        if means is None:
            return None
        rows = means[1]
        return 4 * s["d_model"] * rows, rows * kc.kv_row_bytes(s)
    count = {"flash_fwd": (kc.flash_fwd_flops, kc.flash_fwd_bytes),
             "flash_bwd": (kc.flash_bwd_flops, kc.flash_bwd_bytes)}
    if kernel not in count:
        return None
    B = int(run["traffic"]["batch"]) // int(run["wl"]["chips"])
    return tuple(f(s, B) for f in count[kernel])
