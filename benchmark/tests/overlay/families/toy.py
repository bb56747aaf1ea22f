"""Family `toy`, for benchmark/tests/test_families.py alone: a second family
added as a file. It names its sizes with keys of its own, brings its own
reference (reference/toy.py) and its own counts, and builds the repo's toy
GPT underneath, because the program serves no other model yet."""
from __future__ import annotations

from reference import toy as reference

SIZE_KEYS = ("layers", "heads", "width", "ffn", "context", "vocab")


def sizes(cfg_json):
    return {k: int(cfg_json[k]) for k in SIZE_KEYS}


def vocab_size(cfg_json):
    return int(cfg_json["vocab"])


def build(cfg_json, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForPretraining, GPTModel

    paddle.seed(int(seed))
    s = sizes(cfg_json)
    cfg = GPTConfig(n_layer=s["layers"], n_head=s["heads"],
                    d_model=s["width"], seq_len=s["context"],
                    vocab_size=s["vocab"], dtype=cfg_json["dtype"],
                    dropout=0.0)
    if cfg.d_ff != s["ffn"]:
        raise SystemExit(f"families/toy.py: the model's ffn is {cfg.d_ff}, "
                         f"the configuration file says {s['ffn']}")
    return cfg, GPTForPretraining(GPTModel(cfg))


def criterion():
    from paddle_tpu.models import GPTPretrainingCriterion

    return GPTPretrainingCriterion()


def reference_scorer(cfg_json, cfg, model, padded_len, positions):
    import jax
    import jax.numpy as jnp

    s = sizes(cfg_json)
    weights = {n: t._data for n, t in model.gpt.state_dict().items()}
    score = jax.jit(lambda w, ids, at: reference.logits(
        w, s["layers"], s["heads"], ids, at))
    score(weights, jnp.zeros((padded_len,), jnp.int32),
          jnp.zeros((positions,), jnp.int32)).block_until_ready()
    return lambda ids, at: score(weights, ids, at)


def matmul_params(s):
    return s["layers"] * (4 * s["width"] ** 2 + 2 * s["width"] * s["ffn"]) \
        + s["vocab"] * s["width"]


def train_flops_per_token(run):
    """Its own convention: 6 a matmul weight, attention's scores left out."""
    return 6 * matmul_params(sizes(run["cfg"]))


def decode_step_work(run):
    c = run["counters"]
    steps = c.get("serving.decode_steps")
    if not steps:
        return None
    s = sizes(run["cfg"])
    slots = c["serving.active_slot_steps"] / steps
    return 2 * matmul_params(s) * slots, 2 * matmul_params(s)


def kernel_work(run, kernel):
    return None  # nothing to say: the kernels' readers leave their metrics out
