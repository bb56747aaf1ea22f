"""Family `qwen3_next`: the Qwen3-Next decoder of paddle_tpu/models/
qwen3_next.py — Gated DeltaNet layers (a recurrent state a slot) beside gated
full-attention layers (grouped queries over a paged cache), softmax-routed
experts beside a sigmoid-gated shared expert. The plain reference is
reference/qwen3_next.py; the counts of least work are below, from the
configuration file's own numbers.

The weights are the program's own seeded draws times the file's
`seeded_weights` factors (`_scale_seeded`; why each: the file's
`assumed.seeded_weights`).

The configuration file gives the CHIP'S SHARE of a deployment: `num_experts`
is the experts held here (experts 0 .. num_experts - 1), `vocab_size` the
rows of the vocabulary held here, and `published` the router's width and the
whole vocabulary. `build` hands the program the published router width with
`experts_held`, and the reference is given the same share.

The reference cannot hold its float32 forward beside the engine at the
published widths, so `reference_scorer` calls reference.layer a layer at a
time over the SERVED bf16 weights (upcast inside it, an expert at a time):
a linear layer's recurrence a token at a time over the whole padded
sequence, a full layer's attention over blocks of queries, the head over
blocks of the vocabulary — the same functions `reference.forward` calls, in
the same order.

Every compared position is compared as it is, under the traffic's
`near_tie` (its readings: traffic/longdoc_closed_c32.json `near_tie_why`).
The scorer prints each position with the reference's routing margin there
(`reference.route`'s, in probability, the least over the position's layers):
a choice of experts is a discontinuous function of its input, and the
positions where bf16 serving parts most from the reference are those where
it rightly chose another held expert."""
from __future__ import annotations

import numpy as np

from reference import qwen3_next as reference

_Q_BLOCK = 256      # queries a block of the reference's full attention:
#                     float32 scores of 16 heads x 256 x 17152 are 281 MB
_V_BLOCK = 2374     # rows of the head a block: 18992 = 8 x 2374


def _model_keys(cfg_json):
    """Every key of the published config that the program's config class
    holds, with the router's width as published; the file's other keys
    (name, source, reduced, published, n_routed_experts, ...) are the
    benchmark's own."""
    from paddle_tpu.models import Qwen3NextConfig

    keys = {k: cfg_json[k] for k in Qwen3NextConfig.PUBLISHED}
    keys["num_experts"] = int(cfg_json["published"]["num_experts"])
    return keys


def _held(cfg_json):
    return (0, int(cfg_json["num_experts"]))


def sizes(cfg_json):
    """The file's sizes under the published names."""
    return {k: v for k, v in cfg_json.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def vocab_size(cfg_json):
    return int(cfg_json["vocab_size"])


def build(cfg_json, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models import Qwen3NextConfig, Qwen3NextModel

    if int(cfg_json["n_routed_experts"]) != int(cfg_json["num_experts"]):
        raise SystemExit("families/qwen3_next.py: n_routed_experts repeats "
                         "num_experts (the experts held) for the reader of "
                         "moe.experts_hit_share.serve; the file gives two "
                         "numbers")
    paddle.seed(int(seed))
    cfg = Qwen3NextConfig(dtype=cfg_json["dtype"],
                          experts_held=_held(cfg_json),
                          **_model_keys(cfg_json))
    model = Qwen3NextModel(cfg)
    _scale_seeded(model, cfg_json.get("seeded_weights", {}))
    n = sum(int(t._data.size) for t in model.state_dict().values())
    want = param_counts(cfg_json)["total"]
    if n != want:
        raise SystemExit(f"families/qwen3_next.py: the model holds {n} "
                         f"parameters, the file's sizes give {want}")
    return cfg, model


def _scale_seeded(model, scales):
    """The file's `seeded_weights`: a parameter whose name ends with a key is
    the program's own draw times the key's factor. A key that names no
    parameter is a mistake in the file."""
    import jax.numpy as jnp

    state = model.state_dict()
    for suffix, factor in scales.items():
        names = [n for n in state if n.endswith(suffix)]
        if not names:
            raise SystemExit(f"families/qwen3_next.py: seeded_weights names "
                             f"{suffix!r}, which no parameter's name ends "
                             "with")
        for n in names:
            # in the parameter's own dtype, one parameter at a time
            t = state[n]
            t._data = (t._data * jnp.asarray(factor, t._data.dtype)
                       ).block_until_ready()


def criterion():
    return None  # no training cell: the dropless layer serves only


def reference_scorer(cfg_json, cfg, model, padded_len, positions):
    import jax
    import jax.numpy as jnp

    cfgd, held = _model_keys(cfg_json), _held(cfg_json)
    w = {n: t._data for n, t in model.state_dict().items()}
    L = int(cfgd["num_hidden_layers"])
    kinds = reference.layer_kinds(cfgd)
    # the control reading (configs/*.float8-control.json): the reference
    # over the served weights rounded to a lower precision, a layer at a
    # time as it reads them; such a run has to come out not correct
    low = cfg_json.get("reference_weights")

    def read(a):
        return a if low is None or a.ndim < 2 else \
            a.astype(getattr(jnp, low)).astype(a.dtype)

    def layer_weights(i, as_i):
        """Layer i's weights under layer as_i's names: one executable a
        kind of layer, whichever layer's weights it is given."""
        p = f"layers.{i}."
        return {f"layers.{as_i}." + n[len(p):]: read(a) for n, a in w.items()
                if n.startswith(p)}

    def layer_fn(as_i):
        def run(wl, h, pos):
            margins = []
            with jax.default_matmul_precision("highest"):
                h = reference.layer(wl, as_i, cfgd, h, pos, q_block=_Q_BLOCK,
                                    experts_held=held, margins=margins)
            return h, margins[0]
        return jax.jit(run)

    first = {}
    for i in range(L):  # the first layer of each kind stands for its kind
        first.setdefault(kinds[i], (i, layer_fn(i)))
    embed = jax.jit(lambda we, ids: reference.embed(we, cfgd, ids))

    def head_(wh, h, at):
        with jax.default_matmul_precision("highest"):
            return reference.head(wh, cfgd, h, at, v_block=_V_BLOCK)
    head = jax.jit(head_)
    w_embed = {"embed_tokens.weight": read(w["embed_tokens.weight"])}
    w_head = {k: read(w[k]) for k in ("norm.weight", "lm_head.weight")}

    def score(ids, at, quiet=False):
        h = embed(w_embed, ids)
        pos = jnp.arange(ids.shape[0])
        closest = jnp.full((ids.shape[0],), jnp.inf)
        for i in range(L):
            as_i, fn = first[kinds[i]]
            h, margin = fn(layer_weights(i, as_i), h, pos)
            closest = jnp.minimum(closest, margin)
        logits = head(w_head, h, at)
        if not quiet:
            served = ids[jnp.minimum(at + 1, ids.shape[0] - 1)]
            gaps = logits.max(-1) - jnp.take_along_axis(
                logits, served[:, None], axis=1)[:, 0]
            for a, m, g in zip(np.asarray(at), np.asarray(closest[at]),
                               np.asarray(gaps)):
                print(f"[bench] reference: position {int(a) + 1} routing "
                      f"margin {float(m):.3g} gap {float(g):.5f}", flush=True)
        return logits

    score(jnp.zeros((padded_len,), jnp.int32),
          jnp.zeros((positions,), jnp.int32), quiet=True).block_until_ready()
    return score


# ------------------------------------------------------- least work counts --
def param_counts(c):
    """Numbers of parameters by part, from the file's sizes: `num_experts`
    routed experts held, the router as wide as `published.num_experts`."""
    d, D = c["hidden_size"], c["head_dim"]
    Hq, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    Hk, Hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    K, F = c["linear_conv_kernel_dim"], c["moe_intermediate_size"]
    L = c["num_hidden_layers"]
    n_full = L // c["full_attention_interval"]
    conv = 2 * Hk * dk + Hv * dv
    out = {"linear": d * (2 * Hk * dk + 2 * Hv * dv) + d * 2 * Hv + conv * K
           + Hv * dv * d,
           "linear_small": 2 * Hv + dv,  # A_log, dt_bias, the gated norm
           "full": d * Hq * 2 * D + 2 * d * Hkv * D + Hq * D * d,
           "full_small": 2 * D,  # the q and k norms
           "expert": 3 * d * F,
           "shared": 3 * d * c["shared_expert_intermediate_size"] + d,
           "router": d * c["published"]["num_experts"], "norm": d,
           "embed": c["vocab_size"] * d, "head": c["vocab_size"] * d,
           "layers": L, "full_layers": n_full, "linear_layers": L - n_full,
           "held": c["num_experts"]}
    moe = out["router"] + out["shared"] + out["held"] * out["expert"]
    out["total"] = (out["linear_layers"] * (out["linear"]
                                            + out["linear_small"])
                    + n_full * (out["full"] + out["full_small"])
                    + L * (moe + 2 * out["norm"])
                    + out["embed"] + out["head"] + out["norm"])
    return out


def _attn_flops_per_row(c):
    """QK^T and PV of every query head over one key/value row."""
    return 4 * c["num_attention_heads"] * c["head_dim"]


def _row_bytes(c):
    """K and V of the key/value heads, bf16: 2048 B at 2 x 256."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * 2


def _state_bytes(c):
    """A slot's state in one linear layer, float32: 2 MiB at 32 x 128 x
    128."""
    return 4 * c["linear_num_value_heads"] * c["linear_key_head_dim"] \
        * c["linear_value_head_dim"]


def _state_flops(c):
    """A slot's state update in one linear layer: the decay, S^T k, the
    rank-one update and S^T q, ~7 operations an element of the state."""
    return 7 * c["linear_num_value_heads"] * c["linear_key_head_dim"] \
        * c["linear_value_head_dim"]


def _step(run):
    """(slots, a full layer's rows, experts hit a layer-step) of a mean
    decode step of the window, from the program's counters."""
    k = run["counters"]
    steps, rows = k.get("serving.decode_steps"), k.get(
        "serving.kv_tokens_read")
    layer_steps = k.get("serving.moe_layer_steps")
    if not steps or not rows or not layer_steps:
        return None
    return (k["serving.active_slot_steps"] / steps, rows / steps,
            k["serving.moe_experts_hit"] / layer_steps)


def train_flops_per_token(run):
    return None


def decode_step_work(run):
    """Flops: every active slot's token through the parameters it meets —
    both kinds of mixer, the router, the gated shared expert, of its
    `num_experts_per_tok` routed experts the share held here, the head —
    over the rows each full layer keeps for it, and through each linear
    layer's state. Bytes: the weights a step must read once — everything but
    the experts nobody chose and the embedding table (a lookup) — at 2 B a
    parameter, every full layer's rows once, and every linear layer's state
    read and written once a slot (the convolution's tail, 48 KiB, is left
    out)."""
    got = _step(run)
    if got is None:
        return None
    slots, rows, hit = got
    c = run["cfg"]
    p = param_counts(c)
    L, n_full, n_lin = p["layers"], p["full_layers"], p["linear_layers"]
    outside = n_lin * p["linear"] + n_full * p["full"] \
        + L * (p["router"] + p["shared"]) + p["head"]
    routed = c["num_experts_per_tok"] * p["held"] \
        / c["published"]["num_experts"]  # held experts a token, expected
    flops = 2 * (outside + L * routed * p["expert"]) * slots \
        + _attn_flops_per_row(c) * rows * n_full \
        + _state_flops(c) * slots * n_lin
    nbytes = 2 * (outside + L * hit * p["expert"]) \
        + rows * n_full * _row_bytes(c) + 2 * _state_bytes(c) * slots * n_lin
    return flops, nbytes


def kernel_work(run, kernel):
    """One layer's call: `paged_attention` a full layer's (every row of
    every active slot read once), `gated_delta_step` a linear layer's
    (every active slot's state read and written once)."""
    if kernel not in ("paged_attention", "gated_delta_step"):
        return None
    got = _step(run)
    if got is None:
        return None
    slots, rows, _ = got
    c = run["cfg"]
    if kernel == "paged_attention":
        return _attn_flops_per_row(c) * rows, rows * _row_bytes(c)
    return _state_flops(c) * slots, 2 * _state_bytes(c) * slots
