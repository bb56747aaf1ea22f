"""Family `cohere2_moe`: the Cohere2-MoE decoder of
paddle_tpu/models/cohere2_moe.py — grouped-query attention over window and
full layers, each with its own kind of cache, a parallel attention + expert
block, sigmoid-routed experts beside averaged shared experts. The plain
reference is reference/cohere2_moe.py; the counts of least work are below,
from the configuration file's own numbers.

The configuration file gives the CHIP'S SHARE of a deployment: `num_experts`
is the experts held here (experts 0 .. num_experts - 1), `vocab_size` the
rows of the vocabulary held here, and `published` the router's width and the
whole vocabulary. `build` hands the program the published router width with
`experts_held`, and the reference is given the same share.

The reference cannot hold its float32 forward beside the engine at the
published widths (scores of 128 heads x 8960^2 alone are 41 GB), so
`reference_scorer` calls reference.layer a layer at a time over the SERVED
bf16 weights (upcast inside it, an expert at a time), with attention over
blocks of queries and the head over blocks of the vocabulary: the same
functions `reference.forward` calls, in the same order.

A choice of experts is a discontinuous function of its input: where the
reference's own choice lies within ROUTE_TIE of another one that involves a
HELD expert (`reference.route`'s margin, the least over the position's
layers), bf16 serving may rightly choose the other, and the logits then part
as far as a planted fault moves them. The scorer does not compare such a
position: it returns a row on which the served token reads as the top one,
and says how many positions that was. The others it compares as they are,
under the traffic's `near_tie`."""
from __future__ import annotations

from reference import cohere2_moe as reference

_Q_BLOCK = 64       # queries a block of the reference's attention: float32
#                     scores of 128 heads x 64 x 8960 are 294 MB
_V_BLOCK = 8192     # rows of the head a block
# Below this routing margin (in score) a position is not compared (see
# above). The readings it lies between are in traffic/mixedlen_closed_c32
# .json (`near_tie_why`) and PERF.md section 6, PR 35.
ROUTE_TIE = 0.001


def _model_keys(cfg_json):
    """Every key of the published config that the program's config class
    holds, with the router's width as published; the file's other keys
    (name, source, reduced, published, n_routed_experts, ...) are the
    benchmark's own."""
    from paddle_tpu.models import Cohere2MoeConfig

    keys = {k: cfg_json[k] for k in Cohere2MoeConfig.PUBLISHED}
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
    from paddle_tpu.models import Cohere2MoeConfig, Cohere2MoeModel

    if int(cfg_json["n_routed_experts"]) != int(cfg_json["num_experts"]):
        raise SystemExit("families/cohere2_moe.py: n_routed_experts repeats "
                         "num_experts (the experts held) for the reader of "
                         "moe.experts_hit_share.serve; the file gives two "
                         "numbers")
    paddle.seed(int(seed))
    cfg = Cohere2MoeConfig(dtype=cfg_json["dtype"],
                           experts_held=_held(cfg_json),
                           **_model_keys(cfg_json))
    model = Cohere2MoeModel(cfg)
    n = sum(int(t._data.size) for t in model.state_dict().values())
    want = param_counts(cfg_json)["total"]
    if n != want:
        raise SystemExit(f"families/cohere2_moe.py: the model holds {n} "
                         f"parameters, the file's sizes give {want}")
    return cfg, model


def criterion():
    return None  # no training cell: the dropless layer serves only


def reference_scorer(cfg_json, cfg, model, padded_len, positions):
    import jax
    import jax.numpy as jnp

    cfgd, held = _model_keys(cfg_json), _held(cfg_json)
    w = {n: t._data for n, t in model.state_dict().items()}
    L = int(cfgd["num_hidden_layers"])
    window = int(cfgd["sliding_window"])

    def layer_weights(i, as_i):
        """Layer i's weights under layer as_i's names: one executable a
        kind of layer, whichever layer's weights it is given."""
        p = f"layers.{i}."
        return {f"layers.{as_i}." + n[len(p):]: a for n, a in w.items()
                if n.startswith(p)}

    def layer_fn(as_i):
        def run(wl, h, pos):
            margins = []
            with jax.default_matmul_precision("highest"):
                h = reference.layer(wl, as_i, cfgd, h, pos, q_block=_Q_BLOCK,
                                    experts_held=held, margins=margins)
            return h, margins[0]
        return jax.jit(run)

    kinds = {}
    for i in range(L):  # the first layer of each kind stands for its kind
        kinds.setdefault(cfgd["layer_types"][i], (i, layer_fn(i)))
    embed = jax.jit(lambda we, ids: reference.embed(we, cfgd, ids))

    def head_(wh, h, at):
        with jax.default_matmul_precision("highest"):
            return reference.head(wh, cfgd, h, at, v_block=_V_BLOCK)
    head = jax.jit(head_)
    w_embed = {"embed_tokens.weight": w["embed_tokens.weight"]}
    w_head = {k: w[k] for k in ("norm.weight", "embed_tokens.weight")}

    def score(ids, at, quiet=False):
        h = embed(w_embed, ids)
        pos = jnp.arange(ids.shape[0])
        closest = jnp.full((ids.shape[0],), jnp.inf)
        for i in range(L):
            as_i, fn = kinds[cfgd["layer_types"][i]]
            h, margin = fn(layer_weights(i, as_i), h, pos)
            closest = jnp.minimum(closest, margin)
        logits = head(w_head, h, at)
        undecided = closest[at] < ROUTE_TIE
        served = ids[jnp.minimum(at + 1, ids.shape[0] - 1)]
        flat = jnp.where(jnp.arange(logits.shape[1])[None] == served[:, None],
                         0.0, -1.0)
        if not quiet:
            n_off = int(undecided.sum())
            print(f"[bench] reference: {n_off} of {at.shape[0]} positions "
                  f"not compared (a choice that involves a held expert "
                  f"within {ROUTE_TIE} of another), "
                  f"{at.shape[0] - n_off} compared; the request's last "
                  f"compared position is {int(at.max())} "
                  f"({'past' if int(at.max()) >= window else 'inside'} the "
                  f"window of {window})", flush=True)
        return jnp.where(undecided[:, None], flat, logits)

    score(jnp.zeros((padded_len,), jnp.int32),
          jnp.zeros((positions,), jnp.int32), quiet=True).block_until_ready()
    return score


# ------------------------------------------------------- least work counts --
def param_counts(c):
    """Numbers of parameters by part, from the file's sizes: `num_experts`
    routed experts held, the router as wide as `published.num_experts`."""
    d, D = c["hidden_size"], c["head_dim"]
    Hq, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    L, F = c["num_hidden_layers"], c["intermediate_size"]
    out = {"attn": 2 * d * Hq * D + 2 * d * Hkv * D, "expert": 3 * d * F,
           "shared": 3 * d * F * c["num_shared_experts"],
           "router": d * c["published"]["num_experts"], "norm": d,
           "embed": c["vocab_size"] * d, "layers": L,
           "held": c["num_experts"]}
    out["layer"] = out["attn"] + out["shared"] + out["router"] \
        + out["held"] * out["expert"] + out["norm"]
    out["total"] = L * out["layer"] + out["embed"] + out["norm"]
    return out


def _layer_kinds(c):
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    n_win = sum(k == "sliding_attention" for k in kinds)
    return len(kinds) - n_win, n_win


def _attn_flops_per_row(c):
    """QK^T and PV of every query head over one key/value row."""
    return 4 * c["num_attention_heads"] * c["head_dim"]


def _rows(run):
    """(slots, a full layer's rows, ONE window layer's rows, experts hit a
    layer-step) of a mean decode step, from the program's counters."""
    k = run["counters"]
    steps = k.get("serving.decode_steps")
    full, win = k.get("serving.kv_tokens_read"), k.get(
        "serving.kv_window_rows_read")
    layer_steps = k.get("serving.moe_layer_steps")
    if not steps or not full or not win or not layer_steps:
        return None
    return (k["serving.active_slot_steps"] / steps, full / steps,
            win / steps, k["serving.moe_experts_hit"] / layer_steps)


def _row_bytes(c):
    """K and V of the key/value heads, bf16: 4096 B at 8 x 128."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * 2


def train_flops_per_token(run):
    return None


def decode_step_work(run):
    """Flops: every active slot's token through the parameters it meets —
    attention, the shared experts, the router, of its `num_experts_per_tok`
    routed experts the share held here, the head — and over the rows each
    layer keeps for it. Bytes: the weights a step must read once —
    everything but the experts nobody chose (the embedding table IS the
    head) — at 2 B a parameter, and every layer's rows: a full layer's
    whole contexts, a window layer's at most `sliding_window` a slot."""
    got = _rows(run)
    if got is None:
        return None
    slots, full, win, hit = got
    c = run["cfg"]
    p = param_counts(c)
    n_full, n_win = _layer_kinds(c)
    L = p["layers"]
    outside = L * (p["attn"] + p["shared"] + p["router"] + p["norm"]) \
        + p["embed"] + p["norm"]
    routed = c["num_experts_per_tok"] * p["held"] \
        / c["published"]["num_experts"]  # held experts a token, expected
    rows = n_full * full + n_win * win
    flops = 2 * (outside + L * routed * p["expert"]) * slots \
        + _attn_flops_per_row(c) * rows
    nbytes = 2 * (outside + L * hit * p["expert"]) + rows * _row_bytes(c)
    return flops, nbytes


def kernel_work(run, kernel):
    """One layer's call: `paged_attention` is the FULL layer's (every row
    of every active slot), `paged_attention_window` ONE window layer's (at
    most `sliding_window` rows a slot)."""
    if kernel not in ("paged_attention", "paged_attention_window"):
        return None
    got = _rows(run)
    if got is None:
        return None
    rows = got[1] if kernel == "paged_attention" else got[2]
    c = run["cfg"]
    return _attn_flops_per_row(c) * rows, rows * _row_bytes(c)
