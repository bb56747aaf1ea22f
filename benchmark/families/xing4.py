"""Family `xing4`: the Xing4.0 decoder of paddle_tpu/models/xing4.py —
latent (MLA) attention over a paged latent pool, a dropless sigmoid-routed
expert layer with a shared expert, a four-stream residual. The plain
reference is reference/xing4.py; the counts of least work are below, from
the configuration file's own numbers.

The reference cannot hold its float32 forward beside the engine at the
published widths (scores of 32 heads x 2816^2 alone are 1 GB), so
`reference_scorer` calls reference.layer a layer at a time over the SERVED
bf16 weights (upcast inside it, an expert at a time), with attention over
blocks of queries and the head over blocks of the vocabulary: the same
functions `reference.forward` calls, in the same order.

A choice of experts is a discontinuous function of its input: where the
reference's own choice lies within ROUTE_TIE of another one (its last chosen
expert's biased score against the best one left out, `reference.route`'s
margin, over the position's expert layers), bf16 serving may rightly choose
the other expert, and the logits then part by whole deviations — as far as a
planted fault moves them (PERF.md section 6, PR 30: one flipped expert is
0.5-1.0 of a logit). The comparison cannot tell the two apart there, so the
scorer does not compare such a position: it returns a row on which the
served token reads as the top one, and says how many positions that was.
The others it compares as they are, under the traffic's `near_tie`."""
from __future__ import annotations

from reference import xing4 as reference

_Q_BLOCK = 256      # queries a block of the reference's attention
_V_BLOCK = 8192     # rows of the head a block
# Below this routing margin (in biased score) a position is not compared
# (see above). Read on the chip at the cell's sizes (PERF.md section 6, PR
# 30; 768 positions of 48 requests): the served choice differed from the
# reference's in 40 % of the positions with a margin under 0.00025, 14 %
# at 0.001, 5 % at 0.0025, 2 of ~90 at 0.003-0.005 (gaps of 1.10 and 2.02;
# the largest such margin 0.0046), none of ~300 above, falling e-fold every
# ~0.0012; at 0.01 the expected number of such positions is ~0.0002 a run,
# and 7-10 % of the positions (3-10 of a run's 64) are left to compare, at a
# worst gap of 0.027 over the 179 seen.
ROUTE_TIE = 0.01


def _model_keys(cfg_json):
    """Every key of the published config that the program's config class
    holds; the file's other keys (name, source, reduced, model_type,
    num_nextn_predict_layers, ...) are the benchmark's own."""
    from paddle_tpu.models import Xing4Config

    return {k: cfg_json[k] for k in Xing4Config.PUBLISHED}


def sizes(cfg_json):
    """The file's sizes under the published names."""
    return {k: v for k, v in _model_keys(cfg_json).items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def vocab_size(cfg_json):
    return int(cfg_json["vocab_size"])


def build(cfg_json, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models import Xing4Config, Xing4Model

    if int(cfg_json["num_nextn_predict_layers"]) != 0:
        raise SystemExit("families/xing4.py: the served forward pass has no "
                         "multi-token prediction module; the file must say "
                         "num_nextn_predict_layers 0")
    paddle.seed(int(seed))
    cfg = Xing4Config(dtype=cfg_json["dtype"], **_model_keys(cfg_json))
    got = {k: getattr(cfg, k) for k in sizes(cfg_json)}
    if got != sizes(cfg_json):
        raise SystemExit(f"families/xing4.py: the program's config gives "
                         f"{got}, the configuration file {sizes(cfg_json)}")
    model = Xing4Model(cfg)
    n = sum(int(t._data.size) for t in model.state_dict().values())
    if n != param_counts(cfg_json)["total"]:
        raise SystemExit(f"families/xing4.py: the model holds {n} "
                         f"parameters, the file's sizes give "
                         f"{param_counts(cfg_json)['total']}")
    return cfg, model


def criterion():
    return None  # no training cell: MLA and the dropless layer serve only


def reference_scorer(cfg_json, cfg, model, padded_len, positions):
    import jax
    import jax.numpy as jnp

    cfgd = _model_keys(cfg_json)
    w = {n: t._data for n, t in model.state_dict().items()}
    L, dense = int(cfgd["num_hidden_layers"]), int(
        cfgd["first_k_dense_replace"])

    def layer_weights(i, as_i):
        """Layer i's weights under layer as_i's names: one executable a
        kind of layer, whichever layer's weights it is given."""
        p = f"layers.{i}."
        return {f"layers.{as_i}." + n[len(p):]: a for n, a in w.items()
                if n.startswith(p)}

    def layer_fn(as_i):
        def run(wl, X, pos):
            margins = []  # the layer's routing margins [T], if it routes
            X = reference.layer(wl, as_i, cfgd, X, pos, q_block=_Q_BLOCK,
                                margins=margins)
            return X, (margins[0] if margins
                       else jnp.full((X.shape[0],), jnp.inf))
        return jax.jit(run)

    kinds = {True: (0, layer_fn(0)), False: (dense, layer_fn(dense))}
    embed = jax.jit(lambda we, ids: reference.embed(we, cfgd, ids))
    head = jax.jit(lambda wh, X, at: reference.head(wh, cfgd, X, at,
                                                    v_block=_V_BLOCK))
    w_embed = {"embed_tokens.weight": w["embed_tokens.weight"]}
    w_head = {k: w[k] for k in ("norm.weight", "lm_head.weight")}

    def score(ids, at, quiet=False):
        X = embed(w_embed, ids)
        pos = jnp.arange(ids.shape[0])
        closest = jnp.full((ids.shape[0],), jnp.inf)
        for i in range(L):
            as_i, fn = kinds[i < dense]
            X, margin = fn(layer_weights(i, as_i), X, pos)
            closest = jnp.minimum(closest, margin)
        logits = head(w_head, X, at)
        undecided = closest[at] < ROUTE_TIE
        served = ids[jnp.minimum(at + 1, ids.shape[0] - 1)]
        flat = jnp.where(jnp.arange(logits.shape[1])[None] == served[:, None],
                         0.0, -1.0)
        if not quiet:
            print(f"[bench] reference: {int(undecided.sum())} of "
                  f"{at.shape[0]} positions not compared: a choice of "
                  f"experts within {ROUTE_TIE} of another", flush=True)
        return jnp.where(undecided[:, None], flat, logits)

    score(jnp.zeros((padded_len,), jnp.int32),
          jnp.zeros((positions,), jnp.int32), quiet=True).block_until_ready()
    return score


# ------------------------------------------------------- least work counts --
def param_counts(c):
    """Numbers of parameters by part, from the file's sizes."""
    d, H, n = c["hidden_size"], c["num_attention_heads"], c["hc_mult"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    L, dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    E, F = c["n_routed_experts"], c["moe_intermediate_size"]
    attn = d * rq + rq + rq * H * (dn + dr) + d * (rkv + dr) + rkv \
        + rkv * H * (dn + dv) + H * dv * d
    hc = 2 * (n * d + n * d * (2 * n + n * n) + 3 + 2 * n + n * n)
    norms = 2 * d
    out = {"attn": attn, "hc": hc, "norms": norms,
           "dense_ffn": 3 * d * c["intermediate_size"],
           "expert": 3 * d * F, "shared": 3 * d * F * c["n_shared_experts"],
           "router": d * E + E, "embed": c["vocab_size"] * d,
           "head": c["vocab_size"] * d, "final_norm": d,
           "layers": L, "dense_layers": dense, "moe_layers": L - dense}
    out["total"] = (L * (attn + hc + norms) + dense * out["dense_ffn"]
                    + (L - dense) * (E * out["expert"] + out["shared"]
                                     + out["router"])
                    + out["embed"] + out["head"] + d)
    return out


def _latent_row(c):
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def _attn_flops_per_row(c):
    """Absorbed decode: every head scores a latent row over its 576 lanes
    and weighs its 512 latent lanes."""
    return 2 * c["num_attention_heads"] * (_latent_row(c)
                                           + c["kv_lora_rank"])


def _means(run):
    """(slots, KV rows, experts hit a layer-step) of a mean decode step."""
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
    """Flops: every active slot's token through the parameters it meets
    (its 4 experts and the shared one, not the 64) and over the latent
    rows it holds. Bytes: the weights a step must read once — everything
    but the embedding table and the experts nobody chose — at 2 B a
    parameter, and the latent rows of every layer."""
    means = _means(run)
    if means is None:
        return None
    slots, rows, hit = means
    c = run["cfg"]
    p = param_counts(c)
    L, M = p["layers"], p["moe_layers"]
    outside = L * (p["attn"] + p["hc"] + p["norms"]) \
        + p["dense_layers"] * p["dense_ffn"] \
        + M * (p["shared"] + p["router"]) + p["head"] + p["final_norm"]
    active = outside + M * c["num_experts_per_tok"] * p["expert"]
    flops = 2 * active * slots + _attn_flops_per_row(c) * rows * L
    nbytes = 2 * (outside + M * hit * p["expert"]) \
        + rows * 2 * _latent_row(c) * L
    return flops, nbytes


def kernel_work(run, kernel):
    if kernel != "mla_paged_attention":
        return None
    means = _means(run)
    if means is None:
        return None
    rows = means[1]  # one layer's call: the latent read once for all heads
    c = run["cfg"]
    return _attn_flops_per_row(c) * rows, rows * 2 * _latent_row(c)
