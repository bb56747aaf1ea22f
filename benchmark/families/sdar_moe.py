"""Family `sdar_moe`: the SDAR-MoE decoder of paddle_tpu/models/sdar_moe.py —
block-causal grouped-query attention with query/key norms, softmax-routed
experts without a shared one, generation by diffusion over blocks. The plain
reference is reference/sdar_moe.py; the counts of least work are below, from
the configuration file's own numbers.

The weights are the program's own seeded draws times the file's
`seeded_weights` factors (`_scale_seeded`; why each: the file's
`assumed.seeded_weights`), so that a row's state is its own and the rows of a
forward choose their experts apart, as a trained router's rows do.

The configuration file holds the published config's keys and, under
`generation`, the settings config.json does not hold (block length, denoise
forwards, strategy, threshold, mask id): `build` hands the program both, and
the reference is given the same.

The reference cannot hold its float32 forward beside the engine at the
published widths, so `reference_scorer` calls reference.layer a layer at a
time over the SERVED bf16 weights (upcast inside it, an expert at a time),
with attention over blocks of queries and the head over blocks of the
vocabulary: the same functions `reference.forward` calls, in the same order.

What `score(ids, at)` answers. The driver asks for the logits that decide
the token at `at + 1` given the served sequence. A block-diffusion decoder
decides a token in the denoise forward that unmasks its position, from the
clean blocks before its own and from its own block AS THAT FORWARD SAW IT.
So for each compared position the scorer replays the position's block with
`reference.generate_block` from the served clean prefix (whose keys and
values, a layer, are computed once a request: block-causality makes them
what every later block sees), teacher-forced with the served tokens, and
returns the reference's logits at that position from the forward in which
THE REFERENCE unmasks it. (The prompt's length is the least `at` + 1 and the
sequence's end the largest + 2: the driver compares a request's first and
last tokens. A last block that `max_new_tokens` cut holds positions the
request was never given: there the replay takes the reference's own.)

Two choices are discontinuous, and a position next to either is not
compared: the scorer returns a row on which the served token reads as the
top one, and says how many positions that was, and why. Where the reference's
choice of experts at the position lies within ROUTE_TIE (in probability) of
another one, in any layer of that forward, bf16 serving may rightly choose
the other. Where the reference's choice of WHICH positions to unmask lies
within ORDER_TIE (relative, in confidence) of another choice, in a forward
of the block up to the one that unmasks the position, bf16 serving may
rightly have unmasked the position in another forward, which saw another
block. The others are compared as they are, under the traffic's
`near_tie`."""
from __future__ import annotations

import numpy as np

from reference import sdar_moe as reference

_Q_BLOCK = 128      # queries a block of the reference's attention: float32
#                     scores of 32 heads x 128 x 4864 are 80 MB
_V_BLOCK = 9496     # rows of the head a block: 151936 = 16 x 9496
# Below this routing margin (in probability) and this relative difference in
# confidence a position is not compared (see above). The readings they lie
# between are in traffic/blockgen_closed_c32.json (`near_tie_why`) and
# PERF.md section 6, PR 37.
ROUTE_TIE = 5e-5
ORDER_TIE = 0.01


def _model_keys(cfg_json):
    """Every key of the published config that the program's config class
    holds (the file's other keys are the benchmark's own)."""
    from paddle_tpu.models import SdarMoeConfig

    return {k: cfg_json[k] for k in SdarMoeConfig.PUBLISHED}


def _reference_cfg(cfg_json):
    """What reference/sdar_moe.py reads: the published keys and, flat beside
    them, the generation settings."""
    return {**_model_keys(cfg_json), **cfg_json["generation"]}


def sizes(cfg_json):
    """The file's sizes under the published names."""
    return {k: v for k, v in cfg_json.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def vocab_size(cfg_json):
    return int(cfg_json["vocab_size"])


def build(cfg_json, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models import SdarMoeConfig, SdarMoeModel

    if int(cfg_json["n_routed_experts"]) != int(cfg_json["num_experts"]):
        raise SystemExit("families/sdar_moe.py: n_routed_experts repeats "
                         "num_experts for the reader of "
                         "moe.experts_hit_share.serve; the file gives two "
                         "numbers")
    paddle.seed(int(seed))
    cfg = SdarMoeConfig(dtype=cfg_json["dtype"],
                        generation=cfg_json["generation"],
                        **_model_keys(cfg_json))
    model = SdarMoeModel(cfg)
    _scale_seeded(model, cfg_json.get("seeded_weights", {}))
    n = sum(int(t._data.size) for t in model.state_dict().values())
    want = param_counts(cfg_json)["total"]
    if n != want:
        raise SystemExit(f"families/sdar_moe.py: the model holds {n} "
                         f"parameters, the file's sizes give {want}")
    return cfg, model


def _scale_seeded(model, scales):
    """The file's `seeded_weights`: a parameter whose name ends with a key is
    the program's own draw times the key's factor (why each: the file's
    `assumed.seeded_weights`). A key that names no parameter is a mistake in
    the file."""
    import jax.numpy as jnp

    state = model.state_dict()
    for suffix, factor in scales.items():
        names = [n for n in state if n.endswith(suffix)]
        if not names:
            raise SystemExit(f"families/sdar_moe.py: seeded_weights names "
                             f"{suffix!r}, which no parameter's name ends "
                             "with")
        for n in names:
            # in the parameter's own dtype, one parameter at a time: no
            # float32 copy of an expert bank beside the weights
            t = state[n]
            t._data = (t._data * jnp.asarray(factor, t._data.dtype)
                       ).block_until_ready()


def criterion():
    return None  # no training cell: the dropless layer serves only


def reference_scorer(cfg_json, cfg, model, padded_len, positions):
    import jax
    import jax.numpy as jnp

    cfgd = _reference_cfg(cfg_json)
    w = {n: t._data for n, t in model.state_dict().items()}
    # the control reading (configs/*.float8-control.json): the reference
    # over the served weights rounded to a lower precision, a layer at a
    # time as it reads them; such a run has to come out not correct
    low = cfg_json.get("reference_weights")

    def read(a):
        return a if low is None or a.ndim < 2 else \
            a.astype(getattr(jnp, low)).astype(a.dtype)
    n_layers, B = int(cfgd["num_hidden_layers"]), int(cfgd["block_length"])
    Hkv, D = int(cfgd["num_key_value_heads"]), int(cfgd["head_dim"])

    def layer_weights(i):
        """Layer i's weights under layer 0's names: one executable,
        whichever layer's weights it is given."""
        p = f"layers.{i}."
        return {"layers.0." + n[len(p):]: read(a) for n, a in w.items()
                if n.startswith(p)}

    @jax.jit
    def clean_layer(wl, h, pos):
        """A layer over the whole clean sequence -> (h', K, V)."""
        kv = []
        with jax.default_matmul_precision("highest"):
            h = reference.layer(wl, 0, cfgd, h, pos, q_block=_Q_BLOCK,
                                kv_out=kv)
        return h, kv[0][0], kv[0][1]

    @jax.jit
    def block_layer(wl, hb, posb, k, v, pos, n_clean):
        """A layer over one block's B rows against the first `n_clean`
        clean rows' keys and values -> (hb', routing margin [B])."""
        margins = []
        with jax.default_matmul_precision("highest"):
            hb = reference.layer(wl, 0, cfgd, hb, posb,
                                 past=(k, v, pos, pos < n_clean),
                                 margins=margins)
        return hb, margins[0]

    embed = jax.jit(lambda we, ids: reference.embed(we, cfgd, ids))

    @jax.jit
    def head(wh, h):
        with jax.default_matmul_precision("highest"):
            return reference.head(wh, cfgd, h, v_block=_V_BLOCK)

    w_embed = {"embed_tokens.weight": read(w["embed_tokens.weight"])}
    w_head = {k: read(w[k]) for k in ("norm.weight", "lm_head.weight")}
    mask_id = int(cfgd["mask_token_id"])

    def score(ids, at, quiet=False):
        ids_np, at_np = np.asarray(ids), np.asarray(at)
        P, end = int(at_np.min()) + 1, int(at_np.max()) + 2
        pos = jnp.arange(ids_np.shape[0])
        # the clean sequence's keys and values, a layer (rows past a block's
        # start are not read by that block's replay)
        h, kvs = embed(w_embed, jnp.asarray(ids_np)), []
        for i in range(n_layers):
            h, k, v = clean_layer(layer_weights(i), h, pos)
            kvs.append((k, v))

        def replay(start):
            """generate_block of the block at `start`, teacher-forced."""
            block = np.full(B, mask_id, np.int64)
            masked = np.ones(B, bool)
            forced = np.full(B, -1, np.int64)
            for j in range(B):
                p = start + j
                if p < P:  # the prompt's tail opens the block unmasked
                    block[j], masked[j] = ids_np[p], False
                elif p < end:
                    forced[j] = ids_np[p]
            posb = jnp.arange(start, start + B)

            def block_forward(blk, m):
                hb = embed(w_embed, jnp.asarray(
                    np.where(m, mask_id, blk).astype(np.int32)))
                least = jnp.full((B,), jnp.inf)
                for i in range(n_layers):
                    hb, margin = block_layer(layer_weights(i), hb, posb,
                                             kvs[i][0], kvs[i][1], pos, start)
                    least = jnp.minimum(least, margin)
                return head(w_head, hb), least

            return reference.generate_block(
                None, cfgd, ids_np[:start], block, masked, forced=forced,
                block_forward=block_forward)

        rows, why = [], {"route": 0, "order": 0}
        replays = {}
        for a in at_np:
            p = int(a) + 1
            start = p // B * B
            if start not in replays:
                replays[start] = replay(start)
            tie, row = np.inf, None
            for step in replays[start]:
                tie = min(tie, step["tie"])
                if step["unmask"][p - start]:
                    row, margin = step["logits"][p - start], \
                        float(step["margin"][p - start])
                    break
            if row is None:  # (the tail of a prompt: never asked for)
                raise SystemExit(f"families/sdar_moe.py: position {p} is "
                                 f"not generated (prompt of {P})")
            gap = float(row.max() - row[ids_np[p]])
            left = "order" if tie < ORDER_TIE else \
                "route" if margin < ROUTE_TIE else None
            if left:
                why[left] += 1
                row = np.where(np.arange(row.shape[0]) == ids_np[p], 0.0,
                               -1.0).astype(np.float32)
            if not quiet:
                print(f"[bench] reference: position {p} routing margin "
                      f"{margin:.3g} order tie {tie:.3g} gap {gap:.5f} "
                      + (f"not compared ({left})" if left else "compared"),
                      flush=True)
            rows.append(row)
        if not quiet:
            n_off = why["route"] + why["order"]
            print(f"[bench] reference: {n_off} of {len(rows)} positions not "
                  f"compared ({why['route']} with a choice of experts "
                  f"within {ROUTE_TIE} of another, {why['order']} in a "
                  f"block whose choice of positions to unmask lies within "
                  f"{ORDER_TIE} of another), {len(rows) - n_off} compared; "
                  f"prompt of {P}, {end - P} tokens, {len(replays)} blocks "
                  "replayed", flush=True)
        return jnp.asarray(np.stack(rows))

    # warm both layer executables, the embedding and the head on a dummy
    dummy = np.ones((padded_len,), np.int32)
    score(jnp.asarray(dummy), jnp.asarray(
        np.full((positions,), padded_len - 2 * B, np.int32)), quiet=True)
    return score


# ------------------------------------------------------- least work counts --
def param_counts(c):
    """Numbers of parameters by part, from the file's sizes."""
    d, D = c["hidden_size"], c["head_dim"]
    Hq, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    L, F, E = c["num_hidden_layers"], c["moe_intermediate_size"], \
        c["num_experts"]
    out = {"attn": 2 * d * Hq * D + 2 * d * Hkv * D, "qk_norm": 2 * D,
           "expert": 3 * d * F, "router": d * E, "norm": d,
           "embed": c["vocab_size"] * d, "head": c["vocab_size"] * d,
           "layers": L, "experts": E}
    out["layer"] = out["attn"] + out["qk_norm"] + out["router"] \
        + E * out["expert"] + 2 * out["norm"]
    out["total"] = L * out["layer"] + out["embed"] + out["head"] \
        + out["norm"]
    return out


def _attn_flops_per_row(c):
    """QK^T and PV of every query head over one key/value row, one query
    row."""
    return 4 * c["num_attention_heads"] * c["head_dim"]


def _row_bytes(c):
    """K and V of the key/value heads, bf16: 2048 B at 4 x 128."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * 2


def _step(run):
    """(slots, KV rows read a layer, experts hit a layer-step) of a mean
    forward of the window, from the program's counters. A forward's
    attention reads, a slot, its committed rows and its block: what
    serving.kv_tokens_read counts."""
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
    """A mean forward (a block step, denoise or commit: the same program).
    Flops: every active slot's `block_length` rows through the parameters
    they meet — attention, the router, `num_experts_per_tok` experts, the
    head — and each of them over the rows the slot keeps. Bytes: the weights
    a forward must read once — everything but the experts nobody chose and
    the embedding table (a lookup) — at 2 B a parameter, and every layer's
    rows once a slot (the block's rows share them)."""
    got = _step(run)
    if got is None:
        return None
    slots, rows, hit = got
    c = run["cfg"]
    p = param_counts(c)
    L, B = p["layers"], c["generation"]["block_length"]
    outside = L * (p["attn"] + p["router"]) + p["head"]
    flops = 2 * (outside + L * c["num_experts_per_tok"] * p["expert"]) \
        * slots * B + _attn_flops_per_row(c) * B * rows * L
    nbytes = 2 * (outside + L * hit * p["expert"]) \
        + rows * L * _row_bytes(c)
    return flops, nbytes


def kernel_work(run, kernel):
    """One layer's call of the paged kernel over a block span: the `cur + B`
    rows of every active slot read once, and the block's B query rows over
    each of them."""
    if kernel != "paged_attention":
        return None
    got = _step(run)
    if got is None:
        return None
    c = run["cfg"]
    B = c["generation"]["block_length"]
    return _attn_flops_per_row(c) * B * got[1], got[1] * _row_bytes(c)
