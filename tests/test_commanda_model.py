"""The Cohere2-MoE decoder (command-a-plus-05-2026) against its plain
reference (benchmark/reference/cohere2_moe.py, written from the config
alone), on seeded weights at the tiny preset: hidden 64, 8 query heads over
2 key/value heads of 16, window 24, 16 experts top-2 beside 2 averaged
shared experts, one period of layers (3 sliding + 1 full), float32.

Tolerance of every logit comparison here: 1e-4 absolute. Both sides are
float32 with full-precision matmuls on the CPU and differ by the order of
their reductions only (seen: 3e-7 on logits of deviation 0.25); 1e-4 leaves
room for a BLAS that blocks differently. Every planted fault parts by at
least 5 x that (asserted)."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import Cohere2MoeConfig, Cohere2MoeModel
from paddle_tpu.models import cohere2_moe as program
from paddle_tpu.ops import kv_pool

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from reference import cohere2_moe as ref  # noqa: E402

TOL = 1e-4
# two slots: prompt lengths and decode steps. Window 24 at block 8 is a
# ring of 4 blocks = 32 rows: contexts of 82 and 41 wrap it 2.5 and 1.3 times
T_PROMPT, T_NEW = (50, 9), 32


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(3)
    cfg = Cohere2MoeConfig.preset("tiny")
    model = Cohere2MoeModel(cfg)
    model.eval()
    w = {n: t._data for n, t in model.state_dict().items()}
    rng = np.random.default_rng(0)
    ids = [rng.integers(1, cfg.vocab_size, p + T_NEW).astype(np.int32)
           for p in T_PROMPT]
    cfgd = cfg.as_dict()

    def fwd_of(cfgd):
        # weights are an argument, so every weight-shaped fault below
        # reuses one executable
        return jax.jit(lambda w, ids: ref.forward(
            w, cfgd, ids, jnp.arange(ids.shape[0])))
    fwd = fwd_of(cfgd)
    return {"cfg": cfg, "cfgd": cfgd, "model": model, "w": w, "ids": ids,
            "fwd": fwd, "fwd_of": fwd_of,
            "want": [np.asarray(fwd(w, jnp.asarray(i))) for i in ids]}


@pytest.mark.parametrize("block", [512, 16])
def test_float32_forward_matches_the_reference(tiny, monkeypatch, block):
    """No cache; at a walk block of 16 the 64 positions are 4 x 4 blocks of
    queries and keys with a running softmax, the window layers' walk
    starting two blocks back."""
    monkeypatch.setattr(program, "_WALK_BLOCK", block)
    model = tiny["model"]
    got = np.asarray(jax.jit(lambda ids: model(ids)._data)(
        jnp.asarray(tiny["ids"][0][None, :64])))[0]
    assert np.abs(got - tiny["want"][0][:64]).max() < TOL
    assert tiny["want"][0].std() > 0.05  # logits worth comparing


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_prefill_then_decode_through_ring_and_full_pools(tiny, kernel):
    """Prefill (a prompt of 50 lands its last 24 rows in the ring of 32),
    then 32 decode steps, two slots of different lengths through the window
    layers' rings and the full layer's pool, each layer its own table: the
    logits of every step against the reference's full forward."""
    model = tiny["model"]
    bs, M, B = 8, 11, 2
    spec = model.kv_cache_spec()
    assert spec.kind == "heads" and spec.window == 24
    assert spec.windows == [24, 24, 24, None] and spec.heads() == 2
    ring = spec.ring_blocks(bs)
    assert ring == 4
    ks, vs = spec.allocate(1 + B * M, bs, jnp.float32, slots=B)
    assert [k.shape[0] for k in ks] == [1 + B * ring] * 3 + [1 + B * M]
    full = np.arange(1, 1 + B * M, dtype=np.int32).reshape(B, M)
    tables = np.concatenate([full, kv_pool.ring_table(B, ring)], axis=1)
    head_w, logits_of = model.serving_head()

    def step(ks, vs, ids, offsets, seq_lens, bt, kernel):
        T = ids.shape[1]
        pos = offsets[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        h, new = model(ids, position_ids=pos, caches=list(zip(ks, vs)),
                       cache_offsets=offsets, seq_lens=seq_lens,
                       block_tables=spec.layer_tables(bt, bs),
                       paged_kernel=kernel)
        Bq = ids.shape[0]
        return (logits_of(h._data.reshape(Bq * T, -1), head_w._data
                          ).reshape(Bq, T, -1),
                [c[0]._data for c in new], [c[1]._data for c in new],
                model.step_counters()["moe_experts_hit"])

    prefill = jax.jit(lambda k, v, i, o, s, b: step(k, v, i, o, s, b, None))
    decode = jax.jit(lambda k, v, i, o, s, b: step(k, v, i, o, s, b, kernel))
    for s, P in enumerate(T_PROMPT):  # one slot a call, padded to 64
        ids = np.zeros((1, 64), np.int32)
        ids[0, :P] = tiny["ids"][s][:P]
        lg, ks, vs, _ = prefill(ks, vs, jnp.asarray(ids),
                                jnp.zeros((1,), jnp.int32),
                                jnp.asarray([P], jnp.int32),
                                jnp.asarray(tables[s:s + 1]))
        assert np.abs(np.asarray(lg)[0, :P] - tiny["want"][s][:P]
                      ).max() < TOL
    lens = np.asarray(T_PROMPT, np.int32)
    for t in range(T_NEW):
        ids = np.asarray([[tiny["ids"][s][lens[s]]] for s in range(B)],
                         np.int32)
        lg, ks, vs, hit = decode(ks, vs, jnp.asarray(ids), jnp.asarray(lens),
                                 jnp.asarray(lens + 1), jnp.asarray(tables))
        for s in range(B):
            assert np.abs(np.asarray(lg)[s, 0] - tiny["want"][s][lens[s]]
                          ).max() < TOL, (kernel, t, s)
        lens = lens + 1
    assert 2 <= int(hit) <= 4 * 2 * 2  # 2 slots x top-2 in 4 expert layers


# ----------------------------------------------------------- planted faults --
def _heads_by_modulo(w):
    """Query head h reads key/value head h % Hkv instead of h // (Hq/Hkv):
    the same as moving the query heads (and their rows of o_proj) so that
    place (g, r) holds head r * Hkv + g."""
    Hq, Hkv, D = 8, 2, 16
    perm = np.arange(Hq).reshape(Hq // Hkv, Hkv).T.reshape(-1)
    cols = (perm[:, None] * D + np.arange(D)[None]).reshape(-1)
    out = dict(w)
    for n, a in w.items():
        if n.endswith("q_proj.weight"):
            out[n] = a[:, cols]
        if n.endswith("o_proj.weight"):
            out[n] = a[cols]
    return out


def _float8(w):
    return {n: a.astype(jnp.float8_e4m3fn).astype(a.dtype) for n, a in
            w.items()}


def _rope_split_halves(x, pos, theta):
    D = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, D, 2, dtype=jnp.float32)
                                  / D))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _serial_layer(w, i, cfg, h, pos, q_block=None, experts_held=None,
                  margins=None):
    p = f"layers.{i}."
    sliding = cfg["layer_types"][i] == "sliding_attention"
    eps = float(cfg["layer_norm_eps"])
    h = h + ref.attention(
        w, p + "self_attn.", cfg,
        ref.layer_norm(h, w[p + "input_layernorm.weight"], eps), pos,
        sliding, q_block)
    return h + ref.moe(w, p + "mlp.", cfg, ref.layer_norm(
        h, w[p + "input_layernorm.weight"], eps), experts_held, margins)


def _route_softmax(w, p, cfg, u, experts_held=None):
    """`ref.route` with a softmax over the experts where the sigmoid is."""
    s = jax.nn.softmax(ref._mm(u, w[p + "router.weight"]), -1)
    top, chosen = jax.lax.top_k(s, int(cfg["num_experts_per_tok"]))
    return chosen, top / top.sum(-1, keepdims=True), jnp.zeros(u.shape[0])


_real_attention = ref.attention


def _rope_on_full_layers(w, p, cfg, u, pos, sliding, q_block=None):
    if sliding:
        return _real_attention(w, p, cfg, u, pos, True, q_block)
    return _real_attention(w, p, {**cfg, "sliding_window": 10 ** 6}, u, pos,
                           True, q_block)


# name -> (what to change in the reference's weights, its config, its code)
FAULTS = {
    "window ignored": dict(cfg={"sliding_window": 10 ** 6}),
    "window off by one block": dict(cfg={"sliding_window": 24 + 8}),
    "rotary applied on a full layer": dict(
        code=("attention", _rope_on_full_layers)),
    "rotary on split halves, not adjacent pairs": dict(
        code=("rope", _rope_split_halves)),
    "head map h % 8 for h // 16": dict(weights=_heads_by_modulo),
    "shared experts summed, not averaged": dict(weights=lambda w: {
        n: a * 2.0 if n.endswith("shared.down_proj.weight") else a
        for n, a in w.items()}),  # 2 shared experts: sum = 2 x mean
    "softmax for sigmoid scores": dict(code=("route", _route_softmax)),
    "unnormalised top-k weights": dict(cfg={"norm_topk_prob": False}),
    "a serial block": dict(code=("layer", _serial_layer)),
    "weights rounded to float8": dict(weights=_float8),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_parts_from_the_reference(tiny, monkeypatch, fault):
    """Each fault, planted in the reference (its weights, its config or its
    code: an equivalent of the program computing that instead), moves the
    logits of a 70-token context (past the window of 24) by >= 5 x the
    tolerance: the comparisons above cannot pass with that fault in the
    program."""
    plan = FAULTS[fault]
    if "code" in plan:
        monkeypatch.setattr(ref, *plan["code"])
    cfgd = {**tiny["cfgd"], **plan.get("cfg", {})}
    fwd = tiny["fwd_of"](cfgd) if ("code" in plan or "cfg" in plan) \
        else tiny["fwd"]
    w = plan.get("weights", lambda w: w)(tiny["w"])
    got = np.asarray(fwd(w, jnp.asarray(tiny["ids"][0][:70])))
    assert np.abs(got - tiny["want"][0][:70]).max() >= 5 * TOL, fault


# ------------------------------------------------------- the chip's share --
def _share_model(tiny, lo, hi):
    """A model told it holds experts lo..hi-1, over the full model's
    weights."""
    part = Cohere2MoeModel(Cohere2MoeConfig.preset(
        "tiny", experts_held=(lo, hi)))
    part.eval()
    src = tiny["model"].state_dict()
    for n, t in part.state_dict().items():
        a = src[n]._data
        t._data = a[lo:hi] if ".experts." in n else a
    return part


def test_the_shares_add_up_to_the_uncut_reference_layer(tiny):
    """Eight shares of 2 experts each: their routed parts, with what every
    chip computes alike (the residual, attention and the averaged shared
    experts) counted once, give the uncut reference's layer — and the
    reference's own share is the program's."""
    cfgd, w = tiny["cfgd"], tiny["w"]
    ids = jnp.asarray(tiny["ids"][0][:40])
    pos = jnp.arange(40)
    h = ref.embed(w, cfgd, ids)
    want = np.asarray(ref.layer(w, 0, cfgd, h, pos))
    u = ref.layer_norm(h, w["layers.0.input_layernorm.weight"], 1e-5)
    p = "layers.0.mlp."
    F = cfgd["intermediate_size"]
    sg, su, sd = (w[p + f"shared.{m}_proj.weight"]
                  for m in ("gate", "up", "down"))
    shared = sum(ref.ffn(u, sg[:, j * F:(j + 1) * F], su[:, j * F:(j + 1) * F],
                         sd[j * F:(j + 1) * F]) for j in range(2)) / 2
    alike = np.asarray(h + ref.attention(w, "layers.0.self_attn.", cfgd, u,
                                         pos, True) + shared)
    outs = []
    for lo in range(0, 16, 2):
        layer = _share_model(tiny, lo, lo + 2).layers[0]
        out, _ = layer(h[None], pos[None])
        outs.append(np.asarray(out)[0])
    total = sum(o - alike for o in outs) + alike
    assert np.abs(total - want).max() < TOL
    # the reference given share 3 is the program's share 3
    w3 = {**w, p + "experts.gate_up": w[p + "experts.gate_up"][6:8],
          p + "experts.down": w[p + "experts.down"][6:8]}
    want3 = np.asarray(ref.layer(w3, 0, cfgd, h, pos, experts_held=(6, 8)))
    assert np.abs(outs[3] - want3).max() < TOL
    assert np.abs(outs[3] - want).max() >= 5 * TOL  # a share is not the whole


def test_routing_margin_counts_only_choices_that_involve_a_held_expert(tiny):
    """`reference.route`'s margin with every expert held is the k-th score
    less the (k+1)-th; with a share held it is never smaller, and it is
    infinite for a token none of whose near choices touches the share."""
    cfgd, w = tiny["cfgd"], tiny["w"]
    u = ref.layer_norm(ref.embed(w, cfgd, jnp.asarray(tiny["ids"][0][:60])),
                       w["layers.0.input_layernorm.weight"], 1e-5)
    p = "layers.0.mlp."
    chosen, weight, every = ref.route(w, p, cfgd, u)
    s = np.sort(np.asarray(jax.nn.sigmoid(u @ w[p + "router.weight"])),
                axis=-1)[:, ::-1]
    np.testing.assert_allclose(np.asarray(every), s[:, 1] - s[:, 2],
                               atol=1e-6)
    assert np.abs(np.asarray(weight).sum(-1) - 1.0).max() < 1e-5
    _, _, share = ref.route(w, p, cfgd, u, experts_held=(4, 6))
    assert (np.asarray(share) >= np.asarray(every) - 1e-7).all()
    assert (np.asarray(share) > np.asarray(every) + 1e-4).any()


def test_scorer_compares_what_the_reference_can_decide(tiny, monkeypatch):
    """families/cohere2_moe.py's scorer, a layer at a time over the chip's
    share, gives the reference's logits where every choice that involves a
    held expert is clear of ROUTE_TIE, and a row on which the served token
    reads as the top one where it is not; `build` checks the parameter
    count against the file's sizes."""
    import families

    cfg_json = {**tiny["cfgd"], "family": "cohere2_moe", "dtype": "float32",
                "num_experts": 4, "n_routed_experts": 4,
                "published": {"num_experts": 16}}
    fam = families.of(cfg_json)
    cfg, model = fam.build(cfg_json, 3)
    assert cfg.experts_held == (0, 4) and cfg.num_experts == 16
    with pytest.raises(SystemExit, match="two numbers"):
        fam.build({**cfg_json, "n_routed_experts": 16}, 3)
    counts = fam.param_counts(cfg_json)
    assert counts["total"] == sum(
        int(t._data.size) for t in model.state_dict().values())
    with monkeypatch.context() as m:  # a file whose sizes give another count
        m.setattr(fam, "param_counts",
                  lambda c: {**counts, "total": counts["total"] + 64})
        with pytest.raises(SystemExit, match="parameters"):
            fam.build(cfg_json, 3)
    w = {n: t._data for n, t in model.state_dict().items()}
    ids = jnp.asarray(tiny["ids"][0][:48])
    at = jnp.asarray([3, 30, 46], jnp.int32)
    monkeypatch.setattr(fam, "ROUTE_TIE", 0.0)
    score = fam.reference_scorer(cfg_json, cfg, model, 48, 3)
    got = np.asarray(score(ids, at, quiet=True))
    want = np.asarray(ref.forward(w, tiny["cfgd"], ids, at,
                                  experts_held=(0, 4)))
    assert np.abs(got - want).max() < TOL
    monkeypatch.setattr(fam, "ROUTE_TIE", 10.0)  # nothing is decided
    flat = np.asarray(score(ids, at, quiet=True))
    assert (flat.argmax(-1) == np.asarray(ids)[np.asarray(at) + 1]).all()
