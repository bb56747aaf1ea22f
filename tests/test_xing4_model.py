"""The Xing4.0 decoder against its plain reference (benchmark/reference/
xing4.py, written from the config alone), on seeded weights at the tiny
preset: hidden 64, 4 heads, 8 experts top-2, 4 residual streams, 1 dense +
2 expert layers, float32.

Tolerance of every logit comparison here: 1e-4 absolute. Both sides are
float32 with full-precision matmuls on the CPU and differ by the order of
their reductions only (seen: 2e-7 on logits of deviation 0.16); 1e-4 leaves
room for a BLAS that blocks differently and is 1/1600 of a logit's
deviation. Every planted fault parts by at least 5 x that (asserted)."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import Xing4Config, Xing4Model
from paddle_tpu.models import xing4 as program
from paddle_tpu.nn.moe import DroplessMoE
from paddle_tpu.serving import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from reference import xing4 as ref  # noqa: E402

TOL = 1e-4
T_PROMPT, T_NEW = (13, 6), 8   # two slots: prompt lengths, decode steps


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(3)
    cfg = Xing4Config.preset("tiny")
    model = Xing4Model(cfg)
    model.eval()
    w = {n: t._data for n, t in model.state_dict().items()}
    rng = np.random.default_rng(0)
    ids = [rng.integers(1, cfg.vocab_size, p + T_NEW).astype(np.int32)
           for p in T_PROMPT]
    cfgd = cfg.as_dict()
    # weights are an argument, so every weight-shaped fault below reuses
    # this one executable
    fwd = jax.jit(lambda w, ids: ref.forward(w, cfgd, ids,
                                             jnp.arange(ids.shape[0])))
    return {"cfg": cfg, "cfgd": cfgd, "model": model, "w": w, "ids": ids,
            "fwd": fwd, "want": [np.asarray(fwd(w, jnp.asarray(i)))
                                 for i in ids]}


def test_float32_forward_matches_the_reference(tiny):
    model = tiny["model"]
    got = np.asarray(jax.jit(lambda ids: model(ids)._data)(
        jnp.asarray(tiny["ids"][0][None])))[0]
    assert np.abs(got - tiny["want"][0]).max() < TOL
    assert tiny["want"][0].std() > 0.05  # logits worth comparing


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_prefill_then_decode_through_the_latent_cache(tiny, kernel):
    """Prefill (expanded attention over the pool's view), then 8 absorbed
    decode steps, two slots of different lengths through one paged latent
    pool: the logits of every step against the reference's full forward."""
    model, cfg = tiny["model"], tiny["cfg"]
    bs, M = 8, 4
    spec = model.kv_cache_spec()
    assert spec.kind == "latent" and spec.row_width() == 128
    pools, none = spec.allocate(1 + 2 * M, bs, jnp.float32)
    assert none == [] and pools[0].shape == (1 + 2 * M, bs, 128)
    tables = np.arange(1, 1 + 2 * M, dtype=np.int32).reshape(2, M)
    head_w, logits_of = model.serving_head()

    def step(pools, ids, offsets, seq_lens, bt, kernel):
        T = ids.shape[1]
        pos = offsets[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        h, new = model(ids, position_ids=pos,
                       caches=[(p,) for p in pools], cache_offsets=offsets,
                       seq_lens=seq_lens, block_tables=bt,
                       paged_kernel=kernel)
        B = ids.shape[0]
        return (logits_of(h._data.reshape(B * T, -1), head_w._data
                          ).reshape(B, T, -1),
                [c[0]._data for c in new],
                model.step_counters()["moe_experts_hit"])

    prefill = jax.jit(lambda p, i, o, s, b: step(p, i, o, s, b, None))
    decode = jax.jit(lambda p, i, o, s, b: step(p, i, o, s, b, kernel))
    for s, P in enumerate(T_PROMPT):  # one slot a call, padded to 16
        ids = np.zeros((1, 16), np.int32)
        ids[0, :P] = tiny["ids"][s][:P]
        lg, pools, _ = prefill(pools, jnp.asarray(ids),
                            jnp.zeros((1,), jnp.int32),
                            jnp.asarray([P], jnp.int32),
                            jnp.asarray(tables[s:s + 1]))
        assert np.abs(np.asarray(lg)[0, :P] - tiny["want"][s][:P]
                      ).max() < TOL
    lens = np.asarray(T_PROMPT, np.int32)
    for t in range(T_NEW):
        ids = np.asarray([[tiny["ids"][s][lens[s]]] for s in range(2)],
                         np.int32)
        lg, pools, hit = decode(pools, jnp.asarray(ids), jnp.asarray(lens),
                           jnp.asarray(lens + 1), jnp.asarray(tables))
        for s in range(2):
            assert np.abs(np.asarray(lg)[s, 0] - tiny["want"][s][lens[s]]
                          ).max() < TOL, (kernel, t, s)
        lens = lens + 1
    assert 2 <= int(hit) <= 2 * 8  # 2 slots x top-2 in 2 expert layers


def _identity_h_res(w, n=4):
    """alpha_res 0 and a bias of +30 on the diagonal, -30 off it: Sinkhorn
    of exp(that) is the identity to 1e-26."""
    out = dict(w)
    for name in w:
        if name.endswith("_hc.alpha"):
            out[name] = w[name].at[2].set(0.0)
        if name.endswith("_hc.bias"):
            res = jnp.where(jnp.eye(n, dtype=bool), 30.0, -30.0).reshape(-1)
            out[name] = w[name].at[2 * n:].set(res)
    return out


FAULTS = {
    "one expert's output dropped": lambda w: {
        **w, "layers.1.mlp.experts.down":
        w["layers.1.mlp.experts.down"].at[2].set(0.0)},
    "H_res = identity": _identity_h_res,
    "selection bias ignored": lambda w: {
        n: jnp.zeros_like(a) if n.endswith("router.bias") else a
        for n, a in w.items()},
    "routed_scaling_factor left out": lambda w: {
        n: a / 2.0 if n.endswith("experts.down") else a  # 2 -> 1: linear
        for n, a in w.items()},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_parts_from_the_reference(tiny, fault):
    """Each fault, planted in the reference's weights (an equivalent of
    leaving the term out of its arithmetic), moves the logits by >= 5 x
    the tolerance: the comparison above cannot pass with the term
    missing from the program."""
    got = np.asarray(tiny["fwd"](FAULTS[fault](tiny["w"]),
                                 jnp.asarray(tiny["ids"][0])))
    assert np.abs(got - tiny["want"][0]).max() >= 5 * TOL, fault


def test_an_unrotated_shared_key_parts_from_the_reference(tiny, monkeypatch):
    real = ref.rope
    monkeypatch.setattr(  # k_rope is [T, dr]; the queries are [T, H, dr]
        ref, "rope", lambda x, pos, cfg: x if x.ndim == 2
        else real(x, pos, cfg))
    cfgd = tiny["cfgd"]
    got = np.asarray(jax.jit(lambda w, ids: ref.forward(
        w, cfgd, ids, jnp.arange(10)))(
            tiny["w"], jnp.asarray(tiny["ids"][1][:10])))
    assert np.abs(got - tiny["want"][1][:10]).max() >= 5 * TOL


# ------------------------------------------------------ the expert layer --
@pytest.fixture(scope="module")
def moe_layer():
    paddle.seed(5)
    full = DroplessMoE(48, 24, 8, 2, n_shared=1, routed_scaling_factor=2.0)
    w = {"m." + n: t._data for n, t in full.state_dict().items()}
    cfgd = dict(num_experts_per_tok=2, n_routed_experts=8,
                moe_intermediate_size=24, norm_topk_prob=True,
                routed_scaling_factor=2.0)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((3, 7, 48)),
                    jnp.float32)
    return full, w, cfgd, x


def _share(full, lo, hi):
    """A layer told it holds experts lo..hi-1, over the full layer's
    weights."""
    part = DroplessMoE(48, 24, 8, 2, n_shared=1, routed_scaling_factor=2.0,
                       experts_held=(lo, hi))
    src = dict(full.state_dict())
    for n, t in part.state_dict().items():
        a = src[n]._data
        t._data = a[lo:hi] if n.startswith("experts.") else a
    return part


@pytest.mark.parametrize("cuts", [2, 4])
def test_shares_add_up_to_the_uncut_reference_layer(moe_layer, cuts):
    """Halves and quarters of the experts, each computing its own part of
    the result for the tokens routed to it, with the shared expert (which
    every share computes alike) counted once, give the uncut layer."""
    full, w, cfgd, x = moe_layer
    want = np.asarray(ref.moe(w, "m.", cfgd, x.reshape(21, 48)))
    shared = np.asarray(program.swiglu(
        x.reshape(21, 48), w["m.shared.gate_proj.weight"],
        w["m.shared.up_proj.weight"], w["m.shared.down_proj.weight"]))
    per = 8 // cuts
    total = sum(np.asarray(_share(full, lo, lo + per)(x)._data
                           ).reshape(21, 48) - shared
                for lo in range(0, 8, per)) + shared
    assert np.abs(total - want).max() < TOL
    # and the reference's own share is that share
    lo = per
    part = _share(full, lo, lo + per)
    w_part = {**w, "m.experts.gate_up": w["m.experts.gate_up"][lo:lo + per],
              "m.experts.down": w["m.experts.down"][lo:lo + per]}
    want_part = np.asarray(ref.moe(w_part, "m.", cfgd, x.reshape(21, 48),
                                   experts_held=(lo, lo + per)))
    assert np.abs(np.asarray(part(x)._data).reshape(21, 48)
                  - want_part).max() < TOL


def test_a_batch_routed_to_one_expert_loses_no_token(moe_layer):
    """Every token to experts 3 and 5 (top-2 of 8: a capacity layer would
    drop most of them): each token's result is the reference's, 2 of 8
    experts are hit, and padding rows cost no expert row."""
    full, w, cfgd, x = moe_layer
    bias = jnp.full((8,), -50.0).at[jnp.asarray([3, 5])].set(50.0)
    held = full.router.bias._data
    full.router.bias._data = bias
    try:
        got = np.asarray(full(x)._data).reshape(21, 48)
        assert int(full.last_experts_hit) == 2
        valid = jnp.arange(7)[None] < jnp.asarray([7, 2, 0])[:, None]
        masked = np.asarray(full(x, valid=valid)._data)
    finally:
        full.router.bias._data = held
    want = np.asarray(ref.moe({**w, "m.router.bias": bias}, "m.", cfgd,
                              x.reshape(21, 48)))
    assert np.abs(got - want).max() < TOL
    assert (np.abs(got).max(-1) > 0).all()
    # a padding row keeps the shared expert's part only
    shared = np.asarray(program.swiglu(
        x.reshape(21, 48), w["m.shared.gate_proj.weight"],
        w["m.shared.up_proj.weight"], w["m.shared.down_proj.weight"]
    )).reshape(3, 7, 48)
    assert np.abs(masked[2] - shared[2]).max() < TOL
    assert np.abs(masked[0] - got.reshape(3, 7, 48)[0]).max() < TOL


# ------------------------------------------------------------- the engine --
def test_engine_serves_it_and_refuses_what_a_latent_cache_lacks(tiny):
    model, cfg = tiny["model"], tiny["cfg"]
    eng = GenerationEngine(model, max_batch_size=2, buckets=(16,),
                           max_seq_len=32, block_size=8, rng_seed=0)
    st = eng.stats()
    assert st["kv_cache_kind"] == "latent" and st["kv_row_width"] == 128
    assert st["paged_kernel"] == "xla"
    seq = list(tiny["ids"][1][:6])
    seq.append(eng.prefill(0, seq, max_new_tokens=4))
    for _ in range(3):
        seq.append(int(eng.decode_step()[0]))
    want = np.asarray(tiny["fwd"](tiny["w"], jnp.asarray(
        np.asarray(seq[:-1], np.int32))))[5:]
    # greedy tokens are the reference's argmax, or lie within TOL of it
    gaps = want.max(-1) - want[np.arange(4), seq[6:]]
    assert gaps.max() < TOL
    st = eng.stats()
    assert st["moe_layer_steps"] >= 6 and st["moe_routed_rows"] >= 12
    assert 0 < st["moe_experts_hit"] <= st["moe_layer_steps"] * 8
    with pytest.raises(TypeError, match="handoff.*'latent'"):
        eng.export_request_kv(0)
    with pytest.raises(TypeError, match="handoff.*'latent'"):
        eng.import_request_kv(1, {})
    from paddle_tpu.serving.spec_decode import DraftVerifyEngine
    with pytest.raises(TypeError, match="spec_decode.*'latent'"):
        DraftVerifyEngine(model, model, max_batch_size=2, buckets=(16,),
                          max_seq_len=32, block_size=8)
    with pytest.raises(TypeError, match="mesh.*'latent'"):
        GenerationEngine(model, max_batch_size=2, mesh=object())
    with pytest.raises(TypeError, match="kv_cache_spec"):
        GenerationEngine(paddle.nn.Linear(4, 4))
    assert not hasattr(cfg, "num_nextn_predict_layers")


# ------------------------------------------- the benchmark's scorer of it --
def test_routing_margin_says_where_a_choice_may_flip(moe_layer):
    """`reference.route`'s margin is the last chosen expert's biased score
    less the best one's left out; a perturbation of the input smaller than
    it (in score) cannot change the choice."""
    _, w, cfgd, x = moe_layer
    u = x.reshape(21, 48)
    chosen, weight, margin = ref.route(w, "m.", cfgd, u)
    s = jax.nn.sigmoid(u @ w["m.router.weight"]) + w["m.router.bias"]
    top = np.sort(np.asarray(s), axis=-1)[:, ::-1]
    np.testing.assert_allclose(np.asarray(margin), top[:, 1] - top[:, 2],
                               atol=1e-6)
    noise = 1e-3 * jnp.asarray(
        np.random.default_rng(2).standard_normal(u.shape), jnp.float32)
    s2 = jax.nn.sigmoid((u + noise) @ w["m.router.weight"]) \
        + w["m.router.bias"]
    moved = np.abs(np.asarray(s2 - s)).max(-1)
    again, _, _ = ref.route(w, "m.", cfgd, u + noise)
    same = (np.sort(np.asarray(again), -1)
            == np.sort(np.asarray(chosen), -1)).all(-1)
    assert same[np.asarray(margin) > 2 * moved].all()
    assert np.abs(np.asarray(weight).sum(-1) - 2.0).max() < 1e-5


def test_scorer_compares_what_the_reference_can_decide(tiny, monkeypatch):
    """families/xing4.py's scorer, a layer at a time, gives the reference's
    logits where every choice of experts is clear of ROUTE_TIE, and a row on
    which the served token reads as the top one where it is not."""
    import families

    cfg_json = {**tiny["cfgd"], "family": "xing4", "dtype": "float32",
                "num_nextn_predict_layers": 0}
    fam = families.of(cfg_json)
    ids = jnp.asarray(tiny["ids"][0][:16])
    at = jnp.asarray([3, 9, 14], jnp.int32)
    monkeypatch.setattr(fam, "ROUTE_TIE", 0.0)
    score = fam.reference_scorer(cfg_json, tiny["cfg"], tiny["model"], 16, 3)
    got = np.asarray(score(ids, at, quiet=True))
    want = np.asarray(tiny["fwd"](tiny["w"], ids))[np.asarray(at)]
    assert np.abs(got - want).max() < TOL
    monkeypatch.setattr(fam, "ROUTE_TIE", 10.0)  # nothing is decided
    flat = np.asarray(score(ids, at, quiet=True))
    assert (flat.argmax(-1) == np.asarray(ids)[np.asarray(at) + 1]).all()
    assert (flat.max(-1) - flat[np.arange(3),
                                np.asarray(ids)[np.asarray(at) + 1]] == 0
            ).all()
