"""The Qwen3-Next decoder (qwen3-next-80b-a3b-instruct) against its plain
reference (benchmark/reference/qwen3_next.py, written from the config alone,
the linear layers' recurrence a token at a time), on seeded weights at the
tiny preset: hidden 64, 3 Gated DeltaNet layers (2 key and 4 value heads of
16) and 1 gated full-attention layer (4 query heads over 2 key/value heads
of 32), 16 experts top-2 beside a gated shared expert, float32.

Tolerance of every logit comparison here: 1e-4 absolute. Both sides are
float32 with full-precision matmuls on the CPU and differ by the order of
their reductions and, in the linear layers, by the chunked form (a
triangular solve inside a chunk, a scan over chunks) against the
token-by-token recurrence (seen: 2.4e-7 on logits of deviation 0.16); 1e-4
leaves room for a BLAS that blocks differently. Every planted fault parts by
at least 5 x that (asserted). The state comparisons hold a state to 1e-5 for
the same reasons (its entries are of order 0.1-1)."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import Qwen3NextConfig, Qwen3NextModel
from paddle_tpu.models import qwen3_next as program
from paddle_tpu.nn.moe.dropless import DroplessMoE
from paddle_tpu.ops import kv_pool
from paddle_tpu.ops import pallas_ops as po
from paddle_tpu.profiler import registry
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.serving.scheduler import ContinuousBatchScheduler
from paddle_tpu.serving.spec_decode import DraftVerifyEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from reference import qwen3_next as ref  # noqa: E402

TOL = 1e-4
STATE_TOL = 1e-5
# two slots: prompt lengths (padded to the bucket of 64) and decode steps
T_PROMPT, T_NEW = (21, 50), 9


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(3)
    cfg = Qwen3NextConfig.preset("tiny")
    model = Qwen3NextModel(cfg)
    model.eval()
    w = {n: t._data for n, t in model.state_dict().items()}
    rng = np.random.default_rng(0)
    # one length for both (one executable of the reference): the first slot
    # reads its first 30
    ids = [rng.integers(1, cfg.vocab_size, max(T_PROMPT) + T_NEW).astype(
        np.int32) for _ in T_PROMPT]
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


def test_float32_forward_matches_the_reference(tiny):
    """No cache: the walk over key blocks in the full layer, the chunks of
    64 (two of them over 59 positions) in the linear layers, against the
    token-by-token recurrence."""
    model = tiny["model"]
    got = np.asarray(jax.jit(lambda ids: model(ids)._data)(
        jnp.asarray(tiny["ids"][1][None])))[0]
    assert np.abs(got - tiny["want"][1]).max() < TOL
    assert tiny["want"][1].std() > 0.05  # logits worth comparing
    assert tiny["cfg"].layer_kinds == ["linear_attention"] * 3 \
        + ["full_attention"]


# --------------------------------- prefill, then decode steps, by hand ----
def _served(tiny, kernel, state_kernel, tail=None):
    """The served path by hand, two slots: each prompt prefilled alone at
    the bucket of 64 (the state and the convolution's tail written into its
    slot's entry, the full layer's rows into the pool by whole blocks), then
    T_NEW decode steps of both slots. Returns the logits at every position
    from the prompt's last on, a slot each, and the state pools."""
    model = tiny["model"]
    bs, M, B = 8, 10, 2
    spec = model.kv_cache_spec()
    ks, vs = spec.allocate(1 + B * M, bs, jnp.float32, slots=B)
    table = np.concatenate([np.arange(1, 1 + B * M, dtype=np.int32)
                            .reshape(B, M), np.arange(B, dtype=np.int32)
                            [:, None]], axis=1)
    head_w, logits_of = model.serving_head()

    def step(ks, vs, ids, offsets, seq_lens, bt, kernel):
        T = ids.shape[1]
        pos = offsets[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        h, new = model(ids, position_ids=pos, caches=list(zip(ks, vs)),
                       cache_offsets=offsets, seq_lens=seq_lens,
                       block_tables=spec.layer_tables(bt, bs),
                       paged_kernel=kernel, state_kernel=state_kernel)
        Bq = ids.shape[0]
        return (logits_of(h._data.reshape(Bq * T, -1), head_w._data
                          ).reshape(Bq, T, -1),
                [c[0]._data for c in new], [c[1]._data for c in new])

    prefill = jax.jit(lambda k, v, i, o, s, b: step(k, v, i, o, s, b, None))
    decode = jax.jit(lambda k, v, i, o, s, b: step(k, v, i, o, s, b, kernel))
    got = [[], []]
    for s, P in enumerate(T_PROMPT):
        ids = np.zeros((1, 64), np.int32)
        ids[0, :P] = tiny["ids"][s][:P]
        lg, ks, vs = prefill(ks, vs, jnp.array(ids),
                             jnp.zeros((1,), jnp.int32),
                             jnp.array([P], jnp.int32),
                             jnp.array(table[s:s + 1]))
        got[s].append(np.asarray(lg)[0, P - 1])
    lens = np.asarray(T_PROMPT, np.int32)
    for _ in range(T_NEW):
        ids = np.asarray([[tiny["ids"][s][lens[s]]] for s in range(B)],
                         np.int32)
        lg, ks, vs = decode(ks, vs, jnp.array(ids), jnp.array(lens),
                            jnp.array(lens + 1), jnp.array(table))
        for s in range(B):
            got[s].append(np.asarray(lg)[s, 0])
        lens = lens + 1
    return [np.stack(g) for g in got], (ks, vs)


def _parted(tiny, got):
    """The largest logit difference of the served path from the
    reference's full forward, over both slots' compared positions."""
    return max(float(np.abs(g - tiny["want"][s][P - 1:P + T_NEW]).max())
               for s, (g, P) in enumerate(zip(got, T_PROMPT)))


@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_prefill_then_decode_through_state_and_full_pools(tiny, kernel):
    """Prefill at a bucket of 64 (prompts of 21 and 50: the padding leaves
    the state where the last real token left it) then 9 decode steps of
    both slots, each through the state pools (the update by
    `gated_delta_step` through the interpreter, or its XLA route) and the
    full layer's paged pool: the logits of every step against the
    reference's full forward, within TOL (float32 both sides)."""
    got, (ks, vs) = _served(tiny, kernel, kernel)
    assert _parted(tiny, got) < TOL
    spec = tiny["model"].kv_cache_spec()
    assert spec.state_layers() == [0, 1, 2] and spec.rows_layers() == [3]
    assert [tuple(k.shape) for k in ks[:3]] == [(2, 4, 16, 16)] * 3
    assert [tuple(v.shape) for v in vs[:3]] == [(2, 3, 128)] * 3


def _recurrent(q, k, v, g, beta):
    """The gated delta rule a token at a time (numpy, float64): the
    definition the chunks must give."""
    T, Hk, dk = q.shape
    Hv, dv = v.shape[1], v.shape[2]
    r = Hv // Hk
    S = np.zeros((Hv, dk, dv))
    out = np.zeros((T, Hv, dv))
    for t in range(T):
        for j in range(Hv):
            S[j] *= np.exp(g[t, j])
            kk, qq = k[t, j // r], q[t, j // r]
            S[j] += np.outer(kk, beta[t, j] * (v[t, j] - S[j].T @ kk))
            out[t, j] = S[j].T @ qq
    return out, S


@pytest.mark.parametrize("chunk", [64, 16])
def test_chunks_give_the_recurrence_and_padding_leaves_the_state(chunk):
    """`_chunk_gated_delta` over 50 positions padded to a bucket of 64
    (beta = 0 and g = 0 at the padding, as the prefill sets them): the
    outputs of the 50 and the state after the bucket's tail equal the
    token-by-token recurrence's after the 50th — in chunks of 64 (one,
    padded inside) and of 16 (four, the scan carrying the state)."""
    rng = np.random.default_rng(7)
    T, P, Hk, Hv, d = 64, 50, 2, 4, 16
    q = rng.normal(size=(T, Hk, d))
    k = rng.normal(size=(T, Hk, d))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(T, Hv, d))
    g = -rng.uniform(0.0, 2.0, size=(T, Hv))
    beta = rng.uniform(size=(T, Hv))
    g[P:], beta[P:] = 0.0, 0.0
    o, S = jax.jit(lambda *a: program._chunk_gated_delta(
        *a, chunk=chunk))(*(jnp.asarray(x[None], jnp.float32)
                            for x in (q, k, v, g, beta)))
    want_o, want_S = _recurrent(q[:P], k[:P], v[:P], g[:P], beta[:P])
    assert np.abs(np.asarray(o)[0, :P] - want_o).max() < STATE_TOL
    assert np.abs(np.asarray(S)[0] - want_S).max() < STATE_TOL
    assert np.abs(want_S).max() > 0.1  # a state worth comparing


@pytest.mark.parametrize("shape", [(3, 2, 4, 16, 16), (2, 16, 32, 128, 128)])
def test_gated_delta_step_kernel_matches_its_xla_route(shape):
    """The kernel's body through the Pallas interpreter against the XLA
    route, one decode step (the second shape is the cell's key and value
    heads at 128 x 128): outputs and the state updated in place."""
    B, Hk, Hv, dk, dv = shape
    rng = np.random.default_rng(1)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    S = jnp.asarray(rng.normal(size=(B, Hv, dk, dv)), jnp.float32)
    q = jnp.asarray(unit(rng.normal(size=(B, Hk, dk))), jnp.float32)
    k = jnp.asarray(unit(rng.normal(size=(B, Hk, dk))), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hv, dv)), jnp.float32)
    g = jnp.asarray(-rng.uniform(0, 3, size=(B, Hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(size=(B, Hv)), jnp.float32)
    o1, S1 = po.gated_delta_step(S, q, k, v, g, beta, kernel="interpret")
    o2, S2 = po.gated_delta_step(S, q, k, v, g, beta, kernel="xla")
    assert np.abs(np.asarray(o1) - np.asarray(o2)).max() < STATE_TOL
    assert np.abs(np.asarray(S1) - np.asarray(S2)).max() < STATE_TOL
    assert np.abs(np.asarray(S1) - np.asarray(S)).max() > 0.1


def test_kernel_resolution_from_the_platform_and_the_shape():
    """`select_gated_delta_kernel`: it follows the engine's paged kernel
    (the platform's), keeps the kernel at the cell's shape, and falls back
    loudly where Mosaic could not tile a compiled shape."""
    cell = [(32, 16, 32, 128, 128)] * 9
    assert po.select_gated_delta_kernel("pallas", shapes=cell)[0] == "pallas"
    assert po.select_gated_delta_kernel("interpret", shapes=cell)[0] \
        == "interpret"
    assert po.select_gated_delta_kernel("xla", shapes=cell)[0] == "xla"
    assert po.select_gated_delta_kernel("pallas", shapes=[]) == (
        "xla", "the decoder has no state layer")
    before = registry.counters("serving").get("kernel.fallbacks", 0)
    kind, why = po.select_gated_delta_kernel(
        "pallas", shapes=[(4, 2, 4, 16, 16)])
    assert kind == "xla" and "(8, 128)" in why
    assert registry.counters("serving")["kernel.fallbacks"] == before + 1
    assert po.select_gated_delta_kernel(
        "interpret", shapes=[(4, 2, 4, 16, 16)])[0] == "interpret"


# ----------------------------------------------------------- planted faults --
def _swap_layers(w, i, j):
    """Layers i and j trade places, weights and all."""
    pi, pj = f"layers.{i}.", f"layers.{j}."
    return {(pj + n[len(pi):] if n.startswith(pi) else
             pi + n[len(pj):] if n.startswith(pj) else n): a
            for n, a in w.items()}


def _decay_after(S, q, k, v, g, beta):
    mem = (S * k[:, :, None]).sum(1)
    S = S + k[:, :, None] * (beta[:, None] * (v - mem))[:, None, :]
    S = S * jnp.exp(g)[:, None, None]
    return S, (S * q[:, :, None]).sum(1)


def _norm_w(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


# name -> (what to change in the reference's weights, its config, its code)
FAULTS = {
    "repeat in place of repeat_interleave": dict(
        code=("value_heads", lambda x, r: jnp.tile(x, (1, r, 1)))),
    "no L2 norm of q and k": dict(code=("l2norm", lambda x, eps=1e-6: x)),
    "decay after the update": dict(code=("delta_token", _decay_after)),
    "the attention's output gate dropped": dict(
        code=("output_gate", lambda o, gate: o)),
    "w in place of 1 + w": dict(code=("norm", _norm_w)),
    "rotary over all of a head's dims": dict(
        cfg={"partial_rotary_factor": 1.0}),
    "the full layer at the wrong interval": dict(
        cfg={"full_attention_interval": 3},
        weights=lambda w: _swap_layers(w, 2, 3)),
    "the shared expert's gate dropped": dict(
        code=("shared_gate",
              lambda u, w_sg: jnp.ones((u.shape[0], 1), jnp.float32))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_parts_from_the_reference(tiny, monkeypatch, fault):
    """Each fault, planted in the reference (its weights, its config or its
    code: an equivalent of the program computing that instead), moves the
    logits of the 59-token context by >= 5 x the tolerance: the comparisons
    above cannot pass with that fault in the program."""
    plan = FAULTS[fault]
    if "code" in plan:
        monkeypatch.setattr(ref, *plan["code"])
    cfgd = {**tiny["cfgd"], **plan.get("cfg", {})}
    fwd = tiny["fwd_of"](cfgd)
    w = plan.get("weights", lambda w: w)(tiny["w"])
    got = np.asarray(fwd(w, jnp.asarray(tiny["ids"][1])))
    assert np.abs(got - tiny["want"][1]).max() >= 5 * TOL, fault


def test_a_convolution_tail_off_by_one_parts_at_the_decode_steps(
        tiny, monkeypatch):
    """The fault planted in the program: the tail a prompt hands to its
    decode steps taken one row late (its last real row and one of the
    padding's). The prompt's own logits stay right; the decode steps' part
    from the reference by >= 5 x the tolerance."""
    real = program._conv_tail
    monkeypatch.setattr(program, "_conv_tail",
                        lambda xp, n, K: real(xp, n + 1, K))
    got, _ = _served(tiny, "xla", "xla")
    first = max(float(np.abs(g[0] - tiny["want"][s][P - 1]).max())
                for s, (g, P) in enumerate(zip(got, T_PROMPT)))
    assert first < TOL
    assert _parted(tiny, got) >= 5 * TOL


# ------------------------------------------------------- the chip's share --
def _share_mlp(tiny, lo, hi):
    """Layer 0's expert layer told it holds experts lo..hi-1, over the full
    model's weights."""
    cfg = Qwen3NextConfig.preset("tiny", experts_held=(lo, hi))
    full = tiny["model"].layers[0].mlp
    part = DroplessMoE(
        cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
        cfg.num_experts_per_tok, n_shared=1, experts_held=(lo, hi),
        dtype=cfg.dtype, select_bias=False, router="softmax",
        shared_combine="sigmoid_gate")
    src = full.state_dict()
    for n, t in part.state_dict().items():
        a = src[n]._data
        t._data = a[lo:hi] if n.startswith("experts.") else a
    return part


def test_the_shares_add_up_to_the_uncut_reference_layer(tiny):
    """Eight shares of 2 experts each: their routed parts, with what every
    chip computes alike (the residual, the linear layer and the gated shared
    expert) counted once, give the uncut reference's layer — and the
    reference's own share is the program's."""
    cfgd, w = tiny["cfgd"], tiny["w"]
    p, m = "layers.0.", "layers.0.mlp."

    @jax.jit
    def uncut(w, ids):
        h = ref.embed(w, cfgd, ids)
        h1 = h + ref.linear_attention(w, p + "linear_attn.", cfgd, ref.norm(
            h, w[p + "input_layernorm.weight"], 1e-6))
        u = ref.norm(h1, w[p + "post_attention_layernorm.weight"], 1e-6)
        shared = ref.ffn(u, w[m + "shared.gate_proj.weight"],
                         w[m + "shared.up_proj.weight"],
                         w[m + "shared.down_proj.weight"]) \
            * ref.shared_gate(u, w[m + "shared_gate.weight"])
        return ref.layer(w, 0, cfgd, h, jnp.arange(ids.shape[0])), h1, u, \
            shared

    want, h1, u, shared = uncut(w, jnp.asarray(tiny["ids"][1][:40]))
    outs = [np.asarray(_share_mlp(tiny, lo, lo + 2)(u[None])._data)[0]
            for lo in range(0, 16, 2)]
    total = sum(o - shared for o in outs) + shared + h1
    assert np.abs(total - want).max() < TOL
    # the reference given share 3 is the program's share 3
    w3 = {**w, m + "experts.gate_up": w[m + "experts.gate_up"][6:8],
          m + "experts.down": w[m + "experts.down"][6:8]}
    want3 = np.asarray(jax.jit(lambda w, u: ref.moe(
        w, m, cfgd, u, experts_held=(6, 8)))(w3, u))
    assert np.abs(outs[3] - want3).max() < TOL
    assert np.abs(outs[3] + h1 - want).max() >= 5 * TOL  # not the whole


# -------------------------------------------------------------- the engine --
def test_preemption_by_recompute_rebuilds_the_state(tiny):
    """A request preempted by recompute — released, then prefilled again
    from its prompt and the tokens it was given — gets back the state (and
    the convolution's tail) its decode steps had left, within STATE_TOL,
    and goes on with the same greedy tokens."""
    eng = GenerationEngine(tiny["model"], max_batch_size=2, buckets=(32,),
                           max_seq_len=64, block_size=8, rng_seed=0)
    prompt = tiny["ids"][0][:T_PROMPT[0]].tolist()
    toks = [eng.prefill(0, prompt)]
    for _ in range(6):
        toks.append(int(eng.decode_step()[0]))
    before = [(np.asarray(eng._k[i][0]), np.asarray(eng._v[i][0]))
              for i in eng._states]
    more = [int(eng.decode_step()[0]) for _ in range(3)]
    eng.release(0)
    again = eng.prefill(1, prompt + toks[:-1])
    assert again == toks[-1]
    after = [(np.asarray(eng._k[i][1]), np.asarray(eng._v[i][1]))
             for i in eng._states]
    for (s0, t0), (s1, t1) in zip(before, after):
        assert np.abs(s1 - s0).max() < STATE_TOL
        assert np.abs(t1 - t0).max() < STATE_TOL
    assert [int(eng.decode_step()[1]) for _ in range(3)] == more
    eng.release(1)
    eng.pool.audit()


def test_engine_refuses_what_a_state_cache_cannot_hold(tiny):
    """A cache with state layers: prefix sharing off, and a mesh, chunked
    prefill (the engine's and the scheduler's), the KV handoff and spec
    decode refused with a TypeError, each counted in
    serving.cache_refusals; stats() describe the state layers and their
    bytes."""
    n0 = registry.counters("serving").get("cache_refusals", 0)
    eng = GenerationEngine(tiny["model"], max_batch_size=2, buckets=(32,),
                           max_seq_len=64, block_size=8, rng_seed=0)
    st = eng.stats()
    assert st["prefix_sharing"] is False
    assert st["linear_state_kernel"] == "xla"
    assert st["kv_state_bytes"] == 3 * 2 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    states = [r for r in st["kv_layers"] if "state" in r]
    assert [r["layer"] for r in states] == [0, 1, 2]
    assert states[0]["state"] == [4, 16, 16] \
        and states[0]["conv_tail"] == [3, 128] \
        and states[0]["pool_bytes"] == 2 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert st["kv_layers"][3]["blocks"] == eng.pool.num_blocks
    assert st["kv_pool_bytes"] == 2 * eng.pool.num_blocks * 8 * 64 * 4
    with pytest.raises(TypeError, match="state layers"):
        eng.begin_prefill(0, [1, 2, 3])
    with pytest.raises(TypeError, match="state layers"):
        ContinuousBatchScheduler(eng, prefill_chunk_tokens=16)
    with pytest.raises(TypeError, match="state layers"):
        eng.export_request_kv(0)
    with pytest.raises(TypeError, match="state layers"):
        DraftVerifyEngine(tiny["model"], tiny["model"], max_batch_size=2,
                          buckets=(32,), max_seq_len=64, block_size=8)
    from paddle_tpu.distributed import spmd
    with pytest.raises(TypeError, match="state layers"):
        GenerationEngine(tiny["model"], max_batch_size=2, buckets=(32,),
                         max_seq_len=64, block_size=8,
                         mesh=spmd.serving_mesh(2))
    # prefix sharing off (twice: two engines), then each refusal
    assert registry.counters("serving")["cache_refusals"] - n0 >= 2 + 5


def test_cache_spec_states_and_the_slot_column():
    """`CacheSpec(states=...)`: a state layer has no rows (its `layers`
    entry None), the table row ends in the slot's own column and each layer
    gets its own part; a cache of state layers alone, or a state layer that
    also claims rows, is refused."""
    st = (4, 16, 16, 128, 4)
    spec = kv_pool.CacheSpec("heads", [None, (2, 32)], states=[st, None])
    assert spec.state_layers() == [0] and spec.rows_layers() == [1]
    assert spec.row_width() == 64 and spec.heads(0) == 4
    assert spec.table_cols(10, 8) == 11
    assert spec.state_shapes(0, 3) == ((3, 4, 16, 16), (3, 3, 128))
    bt = jnp.arange(2 * 11, dtype=jnp.int32).reshape(2, 11)
    slot, full = spec.layer_tables(bt, 8)
    assert slot.shape == (2, 1) and full.shape == (2, 10)
    assert (np.asarray(slot)[:, 0] == np.asarray(bt)[:, -1]).all()
    assert spec.writes_blocks(8, 8) and not spec.writes_blocks(4, 8)
    for bad in (dict(layers=[None], states=[st]),
                dict(layers=[(2, 32), (2, 32)], states=[st, None]),
                dict(layers=[None, (2, 32)], states=[None, None])):
        with pytest.raises(ValueError):
            kv_pool.CacheSpec("heads", bad["layers"], states=bad["states"])
