"""The SDAR-MoE decoder (SDAR-30B-A3B-Chat) against its plain reference
(benchmark/reference/sdar_moe.py, written from the config alone), on seeded
weights at the tiny preset: hidden 64, 8 query heads over 2 key/value heads
of 16 with query/key norms, 16 softmax-routed experts top-2, 3 layers,
blocks of 4 with 2 denoise forwards, float32.

Tolerance of every logit comparison here: 1e-4 absolute. Both sides are
float32 with full-precision matmuls on the CPU and differ by the order of
their reductions only (seen: 3e-7 on logits of deviation 0.16); 1e-4 leaves
room for a BLAS that blocks differently. Every planted fault parts by at
least 5 x that (asserted)."""
import itertools
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import SdarMoeConfig, SdarMoeModel
from paddle_tpu.models import cohere2_moe as walk
from paddle_tpu.profiler import registry
from paddle_tpu.serving import (GenerationEngine, GenerationRequest,
                                GenerationServer)
from paddle_tpu.serving import sampling
from paddle_tpu.serving.scheduler import ContinuousBatchScheduler
from paddle_tpu.serving.spec_decode import DraftVerifyEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from reference import sdar_moe as ref  # noqa: E402

TOL = 1e-4
L = 4          # block length
MASK = 509     # the tiny preset's mask id


def _build(seed=3, **generation):
    paddle.seed(seed)
    cfg = SdarMoeConfig.preset("tiny", generation={
        **SdarMoeConfig.PRESETS["tiny"]["generation"], **generation})
    model = SdarMoeModel(cfg)
    model.eval()
    return cfg, model


@pytest.fixture(scope="module")
def tiny():
    cfg, model = _build()
    w = {n: t._data for n, t in model.state_dict().items()}
    rng = np.random.default_rng(0)
    ids = rng.integers(1, 500, 96).astype(np.int32)
    cfgd = {**cfg.as_dict(), **cfg.generation._asdict()}

    def fwd_of(cfgd):
        # weights are an argument, so every weight-shaped fault below
        # reuses one executable
        return jax.jit(lambda w, ids, masked: ref.forward(w, ids, masked,
                                                          cfgd))
    return {"cfg": cfg, "cfgd": cfgd, "model": model, "w": w, "ids": ids,
            "fwd": fwd_of(cfgd), "fwd_of": fwd_of}


def _masks(n):
    """A 70-token context whose last block holds two masks, and one mask in
    an earlier block (as a denoise forward never leaves one: the reference
    does not care)."""
    m = np.zeros(n, bool)
    m[[21, n - 3, n - 1]] = True
    return m


@pytest.mark.parametrize("block", [512, 16])
def test_float32_forward_matches_the_reference(tiny, monkeypatch, block):
    """No cache: the walk over key blocks with every query standing at its
    block's last position; at a walk block of 16 the 64 positions are 4 x 4
    blocks of queries and keys with a running softmax."""
    monkeypatch.setattr(walk, "_WALK_BLOCK", block)
    monkeypatch.setattr(sys.modules[SdarMoeModel.__module__], "_WALK_BLOCK",
                        block)
    masked = _masks(64)
    ids = np.where(masked, MASK, tiny["ids"][:64])
    got = np.asarray(jax.jit(lambda i: tiny["model"](i)._data)(
        jnp.asarray(ids[None])))[0]
    want = np.asarray(tiny["fwd"](tiny["w"], jnp.asarray(tiny["ids"][:64]),
                                  jnp.asarray(masked)))
    keep = np.arange(512) != MASK  # the reference never samples the mask id
    assert np.abs(got - want)[:, keep].max() < TOL
    assert np.isneginf(want[:, MASK]).all()
    assert want[:, keep].std() > 0.05  # logits worth comparing


# --------------------------------------- prefill, then block steps, by hand --
def _paged_steps(tiny, kernel, prompts, n_blocks, commit=True):
    """The served path by hand, two slots: prefill each prompt's whole
    blocks into the pools (no token sampled), then `n_blocks` blocks a slot:
    denoise forwards whose positions to unmask and ids are the reference's
    (so both sides see the same blocks), then a commit forward. Returns, a
    slot, the list of (logits [L, V] of a denoise forward, the reference's
    for it)."""
    model, cfgd, w = tiny["model"], tiny["cfgd"], tiny["w"]
    bs, M, B = 8, 8, len(prompts)
    spec = model.kv_cache_spec()
    assert spec.kind == "heads" and spec.window is None
    assert spec.heads() == 2 and spec.q_per_kv == 4
    ks, vs = spec.allocate(1 + B * M, bs, jnp.float32)
    tables = np.arange(1, 1 + B * M, dtype=np.int32).reshape(B, M)
    head_w, logits_of = model.serving_head()

    def step(ks, vs, ids, offsets, seq_lens, bt, kernel):
        T = ids.shape[1]
        pos = offsets[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        h, new = model(ids, position_ids=pos, caches=list(zip(ks, vs)),
                       cache_offsets=offsets, seq_lens=seq_lens,
                       block_tables=bt, paged_kernel=kernel)
        n = ids.shape[0]
        return (logits_of(h._data.reshape(n * T, -1), head_w._data
                          ).reshape(n, T, -1),
                [c[0]._data for c in new], [c[1]._data for c in new])

    prefill = jax.jit(lambda k, v, i, o, s, b: step(k, v, i, o, s, b, None))
    block = jax.jit(lambda k, v, i, o, s, b: step(k, v, i, o, s, b, kernel))
    cur = np.zeros(B, np.int32)
    seqs = [list(p) for p in prompts]
    for s, p in enumerate(prompts):  # one slot a call, padded to 32
        whole = len(p) // L * L
        ids = np.zeros((1, 32), np.int32)
        ids[0, :whole] = p[:whole]
        _, ks, vs = prefill(ks, vs, jnp.asarray(ids),
                            jnp.zeros((1,), jnp.int32),
                            jnp.asarray([whole], jnp.int32),
                            jnp.asarray(tables[s:s + 1]))
        cur[s] = whole
    out = [[] for _ in prompts]
    for _ in range(n_blocks):
        blk = np.full((B, L), MASK, np.int64)
        masked = np.ones((B, L), bool)
        for s in range(B):
            tail = seqs[s][cur[s]:]
            blk[s, :len(tail)], masked[s, :len(tail)] = tail, False
        plans = [ref.generate_block(w, cfgd, np.asarray(seqs[s][:cur[s]],
                                                        np.int64),
                                    blk[s], masked[s]) for s in range(B)]
        for f in range(max(len(p) for p in plans) + int(commit)):
            # (copies: the host arrays change while a forward that nothing
            # waited for may still read them)
            lg, ks, vs = block(
                ks, vs, jnp.array(np.where(masked, MASK, blk), jnp.int32),
                jnp.array(cur), jnp.array(cur + L), jnp.asarray(tables))
            for s in range(B):
                if f < len(plans[s]):  # a denoise forward of this slot
                    out[s].append((np.asarray(lg)[s], plans[s][f]["logits"]))
                    take = plans[s][f]["unmask"]
                    blk[s] = np.where(take, plans[s][f]["ids"], blk[s])
                    masked[s] &= ~take
        for s in range(B):
            seqs[s] = seqs[s][:cur[s]] + [int(t) for t in blk[s]]
        cur = cur + L
    return out


@pytest.mark.parametrize("tail", [0, 1, 2, 3])
@pytest.mark.parametrize("kernel", ["xla", "interpret"])
def test_prefill_then_block_steps_through_the_paged_cache(tiny, kernel, tail):
    """Prompts with P mod 4 = `tail` (the tail opens the first block
    unmasked) and another length beside them: the logits of every denoise
    forward of three blocks a slot, through the pools and the paged kernel
    over a block span, against the reference's full forward of prefix +
    block."""
    prompts = [tiny["ids"][:20 + tail], tiny["ids"][40:49 + (tail + 2) % 4]]
    got = _paged_steps(tiny, kernel, prompts, 3)
    keep = np.arange(512) != MASK
    for s, steps in enumerate(got):
        # 2 denoise forwards a block; a first block with one position left
        # to fill has one
        assert len(steps) in (5, 6), (s, len(steps))
        for f, (lg, want) in enumerate(steps):
            assert np.abs(lg - want)[:, keep].max() < TOL, (kernel, s, f)


def test_a_denoise_forwards_rows_left_in_the_cache_part_from_the_reference(
        tiny):
    """The planted fault "no commit": the rows a block's LAST denoise
    forward wrote (masks where it unmasked) stay in the cache, and the next
    block reads them."""
    prompts = [tiny["ids"][:20], tiny["ids"][40:50]]
    got = _paged_steps(tiny, "xla", prompts, 2, commit=False)
    keep = np.arange(512) != MASK
    first, second = got[0][:2], got[0][2:]
    assert max(np.abs(a - b)[:, keep].max() for a, b in first) < TOL
    assert max(np.abs(a - b)[:, keep].max() for a, b in second) >= 5 * TOL


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


def _rope_adjacent_pairs(x, pos, theta):
    D = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, D, 2, dtype=jnp.float32)
                                  / D))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _route_sigmoid(w, p, cfg, u):
    """`ref.route` with sigmoid scores where the softmax is."""
    s = jax.nn.sigmoid(ref._mm(u, w[p + "router.weight"]))
    top, chosen = jax.lax.top_k(s, int(cfg["num_experts_per_tok"]))
    return chosen, top / top.sum(-1, keepdims=True), jnp.zeros(u.shape[0])


_real_head = ref.head


def _shifted_head(w, cfg, h, v_block=None):
    """The logits at position i read for the token at i + 1."""
    return jnp.roll(_real_head(w, cfg, h, v_block), 1, axis=0)


# name -> (what to change in the reference's weights, its config, its code)
FAULTS = {
    "causal inside a block": dict(code=("visible", lambda k, q, B:
                                        k[None, :] <= q[:, None])),
    "block boundary off by one": dict(code=("visible", lambda k, q, B:
                                            (k[None, :] + 1) // B
                                            <= (q[:, None] + 1) // B)),
    "shifted logits": dict(code=("head", _shifted_head)),
    "sigmoid for softmax scores": dict(code=("route", _route_sigmoid)),
    "unnormalised top-k weights": dict(cfg={"norm_topk_prob": False}),
    "no query/key norm": dict(code=("qk_norm", lambda x, w, eps: x)),
    "rotary on adjacent pairs, not halves": dict(
        code=("rope", _rope_adjacent_pairs)),
    "head map h % 2 for h // 4": dict(weights=_heads_by_modulo),
    "weights rounded to float8": dict(weights=lambda w: {
        n: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        for n, a in w.items()}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_parts_from_the_reference(tiny, monkeypatch, fault):
    """Each fault, planted in the reference (its weights, its config or its
    code: an equivalent of the program computing that instead), moves the
    logits of a 70-token context by >= 5 x the tolerance: the comparisons
    above cannot pass with that fault in the program."""
    plan = FAULTS[fault]
    masked = jnp.asarray(_masks(70))
    ids = jnp.asarray(tiny["ids"][:70])
    want = np.asarray(tiny["fwd"](tiny["w"], ids, masked))
    if "code" in plan:
        monkeypatch.setattr(ref, *plan["code"])
    cfgd = {**tiny["cfgd"], **plan.get("cfg", {})}
    fwd = tiny["fwd_of"](cfgd) if ("code" in plan or "cfg" in plan) \
        else tiny["fwd"]
    got = np.asarray(fwd(plan.get("weights", lambda w: w)(tiny["w"]), ids,
                         masked))
    keep = np.arange(512) != MASK
    assert np.abs(got - want)[:, keep].max() >= 5 * TOL, fault


# ------------------------------------------------- which positions to unmask --
@pytest.mark.parametrize("strategy", sampling.STRATEGIES)
@pytest.mark.parametrize("n_over", [0, 1, 4])
def test_unmask_rules_on_planted_logits(strategy, n_over):
    """Planted logits put `n_over` of a block's 4 confidences over the
    threshold of 0.9 (the others near 0.5 and below, in a known order), the
    mask id the largest logit of every row: `sample_block` never takes the
    mask id and reads each id's probability; the static rule unmasks the
    ceil(masked / steps_left) most confident whatever the threshold, the
    dynamic rule every one over it and at least the most confident — and the
    reference's `unmask_choice` says the same."""
    V, mask_id = 64, 7
    logits = np.zeros((L, V), np.float32)
    logits[:, mask_id] = 50.0
    best = [11, 12, 13, 14]
    # the probability of the row's best id: over 0.9, or 0.5 - 0.05 j
    want = [0.97 - 0.01 * j if j < n_over else 0.5 - 0.05 * j
            for j in range(L)]
    for j in range(L):
        logits[j, best[j]] = np.log(want[j] / (1 - want[j]) * (V - 2))
    zeros = np.zeros(L, np.float32)
    ids, conf = sampling.sample_block(
        jnp.asarray(logits), jnp.asarray(zeros), jnp.zeros(L, jnp.int32),
        jnp.ones(L, jnp.float32), jnp.zeros((L, V), jnp.float32), mask_id)
    assert list(np.asarray(ids)) == best
    assert np.abs(np.asarray(conf) - want).max() < 1e-5
    cfg = {"strategy": strategy, "confidence_threshold": 0.9,
           "denoising_steps": 2}
    for masked, done in (([1, 1, 1, 1], 0), ([0, 1, 1, 1], 0),
                         ([0, 1, 0, 1], 1), ([0, 0, 0, 0], 1)):
        masked = np.asarray(masked, bool)
        got = np.asarray(sampling.unmask_select(
            conf[None], jnp.asarray(masked[None]),
            jnp.asarray([done], jnp.int32), 2, strategy, 0.9))[0]
        take, _ = ref.unmask_choice(np.asarray(conf), masked, 2 - done, cfg)
        assert (got == take).all(), (masked, done)
        assert not (got & ~masked).any()
        m = int(masked.sum())
        if strategy == "low_confidence_static":
            assert got.sum() == -(-m // (2 - done))
        else:
            over = int((masked & (np.asarray(want) > 0.9)).sum())
            assert got.sum() == (max(over, 1) if m else 0)
        if m:  # the most confident masked position is always taken
            assert got[np.argmax(np.where(masked, want, -1))]


# ------------------------------------------------------------- the engine --
def _engine(model, kernel="xla", slots=2):
    return GenerationEngine(model, max_batch_size=slots, buckets=(16, 32),
                            max_seq_len=64, block_size=8, rng_seed=0,
                            paged_kernel=kernel)


def _generate(eng, slot, prompt, n_new, **knobs):
    assert eng.prefill(slot, prompt, max_new_tokens=n_new, **knobs) is None
    got, lens = [], []
    while len(got) < n_new:
        out = eng.decode_step()[slot]
        if out is not None:
            lens.append((len(out[0]), out[1]))
            got += out[0]
    eng.release(slot)
    return got, lens


def _reference_tokens(w, cfgd, prompt, served):
    """The reference's generation teacher-forced with the served tokens:
    (the worst gap of a served token below the reference's top logit in the
    forward that unmasks it, the forwards every block took)."""
    seq, P, worst, forwards = list(prompt), len(prompt), 0.0, []
    while len(seq) < P + len(served):
        s = len(seq) // L * L
        block, masked = np.full(L, MASK, np.int64), np.ones(L, bool)
        block[:len(seq) - s], masked[:len(seq) - s] = seq[s:], False
        forced = np.full(L, -1, np.int64)
        for j in range(len(seq) - s, L):
            if s + j - P < len(served):
                forced[j] = served[s + j - P]
        steps = ref.generate_block(w, cfgd, np.asarray(seq[:s], np.int64),
                                   block, masked, forced=forced)
        for st in steps:
            for j in np.nonzero(st["unmask"] & (forced >= 0))[0]:
                worst = max(worst, float(st["logits"][j].max()
                                         - st["logits"][j, forced[j]]))
        forwards.append(len(steps))
        seq = seq[:s] + [int(t) for t in steps[-1]["ids"]]
    return worst, forwards


@pytest.mark.parametrize("tail", [0, 1, 2, 3])
@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_engine_generates_what_the_reference_generates(tiny, kernel, tail):
    """GenerationEngine over the block decoder, prompts with P mod 4 =
    `tail` (P = 3: nothing to prefill) and a max_new_tokens of 10, no
    multiple of 4: every token is the reference's argmax in the forward in
    which the reference unmasks it (or within TOL of it); the first block
    gives 4 - tail tokens, the last is cut; positions are each token's
    own."""
    eng = _engine(tiny["model"], kernel)
    assert eng.paged_kernel == ("xla" if kernel == "xla" else "interpret")
    assert eng.generation == tiny["cfg"].generation
    assert eng.stats()["prefix_sharing"] is False
    c0 = registry.counters("serving")
    for P in (8 + tail, 3 if tail == 3 else 16 + tail):
        prompt = tiny["ids"][P:2 * P]
        got, lens = _generate(eng, 0, prompt, 10)
        assert len(got) == 10 and MASK not in got
        sizes = [n for n, _ in lens]
        assert sizes[0] == L - P % L and sum(sizes) == 10
        assert all(n == L for n in sizes[1:-1])
        first = [at for _, at in lens]
        assert first == list(P + np.cumsum([0] + sizes[:-1]))
        worst, forwards = _reference_tokens(tiny["w"], tiny["cfgd"], prompt,
                                            got)
        assert worst < TOL, (kernel, P, worst)
    c1 = registry.counters("serving")
    d = {k: c1[k] - c0.get(k, 0) for k in c1 if k.startswith("diffusion.")}
    assert d["diffusion.tokens_committed"] == 20
    assert d["diffusion.blocks_committed"] == d["diffusion.commit_forwards"]
    # a block costs its denoise forwards (2; 1 where a single position was
    # left) and a commit
    assert d["diffusion.slot_forwards"] >= 3 * d["diffusion.blocks_committed"] - 2
    assert c1["decode_steps"] - c0["decode_steps"] \
        == d["diffusion.slot_forwards"]
    assert eng.pool.audit()["in_use"] == 0


@pytest.mark.parametrize("threshold, forwards", [(0.9, 4), (0.0, 1)])
def test_dynamic_rule_through_the_engine(tiny, threshold, forwards):
    """`low_confidence_dynamic` served: on seeded weights no confidence
    passes 0.9, so a block takes one position a forward (4 denoise forwards
    and a commit); over a threshold of 0 every position passes at once (1
    and a commit). The tokens are the reference's under the same rule."""
    cfg, model = _build(strategy="low_confidence_dynamic",
                        confidence_threshold=threshold)
    w = {n: t._data for n, t in model.state_dict().items()}
    cfgd = {**cfg.as_dict(), **cfg.generation._asdict()}
    eng = _engine(model)
    c0 = registry.counters("serving")
    prompt = tiny["ids"][:12]
    got, _ = _generate(eng, 1, prompt, 8)
    worst, took = _reference_tokens(w, cfgd, prompt, got)
    assert worst < TOL and took == [forwards, forwards]
    c1 = registry.counters("serving")
    assert c1["diffusion.slot_forwards"] - c0["diffusion.slot_forwards"] \
        == 2 * (forwards + 1)


def test_a_prompt_may_hold_the_mask_id(tiny):
    """A position is masked by the slot's STATE, never by comparing ids: a
    prompt with the mask id in a whole block and in the tail that opens the
    first generated block is served as the reference reads it."""
    prompt = tiny["ids"][:14].copy()
    prompt[[5, 13]] = MASK
    eng = _engine(tiny["model"])
    got, lens = _generate(eng, 0, prompt, 6)
    assert [n for n, _ in lens] == [2, 4]
    worst, _ = _reference_tokens(tiny["w"], tiny["cfgd"], prompt, got)
    assert worst < TOL
    clean = tiny["ids"][:14]
    other, _ = _generate(eng, 0, clean, 6)
    assert _reference_tokens(tiny["w"], tiny["cfgd"], clean, other)[0] < TOL


def test_a_sampled_request_is_reproducible_from_its_seed(tiny):
    """Temperature 0.8, top_k 40: the same seed gives the same tokens in
    another slot beside another request; another seed gives others; the mask
    id is never among them."""
    eng = _engine(tiny["model"])
    prompt = tiny["ids"][:13]
    knobs = dict(temperature=0.8, top_k=40)
    a, _ = _generate(eng, 0, prompt, 12, seed=11, **knobs)
    eng.prefill(0, tiny["ids"][30:47], max_new_tokens=40, seed=5, **knobs)
    b, _ = _generate(eng, 1, prompt, 12, seed=11, **knobs)
    eng.release(0)
    c, _ = _generate(eng, 0, prompt, 12, seed=12, **knobs)
    assert a == b and a != c
    assert MASK not in a + c and len(set(a)) > 3


# ------------------------------------------ the scheduler's accounting of it --
def test_scheduler_hands_tokens_over_in_blocks(tiny):
    """Through GenerationServer: `len(tokens) == max_new_tokens` exactly for
    lengths that are no multiple of 4, `tok_ts` in blocks (the first block
    without the prompt's tail, the last cut), `ttft` at the first commit and
    not at prefill, the pool clean at the end."""
    eng = _engine(tiny["model"], slots=3)
    server = GenerationServer(engine=eng, max_queue_size=8)
    try:
        sizes = [(9, 10), (12, 7), (6, 1), (3, 13)]
        hs = [server.submit(tiny["ids"][P:2 * P].tolist(), max_new_tokens=n)
              for P, n in sizes]
        for h, (P, n) in zip(hs, sizes):
            h.result(timeout=300)
            assert h.status == "done" and h.stop_reason == "max_tokens"
            assert len(h.tokens) == n and len(h.tok_ts) == n
            runs = [len(list(g)) for _, g in itertools.groupby(h.tok_ts)]
            first = min(L - P % L, n)
            want = [first] + [L] * ((n - first) // L)
            want += [(n - first) % L] * bool((n - first) % L)
            assert runs == want, (P, n, runs)
            assert h.ttft_s == pytest.approx(h.first_tok_ts - h.submit_ts)
            assert h.first_tok_ts == h.tok_ts[0]
    finally:
        server.shutdown(drain=False, timeout=60)
    assert eng.pool.audit()["in_use"] == 0


def test_cancel_mid_block_leaves_the_pool_clean(tiny):
    """A request cut after a denoise forward of its second block (its block
    half unmasked on the device) gives its slot and blocks back; the slot's
    next request starts from masks."""
    eng = _engine(tiny["model"])
    sched = ContinuousBatchScheduler(eng, max_queue_size=4)
    req = sched.submit(GenerationRequest(tiny["ids"][:10].tolist(),
                                         max_new_tokens=20))
    for _ in range(5):  # admit + first block (2 of 4: 1 + commit), then 1
        sched.step()
    assert len(req.tokens) == 2 and eng._blk_steps[0] >= 1
    assert not eng._blk_masked[0].all()
    sched.cancel_pending("cut")
    assert req.status == "error" and eng.pool.audit()["in_use"] == 0
    assert eng._blk_masked[0].all() and (eng._blk_tokens[0] == MASK).all()
    got, _ = _generate(eng, 0, tiny["ids"][:10], 6)
    assert _reference_tokens(tiny["w"], tiny["cfgd"], tiny["ids"][:10],
                             got)[0] < TOL


REFUSED = {
    "spec decode": lambda eng, model: DraftVerifyEngine(
        model, model, max_batch_size=2, buckets=(16,), max_seq_len=32,
        block_size=8),
    "chunked prefill (begin_prefill)": lambda eng, model: eng.begin_prefill(
        0, list(range(1, 20)), chunk_tokens=8),
    "chunked prefill (prefill_chunk_tokens)": lambda eng, model:
        ContinuousBatchScheduler(eng, prefill_chunk_tokens=8),
    "the KV handoff (export": lambda eng, model: eng.export_request_kv(0),
    "the KV handoff (import": lambda eng, model: eng.import_request_kv(
        1, {}),
    "mesh": lambda eng, model: GenerationEngine(model, max_batch_size=2,
                                                mesh=object()),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_a_block_decoder_cannot_give_is_refused_and_counted(tiny,
                                                                 feature):
    """Each feature written for a token a step raises through
    `engine._refuse` with its reason, and counts in
    serving.cache_refusals; prefix sharing, a default and no call, is
    switched off at engine build and counted there."""
    c0 = registry.counters("serving")["cache_refusals"]
    eng = _engine(tiny["model"])
    assert registry.counters("serving")["cache_refusals"] == c0 + 1
    assert eng.stats()["prefix_sharing"] is False
    with pytest.raises(TypeError, match="diffusion over blocks of 4"):
        REFUSED[feature](eng, tiny["model"])
    assert registry.counters("serving")["cache_refusals"] >= c0 + 2
    with pytest.raises(ValueError, match="whole blocks"):
        GenerationEngine(tiny["model"], max_batch_size=2, block_size=6,
                         max_seq_len=48)


# ------------------------------------------- the benchmark's scorer of it --
def test_scorer_replays_the_block_that_decides_a_token(tiny, monkeypatch):
    """families/sdar_moe.py's scorer, a layer at a time with the clean
    prefix's keys and values kept, gives the reference's logits at a
    position from the forward in which the reference unmasks it; a position
    next to a routing tie or to another choice of positions reads as the
    served token; `build` checks the parameter count, and the cell's
    configuration gives 4,361 M."""
    import families

    cfg_json = {**tiny["cfgd"], "family": "sdar_moe", "dtype": "float32",
                "n_routed_experts": 16,
                "generation": tiny["cfg"].generation._asdict()}
    for k in tiny["cfg"].generation._asdict():
        cfg_json.pop(k)
    fam = families.of(cfg_json)
    cfg, model = fam.build(cfg_json, 3)
    with pytest.raises(SystemExit, match="two numbers"):
        fam.build({**cfg_json, "n_routed_experts": 8}, 3)
    assert fam.param_counts(cfg_json)["total"] == sum(
        int(t._data.size) for t in model.state_dict().values())
    with open(os.path.join(REPO, "benchmark", "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        cell = json.load(f)
    counts = fam.param_counts(cell)
    assert counts["total"] == 4_361_055_744
    assert round(counts["layer"] / 1e6, 1) == 623.1
    assert {k: cell[k] for k in SdarMoeConfig.PUBLISHED
            if k != "num_hidden_layers"} == {
        k: v for k, v in SdarMoeConfig.PUBLISHED.items()
        if k != "num_hidden_layers"}
    assert cell["generation"] == SdarMoeConfig.GENERATION._asdict()
    assert cell["reduced"] == ["num_hidden_layers"]

    w = {n: t._data for n, t in model.state_dict().items()}
    eng = _engine(model)
    prompt = tiny["ids"][:14]
    served, _ = _generate(eng, 0, prompt, 22)
    ids = np.zeros(48, np.int32)
    ids[:36] = list(prompt) + served
    at = np.asarray([13, 20, 27, 34], np.int32)  # tokens 0, 7, 14, 21
    monkeypatch.setattr(fam, "ROUTE_TIE", 0.0)
    monkeypatch.setattr(fam, "ORDER_TIE", 0.0)
    score = fam.reference_scorer(cfg_json, cfg, model, 48, 4)
    got = np.asarray(score(jnp.asarray(ids), jnp.asarray(at), quiet=True))
    for row, a in zip(got, at):
        p = int(a) + 1
        s = p // L * L
        block, masked = np.full(L, MASK, np.int64), np.ones(L, bool)
        forced = np.full(L, -1, np.int64)
        for j in range(L):
            if s + j < 14:
                block[j], masked[j] = ids[s + j], False
            elif s + j < 36:
                forced[j] = ids[s + j]
        steps = ref.generate_block(w, tiny["cfgd"], ids[:s].astype(np.int64),
                                   block, masked, forced=forced)
        want = next(st["logits"][p - s] for st in steps
                    if st["unmask"][p - s])
        keep = np.arange(512) != MASK
        assert np.abs(row - want)[keep].max() < TOL, p
        assert row.max() - row[ids[p]] < TOL  # the served token is its top
    for name in ("ROUTE_TIE", "ORDER_TIE"):  # nothing is decided
        monkeypatch.setattr(fam, name, 10.0)
        flat = np.asarray(score(jnp.asarray(ids), jnp.asarray(at),
                                quiet=True))
        assert (flat.argmax(-1) == ids[at + 1]).all()
        assert set(np.unique(flat)) == {-1.0, 0.0}
        monkeypatch.setattr(fam, name, 0.0)
