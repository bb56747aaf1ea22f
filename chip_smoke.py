#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the main path once on a TPU, through the entry points a user calls,
at the full width of gpt2-medium (24 layers, d_model 1024, 16 heads of 64,
vocab 50304, seq 1024, bf16 compute, weights random from a seed), under
exactly the configuration `import paddle_tpu` gives — no PADDLE_TPU_*
variable may be set. It is the quickest proof that the program runs on the
chip, not a benchmark: it reports facts about the run, never a speed.

    python3 chip_smoke.py

Legs, each in its own child process, one at a time (a chip belongs to one
process; this parent never imports JAX):

  kernels       compiled Pallas paged attention vs its XLA oracle at head_dim
                64 and 128 inside PAGED_PARITY_TOL; flash fwd/bwd vs the XLA
                attention.
  train-1       paddle.jit.TrainStep, AdamW(multi_precision), bs8 x seq1024:
                loss finite and falling, flash resolved to pallas with zero
                fallbacks, no compile after warm-up.
  train-1-lazy  the same model and batch as a plain eager loop under
                paddle.incubate.lazy_eval(): steps captured and donated, no
                capture fallback, first two losses equal to train-1's.
  serve-1       GenerationServer on the paged engine, default kernel choice:
                mixed prompt lengths, a shared prefix, greedy and sampled;
                resolved kernel pallas, one decode executable, zero compiles
                in the third wave, KV pools donated and stored row-major,
                pool audit clean, greedy tokens equal to an engine built
                with paged_kernel="xla".
  train-4       (>= 4 chips) fleet.init dp2 x mp2 use_spmd, eager loop under
                lazy_eval(): first two losses equal to train-1's, step compiles
                flat, no Python collectives, every parameter on four chips,
                memory balanced across them.
  serve-4       (>= 4 chips) GenerationServer(mesh=spmd.serving_mesh(4)):
                greedy tokens equal to serve-1's, KV pools sharded over 'mp'.

"Equal" for greedy token streams means identical, or parting only at a
position where the reference logits of the two choices lie within
NEAR_TIE of each other (random weights give near-uniform logits, and bf16
reduction order legitimately decides such a tie); every such position is
reported with its gap.

On success stdout ends in two lines, one JSON object each. First the
account of the run (also written to chiprun_out/ when that exists):
  {"ok": true, "device": {...}, "versions": {...}, "legs": {leg:
   {"status": "ok", "wall_s": ..., "compile_s": ..., ...}}}
where, with fewer than four chips, the four-chip legs read "not run (N
chips)". Then, as the LAST line, the verdict with exactly these keys, the
device as JAX reports it:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Anything else — no TPU, a leg that fails or raises, an assertion that does
not hold — ends in a non-zero exit code and no result on stdout; the
account of the failure goes to stderr.

`--tiny` runs every leg at toy size on whatever platform JAX finds (for
debugging the script itself on the CPU; its summary says "tiny": true and
names the platform, so it can never pass for a chip run). `--legs a,b`
with `--refs SUMMARY.json` re-runs chosen legs against the recorded
results of an earlier run.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

LEGS = ("kernels", "train-1", "train-1-lazy", "serve-1", "train-4",
        "serve-4")
FOUR_CHIP_LEGS = ("train-4", "serve-4")
# the earlier leg whose recorded result a leg is checked against
REFERENCE = {"train-1-lazy": "train-1", "train-4": "train-1",
             "serve-4": "serve-1"}
BUDGET_S = 1150.0  # the whole run, compilation included, must end by here

FULL = dict(preset="gpt2-medium", overrides={}, batch=8, seq=1024, lr=1e-4,
            train_steps=5, lazy_steps=8, buckets=(64, 256),
            prompt_lens=(24, 48, 48, 100, 180, 33), shared_prefix=32,
            new_tokens=16, score_len=256)
TINY = dict(preset="gpt2-tiny", overrides={"vocab_size": 512}, batch=8,
            seq=128, lr=1e-3, train_steps=5, lazy_steps=8, buckets=(16, 64),
            prompt_lens=(6, 24, 24, 40, 50, 9), shared_prefix=16,
            new_tokens=6, score_len=64)

LOSS_TOL = 0.15   # bf16 losses near 11 are 0.0625 apart: two steps of slack
NEAR_TIE = 0.05   # logit gap below which bf16 arithmetic decides the argmax


def log(msg):
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ parent --

def run_child(leg, refs, tiny, timeout):
    """One leg in its own process. Returns the leg's result dict; a
    failing or overrunning child raises SystemExit."""
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", leg,
           "--refs-json", json.dumps(refs)]
    if tiny:
        cmd.append("--tiny")
    t0 = time.monotonic()
    try:
        r = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                           timeout=max(timeout, 1.0))
    except subprocess.TimeoutExpired:
        raise SystemExit(f"chip_smoke: leg {leg} overran the "
                         f"{BUDGET_S:.0f}s budget") from None
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    if r.returncode != 0 or not lines:
        raise SystemExit(f"chip_smoke: leg {leg} failed (exit "
                         f"{r.returncode}); see stderr above")
    res = json.loads(lines[-1])
    res["wall_s"] = round(time.monotonic() - t0, 1)
    return res


def parent(args):
    set_vars = sorted(k for k in os.environ if k.startswith("PADDLE_TPU_"))
    if set_vars:
        raise SystemExit(f"chip_smoke: unset {set_vars} first — the smoke "
                         "proves the configuration `import paddle_tpu` "
                         "gives, not an overridden one")
    if args.tiny:
        # toy run for debugging this script on the CPU: four virtual
        # devices so the four-chip legs run too
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    selected = args.legs.split(",") if args.legs else list(LEGS)
    unknown = sorted(set(selected) - set(LEGS))
    if unknown:
        raise SystemExit(f"chip_smoke: unknown legs {unknown}")
    legs = {}
    if args.refs:
        with open(args.refs) as f:
            legs = {k: v for k, v in json.load(f)["legs"].items()
                    if isinstance(v, dict) and k not in selected}
    deadline = time.monotonic() + BUDGET_S
    device = None
    for leg in LEGS:
        if leg not in selected:
            legs.setdefault(leg, "not run (not selected)")
            continue
        if leg in FOUR_CHIP_LEGS and device and device["count"] < 4:
            legs[leg] = f"not run ({device['count']} chips)"
            continue
        ref = REFERENCE.get(leg)
        if ref and not isinstance(legs.get(ref), dict):
            raise SystemExit(f"chip_smoke: leg {leg} is checked against "
                             f"{ref}, which has no recorded result (run it, "
                             "or pass --refs)")
        log(f"leg {leg} ...")
        res = run_child(leg, legs, args.tiny, deadline - time.monotonic())
        device = res.pop("device")
        versions = res.pop("versions")
        legs[leg] = res
        log(f"leg {leg} ok in {res['wall_s']}s "
            f"(set-up {res.get('compile_s')}s)")
    summary = {"ok": True, "device": device, "versions": versions,
               "legs": legs}
    if args.tiny:
        summary["tiny"] = True
    if args.legs:
        summary["partial"] = True
    out = json.dumps(summary)
    if os.path.isdir("chiprun_out") and not args.tiny:
        # the chip tool brings this directory back; a later --refs reads it
        name = "chip_smoke_partial.json" if args.legs else "chip_smoke.json"
        with open(os.path.join("chiprun_out", name), "w") as f:
            f.write(out + "\n")
    print(out)
    # the verdict: these keys and no others, last on stdout
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


# ------------------------------------------------------------------- child --

class Ctx:
    """What every leg needs: the package, the device facts, the size."""

    def __init__(self, tiny):
        import jax

        import paddle_tpu as paddle

        self.jax, self.paddle = jax, paddle
        self.size = TINY if tiny else FULL
        dev = jax.devices()[0]
        self.on_tpu = dev.platform == "tpu"
        if not self.on_tpu and not tiny:
            raise SystemExit(
                f"chip_smoke: needs a TPU; JAX found platform "
                f"{dev.platform!r} ({dev.device_kind})")
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        import jaxlib

        from importlib import metadata

        try:
            libtpu = metadata.version("libtpu")
        except metadata.PackageNotFoundError:
            libtpu = None
        self.versions = {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__, "libtpu": libtpu,
                         "python": sys.version.split()[0]}
        # the chip's compile cache; an XLA:CPU toy run keeps none
        self.cache_dir = paddle.sysconfig.enable_compile_cache() \
            if self.on_tpu else None
        paddle.set_device("tpu" if self.on_tpu else "cpu")

    def model(self):
        """gpt2-medium in bf16, weights drawn from seed 0 — the same
        weights in every leg and process."""
        from paddle_tpu.models import (GPTConfig, GPTForPretraining,
                                       GPTModel)

        self.paddle.seed(0)
        cfg = GPTConfig.preset(self.size["preset"], dtype="bfloat16",
                               dropout=0.0, seq_len=self.size["seq"],
                               **self.size["overrides"])
        return cfg, GPTForPretraining(GPTModel(cfg))

    def batch(self, cfg):
        import numpy as np

        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg.vocab_size,
                            (self.size["batch"], self.size["seq"])
                            ).astype(np.int64)
        return toks, np.roll(toks, -1, axis=1)

    def flash_facts(self):
        """Which attention implementation the traces resolved to."""
        from paddle_tpu.profiler import registry

        c = registry.counters("kernel")
        facts = {k: c[k] for k in ("flash.pallas", "flash.xla",
                                   "flash.fallbacks")}
        if self.on_tpu:
            assert facts["flash.pallas"] >= 1 and facts["flash.xla"] == 0 \
                and facts["flash.fallbacks"] == 0, facts
        else:
            assert facts["flash.pallas"] == 0 and facts["flash.xla"] >= 1, \
                facts
        return facts


def _compile_facts(warm, steady):
    """Set-up as JAX itself counted it, and proof the steady window (the
    last steps / the last wave) compiled nothing."""
    assert steady.compiles == 0, \
        f"{steady.compiles} compiles after warm-up"
    return {"compile_s": round(warm.seconds, 1), "compiles": warm.compiles,
            "cache_hits": warm.cache_hits,
            "compiles_after_warmup": steady.compiles}


def _check_losses(losses):
    import math

    assert all(math.isfinite(v) for v in losses), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"


def _check_against_train_1(losses, refs):
    """The first loss (same weights, same batch) and the second (same
    first optimizer step) must match what TrainStep computed."""
    ref = refs["train-1"]["losses"][:2]
    assert all(abs(a - b) <= LOSS_TOL for a, b in zip(losses, ref)), \
        f"losses {losses[:2]} vs train-1's {ref} (tol {LOSS_TOL})"


def leg_train_1(ctx, refs):
    paddle, size = ctx.paddle, ctx.size
    from paddle_tpu.models import GPTPretrainingCriterion

    cfg, model = ctx.model()
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=size["lr"],
                                 multi_precision=True,
                                 parameters=model.parameters())

    def step_fn(tokens, labels):
        loss = crit(model(tokens), labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train = paddle.jit.TrainStep(step_fn, model, opt)
    toks, labels = (paddle.to_tensor(a) for a in ctx.batch(cfg))
    with paddle.profiler.CompileWatch() as warm:
        losses = [float(train(toks, labels)) for _ in range(2)]
    with paddle.profiler.CompileWatch() as steady:
        losses += [float(train(toks, labels))
                   for _ in range(size["train_steps"] - 2)]
    _check_losses(losses)
    return {"losses": losses, **_compile_facts(warm, steady),
            "kernels": ctx.flash_facts()}


def _lazy_loop(ctx, model, opt, toks, labels, steps):
    """A plain eager train loop under lazy_eval(), the last two steps as
    the steady window; returns the losses and the compile facts."""
    paddle = ctx.paddle
    from paddle_tpu.models import GPTPretrainingCriterion

    crit = GPTPretrainingCriterion()
    losses = []

    def run(n):
        for _ in range(n):
            loss = crit(model(toks), labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))

    with paddle.incubate.lazy_eval():
        with paddle.profiler.CompileWatch() as warm:
            run(steps - 2)
        with paddle.profiler.CompileWatch() as steady:
            run(2)
    _check_losses(losses)
    return losses, _compile_facts(warm, steady)


def _lazy_facts(s0):
    from paddle_tpu.core import lazy

    s1 = lazy.stats()
    facts = {k: s1[k] - s0[k] for k in ("captured_steps", "donated_steps",
                                        "capture_fallbacks")}
    assert facts["captured_steps"] >= 1 and facts["donated_steps"] >= 1 \
        and facts["capture_fallbacks"] == 0, facts
    return facts


def leg_train_1_lazy(ctx, refs):
    paddle, size = ctx.paddle, ctx.size
    from paddle_tpu.core import lazy

    cfg, model = ctx.model()
    opt = paddle.optimizer.AdamW(learning_rate=size["lr"],
                                 multi_precision=True,
                                 parameters=model.parameters())
    toks, labels = (paddle.to_tensor(a) for a in ctx.batch(cfg))
    s0 = lazy.stats()
    losses, compiled = _lazy_loop(ctx, model, opt, toks, labels,
                                  size["lazy_steps"])
    _check_against_train_1(losses, refs)
    return {"losses": losses, **compiled, "lazy": _lazy_facts(s0),
            "kernels": ctx.flash_facts()}


def leg_train_4(ctx, refs):
    paddle, jax, size = ctx.paddle, ctx.jax, ctx.size
    from paddle_tpu.core import lazy
    from paddle_tpu.distributed import fleet, spmd
    from paddle_tpu.profiler import registry

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "use_spmd": True}
    fleet.init(is_collective=True, strategy=strategy)
    cfg, model = ctx.model()
    opt = paddle.optimizer.AdamW(learning_rate=size["lr"],
                                 multi_precision=True,
                                 parameters=model.parameters())
    model = fleet.distributed_model(model)
    toks, labels = (spmd.shard_batch(paddle.to_tensor(a))
                    for a in ctx.batch(cfg))
    s0 = lazy.stats()
    losses, compiled = _lazy_loop(ctx, model, opt, toks, labels,
                                  size["lazy_steps"])
    _check_against_train_1(losses, refs)
    c = registry.counters("spmd")
    assert c["python_collectives_per_step"] == 0, c
    spans = sorted({len(p._data.sharding.device_set)
                    for p in model.parameters()})
    assert spans == [4], f"parameters span {spans} devices, not 4"
    facts = {"losses": losses, **compiled, "lazy": _lazy_facts(s0),
             "spmd": {k: c[k] for k in ("step_compiles",
                                        "python_collectives_per_step",
                                        "params_sharded",
                                        "params_replicated")},
             "mesh": {n: int(s) for n, s in zip(
                 spmd.current_mesh().axis_names,
                 spmd.current_mesh().devices.shape)},
             "kernels": ctx.flash_facts()}
    stats = [d.memory_stats() for d in jax.devices()[:4]]
    if all(s and "bytes_in_use" in s for s in stats):
        used = [int(s["bytes_in_use"]) for s in stats]
        assert max(used) <= 2 * min(used), \
            f"memory piled on one chip: {used}"
        facts["bytes_in_use"] = used
    else:  # the CPU backend reports none
        assert not ctx.on_tpu, "TPU devices reported no memory_stats"
        facts["bytes_in_use"] = "not reported by this backend"
    return facts


# ---- serving ---------------------------------------------------------------

def _requests(ctx, cfg, wave):
    """Seeded request mix: different prompt lengths over both buckets, two
    prompts sharing a prefix, greedy and sampled. `wave` reseeds it."""
    import numpy as np

    size = ctx.size
    rng = np.random.default_rng(100 + wave)
    shared = rng.integers(1, cfg.vocab_size, size["shared_prefix"])
    reqs = []
    for i, n in enumerate(size["prompt_lens"]):
        body = rng.integers(1, cfg.vocab_size, n)
        if i in (1, 2):  # same length, same prefix, different tail
            body[:len(shared)] = shared
        greedy = i in (0, 1, 3)
        opts = dict(max_new_tokens=size["new_tokens"], seed=1000 * wave + i)
        if not greedy:
            opts.update(temperature=0.8, top_k=40)
        reqs.append(([int(t) for t in body], greedy, opts))
    return reqs


def _serve_wave(ctx, server, reqs, vocab):
    handles = [server.submit(p, **opts) for p, _, opts in reqs]
    out = []
    for h, (_, _, opts) in zip(handles, reqs):
        h.result(timeout=600)
        assert h.status == "done", (h.status, h.error)
        assert len(h.tokens) == opts["max_new_tokens"], h
        assert all(0 <= t < vocab for t in h.tokens), h.tokens
        out.append([int(t) for t in h.tokens])
    return out


class Scorer:
    """Reference next-token logits from a single-device copy of the model
    (same seed, same weights), built only if a token stream parts."""

    def __init__(self, ctx):
        self.ctx, self.fn = ctx, None

    def logits(self, context):
        import numpy as np

        paddle = self.ctx.paddle
        if self.fn is None:
            _, model = self.ctx.model()
            model.eval()
            self.fn = paddle.jit.to_static(model)
        L = self.ctx.size["score_len"]
        assert len(context) <= L, (len(context), L)
        ids = np.zeros((1, L), np.int64)
        ids[0, :len(context)] = context
        with paddle.no_grad():
            out = self.fn(paddle.to_tensor(ids))
        return np.asarray(out.numpy()[0, len(context) - 1], np.float32)


def _compare_greedy(scorer, prompts, ref, got, what):
    """Token streams must be identical, or part at a near-tie of the
    reference logits. Returns the list of near-tie partings."""
    ties = []
    for r, (prompt, a, b) in enumerate(zip(prompts, ref, got)):
        if a == b:
            continue
        i = next(k for k in range(len(a)) if a[k] != b[k])
        lg = scorer.logits(prompt + a[:i])
        gap = float(abs(lg[a[i]] - lg[b[i]]))
        off_top = float(lg.max() - min(lg[a[i]], lg[b[i]]))
        assert gap <= NEAR_TIE and off_top <= 2 * NEAR_TIE, (
            f"{what}: request {r} parts at token {i} ({a[i]} vs {b[i]}) "
            f"with reference logit gap {gap:.4f}, {off_top:.4f} below the "
            f"top — not a near-tie")
        ties.append({"request": r, "token": i, "logit_gap": round(gap, 4)})
    return ties


def _serve_engine(ctx, model, **kw):
    from paddle_tpu.serving import GenerationEngine, GenerationServer

    eng = GenerationEngine(model, max_batch_size=8,
                           buckets=ctx.size["buckets"], rng_seed=0, **kw)
    return GenerationServer(engine=eng, max_queue_size=32)


def _serve(ctx, refs, mesh=None):
    """The serving leg on one chip (mesh=None) or on an 'mp' mesh."""
    paddle = ctx.paddle
    from paddle_tpu.profiler import registry

    cfg, model = ctx.model()
    kw = {} if mesh is None else {"mesh": mesh}
    want_kernel = "pallas" if ctx.on_tpu else "xla"
    c0 = dict(registry.counters("serving"))

    def delta(name):
        # a span's counters exist from its first use on
        return registry.counters("serving")[name] - c0.get(name, 0)

    # default kernel choice: no paged_kernel argument, no env
    server = _serve_engine(ctx, model, **kw)
    eng = server.engine
    assert eng.paged_kernel == want_kernel, \
        (eng.paged_kernel, eng.stats()["paged_kernel_reason"])
    pool0 = eng._k[0]  # to see the donation happen, not just be asked for
    waves = [_requests(ctx, cfg, w) for w in range(3)]
    with paddle.profiler.CompileWatch() as warm:
        toks = _serve_wave(ctx, server, waves[0], cfg.vocab_size)
        _serve_wave(ctx, server, waves[1], cfg.vocab_size)
    with paddle.profiler.CompileWatch() as steady:
        _serve_wave(ctx, server, waves[2], cfg.vocab_size)
    server.shutdown()
    eng.pool.audit()
    facts = {
        **_compile_facts(warm, steady),
        "paged_kernel": eng.paged_kernel,
        "prefill_kernel": eng.stats()["prefill_kernel"],
        "prefill_flash_calls": delta("prefill_flash_calls"),
        "prefill_calls": delta("prefill_n"),
        "decode_compiles": delta("decode_compiles"),
        "prefill_compiles": delta("prefill_compiles"),
        "kernel_fallbacks": delta("kernel.fallbacks"),
        "prefix_hits": delta("prefix_hits"),
        "requests_failed": delta("requests_failed"),
        "kv_pools_donated": bool(pool0.is_deleted()),
        "kv_pool_row_major": eng.stats()["kv_pool_row_major"],
        "requests": sum(len(w) for w in waves),
    }
    assert facts["decode_compiles"] == 1, facts
    assert facts["prefill_compiles"] == len(ctx.size["buckets"]), facts
    assert facts["kernel_fallbacks"] == 0 and facts["prefix_hits"] >= 1 \
        and facts["requests_failed"] == 0, facts
    assert facts["kv_pools_donated"] == ctx.on_tpu, facts
    assert facts["kv_pool_row_major"] == 1, facts
    # the prompt span reads through the kernel the decode step resolved
    assert facts["prefill_kernel"] == want_kernel, facts
    assert facts["prefill_flash_calls"] == (
        facts["prefill_calls"] if ctx.on_tpu else 0), facts
    greedy = [i for i, (_, g, _) in enumerate(waves[0]) if g]
    facts["greedy_tokens"] = [toks[i] for i in greedy]
    facts["sampled_tokens"] = [t for i, t in enumerate(toks)
                               if i not in greedy]
    prompts = [waves[0][i][0] for i in greedy]
    scorer = Scorer(ctx)
    if mesh is None:
        # the README's contract: greedy tokens do not depend on the kernel
        oracle = _serve_engine(ctx, model, paged_kernel="xla")
        assert oracle.engine.paged_kernel == "xla"
        ref = _serve_wave(ctx, oracle, [waves[0][i] for i in greedy],
                          cfg.vocab_size)
        oracle.shutdown()
        oracle.engine.pool.audit()
        facts["near_ties_vs_xla_kernel"] = _compare_greedy(
            scorer, prompts, ref, facts["greedy_tokens"],
            f"{eng.paged_kernel} vs xla")
    else:
        facts["near_ties_vs_serve_1"] = _compare_greedy(
            scorer, prompts, refs["serve-1"]["greedy_tokens"],
            facts["greedy_tokens"], "serve-4 vs serve-1")
        desc = eng.describe_sharding()
        specs = {json.dumps(p["spec"]) for p in desc["kv_pools"]}
        assert specs == {json.dumps([None, None, "mp"])}, specs
        assert desc["paged_kernel_sharded"] == ctx.on_tpu, desc
        facts["kv_pool_spec"] = [None, None, "mp"]
        facts["mesh"] = desc["mesh"]["axes"]
    return facts


def leg_serve_1(ctx, refs):
    return _serve(ctx, refs)


def leg_serve_4(ctx, refs):
    from paddle_tpu.distributed import spmd

    return _serve(ctx, refs, mesh=spmd.serving_mesh(4))


# ---- kernels ---------------------------------------------------------------

def leg_kernels(ctx, refs):
    """The Pallas kernels against their XLA oracles on small inputs —
    compiled on the chip, the interpreter route elsewhere (flash has no
    interpreter route: off-chip only the paged family is compared)."""
    import numpy as np

    jax, paddle = ctx.jax, ctx.paddle
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_ops as po

    rng = np.random.default_rng(0)
    kind = "pallas" if ctx.on_tpu else "interpret"
    worst = {}
    with paddle.profiler.CompileWatch() as cw:
        B, M = 4, 8
        nb = 1 + B * M
        # head_dim 64 and 128 in both dtypes at decode (T=1) and verify
        # (T=K+1) spans, plus one head width no tile size divides (16 x 80
        # = 1280 lanes: the merged row has to be whole 128-lane tiles)
        cases = [(dh, 16, 16, dt, T) for dh in (64, 128)
                 for dt in (jnp.bfloat16, jnp.float32) for T in (1, 5)]
        cases.append((80, 16, 8, jnp.float32, 3))
        for dh, H, bs, dt, T in cases:
            q = jnp.asarray(rng.normal(size=(B, T, H, dh)), dt)
            kp = jnp.asarray(rng.normal(size=(nb, bs, H, dh)), dt)
            vp = jnp.asarray(rng.normal(size=(nb, bs, H, dh)), dt)
            bt = jnp.asarray(1 + rng.permutation(B * M).reshape(B, M),
                             jnp.int32)
            qo = jnp.asarray([0, 17, M * bs // 2, M * bs - T], jnp.int32)
            got, ref = (np.asarray(po.paged_attention(
                q, kp, vp, bt, qo + T, qo, kernel=k), np.float32)
                for k in (kind, "xla"))
            atol, rtol = po.PAGED_PARITY_TOL[jnp.dtype(dt).name]
            np.testing.assert_allclose(got, ref, atol=atol, rtol=rtol)
            key = f"paged.dh{dh}.{jnp.dtype(dt).name}"
            worst[key] = max(worst.get(key, 0.0),
                             float(np.abs(got - ref).max()))
        # the prompt span's kernel (flash_prefill): a bucket of 64 rows a
        # slot, cold and behind a cached prefix, the second slot's prompt
        # shorter than the bucket
        for dh, H, dt in ((64, 16, jnp.bfloat16), (128, 16, jnp.bfloat16),
                          (64, 16, jnp.float32)):
            q = jnp.asarray(rng.normal(size=(2, 64, H, dh)), dt)
            kp = jnp.asarray(rng.normal(size=(nb, 16, H * dh)), dt)
            vp = jnp.asarray(rng.normal(size=(nb, 16, H * dh)), dt)
            bt = jnp.asarray(1 + rng.permutation(2 * M).reshape(2, M),
                             jnp.int32)
            for qo, sl in (((0, 0), (64, 37)), ((48, 16), (112, 61))):
                qo, sl = jnp.asarray(qo, jnp.int32), jnp.asarray(sl, jnp.int32)
                got, ref = (np.asarray(po.flash_prefill(
                    q, kp, vp, bt, sl, qo, kernel=k), np.float32)
                    for k in (kind, "xla"))
                atol, rtol = po.PAGED_PARITY_TOL[jnp.dtype(dt).name]
                for b in range(2):  # the prompt's rows, not the padding
                    n = int(sl[b] - qo[b])
                    np.testing.assert_allclose(got[b, :n], ref[b, :n],
                                               atol=atol, rtol=rtol)
                    key = f"prefill.dh{dh}.{jnp.dtype(dt).name}"
                    worst[key] = max(worst.get(key, 0.0), float(
                        np.abs(got[b, :n] - ref[b, :n]).max()))
        if ctx.on_tpu:
            for (b, t, n, h), dt in (((2, 512, 8, 64), jnp.bfloat16),
                                     ((2, 512, 4, 128), jnp.bfloat16),
                                     ((1, 256, 4, 64), jnp.float32)):
                q, k, v = (jnp.asarray(rng.normal(size=(b, t, n, h)), dt)
                           for _ in range(3))
                before = dict(po._flash_counters)

                def loss(fn):
                    return lambda a, b_, c: fn(
                        a, b_, c, causal=True).astype(jnp.float32).sum()

                out, ref = (np.asarray(jax.jit(
                    lambda a, b_, c, fn=fn: fn(a, b_, c, causal=True))(
                        q, k, v), np.float32)
                    for fn in (po.flash_attention, po._attention_xla))
                assert po._flash_counters["flash.pallas"] \
                    > before["flash.pallas"]
                fwd = float(np.abs(out - ref).max())
                assert fwd < 0.05, f"flash fwd diverges: {fwd}"
                gp, gx = (jax.jit(jax.grad(loss(fn), argnums=(0, 1, 2)))(
                    q, k, v) for fn in (po.flash_attention,
                                        po._attention_xla))
                key = f"flash.h{h}.{jnp.dtype(dt).name}"
                worst[key + ".fwd"] = fwd
                for name, a, b_ in zip("qkv", gp, gx):
                    a, b_ = (np.asarray(x, np.float32) for x in (a, b_))
                    d = float(np.abs(a - b_).max())
                    bound = 0.25 * max(float(np.abs(b_).mean()), 1.0)
                    assert d < bound, f"flash d{name} diverges: {d}"
                    worst[f"{key}.d{name}"] = d
    return {"compile_s": round(cw.seconds, 1), "compiles": cw.compiles,
            "cache_hits": cw.cache_hits, "paged_kernel": kind,
            "max_abs_diff": {k: round(v, 6) for k, v in worst.items()}}


LEG_FNS = {"kernels": leg_kernels, "train-1": leg_train_1,
           "train-1-lazy": leg_train_1_lazy, "serve-1": leg_serve_1,
           "train-4": leg_train_4, "serve-4": leg_serve_4}


def child(args):
    ctx = Ctx(args.tiny)
    res = LEG_FNS[args.leg](ctx, json.loads(args.refs_json))
    res.update(status="ok", device=ctx.device, versions=ctx.versions,
               cache_dir=ctx.cache_dir)
    print(json.dumps(res), flush=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy size on any platform (debugging this script)")
    ap.add_argument("--legs", help="comma-separated subset of " +
                    ",".join(LEGS))
    ap.add_argument("--refs", help="summary JSON of an earlier run, for "
                    "the legs --legs leaves out")
    ap.add_argument("--leg", choices=LEGS, help=argparse.SUPPRESS)
    ap.add_argument("--refs-json", default="{}", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    return child(args) if args.leg else parent(args)


if __name__ == "__main__":
    sys.exit(main())
