"""Benchmark: GPT pretraining throughput + MFU on one TPU chip.

North star (BASELINE.json): tokens/sec/chip + MFU on GPT. The whole train
step (fwd + bwd + AdamW) is one XLA executable via jit.TrainStep; bf16
compute with fp32 master weights (multi_precision), activation recompute,
Pallas flash attention, all under the configuration `import paddle_tpu`
gives a user (no PADDLE_TPU_* variable is set here).

`python bench.py` runs the TPU_CONFIGS ladder and prints one JSON line per
config, then the best-MFU line once more tagged "best": true:
  {"metric": ..., "value": tokens/sec/chip, "unit": ..., "vs_baseline": ...,
   "platform": "tpu", "device_kind": ..., "device_count": ...}
vs_baseline = MFU / 0.45 (the driver's v5p-128 target ratio).

It runs on a TPU or it fails: with any other platform it prints one line
naming the platform it found and exits non-zero, and a config that fails
fails the run. There is no CPU ladder, no cached line and no replay.

One process per chip: this parent imports only numpy and never touches JAX
— a process that has initialized the TPU backend holds the chip, and a child
that needed it would then fail or hang. Each config runs in its own child,
one at a time, and the children share the persistent compile cache placed by
`paddle_tpu.sysconfig.enable_compile_cache`.

The four CPU gate modes (--ratio, --spmd, --serve, --serve-fleet) are
functional checks on toy models; their timings are not device metrics.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

# (preset, batch, seq_len, recompute_policy[, child env overrides]).
# One rung: the only config shown to run under the package defaults on the
# installed jax/libtpu (PR 21 chip run). The July ladder's next rung,
# gpt2-medium bs12 remat=none, no longer loads on a 16 GB v5e
# ("RESOURCE_EXHAUSTED: Error loading program 'jit_pure': Attempting to
# reserve 9.80G at the bottom of memory"), and a failing child fails the
# run; bs16/dots_attn and seq2048 were not retried. The benchmark PR
# replaces this table with cells.
TPU_CONFIGS = [
    ("gpt2-medium", 8, 1024, "none"),
]

# per-child watchdog, seconds (compile + 11 steps of the largest rung)
CHILD_TIMEOUT = 900.0


def peak_flops_per_chip():
    """bf16 peak FLOP/s of the local accelerator (shared MFU denominator,
    moved to the cost model so profiler.summary() uses the same table)."""
    from paddle_tpu.cost_model import device_peak_flops

    return device_peak_flops()


def _telemetry_line(extra=None):
    """One structured counters line per run (ISSUE 3): the registry
    snapshot — lazy capture counters, jit cache hits/misses, collective
    bytes, dataloader waits, step FLOPs/token gauges — as a driver-
    parseable JSON record. Emitted BEFORE the metric line so the parent
    (which treats the LAST line as the result) forwards both."""
    from paddle_tpu import profiler

    snap = profiler.stats()
    rec = {"metric": "telemetry", "value": 0, "unit": "",
           "vs_baseline": 0, "counters": snap["counters"],
           "gauges": snap["gauges"], "timings": snap["timings"]}
    if extra:
        rec.update(extra)
    print(json.dumps(rec), flush=True)


def run(preset, batch, seq_len, steps=8, warmup=3, dtype="bfloat16",
        policy="full"):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import (GPTConfig, GPTForPretraining, GPTModel,
                                   GPTPretrainingCriterion)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind})")
    paddle.sysconfig.enable_compile_cache()
    paddle.seed(0)
    cfg = GPTConfig.preset(preset, seq_len=seq_len, dtype=dtype,
                           dropout=0.0,
                           use_recompute=(policy != "none"),
                           recompute_policy=None if policy in ("full",
                                                               "none")
                           else policy)
    model = GPTForPretraining(GPTModel(cfg))
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, multi_precision=True,
                                 parameters=model.parameters())

    def step_fn(tokens, labels):
        loss = crit(model(tokens), labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train = paddle.jit.TrainStep(step_fn, model, opt)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (batch, seq_len)).astype(np.int64)
    labels = np.roll(toks, -1, axis=1)
    tokens_t = paddle.to_tensor(toks)
    labels_t = paddle.to_tensor(labels)

    for _ in range(warmup):
        loss = train(tokens_t, labels_t)
    float(loss)  # sync
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = train(tokens_t, labels_t)
    final = float(loss)  # sync
    dt = (time.perf_counter() - t0) / steps

    tokens_per_step = batch * seq_len
    tps = tokens_per_step / dt
    flops = cfg.flops_per_token() * tokens_per_step
    mfu = flops / dt / peak_flops_per_chip()
    # cost-model-derived per-step work → profiler gauges, so
    # Profiler.summary() and the telemetry line report MFU/tokens-per-sec
    paddle.profiler.set_step_metrics(flops_per_step=flops,
                                     tokens_per_step=tokens_per_step)
    return tps, mfu, final, dev


def _run_ratio_child():
    """--ratio mode: lazy-eager (zero-dispatch replay) vs TrainStep on
    the CPU MLP microbench (3-layer MLP, bs64, AdamW). Emits one JSON line:
      {"metric": "lazy/trainstep step-time ratio", ...}
    Methodology: the host this runs on is noisy (absolute ms drift 2-3x
    between runs), so the two loops are INTERLEAVED in small adjacent
    batches and the headline value is the MEDIAN of the per-round
    PAIRED ratios (lazy_i / trainstep_i): each pair shares one time
    window, so machine-wide drift cancels per pair, and the median
    rejects the rounds where a noise spike lands inside exactly one leg
    (a min-of-rounds estimator was observed swinging 1.3x-2.0x run to
    run on identical code). Per-step host times additionally report
    p50/p99 (ISSUE 9: jitter must not hide behind the gate average).
    Both loops read float(loss) every step (the plain-eager-loop
    contract being benchmarked). The lazy leg runs through
    lazy.ReplayStep — the ISSUE-9 replay-by-signature fast path — and
    the record carries its proof obligations:
    fastpath_ops_dispatched_per_step == 0 and fastpath_hit_rate >= 0.9
    over the measured window. vs_baseline is 1.3/ratio: the ISSUE-9
    acceptance gate tightened the ISSUE-2 gate from 2.0 to 1.3."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    # ISSUE 18: the 1.3x gate must hold WITH span tracing armed —
    # tracing that only gates clean while disabled is not deployable.
    # Spans sit around executable calls, never inside the replay loop,
    # so the measured window sees one boolean load per span site.
    os.environ.setdefault("PADDLE_TPU_TRACE", "1")
    import statistics
    import time as _t

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.core import lazy
    from paddle_tpu.profiler import registry as _reg

    def make(seed=7):
        paddle.seed(seed)
        net = nn.Sequential(nn.Linear(64, 256), nn.Tanh(),
                            nn.Linear(256, 256), nn.Tanh(),
                            nn.Linear(256, 8))
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=net.parameters())
        return net, opt

    rng = np.random.default_rng(0)
    xt = paddle.to_tensor(rng.normal(size=(64, 64)).astype(np.float32))
    yt = paddle.to_tensor(rng.normal(size=(64, 8)).astype(np.float32))

    net, opt = make()

    def lazy_body():
        with paddle.incubate.lazy_eval():
            loss = ((net(xt) - yt) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

    replay = lazy.ReplayStep(lazy_body, optimizers=opt)

    def lazy_step():
        return float(replay())

    net2, opt2 = make()

    def step_fn(a, b):
        loss = ((net2(a) - b) ** 2).mean()
        loss.backward()
        opt2.step()
        opt2.clear_grad()
        return loss

    train = paddle.jit.TrainStep(step_fn, net2, opt2)

    # checkpointing rides along by default (ISSUE 4 acceptance: the
    # ratio gate holds WITH a realistic save interval): every CKPT_EVERY
    # steps each leg snapshots params+optimizer and hands the write to
    # the async writer thread — the step must not block on disk.
    # PADDLE_TPU_BENCH_CKPT=0 opts out for A/B comparison.
    ckpt_on = os.environ.get("PADDLE_TPU_BENCH_CKPT", "1") != "0"
    CKPT_EVERY = 10
    mgr = mgr2 = None
    ckpt_step = [0, 0]
    if ckpt_on:
        import shutil
        import tempfile

        from paddle_tpu.incubate import checkpoint as _ckpt

        ckpt_root = tempfile.mkdtemp(prefix="bench_ckpt_")
        mgr = _ckpt.CheckpointManager(os.path.join(ckpt_root, "lazy"),
                                      max_to_keep=2, async_save=True)
        mgr2 = _ckpt.CheckpointManager(os.path.join(ckpt_root, "ts"),
                                       max_to_keep=2, async_save=True)

    def maybe_ckpt(leg, manager, network, optim):
        if manager is None:
            return
        ckpt_step[leg] += 1
        if ckpt_step[leg] % CKPT_EVERY == 0:
            from paddle_tpu.incubate.checkpoint import \
                capture_training_state

            manager.save(capture_training_state(network, optim),
                         step=ckpt_step[leg])

    for _ in range(25):  # warmup: records, promotes, donates, ARMS the
        lazy_step()      # zero-dispatch replay fast path
    for _ in range(5):
        float(train(xt, yt))
    s0 = lazy.stats()
    f0 = dict(_reg.counters("fastpath"))
    lz, ts = [], []
    lz_steps, ts_steps = [], []  # per-step host times (p50/p99 report)
    for _ in range(20):
        t0 = _t.perf_counter()
        for _ in range(10):
            t1 = _t.perf_counter()
            lazy_step()
            lz_steps.append(_t.perf_counter() - t1)
            maybe_ckpt(0, mgr, net, opt)
        lz.append((_t.perf_counter() - t0) / 10 * 1e3)
        t0 = _t.perf_counter()
        for _ in range(10):
            t1 = _t.perf_counter()
            float(train(xt, yt))
            ts_steps.append(_t.perf_counter() - t1)
            maybe_ckpt(1, mgr2, net2, opt2)
        ts.append((_t.perf_counter() - t0) / 10 * 1e3)
    s1 = lazy.stats()
    f1 = dict(_reg.counters("fastpath"))
    if mgr is not None:
        mgr.wait()
        mgr2.wait()
        shutil.rmtree(ckpt_root, ignore_errors=True)
    ratio = statistics.median(a / b for a, b in zip(lz, ts))

    def _pct(xs, q):
        ys = sorted(xs)
        return ys[min(len(ys) - 1, int(round(q * (len(ys) - 1))))] * 1e3

    fp_calls = (f1["hits"] - f0["hits"]) + (f1["misses"] - f0["misses"])
    fp_hit_rate = (f1["hits"] - f0["hits"]) / fp_calls if fp_calls else 0.0
    rec = {
        "metric": "lazy/trainstep step-time ratio (MLP microbench, CPU)",
        "value": round(ratio, 3),
        "unit": "x",
        "vs_baseline": round(1.3 / ratio, 4),
        "gate": 1.3,
        "lazy_ms": round(min(lz), 3),
        "trainstep_ms": round(min(ts), 3),
        "ratio_of_mins": round(min(lz) / min(ts), 3),
        # per-step host-time spread: jitter can't hide behind the mean
        "lazy_step_p50_ms": round(_pct(lz_steps, 0.50), 3),
        "lazy_step_p99_ms": round(_pct(lz_steps, 0.99), 3),
        "trainstep_p50_ms": round(_pct(ts_steps, 0.50), 3),
        "trainstep_p99_ms": round(_pct(ts_steps, 0.99), 3),
        "captured_steps": s1["captured_steps"] - s0["captured_steps"],
        "donated_steps": s1["donated_steps"] - s0["donated_steps"],
        # ISSUE-9 proof obligations over the measured window: zero per-op
        # Python on replayed steps, fast-path hit rate >= 0.9. The
        # window SUM (replay_ops_dispatched delta) is the real proof —
        # the per-step value is last-write-wins and a clean final step
        # could mask a mid-window leak.
        "fastpath_hit_rate": round(fp_hit_rate, 4),
        "fastpath_ops_dispatched_per_step":
            f1["replay_ops_dispatched"] - f0["replay_ops_dispatched"],
        "fastpath_audit_runs": f1["audit_runs"] - f0["audit_runs"],
        "fastpath_demotions": f1["demotions"] - f0["demotions"],
        "ckpt_interval": CKPT_EVERY if ckpt_on else 0,
        "tracing_enabled": os.environ.get("PADDLE_TPU_TRACE") == "1",
        "platform": "cpu",
    }
    # the SPMD one-compilation gate rides every --ratio run (ISSUE 6):
    # its {"metric": "spmd"} line prints before the ratio record so the
    # last-line-wins driver contract still sees the ratio result
    _spmd_line()
    # the telemetry line below carries checkpoint.save.* timings when
    # checkpointing was on (async write wall time, snapshot time)
    _telemetry_line()
    print(json.dumps(rec), flush=True)
    return 0


def _run_spmd_child():
    """--spmd mode: one-compilation SPMD train-step gate (ISSUE 6) on a
    virtual 8-device CPU mesh, dp=4 x mp=2. A tiny mp-layer transformer
    trains under fleet use_spmd + lazy step capture; after warmup the
    steady window must show ZERO new step compiles and ZERO
    Python-dispatched collectives (GSPMD owns all comm inside the one
    captured executable), with loss parity vs the manual-mp path
    (identical model, capture disabled — N per-op executables). The
    captured plan's specs then run through tools/sharding_lint.py;
    problems are reported in the record as warnings, not failures.
    Emitted from every --ratio run (telemetry first, ratio line last)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    # append, don't setdefault: a user-set XLA_FLAGS must not silently
    # drop the 8-device flag the dp4 x mp2 mesh needs
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()
    import importlib.util

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.core import lazy
    from paddle_tpu.distributed import fleet, spmd
    from paddle_tpu.distributed.meta_parallel.mp_layers import (
        ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
        VocabParallelEmbedding)
    from paddle_tpu.profiler import registry as _reg

    V, D, T, B = 64, 32, 16, 8

    class TinyMP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = VocabParallelEmbedding(V, D)
            self.ln = nn.LayerNorm(D)
            self.fc1 = ColumnParallelLinear(D, 4 * D, gather_output=False)
            self.fc2 = RowParallelLinear(4 * D, D, input_is_parallel=True)
            self.head = ColumnParallelLinear(D, V, gather_output=False,
                                             has_bias=False)
            self.ce = ParallelCrossEntropy()

        def forward(self, toks, labels):
            h = self.emb(toks)
            h = h + self.fc2(paddle.nn.functional.relu(
                self.fc1(self.ln(h))))
            return self.ce(self.head(h), labels).mean()

    def make(use_spmd):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 4, "mp_degree": 2, "pp_degree": 1,
            "sharding_degree": 1, "use_spmd": use_spmd}
        fleet.init(is_collective=True, strategy=strategy)
        paddle.seed(123)
        net = TinyMP()
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=net.parameters())
        return fleet.distributed_model(net), opt

    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, (B, T)).astype(np.int64)
    labels = np.roll(toks, -1, 1)

    def run(net, opt, tt, lt, steps, capture):
        def step():
            with lazy.capture_guard(capture), paddle.incubate.lazy_eval():
                loss = net(tt, lt)
                loss.backward()
                opt.step()
                opt.clear_grad()
                return float(loss)

        return [step() for _ in range(steps)]

    # SPMD leg: warmup past promotion+donation, then the gated window
    net, opt = make(True)
    tt = spmd.shard_batch(paddle.to_tensor(toks))
    lt = spmd.shard_batch(paddle.to_tensor(labels))
    warm = run(net, opt, tt, lt, 8, True)
    c0, s0 = dict(_reg.counters("spmd")), lazy.stats()
    steady = run(net, opt, tt, lt, 6, True)
    c1, s1 = dict(_reg.counters("spmd")), lazy.stats()
    desc = spmd.describe_plans()

    # manual-mp oracle: same model/seed/data, capture off — per-op
    # dispatched executables with the same GSPMD layouts
    net2, opt2 = make(False)
    tt2 = paddle.to_tensor(toks)
    lt2 = paddle.to_tensor(labels)
    oracle = run(net2, opt2, tt2, lt2, 14, False)
    parity = max(abs(a - b) for a, b in zip(warm + steady, oracle))

    lint_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools", "sharding_lint.py")
    spec = importlib.util.spec_from_file_location("sharding_lint",
                                                  lint_path)
    slint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(slint)
    problems = slint.lint(desc)

    steady_ok = (
        c1["step_compiles"] == c0["step_compiles"]
        and c1["python_collectives"] == c0["python_collectives"]
        and c1["python_collectives_per_step"] == 0
        and s1["captured_steps"] - s0["captured_steps"] == len(steady)
        and s1["nodes_built"] == s0["nodes_built"]
        and parity < 1e-4)
    _telemetry_line()
    rec = {
        "metric": "spmd",
        "value": c1["python_collectives_per_step"],
        "unit": "python collectives/step",
        "vs_baseline": 1.0 if steady_ok else 0.0,
        "step_compiles": c1["step_compiles"],
        "steady_new_compiles": c1["step_compiles"] - c0["step_compiles"],
        "captured_steps": s1["captured_steps"] - s0["captured_steps"],
        "donated_steps": s1["donated_steps"] - s0["donated_steps"],
        "parity_max_abs_vs_manual_mp": round(parity, 8),
        "params_sharded": c1["params_sharded"],
        "lint_warnings": problems,
        "platform": "cpu",
    }
    print(json.dumps(rec), flush=True)
    pp_ok = _run_spmd_pp_leg(slint)
    ppz_ok = _run_spmd_pp_zero_leg(slint)
    moe_ok = _run_moe_ep_leg(slint)
    return 0 if (steady_ok and pp_ok and ppz_ok and moe_ok) else 1


def _run_spmd_pp_leg(slint):
    """dp2 x mp2 x pp2 gate (ISSUE 15): a gpt2-tiny pipeline trains
    through the one-compilation pp path (distributed.pp_spmd); the
    steady window must replay with ZERO new compiles, ZERO
    Python-dispatched collectives and ZERO dispatched ops (ReplayStep
    armed), with trajectory parity vs a dense single-chip oracle
    (identical seed/init/data — the engine oracle's shard_map needs a
    newer jaxlib at dp/mp>1, tests/test_spmd_pp.py covers it at pp-only).
    Emits the {"metric": "spmd-pp"} line; False fails the --spmd child."""
    import paddle_tpu as paddle
    from paddle_tpu.core import lazy
    from paddle_tpu.distributed import fleet, pp_spmd, spmd
    from paddle_tpu.models import (GPTConfig, GPTForPretraining, GPTModel,
                                   GPTPretrainingCriterion)
    from paddle_tpu.profiler import registry as _reg

    V, T, B, M = 64, 16, 16, 2

    def make_model():
        cfg = GPTConfig.preset("gpt2-tiny", vocab_size=V, n_layer=2,
                               seq_len=T, dropout=0.0, n_head=2,
                               d_model=32)
        paddle.seed(123)
        model = GPTForPretraining(GPTModel(cfg))
        opt = paddle.optimizer.AdamW(1e-3,
                                     parameters=model.parameters())
        return model, opt, GPTPretrainingCriterion()

    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, (B, T)).astype(np.int64)
    labels = np.roll(toks, -1, 1)

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 2, "mp_degree": 2, "pp_degree": 2,
        "sharding_degree": 1, "use_spmd": True}
    strategy.pipeline_configs = {"accumulate_steps": M}
    fleet.init(is_collective=True, strategy=strategy)
    model, opt, crit = make_model()
    model = fleet.distributed_model(model)
    step = pp_spmd.PipelineSpmdStep(model, opt, criterion=crit,
                                    accumulate_steps=M)
    losses = [float(step.train_batch([toks, labels])) for _ in range(8)]
    c0, s0 = dict(_reg.counters("spmd")), lazy.stats()
    f0 = dict(_reg.counters("fastpath"))
    losses += [float(step.train_batch([toks, labels])) for _ in range(4)]
    c1, s1 = dict(_reg.counters("spmd")), lazy.stats()
    f1 = dict(_reg.counters("fastpath"))
    desc = spmd.describe_plans()
    problems = slint.lint(desc)
    donation = step.refresh_pipeline_stats()

    # dense single-chip oracle: same seed/init/data, capture off
    spmd.disable()
    model2, opt2, crit2 = make_model()
    tt2, lt2 = paddle.to_tensor(toks), paddle.to_tensor(labels)

    def dense_step():
        with lazy.capture_guard(False), paddle.incubate.lazy_eval():
            loss = crit2(model2(tt2), lt2)
            loss.backward()
            opt2.step()
            opt2.clear_grad()
            return float(loss)

    oracle = [dense_step() for _ in range(len(losses))]
    parity = max(abs(a - b) for a, b in zip(losses, oracle))
    window = 4
    hits = f1["hits"] - f0["hits"]
    misses = f1["misses"] - f0["misses"]
    pp_ok = (
        c1["step_compiles"] == c0["step_compiles"]
        and c1["python_collectives"] == c0["python_collectives"]
        and c1["python_collectives_per_step"] == 0
        and s1["captured_steps"] - s0["captured_steps"] == window
        and s1["nodes_built"] == s0["nodes_built"]
        and hits == window
        and f1["replay_ops_dispatched"] == f0["replay_ops_dispatched"]
        and parity < 1e-4
        and not problems)
    rec = {
        "metric": "spmd-pp",
        "value": c1["python_collectives_per_step"],
        "unit": "python collectives/step",
        "vs_baseline": 1.0 if pp_ok else 0.0,
        "mesh": "dp2xmp2xpp2",
        "microbatches": M,
        "steady_new_compiles": c1["step_compiles"] - c0["step_compiles"],
        "captured_steps": s1["captured_steps"] - s0["captured_steps"],
        "donated_steps": s1["donated_steps"] - s0["donated_steps"],
        "fastpath_hit_rate": round(hits / max(hits + misses, 1), 4),
        "fastpath_ops_dispatched":
            f1["replay_ops_dispatched"] - f0["replay_ops_dispatched"],
        "stage_classes_carried": donation["carried"],
        "stage_classes_donated": donation["donated"],
        "parity_max_abs_vs_dense": round(parity, 8),
        "lint_warnings": problems,
        "platform": "cpu",
    }
    print(json.dumps(rec), flush=True)
    return pp_ok


def _run_spmd_pp_zero_leg(slint):
    """pp=2 x sharding=2 (x mp=2) gate (ISSUE 16): the topology PR 14
    refused now FOLDS onto the 3-axis mesh ('sharding' collapses into
    'dp' with a device-order-preserving transpose) and a ZeRO-annotated
    (group_sharded_parallel 'p_g_os') gpt2-tiny pipeline trains through
    the SAME one-compilation path: zero new compiles, zero Python
    collectives, zero dispatched ops in the steady window, dense-oracle
    loss parity. Emits the {"metric": "spmd-pp-zero"} line; False fails
    the --spmd child."""
    import paddle_tpu as paddle
    from paddle_tpu.core import lazy
    from paddle_tpu.distributed import fleet, pp_spmd, spmd
    from paddle_tpu.distributed.sharding import group_sharded_parallel
    from paddle_tpu.models import (GPTConfig, GPTForPretraining, GPTModel,
                                   GPTPretrainingCriterion)
    from paddle_tpu.profiler import registry as _reg

    V, T, B, M = 64, 16, 16, 2

    def make_model():
        cfg = GPTConfig.preset("gpt2-tiny", vocab_size=V, n_layer=2,
                               seq_len=T, dropout=0.0, n_head=2,
                               d_model=32)
        paddle.seed(123)
        model = GPTForPretraining(GPTModel(cfg))
        opt = paddle.optimizer.AdamW(1e-3,
                                     parameters=model.parameters())
        return model, opt, GPTPretrainingCriterion()

    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, (B, T)).astype(np.int64)
    labels = np.roll(toks, -1, 1)

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
        "sharding_degree": 2, "use_spmd": True}
    strategy.pipeline_configs = {"accumulate_steps": M}
    fleet.init(is_collective=True, strategy=strategy)
    model, opt, crit = make_model()
    model, opt, _ = group_sharded_parallel(model, opt, "p_g_os")
    model = fleet.distributed_model(model)
    step = pp_spmd.PipelineSpmdStep(model, opt, criterion=crit,
                                    accumulate_steps=M)
    losses = [float(step.train_batch([toks, labels])) for _ in range(8)]
    c0, s0 = dict(_reg.counters("spmd")), lazy.stats()
    f0 = dict(_reg.counters("fastpath"))
    losses += [float(step.train_batch([toks, labels])) for _ in range(4)]
    c1, s1 = dict(_reg.counters("spmd")), lazy.stats()
    f1 = dict(_reg.counters("fastpath"))
    desc = spmd.describe_plans()
    problems = slint.lint(desc)

    # ZeRO really folded: some plan leaf is sharded over the folded
    # 'dp' axis (degree 2 = the sharding group — dp_degree is 1 here)
    plan = next((p for p in desc["plans"]
                 if p.get("first_op") == "pp_pipeline_step"), None)
    zero_folded = plan is not None and any(
        "'dp'" in str(lf.get("spec")) for lf in plan["leaves"])

    # dense single-chip oracle: same seed/init/data, capture off
    spmd.disable()
    model2, opt2, crit2 = make_model()
    tt2, lt2 = paddle.to_tensor(toks), paddle.to_tensor(labels)

    def dense_step():
        with lazy.capture_guard(False), paddle.incubate.lazy_eval():
            loss = crit2(model2(tt2), lt2)
            loss.backward()
            opt2.step()
            opt2.clear_grad()
            return float(loss)

    oracle = [dense_step() for _ in range(len(losses))]
    parity = max(abs(a - b) for a, b in zip(losses, oracle))
    window = 4
    hits = f1["hits"] - f0["hits"]
    misses = f1["misses"] - f0["misses"]
    ppz_ok = (
        c1["step_compiles"] == c0["step_compiles"]
        and c1["python_collectives"] == c0["python_collectives"]
        and c1["python_collectives_per_step"] == 0
        and s1["captured_steps"] - s0["captured_steps"] == window
        and s1["nodes_built"] == s0["nodes_built"]
        and hits == window
        and f1["replay_ops_dispatched"] == f0["replay_ops_dispatched"]
        and zero_folded
        and parity < 1e-4
        and not problems)
    rec = {
        "metric": "spmd-pp-zero",
        "value": c1["python_collectives_per_step"],
        "unit": "python collectives/step",
        "vs_baseline": 1.0 if ppz_ok else 0.0,
        "mesh": "dp1xsh2xpp2xmp2 -> (dp2,pp2,mp2)",
        "zero_level": "p_g_os",
        "zero_folded_to_dp": zero_folded,
        "microbatches": M,
        "steady_new_compiles": c1["step_compiles"] - c0["step_compiles"],
        "captured_steps": s1["captured_steps"] - s0["captured_steps"],
        "donated_steps": s1["donated_steps"] - s0["donated_steps"],
        "fastpath_hit_rate": round(hits / max(hits + misses, 1), 4),
        "fastpath_ops_dispatched":
            f1["replay_ops_dispatched"] - f0["replay_ops_dispatched"],
        "parity_max_abs_vs_dense": round(parity, 8),
        "lint_warnings": problems,
        "platform": "cpu",
    }
    print(json.dumps(rec), flush=True)
    return ppz_ok


def _run_moe_ep_leg(slint):
    """dp=2 x ep=2 gate (ISSUE 20): a gpt2-tiny-moe model (fixed-shape
    top-k routing, expert banks sharded over 'ep') trains through the
    one-compilation path with VARYING batches — routing changes every
    step, the executable must not. The steady window must show zero new
    compiles, zero Python collectives and full capture/donation, with
    loss parity vs the identical model at ep=1 (the all-to-all moves
    experts, not math) and a throughput line vs ep=1 and vs the dense
    (moe_num_experts=0) model of the same dims. Emits the
    {"metric": "moe-ep"} line; False fails the --spmd child."""
    import time as _time

    import paddle_tpu as paddle
    from paddle_tpu.core import lazy
    from paddle_tpu.distributed import fleet, spmd
    from paddle_tpu.models import (GPTConfig, GPTForPretraining, GPTModel,
                                   GPTPretrainingCriterion)
    from paddle_tpu.profiler import registry as _reg

    V, T, B = 64, 16, 8
    WARM, WINDOW = 8, 6

    def make_model(moe):
        preset = "gpt2-tiny-moe" if moe else "gpt2-tiny"
        cfg = GPTConfig.preset(preset, vocab_size=V, n_layer=2,
                               seq_len=T, dropout=0.0, n_head=2,
                               d_model=32)
        paddle.seed(123)
        model = GPTForPretraining(GPTModel(cfg))
        opt = paddle.optimizer.AdamW(1e-3,
                                     parameters=model.parameters())
        return model, opt, GPTPretrainingCriterion()

    def init_fleet(ep):
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 2, "mp_degree": 1, "pp_degree": 1,
            "sharding_degree": 1, "ep_degree": ep, "use_spmd": True}
        fleet.init(is_collective=True, strategy=strategy)

    def run_leg(ep, moe=True):
        init_fleet(ep)
        model, opt, crit = make_model(moe)
        model = fleet.distributed_model(model)
        rng = np.random.default_rng(0)

        def step():
            toks = rng.integers(0, V, (B, T)).astype(np.int64)
            tt = spmd.shard_batch(paddle.to_tensor(toks))
            lt = spmd.shard_batch(paddle.to_tensor(
                np.roll(toks, -1, 1)))
            with lazy.capture_guard(True), paddle.incubate.lazy_eval():
                loss = crit(model(tt), lt)
                aux = model.moe_aux_loss()
                if aux is not None:
                    loss = loss + aux
                loss.backward()
                opt.step()
                opt.clear_grad()
                return float(loss)

        warm = [step() for _ in range(WARM)]
        c0, s0 = dict(_reg.counters("spmd")), lazy.stats()
        t0 = _time.perf_counter()
        steady = [step() for _ in range(WINDOW)]
        step_s = (_time.perf_counter() - t0) / WINDOW
        c1, s1 = dict(_reg.counters("spmd")), lazy.stats()
        return {
            "losses": warm + steady,
            "step_ms": step_s * 1e3,
            "tokens_per_s": B * T / step_s,
            "new_compiles": c1["step_compiles"] - c0["step_compiles"],
            "captured": s1["captured_steps"] - s0["captured_steps"],
            "donated": s1["donated_steps"] - s0["donated_steps"],
            "nodes_built": s1["nodes_built"] - s0["nodes_built"],
            "py_collectives": c1["python_collectives"]
            - c0["python_collectives"],
            "desc": spmd.describe_plans(),
        }

    ep2 = run_leg(2)
    problems = slint.lint(ep2["desc"])
    ep_leaves = sum(
        1 for p in ep2["desc"]["plans"] if p.get("spmd")
        for lf in p["leaves"]
        if lf.get("expert_membership") == "sharded")
    # ep=1 and dense legs re-init the mesh (dropping ep2's plans — its
    # description is already banked above)
    ep1 = run_leg(1)
    dense = run_leg(1, moe=False)
    parity = max(abs(a - b)
                 for a, b in zip(ep2["losses"], ep1["losses"]))
    moe_ok = (
        ep2["new_compiles"] == 0
        and ep2["captured"] == WINDOW
        and ep2["donated"] == WINDOW
        and ep2["nodes_built"] == 0
        and ep2["py_collectives"] == 0
        and ep_leaves > 0
        and parity < 5e-2
        and not problems)
    rec = {
        "metric": "moe-ep",
        "value": round(ep2["tokens_per_s"], 1),
        "unit": "tokens/sec (ep=2)",
        "vs_baseline": 1.0 if moe_ok else 0.0,
        "mesh": "dp2xep2",
        "step_ms_ep2": round(ep2["step_ms"], 3),
        "step_ms_ep1": round(ep1["step_ms"], 3),
        "step_ms_dense": round(dense["step_ms"], 3),
        "tokens_per_s_ep1": round(ep1["tokens_per_s"], 1),
        "tokens_per_s_dense": round(dense["tokens_per_s"], 1),
        "steady_new_compiles": ep2["new_compiles"],
        "captured_steps": ep2["captured"],
        "donated_steps": ep2["donated"],
        "ep_sharded_leaves": ep_leaves,
        "parity_max_abs_ep2_vs_ep1": round(parity, 8),
        "lint_warnings": problems,
        "platform": "cpu",
    }
    print(json.dumps(rec), flush=True)
    return moe_ok


def _note(text):
    print(json.dumps({"metric": "bench-note", "value": 0, "unit": "",
                      "vs_baseline": 0, "note": text}),
          file=sys.stderr, flush=True)


def _spmd_line():
    """Run the --spmd gate in its own subprocess (it needs a virtual
    8-device CPU mesh, which must be forced before jax backend init) and
    forward its JSON lines. Failure is a note, never a run failure."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--spmd"],
            env=env, timeout=360.0, capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        _note("spmd gate: watchdog timeout")
        return
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.strip()]
    if r.returncode != 0:
        _note("spmd gate failed: "
              + (lines[-1] if lines else (r.stderr or "").strip()[-200:]))
    for ln in lines:
        try:
            json.loads(ln)
        except ValueError:
            continue
        print(ln, flush=True)


def _run_serve_child():
    """--serve mode: continuous-batching serving microbench on CPU. A
    gpt-micro GenerationServer takes a staggered mixed workload (prompt
    lengths spanning both buckets, different token budgets, greedy and
    sampled requests) after a warmup pass, and the line reports sustained
    tokens/sec plus mean batch occupancy — the serving-health pair the
    ISSUE-5 acceptance gates on. A second SHARED-PREFIX phase (ISSUE 10)
    sends 8 requests sharing one system prompt through the paged KV +
    radix prefix cache and reports prefix_hit_rate (gate: > 0.5),
    blocks-in-use high-water mark and prefill-FLOPs-saved; the
    0-post-warmup-compile and 0-failed-request gates cover BOTH phases.

    Third phase (ISSUE 12) — CHUNKED-PREFILL inter-token latency: the
    same server replays a decode stream while three near-max-length
    prompts arrive, once with chunking off and once with
    ``prefill_chunk_tokens`` toggled on (same engine, same compiled
    executables), and reports the stream's p99 inter-token gap both
    ways — the line chunking must visibly flatten.

    Fourth phase (ISSUE 12) — SPECULATIVE DECODE: a wider damped-
    residual target (memory-bound decode, the regime speculation pays
    in) plus a 1-layer layer-skip drafter run the SAME greedy+sampled
    workload on a plain server and a DraftVerifyEngine server built on
    identical target weights: tokens must be bitwise-equal, the record
    reports acceptance_rate / accepted_len_mean / spec_tokens_per_s vs
    the plain baseline, and the phase's own 0-verify-recompile and
    0-failed gates ride the existing envelope.

    Fifth phase (ISSUE 14) — PAGED KERNEL: paired single-slot decode on
    identical weights, XLA gather path vs the fused Pallas paged-
    attention kernel (compiled on TPU; the same kernel body through the
    Pallas interpreter on CPU, so the greedy-parity gate runs every
    round instead of silently skipping off-chip). Emits a dedicated
    {"metric": "serving-kernel"} line with selection, parity, tokens/s
    and p50 step-time fields.

    Sixth phase (ISSUE 16) — MESH-SHARDED KERNEL: the fused kernel
    under an mp=2 serving mesh (head-sharded weights + KV pools, the
    kernel called per-shard through shard_map) must decode token-
    bitwise vs the single-chip fused engine with zero post-warmup
    compiles/demotions/fallbacks, and an mp-sharded DraftVerifyEngine
    must stay bitwise too; the live describe_sharding() is linted for
    replicated-but-shardable pools. Emits {"metric":
    "serving-kernel-mp"}; the gate folds into the phase envelope.

    Convention matches --ratio: the telemetry line prints first, the
    {"metric": "serving"} result line stays last."""
    # CPU by DEFAULT (this is the calibrated microbench config); an
    # explicit JAX_PLATFORMS wins
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # ISSUE 18: every serving gate below (0 post-warmup compiles, 0
    # failed, spec bitwise) must hold WITH tracing + latency histograms
    # recording — the observability plane rides the bench, not a
    # separate instrumented build
    os.environ.setdefault("PADDLE_TPU_TRACE", "1")
    # the mesh-kernel phase (ISSUE 16) needs >= 2 devices; force the
    # virtual host mesh the same way --spmd does (append, don't
    # setdefault — a user-set XLA_FLAGS must keep its own flags). On a
    # real TPU the flag only touches the unused host platform.
    _sflags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _sflags:
        os.environ["XLA_FLAGS"] = (
            _sflags + " --xla_force_host_platform_device_count=8").strip()
    import time as _t

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTModel)
    from paddle_tpu.profiler import registry as _reg
    from paddle_tpu.serving import GenerationServer

    _plat = jax.default_backend()
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, n_layer=2, n_head=2, d_model=64,
                    seq_len=64, initializer_range=0.3)
    model = GPTForPretraining(GPTModel(cfg))
    # the 64 bucket exists for the chunked-prefill ITL phase's near-max
    # prompts; it compiles lazily there, not in the phase-1/2 window
    server = GenerationServer(model, max_batch_size=4,
                              buckets=(16, 32, 64), max_queue_size=32)
    server.start()
    rng = np.random.default_rng(0)

    # warmup: compile prefill for BOTH buckets + the decode step once
    for pl in (8, 20):
        server.generate(list(rng.integers(1, 128, pl)), max_new_tokens=4)

    # second weight set for the mid-flight hot-swap (ISSUE 7): same
    # architecture, different init — the swap is real but aval-identical,
    # so the gate can insist on 0 new decode compiles across it
    paddle.seed(1)
    swap_state = GPTForPretraining(GPTModel(cfg)).state_dict()
    paddle.seed(0)

    c0 = dict(_reg.counters("serving"))
    f0 = dict(_reg.counters("fastpath"))
    reqs = []
    t0 = _t.perf_counter()
    for i in range(12):
        pl = int(rng.integers(4, 30))
        reqs.append(server.submit(
            list(rng.integers(1, 128, pl)),
            max_new_tokens=int(rng.integers(8, 24)),
            temperature=0.8 if i % 3 == 0 else 0.0, seed=i))
        _t.sleep(0.01)  # staggered arrivals: admissions land mid-flight
        if i == 6:  # hot-swap lands while earlier requests still decode
            server.swap_weights(swap_state, source="bench --serve")
    for r in reqs:
        r.result(timeout=300)
    dt = _t.perf_counter() - t0
    c1 = dict(_reg.counters("serving"))

    # shared-prefix phase (ISSUE 10): 8 requests share one 16-token
    # system prompt (exactly one KV block at the default block_size), so
    # after the first admission every prefill hands the shared block
    # over by refcount instead of recomputing it — the paged cache's
    # headline win on millions-of-users traffic
    sys_prompt = list(rng.integers(1, 128, 16))
    t0p = _t.perf_counter()
    preqs = [server.submit(
        sys_prompt + list(rng.integers(1, 128, 6)),
        max_new_tokens=6, seed=100 + i) for i in range(8)]
    for r in preqs:
        r.result(timeout=300)
    dtp = _t.perf_counter() - t0p
    c2 = dict(_reg.counters("serving"))
    f2 = dict(_reg.counters("fastpath"))
    hits = c2["prefix_hits"] - c1["prefix_hits"]
    misses = c2["prefix_misses"] - c1["prefix_misses"]
    hit_tokens = c2["prefix_hit_tokens"] - c1["prefix_hit_tokens"]
    # prefill model FLOPs skipped = saved prompt tokens x fwd
    # FLOPs/token (flops_per_token is the fwd+bwd training count; fwd
    # is a third of it)
    flops_saved = hit_tokens * cfg.flops_per_token() / 3
    swap_count = server.scheduler.swap_count
    swap_err = server.scheduler.last_swap_error

    # ---- chunked-prefill inter-token-latency phase (ISSUE 12) --------
    # One decode stream runs while three near-max prompts arrive; the
    # stream's token-arrival gaps are sampled from this thread. Chunking
    # is toggled LIVE on the same scheduler (same engine, same compiled
    # executables), so the two runs differ only in interleave policy.
    def _itl_run(chunk_tokens, seed_base):
        server.scheduler.prefill_chunk_tokens = chunk_tokens
        stream = server.submit(list(rng.integers(1, 128, 6)),
                               max_new_tokens=48, seed=seed_base)
        while not stream.tokens:  # admitted and decoding
            _t.sleep(0.0005)
        arrivals = [(_t.perf_counter(), len(stream.tokens))]
        longs = []
        for i in range(3):
            longs.append(server.submit(
                list(rng.integers(1, 128, 56)), max_new_tokens=4,
                seed=seed_base + 1 + i))
        while not stream.done:
            n = len(stream.tokens)
            if n > arrivals[-1][1]:
                arrivals.append((_t.perf_counter(), n))
            _t.sleep(0.0005)
        for r in longs:
            r.result(timeout=300)
        server.scheduler.prefill_chunk_tokens = None
        gaps = sorted((b[0] - a[0]) / max(1, b[1] - a[1])
                      for a, b in zip(arrivals, arrivals[1:]))
        p99 = gaps[min(len(gaps) - 1, int(round(0.99 * (len(gaps) - 1))))]
        return p99 * 1e3, [stream] + longs

    itl_off_p99, itl_off_reqs = _itl_run(None, 400)
    itl_on_p99, itl_on_reqs = _itl_run(16, 500)
    c3 = dict(_reg.counters("serving"))
    itl_reqs = itl_off_reqs + itl_on_reqs
    server.shutdown()

    # ---- speculative-decode phase (ISSUE 12) -------------------------
    # Single-stream LATENCY mode (max_batch_size=1): a [1, 1] decode
    # step is a pure weight-streaming GEMV — the memory-bound regime a
    # TPU decode lives in, and the one speculation pays in (a [1, K+1]
    # verify reads the weights once for K+1 tokens).  The target damps
    # its later blocks' residuals so the 1-layer LAYER-SKIP drafter
    # (embeddings + block 0 + final LN copied from the target) genuinely
    # correlates — the stand-in for a distilled drafter that untrained
    # random weights cannot otherwise provide.
    def _spec_target(seed=0):
        paddle.seed(seed)
        scfg = GPTConfig(vocab_size=128, n_layer=6, n_head=4,
                         d_model=384, seq_len=128,
                         initializer_range=0.3)
        m = GPTForPretraining(GPTModel(scfg))
        for blk in m.gpt.blocks[1:]:
            for w in (blk.attn.out_proj.weight, blk.mlp.fc2.weight):
                w.set_value(w * paddle.to_tensor(np.float32(0.03)))
        return m, scfg

    def _spec_drafter(target, scfg):
        paddle.seed(1)
        dcfg = GPTConfig(vocab_size=scfg.vocab_size, n_layer=1,
                         n_head=scfg.n_head, d_model=scfg.d_model,
                         seq_len=scfg.seq_len, initializer_range=0.3)
        d = GPTForPretraining(GPTModel(dcfg))
        tsd = target.gpt.state_dict()
        for k, v in d.gpt.state_dict().items():
            if k in tsd:
                v.set_value(tsd[k])
        return d

    from paddle_tpu.serving import DraftVerifyEngine, GenerationEngine

    spec_prompt = list(rng.integers(1, 128, 10))
    SPEC_GREEDY, SPEC_SAMPLED = 60, 40

    def _spec_run(eng, spec_mode):
        step = eng.decode_step_spec if spec_mode else eng.decode_step

        def gen(n, warm=0, **kw):
            out = [eng.prefill(0, spec_prompt, **kw)]
            base = None
            while len(out) < n:
                if base is None and len(out) >= max(1, warm):
                    base = (len(out), _t.perf_counter())  # steady window
                toks = step()
                out.extend(int(x) for x in
                           (toks[0] if spec_mode else [toks[0]]))
            tps = (len(out) - base[0]) / (_t.perf_counter() - base[1])
            eng.release(0)
            return out[:n], tps

        # warmup: long enough for SEVERAL rounds per generation — the
        # first-round (host-rebuilt args), steady (chained jit outputs)
        # and post-release-rebuild argument-commitment patterns each
        # compile their own executable under jax's lowering cache, and
        # all three must be paid here, not in the timed window (a
        # 5-token warmup ran ONE round at high acceptance and leaked a
        # 1.1s compile into the measurement)
        gen(16, seed=98)
        gen(16, seed=99)
        greedy, tps = gen(SPEC_GREEDY, warm=4, seed=0)
        # counters snapshot BETWEEN legs: the reported acceptance_rate
        # must measure the temperature>0 leg alone, not be diluted by
        # the (usually easier) greedy rounds
        mid = dict(_reg.counters("serving"))
        sampled, _ = gen(SPEC_SAMPLED, warm=4, seed=1, temperature=0.8,
                         top_k=40)
        return greedy, sampled, tps, mid

    tmodel, scfg = _spec_target()
    plain_model, _ = _spec_target()
    ekw = dict(max_batch_size=1, buckets=(16,), rng_seed=7,
               block_size=8, max_seq_len=128)
    plain_greedy, plain_sampled, plain_tps, _ = _spec_run(
        GenerationEngine(plain_model, **ekw), False)
    c4 = dict(_reg.counters("serving"))
    spec_eng = DraftVerifyEngine(tmodel, _spec_drafter(tmodel, scfg),
                                 draft_k=4, **ekw)
    spec_greedy, spec_sampled, spec_tps, c4s = _spec_run(spec_eng, True)
    c5 = dict(_reg.counters("serving"))
    spec_eng.pool.audit()
    spec_eng.draft_pool.audit()
    spec_bitwise = (plain_greedy == spec_greedy
                    and plain_sampled == spec_sampled)
    # acceptance over the SAMPLED leg only (temperature 0.8)
    spec_prop = c5["spec_proposed"] - c4s["spec_proposed"]
    spec_acc = (c5["spec_accepted"] - c4s["spec_accepted"]) / spec_prop \
        if spec_prop else 0.0
    spec_sr = c5["spec_slot_rounds"] - c4s["spec_slot_rounds"]
    spec_alm = (c5["spec_emitted"] - c4s["spec_emitted"]) / spec_sr \
        if spec_sr else 0.0
    # the spec engine compiled ONE verify executable (warmup); the
    # measured window added zero
    spec_compiles = c5["verify_compiles"] - c4["verify_compiles"]

    # ---- paged-kernel phase (ISSUE 14) -------------------------------
    # Paired decode on IDENTICAL weights: the PR 9 XLA gather path vs
    # the fused Pallas paged-attention kernel. On TPU the fused engine
    # runs the compiled kernel (real tokens/s comparison); on CPU it
    # runs the SAME kernel body through the Pallas interpreter, so the
    # greedy-parity gate executes on every round instead of silently
    # skipping off-chip (the interpreter's tokens/s is reported but
    # meaningless as a speed number). Both engines are single-slot so
    # the step-time split is pure attention-path delta. The phase model
    # is TILEABLE on purpose (n_head=1 -> head_dim 64): the main serve
    # model's head_dim 32 would silently demote the on-chip pallas leg
    # to xla and make the TPU comparison vacuous.
    paddle.seed(2)
    kcfg = GPTConfig(vocab_size=128, n_layer=2, n_head=1, d_model=64,
                     seq_len=64, initializer_range=0.3)
    kmodel = GPTForPretraining(GPTModel(kcfg))

    def _kernel_run(kind, n=14):
        eng = GenerationEngine(kmodel, max_batch_size=1, buckets=(16,),
                               rng_seed=5, block_size=8,
                               paged_kernel=kind)
        kprompt = [7, 3, 11, 42, 9, 23, 5]
        eng.prefill(0, kprompt, seed=2)       # warmup compile
        for _ in range(3):
            eng.decode_step()
        eng.release(0)
        out = [eng.prefill(0, kprompt, seed=2)]
        times = []
        for _ in range(n - 1):
            t0 = _t.perf_counter()
            out.append(int(eng.decode_step()[0]))
            times.append(_t.perf_counter() - t0)
        eng.release(0)
        times.sort()
        return (out, eng.paged_kernel,
                round((n - 1) / max(sum(times), 1e-9), 1),
                round(times[len(times) // 2] * 1e3, 3))

    kx_toks, _, kx_tps, kx_p50 = _kernel_run("xla")
    kf_toks, fused_kind, kf_tps, kf_p50 = _kernel_run("pallas")
    kernel_parity = kx_toks == kf_toks

    # ---- mesh-sharded kernel phase (ISSUE 16) ------------------------
    # The fused kernel under an mp=2 serving mesh: weights and KV pools
    # head-sharded, the kernel called per-shard through shard_map.
    # Tokens must be BITWISE the single-chip fused engine's (each head's
    # softmax lives whole on one shard), the steady window must add zero
    # decode compiles / demotions / kernel fallbacks, and an mp-sharded
    # DraftVerifyEngine must stay bitwise too. The live engine's
    # describe_sharding() runs through tools/sharding_lint.py — a
    # replicated-but-shardable KV pool is the demotion this phase exists
    # to keep dead.
    mesh_ok = True
    mrec = {"metric": "serving-kernel-mp", "value": 0,
            "unit": "post-warmup compiles", "platform": _plat}
    if jax.device_count() < 2:
        mrec.update(skipped="needs >= 2 devices", vs_baseline=1.0)
    else:
        import importlib.util as _ilu

        from paddle_tpu.distributed import spmd as _spmd

        mcfg = GPTConfig(vocab_size=128, n_layer=2, n_head=2,
                         d_model=128, seq_len=64, initializer_range=0.3)

        def _mesh_model(seed=3):
            paddle.seed(seed)
            return GPTForPretraining(GPTModel(mcfg))

        mekw = dict(max_batch_size=1, buckets=(16,), rng_seed=5,
                    block_size=16)
        mprompt = [7, 3, 11, 42, 9, 23, 5]

        def _mesh_leg(mesh, n=14):
            eng = GenerationEngine(_mesh_model(), paged_kernel="pallas",
                                   mesh=mesh, **mekw)
            eng.prefill(0, mprompt, seed=2)   # warmup compile
            for _ in range(3):
                eng.decode_step()
            eng.release(0)
            mc0 = dict(_reg.counters("serving"))
            mf0 = dict(_reg.counters("fastpath"))
            out = [eng.prefill(0, mprompt, seed=2)]
            times = []
            for _ in range(n - 1):
                t0 = _t.perf_counter()
                out.append(int(eng.decode_step()[0]))
                times.append(_t.perf_counter() - t0)
            eng.release(0)
            mc1 = dict(_reg.counters("serving"))
            mf1 = dict(_reg.counters("fastpath"))
            win = {
                "decode_compiles":
                    mc1["decode_compiles"] - mc0["decode_compiles"],
                "kernel_fallbacks":
                    mc1["kernel.fallbacks"] - mc0["kernel.fallbacks"],
                "decode_demotions":
                    mf1["decode_demotions"] - mf0["decode_demotions"],
            }
            return out, eng, win, round((n - 1) / max(sum(times), 1e-9), 1)

        single_toks, _, _, single_tps = _mesh_leg(None)
        smesh = _spmd.serving_mesh(2)
        mesh_toks, mesh_eng, mwin, mesh_tps = _mesh_leg(smesh)
        mesh_parity = mesh_toks == single_toks
        mdesc = mesh_eng.describe_sharding()
        _lpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "tools", "sharding_lint.py")
        _lspec = _ilu.spec_from_file_location("sharding_lint", _lpath)
        _slint = _ilu.module_from_spec(_lspec)
        _lspec.loader.exec_module(_slint)
        mesh_lint = _slint.lint_engine(mdesc, min_bytes=0)

        # mp-sharded speculative decode: target AND drafter per-shard,
        # tokens bitwise vs the single-chip plain engine
        mplain = GenerationEngine(_mesh_model(), paged_kernel="xla",
                                  **mekw)
        mspec = DraftVerifyEngine(_mesh_model(), _mesh_model(seed=4),
                                  draft_k=3, paged_kernel="pallas",
                                  mesh=smesh, **mekw)

        def _greedy(eng, spec_mode, n=12):
            step = eng.decode_step_spec if spec_mode else eng.decode_step
            out = [eng.prefill(0, mprompt, seed=6)]
            while len(out) < n:
                toks = step()
                out.extend(int(x) for x in
                           (toks[0] if spec_mode else [toks[0]]))
            eng.release(0)
            return out[:n]

        spec_mesh_bitwise = (_greedy(mspec, True)
                             == _greedy(mplain, False))
        mstats = mspec.stats()
        mesh_ok = (mesh_parity and spec_mesh_bitwise
                   and mwin["decode_compiles"] == 0
                   and mwin["kernel_fallbacks"] == 0
                   and mwin["decode_demotions"] == 0
                   and mesh_eng.stats()["paged_kernel_sharded"]
                   and mstats["draft_kernel_sharded"]
                   and not mesh_lint)
        mrec.update({
            "value": mwin["decode_compiles"],
            "vs_baseline": 1.0 if mesh_ok else 0.0,
            "mesh_axes": mesh_eng.stats()["mesh_axes"],
            "fused_kernel": mesh_eng.paged_kernel,
            "paged_kernel_sharded":
                mesh_eng.stats()["paged_kernel_sharded"],
            "draft_kernel_sharded": mstats["draft_kernel_sharded"],
            "mesh_token_parity": mesh_parity,
            "spec_mesh_bitwise": spec_mesh_bitwise,
            "single_chip_tokens_per_s": single_tps,
            "mesh_tokens_per_s": mesh_tps,
            "post_warmup_decode_compiles": mwin["decode_compiles"],
            "post_warmup_kernel_fallbacks": mwin["kernel_fallbacks"],
            "post_warmup_decode_demotions": mwin["decode_demotions"],
            "spec_mesh_refused":
                _reg.counters("serving")["spec_mesh_refused"],
            "lint_warnings": mesh_lint,
        })
    print(json.dumps(mrec), flush=True)

    krec = {
        "metric": "serving-kernel",
        # selection: what the MAIN serving engine above resolved to
        # (auto policy), and what the fused leg of this phase ran
        "paged_kernel": server.engine.paged_kernel,
        "fused_kernel": fused_kind,
        # parity: greedy tokens must be IDENTICAL across kernels
        "kernel_parity": kernel_parity,
        "xla_tokens_per_s": kx_tps,
        "fused_tokens_per_s": kf_tps,
        "xla_p50_step_ms": kx_p50,
        "fused_p50_step_ms": kf_p50,
        "platform": _plat,
    }
    print(json.dumps(krec), flush=True)

    failed = len([r for r in reqs + preqs + itl_reqs
                  if r.status != "done"])
    tokens = sum(len(r.tokens) for r in reqs)
    steps = c1["decode_steps"] - c0["decode_steps"]
    occ = ((c1["active_slot_steps"] - c0["active_slot_steps"])
           / (steps * server.engine.max_batch_size)) if steps else 0.0
    ttft = _reg.timings("serving").get("serving.ttft", {})
    # log2 latency histograms (ISSUE 18): TTFT + inter-token p50/p99
    # from the always-mergeable fixed-bucket records — what a fleet
    # aggregates across pods, reported here from one server
    hists = _reg.histograms("serving")
    h_ttft = hists.get("serving.ttft", {})
    h_itl = hists.get("serving.inter_token", {})
    _telemetry_line()
    rec = {
        "metric": "serving",
        "value": round(tokens / dt, 1),
        "unit": "tokens/s",
        "mean_occupancy": round(occ, 4),
        "requests": len(reqs),
        "tokens": tokens,
        "ttft_ms_mean": round(ttft.get("mean_ms", 0.0), 2),
        "ttft_p50_ms": round(h_ttft.get("p50_ms", 0.0), 2),
        "ttft_p99_ms": round(h_ttft.get("p99_ms", 0.0), 2),
        "inter_token_p50_ms": round(h_itl.get("p50_ms", 0.0), 3),
        "inter_token_p99_ms": round(h_itl.get("p99_ms", 0.0), 3),
        "tracing_enabled": os.environ.get("PADDLE_TPU_TRACE") == "1",
        # train→serve loop gates (ISSUE 7): the mid-flight hot-swap must
        # land (swap_count >= 1) with ZERO failed requests and zero new
        # decode compiles (same-aval swap replays the compiled step).
        # The status scan covers error AND timeout terminals for exactly
        # this run's requests (the counter delta would double-count).
        "swap_count": swap_count,
        "failed_requests": failed,
        "swap_error": repr(swap_err) if swap_err is not None else None,
        # compile gates span the mixed, shared-prefix AND chunked-ITL
        # phases: all three must ride the exact same decode executable
        # (the spec phase below builds separate engines and gates its
        # own verify compiles)
        "decode_compiles": c3["decode_compiles"],
        "decode_compiles_after_warmup":
            c3["decode_compiles"] - c0["decode_compiles"],
        "prefill_compiles": c3["prefill_compiles"],
        # paged KV + radix prefix cache (ISSUE 10): shared-prefix phase
        # health — gate: prefix_hit_rate > 0.5 on the 8-request
        # shared-system-prompt workload
        "prefix_hit_rate":
            round(hits / (hits + misses), 4) if hits + misses else 0.0,
        "prefix_hits": hits,
        "prefix_hit_tokens": hit_tokens,
        "prefill_flops_saved": flops_saved,
        "shared_prefix_tokens_per_sec":
            round(sum(len(r.tokens) for r in preqs) / dtp, 1),
        "kv_blocks_hwm": c2["kv_blocks_hwm"],
        "kv_blocks_total": server.engine.pool.usable_blocks,
        "pool_exhausted": c2["pool_exhausted"] - c0["pool_exhausted"],
        # decode replay fast path (ISSUE 9): steady iterations run with
        # prebuilt device-side args — rebuilds only at batch boundaries
        # (admission/evict/swap), audited on the PADDLE_TPU_AUDIT_EVERY
        # cadence, zero demotions expected
        "decode_fast_steps":
            f2["decode_fast_steps"] - f0["decode_fast_steps"],
        "decode_rebuilds": f2["decode_rebuilds"] - f0["decode_rebuilds"],
        "decode_audit_runs":
            f2["decode_audit_runs"] - f0["decode_audit_runs"],
        "decode_demotions":
            f2["decode_demotions"] - f0["decode_demotions"],
        # chunked prefill (ISSUE 12): the decode stream's p99 inter-
        # token gap while near-max prompts arrive, chunking off vs on —
        # same engine, same executables, only the interleave differs.
        # The flatten ratio is the headline: > 1 means chunking cut the
        # long-prompt stall.
        "p99_inter_token_latency_ms": round(itl_off_p99, 2),
        "p99_inter_token_latency_chunked_ms": round(itl_on_p99, 2),
        "itl_flatten_x": round(itl_off_p99 / itl_on_p99, 2)
        if itl_on_p99 else 0.0,
        "prefill_chunks": c3["prefill_chunks"],
        "chunked_prefills": c3["chunked_prefills"],
        # speculative decode (ISSUE 12): same workload, plain vs draft-
        # verify on identical target weights — bitwise-equal tokens
        # (greedy AND sampled), acceptance measured at temperature > 0,
        # ONE verify executable (the warmup compile), and the tokens/s
        # ratio is the speedup gate at this damped-target config
        "spec_bitwise_equal": spec_bitwise,
        "spec_tokens_per_s": round(spec_tps, 1),
        "plain_tokens_per_s": round(plain_tps, 1),
        "spec_speedup_x": round(spec_tps / plain_tps, 3)
        if plain_tps else 0.0,
        "acceptance_rate": round(spec_acc, 4),
        "accepted_len_mean": round(spec_alm, 2),
        "acceptance_rate_greedy": round(
            (c4s["spec_accepted"] - c4["spec_accepted"])
            / max(1, c4s["spec_proposed"] - c4["spec_proposed"]), 4),
        "spec_draft_k": 4,
        "spec_verify_compiles": spec_compiles,
        # paged-kernel phase (ISSUE 14): the active kernel + the paired
        # parity gate also ride the headline record (full detail in the
        # {"metric": "serving-kernel"} line above)
        "paged_kernel": server.engine.paged_kernel,
        "kernel_parity": kernel_parity,
        "platform": _plat,
    }
    print(json.dumps(rec), flush=True)
    # ISSUE 12 envelope: zero failed, zero post-warmup decode compiles,
    # ONE verify executable, bitwise spec output, a real tokens/s
    # speedup at temperature 0, and chunking visibly flattening the p99
    # inter-token line (measured 40-107x; gate leaves CI-noise margin)
    gates_ok = (failed == 0 and spec_bitwise and spec_compiles == 1
                and rec["decode_compiles_after_warmup"] == 0
                and rec["spec_speedup_x"] > 1.0
                and rec["itl_flatten_x"] > 1.5
                and kernel_parity and mesh_ok)
    return 0 if gates_ok else 1


def _run_serve_fleet_child():
    """--serve-fleet mode (ISSUE 11): cross-process serving fleet on
    CPU. Shared-system-prompt traffic runs against (a) ONE pod, (b) a
    2-pod fleet with prefix-affinity routing, and (c) a 2-pod fleet on
    round-robin; the record gates N-pod tokens/s ≳ linear vs one pod
    (pods are separate processes, so throughput should genuinely
    scale) and prefix-affinity beating round-robin on the aggregate
    prefix_hit_rate. A mid-run fleet-wide checkpoint hot-swap rides the
    2-pod phase with the usual 0-failed / 0-new-decode-compile gates.
    Convention matches --serve: the {"metric": "serving-fleet"} result
    line prints last; exits nonzero when a hard gate fails."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    # ISSUE 18: fleet gates hold with the tracing plane on — the router
    # pins trace ids, the pods ship spans back on stats replies
    os.environ.setdefault("PADDLE_TPU_TRACE", "1")
    import tempfile
    import time as _t

    import paddle_tpu as paddle
    from paddle_tpu.incubate import checkpoint as _ckpt
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTModel)
    from paddle_tpu.serving.fleet import ServingFleet

    cfg_kw = dict(vocab_size=128, n_layer=2, n_head=2, d_model=64,
                  seq_len=64, initializer_range=0.3)
    model_spec = {"kind": "gpt", "seed": 0, "config": cfg_kw}
    engine_kw = dict(max_batch_size=4, buckets=[16, 32], block_size=16,
                     rng_seed=0)
    rng = np.random.default_rng(0)
    # realistic shared-prefix traffic: FOUR distinct 16-token system
    # prompts (one KV block each), 8 requests per prompt. Affinity pins
    # each prompt's traffic to one pod (hit rate up) while distinct
    # prompts spread across pods by load (throughput up) — a single
    # global prefix would concentrate the whole fleet onto one pod.
    sys_prompts = [[int(t) for t in rng.integers(1, 128, 16)]
                   for _ in range(4)]
    traffic = []  # interleaved across prompts, like real arrivals
    for j in range(8):
        for sp in sys_prompts:
            traffic.append(sp + [int(t) for t in rng.integers(1, 128, 6)])

    from paddle_tpu.profiler import registry as _reg

    def run_phase(pods, policy, swap_dir=None):
        # the parent-process "fleet" registry scope accumulates across
        # phases; snapshot it so the record reports THIS phase's deltas
        f0 = dict(_reg.counters("fleet"))
        fleet = ServingFleet(model_spec, pods=pods, engine=engine_kw,
                             policy=policy,
                             server={"max_queue_size": 64}).start()
        # warmup: EVERY pod must compile BOTH prefill buckets + decode
        # before the timed window, or one pod pays a bucket compile
        # mid-measurement. Round-robin the warmup deterministically
        # (load-based spreading can hand one pod only short prompts).
        fleet.router.policy = "round_robin"
        warm = []
        for pl in (8, 20):
            for i in range(pods):
                warm.append(fleet.submit(
                    [int(t) for t in rng.integers(1, 128, pl)],
                    max_new_tokens=4, seed=1000 + pl + i))
                warm[-1].result(300)
        fleet.router.policy = policy
        reqs = []
        t0 = _t.perf_counter()
        for i, prompt in enumerate(traffic):
            reqs.append(fleet.submit(prompt, max_new_tokens=8, seed=i))
        for r in reqs:
            r.result(300)
        dt = _t.perf_counter() - t0
        # fleet-wide hot-swap AFTER the timed window (its synchronous
        # checkpoint load must not pollute the scaling number) but with
        # real in-flight traffic riding across the boundary
        swap_res = None
        swap_reqs = []
        if swap_dir is not None:
            swap_reqs = [fleet.submit(traffic[i], max_new_tokens=12,
                                      seed=2000 + i) for i in range(4)]
            swap_res = fleet.swap_weights(swap_dir, timeout=120)
            for r in swap_reqs:
                r.result(300)
        st = fleet.stats()
        f1 = dict(_reg.counters("fleet"))
        failed = len([r for r in reqs + warm + swap_reqs
                      if r.status != "done"])
        tokens = sum(len(r.tokens) for r in reqs)
        fleet.shutdown()
        return {"tps": tokens / dt, "failed": failed,
                "hit_rate": st["prefix_hit_rate"], "stats": st,
                "hists": st.get("hists", {}),
                "swap": swap_res,
                "router": {k: f1[k] - f0.get(k, 0) for k in f1}}

    def run_handoff(data_plane):
        """Disagg prefill→decode fleet over one data plane, SAME
        traffic: the handoff bytes/s line that justifies the binary
        wire (ISSUE 19). Returns per-plane throughput + wire volume."""
        f0 = dict(_reg.counters("fleet"))
        fleet = ServingFleet(model_spec, roles=("prefill", "decode"),
                             engine=engine_kw, data_plane=data_plane,
                             server={"max_queue_size": 64}).start()
        warm = []
        for pl in (8, 20):
            warm.append(fleet.submit(
                [int(t) for t in rng.integers(1, 128, pl)],
                max_new_tokens=4, seed=3000 + pl))
            warm[-1].result(300)
        c0 = {p: d.get("decode_compiles")
              for p, d in fleet.stats()["pods"].items()}
        t0 = _t.perf_counter()
        reqs = [fleet.submit(prompt, max_new_tokens=8, seed=4000 + i)
                for i, prompt in enumerate(traffic)]
        for r in reqs:
            r.result(300)
        dt = _t.perf_counter() - t0
        st = fleet.stats()
        c1 = {p: d.get("decode_compiles")
              for p, d in st["pods"].items()}
        f1 = dict(_reg.counters("fleet"))
        failed = len([r for r in reqs + warm if r.status != "done"])
        tokens = sum(len(r.tokens) for r in reqs)
        fleet.shutdown()
        nbytes = f1.get("handoff_bytes", 0) - f0.get("handoff_bytes", 0)
        return {"tps": tokens / dt, "dt": dt, "failed": failed,
                "bytes": nbytes, "bytes_per_s": nbytes / dt,
                "binary": (f1.get("handoffs_binary", 0)
                           - f0.get("handoffs_binary", 0)),
                "fallback": (f1.get("handoffs_fallback", 0)
                             - f0.get("handoffs_fallback", 0)),
                "zero_recompile": c1 == c0,
                "wire_retries": st.get("data_plane", {})
                .get("tx_retries", 0)}

    one = run_phase(1, "prefix")
    paddle.seed(1)
    swap_sd = {k: np.asarray(v.numpy())
               for k, v in GPTForPretraining(
                   GPTModel(GPTConfig(**cfg_kw))).gpt.state_dict().items()}
    with tempfile.TemporaryDirectory() as d:
        _ckpt.save_checkpoint(d, {"model": swap_sd}, step=1)
        aff = run_phase(2, "prefix", swap_dir=d)
    rr = run_phase(2, "round_robin")
    hand_bin = run_handoff("binary")
    hand_json = run_handoff("json")

    scaling = aff["tps"] / one["tps"] if one["tps"] else 0.0
    swap_pods_ok = aff["swap"] is not None and all(
        r is not None and r.get("swap_error") is None
        and r.get("applied_step", -1) >= 1
        for r in aff["swap"].values())
    # the decode step compiled exactly once per pod (warmup) and the
    # fleet swap added ZERO — the per-replica zero-recompile contract
    # holding across the fleet
    swap_zero_recompile = all(
        d.get("decode_compiles") == 1
        for d in aff["stats"]["pods"].values())
    # "≳ linear": 2 separate pod processes should scale ~2x on this
    # traffic; the gate is deliberately below 2.0 to absorb CI-box
    # core contention without letting sub-linear regressions hide
    # the binary plane must carry EVERY handoff (no silent JSON
    # fallback), drop no requests, and add no post-warmup compiles —
    # the bytes/s comparison is only honest if both planes went clean
    handoff_ok = (hand_bin["failed"] == 0 and hand_json["failed"] == 0
                  and hand_bin["fallback"] == 0
                  and hand_bin["binary"] >= len(traffic)
                  and hand_bin["zero_recompile"]
                  and hand_json["zero_recompile"])
    # the ≥1.4x scaling gate needs cores for 2 pod processes + the
    # router to actually run in parallel; on a 1-2 core box the number
    # is a statement about the box, not a regression — report it as
    # not measurable (scaling_degraded), don't fail it
    scaling_measurable = (os.cpu_count() or 1) >= 3
    gates_ok = (one["failed"] == 0 and aff["failed"] == 0
                and rr["failed"] == 0
                and (scaling >= 1.4 or not scaling_measurable)
                and aff["hit_rate"] > rr["hit_rate"]
                and swap_pods_ok and swap_zero_recompile
                and handoff_ok)
    _telemetry_line()
    rec = {
        "metric": "serving-fleet",
        "value": round(aff["tps"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(scaling / 2.0, 4),
        "pods": 2,
        "tokens_per_sec_1pod": round(one["tps"], 1),
        "scaling_x": round(scaling, 2),
        "scaling_gate": 1.4,
        "scaling_degraded": not scaling_measurable,
        # prefix-affinity routing must beat round-robin on the same
        # shared-system-prompt traffic (the router's reason to exist)
        "prefix_hit_rate_affinity": round(aff["hit_rate"], 4),
        "prefix_hit_rate_round_robin": round(rr["hit_rate"], 4),
        "affinity_router_hits": aff["router"]["affinity_hits"],
        # fleet-wide swap gates (ISSUE 11): landed on every pod at its
        # decode boundary with zero failed requests and zero new decode
        # compiles (per-pod counts stay at the single warmup compile)
        "fleet_swap_applied": swap_pods_ok,
        "swap_zero_recompile": swap_zero_recompile,
        "failed_requests": one["failed"] + aff["failed"] + rr["failed"],
        "pod_decode_compiles": {
            str(p): d.get("decode_compiles")
            for p, d in aff["stats"]["pods"].items()},
        "orphans_replayed": aff["router"].get("orphans_replayed", 0),
        # fleet-aggregated latency histograms (ISSUE 18): log2 buckets
        # merged across both pods' stats replies — the operator's TTFT /
        # inter-token health line for the whole fleet
        "ttft_p50_ms": round(
            aff["hists"].get("serving.ttft", {}).get("p50_ms", 0.0), 2),
        "ttft_p99_ms": round(
            aff["hists"].get("serving.ttft", {}).get("p99_ms", 0.0), 2),
        "inter_token_p50_ms": round(
            aff["hists"].get("serving.inter_token", {})
            .get("p50_ms", 0.0), 3),
        "inter_token_p99_ms": round(
            aff["hists"].get("serving.inter_token", {})
            .get("p99_ms", 0.0), 3),
        "tracing_enabled": os.environ.get("PADDLE_TPU_TRACE") == "1",
        # pods×hosts scaling line + the KV-handoff wire rate, binary
        # frames vs the old JSON/base64 control-channel hop on the SAME
        # disagg traffic (ISSUE 19)
        "pods_x_hosts": "2x1",
        "handoff_bytes_per_s_binary": round(hand_bin["bytes_per_s"], 1),
        "handoff_bytes_per_s_json": round(hand_json["bytes_per_s"], 1),
        "handoff_wire_bytes_binary": hand_bin["bytes"],
        "handoff_wire_bytes_json": hand_json["bytes"],
        "handoff_json_overhead_x": round(
            hand_json["bytes"] / hand_bin["bytes"], 3)
        if hand_bin["bytes"] else 0.0,
        "disagg_tokens_per_sec_binary": round(hand_bin["tps"], 1),
        "disagg_tokens_per_sec_json": round(hand_json["tps"], 1),
        "handoffs_binary": hand_bin["binary"],
        "handoffs_fallback": hand_bin["fallback"],
        "handoff_gates_ok": handoff_ok,
        "gates_ok": gates_ok,
        "platform": "cpu",
    }
    print(json.dumps(rec), flush=True)
    return 0 if gates_ok else 1


def _run_child(preset, batch, seq, policy="full"):
    """--run mode: execute one config and print its JSON lines
    (telemetry first, the metric record last)."""
    tps, mfu, loss, dev = run(preset, int(batch), int(seq), policy=policy)
    import jax

    _telemetry_line()
    rec = {
        "metric": f"GPT({preset}) train tokens/sec/chip "
                  f"(bf16, seq{seq}, bs{batch}, remat={policy})",
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "mfu": round(mfu, 4),
        "loss": round(loss, 4),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }
    print(json.dumps(rec), flush=True)
    return 0


def _attempt(cfg):
    """Run one config in a child process, forwarding its JSON lines.
    Returns the metric record; any failure — non-zero exit, timeout,
    unparseable output — raises SystemExit with the child's last words,
    so a child failure fails the run."""
    preset, batch, seq, policy = cfg[:4]
    env = dict(os.environ)
    if len(cfg) > 4:
        env.update(cfg[4])
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--run",
             preset, str(batch), str(seq), policy],
            env=env, timeout=CHILD_TIMEOUT, capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        raise SystemExit(f"{cfg}: no result after {CHILD_TIMEOUT:.0f}s")
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.strip()]
    if r.returncode != 0 or not lines:
        raise SystemExit(f"{cfg}: child exited {r.returncode}: "
                         + (r.stderr or r.stdout).strip()[-600:])
    for ln in lines:
        print(ln, flush=True)
    try:
        return json.loads(lines[-1])
    except ValueError:
        raise SystemExit(f"{cfg}: unparseable result {lines[-1][-200:]!r}")


def main():
    modes = {"--run": lambda: _run_child(*sys.argv[2:6]),
             "--ratio": _run_ratio_child, "--spmd": _run_spmd_child,
             "--serve": _run_serve_child,
             "--serve-fleet": _run_serve_fleet_child}
    if len(sys.argv) > 1:
        if sys.argv[1] not in modes:
            raise SystemExit(f"unknown mode {sys.argv[1]!r}; expected one "
                             f"of {sorted(modes)} or no argument")
        return modes[sys.argv[1]]()

    # the first child finds the platform: off-chip it exits non-zero
    # naming what JAX found, and that one line is the whole run
    results = [_attempt(cfg) for cfg in TPU_CONFIGS]
    best = max(results, key=lambda r: r["mfu"])
    print(json.dumps({**best, "best": True}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
