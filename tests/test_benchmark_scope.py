"""benchmark/scope_reduce.py and benchmark/kernel_counts.py (PR 27): the
reduction from a trace to the program's stable names is pinned on recorded
stretches of chip traces, the operation and byte counts on the cells' own
sizes, and every per-layer entry of BENCHMARK.json on a reader file."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")


@pytest.fixture(scope="module")
def bench_path():
    sys.path.insert(0, BENCH)
    yield
    sys.path.remove(BENCH)


def _cfg_json(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _cfg(name):
    """A configuration's sizes, as its family reads them."""
    import families

    cfg_json = _cfg_json(name)
    return families.of(cfg_json).sizes(cfg_json)


def test_selfcheck_on_the_recorded_stretches(bench_path):
    import scope_reduce

    assert scope_reduce.selfcheck() == 0
    with open(os.path.join(BENCH, "testdata", "scope_expected.json")) as f:
        cases = {c["file"]: c for c in json.load(f)["cases"]}
    train = cases["gpt2m_train_named_step.xplane.pb.gz"]
    # the cut steps at both ends are left out: one whole step, 24 layers
    assert train["modules"] == {"train_step": {
        "n": 1, "seconds": pytest.approx(0.2094, abs=1e-3)}}
    assert {k: v["n"] for k, v in train["kernels"].items()} == {
        "flash_fwd": 24, "flash_bwd_dq": 24, "flash_bwd_dkv": 24}
    serve = cases["gpt3_1p3b_serve_decode_step.xplane.pb.gz"]
    assert serve["modules"]["serving_decode"]["n"] == 1
    assert serve["kernels"]["paged_attention"]["n"] == 24
    # the gap under a span that began before the cut is not counted; the
    # one after the whole step lies under the host's wait for its tokens
    assert serve["idle"]["by_span"] == [
        ["serving.decode_sync", serve["idle"]["seconds"]]]


@pytest.mark.parametrize("event, want", [
    ("%flash_fwd.45 = (bf16[128,1024,64]{2,1,0}, f32[128,1024,1]{2,1,0}) "
     "custom-call(bf16[128,1024,64]{2,1,0} %bitcast.3142)", "flash_fwd"),
    ("%paged_attention = bf16[32,1,32,64]{3,2,1,0} custom-call(s32[32,128]"
     "{1,0} %copy-done.117)", "paged_attention"),
    ("%custom-call.498 = bf16[1024,3072]{1,0} custom-call(bf16[256,3072]"
     "{1,0} %slice-done.1928)", None),
    ("%fusion.51 = bf16[100696064]{0} fusion(bf16[100696064]{0} %x), "
     "kind=kLoop", None),
])
def test_kernel_name_of_an_op_event(bench_path, event, want):
    import scope_reduce

    assert scope_reduce.kernel_name(event) == want


def test_module_name_of_a_module_event(bench_path):
    import scope_reduce

    assert scope_reduce.module_name(
        "jit_train_step(13317005092219709454)") == "train_step"
    assert scope_reduce.module_name("jit_serving_decode") == "serving_decode"


def test_names_read_are_names_the_program_writes(bench_path):
    """The benchmark imports nothing from the program's table; this is
    where the two are held together."""
    from paddle_tpu.profiler import spans

    for cell in ("gpt2m_train_named_step", "gpt3_1p3b_serve_decode_step"):
        import scope_reduce

        red = scope_reduce.reduce(os.path.join(
            BENCH, "testdata", cell + ".xplane.pb.gz"))
        assert set(red["modules"]) <= set(spans.EXECUTABLES)
        assert set(red["kernels"]) <= set(spans.KERNELS)
        assert {n for n, _ in red["idle"]["by_span"]} \
            <= set(spans.SPANS) | {"unattributed"}
    import step_timeline

    # the collector's span is step_timeline's to read, from every thread
    assert all(n.startswith(scope_reduce.PROGRAM_SPANS)
               for n in set(spans.SPANS) - {step_timeline.GC_SPAN})
    assert {step_timeline.SERVER_SPAN, step_timeline.WAIT_SPAN,
            step_timeline.GC_SPAN} \
        | {span for _, span in step_timeline.CALLS.values()} \
        | {step_timeline.PREFIX + n for n in step_timeline.HOST_STEP
           + step_timeline.HOST_ADMIT if n != step_timeline.GC_SPAN} \
        <= set(spans.SPANS)
    assert set(step_timeline.CALLS) <= set(spans.EXECUTABLES)
    # what the xing4 family's readers look for (no recorded stretch yet)
    assert "mla_paged_attention" in spans.KERNELS
    assert {"serving.moe_layer_steps", "serving.moe_routed_rows",
            "serving.moe_experts_hit", "serving.kv_tokens_read",
            "serving.prefill_block_writes"} <= set(spans.COUNTERS)
    assert {"hc_mix", "mla_absorb", "moe_router", "moe_experts"} \
        <= set(spans.SCOPES)


def test_step_timeline_selfcheck_on_the_recorded_stretch(bench_path):
    """A cut of the chat cell's chip trace with PR 39's spans: a few steady
    steps and one admission with its rebuild reduce to the recorded classes,
    which sum to scope_reduce's idle seconds of the same stretch, and every
    class is a name the program writes."""
    import scope_reduce
    import step_timeline
    from paddle_tpu.profiler import spans

    assert step_timeline.selfcheck() == 0
    with open(os.path.join(BENCH, "testdata", "step_expected.json")) as f:
        (case,) = json.load(f)["cases"]
    classes = dict(case["by_class"])
    assert sum(classes.values()) == pytest.approx(case["idle_s"], abs=1e-9)
    whole = scope_reduce.reduce(os.path.join(BENCH, "testdata",
                                             case["file"]))["idle"]
    assert case["idle_s"] == pytest.approx(whole["seconds"], rel=0.01)
    # the rule this reader replaces gives the wait over a quarter of it;
    # here the wait keeps only what lies under the calls the cut's ends left out
    by_span = dict(whole["by_span"])
    assert by_span["serving.decode_sync"] > 0.25 * whole["seconds"]
    assert classes["decode_sync"] < 0.03 * case["idle_s"]
    assert case["left_out"] == 2
    assert {"launch", "return", "decode_prepare", "decode_finish", "emit",
            "admit_check", "admit_blocks", "admit_stage",
            "admit_install"} <= set(classes)
    assert {step_timeline.PREFIX + n for n in classes} \
        - {step_timeline.PREFIX + n
           for n in ("launch", "return", "device", "unattributed",
                     step_timeline.GC_SPAN)} <= set(spans.SPANS)
    assert case["calls"]["decode"]["n"] >= 3
    assert case["calls"]["prefill"]["n"] >= 1
    assert case["clock"]["evidence"] == "runtime"
    assert not case["clock"]["contradicted"]


def test_step_timeline_splits_a_hand_built_timeline_to_the_nanosecond(
        bench_path):
    """Five gaps of chip 0 — one before a decode's first op, one straddling
    `serving.decode_sync`'s end, one under no span of the server thread (a
    collection of another thread's inside it), one inside
    `serving.admit_stage`, one after a prefill — cut
    at the server thread's span boundaries and given piece by piece; the
    device's clock 500 ns early, found from the runtime's two host events.
    scope_reduce's rule gives the first gap whole to the wait."""
    import scope_reduce
    import step_timeline

    server = [
        ("serving.sched_step", 10_000, 131_000),
        ("serving.decode_prepare", 11_000, 15_000),
        ("serving.decode_step", 16_000, 120_000),
        ("serving.decode_sync", 26_000, 119_000),
        ("serving.decode_finish", 121_000, 126_000),
        ("serving.emit", 127_000, 130_000),
        ("serving.sched_step", 211_000, 500_000),
        ("serving.admit", 212_000, 420_000),
        ("serving.admit_blocks", 213_000, 220_000),
        ("serving.admit_stage", 221_000, 250_000),
        ("serving.prefill", 251_000, 380_000),
        ("serving.admit_install", 381_000, 400_000),
        ("serving.admit_install", 401_000, 419_000),
        ("serving.decode_prepare", 421_000, 430_000),
        ("serving.decode_step", 431_000, 499_000),  # its event: after the cut
    ]
    runtime = [("DoEnqueueProgram", 30_000, 31_000),
               ("ReadSyncFlag", 100_000, 101_000),
               ("tpu::System::Execute=>Done", 103_000, 104_000),
               ("DoEnqueueProgram", 260_000, 261_000),
               ("ReadSyncFlag", 370_000, 371_000)]
    other = [("host.gc", 150_000, 160_000), ("bench.client_wait", 0, 600_000)]
    host_lines = [[(s, e, n) for n, s, e in line]
                  for line in (server, runtime, other)]
    early = 500  # the device's events, on the host's clock, less this
    ops = [(5_000, 8_000, "%earlier"),         # gap: up to the first op
           (30_000, 100_000, "%decode"),       # gap: the wait's end inside
           (123_000, 135_000, "%other.1"),     # gap: between two iterations
           (205_000, 230_000, "%other.2"),     # gap: inside admit_stage
           (245_000, 260_000, "%threefry"),
           (260_000, 370_000, "%prefill"),     # gap: the prefill's return
           (395_000, 440_000, "%other.3"),
           (440_000, 450_000, "%decode")]      # cut by the trace's end
    modules = [(30_000, 100_000, "serving_decode"),
               (245_000, 250_000, "_threefry_fold_in"),
               (260_000, 370_000, "serving_prefill"),
               (440_000, 450_000, "serving_decode")]
    red = step_timeline.timeline(
        [(s - early, e - early, n) for s, e, n in ops],
        [(s - early, e - early, n) for s, e, n in modules], host_lines)
    assert red["clock"] == {"device_late_us": 0.5, "min_us": 0.5,
                            "max_us": 0.5, "slack_us": 0.0,
                            "evidence": "runtime", "contradicted": 0}
    want = {"launch": 14_000, "decode_prepare": 4_000,
            "return": 19_000 + 10_000,    # decode's, then the prefill's
            "decode_step": 1_000,         # the wait's end to the call's
            "sched_step": 3_000,          # its own: between two children
            "decode_finish": 2_000,
            "unattributed": 2_000 + 60_000, "host.gc": 10_000,
            "admit_stage": 15_000,
            "admit": 1_000, "admit_install": 14_000}
    assert {n: round(s * 1e9) for n, s in red["by_class"]} == want
    assert round(red["idle_s"] * 1e9) == sum(want.values()) \
        == 22_000 + 23_000 + 70_000 + 15_000 + 25_000
    assert red["steps"] == 2 and red["left_out"] == 1
    assert red["calls"]["decode"] == {
        "n": 1, "dispatch_us": {"mean": 10.0, "p95": 10.0},
        "launch_us": {"mean": 14.0, "p95": 14.0},
        "return_us": {"mean": 19.0, "p95": 19.0}}
    assert red["calls"]["prefill"] == {
        "n": 1, "launch_us": {"mean": 9.0, "p95": 9.0},
        "return_us": {"mean": 10.0, "p95": 10.0},
        "turnaround_us": {"mean": 132.0, "p95": 132.0}}
    # the readers' sums, a step
    assert step_timeline.seconds(red, *step_timeline.HOST_STEP) * 1e9 \
        == pytest.approx(19_000)
    assert step_timeline.seconds(red, *step_timeline.HOST_ADMIT) * 1e9 \
        == pytest.approx(30_000)
    # the rule this reader replaces: the whole first gap to the wait
    spans = [sp for line in host_lines for sp in line]
    assert scope_reduce._innermost(spans, (100_000 - early + 123_000 - early)
                                   // 2) == "serving.decode_sync"
    # no server thread in the trace, nothing to say
    assert step_timeline.timeline(ops, modules, host_lines[1:]) is None
    # without the runtime's events the calls' spans bound the clock alone
    loose = step_timeline.timeline(
        [(s - early, e - early, n) for s, e, n in ops],
        [(s - early, e - early, n) for s, e, n in modules],
        [host_lines[0], host_lines[2]])
    assert loose["clock"]["evidence"] == "spans"
    # (the prefill's call begins 8.5 us before its first device event and
    # ends 10.5 us after its last)
    assert loose["clock"]["slack_us"] == pytest.approx((10_500 + 8_500)
                                                       / 2e3)
    assert loose["idle_s"] == red["idle_s"]


def test_step_timeline_follows_a_step_of_the_devices_clock(bench_path):
    """The device's clock against the host's steps inside a chip trace (by
    0.13-0.2 ms about a second after its start, PR 39): every call takes
    the offset its neighbours within the window bound, so the same lags
    read the same before and after the step."""
    import step_timeline

    def iteration(t, early):
        """One steady iteration at host time t: launch 14, return 19 us."""
        server = [("serving.sched_step", t, t + 125_000),
                  ("serving.decode_prepare", t + 1_000, t + 5_000),
                  ("serving.decode_step", t + 6_000, t + 110_000),
                  ("serving.decode_sync", t + 16_000, t + 109_000),
                  ("serving.decode_finish", t + 111_000, t + 120_000),
                  ("serving.emit", t + 121_000, t + 124_000)]
        runtime = [("DoEnqueueProgram", t + 20_000, t + 21_000),
                   ("ReadSyncFlag", t + 90_000, t + 91_000)]
        op = (t + 20_000 - early, t + 90_000 - early)
        return server, runtime, op

    step = 2 * step_timeline.CLOCK_WINDOW_NS
    parts = [iteration(10_000 + k * 130_000, 500) for k in range(3)] \
        + [iteration(10_000 + step + k * 130_000, 300) for k in range(3)]
    host_lines = [[(s, e, n) for srv, _, _ in parts for n, s, e in srv],
                  [(s, e, n) for _, rt, _ in parts for n, s, e in rt]]
    ops = [(0, 1_000, "%first")] + [op + ("%decode",) for _, _, op in parts] \
        + [(parts[-1][2][1] + 30_000, parts[-1][2][1] + 31_000, "%last")]
    modules = [op + ("serving_decode",) for _, _, op in parts]
    red = step_timeline.timeline(ops, modules, host_lines)
    assert red["clock"]["min_us"] == 0.3 and red["clock"]["max_us"] == 0.5
    assert red["clock"]["slack_us"] == 0.0
    assert red["calls"]["decode"]["launch_us"] == {"mean": 14.0, "p95": 14.0}
    assert red["calls"]["decode"]["return_us"] == {"mean": 19.0, "p95": 19.0}
    classes = {n: round(s * 1e9) for n, s in red["by_class"]}
    assert classes["launch"] == 6 * 14_000 and classes["return"] == 6 * 19_000
    # one offset for the whole trace would have read 0.1 us of each call's
    # return as launch, or the other way round
    assert red["steps"] == 6 and red["left_out"] == 0


def test_step_timeline_reads_nested_spans_innermost_first(bench_path):
    import step_timeline

    segs = step_timeline.innermost_segments([
        (0, 100, "a"), (10, 40, "b"), (20, 30, "c"), (40, 60, "d"),
        (200, 300, "e")])
    assert segs == [(0, 10, "a"), (10, 20, "b"), (20, 30, "c"),
                    (30, 40, "b"), (40, 60, "d"), (60, 100, "a"),
                    (200, 300, "e")]


def test_counts_at_the_xing4_cells_sizes(bench_path):
    """ISSUE 30's hand numbers for the cut that is run (1 dense + 5 expert
    layers, all 64 experts, the whole vocabulary)."""
    import families

    c = _cfg_json("xing4.0-29b-a4b")
    fam = families.of(c)
    p = fam.param_counts(c)
    assert p["attn"] == pytest.approx(28.41e6, rel=1e-3)
    assert p["hc"] == pytest.approx(0.72e6, rel=1e-2)
    assert p["expert"] == 3 * 3584 * 1024 and p["shared"] == p["expert"]
    assert p["total"] == pytest.approx(4.793e9, rel=1e-3)
    assert fam.vocab_size(c) == 131072 and fam.criterion() is None
    assert fam.sizes(c)["kv_lora_rank"] == 512
    counters = {"serving.decode_steps": 10, "serving.active_slot_steps": 320,
                "serving.kv_tokens_read": 600000,
                "serving.moe_layer_steps": 50,
                "serving.moe_experts_hit": 2775}
    run = {"cfg": c, "counters": counters}
    flops, nbytes = fam.decode_step_work(run)
    # 1.020 B active parameters a token; 55.5 of 64 experts hit: ~7.7 GB of
    # weights + 60,000 rows x 1,152 B x 6 layers
    assert flops == pytest.approx(2 * 1.020e9 * 32 + 69632 * 60000 * 6,
                                  rel=1e-3)
    assert nbytes == pytest.approx(7.71e9 + 60000 * 1152 * 6, rel=5e-3)
    assert fam.kernel_work(run, "mla_paged_attention") \
        == (69632 * 60000, 1152 * 60000)
    assert fam.kernel_work(run, "paged_attention") is None
    assert fam.train_flops_per_token(run) is None
    assert fam.decode_step_work({"cfg": c, "counters": {}}) is None


def test_counts_at_the_cells_sizes(bench_path):
    import kernel_counts as kc

    m, x = _cfg("gpt2-medium"), _cfg("gpt3-1.3b")
    # PERF.md's hand numbers: 0.41 TFLOP of causal forward a step
    assert 24 * kc.flash_fwd_flops(m, 8) == 2 * 8 * 1024 ** 2 * 1024 * 24
    assert kc.flash_bwd_flops(m, 8) == 2.5 * kc.flash_fwd_flops(m, 8)
    assert kc.flash_bwd_bytes(m, 8) == 2 * kc.flash_fwd_bytes(m, 8)
    assert kc.gpt_matmul_params(x) == 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192) \
        + 50304 * 2048
    assert kc.kv_row_bytes(x) == 8192
    assert kc.decode_step_bytes(x, 0) == 2 * kc.gpt_matmul_params(x)
    assert kc.decode_step_flops(x, 32, 1000) \
        == 64 * kc.gpt_matmul_params(x) + 4 * 2048 * 24 * 1000
    v5e = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    sec, bound = kc.least_seconds(kc.flash_fwd_flops(m, 8),
                                  kc.flash_fwd_bytes(m, 8), v5e)
    assert bound == "flops" and sec == pytest.approx(8.72e-5, rel=1e-2)
    sec, bound = kc.least_seconds(kc.decode_step_flops(x, 32, 20000),
                                  kc.decode_step_bytes(x, 20000), v5e)
    assert bound == "bytes" and sec == pytest.approx(8.0e-3, rel=1e-2)
    assert kc.decode_step_means({"serving.decode_steps": 4}) is None
    assert kc.decode_step_means({
        "serving.decode_steps": 4, "serving.active_slot_steps": 12,
        "serving.kv_tokens_read": 400}) == (3.0, 100.0)


def test_every_per_layer_entry_has_its_reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
    sys.path.insert(0, BENCH)
    try:
        import run as bench_run

        drivers = {}  # cell -> its driver and the one it samples as
        for w in bm["workloads"]:
            with open(os.path.join(BENCH, "traffic",
                                   w["traffic"] + ".json")) as f:
                name = json.load(f)["driver"]
            mod = bench_run.load_module("drivers", name + ".py")
            drivers[w["name"]] = {name, getattr(mod, "SAMPLES_AS", name)}
        for entry in bm["per_layer"]:
            mod = bench_run.load_module("layer_metrics",
                                        entry["name"] + ".py")
            for key in ("name", "unit", "better", "source", "layer",
                        "moves"):
                assert mod.META[key] == entry[key], (entry["name"], key)
            for w in entry["workloads"]:
                assert drivers[w] & set(mod.META["drivers"]), \
                    (entry["name"], w)
    finally:
        sys.path.remove(BENCH)


def test_prompt_block_write_share_reads_a_toy_engines_counters(bench_path):
    """`kv.prompt_block_write_share.serve` on the window deltas of a toy
    gpt engine's counters, taken as run.py takes them: 100 where every
    bucket holds a block or more, and None where none does, on a program
    without the counter (the parent of the block write) and in a window
    without a prefill."""
    import numpy as np

    import run as bench_run
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining, GPTModel
    from paddle_tpu.profiler import registry
    from paddle_tpu.serving import GenerationEngine

    share = bench_run.load_module(
        "layer_metrics", "kv.prompt_block_write_share.serve.py").read
    rng = np.random.default_rng(2)
    for buckets, bs, want in (((16, 32), 4, 100.0), ((8, 24), 32, None)):
        model = GPTForPretraining(GPTModel(GPTConfig(
            vocab_size=64, n_layer=1, n_head=2, d_model=32, seq_len=64)))
        eng = GenerationEngine(model, max_batch_size=1, buckets=buckets,
                               block_size=bs)
        c0 = registry.counters()
        for n in (5, 19):
            eng.prefill(0, [int(x) for x in rng.integers(1, 64, n)],
                        max_new_tokens=2)
            eng.release(0)
        c1 = registry.counters()
        counters = {k: c1[k] - c0.get(k, 0) for k in c1
                    if isinstance(c1[k], (int, float))}
        assert counters["serving.prefill_n"] == 2
        assert share({"counters": counters}) == want
    assert counters["serving.prefill_block_writes"] == 0
    counters.pop("serving.prefill_block_writes")
    assert share({"counters": counters}) is None
    assert share({"counters": {"serving.prefill_n": 0,
                               "serving.prefill_block_writes": 0}}) is None


def _rehearse(cell, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         cell, "--seed", str(seed), "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"]
    return out["metrics"]


def test_rehearsal_reads_the_counter_metrics_and_no_device_metric():
    got = _rehearse("rehearse_serve", 7)
    for name in ("serve.decode_call_ms", "serve.sched_self_ms",
                 "serve.prefill_call_ms", "serve.queue_wait_ms",
                 "serve.loop_idle_share", "serve.itl_p99_ms",
                 "serve.step_host_ms", "serve.admit_host_ms"):
        assert got[name]["value"] >= 0, name
    # every bucket of the toy cell is a prompt span: written as blocks
    assert got["kv.prompt_block_write_share.serve"]["value"] == 100
    # the iteration's host time without the admissions' is the smaller
    assert got["serve.step_host_ms"]["value"] \
        < got["serve.sched_self_ms"]["value"]
    # no device, so nothing is written under a device metric's name
    for name in ("serve.decode_step_mfu", "serve.decode_step_roofline",
                 "kernel.paged_attn_roofline.serve",
                 "device.idle_attributed_share.serve",
                 "serve.idle_launch_ms", "serve.idle_return_ms",
                 "serve.idle_host_step_ms", "serve.idle_host_admit_ms"):
        assert name not in got


def test_rehearsal_of_the_xing4_family_reads_its_counter_metric():
    """The second family end to end on the CPU: the latent cache, the
    dropless experts and the four-stream residual under the same driver,
    `correct` against reference/xing4.py, the expert counter read, and no
    number under a device metric's name (a seed over 2**31, as the
    driver's are)."""
    got = _rehearse("rehearse_serve_xing4", 3000000030)
    assert 0 < got["moe.experts_hit_share.serve"]["value"] <= 100
    assert got["serve.decode_call_ms"]["value"] >= 0
    for name in ("serve.decode_step_mfu", "serve.decode_step_roofline",
                 # the latent rows are written in the decoder's own forward
                 "kv.prompt_block_write_share.serve",
                 "kernel.mla_paged_attn_roofline.serve",
                 "kernel.paged_attn_roofline.serve",
                 "device.idle_attributed_share.serve"):
        assert name not in got
