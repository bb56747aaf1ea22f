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
    assert all(n.startswith(scope_reduce.PROGRAM_SPANS)
               for n in spans.SPANS)
    # what the xing4 family's readers look for (no recorded stretch yet)
    assert "mla_paged_attention" in spans.KERNELS
    assert {"serving.moe_layer_steps", "serving.moe_routed_rows",
            "serving.moe_experts_hit", "serving.kv_tokens_read"} \
        <= set(spans.COUNTERS)
    assert {"hc_mix", "mla_absorb", "moe_router", "moe_experts"} \
        <= set(spans.SCOPES)


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
                 "serve.loop_idle_share", "serve.itl_p99_ms"):
        assert got[name]["value"] >= 0, name
    # no device, so nothing is written under a device metric's name
    for name in ("serve.decode_step_mfu", "serve.decode_step_roofline",
                 "kernel.paged_attn_roofline.serve",
                 "device.idle_attributed_share.serve"):
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
                 "kernel.mla_paged_attn_roofline.serve",
                 "kernel.paged_attn_roofline.serve",
                 "device.idle_attributed_share.serve"):
        assert name not in got
