"""The `cohere2_moe` family and the cell it brings (PR 35), added as files:
configs/command-a-plus-05-2026.json, families/cohere2_moe.py,
reference/cohere2_moe.py, traffic/mixedlen_closed_c32.json,
workloads/commanda_plus_serve_mixedlen_closed.json, two readers and the
rehearsal. Run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

- the configuration file holds every number of the published config but the
  three it says it reduced, and its sizes give 4,733 M parameters;
- the family's least work on a hand-worked step;
- the two new readers on hand-made counters and kernel events, and silent
  where a program keeps no such counter or kernel;
- the rehearsal cell runs on the CPU with --trace 1, `correct` true against
  the reference, and reports the counter metrics.
"""
import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
REPO = os.path.dirname(BENCH)
CELL = "commanda_plus_serve_mixedlen_closed"


@pytest.fixture(scope="module")
def bench_path():
    sys.path[:0] = [REPO, BENCH]
    yield
    sys.path.remove(BENCH)
    sys.path.remove(REPO)


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _run(counters, kernels=None):
    said = []
    run = {"cfg": _json("configs", "command-a-plus-05-2026.json"),
           "wl": _json("workloads", CELL + ".json"),
           "traffic": _json("traffic", "mixedlen_closed_c32.json"),
           "peaks": _json("peaks.json"), "device_kind": "TPU v5 lite",
           "counters": counters, "say": said.append, "said": said}
    if kernels is not None:
        run["scope_reduce"] = {"kernels": kernels, "modules": {}}
    return run


# a step of 32 slots: 150,000 rows in the full layer, 115,000 in each window
# layer, 56 of the 64 held experts hit
STEP = {"serving.decode_steps": 10, "serving.active_slot_steps": 320,
        "serving.kv_tokens_read": 1_500_000,
        "serving.kv_window_rows_read": 1_150_000,
        "serving.moe_layer_steps": 40, "serving.moe_experts_hit": 560}


def test_the_file_is_the_published_config_cut_as_it_says():
    cfg = _json("configs", "command-a-plus-05-2026.json")
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f
                       if '"command-a-plus-05-2026"' in l)
        differs = sorted(k for k, v in row["config"].items()
                         if cfg.get(k) != v)
        assert differs == sorted(cfg["reduced"]), differs
        assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                                "vocab_size": 262144}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"], cfg["n_routed_experts"]) == (4, 16, 32768, 16)
    # no width differs: every width of the published config as it is
    for k in ("hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads",
              "num_experts_per_tok", "num_shared_experts", "sliding_window"):
        assert k not in cfg["reduced"]
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    for key in ("assumed", "changed", "deployment", "left_out", "reference"):
        assert cfg[key]
    bm = _json("..", "BENCHMARK.json")
    entry = next(c for c in bm["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] \
        and entry["source"] == cfg["source"] \
        and entry["file"] == "benchmark/configs/command-a-plus-05-2026.json"
    cell = next(w for w in bm["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (cfg["name"], "mixedlen_closed_c32", 1)


def test_parameter_count_and_least_work_on_a_hand_worked_step(bench_path):
    import families

    run = _run(STEP)
    fam = families.of(run["cfg"])
    p = fam.param_counts(run["cfg"])
    assert p["attn"] == 4096 * 16384 * 2 + 4096 * 1024 * 2 == 142_606_336
    assert p["expert"] == 3 * 4096 ** 2 and p["shared"] == 4 * p["expert"]
    assert p["router"] == 4096 * 128
    assert p["layer"] == 142_606_336 + 201_326_592 + 524_288 \
        + 16 * 50_331_648 + 4096
    assert p["total"] == 4_733_292_544  # 9.47 GB in bf16
    assert fam.vocab_size(run["cfg"]) == 32768
    assert fam.criterion() is None and fam.train_flops_per_token(run) is None
    # one layer's calls: the full layer's rows, ONE window layer's rows,
    # 4,096 B a row (K and V of 8 x 128, bf16), 4 x 128 x 128 flops a row
    assert fam.kernel_work(run, "paged_attention") \
        == (65536 * 150_000, 150_000 * 4096)
    assert fam.kernel_work(run, "paged_attention_window") \
        == (65536 * 115_000, 115_000 * 4096)
    assert fam.kernel_work(run, "mla_paged_attention") is None
    flops, nbytes = fam.decode_step_work(run)
    outside = 4 * (142_606_336 + 201_326_592 + 524_288 + 4096) \
        + 32768 * 4096 + 4096
    rows = 150_000 + 3 * 115_000
    assert nbytes == 2 * (outside + 4 * 14 * 50_331_648) + rows * 4096
    assert flops == 2 * (outside + 4 * 1.0 * 50_331_648) * 32 + 65536 * rows
    # the step's least time at 819 GB/s: the bytes bound it
    assert 13.0e-3 < nbytes / 819e9 < 13.1e-3  # ISSUE 35: 10.7 GB, 13.0 ms
    # a program without the window counter: nothing to say
    old = {k: v for k, v in STEP.items()
           if k != "serving.kv_window_rows_read"}
    assert fam.kernel_work(_run(old), "paged_attention") is None
    assert fam.decode_step_work(_run(old)) is None


def test_the_two_new_readers(bench_path):
    import run as bench_run

    share = bench_run.load_module("layer_metrics",
                                  "kv.window_rows_share.serve.py")
    assert share.read(_run(STEP)) == pytest.approx(100 * 115 / 150)
    assert share.read(_run({"serving.kv_tokens_read": 5})) is None
    assert share.read(_run({"serving.kv_tokens_read": 5,
                            "serving.kv_window_rows_read": 0})) is None
    roof = bench_run.load_module(
        "layer_metrics", "kernel.paged_attn_window_roofline.serve.py")
    # 30 events of 1 ms: 115,000 rows x 4,096 B at 819 GB/s = 0.5751 ms
    run = _run(STEP, {"paged_attention_window": {"n": 30, "seconds": 0.030},
                      "paged_attention": {"n": 10, "seconds": 0.010}})
    assert roof.read(run) == pytest.approx(
        100 * 115_000 * 4096 / 819e9 / 1e-3)
    assert run["said"][0].startswith("paged_attention_window: least 0.5751")
    full = bench_run.load_module("layer_metrics",
                                 "kernel.paged_attn_roofline.serve.py")
    assert full.read(run) == pytest.approx(100 * 150_000 * 4096 / 819e9 / 1e-3)
    # a trace without the window kernel (another family, or the parent)
    assert roof.read(_run(STEP, {"paged_attention": {
        "n": 10, "seconds": 0.010}})) is None
    for mod in (share, roof):
        assert mod.META["moves"] == "tpot_p95_ms" \
            and mod.META["drivers"] == ["serve_closed_loop"]


def test_rehearsal_cell_runs_on_the_cpu_and_reports_the_counter_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "rehearse_serve_commanda", "--seed", "3000000035", "--seconds", "2",
         "--trace", "1"], env=env, capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    assert 40 < m["kv.window_rows_share.serve"]["value"] < 100
    assert 0 < m["moe.experts_hit_share.serve"]["value"] <= 100
    assert m["kernel.fallbacks.serve"]["value"] == 0
    # device metrics need a device trace: left out on the CPU, never 0
    assert "kernel.paged_attn_window_roofline.serve" not in m
    assert "window of 24" in r.stdout  # the scorer says what it compared
