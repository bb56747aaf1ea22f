"""The `qwen3_next` family and the cell it brings, added as files:
configs/qwen3-next-80b-a3b-instruct.json (and its float8 control),
families/qwen3_next.py, reference/qwen3_next.py,
traffic/longdoc_closed_c32.json, workloads/qwen3next_80b_serve_longdoc_closed
.json, two readers and the rehearsal. Run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

- the configuration file holds every number of the published config but the
  three it says it reduced, and its sizes give 2,929 M parameters;
- the family's least work on hand-made counters, and the two new readers,
  silent where a program keeps no such counter or kernel;
- the rehearsal cell runs on the CPU with --trace 1, `correct` true against
  the reference, and reports the counter metrics.
(The served path against the reference, the planted faults and the chip's
share are tests/test_qwen3_next_model.py.)
"""
import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
REPO = os.path.dirname(BENCH)
CELL = "qwen3next_80b_serve_longdoc_closed"
CONFIG = "qwen3-next-80b-a3b-instruct"


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
    run = {"cfg": _json("configs", CONFIG + ".json"),
           "wl": _json("workloads", CELL + ".json"),
           "traffic": _json("traffic", "longdoc_closed_c32.json"),
           "peaks": _json("peaks.json"), "device_kind": "TPU v5 lite",
           "counters": counters, "say": said.append, "said": said}
    if kernels is not None:
        run["scope_reduce"] = {"kernels": kernels, "modules": {}}
    return run


# 10 decode steps of 32 slots at a mean context of 9,000 rows: 2,880,000 rows
# a full layer; 30 of the 64 held experts hit in each of the 12 layers
STEP = {"serving.decode_steps": 10, "serving.active_slot_steps": 320,
        "serving.kv_tokens_read": 2_880_000,
        "serving.moe_layer_steps": 120, "serving.moe_experts_hit": 3600,
        "serving.linear_layer_steps": 90,
        "serving.linear_kernel_layer_steps": 90}


# the source's config.json, every key that says something of the model's
# shape (the HF repo Qwen/Qwen3-Next-80B-A3B-Instruct)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_the_file_is_the_published_config_cut_as_it_says():
    cfg = _json("configs", CONFIG + ".json")
    assert cfg["source"].startswith(
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/")
    differs = sorted(k for k, v in PUBLISHED.items()
                     if cfg.get(k, "absent") != v)
    assert differs == sorted(cfg["reduced"]), differs
    assert {k: PUBLISHED[k] for k in cfg["reduced"]} == cfg["published"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["n_routed_experts"], cfg["vocab_size"]) \
        == (12, 64, 64, 151936 // 8)
    for key in ("assumed", "changed", "deployment", "reference"):
        assert cfg[key]
    bm = _json("..", "BENCHMARK.json")
    entry = next(c for c in bm["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] \
        and entry["source"] == cfg["source"] \
        and entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = next(w for w in bm["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (cfg["name"], "longdoc_closed_c32", 1)
    for name in ("kernel.gated_delta_roofline.serve",
                 "linear.state_kernel_share.serve"):
        new = next(m for m in bm["per_layer"] if m["name"] == name)
        assert new["workloads"] == [CELL] and new["moves"] == "tpot_p95_ms"
    wl, tr = _json("workloads", CELL + ".json"), _json(
        "traffic", "longdoc_closed_c32.json")
    eng = wl["engine"]
    assert eng["blocks_per_slot"] * eng["block_size"] == eng["max_seq_len"] \
        == tr["check"]["padded_len"] \
        == tr["prompt_len"][1] + tr["max_new_tokens"][1]
    assert eng["buckets"][-1] == tr["prompt_len"][1]


def test_the_float8_control_is_the_cell_but_for_its_reference():
    """The control's two files are the cell's, the reference's precision
    apart, and nothing in BENCHMARK.json names them."""
    cfg = _json("configs", CONFIG + ".json")
    low = _json("configs", CONFIG + ".float8-control.json")
    assert low.pop("reference_weights") == "float8_e4m3fn"
    assert low.pop("control_of").startswith(cfg.pop("name") + ":")
    assert low.pop("name") == CONFIG + ".float8-control"
    assert low == cfg
    wl = _json("workloads", CELL + ".json")
    ctl = _json("workloads", CELL + ".float8_control.json")
    assert ctl.pop("config") == CONFIG + ".float8-control"
    for key in ("config", "why", "who"):
        wl.pop(key), ctl.pop(key, None)
    assert ctl == wl
    assert "float8" not in json.dumps(_json("..", "BENCHMARK.json"))


def test_parameter_count_and_least_work(bench_path):
    """2,929 M parameters (435.7 M outside the experts, 2,415.9 M of
    experts, 77.8 M of vocabulary); a decode step's least bytes at the
    hand-made counters (bound by bytes, 5-10 ms) and the kernels' least
    bytes (the state kernel's: 32 slots x 2 MiB read and written)."""
    import families
    import kernel_counts as kc

    run = _run(dict(STEP))
    fam = families.of(run["cfg"])
    p = fam.param_counts(run["cfg"])
    assert p["total"] == 2_929_374_400
    assert (p["linear_layers"], p["full_layers"]) == (9, 3)
    assert p["linear"] == 33_718_272 and p["full"] == 27_262_976
    flops, nbytes = fam.kernel_work(run, "gated_delta_step")
    assert nbytes == 2 * 32 * 32 * 128 * 128 * 4
    flops, nbytes = fam.kernel_work(run, "paged_attention")
    assert nbytes == 288_000 * 2048
    flops, nbytes = fam.decode_step_work(run)
    outside = 9 * p["linear"] + 3 * p["full"] \
        + 12 * (p["router"] + p["shared"]) + p["head"]
    assert nbytes == 2 * (outside + 12 * 30 * p["expert"]) \
        + 3 * 288_000 * 2048 + 9 * 32 * 2 * 32 * 128 * 128 * 4
    peaks = run["peaks"]["devices"]["TPU v5 lite"]
    least, bound = kc.least_seconds(flops, nbytes, peaks)
    assert bound == "bytes" and 5e-3 < least < 10e-3
    assert fam.kernel_work(run, "mla_paged_attention") is None


def test_the_two_new_readers(bench_path):
    import run as bench_run

    share = bench_run.load_module("layer_metrics",
                                  "linear.state_kernel_share.serve.py")
    roof = bench_run.load_module("layer_metrics",
                                 "kernel.gated_delta_roofline.serve.py")
    assert share.read(_run(dict(STEP))) == 100.0
    assert share.read(_run({**STEP, "serving.linear_kernel_layer_steps": 45
                            })) == 50.0
    no_state = {k: v for k, v in STEP.items() if "linear" not in k}
    assert share.read(_run(no_state)) is None  # other families, the parent
    # 9 layer calls a step at 0.2 ms each against 134 MB at 819 GB/s
    run = _run(dict(STEP), kernels={"gated_delta_step": {
        "n": 90, "seconds": 90 * 0.2e-3}})
    got = roof.read(run)
    assert 80 < got < 83, got  # 0.1639 ms least
    assert roof.read(_run(dict(STEP), kernels={})) is None


def test_rehearsal_cell_runs_on_the_cpu_and_reports_the_counter_metrics():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "rehearse_serve_qwen3next", "--seed", "3000000041", "--seconds",
         "3", "--trace", "1"], cwd=REPO, capture_output=True, text=True,
        timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    m = line["metrics"]
    assert m["linear.state_kernel_share.serve"]["value"] == 0.0  # xla here
    assert m["kv.prompt_block_write_share.serve"]["value"] == 100.0
    assert "moe.experts_hit_share.serve" in m
    assert "kernel.gated_delta_roofline.serve" not in m  # no device trace
