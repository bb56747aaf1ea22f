"""The `sdar_moe` family and the cell it brings (PR 37), added as files:
configs/sdar-30b-a3b-chat.json, families/sdar_moe.py, reference/sdar_moe.py,
traffic/blockgen_closed_c32.json, workloads/sdar_30b_serve_blockgen_closed
.json, one reader and the rehearsal. Run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

- the configuration file holds every number of the published config but the
  depth it says it reduced, and its sizes give 4,361 M parameters;
- the family's least work on a hand-worked forward, under 100 % of a
  roofline at a time no kernel can beat;
- the new reader on hand-made counters, and silent where a program keeps no
  such counter;
- the rehearsal cell runs on the CPU with --trace 1, `correct` true against
  the reference, and reports the counter metrics.
(The scorer against `reference.generate_block`, and the served path against
the reference, are tests/test_sdar_model.py.)
"""
import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
REPO = os.path.dirname(BENCH)
CELL = "sdar_30b_serve_blockgen_closed"


@pytest.fixture(scope="module")
def bench_path():
    sys.path[:0] = [REPO, BENCH]
    yield
    sys.path.remove(BENCH)
    sys.path.remove(REPO)


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _run(counters, kernels=None, modules=None):
    said = []
    run = {"cfg": _json("configs", "sdar-30b-a3b-chat.json"),
           "wl": _json("workloads", CELL + ".json"),
           "traffic": _json("traffic", "blockgen_closed_c32.json"),
           "peaks": _json("peaks.json"), "device_kind": "TPU v5 lite",
           "counters": counters, "say": said.append, "said": said}
    if kernels is not None:
        run["scope_reduce"] = {"kernels": kernels, "modules": modules or {}}
    return run


# 10 forwards of 32 slots: 100,000 rows a layer (a slot's committed rows and
# its block), every one of the 128 experts hit in each of the 6 layers; 40
# slot-forwards committed a block of 4
STEP = {"serving.decode_steps": 10, "serving.active_slot_steps": 320,
        "serving.kv_tokens_read": 1_000_000,
        "serving.moe_layer_steps": 60, "serving.moe_experts_hit": 7680,
        "serving.diffusion.slot_forwards": 320,
        "serving.diffusion.tokens_committed": 424}


def test_the_file_is_the_published_config_cut_as_it_says():
    cfg = _json("configs", "sdar-30b-a3b-chat.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f
                       if '"SDAR-30B-A3B-Chat"' in l)
        differs = sorted(k for k, v in row["config"].items()
                         if cfg.get(k, "absent") != v)
        assert differs == sorted(cfg["reduced"]), differs
        assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["n_routed_experts"], cfg["vocab_size"]) \
        == (6, 128, 128, 151936)
    assert cfg["generation"] == {
        "block_length": 4, "denoising_steps": 2,
        "strategy": "low_confidence_static", "confidence_threshold": 0.9,
        "mask_token_id": 151669}
    assert "seeded" not in cfg  # the program's own initialisers
    for key in ("assumed", "changed", "deployment", "reference"):
        assert cfg[key]
    for key in ("block", "rope", "router", "block_length", "mask_token_id",
                "logits", "denoising", "noise_schedule", "seeded_weights"):
        assert cfg["assumed"][key]
    bm = _json("..", "BENCHMARK.json")
    entry = next(c for c in bm["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] \
        and entry["source"] == cfg["source"] \
        and entry["file"] == "benchmark/configs/sdar-30b-a3b-chat.json"
    cell = next(w for w in bm["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (cfg["name"], "blockgen_closed_c32", 1)
    # the cell is in the list of both serving end-to-end metrics and of
    # every per-layer metric that lists commanda's cell, but the two of a
    # window layer
    for m in bm["end_to_end"] + bm["per_layer"]:
        w = m.get("workloads", [])
        if "commanda_plus_serve_mixedlen_closed" in w:
            assert (CELL in w) == ("window" not in m["name"]), m["name"]
    new = next(m for m in bm["per_layer"]
               if m["name"] == "diffusion.tokens_per_forward.serve")
    assert new["workloads"] == [CELL] \
        and new["moves"] == "serve_tokens_per_s"
    wl, tr = _json("workloads", CELL + ".json"), _json(
        "traffic", "blockgen_closed_c32.json")
    eng = wl["engine"]
    assert eng["blocks_per_slot"] * eng["block_size"] == eng["max_seq_len"] \
        == tr["check"]["padded_len"] \
        == tr["prompt_len"][1] + tr["max_new_tokens"][1]
    assert eng["max_seq_len"] % cfg["generation"]["block_length"] == 0


def test_the_float8_control_is_the_cell_but_for_its_reference():
    """The control's two files are the cell's, the reference's precision
    apart, and nothing in BENCHMARK.json names them."""
    cfg = _json("configs", "sdar-30b-a3b-chat.json")
    low = _json("configs", "sdar-30b-a3b-chat.float8-control.json")
    assert low.pop("reference_weights") == "float8_e4m3fn"
    assert low.pop("control_of").startswith(cfg.pop("name") + ":")
    assert low.pop("name") == "sdar-30b-a3b-chat.float8-control"
    assert low == cfg
    wl = _json("workloads", CELL + ".json")
    ctl = _json("workloads", CELL + ".float8_control.json")
    assert ctl.pop("config") == "sdar-30b-a3b-chat.float8-control"
    for key in ("config", "why", "who"):
        wl.pop(key), ctl.pop(key, None)
    assert ctl == wl
    assert "float8" not in json.dumps(_json("..", "BENCHMARK.json"))


@pytest.mark.parametrize("case", ["scaled", "unknown_key", "control"])
def test_seeded_scales_and_the_control_precision(bench_path, case):
    """`seeded_weights` multiplies the parameters its keys name (and names
    no parameter only by mistake); `reference_weights` rounds what the
    reference reads, so its logits part from the plain reference's."""
    import numpy as np

    import families

    tiny = _json("rehearse", "sdar-tiny.json")
    fam = families.of(tiny)
    if case == "unknown_key":
        with pytest.raises(SystemExit, match="no parameter"):
            fam.build({**tiny, "seeded_weights": {"q_norm.wieght": 2}}, 3)
        return
    if case == "scaled":
        scales = {"layers.0.self_attn.q_norm.weight": 3.0,
                  "mlp.experts.down": 0.25}
        plain = fam.build(tiny, 3)[1].state_dict()
        got = fam.build({**tiny, "seeded_weights": scales}, 3)[1].state_dict()
        for name, t in plain.items():
            k = 3.0 if name == "layers.0.self_attn.q_norm.weight" else \
                0.25 if name.endswith("mlp.experts.down") else 1.0
            np.testing.assert_array_equal(np.asarray(got[name]._data),
                                          k * np.asarray(t._data))
        return
    cfg, model = fam.build(tiny, 3)
    model.eval()
    ids = np.random.default_rng(3).integers(1, 500, 64).astype(np.int32)
    at = np.arange(39, 47, dtype=np.int32)
    rows = [np.asarray(fam.reference_scorer(c, cfg, model, 64, 8)(
        ids, at, quiet=True)) for c in (
            tiny, {**tiny, "reference_weights": "float8_e4m3fn"})]
    finite = np.isfinite(rows[0])  # (the mask id reads -inf in both)
    assert np.abs(rows[0][finite] - rows[1][finite]).max() > 1e-3


def test_parameter_count_and_least_work_on_a_hand_worked_forward(bench_path):
    import families
    import kernel_counts as kc

    run = _run(STEP)
    fam = families.of(run["cfg"])
    p = fam.param_counts(run["cfg"])
    assert p["attn"] == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18_874_368
    assert p["expert"] == 3 * 2048 * 768 and p["router"] == 2048 * 128
    assert p["layer"] == 18_874_368 + 256 + 262_144 + 128 * 4_718_592 \
        + 2 * 2048
    assert p["embed"] == p["head"] == 151936 * 2048
    assert p["total"] == 4_361_055_744  # 8.72 GB in bf16
    assert fam.vocab_size(run["cfg"]) == 151936
    assert fam.criterion() is None and fam.train_flops_per_token(run) is None
    # one layer's call: 100,000 rows of 2,048 B (K and V of 4 x 128, bf16)
    # read once, the block's 4 query rows of 32 heads x 128 over each
    assert fam.kernel_work(run, "paged_attention") \
        == (4 * 32 * 128 * 4 * 100_000, 100_000 * 2048)
    assert fam.kernel_work(run, "paged_attention_window") is None
    flops, nbytes = fam.decode_step_work(run)
    outside = 6 * (18_874_368 + 262_144) + 151936 * 2048
    assert nbytes == 2 * (outside + 6 * 128 * 4_718_592) \
        + 100_000 * 6 * 2048
    assert flops == 2 * (outside + 6 * 8 * 4_718_592) * 32 * 4 \
        + 4 * 32 * 128 * 4 * 100_000 * 6
    # the forward's least time at 819 GB/s: the bytes bound it (ISSUE 37
    # reckoned ~11 ms from 7.5 GB of weights beside ~1.2 GB of keys and
    # values; with attention, the router and the head the weights are 8.1 GB)
    least, bound = kc.least_seconds(
        flops, nbytes, run["peaks"]["devices"]["TPU v5 lite"])
    assert bound == "bytes" and 11.3e-3 < least < 11.5e-3
    # a program without the expert counters: nothing to say
    old = {k: v for k, v in STEP.items() if "moe" not in k}
    assert fam.kernel_work(_run(old), "paged_attention") is None
    assert fam.decode_step_work(_run(old)) is None


def test_the_accepted_readers_and_the_new_one(bench_path):
    import run as bench_run

    per = bench_run.load_module("layer_metrics",
                                "diffusion.tokens_per_forward.serve.py")
    assert per.read(_run(STEP)) == pytest.approx(424 / 320)
    assert per.read(_run({"serving.decode_steps": 10})) is None
    assert per.META["moves"] == "serve_tokens_per_s" \
        and per.META["drivers"] == ["serve_closed_loop"]
    hit = bench_run.load_module("layer_metrics",
                                "moe.experts_hit_share.serve.py")
    assert hit.read(_run(STEP)) == pytest.approx(100.0)
    # 60 kernel events of 0.5 ms: 100,000 rows x 2,048 B at 819 GB/s is
    # 0.2501 ms; 10 forwards of 12 ms against a least of 11.4 ms
    run = _run(STEP, {"paged_attention": {"n": 60, "seconds": 0.030}},
               {"serving_decode": {"n": 10, "seconds": 0.120}})
    roof = bench_run.load_module("layer_metrics",
                                 "kernel.paged_attn_roofline.serve.py")
    assert roof.read(run) == pytest.approx(
        100 * 100_000 * 2048 / 819e9 / 0.5e-3)
    step = bench_run.load_module("layer_metrics",
                                 "serve.decode_step_roofline.py")
    assert 90 < step.read(run) < 100
    mfu = bench_run.load_module("layer_metrics", "serve.decode_step_mfu.py")
    assert 0 < mfu.read(run) < 100


def test_rehearsal_cell_runs_on_the_cpu_and_reports_the_counter_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "rehearse_serve_sdar", "--seed", "3000000037", "--seconds", "2",
         "--trace", "1"], env=env, capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    # short requests (8-24 tokens): a first block without the prompt's tail
    # and a last one cut weigh more than in the cell
    assert 0.8 < m["diffusion.tokens_per_forward.serve"]["value"] < 4 / 3
    assert 0 < m["moe.experts_hit_share.serve"]["value"] <= 100
    assert m["kernel.fallbacks.serve"]["value"] == 0
    assert m["compiles_in_window.serve"]["value"] == 0
    # device metrics need a device trace: left out on the CPU, never 0
    assert not [n for n in m if "roofline" in n or "mfu" in n]
    assert "blocks replayed" in r.stdout  # the scorer says what it compared
