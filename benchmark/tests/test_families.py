"""benchmark/families/ and run.py's SAMPLES_AS (PR 29).

Kept under benchmark/ with the yardstick; run with

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

- the `gpt` family gives, number for number, what the drivers and readers
  took from model.py, flops.py and kernel_counts.py before they asked it;
- the six roofline readers read from the recorded chip stretches
  (testdata/) what the expressions they held before give;
- every entry of BENCHMARK.json is a reader that run.py gives its cells;
- a family or a SAMPLES_AS that is not there stops the run, by name;
- a second family under a second driver, ADDED AS FILES to a copy of
  benchmark/ (overlay/), reports the serving and the training contract's
  metrics on the CPU rehearsal: the defect this PR removes.
"""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
REPO = os.path.dirname(BENCH)
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def bench_path():
    sys.path[:0] = [REPO, BENCH]
    yield
    sys.path.remove(BENCH)
    sys.path.remove(REPO)


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _run(config, workload, counters=None, trace=None):
    """What a reader or a family is handed, without the run."""
    import scope_reduce

    wl = _json("workloads", workload + ".json")
    said = []
    run = {"cfg": _json("configs", config + ".json"), "wl": wl,
           "traffic": _json("traffic", wl["traffic"] + ".json"),
           "peaks": _json("peaks.json"), "device_kind": "TPU v5 lite",
           "counters": counters or {}, "say": said.append, "said": said}
    if trace:
        run["scope_reduce"] = scope_reduce.reduce(
            os.path.join(BENCH, "testdata", trace))
    return run


TRAIN = ("gpt2-medium", "gpt2m_train_bs8_s1024")
SERVE = ("gpt3-1.3b", "gpt3_1p3b_serve_chat_closed")


def _counters(steps, slots, rows):
    return {"serving.decode_steps": steps,
            "serving.active_slot_steps": steps * slots,
            "serving.kv_tokens_read": steps * rows}


@pytest.mark.parametrize("config", ["gpt2-medium", "gpt3-1.3b"])
def test_gpt_family_gives_what_the_old_calls_gave(bench_path, config):
    import families
    import flops
    import kernel_counts as kc
    import model as bench_model

    cfg = _json("configs", config + ".json")
    fam = families.of(cfg)
    assert fam is families.of({})  # no "family" key: gpt, loaded once
    s = bench_model.sizes(cfg)
    assert fam.sizes(cfg) == s and fam.vocab_size(cfg) == 50304
    assert fam.build is bench_model.build

    run = _run(config, TRAIN[1])  # batch 8 on one chip
    assert fam.train_flops_per_token(run) \
        == flops.gpt_train_flops_per_token(s)
    assert fam.kernel_work(run, "flash_fwd") \
        == (kc.flash_fwd_flops(s, 8), kc.flash_fwd_bytes(s, 8))
    assert fam.kernel_work(run, "flash_bwd") \
        == (kc.flash_bwd_flops(s, 8), kc.flash_bwd_bytes(s, 8))
    assert fam.kernel_work(run, "some_later_kernel") is None

    for slots, rows in ((32, 20000), (31.9, 16835)):
        run = _run(config, SERVE[1], _counters(537, slots, rows))
        slots, rows = kc.decode_step_means(run["counters"])
        assert fam.decode_step_work(run) == (
            kc.decode_step_flops(s, slots, rows),
            kc.decode_step_bytes(s, rows))
        assert fam.kernel_work(run, "paged_attention") \
            == (4 * s["d_model"] * rows, rows * kc.kv_row_bytes(s))
    # the program keeps no such counters: nothing to say
    run = _run(config, SERVE[1], {"serving.decode_steps": 4})
    assert fam.decode_step_work(run) is None
    assert fam.kernel_work(run, "paged_attention") is None


def test_gpt_family_at_the_cells_sizes(bench_path):
    """The hand numbers of PERF.md, through the family."""
    import families
    import kernel_counts as kc

    run = _run(*TRAIN)
    fam = families.of(run["cfg"])
    assert fam.train_flops_per_token(run) == pytest.approx(2.4249e9,
                                                           rel=1e-4)
    sec, bound = kc.least_seconds(*fam.kernel_work(run, "flash_fwd"), V5E)
    assert bound == "flops" and sec == pytest.approx(8.72e-5, rel=1e-2)
    run = _run(*SERVE, _counters(537, 32, 20000))
    sec, bound = kc.least_seconds(*fam.decode_step_work(run), V5E)
    assert bound == "bytes" and sec == pytest.approx(8.0e-3, rel=1e-2)
    run = _run(*SERVE, _counters(537, 31.9, 16835))
    sec, bound = kc.least_seconds(
        *fam.kernel_work(run, "paged_attention"), V5E)
    assert bound == "bytes" and sec == pytest.approx(0.1684e-3, rel=1e-3)


def _old_train_step_mfu(run, s, ev):
    import flops

    sec, n = ev("modules", "train_step")
    return 100.0 * flops.gpt_train_flops_per_token(s) * 8192 / (sec / n) \
        / 197e12


def _old_flash_fwd(run, s, ev):
    import kernel_counts as kc

    sec, n = ev("kernels", "flash_fwd")
    return 100.0 * kc.least_seconds(kc.flash_fwd_flops(s, 8),
                                    kc.flash_fwd_bytes(s, 8),
                                    V5E)[0] / (sec / n)


def _old_flash_bwd(run, s, ev):
    import kernel_counts as kc

    sec, n = ev("kernels", "flash_bwd_dkv", "flash_bwd_dq")
    return 100.0 * kc.least_seconds(kc.flash_bwd_flops(s, 8),
                                    kc.flash_bwd_bytes(s, 8),
                                    V5E)[0] / (sec / (n / 2))


def _old_decode_step_mfu(run, s, ev):
    import kernel_counts as kc

    sec, n = ev("modules", "serving_decode")
    return 100.0 * kc.decode_step_flops(
        s, *kc.decode_step_means(run["counters"])) / (sec / n) / 197e12


def _old_decode_step_roofline(run, s, ev):
    import kernel_counts as kc

    sec, n = ev("modules", "serving_decode")
    slots, rows = kc.decode_step_means(run["counters"])
    return 100.0 * kc.least_seconds(
        kc.decode_step_flops(s, slots, rows),
        kc.decode_step_bytes(s, rows), V5E)[0] / (sec / n)


def _old_paged_attn(run, s, ev):
    import kernel_counts as kc

    sec, n = ev("kernels", "paged_attention")
    rows = kc.decode_step_means(run["counters"])[1]
    return 100.0 * kc.least_seconds(4 * s["d_model"] * rows,
                                    rows * kc.kv_row_bytes(s),
                                    V5E)[0] / (sec / n)


@pytest.mark.parametrize("name, cell, trace, old, said", [
    ("train.step_mfu", TRAIN, "gpt2m_train_named_step.xplane.pb.gz",
     _old_train_step_mfu, None),
    ("kernel.flash_fwd_roofline.train", TRAIN,
     "gpt2m_train_named_step.xplane.pb.gz", _old_flash_fwd,
     "flash_fwd: least 0.0872 ms a layer (bound: flops), measured "),
    ("kernel.flash_bwd_roofline.train", TRAIN,
     "gpt2m_train_named_step.xplane.pb.gz", _old_flash_bwd,
     "flash_bwd: least 0.2180 ms a layer (bound: flops), measured "),
    ("serve.decode_step_mfu", SERVE,
     "gpt3_1p3b_serve_decode_step.xplane.pb.gz", _old_decode_step_mfu,
     None),
    ("serve.decode_step_roofline", SERVE,
     "gpt3_1p3b_serve_decode_step.xplane.pb.gz", _old_decode_step_roofline,
     "decode step: 31.90 slots, 16835 KV rows; least 7.2428 ms (bound: "
     "bytes), measured "),
    ("kernel.paged_attn_roofline.serve", SERVE,
     "gpt3_1p3b_serve_decode_step.xplane.pb.gz", _old_paged_attn,
     "paged_attention: least 0.1684 ms a layer (bound: bytes), measured "),
])
def test_reader_reads_what_its_old_expression_gave(bench_path, monkeypatch,
                                                   name, cell, trace, old,
                                                   said):
    """On a recorded stretch of a chip trace, to the last digit."""
    import model as bench_model
    import run as bench_run
    import scope_reduce

    run = _run(*cell, _counters(537, 31.9, 16835), trace)
    got = bench_run.load_module("layer_metrics", name + ".py").read(run)
    want = old(run, bench_model.sizes(run["cfg"]),
               lambda *a: scope_reduce.per_event(run, *a))
    assert got == want and 0 < got < 100
    assert run["said"] == [] if said is None \
        else run["said"][0].startswith(said)
    # a family with nothing to say: the metric is left out, never 0
    run["cfg"] = dict(run["cfg"], family="silent")
    monkeypatch.setitem(sys.modules, "bench_family_silent", SimpleNamespace(
        train_flops_per_token=lambda run: None,
        decode_step_work=lambda run: None,
        kernel_work=lambda run, kernel: None))
    assert bench_run.load_module("layer_metrics",
                                 name + ".py").read(run) is None


def _drivers_of_cells():
    out = {}
    for w in _json("..", "BENCHMARK.json")["workloads"]:
        out[w["name"]] = _json("traffic", w["traffic"] + ".json")["driver"]
    return out


@pytest.mark.parametrize("kind, section", [("e2e_metrics", "end_to_end"),
                                           ("layer_metrics", "per_layer")])
def test_every_entry_is_a_reader_run_py_gives_its_cells(bench_path, kind,
                                                        section):
    import run as bench_run

    bm = _json("..", "BENCHMARK.json")
    drivers = _drivers_of_cells()
    given = {}
    for cell, name in drivers.items():
        mod = bench_run.load_module("drivers", name + ".py")
        given[cell] = {m.META["name"]: m.META for m in
                       bench_run.metric_files(kind, name, mod)}
    for entry in bm[section]:
        for cell in entry.get("workloads", list(drivers)):
            meta = given[cell][entry["name"]]
            for key in set(entry) - {"workloads", "bound", "name"}:
                assert meta[key] == entry[key], (entry["name"], key)


def test_samples_as_gives_a_driver_the_other_drivers_readers(bench_path):
    import run as bench_run

    mine = SimpleNamespace()
    names = lambda ms: sorted(m.META["name"] for m in ms)  # noqa: E731
    assert bench_run.metric_files("e2e_metrics", "a_later_driver",
                                  mine) == []
    mine.SAMPLES_AS = "serve_closed_loop"
    assert names(bench_run.metric_files(
        "e2e_metrics", "a_later_driver", mine)) \
        == ["serve_tokens_per_s", "setup_s", "tpot_p95_ms"]
    inner = bench_run.load_module("drivers", "serve_closed_loop.py")
    assert not hasattr(inner, "SAMPLES_AS")
    assert names(bench_run.metric_files("layer_metrics", "a_later_driver",
                                        mine)) \
        == names(bench_run.metric_files("layer_metrics",
                                        "serve_closed_loop", inner))
    mine.SAMPLES_AS = "no_such_driver"
    with pytest.raises(SystemExit) as e:
        bench_run.metric_files("e2e_metrics", "a_later_driver", mine)
    assert "drivers/a_later_driver.py" in str(e.value) \
        and "drivers/no_such_driver.py" in str(e.value)


def test_unknown_family_stops_by_the_files_name(bench_path):
    import families

    with pytest.raises(SystemExit) as e:
        families.of({"name": "some-config", "family": "no_such_family"})
    assert "families/no_such_family.py" in str(e.value) \
        and "some-config" in str(e.value)


@pytest.fixture(scope="module")
def copy_with_added_files(tmp_path_factory):
    """A copy of benchmark/ with overlay/ laid over it: files added, none
    edited. The program comes from the repo (PYTHONPATH)."""
    root = tmp_path_factory.mktemp("added_as_files")
    bench = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    overlay = os.path.join(TESTS, "overlay")
    for d, _, files in os.walk(overlay):
        for fn in files:
            if fn.endswith(".pyc"):
                continue
            dst = os.path.join(bench, os.path.relpath(d, overlay), fn)
            assert not os.path.exists(dst), f"{dst}: would be an edit"
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            shutil.copy(os.path.join(d, fn), dst)
    return str(root)


def _rehearse(root, workload, trace, correct=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", workload, "--seed", "7", "--seconds", "2",
         "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is correct, r.stdout[-3000:]
    # each number compared beside its limit: the line's last key, and the
    # last lines of standard error
    assert list(out)[-1] == "compared" and out["compared"]
    err = r.stderr.strip().splitlines()[-len(out["compared"]):]
    assert err == [f"[bench] compared {k}: {v} (limit {lim})"
                   for k, (v, lim) in out["compared"].items()]
    return out["metrics"], r.stdout, out["compared"]


@pytest.mark.parametrize("trace", [0, 1])
def test_second_family_and_driver_report_the_serving_metrics(
        copy_with_added_files, trace):
    got, said, _ = _rehearse(copy_with_added_files, "toy_serve", trace)
    assert "config toy-tiny traffic toy_chat driver serve_again" in said
    if trace == 0:
        assert sorted(got) == ["serve_tokens_per_s", "setup_s",
                               "tpot_p95_ms"]
        assert all(m["value"] > 0 for m in got.values())
    else:
        for name in ("serve.decode_call_ms", "serve.sched_self_ms",
                     "serve.prefill_call_ms", "serve.queue_wait_ms",
                     "serve.step_ms", "serve.ttft_p50_ms",
                     "serve.itl_p99_ms", "serve.batch_occupancy"):
            assert got[name]["value"] > 0, name
        # no device: nothing under a device metric's name
        assert not [n for n in got if "roofline" in n or "mfu" in n]


@pytest.mark.parametrize("trace", [0, 1])
def test_second_family_and_driver_report_the_training_metrics(
        copy_with_added_files, trace):
    got, said, _ = _rehearse(copy_with_added_files, "toy_train", trace)
    assert "config toy-tiny traffic toy_fixed driver train_again" in said
    # the driver asked the toy family for its count, not flops.py:
    # 6 x (2 x (4 x 128^2 + 2 x 128 x 512) + 384 x 128)
    assert "2.6542e+06 FLOPs/token" in said
    if trace == 0:
        assert sorted(got) == ["setup_s", "train_tokens_per_s"]
        assert all(m["value"] > 0 for m in got.values())
    else:
        assert got["train.dispatch_ms"]["value"] > 0
        assert got["train.step_call_ms"]["value"] > 0
        assert got["compiles_in_window.train"]["value"] == 0
        assert not [n for n in got if "roofline" in n or "mfu" in n]


def test_an_altered_token_reads_not_correct(copy_with_added_files):
    """The rest of a run over a timed path broken underneath: every second
    served token altered where the scheduler hands it out."""
    _, said, compared = _rehearse(copy_with_added_files,
                                  "toy_serve_token_altered", 0,
                                  correct=False)
    gap, limit = compared["worst_logit_gap"]
    assert gap > limit, said[-2000:]
    assert all(v <= lim for k, (v, lim) in compared.items()
               if k not in ("worst_logit_gap",
                            "greedy_requests_compared_at_least"))


def test_a_step_that_leaves_its_state_unchanged_reads_not_correct(
        copy_with_added_files):
    _, said, compared = _rehearse(copy_with_added_files,
                                  "toy_train_state_unchanged", 0,
                                  correct=False)
    final, first = compared["final_loss_below_first"]
    assert final == first and "DOES NOT FALL" in said
