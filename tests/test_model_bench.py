"""Model benchmark harness (tools/model_bench.py — reference
ci_model_benchmark.sh relative-gating role over the five BASELINE
configs)."""
import json
import os
import subprocess
import sys

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None):
    env = dict(os.environ)
    env.update({"PYTHONPATH": _ROOT, "JAX_PLATFORMS": "cpu"})
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "model_bench.py"),
         *args], env=env, capture_output=True, text=True, timeout=420)


class TestModelBench:
    def test_single_config_runs_and_gates(self, tmp_path):
        out1 = str(tmp_path / "a.json")
        r = _run(["--out", out1, "--only", "ernie_static_infer"])
        assert r.returncode == 0, r.stderr[-500:]
        recs = json.load(open(out1))
        assert [x["config"] for x in recs] == ["ernie_static_infer"]
        assert recs[0]["value"] > 0

        # same-snapshot check passes
        out2 = str(tmp_path / "b.json")
        r2 = _run(["--out", out2, "--only", "ernie_static_infer",
                   "--check", out1, "--tol", "1000"])
        assert r2.returncode == 0, r2.stderr[-500:]

        # fabricated 100x regression trips the gate
        fast = [dict(recs[0])]
        fast[0]["per_sample_ms"] = recs[0]["per_sample_ms"] / 100.0
        prev = str(tmp_path / "fast.json")
        json.dump(fast, open(prev, "w"))
        r3 = _run(["--out", str(tmp_path / "c.json"),
                   "--only", "ernie_static_infer", "--check", prev,
                   "--tol", "1.2"])
        assert r3.returncode == 1
        assert "PERF REGRESSION" in r3.stderr
