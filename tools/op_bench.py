"""Op-level fwd/bwd micro-benchmark harness.

Reference parity: `python/paddle/cost_model/static_op_benchmark.json`
(per-op timing snapshots) + `tools/ci_op_benchmark.sh` /
`check_op_benchmark_result.py` (relative perf gating between two builds).

Usage:
  python tools/op_bench.py --out op_bench.json            # measure
  python tools/op_bench.py --out new.json --check old.json --tol 1.15

Measures a representative op set (the families the BASELINE configs lean
on) through the real dispatch layer under jit, fwd and fwd+bwd, on
whatever device JAX selects. `--check` exits 1 if any op regressed more
than `tol`x vs a previous snapshot — the CI gate the reference implements
with an external benchmark repo.

Each case cycles between two distinct input sets, so that no layer below
can answer a repeated dispatch from a cache.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def _cases():
    import paddle_tpu as paddle

    rng = np.random.default_rng(0)

    def t(shape, dtype=np.float32):
        arr = rng.normal(size=shape).astype(dtype)
        x = paddle.to_tensor(arr)
        x.stop_gradient = False
        return x

    def ids(shape, hi):
        x = paddle.to_tensor(rng.integers(0, hi, shape))
        return x

    B = 8
    # two input variants per case: the benchmark cycles them so a
    # dispatch-deduplicating transport cannot elide repeated executions
    def two(maker):
        return (maker(), maker())

    return {
        "matmul_2048": (paddle.matmul,
                        two(lambda: (t((B, 2048)), t((2048, 2048))))),
        "add_bcast": (paddle.add,
                      two(lambda: (t((B, 1024, 64)), t((64,))))),
        "softmax_4096": (paddle.nn.functional.softmax,
                         two(lambda: (t((B, 4096)),))),
        "layer_norm": (
            lambda x, w, b: paddle.nn.functional.layer_norm(
                x, [1024], weight=w, bias=b),
            two(lambda: (t((B, 128, 1024)), t((1024,)), t((1024,))))),
        "gelu": (paddle.nn.functional.gelu, two(lambda: (t((B, 4096)),))),
        "mean_reduce": (lambda x: x.mean(),
                        two(lambda: (t((B, 1024, 256)),))),
        "transpose": (lambda x: x.transpose([0, 2, 1]),
                      two(lambda: (t((B, 512, 512)),))),
        "embedding": (
            lambda idx, w: paddle.nn.functional.embedding(idx, w),
            two(lambda: (ids((B, 128), 1000), t((1000, 512))))),
        "conv2d": (
            lambda x, w: paddle.nn.functional.conv2d(x, w, padding=1),
            two(lambda: (t((B, 64, 56, 56)), t((64, 64, 3, 3))))),
        "cross_entropy": (
            lambda x, y: paddle.nn.functional.cross_entropy(x, y),
            two(lambda: (t((B, 1000)), ids((B,), 1000)))),
    }


def _time_fn(step, n=20):
    """step(i) runs variant i%2; cycling distinct inputs defeats
    dispatch-level deduplication."""
    import jax

    out = step(0)
    jax.block_until_ready(out if not isinstance(out, tuple) else out[0])
    t0 = time.perf_counter()
    for i in range(n):
        out = step(i)
    jax.block_until_ready(out if not isinstance(out, tuple) else out[0])
    return (time.perf_counter() - t0) / n


def measure():
    import paddle_tpu as paddle

    results = {}
    for name, (fn, variants) in _cases().items():
        try:
            # eager dispatch path — the per-op hot loop the reference's op
            # benchmark gates (PHI dispatch there, core/dispatch.py here);
            # each call hits the cached per-op XLA executable
            t_fwd = _time_fn(lambda i: fn(*variants[i % 2])._data)

            def run_bwd(i):
                args = variants[i % 2]
                out = fn(*args)
                loss = out if out.ndim == 0 else (out.astype("float32") ** 2
                                                  ).mean()
                loss.backward()
                for a in args:
                    if hasattr(a, "clear_gradient"):
                        a.clear_gradient()
                return loss._data

            t_bwd = _time_fn(run_bwd, n=5)
            results[name] = {"fwd_ms": round(t_fwd * 1e3, 4),
                             "fwd_bwd_ms": round(t_bwd * 1e3, 4)}
            print(f"{name:18s} fwd {t_fwd*1e3:8.3f} ms   "
                  f"fwd+bwd {t_bwd*1e3:8.3f} ms", flush=True)
        except Exception as exc:  # keep the sweep going
            results[name] = {"error": str(exc)[:200]}
            print(f"{name:18s} ERROR {str(exc)[:80]}", flush=True)
    return results


def check(new, old, tol):
    bad = []
    for name, rec in new.items():
        if name.startswith("_"):  # _device/_ts metadata
            continue
        ref = old.get(name)
        if not ref or "error" in ref:
            continue  # new op or broken baseline: nothing to gate against
        if "error" in rec:
            # op measured fine in the baseline but errors now — the worst
            # possible regression, not a skip
            bad.append(f"{name}: errored (baseline "
                       f"{ref.get('fwd_ms', '?')} ms): {rec['error'][:80]}")
            continue
        for key in ("fwd_ms", "fwd_bwd_ms"):
            if rec[key] > ref[key] * tol:
                bad.append(f"{name}.{key}: {ref[key]:.3f} -> {rec[key]:.3f} "
                           f"ms (> {tol}x)")
    return bad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="op_bench.json")
    ap.add_argument("--check", default=None,
                    help="previous snapshot to gate against")
    ap.add_argument("--tol", type=float, default=1.15)
    args = ap.parse_args()

    import jax

    results = {"_device": str(jax.devices()[0]),
               "_ts": time.strftime("%Y-%m-%d %H:%M:%S"),
               **measure()}
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {args.out}")
    if args.check:
        with open(args.check) as f:
            old = json.load(f)
        bad = check(results, old, args.tol)
        if bad:
            print("PERF REGRESSIONS:\n  " + "\n  ".join(bad))
            return 1
        print("no regressions vs", args.check)
    return 0


if __name__ == "__main__":
    sys.exit(main())
