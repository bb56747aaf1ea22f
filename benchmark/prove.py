#!/usr/bin/env python3
"""benchmark/prove.py — several runs of one cell in one chip call.

Stdlib only and never imports jax (a parent that touched JAX would hold the
chip): it starts run.py once per seed, one process after the other, the
first cold and the rest from the compile cache, and writes every run's last
line, with its seed, wall time and exit code, to the chip tool's output
directory. The whole output of each run goes to a log beside it.

    python3 benchmark/prove.py --workload <cell> --seconds 30 \
        --seeds 3000000001,3000000002 [--trace-seeds 3000000003] [--tag a]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values):
    """Interquartile distance as a share of the median (the contract's)."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--tag", default="set")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    stem = os.path.join(args.out, f"prove_{args.workload}_{args.tag}")
    plan = [(int(s), 0) for s in args.seeds.split(",") if s] \
        + [(int(s), 1) for s in args.trace_seeds.split(",") if s]
    rows = []
    with open(stem + ".jsonl", "w") as out, open(stem + ".log", "w") as log:
        for seed, trace in plan:
            cmd = [sys.executable, os.path.join(HERE, "run.py"),
                   "--workload", args.workload, "--seed", str(seed),
                   "--seconds", str(args.seconds), "--trace", str(trace)]
            if trace and args.keep_trace:
                cmd += ["--keep-trace", f"{stem}_seed{seed}.xplane.pb"]
            t0 = time.monotonic()
            r = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                               stderr=subprocess.STDOUT, text=True)
            wall = time.monotonic() - t0
            log.write(f"==== seed {seed} trace {trace} exit {r.returncode} "
                      f"wall {wall:.1f}s\n{r.stdout}\n")
            log.flush()
            lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
            row = {"seed": seed, "trace": trace, "rc": r.returncode,
                   "wall_s": round(wall, 1), "result": None}
            if r.returncode == 0 and lines:
                try:
                    row["result"] = json.loads(lines[-1])
                except ValueError:
                    pass
            out.write(json.dumps(row) + "\n")
            out.flush()
            rows.append(row)
            facts = {k: v["value"] for k, v in
                     (row["result"] or {}).get("metrics", {}).items()}
            print(f"seed {seed} trace {trace} rc {r.returncode} wall "
                  f"{wall:.1f}s correct "
                  f"{(row['result'] or {}).get('correct')} {facts}",
                  flush=True)
            if row["result"] is None:
                print("\n".join(r.stdout.splitlines()[-40:]), flush=True)
    plain = [r["result"]["metrics"] for r in rows
             if r["result"] and not r["trace"]]
    for name in (plain[0] if plain else ()):
        vals = [m[name]["value"] for m in plain if name in m]
        if len(vals) >= 3:
            print(f"{name}: n {len(vals)} median "
                  f"{statistics.median(vals):.6g} spread "
                  f"{100 * spread(vals):.3f} % (first run, which may "
                  f"compile, included)")
    return 0 if all(r["result"] and r["result"].get("correct")
                    for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
