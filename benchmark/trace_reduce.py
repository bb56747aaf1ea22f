#!/usr/bin/env python3
"""benchmark/trace_reduce.py — from a profiler trace (.xplane.pb) to numbers.

    reduce(path, n_devices) -> {
      "window_s":   traced span: first device op's start to the last one's end
      "busy_s":     union of the device-op intervals, averaged over the chips
      "device_ops": [[group, seconds], ...] total device time by op group
                    (see op_group), most first, summed over the chips
      "idle_gaps":  [[what, seconds], ...] idle time of chip 0 by what the
                    host was doing: the innermost benchmark span (`bench.*`)
                    that covers the gap, or `unattributed`, and the device op
                    the gap came after; most first
      "n_ops", "n_gaps", "n_host_spans": counts, for the reader }

It needs nothing but JAX (`jax.profiler.ProfileData`). What the trace of a
v5e looks like (seen by hand, PR 25) is written down in PERF.md section 3.

    python3 benchmark/trace_reduce.py --dump FILE       what is in a trace
    python3 benchmark/trace_reduce.py --selfcheck       testdata/ vs expected
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"       # the one line reduced: the others nest over it
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
MIN_GAP_NS = 2_000         # shorter gaps are the device's own op-to-op time


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise SystemExit(f"trace_reduce: no .xplane.pb under {trace_dir}")
    return files[-1]


def _load(path):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _device_planes(data):
    planes = [p for p in data.planes if p.name.startswith(DEVICE_PLANE)]
    return sorted(planes, key=lambda p: int(p.name[len(DEVICE_PLANE):]
                                            .split()[0]))


def _ops(plane):
    for line in plane.lines:
        if line.name == OPS_LINE:
            return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events]
    raise SystemExit(f"trace_reduce: plane {plane.name} has no line "
                     f"{OPS_LINE!r}: {[ln.name for ln in plane.lines]}")


_LAYOUT = re.compile(r"\{[^{}]*\}")
_SUFFIX = re.compile(r"\.\d+$")


def _result_type(text):
    """The result type that opens an HLO instruction's right-hand side,
    and what follows it: `(a, b) custom-call(...)` -> (`(a, b)`, rest)."""
    if not text.startswith("("):
        head, _, rest = text.partition(" ")
        return head, rest
    depth = 0
    for i, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return text[:i + 1], text[i + 2:]
    return text, ""


def op_group(name):
    """A device op's group: the trace names an op by its whole HLO line,
    unique per instruction (`%fusion.517 = bf16[8,1024]{1,0:T(8,128)}
    fusion(...), kind=...`). Ops that differ only in their number — the 24
    layers' copies of one fusion or kernel — fall into one group:
    `%fusion fusion -> bf16[8,1024]`, layouts dropped."""
    short, sep, rhs = name.partition(" = ")
    if not sep:
        return _SUFFIX.sub("", name)[:160]
    rtype, rest = _result_type(_LAYOUT.sub("", rhs))
    opcode = rest.split("(", 1)[0].strip()
    return f"{_SUFFIX.sub('', short)} {opcode} -> {rtype}"[:160]


def _union(intervals):
    """Merged [start, end] intervals of sorted (start, end, name) events,
    each with the name of the last op in it."""
    out = []
    for s, e, name in intervals:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1], out[-1][2] = e, name
        else:
            out.append([s, e, name])
    return out


def _host_spans(data):
    spans = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    return spans


def _covering(spans, t):
    """Name of the shortest benchmark span that covers time t."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "unattributed"


def reduce(path, n_devices=1):
    data = _load(path)
    planes = _device_planes(data)
    if len(planes) < n_devices:
        raise SystemExit(f"trace_reduce: {len(planes)} device planes in "
                         f"{path}, the cell uses {n_devices}: "
                         f"{[p.name for p in data.planes]}")
    per_dev = [sorted(_ops(p)) for p in planes[:n_devices]]
    if not all(per_dev):
        raise SystemExit("trace_reduce: a device plane has no op events")
    t_lo = min(ops[0][0] for ops in per_dev)
    t_hi = max(max(e for _, e, _ in ops) for ops in per_dev)
    by_name = collections.Counter()
    busy = []
    for ops in per_dev:
        merged = _union(ops)
        busy.append(sum(e - s for s, e, _ in merged))
        for s, e, name in ops:
            by_name[op_group(name)] += e - s
    spans = _host_spans(data)
    merged0 = _union(per_dev[0])
    gaps = collections.Counter()
    n_gaps = 0
    for (s0, e0, last), (s1, _, _) in zip(merged0, merged0[1:]):
        if s1 - e0 >= MIN_GAP_NS:
            n_gaps += 1
            what = _covering(spans, (e0 + s1) // 2)
            gaps[f"{what} | after {op_group(last)}"] += s1 - e0
    return {
        "window_s": (t_hi - t_lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "device_ops": [[n, ns / 1e9] for n, ns in by_name.most_common(40)],
        "idle_gaps": [[n, ns / 1e9] for n, ns in gaps.most_common(40)],
        "n_ops": sum(len(o) for o in per_dev), "n_gaps": n_gaps,
        "n_host_spans": len(spans),
    }


def dump(path, top=25):
    """What is in a trace: planes, lines, event counts, time ranges, the
    names that take most time on each line. For looking at one by hand."""
    data = _load(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                print(f"  LINE {line.name!r}: 0 events")
                continue
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            tot = collections.Counter()
            cnt = collections.Counter()
            for e in evs:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
            print(f"  LINE {line.name!r}: {len(evs)} events, start_ns "
                  f"{lo} .. {hi} ({(hi - lo) / 1e6:.3f} ms), "
                  f"{len(tot)} names")
            for name, ns in tot.most_common(top):
                print(f"      {ns / 1e6:10.3f} ms  x{cnt[name]:<6d} "
                      f"{name[:140]}")


def selfcheck():
    """The recorded trace under testdata/ must reduce to the recorded
    numbers (times to the nanosecond, names letter for letter)."""
    with open(os.path.join(HERE, "testdata", "expected.json")) as f:
        want = json.load(f)
    got = reduce(os.path.join(HERE, "testdata", want["file"]),
                 n_devices=want["n_devices"])
    bad = []
    for key in ("window_s", "busy_s"):
        if abs(got[key] - want[key]) > 1e-9:
            bad.append(f"{key}: {got[key]!r} != {want[key]!r}")
    for key in ("n_ops", "n_gaps", "n_host_spans"):
        if got[key] != want[key]:
            bad.append(f"{key}: {got[key]} != {want[key]}")
    for key in ("device_ops", "idle_gaps"):
        g = [[n, round(s, 9)] for n, s in got[key][:len(want[key])]]
        w = [[n, round(s, 9)] for n, s in want[key]]
        if g != w:
            bad.append(f"{key}: {g[:3]} ... != {w[:3]} ...")
    if bad:
        print("trace_reduce selfcheck FAILED:\n  " + "\n  ".join(bad))
        return 1
    print(f"trace_reduce selfcheck ok: {want['file']}: window "
          f"{got['window_s']:.6f} s, busy {got['busy_s']:.6f} s, "
          f"{got['n_ops']} ops, {got['n_gaps']} gaps")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dump")
    ap.add_argument("--reduce")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--selfcheck", action="store_true")
    args = ap.parse_args(argv)
    if args.dump:
        dump(args.dump)
    if args.reduce:
        print(json.dumps(reduce(args.reduce, args.devices), indent=1))
    if args.selfcheck:
        return selfcheck()
    return 0


if __name__ == "__main__":
    sys.exit(main())
