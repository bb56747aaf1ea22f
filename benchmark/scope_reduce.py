#!/usr/bin/env python3
"""benchmark/scope_reduce.py — from a profiler trace to device time under the
program's stable names (paddle_tpu/profiler/spans.py), beside trace_reduce.py
and through its loader.

    reduce(path, n_devices) -> {
      "stretch_s":  first device op's start to the last one's end, chip 0
      "modules":    {name: {"n", "seconds"}} the COMPLETE events of line
                    `XLA Modules` by executable name (`jit_train_step(123)`
                    -> `train_step`), summed over the chips. The trace cuts
                    the step it starts in (that event begins with the first
                    op) and often the one it ends in (that event ends with
                    the last op), so an event counts only if it begins and
                    ends at least 1 us inside the stretch: the last step is
                    left out even when it happened to be whole
      "kernels":    {name: {"n", "seconds"}} the `XLA Ops` events inside
                    those complete module events, by kernel name: a Mosaic
                    custom call is named `%<kernel>.<n> = ...` from
                    `pl.pallas_call(name=...)`
      "idle":       {"seconds", "attributed_s", "by_span": [[name, s], ...]}
                    chip 0's gaps of at least 2 us between ops, each put
                    down to the innermost program span (`serving.*`,
                    `train.*`) of ANY host thread that covers the gap's
                    middle, or to `unattributed`. A span that began before
                    the trace did is not in it, so the gaps counted are
                    those from the first recorded program span's start on;
                    with no program span in the trace, none }

A trace of a program without those names (the parent of PR 27) reduces to
empty tables; the readers in layer_metrics/ then return nothing.

    python3 benchmark/scope_reduce.py --reduce FILE [--devices N]
    python3 benchmark/scope_reduce.py --selfcheck     testdata/ vs expected
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402

MODULES_LINE = "XLA Modules"
PROGRAM_SPANS = ("serving.", "train.")
EDGE_NS = 1_000            # a module event this close to an end was cut

_MODULE = re.compile(r"^jit_(.+?)(?:\(\d+\))?$")
_KERNEL = re.compile(r"^%([A-Za-z_][\w-]*?)(?:\.\d+)? = .*custom-call\(")


def module_name(event_name):
    m = _MODULE.match(event_name)
    return m.group(1) if m else event_name


def kernel_name(event_name):
    """The Mosaic kernel an `XLA Ops` event runs, or None for any other op."""
    m = _KERNEL.match(event_name)
    # XLA's own custom calls keep the opcode as their name
    return m.group(1) if m and m.group(1) != "custom-call" else None


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return list(line.events)
    return []


def _program_spans(data):
    spans = []
    for plane in data.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_SPANS):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    return spans


def _innermost(spans, t):
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "unattributed"


def reduce(path, n_devices=1):
    data = trace_reduce._load(path)
    planes = trace_reduce._device_planes(data)[:n_devices]
    if not planes:
        raise SystemExit(f"scope_reduce: no device plane in {path}")
    modules = collections.defaultdict(lambda: {"n": 0, "seconds": 0.0})
    kernels = collections.defaultdict(lambda: {"n": 0, "seconds": 0.0})
    stretch = idle = None
    for k, plane in enumerate(planes):
        evs = sorted(_line(plane, trace_reduce.OPS_LINE),
                     key=lambda e: e.start_ns)
        if not evs:
            raise SystemExit(f"scope_reduce: plane {plane.name} has no ops")
        starts = [e.start_ns for e in evs]
        t_lo = starts[0]
        t_hi = max(e.start_ns + e.duration_ns for e in evs)
        for m in _line(plane, MODULES_LINE):
            m0, m1 = m.start_ns, m.start_ns + m.duration_ns
            if m0 < t_lo + EDGE_NS or m1 > t_hi - EDGE_NS:
                continue  # cut by an end of the trace
            rec = modules[module_name(m.name)]
            rec["n"] += 1
            rec["seconds"] += m.duration_ns / 1e9
            for e in evs[bisect.bisect_left(starts, m0):
                         bisect.bisect_right(starts, m1)]:
                kn = kernel_name(e.name)
                if kn is not None:
                    kernels[kn]["n"] += 1
                    kernels[kn]["seconds"] += e.duration_ns / 1e9
        if k == 0:
            stretch = (t_hi - t_lo) / 1e9
            spans = _program_spans(data)
            first = min((s for s, _, _ in spans), default=t_hi)
            merged = trace_reduce._union(
                [(e.start_ns, e.start_ns + e.duration_ns, "") for e in evs])
            by_span = collections.Counter()
            for (_, e0, _), (s1, _, _) in zip(merged, merged[1:]):
                mid = (e0 + s1) // 2
                if s1 - e0 >= trace_reduce.MIN_GAP_NS and mid >= first:
                    by_span[_innermost(spans, mid)] += s1 - e0
            total = sum(by_span.values())
            idle = {"seconds": total / 1e9,
                    "attributed_s": (total - by_span["unattributed"]) / 1e9,
                    "n_program_spans": len(spans),
                    "by_span": [[n, ns / 1e9]
                                for n, ns in by_span.most_common()]}
    return {"stretch_s": stretch, "modules": dict(modules),
            "kernels": dict(kernels), "idle": idle}


def of_run(run):
    """This run's reduction, made once and said on earlier lines; None when
    the run has no device trace (no --trace, or a CPU rehearsal)."""
    if "scope_reduce" not in run:
        run["scope_reduce"] = None
        if run.get("trace"):
            red = run["scope_reduce"] = reduce(
                trace_reduce.find_xplane(run["tracer"].dir),
                n_devices=int(run["wl"]["chips"]))
            say = run["say"]
            for kind in ("modules", "kernels"):
                for name, rec in sorted(red[kind].items()):
                    say(f"scope_reduce {kind[:-1]} {name}: {rec['n']} "
                        f"complete events, {rec['seconds']:.6f} s, "
                        f"{1e3 * rec['seconds'] / rec['n']:.4f} ms each")
            idle = red["idle"]
            say(f"scope_reduce idle {idle['seconds']:.6f} s of "
                f"{red['stretch_s']:.3f} s, by program span "
                f"({idle['n_program_spans']} in the trace): " + ", ".join(
                    f"{n} {s:.6f}" for n, s in idle["by_span"][:8]))
    return run["scope_reduce"]


def per_event(run, kind, *names):
    """(seconds, events) summed over the named kernels or modules of this
    run's trace; None unless every one of them is there."""
    red = of_run(run)
    if not red or not all(red[kind].get(n, {}).get("n") for n in names):
        return None
    return (sum(red[kind][n]["seconds"] for n in names),
            sum(red[kind][n]["n"] for n in names))


def selfcheck():
    """The recorded stretch under testdata/ must reduce to the recorded
    numbers (times to the nanosecond, names letter for letter)."""
    with open(os.path.join(HERE, "testdata", "scope_expected.json")) as f:
        cases = json.load(f)["cases"]
    bad = []
    for want in cases:
        got = reduce(os.path.join(HERE, "testdata", want["file"]),
                     n_devices=want["n_devices"])
        got = json.loads(json.dumps(got))  # as the file holds it
        for key in ("stretch_s", "modules", "kernels", "idle"):
            if _rounded(got[key]) != _rounded(want[key]):
                bad.append(f"{want['file']}: {key}: {got[key]!r} != "
                           f"{want[key]!r}")
        print(f"scope_reduce: {want['file']}: modules "
              f"{sorted(got['modules'])}, kernels {sorted(got['kernels'])}, "
              f"idle {got['idle']['seconds']:.6f} s")
    if bad:
        print("scope_reduce selfcheck FAILED:\n  " + "\n  ".join(bad))
        return 1
    print("scope_reduce selfcheck ok")
    return 0


def _rounded(x):
    if isinstance(x, float):
        return round(x, 9)
    if isinstance(x, dict):
        return {k: _rounded(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_rounded(v) for v in x]
    return x


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduce")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--selfcheck", action="store_true")
    args = ap.parse_args(argv)
    if args.reduce:
        print(json.dumps(reduce(args.reduce, args.devices), indent=1))
    if args.selfcheck:
        return selfcheck()
    return 0


if __name__ == "__main__":
    sys.exit(main())
