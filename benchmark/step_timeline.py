#!/usr/bin/env python3
"""benchmark/step_timeline.py — the serving device's idle time by CAUSE, beside
scope_reduce.py and through trace_reduce's loader.

scope_reduce.py gives a whole gap of chip 0 to the innermost program span over
the gap's middle, so the gap between two decode steps reads as
`serving.decode_sync`: the chip idle while the host waits for the chip. This
reader cuts every gap at the server thread's span boundaries and gives each
PIECE, by its length, to one class.

The server thread is the host line that holds `serving.sched_step`. Each
`XLA Modules` event of `serving_decode` / `serving_prefill` on chip 0 is
paired with the `serving.decode_step` / `serving.prefill` span that called
it (one executable is in flight at a time, and the span ends with the wait):
the span it overlaps most, by at least half its length. An event cut by an
end of the trace, or whose span is not in the trace, is left out and counted.

**The two clocks.** Device events and host spans of a v5e trace do NOT share
an origin to better than a millisecond or two (PR 39: the device's events lay
0.4 to 1.5 ms EARLY, by the trace, so an executable seemed to start before
the host called it), and the offset STEPS inside a trace (by 0.13 to 0.2 ms
about a second after its start, in every trace looked at). What splits
`launch` from `return` is exactly that offset, so it is measured from the
trace itself, call by call. An executable cannot start before the host
enqueued it nor end after the host knew it was done, so every paired call
bounds the offset d to add to device times from both sides:

    d >= (enqueue on the host) - (first device op)
    d <= (done on the host)    - (last device op)

with, on the host, the TPU runtime's own events inside the call's span
(`DoEnqueueProgram` begins; the first of `ReadSyncFlag` /
`tpu::System::Execute=>Done` begins; libtpu 0.0.34) or, where a call has
none, the call's begin and the wait's end (a wide bracket). A call's d is the
middle of the bracket that the calls within CLOCK_WINDOW_NS of it leave
(its own alone where theirs contradict: the step), half the bracket's width
is its slack: that much of its launch lag may be return lag or the other way
round. The host classes and the sum of launch and return do not depend on d.
A device instant is read with the d of the paired call nearest to it.
`clock` says the mean d, its range over the trace, the mean slack and how
many calls' own runtime bounds contradicted each other (then the spans').

Classes, in the order they are tried:

    host.gc     a collection of Python's collector, on ANY host thread: it
                holds the interpreter lock whichever thread runs it
    return      from the end of an executable's last device op to the end of
                the wait on it (`serving.decode_sync`'s end; `serving.prefill`'s)
    launch      from the begin of the call (`serving.decode_step`'s /
                `serving.prefill`'s begin) to the executable's first device op
    device      inside a paired executable, between its first and last op:
                the device's own waits (a copy, a conditional), no host cause
    <span>      otherwise the innermost `serving.*` span of the server thread,
                without the prefix: decode_prepare, decode_finish, emit,
                sched_step (its self time), admit_check, admit_blocks,
                admit_stage, admit_install, admit (self), loop_idle;
                decode_step /
                decode_sync / prefill only under a call left out
    unattributed  the server thread is under no span

    reduce(path) -> {
      "idle_s":    the classes' sum: chip 0's gaps of at least 2 us from the
                   first program span on, the same gaps as scope_reduce's
                   `idle.seconds` (the two differ by the clock's steps: us)
      "by_class":  [[class, seconds], ...] most first
      "steps":     `serving.decode_step` spans of the server thread begun in
                   the stretch
      "calls":     {"decode" | "prefill": {"n", "<lag>_us": {"mean", "p95"}}}
                   per paired call: dispatch_us (call begin -> dispatch
                   returned: decode only, prefill has no inner span),
                   launch_us, return_us, turnaround_us (the wait's end of the
                   call before -> this call's begin)
      "clock":     {"device_late_us": mean d, "min_us", "max_us", "slack_us",
                   "evidence": "runtime" | "spans", "contradicted": calls}
      "left_out":  module events of the two executables not paired
      "n_server_spans": program spans on the server thread }

A trace of a program without the spans (or with no server thread in it)
reduces to None; the readers in layer_metrics/ then return nothing.

    python3 benchmark/step_timeline.py --reduce FILE
    python3 benchmark/step_timeline.py --selfcheck     testdata/ vs expected
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402

SERVER_SPAN = "serving.sched_step"  # the line that holds it is the server's
GC_SPAN = "host.gc"
CALLS = {"serving_decode": ("decode", "serving.decode_step"),
         "serving_prefill": ("prefill", "serving.prefill")}
WAIT_SPAN = "serving.decode_sync"   # inside a decode call: the wait
PREFIX = "serving."
# host events of the TPU runtime (libtpu 0.0.34) that bound, on the HOST's
# clock, when an executable ran: it starts after the first begins and has
# ended when one of the others begins
ENQUEUE_EVENT = "DoEnqueueProgram"
DONE_EVENTS = ("ReadSyncFlag", "tpu::System::Execute=>Done")
CLOCK_WINDOW_NS = 150_000_000  # the offset is steady over +- this much
# the host's part of an iteration, and of an admission (the readers' sums)
HOST_STEP = ("decode_prepare", "decode_finish", "emit", "sched_step",
             GC_SPAN, "loop_idle")
HOST_ADMIT = ("admit_check", "admit_blocks", "admit_stage", "admit_install",
              "admit")


def _host_lines(data):
    for plane in data.planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                yield [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events]


def innermost_segments(spans):
    """One thread's nested (start, end, name) spans as sorted, disjoint
    (start, end, name) segments, each under its innermost span."""
    segs, stack, t = [], [], None

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                segs.append((t, end, name))
                t = end

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        close_until(s)
        if stack:
            if s > t:
                segs.append((t, s, stack[-1][1]))
            e = min(e, stack[-1][0])  # a child ends with its parent at most
        t = s
        stack.append((e, name))
    close_until(float("inf"))
    return segs


def _layer(segs):
    """Sorted, disjoint (start, end, name) segments with their starts."""
    segs = sorted(segs)
    return [s for s, _, _ in segs], segs


def _take(pieces, layer, out):
    """Give the parts of `pieces` that lie under the layer's segments to
    out[name]; return what is left of them."""
    starts, segs = layer
    left = []
    for a, b in pieces:
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while a < b and i < len(segs):
            s, e, name = segs[i]
            if s >= b:
                break
            if e > a:
                if s > a:
                    left.append((a, s))
                out[name] += min(e, b) - max(s, a)
                a = min(e, b)
            i += 1
        if a < b:
            left.append((a, b))
    return left


def _lags(values):
    values = sorted(values)
    return {"mean": sum(values) / len(values) / 1e3,
            "p95": values[min(len(values) - 1,
                              int(0.95 * len(values)))] / 1e3}


def _pair(modules, by_name, t_lo, t_hi):
    """Each executable's whole module event with the call span it overlaps
    most (device times as recorded: an executable is several ms, the clocks
    part by one or two). Returns (calls, events left out)."""
    waits = by_name[WAIT_SPAN]
    starts = {name: [s for s, _, _ in by_name[name]]
              for name in [WAIT_SPAN] + [span for _, span in CALLS.values()]}
    taken, left_out = {}, 0
    for m0, m1, exe in sorted(modules):
        if exe not in CALLS:
            continue
        kind, span_name = CALLS[exe]
        best = None
        # the spans around the event's begin: one of them called it
        i = bisect.bisect_right(starts[span_name], m0)
        for c0, c1, _ in by_name[span_name][max(i - 2, 0):i + 2]:
            overlap = min(c1, m1) - max(c0, m0)
            if best is None or overlap > best[0]:
                best = (overlap, c0, c1)
        cut = m0 < t_lo + scope_reduce.EDGE_NS \
            or m1 > t_hi - scope_reduce.EDGE_NS
        if cut or best is None or 2 * best[0] < m1 - m0 \
                or best[1] in taken:
            left_out += 1
            continue
        _, c0, c1 = best
        dispatched, done = None, c1
        if kind == "decode":  # the wait inside this call
            j = bisect.bisect_left(starts[WAIT_SPAN], c0)
            if j < len(waits) and waits[j][1] <= c1:
                dispatched, done = waits[j][0], waits[j][1]
        taken[c0] = {"kind": kind, "begin": c0, "dispatched": dispatched,
                     "first_op": m0, "last_op": m1, "done": done,
                     "recorded": (m0, m1)}
    return [taken[c0] for c0 in sorted(taken)], left_out


def _clock(calls, host_lines):
    """Give every paired call its `late`: how late the device's clock is
    against the host's around it, ns (see the module docstring). Returns
    what `clock` says of the whole trace."""
    marks = {name: sorted(s for ln in host_lines for s, _, n in ln
                          if n == name)
             for name in (ENQUEUE_EVENT,) + DONE_EVENTS}

    def first_in(name, a, b):
        i = bisect.bisect_left(marks[name], a)
        return marks[name][i] if i < len(marks[name]) \
            and marks[name][i] <= b else None

    evidence, contradicted = "spans", 0
    for c in calls:  # its own bounds
        lo, hi = c["begin"] - c["first_op"], c["done"] - c["last_op"]
        enq = first_in(ENQUEUE_EVENT, c["begin"], c["done"])
        if enq is not None:
            ends = [t for t in (first_in(name, enq, c["done"])
                                for name in DONE_EVENTS) if t is not None]
            r_lo = enq - c["first_op"]
            r_hi = min(ends, default=c["done"]) - c["last_op"]
            if r_lo <= r_hi:
                lo, hi, evidence = r_lo, r_hi, "runtime"
            else:
                contradicted += 1
        c["bounds"] = (lo, hi)
    begins = [c["begin"] for c in calls]
    for c in calls:  # its neighbours' too, while they agree
        near = calls[bisect.bisect_left(begins, c["begin"] - CLOCK_WINDOW_NS):
                     bisect.bisect_right(begins, c["begin"] + CLOCK_WINDOW_NS)]
        lo = max(d["bounds"][0] for d in near)
        hi = min(d["bounds"][1] for d in near)
        if lo > hi:
            lo, hi = c["bounds"]
        c["late"], c["slack"] = (lo + hi) // 2, (hi - lo) / 2
    lates = [c["late"] for c in calls]
    return {"device_late_us": sum(lates) / len(lates) / 1e3,
            "min_us": min(lates) / 1e3, "max_us": max(lates) / 1e3,
            "slack_us": sum(c["slack"] for c in calls) / len(calls) / 1e3,
            "evidence": evidence, "contradicted": contradicted}


def timeline(ops, modules, host_lines):
    """The reduction on plain tuples (so a hand-built timeline can be given):
    `ops` chip 0's (start, end, name) op events, `modules` its
    (start, end, executable name) module events, `host_lines` one list of
    (start, end, name) a host thread. None without a server thread."""
    server = [ln for ln in host_lines
              if any(n == SERVER_SPAN for _, _, n in ln)]
    if not server or not ops:
        return None
    thread = sorted((sp for sp in max(server, key=len)
                     if sp[2].startswith(scope_reduce.PROGRAM_SPANS)),
                    key=lambda x: (x[0], -x[1]))
    first = min(s for ln in host_lines for s, _, n in ln
                if n.startswith(scope_reduce.PROGRAM_SPANS))
    by_name = collections.defaultdict(list)
    for sp in thread:
        by_name[sp[2]].append(sp)
    t_lo = min(s for s, _, _ in ops)
    t_hi = max(e for _, e, _ in ops)
    calls, left_out = _pair(modules, by_name, t_lo, t_hi)
    clock = _clock(calls, host_lines) if calls else None
    recorded = [c["recorded"][0] for c in calls]

    def on_host(t):
        """A device instant on the host's clock: by the call nearest it."""
        if not calls:
            return t
        i = bisect.bisect_right(recorded, t)
        near = min(calls[max(i - 1, 0):i + 1], key=lambda c: max(
            c["recorded"][0] - t, t - c["recorded"][1], 0))
        return t + near["late"]

    for c in calls:
        c["first_op"] += c["late"]
        c["last_op"] += c["late"]

    layers = [_layer(
        (s, e, GC_SPAN) for s, e, _ in trace_reduce._union(sorted(
            sp for ln in host_lines for sp in ln if sp[2] == GC_SPAN)))]
    for name, a, b in (("return", "last_op", "done"),
                       ("launch", "begin", "first_op"),
                       ("device", "first_op", "last_op")):
        layers.append(_layer((c[a], c[b], name) for c in calls
                             if c[b] > c[a]))
    layers.append(_layer(
        (s, e, n[len(PREFIX):] if n.startswith(PREFIX) else n)
        for s, e, n in innermost_segments(thread)))

    merged = trace_reduce._union(sorted(ops))
    by_class = collections.Counter()
    for (_, e0, _), (s1, _, _) in zip(merged, merged[1:]):
        # scope_reduce's gaps, chosen as it chooses them
        if s1 - e0 < trace_reduce.MIN_GAP_NS or (e0 + s1) // 2 < first:
            continue
        pieces = [(on_host(e0), on_host(s1))]
        for layer in layers:
            pieces = _take(pieces, layer, by_class)
        by_class["unattributed"] += sum(b - a for a, b in pieces)
    by_class = +by_class  # no empty classes

    per_kind = {}
    for kind in ("decode", "prefill"):
        mine = [(k, c) for k, c in enumerate(calls) if c["kind"] == kind]
        if not mine:
            continue
        lags = {
            "dispatch_us": [c["dispatched"] - c["begin"] for _, c in mine
                            if c["dispatched"] is not None],
            "launch_us": [c["first_op"] - c["begin"] for _, c in mine],
            "return_us": [c["done"] - c["last_op"] for _, c in mine],
            "turnaround_us": [c["begin"] - calls[k - 1]["done"]
                              for k, c in mine if k > 0]}
        per_kind[kind] = {"n": len(mine), **{
            name: _lags(values) for name, values in lags.items() if values}}
    steps = sum(1 for s, _, _ in by_name[CALLS["serving_decode"][1]]
                if s <= on_host(t_hi))
    return {"idle_s": sum(by_class.values()) / 1e9,
            "by_class": [[n, ns / 1e9] for n, ns in by_class.most_common()],
            "steps": steps, "calls": per_kind, "clock": clock,
            "left_out": left_out, "n_server_spans": len(thread)}


def reduce(path):
    data = trace_reduce._load(path)
    planes = trace_reduce._device_planes(data)
    if not planes:
        raise SystemExit(f"step_timeline: no device plane in {path}")
    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
           for e in scope_reduce._line(planes[0], trace_reduce.OPS_LINE)]
    modules = [(e.start_ns, e.start_ns + e.duration_ns,
                scope_reduce.module_name(e.name))
               for e in scope_reduce._line(planes[0],
                                           scope_reduce.MODULES_LINE)]
    return timeline(ops, modules, list(_host_lines(data)))


def seconds(red, *classes):
    """Seconds of the named classes of a reduction."""
    return sum(s for n, s in red["by_class"] if n in classes)


def table(red):
    """The reduction as lines, for a run's log."""
    n = red["steps"]
    per = (lambda s: f" ({1e3 * s / n:.4f} ms a step)") if n else \
        (lambda s: "")
    lines = [f"step_timeline idle {red['idle_s']:.6f} s over {n} decode "
             f"steps begun{per(red['idle_s'])}, {red['left_out']} "
             f"executable events left out, by class:"]
    for name, s in red["by_class"]:
        share = 100 * s / red["idle_s"] if red["idle_s"] else 0.0
        lines.append(f"step_timeline   {name:<14s} {s:.6f} s "
                     f"{share:6.2f} %{per(s)}")
    if red["clock"]:
        c = red["clock"]
        lines.append(
            f"step_timeline clock: device events {c['device_late_us']:.1f} "
            f"us late ({c['min_us']:.1f} to {c['max_us']:.1f} over the "
            f"trace; +- {c['slack_us']:.1f} a call: that much of launch may "
            f"be return or the other way), bounded by "
            + {"runtime": "the TPU runtime's host events",
               "spans": "the calls' spans alone"}[c["evidence"]]
            + (f"; {c['contradicted']} calls' runtime events contradicted "
               f"each other" if c["contradicted"] else ""))
    for kind, rec in red["calls"].items():
        lines.append(
            f"step_timeline calls {kind}: {rec['n']} paired; " + "; ".join(
                f"{k} mean {v['mean']:.1f} p95 {v['p95']:.1f}"
                for k, v in rec.items() if k != "n"))
    return lines


def of_run(run):
    """This run's reduction, made once and said on earlier lines; None when
    the run has no device trace or the trace no server thread."""
    if "step_timeline" not in run:
        run["step_timeline"] = None
        if run.get("trace"):
            red = run["step_timeline"] = reduce(
                trace_reduce.find_xplane(run["tracer"].dir))
            for line in table(red) if red else ():
                run["say"](line)
    return run["step_timeline"]


def per_step_ms(run, *classes):
    """Idle ms a decode step begun in the stretch under the named classes;
    None without a reduction or a step."""
    red = of_run(run)
    if not red or not red["steps"]:
        return None
    return 1e3 * seconds(red, *classes) / red["steps"]


def selfcheck():
    """The recorded stretch under testdata/ must reduce to the recorded
    numbers (times to the nanosecond, names letter for letter), and its
    classes must sum to scope_reduce's idle seconds of the same trace."""
    with open(os.path.join(HERE, "testdata", "step_expected.json")) as f:
        cases = json.load(f)["cases"]
    bad = []
    for want in cases:
        path = os.path.join(HERE, "testdata", want["file"])
        got = json.loads(json.dumps(reduce(path)))  # as the file holds it
        for key in ("idle_s", "by_class", "steps", "calls", "clock",
                    "left_out", "n_server_spans"):
            if scope_reduce._rounded(got[key]) \
                    != scope_reduce._rounded(want[key]):
                bad.append(f"{want['file']}: {key}: {got[key]!r} != "
                           f"{want[key]!r}")
        whole = scope_reduce.reduce(path)["idle"]["seconds"]
        if abs(got["idle_s"] - whole) > 0.01 * whole:
            bad.append(f"{want['file']}: classes sum to {got['idle_s']!r}, "
                       f"scope_reduce's idle is {whole!r}")
        print("\n".join(table(got)))
    if bad:
        print("step_timeline selfcheck FAILED:\n  " + "\n  ".join(bad))
        return 1
    print("step_timeline selfcheck ok")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduce")
    ap.add_argument("--selfcheck", action="store_true")
    args = ap.parse_args(argv)
    if args.reduce:
        red = reduce(args.reduce)
        print(json.dumps(red, indent=1))
        if red:
            print("\n".join(table(red)))
    if args.selfcheck:
        return selfcheck()
    return 0


if __name__ == "__main__":
    sys.exit(main())
