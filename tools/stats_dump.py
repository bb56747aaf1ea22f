#!/usr/bin/env python
"""Pretty-print paddle_tpu observability artifacts.

Accepts any of:
  * a chrome-trace JSON exported by `profiler.export_chrome_tracing`
    (host spans + embedded telemetry snapshot),
  * a log / JSONL stream containing `{"metric": "telemetry"}` lines,
  * a bare counters/snapshot JSON dict.

Pure stdlib on purpose — no paddle_tpu / jax import, so it runs anywhere
the artifact landed (CI box, laptop) in milliseconds.

Usage:
    python tools/stats_dump.py /tmp/paddle_tpu_profile/worker0.json
    python tools/stats_dump.py bench_output.log
    python tools/stats_dump.py --traces fleet_trace.json   # per-request
                                                           # waterfall
"""
from __future__ import annotations

import argparse
import json
import sys


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024


def _print_counters(counters, indent="  "):
    if not counters:
        return
    width = max(len(k) for k in counters)
    for k in sorted(counters):
        v = counters[k]
        shown = _fmt_bytes(v) if k.endswith(".bytes") else v
        print(f"{indent}{k:<{width}}  {shown}")


def _print_timings(timings, indent="  "):
    if not timings:
        return
    width = max(len(k) for k in timings)
    print(f"{indent}{'name':<{width}}  {'count':>8} {'total_ms':>12} "
          f"{'mean_ms':>10}")
    for k in sorted(timings):
        rec = timings[k]
        print(f"{indent}{k:<{width}}  {rec.get('count', 0):>8} "
              f"{rec.get('total_s', 0.0) * 1e3:>12.3f} "
              f"{rec.get('mean_ms', 0.0):>10.3f}")


_FT_PREFIXES = ("checkpoint.", "fault.")
_SERVING_PREFIXES = ("serving.",)
_SPMD_PREFIXES = ("spmd.",)
# the train→serve resilience loop (ISSUE 7) cuts across the serving,
# checkpoint and fault scopes; its counters get one section so an operator
# can read the whole loop's health (reshard → hot-swap → replica replay /
# autoscale) at a glance instead of stitching three tables
_TRAIN_SERVE_KEYS = frozenset((
    "checkpoint.sharded_saves", "checkpoint.reshard_loads",
    "serving.weight_swaps", "serving.swap_failures",
    "serving.reprimes", "serving.step_retries",
    "serving.requeued_requests", "serving.replica_restarts",
    "serving.replicas_retired", "serving.scale_ups",
    "serving.scale_downs", "serving.replicas",
    "fault.elastic.generation_bumps"))


def _print_fastpath(counters, gauges):
    """Replay-fast-path health (ISSUE 9): hit rate, audit cadence and
    demotion causes — the three numbers that say whether the steady
    window really ran with zero per-op Python."""
    fp = {k: counters.pop(k) for k in list(counters)
          if k.startswith("fastpath.")}
    fp.update({k: gauges.pop(k) for k in list(gauges)
               if k.startswith("fastpath.")})
    if not fp:
        return
    print("fast path (replay-by-signature):")
    hits = fp.get("fastpath.hits", 0)
    misses = fp.get("fastpath.misses", 0)
    audits = fp.get("fastpath.audit_runs", 0)
    if hits + misses:
        fp.setdefault("fastpath.hit_rate",
                      round(hits / (hits + misses), 4))
    if audits:
        fp["fastpath.steps_per_audit"] = round((hits + misses) / audits, 1)
    causes = {k: v for k, v in fp.items()
              if k.startswith("fastpath.demote.")}
    _print_counters({k: v for k, v in fp.items() if k not in causes})
    if causes:
        print("  demotion causes:")
        _print_counters(causes, indent="    ")


# elastic training loop (ISSUE 13): heartbeat misses, hang trips,
# resizes and fenced zombies are the preemption-survival story — one
# table answers "did the job stay up, and what did it cost"
_ELASTIC_KEYS_PREFIX = "fault.elastic."


def _print_elastic(counters, gauges):
    keys = [k for k in counters if k.startswith(_ELASTIC_KEYS_PREFIX)
            and k != "fault.elastic.generation_bumps"]
    if not any(counters[k] for k in keys):
        # an un-elastic run keeps its zero-initialized keys in the
        # fault-tolerance table below (a dedicated all-zero section
        # would imply the loop ran); any non-zero activity claims the
        # whole group — the remaining zeros ARE the story then (e.g.
        # resizes>0 with fenced_zombies=0 means no zombie ever formed)
        return
    el = {k: counters.pop(k) for k in keys}
    print("elastic training:")
    _print_counters(el)


_FLEET_PREFIXES = ("fleet.",)
_FLEET_HANDOFF_KEYS = frozenset(("serving.handoff_exports",
                                 "serving.handoff_imports"))


def _print_fleet(counters, gauges):
    """Serving-fleet health (ISSUE 11): per-pod restarts/retirements,
    orphan replays (every one is a request that survived a pod death),
    the routing hit rate (how often prefix affinity landed traffic on
    its sticky pod), and the disaggregation handoff counts."""
    fl = {k: counters.pop(k) for k in list(counters)
          if k.startswith(_FLEET_PREFIXES) or k in _FLEET_HANDOFF_KEYS}
    fl.update({k: gauges.pop(k) for k in list(gauges)
               if k.startswith(_FLEET_PREFIXES)})
    if not fl:
        return
    print("serving fleet:")
    hits = fl.get("fleet.affinity_hits", 0)
    total = hits + fl.get("fleet.affinity_misses", 0)
    if total:
        fl.setdefault("fleet.routing_hit_rate", round(hits / total, 4))
    _print_counters(fl)


_SPEC_PREFIXES = ("serving.spec_", "serving.draft_")
_SPEC_KEYS = frozenset(("serving.verify_compiles",
                        "serving.chunked_prefills",
                        "serving.prefill_chunks"))


def _print_spec(counters, gauges):
    """Speculative-decode + chunked-prefill health (ISSUE 12): the
    acceptance rate and mean accepted length say how much the drafter is
    actually buying (1.0 tokens/round = plain-decode speed, K+1 =
    perfect drafter); verify_compiles must stay at one per engine, and
    the chunk counters say whether long prompts really interleaved."""
    sp = {k: counters.pop(k) for k in list(counters)
          if k.startswith(_SPEC_PREFIXES) or k in _SPEC_KEYS}
    sp.update({k: gauges.pop(k) for k in list(gauges)
               if k.startswith(_SPEC_PREFIXES)})
    if not sp:
        return
    print("speculative decode (draft-verify):")
    proposed = sp.get("serving.spec_proposed", 0)
    if proposed:
        sp.setdefault("serving.spec_acceptance_rate",
                      round(sp.get("serving.spec_accepted", 0)
                            / proposed, 4))
    rounds = sp.get("serving.spec_slot_rounds", 0)
    if rounds:
        sp.setdefault("serving.spec_accepted_len_mean",
                      round(sp.get("serving.spec_emitted", 0)
                            / rounds, 2))
    _print_counters(sp)


_PP_PREFIXES = ("pp.",)


def _print_pipeline(counters, gauges):
    """Pipeline-in-one-executable health (ISSUE 15): stages x
    layers-per-stage topology, microbatch count, the static
    stage-transfer (collective-permute) traffic estimate, and per-stage
    donation — stage_classes_donated < stage_classes_carried means some
    stacked stage param re-allocates every step."""
    pl = {k: counters.pop(k) for k in list(counters)
          if k.startswith(_PP_PREFIXES)}
    pl.update({k: gauges.pop(k) for k in list(gauges)
               if k.startswith(_PP_PREFIXES)})
    if not any(pl.values()):
        return
    print("pipeline (spmd pp):")
    carried = pl.get("pp.stage_classes_carried", 0)
    donated = pl.get("pp.stage_classes_donated", 0)
    if carried:
        pl.setdefault("pp.stage_donation_rate",
                      round(donated / carried, 4))
    _print_counters(pl)


_MESH_SERVING_PREFIXES = ("serving.mesh.", "serving.spec_acceptance.")
_MESH_SERVING_KEYS = frozenset(("serving.spec_mesh_refused",
                                "serving.draft_swaps"))


def _print_mesh_serving(counters, gauges):
    """Mesh-sharded serving health (ISSUE 16): which per-shard kernel
    each engine resolved to (sharded=0 on an mp>1 mesh means the fused
    route demoted — indivisible heads), residual spec-engine mesh
    refusals, drafter hot-swaps, and the spec acceptance rate PER WEIGHT
    GENERATION — a post-swap generation whose acceptance does not
    recover means the drafter was not swapped along with the target."""
    ms = {k: counters.pop(k) for k in list(counters)
          if k.startswith(_MESH_SERVING_PREFIXES)
          or k in _MESH_SERVING_KEYS}
    ms.update({k: gauges.pop(k) for k in list(gauges)
               if k.startswith(_MESH_SERVING_PREFIXES)
               or k in _MESH_SERVING_KEYS})
    if not any(bool(v) for v in ms.values()):
        return
    print("mesh serving:")
    _print_counters(ms)


_KERNEL_PREFIXES = ("serving.kernel.", "kernel.", "serving.prefill_kernel",
                    "serving.prefill_flash_calls")


def _print_kernels(counters, gauges):
    """Hot-path kernel selection (ISSUE 14): which implementation each
    family resolved to — serving.kernel.{pallas,xla,interpret} for the
    paged decode/verify family (one bump per engine build), kernel.flash.*
    for the training flash family (one per trace) — plus the fallback
    count; any nonzero serving.kernel.fallbacks means a Pallas-eligible
    call dropped to the gather path (profiler.explain() names why). Beside
    them what the prompt span reads through (gauge serving.prefill_kernel)
    and how many prefill calls ran `flash_prefill`."""
    kn = {k: counters.pop(k) for k in list(counters)
          if k.startswith(_KERNEL_PREFIXES)}
    kn.update({k: gauges.pop(k) for k in list(gauges)
               if k.startswith(_KERNEL_PREFIXES)})
    if not any(v for v in kn.values() if v != "xla"):
        return
    print("kernels:")
    _print_counters(kn)


_MOE_PREFIXES = ("moe.",)


def _print_moe(counters, gauges, hists):
    """Expert-load health (ISSUE 20): per-expert kept-token counts, the
    assigned/kept/dropped totals and the drop fraction say whether the
    router is balanced and how much the capacity factor is costing; the
    expert_load_frac histogram (each expert's share of kept tokens per
    audit) piles into the 1/E bucket under uniform load and spreads
    toward 1.0 when one expert goes hot."""
    mo = {k: counters.pop(k) for k in list(counters)
          if k.startswith(_MOE_PREFIXES)}
    mo.update({k: gauges.pop(k) for k in list(gauges)
               if k.startswith(_MOE_PREFIXES)})
    mh = {k: hists.pop(k) for k in list(hists)
          if k.startswith(_MOE_PREFIXES)}
    if not mo and not mh:
        return
    print("expert load (moe routing):")
    assigned = mo.get("moe.tokens_assigned", 0)
    if assigned:
        mo.setdefault("moe.drop_fraction",
                      round(mo.get("moe.tokens_dropped", 0)
                            / assigned, 4))
    _print_counters(mo)
    for k in sorted(mh):
        h = mh[k]
        # not a latency: mean_ms is the mean load fraction x 1e3 by
        # construction of the shared log2 histogram — undo the scale
        print(f"  {k}  count={h.get('count', 0)} "
              f"mean_load={h.get('mean_ms', 0.0) / 1e3:.4f}")


_KV_POOL_PREFIXES = ("serving.prefix_", "serving.kv_blocks")
_KV_POOL_KEYS = frozenset(("serving.pool_exhausted",))


def _print_kv_pool(counters, gauges):
    """Paged-KV + prefix-cache health (ISSUE 10): the hit rate and the
    blocks-in-use high-water mark say whether shared-prompt traffic is
    actually sharing, and pool_exhausted says whether admission is
    backpressuring on cache memory."""
    kv = {k: counters.pop(k) for k in list(counters)
          if k.startswith(_KV_POOL_PREFIXES) or k in _KV_POOL_KEYS}
    kv.update({k: gauges.pop(k) for k in list(gauges)
               if k.startswith(_KV_POOL_PREFIXES) or k in _KV_POOL_KEYS})
    if not kv:
        return
    print("kv pool (paged + prefix cache):")
    hits = kv.get("serving.prefix_hits", 0)
    misses = kv.get("serving.prefix_misses", 0)
    if hits + misses:
        kv.setdefault("serving.prefix_hit_rate",
                      round(hits / (hits + misses), 4))
    _print_counters(kv)


def _print_hists(hists, indent="  "):
    """Latency histograms (ISSUE 18): fixed log2 buckets, so p50/p99
    are conservative upper-edge estimates — cheap enough to be on for
    every request, honest enough to alarm on."""
    if not hists:
        return
    print("latency histograms (log2 buckets):")
    width = max(len(k) for k in hists)
    print(f"{indent}{'name':<{width}}  {'count':>8} {'mean_ms':>10} "
          f"{'p50_ms':>10} {'p99_ms':>10}")
    for k in sorted(hists):
        h = hists[k]
        print(f"{indent}{k:<{width}}  {h.get('count', 0):>8} "
              f"{h.get('mean_ms', 0.0):>10.3f} "
              f"{h.get('p50_ms', 0.0):>10.3f} "
              f"{h.get('p99_ms', 0.0):>10.3f}")


def _print_snapshot(snap):
    counters = dict(snap.get("counters") or {})
    timings = dict(snap.get("timings") or {})
    gauges = dict(snap.get("gauges") or {})
    hists = dict(snap.get("hists") or {})
    # replay fast path (ISSUE 9) leads: if the hit rate is low or the
    # demotion causes are busy, every other per-step number below is
    # measuring the slow path
    _print_fastpath(counters, gauges)
    # sharding / SPMD lowering (ISSUE 6) first among the specialist
    # sections: step_compiles and python_collectives_per_step ARE the
    # one-compilation health check (1-2 compiles total, 0 per-step
    # Python collectives in steady state)
    sp_counters = {k: counters.pop(k) for k in list(counters)
                   if k.startswith(_SPMD_PREFIXES)}
    if sp_counters:
        print("sharding (spmd):")
        _print_counters(sp_counters)
    # pipeline (ISSUE 15) right after the spmd section: the pp step IS a
    # captured spmd plan, so its topology/donation line reads best next
    # to step_compiles / python_collectives_per_step
    _print_pipeline(counters, gauges)
    # train→serve loop (ISSUE 7) before the per-subsystem sections: these
    # keys are claimed here so serving/fault-tolerance below show pure
    # steady-state health and this section shows pure resilience events
    ts_counters = {k: counters.pop(k) for k in list(counters)
                   if k in _TRAIN_SERVE_KEYS}
    ts_gauges = {k: gauges.pop(k) for k in list(gauges)
                 if k in _TRAIN_SERVE_KEYS}
    if ts_counters or ts_gauges:
        print("train->serve loop:")
        _print_counters(ts_counters)
        _print_counters(ts_gauges)
    # elastic training loop (ISSUE 13) claims its fault.elastic.* keys
    # before the fault-tolerance table: heartbeat misses / hang trips /
    # resizes / fenced zombies read as one preemption-survival story
    _print_elastic(counters, gauges)
    # serving fleet (ISSUE 11) before the per-subsystem serving tables:
    # pod restarts / orphan replays / routing hit rate are the
    # cross-process resilience story, read as one table
    _print_fleet(counters, gauges)
    # mesh serving (ISSUE 16) claims its serving.mesh.* gauges and the
    # spec-engine mesh counters before the kernel/spec tables: the
    # per-shard kernel route and per-generation acceptance are one
    # story
    _print_mesh_serving(counters, gauges)
    # kernel selection (ISSUE 14) claims serving.kernel.* / kernel.*
    # before the serving table: which paged/flash implementation is
    # actually running, and whether anything fell back to the slow path
    _print_kernels(counters, gauges)
    # speculative decode (ISSUE 12) claims its serving.* keys before
    # the kv-pool/serving tables: acceptance rate and chunk counts are
    # the draft-verify subsystem's health line
    _print_spec(counters, gauges)
    # expert load (ISSUE 20) claims its moe.* counters/gauges AND its
    # moe.* histogram before the latency table: the load-fraction
    # histogram is a distribution over shares, not a latency
    _print_moe(counters, gauges, hists)
    # kv pool (ISSUE 10) claims its serving.* keys before the general
    # serving section so cache-memory health reads as one table
    _print_kv_pool(counters, gauges)
    # serving telemetry (ISSUE 5) first: TTFT / tokens-per-sec / occupancy
    # are the operator's serving health triple, pulled out of the general
    # tables (counters, timings AND the throughput/occupancy gauges)
    sv_counters = {k: counters.pop(k) for k in list(counters)
                   if k.startswith(_SERVING_PREFIXES)}
    sv_timings = {k: timings.pop(k) for k in list(timings)
                  if k.startswith(_SERVING_PREFIXES)}
    sv_gauges = {k: gauges.pop(k) for k in list(gauges)
                 if k.startswith(_SERVING_PREFIXES)}
    if sv_counters or sv_timings or sv_gauges:
        print("serving:")
        _print_counters(sv_counters)
        _print_counters(sv_gauges)
        _print_timings(sv_timings)
    # fault-tolerance telemetry (ISSUE 4) gets its own section: recovery
    # counters and checkpoint save/restore timings are the first thing an
    # operator wants after a preemption, not buried in the general table
    ft_counters = {k: counters.pop(k) for k in list(counters)
                   if k.startswith(_FT_PREFIXES)}
    ft_timings = {k: timings.pop(k) for k in list(timings)
                  if k.startswith(_FT_PREFIXES)}
    if ft_counters or ft_timings:
        print("fault tolerance:")
        _print_counters(ft_counters)
        _print_timings(ft_timings)
    if counters:
        print("counters:")
        _print_counters(counters)
    if gauges:
        print("gauges:")
        _print_counters(gauges)
    if timings:
        print("timings:")
        _print_timings(timings)
    _print_hists(hists)


def _dump_waterfall(doc):
    """Per-request waterfall (ISSUE 18): group the merged fleet trace's
    "X" events by their request trace_id and print each request's spans
    in causal order across every process — the one joined view of a
    request's life."""
    procs = {}
    for e in doc.get("traceEvents", ()):
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e.get("pid")] = (e.get("args") or {}).get(
                "name", str(e.get("pid")))
    traces = {}
    for e in doc.get("traceEvents", ()):
        if e.get("ph") != "X":
            continue
        tid = (e.get("args") or {}).get("trace_id") or "(untraced)"
        traces.setdefault(tid, []).append(e)
    if not traces:
        print("no spans in trace")
        return
    bar_w = 40
    for tid in sorted(traces):
        evs = sorted(traces[tid],
                     key=lambda e: (float(e.get("ts", 0.0)),
                                    float(e.get("dur", 0.0))))
        t0 = min(float(e.get("ts", 0.0)) for e in evs)
        t1 = max(float(e.get("ts", 0.0)) + float(e.get("dur", 0.0))
                 for e in evs)
        total = max(t1 - t0, 1e-9)
        print(f"trace {tid}  ({len(evs)} spans, "
              f"{len({e.get('pid') for e in evs})} processes, "
              f"{total / 1e3:.3f}ms)")
        w = max(len(f"{procs.get(e.get('pid'), e.get('pid'))}:"
                    f"{e.get('name', '?')}") for e in evs)
        for e in evs:
            ts = float(e.get("ts", 0.0))
            dur = float(e.get("dur", 0.0))
            lead = int((ts - t0) / total * bar_w)
            fill = max(1, int(dur / total * bar_w))
            bar = " " * lead + "#" * min(fill, bar_w - lead)
            label = (f"{procs.get(e.get('pid'), e.get('pid'))}:"
                     f"{e.get('name', '?')}")
            args = e.get("args") or {}
            extra = ""
            if args.get("bytes") is not None:
                extra = f"  {_fmt_bytes(int(args['bytes']))}"
                if args.get("attempt", 1) not in (1, None):
                    extra += f" (attempt {args['attempt']})"
            print(f"  {label:<{w}}  [{bar:<{bar_w}}] "
                  f"+{(ts - t0) / 1e3:>9.3f}ms {dur / 1e3:>9.3f}ms"
                  f"{extra}")
        # the data-plane cost of this request: time + bytes its KV
        # handoff spent on the wire (frame_tx spans, ISSUE 19)
        tx = [e for e in evs if e.get("name") == "frame_tx"]
        if tx:
            nbytes = sum(int((e.get("args") or {}).get("bytes", 0))
                         for e in tx)
            wire_ms = sum(float(e.get("dur", 0.0)) for e in tx) / 1e3
            print(f"  handoff wire: {len(tx)} bundle(s), "
                  f"{_fmt_bytes(nbytes)}, {wire_ms:.3f}ms on the wire")


def _dump_trace(doc):
    spans = {}
    for e in doc.get("traceEvents", ()):
        if e.get("ph") != "X":
            continue
        rec = spans.setdefault(e.get("name", "?"), [0, 0.0])
        rec[0] += 1
        rec[1] += float(e.get("dur", 0.0)) / 1e3
    if spans:
        print("host spans:")
        width = max(len(k) for k in spans)
        print(f"  {'name':<{width}}  {'count':>8} {'total_ms':>12} "
              f"{'avg_ms':>10}")
        for name, (cnt, tot) in sorted(spans.items(), key=lambda kv:
                                       -kv[1][1]):
            print(f"  {name:<{width}}  {cnt:>8} {tot:>12.3f} "
                  f"{tot / cnt:>10.3f}")
    else:
        print("host spans: (none)")
    meta = doc.get("paddle_tpu", {})
    if meta:
        steps = meta.pop("step_times_ms", None)
        _print_snapshot(meta)
        if steps:
            print(f"steps: {len(steps)} "
                  f"avg={sum(steps) / len(steps):.3f}ms")


def _dump_jsonl(path):
    found = 0
    with open(path) as f:
        for ln in f:
            ln = ln.strip()
            if not ln:
                continue
            try:
                rec = json.loads(ln)
            except ValueError:
                continue
            if isinstance(rec, dict) and rec.get("metric") == "telemetry":
                found += 1
                print(f"-- telemetry record #{found} --")
                _print_snapshot(rec)
    return found


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="trace JSON / telemetry JSONL / "
                                 "counters dict")
    ap.add_argument("--traces", action="store_true",
                    help="render the per-request waterfall (spans "
                         "grouped by trace_id across processes) instead "
                         "of the aggregate span table")
    args = ap.parse_args(argv)
    try:
        with open(args.path) as f:
            doc = json.load(f)
    except ValueError:
        # not one JSON document: scan it as a JSONL/log stream
        if not _dump_jsonl(args.path):
            print(f"{args.path}: no JSON document and no telemetry lines",
                  file=sys.stderr)
            return 1
        return 0
    if args.traces:
        if not (isinstance(doc, dict) and "traceEvents" in doc):
            print(f"{args.path}: --traces needs a chrome-trace JSON "
                  "(no traceEvents key)", file=sys.stderr)
            return 1
        _dump_waterfall(doc)
        return 0
    if isinstance(doc, dict) and "traceEvents" in doc:
        _dump_trace(doc)
    elif isinstance(doc, dict) and ("counters" in doc or "timings" in doc
                                    or "gauges" in doc):
        _print_snapshot(doc)
    elif isinstance(doc, dict):
        _print_counters(doc, indent="")
    else:
        print(f"{args.path}: unrecognized JSON shape", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
