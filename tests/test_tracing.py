"""Fleet-wide request tracing + metrics plane (ISSUE 18).

Covers the acceptance gates:
  * deterministic trace ids from the router-pinned seed (an orphan
    replay joins the SAME trace);
  * bounded span ring, zero-cost when disabled, drain-and-ship wire
    shape;
  * log2 latency histograms: bucket placement, conservative quantiles,
    fleet-side merge; the timing reservoir stays capped (the unbounded-
    growth satellite);
  * spec-acceptance per-generation gauges bounded by the historic
    rollup (the gauge key-leak satellite);
  * flight recorder ring + dump/load round-trip;
  * FleetTraceCollector clock alignment and chrome-trace shape,
    loadable by load_profiler_result and rendered by
    tools/stats_dump.py --traces;
  * the REAL cross-pod round-trip: a disaggregated prefill→decode fleet
    request produces ONE merged trace with a single trace_id spanning
    router + both pod subprocesses, causally ordered.
"""
import json
import os
import subprocess
import sys
import time

import pytest

from paddle_tpu.profiler import registry, tracing

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.disable()
    tracing.drain_spans()
    tracing.flight_clear()
    yield
    tracing.disable()
    tracing.drain_spans()
    tracing.flight_clear()


class TestTraceIds:
    def test_deterministic_and_distinct(self):
        a = tracing.trace_id_for_seed(7)
        assert a == tracing.trace_id_for_seed(7)
        assert len(a) == 16 and int(a, 16) >= 0
        ids = {tracing.trace_id_for_seed(s) for s in range(256)}
        assert len(ids) == 256  # splitmix64 never collides this small

    def test_matches_router_and_scheduler_derivation(self):
        # router, scheduler and engine all derive independently from the
        # seed — one function, one answer, or the trace splits
        from paddle_tpu.serving.scheduler import GenerationRequest

        req = GenerationRequest([1, 2, 3], seed=42)
        assert req.trace_id is None  # derived at submit, not construction
        assert tracing.trace_id_for_seed(42) \
            == tracing.trace_id_for_seed(42)


class TestSpanRing:
    def test_disabled_records_nothing(self):
        tracing.add_span("t", "x", 0.0, 1.0)
        with tracing.span("t", "y"):
            pass
        assert tracing.pending_spans() == 0

    def test_enabled_bounded_and_drained(self):
        tracing.enable(capacity=4)
        for i in range(7):
            tracing.add_span("t", f"s{i}", float(i), float(i) + 0.5)
        assert tracing.pending_spans() == 4
        assert tracing.spans_dropped() == 3
        wire = tracing.drain_spans()
        assert len(wire) == 4 and tracing.pending_spans() == 0
        assert tracing.spans_dropped() == 0  # drain resets the counter
        # wire shape: [trace_id, name, tid, t0, t1] — JSON-serializable
        json.dumps(wire)
        trace_id, name, tid, t0, t1 = wire[0]
        assert (trace_id, name) == ("t", "s0") and t1 > t0

    def test_span_context_manager(self):
        tracing.enable()
        with tracing.span("abc", "work"):
            pass
        ((trace_id, name, _tid, t0, t1),) = tracing.drain_spans()
        assert (trace_id, name) == ("abc", "work") and t1 >= t0


class TestHistograms:
    def test_bucket_placement_and_quantiles(self):
        registry.reset("histtest")
        for ms in (1.0, 1.0, 1.0, 100.0):
            registry.hist_record("lat", ms / 1e3, scope="histtest")
        snap = registry.histograms("histtest")["histtest.lat"]
        assert snap["count"] == 4
        assert abs(snap["total_s"] - 0.103) < 1e-9
        # log2 upper-edge estimates are conservative: within 2x above
        assert 1.0 <= snap["p50_ms"] <= 2.0
        assert 100.0 <= snap["p99_ms"] <= 200.0
        registry.reset("histtest")

    def test_extreme_values_clamp(self):
        registry.reset("histtest")
        registry.hist_record("lat", 0.0, scope="histtest")
        registry.hist_record("lat", -1.0, scope="histtest")
        registry.hist_record("lat", 1e12, scope="histtest")
        snap = registry.histograms("histtest")["histtest.lat"]
        assert snap["count"] == 3
        assert sum(snap["buckets"].values()) == 3
        registry.reset("histtest")

    def test_merge_is_bucketwise(self):
        registry.reset("histtest")
        registry.hist_record("lat", 0.001, scope="histtest")
        a = registry.histograms("histtest")["histtest.lat"]
        registry.reset("histtest")
        registry.hist_record("lat", 0.1, scope="histtest")
        b = registry.histograms("histtest")["histtest.lat"]
        merged = registry.hist_merge({}, a)
        registry.hist_merge(merged, b)
        assert merged["count"] == 2
        assert sum(merged["buckets"].values()) == 2
        assert merged["p99_ms"] >= 100.0
        registry.reset("histtest")

    def test_snapshot_carries_hists(self):
        registry.hist_record("x", 0.01, scope="histtest")
        snap = registry.snapshot()
        assert "histtest.x" in snap["hists"]
        registry.reset("histtest")
        assert "histtest.x" not in registry.snapshot()["hists"]


class TestTimingReservoirBounded:
    """The unbounded-growth satellite: timings() once appended every
    observation to a list — a serving process recording ttft per request
    grew without bound. Now: exact count/total + a capped reservoir."""

    def test_reservoir_caps_and_stats_stay_exact(self):
        registry.reset("restest")
        n = registry.RESERVOIR_CAP * 40
        for i in range(n):
            registry.timing("t", 0.001, scope="restest")
        rec = registry._timing_scopes["restest"]["t"]
        assert len(rec[2]) == registry.RESERVOIR_CAP  # bounded
        out = registry.timings("restest")["restest.t"]
        assert out["count"] == n  # exact despite sampling
        assert abs(out["total_s"] - n * 0.001) < 1e-6
        assert out["p50_ms"] > 0 and out["p99_ms"] >= out["p50_ms"]
        registry.reset("restest")


class TestSpecAcceptanceGaugeRetention:
    """The gauge key-leak satellite: one serving.spec_acceptance.gen<N>
    gauge per weight swap grew the registry forever on a long-lived
    server. Only the last K generations keep live gauges; older ones
    fold into .historic."""

    def test_retire_folds_into_historic(self):
        from paddle_tpu.serving.spec_decode import (
            SPEC_ACCEPT_KEEP_GENERATIONS, DraftVerifyEngine)

        eng = DraftVerifyEngine.__new__(DraftVerifyEngine)
        eng._gen_accept = {g: [g + 1, 10] for g in range(10)}
        eng._accept_historic = [0, 0]
        for g in range(10):
            registry.gauge_set(f"serving.spec_acceptance.gen{g}", 0.5)
        eng._retire_old_generations()
        assert len(eng._gen_accept) == SPEC_ACCEPT_KEEP_GENERATIONS
        assert sorted(eng._gen_accept) == [6, 7, 8, 9]  # newest kept
        gauges = registry.gauges()
        for g in range(6):
            assert f"serving.spec_acceptance.gen{g}" not in gauges
        # historic rollup = sum of the retired generations
        assert eng._accept_historic == [sum(g + 1 for g in range(6)), 60]
        assert gauges["serving.spec_acceptance.historic"] == round(
            eng._accept_historic[0] / 60, 4)
        for g in range(6, 10):
            registry.gauge_drop(f"serving.spec_acceptance.gen{g}")
        registry.gauge_drop("serving.spec_acceptance.historic")


class TestFlightRecorder:
    def test_ring_and_dump_round_trip(self, tmp_path):
        for i in range(5):
            tracing.flight("admit", rid=i, trace_id=f"t{i}", slot=i % 2)
        path = str(tmp_path / "flight.json")
        got = tracing.dump_flight_recorder(reason="unit test", path=path)
        assert got == path
        doc = tracing.load_flight_dump(path)
        assert doc["reason"] == "unit test"
        assert doc["pid"] == os.getpid()
        assert [e["rid"] for e in doc["events"]] == list(range(5))
        assert doc["events"][-1]["detail"] == {"slot": 0}
        # anchor + event wall times let a reader align the dump against
        # a merged trace
        assert doc["clock_anchor"] > 0

    def test_ring_is_bounded(self):
        for i in range(tracing._FLIGHT_CAP + 50):
            tracing.flight("e", rid=i)
        evs = tracing.flight_events()
        assert len(evs) == tracing._FLIGHT_CAP
        assert evs[-1]["rid"] == tracing._FLIGHT_CAP + 49  # newest kept

    def test_load_rejects_non_dump(self, tmp_path):
        p = tmp_path / "not_a_dump.json"
        p.write_text("{}")
        with pytest.raises(ValueError):
            tracing.load_flight_dump(str(p))


class TestClockAlignment:
    def test_offset_from_exchange_midpoint(self):
        # remote clock runs 100s behind: remote_now sampled at local
        # midpoint 5.0 reads -95.0 → offset +100 maps remote onto local
        assert tracing.offset_from_exchange(4.0, 6.0, -95.0) == 100.0

    def test_anchor_roundtrip(self):
        import time as _t

        a = tracing.clock_anchor()
        assert abs((a + tracing.clock()) - _t.time()) < 0.5


class TestFleetTraceCollector:
    def _collector(self):
        c = tracing.FleetTraceCollector()
        c.set_process("router", pid=100, offset=0.0)
        # pod's clock is 10s behind the router's: offset +10 aligns it
        c.add_spans("pod0", [["tr1", "prefill", 1, 1.0, 2.0]],
                    pid=200, offset=10.0)
        c.add_spans("router", [["tr1", "request", 1, 10.5, 13.0],
                               ["", "decode_iter", 1, 12.0, 12.1]])
        return c

    def test_alignment_and_grouping(self):
        c = self._collector()
        assert c.span_count() == 3
        tr = c.traces()
        assert set(tr) == {"tr1", ""}
        spans = tr["tr1"]
        # pod prefill lands INSIDE the router's request span once offset
        assert [s["name"] for s in spans] == ["request", "prefill"]
        assert spans[1]["t0"] == 11.0 and spans[1]["proc"] == "pod0"

    def test_chrome_trace_loadable_and_rendered(self, tmp_path):
        c = self._collector()
        path = str(tmp_path / "trace.json")
        c.write(path)
        from paddle_tpu.profiler import load_profiler_result

        load_profiler_result(path)  # raises on a bad shape
        doc = json.load(open(path))
        names = {e["args"]["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "M"}
        assert names == {"router", "pod0"}
        xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert {e.get("args", {}).get("trace_id")
                for e in xs} == {"tr1", None}
        assert doc["paddle_tpu"]["clock_offsets"]["pod0"] == 10.0
        # the stdlib-only dump tool renders the waterfall from the file
        out = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "stats_dump.py"),
             "--traces", path],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        assert "trace tr1" in out.stdout
        assert "pod0:prefill" in out.stdout
        assert "router:request" in out.stdout


CONFIG = dict(vocab_size=96, n_layer=2, n_head=2, d_model=48,
              seq_len=64, initializer_range=0.35)
MODEL_SPEC = {"kind": "gpt", "seed": 21, "config": CONFIG}
ENGINE_KW = dict(max_batch_size=2, buckets=[16], block_size=4,
                 rng_seed=0)


class TestCrossPodTraceMerge:
    """THE acceptance gate: a disaggregated fleet request produces ONE
    merged chrome trace — a single trace_id whose spans come from three
    real processes (router + prefill pod + decode pod), causally
    ordered on the router's clock."""

    def test_disagg_request_one_trace_three_processes(self, tmp_path):
        from proc_utils import proc_timeout

        from paddle_tpu.serving.fleet import ServingFleet

        tracing.enable()
        fleet = ServingFleet(MODEL_SPEC, roles=["prefill", "decode"],
                             engine=ENGINE_KW,
                             connect_timeout=proc_timeout(120))
        try:
            fleet.start()
            seed = 5
            tokens = fleet.generate([3, 5, 7, 9, 11, 2, 4, 6],
                                    max_new_tokens=4, seed=seed,
                                    result_timeout=proc_timeout(120))
            assert len(tokens) == 4
            path = str(tmp_path / "fleet_trace.json")
            fleet.collect_trace(path)
        finally:
            fleet.shutdown(drain=False)
            tracing.disable()

        from paddle_tpu.profiler import load_profiler_result

        load_profiler_result(path)
        doc = json.load(open(path))
        want = tracing.trace_id_for_seed(seed)
        mine = [e for e in doc["traceEvents"] if e.get("ph") == "X"
                and e.get("args", {}).get("trace_id") == want]
        # ONE trace id across >= 3 distinct pids
        pids = {e["pid"] for e in mine}
        assert len(pids) >= 3, (pids, mine)
        by_name = {}
        for e in mine:
            by_name.setdefault(e["name"], []).append(e)
        for name in ("request", "handoff", "prefill", "kv_export",
                     "kv_import", "decode"):
            assert name in by_name, sorted(by_name)
        # causal order on the merged clock (RTT/2-bounded alignment:
        # allow a generous same-host slack)
        slack_us = 50e3

        def t0(name):
            return min(e["ts"] for e in by_name[name])

        assert t0("prefill") + slack_us >= t0("request")
        assert t0("kv_export") + slack_us >= t0("prefill")
        assert t0("kv_import") + slack_us >= t0("kv_export")
        assert t0("decode") + slack_us >= t0("kv_import")
        # the router's request span covers (within slack) the whole life
        req = by_name["request"][0]
        for e in mine:
            assert e["ts"] + slack_us >= req["ts"]
            assert e["ts"] + e["dur"] <= req["ts"] + req["dur"] + slack_us
        # and the waterfall tool renders it
        out = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "stats_dump.py"),
             "--traces", path],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        assert f"trace {want}" in out.stdout


# ------------------------------------------------------------------------
# PR 27: the one span API (profiler/spans.py) and what it writes
# ------------------------------------------------------------------------

def _tiny_gpt(seed=11):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                      GPTModel)

    paddle.seed(seed)
    cfg = GPTConfig.preset("gpt2-tiny", vocab_size=96, seq_len=64)
    return GPTForPretraining(GPTModel(cfg))


def _host_events(trace_dir):
    """[(start, end, name) of every event on the line] for every line of
    the host plane of the newest .xplane.pb under trace_dir."""
    import glob

    from jax.profiler import ProfileData

    pb = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = ProfileData.from_file(pb)
    return [[(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for e in line.events]
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines]


def _host_lines(trace_dir):
    """[(names on the line)] for every line of that host plane."""
    return [[n for _, _, n in line] for line in _host_events(trace_dir)]


def _server_thread_tree(trace_dir):
    """The program's spans on the line that holds `serving.sched_step`, as
    nested [name, children] lists in time order (`host.gc` set aside: the
    collector runs where it likes)."""
    (line,) = [ln for ln in _host_events(trace_dir)
               if any(n == "serving.sched_step" for _, _, n in ln)]
    root, stack = [], []
    for s, e, name in sorted((sp for sp in line
                              if sp[2].startswith("serving.")),
                             key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        node = [name, []]
        (stack[-1][1] if stack else root).append(node)
        stack.append((e, node[1]))
    return root


# what an iteration carries, nested and in order (profiler/spans.py's rule)
STEADY = [["serving.decode_prepare", []],
          ["serving.decode_step", [["serving.decode_sync", []]]],
          ["serving.decode_finish", []],
          ["serving.emit", []]]
ADMISSION = ["serving.admit", [["serving.admit_check", []],
                               ["serving.admit_blocks", []],
                               ["serving.admit_stage", []],
                               ["serving.prefill", []],
                               ["serving.admit_install", []],
                               ["serving.admit_install", []]]]


class TestSpanApi:
    def test_every_span_name_has_a_call_site_and_every_call_site_a_name(
            self):
        """The table and the code, held together: a name nobody opens (as
        the removed `serving.block_denoise` / `serving.block_commit` would
        be) and a call site outside the table both fail here."""
        import re

        from paddle_tpu.profiler import spans

        root = os.path.join(os.path.dirname(TOOLS), "paddle_tpu")
        opened = set()
        for d, _, files in os.walk(root):
            for fn in files:
                if fn.endswith(".py"):
                    with open(os.path.join(d, fn)) as f:
                        # span("x") / _span("x", ...); tracing.span takes a
                        # trace id first, never a literal
                        opened.update(re.findall(
                            r"""(?<![\w.])_?span\(\s*["']([\w.]+)["']""",
                            f.read()))
        assert opened == set(spans.SPANS)
        assert not {"serving.block_denoise", "serving.block_commit"} & opened

    def test_span_refuses_a_name_outside_the_table(self):
        from paddle_tpu.profiler import RecordEvent, span, spans

        with pytest.raises(ValueError, match="spans.SPANS"):
            span("serving.not_in_the_table")
        with pytest.raises(ValueError):
            spans.scope("not_a_scope")
        with pytest.raises(ValueError):
            spans.device_name("pure")
        before = dict(registry.counters("serving"))
        with RecordEvent("anything a user likes"):
            pass
        # a user's event feeds no counter
        assert registry.counters("serving") == before
        # every table entry says what it covers, and names are scoped
        for table in (spans.SPANS, spans.COUNTERS, spans.KERNELS,
                      spans.EXECUTABLES, spans.SCOPES):
            assert all(isinstance(v, str) and v for v in table.values())
        assert all("." in n for n in list(spans.SPANS) + list(spans.COUNTERS))

    def test_no_sink_on_only_the_counters_move(self):
        from paddle_tpu.profiler import span, timeline

        assert not tracing.enabled() and not timeline.active()
        c0 = registry.counters("serving")
        t0 = registry.timings("serving").get("serving.decode_step",
                                             {"count": 0})["count"]
        with span("serving.decode_step"):
            with span("serving.decode_sync"):
                pass
        c1 = registry.counters("serving")
        assert c1["decode_step_n"] == c0.get("decode_step_n", 0) + 1
        assert c1["decode_sync_n"] == c0.get("decode_sync_n", 0) + 1
        assert c1["decode_step_ns"] > c0.get("decode_step_ns", 0)
        assert isinstance(c1["decode_step_ns"], int)
        # the outer span holds the inner one
        assert c1["decode_step_ns"] - c0.get("decode_step_ns", 0) \
            >= c1["decode_sync_ns"] - c0.get("decode_sync_ns", 0)
        # the reservoir of the same name is still fed (stats_dump, pods)
        assert registry.timings("serving")["serving.decode_step"]["count"] \
            == t0 + 1
        assert "serving.decode_sync" not in registry.timings("serving")
        assert tracing.pending_spans() == 0
        assert timeline.stop() == []

    def test_ring_on_the_span_lands_there_with_its_trace_id(self):
        from paddle_tpu.profiler import span

        tracing.enable()
        with span("serving.admit", trace_id="tr27"):
            pass
        with span("serving.sched_step"):
            pass
        got = tracing.drain_spans()
        assert [(s[0], s[1]) for s in got] == [
            ("tr27", "serving.admit"), ("", "serving.sched_step")]
        assert all(s[4] >= s[3] for s in got)

    def test_profiler_window_puts_the_span_on_the_timeline(self):
        from paddle_tpu.profiler import span, timeline

        timeline.start()
        try:
            with span("train.step"):
                pass
        finally:
            got = timeline.stop()
        assert [s[0] for s in got] == ["train.step"]
        assert got[0][3] >= got[0][2]

    def test_record_event_begin_end_nests_as_before(self):
        from paddle_tpu.profiler import RecordEvent, timeline

        tracing.enable()
        timeline.start()
        try:
            ev = RecordEvent("outer")
            ev.begin()
            ev.begin()
            ev.end()
            ev.end()
            ev.end()  # unmatched: no-op
            with RecordEvent("inner"):
                pass
        finally:
            got = timeline.stop()
        assert [s[0] for s in got] == ["outer", "outer", "inner"]
        # innermost closes first, so the first span ends inside the second
        assert got[0][2] >= got[1][2] and got[0][3] <= got[1][3]
        # the same code as span: the ring holds them too
        assert [s[1] for s in tracing.drain_spans()] == [
            "outer", "outer", "inner"]


class TestProgramSpans:
    @pytest.fixture()
    def server(self):
        from paddle_tpu.serving import GenerationServer

        srv = GenerationServer(_tiny_gpt(), max_batch_size=2,
                               buckets=(8, 16), max_queue_size=8)
        srv.start()
        srv.generate([1, 2, 3], max_new_tokens=3)  # compile outside
        yield srv
        srv.shutdown(timeout=30)

    def test_scheduler_thread_spans_reach_the_profiler_trace(
            self, server, tmp_path):
        import jax

        from paddle_tpu.profiler import RecordEvent

        fp0 = dict(registry.counters("fastpath"))
        c0 = dict(registry.counters("serving"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with RecordEvent("test.main_thread"):
                server.generate([5, 6, 7, 8], max_new_tokens=8)
                # a whole wait for work, begun and ended inside the trace
                time.sleep(3 * server._idle_wait_s)
        finally:
            jax.profiler.stop_trace()
        fp1 = registry.counters("fastpath")
        c1 = registry.counters("serving")
        steps = c1["decode_steps"] - c0["decode_steps"]
        fast = fp1["decode_fast_steps"] - fp0["decode_fast_steps"]
        rebuilt = fp1["decode_rebuilds"] - fp0["decode_rebuilds"]
        assert fast >= 1 and rebuilt >= 1 and fast + rebuilt == steps
        lines = _host_lines(str(tmp_path))
        serving = [ln for ln in lines if "serving.sched_step" in ln]
        # one thread did the serving work, and not the caller's
        assert len(serving) == 1
        assert "test.main_thread" not in serving[0]
        assert any("test.main_thread" in ln for ln in lines)
        names = serving[0]
        # fast path and rebuild path alike: one span a decode iteration
        assert names.count("serving.decode_step") == steps
        assert names.count("serving.decode_sync") == steps
        assert names.count("serving.emit") == steps
        assert names.count("serving.prefill") == 1
        assert names.count("serving.admit") == 1
        assert names.count("serving.sched_step") \
            == c1["sched_steps"] - c0["sched_steps"]
        # the server thread waits for work before and after the request
        assert "serving.loop_idle" in names

    def test_counters_at_the_span_boundaries(self, server):
        # (an earlier request's last step may still be closing its span)
        time.sleep(3 * server._idle_wait_s)
        c0 = dict(registry.counters("serving"))
        h = server.submit([9, 8, 7, 6, 5], max_new_tokens=6)
        h.result(timeout=60)
        # the request is handed over inside the last step: let that step's
        # spans close before reading what they count
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            c1 = registry.counters("serving")
            if c1["sched_step_n"] - c0.get("sched_step_n", 0) \
                    == c1["sched_steps"] - c0["sched_steps"]:
                break
            time.sleep(0.01)
        d = {k: c1[k] - c0.get(k, 0) for k in c1
             if isinstance(c1[k], int)}
        n = d["decode_steps"]
        assert n == 5  # the first token comes from the prefill
        assert d["decode_step_n"] == n == d["decode_sync_n"] == d["emit_n"]
        assert d["sched_step_n"] == d["sched_steps"] >= n
        assert d["prefill_n"] == d["admit_n"] == d["admitted"] == 1
        assert d["queue_wait_ns"] >= 0
        # a 5-token prompt: the steps read 6, 7, 8, 9, 10 rows
        assert d["kv_tokens_read"] == sum(range(6, 6 + n))
        assert d["sched_step_ns"] >= d["decode_step_ns"] + d["prefill_ns"]
        assert d["decode_step_ns"] >= d["decode_sync_ns"] > 0

    def test_iterations_carry_exactly_the_tables_spans(self, server,
                                                        tmp_path):
        """A steady iteration has six spans and an admitting one the
        admission's seven more, nested and ordered as profiler/spans.py says,
        all on the scheduler's thread: nothing of the engine's work is left
        to `serving.sched_step`'s self time."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            server.generate([5, 6, 7, 8], max_new_tokens=6)
            time.sleep(3 * server._idle_wait_s)
        finally:
            jax.profiler.stop_trace()
        steps = [node for node in _server_thread_tree(str(tmp_path))
                 if node[0] == "serving.sched_step"]
        # the first token comes from the prefill: five decode iterations,
        # the first of them in the admitting step
        assert [kids for _, kids in steps] \
            == [[ADMISSION] + STEADY] + [STEADY] * 4

    def test_span_counters_nest(self, server):
        """`<span>_ns` of a parent is at least its children's sum, so the
        differences the benchmark's readers take (`serve.step_host_ms`,
        `serve.admit_host_ms`) and `engine.stats()` gives are self times."""
        time.sleep(3 * server._idle_wait_s)
        c0 = dict(registry.counters("serving"))
        server.generate([9, 8, 7, 6, 5, 4], max_new_tokens=6)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            c1 = registry.counters("serving")
            if c1["sched_step_n"] - c0.get("sched_step_n", 0) \
                    == c1["sched_steps"] - c0["sched_steps"]:
                break
            time.sleep(0.01)
        d = {k: c1[k] - c0.get(k, 0) for k in c1 if isinstance(c1[k], int)}
        assert d["sched_step_ns"] >= d["admit_ns"] + d["decode_prepare_ns"] \
            + d["decode_step_ns"] + d["decode_finish_ns"] + d["emit_ns"]
        assert d["admit_ns"] >= d["admit_check_ns"] + d["admit_blocks_ns"] \
            + d["admit_stage_ns"] + d["prefill_ns"] + d["admit_install_ns"]
        assert d["admit_check_n"] == d["admit_blocks_n"] \
            == d["admit_stage_n"] == d["admit_n"] == 1
        assert d["admit_install_n"] == 2  # the engine's half, the scheduler's
        assert d["decode_prepare_n"] == d["decode_finish_n"] \
            == d["decode_step_n"] == 5
        assert all(d[k + "_ns"] > 0 for k in (
            "admit_check", "admit_blocks", "admit_stage", "admit_install",
            "decode_prepare", "decode_finish"))
        stats = server.engine.stats()
        ns = {k: c1[k + "_ns"] for k in ("sched_step", "decode_step",
                                         "admit", "prefill")}
        assert stats["step_host_ms"] == pytest.approx(
            (ns["sched_step"] - ns["decode_step"] - ns["admit"])
            / c1["sched_steps"] / 1e6, rel=0.05)
        assert stats["admit_host_ms"] == pytest.approx(
            (ns["admit"] - ns["prefill"]) / c1["admitted"] / 1e6, rel=0.05)
        # (means since the PROCESS began: positive in a serving process,
        # where every decode step runs under the scheduler; other tests of
        # this one drive engines by hand)
        assert stats["decode_fast_steps"] \
            == registry.counters("fastpath")["decode_fast_steps"]

    def test_gc_pauses_are_spans_while_a_server_runs(self):
        """`host.gc`: counted and annotated while a server's worker runs,
        on whichever thread collects; the callback goes with the worker."""
        import gc

        from paddle_tpu.profiler import spans
        from paddle_tpu.serving import GenerationServer

        watch = spans._gc_watch
        users = watch._users  # servers other tests of this process left
        srv = GenerationServer(_tiny_gpt(), max_batch_size=1, buckets=(8,))
        try:
            srv.generate([1, 2, 3], max_new_tokens=2)  # the worker runs
            assert watch._users == users + 1 and watch in gc.callbacks
            c0 = dict(registry.counters("host"))
            gc.collect()
            c1 = registry.counters("host")
            assert c1["gc_n"] >= c0.get("gc_n", 0) + 1
            assert c1["gc_gen2_n"] >= c0.get("gc_gen2_n", 0) + 1
            assert c1["gc_ns"] > c0.get("gc_ns", 0)
            assert registry.counters()["host.gc_n"] == c1["gc_n"]
        finally:
            assert srv.shutdown(timeout=30)
        assert watch._users == users
        assert (watch in gc.callbacks) == (users > 0)
        if not users:
            c2 = dict(registry.counters("host"))
            gc.collect()
            assert registry.counters("host") == c2

    def test_tok_ts_one_stamp_a_token(self, server):
        h = server.submit([4, 3, 2, 1], max_new_tokens=7)
        h.result(timeout=60)
        assert len(h.tok_ts) == len(h.tokens) == 7
        assert all(a <= b for a, b in zip(h.tok_ts, h.tok_ts[1:]))
        assert h.tok_ts[0] == h.first_tok_ts
        assert h.tok_ts[-1] == h.last_tok_ts

    def test_train_step_span_and_executable_name(self):
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu import nn, optimizer
        from paddle_tpu.profiler import spans

        paddle.seed(3)
        net = nn.Linear(4, 2)
        opt = optimizer.SGD(0.1, parameters=net.parameters())

        def step_fn(x):
            loss = net(x).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        train = paddle.jit.TrainStep(step_fn, net, opt)
        x = paddle.to_tensor(np.ones((3, 4), np.float32))
        c0 = dict(registry.counters("train"))
        for _ in range(3):
            train(x)
        c1 = registry.counters("train")
        assert c1["step_n"] - c0.get("step_n", 0) == 3
        assert c1["step_ns"] > c0.get("step_ns", 0)
        name = train._compiled.__name__
        assert name == "train_step" and name in spans.EXECUTABLES
        # and the engine's two executables
        from paddle_tpu.serving import GenerationEngine

        eng = GenerationEngine(_tiny_gpt(), max_batch_size=1, buckets=(8,))
        assert eng._prefill_jit.__name__ == "serving_prefill"
        assert eng._decode_jit.__name__ == "serving_decode"
        eng.reprime()
        assert eng._decode_jit.__name__ == "serving_decode"
