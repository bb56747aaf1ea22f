"""Unified runtime telemetry (ISSUE 3): metrics registry, recompile/
fallback explainer, host span timeline + chrome-trace round trip,
FLAGS_benchmark per-op timing, and the scheduler state machine."""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer, profiler
from paddle_tpu.core import lazy
from paddle_tpu.profiler import (Profiler, ProfilerState, RecordEvent,
                                 export_chrome_tracing, load_profiler_result,
                                 make_scheduler, registry, timeline)


class TestScheduler:
    """Reference scheduler state machine: skip_first / closed / ready /
    record windows, repeat exhaustion."""

    def test_skip_first_and_cycle_edges(self):
        sched = make_scheduler(closed=1, ready=1, record=2, repeat=2,
                               skip_first=3)
        S = ProfilerState
        assert [sched(i) for i in range(3)] == [S.CLOSED] * 3  # skip_first
        assert sched(3) is S.CLOSED          # closed slot of cycle 0
        assert sched(4) is S.READY
        assert sched(5) is S.RECORD
        assert sched(6) is S.RECORD_AND_RETURN  # last record slot
        assert sched(7) is S.CLOSED          # cycle 1 begins
        assert sched(10) is S.RECORD_AND_RETURN
        # repeat=2 exhausted: closed forever
        assert all(sched(i) is S.CLOSED for i in range(11, 20))

    def test_record_only_defaults(self):
        sched = make_scheduler(record=1)
        assert sched(0) is ProfilerState.RECORD_AND_RETURN
        assert sched(5) is ProfilerState.RECORD_AND_RETURN

    def test_tuple_scheduler_form(self):
        prof = Profiler(scheduler=(2, 4), timer_only=True)
        S = ProfilerState
        assert prof._scheduler(0) is S.CLOSED
        assert prof._scheduler(1) is S.CLOSED
        assert prof._scheduler(3) is S.RECORD_AND_RETURN


class TestRegistry:
    def test_counters_scoping_reset_preserves_dict(self):
        d = registry.scoped_counters("t_scope", {"a": 0})
        d["a"] += 3
        registry.inc("b", 2, scope="t_scope")
        snap = profiler.stats()["counters"]
        assert snap["t_scope.a"] == 3
        assert snap["t_scope.b"] == 2
        assert profiler.stats("t_scope") == {"a": 3, "b": 2}
        registry.reset("t_scope")
        # keys survive at 0 and the dict object is the same (hot-path
        # aliases like lazy._counters must stay valid)
        assert registry.scoped_counters("t_scope") is d
        assert d["a"] == 0 and d["b"] == 0
        d["a"] += 1  # the += contract still works post-reset
        assert profiler.stats("t_scope")["a"] == 1

    def test_timings_and_gauges(self):
        with registry.time_block("phase_x", scope="t_time"):
            pass
        t = profiler.stats()["timings"]["t_time.phase_x"]
        assert t["count"] == 1 and t["total_s"] >= 0
        registry.gauge_set("t.g", 7.5)
        assert profiler.stats()["gauges"]["t.g"] == 7.5
        registry.reset("t_time")
        assert "t_time.phase_x" not in profiler.stats()["timings"]

    def test_lazy_counters_ride_the_registry(self):
        s0 = profiler.stats("lazy").get("materializations", 0)
        with paddle.incubate.lazy_eval():
            x = paddle.to_tensor(np.ones(4, np.float32))
            float((x * 2).sum())
        assert profiler.stats("lazy")["materializations"] > s0
        # back-compat: lazy.stats() still answers
        assert lazy.stats()["materializations"] == \
            profiler.stats("lazy")["materializations"]

    def test_dispatch_jit_cache_counters(self):
        x = paddle.to_tensor(np.ones((3, 3), np.float32))
        (x + x).numpy()
        s0 = profiler.stats("dispatch")
        (x + x).numpy()
        s1 = profiler.stats("dispatch")
        assert s1["jit_cache_hits"] > s0["jit_cache_hits"]
        assert s1["ops_dispatched"] > s0["ops_dispatched"]


class TestRecordEvent:
    def test_reentrant_begin_nests_via_stack(self):
        timeline.start()
        try:
            ev = RecordEvent("outer")
            ev.begin()
            ev.begin()  # old impl leaked the first annotation here
            ev.end()
            ev.end()
            ev.end()  # unmatched end: no-op, no raise
        finally:
            spans = timeline.stop()
        assert len(spans) == 2
        assert all(s[0] == "outer" for s in spans)

    def test_no_span_outside_profiler_window(self):
        assert not timeline.active()
        with RecordEvent("quiet"):
            pass  # must not blow up, and records nothing


class TestChromeTraceRoundTrip:
    def _model_and_data(self):
        from paddle_tpu.hapi import Model

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        m = Model(net)
        m.prepare(optimizer.SGD(0.1, parameters=net.parameters()),
                  nn.CrossEntropyLoss())
        rng = np.random.default_rng(0)
        X = rng.standard_normal((32, 4)).astype(np.float32)
        y = (X.sum(1) > 0).astype(np.int64)
        return m, [(X[i:i + 8], y[i:i + 8]) for i in range(0, 32, 8)]

    def test_export_load_roundtrip_with_auto_instrumented_spans(
            self, tmp_path):
        m, data = self._model_and_data()
        prof = Profiler(on_trace_ready=export_chrome_tracing(
            str(tmp_path), worker_name="w0"))
        prof.start()
        m.fit(data, epochs=1, verbose=0)
        prof.step()
        prof.stop()
        path = tmp_path / "w0.json"
        assert path.exists(), "host chrome trace not written"
        with open(path) as f:
            doc = json.load(f)
        assert doc["traceEvents"], "no host spans exported"
        res = load_profiler_result(str(path))
        totals = res.span_totals()
        # auto-instrumented: batch fetch + compiled step at runtime,
        # forward/backward/optimizer at TrainStep trace time
        for name in ("dataloader", "train_step", "forward", "backward",
                     "optimizer-step"):
            assert totals.get(name, {}).get("count", 0) >= 1, (name, totals)
        assert "forward" in res.summary()
        # the telemetry snapshot rides in the trace file
        assert "counters" in res.telemetry

    def test_repeated_windows_export_distinct_files(self, tmp_path):
        # closed=1/record=1/repeat=2 → two separated one-step record
        # windows; each must land in its own file, and stop() must not
        # re-export the last window's spans a second time
        prof = Profiler(
            scheduler=make_scheduler(closed=1, record=1, repeat=2),
            on_trace_ready=export_chrome_tracing(str(tmp_path),
                                                 worker_name="rw"))
        prof.start()
        for _ in range(5):
            with RecordEvent("tick"):
                pass
            prof.step()
        prof.stop()
        files = sorted(p.name for p in tmp_path.glob("rw*.json"))
        assert files == ["rw.1.json", "rw.json"], files

    def test_load_rejects_non_trace_json(self, tmp_path):
        p = tmp_path / "not_a_trace.json"
        p.write_text(json.dumps([1, 2, 3]))
        with pytest.raises(ValueError, match="traceEvents"):
            load_profiler_result(str(p))

    def test_timer_only_summary_with_step_metrics(self):
        profiler.set_step_metrics(flops_per_step=1e9, tokens_per_step=512)
        prof = Profiler(timer_only=True)
        prof.start()
        for _ in range(3):
            paddle.randn([4]).numpy()
            prof.step()
        prof.stop()
        s = prof.summary()
        assert "steps=" in s and "tokens/s=" in s
        # the CPU has no peak on record, so no utilization is printed
        # against a made-up one
        assert "MFU=" not in s


class TestExplainer:
    @staticmethod
    def _mk():
        paddle.seed(11)
        net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
        opt = optimizer.Adam(learning_rate=0.01,
                             parameters=net.parameters())
        return net, opt

    @staticmethod
    def _data(batch=16):
        rng = np.random.default_rng(7)
        x = rng.normal(size=(batch, 8)).astype(np.float32)
        y = rng.normal(size=(batch, 4)).astype(np.float32)
        return paddle.to_tensor(x), paddle.to_tensor(y)

    @staticmethod
    def _step(net, opt, xt, yt):
        with paddle.incubate.lazy_eval():
            loss = ((net(xt) - yt) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return float(loss)

    def test_forced_capture_fallback_names_diverging_op(self):
        # test isolation: TestStepCapture (test_lazy_train) builds the
        # IDENTICAL net/opt/data, and its live captured plan would make
        # these steps replay from step 1 — no fresh promotion event, and
        # the old one may have been evicted from the bounded explainer
        # ring by intervening modules (the historical full-suite flake)
        lazy.drop_plans("test isolation: fresh promotion required")
        net, opt = self._mk()
        xt, yt = self._data()
        for _ in range(10):  # promote to captured mode
            self._step(net, opt, xt, yt)
        assert profiler.explain(kind="capture_promotion"), \
            "promotion event missing"
        n0 = len(profiler.explain(kind="capture_fallback"))
        xt2, yt2 = self._data(batch=9)  # aval change → forced fallback
        self._step(net, opt, xt2, yt2)
        evs = profiler.explain(kind="capture_fallback")
        assert len(evs) > n0
        ev = evs[-1]
        # the event names the diverging op and explains the change
        assert ev.get("op"), ev
        assert "why" in ev and "aval" in ev["why"] or \
            ev.get("reason") == "aval", ev
        assert ev["plan_ops"] > 0

    def test_segment_compile_and_jit_miss_events(self):
        with paddle.incubate.lazy_eval():
            x = paddle.to_tensor(np.arange(6, dtype=np.float32))
            float((x * 3 + 1).sum())
        kinds = {e["kind"] for e in profiler.explain()}
        assert "segment_compile" in kinds

    def test_reset_clears_ring(self):
        from paddle_tpu.profiler import explainer

        explainer.record("test_event", op="x", why="y")
        assert profiler.explain(kind="test_event")
        profiler.reset_stats()
        assert not profiler.explain()


class TestBenchmarkFlag:
    def test_per_op_wall_time_recorded(self):
        paddle.set_flags({"FLAGS_benchmark": True})
        try:
            x = paddle.to_tensor(np.ones((8, 8), np.float32))
            (x + x).numpy()
            (x * x).numpy()
        finally:
            paddle.set_flags({"FLAGS_benchmark": False})
        t = profiler.stats()["timings"]
        op_keys = [k for k in t if k.startswith("op_time.")]
        assert op_keys, t
        assert all(t[k]["count"] >= 1 and t[k]["total_s"] > 0
                   for k in op_keys)

    def test_benchmark_bypasses_lazy_accumulation(self):
        paddle.set_flags({"FLAGS_benchmark": True})
        try:
            s0 = profiler.stats("lazy")["materializations"]
            with paddle.incubate.lazy_eval():
                x = paddle.to_tensor(np.ones(4, np.float32))
                y = x * 2  # eager under FLAGS_benchmark: no lazy node
            assert not isinstance(y._data, lazy.LazyArray)
            assert profiler.stats("lazy")["materializations"] == s0
        finally:
            paddle.set_flags({"FLAGS_benchmark": False})


class TestNanInfExplainerDump:
    def test_nan_error_carries_explainer_ring(self):
        paddle.set_flags({"FLAGS_check_nan_inf": True})
        try:
            x = paddle.to_tensor(np.zeros(4, np.float32))
            with pytest.raises(RuntimeError,
                               match="divide.*Nan") as ei:
                x / x
            assert "profiler.explain" in str(ei.value)
        finally:
            paddle.set_flags({"FLAGS_check_nan_inf": False})


class TestCollectiveCounters:
    def test_all_reduce_calls_and_bytes(self):
        import paddle_tpu.distributed as dist

        t = paddle.to_tensor(np.ones((8, 4), np.float32))
        s0 = profiler.stats("collective")
        dist.all_reduce(t)
        s1 = profiler.stats("collective")
        assert s1.get("all_reduce.calls", 0) == \
            s0.get("all_reduce.calls", 0) + 1
        assert s1.get("all_reduce.bytes", 0) >= \
            s0.get("all_reduce.bytes", 0) + 8 * 4 * 4

    def test_all_gather_counted(self):
        import paddle_tpu.distributed as dist

        t = paddle.to_tensor(np.ones((8, 2), np.float32))
        out = []
        s0 = profiler.stats("collective").get("all_gather.calls", 0)
        dist.all_gather(out, t)
        assert profiler.stats("collective")["all_gather.calls"] == s0 + 1


class TestDataLoaderTelemetry:
    def test_prefetch_wait_timing(self):
        from paddle_tpu.io import DataLoader

        data = [np.full((2,), i, np.float32) for i in range(8)]
        loader = DataLoader(data, batch_size=2)
        n = sum(1 for _ in loader)
        assert n == 4
        t = profiler.stats()["timings"]
        assert t.get("timings.dataloader.wait", {}).get("count", 0) >= 4


class TestFastPathTelemetryCost:
    """ISSUE-9 satellite: on a replayed (zero-dispatch) step, telemetry
    is batched into one dict-merge — ZERO calls into the registry's
    function API (inc/timing/tally/gauge_set), zero explainer events,
    and (ISSUE 18) zero histogram records or trace spans land per step.
    A regression here silently re-taxes the hot path."""

    def test_replayed_step_makes_no_registry_calls(self, monkeypatch):
        from paddle_tpu.profiler import explainer as _explainer
        from paddle_tpu.profiler import tracing as _tracing

        paddle.seed(13)
        net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(),
                            nn.Linear(32, 4))
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=net.parameters())
        rng = np.random.default_rng(0)
        xt = paddle.to_tensor(rng.normal(size=(8, 16)).astype(np.float32))
        yt = paddle.to_tensor(rng.normal(size=(8, 4)).astype(np.float32))

        def body():
            with paddle.incubate.lazy_eval():
                loss = ((net(xt) - yt) ** 2).mean()
                loss.backward()
                opt.step()
                opt.clear_grad()
                return loss

        step = lazy.ReplayStep(body, optimizers=opt, audit_every=1000)
        for _ in range(15):  # promote + stabilize + arm
            float(step())
        assert step.armed

        calls = []

        def spy(name):
            orig = getattr(registry, name)

            def wrapper(*a, **k):
                calls.append(name)
                return orig(*a, **k)

            return wrapper

        for name in ("inc", "timing", "tally", "gauge_set",
                     "hist_record"):
            monkeypatch.setattr(registry, name, spy(name))
        orig_record = _explainer.record
        monkeypatch.setattr(
            _explainer, "record",
            lambda *a, **k: calls.append("explain") or orig_record(*a, **k))
        # trace spans must sit AROUND the executable call, never inside
        # the replayed loop: with tracing ON, a replayed step still makes
        # zero add_span calls from this thread's step body
        monkeypatch.setattr(_tracing, "_enabled", True)
        orig_span = _tracing.add_span
        monkeypatch.setattr(
            _tracing, "add_span",
            lambda *a, **k: calls.append("span") or orig_span(*a, **k))

        from paddle_tpu.core import dispatch as _dispatch

        d0 = _dispatch.ops_dispatched()
        n0 = dict(registry.counters("fastpath"))
        for _ in range(20):
            float(step())
        n1 = dict(registry.counters("fastpath"))
        assert n1["hits"] - n0["hits"] == 20  # all 20 replayed
        assert calls == []  # zero per-op (and per-step) registry calls
        assert _dispatch.ops_dispatched() == d0


class TestStatsDumpCLI:
    def test_dump_trace_and_telemetry_line(self, tmp_path, capsys):
        import sys

        sys.path.insert(0, str(__import__("pathlib").Path(
            __file__).resolve().parent.parent / "tools"))
        try:
            import stats_dump
        finally:
            sys.path.pop(0)
        trace = {"traceEvents": [
            {"name": "fwd", "ph": "X", "ts": 0, "dur": 1500,
             "pid": 1, "tid": 1}],
            "paddle_tpu": {"counters": {"lazy.cache_hits": 3}}}
        p = tmp_path / "t.json"
        p.write_text(json.dumps(trace))
        assert stats_dump.main([str(p)]) == 0
        out = capsys.readouterr().out
        assert "fwd" in out and "lazy.cache_hits" in out
        # telemetry JSONL form
        p2 = tmp_path / "t.log"
        p2.write_text('garbage\n' + json.dumps(
            {"metric": "telemetry", "counters": {"a.b": 1},
             "gauges": {}, "timings": {}}) + "\n")
        assert stats_dump.main([str(p2)]) == 0
        assert "a.b" in capsys.readouterr().out
