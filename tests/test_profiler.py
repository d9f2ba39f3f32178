"""Unified tracing + metrics subsystem (ISSUE 2).

Acceptance anchors:
- hierarchical spans: nesting/parentage across threads, thread-safe
  aggregation (the old defaultdict dropped counts under concurrency);
- Chrome-trace JSON: loadable, schema-valid, children contained in
  parents on the same tid;
- histogram percentile estimates match a numpy reference within the
  log-bucket resolution;
- ServingMetrics latency histograms + snapshot percentiles;
- Prometheus text exposition golden;
- per-jit cost attribution (FLOPs/bytes/compile counts);
- end-to-end: a serving-engine run under the profiler produces a
  loadable trace with NESTED prefill/decode spans, p50/p95/p99 step
  latency, and decode-step FLOPs attribution.
"""
import json
import threading

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.framework.monitor import (Histogram, LabeledGauge,
                                          StatRegistry, gauge_set,
                                          histogram_observe,
                                          histogram_snapshot, stat_registry)
from paddle_tpu.utils.profiler import (RecordEvent, reset_profiler,
                                       stop_profiler, summary)


@pytest.fixture(autouse=True)
def _clean_tracer():
    reset_profiler()
    profiler.disable_tracing()
    yield
    reset_profiler()
    profiler.disable_tracing()


class TestSpanHierarchy:
    def test_nesting_and_parentage(self):
        profiler.enable_tracing()
        with RecordEvent("outer"):
            with RecordEvent("mid"):
                with RecordEvent("leaf"):
                    pass
            with RecordEvent("mid2"):
                pass
        spans = {s.name: s for s in profiler.get_spans()}
        assert set(spans) == {"outer", "mid", "mid2", "leaf"}
        outer, mid, leaf = spans["outer"], spans["mid"], spans["leaf"]
        assert outer.parent_id is None and outer.depth == 0
        assert mid.parent_id == outer.span_id and mid.depth == 1
        assert leaf.parent_id == mid.span_id and leaf.depth == 2
        assert spans["mid2"].parent_id == outer.span_id
        # containment: child intervals inside the parent's
        assert outer.start_ns <= mid.start_ns <= mid.end_ns <= outer.end_ns
        assert mid.start_ns <= leaf.start_ns <= leaf.end_ns <= mid.end_ns

    def test_span_args_and_contextmanager(self):
        profiler.enable_tracing()
        with profiler.span("work", step=3, kind="decode") as sp:
            assert sp.name == "work"
        (got,) = profiler.get_spans()
        assert got.args == {"step": 3, "kind": "decode"}

    def test_sibling_threads_get_independent_stacks(self):
        profiler.enable_tracing()
        done = threading.Barrier(3)

        def worker(i):
            with profiler.span(f"t{i}.outer"):
                done.wait()                  # both threads mid-span
                with profiler.span(f"t{i}.inner"):
                    pass

        ts = [threading.Thread(target=worker, args=(i,)) for i in (0, 1)]
        for t in ts:
            t.start()
        done.wait()
        for t in ts:
            t.join()
        spans = {s.name: s for s in profiler.get_spans()}
        for i in (0, 1):
            outer, inner = spans[f"t{i}.outer"], spans[f"t{i}.inner"]
            # parentage never crosses threads even though both stacks
            # were open simultaneously
            assert inner.parent_id == outer.span_id
            assert inner.tid == outer.tid

    def test_aggregate_thread_safety(self):
        # regression (ISSUE 2 satellite): the old module-level
        # defaultdict lost counts when __exit__ raced
        N, T = 200, 8

        def hammer():
            for _ in range(N):
                with RecordEvent("contended"):
                    pass

        threads = [threading.Thread(target=hammer) for _ in range(T)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        agg = profiler.aggregates()["contended"]
        assert agg["calls"] == N * T
        assert agg["total_s"] > 0

    def test_disabled_tracing_keeps_aggregates_drops_spans(self):
        with RecordEvent("quiet"):
            pass
        assert profiler.get_spans() == []
        assert profiler.aggregates()["quiet"]["calls"] == 1

    def test_summary_table(self):
        with RecordEvent("ev_a"):
            pass
        table = summary()
        assert "ev_a" in table and "Calls" in table and "Max(ms)" in table


class TestChromeTrace:
    def test_schema_and_containment(self, tmp_path):
        profiler.enable_tracing()
        with profiler.span("parent"):
            with profiler.span("child"):
                pass
        profiler.instant("step_marker", step=0)
        path = profiler.export_chrome_trace(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        events = doc["traceEvents"]
        assert isinstance(events, list)
        complete = [e for e in events if e["ph"] == "X"]
        instants = [e for e in events if e["ph"] == "i"]
        metas = [e for e in events if e["ph"] == "M"]
        assert {e["name"] for e in complete} == {"parent", "child"}
        assert [e["name"] for e in instants] == ["step_marker"]
        assert any(e["name"] == "process_name" for e in metas)
        for e in complete:
            # required Trace Event Format fields, µs units
            for k in ("pid", "tid", "ts", "dur", "cat", "args"):
                assert k in e, f"missing {k} in {e}"
        par = next(e for e in complete if e["name"] == "parent")
        chl = next(e for e in complete if e["name"] == "child")
        assert chl["args"]["parent_id"] == par["args"]["span_id"]
        assert par["ts"] <= chl["ts"]
        assert chl["ts"] + chl["dur"] <= par["ts"] + par["dur"] + 1e-3
        assert chl["tid"] == par["tid"]

    def test_stop_profiler_writes_profile_path(self, tmp_path):
        with RecordEvent("profiled_event"):
            pass
        ppath = tmp_path / "profile.txt"
        tpath = tmp_path / "timeline.json"
        # regression: profile_path used to be accepted and IGNORED
        stop_profiler(profile_path=str(ppath), timeline_path=str(tpath))
        assert "profiled_event" in ppath.read_text()
        assert "traceEvents" in tpath.read_text()


class TestHistogram:
    def test_percentiles_vs_numpy(self):
        rng = np.random.RandomState(7)
        vals = rng.lognormal(mean=1.0, sigma=1.5, size=4000)
        h = Histogram()
        for v in vals:
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == len(vals)
        np.testing.assert_allclose(snap["sum"], vals.sum(), rtol=1e-9)
        for p in (50, 95, 99):
            ref = np.percentile(vals, p)
            # log-bucket resolution: 20/decade => ~6% worst-case
            assert abs(snap[f"p{p}"] - ref) / ref < 0.12, (p, snap, ref)
        assert snap["min"] == vals.min() and snap["max"] == vals.max()

    def test_percentile_clamped_to_observed_range(self):
        h = Histogram()
        h.observe(5.0)
        assert h.percentile(0) == 5.0
        assert h.percentile(100) == 5.0
        assert h.snapshot()["p99"] == 5.0

    def test_out_of_range_and_nonpositive_values(self):
        h = Histogram()
        for v in (-1.0, 0.0, 1e-9, 1e9):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 4
        assert snap["min"] == -1.0 and snap["max"] == 1e9

    def test_registry_surface(self):
        histogram_observe("t.latency", 10.0)
        histogram_observe("t.latency", 20.0)
        snap = histogram_snapshot("t.latency")
        assert snap["count"] == 2 and snap["sum"] == 30.0
        stat_registry.histogram("t.latency").reset()
        assert histogram_snapshot("t.latency")["count"] == 0

    def test_labeled_gauge(self):
        g = LabeledGauge()
        g.set(3.5, device="tpu0")
        g.set(4.5, device="tpu1")
        assert g.get(device="tpu0") == 3.5
        assert len(g.values()) == 2
        gauge_set("t.mem", 7, kind="host")
        assert stat_registry.labeled_gauge("t.mem").get(kind="host") == 7.0

    def test_histogram_concurrent_observe(self):
        h = Histogram()
        N, T = 500, 4

        def hammer():
            for i in range(N):
                h.observe(1.0 + (i % 7))

        threads = [threading.Thread(target=hammer) for _ in range(T)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count == N * T


class TestExposition:
    def test_golden_text(self):
        reg = StatRegistry()
        reg.get("serving.steps").add(3)
        reg.labeled_gauge("kv.pages").set(12, pool="default")
        h = reg.histogram("lat.ms")
        h.observe(0.5)
        h.observe(2.0)
        text = profiler.prometheus_text(reg)
        lines = text.splitlines()
        assert "# TYPE serving_steps gauge" in lines
        assert "serving_steps 3" in lines
        assert "# TYPE kv_pages gauge" in lines
        assert 'kv_pages{pool="default"} 12' in lines
        assert "# TYPE lat_ms histogram" in lines
        assert 'lat_ms_bucket{le="0.5011872336272722"} 1' in lines
        assert 'lat_ms_bucket{le="+Inf"} 2' in lines
        assert "lat_ms_sum 2.5" in lines
        assert "lat_ms_count 2" in lines
        assert text.endswith("\n")

    def test_metrics_http_server(self):
        import urllib.request

        reg = StatRegistry()
        reg.get("up").add(1)
        srv = profiler.start_metrics_server(port=0, registry=reg)
        try:
            body = urllib.request.urlopen(srv.url, timeout=10).read()
            assert b"up 1" in body
            with pytest.raises(Exception):
                urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/nope", timeout=10)
        finally:
            srv.stop()


class TestJitCost:
    def test_flops_and_compile_attribution(self):
        reg = profiler.JitCostRegistry()
        f = profiler.profiled_jit("test.matmul",
                                  lambda a, b: a @ b, registry=reg)
        x = jnp.ones((32, 32), jnp.float32)
        for _ in range(3):
            out = f(x, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ x))
        snap = reg.snapshot()["test.matmul"]
        assert snap["calls"] == 3
        assert snap["compile_count"] == 1          # one signature
        assert snap["flops"] > 0                   # 2*32^3 on CPU backend
        assert snap["total_flops"] == snap["flops"] * 3
        assert snap["compile_time_s"] > 0
        # new signature => one more compile, not three
        y = jnp.ones((16, 16), jnp.float32)
        f(y, y)
        f(y, y)
        snap = reg.snapshot()["test.matmul"]
        assert snap["compile_count"] == 2
        assert snap["calls"] == 5
        assert len(snap["signatures"]) == 2

    def test_decorator_form_and_fallback_counting(self):
        reg = profiler.JitCostRegistry()

        @profiler.profiled_jit("test.add", registry=reg)
        def g(a):
            return a + 1

        assert int(g(jnp.asarray(1))) == 2
        assert reg.snapshot()["test.add"]["calls"] == 1

    def test_device_memory_stats_shape(self):
        stats = profiler.device_memory_stats()
        assert isinstance(stats, dict)   # empty on CPU — shape only


class TestServingObservability:
    VOCAB, HID = 50, 32

    @pytest.fixture(scope="class")
    def gpt(self):
        from paddle_tpu.text.models import GPTModel

        paddle.seed(23)
        m = GPTModel(vocab_size=self.VOCAB, hidden_size=self.HID,
                     num_layers=2, num_heads=2, ffn_size=64,
                     max_seq_len=64, dropout=0.0)
        m.eval()
        return m

    def test_serving_metrics_latency_histograms(self):
        from paddle_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        for ms in (1.0, 2.0, 4.0, 8.0, 100.0):
            m.on_step(queue_depth=0, running=2, bucket=2, pages_in_use=4,
                      tokens_emitted=2, step_seconds=ms / 1e3)
        m.on_prefill(0.010)
        m.on_decode(0.002)
        m.on_first_token(0.0, 0.050)
        snap = m.snapshot()
        sl = snap["step_latency_ms"]
        assert sl["count"] == 5
        assert 0 < sl["p50"] <= sl["p95"] <= sl["p99"]
        assert sl["p99"] <= 100.0 * 1.001
        assert snap["prefill_latency_ms"]["count"] == 1
        assert snap["decode_latency_ms"]["count"] == 1
        assert abs(snap["ttft_ms"]["p50"] - 50.0) / 50.0 < 0.12
        m.reset()
        assert m.snapshot()["step_latency_ms"]["count"] == 0

    def test_engine_end_to_end_trace_and_attribution(self, gpt, tmp_path):
        """The ISSUE 2 acceptance run: serving under the profiler."""
        from paddle_tpu.serving import ServingEngine

        profiler.enable_tracing()
        profiler.cost_registry.reset()
        eng = ServingEngine(gpt, page_size=4, max_batch_size=4, eos_id=-1)
        rng = np.random.RandomState(0)
        for i in range(4):
            eng.add_request(
                rng.randint(1, self.VOCAB, (4 + 3 * i,)).astype(np.int32),
                max_new_tokens=4)
        outs = eng.drain()
        assert len(outs) == 4

        # --- metrics snapshot: step-latency percentiles ---------------
        snap = eng.metrics.snapshot()
        assert snap["step_latency_ms"]["count"] >= 4
        for k in ("p50", "p95", "p99"):
            assert snap["step_latency_ms"][k] > 0

        # --- per-jit attribution: the unified ragged program ----------
        # (ISSUE 18: the default engine runs ONE serving.ragged_step
        # program for prefill chunks and decode ticks alike)
        costs = eng.stats()["jit_costs"]
        assert costs["serving.ragged_step"]["flops"] > 0
        assert costs["serving.ragged_step"]["compile_count"] >= 1
        # 4 prompts, one plan each (every prompt shorter than the
        # default 64-token chunk) — prefill latency records per plan
        assert snap["prefill_latency_ms"]["count"] == 4

        # --- Chrome trace: loadable, ragged dispatch nested under step
        path = profiler.export_chrome_trace(str(tmp_path / "serve.json"))
        events = json.load(open(path))["traceEvents"]
        by_name = {}
        for e in events:
            if e["ph"] == "X":
                by_name.setdefault(e["name"], []).append(e)
        assert "serving/step" in by_name
        assert "serving/ragged_step" in by_name
        step_ids = {e["args"]["span_id"] for e in by_name["serving/step"]}
        for child in by_name["serving/ragged_step"]:
            assert child["args"]["parent_id"] in step_ids
        # ragged spans carry the batch bucket and row count they ran at
        assert all("bucket" in e["args"] and "rows" in e["args"]
                   for e in by_name["serving/ragged_step"])


class TestRecordEventOverhead:
    def test_disabled_overhead_is_bounded(self):
        """With tracing disabled a RecordEvent is one aggregate update;
        it must stay far under the ISSUE's 2%-of-decode-step budget
        (decode steps are ~ms; assert sub-150µs per event even on a
        loaded 1-core CI host)."""
        import time

        n = 2000
        with RecordEvent("warm"):
            pass
        t0 = time.perf_counter()
        for _ in range(n):
            with RecordEvent("overhead_probe"):
                pass
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 150e-6, f"{per_call * 1e6:.1f}µs per event"


class TestRecordEventInTheProfilerTrace:
    """ISSUE 25: while a ``jax.profiler`` trace runs, a RecordEvent is a
    TraceMe event on its thread's line of the host plane — its args the
    event's stats, nested as the tracer's span stack says — on the clock
    the device planes use.  Nothing switches it on but the trace."""

    @staticmethod
    def _trace(tmp_path, body):
        import glob
        import os

        import jax
        from jax.profiler import ProfileData

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                      "*", "*.xplane.pb"))[0]
        out = []
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("probe/"):
                        out.append((e.name, line.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
        return out

    def test_args_are_stats_and_nesting_matches_the_tracer(self, tmp_path):
        def body():
            with RecordEvent("probe/step", lanes=48, kind="mixed",
                             ratio=0.5, flag=True,
                             skipped=[1, 2], long="x" * 200):
                with RecordEvent("probe/admit") as ev:
                    jnp.ones(8).block_until_ready()
                    ev.set(admitted=3, collapsed=7)
                with RecordEvent("probe/dispatch", rows=64):
                    pass

        profiler.enable_tracing()
        events = {e[0]: e for e in self._trace(tmp_path, body)}
        profiler.disable_tracing()
        assert set(events) == {"probe/step", "probe/admit",
                               "probe/dispatch"}
        # numbers and short strings travel; a list and a long string
        # stay on the tracer span only
        assert events["probe/step"][4] == {"lanes": 48, "kind": "mixed",
                                           "ratio": 0.5, "flag": 1}
        assert events["probe/admit"][4] == {"admitted": 3, "collapsed": 7}
        assert events["probe/dispatch"][4] == {"rows": 64}
        # one thread, one line; children inside the parent, in order
        assert len({e[1] for e in events.values()}) == 1
        _, _, s0, s1, _ = events["probe/step"]
        _, _, a0, a1, _ = events["probe/admit"]
        _, _, d0, d1, _ = events["probe/dispatch"]
        assert s0 <= a0 < a1 <= d0 <= d1 <= s1
        # ... which is what the tracer's own span stack recorded
        spans = {s.name: s for s in profiler.get_spans()}
        assert spans["probe/admit"].parent_id \
            == spans["probe/dispatch"].parent_id \
            == spans["probe/step"].span_id
        assert spans["probe/admit"].args == {"admitted": 3, "collapsed": 7}
        assert spans["probe/step"].args["skipped"] == [1, 2]

    def test_without_a_trace_nothing_is_emitted_and_set_still_works(self):
        profiler.enable_tracing()
        with RecordEvent("probe/quiet") as ev:
            ev.set(n=1)
        (sp,) = [s for s in profiler.get_spans() if s.name == "probe/quiet"]
        assert sp.args == {"n": 1}

    def test_train_batch_is_one_span_per_step_on_both_routes(self):
        """The span moved from fit()'s loop into Model.train_batch: one
        per step whether fit or the caller drives, with the host's phases
        as children."""
        from paddle_tpu import nn, optimizer

        net = nn.Linear(4, 2)
        model = paddle.Model(net)
        model.prepare(optimizer.SGD(0.1, parameters=net.parameters()),
                      nn.MSELoss())
        x = np.ones((8, 4), np.float32)
        y = np.zeros((8, 2), np.float32)
        before = histogram_snapshot("hapi.train_batch_ms")["count"]
        profiler.enable_tracing()
        model.train_batch([x], [y])
        model.fit([(x[i], y[i]) for i in range(8)], epochs=1, verbose=0)
        in_fit = histogram_snapshot("hapi.train_batch_ms")["count"] - before
        spans = profiler.get_spans()
        steps = [s for s in spans if s.name == "hapi/train_batch"]
        assert in_fit >= 2 and len(steps) == 1 + in_fit
        ids = {s.span_id for s in steps}
        for phase in ("inputs", "dispatch", "fetch_loss", "metrics"):
            kids = [s for s in spans
                    if s.name == f"hapi/train_batch/{phase}"]
            assert len(kids) == len(steps)
            assert {k.parent_id for k in kids} == ids
        (epoch,) = [s for s in spans if s.name == "hapi/fit.epoch"]
        assert steps[0].parent_id is None
        assert {s.parent_id for s in steps[1:]} == {epoch.span_id}
