"""Observability subsystem tests: StatsListener -> StatsStorage -> UIServer,
profiler tracing, NaN/Inf panic debug modes, and the unified profiler/
subsystem (span tracer -> Chrome trace, metrics registry -> Prometheus).

Reference parity: SURVEY.md §5 "Metrics/logging" (StatsListener/
InMemoryStatsStorage/FileStatsStorage/UIServer of deeplearning4j-ui-parent),
"Tracing/profiling" (ProfilingListener -> Chrome trace), and OpExecutioner
ProfilingMode OFF/BASIC/NAN_PANIC/INF_PANIC.
"""

import glob
import json
import os
import re
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import profiler
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.profiler import (MetricsRegistry, ProfilingMode,
                                         SpanTracer, trace_span)
from deeplearning4j_tpu.train.listeners import (MetricsListener,
                                                PerformanceListener,
                                                ProfilingListener,
                                                StatsListener)
from deeplearning4j_tpu.ui import (FileStatsStorage, InMemoryStatsStorage,
                                   StatsStorageRouter, UIServer)
from deeplearning4j_tpu.utils.environment import (Environment,
                                                  NumericsPanicError)


@pytest.fixture
def clean_profiler():
    """Tracing on against a clean buffer; everything off afterwards."""
    profiler.get_tracer().clear()
    profiler.enable_tracing()
    yield
    profiler.disable_tracing()
    profiler.set_profiling_mode(None)
    profiler.get_tracer().clear()


def _tiny_net_and_data(seed=0):
    net = zoo.LeNet(num_classes=3, input_shape=(1, 16, 16)).init()
    rng = np.random.RandomState(seed)
    x = rng.randn(8, 16 * 16).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 8)]
    return net, DataSet(x, y)


class TestStatsStorage:
    def test_in_memory_sessions_and_updates(self):
        st = InMemoryStatsStorage()
        events = []
        st.registerStatsStorageListener(lambda e: events.append(e.kind))
        st.putStaticInfo({"session_id": "a", "model_class": "X"})
        st.putUpdate({"session_id": "a", "iteration": 1, "score": 1.0})
        st.putUpdate({"session_id": "a", "iteration": 2, "score": 0.5})
        assert st.listSessionIDs() == ["a"]
        assert st.getStaticInfo("a")["model_class"] == "X"
        assert [u["iteration"] for u in st.getAllUpdates("a")] == [1, 2]
        assert st.getLatestUpdate("a")["score"] == 0.5
        assert st.getAllUpdatesAfter("a", 1)[0]["iteration"] == 2
        assert "new_session" in events and "update" in events

    def test_file_storage_reload(self, tmp_path):
        p = str(tmp_path / "stats.jsonl")
        st = FileStatsStorage(p)
        st.putStaticInfo({"session_id": "s1", "n_parameters": 7})
        st.putUpdate({"session_id": "s1", "iteration": 3, "score": 0.1})
        st.close()
        st2 = FileStatsStorage(p)   # reload from disk
        assert st2.listSessionIDs() == ["s1"]
        assert st2.getStaticInfo("s1")["n_parameters"] == 7
        assert st2.getLatestUpdate("s1")["iteration"] == 3
        st2.close()

    def test_router_fans_out(self, tmp_path):
        a, b = InMemoryStatsStorage(), InMemoryStatsStorage()
        r = StatsStorageRouter(a, b)
        r.putUpdate({"session_id": "x", "iteration": 1})
        assert a.getAllUpdates("x") and b.getAllUpdates("x")


class TestStatsListener:
    def test_records_per_layer_stats_from_fit(self):
        net, ds = _tiny_net_and_data()
        st = InMemoryStatsStorage()
        lst = StatsListener(st, frequency=1, session_id="t1")
        net.setListeners(lst)
        for _ in range(3):
            net.fit(ds)
        ups = st.getAllUpdates("t1")
        assert len(ups) == 3
        u = ups[-1]
        assert np.isfinite(u["score"])
        assert u["minibatch_size"] == 8
        # per-layer records carry param/update stats incl. the ratio chart's
        # numerator/denominator
        assert u["layers"], "no layer stats captured"
        some = next(iter(u["layers"].values()))
        for k in ("param_mean", "param_std", "param_norm", "update_norm",
                  "update_ratio"):
            assert np.isfinite(some[k])
        # training actually moved the weights
        assert any(rec["update_norm"] > 0 for rec in u["layers"].values())
        static = st.getStaticInfo("t1")
        assert static["n_parameters"] > 0
        assert static["model_class"] == "MultiLayerNetwork"

    def test_frequency_sampling(self):
        net, ds = _tiny_net_and_data()
        st = InMemoryStatsStorage()
        net.setListeners(StatsListener(st, frequency=2, session_id="t2"))
        for _ in range(4):
            net.fit(ds)
        iters = [u["iteration"] for u in st.getAllUpdates("t2")]
        assert iters == [2, 4]

    def test_histograms(self):
        net, ds = _tiny_net_and_data()
        st = InMemoryStatsStorage()
        net.setListeners(StatsListener(st, frequency=1, session_id="t3",
                                       with_histograms=True, hist_bins=10))
        net.fit(ds)
        u = st.getLatestUpdate("t3")
        some = next(iter(u["layers"].values()))
        assert len(some["hist_counts"]) == 10
        assert len(some["hist_range"]) == 2

    def test_works_on_computation_graph(self):
        g = zoo.SqueezeNet(num_classes=3, input_shape=(3, 32, 32)).init()
        rng = np.random.RandomState(0)
        x = rng.randn(2, 3, 32, 32).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 2)]
        st = InMemoryStatsStorage()
        g.setListeners(StatsListener(st, frequency=1, session_id="g1"))
        g.fit(DataSet(x, y))
        u = st.getLatestUpdate("g1")
        assert u is not None and u["layers"]


class TestUIServer:
    def test_dashboard_endpoints(self):
        net, ds = _tiny_net_and_data()
        st = InMemoryStatsStorage()
        net.setListeners(StatsListener(st, frequency=1, session_id="ui1"))
        net.fit(ds)
        net.fit(ds)
        server = UIServer(port=0).attach(st)
        try:
            base = server.url
            sessions = json.load(urllib.request.urlopen(base + "api/sessions"))
            assert "ui1" in sessions
            ov = json.load(urllib.request.urlopen(
                base + "api/overview?session=ui1"))
            assert len(ov["iterations"]) == 2
            assert all(np.isfinite(s) for s in ov["scores"])
            mo = json.load(urllib.request.urlopen(
                base + "api/model?session=ui1"))
            assert mo["latest"] and mo["ratio_series"]
            page = urllib.request.urlopen(base).read().decode()
            assert "training UI" in page and "Score vs iteration" in page
        finally:
            server.stop()

    def test_histograms_endpoint(self):
        net, ds = _tiny_net_and_data()
        st = InMemoryStatsStorage()
        net.setListeners(StatsListener(st, frequency=1, session_id="ui2",
                                       with_histograms=True, hist_bins=12))
        net.fit(ds)
        server = UIServer(port=0).attach(st)
        try:
            h = json.load(urllib.request.urlopen(
                server.url + "api/histograms?session=ui2"))
            assert h["iteration"] is not None and h["hists"]
            first = next(iter(h["hists"].values()))
            assert len(first["counts"]) == 12
            assert len(first["range"]) == 2
            # page renders the histogram card
            page = urllib.request.urlopen(server.url).read().decode()
            assert "Parameter histograms" in page
        finally:
            server.stop()


class TestProfiling:
    def test_profiling_listener_writes_trace(self, tmp_path):
        net, ds = _tiny_net_and_data()
        d = str(tmp_path / "trace")
        net.setListeners(ProfilingListener(d, start_iter=1, n_iters=2))
        for _ in range(4):
            net.fit(ds)
        files = glob.glob(os.path.join(d, "**", "*"), recursive=True)
        assert any(("trace" in f or f.endswith(".pb") or ".xplane" in f)
                   and os.path.isfile(f) for f in files), files


class TestNumericsPanic:
    def test_nan_panic_raises(self):
        net, ds = _tiny_net_and_data()
        bad = DataSet(np.full((8, 256), np.nan, np.float32), ds.labels)
        Environment.reset()
        os.environ["DL4J_TPU_NAN_PANIC"] = "1"
        try:
            with pytest.raises(NumericsPanicError, match="NAN_PANIC"):
                net.fit(bad)
        finally:
            os.environ.pop("DL4J_TPU_NAN_PANIC", None)
            Environment.reset()

    def test_no_panic_when_disabled(self):
        net, ds = _tiny_net_and_data()
        bad = DataSet(np.full((8, 256), np.nan, np.float32), ds.labels)
        Environment.reset()
        net.fit(bad)   # silently produces NaN loss, as configured
        assert np.isnan(net.score())

    def test_unified_mode_panics_fit_loop(self):
        """set_profiling_mode(NAN_PANIC) == the env-var knob (unified)."""
        net, ds = _tiny_net_and_data()
        bad = DataSet(np.full((8, 256), np.nan, np.float32), ds.labels)
        profiler.set_profiling_mode(ProfilingMode.NAN_PANIC)
        try:
            with pytest.raises(NumericsPanicError, match="NAN_PANIC"):
                net.fit(bad)
        finally:
            profiler.set_profiling_mode(None)


# ---------------------------------------------------------------------------
# profiler/ subsystem: span tracer
# ---------------------------------------------------------------------------

class TestSpanTracer:
    def test_disabled_records_nothing(self):
        t = profiler.get_tracer()
        t.clear()
        assert not profiler.tracing_enabled()
        with trace_span("should_not_appear"):
            pass
        assert len(t) == 0

    def test_nesting(self, clean_profiler):
        with trace_span("outer", layer="conv"):
            with trace_span("inner"):
                pass
        evs = profiler.get_tracer().events()
        outer = next(e for e in evs if e["name"] == "outer")
        inner = next(e for e in evs if e["name"] == "inner")
        # child's interval is contained in the parent's
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        assert inner["args"]["depth"] == 1
        assert outer["args"]["layer"] == "conv"

    def test_decorator(self, clean_profiler):
        @trace_span("decorated_fn")
        def f(a, b):
            return a + b
        assert f(2, 3) == 5
        assert any(e["name"] == "decorated_fn"
                   for e in profiler.get_tracer().events())

    def test_thread_safety(self, clean_profiler):
        t = profiler.get_tracer()
        barrier = threading.Barrier(8)   # overlap all workers so OS thread
                                         # ids can't be reused between them

        def worker(i):
            barrier.wait()
            for _ in range(50):
                with trace_span(f"w{i}"):
                    pass
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        # a collection while tracing is on leaves a host:gc span of its own
        evs = [e for e in t.events() if e["name"].startswith("w")]
        assert len(evs) == 8 * 50
        assert len({e["tid"] for e in evs}) == 8   # spans keep their thread

    def test_ring_buffer_retention(self):
        t = SpanTracer(capacity=10)
        profiler.enable_tracing()
        try:
            for i in range(25):
                with trace_span(f"s{i}", tracer=t):
                    pass
        finally:
            profiler.disable_tracing()
        evs = t.events()
        assert len(evs) == 10
        assert evs[0]["name"] == "s15" and evs[-1]["name"] == "s24"

    def test_chrome_trace_json_validity(self, clean_profiler):
        with trace_span("a"):
            with trace_span("b"):
                pass
        doc = json.loads(profiler.get_tracer().export_chrome_trace())
        assert "traceEvents" in doc
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(xs) == 2
        for ev in xs:
            for key in ("ph", "ts", "name", "dur", "pid", "tid"):
                assert key in ev
            assert ev["dur"] >= 0
        # thread-name metadata present for perfetto row labels
        assert any(e["ph"] == "M" and e["name"] == "thread_name"
                   for e in doc["traceEvents"])

    def test_export_to_file(self, clean_profiler, tmp_path):
        with trace_span("file_span"):
            pass
        p = str(tmp_path / "trace.json")
        profiler.get_tracer().export_chrome_trace(p)
        with open(p) as f:
            doc = json.load(f)
        assert any(e["name"] == "file_span" for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# profiler/ subsystem: metrics registry
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9eE\.\+\-]+$|^\S+ \+Inf$')


def _assert_valid_exposition(text):
    """Minimal Prometheus text-format 0.0.4 validation."""
    assert text.endswith("\n")
    for line in text.strip().split("\n"):
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert _SAMPLE_RE.match(line), f"bad exposition line: {line!r}"


class TestMetricsRegistry:
    def test_counter_semantics(self):
        r = MetricsRegistry()
        c = r.counter("c_total", "help me")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_semantics(self):
        r = MetricsRegistry()
        g = r.gauge("g", "")
        g.set(10)
        g.inc()
        g.dec(0.5)
        assert g.value == 10.5

    def test_histogram_semantics(self):
        r = MetricsRegistry()
        h = r.histogram("h_seconds", "", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(55.55)
        text = r.exposition()
        assert 'h_seconds_bucket{le="0.1"} 1' in text
        assert 'h_seconds_bucket{le="1"} 2' in text
        assert 'h_seconds_bucket{le="10"} 3' in text      # cumulative
        assert 'h_seconds_bucket{le="+Inf"} 4' in text
        assert "h_seconds_count 4" in text

    def test_labels(self):
        r = MetricsRegistry()
        c = r.counter("ops_total", "", labelnames=("op", "status"))
        c.labels(op="add", status="ok").inc(3)
        c.labels("mul", "err").inc()
        with pytest.raises(ValueError):
            c.inc()            # labelled family: direct ops are an error
        with pytest.raises(ValueError):
            c.labels(op="add")  # wrong arity
        text = r.exposition()
        assert 'ops_total{op="add",status="ok"} 3' in text
        assert 'ops_total{op="mul",status="err"} 1' in text

    def test_get_or_create_and_type_conflict(self):
        r = MetricsRegistry()
        a = r.counter("same", "")
        b = r.counter("same", "")
        assert a is b
        with pytest.raises(ValueError):
            r.gauge("same", "")

    def test_exposition_parses(self):
        r = MetricsRegistry()
        r.counter("c_total", "a counter").inc()
        r.gauge("g", 'with "quotes"').set(-1.5)
        h = r.histogram("h", "", labelnames=("op",), buckets=(1,))
        h.labels(op='we"ird').observe(2)
        _assert_valid_exposition(r.exposition())

    def test_thread_safety(self):
        r = MetricsRegistry()
        c = r.counter("n_total", "")

        def worker():
            for _ in range(1000):
                c.inc()
        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == 8000


# ---------------------------------------------------------------------------
# ProfilingMode + op-dispatch instrumentation
# ---------------------------------------------------------------------------

class TestOpDispatchProfiling:
    def test_mode_derived_from_environment(self):
        Environment.reset()
        os.environ["DL4J_TPU_NAN_PANIC"] = "1"
        try:
            Environment.reset()
            assert profiler.get_profiling_mode() is ProfilingMode.NAN_PANIC
        finally:
            os.environ.pop("DL4J_TPU_NAN_PANIC", None)
            Environment.reset()
        assert profiler.get_profiling_mode() is ProfilingMode.OFF

    def test_basic_mode_counts_dispatches(self):
        from deeplearning4j_tpu.ops import registry as R
        reg = profiler.get_registry()
        profiler.set_profiling_mode(ProfilingMode.BASIC)
        try:
            c = reg.get("dl4j_op_dispatch_total")
            before = c.labels(op="abs").value if c is not None else 0
            R.exec_op("abs", np.array([-1.0, 2.0]))
            R.exec_op("abs", np.array([3.0]))
            after = reg.get("dl4j_op_dispatch_total").labels(op="abs").value
            assert after - before == 2
            lat = reg.get("dl4j_op_dispatch_seconds")
            assert lat is not None
        finally:
            profiler.set_profiling_mode(None)

    def test_op_nan_panic(self):
        from deeplearning4j_tpu.ops import registry as R
        profiler.set_profiling_mode(ProfilingMode.NAN_PANIC)
        try:
            with pytest.raises(NumericsPanicError, match="op 'log'"):
                R.exec_op("log", np.array([-1.0], np.float32))
        finally:
            profiler.set_profiling_mode(None)

    def test_op_inf_panic(self):
        from deeplearning4j_tpu.ops import registry as R
        profiler.set_profiling_mode(ProfilingMode.INF_PANIC)
        try:
            with pytest.raises(NumericsPanicError, match="op 'reciprocal'"):
                R.exec_op("reciprocal", np.array([0.0], np.float32))
        finally:
            profiler.set_profiling_mode(None)

    def test_off_mode_is_uninstrumented(self):
        from deeplearning4j_tpu.ops import registry as R
        assert profiler.get_profiling_mode() is ProfilingMode.OFF
        t = profiler.get_tracer()
        t.clear()
        out = R.exec_op("neg", np.array([1.0]))
        assert float(out[0]) == -1.0
        # nothing per call; the op's build, once a program, may be there
        # (compile:* spans are recorded whatever the mode)
        assert {e["name"].partition(":")[0] for e in t.events()} \
            <= {"compile"}
        t.clear()
        R.exec_op("neg", np.array([2.0]))
        assert len(t) == 0

    def test_op_spans_when_tracing(self, clean_profiler):
        from deeplearning4j_tpu.ops import registry as R
        R.exec_op("square", np.array([2.0]))
        assert any(e["name"] == "op:square"
                   for e in profiler.get_tracer().events())


# ---------------------------------------------------------------------------
# listener-bus -> registry bridges
# ---------------------------------------------------------------------------

class TestMetricsListener:
    def test_bridges_fit_into_registry(self):
        net, ds = _tiny_net_and_data()
        reg = MetricsRegistry()
        net.setListeners(MetricsListener(registry=reg))
        net.fit(ds, epochs=2)
        assert reg.get("dl4j_train_iterations_total").value == 2
        assert reg.get("dl4j_train_epochs_total").value == 2
        assert np.isfinite(reg.get("dl4j_train_score").value)
        assert reg.get("dl4j_train_iteration_seconds").count == 2
        _assert_valid_exposition(reg.exposition())

    def test_performance_listener_emits_throughput(self):
        net, ds = _tiny_net_and_data()
        net.setListeners(PerformanceListener(frequency=1, out=lambda m: None))
        for _ in range(3):
            net.fit(ds)
        g = profiler.get_registry().get("dl4j_throughput_samples_per_sec")
        assert g is not None and g.value > 0
        gb = profiler.get_registry().get("dl4j_throughput_batches_per_sec")
        assert gb is not None and gb.value > 0


# ---------------------------------------------------------------------------
# UIServer profiler endpoints
# ---------------------------------------------------------------------------

class TestProfilerEndpoints:
    def test_metrics_endpoint(self):
        from deeplearning4j_tpu.ops import registry as R
        profiler.set_profiling_mode(ProfilingMode.BASIC)
        try:
            R.exec_op("exp", np.array([1.0]))
            net, ds = _tiny_net_and_data()
            net.fit(ds)
        finally:
            profiler.set_profiling_mode(None)
        server = UIServer(port=0).attach(InMemoryStatsStorage())
        try:
            resp = urllib.request.urlopen(server.url + "metrics")
            ctype = resp.headers.get("Content-Type", "")
            text = resp.read().decode()
        finally:
            server.stop()
        assert ctype.startswith("text/plain")
        _assert_valid_exposition(text)
        # op-dispatch counters and compile-cache hit/miss are exposed
        assert 'dl4j_op_dispatch_total{op="exp"}' in text
        assert "dl4j_native_compile_cache_hits_total" in text
        assert "dl4j_native_compile_cache_misses_total" in text
        assert "dl4j_train_step_seconds_count" in text
        assert "dl4j_train_data_wait_seconds_count" in text

    def test_trace_endpoint_nested_fit_spans(self, clean_profiler):
        net, ds = _tiny_net_and_data()
        net.fit(ds, epochs=2)
        server = UIServer(port=0).attach(InMemoryStatsStorage())
        try:
            doc = json.load(urllib.request.urlopen(server.url + "trace"))
        finally:
            server.stop()
        evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        for ev in evs:
            for key in ("ph", "ts", "name"):
                assert key in ev
        names = {e["name"] for e in evs}
        assert {"fit:epoch", "fit:dispatch", "fit:pull"} <= names
        # real nesting from a real fit() run: step inside its epoch span
        epochs = [e for e in evs if e["name"] == "fit:epoch"]
        steps = [e for e in evs if e["name"] == "fit:dispatch"]
        assert len(epochs) == 2 and len(steps) == 2
        contained = sum(
            1 for s in steps for ep in epochs
            if ep["ts"] <= s["ts"]
            and s["ts"] + s["dur"] <= ep["ts"] + ep["dur"] + 1e-3)
        assert contained == 2
