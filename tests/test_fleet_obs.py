"""Fleet observability plane (ISSUE 16): end-to-end request tracing,
cross-host metric federation, SLO burn-rate gates, and the crash flight
recorder.

The acceptance pins:

- **Tracing**: an ingress request is traced end-to-end — the response
  carries its ``trace_id``, and admission -> queue -> dispatch ->
  respond spans (with the coalesced batch's fan-in links) share one
  trace. A malformed ``traceparent`` mints instead of failing.
- **Federation**: a two-host scrape yields a fleet p99 that matches the
  by-hand merged-bucket computation; counters sum, gauges keep per-host
  identity under a ``host`` label.
- **SLO gates**: a deadline storm flips the multi-window burn-rate gate
  to failing, and a clean drain flips it back through the fast window
  while the slow window still remembers the storm; the
  ``dl4j_slo_burn_rate`` gauge reflects both windows.
- **Flight recorder**: always-on bounded ring; a crash (fit unwind,
  dispatch timeout, dead peer) dumps a debug bundle; a process killed
  mid-dispatch leaves a Perfetto-loadable truncated trace stream AND a
  bundle (``pytest -m chaos``).
- **Multi-host**: two OS worker processes plus an ingress request under
  one ``traceparent`` produce spans from several pids that merge into
  one Perfetto-loadable trace (``pytest -m multihost``).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from deeplearning4j_tpu import profiler
from deeplearning4j_tpu.faults import ServingLoad
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.profiler import (FlightRecorder, HistogramSnapshot,
                                         MetricsAggregator, SLOEngine,
                                         SLOGate, SLOSpec, TraceContext,
                                         merge_chrome_traces,
                                         parse_exposition, record_span,
                                         run_span, spans_for_trace)
from deeplearning4j_tpu.profiler import tracecontext
from deeplearning4j_tpu.profiler.metrics import MetricsRegistry
from deeplearning4j_tpu.serving import (DeadlineExceededError, HttpIngress,
                                        ModelRegistry, ModelServer,
                                        ServerOverloadedError,
                                        ServingRequest)
from deeplearning4j_tpu.train import updaters

NIN, NOUT = 4, 3
REPO = Path(__file__).resolve().parents[1]


def mlp(seed=42):
    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater(updaters.Sgd(0.1)).list()
            .layer(DenseLayer(nOut=8, activation="relu"))
            .layer(OutputLayer(nOut=NOUT, lossFunction="mcxent",
                               activation="softmax"))
            .setInputType(InputType.feedForward(NIN))
            .build())
    return MultiLayerNetwork(conf).init()


def feats(rows, seed=0):
    return np.random.RandomState(seed).randn(rows, NIN).astype(np.float32)


@pytest.fixture()
def net():
    return mlp()


@pytest.fixture()
def traced():
    """Tracing on with a clean ring; everything restored afterwards so
    other tests see the default ship state."""
    tracer = profiler.get_tracer()
    tracer.clear()
    profiler.enable_tracing()
    try:
        yield tracer
    finally:
        profiler.disable_tracing()
        tracer.clear()


def post_json(url, path, payload, headers=None, timeout=30.0):
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    req = urllib.request.Request(f"{url}{path}",
                                 data=json.dumps(payload).encode(),
                                 headers=h)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def get(url, path, headers=None, timeout=10.0):
    req = urllib.request.Request(f"{url}{path}", headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


# ========================================================== trace context
@pytest.mark.quick
class TestTraceContext:
    def test_traceparent_roundtrip(self):
        ctx = TraceContext.new()
        assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
        parsed = TraceContext.from_traceparent(ctx.to_traceparent())
        assert parsed.trace_id == ctx.trace_id
        assert parsed.span_id == ctx.span_id

    def test_malformed_traceparent_mints_none(self):
        bad = [None, "", "garbage", "00-zz-11-01",
               f"ff-{'a' * 32}-{'b' * 16}-01",        # forbidden version
               f"00-{'0' * 32}-{'b' * 16}-01",        # all-zero trace id
               f"00-{'a' * 32}-{'0' * 16}-01"]        # all-zero span id
        for header in bad:
            assert TraceContext.from_traceparent(header) is None, header

    def test_child_keeps_trace_new_span(self):
        root = TraceContext.new()
        kid = root.child()
        assert kid.trace_id == root.trace_id
        assert kid.span_id != root.span_id
        assert kid.parent_id == root.span_id

    def test_record_span_gated(self, traced):
        ctx = TraceContext.new()
        record_span("x", None, 0.0, 1.0)              # ctx None: no-op
        profiler.disable_tracing()
        record_span("x", ctx, 0.0, 1.0)               # tracing off: no-op
        assert spans_for_trace(ctx.trace_id) == []
        profiler.enable_tracing()
        record_span("x", ctx, 0.0, 1.0, args={"k": "v"})
        spans = spans_for_trace(ctx.trace_id)
        assert [s["name"] for s in spans] == ["x"]
        assert spans[0]["args"]["span_id"] == ctx.span_id
        assert spans[0]["args"]["k"] == "v"

    def test_span_nests_under_ambient_and_records_errors(self, traced):
        root = TraceContext.new()
        with tracecontext.use(root):
            with tracecontext.span("hop") as hop:
                assert hop.trace_id == root.trace_id
                assert hop.parent_id == root.span_id
            with pytest.raises(ValueError):
                with tracecontext.span("boom"):
                    raise ValueError("x")
        names = {s["name"]: s for s in spans_for_trace(root.trace_id)}
        assert set(names) == {"hop", "boom"}
        assert names["boom"]["args"]["error"] == "ValueError"

    def test_run_span_stamps_ambient_spans(self, traced):
        with run_span("train:run", model="T") as ctx:
            with profiler.trace_span("train:step"):
                pass
        spans = spans_for_trace(ctx.trace_id)
        names = [s["name"] for s in spans]
        assert "train:run" in names and "train:step" in names
        root = next(s for s in spans if s["name"] == "train:run")
        assert root["args"]["run_id"] == ctx.trace_id

    def test_merge_chrome_traces_dedups_metadata(self):
        meta = {"name": "thread_name", "ph": "M", "pid": 1, "tid": 2,
                "args": {"name": "w"}}
        ev = {"name": "x", "ph": "X", "pid": 1, "tid": 2, "ts": 0, "dur": 1}
        merged = merge_chrome_traces([
            {"traceEvents": [meta, ev]}, [dict(meta), dict(ev)]])
        metas = [e for e in merged["traceEvents"] if e.get("ph") == "M"]
        assert len(metas) == 1
        assert len(merged["traceEvents"]) == 3
        json.dumps(merged)    # Perfetto-loadable = valid JSON document


# ===================================================== serving trace e2e
class TestServingTraceE2E:
    def test_ingress_request_traced_end_to_end(self, net, traced):
        incoming = TraceContext("ab" * 16, "cd" * 8)
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            with HttpIngress(reg, port=0) as ing:
                code, payload, headers = post_json(
                    ing.url, "/v1/models/m:predict",
                    {"instances": feats(2).tolist()},
                    headers={"traceparent": incoming.to_traceparent()})
        assert code == 200
        # THE e2e pin: the response names the trace it belongs to
        assert payload["trace_id"] == incoming.trace_id
        assert headers["traceparent"].split("-")[1] == incoming.trace_id
        spans = spans_for_trace(incoming.trace_id)
        names = {s["name"] for s in spans}
        assert {"ingress:request", "serve:route", "serve:admission",
                "serve:queue", "serve:coalesce", "serve:dispatch",
                "serve:terminal", "ingress:respond"} <= names
        dispatch = next(s for s in spans if s["name"] == "serve:dispatch")
        # fan-in: the dispatch span links the request(s) it served
        links = dispatch["args"]["links"]
        assert any(l["trace_id"] == incoming.trace_id for l in links)
        terminal = next(s for s in spans if s["name"] == "serve:terminal")
        assert terminal["args"]["outcome"] == "completed"

    def test_response_has_trace_id_with_tracing_off(self, net):
        assert not profiler.tracing_enabled()
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            with HttpIngress(reg, port=0) as ing:
                code, payload, headers = post_json(
                    ing.url, "/v1/models/m:predict",
                    {"instances": feats(1).tolist()})
        assert code == 200
        # IDs are always minted; recording stays off
        assert len(payload["trace_id"]) == 32
        assert "traceparent" in headers
        assert spans_for_trace(payload["trace_id"]) == []

    def test_coalesced_fanin_links_every_request(self, net, traced):
        sv = ModelServer(net, batch_limit=8, coalesce_ms=60.0)
        try:
            sv.warmup([(NIN,)])
            reqs = []

            def submit(seed):
                reqs.append(sv.submit(feats(1, seed=seed)))

            ts = [threading.Thread(target=submit, args=(i,))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            for r in reqs:
                r.get(30.0)
        finally:
            sv.close()
        # one coalesced dispatch span, linking BOTH request roots
        dispatches = [s for s in profiler.get_tracer().events()
                      if s["name"] == "serve:dispatch"]
        fan_in = [s for s in dispatches
                  if s["args"].get("requests") == 2]
        assert fan_in, [s["args"] for s in dispatches]
        link_traces = {l["trace_id"] for l in fan_in[-1]["args"]["links"]}
        assert link_traces == {r.trace.trace_id for r in reqs}
        # each request keeps its own trace with its own terminal span
        for r in reqs:
            names = {s["name"] for s in spans_for_trace(r.trace.trace_id)}
            assert "serve:terminal" in names


# ==================================================== metric federation
@pytest.mark.quick
class TestMetricsAggregator:
    HOST_A = """\
# HELP dl4j_serving_latency_seconds Request latency
# TYPE dl4j_serving_latency_seconds histogram
dl4j_serving_latency_seconds_bucket{le="0.1"} 5
dl4j_serving_latency_seconds_bucket{le="0.5"} 8
dl4j_serving_latency_seconds_bucket{le="+Inf"} 10
dl4j_serving_latency_seconds_sum 2.0
dl4j_serving_latency_seconds_count 10
# TYPE dl4j_serving_requests_total counter
dl4j_serving_requests_total{outcome="completed"} 10
# TYPE dl4j_serving_queue_depth gauge
dl4j_serving_queue_depth 3
"""
    HOST_B = """\
# TYPE dl4j_serving_latency_seconds histogram
dl4j_serving_latency_seconds_bucket{le="0.1"} 1
dl4j_serving_latency_seconds_bucket{le="0.5"} 5
dl4j_serving_latency_seconds_bucket{le="+Inf"} 9
dl4j_serving_latency_seconds_sum 3.0
dl4j_serving_latency_seconds_count 9
# TYPE dl4j_serving_requests_total counter
dl4j_serving_requests_total{outcome="completed"} 7
# TYPE dl4j_serving_queue_depth gauge
dl4j_serving_queue_depth 1
"""

    def _agg(self, clock=None):
        agg = MetricsAggregator(max_age=30.0,
                                clock=clock or time.monotonic)
        agg.ingest("a", self.HOST_A)
        agg.ingest("b", self.HOST_B)
        return agg

    def test_fleet_histogram_matches_by_hand_merge(self):
        agg = self._agg()
        snap = agg.fleet_histogram("dl4j_serving_latency_seconds")
        # by hand: cumulative counts sum per bound across hosts
        assert snap.bounds == [0.1, 0.5]
        assert snap.cumulative == [5 + 1, 8 + 5]
        assert snap.count == 19 and snap.sum == 5.0
        # fleet p50 by hand: rank = 0.5*19 = 9.5 falls in (0.1, 0.5]
        # with 6 below and 7 in-bucket -> 0.1 + 0.4 * (9.5-6)/7
        rank, below, in_bucket = 0.5 * 19, 6, 7
        expect_p50 = 0.1 + (0.5 - 0.1) * (rank - below) / in_bucket
        assert abs(agg.quantile("dl4j_serving_latency_seconds", 0.5)
                   - expect_p50) < 1e-12
        # p99 rank (18.81) lands in +Inf: clamps to the top finite bound
        assert agg.quantile("dl4j_serving_latency_seconds", 0.99) == 0.5
        # and the merged quantile math is the same code a local
        # histogram uses (single-host sanity)
        one = HistogramSnapshot([0.1, 0.5], [5, 8], 10, 2.0)
        assert one.quantile(0.5) == 0.1 + 0.4 * (5 - 5) / 3

    def test_counters_sum_and_gauges_keep_host_label(self):
        agg = self._agg()
        assert agg.counter_total("dl4j_serving_requests_total",
                                 {"outcome": "completed"}) == 17.0
        text = agg.exposition()
        assert 'dl4j_serving_requests_total{outcome="completed"} 17' in text
        assert 'dl4j_serving_queue_depth{host="a"} 3' in text
        assert 'dl4j_serving_queue_depth{host="b"} 1' in text
        assert "dl4j_fleet_members 2" in text
        assert "dl4j_fleet_scrapes_total 2" in text
        # merged histogram renders re-cumulated buckets
        assert ('dl4j_serving_latency_seconds_bucket{le="0.5"} 13'
                in text)

    def test_stale_host_drops_out_of_the_merge(self):
        now = [0.0]
        agg = self._agg(clock=lambda: now[0])
        assert agg.hosts() == ["a", "b"]
        now[0] = 20.0
        agg.ingest("b", self.HOST_B)   # b refreshes, a goes stale at 31
        now[0] = 31.0
        assert agg.hosts() == ["b"]
        assert agg.counter_total("dl4j_serving_requests_total",
                                 {"outcome": "completed"}) == 7.0

    def test_fleet_load_totals(self):
        agg = self._agg()
        agg.ingest_load("a", {"totals": {"queue_depth": 3, "max_queue": 8,
                                         "breakers_open": 0,
                                         "shed_rate": 0.2, "ready": True}})
        agg.ingest_load("b", {"totals": {"queue_depth": 1, "max_queue": 8,
                                         "breakers_open": 1,
                                         "shed_rate": 0.0, "ready": True}})
        load = agg.fleet_load()
        assert load["totals"]["queue_depth"] == 4
        assert load["totals"]["max_queue"] == 16
        assert load["totals"]["breakers_open"] == 1
        assert load["totals"]["shed_rate"] == pytest.approx(0.1)
        assert load["totals"]["ready"] is True
        assert load["totals"]["hosts"] == 2

    def test_parse_exposition_tolerates_exemplars(self):
        text = ('# TYPE h histogram\n'
                'h_bucket{le="1.0"} 4 # {trace_id="abc"} 0.73\n'
                'h_bucket{le="+Inf"} 5\n'
                'h_sum 2.5\nh_count 5\n')
        fam = parse_exposition(text)["h"]
        assert fam.samples[("_bucket", (("le", "1.0"),))] == 4.0
        assert fam.samples[("_count", ())] == 5.0


# ======================================================== SLO burn gates
@pytest.mark.quick
class TestSLOGates:
    def _engine(self):
        reg = MetricsRegistry()
        lat = reg.histogram("dl4j_serving_latency_seconds", "lat",
                            buckets=(0.1, 0.25, 1.0))
        outcomes = reg.counter("dl4j_serving_requests_total", "req",
                               labelnames=("outcome",))
        clock = [0.0]
        spec = SLOSpec("serve", objective=0.9, latency_bound=0.25,
                       shed_rate=0.2, availability=0.99,
                       windows=(60.0, 600.0))
        engine = SLOEngine([spec], registry=reg,
                           clock=lambda: clock[0])
        return reg, lat, outcomes, clock, engine

    def test_deadline_storm_flips_gate_then_drain_recovers(self):
        reg, lat, outcomes, clock, engine = self._engine()
        gate = SLOGate(engine)
        # t=0: clean baseline sample
        for _ in range(20):
            lat.observe(0.05)
            outcomes.labels(outcome="completed").inc()
        assert bool(gate())
        # t=30: the storm — slow requests + deadline sheds
        clock[0] = 30.0
        for _ in range(20):
            lat.observe(0.9)
            outcomes.labels(outcome="shed_deadline").inc()
        verdict = gate()
        assert not verdict
        assert verdict.failures == ["serve"]
        windows = verdict.detail["specs"]["serve"]["windows"]
        # the baseline evaluate snapshotted the clean traffic, so the
        # storm delta is 100% bad: latency burn 1.0/0.1 = 10, shed
        # burn 1.0/0.2 = 5
        assert windows["fast"]["burn"] > 1.0
        assert windows["slow"]["burn"] > 1.0
        assert windows["fast"]["criteria"]["latency"] == pytest.approx(10.0)
        assert windows["fast"]["criteria"]["shed"] == pytest.approx(5.0)
        # ...and the gauge carries both windows
        burn = reg.get("dl4j_slo_burn_rate")
        children = {lvals: child.value
                    for lvals, child in burn.children().items()}
        assert children[("serve", "fast")] > 1.0
        assert children[("serve", "slow")] > 1.0
        # t=100: drained — only clean traffic since the storm sample.
        # The fast window (references t=30) sees zero bad observations;
        # the slow window still remembers the storm. Multi-window rule:
        # failing requires BOTH, so the gate flips back immediately.
        clock[0] = 100.0
        for _ in range(20):
            lat.observe(0.05)
            outcomes.labels(outcome="completed").inc()
        verdict = gate()
        assert bool(verdict)
        windows = verdict.detail["specs"]["serve"]["windows"]
        assert windows["fast"]["burn"] <= 1.0
        assert windows["slow"]["burn"] > 1.0
        children = {lvals: child.value
                    for lvals, child in burn.children().items()}
        assert children[("serve", "fast")] <= 1.0
        assert children[("serve", "slow")] > 1.0

    def test_step_time_regression_burn(self):
        reg = MetricsRegistry()
        step = reg.histogram("dl4j_train_iteration_seconds", "step",
                             buckets=(0.1, 1.0))
        clock = [0.0]
        engine = SLOEngine(
            [SLOSpec("train", step_time_baseline=0.1,
                     step_time_regression=1.2)],
            registry=reg, clock=lambda: clock[0])
        step.observe(0.1)
        engine.evaluate()
        clock[0] = 30.0
        for _ in range(10):
            step.observe(0.3)          # 2.5x the allowed 0.12 mean
        detail = engine.evaluate()
        assert detail["failing"] == ["train"]
        crit = detail["specs"]["train"]["windows"]["fast"]["criteria"]
        assert crit["step_time"] == pytest.approx(0.3 / 0.12)

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            SLOSpec("x", objective=1.5)
        with pytest.raises(ValueError):
            SLOSpec("x", shed_rate=0.0)
        with pytest.raises(ValueError):
            SLOSpec("x", availability=1.0)
        with pytest.raises(ValueError):
            SLOSpec("x", windows=(600.0, 60.0))

    def test_verdict_repr_and_bool(self):
        ok = SLOGate(SLOEngine([SLOSpec("s", latency_bound=1.0)],
                               registry=MetricsRegistry()))()
        assert bool(ok) and "passing" in repr(ok)


# ============================================================= exemplars
@pytest.mark.quick
class TestExemplars:
    def test_exemplar_rendered_only_in_openmetrics(self):
        reg = MetricsRegistry()
        h = reg.histogram("dl4j_x_seconds", "x", buckets=(0.1, 1.0))
        h.observe(0.05, exemplar="ab" * 16)
        h.observe(0.5)                   # no exemplar on this bucket
        text = reg.exposition()
        assert "trace_id" not in text    # 0.0.4 dialect: no exemplars
        assert not text.rstrip().endswith("# EOF")
        om = reg.exposition(openmetrics=True)
        assert ('dl4j_x_seconds_bucket{le="0.1"} 1 '
                '# {trace_id="' + "ab" * 16 + '"} 0.05') in om
        assert om.rstrip().endswith("# EOF")

    def test_latest_exemplar_wins_per_bucket(self):
        reg = MetricsRegistry()
        h = reg.histogram("dl4j_y_seconds", "y", buckets=(1.0,))
        h.observe(0.1, exemplar="first")
        h.observe(0.2, exemplar="second")
        om = reg.exposition(openmetrics=True)
        assert 'trace_id="second"' in om and 'trace_id="first"' not in om

    def test_serving_latency_carries_trace_exemplar(self, net, traced):
        sv = ModelServer(net, batch_limit=4, coalesce_ms=0.0,
                         name="exemplar-test")
        try:
            sv.warmup([(NIN,)])
            req = sv.submit(feats(1))
            req.get(30.0)
        finally:
            sv.close()
        om = profiler.get_registry().exposition(openmetrics=True)
        assert f'trace_id="{req.trace.trace_id}"' in om


# ======================================================= flight recorder
@pytest.mark.quick
class TestFlightRecorder:
    def test_ring_is_bounded_and_always_on(self):
        rec = FlightRecorder(capacity=8)
        assert not profiler.tracing_enabled()   # no gate: always on
        for i in range(20):
            rec.record("k", i=i)
        evs = rec.events()
        assert len(evs) == 8
        assert [e["i"] for e in evs] == list(range(12, 20))
        assert rec.events(last=2)[-1]["i"] == 19

    def test_dump_bundle_contents_and_rate_limit(self, tmp_path):
        rec = FlightRecorder(capacity=16, directory=str(tmp_path),
                             min_dump_interval=60.0)
        rec.record("serving:dispatch", server="s", rows=2)
        path = rec.dump("dispatch_timeout",
                        exc=TimeoutError("replica hung"))
        assert path is not None
        bundle = Path(path)
        for name in ("events.json", "trace.json", "metrics.txt",
                     "config.json", "reason.txt"):
            assert (bundle / name).exists(), name
        events = json.loads((bundle / "events.json").read_text())
        assert any(e["kind"] == "serving:dispatch" for e in events)
        reason = (bundle / "reason.txt").read_text()
        assert "dispatch_timeout" in reason and "replica hung" in reason
        config = json.loads((bundle / "config.json").read_text())
        assert config["pid"] == os.getpid()
        # per-reason rate limit: an immediate repeat is suppressed...
        assert rec.dump("dispatch_timeout") is None
        # ...but a different reason still dumps
        assert rec.dump("dead_peer") is not None

    def test_bundle_says_where_the_compile_cache_is(self, tmp_path,
                                                    place_jax_cache):
        """config.json records the one answer ``utils.environment`` gives
        (directory, writable) and the in-process compile counters."""
        place_jax_cache(tmp_path / "jc")
        rec = FlightRecorder(capacity=4, directory=str(tmp_path),
                             min_dump_interval=0.0)
        config = json.loads(
            (Path(rec.dump("r")) / "config.json").read_text())
        cache = config["compile_cache"]
        assert cache["dir"] == str(tmp_path / "jc")
        assert cache["writable"] is True
        assert set(cache["stats"]) == {"memory", "compile_seconds"}

    def test_dump_never_raises(self):
        rec = FlightRecorder(capacity=4, min_dump_interval=0.0)
        rec.record("x")
        # an unwritable directory must degrade, not throw — the flight
        # recorder runs on crash paths
        assert rec.dump("r", directory="/dev/null/nope") is None

    def test_fit_crash_dumps_a_bundle(self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.profiler import flightrec
        from deeplearning4j_tpu.train.resilience import fit_scope
        monkeypatch.setenv("DL4J_FLIGHTREC_DIR", str(tmp_path))
        rec = flightrec.get_flight_recorder()
        rec._last_dump = {}              # reset rate-limit for the test

        class Model:
            _epoch = 0

        with pytest.raises(RuntimeError, match="boom"):
            with fit_scope(None, Model(), epochs=1):
                raise RuntimeError("boom")
        bundles = list(tmp_path.glob("flightrec-*"))
        assert bundles, "fit crash left no flight-recorder bundle"
        reason = (bundles[0] / "reason.txt").read_text()
        assert "fit:RuntimeError" in reason and "boom" in reason


# ================================================================= chaos
@pytest.mark.chaos
class TestChaosTraces:
    def test_every_terminal_outcome_carries_a_trace(self, net, traced):
        """Deadline-storm replay: every request — completed, shed at
        admission, or deadline-expired — ends with a terminal span on
        its own trace (admission rejections expose ``trace_id`` on the
        raised error)."""
        sv = ModelServer(net, batch_limit=2, max_queue=2, coalesce_ms=0.5,
                         default_deadline=0.05)
        try:
            sv.warmup([(NIN,)])
            load = ServingLoad.seeded(5, mix="burst", n=40, rps=400.0,
                                      n_bursts=2, burst_size=15,
                                      max_rows=1)
            results = load.replay(sv.submit, (NIN,))
            outcomes = {"completed": 0, "shed": 0, "deadline": 0}
            for _, h in results:
                if isinstance(h, ServerOverloadedError):
                    outcomes["shed"] += 1
                    # the admission rejection names its trace...
                    tid = h.trace_id
                    assert len(tid) == 32
                else:
                    assert isinstance(h, ServingRequest)
                    tid = h.trace.trace_id
                    try:
                        h.get(30.0)
                        outcomes["completed"] += 1
                    except DeadlineExceededError:
                        outcomes["deadline"] += 1
                # ...and every outcome recorded a terminal span on it
                terminals = [s for s in spans_for_trace(tid)
                             if s["name"] == "serve:terminal"]
                assert len(terminals) == 1, (tid, terminals)
            assert sum(outcomes.values()) == 40
            assert outcomes["completed"] > 0
            # the storm actually exercised non-completed terminals
            assert outcomes["shed"] + outcomes["deadline"] > 0
            # outcome args match: completed terminals say so
            completed = [
                s for _, h in results if isinstance(h, ServingRequest)
                and h._error is None
                for s in spans_for_trace(h.trace.trace_id)
                if s["name"] == "serve:terminal"]
            assert all(s["args"]["outcome"] == "completed"
                       for s in completed)
        finally:
            sv.close()

    def test_killed_mid_dispatch_leaves_trace_and_bundle(self, tmp_path):
        """A process killed while a dispatch is in flight leaves (a) a
        Perfetto-loadable truncated trace stream and (b) a flight
        recorder bundle from the dispatch-timeout watchdog that fired
        before the kill — the crash-forensics contract."""
        script = tmp_path / "victim.py"
        stream = tmp_path / "stream.trace.json"
        frdir = tmp_path / "flightrec"
        frdir.mkdir()
        script.write_text(_KILL_WORKER)
        env = dict(os.environ, DL4J_REPO=str(REPO), JAX_PLATFORMS="cpu",
                   TRACE_STREAM=str(stream), FLIGHTREC_DIR=str(frdir))
        proc = subprocess.run([sys.executable, str(script)],
                              capture_output=True, text=True, env=env,
                              timeout=120)
        assert proc.returncode == 9, proc.stdout + proc.stderr
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("RESULT ")][-1]
        info = json.loads(line[len("RESULT "):])
        # (a) the streamed trace survives truncated and loads
        raw = stream.read_text()
        assert raw.startswith("[")
        assert not raw.rstrip().endswith("]")    # killed = never finalized
        events = json.loads(raw.rstrip().rstrip(",") + "]")
        ok_spans = [e for e in events
                    if e.get("args", {}).get("trace_id") == info["ok_trace"]]
        assert {"serve:dispatch", "serve:terminal"} <= \
            {e["name"] for e in ok_spans}
        # the hung request got at least as far as admission on disk
        hung = [e for e in events
                if e.get("args", {}).get("trace_id") == info["hung_trace"]]
        assert any(e["name"] == "serve:admission" for e in hung)
        # (b) the watchdog's bundle is on disk
        bundles = list(frdir.glob("flightrec-*dispatch_timeout*"))
        assert bundles, list(frdir.iterdir())
        evs = json.loads((bundles[0] / "events.json").read_text())
        assert any(e["kind"] == "serving:dispatch_failure" for e in evs)


_KILL_WORKER = r"""
import json, os, sys, time
sys.path.insert(0, os.environ["DL4J_REPO"])
import numpy as np
from deeplearning4j_tpu import profiler
from deeplearning4j_tpu.profiler import flightrec
from deeplearning4j_tpu.serving import ModelServer

profiler.enable_tracing()
profiler.get_tracer().stream_to(os.environ["TRACE_STREAM"],
                                flush_every=1)
flightrec.configure(directory=os.environ["FLIGHTREC_DIR"],
                    min_dump_interval=0.0)

def fwd(x):
    if float(np.asarray(x).ravel()[0]) < 0:
        time.sleep(60.0)                    # the hung replica
    return np.zeros((int(np.asarray(x).shape[0]), 3), np.float32)

sv = ModelServer(None, forward=fwd, batch_limit=2, max_queue=8,
                 coalesce_ms=0.0, max_retries=0, replica_timeout=0.3,
                 name="victim")
sv.warmup([(4,)])
ok = sv.submit(np.ones((1, 4), np.float32))
ok.get(30.0)
hung = sv.submit(np.full((1, 4), -1.0, np.float32))
deadline = time.monotonic() + 30.0
# wait for the watchdog to abandon the dispatch and dump, then die
# with the forward thread still stuck in fwd() — mid-dispatch
while time.monotonic() < deadline:
    if any(p.name.startswith("flightrec-")
           for p in os.scandir(os.environ["FLIGHTREC_DIR"])):
        break
    time.sleep(0.05)
print("RESULT " + json.dumps({"ok_trace": ok.trace.trace_id,
                              "hung_trace": hung.trace.trace_id}))
sys.stdout.flush()
os._exit(9)
"""


# ============================================================= multihost
@pytest.mark.multihost
class TestMultihostTrace:
    def test_barrier_and_ingress_share_one_trace(self, net, traced,
                                                 tmp_path):
        """THE multihost pin: two OS worker processes run a barrier
        round and the parent serves an ingress request, all under ONE
        traceparent — the merged Chrome trace stitches spans from >= 3
        pids into a single Perfetto-loadable flow."""
        from deeplearning4j_tpu.distributed import SocketCoordinatorServer

        root = TraceContext.new()
        worker = tmp_path / "worker.py"
        worker.write_text(_TRACE_WORKER)
        docs = []
        with SocketCoordinatorServer(participants=2) as srv:
            procs = []
            for rank in ("0", "1"):
                env = dict(os.environ, DL4J_REPO=str(REPO),
                           COORD_RANK=rank, COORD_ADDR=srv.address,
                           TRACEPARENT=root.to_traceparent())
                procs.append(subprocess.Popen(
                    [sys.executable, str(worker)],
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                    env=env, text=True))
            for p in procs:
                out, _ = p.communicate(timeout=60)
                assert p.returncode == 0, out[-2000:]
                line = [l for l in out.splitlines()
                        if l.startswith("RESULT ")][-1]
                docs.append(json.loads(line[len("RESULT "):]))
        # the ingress leg of the same trace, served by the parent
        with ModelRegistry(batch_limit=8, coalesce_ms=0.5) as reg:
            reg.load("m", net, shapes=[(NIN,)])
            with HttpIngress(reg, port=0) as ing:
                code, payload, _ = post_json(
                    ing.url, "/v1/models/m:predict",
                    {"instances": feats(1).tolist()},
                    headers={"traceparent": root.to_traceparent()})
        assert code == 200 and payload["trace_id"] == root.trace_id

        merged = merge_chrome_traces(
            docs + [profiler.get_tracer().to_chrome_trace()])
        spans = spans_for_trace(root.trace_id, merged["traceEvents"])
        names = {s["name"] for s in spans}
        # client barrier spans (workers), server round spans (parent
        # coordinator), and the ingress request — one trace
        assert "coord:barrier" in names
        assert "coord:round" in names
        assert "ingress:request" in names
        pids = {s["pid"] for s in spans}
        assert len(pids) >= 3, pids
        # agreement still holds under tracing
        assert {d["agreed"] for d in docs} == {7}
        json.dumps(merged)      # Perfetto-loadable = valid JSON document


_TRACE_WORKER = r"""
import json, os, sys
sys.path.insert(0, os.environ["DL4J_REPO"])
from deeplearning4j_tpu import profiler
from deeplearning4j_tpu.distributed import SocketCoordinator
from deeplearning4j_tpu.profiler import tracecontext

profiler.enable_tracing()
ctx = tracecontext.TraceContext.from_traceparent(
    os.environ["TRACEPARENT"])
rank = os.environ["COORD_RANK"]
c = SocketCoordinator(os.environ["COORD_ADDR"], participant=f"p{rank}",
                      heartbeat_interval=0.2)
with tracecontext.use(ctx):
    agreed = c.resume_barrier(f"p{rank}", 7 if rank == "0" else 12,
                              timeout=20.0)
c.close()
doc = profiler.get_tracer().to_chrome_trace()
doc["agreed"] = agreed
print("RESULT " + json.dumps(doc))
"""
