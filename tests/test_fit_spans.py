"""The fit loop's spans (``train.stepping.StepSpans``), the staging counter,
``host:gc`` and the step-program map's life cycle: recorded exactly while
``profiler.instrumentation_active()``, and nothing otherwise."""

import gc
import threading
import time
import weakref

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import profiler as prof
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.distributed import GSPMDTrainer, ShardedTrainingPlan
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.mesh import DeviceMesh
from deeplearning4j_tpu.profiler import stepprogram
from deeplearning4j_tpu.train import stepping

STEP_SPANS = [("fit:pull", None), ("fit:stage", None), ("fit:prepare", None),
              ("fit:listeners", "start"), ("fit:dispatch", None),
              ("fit:commit", None), ("fit:listeners", "done")]


def _mln():
    conf = (NeuralNetConfiguration.Builder().seed(1).list()
            .layer(DenseLayer(nOut=8, activation="relu"))
            .layer(OutputLayer(nOut=3, lossFunction="mcxent",
                               activation="softmax"))
            .setInputType(InputType.feedForward(5)).build())
    return MultiLayerNetwork(conf).init()


def _graph():
    g = (NeuralNetConfiguration.Builder().seed(1).graphBuilder()
         .addInputs("in").setInputTypes(InputType.feedForward(5)))
    g.addLayer("d", DenseLayer(nOut=8, activation="relu"), "in")
    g.addLayer("out", OutputLayer(nOut=3, lossFunction="mcxent",
                                  activation="softmax"), "d")
    g.setOutputs("out")
    return ComputationGraph(g.build()).init()


def _batches(n=3, rows=8):
    rng = np.random.RandomState(0)
    return [DataSet(rng.randn(rows, 5).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.randint(0, 3, rows)])
            for _ in range(n)]


def _fit_mln(net, data, **kw):
    net.fit(data, **kw)


def _fit_sharded(net, data, **kw):
    mesh = DeviceMesh.create(data=4, devices=jax.devices()[:4])
    GSPMDTrainer(net, ShardedTrainingPlan(mesh)).fit(data, **kw)


FITS = {"mln": (_mln, _fit_mln), "graph": (_graph, _fit_mln),
        "gspmd": (_mln, _fit_sharded)}


@pytest.fixture
def instrumented():
    """Profiling mode BASIC against a clean ring; everything off after."""
    prof.get_tracer().clear()
    stepprogram.clear()
    prof.set_profiling_mode(prof.ProfilingMode.BASIC)
    yield
    prof.set_profiling_mode(None)
    prof.get_tracer().clear()
    stepprogram.clear()


class Listener:
    def onIterationStart(self, net, iteration):
        pass

    def iterationDone(self, net, iteration, epoch):
        pass


@pytest.mark.parametrize("kind", sorted(FITS))
def test_three_batches_leave_three_of_each_span(kind, instrumented):
    build, fit = FITS[kind]
    net = build()
    net.setListeners(Listener())
    fit(net, _batches(1))           # iteration 1: compiles
    prof.get_tracer().clear()
    fit(net, _batches(3))
    evs = prof.get_tracer().events()
    for name, when in STEP_SPANS:
        got = [e for e in evs if e["name"] == name
               and e["args"].get("when") == when]
        assert [e["args"]["iteration"] for e in got] == [2, 3, 4], name
        assert {e["args"]["parent"] for e in got} == {"fit:epoch"}
    epoch, = [e for e in evs if e["name"] == "fit:epoch"]
    inside = [e for e in evs if e["name"].startswith("fit:")
              and e is not epoch]
    assert len(inside) == 3 * len(STEP_SPANS)
    assert all(epoch["ts"] <= e["ts"] and e["ts"] + e["dur"]
               <= epoch["ts"] + epoch["dur"] + 1e-3 for e in inside)
    # one step's spans follow each other, none overlapping the next
    for it in (2, 3, 4):
        mine = sorted((e for e in inside if e["args"]["iteration"] == it),
                      key=lambda e: e["ts"])
        assert [e["name"] for e in mine] == [n for n, _w in STEP_SPANS]
        assert all(a["ts"] + a["dur"] <= b["ts"] + 1e-3
                   for a, b in zip(mine, mine[1:]))
    assert not [e for e in evs if e["name"].startswith("train:")]


def test_stage_span_and_counter_count_host_bytes_only(instrumented):
    net = _mln()
    data = _batches(2)
    per_batch = data[0].features.nbytes + data[0].labels.nbytes
    c0 = stepping.TRAIN_H2D_BYTES.value
    net.fit(data)
    assert stepping.TRAIN_H2D_BYTES.value - c0 == 2 * per_batch
    stages = [e for e in prof.get_tracer().events()
              if e["name"] == "fit:stage"]
    assert [e["args"]["bytes"] for e in stages] == [per_batch] * 2
    on_device = DataSet(jax.numpy.asarray(data[0].features),
                        jax.numpy.asarray(data[0].labels))
    c1 = stepping.TRAIN_H2D_BYTES.value
    net.fit(on_device)
    assert stepping.TRAIN_H2D_BYTES.value == c1     # placed already


def test_megastep_dispatch_span_carries_its_steps(instrumented):
    net = _mln()
    net.setListeners(Listener())
    net.fit(_batches(4), steps_per_dispatch=2, prefetch=0)
    evs = prof.get_tracer().events()
    dispatches = [e for e in evs if e["name"] == "fit:dispatch"]
    assert [(e["args"]["iteration"], e["args"]["steps"])
            for e in dispatches] == [(1, 2), (3, 2)]
    done = [e for e in evs if e["name"] == "fit:listeners"]
    assert [e["args"]["when"] for e in done] == ["done", "done"]
    assert prof.get_registry().get("dl4j_train_step_seconds").count >= 2


def test_off_nothing_moves():
    """OFF means nothing per step. What happens once a program (its
    build: ``net:init``, ``fit:build``, ``compile:*``) is recorded
    whatever the mode; a dispatch that builds nothing records nothing."""
    prof.set_profiling_mode(prof.ProfilingMode.OFF)
    try:
        prof.get_tracer().clear()
        stepprogram.clear()
        net = _mln()
        c0 = stepping.TRAIN_H2D_BYTES.value
        t0 = stepping.TRAIN_TOKENS.value
        i0 = stepping.TRAIN_ITERATIONS.value
        wait = prof.get_registry().get("dl4j_train_data_wait_seconds")
        w0 = wait.count if wait is not None else 0
        net.fit(_batches(3))
        names = {e["name"] for e in prof.get_tracer().events()}
        assert {"net:init", "fit:build", "compile:trace", "compile:lower",
                "compile:backend"} >= names >= {"net:init", "fit:build"}
        builds = len(prof.get_tracer())
        net.fit(_batches(3))        # the step is built: nothing is added
        assert len(prof.get_tracer()) == builds
        assert stepping.TRAIN_H2D_BYTES.value == c0
        assert stepping.TRAIN_TOKENS.value == t0
        assert stepping.TRAIN_ITERATIONS.value == i0
        wait = prof.get_registry().get("dl4j_train_data_wait_seconds")
        assert (wait.count if wait is not None else 0) == w0
        assert stepprogram._PENDING == [] and stepprogram._MAPS == {}
        assert stepping.step_spans(net) is stepping._OFF
    finally:
        prof.set_profiling_mode(None)


def test_host_gc_span_lives_with_instrumentation():
    assert prof._gc_span not in gc.callbacks
    prof.get_tracer().clear()
    prof.set_profiling_mode("basic")
    try:
        assert prof._gc_span in gc.callbacks
        gc.collect()
        spans = [e for e in prof.get_tracer().events()
                 if e["name"] == "host:gc"]
        assert spans and spans[-1]["args"]["generation"] == 2
        assert spans[-1]["dur"] > 0
    finally:
        prof.set_profiling_mode(None)
    assert prof._gc_span not in gc.callbacks
    prof.enable_tracing()           # the tracer's flag switches it too
    assert prof._gc_span in gc.callbacks
    prof.disable_tracing()
    assert prof._gc_span not in gc.callbacks
    prof.get_tracer().clear()


def test_a_collection_inside_the_tracers_lock_does_not_deadlock():
    """A gc callback runs inside whatever allocated last; its span is set
    aside without the lock and reaches the ring with the next event."""
    tracer = prof.SpanTracer()
    done = threading.Event()

    def work():
        with tracer._lock:
            tracer.defer_event("host:gc", 1.0, 2.0, {"generation": 0})
        tracer.add_event("after", 5.0, 1.0)
        done.set()

    t = threading.Thread(target=work, daemon=True)
    t.start()
    assert done.wait(timeout=10)
    assert [e["name"] for e in tracer.events()] == ["host:gc", "after"]


def test_deferred_spans_drain_in_order_without_recursion():
    """Only fit spans drain the deferred list: a long stretch of
    evaluation between fits leaves thousands of ``host:gc`` spans, which
    the next event moves into the ring flat and oldest first."""
    tracer = prof.SpanTracer()
    for i in range(2000):
        tracer.defer_event("host:gc", float(i), 1.0, {"generation": 0})
    tracer.add_event("after", 5000.0, 1.0)
    evs = tracer.events()
    assert len(evs) == 2001
    assert [e["ts"] for e in evs[:2000]] == [float(i) for i in range(2000)]
    assert evs[-1]["name"] == "after"


def test_deferred_spans_are_bounded_while_nothing_drains_them():
    from deeplearning4j_tpu.profiler import tracer as tracer_mod
    tracer = prof.SpanTracer()
    n = tracer_mod._DEFERRED_CAPACITY
    for i in range(n + 10):
        tracer.defer_event("host:gc", float(i), 1.0)
    assert len(tracer) == n                     # the oldest ten dropped
    assert tracer.events()[0]["ts"] == 10.0
    tracer.clear()
    assert len(tracer) == 0


def test_ring_timestamps_convert_to_perf_counter():
    prof.get_tracer().clear()
    prof.enable_tracing()
    try:
        t0 = time.perf_counter()
        with prof.trace_span("probe"):
            time.sleep(0.002)
        t1 = time.perf_counter()
    finally:
        prof.disable_tracing()
    ev, = [e for e in prof.get_tracer().events() if e["name"] == "probe"]
    start = prof.perf_counter_seconds(ev["ts"])
    end = prof.perf_counter_seconds(ev["ts"] + ev["dur"])
    assert t0 <= start <= end <= t1
    prof.get_tracer().clear()


@pytest.mark.parametrize("kind", ["mln", "graph"])
def test_map_flushes_on_leaving_and_lets_go_of_the_net(kind):
    build, fit = FITS[kind]
    stepprogram.clear()
    net = build()
    prof.set_profiling_mode("basic")
    try:
        fit(net, _batches(2))
        assert len(stepprogram._PENDING) == 1       # noted once, no map yet
        assert stepprogram._MAPS == {}
    finally:
        prof.set_profiling_mode(None)
    assert stepprogram._PENDING == []
    smap = stepprogram.maps()["jit_step"]
    assert {"forward", "backward", "updater"} <= \
        {e.phase for e in smap.values()}
    assert stepprogram.last_flush_s > 0
    ref = weakref.ref(net)
    del net
    gc.collect()
    assert ref() is None
    prof.get_tracer().clear()
    stepprogram.clear()


def test_maps_builds_pending_on_demand(instrumented):
    net = _mln()
    net.fit(_batches(1))
    assert len(stepprogram._PENDING) == 1
    assert "jit_step" in stepprogram.maps()
    assert stepprogram._PENDING == []
    net.fit(_batches(1))            # noted once a function: not again
    assert stepprogram._PENDING == []


def test_flush_compiles_nothing_the_fit_has_not(instrumented):
    """The map is read off the executable the fit already made: lowering
    the noted signature again is a cache hit, not a second compile."""
    net = _mln()
    net.fit(_batches(2))
    seen, listening = [], [True]

    def on_event(event, duration, **_):
        if listening[0] and event.endswith("backend_compile_duration"):
            seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        assert "jit_step" in stepprogram.maps()
    finally:
        listening[0] = False
    assert seen == []


def test_a_step_that_cannot_be_lowered_again_warns_and_is_skipped():
    stepprogram.clear()

    class Broken:
        def lower(self, *a):
            raise ValueError("no")

    stepprogram._PENDING.append((Broken(), ()))
    with pytest.warns(UserWarning, match="step-program map not built"):
        stepprogram.flush()
    assert stepprogram._PENDING == [] and stepprogram._MAPS == {}


class _Recompiled:
    """A step function whose cached executable predates the scopes: the
    plain compile gives ``stale``, a compile that steps past the kept
    executable gives ``fresh``."""

    def __init__(self, stale, fresh):
        self.stale, self.fresh, self.options = stale, fresh, []

    def lower(self, *spec):
        return self

    def compile(self, compiler_options=None):
        self.options.append(compiler_options)
        text = self.fresh if compiler_options else self.stale

        class Compiled:
            def as_text(self):
                return text
        return Compiled()


_STALE = """HloModule jit_step, is_scheduled=true

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %multiply.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(step)/mul"}
}
"""
_FRESH = _STALE.replace("jit(step)/mul", "jit(step)/dl4j_updater/mul")


def test_a_stale_cached_executable_is_compiled_again_for_its_scopes():
    """JAX's compilation-cache key leaves metadata out: an executable
    cached by a tree without the scopes says what that tree said."""
    stepprogram.clear()
    import jax as _jax
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(_jax.config, key)
    step = _Recompiled(_STALE, _FRESH)
    stepprogram._PENDING.append((step, ()))
    stepprogram.flush()
    assert step.options[0] is None and step.options[1]  # second, past it
    assert getattr(_jax.config, key) == was
    assert stepprogram._MAPS["jit_step"]["multiply.1"].phase == "updater"
    stepprogram.clear()


def test_a_program_without_the_scopes_gets_no_map():
    """Never a wrong split: where even a fresh compile names no updater
    scope, the module has no map and every reader reports nothing."""
    stepprogram.clear()
    stepprogram._MAPS["jit_step"] = {"left": "over"}
    stepprogram._PENDING.append((_Recompiled(_STALE, _STALE), ()))
    with pytest.warns(UserWarning, match="not kept"):
        stepprogram.flush()
    assert stepprogram._MAPS == {}
    stepprogram.clear()
