"""The spans of a program's build (``nn.compilecache.watch_builds``,
``train.stepping.dispatch``): ``net:init``, ``fit:build`` and
``compile:trace / lower / backend``, recorded whatever the profiling mode,
with the span that caused each."""

import threading
import warnings

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import profiler as prof
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn import compilecache as cc
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.train import stepping

EVENTS = "/jax/core/compile/"
CACHE = "/jax/compilation_cache/"
COMPILE = (cc.COMPILE_TRACE, cc.COMPILE_LOWER, cc.COMPILE_BACKEND)


def _mln(width=8, init=True):
    conf = (NeuralNetConfiguration.Builder().seed(1).list()
            .layer(DenseLayer(nOut=width, activation="relu"))
            .layer(OutputLayer(nOut=3, lossFunction="mcxent",
                               activation="softmax"))
            .setInputType(InputType.feedForward(5)).build())
    net = MultiLayerNetwork(conf)
    return net.init() if init else net


def _graph(width=8, init=True):
    g = (NeuralNetConfiguration.Builder().seed(1).graphBuilder()
         .addInputs("in").setInputTypes(InputType.feedForward(5)))
    g.addLayer("d", DenseLayer(nOut=width, activation="relu"), "in")
    g.addLayer("out", OutputLayer(nOut=3, lossFunction="mcxent",
                                  activation="softmax"), "d")
    g.setOutputs("out")
    net = ComputationGraph(g.build())
    return net.init() if init else net


NETS = {"mln": (_mln, "MultiLayerNetwork"), "graph": (_graph,
                                                      "ComputationGraph")}


def _batches(n=3, rows=8):
    rng = np.random.RandomState(0)
    return [DataSet(rng.randn(rows, 5).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.randint(0, 3, rows)])
            for _ in range(n)]


@pytest.fixture
def ring():
    """A clean ring with the profiling mode OFF; clean again after."""
    prof.set_profiling_mode(prof.ProfilingMode.OFF)
    tracer = prof.get_tracer()
    tracer.clear()
    yield tracer
    prof.set_profiling_mode(None)
    tracer.clear()


def _named(tracer, *names):
    return sorted((e for e in tracer.events() if e["name"] in names),
                  key=lambda e: e["ts"])


def _inside(outer, evs):
    return [e for e in evs if e is not outer and outer["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1.0]


def _depths(traces):
    """How many of ``traces`` each lies inside, by ``ts`` and ``dur``
    alone, as a reader of the ring has to tell it."""
    return {e["args"]["program"]: sum(1 for o in traces
                                      if e in _inside(o, traces))
            for e in traces}


@pytest.mark.parametrize("kind", sorted(NETS))
def test_first_fit_leaves_one_build_with_its_children_in_order(kind, ring):
    make, cls = NETS[kind]
    net = make()
    ring.clear()
    net.fit(_batches(3))
    build, = _named(ring, cc.FIT_BUILD)
    assert build["args"] == {
        "site": cls + ".fit", "iteration": 1, "steps": 1,
        "new_signature": True, "parent": "fit:dispatch",
        **{k: v for k, v in build["args"].items() if k == "trace_id"}}
    kids = [e for e in _named(ring, *COMPILE)
            if e["args"]["cause"] == cc.FIT_BUILD]
    assert _inside(build, kids) == kids
    # the step itself: the one program handed to the backend, under one
    # spelling in all three kinds, one after the other
    backend, = [e for e in kids if e["name"] == cc.COMPILE_BACKEND]
    outer = [e for e in kids
             if e["args"]["program"] == backend["args"]["program"]]
    assert [e["name"] for e in outer] == list(COMPILE)
    assert all(a["ts"] + a["dur"] <= b["ts"] + 1.0
               for a, b in zip(outer, outer[1:]))
    # what the step calls is traced while the step's own trace is open
    nested = [e for e in kids if e not in outer]
    assert {e["name"] for e in nested} <= {cc.COMPILE_TRACE}
    assert _inside(outer[0], nested) == nested
    assert "depth" not in outer[0]["args"]
    assert outer[2]["args"]["cache"] == "off"
    assert outer[2]["args"]["retrieval_s"] is None


@pytest.mark.parametrize("kind", sorted(NETS))
def test_only_a_dispatch_that_builds_leaves_a_span(kind, ring):
    make, cls = NETS[kind]
    net = make()
    net.fit(_batches(3))
    ring.clear()
    heard = cc.thread_builds().heard
    net.fit(_batches(3))                    # the same shapes: nothing
    assert len(ring) == 0 and cc.thread_builds().heard == heard
    net.fit(_batches(2, rows=4))            # another shape: one build
    build, = _named(ring, cc.FIT_BUILD)
    assert (build["args"]["iteration"], build["args"]["new_signature"]) \
        == (7, True)
    assert cc.thread_builds().cause is None
    ring.clear()
    # the step cache dropped: a rebuild at a signature the churn
    # detector has seen
    net._train_step_cache.clear()
    net.fit(_batches(1))
    build, = _named(ring, cc.FIT_BUILD)
    assert (build["args"]["iteration"], build["args"]["new_signature"]) \
        == (9, False)
    assert {e["name"] for e in _named(ring, *COMPILE)
            if e["args"]["cause"] == cc.FIT_BUILD} == set(COMPILE)


def test_megastep_build_carries_its_steps(ring):
    net = _mln()
    ring.clear()
    net.fit(_batches(4), steps_per_dispatch=2, prefetch=0)
    build, = _named(ring, cc.FIT_BUILD)
    assert (build["args"]["site"], build["args"]["steps"],
            build["args"]["iteration"]) == ("MultiLayerNetwork.megastep",
                                            2, 1)


@pytest.mark.parametrize("kind,width", [("graph", 13), ("mln", 11)])
def test_net_init_is_the_cause_of_what_it_builds(kind, width, ring):
    net = NETS[kind][0](width, init=False)  # a width of this test's own:
    net.init()                              # its initialisers are built
    init, = _named(ring, cc.NET_INIT)
    assert init["args"]["parameters"] == net.numParams() == 9 * width + 3
    assert init["args"]["leaves"] == 4
    kids = _named(ring, *COMPILE)
    assert _inside(init, kids) == kids
    assert cc.COMPILE_BACKEND in {e["name"] for e in kids}
    assert {e["args"]["cause"] for e in kids} == {cc.NET_INIT}
    assert cc.thread_builds().cause is None


def test_a_function_jitted_inside_another_is_traced_inside_it(ring):
    cc.watch_builds()

    @jax.jit
    def inner_rule(x):
        return x * 2.0

    @jax.jit
    def outer_step(x):
        return inner_rule(x) + 1.0

    outer_step(np.ones(3, np.float32))
    traces = _named(ring, cc.COMPILE_TRACE)
    depth = _depths(traces)
    assert (depth["outer_step"], depth["inner_rule"]) == (0, 1)
    # the inner trace ends first, and is in the ring first
    order = [e["args"]["program"] for e in ring.events()
             if e["name"] == cc.COMPILE_TRACE]
    assert order.index("inner_rule") < order.index("outer_step")


def test_a_build_outside_any_cause_has_none(ring):
    jax.jit(lambda x: x * 3.0 + 1.0)(np.ones(7, np.float32))
    outer = [e for e in _named(ring, *COMPILE)
             if e["args"]["program"] == "<lambda>"]
    assert [e["name"] for e in outer][-3:] == list(COMPILE)
    assert {e["args"]["cause"] for e in outer} == {None}
    assert {e["args"]["program"] for e in outer[-3:]} == {"<lambda>"}


def _plant(kind, seconds, name):
    jax.monitoring.record_event_duration_secs(EVENTS + kind, seconds,
                                              fun_name=name)


@pytest.mark.parametrize("events,want", [
    ((CACHE + "cache_hits",), "hit"),
    ((CACHE + "compile_requests_use_cache", CACHE + "cache_misses"), "miss"),
    ((CACHE + "compile_requests_use_cache",), "off"),
])
def test_cache_state_comes_from_jaxs_own_events(events, want, ring):
    cc.watch_builds()
    for event in events:
        jax.monitoring.record_event(event)
    if want == "hit":
        jax.monitoring.record_event_duration_secs(
            CACHE + "cache_retrieval_time_sec", 0.25)
    _plant("backend_compile_duration", 0.5, "jit(planted)")
    _plant("backend_compile_duration", 0.5, "jit_planted")   # state is reset
    first, second = _named(ring, cc.COMPILE_BACKEND)
    assert first["args"]["cache"] == want
    assert first["args"]["retrieval_s"] == (0.25 if want == "hit" else None)
    assert second["args"]["cache"] == "off"
    assert second["args"]["retrieval_s"] is None
    assert first["args"]["program"] == second["args"]["program"] == "planted"
    assert abs(first["dur"] - 0.5e6) < 1.0


def test_a_placed_cache_that_wrote_nothing_still_missed(ring, place_jax_cache,
                                                        tmp_path):
    cc.watch_builds()
    place_jax_cache(str(tmp_path))
    _plant("backend_compile_duration", 0.01, "jit(small)")
    span, = _named(ring, cc.COMPILE_BACKEND)
    assert span["args"]["cache"] == "miss"


def test_planted_traces_are_in_the_ring_as_they_are_heard(ring):
    """A dump of the ring taken in the middle of a build has the traces
    that have ended; how they nest follows from their intervals."""
    cc.watch_builds()
    with cc.cause_span("net:init") as args:
        args["parameters"] = 0
        _plant("jaxpr_trace_duration", 1e-4, "leaf")       # inside inner
        assert [e["args"]["program"]
                for e in _named(ring, cc.COMPILE_TRACE)] == ["leaf"]
        _plant("jaxpr_trace_duration", 1e-2, "inner")      # inside outer
        _plant("jaxpr_trace_duration", 1e-4, "sibling")    # inside outer
        _plant("jaxpr_trace_duration", 1.0, "outer")
        _plant("jaxpr_to_mlir_module_duration", 1e-5, "jit(outer)")
    traces = _named(ring, cc.COMPILE_TRACE)
    assert _depths(traces) == {"outer": 0, "inner": 1, "sibling": 1,
                               "leaf": 2}
    assert {e["args"]["cause"] for e in _named(ring, *COMPILE)} \
        == {"net:init"}
    assert all("depth" not in e["args"] for e in traces)
    lower, = _named(ring, cc.COMPILE_LOWER)
    assert lower["args"]["program"] == "outer"
    assert lower["dur"] == pytest.approx(10.0)


def test_a_trace_with_no_lowering_keeps_its_cause(ring):
    cc.watch_builds()
    with cc.cause_span("net:init"):
        _plant("jaxpr_trace_duration", 1e-3, "only_traced")
    _plant("jaxpr_trace_duration", 1e-3, "after")
    first, second = _named(ring, cc.COMPILE_TRACE)
    assert first["args"] == {"program": "only_traced", "cause": "net:init"}
    assert second["args"] == {"program": "after", "cause": None}


def test_a_cause_that_built_nothing_says_so_and_restores_the_outer(ring):
    cc.watch_builds()
    with cc.cause_span("net:init"):
        inner = cc.BuildCause(cc.FIT_BUILD)
        assert cc.thread_builds().cause == cc.FIT_BUILD
        assert inner.close() is False
        assert cc.thread_builds().cause == "net:init"
        inner = cc.BuildCause(cc.FIT_BUILD)
        _plant("backend_compile_duration", 0.01, "jit(step)")
        assert inner.close() is True
        inner.record({"site": "here"})
    assert cc.thread_builds().cause is None
    build, = _named(ring, cc.FIT_BUILD)
    assert build["args"] == {"site": "here"}
    backend, = _named(ring, cc.COMPILE_BACKEND)
    assert backend["args"]["cause"] == cc.FIT_BUILD


def test_a_listener_that_raises_warns_once_and_the_fit_goes_on(
        ring, monkeypatch):
    net = _mln()
    ring.clear()

    def broken(*a, **k):
        raise RuntimeError("planted")

    monkeypatch.setattr(cc, "_heard", broken)
    monkeypatch.setattr(cc, "_LISTENER_WARNED", False)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        net.fit(_batches(2, rows=6))         # a new shape: JAX reports
        jax.monitoring.record_event_duration_secs(
            EVENTS + "backend_compile_duration", 0.1, fun_name="jit(x)")
    said = [w for w in caught if "build listener failed" in str(w.message)]
    assert len(said) == 1 and "planted" in str(said[0].message)
    assert net._iteration == 2 and np.isfinite(float(net.score()))
    assert not _named(ring, *COMPILE)


def test_watch_builds_twice_registers_once():
    from jax._src import monitoring
    cc.watch_builds()
    cc.watch_builds()
    _mln()
    assert monitoring.get_event_duration_listeners().count(cc._on_duration) \
        == 1
    assert monitoring._event_listeners.count(cc._on_event) == 1


def test_each_thread_has_its_own_cause_and_count(ring):
    cc.watch_builds()
    seen = {}

    def other():
        seen["cause"] = cc.thread_builds().cause
        _plant("backend_compile_duration", 0.01, "jit(elsewhere)")

    st = cc.thread_builds()
    heard = st.heard
    with cc.cause_span("net:init"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen["cause"] is None and st.heard == heard
    span, = _named(ring, cc.COMPILE_BACKEND)
    assert span["args"]["cause"] is None


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps what was open
    when each annotation opened."""

    def __init__(self):
        self.open, self.seen = [], []

    def __call__(self, name, **kw):
        outer = self

        class Ann:
            def __enter__(self):
                outer.seen.append((name, kw, list(outer.open)))
                outer.open.append(name)

            def __exit__(self, *exc):
                outer.open.remove(name)

        return Ann()


def test_a_recompile_in_an_instrumented_fit_shows_under_its_dispatch(
        ring, monkeypatch):
    net = _mln()
    net.fit(_batches(2))
    anns = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", anns)
    prof.set_profiling_mode(prof.ProfilingMode.BASIC)
    try:
        ring.clear()
        net.fit(_batches(2) + _batches(1, rows=4) + _batches(1))
    finally:
        prof.set_profiling_mode(None)
    builds = [(kw, under) for name, kw, under in anns.seen
              if name == "dl4j:fit:build"]
    assert builds == [({"iteration": 5},
                       ["dl4j:fit:epoch", "dl4j:fit:dispatch"])]
    build, = _named(ring, cc.FIT_BUILD)
    assert build["args"]["iteration"] == 5
    dispatch, = [e for e in _named(ring, stepping.FIT_DISPATCH)
                 if e["args"]["iteration"] == 5]
    assert _inside(dispatch, [build]) == [build]


def test_a_step_made_again_in_an_instrumented_fit_is_annotated_too(
        ring, monkeypatch):
    """A step that never dispatched builds whatever the churn detector
    remembers: foreseen from the step's own ``dispatched``."""
    net = _mln()
    net.fit(_batches(2))
    step, = net._train_step_cache.values()
    assert step.dispatched
    net._train_step_cache.clear()
    anns = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", anns)
    prof.set_profiling_mode(prof.ProfilingMode.BASIC)
    try:
        ring.clear()
        net.fit(_batches(2))
    finally:
        prof.set_profiling_mode(None)
    assert [(kw, under) for name, kw, under in anns.seen
            if name == "dl4j:fit:build"] \
        == [({"iteration": 3}, ["dl4j:fit:epoch", "dl4j:fit:dispatch"])]
    build, = _named(ring, cc.FIT_BUILD)
    assert (build["args"]["iteration"], build["args"]["new_signature"]) \
        == (3, False)
