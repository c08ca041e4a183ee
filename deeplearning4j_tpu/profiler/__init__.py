"""Unified tracing + metrics subsystem.

Two halves (SURVEY.md §5 "Tracing/profiling", "Metrics/logging"; the
per-op timeline + counter-registry discipline of TensorFlow (Abadi et
al., 2016) and TVM (Chen et al., 2018)):

- :mod:`tracer` — a thread-safe span tracer: ``with trace_span("op:x")``
  (also usable as a decorator), nestable, ring-buffer retention, Chrome
  Trace Event Format export (Perfetto-loadable). Serves ``GET /trace``.
- :mod:`metrics` — named counters / gauges / fixed-bucket histograms
  with label support and Prometheus text exposition. Serves
  ``GET /metrics``.

Plus :mod:`modes` — the OpExecutioner-style :class:`ProfilingMode`
(OFF/BASIC/NAN_PANIC/INF_PANIC) that gates per-op instrumentation and
unifies the Environment numerics-panic knobs — and :mod:`locks` —
instrumented Lock/RLock/Condition wrappers (``dl4j_lock_{wait,hold}_
seconds`` + ``dl4j_lock_contention_total`` per lock name, gated on the
same ProfilingMode) with a runtime lock-order witness that raises on
A->B/B->A inversions under tests (the dynamic half of the DL4J-E203
static deadlock lint).

Instrumented seams: ``ops.registry`` dispatch, ``native.runtime``
(compile cache, H2D/D2H), ``parallel.{wrapper,data}`` (replication /
shard transfers), the ``nn.{multilayer,graph}`` fit loops (the ``fit:*``
spans of ``train.stepping.StepSpans``, each with its ``iteration``, in
the ring and as ``dl4j:fit:*`` annotations in a ``jax.profiler`` trace;
host enqueue time vs batch wait + the ``dl4j_train_overlap_ratio`` gauge
/ :func:`data_overlap_ratio`; ``dl4j_train_h2d_bytes_total``;
``dl4j_steps_per_dispatch`` for multi-step dispatch; ``host:gc``), the
compiled step itself (:mod:`stepprogram`: which layer and phase each of
its instructions belongs to), the input
pipeline (``dl4j_{async_iterator,prefetch}_queue_depth``,
``dl4j_prefetch_h2d_bytes_total``, and the staged pipeline's per-stage
``dl4j_pipeline_{stage_seconds,stall_seconds,queue_depth,
h2d_bytes_total}``), and the listener bus (``MetricsListener``,
``PerformanceListener``).

The fleet observability plane (ISSUE 16) adds four more modules:
:mod:`tracecontext` (W3C-traceparent distributed tracing — request
flows stitch across ingress, coalesced dispatch, and the coordination
wire), :mod:`aggregate` (cross-host metric federation behind
``GET /v1/fleet/metrics``), :mod:`slo` (declarative SLOs with
multi-window burn-rate gates, ``dl4j_slo_burn_rate``), and
:mod:`flightrec` (always-on crash flight recorder dumping debug
bundles on NonfiniteAttributionError / dispatch timeout / dead peer).

Everything per step is near-zero-cost when disabled: one module-level
flag / enum read before any span or sample is allocated. **"Off" means
nothing per step**, not nothing at all: what happens once a program
(``net:init`` around a network's ``init()``, ``fit:build`` around a
dispatch that built its step, and ``compile:trace`` / ``compile:lower`` /
``compile:backend`` for every program JAX builds, each naming the span
that caused it; ``nn.compilecache.watch_builds``) goes into the ring
whatever the mode: the seconds before the first step run with
instrumentation off everywhere, and a dispatch that builds nothing pays
two clock reads and one compare for it.
"""

import gc as _gc
import time as _time

from deeplearning4j_tpu.profiler import stepprogram
from deeplearning4j_tpu.profiler.aggregate import (FleetScraper,
                                                   HistogramSnapshot,
                                                   MetricsAggregator,
                                                   members_from_coordinator,
                                                   parse_exposition)
from deeplearning4j_tpu.profiler.flightrec import (FlightRecorder,
                                                   get_flight_recorder)
from deeplearning4j_tpu.profiler.locks import (InstrumentedCondition,
                                               InstrumentedLock,
                                               InstrumentedQueue,
                                               InstrumentedRLock,
                                               LockOrderInversionError,
                                               WitnessedLock,
                                               disable_lock_order_witness,
                                               enable_lock_order_witness,
                                               lock_order_edges)
from deeplearning4j_tpu.profiler.metrics import (Counter, Gauge, Histogram,
                                                 MetricsRegistry,
                                                 get_registry)
from deeplearning4j_tpu.profiler.modes import (ProfilingMode,
                                               get_profiling_mode,
                                               set_profiling_mode)
from deeplearning4j_tpu.profiler.slo import (SLOEngine, SLOGate, SLOSpec,
                                             SLOVerdict)
from deeplearning4j_tpu.profiler.tracecontext import (TraceContext,
                                                      current as
                                                      current_trace,
                                                      merge_chrome_traces,
                                                      record_span, run_span,
                                                      span,
                                                      spans_for_trace)
from deeplearning4j_tpu.profiler.tracer import (SpanTracer, disable_tracing,
                                                enable_tracing, get_tracer,
                                                now_us,
                                                perf_counter_seconds,
                                                trace_span, tracing_enabled)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "ProfilingMode", "get_profiling_mode", "set_profiling_mode",
    "SpanTracer", "trace_span", "get_tracer", "enable_tracing",
    "disable_tracing", "tracing_enabled", "instrumentation_active",
    "now_us", "perf_counter_seconds", "observe_region",
    "iter_with_data_wait", "data_overlap_ratio", "stepprogram",
    "TraceContext", "current_trace", "record_span", "span", "run_span",
    "merge_chrome_traces", "spans_for_trace",
    "MetricsAggregator", "HistogramSnapshot", "FleetScraper",
    "parse_exposition", "members_from_coordinator",
    "SLOSpec", "SLOEngine", "SLOGate", "SLOVerdict",
    "FlightRecorder", "get_flight_recorder",
    "InstrumentedLock", "InstrumentedRLock", "InstrumentedCondition",
    "InstrumentedQueue", "WitnessedLock", "LockOrderInversionError",
    "enable_lock_order_witness", "disable_lock_order_witness",
    "lock_order_edges",
]


def instrumentation_active() -> bool:
    """True when any framework instrumentation should record: tracing is
    on or the profiling mode is not OFF. The fit loops check this once
    per iteration so a disabled profiler costs one boolean + enum read."""
    return tracing_enabled() or get_profiling_mode() is not ProfilingMode.OFF


def observe_region(span_name: str, metric_name: str, help_text: str,
                   started_us: float, seconds: float, **args) -> None:
    """Record one already-measured region: a histogram sample in the
    registry plus a span in the tracer's ring. The fit loops use this for
    regions they time with a bare perf_counter, and only while
    :func:`instrumentation_active`: the un-instrumented path stays
    allocation-free."""
    get_registry().histogram(metric_name, help_text).observe(seconds)
    get_tracer().add_event(span_name, started_us, seconds * 1e6,
                           args or None)


# ``host:gc``: one span a collection, in the ring and in a jax.profiler
# trace, while instrumentation is on — a pause of the interpreter is the
# first suspect for an idle device, and nothing else would show it
_GC_OPEN = []


def _gc_span(phase, info):
    if phase == "start":
        import jax
        ann = jax.profiler.TraceAnnotation("dl4j:host:gc")
        ann.__enter__()
        _GC_OPEN.append((now_us(), ann))
    elif _GC_OPEN:
        t0u, ann = _GC_OPEN.pop()
        ann.__exit__(None, None, None)
        # deferred: a collection can start inside the tracer's own lock
        get_tracer().defer_event("host:gc", t0u, now_us() - t0u,
                                 {"generation": info.get("generation"),
                                  "collected": info.get("collected")})


def _on_switch():
    """What starts and ends with instrumentation, after the profiling
    mode or the tracing flag was set."""
    registered = _gc_span in _gc.callbacks
    if instrumentation_active():
        if not registered:
            _gc.callbacks.append(_gc_span)
        return
    if registered:
        _gc.callbacks.remove(_gc_span)
        del _GC_OPEN[:]
    stepprogram.flush()


_SENTINEL = object()

# host enqueue time against batch wait: both halves are HOST times (on an
# asynchronous device dl4j_train_step_seconds times the enqueue of a step,
# not its run), so 1.0 says the fit loop never waited for its iterator and
# low values say it mostly did. Whether the DEVICE waited is another
# question: the fit:* spans beside a device trace answer it. Updated by
# iter_with_data_wait; dl4j_train_data_wait_seconds / _step_seconds hold
# the raw halves.
_OVERLAP_RATIO = get_registry().gauge(
    "dl4j_train_overlap_ratio",
    "Host step-enqueue time as a fraction of enqueue + batch-wait time "
    "(1.0 = the fit loop never waited for its iterator; low values = it "
    "mostly waited. Host times both: not a device utilisation)")


def data_overlap_ratio():
    """Cumulative dispatch/(dispatch + data_wait) from the two fit-loop
    histograms, host times both — the number the data-pipeline bench
    reports. None before any instrumented fit ran."""
    reg = get_registry()
    step = reg.get("dl4j_train_step_seconds")
    wait = reg.get("dl4j_train_data_wait_seconds")
    s = step.sum if step is not None else 0.0
    w = wait.sum if wait is not None else 0.0
    total = s + w
    return None if total == 0 else s / total


def iter_with_data_wait(batches, model):
    """Yield from ``batches`` measuring each pull as ``fit:pull``
    (``dl4j_train_data_wait_seconds`` sample + span, and a
    ``dl4j:fit:pull`` annotation in a jax.profiler trace) — the data-wait
    half of the data-wait-vs-dispatch split both fit loops report
    (``dl4j_train_overlap_ratio`` tracks the running ratio). The span
    carries the iteration of ``model`` the batch is pulled for. The
    terminal pull (StopIteration) is not recorded: it measures
    exhaustion, not a batch wait."""
    import jax
    it = iter(batches)
    while True:
        active = instrumentation_active()
        if active:
            ann = jax.profiler.TraceAnnotation("dl4j:fit:pull")
            ann.__enter__()
            t0u, t0 = now_us(), _time.perf_counter()
        try:
            ds = next(it, _SENTINEL)
        finally:
            if active:
                ann.__exit__(None, None, None)
        if ds is _SENTINEL:
            return
        if active:
            observe_region("fit:pull", "dl4j_train_data_wait_seconds",
                           "Host wait for the next training batch", t0u,
                           _time.perf_counter() - t0, parent="fit:epoch",
                           iteration=model._iteration + 1)
            ratio = data_overlap_ratio()
            if ratio is not None:
                _OVERLAP_RATIO.set(ratio)
        yield ds
