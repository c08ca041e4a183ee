"""AOT compilation ahead of the first dispatch + the unified warmup API.

Every fresh process pays an XLA compile on its first dispatch; that is
why preemption resume (train.resilience), elastic shrink re-warm
(parallel.elastic), serving bucket-ladder warmup (serving.server) and
registry hot-swap staging (serving.registry) are the expensive moments
at scale. Two things make them cheap, and only the first lives here:

- :class:`CachedDispatch` — a ``jax.jit`` drop-in behind the networks'
  signature-keyed step caches. Until :meth:`CachedDispatch.warm` is
  called it delegates straight to the jitted function. ``warm()``
  lowers and compiles a signature WITHOUT executing (warmup never
  touches model state) and keeps the executable in an in-process table.
- :func:`warmup` — the ONE entry point fit, resume, shrink, and
  serving all call: ``warmup(model, [((32, 784), (32, 10))])`` AOT-
  compiles the train step (megastep with ``steps_per_dispatch=K``),
  ``warmup(model, [(8, 3, 32, 32)])`` the inference forward, and
  ``warmup(server, [(4,)])`` delegates to the serving bucket-ladder
  warmup.

The persistent cache — what makes a compile in a SECOND process cheap —
is JAX's own, under ``jit`` and ``lower().compile()`` alike;
``utils.environment`` places it and answers where it is.

Metrics: ``dl4j_compile_cache_{hits,misses}_total{scope="memory"}`` and
``dl4j_compile_seconds{state="cold"}`` (an AOT compile as this process
saw it: seconds where JAX's persistent cache missed, a fraction of one
where it hit). These three and :func:`cache_stats` are the AOT path's
alone; the main path's builds are the spans below.

**Every build, the main path's too** (:func:`watch_builds`): a program a
never-warmed ``CachedDispatch``, ``init()`` or any other ``jax.jit``
builds is traced, lowered and handed to the backend inside one untimed
call. JAX reports each of the three per program
(``jax.monitoring``); one listener a process puts what it hears into the
tracer's ring as it hears it, on the ring's own clock, whatever the
profiling mode (a build happens once a program, not once a step):

- ``compile:trace`` / ``compile:lower`` / ``compile:backend``, args
  ``program`` (the function's name, one spelling for all three) and
  ``cause`` (the ``fit:build`` or ``net:init`` open on this thread, else
  ``None``); on ``compile:backend`` also ``cache`` (``hit``: JAX's
  persistent cache answered, the span is its read, deserialise and load;
  ``miss``: the backend compiled; ``off``: no persistent cache was
  asked) and ``retrieval_s``. A function jitted inside the step is
  traced while the step's trace is open, so ``compile:trace`` spans
  nest: a reader takes the union of their intervals, and which span lies
  in which follows from ``ts`` and ``dur``;
- ``fit:build`` (``train.stepping.dispatch``) around the ``step(*args)``
  of a dispatch during which this thread built a program, and
  ``net:init`` (:func:`cause_span`) around a network's ``init()``.

The seconds of each stage and the hits and misses are sums over these
spans; no counter repeats them.

jax-free at module scope; jax loads lazily, on the compile path.
"""

from __future__ import annotations

import re
import threading
import time
import warnings
from contextlib import contextmanager
from typing import Optional

from deeplearning4j_tpu.profiler.metrics import get_registry
from deeplearning4j_tpu.profiler.tracer import get_tracer, now_us
from deeplearning4j_tpu.utils.environment import jax_compile_cache_status

_REG = get_registry()
CACHE_HITS = _REG.counter(
    "dl4j_compile_cache_hits_total",
    "Dispatches served by an already-AOT-compiled executable of this "
    "process (scope=memory)",
    labelnames=("scope",))
CACHE_MISSES = _REG.counter(
    "dl4j_compile_cache_misses_total",
    "First sight of a dispatch signature on the AOT path in this "
    "process (scope=memory): an AOT compile followed",
    labelnames=("scope",))
COMPILE_SECONDS = _REG.histogram(
    "dl4j_compile_seconds",
    "AOT program acquisition latency (state=cold: lower().compile(), "
    "whether or not JAX's persistent cache served it)",
    labelnames=("state",),
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
             10.0, 30.0, 60.0))

# prebound children: the memory-hit increment sits on the dispatch hot path
_HITS_MEM = CACHE_HITS.labels(scope="memory")
_MISS_MEM = CACHE_MISSES.labels(scope="memory")
_COLD = COMPILE_SECONDS.labels(state="cold")

#: per-process aggregates for cache_stats() — plain numbers mutated
#: under the GIL (single += per event)
_STATS = {"memory_hits": 0, "memory_misses": 0,
          "cold_seconds": 0.0, "cold_compiles": 0}


def cache_stats() -> dict:
    """Per-process snapshot: in-process table hit/miss counts and the
    AOT compiles this process ran, with their seconds. The AOT path's
    alone: the main path's builds are the ``compile:*`` spans of
    :func:`watch_builds`."""
    return {
        "memory": {"hits": _STATS["memory_hits"],
                   "misses": _STATS["memory_misses"]},
        "compile_seconds": {"cold": _STATS["cold_seconds"],
                            "cold_compiles": _STATS["cold_compiles"]},
    }


def reset_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0.0 if k.endswith("seconds") else 0


# --------------------------------------------------------- cached dispatch
#: sentinel parked in CachedDispatch._compiled for signatures whose AOT
#: acquisition failed — the plain-jit fallback is permanent per signature,
#: never a re-lowering per dispatch
_AOT_FAILED = object()


def _leaf_signature(a):
    """Jit-cache-equivalent identity of one argument leaf: shape, dtype,
    weak-type, and (for committed jax arrays) the sharding object itself
    — shardings are hashable, and a mesh/placement change must map to a
    different compiled program."""
    shard = getattr(a, "sharding", None)
    return (tuple(getattr(a, "shape", ())),
            str(getattr(a, "dtype", type(a).__name__)),
            bool(getattr(a, "weak_type", False)),
            shard)


class CachedDispatch:
    """``jax.jit`` drop-in with an in-process table of AOT-compiled
    signatures.

    Construction jits ``fn``. ``__call__`` delegates straight to that
    jit until :meth:`warm` has engaged the AOT path — the default
    behaviour is byte-identical to plain ``jax.jit``. On the AOT path
    each concrete call signature maps to one compiled executable held in
    ``_compiled``; a signature seen first at dispatch is lowered and
    compiled there. Any failure in the AOT machinery falls back to the
    plain jit with a single warning — never a failed dispatch.

    Cost note: the AOT path computes a Python-side signature (flatten +
    per-leaf shape/dtype/sharding) on every call, replacing jit's C++
    dispatch cache — microseconds per hundred leaves. The FULL argument
    tree is keyed deliberately: the parallel wrapper swaps params to
    mesh-replicated shardings without busting the outer step caches, so
    keying only on the data leaves would silently reuse an executable
    compiled for the wrong placement. A program that was never warmed
    never pays this — that path IS plain jit.
    """

    __slots__ = ("_jit", "scope", "_compiled", "_warned", "dispatched")

    def __init__(self, fn, scope: str, donate_argnums=()):
        import jax
        self._jit = jax.jit(fn, donate_argnums=donate_argnums)
        self.scope = scope                  # names the program in warnings
        self._compiled = {}
        self._warned = False
        # set by train.stepping.dispatch: until then the next dispatch
        # builds, whatever the churn detector remembers of the signature
        self.dispatched = False

    # ------------------------------------------------------------- call
    def _signature(self, args):
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(args)
        return (treedef, tuple(_leaf_signature(a) for a in leaves))

    def __call__(self, *args):
        if not self._compiled:
            return self._jit(*args)     # never warmed: plain jit
        sig = self._signature(args)
        exe = self._compiled.get(sig)
        if exe is _AOT_FAILED:
            return self._jit(*args)     # known-bad signature: permanent
        if exe is not None:             # plain-jit fallback, no re-trace
            _STATS["memory_hits"] += 1
            _HITS_MEM.inc()
            return exe(*args)
        _STATS["memory_misses"] += 1
        _MISS_MEM.inc()
        exe = self._acquire(args, sig)
        if exe is None:
            # remember the failure: re-running the (expensive) lowering
            # on every subsequent dispatch would turn each step into a
            # re-trace — the fallback must be as permanent as the
            # warning says it is
            self._compiled[sig] = _AOT_FAILED
            return self._jit(*args)
        return exe(*args)

    def warm(self, *args) -> "CachedDispatch":
        """AOT-compile the program for this argument signature WITHOUT
        executing it — model/optimizer state is never touched, donation
        consumes nothing."""
        sig = self._signature(args)
        if sig not in self._compiled:
            if self._acquire(args, sig) is None:
                self._compiled[sig] = _AOT_FAILED
        return self

    def warmed_signatures(self) -> int:
        return sum(1 for v in self._compiled.values()
                   if v is not _AOT_FAILED)

    # -------------------------------------------------------- acquisition
    def _warn_once(self, what: str, err: BaseException) -> None:
        if not self._warned:
            self._warned = True
            warnings.warn(
                f"compile cache [{self.scope}]: {what} failed "
                f"({type(err).__name__}: {err}) — falling back to plain "
                "jit dispatch for this program", stacklevel=3)

    def _acquire(self, args, sig):
        try:
            lowered = self._jit.lower(*args)
        except Exception as e:
            self._warn_once("AOT lowering", e)
            return None
        try:
            t0 = time.perf_counter()
            exe = lowered.compile()
            dt = time.perf_counter() - t0
        except Exception as e:
            self._warn_once("AOT compile", e)
            return None
        _STATS["cold_seconds"] += dt
        _STATS["cold_compiles"] += 1
        _COLD.observe(dt)
        self._compiled[sig] = exe
        return exe


def cached_dispatch(fn, scope: str, donate_argnums=()) -> CachedDispatch:
    """The seam the networks' step caches call instead of ``jax.jit``."""
    watch_builds()
    return CachedDispatch(fn, scope, donate_argnums=donate_argnums)


# ------------------------------------------------- builds, wherever made
NET_INIT = "net:init"
FIT_BUILD = "fit:build"
COMPILE_TRACE = "compile:trace"
COMPILE_LOWER = "compile:lower"
COMPILE_BACKEND = "compile:backend"

_JAX_EVENTS = "/jax/core/compile/"
# JAX's event -> span; the last two name the program ``jit(<name>)`` /
# ``pmap(<name>)`` (``jit_<name>`` in older releases), the first by the
# function's own name, which is the spelling kept
_WRAPPED = re.compile(r"(?:jit|pmap)(?:\((.*)\)|_(.*))$")
_SPANS = {
    _JAX_EVENTS + "jaxpr_trace_duration": COMPILE_TRACE,
    _JAX_EVENTS + "jaxpr_to_mlir_module_duration": COMPILE_LOWER,
    _JAX_EVENTS + "backend_compile_duration": COMPILE_BACKEND,
}
_CACHE_EVENTS = "/jax/compilation_cache/"
_CACHE_HIT = _CACHE_EVENTS + "cache_hits"
# fires when a program the cache lacked has been compiled and written
_CACHE_MISS = _CACHE_EVENTS + "cache_misses"
_CACHE_RETRIEVAL = _CACHE_EVENTS + "cache_retrieval_time_sec"


class _ThreadBuilds:
    """What one thread's builds share between the listener's calls.

    ``cause``: the span open on this thread that the next build belongs
    to (:class:`BuildCause`). ``heard``: the ``compile:*`` events heard on
    this thread, which a cause compares before and after. ``cache`` /
    ``retrieval_s``: what JAX's cache said since the last
    ``compile:backend``.
    """

    __slots__ = ("cause", "heard", "cache", "retrieval_s")

    def __init__(self):
        self.cause = None
        self.heard = 0
        self.cache = None
        self.retrieval_s = None


_TLS = threading.local()
_WATCH_LOCK = threading.Lock()
_WATCHING = False
_LISTENER_WARNED = False


def thread_builds() -> _ThreadBuilds:
    st = getattr(_TLS, "builds", None)
    if st is None:
        st = _TLS.builds = _ThreadBuilds()
    return st


def watch_builds() -> None:
    """Register the one duration listener and the one event listener of
    this process with ``jax.monitoring``; every later call returns at
    once. Called where programs are made: ``init()`` of both network
    classes and :func:`cached_dispatch`."""
    global _WATCHING
    if _WATCHING:
        return
    with _WATCH_LOCK:
        if _WATCHING:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _WATCHING = True


def _listener_failed(err: BaseException) -> None:
    """A listener that raises would fail the dispatch JAX called it
    from: swallowed, said once."""
    global _LISTENER_WARNED
    if not _LISTENER_WARNED:
        _LISTENER_WARNED = True
        warnings.warn(
            f"compile cache: the build listener failed "
            f"({type(err).__name__}: {err}); compile:* spans may be "
            "missing from here on", stacklevel=3)


def _on_event(event, **_kw):
    try:
        if event == _CACHE_HIT:
            thread_builds().cache = "hit"
        elif event == _CACHE_MISS:
            thread_builds().cache = "miss"
    except Exception as e:
        _listener_failed(e)


def _on_duration(event, duration, **kw):
    try:
        _heard(event, duration, kw.get("fun_name"))
    except Exception as e:
        _listener_failed(e)


def _heard(event, duration, fun_name):
    """One of JAX's three durations, reported as it ended: a span into
    the ring that ends now and began ``duration`` earlier. An inner
    trace ends, and so arrives, before the trace it lies in."""
    span = _SPANS.get(event)
    if span is None:
        if event == _CACHE_RETRIEVAL:
            thread_builds().retrieval_s = duration
        return
    st = thread_builds()
    st.heard += 1
    dur_us = duration * 1e6
    program = str(fun_name)
    wrapped = _WRAPPED.match(program)
    if wrapped:
        program = wrapped.group(1) or wrapped.group(2)
    args = {"program": program, "cause": st.cause}
    if span == COMPILE_BACKEND:
        # a miss too small or too quick to be written fires no event
        args["cache"] = st.cache or (
            "miss" if jax_compile_cache_status()[0] else "off")
        args["retrieval_s"] = st.retrieval_s
        st.cache = st.retrieval_s = None
    get_tracer().add_event(span, now_us() - dur_us, dur_us, args)


class BuildCause:
    """``name`` as the ``cause`` of every program this thread builds from
    here to :meth:`close`, which says whether it built any; :meth:`record`
    then puts the span between the two into the ring, whatever the
    profiling mode. Where nothing was built and nothing is recorded this
    costs two clock reads and one compare."""

    __slots__ = ("name", "_st", "_outer", "_heard", "_t0", "_t1")

    def __init__(self, name: str):
        self.name = name
        st = self._st = thread_builds()
        self._outer, self._heard, st.cause = st.cause, st.heard, name
        self._t0 = now_us()

    def close(self) -> bool:
        self._t1 = now_us()
        self._st.cause = self._outer
        return self._st.heard != self._heard

    def record(self, args: dict) -> None:
        get_tracer().add_event(self.name, self._t0, self._t1 - self._t0,
                               args)


@contextmanager
def cause_span(name: str):
    """A span that is the ``cause`` of every program this thread builds
    inside it (``net:init``), recorded whether it built any or not.
    Yields the dict of its args, for what is known only at its end."""
    watch_builds()
    cause = BuildCause(name)
    args = {}
    try:
        yield args
    finally:
        cause.close()
        cause.record(args)


# ----------------------------------------------------------------- warmup
def _is_shape(spec) -> bool:
    return isinstance(spec, (tuple, list)) \
        and all(isinstance(d, (int,)) for d in spec)


def _zeros(shape, dtype):
    import numpy as np
    return np.zeros(tuple(int(d) for d in shape), dtype=dtype)


def warmup(target, shapes, *, mesh=None, policy=None,
           steps_per_dispatch: int = 1, dtype=None, label_dtype=None,
           strict: bool = False, placement=None, tuned: bool = False):
    """Unified AOT warmup for fit, resume, shrink, and serving.

    ``target`` is a :class:`~deeplearning4j_tpu.serving.server.
    ModelServer` (delegates to its bucket-ladder ``warmup``) or a
    network (MultiLayerNetwork / ComputationGraph). ``shapes`` entries:

    - ``(features_shape, labels_shape)`` — a pair of shape tuples —
      AOT-compiles the TRAIN step for that batch signature (the
      ``lax.scan`` megastep when ``steps_per_dispatch=K>1``; pass the
      per-batch shapes, the K axis is added here). This is what resume
      and elastic shrink warm before re-entering the fit loop.
    - ``features_shape`` — a bare shape tuple — AOT-compiles the
      inference FORWARD (what serving dispatches).

    ``mesh`` enters the device-mesh context during compilation (the
    trace-cache key contains the entered-mesh stack — warm under the
    same context the dispatch will run in); ``placement`` is an
    optional callable staging warm arrays the way the dispatch path
    stages real ones (the elastic wrapper's sharded megabatch layout);
    ``policy`` attaches a PrecisionPolicy first (same as
    ``fit(precision=...)``). ``tuned=True`` consults the autotuner
    record store (ISSUE 17) and applies the winning plan for this
    (model, mesh, backend) BEFORE compiling, so the warmed programs are
    the ones the tuned fit/serve path will dispatch — the plan's
    ``steps_per_dispatch`` also takes over when the caller left the
    default.  Nothing executes: warmup compiles (through JAX's persistent
    cache where one is placed) without touching model/optimizer state."""
    import numpy as np
    if hasattr(target, "buckets") and hasattr(target, "submit"):
        # a ModelServer: its ladder warmup is already the serving-side
        # entry point (and records the zero-recompile churn baseline)
        if tuned:
            from deeplearning4j_tpu.tune import records as _trecords
            m = getattr(target, "model", None)
            if m is not None:
                _trecords.auto_apply(m, mesh=mesh, context="warmup")
        return target.warmup(shapes, strict=strict)
    model = target
    if tuned:
        from deeplearning4j_tpu.tune import records as _trecords
        plan = _trecords.auto_apply(model, mesh=mesh, context="warmup")
        if plan is not None and steps_per_dispatch == 1:
            steps_per_dispatch = plan.steps_per_dispatch
    if policy is not None:
        model.setPrecisionPolicy(policy)
    if not model._initialized:
        model.init()
    model._ensure_opt_state()
    fdt = np.dtype(dtype) if dtype is not None else np.float32
    ldt = np.dtype(label_dtype) if label_dtype is not None else np.float32
    k = max(int(steps_per_dispatch), 1)

    from contextlib import nullcontext
    with (mesh if mesh is not None else nullcontext()):
        for spec in shapes:
            if _is_shape(spec):
                x = _zeros(spec, fdt)
                if placement is not None:
                    x = placement(x)
                model._warm_forward(x)
                continue
            if not (isinstance(spec, (tuple, list)) and len(spec) == 2):
                raise ValueError(
                    f"warmup shape spec {spec!r}: expected a feature shape "
                    "tuple (forward) or a (features_shape, labels_shape) "
                    "pair (train step)")
            fshape, lshape = spec
            if k > 1:
                x = _zeros((k,) + tuple(fshape), fdt)
                y = _zeros((k,) + tuple(lshape), ldt)
            else:
                x = _zeros(fshape, fdt)
                y = _zeros(lshape, ldt)
            if placement is not None:
                x, y = placement(x), placement(y)
            model._warm_dispatch(x, y, steps=k)
    return model


def warm_from_batch_signature(model, batch_sig: dict,
                              steps_per_dispatch: int = 1) -> bool:
    """Warm a train step from the signature a resilience checkpoint
    recorded (``{"features": [shape, dtype], "labels": [...]}``) — the
    resume path's cold-start killer. Best-effort: returns False (never
    raises) when the signature is absent/unusable."""
    if not batch_sig:
        return False
    try:
        f = batch_sig.get("features")
        lab = batch_sig.get("labels")
        if not f or not lab:
            return False
        warmup(model, [(tuple(f[0]), tuple(lab[0]))],
               steps_per_dispatch=steps_per_dispatch,
               dtype=f[1], label_dtype=lab[1])
        return True
    except Exception as e:
        warnings.warn(f"resume warmup skipped: {type(e).__name__}: {e}",
                      stacklevel=2)
        return False


def describe_batch(ds) -> Optional[dict]:
    """The checkpoint-manifest batch signature ``warm_from_batch_
    signature`` consumes: shapes/dtypes of a single-input DataSet (the
    overwhelmingly common resume case). MultiDataSet batches return
    None — their warmup happens through the explicit API."""
    feats = getattr(ds, "features", None)
    labels = getattr(ds, "labels", None)
    if feats is None or labels is None \
            or isinstance(feats, (list, tuple)):
        return None
    try:
        sig = {"features": [list(feats.shape), str(feats.dtype)],
               "labels": [list(labels.shape), str(labels.dtype)]}
    except AttributeError:
        return None
    if getattr(ds, "features_mask", None) is not None \
            or getattr(ds, "labels_mask", None) is not None:
        return None                  # masked signatures: explicit warmup
    return sig
