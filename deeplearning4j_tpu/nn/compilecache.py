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
where it hit).

jax-free at module scope; jax loads lazily, on the compile path.
"""

from __future__ import annotations

import time
import warnings
from typing import Optional

from deeplearning4j_tpu.profiler.metrics import get_registry

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
    AOT compiles this process ran, with their seconds."""
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

    __slots__ = ("_jit", "scope", "_compiled", "_warned")

    def __init__(self, fn, scope: str, donate_argnums=()):
        import jax
        self._jit = jax.jit(fn, donate_argnums=donate_argnums)
        self.scope = scope                  # names the program in warnings
        self._compiled = {}
        self._warned = False

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
    return CachedDispatch(fn, scope, donate_argnums=donate_argnums)


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
