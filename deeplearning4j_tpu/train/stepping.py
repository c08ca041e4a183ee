"""Multi-step on-device training dispatch (megasteps).

The paper's core claim amortizes the reference's hundreds of JNI
crossings per training step down to ~1 dispatch per step (SURVEY.md
§3.1) — but on a high-latency device link one dispatch per step is
still the ceiling. This module batches K same-signature minibatches
into ONE compiled ``lax.scan`` program that performs K full update
steps (forward + loss + backward + clip + updater + frozen-layer
gating) per dispatch, the same move CUDA Graphs makes for kernel-launch
overhead and TensorFlow makes with in-graph loops (Abadi et al., 2016):
per-step host dispatch, listener bookkeeping, and link round trips all
drop by ~K×.

Pieces:

- :class:`MegaBatch` — K stacked batches, ``[K, B, ...]`` per array.
- :func:`group_into_megabatches` — signature-aware grouping of a batch
  stream; signature changes and epoch tails fall back to single-step
  fits, so ``fit(steps_per_dispatch=K)`` is ALWAYS numerically
  equivalent to K single-step fits (the hard guarantee the tests pin).
- :func:`scan_megastep` — wraps a single-step body into the scanned
  K-step program; the body is byte-for-byte the one the single-step
  path jits, so the per-iteration RNG (``fold_in(base, t)``), updater
  math, and frozen-layer gating are identical by construction.
- :func:`fit_epoch_multistep` — the epoch driver both
  ``MultiLayerNetwork.fit`` and ``ComputationGraph.fit`` delegate to:
  megabatch grouping behind a :class:`~deeplearning4j_tpu.data.dataset.
  DevicePrefetcher` (megabatch K+1 stages H2D while K computes), then
  ``model._fit_mega`` / ``model._fit_one`` per item.
"""

from __future__ import annotations

import time as _time
from contextlib import contextmanager
from typing import Iterable, Iterator, List, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import profiler as _prof
from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.nn import compilecache as _cc
from deeplearning4j_tpu.profiler import sanitizer as _sanitizer

# How many update steps the most recent compiled dispatch performed.
STEPS_PER_DISPATCH = _prof.get_registry().gauge(
    "dl4j_steps_per_dispatch",
    "Update steps performed by the most recent compiled train dispatch "
    "(1 = classic per-step dispatch, K = lax.scan megastep)")
# Total update steps, advanced by K per megastep dispatch. A
# dl4j_train_step_seconds sample covers the host's ENQUEUE of one
# dispatch (1 or K steps), so per-step host dispatch time under mixed K is
# rate(dl4j_train_step_seconds_sum) / rate(dl4j_train_iterations_total)
# — NOT sum/count, which a megastep/tail-fallback mix would skew — and on
# an asynchronous device it is not the time a step takes to run.
TRAIN_ITERATIONS = _prof.get_registry().counter(
    "dl4j_train_iterations_total",
    "Update steps performed by compiled train dispatches (a K-step "
    "megastep advances this by K)")


# Host bytes the fit functions themselves placed on the device: what a
# DevicePrefetcher staged ahead is in dl4j_prefetch_h2d_bytes_total.
TRAIN_H2D_BYTES = _prof.get_registry().counter(
    "dl4j_train_h2d_bytes_total",
    "Host bytes stage_batch placed on the device inside the fit loop "
    "(arrays already on the device are not counted)")
# Token ids handed to a step: what a language model's throughput counts.
TRAIN_TOKENS = _prof.get_registry().counter(
    "dl4j_train_tokens_total",
    "Token ids (elements of integer feature batches) stage_batch handed "
    "to a train dispatch inside the fit loop")
# What a looped language model's head read of its passes at the last
# step of a fit (nn.layers.LoopedLMOutputLayer keeps them as its state).
LOOP_EXIT_MASS = _prof.get_registry().gauge(
    "dl4j_loop_exit_mass",
    "Batch mean of the exit distribution's mass on each pass of a looped "
    "model at the last step of the last fit", labelnames=("pass",))
LOOP_PASS_LOSS = _prof.get_registry().gauge(
    "dl4j_loop_pass_loss",
    "Batch mean cross-entropy of each pass's logits of a looped model at "
    "the last step of the last fit", labelnames=("pass",))
# What a sparse-expert layer and a shared language-model head read at
# the last step of a fit (their states: nn.layers.SparseExpertsLayer,
# MTPLMOutputLayer).
MOE_HELD_PAIRS = _prof.get_registry().gauge(
    "dl4j_moe_held_pairs",
    "Routed (token, expert) pairs that met an expert held here, a "
    "sparse-expert layer, at the last step of the last fit",
    labelnames=("layer",))
MOE_EXPERT_LOAD = _prof.get_registry().gauge(
    "dl4j_moe_expert_load",
    "Routed pairs at each expert held here (its id among all the "
    "router's) at the last step of the last fit",
    labelnames=("layer", "expert"))
MOE_PASS_STEPS = _prof.get_registry().gauge(
    "dl4j_moe_pass_steps",
    "Steps a sparse-expert layer has run that took this many passes over "
    "its held pairs (1: they fit the first pass's rows), all the steps "
    "its state has seen up to the last fit's last",
    labelnames=("layer", "passes"))
LM_LOSS = _prof.get_registry().gauge(
    "dl4j_lm_loss",
    "Mean cross-entropy of the main head and of each multi-token-"
    "prediction head at the last step of the last fit",
    labelnames=("head",))
_STEP_SECONDS = _prof.get_registry().histogram(
    "dl4j_train_step_seconds",
    "Host time to enqueue one compiled train dispatch (1 or K steps); "
    "on an asynchronous device not the time the step runs")

FIT_EPOCH = "fit:epoch"
FIT_STAGE = "fit:stage"
FIT_PREPARE = "fit:prepare"
FIT_LISTENERS = "fit:listeners"
FIT_DISPATCH = "fit:dispatch"
FIT_COMMIT = "fit:commit"
FIT_BUILD = _cc.FIT_BUILD


class _NoSpans:
    """What :func:`step_spans` hands out while instrumentation is off:
    every call does nothing, reads no clock and allocates nothing."""

    __slots__ = ()

    def phase(self, name, when=None):
        pass

    def note(self, step, args):
        pass

    def done(self):
        pass


_OFF = _NoSpans()


class StepSpans:
    """The host spans of one pass through ``_fit_one`` / ``_fit_mega``,
    one after the other: ``fit:stage`` (all ``stage_batch`` calls of the
    batch, arg ``bytes``), ``fit:prepare`` (churn fingerprint, step
    lookup, resilience and sanitizer hooks), ``fit:listeners``
    (``when=start``), ``fit:dispatch`` (the host's time to enqueue the
    compiled step — also the ``dl4j_train_step_seconds`` sample),
    ``fit:commit``, ``fit:listeners`` (``when=done``). ``phase`` closes
    the open span and opens the next; each goes into the tracer's ring
    with the ``iteration`` the spans of one step share and its parent's
    name, and is open as ``jax.profiler.TraceAnnotation("dl4j:<name>")``
    meanwhile, so a device trace shows it on the trace's own clock."""

    __slots__ = ("iteration", "steps", "_name", "_when", "_t0", "_t0u",
                 "_ann", "_bytes0")

    def __init__(self, iteration: int, steps: int = 1):
        self.iteration = iteration
        self.steps = steps
        self._name = None

    def phase(self, name, when=None):
        self.done()
        self._name, self._when = name, when
        self._bytes0 = TRAIN_H2D_BYTES.value if name == FIT_STAGE else None
        self._ann = jax.profiler.TraceAnnotation(
            "dl4j:" + name, iteration=self.iteration)
        self._ann.__enter__()
        self._t0u, self._t0 = _prof.now_us(), _time.perf_counter()

    def note(self, step, args):
        """The step function about to be dispatched, for the step-program
        map (once per function; see ``profiler.stepprogram``)."""
        _prof.stepprogram.note(step._jit, args)

    def done(self):
        name = self._name
        if name is None:
            return
        seconds = _time.perf_counter() - self._t0
        self._ann.__exit__(None, None, None)
        self._name = None
        args = {"iteration": self.iteration, "parent": FIT_EPOCH}
        if self._when is not None:
            args["when"] = self._when
        if self._bytes0 is not None:
            args["bytes"] = int(TRAIN_H2D_BYTES.value - self._bytes0)
        if name == FIT_DISPATCH:
            args["steps"] = self.steps
            _STEP_SECONDS.observe(seconds)
        _prof.get_tracer().add_event(name, self._t0u, seconds * 1e6, args)


def dispatch(model, step, args, spans, site: str, new_signature: bool,
             steps: int = 1):
    """``step(*args)``: the compiled dispatch of ``_fit_one`` /
    ``_fit_mega``, inside ``spans``' ``fit:dispatch``. Where this thread
    built a program meanwhile (the listener of ``nn.compilecache`` heard
    one: a first sight of the signature, or a step cache dropped and made
    again at an old one) a ``fit:build`` span around the call goes into
    the ring, the ``cause`` of the ``compile:*`` spans inside it, with
    the churn ``site``, the ``iteration`` that paid, ``steps`` and
    whether the churn detector called the signature new. That is
    recorded whatever the profiling mode: a build happens once a
    program. A dispatch that builds nothing records nothing
    (``nn.compilecache.BuildCause``). While instrumentation is on a build
    that can be foreseen (a new signature, a step that never dispatched)
    is also open as ``dl4j:fit:build`` in a ``jax.profiler`` trace, under
    its step's ``dl4j:fit:dispatch``; one that cannot (the same step
    built again) has its ring span alone."""
    spans.note(step, args)
    ann = None
    if spans is not _OFF and (new_signature or not step.dispatched):
        ann = jax.profiler.TraceAnnotation(
            "dl4j:" + FIT_BUILD, iteration=model._iteration + 1)
        ann.__enter__()
    cause = _cc.BuildCause(FIT_BUILD)
    try:
        return step(*args)
    finally:
        built = cause.close()
        step.dispatched = True
        if ann is not None:
            ann.__exit__(None, None, None)
        if built:
            cause.record({"site": site, "iteration": model._iteration + 1,
                          "steps": steps, "new_signature": new_signature,
                          "parent": FIT_DISPATCH})


def step_spans(model, steps: int = 1):
    """The span recorder of the dispatch about to be made: a
    :class:`StepSpans` while instrumentation is active, else the shared
    no-op — one flag and one enum read a dispatch when it is off."""
    if _prof.instrumentation_active():
        return StepSpans(model._iteration + 1, steps)
    return _OFF


@contextmanager
def epoch_span(model):
    """``fit:epoch``: the parent of every ``fit:*`` span of one epoch."""
    if not _prof.instrumentation_active():
        yield
        return
    t0u = _prof.now_us()
    with jax.profiler.TraceAnnotation("dl4j:" + FIT_EPOCH,
                                      epoch=model._epoch):
        try:
            yield
        finally:
            _prof.get_tracer().add_event(
                FIT_EPOCH, t0u, _prof.now_us() - t0u,
                {"epoch": model._epoch})


def publish_loop_gauges(model) -> None:
    """``dl4j_loop_exit_mass`` / ``dl4j_loop_pass_loss`` from the state the
    last step left in a graph's looped heads, and ``dl4j_moe_held_pairs``
    / ``dl4j_moe_expert_load`` / ``dl4j_moe_pass_steps`` / ``dl4j_lm_loss``
    from its sparse-expert layers' and shared heads'. One device-to-host
    read, so only while instrumentation is active and only once a ``fit``
    call has dispatched its last step: it adds no sync inside the loop."""
    if not _prof.instrumentation_active():
        return
    for name, state in getattr(model, "_states", {}).items():
        if not isinstance(state, dict):
            continue
        if "expert_load" in state:
            load, ran = jax.device_get((state["expert_load"],
                                        state["pass_steps"]))
            held = model.conf.node_by_name[name].obj.held
            MOE_HELD_PAIRS.labels(name).set(float(load.sum()))
            for e, n in zip(held, load):
                MOE_EXPERT_LOAD.labels(name, str(e)).set(float(n))
            for p, n in enumerate(ran):
                MOE_PASS_STEPS.labels(name, str(p + 1)).set(float(n))
        if "head_loss" in state:
            for d, loss in enumerate(jax.device_get(state["head_loss"])):
                LM_LOSS.labels("main" if d == 0 else "mtp" if d == 1
                               else f"mtp{d}").set(float(loss))
        if "exit_mass" not in state:
            continue
        mass, loss = jax.device_get((state["exit_mass"],
                                     state["pass_loss"]))
        for t in range(len(mass)):
            LOOP_EXIT_MASS.labels(str(t + 1)).set(float(mass[t]))
            LOOP_PASS_LOSS.labels(str(t + 1)).set(float(loss[t]))


def stage_batch(model, a, mega: bool = False, features: bool = False):
    """Batch staging for the fit functions: plain ``jnp.asarray`` — or,
    when a :class:`~deeplearning4j_tpu.distributed.gspmd.
    ShardedTrainingPlan` is attached, ``device_put`` per the plan's
    batch PartitionSpec (dim 0 — dim 1 under a ``[K, B, ...]``
    megabatch — sharded over the plan's batch axes, replicated over
    model/seq axes). A no-op copy-wise for arrays a DevicePrefetcher
    already placed with the same sharding. While instrumentation is
    active the bytes of every host array placed count into
    ``dl4j_train_h2d_bytes_total``, and the elements of an integer
    ``features`` batch (token ids) into ``dl4j_train_tokens_total``."""
    if a is None:
        return None
    if _prof.instrumentation_active():
        if not isinstance(a, jax.Array):
            TRAIN_H2D_BYTES.inc(int(getattr(a, "nbytes", 0)))
        if features and np.issubdtype(a.dtype, np.integer) \
                and a.dtype != np.uint8:
            TRAIN_TOKENS.inc(int(a.size))
    plan = getattr(model, "_sharding_plan", None)
    if plan is None:
        return jnp.asarray(a)
    return plan.place(a, mega)


def batch_placement(model):
    """The DevicePrefetcher ``placement(array, mega)`` hook derived from
    the attached sharding plan's batch PartitionSpec — ``None`` (default
    device staging) when no plan is attached."""
    plan = getattr(model, "_sharding_plan", None)
    return None if plan is None else plan.place


def constrain_tree(tree, shardings):
    """``with_sharding_constraint`` over a whole pytree — how the GSPMD
    step pins its outputs (params, ZeRO-sharded updater state) to the
    plan's shardings INSIDE the one compiled program, so XLA cannot
    silently all-gather the sharded state at the step boundary.
    ``shardings=None`` is the identity (pure-replication plans compile
    byte-identical programs to the wrapper path)."""
    if shardings is None:
        return tree
    return jax.tree_util.tree_map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s),
        tree, shardings)


def fence_generation(model):
    """Entry half of the elastic dispatch-commit fence: the generation
    observed before dispatching (None when no fence is attached —
    non-elastic fits pay only this getattr)."""
    fence = getattr(model, "_dispatch_fence", None)
    return None if fence is None else fence.generation


@contextmanager
def dispatch_commit(model, gen):
    """Commit gate for a finished dispatch. Yields True when the
    dispatch may commit its outputs; False when the elastic layer
    bumped the fence while this dispatch was in flight (a watchdog-
    abandoned thread that un-hung after a mesh shrink) — the caller
    must DISCARD the result: the restored checkpoint state must not be
    overwritten, and no bookkeeping (iteration, listeners, checkpoint
    hooks) may run for a step the recovery already rolled back.
    The commit happens under the fence lock, mutually exclusive with
    the shrink path's bump+restore."""
    fence = getattr(model, "_dispatch_fence", None)
    if fence is None:
        yield True
        return
    with fence.lock:
        yield fence.generation == gen


class MegaBatch:
    """K same-signature training batches stacked along a leading axis.

    ``features``/``labels``/masks are ``[K, B, ...]`` arrays (or lists of
    them when ``multi`` — the MultiDataSet/ComputationGraph container);
    ``steps`` is K. Masks are None when absent from every stacked batch.
    """

    __slots__ = ("features", "labels", "features_mask", "labels_mask",
                 "steps", "multi")

    def numExamples(self) -> int:
        a = self.features[0] if self.multi else self.features
        return int(a.shape[0] * a.shape[1])


def batch_signature(ds):
    """Grouping key: two batches may share a compiled megastep iff their
    array shapes/dtypes and mask arities all match (the same condition
    under which the single-step jit cache would reuse one program)."""
    def sig(a):
        return None if a is None else (tuple(a.shape), str(a.dtype))
    if isinstance(ds, MultiDataSet):
        return ("multi",
                tuple(sig(a) for a in ds.features),
                tuple(sig(a) for a in ds.labels),
                tuple(sig(a) for a in (ds.features_masks or ())),
                tuple(sig(a) for a in (ds.labels_masks or ())))
    return ("single", sig(ds.features), sig(ds.labels),
            sig(ds.features_mask), sig(ds.labels_mask))


def _stack(arrs):
    if arrs[0] is None:
        return None
    if any(isinstance(a, jax.Array) for a in arrs):
        return jnp.stack(arrs)
    return np.stack(arrs)


def stack_megabatch(group: List[Union[DataSet, MultiDataSet]]) -> MegaBatch:
    """Stack K same-signature batches into one MegaBatch (host-side
    np.stack unless inputs are already device-resident)."""
    first = group[0]
    mb = MegaBatch()
    mb.steps = len(group)
    if isinstance(first, MultiDataSet):
        mb.multi = True
        mb.features = [_stack([d.features[i] for d in group])
                       for i in range(len(first.features))]
        mb.labels = [_stack([d.labels[i] for d in group])
                     for i in range(len(first.labels))]
        mb.features_mask = (
            [_stack([d.features_masks[i] for d in group])
             for i in range(len(first.features_masks))]
            if first.features_masks else None)
        mb.labels_mask = (
            [_stack([d.labels_masks[i] for d in group])
             for i in range(len(first.labels_masks))]
            if first.labels_masks else None)
    else:
        mb.multi = False
        mb.features = _stack([d.features for d in group])
        mb.labels = _stack([d.labels for d in group])
        mb.features_mask = _stack([d.features_mask for d in group])
        mb.labels_mask = _stack([d.labels_mask for d in group])
    return mb


def group_into_megabatches(batches: Iterable, steps: int) -> Iterator:
    """Yield MegaBatches of ``steps`` consecutive same-signature batches;
    batches stranded by a signature change or the epoch tail are yielded
    as plain DataSets (single-step fits) — equivalence over cleverness.
    Items that arrive ALREADY stacked (a staged pipeline's
    ``dispatch_stream()`` emits contiguous MegaBatch buffers directly —
    no re-stack, one H2D transfer) pass through untouched."""
    if steps <= 1:
        yield from batches
        return
    pending, sig = [], None
    for ds in batches:
        if isinstance(ds, MegaBatch):
            yield from pending
            pending, sig = [], None
            yield ds
            continue
        s = batch_signature(ds)
        if pending and s != sig:
            yield from pending
            pending = []
        sig = s
        pending.append(ds)
        if len(pending) == steps:
            yield stack_megabatch(pending)
            pending = []
    yield from pending


def use_dispatch_stream(data, steps: int, session) -> bool:
    """True when a fit can pull native megabatches from a staged
    pipeline iterator: K matches the iterator's declared staging, no
    resilience session (cursors are recorded per pull — a K-batch pull
    would make them dispatch-granular), and no per-batch preprocessor
    (those run on the host path; use device augmentation instead)."""
    return (steps > 1 and session is None
            and getattr(data, "megabatch_steps", 1) == steps
            and hasattr(data, "dispatch_stream")
            and getattr(data, "_pre", None) is None)


def scan_megastep(body, num_carry: int):
    """Wrap a single-step ``body(*carry, *xs) -> (*new_carry, loss)`` into
    a K-step program: carry threads (params, states, opt_state, t) —
    plus the dynamic loss-scale state ``[scale, good_steps]`` when the
    attached PrecisionPolicy is dynamic (``num_carry=5``) — every xs
    leaf gains a leading K axis, and the K per-step losses come back as
    ONE device vector. The body is the exact function the single-step
    path jits, so K scanned steps == K single-step fits numerically
    (the scale automaton ticks per scanned sub-step exactly as it would
    per dispatch)."""
    def megastep(*args):
        carry, xs = args[:num_carry], args[num_carry:]

        def scan_body(c, x):
            out = body(*c, *x)
            return tuple(out[:-1]), out[-1]

        carry, losses = jax.lax.scan(scan_body, tuple(carry), tuple(xs))
        return (*carry, losses)
    return megastep


def record_megastep(model, losses, steps: int, batch_size: int,
                    san_token=None, spans=_OFF) -> None:
    """Shared post-dispatch bookkeeping for ``_fit_mega`` (both network
    classes): numerics panic gate over the K-loss vector (with first-
    nonfinite provenance when the sanitizer armed ``san_token``), then
    per-step listener delivery — each ``losses[j]`` stays a lazy device
    scalar unless a listener actually pulls ``score()``.

    Listener semantics under megasteps: all K callback pairs fire AFTER
    the dispatch, so a listener that inspects model state (params,
    checkpoints) at iteration N observes the END-OF-DISPATCH state, not
    iteration N's. Iteration-indexed side effects (CheckpointListener
    intervals, EvaluativeListener) should use an interval K divides — or
    choose K to divide the interval — so callbacks land on dispatch
    boundaries where state and iteration number agree."""
    _sanitizer.check(
        model, san_token, losses,
        context=f"megastep losses at iterations "
                f"{model._iteration + 1}..{model._iteration + steps}")
    if _prof.instrumentation_active():
        TRAIN_ITERATIONS.inc(steps)
    model._last_batch_size = batch_size
    if not model._listeners:
        # no one consumes per-step losses: ONE lazy slice for score()
        # instead of K tiny indexing dispatches per megastep
        model._iteration += steps
        model._score = losses[steps - 1]
    else:
        spans.phase(FIT_LISTENERS, "done")   # all K pairs, one span
        for j in range(steps):
            model._score = losses[j]
            model._iteration += 1
            for lst in model._listeners:
                if hasattr(lst, "onIterationStart"):
                    lst.onIterationStart(model, model._iteration)
                if hasattr(lst, "iterationDone"):
                    lst.iterationDone(model, model._iteration, model._epoch)
    spans.done()
    # resilience seam (train.resilience): non-finite recovery, periodic
    # checkpoint, and preemption all act at dispatch granularity — the
    # in-flight megastep always completes before any of them fire
    res = getattr(model, "_resilience", None)
    if res is not None:
        res.after_dispatch(losses, steps)


def fit_epoch_multistep(model, batches: Iterable, steps: int,
                        prefetch: int = 2, placement=None) -> None:
    """One epoch of multi-step dispatch: group the batch stream into
    megabatches and stage each onto the device from a background thread
    (double buffer — megabatch K+1 transfers while K computes), then run
    each through the model's compiled megastep. ``prefetch <= 0`` runs
    the whole pipeline synchronously on the calling thread (no worker
    thread; for iterators backed by thread-affine resources)."""
    from deeplearning4j_tpu.data.dataset import DevicePrefetcher, stage_item

    def drive(items):
        for item in _prof.iter_with_data_wait(items, model):
            if isinstance(item, MegaBatch):
                model._fit_mega(item)
            else:
                model._fit_one(item)

    if prefetch and prefetch > 0:
        with DevicePrefetcher(batches, steps_per_dispatch=steps,
                              prefetch=prefetch, placement=placement) as pf:
            drive(pf)
    else:
        drive(stage_item(item, placement)
              for item in group_into_megabatches(batches, steps))


def apply_tuned_plan(model, tune, steps_per_dispatch: int, prefetch: int):
    """Resolve ``fit(tune=...)`` (ISSUE 17): ``"auto"`` consults the
    autotuner record store for this (model, mesh, backend, jax version)
    key; a :class:`~deeplearning4j_tpu.tune.space.TuningPlan` instance
    applies directly.  The plan's model-level seams (layout, fusion,
    precision) apply through the model's own signature-keyed setters —
    re-applying an equal plan keeps every compiled-step cache — and the
    plan's fit-level knobs take over only where the caller left the
    defaults.  Returns the effective ``(steps_per_dispatch, prefetch)``."""
    from deeplearning4j_tpu.tune import records as _trecords
    from deeplearning4j_tpu.tune.space import TuningPlan
    if isinstance(tune, TuningPlan):
        plan = tune
        plan.apply(model)
    elif tune == "auto":
        plan = _trecords.auto_apply(
            model, mesh=getattr(model, "_sharding_plan", None),
            context="fit")
    else:
        raise ValueError(
            f'tune= expects "auto" or a TuningPlan, got {tune!r}')
    if plan is not None:
        if steps_per_dispatch == 1:
            steps_per_dispatch = plan.steps_per_dispatch
        if prefetch == 2:
            prefetch = plan.prefetch
    return steps_per_dispatch, prefetch
