"""Elastic multi-device training: device-loss detection, dispatch
watchdogs, and coordinated mesh-shrink resume.

Production training systems treat worker failure/restart as part of the
training loop, not an operator incident (TensorFlow system paper,
PAPERS.md), and at collective scale the pathologies are stragglers and
hung allreduces as much as hard crashes ("Scalable Distributed DNN
Training using TensorFlow and CUDA-Aware MPI", PAPERS.md). PR 5 made a
single-process ``fit()`` survive preemption and NaNs; this module makes
a multi-chip :class:`~deeplearning4j_tpu.parallel.wrapper.
ParallelWrapper` run survive the failures that live BELOW the process:

- :class:`DeviceMonitor` — between dispatches, a tiny sentinel dispatch
  per mesh device classifies each as healthy / degraded (probe slower
  than ``degraded_after``) / dead (probe raises). Under a
  :class:`~deeplearning4j_tpu.faults.FaultPlan` the planned device
  losses are injected at this seam, so every shrink path is a seeded
  deterministic chaos test.
- :class:`DispatchWatchdog` — runs the blocking device dispatch on a
  watchdog-supervised thread with a SOFT deadline (exceeding it records
  a ``dl4j_dispatch_watchdog_timeouts_total`` timeout; if the dispatch
  then completes it is a straggler, observed in
  ``dl4j_dispatch_straggler_seconds``) and a HARD grace deadline
  (exceeding that abandons the dispatch and raises
  :class:`DispatchTimeoutError` — the elastic loop probes the devices
  and converts a confirmed loss into the shrink path).
- :class:`CoordinationService` — the multi-host seam for the resume
  barrier: every participant reports its last completed step and all
  agree on the minimum before anyone restarts.
  :class:`InProcessCoordinator` is the in-process implementation;
  file- or socket-based rendezvous plugs in behind the same two-method
  contract later.
- :func:`fit_elastic` — the driver ``ParallelWrapper.fit(elastic=...)``
  delegates to: on device loss it drains in-flight work (the
  DevicePrefetcher's staged megabatches for the OLD mesh layout are
  discarded, never dispatched onto dead devices), runs the resume
  barrier, writes a coordinated checkpoint of the agreed step through
  the PR-5 CheckpointManager, rebuilds a smaller
  :class:`~deeplearning4j_tpu.parallel.mesh.DeviceMesh` from the
  survivors (re-validated through the E101/E102 distribution lints),
  rescales the learning rate per :class:`ElasticConfig.lr_policy`, and
  resumes bit-exactly from the checkpoint on the shrunk mesh.

Metrics: ``dl4j_device_lost_total``, ``dl4j_mesh_shrinks_total``,
``dl4j_dispatch_watchdog_timeouts_total``,
``dl4j_dispatch_straggler_seconds``, ``dl4j_device_probe_seconds``,
``dl4j_elastic_recovery_seconds``.
"""

from __future__ import annotations

import logging
import sys
import threading
import time
import warnings
from contextlib import ExitStack
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set

import jax
import numpy as np

from deeplearning4j_tpu import profiler as _prof
from deeplearning4j_tpu.parallel.mesh import DeviceMesh
from deeplearning4j_tpu.utils.environment import jax_compile_cache_status

logger = logging.getLogger("deeplearning4j_tpu")

_REG = _prof.get_registry()
DEVICE_LOST = _REG.counter(
    "dl4j_device_lost_total",
    "Mesh devices classified dead by the elastic layer's health probes")
MESH_SHRINKS = _REG.counter(
    "dl4j_mesh_shrinks_total",
    "Elastic mesh shrinks performed (coordinated checkpoint + rebuild "
    "on the surviving devices + resume)")
WATCHDOG_TIMEOUTS = _REG.counter(
    "dl4j_dispatch_watchdog_timeouts_total",
    "Dispatches that exceeded the watchdog's soft deadline")
STRAGGLER_SECONDS = _REG.histogram(
    "dl4j_dispatch_straggler_seconds",
    "Wall time of dispatches that exceeded the watchdog deadline but "
    "eventually completed (stragglers)")
PROBE_SECONDS = _REG.histogram(
    "dl4j_device_probe_seconds",
    "Per-device sentinel-dispatch health probe round-trip time")
RECOVERY_SECONDS = _REG.histogram(
    "dl4j_elastic_recovery_seconds",
    "Wall time from device-loss detection to the resumed state on the "
    "shrunk mesh (barrier + checkpoint + rebuild + restore)")


class DeviceLossError(RuntimeError):
    """One or more mesh devices are dead. Carries ``dead`` (device ids)
    and ``surviving`` (live jax devices) so the shrink path can rebuild."""

    def __init__(self, dead: Set[int], surviving: List, step: int):
        self.dead = set(dead)
        self.surviving = list(surviving)
        self.step = int(step)
        super().__init__(
            f"device(s) {sorted(self.dead)} dead at step {step} "
            f"({len(self.surviving)} surviving)")


class DispatchTimeoutError(RuntimeError):
    """A dispatch exceeded the watchdog's hard grace deadline and was
    abandoned. The update for its step(s) never landed; model state is
    the last completed step's."""


class ElasticShrinkError(RuntimeError):
    """The mesh cannot shrink any further (too few survivors, a
    non-data-parallel mesh, shrink budget exhausted, or the shrunk
    configuration fails static validation)."""


@dataclass
class DeviceHealth:
    """One probe sweep's classification."""

    dead: Set[int] = field(default_factory=set)
    degraded: Set[int] = field(default_factory=set)
    probe_seconds: Dict[int, float] = field(default_factory=dict)

    def healthy(self) -> bool:
        return not self.dead


class DeviceMonitor:
    """Sentinel-dispatch device health prober.

    ``probe()`` pushes a tiny array to each device and pulls it back —
    one full host<->device round trip per device, the cheapest dispatch
    that still proves the device answers. A probe that raises marks the
    device DEAD; one slower than ``degraded_after`` seconds marks it
    DEGRADED (recorded, not acted on — degradation is the straggler
    signal, loss is the shrink signal). A
    :class:`~deeplearning4j_tpu.faults.FaultPlan` injects planned
    losses at this seam deterministically.
    """

    def __init__(self, degraded_after: float = 0.25, plan=None):
        self.degraded_after = float(degraded_after)
        self.plan = plan
        self._sentinel = np.ones((8,), np.float32)

    def probe(self, devices, step: Optional[int] = None) -> DeviceHealth:
        health = DeviceHealth()
        planned = set()
        if self.plan is not None:
            planned = self.plan.dead_devices(step)
        for d in devices:
            if d.id in planned:
                health.dead.add(d.id)
                continue
            t0 = time.perf_counter()
            try:
                back = np.asarray(jax.device_put(self._sentinel, d))
                if not np.array_equal(back, self._sentinel):
                    raise RuntimeError(f"sentinel round-trip corrupt on {d}")
            except Exception:
                health.dead.add(d.id)
                continue
            dt = time.perf_counter() - t0
            health.probe_seconds[d.id] = dt
            PROBE_SECONDS.observe(dt)
            if dt > self.degraded_after:
                health.degraded.add(d.id)
        return health


def shrink_mesh_on_dead(mesh: DeviceMesh, plan=None,
                        context: str = "serving") -> Optional[DeviceMesh]:
    """Probe ``mesh``'s devices and return a data-parallel survivor
    mesh when some are dead — or ``None`` when the mesh must stay as it
    is: no deaths, a tensor/sequence-parallel mesh (each device holds
    an unreplicated shard, so dropping one would break the model's
    sharding — mirrors the training shrink guard), or no survivors at
    all. Shared by :class:`~deeplearning4j_tpu.parallel.wrapper.
    ParallelInference` and ``serving.ModelServer`` so the two serving
    paths cannot drift; emits the operator-facing warnings either way
    (``context`` prefixes them)."""
    devices = mesh.devices
    health = DeviceMonitor(plan=plan).probe(devices)
    if not health.dead:
        return None
    if mesh.size("model") * mesh.size("seq") > 1:
        warnings.warn(
            f"{context}: device(s) {sorted(health.dead)} are dead but the "
            "mesh has model/seq axes — cannot shrink a tensor-parallel "
            "mesh; retrying on the full mesh", stacklevel=3)
        return None
    surviving = [d for d in devices if d.id not in health.dead]
    if not surviving:
        warnings.warn(
            f"{context}: every device is dead — keeping the mesh, the "
            "next retry will fail structurally", stacklevel=3)
        return None
    DEVICE_LOST.inc(len(health.dead))
    warnings.warn(
        f"{context}: dropping dead device(s) {sorted(health.dead)}; "
        f"continuing on {len(surviving)} replica(s)", stacklevel=3)
    return DeviceMesh.create(data=len(surviving), model=1, seq=1,
                             devices=surviving)


class DispatchFence:
    """Commit fence between the elastic recovery path and abandoned
    dispatch threads. ``fit_elastic`` attaches one to the model as
    ``_dispatch_fence``; the fit functions read ``generation`` at entry
    and COMMIT their outputs (state assignment + bookkeeping) only if,
    under the lock, the generation is unchanged. The shrink path bumps
    the generation and performs its checkpoint-restore under the same
    lock — so a hung dispatch that un-hangs after the mesh shrank
    discards its result instead of overwriting the restored state (or
    checkpointing a stale step)."""

    def __init__(self):
        self.lock = _prof.InstrumentedLock("elastic:fence")
        self.generation = 0


class DispatchWatchdog:
    """Deadline supervision around a blocking device dispatch.

    ``run(fn, step)`` executes ``fn`` on a dispatch thread and waits:

    - within ``deadline`` s: normal completion.
    - past ``deadline`` but within ``grace`` (default ``4*deadline``):
      a TIMEOUT is recorded; if the dispatch then completes it counts
      as a straggler and its result is used — transient stalls do not
      kill training.
    - past ``grace``: the dispatch is abandoned (the thread is a
      daemon; a truly hung XLA collective cannot be interrupted from
      Python) and :class:`DispatchTimeoutError` is raised. The caller
      must treat the step as never applied.

    ``deadline=None`` disables supervision: the dispatch runs inline on
    the calling thread (fault-injection delays still honored).

    The first ``warmup`` dispatches after :meth:`begin_attempt` are
    UNSUPERVISED (no deadline): they include XLA compilation, whose
    wall time has nothing to do with device health — counting it
    against the deadline would flag every cold start as hung. The
    elastic loop calls ``begin_attempt()`` on entry and again after
    every mesh shrink (a new mesh recompiles). Steady-state dispatches
    that recompile (a new batch signature mid-run) should be covered by
    setting ``deadline`` above worst-case compile time or raising
    ``grace``.
    """

    def __init__(self, deadline: Optional[float] = None,
                 grace: Optional[float] = None, plan=None, warmup: int = 2):
        self.deadline = deadline
        self.grace = grace if grace is not None else (
            None if deadline is None else deadline * 4)
        self.plan = plan
        self.warmup = int(warmup)
        self._lenient = self.warmup
        self.timeouts = 0
        self.stragglers = 0

    def begin_attempt(self, count: Optional[int] = None):
        """The next ``warmup`` dispatches will compile (fresh program /
        fresh mesh): run them unsupervised. ``count`` overrides the
        leniency for callers whose steady-state ``warmup`` is 0 (the
        model server AOT-compiles everything, but a mesh rebuild still
        legitimately compiles once)."""
        self._lenient = max(self._lenient,
                            self.warmup if count is None else int(count))

    def _hold(self, step: int) -> bool:
        """Fault seam: returns False when the planned hang says the
        dispatch never completes."""
        if self.plan is None:
            return True
        return self.plan.dispatch_hold(step)

    def run(self, fn, step: int):
        lenient = self._lenient > 0
        if lenient:
            self._lenient -= 1
        if self.deadline is None or lenient:
            if self._hold(step):
                return fn()
            raise DispatchTimeoutError(
                f"dispatch for step {step} never completed (injected hang "
                "outside watchdog supervision)")
        done = threading.Event()
        result: list = []
        error: list = []

        def work():
            try:
                if self._hold(step):
                    result.append(fn())
            except BaseException as e:      # re-raised on the caller
                error.append(e)
            finally:
                done.set()

        t = threading.Thread(target=work, daemon=True,
                             name=f"dl4j-dispatch-{step}")
        t0 = time.perf_counter()
        t.start()
        timed_out = False
        if not done.wait(self.deadline):
            timed_out = True
            self.timeouts += 1
            WATCHDOG_TIMEOUTS.inc()
            logger.warning("dispatch watchdog: step %d exceeded the %.3gs "
                           "deadline", step, self.deadline)
            remaining = None if self.grace is None \
                else max(self.grace - self.deadline, 0.0)
            if not done.wait(remaining):
                if self.plan is not None:
                    # let an injected hard hang exit WITHOUT dispatching
                    self.plan.release_hangs()
                raise DispatchTimeoutError(
                    f"dispatch for step {step} still running after the "
                    f"{self.grace:.3g}s grace deadline — abandoning it "
                    "(state is the last completed step's)")
        if error:
            raise error[0]
        dt = time.perf_counter() - t0
        if not result:
            # the injected hang was released without dispatching: the
            # step never completed even though the thread exited
            raise DispatchTimeoutError(
                f"dispatch for step {step} never completed")
        if timed_out:
            self.stragglers += 1
            STRAGGLER_SECONDS.observe(dt)
            logger.warning("dispatch watchdog: step %d completed late "
                           "(%.3fs) — straggler recorded", step, dt)
        return result[0]


# ----------------------------------------------------------- coordination
class CoordinationService:
    """Pluggable multi-host rendezvous for the elastic resume barrier.

    ``resume_barrier(participant, step)`` blocks until every participant
    has reported its last locally completed step and returns the agreed
    step — the MINIMUM across participants, i.e. the last GLOBALLY
    completed step every survivor can restore.
    :class:`InProcessCoordinator` serves single-process jobs; REAL
    multi-host jobs pass ``ElasticConfig(coordinator=
    distributed.coordinator.SocketCoordinator(...))`` (TCP rendezvous
    with heartbeats + dead-peer detection) or ``FileCoordinator``
    (shared-filesystem rendezvous) — both implement this same
    two-method contract across OS processes (ISSUE 15 tier 3,
    ``pytest -m multihost``).
    """

    def resume_barrier(self, participant: str, step: int,
                       timeout: float = 60.0) -> int:
        raise NotImplementedError


class InProcessCoordinator(CoordinationService):
    """Threading-based coordinator for single-process (possibly
    multi-threaded-test) jobs. Reusable across successive barriers."""

    def __init__(self, participants: int = 1):
        self.participants = int(participants)
        self._cond = _prof.InstrumentedCondition("elastic:coordinator")
        self._round: Dict[str, int] = {}
        self._results: Dict[int, int] = {}
        self._generation = 0

    def resume_barrier(self, participant: str, step: int,
                       timeout: float = 60.0) -> int:
        with self._cond:
            gen = self._generation
            self._round[str(participant)] = int(step)
            if len(self._round) >= self.participants:
                self._results[gen] = min(self._round.values())
                self._round = {}
                self._generation += 1
                self._cond.notify_all()
                return self._results[gen]
            deadline = time.monotonic() + timeout
            while gen not in self._results:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    arrived = len(self._round)
                    self._round.pop(str(participant), None)
                    raise TimeoutError(
                        f"resume barrier: only {arrived}/"
                        f"{self.participants} participants arrived within "
                        f"{timeout}s")
                self._cond.wait(remaining)
            return self._results[gen]


# ----------------------------------------------------------------- config
@dataclass
class ElasticConfig:
    """Tuning for :func:`fit_elastic` / ``ParallelWrapper.fit(elastic=)``.

    ``lr_policy`` governs the learning-rate rescale on shrink. The
    GLOBAL batch is unchanged by a shrink (each survivor's per-replica
    batch grows), so the linear-scaling rule says the LR should not
    change — ``"none"`` is the default and keeps the shrunk run
    bit-exact with a fresh small-mesh fit. ``"linear"``/``"sqrt"``
    scale by the survivor fraction (or its square root) for recipes
    that tie LR to replica count.
    """

    watchdog_deadline: Optional[float] = None   # soft, seconds; None = off
    watchdog_grace: Optional[float] = None      # hard; default 4x deadline
    watchdog_warmup: int = 2      # unsupervised compile dispatches/attempt
    probe_every: int = 1          # dispatches between health probes; 0 = off
    degraded_after: float = 0.25  # probe slower than this -> degraded
    max_shrinks: int = 4
    min_devices: int = 1
    lr_policy: str = "none"       # none | linear | sqrt
    coordinator: Optional[CoordinationService] = None
    participant: str = "proc0"
    barrier_timeout: float = 60.0


# ------------------------------------------------------------------ driver
def fit_elastic(wrapper, iterator, epochs: int = 1,
                steps_per_dispatch: int = 1, checkpoint=None,
                nan_policy=None, faults=None,
                config: Optional[ElasticConfig] = None):
    """Elastic data-parallel fit over ``wrapper.mesh`` (see module doc).

    Requires ``checkpoint=CheckpointConfig(...)`` — the shrink path
    resumes from the coordinated checkpoint, and a run that cannot
    checkpoint cannot shrink. All PR-5 resilience features
    (``nan_policy``, fault injection, preemption, periodic saves)
    compose with the elastic layer unchanged.
    """
    from deeplearning4j_tpu.train import resilience as _res

    cfg = config or ElasticConfig()
    if checkpoint is None:
        raise ValueError(
            "elastic training requires checkpoint=CheckpointConfig(...): "
            "the mesh-shrink path resumes from the coordinated checkpoint")
    if cfg.lr_policy not in ("none", "linear", "sqrt"):
        # reject before begin_session installs signal handlers — and long
        # before a device loss would surface the typo mid-recovery
        raise ValueError(f"unknown lr_policy {cfg.lr_policy!r} (expected "
                         "none|linear|sqrt)")
    model = wrapper.model
    if not model._initialized:
        model.init()
    model._ensure_opt_state()
    session, stream_iter = _res.begin_session(model, iterator, checkpoint,
                                              nan_policy, faults)
    coordinator = cfg.coordinator or InProcessCoordinator(1)
    monitor = DeviceMonitor(degraded_after=cfg.degraded_after, plan=faults)
    watchdog = DispatchWatchdog(cfg.watchdog_deadline, cfg.watchdog_grace,
                                plan=faults, warmup=cfg.watchdog_warmup)
    model._dispatch_fence = DispatchFence()
    k = max(int(steps_per_dispatch), 1)
    # fit_scope's epoch accounting, shared: every post-shrink re-entry
    # continues toward the same absolute target
    target_epochs = _res.epoch_target(session, model, epochs)
    shrinks = 0
    try:
        while True:
            try:
                _run_epochs(wrapper, model, session, stream_iter,
                            target_epochs, k, monitor, watchdog, cfg)
                return model
            except _res.PreemptionRequested:
                session.on_preempt()
                return model
            except DeviceLossError as e:
                shrinks += 1
                if shrinks > cfg.max_shrinks:
                    raise ElasticShrinkError(
                        f"{shrinks} mesh shrinks exceed max_shrinks="
                        f"{cfg.max_shrinks} — giving up") from e
                _shrink_and_resume(wrapper, model, session, stream_iter, e,
                                   cfg, coordinator, steps_per_dispatch=k)
    finally:
        model._dispatch_fence = None
        session.close(raise_errors=sys.exc_info()[1] is None)


def _run_epochs(wrapper, model, session, iterator, epochs, k, monitor,
                watchdog, cfg):
    """The supervised epoch loop over the CURRENT mesh: one unified
    DevicePrefetcher-fed dispatch loop for K=1 and K>1 (staged items are
    sharded for this mesh; a shrink discards them with the prefetcher)."""
    from deeplearning4j_tpu.data.dataset import DevicePrefetcher, stage_item
    from deeplearning4j_tpu.train.resilience import PreemptionRequested
    from deeplearning4j_tpu.train.stepping import (MegaBatch,
                                                   group_into_megabatches)

    mesh = wrapper.mesh
    watchdog.begin_attempt()    # first dispatches on this mesh compile
    with _prof.trace_span("collective:replicate_params",
                          devices=mesh.size("data")):
        model._params = mesh.replicate(model._params)
        model._states = mesh.replicate(model._states)
        model._opt_state = mesh.replicate(model._opt_state)
    model._t_dev = None     # rebuild the device clock on the new mesh
    n_epochs = max(epochs - model._epoch, 0)
    for _ in range(n_epochs):
        if not session.consume_skip_reset():
            iterator.reset()

        def padded():
            while iterator.hasNext():
                yield wrapper._pad(iterator.next())

        stream = session.wrap_batches(padded())
        dispatches = 0
        with ExitStack() as stack:
            if wrapper.prefetch and wrapper.prefetch > 0:
                items = stack.enter_context(DevicePrefetcher(
                    stream, steps_per_dispatch=k,
                    prefetch=wrapper.prefetch,
                    placement=wrapper._mesh_placement))
            else:   # thread-affine sources: inline staging
                items = (stage_item(it, wrapper._mesh_placement)
                         for it in group_into_megabatches(stream, k))
            it = iter(items)
            while True:
                try:
                    item = next(it)
                except StopIteration:
                    break
                except (PreemptionRequested, DeviceLossError):
                    raise
                except Exception as e:
                    # a staging failure (device_put onto a dying chip)
                    # is a loss signal too: probe before giving up
                    _check_health(monitor, mesh, model._iteration,
                                  cause=e)
                    raise
                step0 = model._iteration + 1

                def fn(i=item):
                    # the jax mesh context is THREAD-LOCAL, and the
                    # trace-cache key contains the entered-mesh stack:
                    # enter it HERE (dispatch thread or inline) and
                    # nowhere else, so warmup and supervised dispatches
                    # trace under the identical context
                    with mesh:
                        if isinstance(i, MegaBatch):
                            model._fit_mega(i)
                        else:
                            model._fit_one(i)
                try:
                    watchdog.run(fn, step0)
                except DispatchTimeoutError as e:
                    # hung dispatch: a dead device is the usual cause —
                    # confirmed loss shrinks, a healthy mesh surfaces
                    # the timeout (the abandoned step MAY have landed;
                    # blind retry could double-apply it)
                    _check_health(monitor, mesh, step0, cause=e)
                    raise
                dispatches += 1
                if cfg.probe_every and dispatches % cfg.probe_every == 0:
                    _check_health(monitor, mesh, model._iteration)
        model._epoch += 1
        session.on_epoch_end()


def _check_health(monitor, mesh: DeviceMesh, step: int, cause=None):
    """Probe every device of ``mesh``; raise DeviceLossError when any
    are dead (chained to ``cause`` when the probe was triggered by a
    dispatch/staging failure)."""
    devices = mesh.devices
    health = monitor.probe(devices, step)
    if health.dead:
        surviving = [d for d in devices if d.id not in health.dead]
        raise DeviceLossError(health.dead, surviving, step) from cause


def _shrink_and_resume(wrapper, model, session, iterator,
                       loss: DeviceLossError, cfg: ElasticConfig,
                       coordinator: CoordinationService,
                       steps_per_dispatch: int = 1):
    """The coordinated shrink: barrier -> checkpoint -> smaller mesh ->
    revalidate -> LR rescale -> restore + data-pipeline rebind."""
    t0 = time.perf_counter()
    DEVICE_LOST.inc(len(loss.dead))
    logger.warning("device loss at step %d: %s dead, %d surviving — "
                   "starting coordinated mesh shrink", loss.step,
                   sorted(loss.dead), len(loss.surviving))
    mesh = wrapper.mesh
    if mesh.size("model") * mesh.size("seq") > 1:
        raise ElasticShrinkError(
            "elastic shrink supports data-parallel meshes only (model/seq "
            f"axes are {mesh.size('model')}x{mesh.size('seq')}): a lost "
            "device holds an unreplicated parameter shard") from loss
    if len(loss.surviving) < max(cfg.min_devices, 1):
        raise ElasticShrinkError(
            f"only {len(loss.surviving)} devices survive (< min_devices="
            f"{cfg.min_devices})") from loss

    # 1. resume barrier: all participants agree on the last GLOBALLY
    #    completed step before anyone restarts
    agreed = coordinator.resume_barrier(cfg.participant,
                                        int(model._iteration),
                                        timeout=cfg.barrier_timeout)
    # 2. coordinated checkpoint OF THE AGREED STEP: written by the
    #    participant(s) standing at it; anyone ahead rolls back to it in
    #    the restore below (writing a local ahead-of-agreement checkpoint
    #    would desync the participants the barrier just synchronized)
    if agreed == int(model._iteration):
        session.checkpoint(status="elastic-shrink")
    else:
        logger.warning("resume barrier agreed on step %d (local %d): "
                       "rolling back to the agreed checkpoint", agreed,
                       model._iteration)
    if session.manager is not None:
        session.manager.flush()     # async writer: restore needs it on disk

    # 3. smaller mesh from the survivors, re-validated statically
    old_data = mesh.size("data")
    new_mesh = DeviceMesh.create(data=len(loss.surviving), model=1, seq=1,
                                 devices=loss.surviving)
    _revalidate_shrink(model, session, new_mesh)

    # 4. per-replica batch grew (global batch unchanged); rescale LR per
    #    policy
    _rescale_lr(model, session, cfg, old_data, len(loss.surviving))

    # 5. restore THE AGREED checkpoint (not the newest — a stale straggler
    #    write or a local ahead-of-agreement save must not hijack the
    #    coordinated resume) and rebind the data pipeline (the old
    #    prefetcher died with the unwind; its staged megabatches for the
    #    old mesh layout were discarded, not dispatched). The fence bump
    #    + restore run under one lock: an abandoned hung dispatch that
    #    un-hangs later sees the new generation and discards its result
    #    instead of overwriting the restored state (see DispatchFence).
    def _restore():
        return session.manager.restore(model, normalizer=session.normalizer,
                                       count_resume=False, step=agreed)
    fence = getattr(model, "_dispatch_fence", None)
    if fence is not None:
        with fence.lock:
            fence.generation += 1
            info = _restore()
    else:
        info = _restore()
    if info is None:
        raise ElasticShrinkError(
            f"mesh shrink: no valid checkpoint for the agreed step "
            f"{agreed} (the coordinated checkpoint is missing or failed "
            "validation)") from loss
    session._cursors.clear()        # pulled-ahead cursors are stale
    cursor = info.get("cursor")
    if cursor is not None and iterator is not None:
        try:
            iterator.seek(cursor)
            session._skip_reset = True
        except NotImplementedError:
            warnings.warn(
                "elastic resume: iterator does not support seek(); "
                "replaying the interrupted epoch from its start",
                stacklevel=2)
    wrapper.mesh = new_mesh
    # 6. survivor-mesh warmup through the unified compile-cache seam:
    #    where JAX's persistent cache is placed, a survivor layout any
    #    earlier run (or process) already compiled is read from disk,
    #    so the post-shrink first dispatch is a read, not an XLA
    #    compile. Best-effort — a warm miss just compiles as before.
    _warm_survivor_mesh(wrapper, model, session, new_mesh,
                        steps_per_dispatch)
    MESH_SHRINKS.inc()
    dt = time.perf_counter() - t0
    RECOVERY_SECONDS.observe(dt)
    logger.info("mesh shrink complete in %.3fs: data axis %d -> %d, "
                "resuming from step %d", dt, old_data,
                len(loss.surviving), model._iteration)


def _warm_survivor_mesh(wrapper, model, session, new_mesh: DeviceMesh,
                        k: int) -> None:
    """AOT-warm the train step for the shrunk layout (module step 6):
    rebuild a zero batch from the checkpoint-recorded batch signature,
    pad + stage it exactly like the dispatch loop will (wrapper._pad +
    _mesh_placement), and compile WITHOUT executing. Gated on JAX's
    persistent cache being placed — without it the first post-shrink
    dispatch compiles under the watchdog's warmup leniency exactly as
    before. Never raises: recovery must not die warming."""
    if jax_compile_cache_status()[0] is None:
        return
    sig = getattr(session, "_last_batch_sig", None)
    if not sig:
        return
    try:
        from deeplearning4j_tpu.data.dataset import DataSet, stage_item
        from deeplearning4j_tpu.train.stepping import stack_megabatch
        f, lab = sig["features"], sig["labels"]
        ds = DataSet(np.zeros(tuple(f[0]), np.dtype(f[1])),
                     np.zeros(tuple(lab[0]), np.dtype(lab[1])))
        ds = wrapper._pad(ds)
        item = stage_item(stack_megabatch([ds] * k) if k > 1 else ds,
                          wrapper._mesh_placement)
        with new_mesh:
            model._warm_dispatch(item.features, item.labels,
                                 fmask=getattr(item, "features_mask", None),
                                 lmask=getattr(item, "labels_mask", None),
                                 steps=k)
        logger.info("elastic shrink: survivor-mesh train step warmed "
                    "through the compile cache (k=%d)", k)
    except Exception as e:
        warnings.warn(f"elastic shrink: survivor-mesh warmup skipped "
                      f"({type(e).__name__}: {e})", stacklevel=2)


def _revalidate_shrink(model, session, new_mesh: DeviceMesh):
    """Static E1xx/W10x pass over the shrunk mesh. Non-E101 errors
    (structural: bad axes, HBM budget) abort the shrink; E101 (batch
    not divisible by the new data axis) only warns — the wrapper pads
    tail shards with zero-weight examples, so training stays correct."""
    batch = None
    it = session.iterator
    if it is not None:
        try:
            b = it.batch()
            if isinstance(b, int) and b > 0:
                batch = b
        except Exception:
            batch = None
    try:
        # .spec() declares the physical device count, so E102 also checks
        # axes-product-vs-survivors consistency
        report = model.validate(batch_size=batch, mesh=new_mesh.spec())
    except Exception as e:          # analysis must never block recovery
        logger.warning("elastic shrink: static revalidation failed (%s) — "
                       "continuing without it", e)
        return
    errors = report.errors()
    hard = [d for d in errors if d.code != "DL4J-E101"]
    if hard:
        raise ElasticShrinkError(
            "shrunk mesh fails static validation: "
            + "; ".join(f"{d.code}: {d.message}" for d in hard))
    for d in errors:                # E101: padding handles raggedness
        warnings.warn(f"elastic shrink: {d.code}: {d.message} "
                      "(tail shards will be zero-weight padded)",
                      stacklevel=2)


def _rescale_lr(model, session, cfg: ElasticConfig, old_n: int, new_n: int):
    if cfg.lr_policy == "none" or old_n == new_n:
        return
    frac = new_n / float(old_n)
    if cfg.lr_policy == "linear":
        factor = frac
    elif cfg.lr_policy == "sqrt":
        factor = frac ** 0.5
    else:
        raise ValueError(f"unknown lr_policy {cfg.lr_policy!r} "
                         "(expected none|linear|sqrt)")
    upd = model.conf.base.updater
    upd._lr_scale = getattr(upd, "_lr_scale", 1.0) * factor
    session._bust_step_caches()     # the scale is baked in at trace time
    logger.info("elastic shrink: lr scale x%.3g (policy=%s, %d -> %d "
                "replicas)", factor, cfg.lr_policy, old_n, new_n)
