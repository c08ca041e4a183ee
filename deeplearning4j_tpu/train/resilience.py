"""Fault-tolerant training: auto-checkpoint/resume, preemption handling,
NaN/Inf recovery policies.

Periodic checkpointing with automatic recovery is a founding design
point of production training systems (TensorFlow, Abadi et al., 2016),
and long data-parallel accelerator jobs make preemption the COMMON
case, not the exception — yet a training loop without this layer loses
a multi-hour ``fit()`` to a single SIGTERM, NaN step, or flaky disk.
This module is the missing layer between the megastep engine and
anything production-shaped:

- :class:`CheckpointConfig` + :class:`CheckpointManager` — periodic
  **atomic** checkpoints of the FULL training state: params, updater
  state, layer states, the per-step RNG counter (the step clock ``t``
  that ``fold_in(seed, t)`` derives dropout keys from), epoch/step,
  the iterator's normalizer, and the data-iterator cursor. Writes go
  to a temp dir finalized by ONE ``os.replace`` (a crash mid-write can
  never leave a half-checkpoint under the real name); every file is
  SHA-256'd into the manifest; ``keep_last=N`` rotation; resume picks
  the newest checkpoint that passes checksum validation and
  QUARANTINES corrupt ones instead of trusting them.
- Preemption handling — SIGTERM/SIGINT (plus pluggable
  :class:`PreemptionSignal` implementations for tests and cluster
  schedulers) finish the in-flight (mega)step, write a checkpoint whose
  manifest is marked ``"preempted"``, and return cleanly from ``fit``.
- :class:`NanPolicy` — upgrades the NAN_PANIC raise-only debug knob to
  actual recovery: ``RAISE``, ``SKIP_STEP`` (drop the poisoned update,
  keep going), ``BACKOFF_LR`` (drop the update AND halve the learning
  rate, recovering it after a cooldown of clean steps), ``ROLLBACK``
  (restore the last good checkpoint). Tune via :class:`NanRecovery`.
- Transient-I/O retry with exponential backoff around checkpoint
  writes/reads (and, via ``data.dataset.RetryingDataSetIterator``,
  around data pulls).

Everything is observable in the profiler registry:
``dl4j_nonfinite_steps_total``, ``dl4j_rollbacks_total``,
``dl4j_checkpoint_seconds``, ``dl4j_resume_total``,
``dl4j_preemptions_total``, ``dl4j_checkpoint_quarantined_total``,
``dl4j_lr_backoffs_total`` (plus ``dl4j_data_retries_total`` from the
data layer). Every recovery path is pinned by a deterministic injected
fault (``deeplearning4j_tpu.faults``) in ``tests/test_resilience.py``.

Usage::

    net.fit(iterator, epochs=3,
            checkpoint=CheckpointConfig("/ckpts", every_steps=200,
                                        resume=True),
            nan_policy=NanPolicy.SKIP_STEP)

Resume is bit-exact: ``fit(N)`` == ``fit(k)`` + preemption + resume for
params, updater state, and the step RNG (pinned for MultiLayerNetwork,
ComputationGraph, and ``steps_per_dispatch>1`` megastep runs).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import queue as _queue
import shutil
import signal as _signal
import sys
import threading
import time
import warnings
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from enum import Enum
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from deeplearning4j_tpu import profiler as _prof
from deeplearning4j_tpu.data.dataset import (DataSetIterator,
                                             RetryingDataSetIterator)
from deeplearning4j_tpu.utils.concurrent import ErrorLatch
from deeplearning4j_tpu.utils.environment import (NumericsPanicError,
                                                  jax_compile_cache_status)

logger = logging.getLogger("deeplearning4j_tpu")

_REG = _prof.get_registry()
NONFINITE_STEPS = _REG.counter(
    "dl4j_nonfinite_steps_total",
    "Update steps whose loss came back NaN/Inf (one per poisoned step, "
    "whatever the recovery policy did about it)")
ROLLBACKS = _REG.counter(
    "dl4j_rollbacks_total",
    "Checkpoint rollbacks performed by NanPolicy.ROLLBACK")
CKPT_SECONDS = _REG.histogram(
    "dl4j_checkpoint_seconds",
    "Wall time to write one atomic training checkpoint")
RESUMES = _REG.counter(
    "dl4j_resume_total",
    "Successful auto-resumes from a validated checkpoint")
PREEMPTIONS = _REG.counter(
    "dl4j_preemptions_total",
    "Preemption requests honored (signal or synthetic) — each wrote a "
    "'preempted' checkpoint when a CheckpointConfig was active")
QUARANTINED = _REG.counter(
    "dl4j_checkpoint_quarantined_total",
    "Checkpoints failing checksum/manifest validation at resume, moved "
    "aside instead of loaded")
LR_BACKOFFS = _REG.counter(
    "dl4j_lr_backoffs_total",
    "Learning-rate halvings performed by NanPolicy.BACKOFF_LR")
CKPT_ASYNC_QUEUE = _REG.gauge(
    "dl4j_checkpoint_async_queue_depth",
    "Snapshots queued for the background checkpoint writer (a "
    "persistently full queue means the writer cannot keep up with "
    "every_steps and save() is applying backpressure)")


class CorruptCheckpointError(RuntimeError):
    """A checkpoint failed validation: unreadable/missing manifest, a
    file named by the manifest absent, or a SHA-256 mismatch. Resume
    quarantines the checkpoint and falls back to the previous one."""


class PreemptionRequested(Exception):
    """Internal control flow: a PreemptionSignal fired; the fit loop
    unwinds to its boundary, writes the 'preempted' checkpoint, and
    returns cleanly."""


class AsyncCheckpointError(RuntimeError):
    """A background checkpoint write failed after its I/O retries. The
    error is raised on the TRAINING thread at the next fit step (or at
    fit exit) — a fit that believes it is checkpointing must not
    silently run bare."""


# --------------------------------------------------------------- I/O retry
def retry_io(fn: Callable, retries: int = 3, backoff: float = 0.05,
             exc=(OSError,)):
    """Run ``fn`` retrying transient I/O failures with exponential
    backoff — the storage layer under a checkpoint (NFS, object-store
    FUSE mounts) fails transiently as a matter of course on large
    clusters."""
    attempt = 0
    while True:
        try:
            return fn()
        except exc:
            if attempt >= retries:
                raise
            time.sleep(backoff * (2 ** attempt))
            attempt += 1


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


# ------------------------------------------------------------ NaN policies
class NanPolicy(Enum):
    """What to do when a step's loss comes back non-finite (upgrades the
    raise-only NAN_PANIC debug mode to recovery)."""

    RAISE = "raise"            # fail fast (NumericsPanicError)
    SKIP_STEP = "skip_step"    # drop the poisoned update, keep training
    BACKOFF_LR = "backoff_lr"  # drop the update + halve LR (cooldown recovery)
    ROLLBACK = "rollback"      # restore the last good checkpoint


@dataclass
class NanRecovery:
    """A NanPolicy plus its tuning. ``fit(nan_policy=...)`` accepts
    either a bare :class:`NanPolicy` (defaults below) or this."""

    policy: NanPolicy
    backoff_factor: float = 0.5   # LR multiplier per BACKOFF_LR event
    cooldown_steps: int = 50      # clean steps before LR recovers one notch
    min_scale: float = 2.0 ** -16  # LR-scale floor: below this, raise
    max_rollbacks: int = 3        # consecutive ROLLBACKs before raising


# --------------------------------------------------------------- config
@dataclass
class CheckpointConfig:
    """Where/when/how to checkpoint. ``every_steps=0`` disables periodic
    saves (preemption and ``every_epochs`` still checkpoint).

    ``async_write=True`` moves serialization + fsync off the training
    thread: ``save()`` takes a device-side snapshot (one cheap on-device
    copy per buffer, safe against the compiled step's donation) and
    enqueues it for a background writer; the fit step continues while
    the writer serializes. The queue is bounded (``async_queue``) so a
    slow disk applies backpressure instead of accumulating snapshots in
    device memory, writer failures surface as
    :class:`AsyncCheckpointError` on the next fit step, and resume/
    rollback reads flush the queue first so they always see the newest
    write."""

    dir: str
    every_steps: int = 0
    every_epochs: int = 0
    resume: bool = False
    keep_last: int = 3
    io_retries: int = 3
    io_backoff: float = 0.05
    async_write: bool = False
    async_queue: int = 2


# ---------------------------------------------------------- preemption
class PreemptionSignal:
    """Pluggable preemption source: ``requested(step)`` is polled after
    every completed (mega)step. Subclass for cluster schedulers that
    announce preemption out-of-band (metadata server, borglet file)."""

    def requested(self, step: int) -> bool:
        return False


class StepPreemption(PreemptionSignal):
    """Synthetic preemption once ``step`` update steps have completed —
    the deterministic stand-in for SIGTERM that the fault harness and
    the resume-equivalence tests use."""

    def __init__(self, step: int):
        self.step = int(step)

    def requested(self, step: int) -> bool:
        return step >= self.step


class SignalPreemption(PreemptionSignal):
    """SIGTERM/SIGINT -> preemption flag. Installed for the duration of
    a resilient ``fit()`` (main thread only — signal handlers cannot be
    installed elsewhere); previous handlers are restored on close.

    ``on_request`` is an optional zero-arg callback invoked from the
    handler so a consumer polling from ANOTHER thread (the model
    server's serve loop reacting to SIGTERM with a drain) wakes
    immediately instead of at its next poll. It must be cheap and
    non-blocking — setting a ``threading.Event`` is the intended use;
    exceptions are swallowed (a failing callback must not break the
    signal handler)."""

    def __init__(self, signals=(_signal.SIGTERM, _signal.SIGINT),
                 on_request=None):
        self.signals = signals
        self.on_request = on_request
        self._event = threading.Event()
        self._prev: Dict[int, Any] = {}

    def install(self) -> bool:
        if threading.current_thread() is not threading.main_thread():
            return False
        for s in self.signals:
            self._prev[s] = _signal.signal(s, self._handler)
        return True

    def uninstall(self):
        for s, prev in self._prev.items():
            try:
                _signal.signal(s, prev)
            except (ValueError, OSError):
                pass
        self._prev = {}

    def _handler(self, signum, frame):
        self._event.set()
        if self.on_request is not None:
            try:
                self.on_request()
            except Exception:
                pass

    def requested(self, step: int) -> bool:
        return self._event.is_set()


# ------------------------------------------------------------- manager
class CheckpointManager:
    """Atomic, checksummed, rotated training checkpoints.

    On-disk layout (one directory per checkpoint, finalized by a single
    ``os.replace`` so readers never observe a partial write)::

        <dir>/ckpt_0000000042/model.zip        full model (params, layer
                                               states, updater state,
                                               step/epoch counters)
        <dir>/ckpt_0000000042/extra.json       iterator cursor + caller
                                               extra state (early stopping)
        <dir>/ckpt_0000000042/normalizer.npz   iterator preprocessor (opt.)
        <dir>/ckpt_0000000042/manifest.json    step/epoch/status + per-file
                                               SHA-256
        <dir>/quarantine_ckpt_.../             failed validation at resume

    ``status`` in the manifest is ``"complete"`` or ``"preempted"``.
    """

    PREFIX = "ckpt_"

    def __init__(self, config: CheckpointConfig, fault_plan=None):
        self.config = config
        self.faults = fault_plan
        self._writer: Optional[_AsyncWriter] = None
        os.makedirs(config.dir, exist_ok=True)

    # ------------------------------------------------------------- naming
    def _name(self, step: int) -> str:
        return f"{self.PREFIX}{step:010d}"

    def checkpoints(self):
        """[(step, path)] ascending by step; quarantined/temp dirs are
        excluded."""
        out = []
        for entry in os.listdir(self.config.dir):
            if not entry.startswith(self.PREFIX):
                continue
            suffix = entry[len(self.PREFIX):]
            if not suffix.isdigit():
                continue
            out.append((int(suffix), os.path.join(self.config.dir, entry)))
        return sorted(out)

    # --------------------------------------------------------------- save
    def save(self, model, status: str = "complete", cursor=None,
             normalizer=None, extra: Optional[dict] = None) -> str:
        """Write one checkpoint. With ``async_write`` the state is
        snapshotted on device and the serialization/fsync happens on the
        background writer; the returned path is where the checkpoint
        WILL land (call :meth:`flush` to wait for it)."""
        if self.config.async_write:
            self.raise_async_errors()
            snap = _StateSnapshot(model)
            if self._writer is None:
                self._writer = _AsyncWriter(self, self.config.async_queue)
            self._writer.submit((snap, status, cursor, normalizer, extra))
            return os.path.join(self.config.dir, self._name(snap._iteration))
        return self._write(model, status, cursor, normalizer, extra)

    def _write(self, model, status: str = "complete", cursor=None,
               normalizer=None, extra: Optional[dict] = None) -> str:
        cfg = self.config
        step, epoch = int(model._iteration), int(model._epoch)
        t0 = time.perf_counter()
        name = self._name(step)
        final = os.path.join(cfg.dir, name)
        tmp = os.path.join(cfg.dir, f".tmp_{name}_{os.getpid()}")
        if os.path.isdir(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)

        def write_model():
            if self.faults is not None \
                    and self.faults.checkpoint_write_error(step):
                raise OSError(
                    f"injected checkpoint write failure at step {step}")
            model.save(os.path.join(tmp, "model.zip"), save_updater=True)
        retry_io(write_model, cfg.io_retries, cfg.io_backoff)
        if normalizer is not None:
            try:
                from deeplearning4j_tpu.train.serializer import ModelSerializer
                ModelSerializer.writeNormalizer(
                    normalizer, os.path.join(tmp, "normalizer.npz"))
            except Exception as e:   # best effort: a normalizer that can't
                warnings.warn(       # serialize must not kill the checkpoint
                    f"checkpoint: could not serialize normalizer: {e}",
                    stacklevel=2)
        with open(os.path.join(tmp, "extra.json"), "w") as f:
            json.dump({"cursor": cursor, "extra": extra or {}}, f)
        files = {fn: _sha256_file(os.path.join(tmp, fn))
                 for fn in sorted(os.listdir(tmp))}
        manifest = {"format": 1, "step": step, "epoch": epoch,
                    "status": status, "files": files,
                    "unix_time": time.time()}
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.isdir(final):     # re-save of the same step (preemption
            shutil.rmtree(final)     # right after a periodic save)
        retry_io(lambda: os.replace(tmp, final), cfg.io_retries,
                 cfg.io_backoff)
        if self.faults is not None:
            self.faults.corrupt_checkpoint(step, final)
        CKPT_SECONDS.observe(time.perf_counter() - t0)
        self._rotate()
        return final

    def _rotate(self):
        cps = self.checkpoints()
        while len(cps) > max(1, self.config.keep_last):
            _, path = cps.pop(0)
            retry_io(lambda p=path: shutil.rmtree(p, ignore_errors=False),
                     self.config.io_retries, self.config.io_backoff)

    # ----------------------------------------------------- async lifecycle
    def flush(self):
        """Block until every queued async write has been attempted (a
        failed attempt is reported by :meth:`raise_async_errors`, not
        here). No-op for sync managers."""
        if self._writer is not None:
            self._writer.flush()
            CKPT_ASYNC_QUEUE.set(0)

    def raise_async_errors(self):
        """Re-raise the FIRST background-write failure (once) as
        AsyncCheckpointError on the calling thread."""
        w = self._writer
        err = w.take_error() if w is not None else None
        if err is not None:
            raise AsyncCheckpointError(
                f"background checkpoint write failed: {err}") from err

    def close_writer(self):
        """Flush and stop the background writer (idempotent)."""
        if self._writer is not None:
            self._writer.close()
            self._writer = None
            CKPT_ASYNC_QUEUE.set(0)

    # ----------------------------------------------------------- validate
    def validate(self, path: str) -> dict:
        """Manifest + per-file SHA-256 validation. Returns the manifest;
        raises CorruptCheckpointError naming the failing entry."""
        man_path = os.path.join(path, "manifest.json")
        try:
            with open(man_path) as f:
                manifest = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            raise CorruptCheckpointError(
                f"{path}: unreadable manifest ({e})") from e
        files = manifest.get("files") or {}
        if "model.zip" not in files:
            raise CorruptCheckpointError(f"{path}: manifest lists no model.zip")
        for fn, digest in files.items():
            fp = os.path.join(path, fn)
            if not os.path.exists(fp):
                raise CorruptCheckpointError(f"{path}: missing file {fn}")
            actual = _sha256_file(fp)
            if actual != digest:
                raise CorruptCheckpointError(
                    f"{path}: checksum mismatch for {fn} (manifest "
                    f"{digest[:12]}..., actual {actual[:12]}...)")
        return manifest

    def latest_valid(self):
        """Newest checkpoint passing validation as (path, manifest), or
        None. Corrupt checkpoints are QUARANTINED (renamed aside) so a
        bad newest write can never shadow a good older one forever."""
        self.flush()    # async writer: never resume past a queued write
        for step, path in reversed(self.checkpoints()):
            try:
                return path, self.validate(path)
            except CorruptCheckpointError as e:
                self._quarantine(path, str(e))
        return None

    def _quarantine(self, path: str, reason: str):
        dst = os.path.join(os.path.dirname(path),
                           "quarantine_" + os.path.basename(path))
        if os.path.isdir(dst):
            shutil.rmtree(dst)
        os.replace(path, dst)
        QUARANTINED.inc()
        warnings.warn(f"quarantined corrupt checkpoint {path}: {reason}",
                      stacklevel=3)

    # ------------------------------------------------------------ restore
    def valid_at_step(self, step: int):
        """The checkpoint for exactly ``step`` as (path, manifest), or
        None when absent/corrupt (a corrupt one is quarantined). The
        elastic resume barrier restores THE AGREED step — the newest
        local checkpoint may be ahead of what every participant can
        reach."""
        self.flush()
        for s, path in self.checkpoints():
            if s == int(step):
                try:
                    return path, self.validate(path)
                except CorruptCheckpointError as e:
                    self._quarantine(path, str(e))
                return None
        return None

    def restore(self, model, normalizer=None, count_resume: bool = True,
                step: Optional[int] = None):
        """Load the newest valid checkpoint — or, with ``step=``, the
        checkpoint for exactly that step — INTO ``model`` (in place:
        params, layer states, updater state, step/epoch, device clock)
        and return ``{"path", "manifest", "cursor", "extra"}`` — or None
        when no valid checkpoint exists."""
        found = self.latest_valid() if step is None \
            else self.valid_at_step(step)
        if found is None:
            return None
        path, manifest = found
        cfg = self.config
        loaded = retry_io(
            lambda: type(model).load(os.path.join(path, "model.zip"),
                                     load_updater=True),
            cfg.io_retries, cfg.io_backoff)
        model._params = loaded._params
        model._states = loaded._states
        model._opt_state = loaded._opt_state
        model._iteration = loaded._iteration
        model._epoch = loaded._epoch
        model._t_dev = None          # clock rebuilds from _iteration
        extra_payload: dict = {}
        extra_path = os.path.join(path, "extra.json")
        if os.path.exists(extra_path):
            with open(extra_path) as f:
                extra_payload = json.load(f)
        norm_path = os.path.join(path, "normalizer.npz")
        if normalizer is not None and os.path.exists(norm_path):
            try:
                from deeplearning4j_tpu.train.serializer import ModelSerializer
                restored = retry_io(
                    lambda: ModelSerializer.restoreNormalizer(norm_path),
                    cfg.io_retries, cfg.io_backoff)
                for k, v in restored.__dict__.items():
                    setattr(normalizer, k, v)
            except Exception as e:
                warnings.warn(f"resume: could not restore normalizer: {e}",
                              stacklevel=2)
        if count_resume:
            RESUMES.inc()
        return {"path": path, "manifest": manifest,
                "cursor": extra_payload.get("cursor"),
                "extra": extra_payload.get("extra") or {}}


# ------------------------------------------------------------- session
@jax.jit
def _copy_leaves(leaves):
    # + 0 under ONE jit: a real on-device copy per buffer (immune to the
    # compiled step's donation), dispatched as a single program
    return [a + 0 for a in leaves]


def _device_copy(tree):
    """On-device snapshot of a pytree's jax.Array leaves in ONE dispatch
    (a per-leaf ``a + 0`` costs a host dispatch per buffer — ~10ms of
    training-thread time per snapshot on a small MLP, which would eat
    the async writer's entire win)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    idx = [i for i, a in enumerate(leaves) if isinstance(a, jax.Array)]
    if idx:
        copies = _copy_leaves([leaves[i] for i in idx])
        for i, c in zip(idx, copies):
            leaves[i] = c
    return jax.tree_util.tree_unflatten(treedef, leaves)


class _StateSnapshot:
    """Device-side snapshot of one model's full training state, duck-
    typed for the model classes' ``save()`` (``ModelSerializer.
    writeModel`` / ``ComputationGraph.save`` only touch ``conf``,
    ``_params``/``_states``/``_opt_state``, and the counters). The
    on-device ``a + 0`` copies are enqueued asynchronously and — unlike
    aliases — survive the compiled step's buffer donation; the writer
    thread's ``np.asarray`` pulls block there, off the critical path."""

    def __init__(self, model):
        self._model_cls = type(model)
        self._serial_type = type(model).__name__   # archive meta["type"]
        self.conf = model.conf
        self._params = _device_copy(model._params)
        self._states = _device_copy(model._states)
        self._opt_state = _device_copy(model._opt_state)
        self._iteration = int(model._iteration)
        self._epoch = int(model._epoch)

    def save(self, path: str, save_updater: bool = True):
        self._model_cls.save(self, path, save_updater)


class _AsyncWriter:
    """Bounded-queue background checkpoint writer. ``submit`` blocks
    when the queue is full (backpressure beats unbounded device-memory
    snapshots); the first write failure is parked in ``error`` for
    :meth:`CheckpointManager.raise_async_errors`."""

    _STOP = object()

    def __init__(self, manager: "CheckpointManager", depth: int):
        self.manager = manager
        self.queue: "_queue.Queue" = _queue.Queue(maxsize=max(1, int(depth)))
        self._pending = ErrorLatch()   # writer thread vs fit thread
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="dl4j-ckpt-writer")
        self._thread.start()

    def take_error(self) -> Optional[BaseException]:
        """Pop the first unreported write failure (fit-thread side)."""
        return self._pending.take()

    def submit(self, job):
        self.queue.put(job)
        CKPT_ASYNC_QUEUE.set(self.queue.qsize())

    def _loop(self):
        while True:
            job = self.queue.get()
            try:
                if job is self._STOP:
                    return
                snap, status, cursor, normalizer, extra = job
                self.manager._write(snap, status=status, cursor=cursor,
                                    normalizer=normalizer, extra=extra)
            except BaseException as e:
                self._pending.record(e)   # first failure wins
            finally:
                self.queue.task_done()
                CKPT_ASYNC_QUEUE.set(self.queue.qsize())

    def flush(self):
        self.queue.join()

    def close(self):
        if self._thread.is_alive():
            self.queue.put(self._STOP)
            self._thread.join(timeout=30.0)


def _find_preprocessor(it):
    """Walk a wrapper chain (retry/fault/async wrappers all expose
    ``.base``) for the innermost iterator's preprocessor."""
    seen = set()
    while it is not None and id(it) not in seen:
        seen.add(id(it))
        pre = getattr(it, "_pre", None)
        if pre is not None:
            return pre
        it = getattr(it, "base", None)
    return None


class TrainingSession:
    """Per-``fit()`` resilience driver, attached as ``model._resilience``
    for the duration of the fit. The fit loops call four hooks:

    - ``before_step()`` / ``before_dispatch()`` — device-copy snapshot
      of (params, states, opt state) when the NaN policy needs one.
    - ``after_step()`` / ``after_dispatch(losses, k)`` — non-finite
      detection + recovery, periodic checkpoint, preemption poll.
    - ``on_epoch_end()`` — epoch-granularity checkpoints.
    - ``on_preempt()`` — the 'preempted' checkpoint.

    Megastep granularity: with ``steps_per_dispatch=K`` recovery acts on
    the whole K-step dispatch (a poisoned sub-step skips/rolls back all
    K — the dispatch is one atomic compiled program).
    """

    def __init__(self, model, checkpoint: Optional[CheckpointConfig] = None,
                 nan_policy=None, faults=None, iterator=None):
        self.model = model
        self.config = checkpoint
        self.manager = (CheckpointManager(checkpoint, fault_plan=faults)
                        if checkpoint is not None else None)
        if isinstance(nan_policy, NanPolicy):
            nan_policy = NanRecovery(nan_policy)
        self.recovery: Optional[NanRecovery] = nan_policy
        self.faults = faults
        self.iterator = iterator
        self.normalizer = _find_preprocessor(iterator)
        self._signals = []
        self._sig_handler: Optional[SignalPreemption] = None
        if faults is not None:
            sig = faults.preemption_signal()
            if sig is not None:
                self._signals.append(sig)
        self._cursors = deque()
        self._cursor_at_step = None
        self._last_batch_sig = None
        self._snapshot = None
        self._skip_reset = False
        self._next_save = None
        self._good_steps = 0
        self._rollbacks_in_row = 0
        self.resumed = False
        self.preempted = False

    # ----------------------------------------------------------- lifecycle
    def start(self):
        if self.manager is not None:
            self._sig_handler = SignalPreemption()
            if self._sig_handler.install():
                self._signals.append(self._sig_handler)
            else:
                self._sig_handler = None

    def close(self, raise_errors: bool = True):
        """End-of-fit teardown: restore signal handlers, detach from the
        model, and drain the async checkpoint writer. ``raise_errors=
        False`` (used while another exception is already unwinding)
        demotes a writer failure to a warning instead of masking the
        primary error."""
        if self._sig_handler is not None:
            self._sig_handler.uninstall()
            self._sig_handler = None
        if getattr(self.model, "_resilience", None) is self:
            self.model._resilience = None
        if self.manager is not None:
            try:
                self.manager.flush()
                self.manager.raise_async_errors()
            except BaseException as e:
                if raise_errors:
                    raise
                warnings.warn(f"async checkpoint writer failed during "
                              f"teardown: {e}", stacklevel=2)
            finally:
                self.manager.close_writer()

    def resume(self) -> bool:
        """Restore the newest valid checkpoint (when ``resume=True``)
        and seek the data iterator to its saved cursor. Returns True
        when a checkpoint was restored."""
        if self.manager is None or not self.config.resume:
            self._arm_next_save()
            return False
        info = self.manager.restore(self.model, normalizer=self.normalizer)
        if info is None:
            self._arm_next_save()
            return False
        cursor = info.get("cursor")
        if cursor is not None and self.iterator is not None:
            try:
                self.iterator.seek(cursor)
                self._skip_reset = True
            except NotImplementedError:
                warnings.warn(
                    "resume: iterator does not support seek(); replaying "
                    "the interrupted epoch from its start", stacklevel=2)
        # a restore is an out-of-band state mutation the provenance
        # sanitizer's replay window cannot reproduce
        from deeplearning4j_tpu.profiler import sanitizer as _san
        _san.invalidate(self.model)
        res_state = (info.get("extra") or {}).get("resilience") or {}
        lr_scale = res_state.get("lr_scale", 1.0)
        upd = self.model.conf.base.updater
        if lr_scale != getattr(upd, "_lr_scale", 1.0):
            upd._lr_scale = lr_scale
            self._bust_step_caches()
        self._good_steps = int(res_state.get("good_steps", 0))
        lss = res_state.get("loss_scale_state")
        if lss is not None and hasattr(self.model, "_dynamic_scaling") \
                and self.model._dynamic_scaling():
            # the dynamic loss-scale automaton resumes exactly where the
            # checkpoint left it (NOT at the policy's init value)
            self.model._scale_state = jax.numpy.asarray(
                lss, jax.numpy.float32)
        self.resumed = True
        self.restored = info
        logger.info("resumed from %s (step %d, status=%s)", info["path"],
                    self.model._iteration, info["manifest"].get("status"))
        self._arm_next_save()
        return True

    def warm_after_resume(self, steps_per_dispatch: int = 1) -> bool:
        """Kill the resume cold start: where JAX's persistent compile
        cache is placed (utils.environment), AOT-warm the train step for
        the batch signature the restored checkpoint recorded — a
        previously-seen (model, shapes, policy) tuple is read from disk
        instead of paying the first-dispatch XLA compile. Fit loops call
        this right after ``begin_session`` (they know the dispatch K).
        Best-effort and gated OFF when no cache dir is configured, so
        un-cached fits behave exactly as before."""
        if not self.resumed:
            return False
        from deeplearning4j_tpu.nn import compilecache as _cc
        if jax_compile_cache_status()[0] is None:
            return False
        sig = ((self.restored.get("extra") or {}).get("resilience")
               or {}).get("batch_signature")
        return _cc.warm_from_batch_signature(
            self.model, sig, steps_per_dispatch=steps_per_dispatch)

    def _arm_next_save(self):
        if self.manager is not None and self.config.every_steps:
            self._next_save = self.model._iteration + self.config.every_steps

    def consume_skip_reset(self) -> bool:
        """True exactly once after a cursor seek: the first epoch's
        ``reset()`` must not wipe the restored position."""
        if self._skip_reset:
            self._skip_reset = False
            return True
        return False

    # ------------------------------------------------------------- batches
    def wrap_batches(self, stream):
        """Record the iterator cursor as each batch is pulled (pull
        order == apply order, so cursor j is the exact resume point
        after update step j lands), and run non-iterator fault
        injection for array/DataSet-fed fits."""
        it = self.iterator
        plan = self.faults if it is None else None  # iterator path injects
        for ds in stream:                           # inside the wrapper
            if plan is not None and plan._on_pull():
                from deeplearning4j_tpu.faults import _poison
                ds = _poison(ds)
            self._cursors.append(None if it is None else it.cursor())
            if self.manager is not None:
                # recorded into the checkpoint manifest so a resumed
                # process can AOT-warm the train step for this signature
                # (nn.compilecache) before its first dispatch
                from deeplearning4j_tpu.nn.compilecache import describe_batch
                self._last_batch_sig = describe_batch(ds)
            yield ds

    # --------------------------------------------------------------- hooks
    def before_step(self):
        if self.faults is not None:
            # planned layer-params poison (provenance-sanitizer pin):
            # lands BEFORE any recovery snapshot and before the
            # sanitizer's own pre-step snapshot, so both observe it
            self.faults.poison_layer_params(self.model,
                                            self.model._iteration + 1)
        rec = self.recovery
        if rec is not None and rec.policy in (NanPolicy.SKIP_STEP,
                                              NanPolicy.BACKOFF_LR):
            m = self.model
            self._snapshot = (_device_copy(m._params),
                              _device_copy(m._states),
                              _device_copy(m._opt_state))

    before_dispatch = before_step

    def after_step(self):
        self._after(1, self.model._score)

    def after_dispatch(self, losses, steps: int, pulls: int = None):
        """``steps`` update steps landed in one dispatch. ``pulls`` is
        how many BATCH PULLS they consumed — equal to ``steps`` for
        megasteps (K batches -> K steps, the default) but 1 for a TBPTT
        batch (1 batch -> ceil(T/L) segment steps), so the cursor queue
        stays aligned with the iterator."""
        self._after(steps, losses, pulls)

    def _after(self, k: int, losses, pulls: int = None):
        for _ in range(min(k if pulls is None else pulls,
                           len(self._cursors))):
            self._cursor_at_step = self._cursors.popleft()
        if self.manager is not None:
            # a background write that failed must surface HERE, on the
            # training thread, not rot silently in the writer
            self.manager.raise_async_errors()
        if self.recovery is not None:
            vals = np.asarray(jax.device_get(losses))
            bad = int(vals.size - np.count_nonzero(np.isfinite(vals)))
            if bad:
                self._handle_nonfinite(k, bad)
            else:
                self._snapshot = None
                self._rollbacks_in_row = 0
                self._recover_lr(k)
        else:
            self._snapshot = None
        m = self.model
        if self._next_save is not None and m._iteration >= self._next_save:
            self.checkpoint()
        if any(s.requested(m._iteration) for s in self._signals):
            raise PreemptionRequested(m._iteration)

    def on_epoch_end(self):
        # an epoch-boundary checkpoint must resume at the START of the
        # next epoch: the last step's cursor points at the exhausted end
        # of the finished epoch, and seeking there on resume would make
        # the first resumed epoch iterate zero batches (silently losing
        # one epoch of training)
        self._cursor_at_step = None
        self._cursors.clear()
        if (self.manager is not None and self.config.every_epochs
                and self.model._epoch % self.config.every_epochs == 0):
            self.checkpoint()

    def on_preempt(self):
        """A PreemptionSignal fired: record it and write the 'preempted'
        checkpoint — the in-flight (mega)step already completed because
        signals are only polled at dispatch boundaries."""
        self.preempted = True
        self.model._preempted = True
        PREEMPTIONS.inc()
        if self.manager is not None:
            self.checkpoint(status="preempted")

    # --------------------------------------------------------- checkpoints
    def checkpoint(self, status: str = "complete"):
        if self.manager is None:
            return None
        # the BACKOFF_LR recovery state is training state too: a resume
        # that silently restored full LR mid-backoff would re-trip the
        # very instability the backoff was suppressing. Likewise the
        # dynamic loss-scale automaton (nn.precision): resuming at the
        # policy's init scale mid-backoff would replay the overflows.
        upd = self.model.conf.base.updater
        res_extra = {
            "lr_scale": float(getattr(upd, "_lr_scale", 1.0)),
            "good_steps": int(self._good_steps),
            "batch_signature": self._last_batch_sig}
        scale_state = getattr(self.model, "_scale_state", None)
        if scale_state is not None:
            res_extra["loss_scale_state"] = [
                float(v) for v in np.asarray(jax.device_get(scale_state))]
        extra = {"resilience": res_extra}
        path = self.manager.save(
            self.model, status=status, cursor=self._cursor_at_step,
            normalizer=self.normalizer, extra=extra)
        if self.config.every_steps:
            self._next_save = self.model._iteration + self.config.every_steps
        return path

    # ---------------------------------------------------------- nonfinite
    def _restore_snapshot(self):
        if self._snapshot is None:
            return
        m = self.model
        m._params, m._states, m._opt_state = self._snapshot
        self._snapshot = None

    def _bust_step_caches(self):
        """An LR-scale change is baked into the compiled step at trace
        time — clear the per-model program caches so the next dispatch
        recompiles with the new scale."""
        m = self.model
        for attr in ("_train_step_cache", "_megastep_cache",
                     "_tbptt_step_cache"):
            cache = getattr(m, attr, None)
            if cache is not None:
                cache.clear()

    def _recover_lr(self, k: int):
        rec = self.recovery
        if rec.policy is not NanPolicy.BACKOFF_LR:
            return
        upd = self.model.conf.base.updater
        scale = getattr(upd, "_lr_scale", 1.0)
        if scale >= 1.0:
            return
        self._good_steps += k
        if self._good_steps >= rec.cooldown_steps:
            upd._lr_scale = min(scale / rec.backoff_factor, 1.0)
            self._good_steps = 0
            self._bust_step_caches()
            logger.info("BACKOFF_LR cooldown elapsed: lr scale %.2g -> %.2g",
                        scale, upd._lr_scale)

    def _handle_nonfinite(self, k: int, bad: int):
        NONFINITE_STEPS.inc(bad)
        rec = self.recovery
        m = self.model
        where = f"iteration {m._iteration}" if k == 1 else \
            f"iterations {m._iteration - k + 1}..{m._iteration} " \
            f"({bad} non-finite)"
        if rec.policy is NanPolicy.RAISE:
            raise NumericsPanicError(
                f"non-finite loss at {where} (NanPolicy.RAISE)")
        if rec.policy is NanPolicy.SKIP_STEP:
            self._restore_snapshot()
            logger.warning("non-finite loss at %s: update skipped "
                           "(NanPolicy.SKIP_STEP)", where)
            return
        if rec.policy is NanPolicy.BACKOFF_LR:
            self._restore_snapshot()
            upd = m.conf.base.updater
            scale = getattr(upd, "_lr_scale", 1.0) * rec.backoff_factor
            if scale < rec.min_scale:
                raise NumericsPanicError(
                    f"non-finite loss at {where}: BACKOFF_LR reached the "
                    f"lr-scale floor ({rec.min_scale:g}) — training cannot "
                    "make progress")
            upd._lr_scale = scale
            LR_BACKOFFS.inc()
            self._good_steps = 0
            self._bust_step_caches()
            logger.warning("non-finite loss at %s: update skipped, lr scale "
                           "-> %.2g (NanPolicy.BACKOFF_LR)", where, scale)
            return
        # ROLLBACK
        if self.manager is None:
            raise NumericsPanicError(
                f"non-finite loss at {where}: NanPolicy.ROLLBACK requires a "
                "CheckpointConfig (no checkpoint to restore)")
        self._rollbacks_in_row += 1
        if self._rollbacks_in_row > rec.max_rollbacks:
            raise NumericsPanicError(
                f"non-finite loss at {where}: {rec.max_rollbacks} "
                "consecutive rollbacks without a clean step — giving up")
        info = self.manager.restore(m, normalizer=self.normalizer,
                                    count_resume=False)
        if info is None:
            raise NumericsPanicError(
                f"non-finite loss at {where}: NanPolicy.ROLLBACK found no "
                "valid checkpoint to restore")
        self._snapshot = None
        ROLLBACKS.inc()
        logger.warning("non-finite loss at %s: rolled back to %s "
                       "(NanPolicy.ROLLBACK)", where, info["path"])


def epoch_target(session: Optional["TrainingSession"], model,
                 epochs: int) -> int:
    """Absolute epoch index a fit should run to: ``epochs`` counts from
    zero for a RESUMED session (the restored checkpoint already banked
    ``model._epoch`` of them) and from the model's current epoch
    otherwise. One definition, shared by :func:`fit_scope` and the
    elastic driver's shrink-retry loop, so the accounting cannot
    drift."""
    if session is not None and session.resumed:
        return epochs
    return model._epoch + epochs


@contextmanager
def fit_scope(session: Optional["TrainingSession"], model, epochs: int):
    """The shared resilience envelope around a fit's epoch loop: yields
    the number of epochs left to run (``epochs`` minus epochs already
    completed by a resumed checkpoint), converts a PreemptionRequested
    unwind into the 'preempted' checkpoint + clean return, and closes
    the session (restoring signal handlers) on every exit path. Used by
    MultiLayerNetwork.fit, ComputationGraph.fit, and ParallelWrapper.fit
    so the recovery protocol cannot drift between the three loops."""
    from deeplearning4j_tpu.profiler import flightrec as _flightrec
    from deeplearning4j_tpu.profiler import tracecontext as _tracectx
    n_epochs = max(epoch_target(session, model, epochs) - model._epoch, 0)
    try:
        # the run's root span: its trace_id doubles as the run_id, and
        # every step/op span recorded inside the fit inherits it via the
        # ambient context — how a training dispatch correlates with the
        # run that issued it
        with _tracectx.run_span("train:run",
                                model=type(model).__name__,
                                epochs=n_epochs):
            yield n_epochs
    except PreemptionRequested:
        if session is None:
            raise
        session.on_preempt()
    except BaseException as e:
        # any other crash unwinding a fit — NonfiniteAttributionError,
        # a dead-device dispatch, an OOM — triggers the flight recorder
        # while the evidence (recent spans, metric state, dispatch
        # signatures) is still in the ring
        _flightrec.get_flight_recorder().dump(
            f"fit:{type(e).__name__}", exc=e)
        raise
    finally:
        if session is not None:
            # surface a failed async checkpoint write at fit exit — unless
            # another exception is already unwinding (don't mask it)
            session.close(raise_errors=sys.exc_info()[1] is None)


def begin_session(model, data, checkpoint=None, nan_policy=None, faults=None):
    """Build and start a TrainingSession for one ``fit()``:

    - wraps a DataSetIterator source with the fault-injection iterator
      (when a FaultPlan is given) and the transient-error retry wrapper,
    - attaches the session as ``model._resilience``,
    - installs the signal handler and performs auto-resume.

    Returns ``(session, data)`` where ``data`` is the possibly-wrapped
    iterator the fit loop should consume instead of the original.
    """
    iterator = data if isinstance(data, DataSetIterator) else None
    wrapped = data
    if iterator is not None:
        from deeplearning4j_tpu.data.dataset import AsyncDataSetIterator
        if checkpoint is not None and isinstance(iterator,
                                                 AsyncDataSetIterator):
            # the async worker pulls ahead of the applied step, so
            # cursor() overstates position by up to prefetch+1 batches —
            # a resumed fit would silently skip those batches
            warnings.warn(
                "checkpointing with an AsyncDataSetIterator source: resume "
                "cursors are APPROXIMATE (the prefetch worker runs ahead of "
                "the applied step). Pass the un-wrapped iterator for exact "
                "resume; fit() overlaps host prep via its own prefetch "
                "paths.", stacklevel=3)
        if faults is not None:
            wrapped = faults.wrap_iterator(wrapped)
        retries = checkpoint.io_retries if checkpoint is not None else 3
        backoff = checkpoint.io_backoff if checkpoint is not None else 0.05
        wrapped = RetryingDataSetIterator(wrapped, max_retries=retries,
                                          backoff=backoff)
    session = TrainingSession(
        model, checkpoint=checkpoint, nan_policy=nan_policy, faults=faults,
        iterator=wrapped if iterator is not None else None)
    model._resilience = session
    session.start()
    try:
        session.resume()
    except BaseException:
        # a failed restore must not leak the installed signal handlers or
        # leave a dead session attached to the model
        session.close()
        raise
    return session, wrapped


# ------------------------------------------------- lifecycle driver state
class DriverStateStore:
    """Atomic, checksummed persistence for the lifecycle driver's state
    machine (ISSUE 20) — the same durability contract as a training
    checkpoint, scaled down to one JSON document: a crash mid-write can
    never leave a half-state under the real name (temp file + one
    ``os.replace``), every load verifies a SHA-256 over the canonical
    payload, and a corrupt file is QUARANTINED (renamed aside) instead
    of trusted, so a resumed driver starts from "no state" rather than
    from garbage. Writes ride :func:`retry_io`.

    The driver persists at every phase transition, so after a SIGKILL
    the successor knows exactly which round/phase/candidate was in
    flight and whether a canary must be aborted before continuing.
    """

    FILENAME = "lifecycle_driver_state.json"

    def __init__(self, state_dir: str, io_retries: int = 3,
                 io_backoff: float = 0.05):
        self.dir = state_dir
        self.path = os.path.join(state_dir, self.FILENAME)
        self._retries = int(io_retries)
        self._backoff = float(io_backoff)
        os.makedirs(state_dir, exist_ok=True)

    @staticmethod
    def _digest(state: dict) -> str:
        canon = json.dumps(state, sort_keys=True,
                           separators=(",", ":")).encode()
        return hashlib.sha256(canon).hexdigest()

    def save(self, state: dict) -> None:
        """Persist ``state`` atomically (JSON-serializable values only)."""
        doc = {"state": state, "sha256": self._digest(state)}
        tmp = self.path + ".tmp"

        def write():
            with open(tmp, "w") as f:
                json.dump(doc, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)

        retry_io(write, retries=self._retries, backoff=self._backoff)

    def load(self) -> Optional[dict]:
        """The last saved state, or None (no state yet, or the file was
        corrupt — in which case it has been quarantined and counted in
        ``dl4j_checkpoint_quarantined_total``)."""
        if not os.path.exists(self.path):
            return None
        try:
            with open(self.path) as f:
                doc = json.load(f)
            state = doc["state"]
            if self._digest(state) != doc["sha256"]:
                raise CorruptCheckpointError(
                    f"driver state {self.path}: checksum mismatch")
            return state
        except (OSError, ValueError, KeyError, TypeError,
                CorruptCheckpointError) as e:
            quarantine = os.path.join(
                self.dir, "quarantine_" + self.FILENAME)
            try:
                os.replace(self.path, quarantine)
            except OSError:
                pass
            QUARANTINED.inc()
            logger.warning(
                "driver state %s failed validation (%s) — quarantined to "
                "%s; the driver resumes stateless", self.path, e, quarantine)
            return None

    def clear(self) -> None:
        try:
            os.remove(self.path)
        except OSError:
            pass
