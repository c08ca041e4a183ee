"""Data-parallel training + parallel inference over the mesh.

Reference parity:
- ``ParallelWrapper`` (SURVEY.md §2.2/§2.3): N replicas fed round-robin,
  periodic averaging / encoded gradient sharing → here: synchronous SPMD —
  batch sharded over the ``data`` axis, params replicated, XLA emits the
  gradient allreduce over ICI. Strictly stronger consistency than the
  reference's async modes at higher throughput (SURVEY.md §2.3 "sync
  allreduce strictly dominates").
- ``ParallelInference`` (SURVEY.md §3.5): request queue + dynamic batching
  across device workers → here: a batcher in front of a data-sharded
  compiled forward.
"""

from __future__ import annotations

import queue
import threading
import warnings

import jax
import numpy as np

from deeplearning4j_tpu import profiler as _prof
from deeplearning4j_tpu.data.dataset import (AsyncDataSetIterator, DataSet,
                                             DataSetIterator)
from deeplearning4j_tpu.parallel.mesh import DeviceMesh


class ParallelWrapper:
    """Sync data-parallel trainer over the mesh (ref: ParallelWrapper).

    Wraps a MultiLayerNetwork; ``fit`` shards each batch over the mesh's
    ``data`` axis and keeps params replicated — the train step is the
    network's own compiled step, so gradients are allreduced by XLA inside
    ONE program (no EncodedGradientsAccumulator, no averaging interval).
    """

    def __init__(self, model, mesh: DeviceMesh = None,
                 prefetch_buffer: int = 2, workers: int = None):
        self.model = model
        self.mesh = mesh or DeviceMesh.data_parallel()
        self.prefetch = prefetch_buffer

    def validate(self, batch_size: int = None, **kw):
        """Static lint of the wrapped model against THIS wrapper's mesh:
        the full configuration analysis plus the E1xx/W10x distribution
        lints (batch divisibility, replicated giants, HBM budget, ...).
        Pass ``batch_size`` for the per-step checks; extra keywords
        forward to ``analysis.analyze`` (``sharding=``, ``hbm_gb=``,
        ``suppress=``, ...)."""
        return self.model.validate(batch_size=batch_size, mesh=self.mesh,
                                   **kw)

    def warmup(self, shapes, *, steps_per_dispatch: int = 1, dtype=None,
               label_dtype=None, policy=None):
        """AOT-warm the wrapped model's programs under THIS wrapper's
        mesh — replicated params, batch-sharded inputs — through the
        PR-13 compile-cache seam (the replication-path warmup the
        elastic shrink path already had). ``shapes`` follows
        ``nn.compilecache.warmup``: ``(features, labels)`` pairs warm
        the train step/megastep, bare feature shapes warm the forward.
        Batch dims are padded up to a multiple of the data-axis width
        exactly like ``fit`` pads real batches, so the warmed program IS
        the dispatched one. Where JAX's persistent cache is placed, a
        fresh process warms from disk."""
        from deeplearning4j_tpu.nn import compilecache as _cc
        model = self.model
        if not model._initialized:
            model.init()
        n = self.mesh.size("data")

        def pad_shape(shape):
            shape = tuple(int(d) for d in shape)
            b = shape[0]
            if b % n:
                b += n - b % n
            return (b,) + shape[1:]

        padded = []
        for spec in shapes:
            if (isinstance(spec, (tuple, list)) and len(spec) == 2
                    and isinstance(spec[0], (tuple, list))):
                padded.append((pad_shape(spec[0]), pad_shape(spec[1])))
            else:
                padded.append(pad_shape(spec))
        k = max(int(steps_per_dispatch), 1)
        if k > 1 and any(not (isinstance(s, (tuple, list)) and len(s) == 2
                              and isinstance(s[0], (tuple, list)))
                         for s in padded):
            raise ValueError(
                "steps_per_dispatch>1 warms the megastep from "
                "(features, labels) pairs; bare forward shapes cannot "
                "be megabatched — warm them in a separate call")
        with self.mesh:
            model._ensure_opt_state()
            model._params = self.mesh.replicate(model._params)
            model._states = self.mesh.replicate(model._states)
            model._opt_state = self.mesh.replicate(model._opt_state)
            model._t_dev = None
            _cc.warmup(model, padded, policy=policy,
                       steps_per_dispatch=k, dtype=dtype,
                       label_dtype=label_dtype,
                       placement=lambda a: self._mesh_placement(a, k > 1))
        return model

    def fit(self, iterator: DataSetIterator, epochs: int = 1,
            steps_per_dispatch: int = 1, checkpoint=None, nan_policy=None,
            faults=None, elastic=None):
        """``steps_per_dispatch=K`` composes the data-parallel path with
        the K-step lax.scan megastep: each megabatch is staged as
        ``[K, B, ...]`` arrays batch-sharded over the mesh's ``data`` axis
        (axis 1) by a DevicePrefetcher, so ONE dispatch per K sharded
        update steps.

        ``checkpoint=``/``nan_policy=``/``faults=`` enable the fault-
        tolerance layer (train.resilience) exactly as on the wrapped
        model's own ``fit``; resume restores the full training state
        BEFORE replication so the restored params are distributed over
        the mesh like freshly initialized ones. With resilience active
        the K=1 AsyncDataSetIterator auto-wrap is skipped so checkpoint
        cursors stay exact (the async worker prefetches ahead of the
        applied step).

        ``elastic=ElasticConfig(...)`` (or ``elastic=True`` for the
        defaults) turns on elastic multi-device training
        (parallel.elastic): device health probes between dispatches, a
        dispatch watchdog, and on device loss a coordinated checkpoint +
        mesh shrink onto the survivors + bit-exact resume. Requires
        ``checkpoint=``; ``self.mesh`` reflects the shrunk mesh after a
        recovery."""
        if elastic is not None and elastic is not False:
            from deeplearning4j_tpu.parallel import elastic as _elastic
            cfg = elastic if isinstance(elastic, _elastic.ElasticConfig) \
                else _elastic.ElasticConfig()
            return _elastic.fit_elastic(
                self, iterator, epochs=epochs,
                steps_per_dispatch=steps_per_dispatch,
                checkpoint=checkpoint, nan_policy=nan_policy, faults=faults,
                config=cfg)
        model = self.model
        if not model._initialized:
            model.init()
        k = int(steps_per_dispatch)
        session = None
        if checkpoint is not None or nan_policy is not None \
                or faults is not None:
            from deeplearning4j_tpu.train import resilience as _resilience
            model._ensure_opt_state()
            session, iterator = _resilience.begin_session(
                model, iterator, checkpoint, nan_policy, faults)
        fresh = False
        if session is None and k <= 1 and self.prefetch \
                and not isinstance(iterator, AsyncDataSetIterator):
            # the wrapper's constructor resets the base and starts
            # prefetching (the K-step path prefetches via DevicePrefetcher
            # instead — its worker already pulls the base iterator)
            iterator = AsyncDataSetIterator(iterator, prefetch=self.prefetch)
            fresh = True
        # replicate params/opt state once; batches are sharded per step
        with self.mesh:
            model._ensure_opt_state()
            with _prof.trace_span("collective:replicate_params",
                                  devices=self.mesh.size("data")):
                model._params = self.mesh.replicate(model._params)
                model._states = self.mesh.replicate(model._states)
                model._opt_state = self.mesh.replicate(model._opt_state)
            # reset the device-resident clock: a _t_dev committed to a single
            # device by a previous non-mesh fit() would make the jitted step
            # see incompatible devices; _ensure_clock rebuilds it (fresh,
            # uncommitted) from _iteration on the first sharded step
            model._t_dev = None
            from deeplearning4j_tpu.train.resilience import fit_scope
            with fit_scope(session, model, epochs) as n_epochs:
                for e in range(n_epochs):
                    if (e or not fresh) and not (
                            session is not None
                            and session.consume_skip_reset()):
                        iterator.reset()
                    if k > 1:
                        self._fit_epoch_multistep(model, iterator, k, session)
                    else:
                        def pulls():
                            while iterator.hasNext():
                                yield iterator.next()
                        stream = session.wrap_batches(pulls()) \
                            if session is not None else pulls()
                        for ds in stream:
                            model._fit_one(self._shard(ds))
                    model._epoch += 1
                    if session is not None:
                        session.on_epoch_end()
        return model

    def _fit_epoch_multistep(self, model, iterator, k: int, session=None):
        from deeplearning4j_tpu.train import stepping as _stepping

        def padded():
            while iterator.hasNext():
                yield self._pad(iterator.next())

        stream = session.wrap_batches(padded()) if session is not None \
            else padded()
        # honor prefetch_buffer exactly: 0 keeps the base iterator on the
        # calling thread (thread-affine data sources) with inline staging,
        # N bounds staged megabatches in device memory to N — each is K
        # minibatches, so the user's bound is a real memory bound
        _stepping.fit_epoch_multistep(
            model, stream, k, prefetch=self.prefetch or 0,
            placement=self._mesh_placement)

    def _mesh_placement(self, a, mega: bool):
        """DevicePrefetcher placement hook: megabatch arrays [K, B, ...]
        shard axis 1 over ``data``; leftover single batches shard axis 0
        (same as _shard_impl)."""
        ndim = np.ndim(a)
        if not mega:
            return jax.device_put(a, self.mesh.batch_sharding(ndim))
        return jax.device_put(
            a, self.mesh.sharding(None, "data", *([None] * (ndim - 2))))

    def _shard(self, ds: DataSet) -> DataSet:
        if _prof.instrumentation_active():
            from deeplearning4j_tpu.parallel.data import SHARD_BYTES
            nbytes = sum(int(np.asarray(a).nbytes)
                         for a in (ds.features, ds.labels) if a is not None)
            SHARD_BYTES.labels(site="wrapper").inc(nbytes)
            with _prof.trace_span("parallel:shard_batch", bytes=nbytes,
                                  devices=self.mesh.size("data")):
                return self._shard_impl(ds)
        return self._shard_impl(ds)

    def _shard_impl(self, ds: DataSet) -> DataSet:
        ds = self._pad(ds)
        out = DataSet.__new__(DataSet)
        put = lambda a: jax.device_put(
            a, self.mesh.batch_sharding(np.ndim(a))) if a is not None else None
        out.features = put(ds.features)
        out.labels = put(ds.labels)
        out.features_mask = put(ds.features_mask)
        out.labels_mask = put(ds.labels_mask)
        return out

    def _pad(self, ds: DataSet) -> DataSet:
        # zero-weight tail padding shared with the GSPMD trainer
        # (parallel.data.pad_to_data_axis): gradients exactly match the
        # unpadded batch
        from deeplearning4j_tpu.parallel.data import pad_to_data_axis
        return pad_to_data_axis(ds, self.mesh.size("data"))

    def averagingFrequency(self, n):
        # API-parity shim: sync SPMD allreduces inside ONE XLA program every
        # step; there is no averaging interval to configure. Warn so callers
        # porting reference configs know the knob has no effect here.
        warnings.warn(
            "ParallelWrapper.averagingFrequency has no effect: gradients are "
            "allreduced synchronously by XLA every step (no interval)",
            stacklevel=2)
        return self

    def workers(self, n):
        warnings.warn(
            "ParallelWrapper.workers has no effect: the worker count is the "
            "mesh's data-axis size (%d); pass a different DeviceMesh instead"
            % self.mesh.size("data"), stacklevel=2)
        return self


_INFERENCE_REPLICA_FAILURES = _prof.get_registry().counter(
    "dl4j_inference_replica_failures_total",
    "Inference forwards that raised or exceeded replica_timeout (each "
    "marks the serving replica set unhealthy and is retried on the "
    "survivors up to max_retries)")


class InferenceFailedError(RuntimeError):
    """An inference batch failed every attempt. ``attempts`` counts the
    forwards tried; ``last_error`` is the final failure."""

    def __init__(self, attempts: int, last_error: BaseException):
        self.attempts = attempts
        self.last_error = last_error
        super().__init__(
            f"inference failed after {attempts} attempt(s); last error: "
            f"{type(last_error).__name__}: {last_error}")


class InferenceShutdownError(RuntimeError):
    """The ParallelInference instance was closed while this request was
    still pending (queued, never dispatched). Retriable against another
    replica — the request was not executed."""

    retriable = True

    def __init__(self):
        super().__init__("ParallelInference closed: request was pending "
                         "and has not been executed — retry elsewhere")


class InferenceObservable:
    """Future-like handle for one inference request (ref: ObservablesProvider)."""

    def __init__(self):
        self._event = threading.Event()
        self._result = None

    def _complete(self, result):
        self._result = result
        self._event.set()

    def _fail(self, exc: Exception):
        self._error = exc
        self._event.set()

    def get(self, timeout: float = None):
        if not self._event.wait(timeout):
            raise TimeoutError("inference result not ready")
        if getattr(self, "_error", None) is not None:
            raise self._error
        return self._result


class ParallelInference:
    """Batched inference server object (ref: ParallelInference,
    InferenceMode.BATCHED): queue requests, coalesce up to batchLimit,
    run ONE sharded forward over the mesh, fan results back out.

    Robustness (ISSUE 6): a forward that raises — or exceeds
    ``replica_timeout`` seconds — marks the replica set unhealthy: the
    mesh devices are health-probed, dead ones dropped (the mesh
    rebuilds on the survivors), and the SAME coalesced batch is retried
    on the surviving replicas up to ``max_retries`` times
    (``dl4j_inference_replica_failures_total`` counts the failures).
    After exhaustion every request in the batch fails with a structured
    :class:`InferenceFailedError` instead of a raw backend exception.

    Superseded by :class:`deeplearning4j_tpu.serving.ModelServer`
    (ISSUE 7) — bounded admission with structured overload errors,
    per-request deadlines, AOT bucket warmup, a circuit breaker, and
    graceful drain. This class is kept for reference API parity; it
    shares the bounded-queue + close() semantics:

    - the request queue is bounded (``max_queue``); a full queue raises
      :class:`~deeplearning4j_tpu.serving.ServerOverloadedError`
      instead of blocking the producer unboundedly.
    - ``close()`` (also the context-manager exit; ``shutdown()`` is the
      reference-named alias) stops the worker and fails every pending
      request with :class:`InferenceShutdownError` — callers blocked in
      ``get(timeout)`` unblock immediately instead of timing out.
    """

    def __init__(self, model, mesh: DeviceMesh = None, batch_limit: int = 32,
                 queue_timeout_ms: float = 5.0, max_retries: int = 2,
                 replica_timeout: float = None, faults=None,
                 max_queue: int = 256):
        self.model = model
        self.mesh = mesh or DeviceMesh.data_parallel()
        self.batch_limit = batch_limit
        self.timeout = queue_timeout_ms / 1000.0
        self.max_retries = int(max_retries)
        self.replica_timeout = replica_timeout
        self.max_queue = int(max_queue)
        self._faults = faults
        self._watchdog = None
        if replica_timeout:
            from deeplearning4j_tpu.parallel.elastic import DispatchWatchdog
            # warmup: the first forwards compile; their wall time says
            # nothing about replica health
            self._watchdog = DispatchWatchdog(deadline=replica_timeout,
                                              grace=replica_timeout)
        self._queue: "queue.Queue" = queue.Queue(maxsize=self.max_queue)
        # instrumented (PR-8 adoption sweep): taken per submit AND by the
        # recovery path's mesh swap — wait-time spikes here are the
        # client-visible symptom of a dead-replica rebuild
        from deeplearning4j_tpu.profiler.locks import InstrumentedLock
        self._submit_lock = InstrumentedLock("parallel_inference_submit")
        self._shutdown = False
        self._worker = threading.Thread(target=self._serve, daemon=True)
        self._worker.start()

    def output(self, x, timeout: float = 30.0):
        """Synchronous single-request API (ref: ParallelInference.output)."""
        return self.submit(x).get(timeout)

    def submit(self, x) -> InferenceObservable:
        obs = InferenceObservable()
        # the lock serializes against close(): no request can slip into
        # the queue after close() drained it (it would hang forever)
        with self._submit_lock:
            if self._shutdown:
                raise InferenceShutdownError()
            try:
                self._queue.put_nowait((np.asarray(x), obs))
            except queue.Full:
                from deeplearning4j_tpu.serving.errors import \
                    ServerOverloadedError
                raise ServerOverloadedError(self._queue.qsize(),
                                            self.max_queue) from None
        return obs

    def _serve(self):
        while not self._shutdown:
            try:
                first = self._queue.get(timeout=0.1)
            except queue.Empty:
                continue
            batch = [first]
            sizes = [first[0].shape[0]]
            while sum(sizes) < self.batch_limit:
                try:
                    item = self._queue.get(timeout=self.timeout)
                    batch.append(item)
                    sizes.append(item[0].shape[0])
                except queue.Empty:
                    break
            try:
                feats = np.concatenate([b[0] for b in batch], axis=0)
                total = feats.shape[0]
                # pad to the next power-of-two bucket (capped at
                # batch_limit): ONE compiled program per bucket size
                # instead of one per coalesced request count
                bucket = 1
                while bucket < total:
                    bucket *= 2
                bucket = min(max(bucket, 1), max(self.batch_limit, total))
                if bucket > total:
                    pad = np.zeros((bucket - total,) + feats.shape[1:],
                                   feats.dtype)
                    feats = np.concatenate([feats, pad], axis=0)
                out = self._forward(feats)[:total]
                pos = 0
                for (x, obs), n in zip(batch, sizes):
                    obs._complete(out[pos:pos + n])
                    pos += n
            except Exception as e:  # fail the requests, keep the server alive
                for _, obs in batch:
                    obs._fail(e)

    # ------------------------------------------------------- fault handling
    def _forward_once(self, feats) -> np.ndarray:
        with self.mesh:
            return np.asarray(self.model.output(feats))

    def _forward(self, feats) -> np.ndarray:
        """One coalesced batch through the sharded forward, with bounded
        retry on a surviving replica set after a failure or timeout."""
        last = None
        attempts = 0
        for _ in range(self.max_retries + 1):
            attempts += 1
            try:
                if self._watchdog is not None:
                    return self._watchdog.run(
                        lambda: self._forward_once(feats), attempts)
                return self._forward_once(feats)
            except Exception as e:
                last = e
                _INFERENCE_REPLICA_FAILURES.inc()
                warnings.warn(
                    f"inference replica failure (attempt {attempts}): "
                    f"{type(e).__name__}: {e} — probing devices and "
                    "retrying on the survivors", stacklevel=2)
                self._drop_dead_replicas()
        raise InferenceFailedError(attempts, last)

    def _drop_dead_replicas(self):
        """Health-probe the serving mesh; rebuild it on the survivors
        when devices are dead (the retried forward then runs only on
        replicas that still answer)."""
        from deeplearning4j_tpu.parallel.elastic import shrink_mesh_on_dead
        new_mesh = shrink_mesh_on_dead(self.mesh, plan=self._faults,
                                       context="inference")
        if new_mesh is None:
            return
        with self._submit_lock:     # submitters/close() read the mesh
            self.mesh = new_mesh
        if self._watchdog is not None:
            self._watchdog.begin_attempt()  # the shrunk forward recompiles

    def close(self, timeout: float = 5.0):
        """Stop the worker and fail every still-pending request with
        :class:`InferenceShutdownError` (previously they silently sat
        in an unbounded queue until their own ``get(timeout)`` gave
        up). Idempotent; also the context-manager exit."""
        with self._submit_lock:
            if self._shutdown:
                return
            self._shutdown = True
        self._worker.join(timeout=timeout)
        while True:
            try:
                _x, obs = self._queue.get_nowait()
            except queue.Empty:
                break
            obs._fail(InferenceShutdownError())

    def shutdown(self):
        """Reference-named alias for :meth:`close`."""
        self.close()

    def __enter__(self) -> "ParallelInference":
        return self

    def __exit__(self, *exc):
        self.close()
