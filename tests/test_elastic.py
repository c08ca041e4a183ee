"""Elastic multi-device training (ISSUE 6): device-loss detection,
dispatch watchdogs, coordinated mesh-shrink resume, and the robust
ParallelInference retry path — every recovery driven by a deterministic
injected fault on the 8-device virtual CPU mesh.

The acceptance pin: an 8-device ParallelWrapper fit that loses half its
devices mid-run writes a coordinated checkpoint of the last globally
completed step, shrinks the mesh, finishes — and its params equal a
FRESH 4-device fit resumed from that same checkpoint, bit-exact.
"""

import threading
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import (DataSet, DevicePrefetcher,
                                             ListDataSetIterator)
from deeplearning4j_tpu.faults import FaultPlan
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import (DeviceMesh, DispatchTimeoutError,
                                         ElasticConfig, ElasticShrinkError,
                                         InferenceFailedError,
                                         InProcessCoordinator,
                                         ParallelInference, ParallelWrapper)
from deeplearning4j_tpu.parallel.elastic import (DEVICE_LOST,
                                                 DeviceMonitor,
                                                 DispatchWatchdog,
                                                 MESH_SHRINKS,
                                                 STRAGGLER_SECONDS,
                                                 WATCHDOG_TIMEOUTS)
from deeplearning4j_tpu.parallel.wrapper import _INFERENCE_REPLICA_FAILURES
from deeplearning4j_tpu.train import updaters
from deeplearning4j_tpu.train.resilience import (CheckpointConfig,
                                                 CheckpointManager, NanPolicy)

NIN, NOUT, BATCH, NBATCH = 6, 3, 8, 10


def mlp(seed=42, lr=0.01):
    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater(updaters.Adam(lr)).list()
            .layer(DenseLayer(nOut=8, activation="relu"))
            .layer(OutputLayer(nOut=NOUT, lossFunction="mcxent",
                               activation="softmax"))
            .setInputType(InputType.feedForward(NIN))
            .build())
    return MultiLayerNetwork(conf).init()


def iterator(seed=5):
    rng = np.random.RandomState(seed)
    x = rng.randn(NBATCH * BATCH, NIN).astype(np.float32)
    y = np.eye(NOUT, dtype=np.float32)[rng.randint(0, NOUT, NBATCH * BATCH)]
    return ListDataSetIterator(DataSet(x, y), batch_size=BATCH)


@pytest.fixture(scope="module")
def devices8():
    assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"
    return jax.devices()


# ============================================================ device monitor
class TestDeviceMonitor:
    def test_all_healthy(self, devices8):
        health = DeviceMonitor().probe(devices8)
        assert health.healthy() and not health.dead
        assert set(health.probe_seconds) == {d.id for d in devices8}

    def test_planned_loss_classified_dead(self, devices8):
        plan = FaultPlan(device_loss_at_step=3, lose_devices=[2, 5])
        mon = DeviceMonitor(plan=plan)
        assert mon.probe(devices8, step=2).dead == set()   # not yet
        health = mon.probe(devices8, step=3)
        assert health.dead == {2, 5}
        assert 2 not in health.probe_seconds               # dead: no probe
        # persistent: still dead later (a lost chip stays lost)
        assert mon.probe(devices8, step=9).dead == {2, 5}
        # step=None = "as of now" (inference-side probes)
        assert mon.probe(devices8).dead == {2, 5}

    def test_degraded_classification(self, devices8):
        health = DeviceMonitor(degraded_after=0.0).probe(devices8)
        # every real probe takes > 0s: all live devices read degraded
        assert health.degraded == {d.id for d in devices8}
        assert not health.dead


# ================================================================= watchdog
class TestDispatchWatchdog:
    def test_returns_result_inline_and_supervised(self):
        assert DispatchWatchdog(warmup=0).run(lambda: 41 + 1, 1) == 42
        wd = DispatchWatchdog(deadline=5.0, warmup=0)
        assert wd.run(lambda: "ok", 1) == "ok"
        assert wd.timeouts == 0

    def test_soft_timeout_records_straggler(self):
        wd = DispatchWatchdog(deadline=0.05, grace=10.0, warmup=0)
        before = (WATCHDOG_TIMEOUTS.value, STRAGGLER_SECONDS.count)
        assert wd.run(lambda: time.sleep(0.2) or "late", 7) == "late"
        assert wd.timeouts == 1 and wd.stragglers == 1
        assert WATCHDOG_TIMEOUTS.value == before[0] + 1
        assert STRAGGLER_SECONDS.count == before[1] + 1

    def test_hard_timeout_abandons_and_raises(self):
        release = threading.Event()
        wd = DispatchWatchdog(deadline=0.05, grace=0.15, warmup=0)
        with pytest.raises(DispatchTimeoutError, match="grace deadline"):
            wd.run(lambda: release.wait(10.0), 3)
        release.set()   # let the abandoned thread exit

    def test_warmup_dispatches_unsupervised(self):
        wd = DispatchWatchdog(deadline=0.05, grace=10.0, warmup=1)
        # a compile-length first dispatch must NOT be flagged...
        assert wd.run(lambda: time.sleep(0.2) or 1, 1) == 1
        assert wd.timeouts == 0
        # ...but the second one is supervised again
        wd.run(lambda: time.sleep(0.2) or 2, 2)
        assert wd.timeouts == 1
        wd.begin_attempt()      # a new mesh attempt re-arms leniency
        assert wd._lenient == 1

    def test_dispatch_error_reraised_on_caller(self):
        wd = DispatchWatchdog(deadline=5.0, warmup=0)
        with pytest.raises(ValueError, match="boom"):
            wd.run(lambda: (_ for _ in ()).throw(ValueError("boom")), 1)


# ============================================================== coordinator
class TestInProcessCoordinator:
    def test_single_participant(self):
        c = InProcessCoordinator(1)
        assert c.resume_barrier("p0", 17) == 17
        assert c.resume_barrier("p0", 23) == 23      # reusable

    def test_agreement_is_min_across_participants(self):
        c = InProcessCoordinator(3)
        results = {}

        def arrive(pid, step):
            results[pid] = c.resume_barrier(pid, step, timeout=10.0)

        threads = [threading.Thread(target=arrive, args=(f"p{i}", s))
                   for i, s in enumerate((7, 5, 6))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == {"p0": 5, "p1": 5, "p2": 5}

    def test_missing_participant_times_out(self):
        c = InProcessCoordinator(2)
        with pytest.raises(TimeoutError, match="1/2 participants"):
            c.resume_barrier("alone", 4, timeout=0.1)


# ============================================================ elastic shrink
class TestElasticShrink:
    def _fit_elastic(self, d, plan, k=1, cfg=None, net=None, lr=0.01):
        net = net or mlp(lr=lr)
        w = ParallelWrapper(net)
        w.fit(iterator(), epochs=1, steps_per_dispatch=k,
              checkpoint=CheckpointConfig(d),
              elastic=cfg or ElasticConfig(), faults=plan)
        return net, w

    def test_loss_of_half_the_mesh_matches_fresh_small_fit(self, tmp_path,
                                                           devices8):
        """THE acceptance pin: 8-device fit loses 4 devices at step 5 ->
        coordinated checkpoint of step 5 -> shrink to 4 -> finish; params
        equal a fresh 4-device fit resumed from that checkpoint."""
        d = str(tmp_path / "c")
        lost_before = DEVICE_LOST.value
        shrinks_before = MESH_SHRINKS.value
        plan = FaultPlan(device_loss_at_step=5, lose_devices=[4, 5, 6, 7])
        net, w = self._fit_elastic(d, plan)
        assert net._iteration == NBATCH
        assert w.mesh.size("data") == 4
        assert {dev.id for dev in w.mesh.devices} == {0, 1, 2, 3}
        assert DEVICE_LOST.value == lost_before + 4
        assert MESH_SHRINKS.value == shrinks_before + 1
        # the coordinated checkpoint holds the last globally completed step
        mgr = CheckpointManager(CheckpointConfig(d))
        [(step, path)] = mgr.checkpoints()
        assert step == 5
        assert mgr.validate(path)["status"] == "elastic-shrink"
        # fresh 4-device run resumed from the same checkpoint: bit-exact
        ref = mlp()
        ParallelWrapper(ref, DeviceMesh.create(data=4,
                                               devices=devices8[:4])).fit(
            iterator(), epochs=1, checkpoint=CheckpointConfig(d, resume=True))
        assert ref._iteration == NBATCH
        assert np.array_equal(np.asarray(net.params()),
                              np.asarray(ref.params()))

    def test_shrink_composes_with_megasteps(self, tmp_path, devices8):
        d = str(tmp_path / "c")
        plan = FaultPlan(device_loss_at_step=4, lose_devices=[4, 5, 6, 7])
        net, w = self._fit_elastic(d, plan, k=2)
        assert net._iteration == NBATCH
        assert w.mesh.size("data") == 4
        ref = mlp()
        ParallelWrapper(ref, DeviceMesh.create(data=4,
                                               devices=devices8[:4])).fit(
            iterator(), epochs=1, steps_per_dispatch=2,
            checkpoint=CheckpointConfig(d, resume=True))
        assert np.array_equal(np.asarray(net.params()),
                              np.asarray(ref.params()))

    def test_hard_hang_with_device_loss_shrinks(self, tmp_path):
        # dispatch 6 hangs forever AND devices 6/7 are dead: the watchdog
        # abandons it, the probe confirms the loss, the mesh shrinks, and
        # batch 6 replays from the step-5 checkpoint
        d = str(tmp_path / "c")
        plan = FaultPlan(hung_dispatch_at=[6], hang_seconds=None,
                         device_loss_at_step=6, lose_devices=[6, 7])
        net, w = self._fit_elastic(
            d, plan, cfg=ElasticConfig(watchdog_deadline=0.1,
                                       watchdog_grace=0.3))
        assert net._iteration == NBATCH
        assert w.mesh.size("data") == 6
        mgr = CheckpointManager(CheckpointConfig(d))
        assert [s for s, _ in mgr.checkpoints()] == [5]

    def test_soft_hang_is_a_straggler_not_a_failure(self, tmp_path):
        d = str(tmp_path / "c")
        before = WATCHDOG_TIMEOUTS.value
        plan = FaultPlan(hung_dispatch_at=[4], hang_seconds=0.5)
        net, w = self._fit_elastic(
            d, plan, cfg=ElasticConfig(watchdog_deadline=0.1,
                                       watchdog_grace=30.0))
        assert net._iteration == NBATCH
        assert w.mesh.size("data") == 8             # no shrink
        assert WATCHDOG_TIMEOUTS.value == before + 1
        # the stall changed nothing numerically
        ref = mlp()
        ParallelWrapper(ref).fit(iterator(), epochs=1,
                                 checkpoint=CheckpointConfig(d + "x"))
        assert np.array_equal(np.asarray(net.params()),
                              np.asarray(ref.params()))

    def test_slow_replica_recorded_as_straggler(self, tmp_path):
        d = str(tmp_path / "c")
        before = STRAGGLER_SECONDS.count
        plan = FaultPlan(slow_replica_at=[5], slow_seconds=0.3)
        net, _ = self._fit_elastic(
            d, plan, cfg=ElasticConfig(watchdog_deadline=0.1,
                                       watchdog_grace=30.0))
        assert net._iteration == NBATCH
        assert STRAGGLER_SECONDS.count == before + 1

    def test_hard_hang_on_healthy_mesh_surfaces(self, tmp_path):
        # no dead device behind the hang: retrying could double-apply the
        # maybe-landed step, so the timeout must surface instead
        d = str(tmp_path / "c")
        net = mlp()
        with pytest.raises(DispatchTimeoutError):
            ParallelWrapper(net).fit(
                iterator(), epochs=1, checkpoint=CheckpointConfig(d),
                elastic=ElasticConfig(watchdog_deadline=0.1,
                                      watchdog_grace=0.3),
                faults=FaultPlan(hung_dispatch_at=[4], hang_seconds=None))

    def test_elastic_requires_checkpoint(self):
        with pytest.raises(ValueError, match="requires checkpoint"):
            ParallelWrapper(mlp()).fit(iterator(), elastic=ElasticConfig())

    def test_too_few_survivors_raises(self, tmp_path):
        d = str(tmp_path / "c")
        plan = FaultPlan(device_loss_at_step=3,
                         lose_devices=[1, 2, 3, 4, 5, 6, 7])
        with pytest.raises(ElasticShrinkError, match="min_devices"):
            self._fit_elastic(d, plan, cfg=ElasticConfig(min_devices=2))

    def test_lr_policy_linear_rescales(self, tmp_path):
        d = str(tmp_path / "c")
        plan = FaultPlan(device_loss_at_step=5, lose_devices=[4, 5, 6, 7])
        net, _ = self._fit_elastic(d, plan,
                                   cfg=ElasticConfig(lr_policy="linear"))
        try:
            assert getattr(net.conf.base.updater, "_lr_scale", 1.0) == 0.5
        finally:
            net.conf.base.updater._lr_scale = 1.0   # don't leak across tests

    def test_lagging_barrier_restores_agreed_step_not_newest(self,
                                                            tmp_path):
        # a participant AHEAD of the agreement must roll back to the
        # agreed checkpoint (not re-load its own newest) and must not
        # write an ahead-of-agreement coordinated checkpoint
        from deeplearning4j_tpu.parallel.elastic import CoordinationService

        class Lagging(CoordinationService):
            def resume_barrier(self, participant, step, timeout=60.0):
                return step - 2     # someone else is two steps behind

        d = str(tmp_path / "c")
        plan = FaultPlan(device_loss_at_step=5, lose_devices=[4, 5, 6, 7])
        net = mlp()
        w = ParallelWrapper(net)
        w.fit(iterator(), epochs=1,
              checkpoint=CheckpointConfig(d, every_steps=1, keep_last=50),
              elastic=ElasticConfig(coordinator=Lagging()), faults=plan)
        assert net._iteration == NBATCH
        assert w.mesh.size("data") == 4
        mgr = CheckpointManager(CheckpointConfig(d))
        statuses = {s: mgr.validate(p)["status"]
                    for s, p in mgr.checkpoints()}
        assert "elastic-shrink" not in statuses.values()
        # steps 4 and 5 were rolled back and REplayed on the shrunk mesh:
        # the post-shrink periodic saves re-wrote them
        assert {4, 5}.issubset(statuses)

    def test_dispatch_fence_discards_abandoned_commit(self):
        # an abandoned hung dispatch that completes AFTER the shrink
        # bumped the fence must not commit its result or run any
        # bookkeeping (iteration, iterationDone listeners, after hooks) —
        # the recovery that bumped the fence owns the model state (it
        # restores from checkpoint: the dispatch DONATED the old buffers)
        from deeplearning4j_tpu.parallel.elastic import DispatchFence
        from deeplearning4j_tpu.train.resilience import _device_copy
        net = mlp()
        ds = next(iter(iterator()))
        net._fit_one(ds)                      # warm/compile
        saved = (_device_copy(net._params), _device_copy(net._states),
                 _device_copy(net._opt_state))
        fence = DispatchFence()
        net._dispatch_fence = fence
        done = []

        class BumpMidDispatch:
            def onIterationStart(self, model, iteration):
                fence.generation += 1         # "shrink" lands mid-flight

            def iterationDone(self, model, iteration, epoch):
                done.append(iteration)
        net.setListeners(BumpMidDispatch())
        before_iter = net._iteration
        net._fit_one(ds)
        assert net._iteration == before_iter      # no bookkeeping
        assert done == []                         # no iterationDone
        # the recovery path restores state after the void; emulate it and
        # confirm training continues normally once the fence is cleared
        net._params, net._states, net._opt_state = saved
        net._t_dev = None
        net._dispatch_fence = None
        net.setListeners()
        net._fit_one(ds)
        assert net._iteration == before_iter + 1

    def test_bad_lr_policy_rejected_up_front(self, tmp_path):
        with pytest.raises(ValueError, match="lr_policy"):
            ParallelWrapper(mlp()).fit(
                iterator(), checkpoint=CheckpointConfig(str(tmp_path)),
                elastic=ElasticConfig(lr_policy="Linear"))

    def test_restore_specific_step(self, tmp_path):
        d = str(tmp_path / "c")
        net = mlp()
        net.fit(iterator(), checkpoint=CheckpointConfig(d, every_steps=2,
                                                        keep_last=50))
        mgr = CheckpointManager(CheckpointConfig(d))
        assert [s for s, _ in mgr.checkpoints()] == [2, 4, 6, 8, 10]
        target = mlp()
        info = mgr.restore(target, step=4)
        assert info["manifest"]["step"] == 4 and target._iteration == 4
        assert mgr.restore(mlp(), step=5) is None     # absent step

    def test_preemption_composes_with_elastic(self, tmp_path):
        d = str(tmp_path / "c")
        net = mlp()
        ParallelWrapper(net).fit(
            iterator(), epochs=1, checkpoint=CheckpointConfig(d),
            elastic=ElasticConfig(), faults=FaultPlan(preempt_at_step=6))
        assert net._preempted and net._iteration == 6
        _, manifest = CheckpointManager(CheckpointConfig(d)).latest_valid()
        assert manifest["status"] == "preempted"


# ===================================================== data-pipeline rebind
class TestPrefetcherRebindAfterShrink:
    """Satellite: a mesh shrink discards staged megabatches laid out for
    the OLD mesh instead of dispatching them; a new prefetcher with the
    new placement serves the remaining batches."""

    def _placement(self, mesh):
        def place(a, mega):
            ndim = np.ndim(a)
            if not mega:
                return jax.device_put(a, mesh.batch_sharding(ndim))
            return jax.device_put(
                a, mesh.sharding(None, "data", *([None] * (ndim - 2))))
        return place

    def _pulls(self, it):
        # feed the prefetcher a generator, as the elastic loop does — a
        # bare DataSetIterator source would be reset by iter()
        while it.hasNext():
            yield it.next()

    def test_staged_items_discarded_then_rebind(self, devices8):
        it = iterator()
        mesh8 = DeviceMesh.data_parallel()
        pf = DevicePrefetcher(self._pulls(it), steps_per_dispatch=1,
                              prefetch=4, placement=self._placement(mesh8))
        first = next(iter(pf))
        assert len(first.features.sharding.device_set) == 8
        time.sleep(0.2)                 # let the worker stage ahead
        pf.close()                      # shrink: staged items discarded
        consumed_pos = it.cursor()["pos"]
        assert consumed_pos > BATCH     # the worker really pulled ahead
        # rebind: seek back to just after the applied batch, new mesh
        it.seek({"pos": BATCH, "epoch": 0})
        mesh4 = DeviceMesh.create(data=4, devices=devices8[:4])
        with DevicePrefetcher(self._pulls(it), steps_per_dispatch=1,
                              prefetch=2,
                              placement=self._placement(mesh4)) as pf2:
            rest = list(pf2)
        assert len(rest) == NBATCH - 1
        assert all(len(b.features.sharding.device_set) == 4 for b in rest)

    def test_sharded_iterator_cursor_protocol(self):
        from deeplearning4j_tpu.parallel.data import ShardedDataSetIterator
        it = ShardedDataSetIterator(iterator(), process_count=2,
                                    process_index=0)
        it.next()
        c = it.cursor()
        assert c == {"pos": BATCH, "epoch": 0}
        nxt = it.next()
        it2 = ShardedDataSetIterator(iterator(), process_count=2,
                                     process_index=0)
        it2.seek(c)
        np.testing.assert_array_equal(it2.next().features, nxt.features)
        # a batch buffered by hasNext() makes the cursor unusable: None
        it.hasNext()
        assert it.cursor() is None


# ======================================================== parallel inference
class _FlakyOutputModel:
    """model.output raises for the first ``fail`` calls, then delegates."""

    def __init__(self, base, fail=1, sleep=0.0):
        self.base = base
        self._fail = fail
        self._sleep = sleep
        self.stall_over = threading.Event()     # a stalled call returned

    def output(self, x):
        if self._fail > 0:
            self._fail -= 1
            if self._sleep:
                time.sleep(self._sleep)
                try:
                    return self.base.output(x)
                finally:
                    self.stall_over.set()
            raise RuntimeError("injected replica failure")
        return self.base.output(x)


class TestParallelInferenceRobustness:
    def _net(self):
        conf = (NeuralNetConfiguration.Builder().seed(1)
                .updater(updaters.Sgd(0.1)).list()
                .layer(DenseLayer(nOut=8, activation="relu"))
                .layer(OutputLayer(nOut=3, lossFunction="mcxent",
                                   activation="softmax"))
                .setInputType(InputType.feedForward(4))
                .build())
        return MultiLayerNetwork(conf).init()

    def test_flaky_replica_retried(self, devices8):
        net = self._net()
        before = _INFERENCE_REPLICA_FAILURES.value
        pi = ParallelInference(_FlakyOutputModel(net, fail=1),
                               DeviceMesh.data_parallel(), max_retries=2)
        try:
            x = np.random.RandomState(0).randn(4, 4).astype(np.float32)
            with pytest.warns(UserWarning, match="replica failure"):
                out = pi.output(x, timeout=30)
            np.testing.assert_allclose(out, np.asarray(net.output(x)),
                                       rtol=1e-4, atol=1e-5)
            assert _INFERENCE_REPLICA_FAILURES.value == before + 1
        finally:
            pi.shutdown()

    def test_exhausted_retries_structured_error(self, devices8):
        net = self._net()
        pi = ParallelInference(_FlakyOutputModel(net, fail=99),
                               DeviceMesh.data_parallel(), max_retries=1)
        try:
            obs = pi.submit(np.zeros((2, 4), np.float32))
            with pytest.warns(UserWarning, match="replica failure"):
                with pytest.raises(InferenceFailedError,
                                   match="after 2 attempt"):
                    obs.get(timeout=30)
        finally:
            pi.shutdown()

    def test_timed_out_replica_retried(self, devices8):
        net = self._net()
        x = np.random.RandomState(1).randn(2, 4).astype(np.float32)
        mesh = DeviceMesh.data_parallel()
        # pre-compile the program the replicas run (the entered mesh is
        # part of its key), so the timeout only measures the stall: a
        # retry that still had to compile overran a short timeout under
        # a loaded machine, and the stall outlasts the timeout fourfold
        with mesh:
            net.output(x)
        before = _INFERENCE_REPLICA_FAILURES.value
        flaky = _FlakyOutputModel(net, fail=1, sleep=2.0)
        pi = ParallelInference(flaky, mesh, max_retries=2,
                               replica_timeout=0.5)
        pi._watchdog._lenient = 0       # compile already done above
        try:
            with pytest.warns(UserWarning, match="replica failure"):
                out = pi.output(x, timeout=30)
            np.testing.assert_allclose(out, np.asarray(net.output(x)),
                                       rtol=1e-4, atol=1e-5)
            assert _INFERENCE_REPLICA_FAILURES.value >= before + 1
        finally:
            pi.shutdown()
            # let the abandoned forward finish cleanly
            assert flaky.stall_over.wait(30)

    def test_tensor_parallel_mesh_not_flattened(self, devices8):
        # a TP serving mesh cannot drop devices (each holds a shard):
        # the failure retries on the FULL mesh instead of rebuilding a
        # data-parallel one that would break the model's sharding
        net = self._net()
        plan = FaultPlan(device_loss_at_step=1, lose_devices=[7])
        pi = ParallelInference(_FlakyOutputModel(net, fail=1),
                               DeviceMesh.create(data=4, model=2),
                               max_retries=2, faults=plan)
        try:
            x = np.random.RandomState(3).randn(4, 4).astype(np.float32)
            with pytest.warns(UserWarning,
                              match="cannot shrink a tensor-parallel"):
                out = pi.output(x, timeout=30)
            np.testing.assert_allclose(out, np.asarray(net.output(x)),
                                       rtol=1e-4, atol=1e-5)
            assert pi.mesh.size("model") == 2     # mesh untouched
        finally:
            pi.shutdown()

    def test_dead_devices_dropped_from_serving_mesh(self, devices8):
        net = self._net()
        plan = FaultPlan(device_loss_at_step=1, lose_devices=[4, 5, 6, 7])
        pi = ParallelInference(_FlakyOutputModel(net, fail=1),
                               DeviceMesh.data_parallel(), max_retries=2,
                               faults=plan)
        try:
            x = np.random.RandomState(2).randn(4, 4).astype(np.float32)
            with pytest.warns(UserWarning, match="dropping dead device"):
                out = pi.output(x, timeout=30)
            np.testing.assert_allclose(out, np.asarray(net.output(x)),
                                       rtol=1e-4, atol=1e-5)
            assert pi.mesh.size("data") == 4
            assert {d.id for d in pi.mesh.devices} == {0, 1, 2, 3}
        finally:
            pi.shutdown()


# ===================================================================== chaos
@pytest.mark.chaos
class TestElasticChaosSweep:
    """Seeded elastic sweeps (tier-1 gate: chaos is a fast marker, not a
    slow one): whatever step the seed draws for the device loss — and
    whatever NaN batches ride along — a checkpointed elastic fit must
    shrink, finish all steps, and end with finite params."""

    @pytest.mark.parametrize("policy", [NanPolicy.SKIP_STEP,
                                        NanPolicy.BACKOFF_LR,
                                        NanPolicy.ROLLBACK])
    @pytest.mark.parametrize("seed", range(2))
    def test_device_loss_times_nan_policy(self, seed, policy, tmp_path):
        plan = FaultPlan.seeded(seed, horizon=NBATCH - 1, n_nan=1,
                                n_data_errors=0, device_loss=4,
                                device_pool=range(8))
        d = str(tmp_path / "c")
        net = mlp()
        w = ParallelWrapper(net)
        w.fit(iterator(), epochs=1,
              checkpoint=CheckpointConfig(d, every_steps=2, io_backoff=0.01),
              nan_policy=policy, elastic=ElasticConfig(), faults=plan)
        try:
            if policy is NanPolicy.ROLLBACK:
                # a rollback rewinds the step counter to the restored
                # checkpoint without rewinding the data stream, so the
                # run legitimately ends a few steps short
                assert NBATCH - 3 <= net._iteration <= NBATCH
            else:
                assert net._iteration == NBATCH
            assert np.isfinite(np.asarray(net.params())).all()
            assert w.mesh.size("data") == 4
        finally:
            net.conf.base.updater._lr_scale = 1.0   # BACKOFF_LR hygiene

    @pytest.mark.parametrize("seed", range(2))
    def test_hung_dispatch_sweep(self, seed, tmp_path):
        rng = np.random.RandomState(seed)
        step = int(rng.randint(3, NBATCH))
        before = WATCHDOG_TIMEOUTS.value
        d = str(tmp_path / "c")
        net = mlp()
        ParallelWrapper(net).fit(
            iterator(), epochs=1, checkpoint=CheckpointConfig(d),
            elastic=ElasticConfig(watchdog_deadline=0.1,
                                  watchdog_grace=30.0),
            faults=FaultPlan(hung_dispatch_at=[step], hang_seconds=0.4))
        assert net._iteration == NBATCH
        assert WATCHDOG_TIMEOUTS.value == before + 1
        assert np.isfinite(np.asarray(net.params())).all()
