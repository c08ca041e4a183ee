"""Multi-step on-device dispatch (lax.scan megasteps) + device prefetch.

The hard guarantee under test (ISSUE 2): ``fit(steps_per_dispatch=K)``
produces the SAME params/opt-state/per-step losses as K single-step
``fit`` calls — same fold_in RNG per iteration, same updater math, same
frozen-layer gating — while dispatching ONE compiled program per K steps.
Plus: DevicePrefetcher staging/shutdown, AsyncDataSetIterator close(),
megabatch grouping edge cases, and the profiler seams.
"""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import profiler
from deeplearning4j_tpu.data import (AsyncDataSetIterator, DataSet,
                                     DevicePrefetcher, IterableDataSetIterator,
                                     ListDataSetIterator, MultiDataSet)
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph, ElementWiseVertex
from deeplearning4j_tpu.nn.layers import (DenseLayer, DropoutLayer, LSTM,
                                          OutputLayer, RnnOutputLayer,
                                          SimpleRnn)
from deeplearning4j_tpu.train import ScoreIterationListener, updaters
from deeplearning4j_tpu.train import stepping


def mlp_conf(seed=42, lr=0.05, dropout=False):
    b = (NeuralNetConfiguration.Builder().seed(seed)
         .updater(updaters.Adam(lr)).list()
         .layer(DenseLayer(nOut=16, activation="relu")))
    if dropout:
        b = b.layer(DropoutLayer(0.5))
    return (b.layer(DenseLayer(nOut=16, activation="relu"))
            .layer(OutputLayer(nOut=3, lossFunction="mcxent",
                               activation="softmax"))
            .setInputType(InputType.feedForward(4))
            .build())


def make_batches(n, batch=16, nin=4, nout=3, seed=0):
    rng = np.random.RandomState(seed)
    return [DataSet(rng.randn(batch, nin).astype(np.float32),
                    np.eye(nout, dtype=np.float32)[rng.randint(0, nout, batch)])
            for _ in range(n)]


def masked_rnn_batches(n, batch=8, C=2, T=6, seed=3):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        x = rng.randn(batch, C, T).astype(np.float32)
        y = (x.sum(axis=1, keepdims=True) > 0).astype(np.float32)
        labels = np.concatenate([y, 1 - y], axis=1)
        lengths = rng.randint(3, T + 1, batch)
        mask = (np.arange(T)[None, :] < lengths[:, None]).astype(np.float32)
        out.append(DataSet(x, labels, features_mask=mask, labels_mask=mask))
    return out


def rnn_conf(seed=2):
    return (NeuralNetConfiguration.Builder().seed(seed)
            .updater(updaters.Adam(0.02)).list()
            .layer(SimpleRnn(nOut=8))
            .layer(RnnOutputLayer(nOut=2, lossFunction="mcxent",
                                  activation="softmax"))
            .setInputType(InputType.recurrent(2, 6))
            .build())


def fit_singly(net, batches):
    for ds in batches:
        net.fit(ds)
    return net


class TestMultiStepEquivalence:
    def test_params_match_k_single_steps(self):
        batches = make_batches(8)
        a = MultiLayerNetwork(mlp_conf()).init()
        a.fit(batches, steps_per_dispatch=4)
        b = fit_singly(MultiLayerNetwork(mlp_conf()).init(), batches)
        assert a.getIterationCount() == b.getIterationCount() == 8
        np.testing.assert_allclose(np.asarray(a.params()),
                                   np.asarray(b.params()),
                                   rtol=1e-5, atol=1e-6)
        # opt-state too (Adam moments)
        fa = jax.tree_util.tree_leaves(a._opt_state)
        fb = jax.tree_util.tree_leaves(b._opt_state)
        for la, lb in zip(fa, fb):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-5, atol=1e-6)

    def test_per_step_losses_match(self):
        batches = make_batches(6)
        a = MultiLayerNetwork(mlp_conf()).init()
        la = ScoreIterationListener(1, out=lambda m: None)
        a.setListeners(la)
        a.fit(batches, steps_per_dispatch=3)
        b = MultiLayerNetwork(mlp_conf()).init()
        lb = ScoreIterationListener(1, out=lambda m: None)
        b.setListeners(lb)
        fit_singly(b, batches)
        assert len(la.history) == len(lb.history) == 6
        np.testing.assert_allclose(la.history, lb.history,
                                   rtol=1e-5, atol=1e-7)

    def test_masked_signature_equivalence(self):
        batches = masked_rnn_batches(4)
        a = MultiLayerNetwork(rnn_conf()).init()
        a.fit(batches, steps_per_dispatch=4)
        b = fit_singly(MultiLayerNetwork(rnn_conf()).init(), batches)
        np.testing.assert_allclose(np.asarray(a.params()),
                                   np.asarray(b.params()),
                                   rtol=1e-5, atol=1e-6)

    def test_dropout_rng_parity(self):
        """fold_in(base, t) per scanned step == per single step, so even
        stochastic nets match bit-for-bit."""
        batches = make_batches(4)
        a = MultiLayerNetwork(mlp_conf(dropout=True)).init()
        a.fit(batches, steps_per_dispatch=4)
        b = fit_singly(MultiLayerNetwork(mlp_conf(dropout=True)).init(),
                       batches)
        np.testing.assert_allclose(np.asarray(a.params()),
                                   np.asarray(b.params()),
                                   rtol=1e-6, atol=1e-7)

    def test_frozen_layers_stay_frozen(self):
        batches = make_batches(4)
        net = MultiLayerNetwork(mlp_conf()).init()
        net._frozen_layers = {0}
        before = np.asarray(net._params[0]["W"]).copy()
        net.fit(batches, steps_per_dispatch=4)
        np.testing.assert_array_equal(np.asarray(net._params[0]["W"]), before)
        # and the unfrozen layers did move
        assert float(np.abs(np.asarray(net._params[-1]["W"])).sum()) > 0

    def test_tail_and_signature_change_fall_back_to_single(self):
        # 5 batches at K=4 -> one megastep + one single step; then a batch
        # with a different shape -> single step. All equivalent.
        batches = make_batches(5) + make_batches(1, batch=12, seed=9)
        a = MultiLayerNetwork(mlp_conf()).init()
        a.fit(batches, steps_per_dispatch=4)
        b = fit_singly(MultiLayerNetwork(mlp_conf()).init(), batches)
        assert a.getIterationCount() == 6
        np.testing.assert_allclose(np.asarray(a.params()),
                                   np.asarray(b.params()),
                                   rtol=1e-5, atol=1e-6)

    def test_iterator_input_and_epochs(self):
        data = DataSet.merge(make_batches(8))
        a = MultiLayerNetwork(mlp_conf()).init()
        a.fit(ListDataSetIterator(data, 16), epochs=2, steps_per_dispatch=4)
        b = MultiLayerNetwork(mlp_conf()).init()
        b.fit(ListDataSetIterator(data, 16), epochs=2)
        assert a.getIterationCount() == b.getIterationCount() == 16
        assert a.getEpochCount() == 2
        np.testing.assert_allclose(np.asarray(a.params()),
                                   np.asarray(b.params()),
                                   rtol=1e-5, atol=1e-6)

    def test_tbptt_path_unaffected(self):
        rng = np.random.RandomState(0)
        x = rng.randn(4, 2, 12).astype(np.float32)
        y = np.tile(np.array([[1, 0], [0, 1]], np.float32)[rng.randint(0, 2, 4)]
                    [:, :, None], (1, 1, 12))
        conf = (NeuralNetConfiguration.Builder().seed(5)
                .updater(updaters.Adam(0.01)).list()
                .layer(LSTM(nOut=6))
                .layer(RnnOutputLayer(nOut=2, lossFunction="mcxent",
                                      activation="softmax"))
                .setInputType(InputType.recurrent(2, 12))
                .build())
        net = MultiLayerNetwork(conf).init()
        net.fitTBPTT(DataSet(x, y), tbptt_length=4)
        assert np.isfinite(net.score())


class TestGraphMultiStep:
    def _build(self):
        b = (NeuralNetConfiguration.Builder().seed(7)
             .updater(updaters.Adam(0.02)).graphBuilder())
        b.addInputs("in").setInputTypes(InputType.feedForward(4))
        b.addLayer("d1", DenseLayer(nOut=8, activation="relu"), "in")
        b.addLayer("d2", DenseLayer(nOut=8, activation="relu"), "d1")
        b.addVertex("add", ElementWiseVertex("Add"), "d1", "d2")
        b.addLayer("out", OutputLayer(nOut=3, lossFunction="mcxent",
                                      activation="softmax"), "add")
        b.setOutputs("out")
        return ComputationGraph(b.build())

    def test_graph_equivalence(self):
        batches = make_batches(6, batch=8)
        a = self._build().init()
        a.fit(batches, steps_per_dispatch=3)
        b = fit_singly(self._build().init(), batches)
        np.testing.assert_allclose(np.asarray(a.params()),
                                   np.asarray(b.params()),
                                   rtol=1e-5, atol=1e-6)

    def test_graph_multidataset_equivalence(self):
        batches = [MultiDataSet([d.features], [d.labels])
                   for d in make_batches(6, batch=8)]
        a = self._build().init()
        a.fit(batches, steps_per_dispatch=3)
        b = fit_singly(self._build().init(), batches)
        np.testing.assert_allclose(np.asarray(a.params()),
                                   np.asarray(b.params()),
                                   rtol=1e-5, atol=1e-6)


class TestParallelMultiStep:
    def test_wrapper_k_step_matches_single_step(self):
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        data = DataSet.merge(make_batches(8))
        a = MultiLayerNetwork(mlp_conf()).init()
        ParallelWrapper(a).fit(ListDataSetIterator(data, 16),
                               steps_per_dispatch=4)
        b = MultiLayerNetwork(mlp_conf()).init()
        ParallelWrapper(b).fit(ListDataSetIterator(data, 16))
        assert a.getIterationCount() == b.getIterationCount() == 8
        np.testing.assert_allclose(np.asarray(a.params()),
                                   np.asarray(b.params()),
                                   rtol=1e-5, atol=1e-6)

    def test_wrapper_prefetch_zero_stays_synchronous(self):
        """prefetch_buffer=0 must keep iterator consumption on the calling
        thread in the K-step path too (thread-affine data sources)."""
        import threading
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

        main = threading.get_ident()
        seen = []

        class AffineIterator(ListDataSetIterator):
            def next(self):
                seen.append(threading.get_ident())
                return super().next()

        data = DataSet.merge(make_batches(4))
        a = MultiLayerNetwork(mlp_conf()).init()
        ParallelWrapper(a, prefetch_buffer=0).fit(
            AffineIterator(data, 16), steps_per_dispatch=2)
        assert seen and all(t == main for t in seen)
        b = MultiLayerNetwork(mlp_conf()).init()
        ParallelWrapper(b, prefetch_buffer=0).fit(AffineIterator(data, 16))
        np.testing.assert_allclose(np.asarray(a.params()),
                                   np.asarray(b.params()),
                                   rtol=1e-5, atol=1e-6)

    def test_fit_prefetch_zero_synchronous_equivalence(self):
        batches = make_batches(6)
        a = MultiLayerNetwork(mlp_conf()).init()
        a.fit(batches, steps_per_dispatch=3, prefetch=0)
        b = fit_singly(MultiLayerNetwork(mlp_conf()).init(), batches)
        np.testing.assert_allclose(np.asarray(a.params()),
                                   np.asarray(b.params()),
                                   rtol=1e-5, atol=1e-6)

    def test_wrapper_k_step_sharded_over_mesh(self):
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        if len(jax.devices()) < 2:
            pytest.skip("needs the virtual multi-device mesh")
        data = DataSet.merge(make_batches(4))
        net = MultiLayerNetwork(mlp_conf()).init()
        ParallelWrapper(net).fit(ListDataSetIterator(data, 16),
                                 steps_per_dispatch=2)
        assert net.getIterationCount() == 4
        assert np.isfinite(net.score())


class TestMegabatchGrouping:
    def test_group_counts(self):
        batches = make_batches(7)
        items = list(stepping.group_into_megabatches(iter(batches), 3))
        megas = [i for i in items if isinstance(i, stepping.MegaBatch)]
        singles = [i for i in items if isinstance(i, DataSet)]
        assert len(megas) == 2 and len(singles) == 1
        assert all(m.steps == 3 for m in megas)
        assert megas[0].features.shape == (3, 16, 4)

    def test_k1_passthrough(self):
        batches = make_batches(3)
        assert list(stepping.group_into_megabatches(iter(batches), 1)) == batches

    def test_signature_change_flushes_pending(self):
        batches = make_batches(2) + make_batches(2, batch=8, seed=5)
        items = list(stepping.group_into_megabatches(iter(batches), 3))
        # no group reaches 3: everything falls through as singles
        assert all(isinstance(i, DataSet) for i in items)
        assert len(items) == 4


class TestDevicePrefetcher:
    def test_yields_staged_megabatches(self):
        batches = make_batches(4)
        with DevicePrefetcher(iter(batches), steps_per_dispatch=2) as pf:
            items = list(pf)
        assert len(items) == 2
        assert all(isinstance(m, stepping.MegaBatch) for m in items)
        assert all(isinstance(m.features, jax.Array) for m in items)
        assert items[0].features.shape == (2, 16, 4)

    def test_stages_single_datasets_too(self):
        batches = make_batches(3)
        with DevicePrefetcher(iter(batches), steps_per_dispatch=2) as pf:
            items = list(pf)
        assert isinstance(items[-1], DataSet)
        assert isinstance(items[-1].features, jax.Array)

    def test_close_is_idempotent_and_stops_worker(self):
        pf = DevicePrefetcher(iter(make_batches(64)), steps_per_dispatch=2,
                              prefetch=1)
        next(pf)
        pf.close()
        pf.close()
        assert pf._thread is None
        with pytest.raises(StopIteration):
            next(pf)

    def test_worker_error_propagates(self):
        def bad():
            yield make_batches(1)[0]
            raise RuntimeError("boom")
        with DevicePrefetcher(bad(), steps_per_dispatch=1) as pf:
            next(pf)
            with pytest.raises(RuntimeError, match="boom"):
                while True:
                    next(pf)

    def test_h2d_bytes_counter_increments(self):
        reg = profiler.get_registry()
        c = reg.get("dl4j_prefetch_h2d_bytes_total")
        before = c.value
        profiler.set_profiling_mode(profiler.ProfilingMode.BASIC)
        try:
            with DevicePrefetcher(iter(make_batches(2)),
                                  steps_per_dispatch=2) as pf:
                list(pf)
        finally:
            profiler.set_profiling_mode(None)
        assert c.value > before

    def test_queue_depth_gauge_registered(self):
        assert profiler.get_registry().get("dl4j_prefetch_queue_depth") is not None


class TestAsyncIteratorLifecycle:
    def test_close_and_context_manager(self):
        it = AsyncDataSetIterator(
            ListDataSetIterator(DataSet.merge(make_batches(4)), 16))
        assert it.hasNext()
        it.close()
        assert not it.hasNext()
        assert it._thread is None
        it.close()  # idempotent
        with AsyncDataSetIterator(
                ListDataSetIterator(DataSet.merge(make_batches(4)), 16)) as it2:
            n = sum(1 for _ in it2)
            assert n == 4
        assert it2._thread is None

    def test_base_iterator_error_propagates(self):
        """A failing base iterator must raise on the consumer side, not
        silently truncate the stream (evaluate() now rides this path)."""
        class FailingIterator(ListDataSetIterator):
            def next(self):
                if self._pos >= self.batch_size:  # fail on batch 2
                    raise IOError("disk gone")
                return super().next()

        it = AsyncDataSetIterator(
            FailingIterator(DataSet.merge(make_batches(4)), 16))
        with it:
            got = [it.next()]
            with pytest.raises(IOError, match="disk gone"):
                while it.hasNext():
                    got.append(it.next())
        assert len(got) == 1

    def test_reset_after_close_restarts(self):
        it = AsyncDataSetIterator(
            ListDataSetIterator(DataSet.merge(make_batches(2)), 16))
        it.close()
        it.reset()
        assert it.hasNext()
        assert sum(1 for _ in it) == 2
        it.close()

    def test_queue_depth_gauge_registered(self):
        assert profiler.get_registry().get(
            "dl4j_async_iterator_queue_depth") is not None


class TestEvaluateBulkPull:
    def test_evaluate_accepts_plain_list(self):
        batches = make_batches(4)
        net = MultiLayerNetwork(mlp_conf()).init()
        ev = net.evaluate(batches)
        assert 0.0 <= ev.accuracy() <= 1.0

    def test_evaluate_prefetch_false_stays_synchronous(self):
        import threading
        main = threading.get_ident()
        seen = []

        class AffineIterator(ListDataSetIterator):
            def next(self):
                seen.append(threading.get_ident())
                return super().next()

        net = MultiLayerNetwork(mlp_conf()).init()
        it = AffineIterator(DataSet.merge(make_batches(3)), 16)
        ev = net.evaluate(it, prefetch=False)
        assert seen and all(t == main for t in seen)
        assert 0.0 <= ev.accuracy() <= 1.0

    def test_evaluate_accepts_generator(self):
        batches = make_batches(3)
        net = MultiLayerNetwork(mlp_conf()).init()
        ev = net.evaluate(iter(batches))
        assert 0.0 <= ev.accuracy() <= 1.0

    def test_evaluate_matches_reference_loop(self):
        split = make_batches(4)
        net = MultiLayerNetwork(mlp_conf()).init()
        net.fit(split, steps_per_dispatch=2)
        from deeplearning4j_tpu.evaluation import Evaluation
        ref = Evaluation()
        for ds in split:
            ref.eval(ds.labels, np.asarray(net.output(ds.features)))
        ev = net.evaluate(ListDataSetIterator(DataSet.merge(split), 16))
        assert ev.accuracy() == pytest.approx(ref.accuracy())

    def test_evaluate_regression_bulk(self):
        rng = np.random.RandomState(0)
        batches = [DataSet(rng.randn(8, 4).astype(np.float32),
                           rng.randn(8, 3).astype(np.float32))
                   for _ in range(3)]
        conf = (NeuralNetConfiguration.Builder().seed(1)
                .updater(updaters.Adam(0.01)).list()
                .layer(DenseLayer(nOut=8, activation="tanh"))
                .layer(OutputLayer(nOut=3, lossFunction="mse",
                                   activation="identity"))
                .setInputType(InputType.feedForward(4))
                .build())
        net = MultiLayerNetwork(conf).init()
        ev = net.evaluateRegression(batches)
        assert np.isfinite(ev.meanSquaredError())

    def test_iterable_adapter(self):
        batches = make_batches(3)
        it = IterableDataSetIterator(batches)
        assert it.hasNext()
        assert sum(1 for _ in it) == 3
        it.reset()
        assert it.hasNext()

    def test_generator_evaluates_every_batch(self):
        """One-shot generators must not lose the buffered first batch to
        the AsyncDataSetIterator wrapper's constructor reset()."""
        batches = make_batches(3)
        seen = []
        it = AsyncDataSetIterator(
            IterableDataSetIterator(ds for ds in batches))
        with it:
            while it.hasNext():
                seen.append(it.next())
        assert len(seen) == 3
        np.testing.assert_array_equal(seen[0].features, batches[0].features)


class TestProfilerSeams:
    def test_megastep_records_span_and_gauge(self):
        profiler.get_tracer().clear()   # the ring is the process's: other
        # files' fits in this worker may have left their spans in it
        profiler.set_profiling_mode(profiler.ProfilingMode.BASIC)
        profiler.enable_tracing()
        try:
            reg = profiler.get_registry()
            h = reg.histogram("dl4j_train_step_seconds",
                              "Compiled train-step dispatch time per iteration")
            c0 = h.count
            net = MultiLayerNetwork(mlp_conf()).init()
            net.fit(make_batches(4), steps_per_dispatch=4)
            assert h.count == c0 + 1  # ONE dispatch for 4 steps
            g = reg.get("dl4j_steps_per_dispatch")
            assert g is not None and g.value == 4
            # megastep advances the iterations counter by K per dispatch
            assert reg.get("dl4j_train_iterations_total").value >= 4
            dispatches = [e for e in profiler.get_tracer().events()
                          if e["name"] == "fit:dispatch"]
            assert [e["args"]["steps"] for e in dispatches] == [4]
            # a single-step dispatch resets the amortization gauge so
            # per-step derivations from dl4j_train_step_seconds stay right
            net.fit(make_batches(1))
            assert g.value == 1
        finally:
            profiler.set_profiling_mode(None)
            profiler.disable_tracing()
            profiler.get_tracer().clear()
