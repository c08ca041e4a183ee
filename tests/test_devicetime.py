"""ISSUE 14 coverage: the per-op device-timing bridge, the NHWC compute
layout seam, the fused BN+activation epilogues, the Rotate/Resize device
augment kernels, and the ParallelWrapper replication-path warmup."""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import profiler as prof
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (ActivationLayer, BatchNormalization,
                                          ConvolutionLayer, DenseLayer,
                                          GlobalPoolingLayer, OutputLayer,
                                          SubsamplingLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler import devicetime as dt


def conv_fixture(hw=12, bn=True, act="relu", seed=9, layout=None,
                 fused=False, channels=8):
    b = (NeuralNetConfiguration.Builder().seed(seed).weightInit("relu")
         .list()
         .layer(ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                 nOut=channels, activation="identity")))
    if bn:
        b = b.layer(BatchNormalization()).layer(ActivationLayer(act))
    b = (b.layer(SubsamplingLayer(poolingType="max", kernelSize=(2, 2),
                                  stride=(2, 2)))
         .layer(DenseLayer(nOut=16, activation="relu"))
         .layer(OutputLayer(nOut=4, lossFunction="mcxent",
                            activation="softmax"))
         .setInputType(InputType.convolutional(hw, hw, 3)))
    net = MultiLayerNetwork(b.build()).init()
    if layout:
        net.setComputeLayout(layout)
    if fused:
        net.setEpilogueFusion(True)
    return net


def small_data(hw=12, n=6, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 3, hw, hw).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.randint(0, classes, n)]
    return x, y


# --------------------------------------------------------- the sync bridge
class TestDeviceTimer:
    def test_off_mode_records_nothing(self):
        """A plain fit under ProfilingMode.OFF never creates the
        dl4j_op_device_seconds series (the bridge is pull-based), and an
        explicit export under OFF is refused."""
        prof.set_profiling_mode(prof.ProfilingMode.OFF)
        net = conv_fixture()
        x, y = small_data()
        net.fit(DataSet(x, y))
        reg = prof.get_registry()
        assert reg.get("dl4j_op_device_seconds") is None
        table = dt.measure(net, x, reps=1, mode="sync")
        assert table.export_metrics("fixture") is False
        assert reg.get("dl4j_op_device_seconds") is None

    def test_basic_mode_exports_labeled_series(self):
        prof.set_profiling_mode(prof.ProfilingMode.BASIC)
        try:
            net = conv_fixture()
            x, _ = small_data()
            table = dt.measure(net, x, reps=1, mode="sync")
            assert table.export_metrics("fixture") is True
            m = prof.get_registry().get("dl4j_op_device_seconds")
            assert m is not None
            labels = set(m.children().keys())
            assert any("conv2d" in lbl for lbl in labels)
        finally:
            prof.set_profiling_mode(prof.ProfilingMode.OFF)

    def test_attribution_matches_flop_model(self):
        """Three-layer fixture: every table row's FLOPs equal the
        analyzer's declared-shape model x batch x train factor, and the
        time shares sum to 1."""
        net = conv_fixture(bn=False)         # conv -> pool -> dense -> out
        x, _ = small_data()
        table = dt.measure(net, x, reps=1, mode="sync")
        assert len(table.rows) == len(net.layers)
        assert sum(r.share for r in table.rows) == pytest.approx(1.0)
        model = {name: f for name, _op, f in dt.layer_flop_model(net.conf)}
        assert any(f > 0 for f in model.values())
        for r in table.rows:
            assert r.flops == model[r.layer] * x.shape[0] * 3.0
            if r.flops:
                assert r.mfu is not None and 0 <= r.mfu <= 1.0
        assert table.top_offenders(2)[0]["device_ms"] >= \
            table.top_offenders(2)[1]["device_ms"]

    def test_graph_attribution(self):
        from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                                 ElementWiseVertex)
        g = (NeuralNetConfiguration.Builder().seed(3).weightInit("relu")
             .graphBuilder().addInputs("in")
             .setInputTypes(InputType.convolutional(8, 8, 3)))
        g.addLayer("c1", ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                          nOut=8, activation="relu"), "in")
        g.addLayer("c2", ConvolutionLayer(kernelSize=(1, 1), nOut=8,
                                          activation="identity"), "c1")
        g.addVertex("add", ElementWiseVertex("Add"), "c2", "c1")
        g.addLayer("gp", GlobalPoolingLayer("avg"), "add")
        g.addLayer("out", OutputLayer(nOut=3, lossFunction="mcxent",
                                      activation="softmax"), "gp")
        g.setOutputs("out")
        net = ComputationGraph(g.build()).init()
        x = np.random.RandomState(0).randn(4, 3, 8, 8).astype(np.float32)
        table = dt.measure(net, x, reps=1, mode="sync")
        names = {r.layer for r in table.rows}
        assert {"c1", "c2", "gp", "out"} <= names
        assert table.total_seconds > 0

    def test_trace_mode_raises_cleanly_off_tpu(self):
        net = conv_fixture()
        x, _ = small_data()
        # auto mode must fall back to sync on the CPU backend
        table = dt.measure(net, x, reps=1, mode="auto")
        assert table.source == "sync"


# ------------------------------------------------------------- NHWC seam
class TestNhwcLayout:
    def test_op_level_bit_exact_fp32(self):
        """conv / pool / BN: NHWC vs NCHW bit-exact in fp32 (jitted)."""
        from deeplearning4j_tpu.ops import convolution as conv_ops
        from deeplearning4j_tpu.ops import normalization as norm_ops
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 8, 12, 12).astype(np.float32))
        w = jnp.asarray(rng.randn(16, 8, 3, 3).astype(np.float32))
        b = jnp.asarray(rng.randn(16).astype(np.float32))
        xt = jnp.transpose(x, (0, 2, 3, 1))

        conv_n = jax.jit(lambda a: conv_ops.conv2d(
            a, w, b, stride=1, pad=1))(x)
        conv_t = jax.jit(lambda a: conv_ops.conv2d(
            a, w, b, stride=1, pad=1, data_format="NHWC"))(xt)
        assert (np.asarray(conv_n)
                == np.asarray(jnp.transpose(conv_t, (0, 3, 1, 2)))).all()

        pool_n = jax.jit(lambda a: conv_ops.maxpool2d(
            a, kernel=2, stride=2))(x)
        pool_t = jax.jit(lambda a: conv_ops.maxpool2d(
            a, kernel=2, stride=2, data_format="NHWC"))(xt)
        assert (np.asarray(pool_n)
                == np.asarray(jnp.transpose(pool_t, (0, 3, 1, 2)))).all()

        g = jnp.asarray(rng.randn(8).astype(np.float32))
        be = jnp.asarray(rng.randn(8).astype(np.float32))
        bn_n = jax.jit(lambda a: norm_ops.batch_norm_train(
            a, g, be, jnp.zeros(8), jnp.ones(8), axis=1))(x)
        bn_t = jax.jit(lambda a: norm_ops.batch_norm_train(
            a, g, be, jnp.zeros(8), jnp.ones(8), axis=3))(xt)
        assert (np.asarray(bn_n[0])
                == np.asarray(jnp.transpose(bn_t[0], (0, 3, 1, 2)))).all()
        assert (np.asarray(bn_n[1]) == np.asarray(bn_t[1])).all()

    def test_small_net_fit_bit_exact_fp32(self):
        """A conv/BN/pool stack under the NHWC seam: the FORWARD is
        bit-exact (same seed, same data; public API unchanged); training
        tracks to fp rounding — the backward's weight-gradient
        reductions legally reassociate per layout, so the params pin is
        a tight allclose, not equality."""
        x, y = small_data()
        ds = DataSet(x, y)
        a = conv_fixture()
        b = conv_fixture(layout="NHWC")
        oa, ob = np.asarray(a.output(x)), np.asarray(b.output(x))
        assert (oa == ob).all()
        for _ in range(3):
            a.fit(ds)
            b.fit(ds)
        assert a.score() == pytest.approx(b.score(), rel=1e-5, abs=1e-6)
        pa = np.asarray(a.params())
        pb = np.asarray(b.params())
        np.testing.assert_allclose(pa, pb, rtol=1e-4, atol=1e-5)

    def test_feedforward_public_layout(self):
        net = conv_fixture(layout="NHWC")
        x, _ = small_data()
        acts = net.feedForward(x)
        assert acts[1].shape[1] == 8          # conv activation is NCHW

    def test_layout_roundtrips_config(self):
        from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
        net = conv_fixture()
        net.conf.base.compute_layout = "NHWC"
        conf2 = MultiLayerConfiguration.from_json(net.conf.to_json())
        assert conf2.base.compute_layout == "NHWC"
        net2 = MultiLayerNetwork(conf2).init()
        assert net2._compute_layout == "NHWC"
        assert net2.layers[0].data_format == "NHWC"

    def test_save_load_roundtrips_nhwc(self, tmp_path):
        """A saved NHWC net reloads with the seam ACTIVE (config records
        the layout; stamped layers alone would corrupt the forward)."""
        net = conv_fixture(layout="NHWC")
        x, _ = small_data()
        ref = np.asarray(net.output(x))
        p = str(tmp_path / "nhwc.zip")
        net.save(p)
        loaded = MultiLayerNetwork.load(p)
        assert loaded._compute_layout == "NHWC"
        assert (np.asarray(loaded.output(x)) == ref).all()

    def test_invalid_layout_rejected(self):
        net = conv_fixture()
        with pytest.raises(ValueError):
            net.setComputeLayout("NCWH")
        with pytest.raises(ValueError):
            NeuralNetConfiguration.Builder().computeLayout("bogus")

    def test_w101_layout_extension(self):
        """Conv W101 points at the NHWC seam under NCHW and detects the
        layout fix when active; firing behaviour itself is unchanged."""
        def wasteful(fmt=None):
            b = (NeuralNetConfiguration.Builder().seed(1).list()
                 .layer(ConvolutionLayer(kernelSize=(3, 3), nOut=300,
                                         activation="relu"))
                 .layer(GlobalPoolingLayer("avg"))
                 .layer(OutputLayer(nOut=4, lossFunction="mcxent",
                                    activation="softmax"))
                 .setInputType(InputType.convolutional(8, 8, 3)))
            net = MultiLayerNetwork(b.build())
            if fmt:
                net.setComputeLayout(fmt)
            return net
        rep = wasteful().validate()
        w101 = [d for d in rep.diagnostics if d.code == "DL4J-W101"]
        assert w101 and "NHWC" in (w101[0].fix_hint or "")
        rep2 = wasteful("NHWC").validate()
        w101b = [d for d in rep2.diagnostics if d.code == "DL4J-W101"]
        assert w101b and "NHWC compute layout is active" in w101b[0].message

    def test_zero_steady_state_recompiles(self):
        from deeplearning4j_tpu.analysis.churn import get_churn_detector
        net = conv_fixture(layout="NHWC", fused=True)
        x, y = small_data()
        ds = DataSet(x, y)
        for _ in range(5):
            net.fit(ds)
        assert get_churn_detector().signature_count(
            "MultiLayerNetwork.fit", owner=net) == 1


# ------------------------------------------------------- fused epilogues
class TestFusedEpilogue:
    def test_generic_fusion_bit_identical_fp32(self):
        x, y = small_data()
        ds = DataSet(x, y)
        a = conv_fixture()
        b = conv_fixture(fused=True)
        assert (np.asarray(a.output(x)) == np.asarray(b.output(x))).all()
        a.fit(ds)
        b.fit(ds)
        assert a.score() == b.score()

    def test_leaky_head_fusion(self):
        x, y = small_data()
        a = conv_fixture(act="leakyrelu")
        b = conv_fixture(act="leakyrelu", fused=True)
        assert (np.asarray(a.output(x)) == np.asarray(b.output(x))).all()
        plan = b._ensure_epilogue_plan()
        assert plan and list(plan.values())[0][2] == pytest.approx(0.01)

    def test_conv_bias_folds(self):
        """The conv+BN+act triple folds the conv bias into the epilogue
        shift: the plan consumes 3 layers and training stays bit-close."""
        b = conv_fixture(fused=True)
        plan = b._ensure_epilogue_plan()
        assert plan.get(0, (0,))[0] == 3      # conv + BN + act
        x, y = small_data()
        ds = DataSet(x, y)
        a = conv_fixture()
        for _ in range(3):
            a.fit(ds)
            b.fit(ds)
        assert abs(a.score() - b.score()) < 1e-5

    def test_interior_preprocessor_blocks_fusion(self):
        """A preprocessor at an INTERIOR index of a fusable block must
        veto the fusion — the fused dispatch jumps straight through the
        block and would silently drop it. One at the block's START is
        applied before the block either way and keeps the fusion."""
        from deeplearning4j_tpu.nn.layers import build_epilogue_plan

        class _Scale:
            def __call__(self, x):
                return x * 2.0

        a = conv_fixture()
        b = conv_fixture(fused=True)
        a.conf.preprocessors[2] = _Scale()   # interior: the act layer
        b.conf.preprocessors[2] = _Scale()
        assert b._ensure_epilogue_plan() == {}
        x, _ = small_data()
        assert (np.asarray(a.output(x)) == np.asarray(b.output(x))).all()
        plan = build_epilogue_plan(b.layers, {0})   # start index: fine
        assert plan.get(0, (0,))[0] == 3

    def test_sanitizer_walker_mirrors_fused_forward(self):
        """The nonfinite-provenance eager walkers consume the epilogue
        plan: with fusion active the replay reproduces the compiled
        fused step BIT-EXACTLY (same bias fold, same split count) so
        attribution cannot land on an ulp-different op."""
        from deeplearning4j_tpu.profiler import sanitizer as san
        net = conv_fixture(fused=True)
        assert net._ensure_epilogue_plan()
        x, _ = small_data()
        xj = jnp.asarray(x)
        key = jax.random.fold_in(
            jax.random.PRNGKey(net.conf.base.seed), jnp.asarray(0, jnp.int32))
        out_c, _ = net._forward(net._params, net._states, xj, True, key)
        walk = list(san._walk_multilayer(net, net._params, net._states,
                                         xj, None, 0, True))
        assert len(walk) == len(net.layers)
        assert (np.asarray(out_c) == np.asarray(walk[-1][3])).all()

    def test_custom_trace_run_not_divided_by_reps(self, monkeypatch):
        """Trace seconds are normalized by ``reps`` only for the default
        run (the only run_fn that loops ``reps`` times) — a caller's
        ``trace_run`` owns its own iteration count."""
        monkeypatch.setattr(
            dt, "_trace_layer_seconds",
            lambda run, programs=None: {
                0: {"forward": 0.9, "backward": 1.8},
                1: {"forward": 0.1, "backward": 0.0}})
        net = conv_fixture()
        x, _ = small_data()
        custom = dt.measure(net, x, mode="trace", reps=3,
                            trace_run=lambda: None)
        assert custom.source == "trace"
        assert custom.rows[0].seconds == pytest.approx(0.9)
        assert custom.rows[0].backward_seconds == pytest.approx(1.8)
        assert custom.as_rows(1)[0]["backward_ms"] == pytest.approx(1800.0)
        default = dt.measure(net, x, mode="trace", reps=3)
        assert default.rows[0].seconds == pytest.approx(0.3)
        assert default.rows[0].backward_seconds == pytest.approx(0.6)

    @pytest.mark.parametrize("policy", [None, "bf16"],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("act", ["relu", "leakyrelu"])
    def test_installed_overrides_leave_fused_epilogue_alone(self, act,
                                                            policy):
        """The conv path's epilogue is the generic ``scale_shift_act``
        whether or not the Pallas overrides are installed: loss and every
        gradient of a fused NHWC conv+BN+activation net agree to the bit,
        at a width (128 channels, 864 rows) a kernel could tile."""
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        x, y = (jnp.asarray(a) for a in small_data())

        def loss_and_grads():
            net = conv_fixture(act=act, layout="NHWC", fused=True,
                               channels=128)
            if policy:
                net.setPrecisionPolicy(policy)
            loss = lambda p: net._loss_and_reg(
                p, net._states, x, y, True, jax.random.PRNGKey(0), None,
                None)[0]
            return jax.value_and_grad(loss)(net._params)

        plain = loss_and_grads()
        pk.install_platform_overrides(interpret=True)
        try:
            installed = loss_and_grads()
        finally:
            pk.uninstall_platform_overrides()
        for a, b in zip(jax.tree_util.tree_leaves(plain),
                        jax.tree_util.tree_leaves(installed)):
            assert (np.asarray(a) == np.asarray(b)).all()
        # BatchNorm's gamma: a gradient that passed through the epilogue
        assert float(jnp.abs(plain[1][1]["gamma"]).max()) > 0

    def test_bf16_loss_parity_fused_nhwc(self):
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        pk.install_platform_overrides(interpret=True)
        try:
            x, y = small_data()
            ds = DataSet(x, y)
            a = conv_fixture().setPrecisionPolicy("bf16")
            b = conv_fixture(layout="NHWC", fused=True)
            b.setPrecisionPolicy("bf16")
            la, lb = [], []
            for _ in range(4):
                a.fit(ds)
                la.append(a.score())
                b.fit(ds)
                lb.append(b.score())
            scale = max(abs(la[0]), 1e-6)
            assert max(abs(p - q) / scale
                       for p, q in zip(la, lb)) < 0.10
        finally:
            pk.uninstall_platform_overrides()

    def test_graph_fusion_plan_and_equality(self):
        from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                                 ElementWiseVertex)

        def build():
            g = (NeuralNetConfiguration.Builder().seed(3).weightInit("relu")
                 .graphBuilder().addInputs("in")
                 .setInputTypes(InputType.convolutional(8, 8, 3)))
            g.addLayer("c1", ConvolutionLayer(kernelSize=(3, 3),
                                              padding=(1, 1), nOut=8,
                                              activation="identity"), "in")
            g.addLayer("bn1", BatchNormalization(), "c1")
            g.addLayer("r1", ActivationLayer("relu"), "bn1")
            g.addLayer("c2", ConvolutionLayer(kernelSize=(1, 1), nOut=8,
                                              activation="identity"), "r1")
            g.addLayer("bn2", BatchNormalization(), "c2")
            g.addVertex("add", ElementWiseVertex("Add"), "bn2", "r1")
            g.addLayer("r2", ActivationLayer("relu"), "add")
            g.addLayer("gp", GlobalPoolingLayer("avg"), "r2")
            g.addLayer("out", OutputLayer(nOut=3, lossFunction="mcxent",
                                          activation="softmax"), "gp")
            g.setOutputs("out")
            return ComputationGraph(g.build()).init()

        b = build().setEpilogueFusion(True)
        plan = b._ensure_epilogue_plan()
        # bn1 -> r1 fuses (conv c1 folds); bn2 feeds the add vertex and
        # must NOT fuse
        assert "bn1" in plan and plan["bn1"][1] == "c1"
        assert "bn2" not in plan
        x = np.random.RandomState(0).randn(4, 3, 8, 8).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[
            np.random.RandomState(0).randint(0, 3, 4)]
        a = build()
        assert np.abs(np.asarray(a.output(x))
                      - np.asarray(b.output(x))).max() < 1e-5
        ds = DataSet(x, y)
        a.fit(ds)
        b.fit(ds)
        assert abs(a.score() - b.score()) < 1e-5

    def test_multi_consumer_conv_fold_bit_exact(self):
        """ISSUE 17 satellite (PR-14 carry): a conv output feeding >1
        consumer no longer blocks the bias fold — the anchor BN takes
        the bias-less output, every OTHER consumer (here a residual Add
        and a graph output tap) reads a re-biased copy that must be
        BIT-IDENTICAL to the unfused conv."""
        from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                                 ElementWiseVertex)

        def build(tap=False):
            g = (NeuralNetConfiguration.Builder().seed(3).weightInit("relu")
                 .graphBuilder().addInputs("in")
                 .setInputTypes(InputType.convolutional(8, 8, 3)))
            g.addLayer("c1", ConvolutionLayer(kernelSize=(3, 3),
                                              padding=(1, 1), nOut=8,
                                              activation="identity"), "in")
            g.addLayer("bn1", BatchNormalization(), "c1")
            g.addLayer("r1", ActivationLayer("relu"), "bn1")
            g.addVertex("add", ElementWiseVertex("Add"), "c1", "r1")
            g.addLayer("gp", GlobalPoolingLayer("avg"), "add")
            g.addLayer("out", OutputLayer(nOut=3, lossFunction="mcxent",
                                          activation="softmax"), "gp")
            g.setOutputs(*(("out", "c1") if tap else ("out",)))
            return ComputationGraph(g.build()).init()

        x = np.random.RandomState(0).randn(4, 3, 8, 8).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[
            np.random.RandomState(0).randint(0, 3, 4)]
        # c1 has THREE consumers (bn1, add, the output tap) and still
        # folds; the tapped conv output is bit-exact vs the unfused net
        a, b = build(tap=True), build(tap=True).setEpilogueFusion(True)
        plan = b._ensure_epilogue_plan()
        assert plan["bn1"][1] == "c1"
        assert "c1" in b._epilogue_shared
        oa, ob = a.output(x), b.output(x)
        assert np.array_equal(np.asarray(oa[1]), np.asarray(ob[1]))
        assert np.abs(np.asarray(oa[0]) - np.asarray(ob[0])).max() < 1e-5
        # train-path loss parity through the residual reader
        a, b = build(), build().setEpilogueFusion(True)
        ds = DataSet(x, y)
        la, lb = [], []
        for _ in range(4):
            a.fit(ds)
            la.append(a.score())
            b.fit(ds)
            lb.append(b.score())
        scale = max(abs(la[0]), 1e-6)
        assert max(abs(p - q) / scale for p, q in zip(la, lb)) < 0.10

    def test_conv_folds_into_one_bn_only(self):
        """A conv feeding TWO fusable BN+relu chains folds into exactly
        one (first in topo order); the other BN reads the re-biased
        conv output so its statistics match the unfused net."""
        from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                                 ElementWiseVertex)

        def build():
            g = (NeuralNetConfiguration.Builder().seed(5).weightInit("relu")
                 .graphBuilder().addInputs("in")
                 .setInputTypes(InputType.convolutional(8, 8, 3)))
            g.addLayer("c1", ConvolutionLayer(kernelSize=(3, 3),
                                              padding=(1, 1), nOut=8,
                                              activation="identity"), "in")
            g.addLayer("bnA", BatchNormalization(), "c1")
            g.addLayer("rA", ActivationLayer("relu"), "bnA")
            g.addLayer("bnB", BatchNormalization(), "c1")
            g.addLayer("rB", ActivationLayer("relu"), "bnB")
            g.addVertex("add", ElementWiseVertex("Add"), "rA", "rB")
            g.addLayer("gp", GlobalPoolingLayer("avg"), "add")
            g.addLayer("out", OutputLayer(nOut=3, lossFunction="mcxent",
                                          activation="softmax"), "gp")
            g.setOutputs("out")
            return ComputationGraph(g.build()).init()

        b = build().setEpilogueFusion(True)
        plan = b._ensure_epilogue_plan()
        folded = [c for _a, c, _al in plan.values() if c]
        assert folded == ["c1"]          # exactly one BN claimed the conv
        assert "c1" in b._epilogue_shared
        x = np.random.RandomState(1).randn(4, 3, 8, 8).astype(np.float32)
        a = build()
        assert np.abs(np.asarray(a.output(x))
                      - np.asarray(b.output(x))).max() < 1e-5


# --------------------------------------------------- augment device kernels
class TestAugmentKernels:
    def test_resize_shape_and_output_hw(self):
        from deeplearning4j_tpu.nn.augment import DeviceAugmentation
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randint(0, 255, (2, 3, 16, 16)).astype(np.uint8))
        aug = DeviceAugmentation(seed=1).resize(8, 10)
        y = aug.apply(x, aug.step_key(jnp.asarray(0)))
        assert y.shape == (2, 3, 8, 10)
        assert aug.output_hw(16, 16) == (8, 10)

    def test_rotate_zero_is_identity(self):
        from deeplearning4j_tpu.nn.augment import DeviceAugmentation
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randint(0, 255, (2, 3, 12, 12)).astype(np.uint8))
        aug = DeviceAugmentation(seed=1).rotate(0.0)
        y = aug.apply(x, aug.step_key(jnp.asarray(0)))
        assert float(jnp.abs(y - x.astype(jnp.float32)).max()) == 0.0

    def test_rotate_matches_pil_at_90(self):
        from PIL import Image
        from deeplearning4j_tpu.nn.augment import DeviceAugmentation
        rng = np.random.RandomState(0)
        img = rng.randint(0, 255, (16, 16)).astype(np.uint8)
        x = jnp.asarray(img[None, None])
        aug = DeviceAugmentation(seed=1).rotate(90.0)
        y = np.asarray(aug.apply(x, aug.step_key(jnp.asarray(0))))[0, 0]
        ref = np.asarray(Image.fromarray(img).rotate(90, Image.BILINEAR),
                         np.float32)
        assert np.abs(y - ref).max() < 1e-2

    def test_random_rotate_deterministic_per_step(self):
        from deeplearning4j_tpu.nn.augment import DeviceAugmentation
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randint(0, 255, (2, 3, 12, 12)).astype(np.uint8))
        aug = DeviceAugmentation(seed=5).rotate(30.0, random=True)
        y1 = aug.apply(x, aug.step_key(jnp.asarray(3)))
        y2 = aug.apply(x, aug.step_key(jnp.asarray(3)))
        y3 = aug.apply(x, aug.step_key(jnp.asarray(4)))
        assert (np.asarray(y1) == np.asarray(y2)).all()
        assert not (np.asarray(y1) == np.asarray(y3)).all()

    def test_from_transforms_maps_rotate_resize(self):
        from deeplearning4j_tpu.data.image import (ResizeImageTransform,
                                                   RotateImageTransform)
        from deeplearning4j_tpu.nn.augment import DeviceAugmentation
        aug = DeviceAugmentation.from_transforms(
            [ResizeImageTransform(8, 8), RotateImageTransform(15.0)], seed=2)
        sigs = [s[0] for s in aug.signature()[1:]]
        assert sigs == ["resize", "rotate"]

    def test_fit_with_device_rotate_resize(self):
        """End-to-end: augmented conv fit stays on-device (no host
        fallback) with a fixed compiled signature."""
        from deeplearning4j_tpu.nn.augment import DeviceAugmentation
        net = conv_fixture(hw=8)
        rng = np.random.RandomState(0)
        x = rng.randint(0, 255, (6, 3, 12, 12)).astype(np.uint8)
        y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 6)]
        aug = (DeviceAugmentation(seed=3).rotate(10.0, random=True)
               .resize(8, 8).scale_to(0.0, 1.0))
        assert aug.output_hw(12, 12) == (8, 8)
        net.fit(DataSet(x, y), augment=aug)
        net.fit(DataSet(x, y), augment=aug)
        assert np.isfinite(net.score())


# ----------------------------------------- wrapper replication-path warmup
class TestWrapperWarmup:
    def test_warmup_then_fit_zero_new_compiles(self):
        from deeplearning4j_tpu.data.dataset import ListDataSetIterator
        from deeplearning4j_tpu.nn import compilecache as cc
        from deeplearning4j_tpu.parallel.mesh import DeviceMesh
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        net = conv_fixture(hw=8)
        x, y = small_data(hw=8, n=16)
        w = ParallelWrapper(net, DeviceMesh.create(data=8))
        before = cc.cache_stats()["compile_seconds"]["cold_compiles"]
        w.warmup([((16, 3, 8, 8), (16, 4))])
        cold = cc.cache_stats()["compile_seconds"]["cold_compiles"]
        assert cold >= before + 1
        w.fit(ListDataSetIterator(DataSet(x, y), batch_size=16),
              epochs=1)
        assert cc.cache_stats()["compile_seconds"]["cold_compiles"] \
            == cold

    def test_warmup_pads_ragged_batch(self):
        from deeplearning4j_tpu.nn import compilecache as cc
        from deeplearning4j_tpu.parallel.mesh import DeviceMesh
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        net = conv_fixture(hw=8)
        w = ParallelWrapper(net, DeviceMesh.create(data=8))
        before = cc.cache_stats()["compile_seconds"]["cold_compiles"]
        # batch 12 pads to 16 (the fit-path _pad rule)
        w.warmup([((12, 3, 8, 8), (12, 4))])
        assert cc.cache_stats()["compile_seconds"]["cold_compiles"] \
            >= before + 1
        assert net._train_step_cache and all(
            d.warmed_signatures() == 1
            for d in net._train_step_cache.values())

    def test_megastep_warmup_rejects_bare_shapes(self):
        from deeplearning4j_tpu.parallel.mesh import DeviceMesh
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        net = conv_fixture(hw=8)
        w = ParallelWrapper(net, DeviceMesh.create(data=8))
        with pytest.raises(ValueError):
            w.warmup([(16, 3, 8, 8)], steps_per_dispatch=2)
