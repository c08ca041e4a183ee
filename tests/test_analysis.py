"""Static analyzer (ISSUE 3): one seeded misconfiguration per diagnostic
code, clean-bill assertions over the whole model zoo + fixtures, the
recompile-churn detector, strict init, did-you-mean kwarg rejection, the
EarlyStoppingTrainer megastep path, the CLI, and the repo lint gate."""

import ast
import importlib.util
import pathlib
import subprocess
import sys
import warnings

import numpy as np
import pytest

import deeplearning4j_tpu.analysis as analysis
from deeplearning4j_tpu.analysis import (DIAGNOSTIC_CODES, Diagnostic,
                                         MeshSpec, ModelValidationError,
                                         PipelineSpec,
                                         RecompileChurnDetector, Severity,
                                         analyze, get_churn_detector)
from deeplearning4j_tpu.data.dataset import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn.config import (InputType, MultiLayerConfiguration,
                                          NeuralNetConfiguration)
from deeplearning4j_tpu.nn.graph import (ComputationGraph, ElementWiseVertex,
                                         MergeVertex)
from deeplearning4j_tpu.nn.layers import (ConvolutionLayer, DenseLayer, LSTM,
                                          OutputLayer, RnnOutputLayer,
                                          SubsamplingLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.train.updaters import Adam, Sgd

REPO = pathlib.Path(__file__).resolve().parent.parent


def _builder(updater=None):
    return (NeuralNetConfiguration.Builder()
            .seed(7).updater(updater or Sgd(0.1)).weightInit("xavier"))


def _mlp_conf(n_in=4, hidden=8, n_out=2, updater=None):
    return (_builder(updater).list()
            .layer(DenseLayer(nOut=hidden, activation="relu"))
            .layer(OutputLayer(nOut=n_out, lossFunction="mcxent",
                               activation="softmax"))
            .setInputType(InputType.feedForward(n_in))
            .build())


def _graph_builder():
    return (_builder().graphBuilder()
            .addInputs("in")
            .setInputTypes(InputType.feedForward(4)))


def _one_hot(n, k=2, seed=0):
    rng = np.random.RandomState(seed)
    y = np.zeros((n, k), np.float32)
    y[np.arange(n), rng.randint(0, k, n)] = 1.0
    return y


class TestSeededDiagnostics:
    """Each documented code fires on its seeded misconfiguration."""

    def test_e001_nin_mismatch(self):
        conf = (_builder().list()
                .layer(DenseLayer(nIn=300, nOut=16))
                .layer(OutputLayer(nOut=4))
                .setInputType(InputType.feedForward(128))
                .build())
        report = conf.validate()
        assert "DL4J-E001" in report.codes()
        assert not report.ok()

    def test_e001_unresolvable_nin(self):
        conf = (_builder().list()
                .layer(DenseLayer(nOut=16))
                .layer(OutputLayer(nOut=4, nIn=16))
                .build())     # no setInputType -> nIn can't be inferred
        assert "DL4J-E001" in conf.validate().codes()

    def test_e002_cycle(self):
        g = (_graph_builder()
             .addLayer("a", DenseLayer(nIn=4, nOut=4), "b")
             .addLayer("b", DenseLayer(nIn=4, nOut=4), "a")
             .addLayer("out", OutputLayer(nIn=4, nOut=2), "b")
             .setOutputs("out"))
        report = g.validate()      # build() would raise; validate reports
        assert "DL4J-E002" in report.codes()

    def test_e003_undefined_input(self):
        g = (_graph_builder()
             .addLayer("out", OutputLayer(nIn=4, nOut=2), "nonexistent")
             .setOutputs("out"))
        report = g.validate()
        assert "DL4J-E003" in report.codes()
        assert report.errors()

    def test_e003_dangling_vertex(self):
        g = (_graph_builder()
             .addLayer("used", DenseLayer(nOut=4), "in")
             .addLayer("orphan", DenseLayer(nOut=4), "in")
             .addLayer("out", OutputLayer(nOut=2), "used")
             .setOutputs("out"))
        report = analyze(g.build())
        dangling = [d for d in report if d.code == "DL4J-E003"]
        assert dangling and dangling[0].severity is Severity.WARNING
        assert "orphan" in dangling[0].location

    def test_e004_duplicate_graph_name(self):
        g = (_graph_builder()
             .addLayer("fc", DenseLayer(nOut=4), "in")
             .addLayer("fc", DenseLayer(nOut=4), "in")
             .addLayer("out", OutputLayer(nOut=2), "fc")
             .setOutputs("out"))
        assert "DL4J-E004" in g.validate().codes()

    def test_e004_duplicate_explicit_layer_name(self):
        conf = (_builder().list()
                .layer(DenseLayer(nOut=8, name="fc"))
                .layer(DenseLayer(nOut=8, name="fc"))
                .layer(OutputLayer(nOut=2))
                .setInputType(InputType.feedForward(4))
                .build())
        assert "DL4J-E004" in conf.validate().codes()

    def test_e005_missing_cnn_to_dense_flatten(self):
        conf = (_builder().list()
                .layer(ConvolutionLayer(nIn=1, nOut=8, kernelSize=(3, 3)))
                .layer(DenseLayer(nIn=800, nOut=10))
                .layer(OutputLayer(nIn=10, nOut=2))
                .build())     # no input type -> no auto preprocessor
        assert "DL4J-E005" in conf.validate().codes()

    def test_e006_elementwise_shape_conflict(self):
        g = (_builder().graphBuilder()
             .addInputs("in")
             .setInputTypes(InputType.convolutional(8, 8, 3))
             .addLayer("a", ConvolutionLayer(nOut=4, kernelSize=(1, 1)), "in")
             .addLayer("b", ConvolutionLayer(nOut=8, kernelSize=(1, 1)), "in")
             .addVertex("add", ElementWiseVertex("Add"), "a", "b")
             .addLayer("out", OutputLayer(nOut=2), "add")
             .setOutputs("out"))
        assert "DL4J-E006" in analyze(g.build()).codes()

    def test_e006_merge_spatial_conflict(self):
        g = (_builder().graphBuilder()
             .addInputs("in")
             .setInputTypes(InputType.convolutional(8, 8, 3))
             .addLayer("a", ConvolutionLayer(nOut=4, kernelSize=(1, 1)), "in")
             .addLayer("b", ConvolutionLayer(nOut=4, kernelSize=(1, 1),
                                             stride=(2, 2)), "in")
             .addVertex("cat", MergeVertex(), "a", "b")
             .addLayer("out", OutputLayer(nOut=2), "cat")
             .setOutputs("out"))
        assert "DL4J-E006" in analyze(g.build()).codes()

    def test_e007_shape_inference_failure(self):
        lb = (_builder().list()
              .layer(DenseLayer())          # nOut missing
              .layer(OutputLayer(nOut=2))
              .setInputType(InputType.feedForward(4)))
        assert "DL4J-E007" in analyze(lb).codes()   # unbuilt builder

    def test_e008_missing_loss_head(self):
        conf = (_builder().list()
                .layer(DenseLayer(nOut=8))
                .layer(DenseLayer(nOut=2))
                .setInputType(InputType.feedForward(4))
                .build())
        assert "DL4J-E008" in conf.validate().codes()

    def test_w001_softmax_mse(self):
        conf = (_builder().list()
                .layer(OutputLayer(nOut=4, lossFunction="mse",
                                   activation="softmax"))
                .setInputType(InputType.feedForward(4))
                .build())
        report = conf.validate()
        assert "DL4J-W001" in report.codes()
        assert report.ok()                  # warning, not error
        assert not report.ok(warnings_as_errors=True)

    def test_w001_sigmoid_multiclass(self):
        conf = (_builder().list()
                .layer(OutputLayer(nOut=4, lossFunction="mcxent",
                                   activation="sigmoid"))
                .setInputType(InputType.feedForward(4))
                .build())
        assert "DL4J-W001" in conf.validate().codes()

    def test_w002_tbptt_without_recurrence(self):
        conf = (_builder().list()
                .layer(DenseLayer(nOut=8))
                .layer(OutputLayer(nOut=2))
                .setInputType(InputType.feedForward(4))
                .backpropType("tbptt", 16)
                .build())
        assert "DL4J-W002" in conf.validate().codes()

    def test_w002_absent_on_recurrent_net(self):
        conf = (_builder().list()
                .layer(LSTM(nOut=8))
                .layer(RnnOutputLayer(nOut=2))
                .setInputType(InputType.recurrent(4, 10))
                .backpropType("tbptt", 16)
                .build())
        assert "DL4J-W002" not in conf.validate().codes()

    def test_w003_frozen_with_stateful_updater(self):
        net = MultiLayerNetwork(_mlp_conf(updater=Adam(1e-3)))
        net._frozen_layers = {0}
        report = net.validate()
        assert "DL4J-W003" in report.codes()
        # Sgd is stateless -> no warning
        net2 = MultiLayerNetwork(_mlp_conf(updater=Sgd(0.1)))
        net2._frozen_layers = {0}
        assert "DL4J-W003" not in net2.validate().codes()

    def test_w101_mxu_padding_waste(self):
        conf = _mlp_conf(hidden=300)        # 300 -> 384 lanes, 22% dead
        report = conf.validate()
        w101 = [d for d in report if d.code == "DL4J-W101"]
        assert w101 and "384" in w101[0].message
        assert "DL4J-W101" not in _mlp_conf(hidden=512).validate().codes()

    def test_w102_non_native_dtype(self):
        conf = (_builder().dataType("float64").list()
                .layer(OutputLayer(nOut=2))
                .setInputType(InputType.feedForward(4))
                .build())
        assert "DL4J-W102" in conf.validate().codes()

    def test_w103_batch_mesh_divisibility(self):
        conf = _mlp_conf()
        assert "DL4J-W103" in conf.validate(batch_size=6,
                                            data_devices=4).codes()
        assert "DL4J-W103" not in conf.validate(batch_size=8,
                                                data_devices=4).codes()


class TestChurnDetector:
    def test_w201_fires_past_threshold(self):
        from deeplearning4j_tpu.profiler.metrics import MetricsRegistry
        reg = MetricsRegistry()
        det = RecompileChurnDetector(threshold=3, registry=reg)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            results = [det.record("test.site", (("shape", i),))
                       for i in range(5)]
        assert results == [True] * 5        # every first sight says so
        flagged = [w for w in caught if "DL4J-W201" in str(w.message)]
        assert len(flagged) == 1                        # 4th distinct > 3,
        assert "4 distinct" in str(flagged[0].message)  # flagged once
        diag, = det.diagnostics_for(None)
        assert isinstance(diag, Diagnostic) and diag.code == "DL4J-W201"
        # repeats are free, and say they are repeats
        assert det.record("test.site", (("shape", 0),)) is False
        assert det.signature_count("test.site") == 5
        child = reg.get("dl4j_recompiles_total").children()[("test.site",)]
        assert child.value == 5
        assert [d.code for d in det.diagnostics_for(None)] == ["DL4J-W201"]
        det.reset()
        assert det.signature_count("test.site") == 0

    def test_fingerprint_shape_dtype_sensitivity(self):
        a = np.zeros((4, 3), np.float32)
        b = np.zeros((5, 3), np.float32)
        c = np.zeros((4, 3), np.float64)
        from deeplearning4j_tpu.analysis import array_fingerprint
        assert array_fingerprint(a) != array_fingerprint(b)
        assert array_fingerprint(a) != array_fingerprint(c)
        assert array_fingerprint(a, None) == array_fingerprint(a, None)

    def test_model_fit_churn_surfaces_in_validate(self):
        det = get_churn_detector()
        old_threshold = det.threshold
        det.threshold = 3
        try:
            net = MultiLayerNetwork(_mlp_conf()).init()
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                for n in (1, 2, 3, 4, 5):   # 5 distinct batch shapes
                    net.fit(DataSet(np.random.RandomState(n)
                                    .rand(n, 4).astype(np.float32),
                                    _one_hot(n)))
            report = net.validate()
            assert "DL4J-W201" in report.codes()
            # a fresh model has no churn findings
            fresh = MultiLayerNetwork(_mlp_conf())
            assert "DL4J-W201" not in fresh.validate().codes()
        finally:
            det.threshold = old_threshold


class TestEntryPoints:
    def test_strict_init_raises_on_errors(self):
        conf = (_builder().list()
                .layer(DenseLayer(nIn=300, nOut=16))
                .layer(OutputLayer(nOut=4))
                .setInputType(InputType.feedForward(128))
                .build())
        net = MultiLayerNetwork(conf)
        with pytest.raises(ModelValidationError) as ei:
            net.init(strict=True)
        assert "DL4J-E001" in str(ei.value)
        net.init()                          # non-strict path unchanged
        assert net._initialized

    def test_strict_init_graph(self):
        g = (_graph_builder()
             .addLayer("fc", DenseLayer(nOut=8), "in")
             .addLayer("out", DenseLayer(nOut=2), "fc")   # not a loss head
             .setOutputs("out"))
        net = ComputationGraph(g.build())
        with pytest.raises(ModelValidationError):
            net.init(strict=True)

    def test_strict_init_passes_clean_model(self):
        net = MultiLayerNetwork(_mlp_conf())
        net.init(strict=True)
        assert net._initialized

    def test_validate_runs_no_jax_trace(self):
        # validate() on an uninitialized net must not allocate params
        net = MultiLayerNetwork(_mlp_conf())
        net.validate()
        assert not net._initialized

    def test_tbptt_config_roundtrip(self):
        conf = (_builder().list()
                .layer(LSTM(nOut=8))
                .layer(RnnOutputLayer(nOut=2))
                .setInputType(InputType.recurrent(4, 10))
                .backpropType("tbptt", 16)
                .build())
        back = MultiLayerConfiguration.from_json(conf.to_json())
        assert back.backprop_type == "tbptt"
        assert back.tbptt_length == 16


class TestDidYouMean:
    def test_layer_kwarg_typo(self):
        with pytest.raises(TypeError, match=r"did you mean 'nOut'"):
            DenseLayer(nOutt=8)

    def test_layer_kwarg_unknown(self):
        with pytest.raises(TypeError, match="unknown config key"):
            ConvolutionLayer(nOut=8, zebra=1)

    def test_subclass_kwargs_still_accepted(self):
        layer = ConvolutionLayer(nOut=8, kernelSize=(3, 3),
                                 convolutionMode="same", hasBias=False)
        assert layer.mode == "same" and not layer.has_bias

    def test_builder_method_typo(self):
        with pytest.raises(AttributeError, match="did you mean 'updater'"):
            NeuralNetConfiguration.Builder().updatr(Sgd(0.1))

    def test_list_builder_method_typo(self):
        with pytest.raises(AttributeError, match="setInputType"):
            _builder().list().setInputTyp(InputType.feedForward(4))


class TestZooCleanBill:
    def test_every_zoo_model_is_clean(self):
        from deeplearning4j_tpu.models.zoo import all_zoo_models
        for name, net in all_zoo_models():
            report = analyze(net)
            assert report.ok(warnings_as_errors=True), \
                f"{name} not clean:\n{report.format()}"

    def test_fixture_configs_are_clean(self):
        fixtures = [
            _mlp_conf(),
            (_builder().list()
             .layer(ConvolutionLayer(nOut=8, kernelSize=(3, 3)))
             .layer(SubsamplingLayer(kernelSize=(2, 2), stride=(2, 2)))
             .layer(DenseLayer(nOut=16, activation="relu"))
             .layer(OutputLayer(nOut=2))
             .setInputType(InputType.convolutional(12, 12, 1))
             .build()),
            (_builder().list()
             .layer(LSTM(nOut=8))
             .layer(RnnOutputLayer(nOut=3))
             .setInputType(InputType.recurrent(5, 7))
             .build()),
        ]
        for conf in fixtures:
            report = conf.validate()
            assert report.ok(warnings_as_errors=True), report.format()

    def test_documented_code_table_is_complete(self):
        assert len(DIAGNOSTIC_CODES) >= 10
        for code in DIAGNOSTIC_CODES:
            assert code.startswith("DL4J-")
        with pytest.raises(ValueError):
            Diagnostic("DL4J-E999", Severity.ERROR, "x", "undocumented")


class TestPureStatic:
    """The analyzer is jax-free: no module-scope jax imports (AST check)
    and the package imports with jax blocked (subprocess check)."""

    @staticmethod
    def _module_scope_imports(tree):
        out = []

        def visit(stmts):
            for node in stmts:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue          # lazy imports are fine
                if isinstance(node, ast.Import):
                    out.extend(a.name for a in node.names)
                elif isinstance(node, ast.ImportFrom):
                    out.append(node.module or "")
                for field in ("body", "orelse", "finalbody", "handlers"):
                    sub = getattr(node, field, None)
                    if sub:
                        visit([s for s in sub if isinstance(s, ast.stmt)])
        visit(tree.body)
        return out

    def test_no_module_scope_jax_imports(self):
        pkg = pathlib.Path(analysis.__file__).parent
        for py in sorted(pkg.glob("*.py")):
            tree = ast.parse(py.read_text(encoding="utf-8"))
            for mod in self._module_scope_imports(tree):
                root = mod.split(".")[0]
                assert root not in ("jax", "jaxlib"), \
                    f"{py.name} imports {mod} at module scope"

    def test_analysis_package_imports_with_jax_blocked(self):
        code = (
            "import sys\n"
            "sys.modules['jax'] = None\n"           # ImportError on import
            "sys.modules['jax.numpy'] = None\n"
            "import deeplearning4j_tpu.analysis as a\n"
            "r = a.ValidationReport(subject='x')\n"
            "a.get_churn_detector().record('s', ((1,), 'f32', False))\n"
            "d = a.Diagnostic('DL4J-E001', a.Severity.ERROR, 'l', 'm')\n"
            "print('PURE-STATIC-OK')\n")
        proc = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "PURE-STATIC-OK" in proc.stdout


class TestEarlyStoppingMegasteps:
    def _train(self, steps_per_dispatch):
        from deeplearning4j_tpu.train.earlystopping import (
            DataSetLossCalculator, EarlyStoppingConfiguration,
            EarlyStoppingTrainer, MaxEpochsTerminationCondition)
        rng = np.random.RandomState(0)
        train = DataSet(rng.rand(32, 4).astype(np.float32), _one_hot(32))
        val = DataSet(rng.rand(16, 4).astype(np.float32), _one_hot(16, seed=1))
        net = MultiLayerNetwork(_mlp_conf()).init(seed=99)
        cfg = EarlyStoppingConfiguration.Builder() \
            .scoreCalculator(DataSetLossCalculator(
                ListDataSetIterator(val, 8))) \
            .epochTerminationConditions(MaxEpochsTerminationCondition(2)) \
            .build()
        trainer = EarlyStoppingTrainer(
            cfg, net, ListDataSetIterator(train, 8),
            steps_per_dispatch=steps_per_dispatch)
        result = trainer.fit()
        return net, result

    def test_k_step_path_matches_single_step(self):
        net1, res1 = self._train(1)
        net2, res2 = self._train(2)
        assert res1.total_epochs == res2.total_epochs == 2
        assert net1._iteration == net2._iteration == 8   # 4 batches x 2
        np.testing.assert_allclose(np.asarray(net1.params()),
                                   np.asarray(net2.params()),
                                   rtol=0, atol=0)       # bit-exact
        assert res2.best_score == pytest.approx(res1.best_score)

    def test_iteration_condition_checked_between_dispatches(self):
        from deeplearning4j_tpu.train.earlystopping import (
            DataSetLossCalculator, EarlyStoppingConfiguration,
            EarlyStoppingTrainer, MaxEpochsTerminationCondition,
            MaxScoreIterationTerminationCondition)
        rng = np.random.RandomState(0)
        train = DataSet(rng.rand(32, 4).astype(np.float32), _one_hot(32))
        net = MultiLayerNetwork(_mlp_conf()).init(seed=99)
        cfg = EarlyStoppingConfiguration.Builder() \
            .scoreCalculator(DataSetLossCalculator(
                ListDataSetIterator(train, 8))) \
            .epochTerminationConditions(MaxEpochsTerminationCondition(3)) \
            .iterationTerminationConditions(
                MaxScoreIterationTerminationCondition(-1.0)) \
            .build()
        result = EarlyStoppingTrainer(cfg, net,
                                      ListDataSetIterator(train, 8),
                                      steps_per_dispatch=2).fit()
        assert result.termination_reason == "IterationTerminationCondition"
        assert net._iteration == 2      # one 2-step dispatch, then stop


class TestCli:
    def test_zoo_lint_exits_zero(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        assert main(["--zoo"]) == 0
        out = capsys.readouterr().out
        assert "19 model(s) linted: 19 clean" in out

    def test_single_model_by_name(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        assert main(["LeNet"]) == 0
        assert "LeNet: clean" in capsys.readouterr().out

    def test_findings_fail_the_exit_code(self, capsys, tmp_path,
                                         monkeypatch):
        mod = tmp_path / "badmodel.py"
        mod.write_text(
            "from deeplearning4j_tpu.nn.config import (InputType,\n"
            "    NeuralNetConfiguration)\n"
            "from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer\n"
            "conf = (NeuralNetConfiguration.Builder().list()\n"
            "        .layer(DenseLayer(nIn=300, nOut=16))\n"
            "        .layer(OutputLayer(nOut=4))\n"
            "        .setInputType(InputType.feedForward(128))\n"
            "        .build())\n")
        monkeypatch.syspath_prepend(str(tmp_path))
        from deeplearning4j_tpu.analysis.__main__ import main
        assert main(["badmodel:conf"]) == 1
        assert "DL4J-E001" in capsys.readouterr().out


class TestRepoLintGate:
    def test_repo_lints_clean(self, capsys):
        spec = importlib.util.spec_from_file_location(
            "repo_lint", REPO / "tools" / "lint.py")
        lint = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lint)
        rc = lint.run_fallback(lint.DEFAULT_PATHS)
        out = capsys.readouterr().out
        assert rc == 0, f"repo lint found issues:\n{out}"


def _wide_mlp(n_in=4096, hidden=4096, n_out=2):
    """64 MiB hidden weight — big enough for the replicated-giant lints."""
    return (_builder().list()
            .layer(DenseLayer(nOut=hidden, activation="relu"))
            .layer(OutputLayer(nOut=n_out))
            .setInputType(InputType.feedForward(n_in))
            .build())


class TestMeshSpec:
    def test_parse_and_coerce(self):
        spec = MeshSpec.parse("data=4,model=2")
        assert spec.axes == {"data": 4, "model": 2}
        assert MeshSpec.coerce("data=8").size("data") == 8
        assert MeshSpec.coerce({"data": 2}).axes == {"data": 2}
        same = MeshSpec({"data": 2})
        assert MeshSpec.coerce(same) is same
        assert MeshSpec.coerce(None) is None

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError):
            MeshSpec.parse("data")
        with pytest.raises(ValueError):
            MeshSpec.parse("data=x")
        with pytest.raises(ValueError):
            MeshSpec.parse("")
        with pytest.raises(TypeError):
            MeshSpec.coerce(42)

    def test_coerce_runtime_device_mesh(self):
        from deeplearning4j_tpu.parallel.mesh import DeviceMesh
        dm = DeviceMesh.create(data=4, model=2)
        spec = MeshSpec.coerce(dm)
        assert spec.axes["data"] == 4 and spec.axes["model"] == 2
        assert dm.spec(hbm_gb=1.0).hbm_gb == 1.0

    def test_pipeline_stage_assignment(self):
        assert PipelineSpec(2).stage_of(4) == [0, 0, 1, 1]
        assert PipelineSpec(2, boundaries=[0, 3]).stage_of(4) == [0, 0, 0, 1]
        with pytest.raises(ValueError):
            PipelineSpec(2, boundaries=[1, 3]).stage_of(4)  # must start at 0
        with pytest.raises(ValueError):
            PipelineSpec(3, boundaries=[0, 2]).stage_of(4)  # count mismatch


class TestDistributionDiagnostics:
    """Seeded fixture per E1xx/W10x code + a clean-bill counterpart."""

    def test_e101_batch_not_divisible(self):
        report = _mlp_conf().validate(batch_size=6, mesh="data=4")
        assert "DL4J-E101" in report.codes()
        assert not report.ok()
        assert "DL4J-E101" not in _mlp_conf().validate(
            batch_size=8, mesh="data=4").codes()

    def test_e102_absent_axis_in_sharding_rule(self):
        report = _mlp_conf().validate(mesh="data=4",
                                      sharding={r"/W$": (None, "model")})
        assert "DL4J-E102" in report.codes()
        assert "DL4J-E102" not in _mlp_conf().validate(
            mesh="data=4,model=1", sharding={r"/W$": (None, "model")}).codes()

    def test_e102_pipeline_axis_absent_or_mismatched(self):
        conf = _mlp_conf()
        r1 = conf.validate(mesh="data=4", pipeline=PipelineSpec(2))
        assert "DL4J-E102" in r1.codes()
        r2 = conf.validate(mesh="data=2,pipe=4", pipeline=PipelineSpec(2))
        assert "DL4J-E102" in r2.codes()

    def test_e102_axes_product_vs_declared_devices(self):
        # ISSUE 6: a mesh declaration that no longer matches the physical
        # device count (the elastic-shrink misconfiguration) is an E102
        from deeplearning4j_tpu.analysis.distribution import MeshSpec
        report = _mlp_conf().validate(
            mesh=MeshSpec({"data": 8}, devices=4))
        assert "DL4J-E102" in report.codes()
        assert "DL4J-E102" not in _mlp_conf().validate(
            mesh=MeshSpec({"data": 4}, devices=4)).codes()
        # DeviceMesh.spec() declares its own (consistent) device count
        from deeplearning4j_tpu.parallel import DeviceMesh
        spec = DeviceMesh.data_parallel().spec()
        assert spec.devices == 8
        assert "DL4J-E102" not in _mlp_conf().validate(mesh=spec).codes()

    def test_e103_tie_split_across_stages(self):
        conf = (_builder().list()
                .layer(DenseLayer(nOut=8, tiedWith="emb"))
                .layer(DenseLayer(nOut=8))
                .layer(DenseLayer(nOut=8))
                .layer(OutputLayer(nOut=8, tiedWith="emb"))
                .setInputType(InputType.feedForward(8))
                .build())
        report = conf.validate(mesh="pipe=2,data=1",
                               pipeline=PipelineSpec(2))
        assert "DL4J-E103" in report.codes()
        # same tie group within one stage: clean
        one_stage = (_builder().list()
                     .layer(DenseLayer(nOut=8, tiedWith="emb"))
                     .layer(OutputLayer(nOut=8, tiedWith="emb"))
                     .layer(DenseLayer(nOut=8))
                     .layer(DenseLayer(nOut=8))
                     .setInputType(InputType.feedForward(8))
                     .build())
        r2 = analyze(one_stage, mesh="pipe=2,data=1",
                     pipeline=PipelineSpec(2))
        assert "DL4J-E103" not in r2.codes()
        assert "DL4J-E008" not in r2.codes() or True  # structure irrelevant

    def test_e104_hbm_budget(self):
        report = _wide_mlp().validate(mesh="data=8", hbm_gb=0.01)
        e104 = [d for d in report if d.code == "DL4J-E104"]
        assert e104 and "HBM budget" in DIAGNOSTIC_CODES["DL4J-E104"]
        assert "exceeds" in e104[0].message
        assert "DL4J-E104" not in _wide_mlp().validate(
            mesh="data=8", hbm_gb=16.0).codes()

    def test_w104_replicated_giant_with_idle_model_axis(self):
        report = _wide_mlp().validate(mesh="data=4,model=2")
        w104 = [d for d in report if d.code == "DL4J-W104"]
        assert w104 and "replicated" in w104[0].message
        # pure DP mesh: replication is the only layout — no warning
        assert "DL4J-W104" not in _wide_mlp().validate(mesh="data=8").codes()
        # sharded by rule: clean
        assert "DL4J-W104" not in _wide_mlp().validate(
            mesh="data=4,model=2",
            sharding={r"/W$": (None, "model")}).codes()

    def test_w105_pipeline_flop_imbalance(self):
        lop = (_builder().list()
               .layer(DenseLayer(nOut=2048, activation="relu"))   # heavy
               .layer(DenseLayer(nOut=8, activation="relu"))
               .layer(DenseLayer(nOut=8, activation="relu"))
               .layer(OutputLayer(nOut=2))
               .setInputType(InputType.feedForward(2048))
               .build())
        report = lop.validate(mesh="pipe=2,data=1",
                              pipeline=PipelineSpec(2))
        assert "DL4J-W105" in report.codes()
        balanced = (_builder().list()
                    .layer(DenseLayer(nOut=512, activation="relu"))
                    .layer(DenseLayer(nOut=512, activation="relu"))
                    .layer(DenseLayer(nOut=512, activation="relu"))
                    .layer(DenseLayer(nOut=512, activation="relu"))
                    .setInputType(InputType.feedForward(512))
                    .build())
        r2 = analyze(balanced, mesh="pipe=2,data=1",
                     pipeline=PipelineSpec(2))
        assert "DL4J-W105" not in r2.codes()

    def test_w106_sub_mxu_shard(self):
        rule = {r"DenseLayer/W$": (None, "model")}   # the 4096x4096 only
        report = _wide_mlp().validate(mesh="data=1,model=64", sharding=rule)
        w106 = [d for d in report if d.code == "DL4J-W106"]
        assert w106 and "MXU" in w106[0].message          # 4096/64 = 64 < 128
        # 4096/8 = 512 lanes per device: healthy
        assert "DL4J-W106" not in _wide_mlp().validate(
            mesh="data=1,model=8", sharding=rule).codes()

    def test_w106_non_divisible_shard(self):
        conf = (_builder().list()
                .layer(DenseLayer(nOut=4096, activation="relu"))
                .layer(OutputLayer(nOut=2))
                .setInputType(InputType.feedForward(4100))
                .build())
        report = conf.validate(mesh="data=1,model=8",
                               sharding={r"/W$": ("model", None)})
        w106 = [d for d in report if d.code == "DL4J-W106"]
        assert w106 and "does not divide" in w106[0].message  # 4100 % 8

    def test_w107_collective_volume(self):
        conf = (_builder().list()
                .layer(DenseLayer(nOut=16384, activation="relu"))
                .layer(OutputLayer(nOut=2))
                .setInputType(InputType.feedForward(16384))
                .build())
        report = conf.validate(mesh="data=8")
        w107 = [d for d in report if d.code == "DL4J-W107"]
        assert w107 and "allreduce" in w107[0].message
        assert "DL4J-W107" not in _mlp_conf().validate(mesh="data=8").codes()

    def test_mesh_replaces_w103_path(self):
        # with a declared mesh the divisibility finding is the E101 error,
        # not the softer W103 hint
        report = _mlp_conf().validate(batch_size=6, mesh="data=4")
        assert "DL4J-W103" not in report.codes()
        legacy = _mlp_conf().validate(batch_size=6, data_devices=4)
        assert "DL4J-W103" in legacy.codes()

    def test_graph_config_gets_distribution_lints(self):
        g = (_graph_builder()
             .addLayer("fc", DenseLayer(nOut=4096, nIn=4096), "in")
             .addLayer("out", OutputLayer(nOut=2), "fc")
             .setOutputs("out"))
        report = analyze(g.build(), mesh="data=4,model=2")
        assert "DL4J-W104" in report.codes()

    def test_parallel_wrapper_validate(self):
        from deeplearning4j_tpu.parallel.mesh import DeviceMesh
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        net = MultiLayerNetwork(_mlp_conf())
        pw = ParallelWrapper(net, mesh=DeviceMesh.data_parallel())
        report = pw.validate(batch_size=6)          # 6 % 8 != 0
        assert "DL4J-E101" in report.codes()
        assert "DL4J-E101" not in pw.validate(batch_size=16).codes()

    def test_zoo_clean_under_data8_mesh(self):
        # zero=True: a data-parallel training plan that shards the
        # updater state is the recommended shipping config (ISSUE 15) —
        # without it the big Adam-state models legitimately earn W109,
        # which TestDistributionAnalysis pins separately
        from deeplearning4j_tpu.models.zoo import all_zoo_models
        for name, net in all_zoo_models():
            if name in ("Xing4", "LFM2"):   # pinned alone: the next tests
                continue
            report = analyze(net, mesh="data=8", zero=True)
            assert report.ok(warnings_as_errors=True), \
                f"{name} not clean under data=8:\n{report.format()}"

    def test_xing4_under_data8_mesh(self):
        # the published 40 layers hold 29 B parameters: replicated over a
        # data mesh they rightly overflow a chip (E104) and their expert
        # and vocabulary tensors rightly earn the all-reduce warning, and
        # nothing else; the share one chip holds under expert parallelism
        # (the size the benchmark trains) is clean
        from deeplearning4j_tpu.models.zoo import Xing4
        report = analyze(Xing4().conf_builder(), mesh="data=8", zero=True)
        assert set(report.codes()) == {"DL4J-E104", "DL4J-W107"}
        e104, = [d for d in report.diagnostics if d.code == "DL4J-E104"]
        assert "140.99 GiB" in e104.message
        warned = {d.location.split("'")[1] for d in report.diagnostics
                  if d.code == "DL4J-W107"}
        assert warned == {"embed", "lm", "mtp_moe"} | {
            f"l{i}_moe" for i in range(2, 40)}
        cut = analyze(Xing4.for_cost_gate().conf_builder(), mesh="data=8",
                      zero=True)
        assert cut.ok(warnings_as_errors=True), cut.format()

    def test_lfm2_under_data8_mesh(self):
        # the published 40 layers hold 24 B parameters, all but 1.6 B in
        # the 38 expert layers' 64 experts: replicated over a data mesh
        # they earn the same two codes as the other sparse decoder, and
        # nothing else (grouped key/value projections, the short
        # convolutions and the tied head are sized by their own shapes:
        # a head tied to the embedding has no tensor to all-reduce); the
        # share one chip holds under expert parallelism is clean
        from deeplearning4j_tpu.models.zoo import LFM2
        report = analyze(LFM2().conf_builder(), mesh="data=8", zero=True)
        assert set(report.codes()) == {"DL4J-E104", "DL4J-W107"}
        e104, = [d for d in report.diagnostics if d.code == "DL4J-E104"]
        assert "111.03 GiB" in e104.message
        warned = {d.location.split("'")[1] for d in report.diagnostics
                  if d.code == "DL4J-W107"}
        assert warned == {f"l{i}_moe" for i in range(2, 40)}
        cut = analyze(LFM2.for_cost_gate().conf_builder(), mesh="data=8",
                      zero=True)
        assert cut.ok(warnings_as_errors=True), cut.format()

    def test_zoo_w109_without_zero_declaration(self):
        # the inverse pin: at least the heavyweight zoo configs DO warn
        # when a data=8 mesh trains with replicated optimizer state
        from deeplearning4j_tpu.models.zoo import VGG16
        report = analyze(VGG16().conf_builder(), mesh="data=8")
        assert "DL4J-W109" in report.codes()


class TestSuppressionConfig:
    def test_validate_suppress(self):
        conf = _mlp_conf(hidden=300)                 # seeds W101
        assert "DL4J-W101" in conf.validate().codes()
        report = conf.validate(suppress=["DL4J-W101"])
        assert "DL4J-W101" not in report.codes()
        # short spelling works too
        assert "DL4J-W101" not in conf.validate(suppress=["w101"]).codes()

    def test_validate_severity_override(self):
        conf = _mlp_conf(hidden=300)
        report = conf.validate(severity_overrides={"W101": "error"})
        w = [d for d in report if d.code == "DL4J-W101"]
        assert w and w[0].severity is Severity.ERROR
        assert not report.ok()
        down = conf.validate(severity_overrides={"W101": Severity.INFO})
        assert down.ok(warnings_as_errors=True)

    def test_unknown_code_rejected(self):
        with pytest.raises(ValueError, match="unknown diagnostic code"):
            _mlp_conf().validate(suppress=["W999"])
        with pytest.raises(ValueError, match="unknown severity"):
            _mlp_conf().validate(severity_overrides={"W101": "loud"})

    def test_strict_init_honors_suppression_semantics(self):
        # an upgraded warning fails strict init; a suppressed error passes
        conf = _mlp_conf(hidden=300)
        report = conf.validate(severity_overrides={"W101": "error"})
        with pytest.raises(ModelValidationError):
            report.raise_if_errors()

    def test_cli_suppress_and_severity(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        # W101 model fails by default, passes when suppressed
        import tests.test_analysis as self_mod          # noqa: F401
        rc_plain = main(["tests.test_analysis:_W101_FIXTURE"])
        assert rc_plain == 1
        rc_sup = main(["tests.test_analysis:_W101_FIXTURE",
                       "--suppress", "W101"])
        assert rc_sup == 0
        rc_info = main(["tests.test_analysis:_W101_FIXTURE",
                        "--severity", "W101=info"])
        assert rc_info == 0
        capsys.readouterr()


#: module-level fixture for the CLI suppression test (resolved by the
#: module:attr target syntax; callables are called with no args)
def _W101_FIXTURE():
    return _mlp_conf(hidden=300)


class TestCliMesh:
    def test_zoo_clean_under_mesh_flag(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        # --zero: see test_zoo_clean_under_data8_mesh (W109 otherwise)
        # 17 clean as ever; the other two are the 29 B and 24 B sparse
        # decoders, which no data mesh trains replicated
        # (test_xing4_under_data8_mesh, test_lfm2_under_data8_mesh)
        assert main(["--zoo", "--mesh", "data=8", "--zero"]) == 1
        out = capsys.readouterr().out
        assert "19 model(s) linted: 17 clean, 2 with findings (2 error(s)" \
            in out
        assert main(["--zoo", "--mesh", "data=8", "--zero",
                     "--suppress", "E104,W107"]) == 0
        assert "19 model(s) linted: 19 clean" in capsys.readouterr().out

    def test_mesh_flag_fails_bad_batch(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        rc = main(["LeNet", "--mesh", "data=8", "--batch-size", "6"])
        assert rc == 1
        assert "DL4J-E101" in capsys.readouterr().out

    def test_hbm_flag(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        rc = main(["VGG16", "--mesh", "data=8", "--hbm-gb", "0.01"])
        assert rc == 1
        assert "DL4J-E104" in capsys.readouterr().out


class TestSameDiffLint:
    def _mlp_graph(self):
        import jax.numpy as jnp                        # noqa: F401
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 3))
        labels = sd.placeHolder("labels", shape=(None, 2))
        rng = np.random.RandomState(0)
        w = sd.var("w", rng.randn(3, 2))
        b = sd.var("b", np.zeros(2))
        z = sd.nn.linear(x, w, b, name="z")
        sd.loss.softmaxCrossEntropy(labels, z, name="loss")
        sd.setLossVariables("loss")
        return sd

    def test_clean_bill(self):
        report = self._mlp_graph().validate()
        assert report.ok(warnings_as_errors=True), report.format()
        assert report.subject == "SameDiff"

    def test_e151_undefined_input(self):
        sd = self._mlp_graph()
        sd._nodes[0].inputs[0] = "ghost"    # simulate a corrupted load
        report = sd.validate()
        assert "DL4J-E151" in report.codes()

    def test_e152_matmul_conflict(self):
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        sd = SameDiff.create()
        a = sd.var("a", np.zeros((3, 4)))
        b = sd.var("b", np.zeros((5, 6)))
        a.mmul(b)
        report = sd.validate()
        e = [d for d in report if d.code == "DL4J-E152"]
        assert e and "contracting dims" in e[0].message

    def test_e152_broadcast_conflict(self):
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        sd = SameDiff.create()
        p = sd.var("p", np.zeros((3, 4)))
        q = sd.var("q", np.zeros((3, 5)))
        p.add(q)
        assert "DL4J-E152" in sd.validate().codes()

    def test_e153_bad_loss_variable(self):
        sd = self._mlp_graph()
        sd.setLossVariables("loss", "no_such_var")
        assert "DL4J-E153" in sd.validate().codes()

    def test_w151_dangling_placeholder(self):
        sd = self._mlp_graph()
        sd.placeHolder("ghost", shape=(None, 3))
        report = sd.validate()
        w = [d for d in report if d.code == "DL4J-W151"]
        assert w and "ghost" in w[0].location

    def test_w152_unused_variable(self):
        sd = self._mlp_graph()
        sd.var("dead", np.zeros((4, 4)))
        report = sd.validate()
        w = [d for d in report if d.code == "DL4J-W152"]
        assert w and "dead" in w[0].location
        # ancestors of the loss are NOT flagged
        assert not any("'w'" in d.location for d in w)

    def test_w153_training_config_without_loss(self):
        from deeplearning4j_tpu.autodiff.samediff import (SameDiff,
                                                          TrainingConfig)
        sd = SameDiff.create()
        sd.var("v", np.zeros((2, 2)))
        sd.setTrainingConfig(TrainingConfig())
        assert "DL4J-W153" in sd.validate().codes()
        sd2 = self._mlp_graph()
        sd2.setTrainingConfig(TrainingConfig())
        assert "DL4J-W153" not in sd2.validate().codes()

    def test_unknown_ops_degrade_gracefully(self):
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 3, 8))
        y = sd.cnn.conv1d(x, sd.var("w", np.zeros((4, 3, 3))))
        (y + y).sum()
        report = sd.validate()                 # no rule for conv1d: no lie
        assert "DL4J-E152" not in report.codes()

    def test_suppress_applies_to_samediff(self):
        sd = self._mlp_graph()
        sd.var("dead", np.zeros((4, 4)))
        assert "DL4J-W152" not in sd.validate(
            suppress=["W152"]).codes()


class TestTbpttFitWiring:
    """fit() honors backpropType('tbptt')/tBPTTLength — equivalent to
    manual fitTBPTT segment fits (clears PR 3's W002 'declared but
    unwired' caveat)."""

    def _net(self, tbptt):
        b = (_builder(Sgd(0.05)).list()
             .layer(LSTM(nOut=6))
             .layer(RnnOutputLayer(nOut=2, lossFunction="mcxent"))
             .setInputType(InputType.recurrent(3, 12)))
        if tbptt:
            b = b.backpropType("tbptt", 4)
        return MultiLayerNetwork(b.build()).init(seed=11)

    def _seq_data(self):
        rng = np.random.RandomState(0)
        feats = rng.rand(5, 3, 12).astype(np.float32)
        labs = np.zeros((5, 2, 12), np.float32)
        labs[::2, 0] = 1.0
        labs[1::2, 1] = 1.0
        return DataSet(feats, labs)

    def test_fit_equals_manual_segment_fits(self):
        ds = self._seq_data()
        auto = self._net(True)
        auto.fit(ds, epochs=2)
        manual = self._net(False)
        for _ in range(2):
            manual.fitTBPTT(ds, 4)
        assert auto._iteration == manual._iteration == 6   # 3 seg x 2 ep
        np.testing.assert_array_equal(np.asarray(auto.params()),
                                      np.asarray(manual.params()))

    def test_fit_differs_from_standard_backprop(self):
        ds = self._seq_data()
        tb = self._net(True)
        tb.fit(ds, epochs=1)
        std = self._net(False)
        std.fit(ds, epochs=1)
        assert tb._iteration == 3 and std._iteration == 1
        assert not np.array_equal(np.asarray(tb.params()),
                                  np.asarray(std.params()))

    def test_non_sequence_batch_falls_back(self):
        conf = (_builder(Sgd(0.1)).list()
                .layer(DenseLayer(nOut=8, activation="relu"))
                .layer(OutputLayer(nOut=2))
                .setInputType(InputType.feedForward(4))
                .backpropType("tbptt", 4)
                .build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(0)
        net.fit(DataSet(rng.rand(6, 4).astype(np.float32), _one_hot(6)))
        assert net._iteration == 1              # plain step, no segments


class TestPureStaticDistribution:
    """Distribution + SameDiff passes run with jax BLOCKED: both operate
    on duck-typed declared shapes only."""

    def test_passes_run_with_jax_blocked(self):
        code = (
            "import sys\n"
            "sys.modules['jax'] = None\n"
            "sys.modules['jax.numpy'] = None\n"
            "from types import SimpleNamespace as NS\n"
            "from deeplearning4j_tpu.analysis import (MeshSpec,\n"
            "    PipelineSpec, analyze_samediff)\n"
            "from deeplearning4j_tpu.analysis.distribution import "
            "lint_entries\n"
            "class FakeLayer:\n"
            "    name = 'fc'\n"
            "    tied_with = None\n"
            "    def param_shapes(self):\n"
            "        return {'W': (4096, 50000), 'b': (50000,)}\n"
            "entries = [('layer 0 (FakeLayer)', FakeLayer(), None, None)]\n"
            "mesh = MeshSpec({'data': 8, 'model': 2}, hbm_gb=0.05)\n"
            "codes = {d.code for d in lint_entries(entries, mesh, 6,\n"
            "                                      'float32')}\n"
            "assert 'DL4J-E101' in codes, codes\n"
            "assert 'DL4J-E104' in codes, codes\n"
            "assert 'DL4J-W104' in codes, codes\n"
            "class Arr:\n"
            "    def __init__(self, shape):\n"
            "        self.shape = shape\n"
            "        self.dtype = 'float32'\n"
            "class Node:\n"
            "    def __init__(self, op, ins, outs):\n"
            "        self.op, self.inputs, self.outputs = op, ins, outs\n"
            "        self.attrs = {}\n"
            "sd = NS(_nodes=[Node('matmul', ['a', 'b'], ['c'])],\n"
            "        _placeholders={}, _constants={},\n"
            "        _variables={'a': Arr((3, 4)), 'b': Arr((5, 6))},\n"
            "        _loss_variables=[], training_config=None)\n"
            "r = analyze_samediff(sd)\n"
            "assert 'DL4J-E152' in [d.code for d in r], r.format()\n"
            "print('PURE-STATIC-DIST-OK')\n")
        proc = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "PURE-STATIC-DIST-OK" in proc.stdout

    def test_new_code_families_documented(self):
        for code in ("DL4J-E101", "DL4J-E102", "DL4J-E103", "DL4J-E104",
                     "DL4J-W104", "DL4J-W105", "DL4J-W106", "DL4J-W107",
                     "DL4J-E151", "DL4J-E152", "DL4J-E153", "DL4J-W151",
                     "DL4J-W152", "DL4J-W153",
                     "DL4J-E161", "DL4J-E162", "DL4J-E163", "DL4J-W161",
                     "DL4J-W162", "DL4J-W163"):
            assert code in DIAGNOSTIC_CODES


class TestReviewRegressions:
    """Pins for the review findings on the distribution/samediff passes."""

    def test_unknown_nonbatch_placeholder_dim_stays_unknown(self):
        # (None, None) placeholder: only dim 0 is the batch — a free
        # feature dim must not fabricate an E152 against W's rows
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, None))
        w = sd.var("w", np.zeros((3, 2)))
        b = sd.var("b", np.zeros(2))
        sd.nn.linear(x, w, b, name="z")
        assert "DL4J-E152" not in sd.validate(batch_size=4).codes()

    def test_e104_budgets_the_heaviest_pipeline_stage(self):
        conf = (_builder().list()                      # 64 MiB per layer
                .layer(DenseLayer(nOut=4096, activation="relu"))
                .layer(DenseLayer(nOut=4096, activation="relu"))
                .setInputType(InputType.feedForward(4096))
                .build())
        mesh = "pipe=2,data=1"
        # total 128 MiB, but each stage holds 64 MiB: a 0.1 GiB budget
        # passes under the pipeline split and fails without it
        ok = analyze(conf, mesh=mesh, pipeline=PipelineSpec(2),
                     hbm_gb=0.1)
        assert "DL4J-E104" not in ok.codes(), ok.format()
        flat = analyze(conf, mesh="data=1", hbm_gb=0.1)
        assert "DL4J-E104" in flat.codes()
        tight = analyze(conf, mesh=mesh, pipeline=PipelineSpec(2),
                        hbm_gb=0.05)
        e = [d for d in tight if d.code == "DL4J-E104"]
        assert e and "pipeline stage" in e[0].location

    def test_w107_clears_when_tensor_is_sharded(self):
        conf = (_builder().list()
                .layer(DenseLayer(nOut=16384, activation="relu"))
                .layer(OutputLayer(nOut=2))
                .setInputType(InputType.feedForward(16384))
                .build())
        assert "DL4J-W107" in conf.validate(mesh="data=8,model=4").codes()
        sharded = conf.validate(mesh="data=8,model=4",
                                sharding={r"DenseLayer/W$": (None, "model")})
        assert "DL4J-W107" not in sharded.codes(), sharded.format()

    def test_hbm_without_mesh_is_an_error_not_a_noop(self):
        with pytest.raises(ValueError, match="mesh"):
            _mlp_conf().validate(hbm_gb=0.001)

    def test_samediff_mesh_kwargs_run_distribution_lints(self):
        # ISSUE 18 flipped this pin: mesh= on a recorded graph now runs
        # the distribution family instead of raising
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 4))
        w = sd.var("w", np.zeros((4, 2), np.float32))
        x.mmul(w)
        report = sd.validate(batch_size=12, mesh="data=8")
        assert "DL4J-E101" in report.codes(), report.format()
        # input_pipeline stays native-config-only
        with pytest.raises(ValueError, match="input_pipeline"):
            sd.validate(input_pipeline="workers=8,batch=256")

    def test_cli_rejects_unknown_codes_cleanly(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        with pytest.raises(SystemExit) as ei:
            main(["LeNet", "--suppress", "W999"])
        assert ei.value.code == 2                      # argparse usage error
        assert "unknown diagnostic code" in capsys.readouterr().err
        with pytest.raises(SystemExit):
            main(["LeNet", "--severity", "W101=loud"])
        with pytest.raises(SystemExit):
            main(["LeNet", "--hbm-gb", "1"])           # no --mesh
        capsys.readouterr()


# --------------------------------------------------------------- ISSUE 8
def _lint_src(tmp_path, source, name="fixture.py", **kw):
    """Write a source fixture and run the concurrency analyzer on it."""
    from deeplearning4j_tpu.analysis.concurrency import analyze_concurrency
    p = tmp_path / name
    p.write_text(source)
    return analyze_concurrency(str(p), **kw)


_E201_BAD = """
import threading

class Worker:
    def __init__(self):
        self.state = "idle"
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        self.state = "running"

    def close(self):
        self._thread.join()
        self.state = "closed"
"""

_E201_CLEAN = """
import threading

class Worker:
    def __init__(self):
        self.state = "idle"
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        with self._lock:
            self.state = "running"

    def close(self):
        self._thread.join()
        with self._lock:
            self.state = "closed"
"""

_E202_BAD = """
import threading

class Stats:
    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()
        self._thread = threading.Thread(target=self._serve, daemon=True)

    def _serve(self):
        self.count += 1

    def snapshot(self):
        return self.count

    def close(self):
        self._thread.join()
"""

_E203_BAD = """
import threading

class A:
    def __init__(self, b: "B"):
        self._lock = threading.Lock()
        self.b = b

    def poke(self):
        with self._lock:
            self.b.poke_back()

    def locked_op(self):
        with self._lock:
            pass

class B:
    def __init__(self, a: "A"):
        self._lock = threading.Lock()
        self.a = a

    def poke_back(self):
        with self._lock:
            pass

    def reverse(self):
        with self._lock:
            self.a.locked_op()
"""

_W210_BAD = """
import time

class Retry:
    def expired(self, deadline):
        return time.time() > deadline

    def backoff(self, started):
        return time.time() - started
"""

_W211_BAD = """
import threading

class Q:
    def __init__(self):
        self._cond = threading.Condition()
        self.items = []

    def take(self):
        with self._cond:
            self._cond.wait(1.0)
            return self.items.pop()
"""

_W211_CLEAN = """
import threading

class Q:
    def __init__(self):
        self._cond = threading.Condition()
        self.items = []

    def take(self):
        with self._cond:
            while not self.items:
                self._cond.wait(1.0)
            return self.items.pop()
"""

_W212_BAD = """
import threading

class Server:
    def __init__(self):
        self._worker = threading.Thread(target=self._serve, daemon=True)
        self._worker.start()

    def _serve(self):
        pass

    def close(self):
        pass
"""

_W213_BAD = """
import threading

class Cache:
    def __init__(self):
        self._lock = threading.Lock()
        self._table = None
        self._thread = threading.Thread(target=self._refresh, daemon=True)

    def _refresh(self):
        with self._lock:
            pass

    def table(self):
        if self._table is None:
            self._table = {}
        return self._table

    def close(self):
        self._thread.join()
"""

_W213_CLEAN = """
import threading

class Cache:
    def __init__(self):
        self._lock = threading.Lock()
        self._table = None
        self._thread = threading.Thread(target=self._refresh, daemon=True)

    def _refresh(self):
        with self._lock:
            pass

    def table(self):
        with self._lock:
            if self._table is None:
                self._table = {}
            return self._table

    def close(self):
        self._thread.join()
"""


class TestConcurrencyDiagnostics:
    """ISSUE 8: one seeded bad fixture + clean bill per E2xx/W21x code."""

    def test_e201_unguarded_cross_thread_mutation(self, tmp_path):
        report = _lint_src(tmp_path, _E201_BAD)
        assert report.codes().count("DL4J-E201") == 2
        assert "state" in report.errors()[0].message

    def test_e201_clean_when_guarded(self, tmp_path):
        report = _lint_src(tmp_path, _E201_CLEAN, name="clean.py")
        assert report.codes() == []

    def test_e202_read_modify_write(self, tmp_path):
        report = _lint_src(tmp_path, _E202_BAD)
        assert "DL4J-E202" in report.codes()
        assert "lost" in report.format() or "loses" in report.format()

    def test_e202_clean_under_lock(self, tmp_path):
        clean = _E202_BAD.replace(
            "        self.count += 1",
            "        with self._lock:\n            self.count += 1")
        report = _lint_src(tmp_path, clean, name="clean.py")
        assert report.codes() == []

    def test_e203_lock_order_cycle(self, tmp_path):
        report = _lint_src(tmp_path, _E203_BAD)
        assert "DL4J-E203" in report.codes()
        assert "A._lock" in report.format()
        # the cycle must anchor to a real source line (line 0 is
        # untriageable and un-noqa-able)
        for d in report:
            if d.code == "DL4J-E203":
                assert ":0" not in d.location, d.location
        assert "B._lock" in report.format()

    def test_e203_not_shadowed_by_same_named_class(self, tmp_path):
        # an unrelated same-named class in an earlier-scanned file must
        # not shadow the real one out of the lock graph
        from deeplearning4j_tpu.analysis.concurrency import \
            analyze_concurrency
        (tmp_path / "a_first.py").write_text(
            "class A:\n    def m(self):\n        pass\n"
            "class B:\n    def m(self):\n        pass\n")
        (tmp_path / "b_cycle.py").write_text(_E203_BAD)
        report = analyze_concurrency(str(tmp_path))
        assert "DL4J-E203" in report.codes()

    def test_e202_inside_match_statement(self, tmp_path):
        src = _E202_BAD.replace(
            "        self.count += 1",
            "        match self.count:\n"
            "            case _:\n"
            "                self.count += 1")
        report = _lint_src(tmp_path, src)
        assert "DL4J-E202" in report.codes()

    def test_e203_clean_when_one_order(self, tmp_path):
        # B.reverse now calls A outside its own lock: edges stay A->B only
        clean = _E203_BAD.replace(
            "    def reverse(self):\n"
            "        with self._lock:\n"
            "            self.a.locked_op()",
            "    def reverse(self):\n"
            "        self.a.locked_op()")
        assert "with self._lock:\n            self.a" not in clean
        report = _lint_src(tmp_path, clean, name="clean.py")
        assert report.codes() == []

    def test_w210_wall_clock_deadline(self, tmp_path):
        report = _lint_src(tmp_path, _W210_BAD)
        assert report.codes().count("DL4J-W210") == 2

    def test_w210_clean_monotonic_and_timestamps(self, tmp_path):
        clean = _W210_BAD.replace("time.time()", "time.monotonic()")
        # a recorded wall-clock timestamp (no arithmetic) stays legal
        clean += "\n\ndef stamp(record):\n"
        clean += "    record['timestamp'] = time.time()\n"
        report = _lint_src(tmp_path, clean, name="clean.py")
        assert report.codes() == []

    def test_w210_attr_assigned_then_subtracted(self, tmp_path):
        src = ("import time\n\n"
               "class T:\n"
               "    def start(self):\n"
               "        self.t0 = time.time()\n"
               "    def elapsed(self):\n"
               "        return time.time() - self.t0\n")
        report = _lint_src(tmp_path, src)
        assert "DL4J-W210" in report.codes()

    def test_w211_wait_without_predicate_loop(self, tmp_path):
        report = _lint_src(tmp_path, _W211_BAD)
        assert "DL4J-W211" in report.codes()

    def test_w211_clean_in_while(self, tmp_path):
        report = _lint_src(tmp_path, _W211_CLEAN, name="clean.py")
        assert "DL4J-W211" not in report.codes()

    def test_w212_thread_never_joined(self, tmp_path):
        report = _lint_src(tmp_path, _W212_BAD)
        assert "DL4J-W212" in report.codes()

    def test_w212_clean_with_join(self, tmp_path):
        clean = _W212_BAD.replace("    def close(self):\n        pass",
                                  "    def close(self):\n"
                                  "        self._worker.join(timeout=5)")
        report = _lint_src(tmp_path, clean, name="clean.py")
        assert "DL4J-W212" not in report.codes()

    def test_w213_unlocked_lazy_init(self, tmp_path):
        report = _lint_src(tmp_path, _W213_BAD)
        assert "DL4J-W213" in report.codes()

    def test_w213_clean_checked_under_lock(self, tmp_path):
        report = _lint_src(tmp_path, _W213_CLEAN, name="clean.py")
        assert "DL4J-W213" not in report.codes()

    def test_inline_noqa_suppresses(self, tmp_path):
        src = _E202_BAD.replace("        self.count += 1",
                                "        self.count += 1  # dl4j: noqa=E202")
        report = _lint_src(tmp_path, src)
        assert "DL4J-E202" not in report.codes()

    def test_noqa_tolerates_spaces_and_trailing_prose(self, tmp_path):
        # 'noqa = E202' must suppress E202 (and ONLY E202), and trailing
        # words after the code list must not corrupt the code set
        for comment in ("# dl4j: noqa = E202",
                        "# dl4j: noqa=E202 reviewed, see PR 8"):
            src = _E202_BAD.replace(
                "        self.count += 1",
                f"        self.count += 1  {comment}")
            report = _lint_src(tmp_path, src)
            assert "DL4J-E202" not in report.codes(), comment

    def test_noqa_with_garbage_codes_suppresses_nothing(self, tmp_path):
        src = _E202_BAD.replace(
            "        self.count += 1",
            "        self.count += 1  # dl4j: noqa=notacode")
        report = _lint_src(tmp_path, src)
        assert "DL4J-E202" in report.codes()

    def test_unparseable_file_is_e299_not_e201(self, tmp_path):
        report = _lint_src(tmp_path, "def broken(:\n")
        assert "DL4J-E299" in report.codes()
        assert "DL4J-E201" not in report.codes()
        # grandfathering a real finding family must NOT hide syntax errors
        report = _lint_src(tmp_path, "def broken(:\n", suppress=["E201"])
        assert "DL4J-E299" in report.codes()

    def test_suppress_and_severity_config(self, tmp_path):
        report = _lint_src(tmp_path, _E202_BAD, suppress=["E202"])
        assert "DL4J-E202" not in report.codes()
        report = _lint_src(tmp_path, _W212_BAD, name="w.py",
                           severity_overrides={"W212": "error"})
        codes = {d.code: d.severity for d in report}
        assert codes["DL4J-W212"] is Severity.ERROR

    def test_unthreaded_unlocked_class_is_exempt(self, tmp_path):
        # plain single-threaded mutable state must not be flagged
        src = ("class Plain:\n"
               "    def __init__(self):\n"
               "        self.count = 0\n"
               "    def inc(self):\n"
               "        self.count += 1\n")
        report = _lint_src(tmp_path, src, name="clean.py")
        assert report.codes() == []

    def test_new_codes_documented(self):
        for code in ("DL4J-E201", "DL4J-E202", "DL4J-E203", "DL4J-W210",
                     "DL4J-W211", "DL4J-W212", "DL4J-W213", "DL4J-E299"):
            assert code in DIAGNOSTIC_CODES


class TestConcurrencyCli:
    def test_cli_path_target_bad_fixture(self, tmp_path, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        p = tmp_path / "bad.py"
        p.write_text(_E202_BAD)
        assert main(["--concurrency", str(p)]) == 1
        assert "DL4J-E202" in capsys.readouterr().out

    def test_cli_module_target_repo_clean(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        assert main(["--concurrency", "deeplearning4j_tpu.serving"]) == 0
        assert "clean" in capsys.readouterr().out

    def test_cli_bad_target_is_clean_usage_error(self, capsys):
        # a typo'd module and an unlintable builtin must be one-line
        # argparse errors (exit 2), not raw tracebacks
        from deeplearning4j_tpu.analysis.__main__ import main
        for target in ("definitely_not_a_module_xyz", "sys"):
            with pytest.raises(SystemExit) as exc:
                main(["--concurrency", target])
            assert exc.value.code == 2
            assert "--concurrency" in capsys.readouterr().err

    def test_cli_suppress_applies(self, tmp_path, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        p = tmp_path / "bad.py"
        p.write_text(_W212_BAD)
        assert main(["--concurrency", str(p), "--suppress", "W212"]) == 0
        capsys.readouterr()

    def test_cli_rejects_mixed_targets(self, tmp_path, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        p = tmp_path / "bad.py"
        p.write_text(_W212_BAD)
        with pytest.raises(SystemExit):
            main(["--concurrency", str(p), "LeNet"])
        capsys.readouterr()


class TestConcurrencySelfLint:
    """The repo lints itself clean — the gate that keeps the E2xx bug
    class out of the package from here on (ISSUE 8 acceptance)."""

    def _lint_mod(self):
        spec = importlib.util.spec_from_file_location(
            "repo_lint", REPO / "tools" / "lint.py")
        lint = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lint)
        return lint

    def test_package_concurrency_clean(self, capsys):
        lint = self._lint_mod()
        rc = lint.run_concurrency()
        out = capsys.readouterr().out
        assert rc == 0, f"concurrency self-lint found issues:\n{out}"

    def test_pyproject_suppressions_parse(self):
        lint = self._lint_mod()
        assert isinstance(lint._pyproject_concurrency_suppress(), list)

    def test_pyproject_multiline_suppress_array(self, tmp_path):
        lint = self._lint_mod()
        (tmp_path / "pyproject.toml").write_text(
            "[tool.dl4j.concurrency]\n"
            "suppress = [\n"
            '    "W212",  # see [tool.other] "docs"]\n'
            '    "E201",\n'
            "]\n")
        old = lint.REPO
        try:
            lint.REPO = tmp_path
            assert lint._pyproject_concurrency_suppress() == ["W212", "E201"]
        finally:
            lint.REPO = old

    def test_typod_suppress_code_is_clean_usage_error(self, tmp_path, capsys):
        lint = self._lint_mod()
        (tmp_path / "pyproject.toml").write_text(
            "[tool.dl4j.concurrency]\n"
            'suppress = ["NOTACODE1"]\n')
        (tmp_path / "empty.py").write_text("x = 1\n")
        old = lint.REPO
        try:
            lint.REPO = tmp_path
            rc = lint.run_concurrency(["empty.py"])
        finally:
            lint.REPO = old
        assert rc == 1
        assert "bad suppress config" in capsys.readouterr().out

    def test_pyproject_suppressions_survive_other_keys(self, tmp_path):
        # other keys, comments with '[', and a following section must not
        # silently defeat the scoped parse
        lint = self._lint_mod()
        (tmp_path / "pyproject.toml").write_text(
            "[tool.dl4j.concurrency]\n"
            "# see [analysis] docs\n"
            'paths = ["deeplearning4j_tpu"]\n'
            'suppress = ["W212", "E201"]\n'
            "[tool.other]\n"
            'suppress = ["W999"]\n')
        old = lint.REPO
        try:
            lint.REPO = tmp_path
            assert lint._pyproject_concurrency_suppress() == ["W212", "E201"]
        finally:
            lint.REPO = old

    def test_gate_fails_on_seeded_regression(self, tmp_path, capsys):
        # the gate must actually have teeth: a bad file inside the tree
        # it lints turns the exit code red
        lint = self._lint_mod()
        bad = tmp_path / "racy.py"
        bad.write_text(_E202_BAD)
        assert lint.run_concurrency([bad.relative_to(REPO)
                                     if bad.is_relative_to(REPO)
                                     else str(bad)]) == 1
        capsys.readouterr()


class TestPureStaticConcurrency:
    """The concurrency pass runs with jax BLOCKED — it reads source
    text, never imports the target (matching the distribution/samediff
    pins)."""

    def test_runs_with_jax_blocked(self):
        code = (
            "import sys\n"
            "sys.modules['jax'] = None\n"
            "sys.modules['jax.numpy'] = None\n"
            "from deeplearning4j_tpu.analysis.concurrency import "
            "analyze_concurrency\n"
            "r = analyze_concurrency('deeplearning4j_tpu/serving')\n"
            "assert r.codes() == [], r.format()\n"
            # and the full-package run stays clean too — over files that
            # themselves import jax (never executed, only parsed)
            "r = analyze_concurrency('deeplearning4j_tpu')\n"
            "assert r.codes() == [], r.format()\n"
            "print('PURE-STATIC-CONCURRENCY-OK')\n")
        proc = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "PURE-STATIC-CONCURRENCY-OK" in proc.stdout


class TestInputPipelineLint:
    """DL4J-W108: can this host feed this chip (analysis/pipeline.py)."""

    def _conv_conf(self):
        return (NeuralNetConfiguration.Builder().list()
                .layer(ConvolutionLayer(nOut=64, kernelSize=(3, 3)))
                .layer(ConvolutionLayer(nOut=128, kernelSize=(3, 3)))
                .layer(DenseLayer(nOut=64, activation="relu"))
                .layer(OutputLayer(nOut=8))
                .setInputType(InputType.convolutional(64, 64, 3))
                .build())

    def test_starved_pipeline_flags_w108(self):
        from deeplearning4j_tpu.analysis import InputPipelineSpec, analyze
        spec = InputPipelineSpec(workers=1, batch_size=256,
                                 decode_ms_per_img=50.0, h2d_mbps=6.2,
                                 dtype="float32")
        report = analyze(self._conv_conf(), input_pipeline=spec)
        w108 = [d for d in report.diagnostics if d.code == "DL4J-W108"]
        assert len(w108) == 1
        assert "cannot feed this chip" in w108[0].message
        assert "uint8" in w108[0].fix_hint      # float32 link: suggest bytes

    def test_fed_pipeline_clean(self):
        from deeplearning4j_tpu.analysis import InputPipelineSpec, analyze
        spec = InputPipelineSpec(workers=256, batch_size=256,
                                 decode_ms_per_img=1.0, h2d_mbps=100000,
                                 dtype="uint8")
        report = analyze(self._conv_conf(), input_pipeline=spec)
        assert "DL4J-W108" not in [d.code for d in report.diagnostics]

    def test_measured_device_rate_overrides_estimate(self):
        from deeplearning4j_tpu.analysis import InputPipelineSpec, analyze
        # decode bound 2000 img/s: above a measured 1000 img/s device
        # rate (clean), below a measured 10000 img/s one (W108)
        base = dict(workers=2, batch_size=64, decode_ms_per_img=1.0,
                    dtype="uint8")
        clean = analyze(self._conv_conf(), input_pipeline=InputPipelineSpec(
            device_img_per_sec=1000, **base))
        assert "DL4J-W108" not in [d.code for d in clean.diagnostics]
        hot = analyze(self._conv_conf(), input_pipeline=InputPipelineSpec(
            device_img_per_sec=10000, **base))
        assert "DL4J-W108" in [d.code for d in hot.diagnostics]

    def test_spec_parse_and_coerce(self):
        from deeplearning4j_tpu.analysis import InputPipelineSpec
        s = InputPipelineSpec.parse(
            "workers=8,batch=256,decode_ms=1.3,h2d_mbps=6.2,hw=224,"
            "dtype=uint8,mfu=0.25")
        assert (s.workers, s.batch_size, s.height, s.width) == \
            (8, 256, 224, 224)
        assert s.assumed_mfu == 0.25
        assert InputPipelineSpec.coerce(s) is s
        d = InputPipelineSpec.coerce({"workers": 2, "batch_size": 32})
        assert d.workers == 2
        with pytest.raises(ValueError, match="known keys"):
            InputPipelineSpec.parse("wrkrs=8")
        with pytest.raises(ValueError, match="workers"):
            InputPipelineSpec.parse("batch=32")

    def test_w108_suppressible_and_documented(self):
        from deeplearning4j_tpu.analysis import InputPipelineSpec, analyze
        assert "DL4J-W108" in DIAGNOSTIC_CODES
        spec = InputPipelineSpec(workers=1, batch_size=256,
                                 decode_ms_per_img=50.0)
        report = analyze(self._conv_conf(), input_pipeline=spec,
                         suppress=["W108"])
        assert "DL4J-W108" not in [d.code for d in report.diagnostics]

    def test_cli_pipeline_flag(self, capsys, tmp_path, monkeypatch):
        mod = tmp_path / "feedmodel.py"
        mod.write_text(
            "from deeplearning4j_tpu.nn.config import (InputType,\n"
            "    NeuralNetConfiguration)\n"
            "from deeplearning4j_tpu.nn.layers import (ConvolutionLayer,\n"
            "    DenseLayer, OutputLayer)\n"
            "conf = (NeuralNetConfiguration.Builder().list()\n"
            "        .layer(ConvolutionLayer(nOut=64, kernelSize=(3, 3)))\n"
            "        .layer(DenseLayer(nOut=64, activation='relu'))\n"
            "        .layer(OutputLayer(nOut=8))\n"
            "        .setInputType(InputType.convolutional(64, 64, 3))\n"
            "        .build())\n")
        monkeypatch.syspath_prepend(str(tmp_path))
        from deeplearning4j_tpu.analysis.__main__ import main
        assert main(["feedmodel:conf", "--pipeline",
                     "workers=1,batch=256,decode_ms=50.0"]) == 1
        assert "DL4J-W108" in capsys.readouterr().out
        # typo'd spec: clean usage error, not a traceback
        with pytest.raises(SystemExit) as ei:
            main(["feedmodel:conf", "--pipeline", "wrkrs=1"])
        assert ei.value.code == 2

    def test_graph_config_needs_measured_rate(self):
        """Graph configs have no jax-free FLOP propagation: without a
        measured device rate the lint stays silent instead of guessing."""
        from deeplearning4j_tpu.analysis import InputPipelineSpec, analyze
        conf = (NeuralNetConfiguration.Builder().graphBuilder()
                .addInputs("in")
                .addLayer("c", ConvolutionLayer(nOut=8, kernelSize=(3, 3)),
                          "in")
                .addLayer("d", DenseLayer(nOut=16, activation="relu"), "c")
                .addLayer("out", OutputLayer(nOut=4), "d")
                .setOutputs("out")
                .setInputTypes(InputType.convolutional(16, 16, 3)))
        spec = InputPipelineSpec(workers=1, batch_size=64,
                                 decode_ms_per_img=50.0, height=16,
                                 width=16)
        r = analyze(conf, input_pipeline=spec)
        assert "DL4J-W108" not in [d.code for d in r.diagnostics]
        spec2 = InputPipelineSpec(workers=1, batch_size=64,
                                  decode_ms_per_img=50.0, height=16,
                                  width=16, device_img_per_sec=10000)
        r2 = analyze(conf, input_pipeline=spec2)
        assert "DL4J-W108" in [d.code for d in r2.diagnostics]


# ------------------------------------------------- numerics lints (ISSUE 11)
class TestNumericsDiagnostics:
    """E301-E303 / W301-W303: one seeded misconfiguration AND one clean
    bill per code, under explicit policies and DataRangeSpec input
    declarations."""

    def _mlp(self, updater=None, **layer_kw):
        from deeplearning4j_tpu.nn.layers import LossLayer  # noqa: F401
        return (_builder(updater).list()
                .layer(DenseLayer(nOut=16, activation="relu", **layer_kw))
                .layer(OutputLayer(nOut=3, lossFunction="mcxent",
                                   activation="softmax"))
                .setInputType(InputType.feedForward(8))
                .build())

    def test_e301_low_precision_updater_state(self):
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        conf = self._mlp(updater=Adam(1e-3))
        pol = PrecisionPolicy("float16", params="float16", loss_scale=1024)
        report = analyze(conf, policy=pol)
        assert "DL4J-E301" in report.codes()
        assert not report.ok()
        # fp32 masters (the default coercion): clean
        assert "DL4J-E301" not in analyze(conf, policy="fp16",
                                          suppress=["E303"]).codes()
        # stateless Sgd tolerates low-precision state declarations
        assert "DL4J-E301" not in analyze(
            self._mlp(), policy=pol).codes()

    def test_e301_contradicting_layer_override(self):
        conf = self._mlp(dataType="float16")
        report = analyze(conf, policy="bf16")
        assert "DL4J-E301" in report.codes()
        # matching override and explicit fp32 island are both fine
        assert "DL4J-E301" not in analyze(
            self._mlp(dataType="bf16"), policy="bf16").codes()
        assert "DL4J-E301" not in analyze(
            self._mlp(dataType="float32"), policy="bf16").codes()

    def test_e302_large_softmax_axis(self):
        conf = (_builder().list()
                .layer(DenseLayer(nOut=1024, activation="softmax"))
                .layer(OutputLayer(nOut=3))
                .setInputType(InputType.feedForward(8)).build())
        assert "DL4J-E302" in analyze(conf, policy="bf16").codes()
        # clean: fp32 policy, small axis, or an explicit fp32 island
        assert "DL4J-E302" not in analyze(conf).codes()
        small = (_builder().list()
                 .layer(DenseLayer(nOut=64, activation="softmax"))
                 .layer(OutputLayer(nOut=3))
                 .setInputType(InputType.feedForward(8)).build())
        assert "DL4J-E302" not in analyze(small, policy="bf16").codes()
        island = (_builder().list()
                  .layer(DenseLayer(nOut=1024, activation="softmax",
                                    dataType="float32"))
                  .layer(OutputLayer(nOut=3))
                  .setInputType(InputType.feedForward(8)).build())
        assert "DL4J-E302" not in analyze(island, policy="bf16").codes()

    def test_e302_loss_head_dragged_low(self):
        conf = (_builder().list()
                .layer(DenseLayer(nOut=16))
                .layer(OutputLayer(nOut=3, dataType="bf16"))
                .setInputType(InputType.feedForward(8)).build())
        assert "DL4J-E302" in analyze(conf, policy="bf16").codes()

    def test_e302_attention_timestep_axis(self):
        from deeplearning4j_tpu.nn.layers import (RnnOutputLayer,
                                                  SelfAttentionLayer)
        def att(t):
            return (_builder().list()
                    .layer(SelfAttentionLayer(nOut=64, nHeads=4,
                                              headSize=16))
                    .layer(RnnOutputLayer(nOut=3, lossFunction="mcxent"))
                    .setInputType(InputType.recurrent(64, t)).build())
        assert "DL4J-E302" in analyze(att(2048), policy="bf16").codes()
        assert "DL4J-E302" not in analyze(att(128), policy="bf16").codes()

    def test_e303_fp16_without_loss_scaling(self):
        conf = self._mlp()
        report = analyze(conf, policy="fp16")
        assert "DL4J-E303" in report.codes()
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        assert "DL4J-E303" not in analyze(
            conf, policy=PrecisionPolicy("float16",
                                         loss_scale=2 ** 15)).codes()

    def test_e303_yolo_overflow_fixture(self):
        """THE acceptance pin: the statically-reconstructed YOLO bug —
        raw [0, 255] input + fp16-class updater state — is E303 at
        validate() time."""
        from deeplearning4j_tpu.nn.layers import LossLayer
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        conf = (_builder(Adam(1e-3)).list()
                .layer(DenseLayer(nOut=32, activation="relu"))
                .layer(LossLayer(lossFunction="mse"))
                .setInputType(InputType.feedForward(16)).build())
        pol = PrecisionPolicy("float16", params="float16",
                              loss_scale=2 ** 15)
        report = conf.validate(policy=pol, data_range="0..255")
        assert "DL4J-E303" in report.codes(), report.format()
        # fp32 updater state holds the ~4e9 second moment: W303 only
        r32 = conf.validate(data_range="0..255")
        assert "DL4J-E303" not in r32.codes()
        assert "DL4J-W303" in r32.codes()
        # normalized input: both clean
        rn = conf.validate(policy=pol, data_range="0..1")
        assert "DL4J-E303" not in rn.codes()
        assert "DL4J-W303" not in rn.codes()

    def test_w301_fp32_sandwich(self):
        conf = (_builder().list()
                .layer(DenseLayer(nOut=16))
                .layer(DenseLayer(nOut=16, dataType="float32"))
                .layer(DenseLayer(nOut=16))
                .layer(OutputLayer(nOut=3))
                .setInputType(InputType.feedForward(8)).build())
        assert "DL4J-W301" in analyze(conf, policy="bf16").codes()
        # an island at the EDGE (before the fp32 loss head) is not churn
        edge = (_builder().list()
                .layer(DenseLayer(nOut=16))
                .layer(DenseLayer(nOut=16, dataType="float32"))
                .layer(OutputLayer(nOut=3))
                .setInputType(InputType.feedForward(8)).build())
        assert "DL4J-W301" not in analyze(edge, policy="bf16").codes()
        assert "DL4J-W301" not in analyze(conf).codes()


    def test_w301_sequential_only(self):
        """Review regression: W301 reasons about layer adjacency, which
        graph node order is not — the lint stays off for graphs."""
        g = (_graph_builder()
             .addLayer("a", DenseLayer(nOut=16), "in")
             .addLayer("b", DenseLayer(nOut=16, dataType="float32"), "in")
             .addLayer("c", DenseLayer(nOut=16), "in")
             .addLayer("m", DenseLayer(nOut=16), "a", "b")
             .addLayer("out", OutputLayer(nOut=2), "m")
             .setOutputs("out"))
        assert "DL4J-W301" not in analyze(g.build(), policy="bf16",
                                          suppress=["E003"]).codes()

    def test_w302_loss_scale_misconfigurations(self):
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        conf = self._mlp()
        assert "DL4J-W302" in analyze(
            conf, policy=PrecisionPolicy("bfloat16",
                                         loss_scale=1024)).codes()
        assert "DL4J-W302" in analyze(
            conf, policy=PrecisionPolicy("float16",
                                         loss_scale=0.5)).codes()
        assert "DL4J-W302" in analyze(
            conf, policy=PrecisionPolicy("float16",
                                         loss_scale=2.0 ** 30)).codes()
        assert "DL4J-W302" not in analyze(
            conf, policy=PrecisionPolicy("float16",
                                         loss_scale=2 ** 15)).codes()

    def test_w303_unnormalized_input(self):
        conf = self._mlp(updater=Adam(1e-3))
        assert "DL4J-W303" in analyze(conf, data_range="0..255").codes()
        assert "DL4J-W303" not in analyze(
            conf, data_range="0..255,normalized").codes()
        assert "DL4J-W303" not in analyze(conf, data_range="-1..1").codes()
        # a BatchNormalization FIRST does the normalizer's job
        from deeplearning4j_tpu.nn.layers import BatchNormalization
        bn = (_builder(Adam(1e-3)).list()
              .layer(BatchNormalization())
              .layer(DenseLayer(nOut=16, activation="relu"))
              .layer(OutputLayer(nOut=3))
              .setInputType(InputType.feedForward(8)).build())
        assert "DL4J-W303" not in analyze(bn, data_range="0..255").codes()

    def test_data_range_spec_parse_and_coerce(self):
        from deeplearning4j_tpu.analysis.numerics import DataRangeSpec
        r = DataRangeSpec.parse("0..255")
        assert (r.lo, r.hi, r.normalized) == (0.0, 255.0, False)
        assert DataRangeSpec.parse("-1..1,normalized").normalized
        assert DataRangeSpec.coerce((0, 255)).hi == 255
        assert DataRangeSpec.coerce({"lo": 0, "hi": 1}).max_abs == 1.0
        with pytest.raises(ValueError):
            DataRangeSpec.parse("255")
        with pytest.raises(ValueError):
            DataRangeSpec.parse("0..255,bogus")
        with pytest.raises(ValueError):
            DataRangeSpec(5, 1)
        with pytest.raises(TypeError):
            DataRangeSpec.coerce(object())

    def test_policy_resolution_precedence(self):
        """Explicit policy > attached network policy > config dataType."""
        from deeplearning4j_tpu.analysis.numerics import resolve_policy
        conf = self._mlp()
        assert resolve_policy(conf).compute == "float32"
        conf.base.dtype = "bfloat16"
        assert resolve_policy(conf).compute == "bfloat16"
        net = MultiLayerNetwork(self._mlp())
        net.setPrecisionPolicy("bf16")
        assert resolve_policy(net.conf, model=net).compute == "bfloat16"
        assert resolve_policy(net.conf, policy="fp16",
                              model=net).compute == "float16"

    def test_attached_policy_feeds_validate(self):
        net = MultiLayerNetwork((_builder().list()
                                 .layer(DenseLayer(nOut=1024,
                                                   activation="softmax"))
                                 .layer(OutputLayer(nOut=3))
                                 .setInputType(InputType.feedForward(8))
                                 .build()))
        assert "DL4J-E302" not in net.validate().codes()
        net.setPrecisionPolicy("bf16")
        assert "DL4J-E302" in net.validate().codes()

    def test_numerics_codes_documented_and_suppressible(self):
        for code in ("DL4J-E301", "DL4J-E302", "DL4J-E303",
                     "DL4J-W301", "DL4J-W302", "DL4J-W303"):
            assert code in DIAGNOSTIC_CODES
        conf = self._mlp(updater=Adam(1e-3))
        assert "DL4J-W303" not in analyze(conf, data_range="0..255",
                                          suppress=["W303"]).codes()

    def test_graph_config_numerics(self):
        g = (_graph_builder()
             .addLayer("fc", DenseLayer(nOut=16, dataType="float16"), "in")
             .addLayer("out", OutputLayer(nOut=2), "fc")
             .setOutputs("out"))
        assert "DL4J-E301" in analyze(g.build(), policy="bf16").codes()

    def test_zoo_clean_under_default_and_bf16(self):
        """CI gate: every zoo model lints clean for the numerics codes
        under the default fp32 policy AND --policy bf16 — no
        suppressions needed."""
        from deeplearning4j_tpu.models.zoo import ZOO_MODELS
        numerics = ("DL4J-E3", "DL4J-W30")
        for name, cls in ZOO_MODELS.items():
            conf = cls().conf_builder()
            for pol in (None, "bf16"):
                rep = analyze(conf, policy=pol)
                bad = [d for d in rep if d.code.startswith(numerics)]
                assert not bad, (name, pol,
                                 [d.format() for d in bad])

    def test_samediff_numerics_kwargs_run_numerics_lints(self):
        # ISSUE 18 flipped this pin: policy=/data_range= on a recorded
        # graph now run the numerics family instead of raising
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 4))
        w = sd.var("w", np.zeros((4, 2), np.float32))
        x.mmul(w)
        report = analyze(sd, batch_size=8, policy="bf16",
                         data_range="0..255")
        assert "DL4J-W303" in report.codes(), report.format()


class TestNumericsCli:
    def test_cli_policy_flag_zoo_model(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        assert main(["LeNet", "--policy", "bf16"]) == 0
        assert main(["LeNet", "--policy",
                     "compute=fp16,params=fp32,loss_scale=32768"]) == 0

    def test_cli_fp16_without_scale_fails(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        assert main(["LeNet", "--policy", "fp16"]) == 1
        assert "DL4J-E303" in capsys.readouterr().out

    def test_cli_bad_policy_and_range_are_usage_errors(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        with pytest.raises(SystemExit) as ei:
            main(["LeNet", "--policy", "float8"])
        assert ei.value.code == 2
        with pytest.raises(SystemExit) as ei:
            main(["LeNet", "--data-range", "255"])
        assert ei.value.code == 2

    def test_cli_data_range_flags_w303(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        import deeplearning4j_tpu.models.zoo as zoo_mod
        # TinyYOLO declares raw pixel input in its docstring; any conv
        # net without a leading BN works for the pin
        rc = main(["TinyYOLO", "--data-range", "0..255"])
        out = capsys.readouterr().out
        assert rc == 1 and "DL4J-W303" in out


class TestPureStaticNumerics:
    def test_numerics_pass_runs_with_jax_blocked(self):
        """analysis/numerics.py imports (and lints duck-typed configs)
        with jax unimportable — the pure-static pin for this pass."""
        code = (
            "import sys, types\n"
            "sys.modules['jax'] = None\n"
            "sys.modules['jax.numpy'] = None\n"
            "from deeplearning4j_tpu.analysis.numerics import (\n"
            "    DataRangeSpec, lint_numerics)\n"
            "from deeplearning4j_tpu.nn.precision import PrecisionPolicy\n"
            "class DenseLayer:\n"
            "    name = 'd'; nIn = 8; nOut = 16; activation = 'relu'\n"
            "    dtype_override = None\n"
            "class LossLayer:\n"
            "    name = 'l'; nIn = 16; nOut = 16; activation = 'identity'\n"
            "    loss_fn = 'mse'; dtype_override = None\n"
            "    def compute_loss(self): pass\n"
            "conf = types.SimpleNamespace(\n"
            "    base=types.SimpleNamespace(updater=None, dtype='float32'),\n"
            "    layers=[DenseLayer(), LossLayer()], input_type=None,\n"
            "    preprocessors={})\n"
            "pol = PrecisionPolicy('float16')\n"
            "diags = lint_numerics(conf, policy=pol,\n"
            "                      data_range=DataRangeSpec(0, 255))\n"
            "codes = [d.code for d in diags]\n"
            "assert 'DL4J-E303' in codes, codes\n"
            "assert 'DL4J-W303' in codes, codes\n"
            "print('PURE-STATIC-NUMERICS-OK')\n")
        proc = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "PURE-STATIC-NUMERICS-OK" in proc.stdout


# ------------------------------------ module-level concurrency (ISSUE 11)
_MODULE_E201_BAD = """
import threading

RESULTS = []
_counter = 0

def worker():
    global _counter
    for _ in range(100):
        _counter += 1
        RESULTS.append(_counter)

def run():
    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return _counter
"""

_MODULE_E201_CLEAN = """
import threading

RESULTS = []
_counter = 0
_LOCK = threading.Lock()

def worker():
    global _counter
    for _ in range(100):
        with _LOCK:
            _counter += 1
            RESULTS.append(_counter)

def run():
    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    with _LOCK:
        return _counter
"""

_MODULE_CLOSURE_EXEMPT = """
import threading

def run():
    results = []
    def work():
        results.append(1)
    t = threading.Thread(target=work)
    t.start()
    t.join()
    return results
"""

_MODULE_QUEUE_EXEMPT = """
import threading
import queue

TASKS = queue.Queue()

def worker():
    while True:
        item = TASKS.get()
        if item is None:
            return
        TASKS.task_done()

def run():
    t = threading.Thread(target=worker)
    t.start()
    TASKS.put(1)
    TASKS.put(None)
    t.join()
"""


class TestModuleLevelConcurrency:
    """E201/E202 inference over module-level functions sharing globals
    via threading.Thread(target=fn) — the PR-8 carried follow-up."""

    def test_bad_fixture_fires_e201_and_e202(self, tmp_path):
        r = _lint_src(tmp_path, _MODULE_E201_BAD)
        assert "DL4J-E202" in r.codes()       # _counter += 1
        assert "DL4J-E201" in r.codes()       # RESULTS.append(...)
        rmw = [d for d in r if d.code == "DL4J-E202"]
        assert "module global" in rmw[0].message

    def test_clean_bill_when_locked(self, tmp_path):
        r = _lint_src(tmp_path, _MODULE_E201_CLEAN)
        assert not [c for c in r.codes() if c.startswith("DL4J-E20")], \
            r.format()

    def test_local_closure_target_is_exempt(self, tmp_path):
        r = _lint_src(tmp_path, _MODULE_CLOSURE_EXEMPT)
        assert not [c for c in r.codes() if c.startswith("DL4J-E20")], \
            r.format()

    def test_threadsafe_module_primitive_is_exempt(self, tmp_path):
        r = _lint_src(tmp_path, _MODULE_QUEUE_EXEMPT)
        assert not [c for c in r.codes() if c.startswith("DL4J-E20")], \
            r.format()

    def test_reachability_via_plain_calls(self, tmp_path):
        src = _MODULE_E201_BAD.replace(
            "def run():",
            "def entry():\n    worker()\n\ndef run():").replace(
            "Thread(target=worker)", "Thread(target=entry)")
        r = _lint_src(tmp_path, src)
        assert "DL4J-E202" in r.codes()       # worker reached via entry()


    def test_local_shadow_of_module_global_is_exempt(self, tmp_path):
        """Review regression: a function-local that shadows a module
        name (plain assignment makes it local for the whole function)
        is not module state."""
        src = (
            "import threading\n"
            "REGISTRY = {}\n"
            "def worker():\n"
            "    REGISTRY = {}\n"
            "    REGISTRY['k'] = 1\n"
            "    REGISTRY.update(a=2)\n"
            "def run():\n"
            "    t = threading.Thread(target=worker)\n"
            "    t.start(); t.join()\n")
        r = _lint_src(tmp_path, src)
        assert not [c for c in r.codes() if c.startswith("DL4J-E20")], \
            r.format()

    def test_annotated_module_global_is_tracked(self, tmp_path):
        """Review regression: `COUNTS: dict = {}` (AnnAssign) is module
        state like a plain assignment."""
        src = (
            "import threading\n"
            "COUNTS: dict = {}\n"
            "def worker():\n"
            "    COUNTS['k'] = 1\n"
            "def run():\n"
            "    t = threading.Thread(target=worker)\n"
            "    t.start(); t.join()\n"
            "    return COUNTS\n")
        r = _lint_src(tmp_path, src)
        assert "DL4J-E201" in r.codes(), r.format()

    def test_noqa_suppresses_module_findings(self, tmp_path):
        src = _MODULE_E201_BAD.replace(
            "        _counter += 1",
            "        _counter += 1  # dl4j: noqa=E202")
        r = _lint_src(tmp_path, src)
        assert "DL4J-E202" not in r.codes()


# --------------------------------------------- W105 FLOP model (ISSUE 11)
class TestFlopModelExtensions:
    """Attention + conv-LSTM FLOP estimates (the PR-4 carried W105
    follow-up), pinned against a BERT-shaped config analytically."""

    def test_attention_flops_match_analytic_bert_block(self):
        from deeplearning4j_tpu.analysis.distribution import (
            _approx_flops, _propagate_types)
        from deeplearning4j_tpu.nn.layers import (RnnOutputLayer,
                                                  SelfAttentionLayer)
        T, H, HEADS, HS = 128, 768, 12, 64
        conf = (_builder().list()
                .layer(SelfAttentionLayer(nOut=H, nHeads=HEADS,
                                          headSize=HS))
                .layer(RnnOutputLayer(nOut=2, lossFunction="mcxent"))
                .setInputType(InputType.recurrent(H, T)).build())
        types = _propagate_types(conf)
        got = _approx_flops(conf.layers[0], types[0][0], types[0][1])
        E = HEADS * HS
        proj = 2 * (3 * H * E + E * H) * T     # Wq/Wk/Wv + Wo, per step
        attn = 2 * 2 * T * T * E               # QK^T + attn@V
        assert got == proj + attn, (got, proj + attn)
        # the attention term is the part the old model undercounted
        assert attn / (proj + attn) > 0.05

    def test_conv_lstm_param_shapes_match_initialize(self):
        from deeplearning4j_tpu.nn.layers import ConvLSTM2D
        layer = ConvLSTM2D(nOut=32, kernelSize=(3, 3))
        layer.nIn = 16
        shapes = layer.param_shapes()
        assert shapes == {"W": (128, 16, 3, 3), "RW": (128, 32, 3, 3),
                          "b": (128,)}

    def test_unknown_timesteps_degrade_to_zero_attention_term(self):
        from deeplearning4j_tpu.analysis.distribution import _attention_flops
        from deeplearning4j_tpu.nn.layers import SelfAttentionLayer
        layer = SelfAttentionLayer(nOut=64, nHeads=4, headSize=16)
        layer.nIn = 64
        assert _attention_flops(layer, InputType.recurrent(64, -1)) == 0
        assert _attention_flops(layer, None) == 0

    def test_w105_counts_attention_stage(self):
        """A transformer stage opposite a tiny dense stage now trips the
        imbalance lint — before the attention term it read as nearly
        empty."""
        from deeplearning4j_tpu.nn.layers import (RnnOutputLayer,
                                                  SelfAttentionLayer)
        conf = (_builder().list()
                .layer(SelfAttentionLayer(nOut=512, nHeads=8, headSize=64))
                .layer(SelfAttentionLayer(nOut=512, nHeads=8, headSize=64))
                .layer(SelfAttentionLayer(nOut=512, nHeads=8, headSize=64))
                .layer(RnnOutputLayer(nOut=2, lossFunction="mcxent"))
                .setInputType(InputType.recurrent(512, 256)).build())
        report = analyze(conf, mesh={"data": 2, "pipe": 2},
                         pipeline=2)
        assert "DL4J-W105" in report.codes(), report.format()

    def test_e303_scaled_gradient_overflow(self):
        """Review regression: the compute-overflow clause tests the
        LOSS-SCALED gradient estimate — a scale big enough to push raw
        [0,255] gradients past fp16-max is flagged even with Sgd (no
        squaring state), and a modest scale on normalized input is
        not."""
        from deeplearning4j_tpu.nn.layers import LossLayer
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        conf = (_builder().list()
                .layer(DenseLayer(nOut=32, activation="relu"))
                .layer(LossLayer(lossFunction="mse"))
                .setInputType(InputType.feedForward(16)).build())
        pol = PrecisionPolicy("float16", loss_scale=2 ** 15)
        assert "DL4J-E303" in analyze(conf, policy=pol,
                                      data_range="0..255").codes()
        assert "DL4J-E303" not in analyze(conf, policy=pol,
                                          data_range="0..1").codes()

    def test_parameter_shadow_is_exempt(self, tmp_path):
        """Review regression: a parameter shadowing a module name binds
        locally — mutating the argument is not a module-global write."""
        src = (
            "import threading\n"
            "RESULTS = []\n"
            "def worker(RESULTS):\n"
            "    RESULTS.append(1)\n"
            "def run():\n"
            "    t = threading.Thread(target=worker, args=([],))\n"
            "    t.start(); t.join()\n")
        r = _lint_src(tmp_path, src)
        assert not [c for c in r.codes() if c.startswith("DL4J-E20")], \
            r.format()


# --------------------------------------------- ISSUE 18: import lints
class TestGraphVertexPropagation:
    """Satellite: per-vertex sharding/type propagation — graph configs
    get the same W105/W106 pipeline findings multilayer configs do."""

    def test_w105_fires_on_graph_pipeline_imbalance(self):
        conf = (_graph_builder()
                .setInputTypes(InputType.feedForward(64))
                .addLayer("a", DenseLayer(nOut=4096), "in")
                .addLayer("b", DenseLayer(nOut=4096), "a")
                .addLayer("c", DenseLayer(nOut=16), "b")
                .addLayer("out", OutputLayer(nOut=4), "c")
                .setOutputs("out").build())
        report = analyze(conf, batch_size=32, mesh="data=2,pipe=2",
                         pipeline=2)
        assert "DL4J-W105" in report.codes(), report.format()

    def test_types_propagate_through_merge_vertex(self):
        from deeplearning4j_tpu.analysis.distribution import \
            _propagate_graph_types
        conf = (_graph_builder()
                .addLayer("a", DenseLayer(nOut=32), "in")
                .addLayer("b", DenseLayer(nOut=32), "in")
                .addVertex("m", MergeVertex(), "a", "b")
                .addLayer("c", DenseLayer(nOut=16), "m")
                .addLayer("out", OutputLayer(nOut=4), "c")
                .setOutputs("out").build())
        types = _propagate_graph_types(conf)
        in_t, out_t = types["c"]
        assert in_t.size == 64          # 32 + 32 through the MergeVertex
        assert out_t.size == 16
        # and the linted graph stays clean under a plain data mesh
        assert analyze(conf, batch_size=32, mesh={"data": 2}).ok()

    def test_balanced_graph_pipeline_clean(self):
        conf = (_graph_builder()
                .setInputTypes(InputType.feedForward(64))
                .addLayer("a", DenseLayer(nOut=256), "in")
                .addLayer("b", DenseLayer(nOut=256), "a")
                .addLayer("c", DenseLayer(nOut=256), "b")
                .addLayer("out", OutputLayer(nOut=256), "c")
                .setOutputs("out").build())
        report = analyze(conf, batch_size=32, mesh="data=2,pipe=2",
                         pipeline=2)
        assert "DL4J-W105" not in report.codes(), report.format()


class TestPureStaticImports:
    """The graph IR and the import lints run with jax BLOCKED — both
    operate on declared shapes and numpy arrays only (ISSUE 18
    acceptance)."""

    def test_graphir_and_imports_run_with_jax_blocked(self):
        code = (
            "import sys\n"
            "sys.modules['jax'] = None\n"
            "sys.modules['jax.numpy'] = None\n"
            "import numpy as np\n"
            "from types import SimpleNamespace as NS\n"
            "from deeplearning4j_tpu.analysis import MeshSpec\n"
            "from deeplearning4j_tpu.analysis import graphir, "
            "imports as imp\n"
            "class Arr:\n"
            "    def __init__(self, shape, dtype='float32'):\n"
            "        self.shape, self.dtype = shape, dtype\n"
            "class Node:\n"
            "    def __init__(self, op, ins, outs):\n"
            "        self.op, self.inputs, self.outputs = op, ins, outs\n"
            "        self.attrs = {}\n"
            "sd = NS(_nodes=[Node('matmul', ['x', 'w'], ['y'])],\n"
            "        _placeholders={'x': ((None, 4096), 'float32')},\n"
            "        _constants={},\n"
            "        _variables={'w': Arr((4096, 260))},\n"
            "        _loss_variables=[], training_config=None)\n"
            "ir = graphir.from_samediff(sd, batch_size=12)\n"
            "lay = {d.code for d in graphir.lint_ir_layout(ir, 12, 8)}\n"
            "assert 'DL4J-W101' in lay, lay\n"
            "mesh = MeshSpec({'data': 8})\n"
            "dist = {d.code for d in\n"
            "        graphir.lint_ir_distribution(ir, mesh, 12)}\n"
            "assert 'DL4J-E101' in dist, dist\n"
            "num = {d.code for d in graphir.lint_ir_numerics(\n"
            "    ir, policy='bf16', data_range='0..255')}\n"
            "assert 'DL4J-W303' in num, num\n"
            "assert imp.lint_placeholder_shape((None, None, 3), 'x')\n"
            "assert imp.lint_narrowed_array(\n"
            "    np.eye(2, dtype=np.float64), 'w')\n"
            "assert imp.fold_overflow_diags(\n"
            "    'Add', 's', [np.asarray([np.inf], np.float32)])\n"
            "print('PURE-STATIC-IMPORTS-OK')\n")
        proc = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "PURE-STATIC-IMPORTS-OK" in proc.stdout


class TestGraphIRParity:
    """from_multilayer is the parity proof: lowering a NATIVE config to
    the IR and linting the IR yields the same distribution codes the
    native pass emits."""

    DIST = {"DL4J-E101", "DL4J-E102", "DL4J-E103", "DL4J-E104",
            "DL4J-W104", "DL4J-W105", "DL4J-W106", "DL4J-W107"}

    def test_from_multilayer_distribution_parity(self):
        from deeplearning4j_tpu.analysis import graphir
        conf = _wide_mlp()
        mesh = MeshSpec({"data": 8, "model": 2}, hbm_gb=0.05)
        native = {d.code
                  for d in analyze(conf, batch_size=6, mesh=mesh)} & self.DIST
        ir = graphir.from_multilayer(conf, batch_size=6)
        lowered = {d.code for d in graphir.lint_ir_distribution(
            ir, mesh, 6)} & self.DIST
        assert native == lowered, (native, lowered)
        assert "DL4J-E101" in lowered    # the set is non-trivial

    def test_onnx_dtype_names_pinned_to_proto(self):
        from deeplearning4j_tpu.analysis import graphir
        from deeplearning4j_tpu.modelimport import onnx_proto as P
        want = {P.DT_FLOAT: "float32", P.DT_UINT8: "uint8",
                P.DT_INT8: "int8", P.DT_UINT16: "uint16",
                P.DT_INT16: "int16", P.DT_INT32: "int32",
                P.DT_INT64: "int64", P.DT_BOOL: "bool",
                P.DT_FLOAT16: "float16", P.DT_DOUBLE: "float64",
                P.DT_UINT32: "uint32", P.DT_UINT64: "uint64",
                P.DT_BFLOAT16: "bfloat16"}
        assert graphir.ONNX_DTYPE_NAMES == want


class TestImportReportMerge:
    """analyze() folds an attached import_report into the validation
    report — import-time findings surface at validate() time."""

    def test_import_report_diags_surface_in_analyze(self):
        from deeplearning4j_tpu.analysis import ValidationReport
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        sd = SameDiff.create()
        x = sd.placeHolder("x", shape=(None, 4))
        w = sd.var("w", np.ones((4, 2), np.float32))
        (x.mmul(w)).rename("y")
        sd.import_report = ValidationReport(
            [Diagnostic("DL4J-W161", Severity.WARNING, "input 'x'",
                        "seeded import finding")], subject="import")
        report = analyze(sd, batch_size=8)
        assert "DL4J-W161" in report.codes(), report.format()
        # suppress= reaches merged import findings too
        quiet = analyze(sd, batch_size=8, suppress=["W161"])
        assert "DL4J-W161" not in quiet.codes()


class TestImportsSelfLint:
    """The imported-fixture gate (tools/lint.py run_imports): the shipped
    TF conformance corpus lints clean with ZERO suppressions."""

    def _lint_mod(self):
        spec = importlib.util.spec_from_file_location(
            "repo_lint", REPO / "tools" / "lint.py")
        lint = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lint)
        return lint

    def test_fixture_corpus_lints_clean(self, capsys):
        lint = self._lint_mod()
        assert lint._pyproject_imports_suppress() == [], \
            "the corpus must stay clean with zero suppressions"
        rc = lint.run_imports()
        out = capsys.readouterr().out
        assert rc == 0, f"imported-fixture gate found issues:\n{out}"

    def test_missing_corpus_skips_clean(self, tmp_path, capsys):
        lint = self._lint_mod()
        assert lint.run_imports(tmp_path / "nope") == 0
        assert "skipped" in capsys.readouterr().out

    def test_pyproject_imports_suppress_parse(self, tmp_path):
        lint = self._lint_mod()
        (tmp_path / "pyproject.toml").write_text(
            "[tool.dl4j.imports]\n"
            'suppress = ["W161"]\n'
            "[tool.other]\n"
            'suppress = ["W999"]\n')
        old = lint.REPO
        try:
            lint.REPO = tmp_path
            assert lint._pyproject_imports_suppress() == ["W161"]
            assert lint._pyproject_concurrency_suppress() == []
        finally:
            lint.REPO = old


class TestCliSameDiff:
    def test_samediff_flag_lints_recorded_graph(self, tmp_path,
                                                monkeypatch, capsys):
        mod = tmp_path / "sdmodel.py"
        mod.write_text(
            "import numpy as np\n"
            "from deeplearning4j_tpu.autodiff.samediff import SameDiff\n"
            "sd = SameDiff.create()\n"
            "x = sd.placeHolder('x', shape=(None, 4))\n"
            "w = sd.var('w', np.ones((4, 2), np.float32))\n"
            "y = x.mmul(w)\n")
        monkeypatch.syspath_prepend(str(tmp_path))
        from deeplearning4j_tpu.analysis.__main__ import main
        assert main(["--samediff", "sdmodel:sd"]) == 0
        assert "clean" in capsys.readouterr().out
