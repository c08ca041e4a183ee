"""Whole-program static cost model (ISSUE 19): chip registry, the
hand-computed MLP liveness pin (fp32 and bf16+masters), a bad-fixture /
clean-bill pair per DL4J-E12x/W12x code, the roofline/capacity planner,
the E104/W109 supersession, the measured-profile W105 satellite, the
tune/ static pruner, bench calibration, the CLI, and the jax-blocked
subprocess pin."""

import importlib.util
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from deeplearning4j_tpu.analysis import (DIAGNOSTIC_CODES, MeshSpec,
                                         StageProfile, analyze)
from deeplearning4j_tpu.analysis import cost as C
from deeplearning4j_tpu.analysis.chipspec import (CHIP_REGISTRY, ChipSpec,
                                                  chip_names)
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.train.updaters import Adam, Sgd

REPO = pathlib.Path(__file__).resolve().parent.parent

#: chip fixtures — deliberately extreme so each code's trigger is
#: unambiguous (the registry chips are the clean-bill side)
TINY = {"name": "tiny", "peak_flops": 1e12, "hbm_gb": 0.001,
        "hbm_gbps": 10.0, "ici_gbps": 1.0}
ONEGB = {"name": "onegb", "peak_flops": 1e12, "hbm_gb": 1.0,
         "hbm_gbps": 100.0, "ici_gbps": 10.0}
SLOWICI = {"name": "slowici", "peak_flops": 1e12, "hbm_gb": 32.0,
           "hbm_gbps": 1000.0, "ici_gbps": 0.001}

B = 32
#: Dense(784->512) + Dense(512->256) + Output(256->10), biases included
P = (784 * 512 + 512) + (512 * 256 + 256) + (256 * 10 + 10)
ACT_ELEMS = 784 + 512 + 256 + 10      # input held for dW + every output


def _mlp(updater=None):
    return (NeuralNetConfiguration.Builder().seed(7)
            .updater(updater or Adam(1e-3)).weightInit("xavier").list()
            .layer(DenseLayer(nOut=512, activation="relu"))
            .layer(DenseLayer(nOut=256, activation="relu"))
            .layer(OutputLayer(nOut=10, lossFunction="mcxent",
                               activation="softmax"))
            .setInputType(InputType.feedForward(784)).build())


def _codes(diags):
    return [d.code for d in diags]


# ============================================================ chip registry
class TestChipSpec:
    def test_registry_covers_target_generations(self):
        assert {"tpu-v3", "tpu-v4", "tpu-v5e", "cpu"} <= set(chip_names())
        v4 = CHIP_REGISTRY["tpu-v4"]
        assert v4.hbm_gb == 32.0
        assert v4.hbm_bytes == 32.0 * (1 << 30)

    def test_coerce_accepts_every_declaration_form(self):
        v4 = ChipSpec.coerce("tpu-v4")
        assert ChipSpec.coerce(v4) is v4
        assert ChipSpec.coerce(None).name == "tpu-v4"     # the default
        custom = ChipSpec.coerce(TINY)
        assert custom.name == "tiny" and custom.hbm_gb == 0.001

    def test_unknown_chip_names_known_ones(self):
        with pytest.raises(ValueError, match="tpu-v4"):
            ChipSpec.coerce("tpu-v9000")

    def test_fp32_runs_at_half_the_mxu_peak(self):
        v4 = CHIP_REGISTRY["tpu-v4"]
        assert v4.peak_for("fp32") == v4.peak_flops / 2
        assert v4.peak_for("bf16") == v4.peak_flops


# ================================================= MLP liveness pin (exact)
class TestMemoryPlanPin:
    """The memory-model conventions, pinned analytically: every component
    of the plan equals the hand-computed value, to the byte."""

    def test_fp32_adam_components_exact(self):
        mem = C.memory_plan(_mlp(), cost=C.CostSpec(chip="tpu-v4"),
                            batch_size=B)
        assert mem.components == {
            "params": P * 4, "grads": P * 4, "fp32 masters": 0,
            "updater state": P * 4 * 2,           # Adam: m + v on masters
            "live activations": B * ACT_ELEMS * 4,
            "megastep staging": 0,                # K=1: no staging
            "prefetch": 2 * B * 784 * 4,          # depth x input bytes
        }
        assert mem.peak_bytes == sum(mem.components.values())

    def test_bf16_low_precision_adds_masters(self):
        mem = C.memory_plan(_mlp(),
                            cost=C.CostSpec(chip="tpu-v4",
                                            precision="bf16"),
                            batch_size=B)
        assert mem.components == {
            "params": P * 2, "grads": P * 2,      # compute dtype
            "fp32 masters": P * 4,                # low precision: masters
            "updater state": P * 4 * 2,           # state on the masters
            "live activations": B * ACT_ELEMS * 2,
            "megastep staging": 0,
            "prefetch": 2 * B * 784 * 2,
        }

    def test_data_axis_shards_activations_not_params(self):
        base = C.memory_plan(_mlp(), batch_size=B)
        sharded = C.memory_plan(_mlp(), mesh="data=8", batch_size=B)
        assert sharded.components["params"] == base.components["params"]
        assert sharded.components["live activations"] == \
            base.components["live activations"] / 8
        assert sharded.components["prefetch"] == \
            base.components["prefetch"] / 8

    def test_megastep_staging_scales_with_k(self):
        mem = C.memory_plan(_mlp(),
                            cost=C.CostSpec(steps_per_dispatch=16,
                                            prefetch=0),
                            batch_size=B)
        assert mem.components["megastep staging"] == 16 * B * 784 * 4
        name, _ = C.memory_plan(
            _mlp(), cost=C.CostSpec(steps_per_dispatch=4096, prefetch=0),
            batch_size=B).dominating()
        assert name == "megastep staging"


# ========================================================== roofline model
class TestStepTime:
    def test_estimate_is_sane_and_bounded(self):
        est = C.step_time(_mlp(), cost=C.CostSpec(chip="tpu-v4"),
                          batch_size=B)
        assert est.step_s > 0
        assert 0 < est.mfu <= 1.0
        assert est.roofline_s >= est.compute_s > 0
        assert est.roofline_s >= est.hbm_s > 0
        assert est.bound in ("compute", "hbm bandwidth", "collectives")
        assert "predicted step" in est.format()

    def test_inference_cheaper_than_training(self):
        train = C.step_time(_mlp(), batch_size=B, train=True)
        infer = C.step_time(_mlp(), batch_size=B, train=False)
        assert infer.step_s < train.step_s
        assert infer.collective_s == 0

    def test_collectives_appear_only_with_a_data_axis(self):
        alone = C.step_time(_mlp(), batch_size=B)
        wide = C.step_time(_mlp(), mesh="data=8", batch_size=B)
        assert alone.collective_s == 0
        assert wide.collective_s > 0

    def test_per_stage_breakdown_under_pipeline(self):
        est = C.step_time(_mlp(), mesh=MeshSpec({"pipe": 2}, pipeline=2),
                          batch_size=B)
        assert est.per_stage is not None and len(est.per_stage) == 2
        assert sum(est.per_stage) == pytest.approx(est.roofline_s)


class TestCapacity:
    def test_min_replicas_is_ceil_of_qps_over_per_replica(self):
        spec = C.CostSpec(buckets=(8,), qps=1000.0)
        cap = C.capacity(_mlp(), spec)
        assert cap["bucket"] == 8
        assert cap["per_replica_qps"] == pytest.approx(
            8 / (cap["latency_ms"] / 1e3))
        assert cap["min_replicas"] == int(np.ceil(
            1000.0 / cap["per_replica_qps"]))


# ===================================== one bad fixture + clean bill per code
class TestCostLints:
    def test_e120_step_peak_overflow_and_clean_bill(self):
        bad = _codes(C.lint_cost(_mlp(), C.CostSpec(chip=TINY),
                                 batch_size=B))
        assert bad == ["DL4J-E120"]
        d = C.lint_cost(_mlp(), C.CostSpec(chip=TINY), batch_size=B)[0]
        assert "dominating" in d.message      # names the liveness term
        assert _codes(C.lint_cost(_mlp(), C.CostSpec(),
                                  batch_size=B)) == []

    def test_w120_remat_when_activations_dominate_near_budget(self):
        bad = _codes(C.lint_cost(
            _mlp(), C.CostSpec(chip=ONEGB, prefetch=0),
            batch_size=100_000))
        assert bad == ["DL4J-W120"]
        assert _codes(C.lint_cost(_mlp(), C.CostSpec(prefetch=0),
                                  batch_size=B)) == []

    def test_w121_comms_bound_needs_declared_batch(self):
        spec = C.CostSpec(chip=SLOWICI)
        bad = _codes(C.lint_cost(_mlp(), spec, mesh="data=8",
                                 batch_size=256))
        assert bad == ["DL4J-W121"]
        # same model/mesh/chip, batch undeclared: the gate holds
        assert _codes(C.lint_cost(_mlp(), spec, mesh="data=8")) == []

    def test_w122_mfu_below_declared_target(self):
        bad = _codes(C.lint_cost(_mlp(), C.CostSpec(mfu_target=0.99),
                                 batch_size=B))
        assert bad == ["DL4J-W122"]
        assert _codes(C.lint_cost(_mlp(), C.CostSpec(mfu_target=1e-9),
                                  batch_size=B)) == []

    def test_e121_serving_bucket_overflow(self):
        bad = _codes(C.lint_cost(_mlp(),
                                 C.CostSpec(chip=TINY, buckets=(8, 1024))))
        assert "DL4J-E121" in bad
        assert _codes(C.lint_cost(_mlp(),
                                  C.CostSpec(buckets=(8, 1024)))) == []

    def test_e122_capacity_shortfall_names_min_replicas(self):
        diags = C.lint_cost(_mlp(), C.CostSpec(qps=1e12, buckets=(8,)))
        assert _codes(diags) == ["DL4J-E122"]
        assert "minimal replica count" in diags[0].message
        lat = C.lint_cost(_mlp(), C.CostSpec(p99_ms=1e-9))
        assert _codes(lat) == ["DL4J-E122"]
        assert "no replica count fixes" in lat[0].message
        assert _codes(C.lint_cost(
            _mlp(), C.CostSpec(qps=1.0, p99_ms=1e6, buckets=(8,)))) == []

    def test_new_codes_documented(self):
        for code in ("DL4J-E120", "DL4J-E121", "DL4J-E122",
                     "DL4J-W120", "DL4J-W121", "DL4J-W122"):
            assert code in DIAGNOSTIC_CODES


# ================================================== analyze() integration
def _wide_mlp():
    return (NeuralNetConfiguration.Builder().seed(7).updater(Adam(1e-3))
            .weightInit("xavier").list()
            .layer(DenseLayer(nOut=4096, activation="relu"))
            .layer(DenseLayer(nOut=4096, activation="relu"))
            .layer(OutputLayer(nOut=10, lossFunction="mcxent"))
            .setInputType(InputType.feedForward(4096)).build())


class TestAnalyzeIntegration:
    def test_cost_supersedes_e104_w109_heuristics(self):
        # without cost=: the params-only heuristics fire on a 4096-wide
        # MLP over data=8 (replicated Adam state above the W109 bar)
        plain = analyze(_wide_mlp(), mesh="data=8").codes()
        assert "DL4J-W109" in plain
        # with cost=: the exact ZeRO-aware liveness plan judges updater
        # state against the DECLARED chip — the heuristics stand down
        costed = analyze(_wide_mlp(), mesh="data=8", cost="tpu-v4")
        assert "DL4J-W109" not in costed.codes()
        assert "DL4J-E104" not in costed.codes()
        assert costed.ok(warnings_as_errors=True), costed.format()

    def test_cost_diagnostics_flow_through_analyze(self):
        report = analyze(_mlp(), cost=C.CostSpec(chip=TINY), batch_size=B)
        assert "DL4J-E120" in report.codes()

    def test_cost_coercion_forms(self):
        assert analyze(_mlp(), cost=True).ok()
        assert analyze(_mlp(), cost="tpu-v5e").ok()
        assert analyze(_mlp(), cost={"chip": "tpu-v3"}).ok()

    def test_plan_report_bundles_everything(self):
        rep = C.plan(_mlp(), cost=C.CostSpec(qps=100.0, buckets=(8,)),
                     batch_size=B)
        out = rep.format()
        assert "step-peak HBM" in out
        assert "predicted step" in out
        assert "QPS/replica" in out
        assert rep.capacity["min_replicas"] >= 1

    def test_profile_without_mesh_is_a_usage_error(self):
        with pytest.raises(ValueError, match="profile"):
            analyze(_mlp(), profile=[{"layer": "x", "device_ms": 1.0}])


# ===================================== W105 measured-profile (ROADMAP carry)
def _four_dense():
    """FLOP-balanced 4-layer stack: the static model sees no imbalance,
    so any W105 must come from MEASURED time."""
    return (NeuralNetConfiguration.Builder().seed(7).updater(Sgd(0.1))
            .weightInit("xavier").list()
            .layer(DenseLayer(nOut=512, activation="relu"))
            .layer(DenseLayer(nOut=512, activation="relu"))
            .layer(DenseLayer(nOut=512, activation="relu"))
            .layer(DenseLayer(nOut=512, activation="relu"))
            .setInputType(InputType.feedForward(512)).build())


class TestStageProfileW105:
    ROWS = [{"layer": "denselayer_0", "device_ms": 40.0},
            {"layer": "denselayer_1", "device_ms": 1.0},
            {"layer": "denselayer_2", "device_ms": 1.0},
            {"layer": "denselayer_3", "device_ms": 1.0}]

    def test_measured_profile_overrides_the_flop_model(self):
        conf = _four_dense()
        flop = analyze(conf, mesh="pipe=2,data=1", pipeline=2)
        assert "DL4J-W105" not in flop.codes()     # FLOP-balanced
        measured = analyze(conf, mesh="pipe=2,data=1", pipeline=2,
                           profile=StageProfile(self.ROWS, source="trace"))
        w105 = [d for d in measured
                if d.code == "DL4J-W105"]
        assert w105, measured.format()
        assert "measured per-stage device time" in w105[0].message
        assert "trace" in w105[0].message          # names the source
        assert "device-ms/step" in w105[0].message

    def test_flop_fallback_names_the_static_model(self):
        lop = (NeuralNetConfiguration.Builder().seed(7).updater(Sgd(0.1))
               .weightInit("xavier").list()
               .layer(DenseLayer(nOut=2048, activation="relu"))
               .layer(DenseLayer(nOut=8, activation="relu"))
               .layer(DenseLayer(nOut=8, activation="relu"))
               .layer(OutputLayer(nOut=2))
               .setInputType(InputType.feedForward(2048)).build())
        report = analyze(lop, mesh="pipe=2,data=1", pipeline=2)
        w105 = [d for d in report if d.code == "DL4J-W105"]
        assert w105, report.format()
        assert "the static FLOP model" in w105[0].message
        assert "GFLOP/example" in w105[0].message

    def test_coerce_json_trace_path(self, tmp_path):
        trace = tmp_path / "trace.json"
        trace.write_text(json.dumps({"rows": self.ROWS,
                                     "source": "bench-r06"}))
        prof = StageProfile.coerce(str(trace))
        assert prof.source == "bench-r06"
        assert len(prof.rows) == 4
        report = analyze(_four_dense(), mesh="pipe=2,data=1", pipeline=2,
                         profile=str(trace))
        assert "DL4J-W105" in report.codes()

    def test_coerce_bad_path_raises(self):
        with pytest.raises(ValueError, match="does not exist"):
            StageProfile.coerce("/nonexistent/trace.json")

    def test_positional_fallback_without_layer_names(self):
        prof = StageProfile([{"device_ms": 40.0}, {"device_ms": 1.0},
                             {"device_ms": 1.0}, {"device_ms": 1.0}])
        report = analyze(_four_dense(), mesh="pipe=2,data=1", pipeline=2,
                         profile=prof)
        assert "DL4J-W105" in report.codes()

    def test_mismatched_profile_degrades_to_flops(self):
        prof = StageProfile([{"layer": "nosuch", "device_ms": 99.0}])
        report = analyze(_four_dense(), mesh="pipe=2,data=1", pipeline=2,
                         profile=prof)
        assert "DL4J-W105" not in report.codes()   # balanced FLOP verdict


# ================================================= tune/ static pruning
class TestTunePruning:
    TOY = {"name": "toy", "peak_flops": 1e12, "hbm_gb": 40.0 / 1024,
           "hbm_gbps": 100.0, "ici_gbps": 10.0}

    def _run(self, **kw):
        from deeplearning4j_tpu import tune as T
        space = T.TuningSpace({"steps_per_dispatch": (1, 16)})
        feats = np.zeros((1024, 784), np.float32)
        res = T.tune(_mlp(), feats, None, budget=8, reps=1, space=space,
                     trial_fn=lambda p: 1.0, parity_fn=lambda p: True,
                     persist=False, **kw)
        return space, res

    def test_dominated_candidate_pruned_with_reason(self):
        space, res = self._run(cost_spec={"chip": self.TOY})
        assert len(res.pruned) >= 1
        plans = {p.steps_per_dispatch for p, _ in res.pruned}
        assert plans == {16}                       # K=16 staging OOMs
        _, reason = res.pruned[0]
        assert "OOM" in reason and "megastep staging" in reason
        assert "pruned" in res.summary()
        # pruning spends no measurement: only the default was timed
        assert [t.plan.signature() for t in res.trials] == \
            [space.default_plan().signature()]

    def test_incumbent_default_never_pruned(self):
        space, res = self._run(cost_spec={"chip": self.TOY})
        default_sig = space.default_plan().signature()
        assert all(p.signature() != default_sig for p, _ in res.pruned)
        assert any(t.phase == "default" for t in res.trials)
        assert res.best_plan == space.default_plan()

    def test_no_cost_spec_means_no_pruning(self):
        _space, res = self._run()
        assert res.pruned == []
        assert {t.plan.steps_per_dispatch for t in res.trials} == {1, 16}

    def test_tuning_report_alias(self):
        from deeplearning4j_tpu import tune as T
        assert T.TuningReport is T.TuneResult


# ==================================================== bench calibration
class TestBenchCalibration:
    @pytest.fixture(scope="class")
    def bench(self):
        spec = importlib.util.spec_from_file_location(
            "bench", REPO / "bench.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_ratio_finite_and_stable(self, bench):
        row = bench.cost_calibration(_mlp(), batch=B,
                                     measured_step_s=0.005)
        assert row["chip"] == "tpu-v5e"
        assert row["predicted_step_ms"] > 0
        assert row["predicted_peak_hbm_mb"] > 0
        assert np.isfinite(row["cost_model_ratio"])
        assert row["cost_model_ratio"] == pytest.approx(
            0.005 / (row["predicted_step_ms"] / 1e3), rel=1e-2)
        again = bench.cost_calibration(_mlp(), batch=B,
                                       measured_step_s=0.005)
        assert again["predicted_step_ms"] == row["predicted_step_ms"]
        assert again["cost_model_ratio"] == row["cost_model_ratio"]

    def test_precision_changes_the_prediction(self, bench):
        fp32 = bench.cost_calibration(_mlp(), batch=B,
                                      measured_step_s=0.005)
        bf16 = bench.cost_calibration(_mlp(), batch=B,
                                      measured_step_s=0.005,
                                      precision="bf16")
        assert bf16["predicted_peak_hbm_mb"] != fp32["predicted_peak_hbm_mb"]


# ============================================================== serving
class TestServingCost:
    def test_server_validate_runs_serving_cost_codes(self):
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.serving import ModelServer
        conf = (NeuralNetConfiguration.Builder().seed(42)
                .updater(Sgd(0.1)).list()
                .layer(DenseLayer(nOut=8, activation="relu"))
                .layer(OutputLayer(nOut=3, lossFunction="mcxent",
                                   activation="softmax"))
                .setInputType(InputType.feedForward(4)).build())
        sv = ModelServer(MultiLayerNetwork(conf).init(), batch_limit=8,
                         max_queue=32, coalesce_ms=1.0)
        try:
            nano = {"name": "nano", "peak_flops": 1e12, "hbm_gb": 1e-7,
                    "hbm_gbps": 10.0, "ici_gbps": 1.0}
            bad = sv.validate(cost={"chip": nano, "p99_ms": 1e-9})
            got = {d.code for d in bad.diagnostics}
            assert {"DL4J-E121", "DL4J-E122"} <= got, bad.format()
            clean = sv.validate(cost="tpu-v4")
            assert not [d for d in clean.diagnostics
                        if d.code.startswith(("DL4J-E12", "DL4J-W12"))]
        finally:
            sv.close()


# ============================================ the hybrid sparse decoder's cut
class TestHybridDecoderCut:
    """The memory and FLOP models on ``zoo.LFM2``'s one-chip cut (the
    size the benchmark trains): a gated short convolution, grouped
    key/value projections, experts without a shared expert and a head
    with no table of its own are sized by their own declared shapes."""

    def _cut(self):
        from deeplearning4j_tpu.models.zoo import LFM2
        net = LFM2.for_cost_gate().conf_builder()
        net.conf.base.dtype = "bfloat16"
        return net

    def test_the_cut_fits_a_v5e_at_four_sequences_a_step(self):
        # the chip's compiler reads 11.46 GiB for this step (PERF.md);
        # the published model replicated does not fit anything
        mem = C.memory_plan(self._cut(), cost=C.CostSpec(chip="tpu-v5e"),
                            batch_size=4)
        assert 10.5 * 2 ** 30 < mem.peak_bytes < 12.5 * 2 ** 30
        parts = dict(mem.components)
        masters = 469_284_992 * 4
        assert parts["fp32 masters"] == masters
        assert parts["updater state"] == 2 * masters
        from deeplearning4j_tpu.models.zoo import LFM2
        whole = C.memory_plan(LFM2().conf_builder(),
                              cost=C.CostSpec(chip="tpu-v5e"), batch_size=1)
        assert whole.peak_bytes > 20 * 16 * 2 ** 30

    @pytest.mark.parametrize("node, params, flops", [
        # [C, 3C] + [3, C] + [C, C]: two products and three taps a token
        ("l0_conv", 16_783_360, 8192 * 2 * 16_783_360),
        # 32 query heads over 8 key/value heads of 64, two 64-wide gains;
        # the static model counts the core's whole square
        ("l1_attn", 10_485_888, 8192 * 2 * 10_485_760
         + 4 * 8192 ** 2 * 2048),
        # router + 8 held experts, no shared expert: 0.5 experts a token
        ("l1_moe", 75_628_544, 8192 * 2 * (2048 * 64
                                             + 0.5 * 3 * 2048 * 1536)),
        ("l0_mlp", 72_351_744, 8192 * 2 * 72_351_744),
        ("lm", 0, 8192 * 2 * 2048 * 8192), ("embed", 16_777_216, 0),
    ])
    def test_a_new_layers_parameters_and_flops(self, node, params, flops):
        import math
        from deeplearning4j_tpu.profiler import devicetime
        conf = self._cut().conf
        shapes = conf.node_by_name[node].obj.param_shapes()
        assert sum(math.prod(s) for s in shapes.values()) == params
        rows = {n: f for n, _op, f in devicetime.layer_flop_model(conf)}
        assert rows[node] == flops


# ========================================================= CLI acceptance
class TestCliCost:
    def test_zoo_clean_under_cost_flag(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        assert main(["--zoo", "--mesh", "data=8", "--cost",
                     "--chip", "tpu-v4"]) == 0
        assert "19 model(s) linted: 19 clean" in capsys.readouterr().out

    def test_chip_implies_cost_and_validates(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        with pytest.raises(SystemExit):
            main(["LeNet", "--chip", "not-a-chip"])
        assert "known chips" in capsys.readouterr().err

    def test_profile_flag_needs_mesh(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        with pytest.raises(SystemExit):
            main(["LeNet", "--profile", "x.json"])
        assert "--mesh" in capsys.readouterr().err

    def test_repo_lint_gate_has_cost_hook(self):
        spec = importlib.util.spec_from_file_location(
            "lintmod", REPO / "tools" / "lint.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_cost() == 0


# ================================================== jax-free subprocess pin
class TestPureStaticCost:
    def test_cost_model_runs_with_jax_blocked(self):
        code = (
            "import sys\n"
            "sys.modules['jax'] = None\n"
            "sys.modules['jax.numpy'] = None\n"
            "from types import SimpleNamespace as NS\n"
            "from deeplearning4j_tpu.analysis import chipspec\n"
            "from deeplearning4j_tpu.analysis import cost as C\n"
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
            "        _variables={'w': Arr((4096, 256))},\n"
            "        _loss_variables=[], training_config=None)\n"
            "chip = chipspec.ChipSpec.coerce('tpu-v4')\n"
            "mem = C.memory_plan(sd, cost=C.CostSpec(chip=chip),\n"
            "                    batch_size=16)\n"
            "assert mem.peak_bytes > 0, mem.components\n"
            "est = C.step_time(sd, batch_size=16)\n"
            "assert est.step_s > 0 and 0 < est.mfu <= 1\n"
            "diags = C.lint_cost(sd, C.CostSpec(\n"
            "    chip={'name': 't', 'peak_flops': 1e12, 'hbm_gb': 1e-6,\n"
            "          'hbm_gbps': 10.0, 'ici_gbps': 1.0}), batch_size=16)\n"
            "assert [d.code for d in diags] == ['DL4J-E120'], diags\n"
            "print('PURE-STATIC-COST-OK')\n")
        proc = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "PURE-STATIC-COST-OK" in proc.stdout
