"""ISSUE 17 coverage: the ``tune/`` autotuner — search space, persistent
records, the driver's search phases, the loss-parity gate, the
auto-apply wiring (``fit(tune="auto")`` / ``warmup(tuned=True)`` /
registry load), the proactive conv-stack lint, and the CLI acceptance
path (tune in one process, zero-compile apply in a fresh one)."""

import json
import os
import subprocess
import sys
import warnings
from types import SimpleNamespace
from unittest import mock

import numpy as np
import pytest

from deeplearning4j_tpu.analysis import churn as _churn
from deeplearning4j_tpu.analysis import layout as _layout
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn import compilecache as cc
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import (ConvolutionLayer, DenseLayer,
                                          OutputLayer, SubsamplingLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.train import stepping
from deeplearning4j_tpu.tune import driver as tdriver
from deeplearning4j_tpu.tune import records as trecords
from deeplearning4j_tpu.tune.space import (AXES, TuningPlan, TuningSpace,
                                           axis_priority)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def store(tmp_path):
    """A per-test tuning-record directory, warned-set cleared."""
    trecords.configure(str(tmp_path))
    trecords.reset_warned()
    yield str(tmp_path)
    trecords.reset_configuration()
    trecords.reset_warned()


def tiny_net(seed=7):
    conf = (NeuralNetConfiguration.Builder().seed(seed).weightInit("relu")
            .list()
            .layer(ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                    nOut=8, activation="relu"))
            .layer(SubsamplingLayer(poolingType="max", kernelSize=(2, 2),
                                    stride=(2, 2)))
            .layer(DenseLayer(nOut=16, activation="relu"))
            .layer(OutputLayer(nOut=4, lossFunction="mcxent",
                               activation="softmax"))
            .setInputType(InputType.convolutional(8, 8, 3))
            .build())
    return MultiLayerNetwork(conf).init()


def tiny_data(n=4):
    rng = np.random.RandomState(0)
    x = rng.randn(n, 3, 8, 8).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, n)]
    return x, y


# ------------------------------------------------------------- the space
class TestTuningSpace:

    def test_for_model_enumeration_deterministic(self):
        space = TuningSpace.for_model(max_steps_per_dispatch=16)
        assert space.size == 96
        a = [p.signature() for p in space.enumerate_plans()]
        b = [p.signature() for p in space.enumerate_plans()]
        assert a == b
        assert len(set(a)) == 96          # every signature is unique

    def test_sample_deterministic_across_seeds(self):
        space = TuningSpace.for_model(max_steps_per_dispatch=16)
        s1 = [p.signature() for p in space.sample(10, seed=3)]
        s2 = [p.signature() for p in space.sample(10, seed=3)]
        s3 = [p.signature() for p in space.sample(10, seed=4)]
        assert s1 == s2
        assert s1 != s3
        assert len(set(s1)) == 10

    def test_plan_config_roundtrip_and_replace(self):
        plan = TuningPlan(compute_layout="NHWC", fuse_epilogues=True,
                          steps_per_dispatch=4, precision="bf16",
                          prefetch=0)
        back = TuningPlan.from_config(plan.to_config())
        assert back.signature() == plan.signature()
        assert back == plan
        other = plan.replace(precision=None)
        assert other.precision is None
        assert other.compute_layout == "NHWC"
        assert other != plan

    def test_plan_validation(self):
        with pytest.raises(ValueError):
            TuningPlan(compute_layout="NCWH")
        with pytest.raises(ValueError):
            TuningPlan(steps_per_dispatch=0)
        with pytest.raises(ValueError):
            TuningPlan(prefetch=-1)
        with pytest.raises(ValueError):
            TuningSpace({"bogus_axis": (1, 2)})

    def test_neighbors_differ_in_exactly_one_axis(self):
        space = TuningSpace.for_model(max_steps_per_dispatch=16)
        base = space.default_plan()
        base_cfg = base.to_config()
        for axis, nb in space.neighbors(base, list(AXES)):
            diff = [k for k, v in nb.to_config().items()
                    if base_cfg.get(k) != v]
            assert diff == [axis]

    def test_axis_priority_offender_seeded(self):
        assert axis_priority(None) == list(AXES)
        conv = SimpleNamespace(
            top_offenders=lambda n: ["conv2d_nchw fwd", "maxpool"])
        order = axis_priority(conv)
        assert order[0] == "compute_layout"
        mm = SimpleNamespace(top_offenders=lambda n: ["dense matmul"])
        assert axis_priority(mm)[0] == "precision"


# ----------------------------------------------------------- the records
class TestTuningRecords:

    def test_put_lookup_roundtrip(self, store):
        plan = TuningPlan(compute_layout="NHWC", steps_per_dispatch=4)
        rec = trecords.TuningRecord("fp-abc", plan, cost_s=0.010,
                                    default_cost_s=0.015, trials=12,
                                    model_name="tiny")
        path = trecords.put(rec)
        assert path is not None and os.path.exists(path)
        assert os.path.basename(path).startswith("tr_")
        got = trecords.lookup("fp-abc")
        assert got is not None
        assert got.plan.signature() == plan.signature()
        assert got.speedup == pytest.approx(1.5)
        assert got.model_name == "tiny"

    def test_key_isolation_mesh_backend_fp(self, store):
        plan = TuningPlan()
        trecords.put(trecords.TuningRecord("fp-a", plan, cost_s=0.01))
        assert trecords.lookup("fp-a") is not None
        # a different mesh, backend, or fingerprint never cross-applies
        assert trecords.lookup("fp-a", mesh="data=8") is None
        assert trecords.lookup("fp-a", backend="tpu") is None
        assert trecords.lookup("fp-b") is None

    def test_corrupt_record_quarantined(self, store):
        plan = TuningPlan(precision="bf16")
        path = trecords.put(
            trecords.TuningRecord("fp-q", plan, cost_s=0.01))
        raw = open(path, "rb").read()
        with open(path, "wb") as f:          # flip payload bytes
            f.write(raw[:-8] + b"XXXXXXXX")
        with pytest.warns(UserWarning, match="quarantine"):
            assert trecords.lookup("fp-q") is None
        names = os.listdir(store)
        assert any(n.startswith("quarantine_") for n in names)
        assert not any(n.startswith("tr_") for n in names)

    def test_disabled_store_is_inert(self, store):
        trecords.configure(None)
        with pytest.warns(UserWarning, match="disabled"):
            assert trecords.put(
                trecords.TuningRecord("fp-x", TuningPlan(),
                                      cost_s=0.01)) is None
        assert trecords.lookup("fp-x") is None
        assert trecords.record_dir() is None

    def test_mesh_signature_forms(self):
        assert trecords.mesh_signature(None) == "none"
        assert trecords.mesh_signature("data=8") == "data=8"
        from deeplearning4j_tpu.parallel.mesh import DeviceMesh
        sig = trecords.mesh_signature(DeviceMesh.data_parallel())
        assert "=" in sig                    # axis=size form, stable
        assert sig == trecords.mesh_signature(DeviceMesh.data_parallel())

    def test_fingerprint_is_seam_neutral(self):
        """Applying a plan stamps compute_layout/data_format into the
        config — the record-store identity must NOT move, or the record
        would stop matching the very model it tuned."""
        net = tiny_net()
        fp = trecords.model_fingerprint(net)
        TuningPlan(compute_layout="NHWC", fuse_epilogues=True,
                   precision="bf16").apply(net)
        assert trecords.model_fingerprint(net) == fp
        # a genuinely different architecture still gets its own key
        other = MultiLayerNetwork(
            (NeuralNetConfiguration.Builder().seed(7).weightInit("relu")
             .list()
             .layer(DenseLayer(nOut=16, activation="relu"))
             .layer(OutputLayer(nOut=4, lossFunction="mcxent",
                                activation="softmax"))
             .setInputType(InputType.feedForward(8)).build())).init()
        assert trecords.model_fingerprint(other) != fp

    def test_auto_apply_warns_once_per_key(self, store):
        net = tiny_net()
        with pytest.warns(UserWarning, match="no tuning record"):
            assert trecords.auto_apply(net) is None
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert trecords.auto_apply(net) is None   # same key: silent
        assert not [x for x in w
                    if "no tuning record" in str(x.message)]
        trecords.reset_warned()
        with pytest.warns(UserWarning, match="no tuning record"):
            trecords.auto_apply(net)


# ---------------------------------------------------- the search driver
TARGET = TuningPlan(compute_layout="NHWC", fuse_epilogues=True,
                    steps_per_dispatch=4, precision="bf16", prefetch=0)
_COST_AXES = ("compute_layout", "fuse_epilogues", "steps_per_dispatch",
              "precision", "prefetch")


def planted_cost(plan):
    """Monotone planted-optimum landscape: every axis matching TARGET
    shaves 12% — greedy refinement provably climbs to the optimum."""
    matches = sum(getattr(plan, a) == getattr(TARGET, a)
                  for a in _COST_AXES)
    return 1.0 - 0.12 * matches


class TestDriver:

    def test_finds_planted_optimum(self):
        space = TuningSpace({"compute_layout": ("NCHW", "NHWC"),
                             "fuse_epilogues": (False, True),
                             "steps_per_dispatch": (1, 4),
                             "precision": (None, "bf16"),
                             "prefetch": (0, 2)})
        calls = []

        def trial(plan):
            calls.append(plan.signature())
            return planted_cost(plan)

        res = tdriver.tune(object(), None, None, budget=48, reps=1,
                           space=space, trial_fn=trial,
                           parity_fn=lambda p: True, persist=False)
        assert res.best_plan == TARGET
        assert res.best_cost_s == pytest.approx(0.4)
        assert res.default_cost_s == pytest.approx(1.0)
        assert res.speedup == pytest.approx(2.5)
        assert len(calls) <= 48
        assert len(calls) == len(set(calls))   # no duplicate measurement

    def test_budget_respected_and_refinement_runs(self):
        space = TuningSpace.for_model(max_steps_per_dispatch=16)
        calls = []

        def trial(plan):
            calls.append(plan.signature())
            return planted_cost(plan)

        res = tdriver.tune(object(), None, None, budget=24, reps=1,
                           space=space, trial_fn=trial,
                           parity_fn=lambda p: True, persist=False)
        assert len(calls) <= 24
        assert len(calls) == len(set(calls))
        assert res.best_cost_s < res.default_cost_s
        phases = {t.phase for t in res.trials}
        assert "default" in phases and "explore" in phases
        assert "refine" in phases              # greedy walk actually ran

    def test_parity_gate_rejects_back_to_default(self):
        space = TuningSpace({"precision": (None, "bf16")})
        res = tdriver.tune(object(), None, None, budget=4, reps=1,
                           space=space, trial_fn=planted_cost,
                           parity_fn=lambda p: False, persist=False)
        assert res.best_plan == space.default_plan()
        assert res.rejected
        plan, reason = res.rejected[0]
        assert "loss parity" in reason
        assert plan.precision == "bf16"

    def test_baseline_failure_raises(self):
        def broken(plan):
            raise ValueError("no device")
        with pytest.raises(RuntimeError, match="baseline"):
            tdriver.tune(object(), None, None, budget=4,
                         space=TuningSpace({"prefetch": (0, 2)}),
                         trial_fn=broken, persist=False)

    def test_real_search_persists_record(self, store):
        x, y = tiny_data()
        space = TuningSpace({"steps_per_dispatch": (1, 2)})
        res = tdriver.tune(lambda: tiny_net(), x, y, budget=3, reps=1,
                           base_steps=2, space=space,
                           parity_guard=False, model_name="tiny")
        assert res.record is not None
        assert any(n.startswith("tr_") for n in os.listdir(store))
        got = trecords.lookup(tiny_net())     # a fresh, equal-config net
        assert got is not None
        assert got.plan.signature() == res.best_plan.signature()
        assert got.trials == len(res.trials)

    def test_loss_parity_gate_real_curves(self):
        x, y = tiny_data()
        factory = lambda: tiny_net(seed=5)    # noqa: E731
        # NHWC is the bit-compatible seam: parity must hold
        assert tdriver.loss_parity(factory, TuningPlan("NHWC"), x, y,
                                   steps=3)

        class BrokenPlan(TuningPlan):
            """A plan whose apply() perturbs the weights — numerics
            diverge and the gate must reject it."""
            def apply(self, model):
                ds = DataSet(x, y)
                for _ in range(4):
                    model.fit(ds)
                return super().apply(model)

        assert not tdriver.loss_parity(factory, BrokenPlan(), x, y,
                                       steps=3)


# -------------------------------------------------- fit-level auto-apply
class TestApplyTunedPlan:

    def test_plan_instance_applies_direct(self):
        net = tiny_net()
        plan = TuningPlan(compute_layout="NHWC", fuse_epilogues=True,
                          steps_per_dispatch=4, prefetch=0)
        k, p = stepping.apply_tuned_plan(net, plan, 1, 2)
        assert (k, p) == (4, 0)
        assert net._compute_layout == "NHWC"
        assert net._fuse_epilogues is True

    def test_caller_overrides_win(self):
        net = tiny_net()
        plan = TuningPlan(steps_per_dispatch=4, prefetch=0)
        # a caller who explicitly set k keeps it; defaults yield to plan
        k, p = stepping.apply_tuned_plan(net, plan, 2, 2)
        assert (k, p) == (2, 0)
        k, p = stepping.apply_tuned_plan(net, plan, 1, 4)
        assert (k, p) == (4, 4)

    def test_bad_value_raises(self):
        with pytest.raises(ValueError, match="TuningPlan"):
            stepping.apply_tuned_plan(tiny_net(), "bogus", 1, 2)

    def test_auto_consults_store(self, store):
        net = tiny_net()
        plan = TuningPlan(compute_layout="NHWC", steps_per_dispatch=2)
        trecords.put(trecords.TuningRecord(
            trecords.model_fingerprint(net), plan, cost_s=0.01))
        k, p = stepping.apply_tuned_plan(net, "auto", 1, 2)
        assert k == 2
        assert net._compute_layout == "NHWC"


# --------------------------------------------- end-to-end apply surfaces
class TestAutoApplyEndToEnd:

    def _seed_record(self, net, mesh=None, k=2):
        plan = TuningPlan(compute_layout="NHWC", fuse_epilogues=True,
                          steps_per_dispatch=k, prefetch=0)
        trecords.put(trecords.TuningRecord(
            trecords.model_fingerprint(net), plan, cost_s=0.005,
            default_cost_s=0.010, mesh=mesh))
        return plan

    def test_fit_auto_applies_with_zero_churn(self, store):
        net = tiny_net()
        plan = self._seed_record(net)
        x, y = tiny_data()
        batches = [DataSet(x, y)] * plan.steps_per_dispatch
        net.fit(batches, tune="auto")
        assert net._compute_layout == "NHWC"
        assert net._fuse_epilogues is True
        # steady state: repeated tuned fits re-hit the SAME record (the
        # seam-neutral fingerprint) and add NO new step signatures
        det = _churn.get_churn_detector()
        det.reset()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            net.fit(batches, tune="auto")
            net.fit(batches, tune="auto")
        assert not [x for x in w if "no tuning record" in str(x.message)]
        counts = [det.signature_count(s, owner=net)
                  for s in ("MultiLayerNetwork.fit",
                            "MultiLayerNetwork.megastep")]
        assert all(c <= 1 for c in counts)
        assert any(c == 1 for c in counts)

    def test_warmup_tuned_applies_plan(self, store):
        net = tiny_net()
        self._seed_record(net)
        cc.warmup(net, [((4, 3, 8, 8), (4, 4))], tuned=True)
        assert net._compute_layout == "NHWC"
        assert net._fuse_epilogues is True

    def test_registry_load_tuned_applies_plan(self, store):
        from deeplearning4j_tpu.serving.registry import ModelRegistry
        reg = ModelRegistry()
        try:
            net = tiny_net()
            # the record is keyed under the REGISTRY's mesh — a plan
            # tuned for another mesh must not cross-apply
            self._seed_record(net, mesh=reg.mesh)
            with pytest.warns(UserWarning, match="W111"):
                # warm=False on the first version rolls unwarmed — the
                # W111 lint is expected and not under test here
                ver = reg.load("tuned-model", net, warm=False,
                               tuned=True)
            assert ver == 1
            assert net._compute_layout == "NHWC"
            assert net._fuse_epilogues is True
        finally:
            reg.close()


# --------------------------------------------- proactive conv-stack lint
class TestConvStackLint:

    def _located(self, n=3, fmt=None):
        out = []
        for i in range(n):
            layer = ConvolutionLayer(kernelSize=(3, 3), nOut=8,
                                     activation="relu")
            if fmt is not None:
                layer.data_format = fmt      # the NHWC seam's stamp
            out.append((f"layer[{i}]", layer))
        return out

    def test_fires_on_tpu_backend(self):
        diags = _layout.lint_conv_stack(self._located(3), backend="tpu")
        assert len(diags) == 1
        d = diags[0]
        assert d.code == "DL4J-W101"
        assert "3 conv layers" in d.message
        assert "relayout" in d.message
        assert "tune" in d.fix_hint          # points at the autotuner

    def test_silent_off_tpu_and_when_nhwc(self):
        located = self._located(3)
        assert _layout.lint_conv_stack(located, backend="cpu") == []
        assert _layout.lint_conv_stack(located, backend=None) == []
        # config-level NHWC declaration
        assert _layout.lint_conv_stack(located, compute_layout="NHWC",
                                       backend="tpu") == []
        # per-layer NHWC stamp (what an applied plan sets)
        assert _layout.lint_conv_stack(self._located(3, fmt="NHWC"),
                                       backend="tpu") == []
        # a single conv is dispatch noise, not a stack
        assert _layout.lint_conv_stack(self._located(1),
                                       backend="tpu") == []

    def test_validate_flags_then_clean_after_seam(self):
        # two convs: enough of a stack for the proactive lint
        net = MultiLayerNetwork(
            (NeuralNetConfiguration.Builder().seed(7).weightInit("relu")
             .list()
             .layer(ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                     nOut=8, activation="relu"))
             .layer(ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                     nOut=8, activation="relu"))
             .layer(OutputLayer(nOut=4, lossFunction="mcxent",
                                activation="softmax"))
             .setInputType(InputType.convolutional(8, 8, 3))
             .build())).init()
        with mock.patch.object(_layout, "_default_backend",
                               return_value="tpu"):
            report = net.validate()
            hits = [d for d in report if d.code == "DL4J-W101"
                    and "relayout" in d.message]
            assert hits
            net.setComputeLayout("NHWC")
            report = net.validate()
            assert not [d for d in report if d.code == "DL4J-W101"
                        and "relayout" in d.message]


# ------------------------------------------------------ CLI + acceptance
def _run_cli(args, env):
    return subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.tune"] + args,
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420)


class TestCLI:

    def test_cli_tunes_persists_and_fresh_process_applies(self, tmp_path):
        """The ISSUE-17 acceptance path: the CLI search finds a plan no
        worse than the default, persists it, and a FRESH process's
        ``fit(tune="auto")`` applies it."""
        rdir = str(tmp_path / "records")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = _run_cli(["lenet", "--budget", "8", "--batch", "4",
                         "--hw", "32", "--classes", "10", "--reps", "1",
                         "--steps", "2", "--dir", rdir,
                         "--no-parity", "--json"],
                        env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        payload = json.loads(proc.stdout)
        assert payload["model"] == "LeNet"
        assert payload["trials"] == 8
        assert payload["best_ms_per_step"] <= payload["default_ms_per_step"]
        assert payload["speedup"] >= 1.0
        assert payload["persisted"] is True
        assert any(n.startswith("tr_") for n in os.listdir(rdir))

        script = tmp_path / "fresh_apply.py"
        script.write_text(f"""
import numpy as np
import sys
sys.path.insert(0, {REPO!r})
from deeplearning4j_tpu.tune import records
from deeplearning4j_tpu.models.zoo import LeNet
from deeplearning4j_tpu.data.dataset import DataSet

records.configure({rdir!r})
net = LeNet(seed=11, num_classes=10, input_shape=(3, 32, 32)).init()
plan = records.best_plan(net)
assert plan is not None, "fresh process found no tuning record"
rng = np.random.RandomState(0)
x = rng.randn(4, 3, 32, 32).astype(np.float32)
y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, 4)]
batches = [DataSet(x, y)] * max(1, plan.steps_per_dispatch)
net.fit(batches, tune="auto")
assert net._compute_layout == plan.compute_layout
print("FRESH-OK", plan.signature())
""")
        proc2 = subprocess.run([sys.executable, str(script)], cwd=REPO,
                               env=env, capture_output=True, text=True,
                               timeout=240)
        assert proc2.returncode == 0, \
            proc2.stderr[-2000:] + proc2.stdout[-500:]
        assert "FRESH-OK" in proc2.stdout

    @pytest.mark.slow
    def test_resnet50_budget_20_reduces_step_time(self, tmp_path):
        """The headline acceptance run: ``python -m
        deeplearning4j_tpu.tune resnet50 --budget 20`` (CPU-sized
        input) finds a measurably faster plan and persists it."""
        rdir = str(tmp_path / "records")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "deeplearning4j_tpu.tune", "resnet50",
             "--budget", "20", "--batch", "2", "--hw", "32",
             "--classes", "10", "--reps", "1", "--steps", "2",
             "--dir", rdir, "--no-parity", "--json"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=3600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        payload = json.loads(proc.stdout)
        assert payload["trials"] == 20
        assert payload["persisted"] is True
        # the tentpole claim: search finds a measurably faster plan
        assert payload["best_ms_per_step"] < payload["default_ms_per_step"]
        assert payload["speedup"] > 1.0
