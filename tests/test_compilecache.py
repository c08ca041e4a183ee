"""AOT compilation ahead of dispatch + unified warmup, under JAX's own
persistent cache.

Pins:

- CachedDispatch: plain-jit passthrough until warmed, AOT warm()
  compiles without executing, shardings are part of a signature.
- THE cross-process pin: a second fresh process sharing a
  ``JAX_COMPILATION_CACHE_DIR`` writes no new entry, sees hits, and ends
  bit-identical — across fit, graph fit, megastep, serving warmup,
  resume and an 8-device GSPMD fit; another PrecisionPolicy does write
  new entries (no false sharing).
- The existing zero-steady-state-recompile pins stay green on the AOT
  path (megastep, serving buckets, precision re-attach).
- The warm-ahead gates (resume, elastic shrink) open only where a
  persistent cache is placed.
- DL4J-W112: serving warmup without a (writable) persistent cache dir.
"""

import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.analysis import get_churn_detector
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn import compilecache as cc
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.train.updaters import Adam
from deeplearning4j_tpu.serving.server import ModelServer


@pytest.fixture(autouse=True)
def _clean_cache_state(place_jax_cache):
    """Every test starts with no persistent cache placed and zeroed
    stats, whatever the environment the suite runs in."""
    place_jax_cache(None)
    cc.reset_stats()
    yield
    cc.reset_stats()


@pytest.fixture
def placed_cache(place_jax_cache, tmp_path):
    """A placed persistent cache as the package sees it (the gates and
    W112 ask ``utils.environment``)."""
    place_jax_cache(tmp_path)
    return str(tmp_path)


def _mlp_conf(seed=7, hidden=16):
    return (NeuralNetConfiguration.Builder().seed(seed).updater(Adam(0.01))
            .weightInit("xavier").list()
            .layer(DenseLayer(nOut=hidden, activation="relu"))
            .layer(OutputLayer(nOut=3, lossFunction="mcxent",
                               activation="softmax"))
            .setInputType(InputType.feedForward(8))
            .build())


def _graph_conf(seed=7):
    return (NeuralNetConfiguration.Builder().seed(seed).updater(Adam(0.01))
            .graphBuilder()
            .addInputs("in")
            .setInputTypes(InputType.feedForward(8))
            .addLayer("fc", DenseLayer(nOut=16, activation="relu"), "in")
            .addLayer("out", OutputLayer(nOut=3, lossFunction="mcxent",
                                         activation="softmax"), "fc")
            .setOutputs("out")
            .build())


def _data(n=16, seed=0):
    rng = np.random.RandomState(seed)
    return DataSet(rng.randn(n, 8).astype(np.float32),
                   np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)])


def _iterator(seed=0, n=48, batch=8):
    """Cursor-capable source (exact resume needs seek())."""
    from deeplearning4j_tpu.data.dataset import ListDataSetIterator
    return ListDataSetIterator(_data(n, seed), batch_size=batch)


# -------------------------------------------------------- cached dispatch
class TestCachedDispatch:
    def test_passthrough_when_disabled(self):
        calls = []

        def f(x):
            calls.append(1)
            return x * 2
        d = cc.cached_dispatch(f, "test:pt")
        out = d(jnp.ones((4,)))
        assert float(out[0]) == 2.0
        assert d.warmed_signatures() == 0     # plain jit path, no AOT

    def test_warm_compiles_without_executing(self):
        executed = []

        def f(x):
            executed.append(1)                # traced once, run never
            return x + 1
        d = cc.cached_dispatch(f, "test:warm")
        d.warm(jnp.zeros((4,)))
        assert d.warmed_signatures() == 1
        assert cc.cache_stats()["compile_seconds"]["cold_compiles"] == 1
        assert executed == [1]
        # the call now hits the memory tier
        cc.reset_stats()
        assert float(d(jnp.ones((4,)))[0]) == 2.0
        assert cc.cache_stats()["memory"]["hits"] == 1

    def test_aot_failure_falls_back_for_good(self):
        """A signature whose AOT acquisition fails is served by the
        plain jit from then on: one warning, no lowering per dispatch."""
        d = cc.cached_dispatch(lambda x: x - 1, "test:fb")
        d.warm(jnp.zeros((2,)))               # engages the AOT path
        jit, lowerings = d._jit, []

        class Refusing:
            def lower(self, *args):
                lowerings.append(1)
                raise RuntimeError("injected lowering failure")

            def __call__(self, *args):
                return jit(*args)
        d._jit = Refusing()
        with pytest.warns(UserWarning, match="AOT lowering failed"):
            out = d(jnp.ones((3,)))           # a new signature
        assert float(out[0]) == 0.0           # dispatch survived
        with warnings.catch_warnings():
            warnings.simplefilter("error")    # and stays silent
            assert float(d(jnp.ones((3,)))[0]) == 0.0
        assert lowerings == [1] and d.warmed_signatures() == 1

    def test_sharding_in_signature(self):
        from deeplearning4j_tpu.parallel.mesh import DeviceMesh
        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device")

        def f(x):
            return x * 2
        d = cc.cached_dispatch(f, "test:shard")
        mesh = DeviceMesh.data_parallel()
        host = jnp.zeros((8, 4))
        with mesh:
            sharded = jax.device_put(host, mesh.batch_sharding(2))
            d.warm(sharded)
        d.warm(host)
        # two placements, two programs — a mesh change can never reuse
        # the single-device executable
        assert d.warmed_signatures() == 2


# ------------------------------------------------------------ model paths
class TestModelIntegration:
    def test_fit_bit_exact_with_cache(self):
        """A fit dispatched through AOT-compiled executables ends where
        a plain-jit fit ends."""
        ds = _data()
        base = MultiLayerNetwork(_mlp_conf()).init()
        base.fit(ds, epochs=3)
        cached = MultiLayerNetwork(_mlp_conf()).init()
        cc.warmup(cached, [((16, 8), (16, 3))])
        cached.fit(ds, epochs=3)
        assert np.array_equal(np.asarray(base.params()),
                              np.asarray(cached.params()))
        s = cc.cache_stats()
        assert s["compile_seconds"]["cold_compiles"] == 1
        assert s["memory"]["hits"] == 3

    def test_megastep_with_cache_bit_exact(self):
        batches = [_data(8, seed=i) for i in range(4)]
        base = MultiLayerNetwork(_mlp_conf()).init()
        base.fit(list(batches), epochs=1, steps_per_dispatch=2)
        cached = MultiLayerNetwork(_mlp_conf()).init()
        cc.warmup(cached, [((8, 8), (8, 3))], steps_per_dispatch=2)
        cached.fit(list(batches), epochs=1, steps_per_dispatch=2)
        assert np.array_equal(np.asarray(base.params()),
                              np.asarray(cached.params()))
        assert cc.cache_stats()["memory"]["hits"] == 2

    def test_graph_fit_with_cache(self):
        ds = _data()
        base = ComputationGraph(_graph_conf()).init()
        base.fit(ds, epochs=2)
        cached = ComputationGraph(_graph_conf()).init()
        cc.warmup(cached, [((16, 8), (16, 3))])
        cached.fit(ds, epochs=2)
        lb = [np.asarray(v) for v in jax.tree_util.tree_leaves(base._params)]
        lc = [np.asarray(v)
              for v in jax.tree_util.tree_leaves(cached._params)]
        assert all(np.array_equal(x, y) for x, y in zip(lb, lc))
        assert cc.cache_stats()["memory"]["hits"] == 2

    def test_zero_steady_state_recompiles_with_cache(self):
        """The churn-detector pin on the AOT path: 20 steps of
        steady-state fit = ONE signature at the fit site, one compile."""
        det = get_churn_detector()
        net = MultiLayerNetwork(_mlp_conf()).init()
        cc.warmup(net, [((16, 8), (16, 3))])
        before = det.signature_count("MultiLayerNetwork.fit", owner=net)
        for _ in range(20):
            net.fit(_data(), epochs=1)
        assert det.signature_count("MultiLayerNetwork.fit",
                                   owner=net) - before == 1
        s = cc.cache_stats()
        assert s["compile_seconds"]["cold_compiles"] == 1
        assert s["memory"]["misses"] == 0 and s["memory"]["hits"] == 20

    def test_warmup_api_forward_and_train(self):
        net = MultiLayerNetwork(_mlp_conf()).init()
        cc.warmup(net, [((16, 8), (16, 3)), (16, 8)])
        s = cc.cache_stats()
        assert s["compile_seconds"]["cold_compiles"] == 2
        p_before = np.asarray(net.params())
        cc.reset_stats()
        net.fit(_data(), epochs=1)            # no compile at dispatch
        net.output(np.zeros((16, 8), np.float32))
        s = cc.cache_stats()
        assert s["compile_seconds"]["cold_compiles"] == 0
        assert s["memory"]["hits"] >= 2
        # warmup itself never touched state
        net2 = MultiLayerNetwork(_mlp_conf()).init()
        assert np.array_equal(p_before, np.asarray(net2.params()))

    def test_warmup_megastep(self):
        net = MultiLayerNetwork(_mlp_conf()).init()
        cc.warmup(net, [((8, 8), (8, 3))], steps_per_dispatch=2)
        cc.reset_stats()
        net.fit([_data(8, seed=i) for i in range(2)], epochs=1,
                steps_per_dispatch=2)
        assert cc.cache_stats()["compile_seconds"]["cold_compiles"] == 0

    def test_warmup_graph(self):
        g = ComputationGraph(_graph_conf()).init()
        cc.warmup(g, [((16, 8), (16, 3)), (16, 8)])
        cc.reset_stats()
        g.fit(_data(), epochs=1)
        g.output(np.zeros((16, 8), np.float32))
        assert cc.cache_stats()["compile_seconds"]["cold_compiles"] == 0

    def test_warmup_bad_spec_rejected(self):
        net = MultiLayerNetwork(_mlp_conf()).init()
        with pytest.raises(ValueError, match="warmup shape spec"):
            cc.warmup(net, [((1, 2), (3, 4), (5, 6))])

    def test_warmup_delegates_to_server(self):
        net = MultiLayerNetwork(_mlp_conf()).init()
        sv = ModelServer(net, batch_limit=8, name="cc-deleg")
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                cc.warmup(sv, [(8,)])
            assert sv._warmed and sv.recompiles_after_warmup() == 0
        finally:
            sv.close()


# ---------------------------------------------------------------- serving
class TestServingCache:
    def test_serving_warmup_zero_recompiles_with_cache(self):
        """A server over a model whose forward is on the AOT path: the
        ladder warmup compiles each bucket ahead, and traffic is served
        by those executables."""
        net = MultiLayerNetwork(_mlp_conf()).init()
        cc.warmup(net, [(8, 8)])              # engages the AOT path
        sv = ModelServer(net, batch_limit=8, name="cc-srv1")
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                sv.warmup([(8,)])
            cc.reset_stats()
            out = sv.output(np.random.RandomState(0)
                            .randn(4, 8).astype(np.float32))
            assert out.shape == (4, 3)
            assert sv.recompiles_after_warmup() == 0
            s = cc.cache_stats()
            assert s["compile_seconds"]["cold_compiles"] == 0
            assert s["memory"]["hits"] >= 1 and s["memory"]["misses"] == 0
        finally:
            sv.close()


# ----------------------------------------------------------------- resume
class TestResumeWarmup:
    def test_checkpoint_records_batch_signature(self, tmp_path):
        from deeplearning4j_tpu.train.resilience import CheckpointConfig
        net = MultiLayerNetwork(_mlp_conf()).init()
        ck = str(tmp_path / "ck")
        net.fit([_data(), _data(16, 1)], epochs=1,
                checkpoint=CheckpointConfig(ck, every_steps=1))
        cps = sorted(d for d in os.listdir(ck) if d.startswith("ckpt_"))
        with open(os.path.join(ck, cps[-1], "extra.json")) as f:
            extra = json.load(f)
        sig = extra["extra"]["resilience"]["batch_signature"]
        assert sig["features"] == [[16, 8], "float32"]
        assert sig["labels"] == [[16, 3], "float32"]

    def test_resume_warms_from_recorded_signature(self, tmp_path,
                                                  placed_cache):
        """Where a persistent cache is placed, the resumed fit compiles
        its step from the checkpoint's batch signature BEFORE the first
        dispatch, and every dispatch is served by that executable."""
        from deeplearning4j_tpu.train.resilience import CheckpointConfig
        ck = str(tmp_path / "ck")
        a = MultiLayerNetwork(_mlp_conf()).init()
        a.fit([_data(), _data(16, 1)], epochs=1,
              checkpoint=CheckpointConfig(ck, every_steps=1))
        # a "fresh process" stand-in: new model object, resume=True
        cc.reset_stats()
        b = MultiLayerNetwork(_mlp_conf()).init()
        b.fit([_data(), _data(16, 1)], epochs=2,
              checkpoint=CheckpointConfig(ck, resume=True))
        s = cc.cache_stats()
        assert s["compile_seconds"]["cold_compiles"] == 1
        assert s["memory"]["hits"] >= 1 and s["memory"]["misses"] == 0

    def test_resume_warm_noop_without_cache(self, tmp_path):
        """No cache dir configured -> warm_after_resume is a no-op and
        resumed fits behave exactly as before (and stay bit-exact)."""
        from deeplearning4j_tpu.train.resilience import CheckpointConfig
        from deeplearning4j_tpu.faults import FaultPlan
        ck = str(tmp_path / "ck")
        full = MultiLayerNetwork(_mlp_conf()).init()
        full.fit(_iterator(), epochs=1)
        part = MultiLayerNetwork(_mlp_conf()).init()
        part.fit(_iterator(), epochs=1,
                 checkpoint=CheckpointConfig(ck, every_steps=1),
                 faults=FaultPlan(preempt_at_step=2))
        resumed = MultiLayerNetwork(_mlp_conf()).init()
        resumed.fit(_iterator(), epochs=1,
                    checkpoint=CheckpointConfig(ck, resume=True))
        assert np.array_equal(np.asarray(full.params()),
                              np.asarray(resumed.params()))
        assert cc.cache_stats()["compile_seconds"]["cold_compiles"] == 0


# ---------------------------------------------------------------- elastic
class TestElasticWarm:
    def test_survivor_mesh_warm_populates_cache(self, placed_cache):
        """The shrink path's warm seam: given a checkpoint-recorded
        batch signature, the survivor-mesh megastep is AOT-compiled
        (padded + sharded like the dispatch loop stages it) without
        touching model state, and a repeat warm compiles nothing."""
        import types
        from deeplearning4j_tpu.parallel.elastic import _warm_survivor_mesh
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device")
        net = MultiLayerNetwork(_mlp_conf()).init()
        wrapper = ParallelWrapper(net)
        session = types.SimpleNamespace(_last_batch_sig={
            "features": [[16, 8], "float32"],
            "labels": [[16, 3], "float32"]})
        p_before = np.asarray(net.params())
        _warm_survivor_mesh(wrapper, net, session, wrapper.mesh, k=2)
        assert cc.cache_stats()["compile_seconds"]["cold_compiles"] == 1
        assert np.array_equal(p_before, np.asarray(net.params()))
        assert [d.warmed_signatures()
                for d in net._megastep_cache.values()] == [1]
        _warm_survivor_mesh(wrapper, net, session, wrapper.mesh, k=2)
        assert cc.cache_stats()["compile_seconds"]["cold_compiles"] == 1

    def test_survivor_warm_noop_without_cache(self):
        import types
        from deeplearning4j_tpu.parallel.elastic import _warm_survivor_mesh
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        net = MultiLayerNetwork(_mlp_conf()).init()
        wrapper = ParallelWrapper(net)
        session = types.SimpleNamespace(_last_batch_sig={
            "features": [[16, 8], "float32"],
            "labels": [[16, 3], "float32"]})
        _warm_survivor_mesh(wrapper, net, session, wrapper.mesh, k=1)
        assert net._megastep_cache == {} and net._train_step_cache == {}


# ---------------------------------------------------------- cross-process
# One case of the script = one fresh process, over this file's own models
# and data. JAX's persistent cache is placed by the variable alone; the
# two thresholds are lowered so that programs this small are kept at all.
_XPROC = r"""
import json, os, sys, warnings
warnings.simplefilter("ignore")
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
hits = []
jax.monitoring.register_event_listener(
    lambda event, **kw: hits.append(1)
    if event == "/jax/compilation_cache/cache_hits" else None)
import numpy as np
from test_compilecache import (ComputationGraph, MultiLayerNetwork, cc,
                               _data, _graph_conf, _iterator, _mlp_conf)

case, work = sys.argv[1], sys.argv[2]


def mln():
    return MultiLayerNetwork(_mlp_conf()).init()


def leaves(net):
    return np.concatenate([np.asarray(v).ravel() for v in
                           jax.tree_util.tree_leaves(net._params)])


if case == "mln_fit":
    net = mln()
    net.fit(_data(), epochs=2)
    out = leaves(net)
elif case == "policy_bf16":
    net = mln()
    net.fit(_data(), epochs=2, precision="bf16")
    out = leaves(net)
elif case == "graph_fit":
    net = ComputationGraph(_graph_conf()).init()
    net.fit(_data(), epochs=2)
    out = leaves(net)
elif case == "megastep_k4":
    net = mln()
    net.fit([_data(8, seed=i) for i in range(4)], epochs=1,
            steps_per_dispatch=4)
    out = leaves(net)
elif case == "serving_warmup":
    from deeplearning4j_tpu.serving.server import ModelServer
    sv = ModelServer(mln(), batch_limit=8, name="xproc")
    # the variable this process started under is a placed cache
    assert "DL4J-W112" not in sv.validate(check_cache=True).codes()
    sv.warmup([(8,)])
    out = np.asarray(sv.output(_data(4).features))
    sv.close()
elif case in ("resume_kill", "resume_warm"):
    from deeplearning4j_tpu.faults import FaultPlan
    from deeplearning4j_tpu.train.resilience import CheckpointConfig
    ck = os.path.join(work, "ck")
    net = mln()
    if case == "resume_kill":       # the uninterrupted run is the answer
        net.fit(_iterator(), epochs=1)
        mln().fit(_iterator(), epochs=1,
                  checkpoint=CheckpointConfig(ck, every_steps=1),
                  faults=FaultPlan(preempt_at_step=2))
    else:
        net.fit(_iterator(), epochs=1,
                checkpoint=CheckpointConfig(ck, resume=True))
    out = leaves(net)
elif case == "gspmd_fit_8dev":
    from deeplearning4j_tpu.data.dataset import ListDataSetIterator
    from deeplearning4j_tpu.distributed import (GSPMDTrainer,
                                                ShardedTrainingPlan)
    from deeplearning4j_tpu.parallel.mesh import DeviceMesh
    assert len(jax.devices()) == 8
    net = mln()
    GSPMDTrainer(net, ShardedTrainingPlan(DeviceMesh.data_parallel())).fit(
        ListDataSetIterator(_data(48), 16), epochs=2)
    out = leaves(net)
else:
    raise SystemExit(f"unknown case {case!r}")
print(json.dumps({"hits": len(hits),
                  "aot_compiles":
                      cc.cache_stats()["compile_seconds"]["cold_compiles"],
                  "out": out.astype(np.float64).tolist()}))
"""


def _run_xproc(case, work):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(work, "jax_cache")
    tests = os.path.dirname(os.path.abspath(__file__))  # this file's makers
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(tests), tests, env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _XPROC, case, work],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    entries = set(os.listdir(env["JAX_COMPILATION_CACHE_DIR"]))
    return json.loads(proc.stdout.strip().splitlines()[-1]), entries


@pytest.mark.parametrize("first,second", [
    ("mln_fit", "mln_fit"),
    ("graph_fit", "graph_fit"),
    ("megastep_k4", "megastep_k4"),
    ("serving_warmup", "serving_warmup"),
    # killed after a checkpoint, resumed (and warmed ahead) by the second
    ("resume_kill", "resume_warm"),
    # the mesh on which a private store of serialized executables once
    # handed back an executable for the wrong number of shards
    ("gspmd_fit_8dev", "gspmd_fit_8dev"),
    ("mln_fit", "policy_bf16"),
], ids=["mln_fit", "graph_fit", "megastep_k4", "serving_warmup",
        "resume_warm", "gspmd_fit_8dev", "policy_change"])
def test_second_process_compiles_from_jax_cache(first, second, tmp_path):
    """THE cross-process pin: a second fresh process sharing the first's
    ``JAX_COMPILATION_CACHE_DIR`` writes NO new entry, sees hits, and
    ends bit-identical. Another PrecisionPolicy is another program and
    does write (no false sharing)."""
    r1, e1 = _run_xproc(first, str(tmp_path))
    assert e1                                 # the first process populated
    r2, e2 = _run_xproc(second, str(tmp_path))
    assert r2["hits"] >= 1
    if second == "policy_bf16":
        assert e2 - e1 and r2["out"] != r1["out"]
        return
    assert e2 == e1
    assert r2["out"] == r1["out"]             # cached exe = same math
    if second == "resume_warm":               # the gate opened: the step
        assert r2["aot_compiles"] == 1        # was compiled ahead, once


# ------------------------------------------------------------------- W112
class TestW112:
    def _server(self):
        return ModelServer(MultiLayerNetwork(_mlp_conf()).init(),
                           batch_limit=8, name="w112")

    def test_warmup_without_cache_warns_w112(self):
        sv = self._server()
        try:
            with pytest.warns(UserWarning, match="DL4J-W112"):
                sv.warmup([(8,)])
        finally:
            sv.close()

    def test_warmup_with_cache_no_w112(self, placed_cache):
        sv = self._server()
        try:
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                sv.warmup([(8,)])
            assert not any("W112" in str(w.message) for w in caught)
        finally:
            sv.close()

    def test_unwritable_dir_warns_w112(self, tmp_path, place_jax_cache):
        blocker = tmp_path / "blocker"          # file-as-parent: root-proof
        blocker.write_text("x")
        place_jax_cache(blocker / "cache")
        sv = self._server()
        try:
            with pytest.warns(UserWarning, match="writable"):
                sv.warmup([(8,)])
        finally:
            sv.close()

    def test_static_validate_stays_silent(self):
        """A pure-static validate() (no warmup) must NOT fire W112 —
        the pre-existing clean-bill pins depend on it."""
        sv = self._server()
        try:
            assert "DL4J-W112" not in sv.validate().codes()
            assert "DL4J-W112" in sv.validate(check_cache=True).codes()
        finally:
            sv.close()

    def test_lint_compile_cache_direct(self, tmp_path, place_jax_cache):
        from deeplearning4j_tpu.analysis import lint_compile_cache
        diags = lint_compile_cache()
        assert diags and diags[0].code == "DL4J-W112"
        assert "JAX_COMPILATION_CACHE_DIR" in diags[0].fix_hint
        assert "place_jax_compile_cache" in diags[0].fix_hint
        place_jax_cache(tmp_path)
        assert lint_compile_cache() == []

    def test_placed_but_switched_off_warns_w112(self, placed_cache):
        """A directory jax was told not to use is no cache."""
        from deeplearning4j_tpu.analysis import lint_compile_cache
        assert lint_compile_cache() == []
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            diags = lint_compile_cache()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
        assert [d.code for d in diags] == ["DL4J-W112"]

    def test_w112_suppressible(self):
        sv = self._server()
        try:
            report = sv.validate(check_cache=True)
            assert "DL4J-W112" in report.codes()
            report2 = report.apply_config(suppress=["DL4J-W112"])
            assert "DL4J-W112" not in report2.codes()
        finally:
            sv.close()

    def test_w112_documented(self):
        from deeplearning4j_tpu.analysis.diagnostics import DIAGNOSTIC_CODES
        assert "DL4J-W112" in DIAGNOSTIC_CODES
        assert "JAX_COMPILATION_CACHE_DIR" in DIAGNOSTIC_CODES["DL4J-W112"]


# ------------------------------------------------------- tracer streaming
class TestTraceStreaming:
    def test_stream_past_ring_horizon(self, tmp_path):
        from deeplearning4j_tpu.profiler.tracer import SpanTracer
        tr = SpanTracer(capacity=10)
        path = str(tmp_path / "trace.json")
        tr.stream_to(path)
        for i in range(50):
            tr.add_event(f"span{i}", float(i), 1.0)
        assert len(tr) == 10                  # ring kept only the tail
        out = tr.stop_stream()
        assert out == path
        with open(path) as f:
            doc = json.load(f)                # valid JSON array
        names = [e["name"] for e in doc if e.get("ph") == "X"]
        assert names[:1] == ["span0"] and len(names) == 50

    def test_stream_truncated_is_loadable_prefix(self, tmp_path):
        """A killed process's stream (no stop_stream) is a truncated
        JSON array whose events are still individually parseable."""
        from deeplearning4j_tpu.profiler.tracer import (SpanTracer,
                                                        _STREAM_FLUSH_EVERY)
        tr = SpanTracer(capacity=4)
        path = str(tmp_path / "t.json")
        tr.stream_to(path)
        for i in range(_STREAM_FLUSH_EVERY + 10):
            tr.add_event(f"s{i}", float(i), 1.0)
        with open(path) as f:                 # flushed prefix on disk
            body = f.read()
        assert body.startswith("[\n")
        first = body[2:].split(",\n")[0]
        assert json.loads(first)["name"] == "s0"
        tr.stop_stream()

    def test_stream_to_same_path_idempotent(self, tmp_path):
        from deeplearning4j_tpu.profiler.tracer import SpanTracer
        tr = SpanTracer()
        path = str(tmp_path / "t.json")
        tr.stream_to(path)
        tr.add_event("a", 0.0, 1.0)
        tr.stream_to(path)                    # no restart, no truncation
        tr.add_event("b", 1.0, 1.0)
        tr.stop_stream()
        with open(path) as f:
            doc = json.load(f)
        assert [e["name"] for e in doc if e.get("ph") == "X"] == ["a", "b"]

    def test_stream_via_global_tracer_spans(self, tmp_path):
        from deeplearning4j_tpu import profiler as prof
        tr = prof.get_tracer()
        path = str(tmp_path / "g.json")
        tr.stream_to(path)
        prof.enable_tracing()
        try:
            with prof.trace_span("test:streamed"):
                pass
        finally:
            prof.disable_tracing()
            tr.stop_stream()
        with open(path) as f:
            doc = json.load(f)
        assert any(e["name"] == "test:streamed" for e in doc)


# ------------------------------------------------- dynamic loss scaling
class TestDynamicLossScaling:
    def test_policy_coercion_and_signature(self):
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        p = PrecisionPolicy("fp16", loss_scale="dynamic")
        assert p.is_dynamic and p.numeric_loss_scale() == 2.0 ** 15
        assert p.loss_scale_init == 2.0 ** 15
        q = PrecisionPolicy.from_config(p.to_config())
        assert q == p and q.signature() == p.signature()
        # a different knob = a different signature (cache bust)
        r = PrecisionPolicy("fp16", loss_scale="dynamic",
                            growth_interval=10)
        assert r.signature() != p.signature()
        with pytest.raises(ValueError, match="only string value"):
            PrecisionPolicy("fp16", loss_scale="auto")

    def test_static_pins_unchanged(self):
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        p = PrecisionPolicy("fp16", loss_scale=2048.0)
        assert not p.is_dynamic and p.numeric_loss_scale() == 2048.0
        assert p.signature() == ("float16", "float32", 2048.0)

    def test_no_overflow_equals_static_bit_exact(self):
        """With no overflow and growth disabled, dynamic(init=S) ==
        static(S) bit-exactly — the automaton is pure bookkeeping until
        something overflows. Checked op by op as well as compiled."""
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        ds = _data()

        def pair():
            dyn = MultiLayerNetwork(_mlp_conf()).init()
            dyn.fit(ds, epochs=3, precision=PrecisionPolicy(
                "fp16", loss_scale="dynamic", loss_scale_init=2.0 ** 10,
                growth_interval=10 ** 9))
            st = MultiLayerNetwork(_mlp_conf()).init()
            st.fit(ds, epochs=3, precision=PrecisionPolicy(
                "fp16", loss_scale=2.0 ** 10))
            assert dyn.current_loss_scale() == 2.0 ** 10
            return np.asarray(dyn.params()), np.asarray(st.params())
        with jax.disable_jit():
            dyn, st = pair()
        assert np.array_equal(dyn, st)
        dyn, st = pair()
        assert np.array_equal(dyn, st)

    def test_overflow_skips_update_and_backs_off(self):
        """An absurd init scale overflows the fp16 backward: the step's
        update is DROPPED (params unchanged) and the scale halves."""
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        net = MultiLayerNetwork(_mlp_conf()).init()
        net.setPrecisionPolicy(PrecisionPolicy(
            "fp16", loss_scale="dynamic", loss_scale_init=2.0 ** 31))
        before = np.asarray(net.params())
        net.fit(_data(), epochs=1)
        assert np.array_equal(before, np.asarray(net.params()))
        assert net.current_loss_scale() == 2.0 ** 30
        # ...and training still makes progress once the scale descends
        for _ in range(25):
            net.fit(_data(), epochs=1)
        assert not np.array_equal(before, np.asarray(net.params()))
        assert net.current_loss_scale() < 2.0 ** 31

    def test_growth_after_interval(self):
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        net = MultiLayerNetwork(_mlp_conf()).init()
        net.setPrecisionPolicy(PrecisionPolicy(
            "fp16", loss_scale="dynamic", loss_scale_init=4.0,
            growth_interval=2))
        for _ in range(4):
            net.fit(_data(), epochs=1)
        assert net.current_loss_scale() == 16.0     # 4 -> 8 -> 16

    def test_growth_capped_at_max(self):
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        net = MultiLayerNetwork(_mlp_conf()).init()
        net.setPrecisionPolicy(PrecisionPolicy(
            "fp16", loss_scale="dynamic", loss_scale_init=8.0,
            growth_interval=1, max_loss_scale=16.0))
        for _ in range(5):
            net.fit(_data(), epochs=1)
        assert net.current_loss_scale() == 16.0

    def test_megastep_dynamic_equals_single_steps(self):
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        batches = [_data(8, seed=i) for i in range(4)]
        pol = PrecisionPolicy("fp16", loss_scale="dynamic",
                              loss_scale_init=2.0 ** 10,
                              growth_interval=3)
        a = MultiLayerNetwork(_mlp_conf()).init()
        a.fit(list(batches), epochs=1, steps_per_dispatch=2, precision=pol)
        b = MultiLayerNetwork(_mlp_conf()).init()
        b.fit(list(batches), epochs=1, precision=pol)
        assert np.array_equal(np.asarray(a.params()), np.asarray(b.params()))
        assert a.current_loss_scale() == b.current_loss_scale()

    def test_graph_dynamic_scaling(self):
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        ds = _data()
        dyn = ComputationGraph(_graph_conf()).init()
        dyn.fit(ds, epochs=2, precision=PrecisionPolicy(
            "fp16", loss_scale="dynamic", loss_scale_init=2.0 ** 10,
            growth_interval=10 ** 9))
        st = ComputationGraph(_graph_conf()).init()
        st.fit(ds, epochs=2, precision=PrecisionPolicy(
            "fp16", loss_scale=2.0 ** 10))
        ld = [np.asarray(v) for v in jax.tree_util.tree_leaves(dyn._params)]
        ls = [np.asarray(v) for v in jax.tree_util.tree_leaves(st._params)]
        assert all(np.array_equal(x, y) for x, y in zip(ld, ls))

    def test_scale_carried_through_checkpoint_resume(self, tmp_path):
        """Resume restores the automaton mid-flight: interrupted + resumed
        == uninterrupted, scale state included (bit-exact)."""
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        from deeplearning4j_tpu.train.resilience import CheckpointConfig
        from deeplearning4j_tpu.faults import FaultPlan
        pol = PrecisionPolicy("fp16", loss_scale="dynamic",
                              loss_scale_init=4.0, growth_interval=2)
        full = MultiLayerNetwork(_mlp_conf()).init()
        full.fit(_iterator(), epochs=1, precision=pol)
        ck = str(tmp_path / "ck")
        part = MultiLayerNetwork(_mlp_conf()).init()
        part.fit(_iterator(), epochs=1, precision=pol,
                 checkpoint=CheckpointConfig(ck, every_steps=1),
                 faults=FaultPlan(preempt_at_step=3))
        assert part.current_loss_scale() > 4.0      # grew before preempt
        res = MultiLayerNetwork(_mlp_conf()).init()
        res.fit(_iterator(), epochs=1, precision=pol,
                checkpoint=CheckpointConfig(ck, resume=True))
        assert np.array_equal(np.asarray(full.params()),
                              np.asarray(res.params()))
        assert res.current_loss_scale() == full.current_loss_scale()

    def test_policy_reattach_keeps_programs(self):
        """Equal dynamic policy re-attach keeps the compiled step (zero
        recompiles); a changed one busts it — on the AOT path."""
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        net = MultiLayerNetwork(_mlp_conf()).init()
        pol = PrecisionPolicy("fp16", loss_scale="dynamic",
                              loss_scale_init=2.0 ** 10)
        cc.warmup(net, [((16, 8), (16, 3))], policy=pol)
        net.fit(_data(), epochs=1, precision=pol)
        step = net._train_step_cache[(False, False)]
        net.setPrecisionPolicy(PrecisionPolicy(
            "fp16", loss_scale="dynamic", loss_scale_init=2.0 ** 10))
        assert net._train_step_cache[(False, False)] is step
        net.setPrecisionPolicy(PrecisionPolicy(
            "fp16", loss_scale="dynamic", loss_scale_init=2.0 ** 8))
        assert (False, False) not in net._train_step_cache

    def test_sanitizer_attribution_with_dynamic_policy(self):
        """NAN_PANIC provenance must survive a dynamic policy: the
        replay rolls the scale carry and attributes the poisoned batch
        instead of crashing on the extra step argument."""
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        from deeplearning4j_tpu.profiler.modes import (ProfilingMode,
                                                       set_profiling_mode)
        from deeplearning4j_tpu.profiler.sanitizer import \
            NonfiniteAttributionError
        net = MultiLayerNetwork(_mlp_conf()).init()
        net.setPrecisionPolicy(PrecisionPolicy(
            "fp16", loss_scale="dynamic", loss_scale_init=2.0 ** 8))
        set_profiling_mode(ProfilingMode.NAN_PANIC)
        try:
            net.fit(_data(), epochs=1)        # clean dispatch first
            bad = _data(seed=1)
            bad.features[0, 0] = np.nan
            with pytest.raises(NonfiniteAttributionError, match="batch"):
                net.fit(bad, epochs=1)
        finally:
            set_profiling_mode(ProfilingMode.OFF)

    def test_w302_handles_dynamic(self):
        from deeplearning4j_tpu.nn.precision import PrecisionPolicy
        from deeplearning4j_tpu.analysis.numerics import lint_numerics
        # dynamic on bf16 is still pointless -> W302; on fp16 it is the
        # recommended configuration -> silent, and E303 (missing scale)
        # must NOT fire
        conf = _mlp_conf()
        rep = lint_numerics(conf, policy=PrecisionPolicy(
            "bf16", loss_scale="dynamic"))
        assert "DL4J-W302" in [d.code for d in rep]
        rep = lint_numerics(conf, policy=PrecisionPolicy(
            "fp16", loss_scale="dynamic"))
        codes = [d.code for d in rep]
        assert "DL4J-E303" not in codes and "DL4J-W302" not in codes
        # a dynamic automaton whose INIT scale already overflows the
        # declared range is judged at that worst case: every run starts
        # by dropping updates until backoff converges -> E303
        rep = lint_numerics(conf, policy=PrecisionPolicy(
            "fp16", loss_scale="dynamic", loss_scale_init=2.0 ** 24),
            data_range="0..255")
        assert "DL4J-E303" in [d.code for d in rep]

    def test_cli_accepts_dynamic_policy(self, capsys):
        from deeplearning4j_tpu.analysis.__main__ import main
        rc = main(["LeNet", "--policy",
                   "compute=fp16,loss_scale=dynamic,growth_interval=100",
                   "--warnings-ok"])
        assert rc == 0
        with pytest.raises(SystemExit):        # typo'd scale: clean usage
            main(["LeNet", "--policy", "compute=fp16,loss_scale=auto"])
        capsys.readouterr()
