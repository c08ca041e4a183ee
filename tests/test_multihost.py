"""Multi-host wiring tests: 2 real processes on one machine, wired into a
single global device mesh via ``initializeDistributed`` (gloo CPU
collectives), per-process data sharding, and the sharded checkpoint
layout — plus (ISSUE 15 tier 3, ``pytest -m multihost``) the socket/file
CoordinationService: 2 OS worker processes rendezvous at the PR-6 resume
barrier over TCP, agree on the min step bit-exactly like the in-process
coordinator, and a peer that stops heartbeating surfaces the structured
dead-peer error instead of N independent timeouts.

Reference parity: SURVEY.md §5 "Distributed communication backend" / §7
hard-part #7 — the reference proves its Spark+Aeron plumbing with
multi-worker integration tests; here two OS processes really rendezvous,
train the same SPMD step on a mesh spanning both, and checkpoint/restore
shard-wise.
"""

import json
import os
import socket
import subprocess
import sys
import threading

import numpy as np
import pytest

_WORKER = r"""
import json, os, sys
sys.path.insert(0, os.environ["DL4J_REPO"])
import numpy as np

# pin to the CPU BEFORE the backend initializes (same move as
# tests/conftest.py): a worker must never reach for an accelerator
import jax
jax.config.update("jax_platforms", "cpu")

from deeplearning4j_tpu.parallel.init import initializeDistributed
info = initializeDistributed()
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.data.dataset import DataSet, ListDataSetIterator
from deeplearning4j_tpu.parallel.data import (ShardedDataSetIterator,
                                              make_global_view)
from deeplearning4j_tpu.parallel import checkpoint as ckpt

assert info.process_count == 2, info
assert info.global_device_count == 4, info

mesh = Mesh(np.asarray(jax.devices()).reshape(4), ("data",))

# deterministic global dataset, identical on both ranks
rng = np.random.RandomState(0)
X = rng.randn(16, 8).astype(np.float32)
W_true = rng.randn(8, 1).astype(np.float32)
Y = X @ W_true
base = ListDataSetIterator(DataSet(X, Y), batch_size=16)
it = ShardedDataSetIterator(base)
assert it.batch() == 8

params = {"W": jnp.zeros((8, 1), jnp.float32)}
rep = NamedSharding(mesh, P())
params = jax.device_put(params, rep)

@jax.jit
def step(params, x, y):
    def loss_fn(p):
        return jnp.mean((x @ p["W"] - y) ** 2)
    l, g = jax.value_and_grad(loss_fn)(params)
    return jax.tree_util.tree_map(lambda p, gg: p - 0.1 * gg, params, g), l

losses = []
for _ in range(12):
    it.reset()
    while it.hasNext():
        ds = it.next()
        x = make_global_view(ds.features, mesh, P("data"))
        y = make_global_view(ds.labels, mesh, P("data"))
        params, l = step(params, x, y)
        losses.append(float(l))

out_dir = os.environ["DL4J_CKPT_DIR"]
ckpt.save_sharded(out_dir, params, step=12)

# restore into the same sharding and verify
restored, got_step = ckpt.load_sharded(out_dir, params)
np.testing.assert_allclose(np.asarray(restored["W"]),
                           np.asarray(params["W"]), rtol=0, atol=0)
assert got_step == 12

print("RESULT " + json.dumps({
    "rank": info.process_index,
    "losses": [round(v, 8) for v in losses],
    "w_sum": float(np.asarray(params["W"]).sum()),
}))
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.mark.multihost
def test_two_process_train_and_checkpoint(tmp_path):
    port = _free_port()
    ckpt_dir = str(tmp_path / "ckpt")
    worker = str(tmp_path / "worker.py")
    with open(worker, "w") as f:
        f.write(_WORKER)
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "DL4J_TPU_COORDINATOR": f"127.0.0.1:{port}",
            "DL4J_TPU_NUM_PROCESSES": "2",
            "DL4J_TPU_PROCESS_ID": str(rank),
            "DL4J_REPO": os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            "DL4J_CKPT_DIR": ckpt_dir,
        })
        procs.append(subprocess.Popen([sys.executable, worker],
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT, env=env,
                                      text=True))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process workers timed out")
        outs.append(out)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
    results = []
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("RESULT ")][-1]
        results.append(json.loads(line[len("RESULT "):]))
    assert {r["rank"] for r in results} == {0, 1}
    # SPMD: both processes computed identical global losses and params
    assert results[0]["losses"] == results[1]["losses"]
    assert results[0]["w_sum"] == pytest.approx(results[1]["w_sum"])
    # training converged on the global (not process-local) problem
    assert results[0]["losses"][-1] < results[0]["losses"][0] * 0.1
    # both processes' shard files exist + one merged manifest
    files = os.listdir(ckpt_dir)
    assert "manifest.json" in files
    assert "shards_p0.npz" in files and "shards_p1.npz" in files


class TestShardedCheckpointSingleProcess:
    """Same layout on the 8-virtual-device mesh: sharded leaves write one
    shard per device index; load assembles exactly the addressable set."""

    def test_sharded_params_roundtrip(self, tmp_path):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.parallel import checkpoint as ckpt

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        tree = {
            "W": jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                                NamedSharding(mesh, P("data"))),
            "b": jax.device_put(jnp.ones((8,)), NamedSharding(mesh, P())),
            "step_count": 7,   # non-array leaf
        }
        d = str(tmp_path / "ck")
        ckpt.save_sharded(d, tree, step=3)
        restored, step = ckpt.load_sharded(d, tree)
        assert step == 3
        np.testing.assert_array_equal(np.asarray(restored["W"]),
                                      np.asarray(tree["W"]))
        np.testing.assert_array_equal(np.asarray(restored["b"]),
                                      np.asarray(tree["b"]))
        # shardings preserved; scalar leaves keep their Python type
        assert restored["W"].sharding.spec == P("data")
        assert restored["step_count"] == 7
        assert isinstance(restored["step_count"], int)

    def test_sharded_save_into_host_tree_assembles_all_shards(self, tmp_path):
        """ADVICE r3 (medium): restoring a sharded checkpoint into a plain
        numpy/host target must assemble the FULL global array, not silently
        return the first shard's slice."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.parallel import checkpoint as ckpt

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        full = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        tree = {"W": jax.device_put(full, NamedSharding(mesh, P("data")))}
        d = str(tmp_path / "ck3")
        ckpt.save_sharded(d, tree, step=1)
        # target is a host numpy tree: no sharding info at all
        restored, step = ckpt.load_sharded(d, {"W": np.zeros((8, 8),
                                                            np.float32)})
        assert step == 1
        assert restored["W"].shape == (8, 8)
        np.testing.assert_array_equal(np.asarray(restored["W"]),
                                      np.asarray(full))

    def test_topology_change_reshards_on_load(self, tmp_path):
        # ISSUE 6: a checkpoint saved under one mesh layout loads under
        # another — each target shard is stitched from the saved shards
        # (the elastic shrink/grow resume path)
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.parallel import checkpoint as ckpt

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        rng = np.random.RandomState(3)
        full = rng.randn(8, 4).astype(np.float32)
        sharded = jax.device_put(jnp.asarray(full),
                                 NamedSharding(mesh, P("data")))
        d = str(tmp_path / "ck2")
        ckpt.save_sharded(d, {"W": sharded})
        # replicated target: the full array assembles from the 8 shards
        repl = jax.device_put(jnp.zeros((8, 4)), NamedSharding(mesh, P()))
        restored, _ = ckpt.load_sharded(d, {"W": repl})
        np.testing.assert_array_equal(np.asarray(restored["W"]), full)
        # 4-device shrunk mesh: each wider shard stitches from two saved
        half = Mesh(np.array(jax.devices()[:4]).reshape(4), ("data",))
        tgt = jax.device_put(jnp.zeros((8, 4)), NamedSharding(half, P("data")))
        restored, _ = ckpt.load_sharded(d, {"W": tgt})
        np.testing.assert_array_equal(np.asarray(restored["W"]), full)
        assert len(restored["W"].sharding.device_set) == 4

    def test_uncoverable_topology_still_fails_loudly(self, tmp_path):
        # shards that genuinely can't tile the requested slice (a shard
        # missing from the manifest) must raise, never return garbage
        import jax
        import jax.numpy as jnp
        import json
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.parallel import checkpoint as ckpt

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        sharded = jax.device_put(jnp.zeros((8, 4)),
                                 NamedSharding(mesh, P("data")))
        d = str(tmp_path / "ck4")
        ckpt.save_sharded(d, {"W": sharded})
        man = os.path.join(d, "manifest.json")
        with open(man) as f:
            manifest = json.load(f)
        manifest["leaves"]["W"]["shards"].pop("0:1;0:4")
        with open(man, "w") as f:
            json.dump(manifest, f)
        repl = jax.device_put(jnp.zeros((8, 4)), NamedSharding(mesh, P()))
        with pytest.raises(FileNotFoundError, match="cover only"):
            ckpt.load_sharded(d, {"W": repl})


# ===================================================== socket coordinator
# ISSUE 15 tier 3: the PR-6 barrier protocol over real OS processes.
# Workers are jax-free on purpose — the coordinator is pure wire
# protocol, and jax-free workers keep the socket tests well under the
# 30 s budget the tier-1 gate expects.

_BARRIER_WORKER = r"""
import json, os, sys
sys.path.insert(0, os.environ["DL4J_REPO"])
from deeplearning4j_tpu.distributed import SocketCoordinator

rank = os.environ["COORD_RANK"]
addr = os.environ["COORD_ADDR"]
steps = json.loads(os.environ["COORD_STEPS"])
c = SocketCoordinator(addr, participant=f"p{rank}",
                      heartbeat_interval=0.2)
agreed = [c.resume_barrier(f"p{rank}", s, timeout=20.0) for s in steps]
c.close()
print("RESULT " + json.dumps({"rank": rank, "agreed": agreed}))
"""

_DEAD_PEER_WORKER = r"""
import json, os, sys
sys.path.insert(0, os.environ["DL4J_REPO"])
from deeplearning4j_tpu.distributed import DeadPeerError, SocketCoordinator

c = SocketCoordinator(os.environ["COORD_ADDR"], participant="alive",
                      heartbeat_interval=0.2)
try:
    c.resume_barrier("alive", 5, timeout=20.0)
    out = {"error": None}
except DeadPeerError as e:
    out = {"error": "dead_peer", "peer": e.peer,
           "generation": e.generation}
c.close()
print("RESULT " + json.dumps(out))
"""

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(script_path, extra_env):
    env = dict(os.environ)
    env["DL4J_REPO"] = _REPO
    env.update(extra_env)
    return subprocess.Popen([sys.executable, script_path],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, env=env, text=True)


def _result(proc, timeout=60):
    out, _ = proc.communicate(timeout=timeout)
    assert proc.returncode == 0, f"worker failed:\n{out[-2000:]}"
    line = [l for l in out.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.multihost
class TestSocketCoordinatorMultiProcess:
    def test_two_process_barrier_agrees_with_in_process(self, tmp_path):
        """THE tier-3 pin: 2 OS worker processes run two successive
        resume barriers over the socket coordinator and agree on
        exactly the steps the in-process coordinator agrees on for the
        same inputs (min per round; barriers reusable)."""
        from deeplearning4j_tpu.distributed import SocketCoordinatorServer
        from deeplearning4j_tpu.parallel.elastic import InProcessCoordinator

        steps = {"0": [12, 20], "1": [7, 25]}
        # in-process reference for the same arrival steps
        ref = InProcessCoordinator(2)
        ref_agreed = {r: [] for r in steps}

        def arrive(rank):
            for s in steps[rank]:
                ref_agreed[rank].append(
                    ref.resume_barrier(f"p{rank}", s, timeout=10.0))
        ts = [threading.Thread(target=arrive, args=(r,)) for r in steps]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

        worker = str(tmp_path / "worker.py")
        with open(worker, "w") as f:
            f.write(_BARRIER_WORKER)
        with SocketCoordinatorServer(participants=2) as srv:
            procs = [_spawn(worker, {"COORD_RANK": r,
                                     "COORD_ADDR": srv.address,
                                     "COORD_STEPS": json.dumps(steps[r])})
                     for r in steps]
            results = {res["rank"]: res["agreed"]
                       for res in (_result(p) for p in procs)}
        assert results == ref_agreed == {"0": [7, 20], "1": [7, 20]}

    def test_dead_peer_surfaces_structured_error(self, tmp_path):
        """A registered peer that stops heartbeating while a barrier is
        pending fails the round for the survivor with DeadPeerError
        (peer name + generation), not a bare timeout."""
        from deeplearning4j_tpu.distributed import (SocketCoordinator,
                                                    SocketCoordinatorServer)
        worker = str(tmp_path / "worker.py")
        with open(worker, "w") as f:
            f.write(_DEAD_PEER_WORKER)
        with SocketCoordinatorServer(participants=2,
                                     heartbeat_timeout=0.6) as srv:
            # the doomed peer registers, then dies (heartbeats stop)
            doomed = SocketCoordinator(srv.address, participant="doomed",
                                       heartbeat_interval=0.2)
            doomed.hello()
            doomed.close()
            res = _result(_spawn(worker, {"COORD_ADDR": srv.address}))
        assert res == {"error": "dead_peer", "peer": "doomed",
                       "generation": 0}

    def test_coord_peer_death_fault_kind(self):
        """The faults.py seam: a FaultPlan-planned peer death fires the
        dead-peer path deterministically even while the peer's process
        keeps heartbeating — every barrier failure mode is a seeded
        chaos test, per the resilience-stack contract."""
        from deeplearning4j_tpu.distributed import (DeadPeerError,
                                                    SocketCoordinator,
                                                    SocketCoordinatorServer)
        from deeplearning4j_tpu.faults import FaultPlan
        plan = FaultPlan(coord_peer_death={"participant": "zombie",
                                           "generation": 0})
        with SocketCoordinatorServer(participants=2, heartbeat_timeout=0.5,
                                     plan=plan) as srv:
            zombie = SocketCoordinator(srv.address, participant="zombie",
                                       heartbeat_interval=0.1)
            zombie.hello()          # keeps heartbeating, but planned dead
            alive = SocketCoordinator(srv.address, participant="alive")
            with pytest.raises(DeadPeerError) as ei:
                alive.resume_barrier("alive", 3, timeout=10.0)
            assert ei.value.peer == "zombie"
            zombie.close()
            alive.close()

    def test_barrier_timeout_when_peer_never_registers(self):
        from deeplearning4j_tpu.distributed import (SocketCoordinator,
                                                    SocketCoordinatorServer)
        with SocketCoordinatorServer(participants=2) as srv:
            c = SocketCoordinator(srv.address, participant="alone")
            with pytest.raises(TimeoutError, match="1/2 participants"):
                c.resume_barrier("alone", 4, timeout=0.4)
            c.close()


@pytest.mark.multihost
class TestFileCoordinator:
    def test_two_process_file_barrier(self, tmp_path):
        """Shared-filesystem rendezvous: 2 OS processes agree on the min
        step with no server process at all."""
        script = str(tmp_path / "fworker.py")
        with open(script, "w") as f:
            f.write(r"""
import json, os, sys
sys.path.insert(0, os.environ["DL4J_REPO"])
from deeplearning4j_tpu.distributed import FileCoordinator
c = FileCoordinator(os.environ["COORD_DIR"], participants=2,
                    participant=os.environ["COORD_RANK"])
agreed = c.resume_barrier(os.environ["COORD_RANK"],
                          int(os.environ["COORD_STEP"]), timeout=20.0)
c.close()
print("RESULT " + json.dumps({"agreed": agreed}))
""")
        d = str(tmp_path / "coord")
        procs = [_spawn(script, {"COORD_DIR": d, "COORD_RANK": f"p{i}",
                                 "COORD_STEP": str(s)})
                 for i, s in enumerate((9, 4))]
        results = [_result(p) for p in procs]
        assert [r["agreed"] for r in results] == [4, 4]

    def test_file_dead_peer(self, tmp_path):
        from deeplearning4j_tpu.distributed import (DeadPeerError,
                                                    FileCoordinator)
        d = str(tmp_path / "coord2")
        dead = FileCoordinator(d, participants=2, participant="dead",
                               heartbeat_timeout=0.5,
                               heartbeat_interval=0.1)
        # simulate a CRASH (not a clean close, which retires the
        # heartbeat file): the heartbeat thread just stops
        dead._closed.set()
        dead._hb_thread.join(timeout=2.0)
        alive = FileCoordinator(d, participants=2, participant="alive",
                                heartbeat_timeout=0.5)
        with pytest.raises(DeadPeerError) as ei:
            alive.resume_barrier("alive", 3, timeout=10.0)
        assert ei.value.peer == "dead"
        alive.close()

    def test_reused_directory_ignores_previous_runs_files(self, tmp_path):
        """A coordination directory reused after a crash must not agree
        on the previous run's steps (stale gen files) or flag its dead
        participants (stale hb files) — freshness-floored by mtime."""
        import time as _time
        from deeplearning4j_tpu.distributed import FileCoordinator
        d = str(tmp_path / "coord3")
        os.makedirs(d)
        past = _time.time() - 60
        for fname in ("gen0_ghost.json", "hb_ghost"):
            path = os.path.join(d, fname)
            with open(path, "w") as f:
                f.write('{"step": 1}')
            os.utime(path, (past, past))
        results = {}

        def arrive(name, step):
            c = FileCoordinator(d, participants=2, participant=name,
                                heartbeat_timeout=5.0)
            results[name] = c.resume_barrier(name, step, timeout=10.0)
            c.close()
        ts = [threading.Thread(target=arrive, args=(n, s))
              for n, s in (("a", 9), ("b", 6))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # the ghost's stale step-1 arrival did NOT join the round
        assert results == {"a": 6, "b": 6}

    def test_quick_restart_ignores_previous_runs_result(self, tmp_path):
        """A supervisor restarting a worker into a reused directory
        within seconds must NOT consume the dead run's result file:
        acceptance is floored on this run's own arrival mtime, not on
        construction time."""
        from deeplearning4j_tpu.distributed import FileCoordinator
        d = str(tmp_path / "coord5")
        os.makedirs(d)
        with open(os.path.join(d, "result_gen0.json"), "w") as f:
            f.write('{"step": 999}')        # written moments ago
        c = FileCoordinator(d, participants=2, participant="a")
        with pytest.raises(TimeoutError):
            c.resume_barrier("a", 5, timeout=1.0)
        c.close()

    def test_staggered_construction_still_agrees(self, tmp_path):
        """A peer that constructs (and arrives) seconds before another
        even builds its coordinator must still be counted — liveness is
        heartbeat freshness, not file age vs construction time."""
        import time as _time
        from deeplearning4j_tpu.distributed import FileCoordinator
        d = str(tmp_path / "coord4")
        results = {}
        early = FileCoordinator(d, participants=2, participant="early",
                                heartbeat_interval=0.2)

        def arrive_early():
            results["early"] = early.resume_barrier("early", 11,
                                                    timeout=20.0)
        t = threading.Thread(target=arrive_early)
        t.start()
        _time.sleep(1.5)        # "early" has long since arrived
        late = FileCoordinator(d, participants=2, participant="late",
                               heartbeat_interval=0.2)
        results["late"] = late.resume_barrier("late", 4, timeout=20.0)
        t.join()
        early.close()
        late.close()
        assert results == {"early": 4, "late": 4}


@pytest.mark.multihost
class TestElasticOverSocketCoordinator:
    def test_fit_elastic_shrinks_through_the_socket_barrier(self, tmp_path,
                                                            devices):
        """``ParallelWrapper.fit(elastic=...)`` with the SOCKET
        coordinator plugged into ElasticConfig: a device loss runs the
        coordinated shrink with the resume barrier over TCP — the
        in-process stand-in is genuinely replaced, fit completes on the
        survivor mesh."""
        from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
        from deeplearning4j_tpu.distributed import (SocketCoordinator,
                                                    SocketCoordinatorServer)
        from deeplearning4j_tpu.faults import FaultPlan
        from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                           NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.parallel import ParallelWrapper
        from deeplearning4j_tpu.parallel.elastic import ElasticConfig
        from deeplearning4j_tpu.train import updaters
        from deeplearning4j_tpu.train.resilience import CheckpointConfig

        conf = (NeuralNetConfiguration.Builder().seed(3)
                .updater(updaters.Sgd(0.05)).list()
                .layer(DenseLayer(nOut=16, activation="relu"))
                .layer(OutputLayer(nOut=2, lossFunction="mcxent",
                                   activation="softmax"))
                .setInputType(InputType.feedForward(8))
                .build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.RandomState(0)
        ds = DataSet(rng.randn(64, 8).astype(np.float32),
                     np.eye(2, dtype=np.float32)[rng.randint(0, 2, 64)])
        plan = FaultPlan(device_loss_at_step=3, lose_devices=[6, 7])
        with SocketCoordinatorServer(participants=1) as srv:
            coord = SocketCoordinator(srv.address, participant="proc0")
            w = ParallelWrapper(net)
            w.fit(ListDataSetIterator(ds, 8), epochs=1,
                  checkpoint=CheckpointConfig(str(tmp_path / "ck")),
                  elastic=ElasticConfig(coordinator=coord),
                  faults=plan)
            coord.close()
        assert w.mesh.size("data") == 6
        assert net._iteration == 8
        assert np.isfinite(float(net.score()))
