"""``correct`` has to come out false when the timed path is broken
underneath. Tiny sizes on the CPU; the harness's look for a chip is
skipped, the rest of a run is driven. The last test adds a configuration,
a traffic mix, a driver and a per-layer metric as new files and entries
only. (The control is in ``test_chipbench_control.py``.)"""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny
from chipbench import run as runmod
from chipbench.drivers import fit_iterator as drv
from chipbench.manifest import Manifest

CELL = "tinyyolo-fit-b256"


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("tinyfaults"),
                          settings={"precision": "fp32"})


def _run(manifest, fit, cell=CELL, chips=1):
    return runmod.run_cell(manifest, tiny.run_args(cell, seed=23),
                           jax.devices()[:chips], tiny.v5e_peak(),
                           interpret_kernels=True, fit=fit)


def state_unchanged(net, iterator):
    """A step that returns its state unchanged: the parameters are what
    they were when the call began."""
    before = jax.tree_util.tree_map(jnp.copy, net._params)
    drv.fit_call(net, iterator)
    net._params = before


def half_batch(net, iterator):
    """Half of every batch left out, the mean taken over the rest."""
    from deeplearning4j_tpu.data.dataset import DataSet
    inner_next = iterator.next

    def next_half():
        ds = inner_next()
        n = ds.features.shape[0] // 2
        return DataSet(ds.features[:n], ds.labels[:n])
    iterator.next = next_half
    drv.fit_call(net, iterator)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch],
                         ids=lambda f: f.__name__)
def test_a_broken_timed_path_is_not_correct(manifest, fault):
    line = _run(manifest, fault)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    failed = [k for k, c in line["checks"].items()
              if isinstance(c, dict) and not c["ok"]]
    assert failed


def test_the_sound_path_is_correct(manifest):
    assert _run(manifest, drv.fit_call)["correct"] is True


# ----------------------------------------------------------- add by files
NEW_METRIC = '''"""Steps the window completed (a count the driver keeps)."""


def read(ctx):
    return float(ctx.result["steps"])
'''


def test_a_cell_is_added_by_new_files_and_entries_alone(tmp_path):
    """A later PR's configuration, traffic mix, driver and per-layer
    metric: files of their own and entries in ``BENCHMARK.json``; no file
    that was there is edited, and the new cell runs."""
    m = tiny.tiny_root(tmp_path, settings={"precision": "fp32"})
    bench = m.bench_dir
    before = {}
    for base, _d, files in os.walk(bench):
        for f in files:
            p = os.path.join(base, f)
            before[p] = open(p, "rb").read()

    cfg_dir = os.path.join(bench, "configs", "tinyyolo-voc-3class")
    shutil.copytree(os.path.join(bench, "configs", "tinyyolo-voc-bf16"),
                    cfg_dir)
    cfg = json.load(open(os.path.join(cfg_dir, "config.json")))
    cfg.update(name="tinyyolo-voc-3class", num_classes=3)
    json.dump(cfg, open(os.path.join(cfg_dir, "config.json"), "w"))
    shutil.copy(os.path.join(bench, "drivers", "fit_iterator.py"),
                os.path.join(bench, "drivers", "fit_again.py"))
    json.dump({"driver": "fit_again", "batch": 4, "pool": 3,
               "check_steps": 3, "trace_after_steps": 1, "trace_steps": 2},
              open(os.path.join(bench, "traffic", "fit-b4.json"), "w"))
    json.dump({"limits": {"loss1_gap": 0.01, "grad_gap": 0.1}},
              open(os.path.join(bench, "cells", "yolo3-b4.json"), "w"))
    open(os.path.join(bench, "metrics", "steps_in_window.py"), "w").write(
        NEW_METRIC)
    data = json.load(open(os.path.join(m.root, "BENCHMARK.json")))
    data["configs"].append({
        "name": "tinyyolo-voc-3class", "source": "a test",
        "file": "chipbench/configs/tinyyolo-voc-3class/config.json",
        "reduced": [], "why": "a test"})
    data["workloads"].append({
        "name": "yolo3-b4", "config": "tinyyolo-voc-3class",
        "traffic": "fit-b4", "chips": 1, "why": "a test"})
    data["end_to_end"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["yolo3-b4"]})
    json.dump(data, open(os.path.join(m.root, "BENCHMARK.json"), "w"))

    m2 = Manifest(root=m.root, bench_dir=bench)
    line = runmod.run_cell(m2, tiny.run_args("yolo3-b4", seed=1),
                           jax.devices()[:1], tiny.v5e_peak(),
                           interpret_kernels=True)
    assert line["correct"] and "steps_in_window" in line["metrics"]
    assert line["metrics"]["steps_in_window"]["value"] == line["attempted"]
    assert set(line["checks"]) >= {"loss1_gap", "grad_gap"}
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"
