"""A tiny copy of the benchmark for the CPU tests: the harness's own data
files copied into a temporary root, with the image sizes, batch and pool
cut so that a run takes seconds. Never used by the measuring command."""

import argparse
import json
import os
import shutil

import chipbench
from chipbench import peaks
from chipbench.manifest import Manifest

BENCH_DIR = os.path.dirname(os.path.abspath(chipbench.__file__))
REPO = os.path.dirname(BENCH_DIR)

TINY_CONFIG = {
    "resnet50-imagenet-bf16": {"input_shape": [3, 32, 32], "num_classes": 10},
    "tinyyolo-voc-bf16": {"input_shape": [3, 64, 64]},
    "resnet50-imagenet-bf16-dp4": {"input_shape": [3, 32, 32],
                                   "num_classes": 10},
}
TINY_TRAFFIC = {"batch": 8, "pool": 3, "trace_after_steps": 1,
                "trace_steps": 2}


def _dump(path, data):
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def tiny_root(tmp, settings=None, traffic=None):
    """Copy ``BENCHMARK.json`` and ``chipbench/``'s data into ``tmp`` at a
    tiny size; return a :class:`Manifest` over the copy. ``settings``
    overrides every configuration's ``settings`` (the CPU cannot run bf16
    convolutions fast, and a comparison with the reference wants fp32)."""
    tmp = str(tmp)
    bench = os.path.join(tmp, "chipbench")
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    data = json.load(open(os.path.join(tmp, "BENCHMARK.json")))
    for entry in data["configs"]:
        path = os.path.join(tmp, entry["file"])
        cfg = json.load(open(path))
        cfg.update(TINY_CONFIG.get(entry["name"], {}))
        if settings is not None:
            cfg["settings"] = {**cfg["settings"], **settings}
        _dump(path, cfg)
    for name in os.listdir(os.path.join(bench, "traffic")):
        path = os.path.join(bench, "traffic", name)
        t = json.load(open(path))
        t.update(TINY_TRAFFIC)
        t.update(traffic or {})
        _dump(path, t)
    return Manifest(root=tmp, bench_dir=bench)


def run_args(workload, seed=7, seconds=1.0, trace=0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)


def v5e_peak():
    return peaks.peaks_for("TPU v5 lite")
