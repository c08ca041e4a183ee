"""``BENCHMARK.json`` and the files its names point at.

Everything that belongs to one configuration, one traffic mix, one cell or
one metric sits in a file of its own, found here by name:

- configuration ``<c>``: the ``file`` its entry gives (``config.json``),
  with ``model.py`` and ``reference.py`` beside it;
- traffic ``<t>``: ``chipbench/traffic/<t>.json``, naming a driver
  ``chipbench/drivers/<driver>.py``;
- cell ``<w>``: ``chipbench/cells/<w>.json`` with the limits of its
  ``correct`` (and, optionally, traffic parameters it overrides);
- metric ``<m>``: ``chipbench/metrics/<m>.py`` with ``read(ctx)``.

A later PR adds files and entries; nothing here needs an edit.
"""

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _modname(*parts):
    return "chipbench_dyn_" + "_".join(
        p.replace("-", "_").replace(".", "_") for p in parts)


class Manifest:
    def __init__(self, root=ROOT, bench_dir=HERE):
        self.root = root
        self.bench_dir = bench_dir
        self.data = load_json(os.path.join(root, "BENCHMARK.json"))

    def workload(self, name):
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def cell(self, name):
        """Everything a run of one cell needs, loaded by name."""
        w = self.workload(name)
        entry = next(c for c in self.data["configs"]
                     if c["name"] == w["config"])
        cfg_path = os.path.join(self.root, entry["file"])
        cfg_dir = os.path.dirname(cfg_path)
        cell_file = load_json(os.path.join(self.bench_dir, "cells",
                                           name + ".json"))
        traffic = load_json(os.path.join(self.bench_dir, "traffic",
                                         w["traffic"] + ".json"))
        traffic.update(cell_file.get("traffic", {}))
        return {
            "name": name, "chips": w["chips"], "cfg": load_json(cfg_path),
            "model": load_module(os.path.join(cfg_dir, "model.py"),
                                 _modname(w["config"], "model")),
            "reference": load_module(os.path.join(cfg_dir, "reference.py"),
                                     _modname(w["config"], "reference")),
            "traffic": traffic,
            "driver": load_module(
                os.path.join(self.bench_dir, "drivers",
                             traffic["driver"] + ".py"),
                _modname("driver", traffic["driver"])),
            "limits": cell_file["limits"],
        }

    def metrics_for(self, cell_name, kind):
        """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
        that list it under ``workloads``, or list nothing."""
        return [m for m in self.data[kind]
                if "workloads" not in m or cell_name in m["workloads"]]

    def reader(self, metric_name):
        return load_module(
            os.path.join(self.bench_dir, "metrics", metric_name + ".py"),
            _modname("metric", metric_name)).read
