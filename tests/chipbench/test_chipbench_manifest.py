"""``BENCHMARK.json`` against the contract's rules a file can be checked
for, and every name in it against the files it has to find."""

import json
import os
import re

import pytest

from chipbench.manifest import Manifest, ROOT

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head_dim", "expansion", "experts_per")

CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert len(json.dumps(BENCH)) < 64 * 1024
    # 338 runs of a full check with 24 cells must fit into 43200 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_command_names_no_file_outside_paths():
    assert len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
        assert 1 <= len(word) <= 200
    for p in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        assert os.path.isdir(os.path.join(ROOT, p))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    for key in ("source", "why"):
        assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
            and "\t" not in entry[key]
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    cfg = json.load(open(os.path.join(ROOT, entry["file"])))
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key)
        assert not key.endswith(("_dim", "_rank"))
        assert not any(w in key for w in WIDTH_WORDS)
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    files = [c["file"] for c in BENCH["configs"]]
    assert files.count(entry["file"]) == 1


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert pairs.count((cell["config"], cell["traffic"])) == 1


def test_four_chip_cells_within_a_quarter():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in BENCH["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) <= allowed
    assert set(metric) >= allowed - {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert 1 <= len(metric["layer"]) <= 200
        assert metric["moves"] in [m["name"] for m in BENCH["end_to_end"]]
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"
    for w in metric.get("workloads", []):
        assert w in CELLS
    names = [m["name"] for m in METRICS]
    assert names.count(metric["name"]) == 1


def test_setup_s_is_an_end_to_end_metric_with_the_contracts_bound():
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.1


def test_kernel_rooflines_stand_beside_a_step_mfu():
    moved = {m["moves"] for m in BENCH["per_layer"]
             if m["name"].endswith("_roofline")}
    for target in moved:
        assert any("mfu" in re.split(r"[._]", m["name"])
                   and m["moves"] == target for m in BENCH["per_layer"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough_and_finds_its_files(cell):
    m = Manifest()
    e2e = [x["name"] for x in m.metrics_for(cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = m.metrics_for(cell, "per_layer")
    assert per_layer
    for metric in per_layer:
        assert metric["moves"] in e2e
    loaded = m.cell(cell)
    for fn in ("build", "param_spec", "flops_per_sample", "n_matmuls",
               "read_leaves"):
        assert callable(getattr(loaded["model"], fn))
    assert callable(loaded["reference"].make_loss)
    assert callable(loaded["driver"].run)
    limits = loaded["limits"]
    assert limits and all(isinstance(v, (int, float)) and v > 0
                          for v in limits.values())
    for metric in m.metrics_for(cell, "end_to_end") + per_layer:
        assert callable(m.reader(metric["name"]))


@pytest.mark.parametrize("path", BENCH["paths"])
def test_files_under_paths_are_named_from_a_names_characters(path):
    for base, _dirs, files in os.walk(os.path.join(ROOT, path)):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
