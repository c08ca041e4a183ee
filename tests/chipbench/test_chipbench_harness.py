"""The harness end to end on the CPU at a tiny size, through an entry of
the test's own (``chipbench_tiny``): everything after the look for a chip.
The runs are in float32, where the program and the plain reference have to
agree closely: that is the comparison of ``nn/`` with each configuration's
``reference.py`` (loss and gradients, ResNet-50 at 32x32 and Tiny YOLO at
64x64 with objects in the labels)."""

import json
import os

import jax
import pytest

import chipbench_tiny as tiny
from chipbench import run as runmod

# ResNet-50 runs once, through the four-chip cell (the same net and the same
# reference as the one-chip cell's); Tiny YOLO runs the NHWC, fused and
# Pallas (interpreted) path
CELLS = {"tinyyolo-fit-b256": 1, "resnet50-dp4-b1024": 4}
_LINES = {}


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("tinybench"),
                          settings={"precision": "fp32"})


def line_of(manifest, cell):
    """The result line of one tiny run of ``cell``, made once a module."""
    if cell not in _LINES:
        line = runmod.run_cell(manifest, tiny.run_args(cell, seed=2 ** 31 + 9),
                               jax.devices()[:CELLS[cell]], tiny.v5e_peak(),
                               interpret_kernels=True)
        _LINES[cell] = line
    return _LINES[cell]


@pytest.mark.parametrize("cell", CELLS)
def test_last_line_has_the_contracts_keys(manifest, cell):
    line = line_of(manifest, cell)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert json.loads(json.dumps(line)) == line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"img_per_s_per_chip", "peak_hbm_gib",
                                    "setup_s"}
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == CELLS[cell]


def value(checks, name):
    """A number compared (held to a limit, or only shown)."""
    c = checks[name]
    return c["value"] if isinstance(c, dict) else c


@pytest.mark.parametrize("cell", CELLS)
def test_program_agrees_with_the_plain_reference_in_float32(manifest, cell):
    """Loss of each of the first three steps; the first gradient, by its
    norm and by its direction, on the worst leaf; the parameters' change by
    the worst live leaf."""
    checks = line_of(manifest, cell)["checks"]
    assert line_of(manifest, cell)["correct"], checks
    assert value(checks, "loss1_gap") < 1e-4
    assert value(checks, "loss2_gap") < 0.02
    assert value(checks, "loss3_gap") < 0.02
    assert value(checks, "grad_gap") < 0.01
    assert value(checks, "graddir_gap") < 0.01
    assert value(checks, "change_gap") < 0.05


@pytest.mark.parametrize("cell", CELLS)
def test_every_number_compared_stands_beside_its_limit(manifest, cell):
    checks = line_of(manifest, cell)["checks"]
    limits = manifest.cell(cell)["limits"]
    for name, limit in limits.items():
        assert checks[name]["limit"] == limit
        assert checks[name]["ok"] == (checks[name]["value"] <= limit)


def test_main_refuses_to_run_without_a_tpu(capsys):
    rc = runmod.main(["--workload", "resnet50-fit-b256", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no TPU" in out.err


def test_main_refuses_an_unknown_device_kind(monkeypatch, capsys):
    class Fake:
        platform, device_kind = "tpu", "TPU v9 imagined"
    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    rc = runmod.main(["--workload", "resnet50-fit-b256", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no peaks" in out.err


def test_main_refuses_fewer_chips_than_the_cell_asks_for(monkeypatch, capsys):
    class Fake:
        platform, device_kind = "tpu", "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    rc = runmod.main(["--workload", "resnet50-dp4-b1024", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "4 chip(s)" in out.err


def test_traced_run_reports_the_per_layer_metrics(manifest, monkeypatch):
    """A ``--trace 1`` run on the CPU, with the recorded v5e trace handed to
    the reduction in place of the CPU's own (which has no device plane)."""
    from chipbench import trace
    recording = os.path.join(os.path.dirname(__file__),
                             "resnet50-fit-b256.v5e.2steps.json.gz")
    monkeypatch.setattr(
        trace, "reduce_xspace", lambda path, step_module=None:
        trace.reduce_raw(trace.load_recording(recording), step_module))
    cell = "tinyyolo-fit-b256"
    line = runmod.run_cell(manifest, tiny.run_args(cell, seed=5, trace=1,
                                                   seconds=2.0),
                           jax.devices()[:1], tiny.v5e_peak(),
                           interpret_kernels=True)
    want = {m["name"] for m in manifest.metrics_for(cell, "per_layer")}
    assert set(line["metrics"]) == want - {"collective_exposed_share",
                                           "collectives_per_step"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["device"]["busy_s"] > 0
    assert line["device"]["window_s"] >= line["device"]["busy_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    assert list(line)[-1] == "checks" and line["correct"]
