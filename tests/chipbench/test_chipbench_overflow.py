"""``moe_overflow_step_share`` (PR 36): the reader on hand-made gauges
whose answers are known, no reading and no raise from a program without
the gauge (the parent has none), the manifest's entry found by name, and
a ``--trace 1`` run of the tiny hybrid cell on the CPU that reports it."""

import os
import types

import jax
import pytest

import chipbench_tiny as tiny
import test_chipbench_lfm2 as lfm2
from chipbench import programspans as ps
from chipbench import run as runmod
from chipbench import trace, xingmarks as xm
from chipbench.manifest import Manifest
from test_chipbench_lfm2 import manifest  # noqa: F401  (the tiny cell)

NAME = "moe_overflow_step_share"
CELLS = ["xing4-fit-s4096-b1", "lfm2-fit-s8192-b4"]


def ctx_of(model=None, traced=True):
    return types.SimpleNamespace(
        model=model or types.SimpleNamespace(), cfg={},
        result={"traced": (0.0, 1.0, 3, 5) if traced else None})


def read(ctx):
    return Manifest().reader(NAME)(ctx)


@pytest.mark.parametrize("steps, want", [
    # two layers, ten steps each: none took a second pass
    ({("l1_moe", "1"): 10.0, ("l1_moe", "2"): 0.0,
      ("l2_moe", "1"): 10.0, ("l2_moe", "2"): 0.0}, 0.0),
    # 2 of 20 layer-steps took two passes, 1 took three
    ({("l1_moe", "1"): 8.0, ("l1_moe", "2"): 2.0, ("l1_moe", "3"): 0.0,
      ("l2_moe", "1"): 9.0, ("l2_moe", "2"): 0.0, ("l2_moe", "3"): 1.0},
     15.0),
    # every step of the one layer overflowed
    ({("l1_moe", "1"): 0.0, ("l1_moe", "4"): 5.0}, 100.0),
    # a state that has run no step, a program that never set the gauge,
    # and one that has none of that name: no reading
    ({("l1_moe", "1"): 0.0, ("l1_moe", "2"): 0.0}, None),
    ({}, None), (None, None)])
def test_the_share_of_layer_steps_that_took_a_further_pass(monkeypatch, steps,
                                                           want):
    monkeypatch.setattr(
        xm, "gauge", lambda name: steps if name == "dl4j_moe_pass_steps"
        else None)
    got = read(ctx_of())
    assert got is None if want is None else got == pytest.approx(want)
    # only a traced run turns the program's instrumentation on
    assert read(ctx_of(traced=False)) is None


def test_a_layer_another_model_left_in_the_registry_is_not_counted(
        monkeypatch):
    steps = {("l1_moe", "1"): 10.0, ("l1_moe", "2"): 0.0,
             ("mtp_moe", "1"): 0.0, ("mtp_moe", "2"): 30.0}
    monkeypatch.setattr(xm, "gauge", lambda name: steps)
    mine = types.SimpleNamespace(expert_layers_of=lambda cfg: ["l1_moe"])
    assert read(ctx_of(mine)) == 0.0
    assert read(ctx_of()) == pytest.approx(75.0)


def test_the_manifest_lists_the_metric_for_the_two_sparse_cells():
    """Found by name, wherever later PRs append theirs."""
    m = Manifest()
    (entry,) = [e for e in m.data["per_layer"] if e["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "step program",
                     "moves": "img_per_s_per_chip", "workloads": CELLS}
    for cell in CELLS:
        assert NAME in {e["name"] for e in m.metrics_for(cell, "per_layer")}
        assert "img_per_s_per_chip" in {
            e["name"] for e in m.metrics_for(cell, "end_to_end")}
    others = {w["name"] for w in m.data["workloads"]} - set(CELLS)
    for cell in others:
        assert NAME not in {e["name"]
                            for e in m.metrics_for(cell, "per_layer")}
    assert os.path.isfile(os.path.join(tiny.BENCH_DIR, "metrics",
                                       NAME + ".py"))


def test_a_traced_run_of_the_tiny_hybrid_cell_reports_it(manifest,  # noqa: F811
                                                         monkeypatch):
    """The gauge is the live program's (4 of 16 experts held: two passes
    at most); the trace is ``test_chipbench_lfm2``'s hand-made one, since
    the CPU's own has no device plane."""
    red, maps = lfm2.made_up()
    monkeypatch.setattr(trace, "reduce_xspace",
                        lambda path, step_module=None: red)
    monkeypatch.setattr(ps, "from_program", lambda: ([], maps))
    line = runmod.run_cell(
        manifest, tiny.run_args(lfm2.CELL, seed=2 ** 31 + 36, trace=1,
                                seconds=0.5),
        jax.devices()[:1], tiny.v5e_peak())
    got = line["metrics"][NAME]
    assert got["unit"] == "%" and 0.0 <= got["value"] <= 100.0
    steps = xm.gauge("dl4j_moe_pass_steps")
    assert {"l1_moe", "l4_moe"} <= {k[0] for k in steps}
    assert {k[1] for k in steps if k[0] == "l1_moe"} == {"1", "2"}
    # every step of the run is in a slot: set-up's two and the window's
    assert sum(n for k, n in steps.items() if k[0] == "l1_moe") >= 3
    assert line["correct"]
