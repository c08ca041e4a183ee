"""The readers of the program's build spans (``chipbench.buildspans`` and
the seven ``setup_*`` metric files on it): on hand-made spans whose
answers are known, against the manifest, and end to end through the tiny
CPU harness."""

import json
import os
import types

import jax
import pytest

import chipbench_tiny as tiny
from chipbench import buildspans as bs
from chipbench import run as runmod
from chipbench import trace
from chipbench.manifest import Manifest

HERE = os.path.dirname(__file__)
RECORDING = os.path.join(HERE, "tinyyolo-fit-b256.v5e.2steps.pr26.json.gz")
NEW = {"setup_init_s": ("s", "program_span"),
       "setup_step_trace_s": ("s", "program_span"),
       "setup_step_lower_s": ("s", "program_span"),
       "setup_step_backend_s": ("s", "program_span"),
       "setup_step_build_self_s": ("s", "program_span"),
       "setup_programs_built": ("count", "program_span"),
       "setup_cache_misses": ("count", "program_span")}
STEP_PARTS = ["setup_step_trace_s", "setup_step_lower_s",
              "setup_step_backend_s", "setup_step_build_self_s"]
CELLS = ["resnet50-fit-b256", "tinyyolo-fit-b256", "resnet50-dp4-b1024",
         "ouro-fit-s4096-b1", "xing4-fit-s4096-b1", "lfm2-fit-s8192-b4"]
S = 1e6     # the ring's clock is in microseconds


def span(name, t0, t1, tid=1, **args):
    return {"name": name, "ph": "X", "ts": t0 * S, "dur": (t1 - t0) * S,
            "pid": 1, "tid": tid, "args": args}


def made_up():
    """A set-up of 40 s. ``init()`` runs [1, 3] and builds two small
    programs; the harness builds one of its own at 4 (no cause, a miss);
    the first dispatch [10, 30] builds the step: its trace [10.5, 19.5]
    holds a rule traced at [12, 14] and, inside that, a leaf at [12.5,
    13], then lowering [20, 22] with a trace of its own at [21, 21.5],
    then the backend [22.5, 29.5] (a hit). The window opens at 40 and
    rebuilds one program at 50."""
    return [
        span(bs.TRACE, 1.0, 1.1, program="_uniform", cause=bs.NET_INIT),
        span(bs.LOWER, 1.1, 1.2, program="_uniform", cause=bs.NET_INIT),
        span(bs.BACKEND, 1.2, 1.7, program="_uniform", cause=bs.NET_INIT,
             cache="hit", retrieval_s=0.4),
        span(bs.BACKEND, 2.0, 2.5, program="_normal", cause=bs.NET_INIT,
             cache="hit", retrieval_s=0.4),
        span(bs.NET_INIT, 1.0, 3.0, parameters=75, leaves=4),
        span(bs.BACKEND, 4.0, 4.25, program="norms", cause=None,
             cache="miss", retrieval_s=None),
        span(bs.TRACE, 12.5, 13.0, program="leaf", cause=bs.FIT_BUILD),
        span(bs.TRACE, 12.0, 14.0, program="rule", cause=bs.FIT_BUILD),
        span(bs.TRACE, 10.5, 19.5, program="step", cause=bs.FIT_BUILD),
        span(bs.TRACE, 21.0, 21.5, program="helper", cause=bs.FIT_BUILD),
        span(bs.LOWER, 20.0, 22.0, program="step", cause=bs.FIT_BUILD),
        span(bs.BACKEND, 22.5, 29.5, program="step", cause=bs.FIT_BUILD,
             cache="hit", retrieval_s=6.0),
        span(bs.FIT_BUILD, 10.0, 30.0, site="ComputationGraph.fit",
             iteration=1, steps=1, new_signature=True,
             parent="fit:dispatch"),
        span("fit:pull", 40.0, 40.001, iteration=4, parent="fit:epoch"),
        span(bs.BACKEND, 50.0, 51.0, program="step", cause=bs.FIT_BUILD,
             cache="miss", retrieval_s=None),
        span(bs.FIT_BUILD, 49.0, 52.0, site="ComputationGraph.fit",
             iteration=9, steps=1, new_signature=True,
             parent="fit:dispatch"),
        span("fit:epoch", 39.9, 60.0, epoch=0),
    ]


def test_window_starts_at_the_earliest_mark():
    assert bs.window_start(made_up()) == pytest.approx(39.9 * S)
    assert bs.window_start([e for e in made_up()
                            if not e["name"].startswith("fit:")]) is None


def test_sums_count_a_nested_interval_once():
    got = bs.split(made_up(), 39.9 * S)
    assert got["init_s"] == pytest.approx(2.0)
    assert got["step_trace_s"] == pytest.approx(9.0)    # not 9 + 2 + 0.5,
    assert got["step_lower_s"] == pytest.approx(2.0)    # nor the helper's
    assert got["step_backend_s"] == pytest.approx(7.0)
    assert got["step_build_s"] == pytest.approx(20.0)
    assert got["step_build_self_s"] == pytest.approx(2.0)
    assert sum(got[k[len("setup_"):]] for k in STEP_PARTS) \
        == pytest.approx(got["step_build_s"], abs=1e-9)
    assert got["programs_built"] == 4 and got["cache_misses"] == 1


def test_without_a_cut_the_windows_rebuild_counts_too():
    got = bs.split(made_up())
    assert got["step_build_s"] == pytest.approx(23.0)
    assert got["step_backend_s"] == pytest.approx(8.0)
    assert got["programs_built"] == 5 and got["cache_misses"] == 2


def test_another_threads_build_is_not_this_dispatchs_child():
    evs = made_up() + [span(bs.BACKEND, 15.0, 16.0, tid=2, program="other",
                            cause=bs.FIT_BUILD, cache="hit")]
    got = bs.split(evs, 39.9 * S)
    assert got["step_backend_s"] == pytest.approx(7.0)
    assert got["programs_built"] == 5


def test_a_child_is_clipped_to_its_build():
    evs = [span(bs.TRACE, 9.0, 12.0, program="step", cause=bs.FIT_BUILD),
           span(bs.FIT_BUILD, 10.0, 13.0, iteration=1)]
    got = bs.split(evs)
    assert got["step_trace_s"] == pytest.approx(2.0)
    assert got["step_build_self_s"] == pytest.approx(1.0)


def test_a_tree_without_spans_reads_none_and_an_idle_setup_zero():
    parent = [span("fit:pull", 40.0, 40.001, iteration=4),
              span("fit:dispatch", 40.1, 40.2, iteration=4)]
    assert bs.split(parent, 40.0 * S) is None
    assert bs.split([], None) is None and bs.split(None) is None
    idle = parent + [span(bs.NET_INIT, 1.0, 1.5, parameters=75, leaves=4)]
    got = bs.split(idle, 40.0 * S)
    assert got == {"init_s": pytest.approx(0.5), "step_trace_s": 0.0,
                   "step_lower_s": 0.0, "step_backend_s": 0.0,
                   "step_build_self_s": 0.0, "step_build_s": 0.0,
                   "programs_built": 0, "cache_misses": 0}


def ctx_of(traced=(1.0, 2.0, 4, 6)):
    return types.SimpleNamespace(result={"traced": traced})


@pytest.mark.parametrize("name", sorted(NEW))
def test_readers_read_the_ring_in_a_traced_run_only(name, monkeypatch):
    read = Manifest().reader(name)
    monkeypatch.setattr(bs, "from_program", made_up)
    want = bs.split(made_up(), 39.9 * S)[name[len("setup_"):]]
    assert read(ctx_of()) == want
    assert read(ctx_of(traced=None)) is None
    monkeypatch.setattr(bs, "from_program", lambda: None)
    assert read(ctx_of()) is None       # a program without a tracer
    monkeypatch.setattr(bs, "from_program", lambda: [
        span("fit:pull", 40.0, 40.001, iteration=4)])
    assert read(ctx_of()) is None       # the parent's tree: no such spans


def test_manifest_lists_the_seven_for_the_six_cells():
    """Found by name, so that a later PR may append a metric, or a cell to
    these metrics' lists, without this test failing."""
    manifest = Manifest()
    entries = {m["name"]: m for m in manifest.data["per_layer"]}
    for name, (unit, source) in NEW.items():
        entry = dict(entries[name])
        assert entry.pop("workloads")[:len(CELLS)] == CELLS
        assert entry == {"name": name, "unit": unit, "better": "lower",
                         "source": source, "layer": "compile",
                         "moves": "setup_s"}
        assert callable(manifest.reader(name))
    for cell in CELLS:
        listed = {m["name"] for m in manifest.metrics_for(cell, "per_layer")}
        assert set(NEW) <= listed


# ------------------------------------------- the tiny harness, end to end
@pytest.fixture
def no_jax_cache():
    """No persistent compilation cache for one test, whatever an earlier
    test of this process placed: jax decides at a compile whether it uses
    one and keeps that decision, so it is reset on both sides."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_tiny_traced_run_reports_all_seven(tmp_path, monkeypatch, capsys,
                                           no_jax_cache):
    """A ``--trace 1`` run of the tiny Tiny YOLO cell on the CPU: set-up
    builds the net's step with the profiling mode off, and the seven
    readers read it out of the live program's ring."""
    from deeplearning4j_tpu.profiler import get_tracer
    manifest = tiny.tiny_root(tmp_path, settings={"precision": "fp32"})
    monkeypatch.setattr(
        trace, "reduce_xspace", lambda path, step_module=None:
        trace.reduce_raw(trace.load_recording(RECORDING), step_module))
    get_tracer().clear()        # other tests' windows left their marks
    cell = "tinyyolo-fit-b256"
    line = runmod.run_cell(manifest, tiny.run_args(cell, seed=13, trace=1,
                                                   seconds=1.0),
                           jax.devices()[:1], tiny.v5e_peak(),
                           interpret_kernels=True)
    got = line["metrics"]
    for name, (unit, _source) in NEW.items():
        assert got[name]["unit"] == unit, name
    value = {name: got[name]["value"] for name in NEW}
    events = bs.from_program()
    again = bs.split(events, bs.window_start(events))
    assert value == {name: again[name[len("setup_"):]] for name in NEW}
    # every run makes its net anew, so its step is traced, lowered and
    # handed to the backend whatever this process has built before
    assert all(value[name] > 0 for name in STEP_PARTS)
    assert sum(value[name] for name in STEP_PARTS) \
        == pytest.approx(again["step_build_s"], abs=1e-3)
    assert value["setup_init_s"] > 0
    assert value["setup_programs_built"] >= 1
    assert value["setup_cache_misses"] == 0         # no cache is placed
    assert got["compiles_in_window"]["value"] == 0
    said = [ln for ln in capsys.readouterr().err.splitlines()
            if ln.startswith("chipbench run: ")]
    phases = json.loads(said[-1][len("chipbench run: "):])["setup_phases"]
    assert value["setup_init_s"] + again["step_build_s"] \
        <= phases["build_net"] + phases["first_steps"]
    assert line["correct"]
