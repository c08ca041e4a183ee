"""The readers of the program's own instruments (``chipbench.programspans``
and the thirteen metric files on it): on a hand-made trace whose answers are
known, on a two-step TPU v5e recording of ``tinyyolo-fit-b256`` with the
program's spans and map saved beside it (my chip run, PR 26), and end to
end through the tiny CPU harness."""

import os
import types

import jax
import pytest

import chipbench_tiny as tiny
from chipbench import programspans as ps
from chipbench import run as runmod
from chipbench import trace
from chipbench.manifest import Manifest

HERE = os.path.dirname(__file__)
RECORDING = os.path.join(HERE, "tinyyolo-fit-b256.v5e.2steps.pr26.json.gz")
BESIDE = os.path.join(HERE,
                      "tinyyolo-fit-b256.v5e.2steps.pr26.program.json.gz")
NEW = ["fwd_device_ms", "bwd_device_ms", "updater_device_ms",
       "phase_unsure_share", "pallas_device_ms", "pallas_roofline",
       "host_step_ms", "stage_ms", "h2d_mib_per_step", "dispatch_lead_ms",
       "idle_host_bound_share", "gc_pause_ms_per_step", "mixed_device_ms"]
MS = 1_000_000          # ns
OFFSET_S = 1000.0       # the hand-made trace's clock runs 1000 s ahead


# ------------------------------------------------------ a hand-made run
def made_up(skew_step=None, skew_s=0.0):
    """Three steps of 100 ms. Step k's program runs [10, 90] ms into its
    slot: forward 30 ms, backward 38 ms, a mixed fusion 5 ms, one Pallas
    kernel 4 ms (4 MB), a copy the map does not list 3 ms, idle between.
    The host dispatched step k 250 ms before its program started, but
    step 2 only 1 ms after step 1's program ended."""
    ops, modules, step_spans, spans = [], [], [], []
    for k in range(3):
        t = k * 100 * MS
        modules.append(["jit_step(77)", t + 10 * MS, 80 * MS])
        cur = t + 10 * MS
        for name, dur, nbytes in (
                ("%fusion.1 = bf16[8,8] fusion kOutput of 2", 30, 10),
                ("%fusion.2 = bf16[8,8] fusion kOutput of 3", 38, 10),
                ("%fusion.3 = f32[8,8] fusion kOutput of 4", 5, 10),
                ("%dl4j_scale_shift_act.1 = bf16[8,8] custom-call", 4,
                 4_000_000),
                ("%copy.9 = f32[8,8] copy", 3, 10)):
            ops.append([name, cur, dur * MS, nbytes])
            cur += dur * MS
        start_s = (t + 10 * MS) * 1e-9
        dispatched = start_s - 0.250 if k != 2 \
            else (100 + 90 + 1) * 1e-3     # after step 1's program ended
        skew = skew_s if k == skew_step else 0.0
        # chipbench:step opens 5 us before listeners(start) ends and
        # closes 5 us after listeners(done) begins
        a1 = dispatched - 0.0010
        b0 = dispatched + 0.0002
        step_spans.append([int((a1 - 5e-6 + skew) * 1e9),
                           int((b0 - a1 + 10e-6) * 1e9)])
        it = k + 9

        def span(name, t0, t1, when=None, nbytes=None):
            spans.append({"name": name, "t0": t0 - OFFSET_S,
                          "t1": t1 - OFFSET_S, "iteration": it,
                          "when": when, "bytes": nbytes})
        span("fit:pull", a1 - 0.0100, a1 - 0.0099)
        span("fit:stage", a1 - 0.0099, a1 - 0.0029, nbytes=123)
        span("fit:prepare", a1 - 0.0029, a1 - 0.0001)
        span("fit:listeners", a1 - 0.0001, a1, when="start")
        span("fit:dispatch", a1, dispatched)
        span("fit:commit", dispatched, b0)
        span("fit:listeners", b0, b0 + 0.0001, when="done")
    spans.append({"name": "host:gc", "t0": 0.05 - OFFSET_S,
                  "t1": 0.056 - OFFSET_S, "iteration": None, "when": None,
                  "bytes": None})
    spans.append({"name": "fit:dispatch", "t0": -5.0 - OFFSET_S,
                  "t1": -4.9 - OFFSET_S, "iteration": 3, "when": None,
                  "bytes": None})       # long before the traced stretch
    raw = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
           "host": {trace.STEP_SPAN: step_spans}}
    maps = {"jit_step": {
        "fusion.1": ["forward", "dl4j_L0_conv", None, False],
        "fusion.2": ["backward", "dl4j_L0_conv", None, False],
        "fusion.3": ["backward", "dl4j_L0_conv", None, True],
        "dl4j_scale_shift_act.1": ["forward", "dl4j_L1_bn",
                                   "dl4j_scale_shift_act", False]}}
    traced = (-0.3 - OFFSET_S, 0.4 - OFFSET_S, 8, 11)
    return trace.reduce_raw(raw), spans, maps, traced


def ctx_of(red, spans, maps, traced, steps=30):
    ctx = types.SimpleNamespace(
        reduced=red, peak=tiny.v5e_peak(), cfg={}, model=None,
        result={"traced": traced, "steps": steps, "window_s": 3.0})
    ctx.programspans = ps.Joined(red, traced, spans, maps)
    return ctx


def read(name, ctx):
    return Manifest().reader(name)(ctx)


@pytest.fixture(scope="module")
def made():
    return ctx_of(*made_up())


def test_phases_split_the_step(made):
    assert read("fwd_device_ms", made) == pytest.approx(34.0)   # + kernel
    assert read("bwd_device_ms", made) == pytest.approx(38.0)
    assert read("updater_device_ms", made) == 0.0
    assert read("mixed_device_ms", made) == pytest.approx(5.0)
    assert read("phase_unsure_share", made) == pytest.approx(100 * 8 / 80)
    per = ps.phase_seconds(made.reduced, made.programspans.maps)
    total = sum(per[p][1] for p in per)
    assert 1e3 * total == pytest.approx(read("step_device_ms", made))


def test_pallas_kernels(made):
    assert read("pallas_device_ms", made) == pytest.approx(4.0)
    assert read("pallas_roofline", made) == \
        pytest.approx(100 * 4e6 / 819e9 / 4e-3)
    no_kernel = {"jit_step": {"fusion.1": ["forward", None, None, False]}}
    ctx = ctx_of(made.reduced, made.programspans.spans, no_kernel,
                 made.programspans.traced)
    assert read("pallas_device_ms", ctx) == 0.0
    assert read("pallas_roofline", ctx) == 0.0


def test_host_side_spans(made):
    assert len(made.programspans.iterations) == 3   # iteration 3 is not
    assert read("host_step_ms", made) == pytest.approx(11.3)
    assert read("stage_ms", made) == pytest.approx(7.0)
    assert read("gc_pause_ms_per_step", made) == pytest.approx(6.0 / 3)


def test_clocks_join_and_the_device_metrics_follow(made):
    assert made.programspans.offset == pytest.approx(OFFSET_S, abs=20e-6)
    assert read("dispatch_lead_ms", made) == pytest.approx(250.0, abs=0.05)
    # idle inside the stretch: 20 + 20 ms between the programs; the gap
    # before step 2's program began 1 ms before its dispatch had ended
    assert read("idle_host_bound_share", made) == pytest.approx(50.0)


def test_skewed_brackets_are_refused(capsys):
    ctx = ctx_of(*made_up(skew_step=1, skew_s=0.004))
    assert ctx.programspans.offset is None
    assert "no common clock offset" in capsys.readouterr().err
    assert read("dispatch_lead_ms", ctx) is None
    assert read("idle_host_bound_share", ctx) is None
    assert read("host_step_ms", ctx) == pytest.approx(11.3)    # host only
    ok = ctx_of(*made_up(skew_step=1, skew_s=0.0003))   # inside 0.5 ms
    assert ok.programspans.offset is not None


def test_h2d_reads_both_counters(made, monkeypatch):
    totals = {"dl4j_train_h2d_bytes_total": 30 * 2 ** 20,
              "dl4j_prefetch_h2d_bytes_total": 15 * 2 ** 20}
    monkeypatch.setattr(ps, "counter_total", totals.get)
    assert read("h2d_mib_per_step", made) == pytest.approx(1.5)
    del totals["dl4j_train_h2d_bytes_total"]    # a program without it
    assert read("h2d_mib_per_step", made) is None


@pytest.mark.parametrize("name", NEW)
def test_nothing_without_a_trace_or_without_the_program(name, made):
    untraced = types.SimpleNamespace(
        reduced=None, peak=tiny.v5e_peak(), cfg={}, model=None,
        result={"traced": None, "steps": 30, "window_s": 3.0})
    assert read(name, untraced) is None
    # a traced run of a program with neither map nor spans (the parent)
    bare = ctx_of(made.reduced, None, None, made.programspans.traced)
    if name != "h2d_mib_per_step":      # a counter: read from the registry
        assert read(name, bare) is None


def test_manifest_lists_the_new_metrics_last_and_where_they_read():
    data = Manifest().data["per_layer"]
    assert [m["name"] for m in data][-len(NEW):] == NEW
    cells = [w["name"] for w in Manifest().data["workloads"]]
    for m in data[-len(NEW):]:
        assert m["moves"] == "img_per_s_per_chip"
        assert m["source"] == ("program_counter"
                               if m["name"] == "h2d_mib_per_step"
                               else "program_span")
        assert set(m["workloads"]) <= set(cells)
        # one chip fuses every update into a weight gradient: only the
        # four-chip step has an updater of its own to read
        assert m["workloads"] == ["resnet50-dp4-b1024"] \
            if m["name"] == "updater_device_ms" \
            else "resnet50-fit-b256" in m["workloads"]


# ------------------------------------------------- the v5e recording
@pytest.fixture(scope="module")
def recorded():
    red = trace.reduce_raw(trace.load_recording(RECORDING))
    spans, maps, traced = ps.load_beside(BESIDE)
    return ctx_of(red, spans, maps, traced, steps=117)


@pytest.mark.parametrize("name", [n for n in NEW if n != "h2d_mib_per_step"])
def test_every_reader_reads_the_recording(name, recorded):
    value = read(name, recorded)
    assert value is not None and value == value
    if name.endswith(("_share", "_roofline")):
        assert 0.0 <= value <= 100.0


def test_recording_phases_add_up_to_the_step(recorded):
    parts = [read(n, recorded) for n in
             ("fwd_device_ms", "bwd_device_ms", "updater_device_ms",
              "mixed_device_ms")]
    per = ps.phase_seconds(recorded.reduced, recorded.programspans.maps)
    other = 1e3 * sum(per[ps.OTHER]) / len(per[ps.OTHER])
    step = read("step_device_ms", recorded)
    assert sum(parts) + other == pytest.approx(step, rel=0.01)
    assert parts[0] > 10 and parts[1] > 10      # ms: a real split
    assert parts[3] > 10    # the weight gradients fused with Adam
    assert read("phase_unsure_share", recorded) < 25.0
    assert os.path.getsize(RECORDING) < 1_000_000
    assert os.path.getsize(BESIDE) < 1_000_000


def test_recording_clock_check_passes_and_host_runs_ahead(recorded):
    assert recorded.programspans.offset is not None
    assert read("dispatch_lead_ms", recorded) > 50.0
    kernels = ps.kernel_ops(recorded.reduced, recorded.programspans.maps)
    assert len(kernels) == 2 * 5        # five epilogue calls a step


# ------------------------------------------- the tiny harness, end to end
def test_tiny_traced_run_reports_the_host_side_readers(tmp_path,
                                                       monkeypatch):
    """A ``--trace 1`` run of the tiny Tiny YOLO cell on the CPU, the v5e
    recording handed to the reduction: the readers that need no device
    clock read the live program's ring and counters."""
    manifest = tiny.tiny_root(tmp_path, settings={"precision": "fp32"})
    monkeypatch.setattr(
        trace, "reduce_xspace", lambda path, step_module=None:
        trace.reduce_raw(trace.load_recording(RECORDING), step_module))
    cell = "tinyyolo-fit-b256"
    # the counters run for the life of the process: other tests' bytes
    before = sum(ps.counter_total(n) or 0.0 for n in (
        "dl4j_train_h2d_bytes_total", "dl4j_prefetch_h2d_bytes_total"))
    line = runmod.run_cell(manifest, tiny.run_args(cell, seed=11, trace=1,
                                                   seconds=1.0),
                           jax.devices()[:1], tiny.v5e_peak(),
                           interpret_kernels=True)
    got = line["metrics"]
    cfg = manifest.cell(cell)["cfg"]
    c, h, w = cfg["input_shape"]
    batch = tiny.TINY_TRAFFIC["batch"]
    grid = h // 32
    want_bytes = batch * c * h * w + batch * (4 + cfg["num_classes"]) \
        * grid * grid * 4
    steps = line["attempted"]
    assert got["h2d_mib_per_step"]["value"] == \
        pytest.approx((before / steps + want_bytes) / 2 ** 20, rel=1e-9)
    for name in ("host_step_ms", "stage_ms"):
        assert got[name]["value"] > 0
    assert got["gc_pause_ms_per_step"]["value"] >= 0
    assert got["stage_ms"]["value"] < got["host_step_ms"]["value"]
    for name in ("fwd_device_ms", "phase_unsure_share", "pallas_device_ms"):
        assert name in got      # a map was flushed: jit_step
    assert line["correct"]
