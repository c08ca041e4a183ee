"""The reduction from a profiler trace to numbers, on a recorded trace:
the first two traced steps of ``resnet50-fit-b256`` on a TPU v5e (PR 25),
kept by ``chipbench.look --record`` as plain data (compact instruction
names, start and duration in ns, HBM bytes as compiled). Pins how an event
is sorted into conv / other / collective."""

import os

import pytest

from chipbench import trace

RECORDING = os.path.join(os.path.dirname(__file__),
                         "resnet50-fit-b256.v5e.2steps.json.gz")


@pytest.fixture(scope="module")
def red():
    return trace.reduce_raw(trace.load_recording(RECORDING))


def test_step_marks_and_window(red):
    assert len(red.devices) == 1 and red.steps == 2
    dev = red.busiest()
    assert [m[2].split("(")[0] for m in dev.modules] == ["jit_step"] * 2
    assert red.window == (dev.modules[0][0], dev.modules[-1][1])
    assert 0.15 < red.window_s < 0.3          # two steps of about 0.11 s
    assert all(red.window[0] <= o[0] and o[1] <= red.window[1]
               for o in dev.ops)


def test_every_convolution_is_seen_and_nothing_else_is_one(red):
    dev = red.busiest()
    conv = [o for o in dev.ops if o[3] == "conv"]
    # 53 convolutions and the dense head: forward, input gradient and
    # weight gradient of each, less the stem's input gradient
    assert len(conv) == 2 * 161
    assert all(" fusion kOutput of " in o[2] for o in conv)
    assert not dev.intervals("collective")
    kinds = {o[2].split(" = ")[1].split()[1] for o in dev.ops
             if o[3] == "other" and " = " in o[2]}
    assert "custom-call" in kinds           # the Pallas epilogues
    assert "select-and-scatter" in kinds    # the stem max-pool's backward
    assert "convolution" not in kinds and "dot" not in kinds


def test_busy_time_and_classes_add_up(red):
    dev = red.busiest()
    busy = dev.busy()
    assert 0.95 * red.window_s < busy <= red.window_s
    # operations on one core do not overlap: the classes' seconds sum to
    # the busy time
    assert dev.seconds("conv") + dev.seconds("other") == \
        pytest.approx(busy, rel=1e-3)
    assert dev.seconds("conv") > 0 and dev.seconds("other") > 0


def test_bytes_as_compiled_stay_under_the_hbm_peak(red):
    dev = red.busiest()
    for cls in ("conv", "other"):
        share = dev.bytes(cls) / 819e9 / dev.seconds(cls)
        assert 0.2 < share < 1.0


def test_breakdown_is_short_and_names_what_the_host_did(red):
    b = trace.breakdown(red)
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    for label, seconds in b["device_ops"]:
        assert label.split(":")[0] in ("conv", "other", "collective")
        assert len(label) < 120 and seconds > 0
    top = [s for _l, s in b["device_ops"]]
    assert top == sorted(top, reverse=True)
    for what, seconds in b["idle_gaps"]:
        assert what in (trace.STEP_SPAN, trace.PULL_SPAN, "between_spans")
        assert seconds >= 0


def test_recording_round_trip(tmp_path, red):
    raw = trace.load_recording(RECORDING)
    path = str(tmp_path / "again.json.gz")
    trace.save_recording(raw, path, steps=1)
    one = trace.reduce_raw(trace.load_recording(path))
    assert one.steps == 1
    assert one.window_s == pytest.approx(red.window_s / 2, rel=0.05)
