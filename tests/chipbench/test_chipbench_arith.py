"""The yardstick's arithmetic on hand-made inputs: the metric readers, the
FLOP functions, the trace's interval sums and name parsing, the comparison
and the weights from a seed."""

import json
import math
import os
import types

import pytest

from chipbench import compare, peaks, trace
from chipbench.manifest import Manifest, ROOT, load_module

M = Manifest()
V5E = peaks.peaks_for("TPU v5 lite")


def _model(config):
    return load_module(os.path.join(ROOT, "chipbench", "configs", config,
                                    "model.py"), "t_" + config.replace("-", "_"))


def _cfg(config):
    return json.load(open(os.path.join(ROOT, "chipbench", "configs", config,
                                       "config.json")))


def _device(ops, modules):
    dev = trace.DeviceOps("/device:TPU:0")
    dev.ops = [(s, e, n, trace.classify(n), b) for s, e, n, b in ops]
    dev.modules = modules
    return dev


def _reduced(ops, modules):
    red = trace.Reduced()
    red.devices = [_device(ops, modules)]
    red.window = (modules[0][0], modules[-1][1])
    red.steps = len(modules)
    return red


def _ctx(result=None, reduced=None, config="resnet50-imagenet-bf16"):
    return types.SimpleNamespace(result=result or {}, reduced=reduced,
                                 peak=V5E, cfg=_cfg(config),
                                 model=_model(config))


CONV = "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a, " \
       "bf16[8,8]{1,0} %b), kind=kOutput, calls=%fc"
LOOP = "%fusion.2 = bf16[1000]{0} fusion(bf16[1000]{0} %a), kind=kLoop, " \
       "calls=%fc2"
ALLR = "%all-reduce.3 = f32[64]{0} all-reduce(f32[64]{0} %x), to_apply=%r"


# ------------------------------------------------------------ FLOP functions
@pytest.mark.parametrize("config,gflop", [
    ("resnet50-imagenet-bf16", 7.72), ("tinyyolo-voc-bf16", 6.97),
    ("resnet50-imagenet-bf16-dp4", 7.72)])
def test_flops_per_sample_matches_bench_py(config, gflop):
    got = _model(config).flops_per_sample(_cfg(config)) / 1e9
    assert abs(got - gflop) < 0.01


@pytest.mark.parametrize("config,n", [
    ("resnet50-imagenet-bf16", 54), ("tinyyolo-voc-bf16", 9)])
def test_n_matmuls(config, n):
    assert _model(config).n_matmuls(_cfg(config)) == n


def test_flops_per_sample_agrees_with_the_programs_own_count():
    import bench
    assert _model("resnet50-imagenet-bf16").flops_per_sample(
        _cfg("resnet50-imagenet-bf16")) == pytest.approx(
            bench.resnet50_flops(224), rel=1e-6)
    assert _model("tinyyolo-voc-bf16").flops_per_sample(
        _cfg("tinyyolo-voc-bf16")) == pytest.approx(
            bench.darknet_tiny_flops(416), rel=1e-6)


# ------------------------------------------------------------ metric readers
def test_img_per_s_per_chip():
    r = {"steps": 100, "batch": 1024, "window_s": 20.0, "chips": 4}
    assert M.reader("img_per_s_per_chip")(_ctx(r)) == 100 * 1024 / 20.0 / 4


def test_peak_hbm_gib_sums_both_counters_on_the_fullest_chip():
    stats = [{"peak_bytes_in_use": 2 ** 30, "peak_bytes_reserved": 2 ** 31},
             {"peak_bytes_in_use": 2 ** 29, "peak_bytes_reserved": 2 ** 30}]
    assert M.reader("peak_hbm_gib")(_ctx({"memory_stats": stats})) == 3.0
    assert trace.memory_peak_bytes(stats) == 3 * 2 ** 30


def test_setup_s():
    assert M.reader("setup_s")(_ctx({"setup_s": 12.5})) == 12.5


def test_step_mfu():
    red = _reduced([(0.0, 0.1, CONV, 0)], [(0.0, 0.1, "jit_step(1)"),
                                           (0.1, 0.2, "jit_step(1)")])
    ctx = _ctx({"batch": 256, "chips": 1}, red)
    flops = 3 * ctx.model.flops_per_sample(ctx.cfg) * 256 * 2
    assert M.reader("step_mfu")(ctx) == pytest.approx(
        100 * flops / (0.2 * 197e12))


def test_conv_roofline_counts_required_flops_over_conv_time():
    ops = [(i * 1e-3, i * 1e-3 + 5e-4, CONV, 0) for i in range(161)]
    ops.append((0.17, 0.18, LOOP, 2000))
    red = _reduced(ops, [(0.0, 0.2, "jit_step(1)")])
    ctx = _ctx({"batch": 256, "chips": 1}, red)
    flops = 3 * ctx.model.flops_per_sample(ctx.cfg) * 256
    assert M.reader("conv_roofline")(ctx) == pytest.approx(
        100 * flops / 197e12 / (161 * 5e-4))


def test_conv_roofline_reads_nothing_when_the_convolutions_are_not_seen():
    red = _reduced([(0.0, 0.01, CONV, 0), (0.02, 0.03, LOOP, 2000)],
                   [(0.0, 0.2, "jit_step(1)")])
    assert M.reader("conv_roofline")(_ctx({"batch": 256, "chips": 1},
                                          red)) is None


def test_nonconv_roofline_is_bytes_as_compiled_over_other_time():
    red = _reduced([(0.0, 0.01, CONV, 10 ** 9), (0.02, 0.03, LOOP, 4 * 10 ** 9),
                    (0.03, 0.04, ALLR, 10 ** 9)],
                   [(0.0, 0.2, "jit_step(1)")])
    assert M.reader("nonconv_roofline")(_ctx({}, red)) == pytest.approx(
        100 * 4e9 / 819e9 / 0.01)


@pytest.mark.parametrize("name", ["step_mfu", "conv_roofline",
                                  "nonconv_roofline", "step_device_ms",
                                  "device_idle_share",
                                  "collective_exposed_share",
                                  "collectives_per_step"])
def test_trace_readers_read_nothing_without_a_trace(name):
    assert M.reader(name)(_ctx({"batch": 1, "chips": 1,
                                "counters": {}})) is None


def test_device_idle_share_and_step_device_ms():
    red = _reduced([(0.00, 0.04, CONV, 0), (0.03, 0.06, LOOP, 0),
                    (0.10, 0.18, CONV, 0)],
                   [(0.0, 0.1, "jit_step(1)"), (0.1, 0.2, "jit_step(1)")])
    assert M.reader("device_idle_share")(_ctx({}, red)) == pytest.approx(30.0)
    assert M.reader("step_device_ms")(_ctx({}, red)) == pytest.approx(70.0)


def test_collective_metrics():
    red = _reduced([(0.00, 0.05, CONV, 0), (0.04, 0.08, ALLR, 0),
                    (0.09, 0.10, ALLR, 0)], [(0.0, 0.1, "jit_step(1)")])
    assert M.reader("collective_exposed_share")(_ctx({}, red)) == \
        pytest.approx(40.0)
    assert M.reader("collectives_per_step")(_ctx({}, red)) == 2.0
    one_chip = _reduced([(0.0, 0.05, CONV, 0)], [(0.0, 0.1, "jit_step(1)")])
    assert M.reader("collective_exposed_share")(_ctx({}, one_chip)) is None
    assert M.reader("collectives_per_step")(_ctx({}, one_chip)) is None


def test_data_wait_share_and_compiles_in_window():
    c = {"data_wait_s": 0.5, "recompiles": 1.0, "jax_compiles": 2,
         "data_wait_recorded": True}
    ctx = _ctx({"counters": c, "window_s": 10.0})
    assert M.reader("data_wait_share")(ctx) == pytest.approx(5.0)
    assert M.reader("compiles_in_window")(ctx) == 3.0
    c["data_wait_recorded"] = False
    assert M.reader("data_wait_share")(ctx) is None


# ------------------------------------------------------------------- trace
def test_union_seconds_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert trace.union_seconds(iv) == 3.0
    assert trace.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert trace.union_seconds([]) == 0.0


@pytest.mark.parametrize("name,cls", [
    (CONV, "conv"), (LOOP, "other"), (ALLR, "collective"),
    ("%convolution.5 = bf16[2,3]{1,0} convolution(bf16[2,2]{1,0} %a, "
     "bf16[2,3]{1,0} %b), window={}", "conv"),
    ("%fusion.2186 = bf16[256,56,56,64]{0,3,2,1:T(8,128)(2,1)S(1)} "
     "fusion(bf16[256,112,112,64]{0,3,2,1} %fusion.34), kind=kOutput, "
     "calls=%fused_computation.3725", "other"),
    ("%custom-call.14 = bf16[256,28,28,128]{3,0,2,1} custom-call(bf16[4]{0} "
     "%x), custom_call_target=\"tpu_custom_call\"", "other"),
    ("%all-gather-start.2 = (f32[16]{0}, f32[64]{0}) all-gather-start("
     "f32[16]{0} %p), dimensions={0}", "collective"),
    ("%reduce-scatter.1 = f32[16]{0} reduce-scatter(f32[64]{0} %g)",
     "collective"),
    ("jit_step(123)", "other"), ("%fusion.9", "other")])
def test_classify_by_instruction_text(name, cls):
    assert trace.classify(name) == cls
    assert trace.classify(trace.compact(name)) == cls


def test_compact_keeps_the_largest_output_and_counts_operands():
    name = ("%multiply_reduce_fusion.2 = (bf16[256]{0:T(256)(128)(2,1)S(1)},"
            " bf16[256,56,56,256]{3,0,2,1:T(8,128)(2,1)}) fusion("
            "bf16[256,56,56,256]{3,0,2,1:T(8,128)(2,1)} %get-tuple-element.1,"
            " bf16[64,256,1,1]{1,3,2,0:T(2,128)(2,1)S(1)} %copy-done.170), "
            "kind=kOutput, calls=%fused_computation.72")
    assert trace.compact(name) == ("%multiply_reduce_fusion.2 = "
                                   "bf16[256,56,56,256] fusion kOutput of 2")


def test_hbm_bytes_leaves_out_what_sits_in_on_chip_memory():
    name = ("%f = (bf16[256]{0:T(256)S(1)}, bf16[10,10]{1,0:T(8,128)(2,1)}) "
            "fusion(f32[10,10]{1,0} %a, bf16[64]{0:T(256)S(1)} %b, "
            "pred[8]{0} %c), kind=kLoop, calls=%fc")
    assert trace.hbm_bytes(name) == 100 * 2 + 100 * 4 + 8
    assert trace.hbm_bytes("jit_step(1)") == 0


# -------------------------------------------------------------- comparison
def _side(grads=None, change=None, losses=None):
    import numpy as np
    g = {"a": [3.0, 4.0], "b": [0.0, 10.0], "dead": [1e-6, 0.0]}
    g.update(grads or {})
    c = {"a": 0.1, "b": 0.2, "dead": 0.3}
    c.update(change or {})
    return {"losses": losses or [2.0, 1.0, 0.5],
            "first_grads": {k: np.asarray(v, np.float32)
                            for k, v in g.items()},
            "change_norms": c}


def test_compare_numbers_gaps():
    n = compare.numbers(
        _side(grads={"b": [0.0, 12.5]}, losses=[2.2, 1.0, 0.5],
              change={"a": 0.15, "dead": 0.0}), _side())
    assert n["loss1_gap"] == pytest.approx(0.1)
    assert n["loss2_gap"] == 0.0
    assert n["grad_gap"] == pytest.approx(0.25) and n["grad_gap_leaf"] == "b"
    assert n["grad_mid_gap"] == 0.0
    # the dead leaf (gradient under a thousandth of the median) is left out
    assert n["change_gap"] == pytest.approx(0.05 / 0.15)
    assert n["change_gap_leaf"] == "a"
    assert n["change_mid_gap"] == pytest.approx(0.05 / 0.15 / 2)


def test_direction_gap_sees_what_a_gap_of_norms_cannot():
    """A gradient turned by a right angle keeps its norm."""
    n = compare.numbers(_side(grads={"a": [4.0, -3.0]}), _side())
    assert n["grad_gap"] == pytest.approx(0.0, abs=1e-6)
    assert n["graddir_gap"] == pytest.approx(2 ** 0.5, rel=1e-6)
    assert n["graddir_gap_leaf"] == "a" and n["graddir_mid_gap"] == 0.0
    # "b" has the largest reference gradient, and it has not turned
    assert n["graddir_top_leaf"] == "b" and n["graddir_top_gap"] == 0.0
    n = compare.numbers(_side(grads={"b": [1.0, 10.0]}), _side())
    assert n["graddir_top_gap"] == pytest.approx(0.1)


def test_compare_measures_a_small_leaf_against_the_median_leaf():
    n = compare.numbers(_side(grads={"dead": [2e-6, 0.0]}), _side())
    assert n["grad_gap"] == pytest.approx(1e-6 / 5.0)
    assert n["graddir_gap"] == pytest.approx(1e-6 / 5.0)


def test_judge_holds_every_limit_and_fails_what_is_not_finite():
    limits = {"loss1_gap": 0.2, "grad_gap": 0.2}
    ok, checks = compare.judge(
        compare.numbers(_side(losses=[2.2, 1.0, 0.5]), _side()), limits)
    assert ok and checks["loss1_gap"] == {
        "value": pytest.approx(0.1), "limit": 0.2, "ok": True}
    assert checks["graddir_gap"] == 0.0             # shown, not held
    ok, checks = compare.judge(
        compare.numbers(_side(grads={"a": [4.5, 6.0]}), _side()), limits)
    assert not ok and not checks["grad_gap"]["ok"]
    ok, _ = compare.judge(
        compare.numbers(_side(losses=[math.nan, 1.0, 0.5]), _side()), limits)
    assert not ok
    ok, _ = compare.judge({"grad_gap": 0.0}, limits)   # a number is missing
    assert not ok


def test_unchanged_state_reads_one():
    n = compare.numbers(_side(change={"a": 0.0, "b": 0.0, "dead": 0.0}),
                        _side())
    assert n["change_gap"] == pytest.approx(1.0)
    # a leaf smaller than the median leaf is measured against the median
    assert n["change_mid_gap"] == pytest.approx((1.0 + 0.1 / 0.15) / 2)


# ------------------------------------------------------------------ others
def test_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 - 1, 2 ** 31 + 5, 2200000001])
def test_weights_from_any_seed_up_to_a_little_over_2_to_31(seed):
    import numpy as np
    from chipbench.weights import make_weights
    spec = [("c/W", (4, 3, 3, 3), "he", 27), ("bn/gamma", (4,), "gamma", 0)]
    a, b = make_weights(spec, seed), make_weights(spec, seed)
    other = make_weights(spec, seed + 1)
    assert np.array_equal(a["c/W"], b["c/W"])
    assert not np.array_equal(a["c/W"], other["c/W"])
    assert a["c/W"].dtype == np.float32 and np.isfinite(a["c/W"]).all()


def test_host_batches_repeat_from_the_seed_and_every_row_differs():
    import numpy as np
    from chipbench.drivers.fit_iterator import make_batches, window_order
    cfg = {**_cfg("tinyyolo-voc-bf16"), "input_shape": [3, 64, 64]}
    traffic = {"batch": 6, "pool": 2}
    a = make_batches(cfg, traffic, 2 ** 31 + 3)
    b = make_batches(cfg, traffic, 2 ** 31 + 3)
    assert all(np.array_equal(x, u) and np.array_equal(y, v)
               for (x, y), (u, v) in zip(a, b))
    x, y = a[0]
    assert x.dtype == np.uint8 and x.shape == (6, 3, 64, 64)
    assert len({row.tobytes() for row in x}) == 6
    assert y.shape == (6, 24, 2, 2)
    assert (y[:, 4:].sum(axis=(1, 2, 3)) >= 1).all()    # objects present
    assert sorted(window_order(8, 5)) == list(range(8))
    assert list(window_order(8, 5)) != list(window_order(8, 6))
