"""The looped language model's configuration, cell, driver and metric
readers (PR 28): the sizes pinned at the published widths, the tiny cell
end to end through ``run_cell`` on the CPU in float32, both planted faults
and the fp8 control not ``correct``, and every new reader on a hand-made
trace whose answers are known. ``chipbench_tiny.tiny_root`` shrinks only
the configurations it knows: this file shrinks its own copy of the new
one."""

import json
import os
import types

import jax
import pytest

import chipbench_tiny as tiny
from chipbench import compare, programspans as ps
from chipbench import run as runmod
from chipbench import trace
from chipbench.manifest import Manifest

CELL = "ouro-fit-s4096-b1"
CONFIG = "ouro-2.6b-l6-bf16"
NEW = ["tok_per_s_per_chip", "loop_stack_device_ms", "head_loss_device_ms",
       "remat_device_ms", "attn_device_ms", "attn_roofline"]
TINY = dict(num_layers=2, hidden_size=64, num_attention_heads=4, head_dim=16,
            intermediate_size=176, vocab_size=512, seq_len=32)
MS = 1_000_000          # ns
_LINE = {}


def _dump(path, data):
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    m = tiny.tiny_root(tmp_path_factory.mktemp("tinyouro"),
                       settings={"precision": "fp32"})
    entry = next(c for c in m.data["configs"] if c["name"] == CONFIG)
    path = os.path.join(m.root, entry["file"])
    cfg = json.load(open(path))
    cfg.update(TINY)
    _dump(path, cfg)
    path = os.path.join(m.bench_dir, "traffic", "fit-tokens-s4096-b1.json")
    traffic = json.load(open(path))
    traffic.update(batch=2, seq_len=32)
    _dump(path, traffic)
    return m


def line_of(manifest):
    if not _LINE:
        _LINE.update(runmod.run_cell(
            manifest, tiny.run_args(CELL, seed=2 ** 31 + 2357),
            jax.devices()[:1], tiny.v5e_peak()))
    return _LINE


def value(checks, name):
    c = checks[name]
    return c["value"] if isinstance(c, dict) else c


# ------------------------------------------------------------- the sizes
def test_the_configuration_holds_the_published_widths():
    real = Manifest()
    cfg = real.cell(CELL)["cfg"]
    published = {"hidden_size": 2048, "num_attention_heads": 16,
                 "num_key_value_heads": 16, "head_dim": 128,
                 "intermediate_size": 5632, "vocab_size": 49152,
                 "rms_norm_eps": 1e-6, "rope_theta": 1000000,
                 "total_ut_steps": 4, "early_exit_threshold": 1,
                 "max_position_embeddings": 65536, "num_hidden_layers": 48,
                 "tie_word_embeddings": False, "sliding_window": None}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_layers"] and cfg["num_layers"] == 6
    assert cfg["published"] == {"num_layers": 48}
    assert cfg["stated_precision"] == "bfloat16" \
        and cfg["control_precision"] == "fp8"
    assert {"sandwich_norm", "attention_bias", "loss", "updater", "seq_len",
            "tokens", "exit_gate", "weights"} <= set(cfg["assumed"])
    assert "eight" in cfg["deployment"]
    entry = next(c for c in real.data["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and "2510.25741" in cfg["source"]
    assert entry["source"].startswith(
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    w = real.workload(CELL)
    assert (w["config"], w["traffic"], w["chips"]) \
        == (CONFIG, "fit-tokens-s4096-b1", 1)


def test_flops_matmuls_and_parameters_are_pinned():
    cell = Manifest().cell(CELL)
    cfg, model = cell["cfg"], cell["model"]
    assert model.layer_matmul_params(cfg) == 51_380_224
    assert model.attention_flops(cfg) == 2 * 4096 ** 2 * 2048 == 68_719_476_736
    assert model.attention_bytes(cfg) == 4 * 4096 * 2048 * 2
    assert model.layer_applications(cfg) == 24
    want = 24 * (2 * 4096 * 51_380_224 + 2 * 4096 ** 2 * 2048) \
        + 4 * 2 * 4096 * 2048 * 49152
    assert model.flops_per_sample(cfg) == want
    assert round(want / 1e12, 2) == 15.05
    assert model.n_matmuls(cfg) == 24 * 9 + 4 == 220
    n = sum(int(__import__("math").prod(s))
            for _n, s, _k, _f in model.param_spec(cfg))
    assert n == 509_661_185
    kinds = {name: kind for name, _s, kind, _f in model.param_spec(cfg)}
    # the looped layers' leaves come stacked over the six layers
    shapes = {name: tuple(s) for name, s, _k, _f in model.param_spec(cfg)}
    assert shapes["stack/attn.Wq"] == (6, 2048, 2048)
    assert shapes["stack/mlp.Wd"] == (6, 5632, 2048)
    assert len(shapes) == 11 + 5
    assert kinds["stack/n1.gain"] == kinds["stack/n3.gain"] \
        == kinds["fnorm/gain"] == "gamma"
    assert kinds["stack/n2.gain"] == kinds["stack/n4.gain"] == "gamma_last"
    assert kinds["lm/gate_w"] == kinds["lm/gate_b"] == "small"
    assert kinds["stack/mlp.Wd"] == kinds["embed/W"] == kinds["lm/W"] == "he"
    assert cell["traffic"]["batch"] == 1 \
        and cell["traffic"]["seq_len"] == cfg["seq_len"] == 4096
    assert set(cell["limits"]) >= {"loss1_gap", "graddir_mid_gap",
                                   "graddir_top_gap"}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(os.path.dirname(Manifest().cell(CELL)
                                        ["reference"].__file__),
                        "reference.py")
    text = open(path).read()
    assert "deeplearning4j_tpu" not in text.replace(
        "Nothing of the program is imported", "")
    assert "import jax" in text


# --------------------------------------------------- the tiny cell, end to end
def test_last_line_has_the_contracts_keys(manifest):
    line = line_of(manifest)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert json.loads(json.dumps(line)) == line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"img_per_s_per_chip", "peak_hbm_gib",
                                    "setup_s"}


def test_program_agrees_with_the_plain_reference_in_float32(manifest):
    line = line_of(manifest)
    checks = line["checks"]
    assert line["correct"], checks
    assert value(checks, "loss1_gap") < 1e-5
    assert value(checks, "loss2_gap") < 1e-3
    assert "loss3_gap" not in checks     # two check steps: see the traffic
    assert value(checks, "grad_gap") < 1e-3
    assert value(checks, "graddir_gap") < 1e-3
    assert value(checks, "change_gap") < 0.02
    for name, limit in manifest.cell(CELL)["limits"].items():
        assert checks[name]["limit"] == limit and checks[name]["ok"]


@pytest.fixture(scope="module")
def first_steps(manifest):
    """What set-up keeps of the program's first steps, and the batches."""
    cell = manifest.cell(CELL)
    import time
    result = cell["driver"].run(cell, tiny.run_args(CELL, seed=2 ** 31 + 99,
                                                    seconds=0.2),
                                time.perf_counter())
    return cell, result


@pytest.mark.parametrize("fault", ["three_passes", "last_pass_grad"])
def test_a_planted_fault_is_not_correct(first_steps, fault):
    """Three passes for four, and the looped weights' gradient taken from
    their last use alone: each fails a limit of the cell's file."""
    cell, result = first_steps
    faulty = dict(cell, loss=cell["reference"].make_loss(cell["cfg"],
                                                         fault=fault))
    ref = cell["driver"].reference_numbers(faulty, result["batches"],
                                           2 ** 31 + 99)
    ok, checks = compare.judge(compare.numbers(result["checked"], ref),
                               cell["limits"])
    assert not ok, checks
    failed = {k for k, c in checks.items()
              if isinstance(c, dict) and not c["ok"]}
    if fault == "last_pass_grad":
        # the head is not a looped weight: the top leaf's gradient stays
        # whole, the middle leaf's does not
        assert "graddir_mid_gap" in failed and "grad_mid_gap" in failed
    else:
        assert failed & {"graddir_top_gap", "graddir_mid_gap"}


def test_the_fp8_control_is_not_correct(first_steps):
    cell, result = first_steps
    control = {k: v for k, v in cell.items() if k != "loss"}
    ref = cell["driver"].reference_numbers(control, result["batches"],
                                           2 ** 31 + 99, precision="fp8")
    ok, checks = compare.judge(compare.numbers(result["checked"], ref),
                               cell["limits"])
    assert not ok, checks
    with pytest.raises(ValueError, match="unknown planted fault"):
        cell["reference"].make_loss(cell["cfg"], fault="none")


def test_the_driver_feeds_distinct_seeded_token_batches(manifest):
    cell = manifest.cell(CELL)
    drv = cell["driver"]
    a = drv.make_batches(cell["cfg"], cell["traffic"], 2 ** 31 + 5)
    b = drv.make_batches(cell["cfg"], cell["traffic"], 2 ** 31 + 5)
    c = drv.make_batches(cell["cfg"], cell["traffic"], 2 ** 31 + 6)
    assert len(a) == cell["traffic"]["pool"]
    x, y = a[0]
    assert x.dtype == y.dtype == "int32" and x.shape == y.shape == (2, 32)
    assert (x[:, 1:] == y[:, :-1]).all()        # labels: shifted by one
    assert 0 <= x.min() and x.max() < cell["cfg"]["vocab_size"]
    assert all((p[0] == q[0]).all() for p, q in zip(a, b))
    assert not (a[0][0] == c[0][0]).all() and not (a[0][0] == a[1][0]).all()
    bad = dict(cell["traffic"], seq_len=16)
    with pytest.raises(ValueError, match="not the\n? ?configuration's|seq_len"):
        drv.make_batches(cell["cfg"], bad, 1)


# ------------------------------------------------- the readers, by hand
def made_up(marks=True):
    """Two steps of 100 ms; a step's program runs [10, 92] ms into its
    slot: a looped layer's forward matmul 20 ms (pass 1), its attention
    core 8 ms forward (pass 2) + 6 ms rematerialised + 10 ms backward, a
    rematerialised stack matmul 12 ms, a head 9 ms forward + 5 ms
    rematerialised + 7 ms backward, Adam on the embedding 3 ms, a copy the
    map does not list 2 ms."""
    rows = (("fusion.1", 20, ["forward", "dl4j_L2_l0_mlp", None, False, 1,
                              None, False]),
            ("fusion.2", 8, ["forward", "dl4j_L3_l0_attn", None, False, 2,
                             "attn_core", False]),
            ("fusion.3", 6, ["backward", "dl4j_L3_l0_attn", None, False, 2,
                             "attn_core", True]),
            ("fusion.4", 10, ["backward", "dl4j_L3_l0_attn", None, False, 2,
                              "attn_core", False]),
            ("fusion.5", 12, ["backward", "dl4j_L2_l0_mlp", None, True, 1,
                              None, True]),
            ("fusion.6", 9, ["forward", "dl4j_loss", None, False, 3,
                             "head_loss", False]),
            ("fusion.7", 5, ["backward", "dl4j_loss", None, False, 3,
                             "head_loss", True]),
            ("fusion.8", 7, ["backward", "dl4j_loss", None, False, 3,
                             "head_loss", False]),
            ("fusion.9", 3, ["updater", "dl4j_updater", None, False, None,
                             None, False]),
            ("copy.1", 2, None))
    ops, modules = [], []
    for k in range(2):
        t = k * 100 * MS
        modules.append(["jit_step(5)", t + 10 * MS, 82 * MS])
        cur = t + 10 * MS
        for name, dur, _entry in rows:
            ops.append([f"%{name} = bf16[8,8] fusion kOutput of 2", cur,
                        dur * MS, 10])
            cur += dur * MS
    raw = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
           "host": {}}
    entries = {name: (e if marks else e[:4]) for name, _d, e in rows
               if e is not None}
    return trace.reduce_raw(raw), {"jit_step": entries}


def ctx_of(red, maps, model=None, cfg=None):
    traced = (0.0, 1.0, 3, 5)
    ctx = types.SimpleNamespace(
        reduced=red, peak=tiny.v5e_peak(), cfg=cfg or {}, model=model,
        result={"traced": traced, "steps": 20, "window_s": 10.0, "batch": 1,
                "chips": 1})
    ctx.programspans = ps.Joined(red, traced, [], maps)
    return ctx


def read(name, ctx):
    return Manifest().reader(name)(ctx)


def test_the_readers_on_a_hand_made_trace():
    red, maps = made_up()
    model = types.SimpleNamespace(
        core_flops=lambda cfg: 197e12 * 1e-3,       # 1 ms at the peak
        attention_applications=lambda cfg: 2)
    ctx = ctx_of(red, maps, model)
    assert read("loop_stack_device_ms", ctx) == pytest.approx(56.0)
    assert read("head_loss_device_ms", ctx) == pytest.approx(21.0)
    assert read("attn_device_ms", ctx) == pytest.approx(24.0)
    assert read("remat_device_ms", ctx) == pytest.approx(23.0)
    # 3 x 1 ms x 2 applications required, over 24 ms
    assert read("attn_roofline", ctx) == pytest.approx(100 * 6 / 24)
    # the parts the acceptance sums: stack + heads against the whole step
    assert read("step_device_ms", ctx) == pytest.approx(82.0)


def test_a_program_without_the_marks_gives_no_reading():
    """The parent's map has four fields an entry and no token counter:
    every new reader then returns nothing and raises nothing."""
    red, maps = made_up(marks=False)
    model = types.SimpleNamespace()
    ctx = ctx_of(red, maps, model)
    for name in NEW[1:]:
        assert read(name, ctx) is None
    ctx = ctx_of(red, None, model)
    for name in NEW[1:]:
        assert read(name, ctx) is None
    untraced = ctx_of(red, maps, model)
    untraced.result["traced"] = None
    assert read("tok_per_s_per_chip", untraced) is None


def test_tokens_a_second_reads_the_programs_counter(monkeypatch):
    red, maps = made_up()
    ctx = ctx_of(red, maps)
    monkeypatch.setattr(ps, "counter_total",
                        lambda name: {"dl4j_train_tokens_total": 81920.0}
                        .get(name))
    # 4,096 ids a step over the window's 20 steps; 2 traced steps in the
    # 182 ms from the first step program's start to the last one's end
    assert read("tok_per_s_per_chip", ctx) == pytest.approx(
        81920 / 20 * 2 / 0.182)
    monkeypatch.setattr(ps, "counter_total", lambda name: None)
    assert read("tok_per_s_per_chip", ctx) is None


def test_manifest_lists_the_new_metrics_for_the_new_cell_alone():
    """Found by name, wherever later PRs append theirs: the cell is one of
    the cells each lists."""
    data = Manifest().data["per_layer"]
    by_name = {m["name"]: m for m in data}
    layers = {"tok_per_s_per_chip": "fit driver and input",
              "attn_device_ms": "kernels", "attn_roofline": "kernels"}
    for m in (by_name[name] for name in NEW):
        assert CELL in m["workloads"]
        assert m["moves"] == "img_per_s_per_chip"
        assert m["layer"] == layers.get(m["name"], "step program")
        assert m["source"] == ("program_counter"
                               if m["name"] == "tok_per_s_per_chip"
                               else "program_span")
    # the seven that list no cell run here too, as they stand
    everywhere = {m["name"] for m in data if "workloads" not in m}
    assert everywhere == {"step_mfu", "step_device_ms", "conv_roofline",
                          "nonconv_roofline", "device_idle_share",
                          "data_wait_share", "compiles_in_window"}
    got = {m["name"] for m in Manifest().metrics_for(CELL, "per_layer")}
    assert got >= everywhere | set(NEW)


def test_traced_run_reports_the_new_per_layer_metrics(manifest, monkeypatch):
    """A ``--trace 1`` run of the tiny cell on the CPU, with the hand-made
    trace handed to the reduction in place of the CPU's own (which has no
    device plane) and its map to the join: the program's counter is live,
    the marks are the hand-made ones."""
    red, maps = made_up()
    monkeypatch.setattr(trace, "reduce_xspace",
                        lambda path, step_module=None: red)
    monkeypatch.setattr(ps, "from_program", lambda: ([], maps))
    line = runmod.run_cell(manifest, tiny.run_args(CELL, seed=11, trace=1,
                                                   seconds=1.0),
                           jax.devices()[:1], tiny.v5e_peak())
    got = line["metrics"]
    assert set(NEW) <= set(got)
    assert got["tok_per_s_per_chip"]["unit"] == "tok/s/chip"
    # every step of the window handed the loop 2 x 32 tokens
    assert got["tok_per_s_per_chip"]["value"] > 0
    assert got["loop_stack_device_ms"]["value"] == pytest.approx(56.0)
    assert got["compiles_in_window"]["value"] == 0
    assert line["correct"]
