"""The hybrid decoder's configuration, cell and metric readers (PR 35):
the widths pinned against the catalog row key by key, the cut written
into the file, parameter and FLOP counts pinned, the tiny cell end to end
through ``run_cell`` on the CPU in float32, every planted fault and the
fp8 control not ``correct``, a tree without the new layer failing as the
cell is loaded, and every new reader on a hand-made trace whose answers
are known. ``chipbench_tiny.tiny_root`` shrinks only the configurations
it knows: this file shrinks its own copy of the new one."""

import json
import os
import time
import types

import jax
import numpy as np
import pytest

import chipbench_tiny as tiny
from chipbench import compare, programspans as ps
from chipbench import run as runmod
from chipbench import trace, xingmarks as xm
from chipbench.manifest import Manifest

CELL = "lfm2-fit-s8192-b4"
CONFIG = "lfm2-24b-a2b-l5-bf16"
TRAFFIC = "fit-tokens-lean-s8192-b4"
URL = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
NEW = ["shortconv_device_ms", "shortconv_roofline", "attn_device_ms",
       "attn_roofline", "moe_device_ms", "moe_expert_roofline",
       "routed_tokens_per_expert", "moe_load_max_over_mean"]
TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=48, moe_intermediate_size=16, vocab_size=64,
            seq_len=32, held_experts=[0, 1, 2, 3], num_experts=4)
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv", "full_attention", "conv"] * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
REDUCED = {"num_dense_layers": 1, "num_experts": 8, "vocab_size": 8192}
SEED = 2 ** 31 + 99
MS = 1_000_000          # ns
_LINE = {}


def _dump(path, data):
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    m = tiny.tiny_root(tmp_path_factory.mktemp("tinylfm2"),
                       settings={"precision": "fp32"})
    entry = next(c for c in m.data["configs"] if c["name"] == CONFIG)
    path = os.path.join(m.root, entry["file"])
    cfg = json.load(open(path))
    cfg.update(TINY)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    _dump(path, cfg)
    path = os.path.join(m.bench_dir, "traffic", TRAFFIC + ".json")
    traffic = json.load(open(path))
    traffic.update(batch=2, seq_len=32, check_steps=2)
    _dump(path, traffic)
    return m


def line_of(manifest):
    if not _LINE:
        _LINE.update(runmod.run_cell(
            manifest, tiny.run_args(CELL, seed=2 ** 31 + 2357, seconds=0.5),
            jax.devices()[:1], tiny.v5e_peak()))
    return _LINE


def value(checks, name):
    c = checks[name]
    return c["value"] if isinstance(c, dict) else c


# ------------------------------------------------------------- the sizes
def test_the_pinned_row_is_the_catalogs():
    """Where the guide's catalog is installed, the row pinned above is
    its row, letter for letter."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    rows = [json.loads(line) for line in open(path) if line.strip()]
    row, = [r for r in rows if r["name"] == "LFM2-24B-A2B"]
    assert row["config"] == PUBLISHED and row["source_url"] == URL


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_holds_the_published_value_of(key):
    cfg = Manifest().cell(CELL)["cfg"]
    if key in REDUCED:
        assert key in cfg["reduced"] and cfg[key] == REDUCED[key]
        assert cfg["published"][key] == PUBLISHED[key]
    else:
        assert cfg[key] == PUBLISHED[key]


def test_the_cut_is_written_into_the_file():
    real = Manifest()
    cfg = real.cell(CELL)["cfg"]
    assert cfg["reduced"] == ["num_layers", "num_dense_layers",
                              "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_layers": 40, "num_dense_layers": 2,
                                "num_experts": 64, "vocab_size": 65536}
    assert cfg["num_layers"] == 5 == len(cfg["held_layers"])
    assert cfg["held_layers"] == [0, 2, 3, 4, 5]
    assert cfg["held_experts"] == list(range(8))
    # one leading dense layer, then one whole period in the published
    # order and ratio: full_attention, conv, conv, conv
    kinds = [cfg["layer_types"][i] for i in cfg["held_layers"]]
    assert kinds == ["conv", "full_attention", "conv", "conv", "conv"]
    assert sorted(kinds[1:]) == sorted(cfg["layer_types"][:4])
    # no width is cut, and nothing goes below a floor of the guide
    assert not any(w in k for k in cfg["reduced"]
                   for w in ("hidden", "_dim", "_rank", "intermediate",
                             "per_tok", "head", "conv"))
    assert cfg["num_layers"] - cfg["num_dense_layers"] >= 4
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["stated_precision"] == "bfloat16" \
        and cfg["control_precision"] == "fp8"
    assert cfg["zoo_model"] == "LFM2"
    assert {"tied_embedding", "router", "select_bias", "gate_divisor_eps",
            "head_dim", "qk_norm", "rope", "short_conv", "seq_len",
            "tokens", "updater", "weights"} <= set(cfg["assumed"])
    for word in ("eight", "8 a chip", "an eighth a chip", "pipeline",
                 "2,048 tokens an expert", "4,096", "no code stands in"):
        assert word in cfg["deployment"], word
    entry = next(c for c in real.data["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == URL
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}/config.json"
    w = real.workload(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert "2,048 tokens an expert" in w["why"] and len(w["why"]) <= 200


def test_parameters_flops_and_matmuls_are_pinned():
    cell = Manifest().cell(CELL)
    cfg, model = cell["cfg"], cell["model"]
    assert model.n_params(cfg) == 469_284_992
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    dense, experts = 3 * 2048 * 11776, 8 * 3 * 2048 * 1536 + 2048 * 64
    assert (conv, attn) == (16_783_360, 10_485_888)
    assert model.n_params(cfg) == 8192 * 2048 + 2048 + 5 * 2 * 2048 \
        + 4 * conv + attn + dense + 4 * experts
    assert model.attention_projection_params(cfg) == 10_485_760
    assert model.core_flops(cfg) == 2 * 8192 ** 2 * 32 * 64
    assert model.attention_applications(cfg) == 1
    assert model.shortconv_mixers(cfg) == 4
    assert model.shortconv_flops(cfg) == 2 * 4 * 2048 ** 2 + 2 * 3 * 2048
    assert len(model.expert_layers_of(cfg)) == 4
    assert model.expected_pairs(cfg) == 4096
    assert model.expected_pairs(cfg, batch=4) == 8 * 2048
    assert model.expert_product_flops(cfg, 2048) == 6 * 2048 * 2048 * 1536
    assert model.expert_product_bytes(cfg, 2048) == 2 * (
        3 * 8 * 2048 * 1536 + 2 * 2048 * 2048)
    parts = model.flops_by_part(cfg)
    token = {k: v / 8192 for k, v in parts.items()}
    assert token == {
        "shortconv": 4 * (8 * 2048 ** 2 + 6 * 2048),
        "attn_proj": 2 * 10_485_760, "attn_core": 2 * 8192 * 2048,
        "dense_mlp": 6 * 2048 * 11776, "router": 4 * 2 * 2048 * 64,
        "experts": 4 * 6 * 0.5 * 2048 * 1536, "head": 2 * 2048 * 8192}
    assert model.flops_per_sample(cfg) == sum(parts.values())
    assert round(sum(token.values()) / 1e6, 1) == 405.8
    assert round(model.flops_per_sample(cfg) / 1e12, 3) == 3.325
    assert model.n_matmuls(cfg) == 1 + (2 + 3) + (6 + 1) + 3 * (2 + 1) == 22
    shapes = {n: tuple(s) for n, s, _k, _f in model.param_spec(cfg)}
    kinds = {n: (k, f) for n, _s, k, f in model.param_spec(cfg)}
    assert shapes["l0_conv/Win"] == (2048, 6144)
    assert shapes["l0_conv/Wc"] == (3, 2048)
    assert shapes["l1_attn/Wq"] == shapes["l1_attn/Wo"] == (2048, 2048)
    assert shapes["l1_attn/Wk"] == shapes["l1_attn/Wv"] == (2048, 512)
    assert shapes["l1_attn/qn"] == shapes["l1_attn/kn"] == (64,)
    assert shapes["l1_moe/Eg"] == (8, 2048, 1536)
    assert shapes["l1_moe/Wr"] == (2048, 64)
    assert shapes["embed/W"] == (8192, 2048)
    assert "l0_moe/Wr" not in shapes and "l0_mlp/Wg" in shapes
    assert "l1_mlp/Wg" not in shapes and "l0_attn/Wq" not in shapes
    assert not any(n.startswith("lm/") or "/S" in n for n in shapes)
    assert kinds["l0_conv/Wc"] == ("he", 3)
    assert kinds["l1_attn/qn"][0] == kinds["fnorm/gain"][0] == "gamma"
    states = model.state_spec(cfg)
    assert [n for n, *_ in states] == [f"l{i}_moe/select_bias"
                                       for i in range(1, 5)]
    # 0.01 N, named in the file; the maker's ``he`` at fan-in 2 / std**2
    assert cfg["select_bias_std"] == 0.01
    assert all(tuple(s) == (64,) and (k, f) == ("he", 20000)
               and (2 / f) ** 0.5 == cfg["select_bias_std"]
               for _n, s, k, f in states)
    assert cell["traffic"]["batch"] == 4 \
        and cell["traffic"]["seq_len"] == cfg["seq_len"] == 8192
    assert cell["traffic"]["driver"] == "fit_tokens_lean"
    assert cell["traffic"]["check_steps"] == 1
    assert set(cell["limits"]) >= {"loss1_gap", "graddir_mid_gap",
                                   "graddir_top_gap", "change_gap",
                                   "route_flip_share"}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(os.path.dirname(Manifest().cell(CELL)
                                        ["reference"].__file__),
                        "reference.py")
    text = open(path).read()
    assert "deeplearning4j_tpu" not in text
    assert "import jax" in text and "chipbench" in text


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
    assert "loss3_gap" not in checks
    assert value(checks, "grad_gap") < 1e-3
    assert value(checks, "graddir_gap") < 1e-3
    assert value(checks, "change_gap") < 0.02
    assert value(checks, "grad_routed_gap") < 1e-4      # shown, not held
    assert checks["grad_routed_leaf"].split("/")[1] in ("Eg", "Eu", "Ed")
    for name, limit in manifest.cell(CELL)["limits"].items():
        assert checks[name]["limit"] == limit and checks[name]["ok"]
    # in float32 the program chooses the reference's own experts
    assert value(checks, "route_flip_share") == 0.0
    assert value(checks, "route_worst_margin") == 0.0


@pytest.fixture(scope="module")
def first_steps(manifest):
    """What set-up keeps of the program's first steps, and the batches."""
    cell = manifest.cell(CELL)
    result = cell["driver"].run(cell, tiny.run_args(CELL, seed=SEED,
                                                    seconds=0.2),
                                time.perf_counter())
    return cell, result


def _judged(first_steps, **how):
    cell, result = first_steps
    drv = cell["driver"]
    ref = drv.reference_numbers(cell, result["batches"], SEED,
                                selected=result["checked"]["selected"],
                                **how)
    ok, checks = compare.judge(drv.numbers(result["checked"], ref),
                               cell["limits"])
    return ok, checks, {k for k, c in checks.items()
                        if isinstance(c, dict) and not c["ok"]}


def test_the_reference_follows_the_programs_choice(first_steps):
    cell, result = first_steps
    chosen = result["checked"]["selected"]
    assert len(chosen) == cell["traffic"]["check_steps"] == 2
    assert set(chosen[0]) == {f"l{i}_moe" for i in range(1, 5)}
    assert chosen[0]["l1_moe"].shape == (2 * 32, 4) \
        and chosen[0]["l1_moe"].dtype == np.int32
    assert 0 <= chosen[0]["l1_moe"].min() and chosen[0]["l1_moe"].max() < 16
    ok, checks, failed = _judged(first_steps)
    assert ok and not failed, checks
    wrong = [{k: np.broadcast_to(np.arange(12, 16, dtype=np.int32), a.shape)
              for k, a in step.items()} for step in chosen]
    drv = cell["driver"]
    ref = drv.reference_numbers(cell, result["batches"], SEED,
                                selected=wrong)
    ok, checks = compare.judge(drv.numbers(result["checked"], ref),
                               cell["limits"])
    assert not ok and not checks["route_worst_margin"]["ok"]


@pytest.mark.parametrize("fault", ["top3", "held_divisor", "kv_head_mod",
                                   "no_qk_norm", "conv_acausal",
                                   "no_out_gate", "untied_head"])
def test_a_planted_fault_is_not_correct(first_steps, fault):
    """Three experts a token for four, the gates normalised over the held
    experts only, query heads reading the wrong key/value head, q and k
    unnormed, the convolution's taps reaching forward, the output gate
    left out, a head that is not the embedding's table: each fails a
    limit of the cell's file."""
    ok, checks, failed = _judged(first_steps, fault=fault)
    assert not ok, checks
    assert failed & {"graddir_top_gap", "graddir_mid_gap", "loss1_gap",
                     "grad_mid_gap", "change_gap"}


def test_the_fp8_control_is_not_correct(first_steps):
    ok, checks, _failed = _judged(first_steps, precision="fp8")
    assert not ok, checks


@pytest.mark.parametrize("factor", [1.5, 1 / 1.5])
def test_a_wrong_step_size_is_not_correct(first_steps, factor):
    cell, result = first_steps
    cfg = dict(cell["cfg"], updater=dict(
        cell["cfg"]["updater"], lr=cell["cfg"]["updater"]["lr"] * factor))
    wrong = {k: v for k, v in cell.items()
             if not (isinstance(k, tuple) and k[0] == "lean_reference")}
    wrong["cfg"] = cfg
    drv = cell["driver"]
    ref = drv.reference_numbers(wrong, result["batches"], SEED,
                                selected=result["checked"]["selected"])
    ok, checks = compare.judge(drv.numbers(result["checked"], ref),
                               cell["limits"])
    assert not ok
    assert {k for k, c in checks.items()
            if isinstance(c, dict) and not c["ok"]} == {"change_gap"}


# ---------------------------------------------------------------- the model
def test_the_net_holds_the_seeded_weights_and_selection_biases(manifest):
    cell = manifest.cell(CELL)
    drv, model, cfg = cell["driver"], cell["model"], cell["cfg"]
    spec = model.param_spec(cfg)
    weights = drv.make_weights(spec, SEED)
    states = drv.make_states(model, cfg, SEED)
    assert set(states) == {f"l{i}_moe/select_bias" for i in range(1, 5)}
    assert states["l1_moe/select_bias"].shape == (16,)
    assert 0.003 < float(np.std(states["l1_moe/select_bias"])) < 0.03
    assert float(np.std(weights["l0_conv/Wc"])) == pytest.approx(
        (2 / 3) ** 0.5, rel=0.25)
    net = model.build(cfg, weights, states=states, batch=2)
    assert np.array_equal(net._states["l1_moe"]["select_bias"],
                          states["l1_moe/select_bias"])
    assert net._states["l1_moe"]["expert_load"].shape == (4,)
    assert net._states["l1_moe"]["selected"].shape == (2 * 32, 4)
    assert set(model.read_selected(net)) == set(model.expert_layers_of(cfg))
    assert model.expert_layers_of(cfg) == [f"l{i}_moe" for i in range(1, 5)]
    assert model.routed_leaves(cfg)[:3] == ["l1_moe/Eg", "l1_moe/Eu",
                                            "l1_moe/Ed"]
    assert net.conf.node_by_name["l1_moe"].obj.n_experts == 16
    assert set(model.read_leaves(net, "params")) == {n for n, *_ in spec}
    assert net._params["lm"] == {}
    assert type(net).__name__ == "ComputationGraph"
    bad = dict(weights)
    bad["l0_conv/Wc"] = bad["l0_conv/Wc"][:2]
    with pytest.raises(ValueError, match="the zoo's LFM2 wants"):
        model.build(cfg, bad, states=states)


def test_a_tree_without_the_new_layer_fails_as_the_cell_is_loaded(
        manifest, monkeypatch):
    """What the parent commit does with the new cell: the configuration's
    ``model.py`` imports the gated short-convolution layer at module
    level, so ``Manifest.cell()`` raises before any driver runs or any
    weights are made."""
    from deeplearning4j_tpu.nn import layers
    monkeypatch.delattr(layers, "GatedShortConvLayer")
    made = []
    from chipbench.drivers import fit_tokens_lean
    monkeypatch.setattr(fit_tokens_lean, "make_weights",
                        lambda *a, **k: made.append(1))
    with pytest.raises(ImportError, match="GatedShortConvLayer"):
        manifest.cell(CELL)
    with pytest.raises(ImportError):
        runmod.run_cell(manifest, tiny.run_args(CELL), jax.devices()[:1],
                        tiny.v5e_peak())
    assert not made
    # the accepted sparse cell still loads there
    assert manifest.cell("xing4-fit-s4096-b1")["model"] is not None


def test_the_cell_feeds_four_sequences_a_step(manifest):
    cell = manifest.cell(CELL)
    a = cell["driver"].make_batches(cell["cfg"], cell["traffic"],
                                    2 ** 31 + 5)
    assert len(a) == cell["traffic"]["pool"]
    x, y = a[0]
    assert x.dtype == y.dtype == "int32" and x.shape == y.shape == (2, 32)
    assert (x[:, 1:] == y[:, :-1]).all()
    assert 0 <= x.min() and x.max() < cell["cfg"]["vocab_size"] == 64
    real = Manifest().cell(CELL)["traffic"]
    assert (real["batch"], real["seq_len"], real["pool"]) == (4, 8192, 8)
    assert real["batch"] * real["seq_len"] * 4 // 64 == 2048


# ------------------------------------------------- the readers, by hand
def made_up(marks=True):
    """Two steps of 100 ms; a step's program runs [10, 90] ms into its
    slot: the dense MLP's forward matmul 20 ms, a short-convolution mixer
    9 ms forward + 5 rematerialised + 10 backward, the attention core 8 ms
    forward + 6 rematerialised + 10 backward, a router 4 ms, the grouped
    products 3 ms forward + 6 backward, the head 6 ms, Adam 3 ms."""
    e = lambda phase, layer, part=None, remat=False: [  # noqa: E731
        phase, layer, None, False, None, part, remat]
    rows = (("fusion.1", 20, e("forward", "dl4j_L5_l0_mlp")),
            ("fusion.2", 9, e("forward", "dl4j_L2_l0_conv", "shortconv")),
            ("fusion.3", 5, e("backward", "dl4j_L2_l0_conv", "shortconv",
                              True)),
            ("fusion.4", 10, e("backward", "dl4j_L2_l0_conv", "shortconv")),
            ("fusion.5", 8, e("forward", "dl4j_L8_l1_attn", "attn_core")),
            ("fusion.6", 6, e("backward", "dl4j_L8_l1_attn", "attn_core",
                              True)),
            ("fusion.7", 10, e("backward", "dl4j_L8_l1_attn", "attn_core")),
            ("fusion.8", 4, e("forward", "dl4j_L11_l1_moe", "moe")),
            ("custom-call.1", 3, e("forward", "dl4j_L11_l1_moe",
                                   "moe_experts")),
            ("custom-call.2", 6, e("backward", "dl4j_L17_l2_moe",
                                   "moe_experts")),
            ("fusion.9", 6, e("forward", "dl4j_loss", "head_loss")),
            ("fusion.10", 3, e("updater", "dl4j_updater")))
    ops, modules = [], []
    for k in range(2):
        t = k * 100 * MS
        modules.append(["jit_step(5)", t + 10 * MS, 90 * MS])
        cur = t + 10 * MS
        for name, dur, _entry in rows:
            ops.append([f"%{name} = bf16[8,8] fusion kOutput of 2", cur,
                        dur * MS, 10])
            cur += dur * MS
    raw = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
           "host": {}}
    entries = {name: (e_ if marks else e_[:4]) for name, _d, e_ in rows}
    return trace.reduce_raw(raw), {"jit_step": entries}


def ctx_of(red, maps, model=None, cfg=None):
    traced = (0.0, 1.0, 3, 5)
    ctx = types.SimpleNamespace(
        reduced=red, peak=tiny.v5e_peak(), model=model,
        cfg=cfg or {"seq_len": 100, "num_experts_per_tok": 4,
                    "held_experts": [0, 1]},
        result={"traced": traced, "steps": 20, "window_s": 10.0, "batch": 2,
                "chips": 1})
    ctx.programspans = ps.Joined(red, traced, [], maps)
    return ctx


def read(name, ctx):
    return Manifest().reader(name)(ctx)


GAUGES = {"dl4j_moe_held_pairs": {("l1_moe",): 60.0, ("l2_moe",): 40.0,
                                  ("mtp_moe",): 1000.0},
          "dl4j_moe_expert_load": {("l1_moe", "0"): 40.0,
                                   ("l1_moe", "1"): 20.0,
                                   ("l2_moe", "0"): 20.0,
                                   ("l2_moe", "1"): 20.0}}


def _model():
    return types.SimpleNamespace(
        core_flops=lambda cfg: 197e12 * 1e-3,           # 1 ms at the peak
        attention_applications=lambda cfg: 2,
        shortconv_flops=lambda cfg: 197e12 * 1e-5,      # 1 ms a 100 tokens
        shortconv_mixers=lambda cfg: 2,
        expert_layers_of=lambda cfg: ["l1_moe", "l2_moe"],
        # 60 pairs: FLOPs 0.6 ms, bytes 0.3 ms; 40 pairs: 0.4 and 0.5;
        # another model's layer left in the registry: 10 ms
        expert_product_flops=lambda cfg, n: 197e12 * 1e-5 * n,
        expert_product_bytes=lambda cfg, n: 819e9 * (
            0.3e-3 if n == 60 else 0.5e-3))


def test_the_readers_on_a_hand_made_trace(monkeypatch):
    red, maps = made_up()
    monkeypatch.setattr(xm, "gauge", lambda name: {
        k: v for k, v in GAUGES[name].items() if k[0] != "mtp_moe"})
    ctx = ctx_of(red, maps, _model())
    assert read("shortconv_device_ms", ctx) == pytest.approx(24.0)
    # 3 x 1 ms a 100 tokens x 2 sequences x 2 mixers required, over 24 ms
    assert read("shortconv_roofline", ctx) == pytest.approx(100 * 12 / 24)
    assert read("attn_device_ms", ctx) == pytest.approx(24.0)
    # 3 x 1 ms x 2 applications x 2 sequences required, over 24 ms
    assert read("attn_roofline", ctx) == pytest.approx(100 * 12 / 24)
    assert read("moe_device_ms", ctx) == pytest.approx(13.0)
    # 3 x (0.6 + 0.5) ms required, over the 9 ms of the grouped products
    assert read("moe_expert_roofline", ctx) == pytest.approx(100 * 3.3 / 9)
    # (60 + 40) pairs over 2 layers of 2 held experts
    assert read("routed_tokens_per_expert", ctx) == pytest.approx(25.0)
    assert read("moe_load_max_over_mean", ctx) == pytest.approx(40 / 30)
    assert read("step_device_ms", ctx) == pytest.approx(90.0)


def test_the_load_an_expert_reads_this_configurations_layers_alone(
        monkeypatch):
    """The registry is the process's: a layer another model left there
    (the CPU tests run several in one process) is not this cell's."""
    red, maps = made_up()
    monkeypatch.setattr(xm, "gauge", GAUGES.get)
    assert read("routed_tokens_per_expert", ctx_of(red, maps, _model())) \
        == pytest.approx(25.0)


def test_a_program_without_the_marks_gives_no_reading(monkeypatch):
    """The parent's map knows no ``shortconv`` part and its model file no
    count: every new reader returns nothing and raises nothing."""
    model = types.SimpleNamespace()
    for marks, maps_on in ((False, True), (True, False)):
        red, maps = made_up(marks=marks)
        ctx = ctx_of(red, maps if maps_on else None, model)
        monkeypatch.setattr(xm, "gauge", lambda name: None)
        for name in NEW:
            assert read(name, ctx) is None, name
    # a map of the parent's kind (no shortconv part) on a sparse model
    red, maps = made_up()
    for entry in maps["jit_step"].values():
        if entry[5] == "shortconv":
            entry[5] = None
    monkeypatch.setattr(xm, "gauge", GAUGES.get)
    ctx = ctx_of(red, maps, model)
    for name in ("shortconv_device_ms", "shortconv_roofline",
                 "attn_roofline", "moe_expert_roofline",
                 "routed_tokens_per_expert"):
        assert read(name, ctx) is None, name
    assert read("attn_device_ms", ctx) == pytest.approx(24.0)
    assert read("moe_device_ms", ctx) == pytest.approx(13.0)
    untraced = ctx_of(red, maps, _model())
    untraced.result["traced"] = None
    assert read("routed_tokens_per_expert", untraced) is None
    assert read("moe_load_max_over_mean", untraced) is None


def test_manifest_lists_the_new_metrics_for_the_new_cell_alone():
    """Found by name, wherever later PRs append theirs: the cell is one of
    the cells each lists."""
    data = {m["name"]: m for m in Manifest().data["per_layer"]}
    assert set(NEW) <= set(data)
    kernels = {"shortconv_roofline", "attn_device_ms", "attn_roofline",
               "moe_expert_roofline"}
    counters = {"routed_tokens_per_expert", "moe_load_max_over_mean"}
    units = {"routed_tokens_per_expert": "tok",
             "moe_load_max_over_mean": "x"}
    for name in NEW:
        m = data[name]
        assert CELL in m["workloads"]
        assert m["moves"] == "img_per_s_per_chip"
        assert m["layer"] == ("kernels" if name in kernels
                              else "step program")
        assert m["source"] == ("program_counter" if name in counters
                               else "program_span")
        assert m["unit"] == units.get(
            name, "%" if name.endswith("_roofline") else "ms")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    everywhere = {m["name"] for m in data.values() if "workloads" not in m}
    got = {m["name"] for m in Manifest().metrics_for(CELL, "per_layer")}
    assert got >= everywhere | set(NEW)
    assert {"step_mfu", "step_device_ms", "device_idle_share",
            "compiles_in_window"} <= everywhere
    four = [w for w in Manifest().data["workloads"] if w["chips"] == 4]
    assert len(four) == 1
    for name in NEW:
        path = os.path.join(tiny.BENCH_DIR, "metrics", name + ".py")
        assert os.path.isfile(path)


def test_traced_run_reports_the_new_per_layer_metrics(manifest, monkeypatch):
    """A ``--trace 1`` run of the tiny cell on the CPU, with the hand-made
    trace handed to the reduction in place of the CPU's own (which has no
    device plane) and its map to the join: the gauges, counters and
    fit-loop spans are the live program's, the marks are the hand-made
    ones. Every per-layer metric the cell lists reads a number."""
    red, maps = made_up()
    monkeypatch.setattr(trace, "reduce_xspace",
                        lambda path, step_module=None: red)
    live = ps.from_program
    monkeypatch.setattr(ps, "from_program", lambda: (live()[0], maps))
    line = runmod.run_cell(manifest, tiny.run_args(CELL, seed=11, trace=1,
                                                   seconds=0.5),
                           jax.devices()[:1], tiny.v5e_peak())
    got = line["metrics"]
    listed = {m["name"] for m in manifest.metrics_for(CELL, "per_layer")}
    # the hand-made trace holds no op of the `other` class and fewer
    # product events a step than the configuration has matmuls, so the two
    # class rooflines read nothing by their own rules here;
    # test_chipbench_arith.py reads them on traces made for them
    unread = {"conv_roofline", "nonconv_roofline"}
    assert listed - unread <= set(got), sorted(listed - unread - set(got))
    assert set(NEW) <= set(got), sorted(set(NEW) - set(got))
    assert "step_mfu" in got and "step_device_ms" in got
    assert got["shortconv_device_ms"]["value"] == pytest.approx(24.0)
    # 64 tokens, top-4 of 16: 16 tokens an expert, more or less
    assert 6 < got["routed_tokens_per_expert"]["value"] < 30
    assert got["routed_tokens_per_expert"]["unit"] == "tok"
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert got["moe_expert_roofline"]["value"] > 0
    assert got["compiles_in_window"]["value"] == 0
    assert line["correct"]
