"""The sparse decoder's configuration, cell, lean driver and metric
readers (PR 33): the widths pinned against the catalog row key by key,
parameter and FLOP counts pinned, the tiny cell end to end through
``run_cell`` on the CPU in float32, every planted fault (the model's,
the selection bias's two, a wrong step size) and the fp8 control not
``correct``, the leaves at Adam's eps left out of the change, and every
new reader on a hand-made trace whose
answers are known. ``chipbench_tiny.tiny_root`` shrinks only the
configurations it knows: this file shrinks its own copy of the new one."""

import json
import math
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

CELL = "xing4-fit-s4096-b1"
CONFIG = "xing4.0-29b-a4b-l5-bf16"
TRAFFIC = "fit-tokens-lean-s4096-b1"
URL = "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/" \
    "config.json"
NEW = ["mla_core_device_ms", "mla_core_roofline", "moe_device_ms",
       "moe_expert_roofline", "mhc_device_ms", "mhc_roofline",
       "mtp_device_ms", "moe_held_pair_share", "moe_load_max_over_mean"]
TINY = dict(num_layers=2, hidden_size=32, num_attention_heads=4,
            q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
            moe_intermediate_size=16, vocab_size=64, seq_len=32,
            held_experts=[0, 1, 2, 3], n_routed_experts=4)
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"first_k_dense_replace": 1, "n_routed_experts": 8,
           "vocab_size": 16384}
SEED = 2 ** 31 + 99
MS = 1_000_000          # ns
_LINE = {}


def _dump(path, data):
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    m = tiny.tiny_root(tmp_path_factory.mktemp("tinyxing4"),
                       settings={"precision": "fp32"})
    entry = next(c for c in m.data["configs"] if c["name"] == CONFIG)
    path = os.path.join(m.root, entry["file"])
    cfg = json.load(open(path))
    cfg.update(TINY)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16)
    _dump(path, cfg)
    path = os.path.join(m.bench_dir, "traffic", TRAFFIC + ".json")
    traffic = json.load(open(path))
    traffic.update(batch=1, seq_len=32, check_steps=2)
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
    assert cfg["reduced"] == ["num_layers", "first_k_dense_replace",
                              "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"num_layers": 40, "first_k_dense_replace": 2,
                                "n_routed_experts": 64, "vocab_size": 131072}
    assert cfg["num_layers"] == 5 and cfg["held_experts"] == list(range(8))
    # no width is cut, and nothing goes below a floor of the guide
    assert not any(w in k for k in cfg["reduced"]
                   for w in ("hidden", "_dim", "_rank", "intermediate",
                             "per_tok"))
    assert cfg["num_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["stated_precision"] == "bfloat16" \
        and cfg["control_precision"] == "fp8"
    assert {"streams", "mhc", "select_bias", "router", "rope", "mtp",
            "seq_len", "tokens", "updater", "weights"} <= set(cfg["assumed"])
    for word in ("eight", "8 a chip", "pipeline", "256 tokens an expert",
                 "2,048"):
        assert word in cfg["deployment"], word
    entry = next(c for c in real.data["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(URL)
    assert entry["reduced"] == cfg["reduced"]
    w = real.workload(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert "256 tokens an expert" in w["why"] and len(w["why"]) <= 200


def test_parameters_flops_and_matmuls_are_pinned():
    cell = Manifest().cell(CELL)
    cfg, model = cell["cfg"], cell["model"]
    assert model.n_params(cfg) == 913_469_764
    assert model.n_params(dict(cfg, num_nextn_predict_layers=0)) \
        == 759_346_190
    assert model.attention_projection_params(cfg) == 28_409_856
    assert model.core_flops(cfg) == 4096 ** 2 * 32 * 320
    assert model.attention_applications(cfg) == 6
    assert model.expert_layers(cfg) == 5 and model.sub_blocks(cfg) == 12
    assert model.expected_pairs(cfg) == 2048
    assert model.expert_product_flops(cfg, 2048) == 6 * 2048 * 3584 * 1024
    assert model.expert_product_bytes(cfg, 2048) == 2 * (
        3 * 8 * 3584 * 1024 + 2 * 2048 * 3584)
    assert model.stream_bytes(cfg) == 2 * 4096 * 4 * 3584
    token = 2 * 28_409_856 + 4096 * 32 * 320 + 4 * 4 * 3584 * 24
    dense = token + 2 * 3 * 3584 * 9216
    expert = token + 2 * (3 * 3584 * 1024 + 3584 * 64) \
        + 6 * 0.5 * 3584 * 1024
    want = 4096 * (dense + 5 * expert + 2 * 2 * 3584 * 3584
                   + 2 * 2 * 3584 * 16384)
    assert model.flops_per_sample(cfg) == pytest.approx(want, rel=1e-12)
    assert round(want / 1e12, 2) == 5.13
    assert model.n_matmuls(cfg) == 10 + 4 * 11 + 12 + 2 == 68
    shapes = {n: tuple(s) for n, s, _k, _f in model.param_spec(cfg)}
    kinds = {n: k for n, _s, k, _f in model.param_spec(cfg)}
    assert shapes["l1_moe/Eg"] == (8, 3584, 1024)
    assert shapes["l1_moe/Wr"] == (3584, 64)
    assert shapes["l0_attn/Wqb"] == (768, 32 * 192)
    assert shapes["l0_attn/Wkva"] == (3584, 512 + 64)
    assert shapes["l0_attn/Wkvb"] == (512, 32 * 256)
    assert shapes["l0_attn/Wo"] == (4096, 3584)
    assert shapes["l0_hw1/phi_res"] == (14336, 16)
    assert shapes["mtp_join/W"] == (7168, 3584)
    assert shapes["embed/W"] == (16384, 3584) == shapes["lm/W"][::-1]
    assert "l0_moe/Wr" not in shapes and "l0_mlp/Wg" in shapes
    assert kinds["l0_hw1/b_res"] == "near_identity"
    assert kinds["l0_hr1/alpha_pre"] == kinds["l0_hw1/alpha_res"] == "alpha"
    assert kinds["l0_n1/gain"] == kinds["fnorm/gain"] == "gamma"
    states = model.state_spec(cfg)
    assert [n for n, *_ in states] == [f"l{i}_moe/select_bias"
                                       for i in range(1, 5)] \
        + ["mtp_moe/select_bias"]
    assert all(tuple(s) == (64,) for _n, s, _k, _f in states)
    assert cell["traffic"]["batch"] == 1 \
        and cell["traffic"]["seq_len"] == cfg["seq_len"] == 4096
    assert cell["traffic"]["driver"] == "fit_tokens_lean"
    assert set(cell["limits"]) >= {"loss1_gap", "graddir_mid_gap",
                                   "graddir_top_gap"}


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


def test_the_reference_follows_the_programs_choice_and_judges_it(
        first_steps):
    """Rounding moves a choice across a line it lies on; a choice far
    below the line is a fault, whatever the gradients then say."""
    cell, result = first_steps
    drv = cell["driver"]
    chosen = result["checked"]["selected"]
    assert len(chosen) == cell["traffic"]["check_steps"] == 2
    assert set(chosen[0]) == {"l1_moe", "mtp_moe"}
    assert chosen[0]["l1_moe"].shape == (32, 4) \
        and chosen[0]["l1_moe"].dtype == np.int32
    assert 0 <= chosen[0]["l1_moe"].min() and chosen[0]["l1_moe"].max() < 16
    assert not np.array_equal(chosen[0]["l1_moe"], chosen[1]["l1_moe"])
    followed = drv.reference_numbers(cell, result["batches"], SEED,
                                     selected=chosen)
    alone = drv.reference_numbers(cell, result["batches"], SEED)
    assert len(followed["routing"]) == 2
    assert followed["routing"][0] == {
        layer: {"flips": 0.0, "pairs": 32 * 4.0, "worst": 0.0}
        for layer in ("l1_moe", "mtp_moe")}
    assert followed["losses"] == alone["losses"]
    # every token sent to the four lowest-scored... to fixed experts 12-15
    wrong = [{k: np.broadcast_to(np.arange(12, 16, dtype=np.int32), a.shape)
              for k, a in step.items()} for step in chosen]
    ref = drv.reference_numbers(cell, result["batches"], SEED,
                                selected=wrong)
    ok, checks = compare.judge(drv.numbers(result["checked"], ref),
                               cell["limits"])
    assert not ok
    assert not checks["route_worst_margin"]["ok"]
    assert value(checks, "route_flip_share") > 0.5


@pytest.fixture(scope="module")
def first_steps(manifest):
    """What set-up keeps of the program's first steps, and the batches."""
    cell = manifest.cell(CELL)
    result = cell["driver"].run(cell, tiny.run_args(CELL, seed=SEED,
                                                    seconds=0.2),
                                time.perf_counter())
    return cell, result


@pytest.mark.parametrize("fault", ["top3", "held_divisor", "no_sinkhorn",
                                   "no_mtp_loss", "no_yarn_scale"])
def test_a_planted_fault_is_not_correct(first_steps, fault):
    """Three experts a token for four, the gates normalised over the held
    experts only, ``H_res`` left unprojected, the module's loss left out,
    YaRN's temperature left out of the scores: each fails a limit of the
    cell's file."""
    cell, result = first_steps
    ref = cell["driver"].reference_numbers(
        cell, result["batches"], SEED, fault=fault,
        selected=result["checked"]["selected"])
    ok, checks = compare.judge(cell["driver"].numbers(result["checked"],
                                                      ref), cell["limits"])
    assert not ok, checks
    failed = {k for k, c in checks.items()
              if isinstance(c, dict) and not c["ok"]}
    assert failed & {"graddir_top_gap", "graddir_mid_gap", "loss1_gap"}


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


def test_a_selection_without_its_bias_is_not_correct(first_steps):
    """Followed, the choice is judged in the reference's selection
    scores: one made with the bias lies far from the line of scores
    without it (the fault is the reference's here, the distance the
    same)."""
    ok, checks, failed = _judged(first_steps, fault="no_select_bias")
    assert not ok, checks
    assert "route_flip_share" in failed \
        and failed <= {"route_flip_share", "route_worst_margin"}


def test_the_bias_in_the_gates_turns_the_experts_gradients(first_steps):
    """The selection bias in the gates: in float32 it moves the loss,
    turns every leaf and scales the routed experts' gradients (sound
    readings: under 1e-5, 1e-3 and 1e-4). On the chip, under bfloat16's
    limits, no held number sees it (PERF.md section 6): there it shows
    in ``grad_routed_gap`` alone, which rounding leaves at 0.003."""
    ok, checks, failed = _judged(first_steps, fault="bias_in_gates")
    assert value(checks, "graddir_mid_gap") > 0.02
    assert value(checks, "loss1_gap") > 1e-4
    assert value(checks, "grad_routed_gap") > 0.01


@pytest.mark.parametrize("factor", [1.5, 1 / 1.5, 0.447])
def test_a_wrong_step_size_is_not_correct(first_steps, factor):
    """A learning rate a half off, either way, and what a first step
    without Adam's bias correction is (0.447 of the step): the change
    norms alone say so. What no first step can show: ``beta1``,
    ``beta2`` (the corrected first step holds neither) and a wrong
    ``eps`` wherever the gradient is far above it."""
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


def test_leaves_at_adams_eps_are_left_out_of_the_change():
    """``step_size_share``: 0 for a sign step, a half where every
    element lies at ``e1 = eps / sqrt(1 - beta2)``, 0 for no gradient;
    ``numbers`` takes such a leaf out of both change norms and counts
    it."""
    drv = Manifest().cell(CELL)["driver"]
    hp = {"eps": 1e-8, "beta2": 0.95, "beta1": 0.9, "lr": 3e-4}
    e1 = 1e-8 / math.sqrt(0.05)
    g = np.float32
    assert drv.step_size_share(np.full(64, 1e-3, g), hp) \
        == pytest.approx(e1 / 1e-3, rel=1e-3)
    assert drv.step_size_share(np.full(64, -e1, g), hp) \
        == pytest.approx(0.5, rel=1e-5)
    assert drv.step_size_share(np.zeros(64, g), hp) == 0.0
    mixed = np.concatenate([np.full(32, 1.0, g), np.full(32, e1, g)])
    assert drv.step_size_share(mixed, hp) \
        == pytest.approx(0.25 * 0.5 / 1.25, rel=1e-4)
    grads = {"firm": np.ones(4, g), "soft": np.full(4, 0.5, g)}
    program = {"losses": [1.0], "first_grads": grads,
               "change_norms": {"firm": 1.0, "soft": 0.5}}
    reference = dict(program, change_norms={"firm": 1.0, "soft": 0.2},
                     size_led={"firm": 4.5e-8, "soft": 0.8}, routing=[])
    nums = drv.numbers(program, reference)
    assert nums["change_gap"] == 0.0 and nums["change_left_out"] == 1.0
    reference["size_led"]["soft"] = 0.0
    nums = drv.numbers(program, reference)
    assert nums["change_gap"] == pytest.approx(0.3 / 0.6) \
        and nums["change_gap_leaf"] == "soft"


def test_the_fp8_control_is_not_correct(first_steps):
    cell, result = first_steps
    ref = cell["driver"].reference_numbers(
        cell, result["batches"], SEED, precision="fp8",
        selected=result["checked"]["selected"])
    ok, checks = compare.judge(cell["driver"].numbers(result["checked"],
                                                      ref), cell["limits"])
    assert not ok, checks


# ---------------------------------------------------------------- the driver
def test_the_driver_makes_weights_a_leaf_at_a_time_from_the_seed(manifest):
    cell = manifest.cell(CELL)
    drv, model, cfg = cell["driver"], cell["model"], cell["cfg"]
    spec = model.param_spec(cfg)
    a, b = drv.make_weights(spec, SEED), drv.make_weights(spec, SEED)
    c = drv.make_weights(spec, SEED + 1)
    assert list(a) == [n for n, *_ in spec]
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["embed/W"], c["embed/W"])
    i = [n for n, *_ in spec].index("l1_moe/Eg")
    assert np.array_equal(drv.make_leaf(spec, SEED, i), a["l1_moe/Eg"])
    # two leaves of one shape are two draws
    assert not np.array_equal(a["l1_moe/Eg"], a["l1_moe/Eu"])
    assert np.all(a["l0_hr1/alpha_pre"] == np.float32(0.01))
    res = np.asarray(a["l0_hw1/b_res"])
    assert np.allclose(np.diag(res), 3.0, atol=0.6) \
        and np.abs(res - np.diag(np.diag(res))).max() < 0.6
    assert float(np.std(a["l1_moe/Eg"])) == pytest.approx(
        math.sqrt(2 / 32), rel=0.1)
    states = drv.make_states(model, cfg, SEED)
    assert set(states) == {"l1_moe/select_bias", "mtp_moe/select_bias"}
    assert states["l1_moe/select_bias"].shape == (16,)
    assert not np.array_equal(states["l1_moe/select_bias"],
                              states["mtp_moe/select_bias"])
    zero = drv.change_norms(a, spec, SEED)
    assert set(zero) == set(a) and max(zero.values()) == 0.0
    with pytest.raises(ValueError, match="unknown init kind"):
        drv.make_leaf([("x", (2,), "he_small", 2)], 1, 0)


def test_the_net_holds_the_seeded_selection_biases(manifest):
    cell = manifest.cell(CELL)
    drv, model, cfg = cell["driver"], cell["model"], cell["cfg"]
    states = drv.make_states(model, cfg, SEED)
    net = model.build(cfg, drv.make_weights(model.param_spec(cfg), SEED),
                      states=states)
    assert np.array_equal(net._states["l1_moe"]["select_bias"],
                          states["l1_moe/select_bias"])
    assert net._states["l1_moe"]["expert_load"].shape == (4,)
    assert net._states["l1_moe"]["selected"].shape == (32, 4)
    assert set(model.read_selected(net)) == {"l1_moe", "mtp_moe"}
    assert model.expert_layers_of(cfg) == ["l1_moe", "mtp_moe"]
    assert net.conf.node_by_name["l1_moe"].obj.n_experts == 16
    assert set(model.read_leaves(net, "params")) \
        == {n for n, *_ in model.param_spec(cfg)}
    bad = dict(drv.make_weights(model.param_spec(cfg), SEED))
    bad["l0_attn/Wo"] = bad["l0_attn/Wo"][:, :3]
    with pytest.raises(ValueError, match="the zoo's Xing4 wants"):
        model.build(cfg, bad, states=states)


def test_a_tree_without_the_expert_layer_fails_before_any_weights(
        manifest, monkeypatch):
    """What the parent commit does with the new cell: the driver's first
    statement imports the sparse-expert layer."""
    from deeplearning4j_tpu.nn import layers
    cell = manifest.cell(CELL)
    monkeypatch.delattr(layers, "SparseExpertsLayer")
    made = []
    monkeypatch.setattr(cell["driver"], "make_weights",
                        lambda *a, **k: made.append(1))
    with pytest.raises(ImportError):
        cell["driver"].run(cell, tiny.run_args(CELL), time.perf_counter())
    assert not made


def test_the_driver_feeds_fit_tokens_traffic(manifest):
    cell = manifest.cell(CELL)
    drv = cell["driver"]
    a = drv.make_batches(cell["cfg"], cell["traffic"], 2 ** 31 + 5)
    assert len(a) == cell["traffic"]["pool"]
    x, y = a[0]
    assert x.dtype == y.dtype == "int32" and x.shape == y.shape == (1, 32)
    assert (x[:, 1:] == y[:, :-1]).all()
    assert 0 <= x.min() and x.max() < cell["cfg"]["vocab_size"] == 64
    from chipbench.drivers import fit_tokens
    assert drv.make_batches is fit_tokens.make_batches
    assert drv.fit_call is fit_tokens.fit_call


# ------------------------------------------------- the readers, by hand
def made_up(marks=True):
    """Two steps of 100 ms; a step's program runs [10, 90] ms into its
    slot: a dense MLP's forward matmul 20 ms, the attention core 8 ms
    forward + 6 rematerialised + 10 backward, a hyper-connection write 5
    forward + 7 backward, a router 4 ms, the grouped products 3 ms forward
    + 6 backward (one of them in the multi-token-prediction module), the
    module's joining projection 2 ms, its share of the head 6 ms, Adam
    3 ms."""
    e = lambda phase, layer, part=None, remat=False, ut=None: [  # noqa: E731
        phase, layer, None, False, ut, part, remat]
    rows = (("fusion.1", 20, e("forward", "dl4j_L9_l0_mlp")),
            ("fusion.2", 8, e("forward", "dl4j_L5_l0_attn", "attn_core")),
            ("fusion.3", 6, e("backward", "dl4j_L5_l0_attn", "attn_core",
                              True)),
            ("fusion.4", 10, e("backward", "dl4j_L5_l0_attn", "attn_core")),
            ("fusion.5", 5, e("forward", "dl4j_L6_l0_hw1", "mhc")),
            ("fusion.6", 7, e("backward", "dl4j_L6_l0_hw1", "mhc")),
            ("fusion.7", 4, e("forward", "dl4j_L17_l1_moe", "moe")),
            ("custom-call.1", 3, e("forward", "dl4j_L17_l1_moe",
                                   "moe_experts")),
            ("custom-call.2", 6, e("backward", "dl4j_L60_mtp_moe",
                                   "moe_experts")),
            ("fusion.8", 2, e("forward", "dl4j_L50_mtp_join")),
            ("fusion.9", 6, e("forward", "dl4j_loss", "head_loss", False,
                              2)),
            ("fusion.10", 3, e("updater", "dl4j_updater")))
    ops, modules = [], []
    for k in range(2):
        t = k * 100 * MS
        modules.append(["jit_step(5)", t + 10 * MS, 80 * MS])
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
        cfg=cfg or {"seq_len": 100, "num_experts_per_tok": 4},
        result={"traced": traced, "steps": 20, "window_s": 10.0, "batch": 1,
                "chips": 1})
    ctx.programspans = ps.Joined(red, traced, [], maps)
    return ctx


def read(name, ctx):
    return Manifest().reader(name)(ctx)


GAUGES = {"dl4j_moe_held_pairs": {("l1_moe",): 60.0, ("mtp_moe",): 40.0},
          "dl4j_moe_expert_load": {("l1_moe", "0"): 40.0,
                                   ("l1_moe", "1"): 20.0,
                                   ("mtp_moe", "0"): 20.0,
                                   ("mtp_moe", "1"): 20.0}}


def test_the_readers_on_a_hand_made_trace(monkeypatch):
    red, maps = made_up()
    monkeypatch.setattr(xm, "gauge", GAUGES.get)
    model = types.SimpleNamespace(
        core_flops=lambda cfg: 197e12 * 1e-3,           # 1 ms at the peak
        attention_applications=lambda cfg: 2,
        stream_bytes=lambda cfg: 819e9 * 0.5e-3,        # 0.5 ms at the peak
        sub_blocks=lambda cfg: 2,
        # 60 pairs: FLOPs 0.6 ms, bytes 0.3 ms; 40 pairs: 0.4 and 0.5
        expert_product_flops=lambda cfg, n: 197e12 * 1e-5 * n,
        expert_product_bytes=lambda cfg, n: 819e9 * (
            0.3e-3 if n == 60 else 0.5e-3))
    ctx = ctx_of(red, maps, model)
    assert read("mla_core_device_ms", ctx) == pytest.approx(24.0)
    # 3 x 1 ms x 2 applications required, over 24 ms
    assert read("mla_core_roofline", ctx) == pytest.approx(100 * 6 / 24)
    assert read("mhc_device_ms", ctx) == pytest.approx(12.0)
    # 4 x 0.5 ms x 2 sub-blocks required, over 12 ms
    assert read("mhc_roofline", ctx) == pytest.approx(100 * 4 / 12)
    assert read("moe_device_ms", ctx) == pytest.approx(13.0)
    # 3 x (0.6 + 0.5) ms required, over the 9 ms of the grouped products
    assert read("moe_expert_roofline", ctx) == pytest.approx(100 * 3.3 / 9)
    # the module's own layers: its grouped product and joining projection
    assert read("mtp_device_ms", ctx) == pytest.approx(8.0)
    assert read("moe_held_pair_share", ctx) == pytest.approx(
        100 * 100 / (100 * 4 * 2))
    assert read("moe_load_max_over_mean", ctx) == pytest.approx(40 / 30)
    assert read("step_device_ms", ctx) == pytest.approx(80.0)


def test_a_program_without_the_marks_gives_no_reading(monkeypatch):
    """The parent's map knows none of the parts and its registry none of
    the gauges: every new reader returns nothing and raises nothing."""
    model = types.SimpleNamespace()
    for marks, maps_on in ((False, True), (True, False)):
        red, maps = made_up(marks=marks)
        ctx = ctx_of(red, maps if maps_on else None, model)
        monkeypatch.setattr(xm, "gauge", lambda name: None)
        for name in NEW:
            assert read(name, ctx) is None, name
    # marks of an older program (attention core and heads only): the new
    # parts read nothing, the core reads its time
    red, maps = made_up()
    for entry in maps["jit_step"].values():
        if entry[5] in ("mhc", "moe", "moe_experts"):
            entry[5] = None
        entry[1] = entry[1].replace("_mtp_", "_x_")
    ctx = ctx_of(red, maps, model)
    for name in ("moe_device_ms", "mhc_device_ms", "mtp_device_ms",
                 "mhc_roofline", "moe_expert_roofline", "mla_core_roofline"):
        assert read(name, ctx) is None, name
    assert read("mla_core_device_ms", ctx) == pytest.approx(24.0)
    untraced = ctx_of(red, maps, model)
    untraced.result["traced"] = None
    monkeypatch.setattr(xm, "gauge", GAUGES.get)
    assert read("moe_held_pair_share", untraced) is None
    assert read("moe_load_max_over_mean", untraced) is None


def test_the_gauges_are_read_from_the_programs_registry():
    from deeplearning4j_tpu.train import stepping
    stepping.MOE_HELD_PAIRS.labels("l9_moe").set(7.0)
    assert xm.gauge("dl4j_moe_held_pairs")[("l9_moe",)] == 7.0
    assert xm.gauge("dl4j_no_such_gauge") is None


def test_manifest_lists_the_new_metrics_last_for_the_new_cell_alone():
    data = Manifest().data["per_layer"]
    assert [m["name"] for m in data][-len(NEW):] == NEW
    layers = {"mla_core_device_ms": "kernels", "mla_core_roofline": "kernels",
              "moe_expert_roofline": "kernels", "mhc_roofline": "kernels"}
    for m in data[-len(NEW):]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "img_per_s_per_chip"
        assert m["layer"] == layers.get(m["name"], "step program")
        assert m["source"] == ("program_counter"
                               if m["name"] in ("moe_held_pair_share",
                                                "moe_load_max_over_mean")
                               else "program_span")
        assert m["unit"] == "%" if m["name"].endswith(
            ("_roofline", "_share")) else m["unit"] in ("ms", "x")
    everywhere = {m["name"] for m in data if "workloads" not in m}
    got = {m["name"] for m in Manifest().metrics_for(CELL, "per_layer")}
    assert got == everywhere | set(NEW)
    assert len(Manifest().data["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in Manifest().data["workloads"]) == 1


def test_traced_run_reports_the_new_per_layer_metrics(manifest, monkeypatch):
    """A ``--trace 1`` run of the tiny cell on the CPU, with the hand-made
    trace handed to the reduction in place of the CPU's own (which has no
    device plane) and its map to the join: the gauges are the live
    program's, the marks are the hand-made ones."""
    red, maps = made_up()
    monkeypatch.setattr(trace, "reduce_xspace",
                        lambda path, step_module=None: red)
    monkeypatch.setattr(ps, "from_program", lambda: ([], maps))
    line = runmod.run_cell(manifest, tiny.run_args(CELL, seed=11, trace=1,
                                                   seconds=0.5),
                           jax.devices()[:1], tiny.v5e_peak())
    got = line["metrics"]
    assert set(NEW) <= set(got), sorted(set(NEW) - set(got))
    assert got["mhc_device_ms"]["value"] == pytest.approx(12.0)
    # 4 of 16 experts held, top-4: a quarter of the pairs, more or less
    assert 10 < got["moe_held_pair_share"]["value"] < 45
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert got["moe_expert_roofline"]["value"] > 0
    assert got["compiles_in_window"]["value"] == 0
    assert line["correct"]
