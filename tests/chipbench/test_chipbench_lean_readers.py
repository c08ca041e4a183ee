"""The readers the sparse and hybrid decoders' cells share with the other
cells: the seven readers that were another reader by import under a
cell's own name, folded into ``attn_*`` and ``moe_*`` and read as before
to the last digit; the one pair of names each language model's
``model.py`` gives the attention core's count; and the twelve readers of
the fit loop, the phases, the heads and the rematerialised work that both
cells now list, each on a hand-made trace of a lean step whose answers
are known."""

import types

import pytest

import chipbench_tiny as tiny
from chipbench import loopmarks as lm
from chipbench import programspans as ps
from chipbench import trace, xingmarks as xm
from chipbench.manifest import Manifest

XING4, LFM2 = "xing4-fit-s4096-b1", "lfm2-fit-s8192-b4"
MS = 1_000_000          # ns


# ------------------------------------------------------ a hand-made step
def made_up():
    """Three steps of 100 ms; a step's program runs [2, 98] ms into its
    slot: a dense MLP's forward matmul 20 ms, a mixer 9 forward + 5
    rematerialised + 10 backward, the attention core 8 + 6 + 10, a router
    4, the grouped products 3 forward + 6 backward, the head and loss 6,
    Adam 3, a weight gradient fused with its update 4 and a copy the map
    does not list 2. The host's iterations: ``fit:pull`` 0.1 ms, then
    ``fit:stage`` 1.5 ms, 9 ms to ``fit:listeners(done)``'s end; one
    ``host:gc`` of 3 ms."""
    e = lambda phase, layer, part=None, remat=False, mixed=False: [  # noqa
        phase, layer, None, mixed, None, part, remat]
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
            ("fusion.10", 3, e("updater", "dl4j_updater")),
            ("fusion.11", 4, e("backward", "dl4j_L5_l0_mlp", mixed=True)),
            ("copy.1", 2, None))
    ops, modules, spans = [], [], []
    for k in range(3):
        t = k * 100 * MS
        modules.append(["jit_step(5)", t + 2 * MS, 96 * MS])
        cur = t + 2 * MS
        for name, dur, _entry in rows:
            ops.append([f"%{name} = bf16[8,8] fusion kOutput of 2", cur,
                        dur * MS, 10])
            cur += dur * MS
        a = k * 0.1 + 0.001

        def span(name, t0, t1, when=None):
            spans.append({"name": name, "t0": a + t0, "t1": a + t1,
                          "iteration": k + 4, "when": when, "bytes": None})
        span("fit:pull", 0.0, 0.0001)
        span("fit:stage", 0.0001, 0.0016)
        span("fit:prepare", 0.0016, 0.0020)
        span("fit:listeners", 0.0020, 0.0021, when="start")
        span("fit:dispatch", 0.0021, 0.0080)
        span("fit:commit", 0.0080, 0.0089)
        span("fit:listeners", 0.0089, 0.0090, when="done")
    spans.append({"name": "host:gc", "t0": 0.05, "t1": 0.053,
                  "iteration": None, "when": None, "bytes": None})
    raw = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
           "host": {}}
    maps = {"jit_step": {name: entry for name, _d, entry in rows if entry}}
    return trace.reduce_raw(raw), spans, maps


def ctx_of(cell, steps=30):
    """A traced run's context of ``cell`` on the hand-made step: the
    cell's own configuration and ``model.py``, batch and chips."""
    red, spans, maps = made_up()
    loaded = Manifest().cell(cell)
    traced = (0.0, 0.3, 4, 6)
    ctx = types.SimpleNamespace(
        reduced=red, peak=tiny.v5e_peak(), cfg=loaded["cfg"],
        model=loaded["model"],
        result={"traced": traced, "steps": steps, "window_s": 10.0,
                "batch": loaded["traffic"]["batch"], "chips": 1})
    ctx.programspans = ps.Joined(red, traced, spans, maps)
    return ctx


def read(name, ctx):
    return Manifest().reader(name)(ctx)


# ---------------------------------------------------- the folded readers
def _core_ms(ctx):
    return xm.ms_or_none(ctx, lm.in_attention)


def _core_roofline(ctx):
    ms = xm.ms_or_none(ctx, lm.in_attention)
    flops = getattr(ctx.model, "core_flops", None)
    if ms is None or flops is None:
        return None
    required = 3.0 * flops(ctx.cfg) * ctx.model.attention_applications(
        ctx.cfg) * ctx.result["batch"] / ctx.result["chips"]
    return 100.0 * required / ctx.peak["flops_per_s"] / (ms * 1e-3)


def _moe_ms(ctx):
    return xm.ms_or_none(ctx, xm.in_moe)


def _expert_roofline(ctx):
    ms = xm.ms_or_none(ctx, xm.in_experts)
    need = sum(max(ctx.model.expert_product_flops(ctx.cfg, n)
                   / ctx.peak["flops_per_s"],
                   ctx.model.expert_product_bytes(ctx.cfg, n)
                   / ctx.peak["hbm_bytes_per_s"])
               for n in xm.held_pairs(ctx).values())
    return 100.0 * 3.0 * need / ctx.result["chips"] / (ms * 1e-3)


def _load_max_over_mean(ctx):
    layers = {}
    for (layer, _expert), n in xm.gauge("dl4j_moe_expert_load").items():
        layers.setdefault(layer, []).append(n)
    return max(max(ns) * len(ns) / sum(ns) for ns in layers.values())


#: (cell, the reader it lists now, the alias it listed before, the alias's
#: rule as its file had it; a ``routed_*`` file imported ``moe_*``'s read)
FOLDED = [
    (XING4, "attn_device_ms", "mla_core_device_ms", _core_ms),
    (XING4, "attn_roofline", "mla_core_roofline", _core_roofline),
    (LFM2, "attn_device_ms", "gqa_core_device_ms", _core_ms),
    (LFM2, "attn_roofline", "gqa_core_roofline", _core_roofline),
    (LFM2, "moe_device_ms", "routed_device_ms", _moe_ms),
    (LFM2, "moe_expert_roofline", "routed_expert_roofline",
     _expert_roofline),
    (LFM2, "moe_load_max_over_mean", "routed_load_max_over_mean",
     _load_max_over_mean),
]
GAUGES = {"dl4j_moe_held_pairs": {("l1_moe",): 16384.0, ("l2_moe",): 8192.0},
          "dl4j_moe_expert_load": {("l1_moe", "0"): 3000.0,
                                   ("l1_moe", "1"): 1000.0,
                                   ("l2_moe", "0"): 1024.0,
                                   ("l2_moe", "1"): 1024.0}}


@pytest.mark.parametrize("cell,name,alias,rule", FOLDED,
                         ids=[f"{c}-{n}" for c, n, _a, _r in FOLDED])
def test_a_folded_reader_reads_what_its_alias_read(cell, name, alias, rule,
                                                   monkeypatch):
    monkeypatch.setattr(xm, "gauge", GAUGES.get)
    ctx = ctx_of(cell)
    value = read(name, ctx)
    assert value is not None and value == rule(ctx)     # to the last digit
    data = {m["name"]: m for m in Manifest().data["per_layer"]}
    assert cell in data[name]["workloads"] and alias not in data
    with pytest.raises(FileNotFoundError):
        Manifest().reader(alias)


def test_the_folded_readings_by_hand(monkeypatch):
    """The same readings from the hand-made step's own numbers."""
    monkeypatch.setattr(xm, "gauge", GAUGES.get)
    ctx = ctx_of(LFM2)
    assert read("attn_device_ms", ctx) == pytest.approx(24.0)
    # 3 x 2 x 8192^2 x 32 x 64 FLOPs a sequence x 4 sequences over 24 ms
    assert read("attn_roofline", ctx) == pytest.approx(
        100 * 3 * 2 * 8192 ** 2 * 32 * 64 * 4 / 197e12 / 24e-3)
    assert read("moe_device_ms", ctx) == pytest.approx(13.0)
    assert read("moe_load_max_over_mean", ctx) == pytest.approx(1.5)


# ------------------------------------------- the core's count, one pair
#: what ``attn_roofline`` (Ouro) and ``mla_core_roofline`` (the other two)
#: multiplied before the names were one pair: forward FLOPs of the causal
#: half a sequence, over all applications of the core
CORE = [("ouro-fit-s4096-b1", 2 * 4096 ** 2 * 16 * 128 * 24),
        (XING4, 4096 ** 2 * 32 * (128 + 64 + 128) * 6),
        (LFM2, 2 * 8192 ** 2 * 32 * 64 * 1)]


@pytest.mark.parametrize("cell,flops", CORE, ids=[c for c, _f in CORE])
def test_the_core_count_has_one_pair_of_names(cell, flops):
    loaded = Manifest().cell(cell)
    model, cfg = loaded["model"], loaded["cfg"]
    assert model.core_flops(cfg) * model.attention_applications(cfg) \
        == flops
    if hasattr(model, "layer_applications"):        # the looped model
        assert model.core_flops(cfg) == model.attention_flops(cfg)
        assert model.attention_applications(cfg) \
            == model.layer_applications(cfg)


# ------------------------------------- what the two cells list besides
#: the readers both lean cells list beside the CNN or looped cells, with
#: what each reads on the hand-made step (ms a step unless said)
APPENDED = {
    "remat_device_ms": 11.0,             # the mixer's 5 and the core's 6
    "head_loss_device_ms": 6.0,
    "fwd_device_ms": 50.0,               # 20 + 9 + 8 + 4 + 3 + 6
    "bwd_device_ms": 37.0,               # run again 11 with backward 26
    "updater_device_ms": 3.0,
    "mixed_device_ms": 4.0,
    "phase_unsure_share": 100 * 6 / 96,  # mixed 4 and the copy 2, in %
    "host_step_ms": 9.0,
    "stage_ms": 1.5,
    "gc_pause_ms_per_step": 1.0,         # 3 ms over 3 traced steps
    # 40,960 ids over 30 steps; 3 traced steps in 296 ms
    "tok_per_s_per_chip": 40960 / 30 * 3 / 0.296,
    "h2d_mib_per_step": 1.0,             # 30 MiB over 30 steps
}
COUNTERS = {"dl4j_train_tokens_total": 40960.0,
            "dl4j_train_h2d_bytes_total": 30.0 * 2 ** 20}


@pytest.mark.parametrize("cell", [XING4, LFM2])
@pytest.mark.parametrize("name", sorted(APPENDED))
def test_an_appended_reader_reads_the_lean_cell(name, cell, monkeypatch):
    monkeypatch.setattr(ps, "counter_total", COUNTERS.get)
    m = next(m for m in Manifest().data["per_layer"] if m["name"] == name)
    assert cell in m["workloads"]
    assert read(name, ctx_of(cell)) == pytest.approx(APPENDED[name])


def test_the_phases_add_up_to_the_step():
    """fwd + bwd + updater + mixed and the ops the map does not know is
    the step, rematerialised work counted once (in the backward pass)."""
    ctx = ctx_of(LFM2)
    parts = sum(APPENDED[n] for n in ("fwd_device_ms", "bwd_device_ms",
                                      "updater_device_ms",
                                      "mixed_device_ms"))
    assert parts + 2.0 == pytest.approx(read("step_device_ms", ctx))
