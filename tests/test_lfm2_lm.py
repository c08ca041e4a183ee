"""Grouped key/value heads in the causal core, q/k norm, the gated short
convolution, sparse experts without a shared expert, a head tied to the
embedding and ``zoo.LFM2`` on the CPU at a tiny size, in float32: the whole
model against the benchmark's plain reference
(``chipbench/configs/lfm2-24b-a2b-l5-bf16/reference.py``) through the
harness's own ``compare``, and each new piece alone against a few lines of
``jax.numpy``."""

import functools
import hashlib
import importlib.util
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, refnn
from chipbench.drivers import fit_tokens_lean as lean
from deeplearning4j_tpu import profiler
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                         ComputationGraphConfiguration)
from deeplearning4j_tpu.ops import attention as attention_ops
from deeplearning4j_tpu.profiler import stepprogram

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG_DIR = os.path.join(ROOT, "chipbench", "configs", "lfm2-24b-a2b-l5-bf16")
TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=48, moe_intermediate_size=16, vocab_size=64,
            seq_len=32, held_experts=[0, 1, 2, 3], num_experts=4)
SEED = 2 ** 31 + 31
HI = jax.lax.Precision.HIGHEST


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "lfm2_" + name, os.path.join(CFG_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MODEL, REFERENCE = _load("model"), _load("reference")


def tiny_cfg(**over):
    cfg = json.load(open(os.path.join(CFG_DIR, "config.json")))
    cfg.update(TINY)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg.update(over)
    return cfg


def tokens(cfg, n_batches=3, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    rows = [rng.integers(0, cfg["vocab_size"], (batch, cfg["seq_len"] + 1),
                         dtype=np.int32) for _ in range(n_batches)]
    return [(r[:, :-1].copy(), r[:, 1:].copy()) for r in rows]


def tiny_net(cfg=None):
    cfg = cfg or tiny_cfg()
    return MODEL.build(cfg, lean.make_weights(MODEL.param_spec(cfg), SEED),
                       states=lean.make_states(MODEL, cfg, SEED)), cfg


def rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


# ------------------------------------------------ program against reference
def test_fit_agrees_with_the_plain_reference_in_float32():
    """Two losses, every leaf's first gradient and the parameters'
    changes over two Adam steps of ``net.fit`` against the plain
    reference's, from the same seeded weights, selection biases and
    batches."""
    net, cfg = tiny_net()
    batches = tokens(cfg)
    cell = {"cfg": cfg, "model": MODEL, "reference": REFERENCE,
            "traffic": {"check_steps": 2}}
    losses, first_m = [], None
    for x, y in batches[:2]:
        net.fit(DataSet(x, y))
        losses.append(float(net._score))
        if first_m is None:
            first_m = jax.device_get(MODEL.read_leaves(net, "m"))
    got = {"losses": losses,
           "first_grads": {k: v / 0.1 for k, v in first_m.items()},
           "change_norms": lean.change_norms(
               MODEL.read_leaves(net, "params"), MODEL.param_spec(cfg),
               SEED)}
    want = lean.reference_numbers(cell, batches, SEED)
    assert set(got["first_grads"]) == set(want["first_grads"]) \
        == {n for n, *_ in MODEL.param_spec(cfg)}
    nums = compare.numbers(got, want)
    assert nums["loss1_gap"] < 1e-6 and nums["loss2_gap"] < 1e-5
    assert nums["grad_gap"] < 1e-3 and nums["graddir_gap"] < 1e-3
    assert nums["change_gap"] < 1e-3
    assert losses[0] == pytest.approx(math.log(64), rel=0.35)


@functools.lru_cache(maxsize=None)
def _reference(fault=None):
    """``(loss, gradients)`` of the reference on one seeded batch."""
    cfg = tiny_cfg()
    batch = tokens(cfg, 1)[0]
    loss = REFERENCE.make_loss(cfg, fault=fault)
    ops = refnn.Ops("f32")
    fn = jax.value_and_grad(lambda p, s, x, y: loss(p, s, x, y, ops)[0])
    return jax.jit(fn)(lean.make_weights(MODEL.param_spec(cfg), SEED),
                       lean.make_states(MODEL, cfg, SEED), *batch)


@pytest.mark.parametrize("fault", [f for f in REFERENCE.FAULTS if f])
def test_a_planted_fault_moves_the_references_loss_or_gradient(fault):
    (sound, g0), (wrong, g1) = _reference(), _reference(fault)
    if fault == "untied_head":
        # the loss is the same; the embedding loses the head's gradient
        assert float(wrong) == pytest.approx(float(sound), rel=1e-6)
        assert compare.norm(g1["embed/W"] - g0["embed/W"]) \
            > 0.1 * compare.norm(g0["embed/W"])
    else:
        assert abs(float(wrong) - float(sound)) > 1e-4 * float(sound)


def test_an_unknown_fault_is_refused():
    with pytest.raises(ValueError, match="unknown planted fault"):
        REFERENCE.make_loss(tiny_cfg(), fault="no_such")


def test_the_reference_imports_nothing_of_the_program():
    text = open(os.path.join(CFG_DIR, "reference.py")).read()
    assert "deeplearning4j_tpu" not in text and "import jax" in text


# ------------------------------------------- the core, grouped key/value heads
def _qkv(B, T, H, Hk, D, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(B + T + H), 4)
    return (jax.random.normal(ks[0], (B, T, H, D), dtype),
            jax.random.normal(ks[1], (B, T, Hk, D), dtype),
            jax.random.normal(ks[2], (B, T, Hk, D), dtype),
            jax.random.normal(ks[3], (B, T, H, D), dtype))


#: (B, T, H, Hk, score bytes allowed, the plan they must give)
_PLANS = {
    "one_block": (2, 48, 8, 2, None, (48, 8, 2)),
    "head_groups": (1, 1024, 8, 2, 4 * 4 * 512 * 1024, (512, 4, 1)),
    "sequence_groups": (3, 1024, 8, 2, 4 * 4 * 512 * 1024, (512, 4, 1)),
    "halved_block": (2, 1024, 8, 2, 4 * 4 * 256 * 1024, (256, 4, 1)),
    "one_reader_group": (1, 1024, 8, 8, 4 * 2 * 512 * 1024, (512, 2, 1)),
}


@pytest.mark.parametrize("case", sorted(_PLANS))
def test_the_grouped_core_equals_the_core_on_repeated_keys_and_values(
        case, monkeypatch):
    """8 query heads over 2 key/value heads against the same core handed
    ``k``, ``v`` repeated four times: the output and all three gradients,
    ``dk`` and ``dv`` summed over a head's four readers; with head
    groups, sequence groups and a halved block."""
    B, T, H, Hk, allowed, plan = _PLANS[case]
    if allowed:
        monkeypatch.setattr(attention_ops, "CAUSAL_SCORE_BYTES", allowed)
    q, k, v, w = _qkv(B, T, H, Hk, 16)
    assert attention_ops._plan_of(q, k) == plan
    G = H // Hk

    def grouped(q, k, v):
        return jnp.sum(attention_ops.causal_attention(q, k, v) * w)

    def repeated(q, k, v):
        return jnp.sum(attention_ops.causal_attention(
            q, jnp.repeat(k, G, 2), jnp.repeat(v, G, 2)) * w)
    got = jax.value_and_grad(grouped, (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(repeated, (0, 1, 2))(q, k, v)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, b in zip(got[1], want[1]):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_the_grouped_core_against_plain_softmax():
    q, k, v, _ = _qkv(1, 24, 4, 2, 8)
    got = attention_ops.causal_attention(q, k, v)
    kk, vv = jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision=HI) * 8 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((24, 24), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv,
                      precision=HI)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def _core_text(B, T, H, Hk, D):
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.float32)
    k = jax.ShapeDtypeStruct((B, T, Hk, D), jnp.float32)

    def f(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(attention_ops.causal_attention(q, k, v)),
            (0, 1, 2))(q, k, v)
    return jax.jit(f).lower(q, k, k).as_text()


#: sha256 of the forward + backward lowering at ``Hk == H``, taken from
#: the tree before grouped heads existed (commit ec027c9) under this
#: installation's jax: with every head its own, the core is that program
_PARENT_TEXT = {(2, 48, 4, 8): "3d0b4c40cd25d0b2",
                (1, 1024, 8, 64): "619583ae5286e95f"}


@pytest.mark.parametrize("shape", sorted(_PARENT_TEXT))
def test_with_every_head_its_own_the_core_lowers_to_the_parents_text(shape):
    B, T, H, D = shape
    text = _core_text(B, T, H, H, D)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _PARENT_TEXT[shape]
    # no tensor carries a readers' axis; the grouped core's do
    five = re.compile(r"tensor<(\d+x){5}")
    assert not five.search(text)
    assert five.search(_core_text(B, T, H, H // 2, D))


@pytest.mark.parametrize("shape, plan", [
    ((1, 4096, 16, None), (512, 4, 1)),     # the looped model's cell
    ((1, 4096, 32, None), (512, 4, 1)),     # the sparse decoder's
    ((4, 8192, 32, 8), (256, 4, 1)),        # this PR's: a key/value head
    ((1, 100, 6, 3), (100, 6, 1)),          # no multiple of the block
    ((2, 2048, 8, 8), (512, 4, 2)),
])
def test_the_plan_follows_from_the_shapes(shape, plan):
    B, T, H, Hk = shape
    q = jax.ShapeDtypeStruct((B, T, H, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, T, Hk or H, 64), jnp.bfloat16)
    assert attention_ops._plan_of(q, k) == plan


def test_the_core_refuses_heads_that_do_not_divide():
    q, k, v, _ = _qkv(1, 8, 6, 4, 4)
    with pytest.raises(ValueError, match="6 query heads do not divide"):
        attention_ops.causal_attention(q, k, v)
    with pytest.raises(ValueError, match="do not divide"):
        attention_ops.causal_attention(q, k[:, :, :3], v[:, :, :2])


def test_the_counter_keeps_its_two_paths():
    q, k, v, _ = _qkv(1, 1024, 4, 2, 8)
    before = {p: attention_ops._CORE_LOWERED.labels(p).value
              for p in ("blocked", "single")}
    attention_ops.causal_attention(q, k, v)
    attention_ops.causal_attention(q[:, :40], k[:, :40], v[:, :40])
    assert attention_ops._CORE_LOWERED.labels("blocked").value \
        == before["blocked"] + 1
    assert attention_ops._CORE_LOWERED.labels("single").value \
        == before["single"] + 1
    assert set(attention_ops._CORE_LOWERED.children()) \
        == {("blocked",), ("single",)}


# ----------------------------------------------------- the attention layer
def _attn(**kw):
    layer = L.CausalSelfAttentionLayer(nHeads=4, headSize=8, ropeTheta=1e6,
                                       weightInit="xavier", **kw)
    layer.infer_nin(InputType.recurrent(32, 16))
    return layer


def test_the_attention_layers_defaults_are_what_they_were():
    layer = _attn()
    assert list(layer.param_shapes()) == ["Wq", "Wk", "Wv", "Wo"]
    assert layer.param_shapes()["Wk"] == (32, 32)
    assert layer.fp32_leaves == () and not layer.qk_norm
    assert layer.n_kv_heads is None
    p, st = layer.initialize(jax.random.PRNGKey(0))
    assert list(p) == ["Wq", "Wk", "Wv", "Wo"] and st == {}
    # a configuration saved before the new keys existed loads and runs
    old = {k: v for k, v in layer.to_config().items()
           if k not in ("n_kv_heads", "qk_norm", "qk_norm_eps")}
    assert set(old) == set(layer.to_config())
    again = L.layer_from_config(old)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    np.testing.assert_array_equal(
        again.apply(p, {}, x, True, None)[0],
        layer.apply(p, {}, x, True, None)[0])


def test_grouped_heads_and_qk_norm_against_a_few_lines_of_jnp():
    layer = _attn(nKVHeads=2, qkNorm=True, qkNormEps=1e-5)
    assert layer.param_shapes() == {
        "Wq": (32, 32), "Wk": (32, 16), "Wv": (32, 16), "Wo": (32, 32),
        "qn": (8,), "kn": (8,)}
    assert layer.fp32_leaves == ("qn", "kn")
    p, _ = layer.initialize(jax.random.PRNGKey(0))
    assert float(p["qn"][0]) == 1.0
    p["qn"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (8,))
    p["kn"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(6), (8,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    got, _ = layer.apply(p, {}, x, True, None)
    q = rms((x @ p["Wq"]).reshape(2, 16, 4, 8), p["qn"], 1e-5)
    k = rms((x @ p["Wk"]).reshape(2, 16, 2, 8), p["kn"], 1e-5)
    v = (x @ p["Wv"]).reshape(2, 16, 2, 8)
    q, k = (jnp.stack([REFERENCE.rope(t[b], 1e6) for b in range(2)])
            for t in (q, k))
    k, v = jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * 8 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision=HI)
    np.testing.assert_allclose(got, o.reshape(2, 16, 32) @ p["Wo"],
                               rtol=2e-4, atol=2e-5)
    # json keeps the new keys
    again = L.layer_from_config(json.loads(json.dumps(layer.to_config())))
    assert again.n_kv_heads == 2 and again.qk_norm \
        and again.qk_norm_eps == 1e-5


def test_key_value_heads_have_to_divide_the_query_heads():
    with pytest.raises(ValueError, match="do not divide over nKVHeads=3"):
        L.CausalSelfAttentionLayer(nHeads=4, nKVHeads=3)
    with pytest.raises(TypeError, match="did you mean 'nKVHeads'"):
        L.CausalSelfAttentionLayer(nHeads=4, nKVHead=2)


# ------------------------------------------------- the gated short convolution
def _conv(k=3, **kw):
    layer = L.GatedShortConvLayer(kernelSize=k, weightInit="xavier", **kw)
    layer.infer_nin(InputType.recurrent(12, 20))
    return layer


def _conv_loop(p, x, k):
    """The layer token by token, in Python."""
    x, C = np.asarray(x, np.float64), x.shape[-1]
    win, wc, wout = (np.asarray(p[n], np.float64)
                     for n in ("Win", "Wc", "Wout"))
    out = np.zeros(x.shape[:2] + (wout.shape[1],))
    for b in range(x.shape[0]):
        bgz = x[b] @ win
        pz = bgz[:, :C] * bgz[:, 2 * C:]
        for t in range(x.shape[1]):
            c = np.zeros(C)
            for j in range(k):
                if t - (k - 1) + j >= 0:
                    c += wc[j] * pz[t - (k - 1) + j]
            out[b, t] = (bgz[t, C:2 * C] * c) @ wout
    return out


@pytest.mark.parametrize("k", [1, 2, 3, 4])
def test_the_short_convolution_equals_a_loop_over_tokens(k):
    layer = _conv(k)
    assert layer.param_shapes() == {"Win": (12, 36), "Wc": (k, 12),
                                    "Wout": (12, 12)}
    p, st = layer.initialize(jax.random.PRNGKey(k))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 20, 12))
    got, _ = layer.apply(p, st, x, True, None)
    np.testing.assert_allclose(got, _conv_loop(p, x, k), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("t", [0, 7, 19])
def test_the_short_convolution_is_causal_and_keeps_sequences_apart(t):
    layer = _conv(3)
    p, st = layer.initialize(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 20, 12))
    base, _ = layer.apply(p, st, x, True, None)
    moved, _ = layer.apply(p, st, x.at[1, t].add(1.0), True, None)
    diff = np.abs(np.asarray(moved - base)).sum(-1)
    # nothing before t, nothing beyond the kernel's reach, no other row
    assert diff[1, :t].max(initial=0.0) == 0.0
    assert diff[1, t] > 0 and diff[1, t + 3:].max(initial=0.0) == 0.0
    assert diff[0].max() == 0.0 and diff[2].max() == 0.0


def test_the_short_convolutions_gradient_through_the_taps():
    layer = _conv(3)
    p, st = layer.initialize(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 6, 12))

    def plain(p, x):
        bgz = x[0] @ p["Win"]
        pz = jnp.pad(bgz[:, :12] * bgz[:, 24:], ((2, 0), (0, 0)))
        c = sum(p["Wc"][j] * pz[j:j + 6] for j in range(3))
        return jnp.sum(jnp.square((bgz[:, 12:24] * c) @ p["Wout"]))
    got = jax.grad(lambda p, x: jnp.sum(jnp.square(
        layer.apply(p, st, x, True, None)[0])), (0, 1))(p, x)
    want = jax.grad(plain, (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5)


def test_bfloat16_streams_sum_their_taps_in_float32():
    layer = _conv(3)
    p, st = layer.initialize(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 20, 12))
    cast, xb = L.policy_cast(layer, p, x, jnp.bfloat16)
    assert all(a.dtype == jnp.bfloat16 for a in cast.values())
    got, _ = layer.apply(cast, st, xb, True, None)
    assert got.dtype == jnp.bfloat16
    text = jax.jit(lambda p, x: layer.apply(p, st, x, True, None)[0]) \
        .lower(cast, xb).as_text()
    assert "convolution" not in text            # shifted multiply-adds
    assert re.search(r"tensor<2x20x12xf32>", text)      # the taps' sum
    assert not re.search(r"tensor<2x20x3x12x", text)    # no window tensor
    np.testing.assert_allclose(got.astype(jnp.float32),
                               _conv_loop(p, x, 3), rtol=0.1, atol=0.05)


def test_the_short_convolution_counts_its_lowerings_and_refuses_typos():
    layer = _conv(3)
    p, st = layer.initialize(jax.random.PRNGKey(1))
    before = L._SHORTCONV_LOWERED.labels("shifted_taps").value
    jax.jit(lambda x: layer.apply(p, st, x, True, None)[0]).lower(
        jnp.zeros((1, 20, 12)))
    assert L._SHORTCONV_LOWERED.labels("shifted_taps").value == before + 1
    assert profiler.get_registry().get("dl4j_shortconv_lowered_total") \
        is L._SHORTCONV_LOWERED
    with pytest.raises(ValueError, match="feature-last"):
        layer.apply(p, st, jnp.zeros((2, 12, 20)), True, None)
    with pytest.raises(TypeError, match="did you mean 'kernelSize'"):
        L.GatedShortConvLayer(kernelSze=3)
    with pytest.raises(ValueError, match="kernelSize must be at least 1"):
        L.GatedShortConvLayer(kernelSize=0)


# ------------------------------------------ experts without a shared expert
def _moe(held=None, n=16, k=4, **kw):
    layer = L.SparseExpertsLayer(nExperts=n, nExpertsPerTok=k, nHidden=8,
                                 heldExperts=held, weightInit="xavier", **kw)
    layer.infer_nin(InputType.recurrent(12, 24))
    return layer


def _plain_moe(p, bias, x, held, k=4):
    """Every held expert on every token, weighted by its gate; no shared
    expert."""
    s = jax.nn.sigmoid(jnp.dot(x, p["Wr"], precision=HI))
    _, sel = jax.lax.top_k(s + bias, k)
    picked = jnp.take_along_axis(s, sel, -1)
    gate = picked / jnp.sum(picked, -1, keepdims=True)
    out = 0.0
    for row, e in enumerate(held):
        w = jnp.sum(jnp.where(sel == e, gate, 0.0), -1)
        h = jax.nn.silu(x @ p["Eg"][row]) * (x @ p["Eu"][row])
        out = out + (h @ p["Ed"][row]) * w[:, None]
    return out


def test_one_shared_expert_is_todays_layer_key_for_key():
    """``nSharedExperts=1`` is the default: the parameter keys in their
    order, and an output that is, bit for bit, the layer without a shared
    expert plus the shared expert's product."""
    with_shared, without = _moe(nSharedExperts=1), _moe(nSharedExperts=0)
    assert list(with_shared.param_shapes()) \
        == list(_moe().param_shapes()) \
        == ["Wr", "Eg", "Eu", "Ed", "Sg", "Su", "Sd"]
    assert list(without.param_shapes()) == ["Wr", "Eg", "Eu", "Ed"]
    p, st = with_shared.initialize(jax.random.PRNGKey(0))
    p0, st0 = without.initialize(jax.random.PRNGKey(0))
    assert list(p0) == ["Wr", "Eg", "Eu", "Ed"] and set(st0) == set(st)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 12))
    full, _ = with_shared.apply(p, st, x, True, None)
    routed, _ = without.apply({k: p[k] for k in p0}, st, x, True, None)
    xf = x.reshape(-1, 12)
    shared = (jax.nn.silu(xf @ p["Sg"]) * (xf @ p["Su"])) @ p["Sd"]
    np.testing.assert_array_equal(
        full, (routed.reshape(-1, 12) + shared).reshape(full.shape))
    # a configuration saved before the key existed has its shared expert
    old = {k: v for k, v in with_shared.to_config().items()
           if k != "n_shared"}
    assert L.layer_from_config(old).n_shared == 1
    assert "n_shared" in without.to_config()
    with pytest.raises(ValueError, match="one shared expert or none"):
        _moe(nSharedExperts=2)


def test_without_a_shared_expert_against_every_expert_on_every_token():
    layer = _moe(nSharedExperts=0)
    p, st = layer.initialize(jax.random.PRNGKey(3))
    st["select_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 12))
    got, new = layer.apply(p, st, x, True, None)
    want = _plain_moe(p, st["select_bias"], x.reshape(-1, 12),
                      list(range(16)))
    np.testing.assert_allclose(got.reshape(-1, 12), want, rtol=2e-4,
                               atol=2e-5)
    assert float(jnp.sum(new["expert_load"])) == 2 * 24 * 4


@pytest.mark.parametrize("lift", [0.0, 10.0])
def test_the_routed_sum_alone_with_and_without_a_second_pass(lift):
    """2 of 16 held: a pass takes 48 of the 192 pairs' rows. With the
    bias lifted every token takes both held experts, 96 pairs in two
    passes; the layer without a shared expert and its gradient are the
    plain form's either way."""
    layer = _moe([2, 11], nSharedExperts=0)
    p, st = layer.initialize(jax.random.PRNGKey(5))
    st["select_bias"] = jnp.zeros(16).at[jnp.asarray([2, 11])].set(lift)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 12))

    def mine(p, x):
        out, new = layer.apply(p, st, x, True, None)
        return jnp.sum(jnp.square(out)), new

    def plain(p, x):
        return jnp.sum(jnp.square(_plain_moe(
            p, st["select_bias"], x.reshape(-1, 12), [2, 11])))
    (got, new), dgot = jax.value_and_grad(mine, argnums=(0, 1),
                                          has_aux=True)(p, x)
    want, dwant = jax.value_and_grad(plain, argnums=(0, 1))(p, x)
    np.testing.assert_allclose(got, want, rtol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(dgot),
                    jax.tree_util.tree_leaves(dwant)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5)
    pairs = float(jnp.sum(new["expert_load"]))
    assert pairs == 96 if lift else pairs <= 48
    assert np.array_equal(new["pass_steps"], np.eye(4)[1 if lift else 0])


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test: 16 experts in 4 shares of 4, every share
    routing over all 16; the shares' outputs add up to the uncut plain
    layer's: there is no shared expert to count once."""
    whole = _moe(nSharedExperts=0)
    p, st = whole.initialize(jax.random.PRNGKey(5))
    st["select_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 12))
    uncut = _plain_moe(p, st["select_bias"], x.reshape(-1, 12),
                       list(range(16)))
    total, pairs = 0.0, 0.0
    for c in range(4):
        held = list(range(4 * c, 4 * c + 4))
        part = _moe(held, nSharedExperts=0)
        assert part.param_shapes() == {"Wr": (12, 16), "Eg": (4, 12, 8),
                                       "Eu": (4, 12, 8), "Ed": (4, 8, 12)}
        pp = dict(p, **{k: p[k][jnp.asarray(held)]
                        for k in ("Eg", "Eu", "Ed")})
        out, new = part.apply(pp, {**st, "expert_load": jnp.zeros(4)}, x,
                              True, None)
        total = total + out
        pairs += float(jnp.sum(new["expert_load"]))
    np.testing.assert_allclose(total.reshape(-1, 12), uncut, rtol=2e-4,
                               atol=2e-5)
    assert pairs == 2 * 24 * 4


def test_the_expert_layers_flops_leave_the_shared_expert_out():
    it = InputType.recurrent(12, 24)
    per_expert = 3 * 12 * 8
    assert _moe([0, 1, 2, 3], nSharedExperts=0).forward_flops(it) \
        == 24 * 2 * (12 * 16 + per_expert * (4 * 4 / 16))
    assert _moe([0, 1, 2, 3]).forward_flops(it) \
        == 24 * 2 * (12 * 16 + per_expert * (1 + 4 * 4 / 16))


# ------------------------------------------------------------ the tied head
def test_the_head_is_the_embeddings_table_and_sums_both_gradients():
    net, cfg = tiny_net()
    assert net.conf.param_owner["lm"] == "embed"
    assert net._params["lm"] == {}
    head = net.conf.node_by_name["lm"].obj
    assert head.param_shapes() == {} and head.tied_with == "embed"
    assert head.initialize(jax.random.PRNGKey(0))[0] == {}
    x, y = tokens(cfg, 1)[0]
    logits = net.output(x)
    assert logits.shape == (2, 64, 32)      # public layout [N, V, T]
    ins, labels = {"tokens": jnp.asarray(x)}, [jnp.asarray(y)]

    def loss(params):
        return net._loss_and_reg(params, net._states, ins, labels, True,
                                 jax.random.PRNGKey(0), None, None)[0]
    g = jax.grad(loss)(net._params)
    # rows of ids the batch never drew still get the head's gradient
    unseen = sorted(set(range(64)) - set(np.asarray(x).ravel().tolist()))
    assert unseen and float(jnp.abs(g["embed"]["W"][unseen[0]]).sum()) > 0
    assert "lm" not in g or g["lm"] == {}
    # an untied head of the same class keeps a table of its own
    own = L.MTPLMOutputLayer(nOut=64)
    own.infer_nin(InputType.recurrent(32, 32))
    assert own.param_shapes() == {"W": (32, 64)}


def test_a_tied_head_that_names_no_node_is_refused_when_the_graph_is_built():
    g = NeuralNetConfiguration.Builder().graphBuilder()
    g.addInputs("tokens")
    g.setInputTypes(InputType.recurrent(64, 16))
    g.addLayer("embed", L.EmbeddingSequenceLayer(nOut=8), "tokens")
    g.addLayer("lm", L.MTPLMOutputLayer(nOut=64, tiedWith="embd"), "embed")
    g.setOutputs("lm")
    with pytest.raises(ValueError, match="lm: tiedWith='embd' names no node"):
        g.build()


@pytest.mark.parametrize("blocks", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_loss_under_a_table_is_the_loss_under_its_transpose(
        blocks, dtype, monkeypatch):
    """``blocked_cross_entropy(table=True)`` reads an embedding's table
    [nOut, nIn] as it lies: values and both gradients are those of the
    head ``table^T``, in one vocabulary block and in four, and no
    transposed copy of the table or of its gradient is made."""
    monkeypatch.setattr(L, "HEAD_LOGIT_BYTES", 4 * 2 * 16 * 64 // blocks)
    ks = jax.random.split(jax.random.PRNGKey(blocks), 3)
    h = jax.random.normal(ks[0], (2, 16, 8), dtype)
    table = jax.random.normal(ks[1], (64, 8))
    y = jax.random.randint(ks[2], (2, 16), 0, 64)

    def tied(h, table):
        return jnp.sum(L.blocked_cross_entropy((h,), table, y, table=True))

    def plain(h, table):
        return jnp.sum(L.blocked_cross_entropy((h,), table.T, y))
    got = jax.value_and_grad(tied, (0, 1))(h, table)
    if dtype == jnp.float32 or blocks > 1:
        # (XLA's CPU backend cannot run the one-block plain form in
        # bfloat16: it folds the gradient's transpose into the product)
        want = jax.value_and_grad(plain, (0, 1))(h, table)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        assert float(got[0]) == pytest.approx(float(want[0]), rel=tol)
        for a, b in zip(got[1], want[1]):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(a.astype(jnp.float32),
                                       b.astype(jnp.float32), rtol=tol,
                                       atol=tol)
    text = jax.jit(jax.grad(tied, (0, 1))).lower(h, table).as_text()
    assert "stablehlo.transpose" not in text


# ---------------------------------------------------------------- the model
def test_the_layers_differ_in_kind_by_the_pattern():
    net, cfg = tiny_net()
    kinds = {n.name: type(n.obj).__name__ for n in net.conf.topo
             if n.kind == "layer"}
    assert [kinds[f"l{i}_{m}"] for i, m in enumerate(
        ["conv", "attn", "conv", "conv", "conv"])] \
        == ["GatedShortConvLayer", "CausalSelfAttentionLayer"] \
        + ["GatedShortConvLayer"] * 3
    assert kinds["l0_mlp"] == "GatedMLP"
    assert all(kinds[f"l{i}_moe"] == "SparseExpertsLayer"
               for i in range(1, 5))
    attn = net.conf.node_by_name["l1_attn"].obj
    assert (attn.n_heads, attn.n_kv_heads, attn.head_size) == (4, 2, 8)
    assert attn.qk_norm and attn.qk_norm_eps == 1e-5 \
        and attn.rope_theta == 1e6
    moe = net.conf.node_by_name["l1_moe"].obj
    assert moe.n_shared == 0 and moe.n_experts == 16 \
        and moe.held == [0, 1, 2, 3] and moe.scaling == 1.0
    assert net.conf.node_by_name["l0_conv"].obj.kernel_size == 3
    assert MODEL.layers_of(cfg) == REFERENCE.layers_of(cfg)


def test_the_published_model_is_forty_layers_in_the_published_order():
    conf = zoo.LFM2().conf_builder().conf
    names = [n.name for n in conf.topo]
    attn = [i for i in range(40) if f"l{i}_attn" in names]
    assert attn == list(range(2, 40, 4)) and "l39_conv" in names
    assert [i for i in range(40) if f"l{i}_mlp" in names] == [0, 1]
    assert conf.node_by_name["l2_attn"].obj.param_shapes()["Wk"] \
        == (2048, 512)
    assert conf.node_by_name["l5_moe"].obj.param_shapes()["Eg"] \
        == (64, 2048, 1536)
    assert conf.node_by_name["embed"].obj.param_shapes()["W"] \
        == (65536, 2048)
    cut = zoo.LFM2.for_cost_gate().conf_builder().conf
    assert sum(math.prod(s) for n in cut.topo if n.kind == "layer"
               for s in n.obj.param_shapes().values()) == 469_284_992
    with pytest.raises(ValueError, match="'conv' or 'full_attention'"):
        zoo.LFM2(layer_types=["conv", "sliding_attention"])


def test_a_wrong_shaped_weight_is_refused():
    cfg = tiny_cfg()
    bad = dict(lean.make_weights(MODEL.param_spec(cfg), SEED))
    bad["l1_attn/Wk"] = bad["l1_attn/Wk"][:, :3]
    with pytest.raises(ValueError, match="the zoo's LFM2 wants"):
        MODEL.build(cfg, bad, states=lean.make_states(MODEL, cfg, SEED))
    with pytest.raises(ValueError, match="the program normalises the gates"):
        MODEL.build(dict(cfg, norm_topk_prob=False), bad)


def test_the_stack_is_cut_a_sub_block_at_a_time():
    net, _ = tiny_net()
    got = [[n.name for n in s] for s in net.conf.stack_stretches]
    assert got[:5] == [["embed"], ["l0_n1", "l0_conv", "l0_add1"],
                       ["l0_n2", "l0_mlp", "l0_add2"],
                       ["l1_n1", "l1_attn", "l1_add1"],
                       ["l1_n2", "l1_moe", "l1_add2"]]
    assert got[-2:] == [["fnorm"], ["lm"]]
    assert sum(len(s) for s in got) == len(net.conf.topo)


def test_rematerialised_and_plain_step_give_the_same_values():
    net, cfg = tiny_net()
    x, y = tokens(cfg, 1)[0]
    ins, labels = {"tokens": jnp.asarray(x)}, [jnp.asarray(y)]
    key = jax.random.PRNGKey(3)

    def loss(params, remat):
        return net._loss_and_reg(params, net._states, ins, labels, True, key,
                                 None, None, remat=remat)
    plain = jax.jit(jax.value_and_grad(lambda p: loss(p, False),
                                       has_aux=True))(net._params)
    remat = jax.jit(jax.value_and_grad(lambda p: loss(p, True),
                                       has_aux=True))(net._params)
    assert float(plain[0][0]) == pytest.approx(float(remat[0][0]), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(plain[1]),
                    jax.tree_util.tree_leaves(remat[1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=3e-6)


def test_json_round_trip_and_save_and_load_keep_the_new_layers(tmp_path):
    net, cfg = tiny_net()
    text = net.conf.to_json()
    conf = ComputationGraphConfiguration.from_json(text)
    assert conf.to_json() == text and conf.remat_stack
    assert isinstance(conf.node_by_name["l0_conv"].obj,
                      L.GatedShortConvLayer)
    assert conf.node_by_name["l1_attn"].obj.n_kv_heads == 2
    assert conf.node_by_name["l1_moe"].obj.n_shared == 0
    assert conf.node_by_name["lm"].obj.tied_with == "embed"
    batches = tokens(cfg, 2)
    net.fit(DataSet(*batches[0]))
    path = str(tmp_path / "lfm2.zip")
    net.save(path)
    loaded = ComputationGraph.load(path)
    assert loaded.numParams() == net.numParams() == MODEL.n_params(cfg)
    net.fit(DataSet(*batches[1]))
    loaded.fit(DataSet(*batches[1]))
    assert float(loaded.score()) == pytest.approx(float(net.score()),
                                                  rel=1e-6)


def test_the_zoo_model_inits_and_trains_with_its_own_weights():
    net = zoo.LFM2(layer_types=["conv", "full_attention"],
                   num_dense_layers=1, hidden_size=16, num_attention_heads=2,
                   num_key_value_heads=1, intermediate_size=24,
                   moe_intermediate_size=8, num_experts=8,
                   held_experts=[0, 1], num_experts_per_tok=2, vocab_size=32,
                   seq_len=8).init()
    assert net._params["lm"] == {}
    rows = np.random.default_rng(0).integers(0, 32, (2, 9)).astype(np.int32)
    first = None
    for _ in range(5):
        net.fit(DataSet(rows[:, :-1], rows[:, 1:]))
        first = first if first is not None else float(net._score)
    assert float(net._score) < first


def test_the_model_trains_under_the_bf16_policy():
    net, cfg = tiny_net()
    net.setPrecisionPolicy("bf16")
    batches = tokens(cfg, 2)
    net.fit(DataSet(*batches[0]))
    first = float(net._score)
    net.fit(DataSet(*batches[0]))
    assert math.isfinite(first) and float(net._score) < first
    assert net._params["l1_attn"]["qn"].dtype == jnp.float32
    attn = net.conf.node_by_name["l1_attn"].obj
    cast, _ = L.policy_cast(attn, net._params["l1_attn"],
                            jnp.zeros((1, 32, 32)), jnp.bfloat16)
    assert cast["qn"].dtype == cast["kn"].dtype == jnp.float32
    assert cast["Wk"].dtype == jnp.bfloat16


# ------------------------------------------------------------ the instruments
def test_the_step_program_carries_the_new_part_and_the_gauges_read():
    from deeplearning4j_tpu.train import stepping
    net, cfg = tiny_net()
    profiler.set_profiling_mode("basic")
    try:
        stepprogram.clear()
        lowered = L._SHORTCONV_LOWERED.labels("shifted_taps").value
        net.fit(DataSet(*tokens(cfg, 1)[0]))
        # once a layer a traced call: forward and the rematerialised
        # forward of four mixers
        assert L._SHORTCONV_LOWERED.labels("shifted_taps").value - lowered \
            in (4, 8)
        pairs = {k[0]: c.value for k, c in
                 stepping.MOE_HELD_PAIRS.children().items()}
        assert set(pairs) >= set(MODEL.expert_layers_of(cfg))
        assert 0 < pairs["l4_moe"] <= 2 * 32 * 4
        # 4 of 16 held: two passes at most, and the one step this net has
        # run stands in one of the two slots of every expert layer
        for layer in MODEL.expert_layers_of(cfg):
            ran = [c.value for k, c in
                   stepping.MOE_PASS_STEPS.children().items()
                   if k[0] == layer]
            assert len(ran) == 2 and sorted(ran) == [0.0, 1.0], layer
        maps = stepprogram.maps()
    finally:
        profiler.set_profiling_mode(None)
        stepprogram.clear()
    entries = [e for m in maps.values() for e in m.values()]
    assert {"shortconv", "attn_core", "head_loss", "moe",
            "moe_experts"} <= {e.part for e in entries}
    conv = [e for e in entries if e.part == "shortconv"]
    assert all(e.layer and e.layer.endswith("_conv") for e in conv)
    assert {e.phase for e in conv} >= {"forward", "backward"}
    assert any(e.remat for e in conv)
    core = [e for e in entries if e.part == "attn_core"]
    assert all(e.layer and e.layer.endswith("l1_attn") for e in core)


def test_the_shortconv_scope_is_a_part_of_the_map():
    assert stepprogram.PARTS[stepprogram.SHORTCONV_SCOPE] == "shortconv"
    assert stepprogram.marks(
        "jit(step)/jvp(dl4j_L2_l0_conv)/dl4j_shortconv/dot_general") \
        == (None, "shortconv", False)
    assert stepprogram.marks(
        "transpose(jvp(dl4j_L2_l0_conv))/rematted_computation/"
        "dl4j_shortconv/mul") == (None, "shortconv", True)


def test_the_flop_models_know_the_new_layers():
    """The static model (``analysis``) and the per-layer one
    (``profiler.devicetime``) agree with each other and, the attention
    core's whole square apart (the benchmark counts the causal half it
    requires), with the benchmark's count; the tied head is a product,
    the embedding's table none."""
    from deeplearning4j_tpu.analysis import graphir
    from deeplearning4j_tpu.profiler import devicetime
    net, cfg = tiny_net()
    rows = {name: f for name, _op, f in devicetime.layer_flop_model(net.conf)}
    ir = graphir.from_graph(net.conf, batch_size=1)
    assert ir.total_flops() == sum(rows.values())
    square = MODEL.attention_applications(cfg) * MODEL.core_flops(cfg)
    assert sum(rows.values()) == pytest.approx(
        MODEL.flops_per_sample(cfg) + square, rel=1e-9)
    assert rows["l0_conv"] == 32 * MODEL.shortconv_flops(cfg) \
        == 32 * (2 * 4 * 32 * 32 + 2 * 3 * 32)
    assert rows["l1_attn"] == 2 * 32 * (2 * 32 * 32 + 2 * 32 * 16) \
        + 4 * 32 * 32 * 4 * 8
    assert rows["l1_moe"] == 2 * 32 * (32 * 16 + 3 * 32 * 16 * (4 * 4 / 16))
    assert rows["l0_mlp"] == 2 * 32 * 3 * 32 * 48
    assert rows["lm"] == 2 * 32 * 32 * 64 and rows["embed"] == 0
    parts = MODEL.flops_by_part(cfg)
    assert parts["shortconv"] == 4 * rows["l0_conv"]
    assert parts["attn_core"] == square
