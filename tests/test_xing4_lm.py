"""Latent attention, a chip's share of sparse experts, hyper-connection
residual streams, a multi-token-prediction head, rematerialisation of an
unlooped stack and ``zoo.Xing4`` on the CPU at a tiny size, in float32:
the whole model against the benchmark's plain reference
(``chipbench/configs/xing4.0-29b-a4b-l5-bf16/reference.py``) through the
harness's own ``compare``, and each new layer alone against a few lines of
``jax.numpy``."""

import importlib.util
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.test_util import check_grads

from chipbench import compare, refnn
from chipbench.drivers import fit_tokens_lean as lean
from deeplearning4j_tpu import profiler
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                         ComputationGraphConfiguration,
                                         LabelsVertex)
from deeplearning4j_tpu.ops import attention as attention_ops
from deeplearning4j_tpu.profiler import stepprogram
from deeplearning4j_tpu.train import stepping

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG_DIR = os.path.join(ROOT, "chipbench", "configs",
                       "xing4.0-29b-a4b-l5-bf16")
TINY = dict(num_layers=3, hidden_size=32, num_attention_heads=4,
            q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
            moe_intermediate_size=16, vocab_size=64, seq_len=32,
            held_experts=[0, 1, 2, 3], n_routed_experts=4)
SEED = 2 ** 31 + 29
HI = jax.lax.Precision.HIGHEST


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "xing4_" + name, os.path.join(CFG_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MODEL, REFERENCE = _load("model"), _load("reference")


def tiny_cfg(mtp=1, **over):
    cfg = json.load(open(os.path.join(CFG_DIR, "config.json")))
    cfg.update(TINY, num_nextn_predict_layers=mtp)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16)
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


def rms(x, gain, eps=1e-6):
    out = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return out if gain is None else out * gain


# ------------------------------------------------ program against reference
def test_fit_agrees_with_the_plain_reference_in_float32():
    """Two losses, every leaf's first gradient and the parameters'
    changes of ``net.fit`` against the plain reference's update steps,
    from the same seeded weights, selection biases and batches."""
    net, cfg = tiny_net()
    batches = tokens(cfg, batch=1)
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
    assert set(got["first_grads"]) == set(want["first_grads"])
    nums = compare.numbers(got, want)
    assert nums["loss1_gap"] < 1e-6 and nums["loss2_gap"] < 1e-5
    assert nums["grad_gap"] < 1e-3 and nums["graddir_gap"] < 1e-3
    assert nums["change_gap"] < 1e-3
    assert losses[0] == pytest.approx(1.3 * math.log(64), rel=0.35)


def _reference_loss(cfg, batch, fault=None, grad=False):
    loss = REFERENCE.make_loss(cfg, fault=fault)
    ops = refnn.Ops("f32")
    fn = lambda p, s, x, y: loss(p, s, x, y, ops)[0]    # noqa: E731
    fn = jax.value_and_grad(fn) if grad else fn
    return jax.jit(fn)(lean.make_weights(MODEL.param_spec(cfg), SEED),
                       lean.make_states(MODEL, cfg, SEED), *batch)


def test_without_the_module_the_gradients_agree_too():
    """``num_nextn_predict_layers`` 0: one head, no labels in the
    forward pass; loss and every leaf's gradient against the plain
    reference's."""
    net, cfg = tiny_net(tiny_cfg(0, num_layers=2))
    assert "next" not in net.conf.node_by_name
    x, y = tokens(cfg, 1, batch=1)[0]
    want_loss, want = _reference_loss(cfg, (x, y), grad=True)

    def loss(params):
        return net._loss_and_reg(params, net._states,
                                 {"tokens": jnp.asarray(x)},
                                 [jnp.asarray(y)], True,
                                 jax.random.PRNGKey(0), None, None,
                                 remat=True)[0]
    got_loss, got = jax.jit(jax.value_and_grad(loss))(net._params)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    for name, g in want.items():
        node, leaf = name.split("/")
        np.testing.assert_allclose(got[node][leaf], g, rtol=2e-3, atol=2e-6)


@pytest.mark.parametrize("fault", [f for f in REFERENCE.FAULTS if f])
def test_a_planted_fault_moves_the_references_loss(fault):
    cfg = tiny_cfg(num_layers=2, hc_sinkhorn_iters=4)
    batch = tokens(cfg, 1, batch=1)[0]
    want = float(_reference_loss(cfg, batch))
    got = float(_reference_loss(cfg, batch, fault=fault))
    assert abs(got - want) / want > 5e-4


def test_an_unknown_fault_is_refused():
    with pytest.raises(ValueError, match="unknown planted fault"):
        REFERENCE.make_loss(tiny_cfg(), fault="top5")


# ------------------------------------------------------- latent attention
def _mla(rope_scaling=None):
    layer = L.LatentAttentionLayer(
        nHeads=4, qLoraRank=16, kvLoraRank=8, qkNopeHeadDim=8,
        qkRopeHeadDim=4, vHeadDim=8, ropeTheta=10000.0,
        ropeScaling=rope_scaling, weightInit="xavier")
    layer.infer_nin(InputType.recurrent(32, 16))
    return layer


YARN = dict(factor=64, original_max_position_embeddings=16, beta_fast=32,
            beta_slow=1, mscale=1, mscale_all_dim=1)


@pytest.mark.parametrize("scaling", [None, YARN])
def test_latent_attention_against_a_few_lines_of_jnp(scaling):
    layer = _mla(scaling)
    p, _ = layer.initialize(jax.random.PRNGKey(0))
    p["q_gain"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (16,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    got, _ = layer.apply(p, {}, x, True, jax.random.PRNGKey(2))
    inv = jnp.asarray(REFERENCE.yarn_inv_freq(4, 10000.0, scaling)) \
        if scaling else 10000.0 ** (-jnp.arange(2) * 2.0 / 4)
    m = 0.1 * math.log(64) + 1.0 if scaling else 1.0
    for b in range(2):
        u = x[b]
        q = (rms(u @ p["Wqa"], p["q_gain"]) @ p["Wqb"]).reshape(16, 4, 12)
        ckv = u @ p["Wkva"]
        kv = (rms(ckv[:, :8], p["kv_gain"]) @ p["Wkvb"]).reshape(16, 4, 16)
        k_rope = REFERENCE.rope(ckv[:, 8:].reshape(16, 1, 4), inv)
        q = jnp.concatenate([q[..., :8], REFERENCE.rope(q[..., 8:], inv)],
                            -1)
        k = jnp.concatenate([kv[..., :8],
                             jnp.broadcast_to(k_rope, (16, 4, 4))], -1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * m * m / math.sqrt(12)
        s = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., 8:])
        np.testing.assert_allclose(got[b], o.reshape(16, 32) @ p["Wo"],
                                   rtol=2e-4, atol=2e-5)


def test_yarn_frequencies_match_the_published_formula():
    got = attention_ops.yarn_inv_freq(64, 10000.0, 64, 4096, 32, 1)
    want = REFERENCE.yarn_inv_freq(
        64, 10000.0, dict(factor=64, original_max_position_embeddings=4096,
                          beta_fast=32, beta_slow=1))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 10000.0 ** (-np.arange(32) * 2.0 / 64)
    # the fastest pairs turn as without scaling, the slowest 64 times
    # slower, the ramp between is monotone
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[-5:], plain[-5:] / 64, rtol=1e-6)
    assert np.all(np.diff(got / plain) <= 1e-6)
    assert attention_ops.yarn_mscale(64, 1) == pytest.approx(
        0.1 * math.log(64) + 1)
    assert attention_ops.yarn_mscale(1, 1) == 1.0
    assert _mla(YARN).inv_freq()[1] == pytest.approx(
        (0.1 * math.log(64) + 1) ** 2 / math.sqrt(12))
    assert _mla(None).inv_freq() == (None, 12 ** -0.5)


@pytest.mark.parametrize("block", [8, 512])
def test_the_causal_core_with_two_head_sizes_and_a_scale(block, monkeypatch):
    """192/128-style heads (here 12 and 8) and a scale handed in, against
    a plain masked softmax; ``check_grads`` through the hand-written
    pair."""
    monkeypatch.setattr(attention_ops, "CAUSAL_QUERY_BLOCK", block)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 16, 2, 12))
    k = jax.random.normal(ks[1], (1, 16, 2, 12))
    v = jax.random.normal(ks[2], (1, 16, 2, 8))
    scale = 0.41
    got = attention_ops.causal_attention(q, k, v, scale=scale)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    assert got.shape == (1, 16, 2, 8)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    check_grads(lambda q, k, v: attention_ops.causal_attention(
        q, k, v, scale=scale), (q, k, v), order=1, modes=("rev",),
        atol=5e-2, rtol=5e-2)


def test_the_causal_core_without_a_scale_is_the_old_one():
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 2, 8))
    a = attention_ops.causal_attention(q, q, q)
    b = attention_ops.causal_attention(q, q, q, scale=8 ** -0.5)
    np.testing.assert_allclose(a, b, rtol=1e-6)


# ----------------------------------------------------------- sparse experts
def _moe(held=None, n=16, k=4, **kw):
    layer = L.SparseExpertsLayer(nExperts=n, nExpertsPerTok=k, nHidden=8,
                                 heldExperts=held,
                                 routedScalingFactor=2.0,
                                 weightInit="xavier", **kw)
    layer.infer_nin(InputType.recurrent(12, 24))
    return layer


def _plain_moe(p, bias, x, held, k=4, shared=True):
    """Every held expert on every token, weighted by its gate."""
    s = jax.nn.sigmoid(jnp.dot(x, p["Wr"], precision=HI))
    _, sel = jax.lax.top_k(s + bias, k)
    picked = jnp.take_along_axis(s, sel, -1)
    gate = 2.0 * picked / jnp.sum(picked, -1, keepdims=True)
    out = 0.0
    for row, e in enumerate(held):
        w = jnp.sum(jnp.where(sel == e, gate, 0.0), -1)
        h = jax.nn.silu(x @ p["Eg"][row]) * (x @ p["Eu"][row])
        out = out + (h @ p["Ed"][row]) * w[:, None]
    if shared:
        out = out + (jax.nn.silu(x @ p["Sg"]) * (x @ p["Su"])) @ p["Sd"]
    return out


def _whole_layer(seed=0):
    whole = _moe()
    p, st = whole.initialize(jax.random.PRNGKey(seed))
    st["select_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 24, 12))
    return whole, p, st, x


def test_routing_bias_in_selection_only_and_divisor_over_all_four():
    layer, p, st, x = _whole_layer()
    xf = x.reshape(-1, 12)
    sel, gate = layer.route(xf, p["Wr"], st["select_bias"])
    s = jax.nn.sigmoid(jnp.dot(xf, p["Wr"], precision=HI))
    want_sel = np.argsort(-(s + st["select_bias"]), axis=-1)[:, :4]
    assert np.array_equal(np.sort(sel, -1), np.sort(want_sel, -1))
    # the bias moved the selection away from the plain top-4 somewhere
    plain = np.sort(np.argsort(-s, axis=-1)[:, :4], -1)
    assert not np.array_equal(np.sort(sel, -1), plain)
    picked = np.take_along_axis(np.asarray(s), np.asarray(sel), -1)
    np.testing.assert_allclose(
        gate, 2.0 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.sum(gate, -1), 2.0, rtol=1e-6)


def test_the_whole_layer_against_every_expert_on_every_token():
    layer, p, st, x = _whole_layer()
    got, new = layer.apply(p, st, x, True, jax.random.PRNGKey(0))
    want = _plain_moe(p, st["select_bias"], x.reshape(-1, 12),
                      list(range(16)))
    np.testing.assert_allclose(got.reshape(-1, 12), want, rtol=2e-4,
                               atol=2e-5)
    assert float(jnp.sum(new["expert_load"])) == 2 * 24 * 4
    assert np.array_equal(new["select_bias"], st["select_bias"])


def test_the_shares_of_eight_chips_add_up_to_the_uncut_layer():
    """The guide's share test: over 8 shares of 2 experts (of 16), the
    routed parts plus the shared expert counted once are the whole
    layer's output; every share routes over all 16."""
    whole, p, st, x = _whole_layer()
    full, _ = whole.apply(p, st, x, True, jax.random.PRNGKey(0))
    shared = (jax.nn.silu(x @ p["Sg"]) * (x @ p["Su"])) @ p["Sd"]
    total, pairs = shared, 0.0
    for c in range(8):
        held = [2 * c, 2 * c + 1]
        part = _moe(held)
        pp = dict(p, **{k: p[k][jnp.asarray(held)]
                        for k in ("Eg", "Eu", "Ed")})
        out, new = part.apply(pp, {**st, "expert_load": jnp.zeros(2)}, x,
                              True, jax.random.PRNGKey(0))
        total = total + (out - shared)
        pairs += float(jnp.sum(new["expert_load"]))
        assert part.param_shapes()["Eg"] == (2, 12, 8)
        assert part.param_shapes()["Wr"] == (12, 16)
    np.testing.assert_allclose(total, full, rtol=2e-4, atol=2e-5)
    assert pairs == 2 * 24 * 4


@pytest.mark.parametrize("held", [[3], [3, 5], [0, 1, 2, 3, 4, 5, 6, 7]])
def test_no_token_is_dropped_under_a_planted_imbalance(held):
    """Every token's first choice is expert 3: it gets all 48 tokens and
    the layer computes every one of them."""
    layer = _moe(held)
    p, st = layer.initialize(jax.random.PRNGKey(0))
    st["select_bias"] = jnp.zeros(16).at[3].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 12))
    got, new = layer.apply(p, st, x, True, jax.random.PRNGKey(0))
    assert float(new["expert_load"][held.index(3)]) == 48
    want = _plain_moe(p, st["select_bias"], x.reshape(-1, 12), held)
    np.testing.assert_allclose(got.reshape(-1, 12), want, rtol=2e-4,
                               atol=2e-5)


def test_the_expert_layers_gradient_against_the_plain_form():
    layer = _moe([1, 4, 6])
    p, st = layer.initialize(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 12))

    def mine(p, x):
        return jnp.sum(jnp.square(layer.apply(
            p, st, x, True, jax.random.PRNGKey(0))[0]))

    def plain(p, x):
        return jnp.sum(jnp.square(_plain_moe(
            p, st["select_bias"], x.reshape(-1, 12), [1, 4, 6])))
    got = jax.grad(mine, argnums=(0, 1))(p, x)
    want = jax.grad(plain, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5)


def _quarter_layer():
    """4 of 32 experts held, 48 tokens x 4: a pass takes 48 of the 192
    pairs' rows, four passes at most."""
    layer = _moe([3, 9, 17, 30], n=32)
    p, st = layer.initialize(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 12))
    return layer, p, st, x


def _bias_for(layer, p, x, pairs):
    """A selection bias on the held experts under which exactly ``pairs``
    of the selected (token, expert) pairs meet one: the count only grows
    with the bias, a pair at a time."""
    held = jnp.zeros(layer.n_experts).at[jnp.asarray(layer.held)].set(1.0)
    lo, hi = -2.0, 2.0
    for _ in range(60):
        mid = (lo + hi) / 2
        sel, _ = layer.route(x.reshape(-1, 12), p["Wr"], mid * held)
        got = int(jnp.sum(held[sel]))
        if got == pairs:
            return mid * held
        lo, hi = (mid, hi) if got < pairs else (lo, mid)
    raise AssertionError(f"no bias gives {pairs} held pairs")


@pytest.mark.parametrize("pairs", [0, 47, 48, 49, 97, 192])
def test_the_passes_against_the_plain_form_at_a_planted_held_count(pairs):
    """None, one short of a pass's rows, just its rows, one more (a
    second pass for one pair), a third pass's first pair, and every pair
    of every token: the output, the gradients and the slot of
    ``pass_steps`` that counts the step."""
    layer, p, st, x = _quarter_layer()
    rows = L._routed_rows(192, 4, 32)
    assert rows == 48 and st["pass_steps"].shape == (4,)
    st["select_bias"] = _bias_for(layer, p, x, pairs)

    def mine(p, x):
        out, new = layer.apply(p, st, x, True, jax.random.PRNGKey(0))
        return jnp.sum(jnp.square(out)), (out, new)

    def plain(p, x):
        out = _plain_moe(p, st["select_bias"], x.reshape(-1, 12),
                         layer.held)
        return jnp.sum(jnp.square(out)), out
    (_, (got, new)), dgot = jax.value_and_grad(
        mine, argnums=(0, 1), has_aux=True)(p, x)
    (_, want), dwant = jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True)(p, x)
    assert float(jnp.sum(new["expert_load"])) == pairs
    np.testing.assert_allclose(got.reshape(-1, 12), want, rtol=2e-4,
                               atol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(dgot),
                    jax.tree_util.tree_leaves(dwant)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5)
    slot = max(-(-pairs // rows), 1) - 1
    assert np.array_equal(new["pass_steps"], np.eye(4)[slot])
    # a second step adds to the slots, it does not replace them
    _, again = layer.apply(p, new, x, True, jax.random.PRNGKey(0))
    assert np.array_equal(again["pass_steps"], 2 * np.eye(4)[slot])


def test_a_pass_takes_twice_the_uniform_share_of_the_pairs():
    assert L.ROUTED_ROWS_OVER_UNIFORM == 2
    # the two cells: 8 of 64 held, top-4, 32,768 and 4,096 tokens
    assert L._routed_rows(131072, 8, 64) == 32768
    assert L._routed_rows(16384, 8, 64) == 4096
    assert L._routed_passes(8, 64) == 4
    # rounded up to whole tiles of rows, never beyond the pairs
    assert L._routed_rows(192, 1, 16) == 24
    assert L._routed_rows(100, 1, 16) == 16
    assert L._routed_rows(20, 3, 16) == 8
    assert L._routed_rows(192, 8, 16) == 192 == L._routed_rows(192, 16, 16)
    assert L._routed_passes(8, 16) == 1 == L._routed_passes(16, 16)
    assert L._routed_passes(3, 16) == 3
    # whatever the batch, no more passes than the state has slots
    for pairs in (4, 20, 100, 192, 1000):
        for held, n in ((1, 16), (3, 16), (5, 64)):
            rows = L._routed_rows(pairs, held, n)
            assert -(-pairs // rows) <= L._routed_passes(held, n)


def _lowered(layer, tokens=64):
    p, st = layer.initialize(jax.random.PRNGKey(0))
    x = jnp.zeros((1, tokens, 12))

    def loss(p, x):
        out, new = layer.apply(p, st, x, True, jax.random.PRNGKey(0))
        return jnp.sum(out), new
    return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        p, x).as_text()


def test_a_layer_that_holds_every_expert_lowers_with_no_conditional():
    text = _lowered(_moe(None))
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert "stablehlo.case" in _lowered(_moe([3, 5]))


def test_an_eighth_held_lowers_with_no_tensor_of_all_the_pairs_rows():
    """64 tokens x 4 = 256 pairs, 8 of 64 held: a pass takes 64 rows, and
    nothing 256 rows long is as wide as the tokens (12) or an expert's
    inner width (20), forward or backward."""
    layer = L.SparseExpertsLayer(nExperts=64, nExpertsPerTok=4, nHidden=20,
                                 heldExperts=list(range(8)),
                                 weightInit="xavier")
    layer.infer_nin(InputType.recurrent(12, 64))
    text = _lowered(layer)
    assert re.search(r"tensor<64x(12|20)x", text)
    assert not re.search(r"tensor<256x(12|20)x", text)
    assert not re.search(r"tensor<64x4x(12|20)x", text)
    assert re.search(r"tensor<256xi32>", text)      # the pair ids are


@pytest.mark.parametrize("rows", [48, 20, 60])
def test_the_layer_keeps_what_it_selected(rows):
    layer = _moe([0, 1], keepSelected=rows)
    p, st = layer.initialize(jax.random.PRNGKey(0))
    assert st["selected"].shape == (rows, 4) and int(st["selected"][0, 0]) \
        == -1
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 12))
    _, new = layer.apply(p, st, x, True, jax.random.PRNGKey(0))
    sel, _ = layer.route(x.reshape(-1, 12), p["Wr"], st["select_bias"])
    assert new["selected"].shape == (rows, 4)
    assert np.array_equal(new["selected"][:min(rows, 48)],
                          sel[:min(rows, 48)])
    assert np.all(np.asarray(new["selected"][48:]) == -1)
    assert "selected" not in _moe([0, 1]).initialize(
        jax.random.PRNGKey(0))[1]


def test_held_experts_have_to_be_distinct_ids_of_the_router():
    with pytest.raises(ValueError, match="distinct ids below 16"):
        _moe([1, 1])
    with pytest.raises(ValueError, match="distinct ids below 16"):
        _moe([16])
    with pytest.raises(ValueError, match="needs nExperts"):
        L.SparseExpertsLayer(nHidden=8)


# --------------------------------------------------------- hyper-connections
def test_sinkhorn_columns_and_rows_after_twenty_rounds():
    m = jnp.exp(jnp.clip(
        3.0 * jnp.eye(4) + 0.3 * jax.random.normal(jax.random.PRNGKey(0),
                                                   (5, 7, 4, 4)), -30, 30))
    out = L.sinkhorn(m, 20, 1e-6)
    np.testing.assert_allclose(jnp.sum(out, -2), 1.0, atol=1e-5)
    np.testing.assert_allclose(jnp.sum(out, -1), 1.0, atol=1e-3)
    assert float(jnp.min(out)) > 0
    np.testing.assert_allclose(out, REFERENCE.sinkhorn(m, 20, 1e-6),
                               rtol=1e-6)


def test_sinkhorns_gradient_through_the_twenty_rounds():
    z = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (3, 4, 4))
    w = jax.random.normal(jax.random.PRNGKey(2), (3, 4, 4))
    check_grads(lambda z: jnp.sum(w * L.sinkhorn(jnp.exp(z), 20, 1e-6)),
                (z,), order=1, modes=("rev",), atol=1e-2, rtol=1e-2)


def _hc(cls, features=4 * 6, **kw):
    layer = cls(nStreams=4, eps=1e-6, weightInit="xavier", **kw)
    layer.infer_nin(InputType.recurrent(features, 5))
    return layer


def _hc_params(read, write):
    """Parameters away from their opening values, so that every leaf has
    a gradient of some size."""
    rp, _ = read.initialize(jax.random.PRNGKey(0))
    wp, _ = write.initialize(jax.random.PRNGKey(1))
    rp["b_pre"] = jnp.asarray([0.1, -0.2, 0.3, 0.0])
    rp["alpha_pre"] = jnp.asarray([0.7])
    wp["b_res"] = wp["b_res"] + 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (4, 4))
    wp["b_post"] = jnp.asarray([-0.1, 0.2, 0.0, 0.3])
    wp["alpha_res"], wp["alpha_post"] = jnp.asarray([0.5]), jnp.asarray([0.6])
    return rp, wp


def _plain_read(rp, x):
    """``u`` in the einsum form, float32."""
    N, T, F = x.shape
    h_pre = jax.nn.sigmoid(rp["alpha_pre"][0] * jnp.dot(
        rms(x, None), rp["phi_pre"], precision=HI) + rp["b_pre"])
    return jnp.einsum("ntk,ntkc->ntc", h_pre, x.reshape(N, T, 4, F // 4),
                      precision=HI)


def _plain_write(wp, x, y):
    """``X'`` in the einsum form, float32."""
    N, T, F = x.shape
    xt = rms(x, None)
    h_post = 2 * jax.nn.sigmoid(wp["alpha_post"][0] * jnp.dot(
        xt, wp["phi_post"], precision=HI) + wp["b_post"])
    res = wp["alpha_res"][0] * jnp.dot(xt, wp["phi_res"], precision=HI) \
        .reshape(N, T, 4, 4) + wp["b_res"]
    h_res = L.sinkhorn(jnp.exp(jnp.clip(res, -30, 30)), 20, 1e-6)
    out = jnp.einsum("ntij,ntjc->ntic", h_res, x.reshape(N, T, 4, F // 4),
                     precision=HI) + h_post[..., None] * y[:, :, None, :]
    return out.reshape(N, T, F)


_HC_CASES = {
    # what runs: (the layers' form, the plain form), both of
    # (read's parameters, write's parameters, X, y) -> outputs
    "read": (lambda r, w, rp, wp, x, y: (r.apply(rp, {}, x, True, None)[0],),
             lambda rp, wp, x, y: (_plain_read(rp, x),)),
    "write": (lambda r, w, rp, wp, x, y:
              (w.apply(wp, {}, (x, y), True, None)[0],),
              lambda rp, wp, x, y: (_plain_write(wp, x, y),)),
    "read_tanh_write": (
        lambda r, w, rp, wp, x, y: (w.apply(
            wp, {}, (x, jnp.tanh(r.apply(rp, {}, x, True, None)[0]) + y),
            True, None)[0],),
        lambda rp, wp, x, y: (_plain_write(
            wp, x, jnp.tanh(_plain_read(rp, x)) + y),)),
}


def _hc_case(what, dtype, T, wrap=lambda f: f):
    """``(outputs, gradients)`` of the layers' form in ``dtype`` streams
    and of the plain form in float32 on the same numbers, under one
    weighted sum of the outputs."""
    read, write = _hc(L.HyperConnectionRead), _hc(L.HyperConnectionWrite)
    rp, wp = _hc_params(read, write)
    ks = jax.random.split(jax.random.PRNGKey(T), 3)
    # numbers that are exact in bfloat16, for both forms
    x = jax.random.normal(ks[0], (2, T, 24)).astype(jnp.bfloat16)
    y = jax.random.normal(ks[1], (2, T, 6)).astype(jnp.bfloat16)
    mine, plain = _HC_CASES[what]
    shape = (2, T, 6) if what == "read" else (2, T, 24)
    weight = jax.random.normal(ks[2], shape).astype(jnp.bfloat16) \
        .astype(jnp.float32)

    def total(outs):
        return sum(jnp.sum(weight * o.astype(jnp.float32)) for o in outs)
    f32 = lambda a: a.astype(jnp.float32)     # noqa: E731
    got = jax.value_and_grad(
        lambda rp, wp, x, y: (lambda o: (total(o), o))(
            wrap(lambda *a: mine(read, write, *a))(rp, wp, x, y)),
        argnums=(0, 1, 2, 3), has_aux=True)(rp, wp, x.astype(dtype),
                                            y.astype(dtype))
    want = jax.value_and_grad(
        lambda rp, wp, x, y: (lambda o: (total(o), o))(plain(rp, wp, x, y)),
        argnums=(0, 1, 2, 3), has_aux=True)(rp, wp, f32(x), f32(y))
    return got, want


@pytest.mark.parametrize("T", [5, 128])
@pytest.mark.parametrize("what", sorted(_HC_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_hyper_connection_pairs_against_autodiff_of_the_plain_form(
        dtype, what, T):
    """The read and the write are forward/backward pairs written by hand:
    outputs and the gradients of ``X``, ``y`` and all nine parameter
    leaves against ``jax.grad`` of the einsum form. Float32 streams to
    float32's rounding; bfloat16 streams inside bfloat16's rounding of
    what comes and goes in it (``u``, ``X'``, ``dX``, ``dy``), the maps
    and every parameter's gradient at float32's precision: where no
    rounded output lies between the parameters and the loss (the read
    alone, the write alone) they agree with float32's to 1e-4."""
    ((_, outs), grads), ((_, outs32), grads32) = _hc_case(what, dtype, T)
    exact = dtype == jnp.float32
    step = 1e-5 if exact else 2.0 ** -8        # one rounding of an output
    for o, o32 in zip(outs, outs32):
        assert o.dtype == dtype
        # the chain rounds u on the way to X'
        np.testing.assert_allclose(o.astype(jnp.float32), o32,
                                   rtol=(1 if exact else 4) * step,
                                   atol=(1 if exact else 4) * step)
    (rp, wp, dx, dy), (rp32, wp32, dx32, dy32) = grads, grads32
    for g, g32 in ((dx, dx32), (dy, dy32)):
        assert g.dtype == dtype
        scale = max(float(jnp.max(jnp.abs(g32))), 1e-30)
        np.testing.assert_allclose(g.astype(jnp.float32), g32,
                                   rtol=(1 if exact else 4) * step,
                                   atol=(1 if exact else 4) * step * scale)
    used = {"read": [rp], "write": [wp], "read_tanh_write": [rp, wp]}[what]
    tol = 1e-5 if exact else (1e-4 if what != "read_tanh_write" else 2e-2)
    n = 0
    for tree, tree32 in ((rp, rp32), (wp, wp32)):
        for leaf in tree32:
            assert tree[leaf].dtype == jnp.float32
            scale = float(jnp.max(jnp.abs(tree32[leaf])))
            if any(tree is u for u in used):
                assert scale > 0, leaf
                n += 1
            np.testing.assert_allclose(tree[leaf], tree32[leaf], rtol=tol,
                                       atol=tol * scale, err_msg=leaf)
    assert n == {"read": 3, "write": 6, "read_tanh_write": 9}[what]


def test_the_pairs_under_a_checkpoint_give_the_same_values():
    """``rematerializeStack()`` wraps a sub-block in ``jax.checkpoint``:
    the forward rules run again in the backward pass, and nothing
    changes."""
    plain, _ = _hc_case("read_tanh_write", jnp.bfloat16, 5)
    remat, _ = _hc_case("read_tanh_write", jnp.bfloat16, 5,
                        wrap=jax.checkpoint)
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(remat)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("what", ["read", "write"])
def test_the_forward_rules_keep_no_float32_stream(what):
    """What a forward rule hands its backward rule: ``X`` (and ``y``) as
    they came, the parameters, the maps before their activation [k, N, T]
    and the norm's divisor [N, T]; on bfloat16 streams no float32 array of
    a stream's size or more."""
    N, T, C, n = 2, 16, 64, 4
    bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)   # noqa: E731
    layer = _hc(L.HyperConnectionRead if what == "read"
                else L.HyperConnectionWrite, n * C)
    params = jax.eval_shape(lambda: layer.initialize(jax.random.PRNGKey(0))[0])
    if what == "read":
        out, kept = jax.eval_shape(
            lambda p, x: L._hc_read_fwd(x, p["phi_pre"], p["alpha_pre"],
                                        p["b_pre"], 1e-6),
            params, bf(N, T, n * C))
        k = n
    else:
        out, kept = jax.eval_shape(
            lambda p, x, y: L._hc_write_fwd(x, y, p, 20, 1e-6, (-30., 30.)),
            params, bf(N, T, n * C), bf(N, T, C))
        k = n + n * n
    assert out.dtype == jnp.bfloat16
    leaves = jax.tree_util.tree_leaves(kept)
    own = [tuple(a.shape) for a in jax.tree_util.tree_leaves(params)]
    for leaf in leaves:
        assert leaf.dtype == jnp.bfloat16 or leaf.size < N * T * C \
            or tuple(leaf.shape) in own, leaf
    small = sorted(tuple(a.shape) for a in leaves
                   if a.dtype == jnp.float32 and a.shape[-2:] == (N, T))
    assert small == [(N, T), (k, N, T)]
    streams = [a.shape for a in leaves if a.dtype == jnp.bfloat16]
    assert streams == [(N, T, n * C)] + ([(N, T, C)] if what == "write"
                                         else [])
    assert len(leaves) == len(jax.tree_util.tree_leaves(params)) \
        + len(streams) + 2


def test_a_sub_block_under_hyper_connections_against_jnp():
    read, write = _hc(L.HyperConnectionRead), _hc(L.HyperConnectionWrite)
    rp, _ = read.initialize(jax.random.PRNGKey(0))
    wp, _ = write.initialize(jax.random.PRNGKey(1))
    rp["b_pre"] = jnp.asarray([0.1, -0.2, 0.3, 0.0])
    wp["b_res"] = wp["b_res"] + 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (4, 4))
    wp["alpha_res"] = jnp.asarray([0.5])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 24))
    u, _ = read.apply(rp, {}, x, True, None)
    y = jnp.tanh(u)
    out, _ = write.apply(wp, {}, (x, y), True, None)
    X = x.reshape(2, 5, 4, 6)
    xt = rms(x, None)
    h_pre = jax.nn.sigmoid(0.01 * xt @ rp["phi_pre"] + rp["b_pre"])
    np.testing.assert_allclose(u, jnp.einsum("ntk,ntkc->ntc", h_pre, X),
                               rtol=1e-5, atol=1e-6)
    h_post = 2 * jax.nn.sigmoid(0.01 * xt @ wp["phi_post"] + wp["b_post"])
    res = 0.5 * (xt @ wp["phi_res"]).reshape(2, 5, 4, 4) + wp["b_res"]
    h_res = L.sinkhorn(jnp.exp(jnp.clip(res, -30, 30)), 20, 1e-6)
    want = jnp.einsum("ntij,ntjc->ntic", h_res, X) \
        + h_post[..., None] * y[:, :, None, :]
    np.testing.assert_allclose(out, want.reshape(2, 5, 24), rtol=1e-5,
                               atol=1e-6)
    hp, hr = write.maps(wp, x)
    np.testing.assert_allclose(hr, h_res, rtol=1e-5)
    np.testing.assert_allclose(hp, h_post, rtol=1e-5)
    assert write.param_shapes()["phi_res"] == (24, 16)
    assert read.nOut == 6 and write.nOut == 24


def test_bfloat16_streams_get_their_maps_at_float32s_precision():
    """Streams in bfloat16 are exact in it: three bfloat16 pieces of
    ``phi`` in one product give what float32 at full precision gives, and
    the gradient of ``phi`` is that of the plain product."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 96)) \
        .astype(jnp.bfloat16)
    phis = [jax.random.normal(jax.random.PRNGKey(i), (96, k))
            for i, k in ((1, 4), (2, 16))]
    got = L._stream_maps(x, phis, 1e-6)
    want = L._stream_maps(x.astype(jnp.float32), phis, 1e-6)
    one_pass = [jnp.moveaxis(jnp.dot(x, p.astype(jnp.bfloat16),
                                     preferred_element_type=jnp.float32),
                             -1, 0) for p in phis]
    assert [g.shape for g in got] == [(4, 2, 16), (16, 2, 16)]
    for g, w, rough in zip(got, want, one_pass):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
        scale = jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32)), -1))
        assert float(jnp.max(jnp.abs(rough / scale - w))) > 1e-3

    def total(phis, x):
        return sum(jnp.sum(jnp.sin(z)) for z in L._stream_maps(x, phis,
                                                               1e-6))
    g16 = jax.grad(total)(phis, x)
    g32 = jax.grad(total)(phis, x.astype(jnp.float32))
    for a, b in zip(g16, g32):
        np.testing.assert_allclose(a, b, rtol=0.02, atol=0.02)
    # through the layers' own backward rules ``phi``'s gradient is float32's
    # too: the cotangent goes into the product in three bfloat16 pieces,
    # where one rounded piece (the transpose autodiff makes of the forward
    # product, above) is off in the third digit
    read, write = _hc(L.HyperConnectionRead, 96), _hc(L.HyperConnectionWrite,
                                                    96)
    rp, wp = _hc_params(read, write)
    y = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 24)) \
        .astype(jnp.bfloat16)

    wu, wx = (jax.random.normal(jax.random.PRNGKey(k), shape)
              .astype(jnp.bfloat16).astype(jnp.float32)
              for k, shape in ((6, (2, 16, 24)), (7, (2, 16, 96))))

    def through(x, y):
        # a weighted sum: the outputs' own rounding to bfloat16 does not
        # reach the gradients, the products' precision does
        def loss(rp, wp):
            u, _ = read.apply(rp, {}, x, True, None)
            out, _ = write.apply(wp, {}, (x, y), True, None)
            return jnp.sum(wu * u.astype(jnp.float32)) \
                + jnp.sum(wx * out.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1))(rp, wp)
    (r16, w16), (r32, w32) = through(x, y), through(
        x.astype(jnp.float32), y.astype(jnp.float32))
    for a, b in ((r16["phi_pre"], r32["phi_pre"]),
                 (w16["phi_post"], w32["phi_post"]),
                 (w16["phi_res"], w32["phi_res"])):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4 * float(
            jnp.max(jnp.abs(b))))
    rough = float(jnp.max(jnp.abs(g16[0] - g32[0]))
                  / jnp.max(jnp.abs(g32[0])))
    assert rough > 1e-3


def test_streams_copy_in_and_sum_out():
    e = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 6))
    into = L.HyperConnectionIn(nStreams=4)
    into.infer_nin(InputType.recurrent(6, 5))
    out = _hc(L.HyperConnectionOut)
    x, _ = into.apply({}, {}, e, True, None)
    assert x.shape == (2, 5, 24) and into.nOut == 24 and out.nOut == 6
    np.testing.assert_allclose(x.reshape(2, 5, 4, 6)[:, :, 2], e)
    np.testing.assert_allclose(out.apply({}, {}, x, True, None)[0], 4 * e,
                               rtol=1e-6)
    with pytest.raises(ValueError, match="do not divide"):
        L.HyperConnectionOut(nStreams=5).infer_nin(
            InputType.recurrent(24, 5))


def test_the_clamp_keeps_the_residual_map_finite():
    write = _hc(L.HyperConnectionWrite)
    wp, _ = write.initialize(jax.random.PRNGKey(1))
    wp["b_res"] = 200.0 * jnp.eye(4)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 5, 24))
    _, hr = write.maps(wp, x)
    assert bool(jnp.all(jnp.isfinite(hr)))
    np.testing.assert_allclose(hr, jnp.broadcast_to(jnp.eye(4), hr.shape),
                               atol=1e-6)


# ------------------------------------------------- multi-token prediction
def test_mtp_join_against_jnp():
    join = L.MTPJoinLayer(weightInit="xavier")
    join.infer_nin(InputType.recurrent(6, 5))
    p, _ = join.initialize(jax.random.PRNGKey(0))
    p["e_gain"] = jnp.arange(1.0, 7.0)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 6))
    e = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 6))
    got, _ = join.apply(p, {}, (h, e), True, None)
    want = jnp.concatenate([rms(h, p["h_gain"]), rms(e, p["e_gain"])], -1) \
        @ p["W"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert join.param_shapes()["W"] == (12, 6)


@pytest.mark.parametrize("masked", [False, True])
def test_the_shared_heads_shift_and_mask(masked):
    """The module's state at position i is scored against the label at
    i + 1 and its last position has none; each loss a mean over its own
    unmasked positions; one ``W``."""
    head = L.MTPLMOutputLayer(nOut=11, mtpWeight=0.3, weightInit="xavier")
    head.infer_nin(InputType.recurrent(6, 8))
    p, _ = head.initialize(jax.random.PRNGKey(0))
    h0 = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 6))
    h1 = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 6))
    y = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, 11)
    mask = (jax.random.uniform(jax.random.PRNGKey(4), (2, 8)) > 0.3) \
        .astype(jnp.float32) if masked else None
    loss, state = head.loss_from(p, (h0, h1), y, mask=mask)
    m = jnp.ones((2, 8)) if mask is None else mask

    def ce(h, labels):
        logp = jax.nn.log_softmax(h @ p["W"], -1)
        return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    main = jnp.sum(ce(h0, y) * m) / jnp.sum(m)
    m1 = m[:, 1:]
    mtp = jnp.sum(ce(h1[:, :-1], y[:, 1:]) * m1) / jnp.sum(m1)
    assert float(loss) == pytest.approx(float(main + 0.3 * mtp), rel=1e-5)
    np.testing.assert_allclose(state["head_loss"], [main, mtp], rtol=1e-5)
    alone, _ = head.loss_from(p, h0, y, mask=mask)
    assert float(alone) == pytest.approx(float(main), rel=1e-5)
    logits, _ = head.apply(p, {}, (h0, h1), False, None)
    np.testing.assert_allclose(logits, h0 @ p["W"], rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="loss_from"):
        head.compute_loss(y, logits)


def test_the_module_shares_embedding_norm_and_head_with_the_main_model():
    net, cfg = tiny_net()
    assert net._params["mtp_embed"] == {} and net._params["mtp_fnorm"] == {}
    assert net.conf.param_owner["mtp_embed"] == "embed"
    assert net.conf.param_owner["mtp_fnorm"] == "fnorm"
    assert net.numParams() == MODEL.n_params(cfg)
    assert isinstance(net.conf.node_by_name["next"].obj, LabelsVertex)
    # token 5 is never an input, only a label: its embedding row has a
    # gradient because the module looked it up
    ins = {"tokens": jnp.zeros((1, 32), jnp.int32)}
    labels = [jnp.full((1, 32), 5, jnp.int32)]

    def loss(params):
        return net._loss_and_reg(params, net._states, ins, labels, True,
                                 jax.random.PRNGKey(0), None, None)[0]
    g = jax.grad(loss)(net._params)
    assert float(jnp.max(jnp.abs(g["embed"]["W"][5]))) > 0
    assert float(jnp.max(jnp.abs(g["embed"]["W"][7]))) == 0
    assert float(jnp.max(jnp.abs(g["fnorm"]["gain"]))) > 0
    assert g["mtp_embed"] == {} and g["mtp_fnorm"] == {}


def test_inference_leaves_the_module_out():
    net, cfg = tiny_net()
    x, _ = tokens(cfg, 1)[0]
    out = net.output(x)
    assert out.shape == (2, 64, 32)
    without, _ = tiny_net(tiny_cfg(0))
    without._params = {k: net._params[k] for k in without._params}
    without._states = {k: net._states[k] for k in without._states}
    np.testing.assert_allclose(out, without.output(x), rtol=1e-5, atol=1e-6)


# ------------------------------------------- rematerialising a plain stack
def test_the_plain_stack_is_cut_a_sub_block_at_a_time():
    net, _ = tiny_net()
    got = [[n.name for n in s] for s in net.conf.stack_stretches]
    assert got[:4] == [["embed"], ["hc_in"],
                       ["l0_hr1", "l0_n1", "l0_attn", "l0_hw1"],
                       ["l0_hr2", "l0_n2", "l0_mlp", "l0_hw2"]]
    assert ["l2_hr2", "l2_n2", "l2_moe", "l2_hw2"] in got
    assert ["next", "mtp_embed", "mtp_join"] in got
    assert ["mtp_hr1", "mtp_n1", "mtp_attn", "mtp_hw1"] in got
    assert got[-1] == ["lm"]
    assert sum(len(s) for s in got) == len(net.conf.topo)
    # a graph that does not ask has none, and compiles what it always did
    assert zoo.Ouro(num_layers=1, hidden_size=16, num_heads=2, head_dim=8,
                    intermediate_size=16, vocab_size=32, seq_len=8,
                    total_ut_steps=2).conf_builder().conf.stack_stretches \
        is None


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
    for a, b in zip(jax.tree_util.tree_leaves(plain[0][1]),
                    jax.tree_util.tree_leaves(remat[0][1])):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    text = jax.jit(jax.grad(lambda p: loss(p, True)[0])).lower(
        net._params).compile().as_text()
    assert stepprogram.REMAT_MARK in text
    text = jax.jit(jax.grad(lambda p: loss(p, False)[0])).lower(
        net._params).compile().as_text()
    assert "/" + stepprogram.REMAT_MARK + "/dl4j_L" not in text


def test_a_stretch_count_follows_the_values_alive():
    """A value that lives long raises the count at every cut it passes
    and forbids none: the main hidden state lives across the whole
    module."""
    cut = ComputationGraphConfiguration._stack_stretches
    net, _ = tiny_net()
    top = [n for n in net.conf.topo if n.loop is None]
    names = [[n.name for n in s] for s in cut(top, ["tokens"], ["lm"])]
    assert ["mtp_hc_in"] in names and ["fnorm"] in names


# ------------------------------------------------------------ serialisation
def test_json_round_trip_keeps_the_new_layers():
    net, cfg = tiny_net()
    text = net.conf.to_json()
    conf = ComputationGraphConfiguration.from_json(text)
    assert conf.to_json() == text
    assert conf.remat_stack and conf.stack_stretches is not None
    assert [n.name for n in conf.topo] == [n.name for n in net.conf.topo]
    moe = conf.node_by_name["l1_moe"].obj
    assert isinstance(moe, L.SparseExpertsLayer)
    assert moe.held == [0, 1, 2, 3] and moe.n_experts == 16
    attn = conf.node_by_name["l0_attn"].obj
    assert isinstance(attn, L.LatentAttentionLayer)
    assert attn.rope_scaling["factor"] == 64
    assert conf.node_by_name["mtp_embed"].obj.tied_with == "embed"
    assert conf.node_by_name["l0_hw1"].inputs == ["hc_in", "l0_attn"]
    assert conf.node_by_name["lm"].inputs == ["fnorm", "mtp_fnorm"]
    again = ComputationGraph(conf).init()
    again._params, again._states = net._params, net._states
    x, y = tokens(cfg, 1)[0]
    assert again.score(DataSet(x, y)) == net.score(DataSet(x, y))


@pytest.mark.parametrize("saved_before", [(), ("pass_steps",)])
def test_save_and_load_keep_weights_states_and_loss(tmp_path, saved_before):
    """``saved_before``: state leaves the archive lacks, as one written
    before the layer had them; they come back as ``init()`` makes them."""
    net, cfg = tiny_net()
    batches = tokens(cfg, 3)
    net.fit(DataSet(*batches[0]))
    path = str(tmp_path / "xing4.zip")
    states = net._states
    net._states = {name: {k: v for k, v in state.items()
                          if k not in saved_before}
                   for name, state in states.items()}
    net.save(path)
    net._states = states
    loaded = ComputationGraph.load(path)
    assert loaded.numParams() == net.numParams()
    # 4 of 16 held: two slots, and the one step stands in one of them
    assert sorted(loaded._states["l1_moe"]["pass_steps"]) \
        == ([0.0, 0.0] if saved_before else [0.0, 1.0])
    np.testing.assert_allclose(loaded._states["l1_moe"]["select_bias"],
                               net._states["l1_moe"]["select_bias"])
    net.fit(DataSet(*batches[1]))
    loaded.fit(DataSet(*batches[1]))
    assert float(loaded.score()) == pytest.approx(float(net.score()),
                                                  rel=1e-6)


def test_the_zoo_model_inits_and_trains_with_its_own_weights():
    net = zoo.Xing4(num_layers=2, first_k_dense=1, hidden_size=16,
                    num_heads=2, q_lora_rank=8, kv_lora_rank=8,
                    qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=4,
                    intermediate_size=24, moe_intermediate_size=8,
                    n_routed_experts=8, held_experts=[0, 1],
                    num_experts_per_tok=2, vocab_size=32, seq_len=8).init()
    rows = np.random.default_rng(0).integers(0, 32, (2, 9)).astype(np.int32)
    first = None
    for _ in range(5):
        net.fit(DataSet(rows[:, :-1], rows[:, 1:]))
        first = first if first is not None else float(net._score)
    assert float(net._score) < first
    with pytest.raises(ValueError, match="one multi-token"):
        zoo.Xing4(num_nextn_predict_layers=2, num_layers=1,
                  first_k_dense=0).conf_builder()


def test_a_layer_refuses_the_public_layout_and_typos():
    layer = _mla()
    with pytest.raises(ValueError, match="feature-last"):
        layer.apply(layer.initialize(jax.random.PRNGKey(0))[0], {},
                    jnp.zeros((2, 32, 16)), True, None)
    with pytest.raises(TypeError, match="did you mean 'qLoraRank'"):
        L.LatentAttentionLayer(qLoraRnk=4)
    with pytest.raises(ValueError, match="even qkRopeHeadDim"):
        L.LatentAttentionLayer(nHeads=1, qLoraRank=4, kvLoraRank=4,
                               qkNopeHeadDim=4, qkRopeHeadDim=3, vHeadDim=4)


# ------------------------------------------------------------ the instruments
def test_the_step_program_carries_the_new_parts_and_the_gauges_read(
        monkeypatch):
    net, cfg = tiny_net()
    texts = []
    parse = stepprogram.parse
    monkeypatch.setattr(stepprogram, "parse",
                        lambda text: texts.append(text) or parse(text))
    profiler.set_profiling_mode("basic")
    try:
        stepprogram.clear()
        lowered = L._MOE_LOWERED.labels("compact").value
        pairs_lowered = L._MHC_LOWERED.labels("pair").value
        net.fit(DataSet(*tokens(cfg, 1)[0]))
        assert L._MOE_LOWERED.labels("compact").value > lowered
        assert [k for k in L._MOE_LOWERED.children()] == [("compact",)]
        # once a read or write layer a traced step: one path, one value
        hyper = [n for n in net.conf.topo if isinstance(
            n.obj, (L.HyperConnectionRead, L.HyperConnectionWrite))]
        assert len(hyper) == 2 * 2 * 4      # three layers and the module
        assert L._MHC_LOWERED.labels("pair").value - pairs_lowered \
            == len(hyper)
        assert [k for k in L._MHC_LOWERED.children()] == [("pair",)]
        assert profiler.get_registry().get("dl4j_mhc_lowered_total") \
            is L._MHC_LOWERED
        pairs = {k[0]: c.value for k, c in
                 stepping.MOE_HELD_PAIRS.children().items()}
        assert set(pairs) >= {"l1_moe", "l2_moe", "mtp_moe"}
        load = sum(c.value for k, c in
                   stepping.MOE_EXPERT_LOAD.children().items()
                   if k[0] == "l1_moe")
        assert load == pairs["l1_moe"] and 0 < load <= 2 * 32 * 4
        # 4 of 16 held: two passes at most, and the one step this net
        # has run stands in one of the two slots of every expert layer
        for layer in ("l1_moe", "l2_moe", "mtp_moe"):
            ran = {k[1]: c.value for k, c in
                   stepping.MOE_PASS_STEPS.children().items()
                   if k[0] == layer}
            assert set(ran) == {"1", "2"} and sorted(ran.values()) \
                == [0.0, 1.0], layer
        assert stepping.LM_LOSS.labels("main").value > 0
        assert stepping.LM_LOSS.labels("mtp").value > 0
        maps = stepprogram.maps()
    finally:
        profiler.set_profiling_mode(None)
        stepprogram.clear()
    entries = [e for m in maps.values() for e in m.values()]
    parts = {e.part for e in entries}
    assert {"attn_core", "head_loss", "mhc", "moe", "moe_experts"} <= parts
    assert any(e.remat and e.phase == "backward" for e in entries)
    experts = [e for e in entries if e.part == "moe_experts"]
    assert all(e.layer and e.layer.endswith("_moe") for e in experts)
    assert any(e.layer and "_mtp_" in e.layer for e in entries)
    heads = {e.loop_pass for e in entries if e.part == "head_loss"}
    assert {1, 2} <= heads
    # the hyper-connections' backward is two rules written by hand, not
    # the transpose of their forward: every op of them stands in the map
    # as the hyper-connections', backward, in its read or write layer,
    # and what a stretch runs again of the forward rules carries the
    # remat mark beside
    (by_name,), (text,) = maps.values(), texts
    seen = {"_read_bwd": 0, "_write_bwd": 0, "remat": 0}
    for line in text.split("\n"):
        name, op = stepprogram._INSTRUCTION.match(line), \
            stepprogram._OP_NAME.search(line)
        if not name or not op or name.group(1) not in by_name:
            continue
        entry, op = by_name[name.group(1)], op.group(1).partition(";")[0]
        rule = [r for r in ("_read_bwd", "_write_bwd")
                if f"jit({r})" in op]
        if rule:
            # (a fusion may hold the maps' small function once for the
            # forward run again and for the rule, and then says remat)
            assert (entry.part, entry.phase) == ("mhc", "backward"), line
            assert entry.layer and ("_hr" if rule[0] == "_read_bwd"
                                    else "_hw") in entry.layer, line
            seen[rule[0]] += 1
        elif "jit(_read_fwd)" in op and stepprogram.REMAT_MARK in op:
            assert (entry.part, entry.phase, entry.remat) \
                == ("mhc", "backward", True), line
            seen["remat"] += 1
    assert min(seen.values()) >= len(hyper) // 2, seen


def test_marks_of_nested_scopes_take_the_innermost():
    name = "jit(step)/jvp(dl4j_L9_l1_moe)/dl4j_moe/dl4j_moe_experts/dot"
    assert stepprogram.marks(name) == (None, "moe_experts", False)
    assert stepprogram.marks(name.replace("/dl4j_moe_experts", "")) \
        == (None, "moe", False)
    assert stepprogram.marks("jvp(dl4j_L3_l0_hw1)/dl4j_mhc/mul") \
        == (None, "mhc", False)
    assert stepprogram.marks(
        "transpose(jvp(dl4j_L2_attn))/rematted_computation/"
        "dl4j_attn_core/dot") == (None, "attn_core", True)


def test_a_policy_keeps_the_named_leaves_float32():
    layer = _moe([0, 1])
    p, _ = layer.initialize(jax.random.PRNGKey(0))
    cast, x = L.policy_cast(layer, p, jnp.zeros((1, 24, 12)), jnp.bfloat16)
    assert cast["Wr"].dtype == jnp.float32
    assert cast["Eg"].dtype == jnp.bfloat16 and x.dtype == jnp.bfloat16
    attn = _mla()
    cast, _ = L.policy_cast(attn, attn.initialize(jax.random.PRNGKey(0))[0],
                            jnp.zeros((1, 16, 32)), jnp.bfloat16)
    assert cast["q_gain"].dtype == cast["kv_gain"].dtype == jnp.float32
    assert cast["Wqa"].dtype == jnp.bfloat16


def test_the_model_trains_under_the_bf16_policy():
    net, cfg = tiny_net()
    net.setPrecisionPolicy("bf16")
    batches = tokens(cfg, 2)
    net.fit(DataSet(*batches[0]))
    first = float(net._score)
    net.fit(DataSet(*batches[0]))
    assert math.isfinite(first) and float(net._score) < first
    assert net._params["l1_moe"]["Eg"].dtype == jnp.float32


def test_the_flop_models_know_the_new_layers():
    """The static model (``analysis``) and the per-layer one
    (``profiler.devicetime``) agree with each other and, the parts they
    count otherwise apart, with the benchmark's count: they take the
    whole square of the attention core (the benchmark the causal half it
    requires) and add the hyper-connections' mixing to their maps; a tied
    embedding's table is no matmul."""
    from deeplearning4j_tpu.analysis import graphir
    from deeplearning4j_tpu.profiler import devicetime
    net, cfg = tiny_net()
    rows = {name: f for name, _op, f in devicetime.layer_flop_model(net.conf)}
    ir = graphir.from_graph(net.conf, batch_size=1)
    assert ir.total_flops() == sum(rows.values())
    square = MODEL.attention_applications(cfg) * MODEL.core_flops(cfg)
    import re
    streams = sum(f for name, f in rows.items()
                  if re.search(r"(^|_)(h[rw][12]|hc_in|hc_out)$", name))
    maps = MODEL.sub_blocks(cfg) * 2 * 32 * 4 * 32 * (2 * 4 + 16)
    assert sum(rows.values()) - streams + maps == pytest.approx(
        MODEL.flops_per_sample(cfg) + square, rel=1e-9)
    assert rows["l0_mlp"] == 2 * 32 * 3 * 32 * 48
    assert rows["l1_moe"] == 2 * 32 * (32 * 16 + 3 * 32 * 16 * (1 + 4 * 4 / 16))
    assert rows["lm"] == 2 * 2 * 32 * 32 * 64
    assert rows["mtp_join"] == 2 * 32 * 64 * 32
    assert rows["mtp_embed"] == rows["embed"] == 0
    assert rows["l0_hr1"] == 2 * 32 * (128 * 4 + 4 * 128)
    assert rows["l0_hw1"] == 2 * 32 * (128 * 20 + 4 * 128)
    assert rows["l0_attn"] == 2 * 32 * (32 * 16 + 16 * 48 + 32 * 12 + 8 * 64
                                        + 32 * 32) + 2 * 32 * 32 * 4 * 20


def test_a_kernel_of_the_compilers_takes_its_operands_entry():
    """What the chip's compiler makes of a grouped product: a
    ``ragged-dot-*`` custom-call whose metadata keeps no scope. The map
    gives it the layer and phase of the rows it multiplies and the part
    ``moe_experts``."""
    text = '''HloModule jit_step, is_scheduled=true

ENTRY %main.1 (p0: bf16[64,8], p1: bf16[2,8,4]) -> bf16[64,4] {
  %p0 = bf16[64,8]{1,0} parameter(0)
  %p1 = bf16[2,8,4]{2,1,0} parameter(1)
  %gather.3 = bf16[64,8]{1,0} fusion(%p0), kind=kLoop, calls=%fused.3, metadata={op_name="jit(step)/transpose(jvp(dl4j_L9_l1_moe))/rematted_computation/dl4j_moe/gather"}
  %ragged-dot-metadata.1 = (s32[3]{0}, s32[5]{0}) custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none.7 = bf16[64,4]{1,0} custom-call(%gather.3, %p1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %adam.2 = f32[2,8,4]{2,1,0} fusion(%p1), kind=kLoop, calls=%fused.9, metadata={op_name="jit(step)/dl4j_updater/mul"}
  ROOT %silu.4 = bf16[64,4]{1,0} fusion(%ragged-dot-none.7), kind=kLoop, calls=%fused.4, metadata={op_name="jit(step)/jvp(dl4j_L9_l1_moe)/dl4j_moe/dl4j_moe_experts/mul"}
}
'''
    got = stepprogram.parse(text)
    assert got["ragged-dot-none.7"] == stepprogram.Entry(
        "backward", "dl4j_L9_l1_moe", None, False, None, "moe_experts", True)
    # nothing to adopt from: still the part its name stands for
    assert got["ragged-dot-metadata.1"].part == "moe_experts"
    assert got["ragged-dot-metadata.1"].phase == "other"
    assert got["silu.4"].part == "moe_experts"
