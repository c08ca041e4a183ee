"""The loop construct (``LoopVertex``), the feature-last sequence layers and
``zoo.Ouro`` on the CPU at a tiny size: d = 64, 4 heads of 16, F = 176,
V = 512, 2 layers, S = 32, T = 4 and T = 1, in float32, against the
benchmark's plain reference (``chipbench/configs/ouro-2.6b-l6-bf16/
reference.py``) through the harness's own ``compare``."""

import copy
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, refnn
from chipbench.weights import make_weights
from deeplearning4j_tpu import profiler
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                         ComputationGraphConfiguration,
                                         ElementWiseVertex, LoopVertex,
                                         PassVertex)
from deeplearning4j_tpu.ops import attention as attention_ops
from deeplearning4j_tpu.profiler import stepprogram
from deeplearning4j_tpu.train import stepping, updaters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG_DIR = os.path.join(ROOT, "chipbench", "configs", "ouro-2.6b-l6-bf16")
TINY = dict(num_layers=2, hidden_size=64, num_attention_heads=4, head_dim=16,
            intermediate_size=176, vocab_size=512, seq_len=32)
SEED = 2 ** 31 + 17


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "ouro_" + name, os.path.join(CFG_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MODEL, REFERENCE = _load("model"), _load("reference")


def tiny_cfg(passes=4):
    cfg = json.load(open(os.path.join(CFG_DIR, "config.json")))
    cfg.update(TINY, total_ut_steps=passes)
    return cfg


def tokens(cfg, n_batches=3, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    rows = [rng.integers(0, cfg["vocab_size"], (batch, cfg["seq_len"] + 1),
                         dtype=np.int32) for _ in range(n_batches)]
    return [(r[:, :-1].copy(), r[:, 1:].copy()) for r in rows]


def tiny_net(passes=4, **kw):
    cfg = tiny_cfg(passes)
    return zoo.Ouro(num_layers=2, hidden_size=64, num_heads=4, head_dim=16,
                    intermediate_size=176, vocab_size=512,
                    total_ut_steps=passes, seq_len=32, **kw), cfg


# ------------------------------------------------ program against reference
@pytest.mark.parametrize("passes", [4, 1])
def test_fit_agrees_with_the_plain_reference_in_float32(passes):
    """Three losses, the first gradients and the parameters' changes of
    ``net.fit`` against ``refnn.train_steps`` over ``reference.py``, from
    the same seeded weights and batches."""
    cfg = tiny_cfg(passes)
    spec = MODEL.param_spec(cfg)
    net = MODEL.build(cfg, make_weights(spec, SEED))
    batches = tokens(cfg)
    losses, first_m = [], None
    for x, y in batches:
        net.fit(DataSet(x, y))
        losses.append(float(net.score()))
        if first_m is None:
            first_m = jax.device_get(MODEL.read_leaves(net, "m"))
    beta1 = cfg["updater"]["beta1"]
    start = make_weights(spec, SEED)
    after = MODEL.read_leaves(net, "params")
    program = {
        "losses": losses,
        "first_grads": {k: v / (1 - beta1) for k, v in first_m.items()},
        "change_norms": {k: float(jnp.linalg.norm(after[k] - start[k]))
                         for k in start}}
    hp = {k: v for k, v in cfg["updater"].items() if k != "kind"}
    ref = refnn.train_steps(REFERENCE.make_loss(cfg), make_weights(spec, SEED),
                            batches, hp)
    ref["change_norms"] = {k: float(jnp.linalg.norm(ref["params"][k]
                                                    - start[k]))
                           for k in start}
    ref["first_grads"] = jax.device_get(ref["first_grads"])
    nums = compare.numbers(program, ref)
    assert nums["loss1_gap"] < 1e-5 and nums["loss3_gap"] < 1e-3, nums
    assert nums["grad_gap"] < 1e-4 and nums["graddir_gap"] < 1e-4, nums
    assert nums["change_gap"] < 1e-2, nums


# ------------------------------------------------------- one set of weights
def test_a_looped_weight_exists_once_and_its_gradient_sums_its_uses():
    zoo_model, cfg = tiny_net(4)
    net = zoo_model.init()
    n_layer = MODEL.layer_matmul_params(cfg) + 4 * 64
    assert net.numParams() == 2 * 512 * 64 + 2 * n_layer + 64 + 64 + 1
    assert int(net.params().shape[0]) == net.numParams()
    assert "l0_attn (CausalSelfAttentionLayer)" in net.summary()
    assert net.summary().count("l0_attn (") == 1
    x, y = tokens(cfg, 1)[0]
    ins, labels = {"tokens": jnp.asarray(x)}, [jnp.asarray(y)]
    key = jax.random.PRNGKey(0)

    def loss(params):
        return net._loss_and_reg(params, net._states, ins, labels, True, key,
                                 None, None)[0]
    whole = jax.grad(loss)(net._params)["l1_mlp"]["Wd"]

    # the same graph with the body written out once a pass: four copies of
    # every layer, each holding the looped layer's weights
    unrolled = _unrolled_copy(net, 4)
    params4 = {name: net._params[name.split("@")[0]]
               for name in unrolled._params}

    def loss4(params):
        return unrolled._loss_and_reg(params, unrolled._states, ins, labels,
                                      True, key, None, None)[0]
    assert float(loss4(params4)) == pytest.approx(float(loss(net._params)),
                                                  rel=1e-6)
    per_use = jax.grad(loss4)(params4)
    uses = [per_use[f"l1_mlp@{t}"]["Wd"] for t in range(4)]
    assert all(float(jnp.linalg.norm(u)) > 0 for u in uses)
    np.testing.assert_allclose(whole, sum(uses), rtol=2e-4, atol=1e-7)


def _unrolled_copy(net, passes):
    """``net``'s graph with no loop: the body's nodes repeated a pass, as
    ``name@t``, the head reading the passes through a tuple vertex."""
    from deeplearning4j_tpu.nn.graph import GraphVertex

    class Passes(GraphVertex):
        def apply(self, *xs):
            return tuple(xs)

        def output_type(self, *its):
            return InputType(its[0].kind, **{**its[0].dims,
                                             "passes": len(its)})
    conf = net.conf
    g = NeuralNetConfiguration.Builder().seed(conf.base.seed) \
        .updater(conf.base.updater).graphBuilder()
    g.addInputs("tokens")
    g.setInputTypes(conf.input_types["tokens"])
    g.addLayer("embed", copy.deepcopy(conf.node_by_name["embed"].obj),
               "tokens")
    carried, outs = "embed", []
    for t in range(passes):
        rename = lambda r: carried if r == "ut" else f"{r}@{t}"  # noqa: E731
        for node in conf.loop_bodies["ut"]:
            ins = [rename(r) for r in node.inputs]
            if node.kind == "layer":
                g.addLayer(f"{node.name}@{t}", copy.deepcopy(node.obj), *ins)
            else:
                g.addVertex(f"{node.name}@{t}", copy.deepcopy(node.obj), *ins)
        carried = f"fnorm@{t}"
        outs.append(carried)
    g.addVertex("passes", Passes(), *outs)
    g.addLayer("lm", copy.deepcopy(conf.node_by_name["lm"].obj), "passes")
    g.setOutputs("lm")
    out = ComputationGraph(g.build()).init()
    out._states["lm"] = net._states["lm"]
    return out


def test_one_pass_is_the_same_layers_stacked_plainly_bit_for_bit():
    zoo_model, cfg = tiny_net(1)
    looped = zoo_model.init()
    plain = _unrolled_copy(looped, 1)
    for name in plain._params:
        plain._params[name] = jax.tree_util.tree_map(
            jnp.array, looped._params[name.split("@")[0]])
    for x, y in tokens(cfg, 3):
        looped.fit(DataSet(x, y))
        plain.fit(DataSet(x, y))
        assert float(looped.score()) == float(plain.score())
    for name in plain._params:
        for leaf, a in plain._params[name].items():
            np.testing.assert_array_equal(
                a, looped._params[name.split("@")[0]][leaf])


@pytest.mark.parametrize("block", [8, 512])
def test_rematerialised_and_plain_step_give_the_same_values(block,
                                                            monkeypatch):
    # the attention core in four query blocks, and in one
    monkeypatch.setattr(attention_ops, "CAUSAL_QUERY_BLOCK", block)
    zoo_model, cfg = tiny_net(4)
    net = zoo_model.init()
    x, y = tokens(cfg, 1)[0]
    ins, labels = {"tokens": jnp.asarray(x)}, [jnp.asarray(y)]
    key = jax.random.PRNGKey(3)

    def loss(params, remat):
        return net._loss_and_reg(params, net._states, ins, labels, True, key,
                                 None, None, remat=remat)[0]
    plain = jax.jit(jax.value_and_grad(lambda p: loss(p, False)))(net._params)
    remat = jax.jit(jax.value_and_grad(lambda p: loss(p, True)))(net._params)
    assert float(plain[0]) == pytest.approx(float(remat[0]), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(plain[1]),
                    jax.tree_util.tree_leaves(remat[1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
    # and the train step asks for it: its program runs forward work again
    text = jax.jit(jax.grad(lambda p: loss(p, True))).lower(
        net._params).compile().as_text()
    assert stepprogram.REMAT_MARK in text
    text = jax.jit(jax.grad(lambda p: loss(p, False))).lower(
        net._params).compile().as_text()
    assert "/" + stepprogram.REMAT_MARK + "/dl4j_L" not in text


def test_the_body_is_cut_where_one_value_goes_on():
    net = tiny_net(4)[0].conf_builder()
    segs = [[n.name for n in seg] for seg in net.conf.loop_segments["ut"]]
    assert segs == [["l0_n1", "l0_attn", "l0_n2", "l0_add1"],
                    ["l0_n3", "l0_mlp", "l0_n4", "l0_add2"],
                    ["l1_n1", "l1_attn", "l1_n2", "l1_add1"],
                    ["l1_n3", "l1_mlp", "l1_n4", "l1_add2"], ["fnorm"]]
    names = [n.name for n in net.conf.topo]
    assert names.index("ut") < names.index("l0_n1") < names.index("fnorm") \
        < names.index("lm")
    assert net.conf.types["ut"].dims["passes"] == 4
    assert net.conf.types["fnorm"] == InputType.recurrent(64, 32)


# ------------------------------------------------------------ serialisation
def test_json_round_trip_keeps_the_loop():
    net = tiny_net(4)[0].init()
    text = net.conf.to_json()
    conf = ComputationGraphConfiguration.from_json(text)
    assert conf.to_json() == text
    assert [n.name for n in conf.topo] == [n.name for n in net.conf.topo]
    assert conf.node_by_name["ut"].obj.steps == 4
    assert conf.node_by_name["ut"].obj.output == "fnorm"
    assert conf.node_by_name["l1_mlp"].loop == "ut"
    assert isinstance(conf.node_by_name["l0_attn"].obj,
                      L.CausalSelfAttentionLayer)
    assert conf.node_by_name["l0_attn"].obj.rope_theta == 1e6
    again = ComputationGraph(conf).init()
    again._params = net._params
    cfg = tiny_cfg()
    x, y = tokens(cfg, 1)[0]
    assert again.score(DataSet(x, y)) == net.score(DataSet(x, y))


def test_save_and_load_keep_weights_updater_state_and_loss(tmp_path):
    zoo_model, cfg = tiny_net(4)
    net = zoo_model.init()
    batches = tokens(cfg, 3)
    net.fit(DataSet(*batches[0]))
    path = str(tmp_path / "ouro.zip")
    net.save(path)
    loaded = ComputationGraph.load(path)
    assert loaded.numParams() == net.numParams()
    net.fit(DataSet(*batches[1]))
    loaded.fit(DataSet(*batches[1]))
    assert float(loaded.score()) == pytest.approx(float(net.score()),
                                                  rel=1e-6)


def test_a_sharding_rule_sees_a_looped_weight_once():
    """``ShardedTrainingPlan`` names a leaf ``<node>/<param>``: a looped
    layer is one node, so one regex match shards all its uses; the
    updater's state follows the same tree. (``nn/transfer.py`` freezes by
    layer index in a MultiLayerNetwork and has no graph API to see.)"""
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.distributed.gspmd import ShardedTrainingPlan
    from deeplearning4j_tpu.parallel.mesh import DeviceMesh
    net = tiny_net(4)[0].init()
    net._ensure_opt_state()
    plan = ShardedTrainingPlan(
        DeviceMesh.create(data=2, model=4),
        rules={r"^l\d+_attn/W[qkv]$": (None, "model"),
               r"^l\d+_mlp/Wd$": ("model", None)})
    sh = plan.param_shardings(net)
    assert sh["l1_attn"]["Wq"].spec == P(None, "model")
    assert sh["l1_attn"]["Wo"].spec == P()
    assert sh["l0_mlp"]["Wd"].spec == P("model", None)
    assert sh["lm"]["W"].spec == P()
    assert sorted(sh) == sorted(net._params)
    opt = plan.opt_shardings(net)
    assert opt["l1_attn"]["Wq"]["m"].spec == P(None, "model")
    leaves = jax.tree_util.tree_leaves(net._params)
    assert sum(int(np.prod(a.shape)) for a in leaves) == net.numParams()


# ------------------------------------------------------------- the builder
def _builder():
    return NeuralNetConfiguration.Builder().updater(updaters.Adam(1e-3)) \
        .graphBuilder().addInputs("x") \
        .setInputTypes(InputType.recurrent(8, 5))


def test_a_loop_has_to_be_closed_and_cannot_nest():
    g = _builder().beginLoop("loop", "x", steps=2)
    with pytest.raises(ValueError, match="never closed"):
        g.build()
    with pytest.raises(ValueError, match="do not nest"):
        g.beginLoop("inner", "loop", steps=2)
    with pytest.raises(ValueError, match="no loop is open"):
        _builder().endLoop("x")
    with pytest.raises(ValueError, match="steps must be >= 1"):
        LoopVertex(0)


def test_a_pass_has_to_hand_on_the_type_it_took():
    g = _builder().beginLoop("loop", "x", steps=2) \
        .addLayer("wide", L.GatedMLP(nOut=16, nHidden=12), "loop") \
        .endLoop("wide") \
        .addLayer("out", L.LoopedLMOutputLayer(nOut=4), "loop") \
        .setOutputs("out")
    with pytest.raises(ValueError, match="hand on the type it took"):
        g.build()


def test_nothing_outside_reads_a_body_node_but_through_the_loop():
    g = _builder().beginLoop("loop", "x", steps=2) \
        .addLayer("mlp", L.GatedMLP(nHidden=12), "loop").endLoop("mlp") \
        .addLayer("out", L.LoopedLMOutputLayer(nOut=4), "mlp") \
        .setOutputs("out")
    with pytest.raises(ValueError, match="unresolved"):
        g.build()


def test_pass_vertex_hands_one_pass_to_an_ordinary_layer():
    g = _builder().beginLoop("loop", "x", steps=3) \
        .addLayer("mlp", L.GatedMLP(nHidden=12), "loop") \
        .addVertex("add", ElementWiseVertex("Add"), "loop", "mlp") \
        .endLoop("add") \
        .addVertex("last", PassVertex(-1), "loop") \
        .addLayer("out", L.RnnOutputLayer(nOut=4, lossFunction="mcxent"),
                  "last").setOutputs("out")
    net = ComputationGraph(g.build()).init()
    x = np.random.default_rng(0).normal(size=(2, 8, 5)).astype(np.float32)
    out = net.output(x)
    assert out.shape == (2, 4, 5)
    # by hand: three times h <- h + mlp(h), feature-last
    p = net._params["mlp"]
    h = jnp.swapaxes(jnp.asarray(x), 1, 2)
    for _ in range(3):
        h = h + (jax.nn.silu(h @ p["Wg"]) * (h @ p["Wu"])) @ p["Wd"]
    z = h @ net._params["out"]["W"] + net._params["out"]["b"]
    np.testing.assert_allclose(out, jnp.swapaxes(jax.nn.softmax(z), 1, 2),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="one activation a"):
        net.feedForward(x)


# --------------------------------------------------------- hand-written cases
def test_rotary_embedding_against_a_hand_written_case():
    x = jnp.arange(2 * 3 * 1 * 4, dtype=jnp.float32).reshape(1, 3, 2, 4)[
        :, :, :1] + 1.0                                  # [1, 3, 1, 4]
    out = np.asarray(attention_ops.rotary_embedding(x, theta=100.0))
    for pos in range(3):
        a, b, c, d = np.asarray(x)[0, pos, 0]
        # pairs (0, 2) and (1, 3); angles pos * 100^(0) and pos * 100^(-1/2)
        t0, t1 = pos * 1.0, pos * 0.1
        want = [a * np.cos(t0) - c * np.sin(t0), b * np.cos(t1) - d * np.sin(t1),
                c * np.cos(t0) + a * np.sin(t0), d * np.cos(t1) + b * np.sin(t1)]
        np.testing.assert_allclose(out[0, pos, 0], want, rtol=1e-5, atol=1e-6)
    # a rotation: norms kept, position 0 untouched
    np.testing.assert_allclose(np.linalg.norm(out, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-6)
    np.testing.assert_array_equal(out[0, 0], np.asarray(x)[0, 0])
    ref = REFERENCE.rope(x[0], 100.0)
    np.testing.assert_allclose(out[0], ref, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("block", [4, 16])
def test_causal_attention_masks_the_future(block, monkeypatch):
    monkeypatch.setattr(attention_ops, "CAUSAL_QUERY_BLOCK", block)
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 16, 2, 8)), jnp.float32)
               for _ in range(3))
    attn = jax.jit(lambda *a: attention_ops.causal_attention(*a))
    out = attn(q, k, v)
    # by hand, one head and one row at a time
    for h in range(2):
        for t in range(16):
            s = np.asarray(q[0, t, h]) @ np.asarray(k[0, :t + 1, h]).T / 8 ** 0.5
            w = np.exp(s - s.max())
            w /= w.sum()
            np.testing.assert_allclose(out[0, t, h],
                                       w @ np.asarray(v[0, :t + 1, h]),
                                       rtol=1e-4, atol=1e-5)
    # a later token changes nothing before it
    k2 = k.at[0, 9].set(100.0)
    out2 = attn(q, k2, v)
    np.testing.assert_array_equal(out[0, :9], out2[0, :9])
    assert not np.allclose(out[0, 9:], out2[0, 9:])
    # the same values and gradients inside a rematerialised call as outside
    loss = lambda q, k, v: jnp.sum(attention_ops.causal_attention(q, k, v) ** 2)
    g1 = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(jax.checkpoint(lambda *a: loss(*a)),
                          argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        out, jax.checkpoint(
            lambda *a: attention_ops.causal_attention(*a))(q, k, v),
        rtol=1e-5, atol=1e-6)


def _plain_attention(q, k, v):
    """The whole square in float32: scores, -inf above the diagonal,
    softmax, weighted sum."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads, size", [(2, 8), (4, 16)])
@pytest.mark.parametrize("one_group", [True, False])
@pytest.mark.parametrize("T, path", [(32, "blocked"), (30, "single"),
                                     (8, "single")])
def test_the_causal_pair_against_a_plain_float32_attention(
        T, path, one_group, heads, size, dtype, monkeypatch):
    """Values and ``dq, dk, dv`` of the hand-written forward and backward
    against autodiff of the plain square: a T of four blocks, one that is
    no multiple of the block and one of one block; all heads at once and
    a head a group."""
    monkeypatch.setattr(attention_ops, "CAUSAL_QUERY_BLOCK", 8)
    if not one_group:
        blk = 8 if path == "blocked" else T
        monkeypatch.setattr(attention_ops, "CAUSAL_SCORE_BYTES",
                            4 * 2 * blk * T)
    assert attention_ops._causal_plan(2, T, heads) \
        == (8 if path == "blocked" else T, heads if one_group else 1)
    rng = np.random.default_rng(T + heads)
    q, k, v, do = (jnp.asarray(rng.normal(size=(2, T, heads, size)), dtype)
                   for _ in range(4))
    before = attention_ops._CORE_LOWERED.labels(path).value

    def both(fn, q, k, v, do):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(do.astype(out.dtype))
    out, *grads = jax.jit(lambda *a: both(
        attention_ops.causal_attention, *a))(q, k, v, do)
    assert attention_ops._CORE_LOWERED.labels(path).value == before + 1
    want, *want_grads = jax.jit(lambda *a: both(_plain_attention, *a))(
        q, k, v, do)
    assert out.dtype == q.dtype and all(g.dtype == q.dtype for g in grads)
    # bfloat16: what the autodiff path this pair replaced met on the same
    # cases (largest gaps 0.012, 0.016, 0.016, 0.031)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" \
        else dict(rtol=2e-2, atol=5e-2)
    for got, ref in zip([out] + grads, [want] + want_grads):
        np.testing.assert_allclose(np.asarray(got, np.float32), ref, **tol)


def test_the_causal_pair_passes_check_grads_and_keeps_nothing_square():
    from jax.test_util import check_grads
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 12, 2, 4)), jnp.float32)
               for _ in range(3))
    for block in (4, 16):       # three blocks, and one
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(attention_ops, "CAUSAL_QUERY_BLOCK", block)
            check_grads(lambda *a: attention_ops.causal_attention(*a),
                        (q, k, v), order=1, modes=("rev",), atol=1e-2,
                        rtol=1e-2, eps=1e-3)
    # what the backward keeps: q, k, v, O and the row log-sum-exp
    T = 64
    q, k, v = (jnp.zeros((1, T, 2, 4), jnp.float32) for _ in range(3))
    kept = jax.tree_util.tree_leaves(jax.vjp(
        lambda *a: attention_ops.causal_attention(*a), q, k, v)[1])
    assert sorted(a.size for a in kept) == [2 * T] + [T * 2 * 4] * 4


def test_exit_distribution_sums_to_one_and_matches_the_formula():
    gates = jnp.asarray(np.random.default_rng(2).normal(size=(4, 3, 5)) * 3,
                        jnp.float32)
    p = np.exp(np.asarray(L.exit_log_distribution(gates)))
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    lam = 1 / (1 + np.exp(-np.asarray(gates, np.float64)))
    want = [lam[0], lam[1] * (1 - lam[0]),
            lam[2] * (1 - lam[0]) * (1 - lam[1]),
            (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])]
    np.testing.assert_allclose(p, np.stack(want), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(REFERENCE.exit_distribution(jnp.asarray(lam)),
                               np.stack(want), rtol=1e-5, atol=1e-7)
    # one pass: everything exits there; saturated gates stay finite
    assert np.exp(np.asarray(L.exit_log_distribution(gates[:1]))).min() == 1.0
    far = L.exit_log_distribution(jnp.full((4, 1), 200.0))
    assert np.isfinite(np.asarray(far)[0]).all()


def test_the_output_layer_takes_integer_labels_and_never_keeps_two_logits():
    layer = L.LoopedLMOutputLayer(nOut=16, beta=0.1)
    layer.set_defaults(NeuralNetConfiguration())
    layer.infer_nin(InputType("rnn", size=8, timesteps=4, passes=2))
    params, state = layer.initialize(jax.random.PRNGKey(0))
    assert set(state) == {"exit_mass", "pass_loss"} \
        and state["exit_mass"].shape == (2,)
    rng = np.random.default_rng(0)
    h = tuple(jnp.asarray(rng.normal(size=(3, 4, 8)), jnp.float32)
              for _ in range(2))
    y = jnp.asarray(rng.integers(0, 16, (3, 4)), jnp.int32)
    loss, aux = layer.loss_from(params, h, y)
    ces, lams = [], []
    for a in h:
        logp = jax.nn.log_softmax(a @ params["W"])
        ces.append(-jnp.take_along_axis(logp, y[..., None], -1)[..., 0])
        lams.append(jax.nn.sigmoid(a @ params["gate_w"] + params["gate_b"][0]))
    p = jnp.stack([lams[0], 1 - lams[0]])
    want = jnp.mean(p[0] * ces[0] + p[1] * ces[1]
                    + 0.1 * jnp.sum(p * jnp.log(p), axis=0))
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_allclose(aux["exit_mass"], p.mean(axis=(1, 2)),
                               rtol=1e-5)
    np.testing.assert_allclose(aux["pass_loss"],
                               [float(c.mean()) for c in ces], rtol=1e-5)
    # a masked position carries no loss
    mask = jnp.ones((3, 4)).at[0, 0].set(0.0)
    masked, _ = layer.loss_from(params, h, y, mask=mask)
    y2 = y.at[0, 0].set((int(y[0, 0]) + 1) % 16)
    assert float(layer.loss_from(params, h, y2, mask=mask)[0]) \
        == pytest.approx(float(masked), rel=1e-6)
    # under bf16 the logits stay float32 and the master head float32
    hb = tuple(a.astype(jnp.bfloat16) for a in h)
    grads = jax.grad(lambda p: layer.loss_from(p, hb, y)[0])(params)
    assert grads["W"].dtype == jnp.float32
    assert layer.apply(params, state, hb, False, None)[0].dtype == jnp.float32
    with pytest.raises(ValueError, match="from its input"):
        layer.compute_loss(y, None)


def _plain_cross_entropy(hs, w, labels):
    """Float32 ``log_softmax`` of every pass's whole [T, nOut] logits."""
    logp = jax.nn.log_softmax(jnp.einsum(
        "pntd,dv->pntv", jnp.stack(hs).astype(jnp.float32), w,
        precision="highest"))
    return -jnp.take_along_axis(logp, labels[None, ..., None], -1)[..., 0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_out, block, path", [
    (48, 16, "blocked"), (48, 64, "single"), (40, 16, "single")])
def test_the_blocked_head_against_a_plain_float32_log_softmax(
        n_out, block, path, dtype, monkeypatch):
    """``ce``, every pass's ``dh`` and the summed ``dW`` of the
    hand-written pair against autodiff of the plain log-softmax: a
    vocabulary of three blocks, of one, and one that is no multiple of the
    block, two passes; the counter says which path, once a traced call."""
    P, N, T, D = 2, 2, 6, 8
    monkeypatch.setattr(L, "HEAD_LOGIT_BYTES", 4 * N * T * block)
    assert L._head_tile(N * T, n_out) == (
        N * T, block if path == "blocked" else n_out)
    rng = np.random.default_rng(n_out + block)
    hs = tuple(jnp.asarray(rng.normal(size=(N, T, D)), dtype)
               for _ in range(P))
    w = jnp.asarray(rng.normal(size=(D, n_out)), jnp.float32)
    y = jnp.asarray(rng.integers(0, n_out, (N, T)), jnp.int32)
    g = jnp.asarray(rng.normal(size=(P, N, T)), jnp.float32)
    before = {p: L._HEAD_LOWERED.labels(p).value
              for p in ("blocked", "single")}

    def both(fn, hs, w, y, g):
        ce, vjp = jax.vjp(lambda hs, w: fn(hs, w, y), hs, w)
        return (ce,) + vjp(g)
    fn = jax.jit(lambda *a: both(L.blocked_cross_entropy, *a))
    ce, dhs, dw = fn(hs, w, y, g)
    fn(hs, w, y, g)                 # a second call traces nothing
    other = "single" if path == "blocked" else "blocked"
    assert L._HEAD_LOWERED.labels(path).value == before[path] + 1
    assert L._HEAD_LOWERED.labels(other).value == before[other]
    assert ce.dtype == dw.dtype == jnp.float32 and ce.shape == (P, N, T)
    assert all(dh.dtype == h.dtype for dh, h in zip(dhs, hs))
    want_ce, want_dhs, want_dw = jax.jit(
        lambda *a: both(_plain_cross_entropy, *a))(hs, w, y, g)
    # bfloat16: the operands of the three products are rounded, as the
    # path this pair replaced rounded them
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" \
        else dict(rtol=3e-2, atol=6e-2)
    for got, ref in zip((ce, dw) + dhs, (want_ce, want_dw) + want_dhs):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32), **tol)


def test_the_blocked_head_passes_check_grads_and_keeps_nothing_of_the_logits():
    from jax.test_util import check_grads
    rng = np.random.default_rng(5)
    P, N, T, D, V = 2, 1, 6, 4, 24
    hs = tuple(jnp.asarray(rng.normal(size=(N, T, D)), jnp.float32)
               for _ in range(P))
    w = jnp.asarray(rng.normal(size=(D, V)), jnp.float32)
    y = jnp.asarray(rng.integers(0, V, (N, T)), jnp.int32)
    for block in (8, 32):       # three blocks, and one
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(L, "HEAD_LOGIT_BYTES", 4 * N * T * block)
            check_grads(lambda hs, w: L.blocked_cross_entropy(hs, w, y),
                        (hs, w), order=1, modes=("rev",), atol=1e-2,
                        rtol=1e-2, eps=1e-3)
    # what the backward keeps: each pass's h, W, the labels and the row
    # log-sum-exp of every pass
    T, V = 16, 64
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(L, "HEAD_LOGIT_BYTES", 4 * T * 16)
        kept = jax.tree_util.tree_leaves(jax.vjp(
            lambda hs, w: L.blocked_cross_entropy(
                hs, w, jnp.zeros((1, T), jnp.int32)),
            (jnp.zeros((1, T, D)),) * P, jnp.zeros((D, V)))[1])
    assert sorted(a.size for a in kept) == [T, P * T, T * D, T * D, D * V]
    assert all(a.size < T * V for a in kept)


@pytest.mark.parametrize("passes", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("table", [True, False], ids=["table", "head"])
@pytest.mark.parametrize("N, T, n_out, tiles", [
    (2, 24, 24, (3, 3)), (2, 24, 12, (3, 1)), (2, 20, 24, (3, 3))],
    ids=["rows_x_columns", "rows_x_one_column", "a_short_last_row_block"])
def test_the_tiled_head_against_a_plain_float32_log_softmax(
        N, T, n_out, tiles, table, dtype, passes, monkeypatch):
    """Beyond a tile's rows the positions go in row blocks, each over its
    vocabulary blocks: ``ce``, every pass's ``dh`` and the summed ``dw``
    against autodiff of the plain log-softmax, for three row blocks of 16
    over three column blocks of 8, over one block of 12 columns that is no
    multiple of 8, and where the last row block holds 8 rows; under a head
    [nIn, nOut] and an embedding's table [nOut, nIn]; the counter says
    ``tiled`` once a traced call."""
    from jax.test_util import check_grads
    D = 8
    monkeypatch.setattr(L, "HEAD_LOGIT_BYTES", 4 * 128)
    rows, block = L._head_tile(N * T, n_out)
    assert (rows, block) == (16, n_out // tiles[1])
    assert (-(-N * T // rows), n_out // block) == tiles
    rng = np.random.default_rng(N * T + n_out)
    hs = tuple(jnp.asarray(rng.normal(size=(N, T, D)), dtype)
               for _ in range(passes))
    w = jnp.asarray(rng.normal(size=(D, n_out)), jnp.float32)
    y = jnp.asarray(rng.integers(0, n_out, (N, T)), jnp.int32)
    g = jnp.asarray(rng.normal(size=(passes, N, T)), jnp.float32)
    before = {p: L._HEAD_LOWERED.labels(p).value
              for p in ("tiled", "blocked", "single")}

    def tiled(hs, w, y):
        return L.blocked_cross_entropy(hs, w.T if table else w, y,
                                       table=table)

    def both(fn, hs, w, y, g):
        ce, vjp = jax.vjp(lambda hs, w: fn(hs, w, y), hs, w)
        return (ce,) + vjp(g)
    fn = jax.jit(lambda *a: both(tiled, *a))
    ce, dhs, dw = fn(hs, w, y, g)
    fn(hs, w, y, g)                 # a second call traces nothing
    assert {p: L._HEAD_LOWERED.labels(p).value - n
            for p, n in before.items()} \
        == {"tiled": 1, "blocked": 0, "single": 0}
    assert ce.dtype == dw.dtype == jnp.float32 \
        and ce.shape == (passes, N, T)
    assert all(dh.dtype == h.dtype and dh.shape == h.shape
               for dh, h in zip(dhs, hs))
    want_ce, want_dhs, want_dw = jax.jit(
        lambda *a: both(_plain_cross_entropy, *a))(hs, w, y, g)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" \
        else dict(rtol=3e-2, atol=6e-2)
    for got, ref in zip((ce, dw) + dhs, (want_ce, want_dw) + want_dhs):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32), **tol)
    if dtype == "float32":
        check_grads(lambda hs, w: tiled(hs, w, y), (hs, w), order=1,
                    modes=("rev",), atol=1e-2, rtol=1e-2, eps=1e-3)


def _values(jaxpr):
    """Every value a jaxpr's equations make, those of the jaxprs they
    call among them."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _values(inner)


def test_a_tile_is_rows_by_columns_and_dh_is_summed_a_row_block_at_a_time():
    """The rule alone, at the three cells' shapes: 4,096 positions are one
    row block over 2,048-column blocks (what the two cells at one sequence
    lowered to before there were row blocks), 4 x 8,192 positions go in
    tiles of 4,096 x 2,048. At a tiled shape nothing the backward rule
    makes is a float32 value of rows x nIn elements: ``dh`` is summed a
    row block at a time and rounded before the blocks are joined."""
    assert L._head_tile(4096, 49152) == (4096, 2048)
    assert L._head_tile(4096, 16384) == (4096, 2048)
    assert L._head_tile(4 * 8192, 8192) == (4096, 2048)
    assert L._head_tile(3 * 4096 + 8, 8192)[0] == 4096
    N, T, D, V = 2, 64, 16, 64
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(L, "HEAD_LOGIT_BYTES", 4 * 32 * 16)
        assert L._head_tile(N * T, V) == (32, 16)
        lowered = L._HEAD_LOWERED.labels("tiled").value
        h = jnp.zeros((N, T, D), jnp.bfloat16)
        _, vjp = jax.vjp(
            lambda h, w: L.blocked_cross_entropy(
                (h,), w, jnp.zeros((N, T), jnp.int32), table=True),
            h, jnp.zeros((V, D)))
        made = list(_values(jax.make_jaxpr(vjp)(
            jnp.zeros((1, N, T))).jaxpr))
    assert L._HEAD_LOWERED.labels("tiled").value == lowered + 1
    sizes = {(str(a.dtype), a.size) for a in made}
    assert ("float32", 32 * D) in sizes and ("float32", V * D) in sizes
    assert ("bfloat16", N * T * D) in sizes
    assert ("float32", N * T * D) not in sizes


#: sha256 of the head's forward + backward lowering at 4,096 positions,
#: (passes, T, nIn, nOut, table), taken from the tree before there were row
#: blocks (commit 577310b) under this installation's jax: with all rows in
#: one block the rule is that program, which is why ``_ce_bwd`` indexes a
#: pass's ``lses`` and ``gs`` a tile at a time
_PARENT_TEXT = {(2, 4096, 64, 8192, False): "60b4d8adb1715df9",
                (1, 4096, 64, 4096, True): "286aa5e4e75285fd"}


@pytest.mark.parametrize("shape", sorted(_PARENT_TEXT))
def test_one_row_block_lowers_to_the_text_before_there_were_row_blocks(shape):
    import hashlib
    P, T, D, V, table = shape
    assert L._head_tile(T, V) == (T, 2048)

    def f(hs, w, y):
        return jax.value_and_grad(lambda hs, w: jnp.sum(
            L.blocked_cross_entropy(hs, w, y, table=table)), (0, 1))(hs, w)
    text = jax.jit(f).lower(
        (jax.ShapeDtypeStruct((1, T, D), jnp.bfloat16),) * P,
        jax.ShapeDtypeStruct((V, D) if table else (D, V), jnp.float32),
        jax.ShapeDtypeStruct((1, T), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _PARENT_TEXT[shape]


def test_a_sequence_layer_refuses_the_public_layout():
    norm = L.RMSNorm()
    norm.infer_nin(InputType.recurrent(8, 5))
    params, _ = norm.initialize(None)
    with pytest.raises(ValueError, match="computes feature-last"):
        norm.apply(params, {}, jnp.zeros((2, 8, 5)), False, None)
    out, _ = norm.apply(params, {}, jnp.full((2, 5, 8), 3.0), False, None)
    np.testing.assert_allclose(out, 1.0, rtol=1e-5)
    with pytest.raises(ValueError, match="needs nHidden"):
        L.GatedMLP()
    attn = L.CausalSelfAttentionLayer(nHeads=3)
    with pytest.raises(ValueError, match="does not divide"):
        attn.infer_nin(InputType.recurrent(8, 5))
    with pytest.raises(TypeError, match="did you mean 'ropeTheta'"):
        L.CausalSelfAttentionLayer(ropeTeta=1.0)


# ------------------------------------------------------------ the instruments
def test_the_step_program_says_pass_part_and_remat():
    net = tiny_net(4)[0].init()
    cfg = tiny_cfg()
    profiler.set_profiling_mode("basic")
    try:
        stepprogram.clear()
        tokens_before = stepping.TRAIN_TOKENS.value
        net.fit(DataSet(*tokens(cfg, 1)[0]))
        assert stepping.TRAIN_TOKENS.value - tokens_before == 2 * 32
        mass = [stepping.LOOP_EXIT_MASS.labels(str(t)).value
                for t in range(1, 5)]
        assert sum(mass) == pytest.approx(1.0, rel=1e-5)
        assert stepping.LOOP_PASS_LOSS.labels("4").value > 0
        maps = stepprogram.maps()
    finally:
        profiler.set_profiling_mode(None)
        stepprogram.clear()
    entries = [e for m in maps.values() for e in m.values()]
    passes = {e.loop_pass for e in entries}
    assert {1, 2, 3, 4} <= passes
    parts = {e.part for e in entries}
    assert {"attn_core", "head_loss"} <= parts
    assert any(e.remat and e.phase == "backward" for e in entries)
    assert not any(e.remat and e.phase == "forward" for e in entries)
    attn = [e for e in entries if e.part == "attn_core"]
    assert all(e.layer and "_attn" in e.layer and e.loop_pass for e in attn)
    heads = [e for e in entries if e.part == "head_loss"]
    assert all(e.layer == stepprogram.LOSS_SCOPE for e in heads)


@pytest.mark.parametrize("block, path", [(8, "blocked"), (512, "single")])
def test_the_map_marks_the_cores_backward_rule(block, path, monkeypatch):
    """The attention core's backward is a rule written by hand, not the
    transpose of its forward: every product of it must still stand in the
    map as the core's, backward, in its pass; the forward the stretch runs
    again carries the remat mark; and the counter says which path a T of
    32 took with a block that divides it and one that does not."""
    monkeypatch.setattr(attention_ops, "CAUSAL_QUERY_BLOCK", block)
    texts = []
    parse = stepprogram.parse
    monkeypatch.setattr(stepprogram, "parse",
                        lambda text: texts.append(text) or parse(text))
    lowered = {p: attention_ops._CORE_LOWERED.labels(p).value
               for p in ("blocked", "single")}
    net = tiny_net(2)[0].init()
    profiler.set_profiling_mode("basic")
    try:
        stepprogram.clear()
        net.fit(DataSet(*tokens(tiny_cfg(2), 1)[0]))
        maps = stepprogram.maps()
    finally:
        profiler.set_profiling_mode(None)
        stepprogram.clear()
    other = "single" if path == "blocked" else "blocked"
    assert attention_ops._CORE_LOWERED.labels(path).value > lowered[path]
    assert attention_ops._CORE_LOWERED.labels(other).value == lowered[other]
    (entries,), (text,) = maps.values(), texts
    seen = {"forward": 0, "remat": 0, "backward": 0, "rule_only": 0}
    for line in text.split("\n"):
        name, op = stepprogram._INSTRUCTION.match(line), \
            stepprogram._OP_NAME.search(line)
        if not name or not op or " dot(" not in line \
                or stepprogram.ATTN_CORE_SCOPE not in op.group(1) \
                or name.group(1) not in entries:
            continue
        entry, op = entries[name.group(1)], op.group(1)
        assert entry.part == "attn_core" and "_attn" in entry.layer, line
        assert entry.loop_pass == int(
            op[op.index("dl4j_ut") + len("dl4j_ut")]), line
        if "transpose(" not in op:
            assert (entry.phase, entry.remat) == ("forward", False), line
            seen["forward"] += 1
        elif stepprogram.REMAT_MARK in op:
            assert (entry.phase, entry.remat) == ("backward", True), line
            seen["remat"] += 1
        else:
            assert (entry.phase, entry.remat) == ("backward", False), line
            seen["backward"] += 1
            seen["rule_only"] += "bhqk,bqhd->bkhd" in op
    # two passes of two layers, a block: the forward's two products, the
    # same two run again, and the rule's five (dv and dk by the one the
    # forward never runs), of which the compiler may merge the scores with
    # those of the forward run again
    blocks = 32 // block or 1
    assert seen.pop("backward") in (16 * blocks, 20 * blocks)
    assert seen == {"forward": 8 * blocks, "remat": 8 * blocks,
                    "rule_only": 8 * blocks}, seen


def test_the_map_marks_the_heads_backward_rule(monkeypatch):
    """The head's cross-entropy is a forward/backward pair written by hand
    over vocabulary blocks: every op of it must stand in the map as the
    heads', in its pass; the rule's ops, the block's product run again
    among them, are backward work and none is rematerialised (nothing is
    left for a checkpoint to run again), while the stack's stretches still
    are; and the counter reads ``blocked`` once, for the one call site."""
    monkeypatch.setattr(L, "HEAD_LOGIT_BYTES", 4 * 2 * 32 * 128)
    assert L._head_tile(2 * 32, 512) == (2 * 32, 128)
    texts = []
    parse = stepprogram.parse
    monkeypatch.setattr(stepprogram, "parse",
                        lambda text: texts.append(text) or parse(text))
    lowered = {p: L._HEAD_LOWERED.labels(p).value
               for p in ("blocked", "single")}
    net = tiny_net(2)[0].init()
    profiler.set_profiling_mode("basic")
    try:
        stepprogram.clear()
        net.fit(DataSet(*tokens(tiny_cfg(2), 1)[0]))
        maps = stepprogram.maps()
    finally:
        profiler.set_profiling_mode(None)
        stepprogram.clear()
    assert L._HEAD_LOWERED.labels("blocked").value == lowered["blocked"] + 1
    assert L._HEAD_LOWERED.labels("single").value == lowered["single"]
    (entries,), (text,) = maps.values(), texts
    seen = {"forward": 0, "backward": 0, "products": 0}
    for line in text.split("\n"):
        name, op = stepprogram._INSTRUCTION.match(line), \
            stepprogram._OP_NAME.search(line)
        if not name or not op or name.group(1) not in entries:
            continue
        op = op.group(1).partition(";")[0]
        rule = "jit(_ce_bwd_block)" in op
        if not rule and "jit(_ce_fwd_block)" not in op:
            continue
        entry = entries[name.group(1)]
        assert entry.part == "head_loss" \
            and entry.layer == stepprogram.LOSS_SCOPE, line
        # (a product knows its pass; the label's mask is the same in every
        # pass, so the compiler may share it, and the stacking of the
        # passes' results is no pass's)
        if " dot(" in line:
            assert entry.loop_pass == int(
                op[op.index("dl4j_ut") + len("dl4j_ut")]), line
        assert (entry.phase, entry.remat) == (
            "backward" if rule else "forward", False), line
        seen["backward" if rule else "forward"] += 1
        seen["products"] += rule and " dot(" in line
    # two passes of four blocks: the rule's three products a block, of
    # which the compiler may fold some into fusions
    assert seen["forward"] >= 8 and seen["backward"] >= 16, seen
    assert 8 <= seen["products"] <= 24, seen
    heads = [e for e in entries.values() if e.part == "head_loss"]
    assert heads and not any(e.remat for e in heads)
    assert any(e.remat and e.phase == "backward" and e.part != "head_loss"
               for e in entries.values())


def test_marks_of_an_op_name():
    name = ("jit(step)/transpose(jvp(dl4j_ut3))/jvp(dl4j_ut3)/checkpoint/"
            "rematted_computation/dl4j_L7_l0_attn/dl4j_attn_core/dot_general")
    assert stepprogram.marks(name) == (3, "attn_core", True)
    assert stepprogram.classify(name) == ("backward", "dl4j_L7_l0_attn")
    assert stepprogram.marks("jit(step)/jvp(dl4j_L1_conv)/conv") \
        == (None, None, False)
    assert stepprogram.marks(
        "jit(step)/jvp(dl4j_loss)/dl4j_ut1/dl4j_head_loss/dot_general") \
        == (1, "head_loss", False)
    assert stepprogram.Entry("forward", "dl4j_L1_conv", None, False) \
        == stepprogram.Entry("forward", "dl4j_L1_conv", None, False, None,
                             None, False)


def test_the_flop_models_know_the_looped_layers():
    from deeplearning4j_tpu.analysis import graphir
    from deeplearning4j_tpu.profiler import devicetime
    cfg = tiny_cfg()
    net = tiny_net(4)[0].conf_builder()
    ir = graphir.from_graph(net.conf, batch_size=1)
    want = MODEL.flops_per_sample(cfg) \
        + 24 * 0     # nothing else multiplies
    # the static model counts the whole square of the attention core, the
    # benchmark's the causal half it requires
    square = MODEL.layer_applications(cfg) * MODEL.attention_flops(cfg)
    assert ir.total_flops() == pytest.approx(want + square, rel=1e-6)
    rows = {name: f for name, _op, f in devicetime.layer_flop_model(net.conf)}
    assert rows["l0_mlp"] == 4 * 2 * 32 * 3 * 64 * 176
    assert rows["lm"] == 4 * 2 * 32 * 64 * 512
