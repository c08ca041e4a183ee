"""Where a large weight's gradient meets Adam's update: a float32 matrix of
at least ``UPDATE_APART_ELEMENTS`` elements whose products run over more
than ``UPDATE_APART_ROWS`` rows hands its gradient over in the compute
dtype, apart from the product's fusion (``nn.layers._cast_apart``); every
other leaf keeps the fused form and lowers to the text it always did."""

import hashlib
import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.drivers import fit_iterator
from deeplearning4j_tpu import profiler
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.multilayer import _process_and_apply_grads
from deeplearning4j_tpu.train import updaters

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "chipbench", "configs")
LFM2 = "lfm2-24b-a2b-l5-bf16"

#: the hybrid cell's leaves held apart at its 4 x 8,192 rows a step: the
#: dense MLP, the four mixers' two projections, the attention's Wq and Wo
HYBRID_APART = {"l0_mlp/Wg", "l0_mlp/Wu", "l0_mlp/Wd", "l1_attn/Wq",
                "l1_attn/Wo"} | {f"l{i}_conv/{leaf}" for i in (0, 2, 3, 4)
                                 for leaf in ("Win", "Wout")}


def _model(name):
    spec = importlib.util.spec_from_file_location(
        "apart_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(CONFIGS, name, "model.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, json.load(open(os.path.join(CONFIGS, name, "config.json")))


def _counts():
    return {p: L._UPDATE_APART_LOWERED.labels(p).value
            for p in ("apart", "fused")}


# ---------------------------------------------------------------- the rule
@pytest.mark.parametrize("rows", [4096, 16384, 32768])
def test_the_rule_holds_apart_the_hybrid_cells_thirteen_matrices(rows):
    """At 32,768 rows: the dense MLP, the mixers' projections, Wq and Wo;
    never Wk and Wv [2048, 512], the router [2048, 64], the 3-D expert
    stacks or a norm's gain. At 4,096 and 16,384 rows: nothing. (The
    embedding's table meets token ids, not rows of activations: below.)"""
    model, cfg = _model(LFM2)
    apart = {name for name, shape, _k, _f in model.param_spec(cfg)
             if name != "embed/W" and L.update_apart(tuple(shape), rows)}
    assert apart == (HYBRID_APART if rows > 16384 else set())
    shapes = {name: tuple(s) for name, s, _k, _f in model.param_spec(cfg)}
    assert shapes["l1_attn/Wk"] == (2048, 512)
    assert shapes["l1_moe/Wr"] == (2048, 64)
    assert len(shapes["l1_moe/Eg"]) == 3


@pytest.mark.parametrize("shape", [(1024, 1024, 3, 3), (2048, 512, 1, 1),
                                   (8, 2048, 1536), (2048, 1000), (2048,)])
def test_kernels_stacks_small_matrices_and_vectors_stay_fused(shape):
    assert not L.update_apart(shape, 256 * 416 * 416)
    assert L.update_apart((2048, 2048), 16385)
    assert not L.update_apart((2048, 2048), 16384)


def test_policy_cast_counts_each_cast_leaf_and_reads_rows_off_the_input():
    mlp = L.GatedMLP(nHidden=2048)
    mlp.infer_nin(InputType.recurrent(2048, 8192))
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32)
              for k, s in mlp.param_shapes().items()}

    def cast(layer, params, x):
        before = _counts()
        out = jax.eval_shape(
            lambda p, x: L.policy_cast(layer, p, x, jnp.bfloat16), params, x)
        return out, {p: _counts()[p] - before[p] for p in before}

    (p, x), got = cast(mlp, params, jax.ShapeDtypeStruct((4, 8192, 2048),
                                                         jnp.float32))
    assert got == {"apart": 3, "fused": 0}
    assert all(a.dtype == jnp.bfloat16 for a in p.values())
    assert x.dtype == jnp.bfloat16
    _, got = cast(mlp, params, jax.ShapeDtypeStruct((1, 4096, 2048),
                                                    jnp.bfloat16))
    assert got == {"apart": 0, "fused": 3}
    # an embedding's table meets token ids: no rows, the fused form
    embed = L.EmbeddingSequenceLayer(nIn=8192, nOut=2048)
    _, got = cast(embed, {"W": jax.ShapeDtypeStruct((8192, 2048),
                                                    jnp.float32)},
                  jax.ShapeDtypeStruct((4, 8192), jnp.int32))
    assert got == {"apart": 0, "fused": 1}
    # a tied head takes its master as it is, uncast and uncounted
    head = L.MTPLMOutputLayer(nOut=8192, tiedWith="embed")
    (p, _), got = cast(head, {"W": jax.ShapeDtypeStruct((8192, 2048),
                                                        jnp.float32)},
                       jax.ShapeDtypeStruct((4, 8192, 2048), jnp.bfloat16))
    assert got == {"apart": 0, "fused": 0} and p["W"].dtype == jnp.float32
    assert profiler.get_registry().get("dl4j_update_apart_lowered_total") \
        is L._UPDATE_APART_LOWERED


# ------------------------------------------------------------- the numbers
def _mlp_step():
    """One ``GatedMLP`` forward, backward and Adam update of its three
    leaves through the train step's own update path; bf16 compute."""
    mlp = L.GatedMLP(nHidden=48)
    mlp.infer_nin(InputType.recurrent(32, 40))
    adam = updaters.Adam(3e-4, beta1=0.9, beta2=0.95, epsilon=1e-8)
    settings = types.SimpleNamespace(grad_norm=None)

    def step(p, opt, x, dy, t):
        def loss(p):
            cast, xb = L.policy_cast(mlp, p, x, jnp.bfloat16)
            y, _ = mlp.apply(cast, {}, xb, True, None)
            return jnp.sum((y * dy).astype(jnp.float32))
        g = jax.grad(loss)(p)
        return _process_and_apply_grads(settings, adam, p, g, opt, t)

    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    p = {k: 0.2 * jax.random.normal(key, s) for (k, s), key in
         zip(mlp.param_shapes().items(), ks)}
    opt = {k: {"m": 0.01 * jax.random.normal(ks[3], v.shape),
               "v": 1e-4 * jnp.abs(jax.random.normal(ks[4], v.shape))}
           for k, v in p.items()}
    x = jax.random.normal(ks[3], (3, 40, 32), jnp.bfloat16)
    dy = jax.random.normal(ks[4], (3, 40, 32), jnp.bfloat16)
    return step, (p, opt, x, dy, jnp.float32(4.0))


@pytest.mark.parametrize("form", ["apart", "fused"])
def test_one_gated_mlp_step_gives_the_fused_forms_numbers_to_the_bit(
        form, monkeypatch):
    """120 rows over a rule cut to this size: new parameters and both
    moments equal the fused form's bit for bit (on the CPU, which rounds
    the product to its declared bf16 in both forms), and only the apart
    form's text holds the three barriers."""
    step, args = _mlp_step()

    def traced():       # a new function each time: traced again
        return jax.jit(lambda *a: step(*a))
    monkeypatch.setattr(L, "UPDATE_APART_ELEMENTS", 32 * 48)
    monkeypatch.setattr(L, "UPDATE_APART_ROWS", 1 << 62)
    want = traced()(*args)
    fused_text = traced().lower(*args).as_text()
    monkeypatch.setattr(L, "UPDATE_APART_ROWS",
                        100 if form == "apart" else 1 << 62)
    before = _counts()
    text = traced().lower(*args).as_text()
    assert _counts()[form] - before[form] == 3
    got = traced()(*args)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "optimization_barrier" not in fused_text
    assert text.count("optimization_barrier") == (3 if form == "apart"
                                                  else 0)
    assert (text == fused_text) == (form == "fused")


# ------------------------------------------------- the cells' whole steps
class _Abstract:
    """A weight the configurations' ``put_weights`` can index and shape."""

    def __init__(self, shape):
        self.shape, self.dtype = tuple(shape), jnp.float32

    def __getitem__(self, _i):
        return _Abstract(self.shape[1:])


def _lowered_step(name, batch):
    """The cell's whole train step lowered from abstract shapes (no
    weights are made): its text, and the counter's moves."""
    model, cfg = _model(name)
    kw = {}
    if hasattr(model, "state_spec"):
        kw = {"batch": batch, "states": {
            n: jax.ShapeDtypeStruct(tuple(s), jnp.float32)
            for n, s, _k, _f in model.state_spec(cfg)}}
    net = fit_iterator.configure(model.build(
        cfg, {n: _Abstract(s) for n, s, _k, _f in model.param_spec(cfg)},
        chips=1, **kw), cfg)

    def sds(a):
        return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
    params = jax.tree_util.tree_map(
        sds, net._params, is_leaf=lambda a: isinstance(a, _Abstract))
    adam = net.conf.base.updater
    opt = jax.eval_shape(
        lambda p: jax.tree_util.tree_map(adam.init_state, p), params)
    step, dummy = net._step_for(False, 1, 1)
    ids = jax.ShapeDtypeStruct((batch, cfg["seq_len"]), jnp.int32)
    before = _counts()
    text = step._jit.lower(
        params, jax.tree_util.tree_map(sds, net._states), opt,
        jax.ShapeDtypeStruct((), jnp.int32), {net.conf.graph_inputs[0]: ids},
        [ids], [sds(a) for a in dummy]).as_text()
    return text, {p: _counts()[p] - before[p] for p in before}


#: sha256 of the lowered train step of the two cells at 4,096 rows, taken
#: from the tree before the rule existed (commit f620644) under this
#: installation's jax: below the rule's rows every cast is the fused one,
#: and the step is that program
_PARENT_STEP = {"ouro-2.6b-l6-bf16": "d7507d5a4276cda9",
                "xing4.0-29b-a4b-l5-bf16": "9ca4dc52c2ba97af"}


@pytest.mark.parametrize("name, batch, apart", [
    ("ouro-2.6b-l6-bf16", 1, 0), ("xing4.0-29b-a4b-l5-bf16", 1, 0),
    (LFM2, 4, 13)])
def test_the_cells_steps_hold_apart_what_the_rule_says(name, batch, apart):
    """The hybrid cell's step holds its thirteen matrices apart; the two
    cells at 4,096 rows hold none and lower to the parent's text."""
    profiler.set_profiling_mode(None)
    text, moved = _lowered_step(name, batch)
    assert moved["apart"] == apart and moved["fused"] > 0
    if name in _PARENT_STEP:
        assert hashlib.sha256(text.encode()).hexdigest()[:16] \
            == _PARENT_STEP[name]
