"""The step program's own map (``profiler.stepprogram``): which layer and
phase each instruction of a compiled train step belongs to, read from the
program's text — on steps compiled here for the CPU, on hand-written
fusions, and on an excerpt of the ResNet-50 step as compiled for a
described TPU v5e (sandbox compile, batch 256 at 224x224, PR 26)."""

import os

import numpy as np
import pytest

from deeplearning4j_tpu.distributed.gspmd import compiled_train_step_hlo
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.graph import ComputationGraph, ElementWiseVertex
from deeplearning4j_tpu.nn.layers import (BatchNormalization,
                                          ConvolutionLayer, DenseLayer,
                                          GlobalPoolingLayer, OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler import devicetime
from deeplearning4j_tpu.profiler import stepprogram as sp
from deeplearning4j_tpu.train.updaters import Adam

EXCERPT = os.path.join(os.path.dirname(__file__), "fixtures",
                       "resnet50_step.v5e.excerpt.hlo")


# ------------------------------------------------------------- op names
@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(dl4j_L12_conv)/conv_general_dilated",
     ("forward", "dl4j_L12_conv")),
    ("jit(step)/transpose(jvp(dl4j_L12_conv))/conv_general_dilated",
     ("backward", "dl4j_L12_conv")),
    ("jit(step)/dl4j_updater/mul", ("updater", "dl4j_updater")),
    ("jit(step)/jvp(dl4j_loss)/reduce_sum", ("forward", "dl4j_loss")),
    ("jit(step)/transpose(jvp(dl4j_loss))/mul", ("backward", "dl4j_loss")),
    ("jit(step)/dl4j_augment/mul", ("forward", "dl4j_augment")),
    ("jit(fwd)/dl4j_L3_s0b0-c1/add", ("forward", "dl4j_L3_s0b0-c1")),
    ("jit(step)/jvp()/add", ("forward", None)),
    ("jit(step)/transpose(jvp())/mul", ("backward", None)),
    ("jit(step)/add", ("other", None)),
    ("params[27]['W']", ("other", None)),
    ("jit(megastep)/while/body/transpose(jvp(dl4j_L1_bn))/mul",
     ("backward", "dl4j_L1_bn")),
    ("jit(step)/transpose(jvp(dl4j_loss))/mul;jit(step)/dl4j_updater/mul",
     ("backward", "dl4j_loss")),
])
def test_classify(op_name, want):
    assert sp.classify(op_name) == want


# ------------------------------------------------- hand-written fusions
def _module(fused_body, fusion_meta=""):
    return f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {{
  %p0 = f32[8,8]{{1,0}} parameter(0)
  %p1 = f32[8,8]{{1,0}} parameter(1)
{fused_body}
}}

ENTRY %main.9 (a: f32[8,8], b: f32[8,8]) -> f32[8,8] {{
  %a = f32[8,8]{{1,0}} parameter(0), metadata={{op_name="a"}}
  %b = f32[8,8]{{1,0}} parameter(1)
  %copy.1 = f32[8,8]{{0,1}} copy(%a)
  ROOT %fusion.7 = f32[8,8]{{1,0}} fusion(%copy.1, %b), kind=kOutput, calls=%fused_computation.1{fusion_meta}
}}
"""


def _line(name, op, op_name, root=False):
    return (f"  {'ROOT ' if root else ''}%{name} = f32[8,8]{{1,0}} "
            f"{op}(%p0, %p1), metadata={{op_name=\"{op_name}\"}}")


BWD = "jit(step)/transpose(jvp(dl4j_L4_conv))/"
FWD = "jit(step)/jvp(dl4j_L4_conv)/"
UPD = "jit(step)/dl4j_updater/"

FUSIONS = {
    # a weight-gradient convolution fused with Adam's update: two phases'
    # work in one op
    "wgrad_plus_adam": (
        [_line("convolution.1", "convolution", BWD + "conv_general_dilated"),
         _line("multiply.1", "multiply", UPD + "mul"),
         _line("subtract.1", "subtract", UPD + "sub", root=True)],
        sp.Entry("backward", "dl4j_L4_conv", None, True)),
    # a backward convolution that recomputes its activation: backward
    "bwd_conv_with_recompute": (
        [_line("maximum.1", "maximum", FWD + "max"),
         _line("convolution.1", "convolution", BWD + "conv_general_dilated",
               root=True)],
        sp.Entry("backward", "dl4j_L4_conv", None, False)),
    # Adam over one leaf, with the last convert of its gradient: updater
    "adam_with_gradient_convert": (
        [_line("convert.1", "convert", BWD + "convert_element_type"),
         _line("divide.1", "divide", UPD + "div"),
         _line("subtract.1", "subtract", UPD + "sub", root=True)],
        sp.Entry("updater", "dl4j_updater", None, False)),
    # a bias gradient's reduction fused with its update: mixed
    "bias_grad_plus_adam": (
        [_line("reduce.1", "reduce", BWD + "reduce_sum"),
         _line("subtract.1", "subtract", UPD + "sub", root=True)],
        sp.Entry("backward", "dl4j_L4_conv", None, True)),
    "forward_only": (
        [_line("convolution.1", "convolution", FWD + "conv_general_dilated"),
         _line("add.1", "add", FWD + "add", root=True)],
        sp.Entry("forward", "dl4j_L4_conv", None, False)),
    # forward recompute read by backward elementwise work: runs in the
    # backward pass
    "elementwise_fwd_and_bwd": (
        [_line("multiply.1", "multiply", FWD + "mul"),
         _line("select.1", "select", BWD + "select_n", root=True)],
        sp.Entry("backward", "dl4j_L4_conv", None, False)),
}


@pytest.mark.parametrize("case", sorted(FUSIONS))
def test_fusion(case):
    body, want = FUSIONS[case]
    got = sp.parse(_module("\n".join(body)))
    assert got["fusion.7"] == want
    # the compiler's unnamed copy works for the fusion that reads it (PR 28)
    assert got["copy.1"] == want._replace(mixed=False)
    assert "convolution.1" not in got     # a fusion's inside is not listed
    assert sp.module_name(_module("")) == "jit_step"


def test_fusion_without_known_instructions_takes_its_own_name():
    text = _module("  ROOT %add.1 = f32[8,8]{1,0} add(%p0, %p1)",
                   ', metadata={op_name="jit(step)/dl4j_updater/add"}')
    assert sp.parse(text)["fusion.7"] == \
        sp.Entry("updater", "dl4j_updater", None, False)


# ------------------------------------------- steps compiled here (CPU)
def _mln():
    conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-3))
            .list()
            .layer(ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                    nOut=4, activation="relu"))
            .layer(BatchNormalization())
            .layer(ConvolutionLayer(kernelSize=(3, 3), nOut=6,
                                    activation="relu"))
            .layer(DenseLayer(nOut=8, activation="relu"))
            .layer(OutputLayer(nOut=3, lossFunction="mcxent",
                               activation="softmax"))
            .setInputType(InputType.convolutional(8, 8, 2)).build())
    return MultiLayerNetwork(conf).init()


def _graph():
    g = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-3))
         .graphBuilder().addInputs("in")
         .setInputTypes(InputType.convolutional(8, 8, 2)))
    g.addLayer("c1", ConvolutionLayer(kernelSize=(3, 3), padding=(1, 1),
                                      nOut=4, activation="relu"), "in")
    g.addLayer("c2", ConvolutionLayer(kernelSize=(1, 1), nOut=4,
                                      activation="identity"), "c1")
    g.addVertex("add", ElementWiseVertex("Add"), "c2", "c1")
    g.addLayer("gp", GlobalPoolingLayer("avg"), "add")
    g.addLayer("out", OutputLayer(nOut=3, lossFunction="mcxent",
                                  activation="softmax"), "gp")
    g.setOutputs("out")
    return ComputationGraph(g.build()).init()


def _batch(k=None):
    rng = np.random.RandomState(0)
    lead = (k,) if k else ()
    x = rng.randn(*lead, 4, 2, 8, 8).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, lead + (4,))]
    return x, y


def _conv_scopes(net):
    if hasattr(net.conf, "graph_inputs"):
        return [devicetime.scope_name(i, n.name)
                for i, n in enumerate(net.conf.topo)
                if n.kind == "layer"
                and isinstance(n.obj, ConvolutionLayer)]
    return [devicetime.scope_name(i, type(layer).__name__)
            for i, layer in enumerate(net.layers)
            if isinstance(layer, ConvolutionLayer)]


@pytest.mark.parametrize("build", [_mln, _graph], ids=["mln", "graph"])
def test_compiled_step_maps_layers_phases_and_the_updater(build):
    net = build()
    text = compiled_train_step_hlo(net, *_batch())
    assert sp.module_name(text) == "jit_step"
    got = sp.parse(text)
    seen = {(e.layer, e.phase) for e in got.values()}
    scopes = _conv_scopes(net)
    assert len(scopes) == 2
    for scope in scopes:        # each convolution under its own layer
        assert (scope, "forward") in seen, scope
        assert (scope, "backward") in seen, scope
    assert ("dl4j_updater", "updater") in seen
    assert any(layer == "dl4j_loss" for layer, _p in seen)
    # a layer is named only with a phase; what has none is plain "other"
    assert all(e.layer is None for e in got.values() if e.phase == "other")
    n_updater = sum(1 for e in got.values() if e.phase == "updater")
    assert n_updater >= 5       # Adam over every leaf, not a stray op


def test_megastep_while_body_is_read():
    net = _mln()
    text = compiled_train_step_hlo(net, *_batch(k=2), steps=2)
    assert sp.module_name(text) == "jit_megastep"
    assert " while(" in text
    got = sp.parse(text)
    phases = {e.phase for e in got.values()}
    assert {"forward", "backward", "updater"} <= phases
    comps, entry = sp._split(text)
    entry_names = {name for name, *_ in comps[entry]}
    in_body = [n for n, e in got.items()
               if n not in entry_names and e.phase == "backward"]
    assert in_body              # the scan body's own instructions


# -------------------------------------- the v5e ResNet-50 step's text
V5E = {
    "fusion": sp.Entry("forward", "dl4j_L0_stem_conv", None, False),
    "fusion.2113": sp.Entry("backward", "dl4j_L37_s1b0_bn1", None, False),
    "multiply_convert_fusion.27":
        sp.Entry("backward", "dl4j_L173_fc", None, False),
    # the last layer's weight gradient fused with Adam's update
    "divide_subtract_fusion.4":
        sp.Entry("backward", "dl4j_L173_fc", None, True),
    "divide_subtract_fusion.81":
        sp.Entry("updater", "dl4j_updater", None, False),
    "dl4j_scale_shift_act.26":
        sp.Entry("forward", "dl4j_L37_s1b0_bn1", "dl4j_scale_shift_act",
                 False),
    "copy.3246": sp.Entry("other", None, None, False),
    "copy-start.101": sp.Entry("other", None, None, False),
}


@pytest.mark.parametrize("name", sorted(V5E))
def test_v5e_resnet50_excerpt(name):
    with open(EXCERPT) as f:
        got = sp.parse(f.read())
    assert set(got) == set(V5E)
    assert got[name] == V5E[name]


def test_unnamed_data_movements_adopt_their_reader():
    """The compiler's asynchronous copies and slices carry no ``op_name``:
    a ``-start`` adopts, through its ``-done``, the entry of the fusion
    that reads what it moved; one nobody listed reads stays ``other``."""
    meta = (', metadata={op_name="jit(step)/transpose(jvp(dl4j_ut2))/'
            'jvp(dl4j_ut2)/checkpoint/rematted_computation/dl4j_L3_attn/'
            'dl4j_attn_core/dot_general"}')
    text = f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {{
  %p0 = f32[8,8]{{1,0}} parameter(0)
  %p1 = f32[8,8]{{1,0}} parameter(1)
  ROOT %dot.1 = f32[8,8]{{1,0}} dot(%p0, %p1){meta}
}}

ENTRY %main.1 (a: f32[8,8], b: f32[8,8]) -> f32[8,8] {{
  %a = f32[8,8]{{1,0}} parameter(0)
  %b = f32[8,8]{{1,0}} parameter(1)
  %slice-start.1 = (f32[8,8]{{1,0}}, f32[8,8]{{1,0}}, u32[]) slice-start(%a)
  %slice-done.1 = f32[8,8]{{1,0}} slice-done(%slice-start.1)
  %copy-start.9 = (f32[8,8]{{1,0}}, f32[8,8]{{1,0}}, u32[]) copy-start(%b)
  %copy-done.9 = f32[8,8]{{1,0}} copy-done(%copy-start.9)
  ROOT %fusion.7 = f32[8,8]{{1,0}} fusion(%slice-done.1, %b), kind=kOutput, calls=%fused_computation.1
}}
"""
    got = sp.parse(text)
    want = sp.Entry("backward", "dl4j_L3_attn", None, False, 2, "attn_core",
                    True)
    assert got["fusion.7"] == want
    assert got["slice-done.1"] == got["slice-start.1"] == want
    assert got["copy-done.9"].phase == got["copy-start.9"].phase == "other"
