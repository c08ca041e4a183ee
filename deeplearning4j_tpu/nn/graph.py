"""ComputationGraph — DAG networks with graph vertices.

Reference parity: ``org.deeplearning4j.nn.graph.ComputationGraph`` +
``ComputationGraphConfiguration.GraphBuilder`` + vertex impls
``org.deeplearning4j.nn.graph.vertex.impl.{MergeVertex, ElementWiseVertex,
SubsetVertex, L2NormalizeVertex, ScaleVertex, ShiftVertex, StackVertex,
UnstackVertex, PreprocessorVertex}`` (SURVEY.md §2.2 "ComputationGraph
vertices", call stack §3.2). ResNet skip connections and YOLO routes are
built from these.

TPU-native: same design as MultiLayerNetwork — the whole DAG traces into
ONE compiled step; topological order is computed once from the config.
Multiple inputs and multiple outputs (MultiDataSet) are supported.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import profiler as _prof
from deeplearning4j_tpu.analysis import churn as _churn
from deeplearning4j_tpu.data.dataset import DataSet, DataSetIterator, MultiDataSet
from deeplearning4j_tpu.evaluation.evaluation import Evaluation
from deeplearning4j_tpu.nn import augment as _augment_mod
from deeplearning4j_tpu.nn import compilecache as _cc
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import preprocessors as pp
from deeplearning4j_tpu.nn.config import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import (_dynamic_scale_next,
                                              _grads_all_finite,
                                              _maybe_attach_env_profiler,
                                              _predict_batches,
                                              _process_and_apply_grads,
                                              _select_update,
                                              _unscale_grads)
from deeplearning4j_tpu.profiler import devicetime as _devicetime
from deeplearning4j_tpu.profiler import sanitizer as _sanitizer
from deeplearning4j_tpu.profiler import stepprogram as _stepprogram
from deeplearning4j_tpu.train import stepping as _stepping

_MASK_AWARE = (L.LSTM, L.SimpleRnn, L.Bidirectional, L.LastTimeStep,
               L.GlobalPoolingLayer, L.SelfAttentionLayer,
               L.RecurrentAttentionLayer)


class GraphVertex:
    """Non-layer DAG node (ref: org.deeplearning4j.nn.conf.graph.*Vertex)."""

    def apply(self, *inputs):
        raise NotImplementedError

    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def to_config(self):
        d = {"@class": type(self).__name__}
        d.update({k: (list(v) if isinstance(v, tuple) else v)
                  for k, v in self.__dict__.items()})
        return d

    @classmethod
    def from_config(cls, d):
        obj = cls.__new__(cls)
        for k, v in d.items():
            if k != "@class":
                setattr(obj, k, v)
        return obj


class MergeVertex(GraphVertex):
    """Concat along the channel/feature axis (ref: MergeVertex)."""

    def apply(self, *inputs):
        axis = 1 if inputs[0].ndim >= 3 else -1
        return jnp.concatenate(inputs, axis=axis)

    def output_type(self, *its: InputType) -> InputType:
        it = its[0]
        if it.kind == "cnn":
            return InputType.convolutional(it.height, it.width,
                                           sum(i.channels for i in its))
        if it.kind == "rnn":
            return InputType.recurrent(sum(i.size for i in its),
                                       it.dims.get("timesteps", -1))
        return InputType.feedForward(sum(i.arrayElementsPerExample() for i in its))


class ElementWiseVertex(GraphVertex):
    """Add/Product/Subtract/Average/Max of same-shape inputs
    (ref: ElementWiseVertex). The ResNet residual-add."""

    def __init__(self, op: str = "Add"):
        self.op = op.lower()

    def apply(self, *inputs):
        if self.op == "add":
            out = inputs[0]
            for i in inputs[1:]:
                out = out + i
            return out
        if self.op == "product":
            out = inputs[0]
            for i in inputs[1:]:
                out = out * i
            return out
        if self.op == "subtract":
            return inputs[0] - inputs[1]
        if self.op == "average":
            return sum(inputs) / len(inputs)
        if self.op == "max":
            out = inputs[0]
            for i in inputs[1:]:
                out = jnp.maximum(out, i)
            return out
        raise ValueError(self.op)


class DotProductVertex(GraphVertex):
    """Per-example dot product of two same-shape inputs, with optional L2
    normalization first (the Keras ``Dot``/cosine-proximity merge; ref:
    KerasDot in the reference's keras-import merge family)."""

    def __init__(self, normalize: bool = False):
        self.normalize = normalize

    def apply(self, a, b):
        if a.ndim != 2 or b.ndim != 2:
            raise ValueError(
                f"DotProductVertex supports rank-2 [N, C] inputs (got ranks "
                f"{a.ndim}/{b.ndim}); higher-rank Keras Dot contractions "
                f"do not import")
        if self.normalize:
            a = a / jnp.maximum(jnp.linalg.norm(a, axis=-1, keepdims=True),
                                1e-12)
            b = b / jnp.maximum(jnp.linalg.norm(b, axis=-1, keepdims=True),
                                1e-12)
        return jnp.sum(a * b, axis=-1, keepdims=True)

    def output_type(self, *its: InputType) -> InputType:
        return InputType.feedForward(1)

    def to_config(self):
        return {"@class": "DotProductVertex", "normalize": self.normalize}


class SubsetVertex(GraphVertex):
    """Channel-range slice (ref: SubsetVertex)."""

    def __init__(self, frm: int, to: int):
        self.frm, self.to = frm, to

    def apply(self, x):
        if x.ndim >= 3:
            return x[:, self.frm:self.to + 1]
        return x[:, self.frm:self.to + 1]

    def output_type(self, it: InputType) -> InputType:
        n = self.to - self.frm + 1
        if it.kind == "cnn":
            return InputType.convolutional(it.height, it.width, n)
        if it.kind == "rnn":
            return InputType.recurrent(n, it.dims.get("timesteps", -1))
        return InputType.feedForward(n)


class L2NormalizeVertex(GraphVertex):
    """Per-example L2 normalize (ref: L2NormalizeVertex; FaceNet uses it)."""

    def __init__(self, eps: float = 1e-8):
        self.eps = eps

    def apply(self, x):
        flat = x.reshape(x.shape[0], -1)
        n = jnp.sqrt(jnp.sum(flat * flat, axis=1, keepdims=True))
        out = flat / jnp.maximum(n, self.eps)
        return out.reshape(x.shape)


class ScaleVertex(GraphVertex):
    """(ref: ScaleVertex)"""

    def __init__(self, scale: float):
        self.scale = scale

    def apply(self, x):
        return x * self.scale


class ShiftVertex(GraphVertex):
    """(ref: ShiftVertex)"""

    def __init__(self, shift: float):
        self.shift = shift

    def apply(self, x):
        return x + self.shift


class StackVertex(GraphVertex):
    """Stack along batch (ref: StackVertex)."""

    def apply(self, *inputs):
        return jnp.concatenate(inputs, axis=0)


class UnstackVertex(GraphVertex):
    """Take slice i of a StackVertex output (ref: UnstackVertex)."""

    def __init__(self, frm: int, stack_size: int):
        self.frm, self.stack_size = frm, stack_size

    def apply(self, x):
        n = x.shape[0] // self.stack_size
        return x[self.frm * n:(self.frm + 1) * n]


class PreprocessorVertex(GraphVertex):
    """Wraps an input preprocessor as a vertex (ref: PreprocessorVertex)."""

    def __init__(self, preproc):
        self.preproc = preproc

    def apply(self, x):
        return self.preproc(x)

    def output_type(self, it: InputType) -> InputType:
        return self.preproc.output_type(it)

    def to_config(self):
        return {"@class": "PreprocessorVertex",
                "preproc_class": type(self.preproc).__name__,
                "preproc_args": dict(self.preproc.__dict__)}

    @classmethod
    def from_config(cls, d):
        pc = getattr(pp, d["preproc_class"])
        obj = pc.__new__(pc)
        obj.__dict__.update(d["preproc_args"])
        return PreprocessorVertex(obj)


class LoopVertex(GraphVertex):
    """Runs a sub-graph of this graph ``steps`` times on its own output
    with ONE set of parameters (a looped / universal-transformer stack).

    Built with ``GraphBuilder.beginLoop(name, input, steps)`` ...
    ``endLoop(output)``: the nodes added in between are the body. They are
    ordinary nodes of the graph — each layer's parameters, updater state,
    checkpoint entries and sharding paths exist once, under the layer's
    own name — that read the carried value under the loop's name and are
    applied ``steps`` times a forward pass; a looped weight's gradient is
    the sum over its uses. Pass 1 starts from the loop's input; pass t+1
    from ``output``'s value in pass t, which must have the input's type.
    Outside the body the loop's name stands for EVERY pass's output, in
    order: a layer that takes passes (``LoopedLMOutputLayer``) reads them
    all, :class:`PassVertex` picks one for an ordinary layer. With
    ``steps=1`` the graph computes exactly what the body's nodes stacked
    plainly compute. The loop is unrolled into the one compiled step
    (every pass's ops carry ``dl4j_ut<t>``); in a train step each
    single-entry, single-exit stretch of the body is rematerialised in
    the backward pass (see ``ComputationGraph._forward``)."""

    def __init__(self, steps: int = 1, output: str = None):
        steps = int(steps)
        if steps < 1:
            raise ValueError(f"LoopVertex: steps must be >= 1, got {steps}")
        self.steps = steps
        self.output = output

    def apply(self, *inputs):
        raise NotImplementedError(
            "a LoopVertex is run by ComputationGraph._forward, which "
            "applies its body; it has no apply of its own")

    def output_type(self, it: InputType) -> InputType:
        return InputType(it.kind, **{**it.dims, "passes": self.steps})


class PassVertex(GraphVertex):
    """One pass's output of a :class:`LoopVertex` (``index`` counts from
    0; -1 is the last pass), for an ordinary layer to read."""

    def __init__(self, index: int = -1):
        self.index = int(index)

    def apply(self, passes):
        return passes[self.index]

    def output_type(self, it: InputType) -> InputType:
        return InputType(it.kind, **{k: v for k, v in it.dims.items()
                                     if k != "passes"})


class LabelsVertex(GraphVertex):
    """The labels of graph output ``index`` as a value nodes can read: a
    multi-token-prediction module is fed the embedding of the NEXT token,
    which a train step has as its label. It takes no input. Outside a
    train step (``output()``) it has no value, and every node that needs
    it is left out of the forward pass."""

    def __init__(self, index: int = 0):
        self.index = int(index)

    def apply(self, *inputs):
        raise NotImplementedError(
            "a LabelsVertex is filled in by ComputationGraph._forward "
            "from the step's labels; it has no apply of its own")

    def output_type(self, *its: InputType) -> InputType:
        return its[0] if its else InputType.recurrent(1)


_VERTEX_CLASSES = {c.__name__: c for c in
                   [MergeVertex, ElementWiseVertex, SubsetVertex,
                    DotProductVertex, L2NormalizeVertex, ScaleVertex,
                    ShiftVertex, StackVertex, UnstackVertex,
                    PreprocessorVertex, LoopVertex, PassVertex,
                    LabelsVertex]}


class _GraphNode:
    def __init__(self, name: str, kind: str, obj, inputs: List[str],
                 loop: str = None):
        self.name = name
        self.kind = kind      # 'layer' | 'vertex'
        self.obj = obj
        self.inputs = inputs
        self.loop = loop      # the LoopVertex whose body this node is in


class GraphBuilder:
    """ref: ComputationGraphConfiguration.GraphBuilder."""

    def __init__(self, base: NeuralNetConfiguration):
        self.base = base
        self.nodes: List[_GraphNode] = []
        self.graph_inputs: List[str] = []
        self.graph_outputs: List[str] = []
        self.input_types: Dict[str, InputType] = {}
        self._loop: Optional[str] = None    # the loop being built, if any
        self.remat_stack = False

    def rematerializeStack(self, on: bool = True):
        """Ask the train step to rematerialise the graph OUTSIDE its loops
        a stretch at a time too (``ComputationGraphConfiguration.
        _stack_stretches``): a deep plain stack then keeps one activation
        a stretch instead of every layer's internals. Off by default: a
        graph that does not ask compiles the step it always did."""
        self.remat_stack = bool(on)
        return self

    def addInputs(self, *names):
        self.graph_inputs.extend(names)
        return self

    def setInputTypes(self, *types):
        for name, t in zip(self.graph_inputs, types):
            self.input_types[name] = t
        return self

    def addLayer(self, name: str, layer, *inputs):
        layer.name = name
        self.nodes.append(_GraphNode(name, "layer", layer, list(inputs),
                                     self._loop))
        return self

    def addVertex(self, name: str, vertex: GraphVertex, *inputs):
        self.nodes.append(_GraphNode(name, "vertex", vertex, list(inputs),
                                     self._loop))
        return self

    def beginLoop(self, name: str, input: str, steps: int):
        """Open a :class:`LoopVertex`: until ``endLoop`` every node added
        belongs to its body, where ``name`` is the carried value."""
        if self._loop is not None:
            raise ValueError(f"beginLoop('{name}'): loop '{self._loop}' is "
                             f"still open; loops do not nest")
        self.addVertex(name, LoopVertex(steps), input)
        self._loop = name
        return self

    def endLoop(self, output: str):
        """Close the open loop; ``output`` is the body node whose value
        the next pass starts from and each pass hands out."""
        if self._loop is None:
            raise ValueError("endLoop: no loop is open")
        loop = next(n for n in self.nodes if n.name == self._loop)
        loop.obj.output = output
        self._loop = None
        return self

    def setOutputs(self, *names):
        self.graph_outputs = list(names)
        return self

    def build(self) -> "ComputationGraphConfiguration":
        if self._loop is not None:
            raise ValueError(f"build: loop '{self._loop}' was never closed "
                             f"(endLoop)")
        return ComputationGraphConfiguration(self)

    def validate(self, batch_size: int = None, data_devices: int = None,
                 **kw):
        """Static lint of the (possibly not-yet-buildable) graph — unlike
        ``build()``, a cyclic or dangling graph comes back as E002/E003
        diagnostics instead of a ValueError. Extra keywords pass through
        to ``analysis.analyze`` (``mesh=``, ``suppress=``, ...)."""
        from deeplearning4j_tpu.analysis import analyze
        return analyze(self, batch_size=batch_size,
                       data_devices=data_devices, **kw)

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        from deeplearning4j_tpu.nn.config import _builder_typo
        raise _builder_typo(self, name)


class ComputationGraphConfiguration:
    """ref: org.deeplearning4j.nn.conf.ComputationGraphConfiguration."""

    def __init__(self, builder: GraphBuilder):
        self.base = builder.base
        self.nodes = builder.nodes
        self.graph_inputs = builder.graph_inputs
        self.graph_outputs = builder.graph_outputs
        self.input_types = builder.input_types
        self.remat_stack = bool(getattr(builder, "remat_stack", False))
        self.preprocessors: Dict[str, Any] = {}
        self.node_by_name = {n.name: n for n in self.nodes}
        # a layer whose ``tiedWith`` names another node of this graph has
        # no parameters of its own: it is applied with that node's (an
        # embedding or a norm used at two places; the gradient is the sum)
        self.param_owner = {
            n.name: (n.obj.tied_with if n.kind == "layer" and getattr(
                n.obj, "tied_with", None) in self.node_by_name else n.name)
            for n in self.nodes}
        for n in self.nodes:
            if n.kind == "layer" and isinstance(n.obj, L.MTPLMOutputLayer) \
                    and n.obj.tied_with and self.param_owner[n.name] == n.name:
                raise ValueError(
                    f"{n.name}: tiedWith={n.obj.tied_with!r} names no node "
                    f"of this graph, and a tied head has no W of its own "
                    f"(nodes: {sorted(self.node_by_name)})")
        self._toposort()
        if self.input_types:
            self._propagate_types()

    def validate(self, batch_size: int = None, data_devices: int = None,
                 **kw):
        """Static lint — see ``deeplearning4j_tpu.analysis.analyze``."""
        from deeplearning4j_tpu.analysis import analyze
        return analyze(self, batch_size=batch_size,
                       data_devices=data_devices, **kw)

    @staticmethod
    def _sorted(nodes, seen):
        order, seen = [], set(seen)
        remaining = list(nodes)
        while remaining:
            progressed = False
            for n in list(remaining):
                if all(i in seen for i in n.inputs):
                    order.append(n)
                    seen.add(n.name)
                    remaining.remove(n)
                    progressed = True
            if not progressed:
                missing = {i for n in remaining for i in n.inputs if i not in seen}
                raise ValueError(f"graph has unresolved inputs/cycle: {missing}")
        return order, seen

    def _toposort(self):
        """``topo``: every node once, in an order a forward pass can
        follow, a loop's body right after its :class:`LoopVertex`. A body
        reads its own nodes, the carried value and what exists before the
        loop; nothing outside reads a body node but through the loop."""
        top, seen_all = self._sorted(
            [n for n in self.nodes if n.loop is None], self.graph_inputs)
        self.loop_bodies: Dict[str, List[_GraphNode]] = {}
        self.loop_segments: Dict[str, List[List[_GraphNode]]] = {}
        order, seen = [], set(self.graph_inputs)
        for n in top:
            order.append(n)
            seen.add(n.name)
            if not isinstance(n.obj, LoopVertex):
                continue
            body, _ = self._sorted(
                [b for b in self.nodes if b.loop == n.name], seen)
            out = n.obj.output
            if not body or out not in {b.name for b in body}:
                raise ValueError(
                    f"loop '{n.name}': endLoop must name one of its body's "
                    f"nodes, got {out!r}")
            order.extend(body)
            self.loop_bodies[n.name] = body
            self.loop_segments[n.name] = self._segments(n.name, body, out)
        stray = [b.name for b in self.nodes
                 if b.loop is not None and b.loop not in self.loop_bodies]
        if stray:
            raise ValueError(f"nodes {stray} belong to a loop the graph "
                             f"does not have")
        self.topo = order
        self.stack_stretches = self._stack_stretches(
            top, self.graph_inputs, self.graph_outputs) \
            if self.remat_stack else None

    @staticmethod
    def _stack_stretches(top, inputs, outputs):
        """The nodes outside every loop cut into the stretches a train
        step rematerialises one at a time (``rematerializeStack``): after
        a node where no later cut would have fewer values alive (made so
        far and still read later). A residual block, or a sub-block
        between a hyper-connection's read and write, is one stretch; a
        value that lives long (a hidden state a second head reads) raises
        the count for every cut it passes and forbids none. The output
        layers at the end are no stretch: they work the loss out."""
        n_body = len(top)
        while n_body and top[n_body - 1].name in outputs:
            n_body -= 1
        made, alive = set(inputs), []
        for i, node in enumerate(top[:n_body]):
            made.add(node.name)
            later = {r for b in top[i + 1:] for r in b.inputs}
            alive.append(len(made & later))
        stretches, cur = [], []
        for i, node in enumerate(top[:n_body]):
            cur.append(node)
            if alive[i] <= min(alive[i:]):
                stretches.append(cur)
                cur = []
        return stretches + [[n] for n in top[n_body:]]

    @staticmethod
    def _segments(loop, body, out):
        """The body cut wherever exactly one value goes on (the carried
        value or a body node's output that a later body node, or the next
        pass, reads): the smallest single-entry, single-exit stretches,
        which the train step rematerialises one at a time. A residual
        block is one stretch (its input is read again by the add), a
        plain chain is one a node."""
        made = {loop}
        segs, cur = [], []
        for i, node in enumerate(body):
            cur.append(node)
            made.add(node.name)
            later = {r for b in body[i + 1:] for r in b.inputs}
            live = {v for v in made if v in later}
            if i == len(body) - 1:
                live.add(out)
            if len(live) == 1:
                segs.append(cur)
                cur = []
        if cur:
            segs.append(cur)
        return segs

    def _propagate_types(self):
        types: Dict[str, InputType] = dict(self.input_types)
        carried: Dict[str, InputType] = {}
        for node in self.topo:
            in_types = [types[i] for i in node.inputs]
            if node.kind == "layer":
                layer = node.obj
                pre = pp.preprocessor_for(in_types[0], layer)
                if pre is not None:
                    self.preprocessors[node.name] = pre
                    in_types[0] = pre.output_type(in_types[0])
                layer.set_defaults(self.base)
                layer.infer_nin(in_types[0])
                if hasattr(layer, "set_input_count"):
                    layer.set_input_count(len(in_types))
                types[node.name] = layer.output_type(in_types[0])
            elif isinstance(node.obj, LoopVertex):
                # inside the body the loop's name is the carried value
                types[node.name] = carried[node.name] = in_types[0]
            else:
                types[node.name] = node.obj.output_type(*in_types)
            loop = node.loop
            if loop is not None and node is self.loop_bodies[loop][-1]:
                vertex = self.node_by_name[loop].obj
                if types[vertex.output] != carried[loop]:
                    raise ValueError(
                        f"loop '{loop}': its body turns {carried[loop]} "
                        f"into {types[vertex.output]}; a pass must hand "
                        f"on the type it took")
                types[loop] = vertex.output_type(carried[loop])
        self.types = types

    def to_json(self) -> str:
        import json
        return json.dumps({
            "base": self.base.to_config(),
            "inputs": self.graph_inputs,
            "outputs": self.graph_outputs,
            "input_types": {k: v.to_config() for k, v in self.input_types.items()},
            **({"remat_stack": True} if self.remat_stack else {}),
            "nodes": [{"name": n.name, "kind": n.kind,
                       "inputs": n.inputs, "conf": n.obj.to_config(),
                       **({"loop": n.loop} if n.loop else {})}
                      for n in self.nodes],
        })

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        import json
        d = json.loads(s)
        b = GraphBuilder(NeuralNetConfiguration.from_config(d["base"]))
        b.addInputs(*d["inputs"])
        b.input_types = {k: InputType.from_config(v)
                         for k, v in d["input_types"].items()}
        for nd in d["nodes"]:
            b._loop = nd.get("loop")
            if nd["kind"] == "layer":
                obj = L.layer_from_config(nd["conf"])
                b.addLayer(nd["name"], obj, *nd["inputs"])
            else:
                cls = _VERTEX_CLASSES[nd["conf"]["@class"]]
                b.addVertex(nd["name"], cls.from_config(nd["conf"]), *nd["inputs"])
        b._loop = None
        b.setOutputs(*d["outputs"])
        b.rematerializeStack(d.get("remat_stack", False))
        return ComputationGraphConfiguration(b)


class ComputationGraph:
    """DAG network (ref: org.deeplearning4j.nn.graph.ComputationGraph)."""

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self._params: Dict[str, Dict] = {}
        self._states: Dict[str, Dict] = {}
        self._opt_state = None
        self._iteration = 0
        self._t_dev = None  # device-resident iteration counter (see _ensure_clock)
        self._epoch = 0
        self._score = float("nan")
        self._listeners: List[Any] = []
        self._train_step_cache = {}
        self._megastep_cache = {}
        self._fwd_cache = None
        self._augment = None    # DeviceAugmentation (see setDeviceAugmentation)
        self._precision = None  # PrecisionPolicy (see setPrecisionPolicy)
        self._sharding_plan = None  # ShardedTrainingPlan (see setShardingPlan)
        self._scale_state = None  # dynamic loss scale [scale, good_steps]
        self._initialized = False
        # NHWC compute layout + fused epilogues (ISSUE 14) — opt-in,
        # public NCHW API unchanged (see MultiLayerNetwork)
        self._compute_layout = "NCHW"
        self._fuse_epilogues = False
        self._epilogue_plan = None
        self._epilogue_shared = None
        fmt = getattr(conf.base, "compute_layout", None)
        if fmt and fmt != "NCHW":
            self.setComputeLayout(fmt)

    def validate(self, batch_size: int = None, data_devices: int = None,
                 **kw):
        """Static lint of this graph network (configuration analysis plus
        model-level findings) — see MultiLayerNetwork.validate."""
        from deeplearning4j_tpu.analysis import analyze
        return analyze(self, batch_size=batch_size,
                       data_devices=data_devices, **kw)

    def init(self, seed: int = None, strict: bool = False):
        if strict:
            self.validate().raise_if_errors()
        seed = self.conf.base.seed if seed is None else seed
        # net:init: the cause of the small programs made below
        with _cc.cause_span(_cc.NET_INIT) as made:
            key = jax.random.PRNGKey(seed)
            self._params, self._states = {}, {}
            for node in self.conf.topo:
                if node.kind == "layer":
                    key, sub = jax.random.split(key)
                    p, s = node.obj.initialize(sub)
                    tied = self.conf.param_owner[node.name] != node.name
                    self._params[node.name] = {} if tied else p
                    self._states[node.name] = s
            made["parameters"] = self.numParams()
            made["leaves"] = len(
                jax.tree_util.tree_leaves(self._params))
        self._opt_state = None
        self._train_step_cache = {}
        self._megastep_cache = {}
        self._fwd_cache = None
        self._scale_state = None
        self._initialized = True
        _sanitizer.invalidate(self)   # re-init = out-of-band state reset
        return self

    # --------------------------------------------------------------- forward
    def _compute_dtype(self):
        """Effective compute dtype: attached PrecisionPolicy wins, else
        the config dataType (see MultiLayerNetwork._compute_dtype)."""
        pol = self._precision
        if pol is not None:
            return pol.compute_jnp()
        return L.compute_dtype_of(self.conf.base.dtype)

    def _forward(self, params, states, inputs: Dict[str, Any], train, key,
                 fmask=None, remat: bool = False, heads: Dict = None,
                 labels: List = None):
        """One forward pass over ``conf.topo``. ``remat``: the train step
        asks for each single-entry, single-exit stretch of a loop's body
        (``conf.loop_segments``; of a loop of more than one pass) to be
        rematerialised in the backward pass, so a looped stack keeps one
        activation a stretch and pass
        instead of every layer's internals ``steps`` times over; a graph
        without a loop compiles the same program either way, unless it
        asked for its plain stack to be rematerialised too
        (``GraphBuilder.rematerializeStack``: then every stretch of
        ``conf.stack_stretches`` is). ``heads``:
        a dict the loss wants filled with ``{output name: (cast params,
        input)}`` for output layers that compute their loss from their
        input (``loss_from_input``) and are then not applied. ``labels``:
        the step's labels, for a :class:`LabelsVertex`; without them such
        a vertex has no value, and neither has any node that reads it
        (``None`` in place of an output that needs one)."""
        cdt = self._compute_dtype()
        nhwc = self._compute_layout == "NHWC"
        plan = self._ensure_epilogue_plan() if self._fuse_epilogues else {}
        fused_act = {act: bn for bn, (act, _c, _a) in plan.items()}
        fused_conv = {c for _a, c, _al in plan.values() if c}
        shared = self._epilogue_shared if self._fuse_epilogues else set()
        env = {k: (v.astype(jnp.float32)
                   if cdt is None and getattr(v, "dtype", None) == jnp.uint8
                   else v)
               for k, v in inputs.items()}   # on-device image-byte cast
        # node name -> its output is in the compute layout (NHWC for image
        # maps, feature-last for sequences), not the public one
        fmt = {k: False for k in env}
        pending_bias: Dict[str, Any] = {}    # fused conv name -> cast bias
        # shared folded convs: env[] holds the BIAS-LESS output (what the
        # fused BN wants); every other consumer reads this re-biased copy
        # (bit-identical to the unfused conv, see L.conv_bias_add)
        biased: Dict[str, Any] = {}
        index_of = {n.name: i for i, n in enumerate(self.conf.topo)}
        owner = self.conf.param_owner

        def read(env, name, consumer=None):
            if name in biased:
                if consumer is not None and consumer in plan \
                        and plan[consumer][1] == name:
                    return env[name]     # the anchor BN folds the bias
                return biased[name]
            return env[name]

        def apply_node(node, params, states, env, fmt, new_states, key):
            """Run one node: fills ``env``, ``fmt`` and ``new_states``
            under the node's name and returns the key to go on with."""
            if isinstance(node.obj, LabelsVertex):
                env[node.name] = None if labels is None \
                    else labels[node.obj.index]
                fmt[node.name] = False
                return key
            takes = getattr(node.obj, "n_inputs", 1) \
                if node.kind == "layer" else 1
            gone = [env[i] is None for i in node.inputs]
            if gone[0] or (any(gone) and takes is not None):
                # a value only a train step has (see LabelsVertex)
                env[node.name], fmt[node.name] = None, False
                if node.kind == "layer":
                    new_states[node.name] = states[node.name]
                return key
            if takes != 1:
                return apply_joint(node, params, states, env, fmt,
                                   new_states, key)
            if node.name in fused_act:
                # folded into its BN's scale_shift_act epilogue; keep the
                # RNG stream identical to the unfused forward
                key, _ = jax.random.split(key)
                env[node.name] = env[fused_act[node.name]]
                fmt[node.name] = fmt[fused_act[node.name]]
                new_states[node.name] = states[node.name]
                return key
            # every op of a node, the casts and layout steps around its
            # apply included, carries the node's scope: the step-program
            # map (profiler.stepprogram) reads layer and phase off it
            with jax.named_scope(_devicetime.scope_name(index_of[node.name],
                                                        node.name)):
                if node.kind == "layer":
                    x = read(env, node.inputs[0], node.name)
                    cur_nhwc = fmt[node.inputs[0]]
                    if node.name in self.conf.preprocessors:
                        if cur_nhwc:
                            x, cur_nhwc = L.to_public(x), False
                        x = self.conf.preprocessors[node.name](x)
                    x, cur_nhwc = L.layout_step(node.obj, x, cur_nhwc, nhwc,
                                                sequences=True)
                    p = params[owner[node.name]]
                    if cdt is not None:
                        p, x = L.policy_cast(node.obj, p, x, cdt)
                    key, sub = jax.random.split(key)
                    if node.name in plan:          # BN anchoring a fusion
                        act_name, conv_name, alpha = plan[node.name]
                        out, ns = L.fused_bn_act(
                            node.obj, p, states[node.name], x, train, alpha,
                            bias=pending_bias.pop(conv_name, None))
                    elif node.name in fused_conv:  # bias folds into the BN
                        out, ns = node.obj.apply(p, states[node.name], x,
                                                 train, sub, skip_bias=True)
                        pending_bias[node.name] = p.get("b")
                        if node.name in shared:
                            biased[node.name] = L.conv_bias_add(
                                node.obj, out, p.get("b"))
                    elif heads is not None and \
                            getattr(node.obj, "loss_from_input", False):
                        heads[node.name] = (p, x)
                        out, ns = None, states[node.name]
                    elif isinstance(node.obj, _MASK_AWARE):
                        out, ns = node.obj.apply(p, states[node.name],
                                                 x, train, sub, mask=fmask)
                    else:
                        out, ns = node.obj.apply(p, states[node.name],
                                                 x, train, sub)
                    new_states[node.name] = ns
                    fmt[node.name] = cur_nhwc and \
                        getattr(out, "ndim", 0) == L.rank_of(x)
                else:
                    xs = [read(env, i) for i in node.inputs]
                    in_fmts = [fmt[i] for i in node.inputs]
                    transparent = isinstance(
                        node.obj, (ElementWiseVertex, ScaleVertex,
                                   ShiftVertex, PassVertex))
                    if transparent and any(in_fmts) and all(in_fmts):
                        out_nhwc = True        # elementwise: keep layout
                    elif transparent and any(in_fmts) \
                            and all(L.rank_of(a) == 3 for a in xs):
                        # sequences: the one input still [N, C, T] (an
                        # embedding meeting the residual stream) turns
                        # once, not the stream at every add
                        xs = [a if f else jnp.swapaxes(a, 1, 2)
                              for a, f in zip(xs, in_fmts)]
                        out_nhwc = True
                    else:
                        xs = [L.to_public(a) if f else a
                              for a, f in zip(xs, in_fmts)]
                        out_nhwc = False
                    if cdt is not None and len(xs) > 1:
                        # merge/elementwise vertices: align mixed fp32/bf16
                        # inputs (e.g. a BN branch meeting a conv branch)
                        if any(getattr(a, "dtype", None) == jnp.bfloat16
                               for a in xs):
                            xs = [a.astype(jnp.bfloat16)
                                  if getattr(a, "dtype", None) == jnp.float32
                                  else a for a in xs]
                    out = node.obj.apply(*xs)
                    fmt[node.name] = out_nhwc and \
                        getattr(out, "ndim", 0) in (3, 4)
            env[node.name] = out
            return key

        def apply_joint(node, params, states, env, fmt, new_states, key):
            """A layer that takes several inputs (``n_inputs``; those a
            head finds present where it takes any number): each input
            turned and cast as a lone one would be, the layer handed the
            tuple."""
            with jax.named_scope(_devicetime.scope_name(index_of[node.name],
                                                        node.name)):
                p, xs, last = params[owner[node.name]], [], False
                for i in node.inputs:
                    if env[i] is None:
                        continue
                    x, last = L.layout_step(node.obj, read(env, i),
                                            fmt[i], nhwc, sequences=True)
                    if cdt is not None:
                        cast, x = L.policy_cast(node.obj, p, x, cdt)
                    xs.append(x)
                if cdt is not None:
                    p = cast
                key, sub = jax.random.split(key)
                if heads is not None and \
                        getattr(node.obj, "loss_from_input", False):
                    heads[node.name] = (p, tuple(xs))
                    out, ns = None, states[node.name]
                else:
                    out, ns = node.obj.apply(p, states[node.name],
                                             tuple(xs), train, sub)
                new_states[node.name] = ns
                fmt[node.name] = last and \
                    getattr(out, "ndim", 0) == L.rank_of(xs[0])
            env[node.name] = out
            return key

        def run_segment(seg, src, env, fmt, new_states, key, checkpointed):
            """One stretch of a loop's body, from the one value ``src``
            that enters it to its last node's output; ``checkpointed``:
            rematerialise it in the backward pass."""
            names = [n.name for n in seg]

            def stretch(p_seg, s_seg, x, key):
                local, local_fmt, ns = dict(env), dict(fmt), {}
                local[src] = x
                for n in seg:
                    key = apply_node(n, p_seg, s_seg, local, local_fmt,
                                     ns, key)
                fmt.update({k: local_fmt[k] for k in names})
                return local[names[-1]], ns, key

            if checkpointed:
                stretch = jax.checkpoint(stretch)
            out, ns, key = stretch(
                {k: params[k] for k in names if k in params},
                {k: states[k] for k in names if k in states}, env[src], key)
            env[names[-1]] = out
            new_states.update(ns)
            return key

        def run_loop(node, key):
            """Every pass of a LoopVertex, unrolled; the loop's name then
            stands for the tuple of the passes' outputs."""
            loop = node.obj
            x = read(env, node.inputs[0])
            cur = fmt[node.inputs[0]]
            passes = []
            for t in range(loop.steps):
                with jax.named_scope(_stepprogram.pass_scope(t + 1)):
                    env[node.name], fmt[node.name] = x, cur
                    src = node.name
                    for seg in self.conf.loop_segments[node.name]:
                        # one pass IS the plain stack, program and all
                        key = run_segment(seg, src, env, fmt, new_states,
                                          key, remat and loop.steps > 1)
                        src = seg[-1].name
                    x, cur = env[loop.output], fmt[loop.output]
                    passes.append(x)
            env[node.name], fmt[node.name] = tuple(passes), cur
            return key

        def run_stretch(seg, outs, key):
            """One stretch of the plain stack (``conf.stack_stretches``),
            rematerialised in the backward pass: what it reads from
            before it goes in, what is read after it (``outs``) comes
            out, and nothing in between is kept."""
            names = [n.name for n in seg]
            srcs = sorted({i for n in seg for i in n.inputs} - set(names))

            def stretch(p_seg, s_seg, xs, key):
                local, local_fmt, ns = dict(env), dict(fmt), {}
                local.update(xs)
                for n in seg:
                    key = apply_node(n, p_seg, s_seg, local, local_fmt, ns,
                                     key)
                fmt.update({k: local_fmt[k] for k in names})
                return {k: local[k] for k in outs}, ns, key

            made, ns, key = jax.checkpoint(stretch)(
                {k: params[k] for k in {owner[n] for n in names}
                 if k in params},
                {k: states[k] for k in names if k in states},
                {k: env[k] for k in srcs}, key)
            env.update(made)
            new_states.update(ns)
            return key

        new_states = {}
        # the nodes outside every loop, one by one or, where the graph
        # asked and a train step runs, a rematerialised stretch at a time
        # (a loop rematerialises its own body; a head works the loss out)
        stretches = self.conf.stack_stretches \
            if remat and not self._fuse_epilogues else None
        units = stretches or [[n] for n in self.conf.topo if n.loop is None]
        needed, read_later = set(self.conf.graph_outputs), []
        for seg in reversed(units):
            read_later.append(sorted({n.name for n in seg} & needed))
            needed |= {i for n in seg for i in n.inputs}
        for seg, outs in zip(units, reversed(read_later)):
            if stretches and not any(
                    isinstance(n.obj, LoopVertex)
                    or n.name in self.conf.graph_outputs for n in seg):
                key = run_stretch(seg, outs, key)
                continue
            for node in seg:
                key = run_loop(node, key) \
                    if isinstance(node.obj, LoopVertex) else \
                    apply_node(node, params, states, env, fmt, new_states,
                               key)
        return [L.to_public(read(env, o)) if fmt.get(o) else read(env, o)
                for o in self.conf.graph_outputs], new_states

    def _as_input_dict(self, inputs) -> Dict[str, jnp.ndarray]:
        if isinstance(inputs, dict):
            return {k: jnp.asarray(v) for k, v in inputs.items()}
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        return {name: jnp.asarray(a)
                for name, a in zip(self.conf.graph_inputs, inputs)}

    def output(self, *inputs, train: bool = False):
        """ref: ComputationGraph.output — returns list of output arrays
        (single array if one output)."""
        ins = self._as_input_dict(inputs[0] if len(inputs) == 1 else list(inputs))
        outs = self._jit_forward()(self._params, self._states, ins,
                                   jax.random.PRNGKey(0))
        return outs[0] if len(outs) == 1 else outs

    def _jit_forward(self):
        if self._fwd_cache is None:
            def fwd(params, states, ins, key):
                outs, _ = self._forward(params, states, ins, False, key)
                return outs
            # behind the compile-cache seam — see MultiLayerNetwork.
            # _jit_forward (serving warmup)
            self._fwd_cache = _cc.cached_dispatch(fwd, "graph:forward")
        return self._fwd_cache

    def _warm_forward(self, x) -> "ComputationGraph":
        """AOT-compile the inference forward for this input signature
        without executing it (the ``compilecache.warmup`` seam). ``x``:
        one array, a list matching ``graph_inputs``, or a name->array
        dict."""
        ins = self._as_input_dict(x)
        self._jit_forward().warm(self._params, self._states, ins,
                                 jax.random.PRNGKey(0))
        return self

    def _warm_dispatch(self, x, y, fmask=None, lmask=None,
                       steps: int = 1) -> "ComputationGraph":
        """AOT-compile the train step (or K-step megastep) for this
        batch signature without executing it — see
        MultiLayerNetwork._warm_dispatch. ``x``/``y`` accept single
        arrays or lists for multi-input/multi-output graphs (``fmask``
        is unused — graph fits carry no feature mask)."""
        self._ensure_opt_state()
        xs = list(x) if isinstance(x, (list, tuple)) else [x]
        ins = {name: jnp.asarray(a)
               for name, a in zip(self.conf.graph_inputs, xs)}
        ys = list(y) if isinstance(y, (list, tuple)) else [y]
        labels = [jnp.asarray(a) for a in ys]
        lmasks = None
        if lmask is not None:
            lms = list(lmask) if isinstance(lmask, (list, tuple)) else [lmask]
            lmasks = [jnp.asarray(m) for m in lms]
        sig = lmasks is not None
        step, dummy = self._step_for(sig, steps, len(labels))
        clock = jnp.asarray(self._iteration, jnp.int32)
        args = [self._params, self._states, self._opt_state, clock]
        if self._dynamic_scaling():
            args.append(self._ensure_scale_state())
        args += [ins, labels, lmasks if lmasks is not None else dummy]
        step.warm(*args)
        return self

    def feedForward(self, inputs, train: bool = False):
        """Per-node activations, PUBLIC layout (NCHW) even under the
        NHWC compute seam."""
        if self.conf.loop_bodies:
            raise ValueError(
                "feedForward: a node in a loop's body has one activation a "
                "pass; read the passes with output() through a PassVertex")
        if any(getattr(n.obj, "n_inputs", 1) != 1
               or isinstance(n.obj, LabelsVertex) for n in self.conf.topo):
            raise ValueError(
                "feedForward: this graph has layers of several inputs or "
                "reads its labels in the forward pass; use output()")
        ins = self._as_input_dict(inputs)
        env = dict(ins)
        key = jax.random.PRNGKey(0)
        nhwc = self._compute_layout == "NHWC"
        fmt = {k: False for k in env}
        acts = {}
        for node in self.conf.topo:
            if node.kind == "layer":
                x = env[node.inputs[0]]
                cur_nhwc = fmt[node.inputs[0]]
                if node.name in self.conf.preprocessors:
                    if cur_nhwc:
                        x, cur_nhwc = L.to_public(x), False
                    x = self.conf.preprocessors[node.name](x)
                x, cur_nhwc = L.layout_step(node.obj, x, cur_nhwc, nhwc,
                                            sequences=True)
                key, sub = jax.random.split(key)
                if isinstance(node.obj, _MASK_AWARE):
                    out, _ = node.obj.apply(self._params[node.name],
                                            self._states[node.name], x, train,
                                            sub, mask=None)
                else:
                    out, _ = node.obj.apply(self._params[node.name],
                                            self._states[node.name], x, train, sub)
                fmt[node.name] = cur_nhwc and \
                    getattr(out, "ndim", 0) == L.rank_of(x)
            else:
                xs = [L.to_public(env[i]) if fmt[i] else env[i]
                      for i in node.inputs]
                out = node.obj.apply(*xs)
                fmt[node.name] = False
            env[node.name] = out
            acts[node.name] = L.to_public(out) if fmt[node.name] else out
        return acts

    # ------------------------------------------------------------------ loss
    def _output_layers(self):
        outs = []
        for name in self.conf.graph_outputs:
            node = self.conf.node_by_name[name]
            if node.kind != "layer" or not isinstance(node.obj, L.BaseOutputLayer):
                raise ValueError(f"graph output '{name}' must be an output layer")
            outs.append(node.obj)
        return outs

    def _loss_and_reg(self, params, states, ins, labels: List, train, key,
                      fmask, lmasks: Optional[List], remat: bool = False):
        heads: Dict[str, Any] = {}
        outs, new_states = self._forward(params, states, ins, train, key,
                                         fmask, remat=remat, heads=heads,
                                         labels=labels)
        with jax.named_scope(_stepprogram.LOSS_SCOPE):
            out_layers = self._output_layers()
            loss = 0.0
            for i, (ol, out) in enumerate(zip(out_layers, outs)):
                lm = lmasks[i] if lmasks is not None else None
                if ol.name in heads:
                    # the layer works its loss out from its input (a
                    # vocabulary block's logits alive at a time) and hands
                    # its per-pass readings on as its state
                    l, new_states[ol.name] = ol.loss_from(
                        *heads[ol.name], labels[i], mask=lm)
                    loss = loss + l
                    continue
                loss = loss + ol.compute_loss(labels[i], out, mask=lm)
            reg = 0.0
            for node in self.conf.topo:
                if node.kind != "layer":
                    continue
                layer = node.obj
                l1 = layer.l1 or 0.0
                l2 = layer.l2 or 0.0
                p = params.get(node.name) or {}
                if l1 == 0.0 and l2 == 0.0:
                    continue
                for pname, w in p.items():
                    if not pname.startswith(("W", "RW")):
                        continue
                    if l2:
                        reg = reg + 0.5 * l2 * jnp.sum(jnp.square(w))
                    if l1:
                        reg = reg + l1 * jnp.sum(jnp.abs(w))
            return loss + reg, new_states

    # ------------------------------------------------------------------- fit
    def _make_train_step(self, with_lmasks: bool, steps: int = 1):
        """Compile the train step; ``steps=K`` wraps the SAME body in one
        lax.scan program doing K update steps per dispatch (see
        MultiLayerNetwork._make_train_step). The step builder, not the
        user, decides what is rematerialised: the body of every
        LoopVertex, a stretch at a time (``_forward(remat=True)``); a
        graph without a loop compiles the program it always did."""
        base = self.conf.base
        updater = base.updater

        seed = base.seed

        augment = self._augment
        # static loss scaling under the precision seam — see
        # MultiLayerNetwork._make_train_step
        pol = self._precision
        if pol is not None and pol.is_dynamic:
            return self._make_dynamic_train_step(steps=steps,
                                                 with_lmasks=with_lmasks)
        loss_scale = pol.loss_scale if pol is not None else None
        # GSPMD output sharding constraints — see
        # MultiLayerNetwork._make_train_step
        plan = self._sharding_plan
        psh, osh = (None, None) if plan is None \
            else plan.step_constraints(self)

        def step(params, states, opt_state, t, ins, labels, lmasks):
            # per-step RNG from the donated device counter (see
            # MultiLayerNetwork._make_train_step: avoids a host->device
            # upload per iteration, stays resume-deterministic)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
            if augment is not None:
                # on-device augmentation prelude: every 4-D (NCHW image)
                # input runs the seeded chain; non-image inputs pass
                # through (nn.augment.maybe_augment)
                ins = {name: _augment_mod.maybe_augment(augment, v, t)
                       for name, v in ins.items()}

            def loss_fn(p):
                loss, ns = self._loss_and_reg(
                    p, states, ins, labels, True, key,
                    None, lmasks if with_lmasks else None, remat=True)
                if loss_scale:
                    loss = loss * loss_scale
                return loss, ns
            (loss, new_states), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            if loss_scale:
                inv = 1.0 / loss_scale
                loss = loss * inv           # listeners/score see true loss
                grads = _unscale_grads(grads, inv)
            new_params, new_opt = _process_and_apply_grads(
                base, updater, params, grads, opt_state, t.astype(jnp.float32))
            new_params = _stepping.constrain_tree(new_params, psh)
            new_opt = _stepping.constrain_tree(new_opt, osh)
            return new_params, new_states, new_opt, t + 1, loss
        # donate params/states/opt_state/t: the step consumes and replaces
        # them, halving peak HBM for the update and letting dependent
        # dispatches queue without a host round trip. Behind the
        # compile-cache seam (nn.compilecache) like the MLN steps.
        if steps > 1:
            return _cc.cached_dispatch(
                _stepping.scan_megastep(step, 4), "graph:megastep",
                donate_argnums=(0, 1, 2, 3))
        return _cc.cached_dispatch(step, "graph:train_step",
                                   donate_argnums=(0, 1, 2, 3))

    def _make_dynamic_train_step(self, steps: int, with_lmasks: bool):
        """Train step under ``PrecisionPolicy(loss_scale="dynamic")`` —
        the grow/backoff automaton traced into the compiled program; see
        MultiLayerNetwork._make_dynamic_train_step (this is its graph
        mirror: ins dict + labels list, no feature mask)."""
        base = self.conf.base
        updater = base.updater
        seed = base.seed
        augment = self._augment
        pol = self._precision
        plan = self._sharding_plan
        psh, osh = (None, None) if plan is None \
            else plan.step_constraints(self)

        def step(params, states, opt_state, t, scale_state, ins, labels,
                 lmasks):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
            if augment is not None:
                ins = {name: _augment_mod.maybe_augment(augment, v, t)
                       for name, v in ins.items()}
            scale = scale_state[0]

            def loss_fn(p):
                loss, ns = self._loss_and_reg(
                    p, states, ins, labels, True, key,
                    None, lmasks if with_lmasks else None, remat=True)
                return loss * scale, ns
            (loss, new_states), grads = \
                jax.value_and_grad(loss_fn, has_aux=True)(params)
            inv = 1.0 / scale
            loss = loss * inv           # listeners/score see true loss
            grads = _unscale_grads(grads, inv)
            ok = _grads_all_finite(grads)
            new_params, new_opt = _process_and_apply_grads(
                base, updater, params, grads, opt_state,
                t.astype(jnp.float32))
            new_params = _select_update(ok, new_params, params)
            new_opt = _select_update(ok, new_opt, opt_state)
            new_states = _select_update(ok, new_states, states)
            new_params = _stepping.constrain_tree(new_params, psh)
            new_opt = _stepping.constrain_tree(new_opt, osh)
            return (new_params, new_states, new_opt, t + 1,
                    _dynamic_scale_next(pol, scale_state, ok), loss)
        if steps > 1:
            return _cc.cached_dispatch(
                _stepping.scan_megastep(step, 5), "graph:megastep",
                donate_argnums=(0, 1, 2, 3, 4))
        return _cc.cached_dispatch(step, "graph:train_step",
                                   donate_argnums=(0, 1, 2, 3, 4))

    def _step_for(self, sig, steps: int, n_labels: int):
        """(compiled step, dummy mask list) for one mask signature ×
        dispatch K — THE single lookup `_fit_one`, `_fit_mega`, and
        `_warm_dispatch` share (see MultiLayerNetwork._step_for)."""
        if steps > 1:
            if (sig, steps) not in self._megastep_cache:
                self._megastep_cache[(sig, steps)] = \
                    self._make_train_step(sig, steps=steps)
            return (self._megastep_cache[(sig, steps)],
                    [jnp.zeros((steps, 1))] * n_labels)
        if sig not in self._train_step_cache:
            self._train_step_cache[sig] = self._make_train_step(sig)
        return self._train_step_cache[sig], [jnp.zeros((1,))] * n_labels

    def _dynamic_scaling(self) -> bool:
        pol = self._precision
        return pol is not None and pol.is_dynamic

    def _ensure_scale_state(self):
        """Device-resident ``[scale, good_steps]`` dynamic loss-scale
        carry — see MultiLayerNetwork._ensure_scale_state."""
        if self._scale_state is None:
            s = jnp.asarray(
                [float(self._precision.loss_scale_init), 0.0], jnp.float32)
            if self._sharding_plan is not None:  # see _ensure_clock
                s = jax.device_put(s, self._sharding_plan.mesh.replicated())
            self._scale_state = s
        return self._scale_state

    def current_loss_scale(self):
        """Live dynamic loss scale / static scale / None — see
        MultiLayerNetwork.current_loss_scale."""
        if self._dynamic_scaling():
            if self._scale_state is None:
                return float(self._precision.loss_scale_init)
            return float(np.asarray(jax.device_get(self._scale_state))[0])
        pol = self._precision
        return pol.loss_scale if pol is not None else None

    def _ensure_opt_state(self):
        if self._opt_state is None:
            updater = self.conf.base.updater
            self._opt_state = jax.tree_util.tree_map(
                lambda p: updater.init_state(p), self._params,
                is_leaf=lambda x: isinstance(x, jax.Array))

    def _ensure_clock(self):
        """Device-resident iteration counter (int32 scalar), donated and
        incremented inside the compiled step — see
        MultiLayerNetwork._ensure_clock (incl. the GSPMD-plan commit)."""
        if self._t_dev is None:
            t = jnp.asarray(self._iteration, jnp.int32)
            if self._sharding_plan is not None:
                t = jax.device_put(t, self._sharding_plan.mesh.replicated())
            self._t_dev = t
        return self._t_dev

    def setComputeLayout(self, fmt: str) -> "ComputationGraph":
        """NHWC compute layout for the conv stacks — semantics identical
        to ``MultiLayerNetwork.setComputeLayout`` (channels-minor conv/
        pool/BN inside the compiled step, transpose-at-boundary, public
        NCHW API unchanged; elementwise vertices — the ResNet residual
        add — stay in NHWC between aware layers)."""
        if fmt not in ("NCHW", "NHWC"):
            raise ValueError(f"compute layout must be 'NCHW' or 'NHWC', "
                             f"got {fmt!r}")
        if fmt != getattr(self, "_compute_layout", "NCHW"):
            self._train_step_cache.clear()
            self._megastep_cache.clear()
            self._fwd_cache = None
        self._compute_layout = fmt
        # recorded on the config too, so save/load round-trips the seam
        self.conf.base.compute_layout = fmt
        L.stamp_layout([n.obj for n in self.conf.topo if n.kind == "layer"],
                       fmt)
        return self

    def setEpilogueFusion(self, enabled: bool = True) -> "ComputationGraph":
        """Fuse conv-bias+BN+relu / BN+leaky blocks into one
        ``scale_shift_act`` dispatch — see
        ``MultiLayerNetwork.setEpilogueFusion``. On a graph, a fusion
        anchors at a BatchNormalization node whose ONLY consumer is a
        relu/leaky ActivationLayer node.  A conv whose output feeds
        MORE consumers than the BN still folds: the BN takes the
        bias-less output (bias rides in its shift) and the other
        consumers read a bit-identical re-biased copy, so residual
        taps off a conv no longer block the fold."""
        enabled = bool(enabled)
        if enabled != self._fuse_epilogues:
            self._train_step_cache.clear()
            self._megastep_cache.clear()
            self._fwd_cache = None
            self._epilogue_plan = None
            self._epilogue_shared = None
        self._fuse_epilogues = enabled
        return self

    def _ensure_epilogue_plan(self):
        """{bn_node: (act_node, folded_conv_node|None, alpha)} — static,
        built once per fusion toggle from the graph topology.  Also
        builds ``self._epilogue_shared``: folded convs whose output has
        consumers BESIDES the anchoring BN — ``_forward`` materializes a
        bit-identical re-biased copy for those readers (the fold itself
        still skips the bias and rides it in the BN shift)."""
        if (self._epilogue_plan is not None
                and getattr(self, "_epilogue_shared", None) is not None):
            return self._epilogue_plan
        conf = self.conf
        consumers: Dict[str, List[str]] = {}
        for node in conf.topo:
            for inp in node.inputs:
                consumers.setdefault(inp, []).append(node.name)
        for out in conf.graph_outputs:
            consumers.setdefault(out, []).append("__output__")
        plan: Dict[str, tuple] = {}
        folded: set = set()          # convs already claimed by an earlier BN
        shared: set = set()          # folded convs with extra consumers
        by_name = conf.node_by_name
        for node in conf.topo:
            if node.kind != "layer" or node.loop is not None \
                    or not L.fusable_bn(node.obj):
                continue
            cons = consumers.get(node.name, [])
            if len(cons) != 1 or cons[0] == "__output__":
                continue
            act_node = by_name[cons[0]]
            if (act_node.kind != "layer" or len(act_node.inputs) != 1
                    or act_node.name in conf.preprocessors):
                continue
            alpha = L.activation_alpha(act_node.obj)
            if alpha is None:
                continue
            conv_name = None
            src = by_name.get(node.inputs[0]) if node.inputs else None
            # a conv feeding >1 consumer no longer blocks the fold; it
            # folds into AT MOST one BN (first in topo order), and any
            # other consumer reads the re-biased copy
            if (src is not None and src.kind == "layer"
                    and L.fusable_conv(src.obj) and src.obj.has_bias
                    and src.name not in folded
                    and node.name not in conf.preprocessors):
                conv_name = src.name
                folded.add(src.name)
                if len(consumers.get(src.name, [])) > 1:
                    shared.add(src.name)
            plan[node.name] = (act_node.name, conv_name, alpha)
        self._epilogue_plan = plan
        self._epilogue_shared = shared
        return plan

    def setDeviceAugmentation(self, augment) -> "ComputationGraph":
        """Attach (or detach with ``None``) a
        :class:`~deeplearning4j_tpu.nn.augment.DeviceAugmentation` — the
        seeded on-device crop/flip/normalize prelude; semantics identical
        to ``MultiLayerNetwork.setDeviceAugmentation`` (image inputs
        only; a changed chain invalidates the compiled step caches)."""
        cur = getattr(self, "_augment", None)
        same = (augment.signature() if augment is not None else None) == \
            (cur.signature() if cur is not None else None)
        self._augment = augment
        if not same:
            self._train_step_cache.clear()
            self._megastep_cache.clear()
        return self

    def setShardingPlan(self, plan) -> "ComputationGraph":
        """Attach (or detach with ``None``) a
        :class:`~deeplearning4j_tpu.distributed.gspmd.
        ShardedTrainingPlan` — semantics identical to
        ``MultiLayerNetwork.setShardingPlan`` (NamedSharding placement
        on params/updater state, plan-derived batch staging, output
        sharding constraints inside the ONE compiled step; a changed
        plan signature busts the step caches, an equal one keeps
        them)."""
        cur = self._sharding_plan
        same = (plan.signature() if plan is not None else None) == \
            (cur.signature() if cur is not None else None)
        self._sharding_plan = plan
        if not same:
            self._train_step_cache.clear()
            self._megastep_cache.clear()
            self._fwd_cache = None
            self._t_dev = None  # the device clock moves to the plan's mesh
        return self

    def setPrecisionPolicy(self, policy) -> "ComputationGraph":
        """Attach (or detach with ``None``) a
        :class:`~deeplearning4j_tpu.nn.precision.PrecisionPolicy` (or a
        dtype string like ``"bf16"``) — semantics identical to
        ``MultiLayerNetwork.setPrecisionPolicy`` (fp32 master params,
        loss scaling around the backward pass, signature-keyed cache
        bust on change, zero steady-state recompiles on re-attach)."""
        from deeplearning4j_tpu.nn.precision import (PrecisionPolicy,
                                                     runtime_check)
        policy = PrecisionPolicy.coerce(policy)
        if policy is not None:
            runtime_check(policy)
        cur = self._precision
        same = (policy.signature() if policy is not None else None) == \
            (cur.signature() if cur is not None else None)
        self._precision = policy
        if not same:
            self._train_step_cache.clear()
            self._megastep_cache.clear()
            self._fwd_cache = None
            self._scale_state = None    # dynamic loss scale restarts with
        return self                     # its policy's init value

    def fit(self, data, labels=None, epochs: int = 1,
            steps_per_dispatch: int = 1, prefetch: int = 2,
            checkpoint=None, nan_policy=None, faults=None, augment=None,
            precision=None, tune=None):
        """Accepts a DataSetIterator, DataSet, MultiDataSet, or arrays.
        ``precision=`` attaches a mixed-precision policy (see
        :meth:`setPrecisionPolicy`).
        ``tune="auto"`` applies the autotuner record store's winning
        plan for this (model, mesh, backend) — see MultiLayerNetwork.fit
        and ``tune/``; a ``TuningPlan`` instance applies directly.
        ``steps_per_dispatch=K`` runs K update steps per compiled dispatch
        with double-buffered device prefetch (``prefetch=0`` = synchronous
        consumption on the calling thread) — see MultiLayerNetwork.fit.
        ``checkpoint=``/``nan_policy=``/``faults=`` enable the fault-
        tolerance layer (atomic checkpoint + auto-resume, NaN recovery
        policies, deterministic fault injection) — semantics identical to
        MultiLayerNetwork.fit, as are ``augment=`` (on-device
        augmentation) and the native megabatch pull from staged pipeline
        iterators."""
        if not self._initialized:
            self.init()
        self._ensure_opt_state()
        if tune is not None:
            steps_per_dispatch, prefetch = _stepping.apply_tuned_plan(
                self, tune, steps_per_dispatch, prefetch)
        if augment is not None:
            self.setDeviceAugmentation(augment)
        if precision is not None:
            self.setPrecisionPolicy(precision)
        _maybe_attach_env_profiler(self)
        session = None
        if checkpoint is not None or nan_policy is not None \
                or faults is not None:
            from deeplearning4j_tpu.train import resilience as _resilience
            session, data = _resilience.begin_session(
                self, data, checkpoint, nan_policy, faults)
            # resume cold-start killer — see MultiLayerNetwork.fit
            session.warm_after_resume(steps_per_dispatch)

        def batches():
            if isinstance(data, DataSetIterator):
                if session is None or not session.consume_skip_reset():
                    data.reset()
                if _stepping.use_dispatch_stream(data, steps_per_dispatch,
                                                 session):
                    yield from data.dispatch_stream()
                    return
                while data.hasNext():
                    yield data.next()
            elif isinstance(data, (DataSet, MultiDataSet)):
                yield data
            elif isinstance(data, (list, tuple)) and data and \
                    isinstance(data[0], (DataSet, MultiDataSet)):
                yield from data
            else:
                yield DataSet(np.asarray(data), np.asarray(labels))

        def epoch_stream():
            return session.wrap_batches(batches()) if session is not None \
                else batches()

        from deeplearning4j_tpu.train.resilience import fit_scope
        with fit_scope(session, self, epochs) as n_epochs:
            for _ in range(n_epochs):
                with _stepping.epoch_span(self):
                    # data-wait vs compute split (see MultiLayerNetwork.fit)
                    if steps_per_dispatch > 1:
                        # plan-derived prefetcher placement (see
                        # MultiLayerNetwork.fit)
                        _stepping.fit_epoch_multistep(
                            self, epoch_stream(), steps_per_dispatch,
                            prefetch,
                            placement=_stepping.batch_placement(self))
                    else:
                        for ds in _prof.iter_with_data_wait(epoch_stream(),
                                                          self):
                            self._fit_one(ds)
                self._epoch += 1
                for lst in self._listeners:
                    if hasattr(lst, "onEpochEnd"):
                        lst.onEpochEnd(self)
                if session is not None:
                    session.on_epoch_end()
        _stepping.publish_loop_gauges(self)
        return self

    def _fit_one(self, ds):
        if self._sharding_plan is not None:
            self._sharding_plan.ensure_placed(self)  # GSPMD placement guard
        spans = _stepping.step_spans(self)
        spans.phase(_stepping.FIT_STAGE)
        stage = lambda a: _stepping.stage_batch(self, a)
        stage_x = lambda a: _stepping.stage_batch(self, a, features=True)
        if isinstance(ds, MultiDataSet):
            ins = {name: stage_x(a)
                   for name, a in zip(self.conf.graph_inputs, ds.features)}
            labels = [stage(a) for a in ds.labels]
            lmasks = [stage(m) for m in ds.labels_masks] \
                if ds.labels_masks else None
        else:
            ins = {self.conf.graph_inputs[0]: stage_x(ds.features)}
            labels = [stage(ds.labels)]
            lmasks = [stage(ds.labels_mask)] if ds.labels_mask is not None else None
        spans.phase(_stepping.FIT_PREPARE)
        # recompile-churn seam (see MultiLayerNetwork._fit_one)
        new_sig = _churn.get_churn_detector().record(
            "ComputationGraph.fit",
            _churn.array_fingerprint(
                [ins[k] for k in sorted(ins)], labels, lmasks), owner=self)
        sig = lmasks is not None
        step, dummy = self._step_for(sig, 1, len(labels))
        # fence read at dispatch ENTRY: any elastic recovery landing after
        # this point voids the whole dispatch, hooks included
        gen = _stepping.fence_generation(self)
        res = getattr(self, "_resilience", None)
        if res is not None:
            res.before_step()
        # provenance sanitizer — see MultiLayerNetwork._fit_one
        tok = _sanitizer.snapshot(self, "graph", ins=ins, labels=labels,
                                  lmasks=lmasks)
        spans.phase(_stepping.FIT_LISTENERS, "start")
        for lst in self._listeners:
            if hasattr(lst, "onIterationStart"):
                # 1-based, matching iterationDone: hook pair refers to the
                # same step number
                lst.onIterationStart(self, self._iteration + 1)
        if _prof.instrumentation_active():
            # keep the amortization-factor gauge consistent with the
            # histogram samples this block records
            _stepping.STEPS_PER_DISPATCH.set(1)
            _stepping.TRAIN_ITERATIONS.inc()
        dyn = self._dynamic_scaling()
        # the host's time to ENQUEUE the step: see MultiLayerNetwork._fit_one
        spans.phase(_stepping.FIT_DISPATCH)
        args = [self._params, self._states, self._opt_state,
                self._ensure_clock()]
        if dyn:     # dynamic loss scale: an extra donated carry
            args.append(self._ensure_scale_state())
        args += [ins, labels, lmasks if lmasks is not None else dummy]
        out = _stepping.dispatch(self, step, args, spans,
                                 "ComputationGraph.fit", new_sig)
        spans.phase(_stepping.FIT_COMMIT)
        with _stepping.dispatch_commit(self, gen) as ok:
            if not ok:      # elastic recovery rolled this step back while
                spans.done()    # the dispatch was hung: discard, no
                return          # bookkeeping
            if dyn:
                (self._params, self._states, self._opt_state, self._t_dev,
                 self._scale_state, loss) = out
            else:
                self._params, self._states, self._opt_state, self._t_dev, \
                    loss = out
        # on-device; score() converts lazily (per-step host sync is ~20x the
        # step cost through a high-latency device link)
        self._score = loss
        _sanitizer.check(self, tok, loss,
                         context=f"loss at iteration {self._iteration}")
        self._last_batch_size = int(next(iter(ins.values())).shape[0])
        self._iteration += 1
        spans.phase(_stepping.FIT_LISTENERS, "done")
        for lst in self._listeners:
            if hasattr(lst, "iterationDone"):
                lst.iterationDone(self, self._iteration, self._epoch)
        spans.done()
        if res is not None:
            res.after_step()

    def _fit_mega(self, mb):
        """One multi-step dispatch over K stacked batches — the graph
        counterpart of MultiLayerNetwork._fit_mega."""
        if not self._initialized:
            self.init()
        self._ensure_opt_state()
        if self._sharding_plan is not None:
            self._sharding_plan.ensure_placed(self)  # see _fit_one
        k = mb.steps
        spans = _stepping.step_spans(self, k)
        spans.phase(_stepping.FIT_STAGE)
        stage = lambda a: _stepping.stage_batch(self, a, mega=True)
        stage_x = lambda a: _stepping.stage_batch(self, a, mega=True,
                                                  features=True)
        if mb.multi:
            ins = {name: stage_x(a)
                   for name, a in zip(self.conf.graph_inputs, mb.features)}
            labels = [stage(a) for a in mb.labels]
            lmasks = [stage(m) for m in mb.labels_mask] \
                if mb.labels_mask else None
        else:
            ins = {self.conf.graph_inputs[0]: stage_x(mb.features)}
            labels = [stage(mb.labels)]
            lmasks = [stage(mb.labels_mask)] \
                if mb.labels_mask is not None else None
        spans.phase(_stepping.FIT_PREPARE)
        new_sig = _churn.get_churn_detector().record(
            "ComputationGraph.megastep",
            _churn.array_fingerprint(
                [ins[k] for k in sorted(ins)], labels, lmasks), owner=self)
        sig = lmasks is not None
        step, dummy = self._step_for(sig, k, len(labels))
        gen = _stepping.fence_generation(self)  # dispatch entry (see _fit_one)
        res = getattr(self, "_resilience", None)
        if res is not None:
            res.before_dispatch()
        tok = _sanitizer.snapshot(self, "graph_mega", ins=ins, labels=labels,
                                  lmasks=lmasks)   # see _fit_one
        if _prof.instrumentation_active():
            _stepping.STEPS_PER_DISPATCH.set(k)
        dyn = self._dynamic_scaling()
        spans.phase(_stepping.FIT_DISPATCH)
        args = [self._params, self._states, self._opt_state,
                self._ensure_clock()]
        if dyn:     # dynamic loss scale: an extra scanned carry
            args.append(self._ensure_scale_state())
        args += [ins, labels, lmasks if lmasks is not None else dummy]
        out = _stepping.dispatch(self, step, args, spans,
                                 "ComputationGraph.megastep", new_sig, k)
        spans.phase(_stepping.FIT_COMMIT)
        with _stepping.dispatch_commit(self, gen) as ok:
            if not ok:
                spans.done()
                return      # abandoned dispatch: see dispatch_commit
            if dyn:
                (self._params, self._states, self._opt_state, self._t_dev,
                 self._scale_state, losses) = out
            else:
                self._params, self._states, self._opt_state, self._t_dev, \
                    losses = out
        _stepping.record_megastep(self, losses, k,
                                  int(next(iter(ins.values())).shape[1]),
                                  san_token=tok, spans=spans)

    # ------------------------------------------------------------- utilities
    def score(self, ds=None) -> float:
        if ds is None:
            if self._score is not None and not isinstance(self._score, float):
                self._score = float(self._score)
            return self._score
        if isinstance(ds, MultiDataSet):
            ins = {n: jnp.asarray(a) for n, a in zip(self.conf.graph_inputs, ds.features)}
            labels = [jnp.asarray(a) for a in ds.labels]
        else:
            ins = {self.conf.graph_inputs[0]: jnp.asarray(ds.features)}
            labels = [jnp.asarray(ds.labels)]
        loss, _ = self._loss_and_reg(self._params, self._states, ins, labels,
                                     False, jax.random.PRNGKey(0), None, None)
        return float(loss)

    def evaluate(self, iterator, evaluation=None, pull_chunk: int = None,
                 prefetch: bool = True) -> Evaluation:
        """Accepts a DataSetIterator or any iterable of DataSets; forwards
        dispatch per batch, predictions pulled D2H in chunked bulk
        device_gets (see nn.multilayer._predict_batches; ``pull_chunk``
        bounds on-device prediction residency, ``prefetch=False`` keeps
        consumption on the calling thread)."""
        from deeplearning4j_tpu.nn.multilayer import _EVAL_PULL_CHUNK
        ev = evaluation or Evaluation()
        for labels, preds, mask in _predict_batches(
                self.output, iterator, pull_chunk or _EVAL_PULL_CHUNK,
                prefetch):
            ev.eval(labels, preds, mask=mask)
        return ev

    def params(self) -> jnp.ndarray:
        # host-side gather before concat for heterogeneously-sharded
        # GSPMD leaves — see MultiLayerNetwork.params() (device-side
        # concatenate over mixed shardings silently misassembles on
        # this jax version); uniform shardings keep the device path
        leaves = jax.tree_util.tree_leaves(self._params)
        if not leaves:
            return jnp.zeros((0,))
        if len({getattr(p, "sharding", None) for p in leaves}) > 1:
            host = jax.device_get(leaves)
            return jnp.asarray(np.concatenate([np.ravel(p) for p in host]))
        return jnp.concatenate([jnp.ravel(p) for p in leaves])

    def numParams(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self._params))

    def setListeners(self, *listeners):
        self._listeners = list(listeners)

    def getLayer(self, name: str):
        return self.conf.node_by_name[name].obj

    def summary(self) -> str:
        lines = ["=" * 78,
                 f"{'Name (Type)':<38}{'In':<20}{'Params':<10}", "=" * 78]
        total = 0
        for node in self.conf.topo:
            n = sum(int(np.prod(p.shape))
                    for p in jax.tree_util.tree_leaves(self._params.get(node.name, {})))
            total += n
            lines.append(f"{f'{node.name} ({type(node.obj).__name__})':<38}"
                         f"{','.join(node.inputs):<20}{n:<10}")
        lines.append(f"Total params: {total}")
        return "\n".join(lines)

    # ------------------------------------------------------------ save / load
    def save(self, path: str, save_updater: bool = True):
        """Atomic (temp + os.replace) model archive — a crash mid-write
        never leaves a truncated zip under ``path`` (serializer parity
        with ModelSerializer.writeModel)."""
        from deeplearning4j_tpu.train.serializer import write_model_zip
        meta = {"type": "ComputationGraph", "iteration": self._iteration,
                "epoch": self._epoch,
                "save_updater": bool(save_updater and self._opt_state is not None)}
        arrays = {}
        for name, p in self._params.items():
            for k, arr in p.items():
                arrays[f"p::{name}::{k}"] = np.asarray(arr)
        for name, s in self._states.items():
            for k, arr in s.items():
                arrays[f"s::{name}::{k}"] = np.asarray(arr)
        if meta["save_updater"]:
            leaves, _ = jax.tree_util.tree_flatten(self._opt_state)
            for j, leaf in enumerate(leaves):
                arrays[f"u::{j}"] = np.asarray(leaf)
        write_model_zip(path, self.conf.to_json(), meta, arrays)

    @staticmethod
    def load(path: str, load_updater: bool = True) -> "ComputationGraph":
        """Raises ``serializer.CorruptModelError`` naming the bad entry on
        a truncated/damaged archive instead of a raw KeyError."""
        from deeplearning4j_tpu.train.serializer import (CorruptModelError,
                                                         read_model_zip,
                                                         require_array)
        conf_json, meta, arrays = read_model_zip(path)
        try:
            conf = ComputationGraphConfiguration.from_json(conf_json)
        except Exception as e:
            raise CorruptModelError(path, "conf.json",
                                    f"unparseable configuration ({e})") from e
        net = ComputationGraph(conf)
        net.init()
        for k in arrays.files:
            parts = k.split("::")
            if parts[0] == "p":
                net._params[parts[1]][parts[2]] = jnp.asarray(arrays[k])
            elif parts[0] == "s":
                net._states[parts[1]][parts[2]] = jnp.asarray(arrays[k])
        net._iteration = meta["iteration"]
        net._epoch = meta["epoch"]
        if load_updater and meta.get("save_updater"):
            net._ensure_opt_state()
            leaves, treedef = jax.tree_util.tree_flatten(net._opt_state)
            new_leaves = [jnp.asarray(require_array(arrays, f"u::{j}", path))
                          for j in range(len(leaves))]
            net._opt_state = jax.tree_util.tree_unflatten(treedef, new_leaves)
        return net
