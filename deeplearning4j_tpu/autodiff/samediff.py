"""SameDiff-equivalent graph/autodiff engine — whole-program XLA compiled.

Reference parity: ``org.nd4j.autodiff.samediff.SameDiff`` + ``SDVariable``
+ the op namespaces ``SDMath/SDNN/SDCNN/SDRNN/SDLoss/SDRandom/SDLinalg/
SDBitwise`` and the execution sessions
``internal.{AbstractSession,InferenceSession,TrainingSession}``
(SURVEY.md §2.2, call stack §3.3).

TPU-native architecture (the single biggest divergence from the reference,
deliberately — SURVEY.md §1): the reference *interprets* the graph op-by-op
in Java, crossing JNI per op. Here the recorded graph is *traced into ONE
jax program* and compiled by XLA per (outputs, placeholder-shapes)
signature — so a whole training step (forward + backward + updater) is a
single fused executable, and gradients come from program transformation
(``jax.grad``) instead of per-op ``doDiff`` chain rule bookkeeping.

Graph model:
- ``variable``  — trainable array (ref: SDVariable VARIABLE type)
- ``constant``  — non-trainable array (ref: CONSTANT)
- ``placeholder`` — fed at execution (ref: PLACEHOLDER)
- op nodes — name-addressed, created through the op namespaces; creation
  order IS topological order (the builder API can't reference a var
  before it exists, same invariant the reference exploits).

Control flow: ``sd.while_loop`` / ``sd.cond`` lower to ``lax.while_loop``
/ ``lax.cond`` instead of interpreting TF-style Enter/Exit/Merge/Switch
frames (SURVEY.md §3.3) — compiler-friendly by construction.
"""

from __future__ import annotations

import base64
import json
import os
import zipfile
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops import registry as op_registry
from deeplearning4j_tpu.train import updaters as upd
from deeplearning4j_tpu.train.updaters import IUpdater


class _Node:
    __slots__ = ("op", "fn", "inputs", "outputs", "attrs", "rebuild")

    def __init__(self, op: str, fn: Callable, inputs: List[str],
                 outputs: List[str], attrs: Dict[str, Any],
                 rebuild: str = None):
        self.op = op
        self.fn = fn
        self.inputs = inputs
        self.outputs = outputs
        self.attrs = attrs
        # Key into _FN_REBUILDERS: nodes whose callable is a closure (not a
        # plain registry op) serialize by recording this key + attrs, and
        # load() rebuilds the closure — same pattern as _make_rng_fn.
        self.rebuild = rebuild


class SDVariable:
    """Symbolic handle into a SameDiff graph (ref: SDVariable)."""

    def __init__(self, sd: "SameDiff", name: str, var_type: str,
                 shape: Optional[Tuple] = None, dtype=None):
        self.sd = sd
        self.name = name
        self.var_type = var_type  # VARIABLE | CONSTANT | PLACEHOLDER | ARRAY
        self._shape = shape
        self.dtype = dtype

    # value access (eager fetch after eval)
    def eval(self, placeholders: Dict[str, Any] = None):
        return self.sd.output(placeholders or {}, [self.name])[self.name]

    def getArr(self):
        if self.var_type == "VARIABLE":
            return self.sd._variables[self.name]
        if self.var_type == "CONSTANT":
            return self.sd._constants[self.name]
        return self.eval()

    def setArray(self, arr):
        if self.var_type == "VARIABLE":
            self.sd._variables[self.name] = jnp.asarray(arr)
        elif self.var_type == "CONSTANT":
            self.sd._constants[self.name] = jnp.asarray(arr)
        else:
            raise ValueError(f"cannot set array on {self.var_type} '{self.name}'")

    @property
    def shape(self):
        return self._shape

    def rename(self, new_name: str) -> "SDVariable":
        self.sd._rename(self.name, new_name)
        self.name = new_name
        return self

    # ---- fluent op builders (each records a node) ----
    def _bin(self, other, op, reverse=False):
        o = self.sd._as_var(other)
        a, b = (o, self) if reverse else (self, o)
        return self.sd._record(op, [a.name, b.name])

    def add(self, o): return self._bin(o, "add")
    def sub(self, o): return self._bin(o, "subtract")
    def mul(self, o): return self._bin(o, "multiply")
    def div(self, o): return self._bin(o, "divide")
    def rsub(self, o): return self._bin(o, "subtract", reverse=True)
    def rdiv(self, o): return self._bin(o, "divide", reverse=True)
    def pow(self, o): return self._bin(o, "pow")
    __add__ = add
    __radd__ = add
    __sub__ = sub
    def __rsub__(self, o): return self.rsub(o)
    __mul__ = mul
    __rmul__ = mul
    __truediv__ = div
    def __rtruediv__(self, o): return self.rdiv(o)
    __pow__ = pow
    def __neg__(self): return self.sd._record("neg", [self.name])
    def __matmul__(self, o): return self.mmul(o)

    def mmul(self, other, transpose_a=False, transpose_b=False):
        return self.sd._record("matmul", [self.name, self.sd._as_var(other).name],
                               attrs={"transpose_a": transpose_a, "transpose_b": transpose_b})

    def gt(self, o): return self._bin(o, "greater")
    def lt(self, o): return self._bin(o, "less")
    def gte(self, o): return self._bin(o, "greater_equal")
    def lte(self, o): return self._bin(o, "less_equal")
    def eq(self, o): return self._bin(o, "equals")
    def neq(self, o): return self._bin(o, "not_equals")

    def _un(self, op, **attrs):
        return self.sd._record(op, [self.name], attrs=attrs)

    def neg(self): return self._un("neg")
    def abs(self): return self._un("abs")
    def exp(self): return self._un("exp")
    def log(self): return self._un("log")
    def sqrt(self): return self._un("sqrt")
    def square(self): return self._un("square")
    def tanh(self): return self._un("tanh")
    def sigmoid(self): return self._un("sigmoid")
    def relu(self): return self._un("relu")
    def softmax(self, axis=-1): return self._un("softmax", axis=axis)

    def sum(self, *axes, keepdims=False):
        return self._un("reduce_sum", axis=list(axes) or None, keepdims=keepdims)
    def mean(self, *axes, keepdims=False):
        return self._un("reduce_mean", axis=list(axes) or None, keepdims=keepdims)
    def max(self, *axes, keepdims=False):
        return self._un("reduce_max", axis=list(axes) or None, keepdims=keepdims)
    def min(self, *axes, keepdims=False):
        return self._un("reduce_min", axis=list(axes) or None, keepdims=keepdims)
    def std(self, *axes): return self.sd.math.std(self, *axes)
    def argmax(self, axis=None): return self._un("argmax", axis=axis)
    def norm2(self, *axes): return self._un("reduce_norm2", axis=list(axes) or None)

    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return self._un("reshape", shape=shape)

    def transpose(self, *perm):
        return self._un("transpose", perm=list(perm) or None)

    def castTo(self, dtype):
        return self._un("cast", dtype=np.dtype(dtype).name)

    def get(self, idx):
        # serializable when the index is basic (ints/slices/ellipsis/newaxis/
        # 1-D int lists); advanced indices (nd arrays, bool masks, traced
        # arrays) keep exact numpy semantics via a closure and are simply not
        # serializable (save() reports it)
        try:
            attrs = {"index": _encode_index(idx)}
        except TypeError:
            return self.sd._record_fn("getitem", lambda x: x[idx], [self.name])
        return self.sd._record_fn("getitem", _make_getitem_fn(attrs),
                                  [self.name], attrs=attrs, rebuild="getitem")

    __getitem__ = get

    def __repr__(self):
        return f"SDVariable(name='{self.name}', type={self.var_type}, shape={self._shape})"


class _Namespace:
    """Base for op namespaces: methods record registry ops."""

    def __init__(self, sd: "SameDiff"):
        self.sd = sd

    def _rec(self, op, inputs, name=None, n_out=1, **attrs):
        names = [v.name if isinstance(v, SDVariable) else self.sd._as_var(v).name
                 for v in inputs]
        return self.sd._record(op, names, name=name, n_out=n_out, attrs=attrs)


class SDMath(_Namespace):
    """ref: org.nd4j.autodiff.samediff.ops.SDMath."""

    def __getattr__(self, op):
        # generic passthrough for elementwise/pairwise/reduce registry ops
        if op_registry.has(op):
            def method(*inputs, name=None, **attrs):
                return self._rec(op, list(inputs), name=name, **attrs)
            return method
        raise AttributeError(op)

    def std(self, x, *axes, name=None):
        return self.sd._record_fn(
            "std", _make_std_fn({}), [x.name], name=name,
            attrs={"axis": tuple(axes) or None}, rebuild="std")

    def variance(self, x, *axes, name=None):
        return self.sd._record_fn(
            "variance", _make_variance_fn({}), [x.name], name=name,
            attrs={"axis": tuple(axes) or None}, rebuild="variance")


class SDNN(_Namespace):
    """ref: ops.SDNN."""

    def linear(self, x, w, b, name=None):
        return self._rec("xw_plus_b", [x, w, b], name=name)

    def reluLayer(self, x, w, b, name=None):
        return self._rec("relu_layer", [x, w, b], name=name)

    def softmax(self, x, axis=-1, name=None):
        return self._rec("softmax", [x], name=name, axis=axis)

    def logSoftmax(self, x, name=None):
        return self._rec("log_softmax", [x], name=name)

    def relu(self, x, name=None): return self._rec("relu", [x], name=name)
    def gelu(self, x, name=None): return self._rec("gelu", [x], name=name)
    def sigmoid(self, x, name=None): return self._rec("sigmoid", [x], name=name)
    def tanh(self, x, name=None): return self._rec("tanh", [x], name=name)
    def swish(self, x, name=None): return self._rec("swish", [x], name=name)

    def biasAdd(self, x, b, name=None): return self._rec("bias_add", [x, b], name=name)

    def layerNorm(self, x, gain, bias=None, axis=-1, name=None):
        ins = [x, gain] + ([bias] if bias is not None else [])
        return self._rec("layer_norm", ins, name=name, axis=axis)

    def batchNorm(self, x, mean, var, gamma, beta, eps=1e-5, axis=1, name=None):
        return self._rec("batchnorm_sd", [x, mean, var, gamma, beta],
                         name=name, eps=eps, axis=axis)

    def dropout(self, x, rate, name=None):
        """Dropout with the graph's per-step RNG stream (active only when
        the execution requests training mode)."""
        sd = self.sd
        return sd._record_rng("dropout", [sd._as_var(x).name], name=name,
                              params={"rate": rate})

    def multiHeadDotProductAttention(self, q, kv, wq, wk, wv, wo,
                                     num_heads, mask=None, name=None):
        ins = [q, kv, wq, wk, wv, wo] + ([mask] if mask is not None else [])
        attrs = {"num_heads": num_heads, "has_mask": mask is not None}
        return self.sd._record_fn("multi_head_dot_product_attention",
                                  _make_mha_fn(attrs),
                                  [self.sd._as_var(v).name for v in ins],
                                  name=name, attrs=attrs,
                                  rebuild="multi_head_dot_product_attention")


class SDCNN(_Namespace):
    """ref: ops.SDCNN."""

    def conv2d(self, x, w, b=None, name=None, **attrs):
        ins = [x, w] + ([b] if b is not None else [])
        return self._rec("conv2d", ins, name=name, **attrs)

    def conv1d(self, x, w, b=None, name=None, **attrs):
        ins = [x, w] + ([b] if b is not None else [])
        return self._rec("conv1d", ins, name=name, **attrs)

    def deconv2d(self, x, w, b=None, name=None, **attrs):
        ins = [x, w] + ([b] if b is not None else [])
        return self._rec("deconv2d", ins, name=name, **attrs)

    def depthWiseConv2d(self, x, w, b=None, name=None, **attrs):
        ins = [x, w] + ([b] if b is not None else [])
        return self._rec("depthwise_conv2d", ins, name=name, **attrs)

    def separableConv2d(self, x, wd, wp, b=None, name=None, **attrs):
        ins = [x, wd, wp] + ([b] if b is not None else [])
        return self._rec("sconv2d", ins, name=name, **attrs)

    def maxPooling2d(self, x, name=None, **attrs):
        return self._rec("maxpool2d", [x], name=name, **attrs)

    def avgPooling2d(self, x, name=None, **attrs):
        return self._rec("avgpool2d", [x], name=name, **attrs)

    def upsampling2d(self, x, scale=2, name=None):
        return self._rec("upsampling2d", [x], name=name, scale=scale)

    def im2Col(self, x, name=None, **attrs):
        return self._rec("im2col", [x], name=name, **attrs)

    def spaceToDepth(self, x, block, name=None):
        return self._rec("space_to_depth", [x], name=name, block_size=block)

    def depthToSpace(self, x, block, name=None):
        return self._rec("depth_to_space", [x], name=name, block_size=block)


class SDRNN(_Namespace):
    """ref: ops.SDRNN."""

    def lstmLayer(self, x_tnc, w_ih, w_hh, b, name=None):
        return self._rec("lstmLayer_out", [x_tnc, w_ih, w_hh, b], name=name)

    def gru(self, x_tnc, w_ih, w_hh, b_ih, b_hh, name=None):
        return self._rec("gru_out", [x_tnc, w_ih, w_hh, b_ih, b_hh], name=name)


class SDLoss(_Namespace):
    """ref: ops.SDLoss."""

    def mse(self, labels, preds, name=None):
        return self._rec("mean_sqerr_loss", [labels, preds], name=name)

    def meanSquaredError(self, labels, preds, name=None):
        return self._rec("mean_sqerr_loss", [labels, preds], name=name)

    def softmaxCrossEntropy(self, labels, logits, name=None):
        return self._rec("softmax_cross_entropy_loss", [labels, logits], name=name)

    def sigmoidCrossEntropy(self, labels, logits, name=None):
        return self._rec("sigmoid_cross_entropy_loss", [labels, logits], name=name)

    def sparseSoftmaxCrossEntropy(self, labels, logits, name=None):
        return self._rec("sparse_softmax_cross_entropy_loss", [labels, logits], name=name)

    def absoluteDifference(self, labels, preds, name=None):
        return self._rec("absolute_difference_loss", [labels, preds], name=name)

    def cosineDistance(self, labels, preds, name=None):
        return self._rec("cosine_distance_loss", [labels, preds], name=name)

    def hingeLoss(self, labels, preds, name=None):
        return self._rec("hinge_loss", [labels, preds], name=name)

    def huberLoss(self, labels, preds, delta=1.0, name=None):
        return self._rec("huber_loss", [labels, preds], name=name, delta=delta)

    def logLoss(self, labels, preds, name=None):
        return self._rec("log_loss", [labels, preds], name=name)

    def l2Loss(self, x, name=None):
        return self._rec("l2_loss", [x], name=name)


class SDRandom(_Namespace):
    """ref: ops.SDRandom — draws use the graph's per-execution RNG stream."""

    def _rng_op(self, opname, shape, name=None, **attrs):
        return self.sd._record_rng(opname, [], name=name,
                                   params={"shape": tuple(shape), **attrs})

    def uniform(self, low, high, shape, name=None):
        return self._rng_op("random_uniform", shape, name=name, minval=low, maxval=high)

    def normal(self, mean, stddev, shape, name=None):
        return self._rng_op("random_normal", shape, name=name, mean=mean, stddev=stddev)

    def bernoulli(self, p, shape, name=None):
        return self._rng_op("random_bernoulli", shape, name=name, p=p)


class SDLinalg(_Namespace):
    """ref: ops.SDLinalg."""

    def mmul(self, a, b, name=None):
        return self._rec("matmul", [a, b], name=name)

    def cholesky(self, a, name=None): return self._rec("cholesky", [a], name=name)
    def qr(self, a, name=None): return self._rec("qr", [a], name=name, n_out=2)
    def svd(self, a, name=None): return self._rec("svd", [a], name=name, n_out=3)
    def inverse(self, a, name=None): return self._rec("matrix_inverse", [a], name=name)
    def det(self, a, name=None): return self._rec("matrix_determinant", [a], name=name)
    def solve(self, a, b, name=None): return self._rec("solve", [a, b], name=name)


class SDBitwise(_Namespace):
    """ref: ops.SDBitwise."""

    def and_(self, a, b, name=None): return self._rec("bitwise_and", [a, b], name=name)
    def or_(self, a, b, name=None): return self._rec("bitwise_or", [a, b], name=name)
    def xor(self, a, b, name=None): return self._rec("bitwise_xor", [a, b], name=name)
    def leftShift(self, a, b, name=None): return self._rec("left_shift", [a, b], name=name)
    def rightShift(self, a, b, name=None): return self._rec("right_shift", [a, b], name=name)


class SDImage(_Namespace):
    """ref: ops.SDImage."""

    def resizeBiLinear(self, x, h, w, name=None):
        return self._rec("resize_bilinear", [x], name=name, size=(h, w))

    def resizeNearestNeighbor(self, x, h, w, name=None):
        return self._rec("resize_nearest_neighbor", [x], name=name, size=(h, w))

    def nonMaxSuppression(self, boxes, scores, max_out, iou_threshold=0.5, name=None):
        return self._rec("non_max_suppression", [boxes, scores], name=name,
                         max_out=max_out, iou_threshold=iou_threshold)


class TrainingConfig:
    """ref: org.nd4j.autodiff.samediff.TrainingConfig (builder)."""

    def __init__(self, updater: IUpdater = None, l1: float = 0.0, l2: float = 0.0,
                 data_set_feature_mapping: Sequence[str] = ("features",),
                 data_set_label_mapping: Sequence[str] = ("labels",),
                 clip_value: float = 0.0, clip_norm: float = 0.0,
                 clip_global_norm: float = 0.0):
        self.updater = updater or upd.Adam()
        self.l1 = l1
        self.l2 = l2
        self.data_set_feature_mapping = list(data_set_feature_mapping)
        self.data_set_label_mapping = list(data_set_label_mapping)
        self.clip_value = clip_value
        self.clip_norm = clip_norm
        self.clip_global_norm = clip_global_norm

    def to_config(self):
        d = dict(self.__dict__)
        d["updater"] = self.updater.to_config()
        return d

    @staticmethod
    def from_config(d):
        d = dict(d)
        d["updater"] = IUpdater.from_config(d["updater"])
        tc = TrainingConfig.__new__(TrainingConfig)
        tc.__dict__.update(d)
        return tc


class History:
    """ref: org.nd4j.autodiff.listeners.records.History."""

    def __init__(self):
        self.loss_curve: List[float] = []

    def lossCurve(self):
        return self.loss_curve


class SameDiff:
    """The graph builder + executor (ref: SameDiff, one huge class there;
    execution here delegates to XLA instead of InferenceSession)."""

    def __init__(self):
        self._variables: Dict[str, jax.Array] = {}     # trainable
        self._constants: Dict[str, jax.Array] = {}
        self._placeholders: Dict[str, Tuple] = {}      # name -> (shape, dtype)
        self._vars: Dict[str, SDVariable] = {}
        self._nodes: List[_Node] = []
        self._producers: Dict[str, _Node] = {}
        self._loss_variables: List[str] = []
        self._name_counter: Dict[str, int] = {}
        self._fn_cache: Dict[Any, Callable] = {}
        self._grad_cache: Dict[Any, Callable] = {}
        self.training_config: Optional[TrainingConfig] = None
        self._train_step_cache = None
        self._updater_state: Optional[Dict] = None
        self._step = 0
        self._listeners: List[Any] = []
        # op namespaces
        self.math = SDMath(self)
        self.nn = SDNN(self)
        self.cnn = SDCNN(self)
        self.rnn = SDRNN(self)
        self.loss = SDLoss(self)
        self.random = SDRandom(self)
        self.linalg = SDLinalg(self)
        self.bitwise = SDBitwise(self)
        self.image = SDImage(self)

    # ------------------------------------------------------------- creation
    @staticmethod
    def create() -> "SameDiff":
        return SameDiff()

    def _unique(self, base: str) -> str:
        if base not in self._vars and base not in self._placeholders:
            return base
        n = self._name_counter.get(base, 0)
        while True:
            n += 1
            cand = f"{base}_{n}"
            if cand not in self._vars and cand not in self._placeholders:
                self._name_counter[base] = n
                return cand

    def placeHolder(self, name: str, shape=None, dtype=jnp.float32) -> SDVariable:
        v = SDVariable(self, name, "PLACEHOLDER", tuple(shape) if shape else None, dtype)
        self._placeholders[name] = (shape, dtype)
        self._vars[name] = v
        return v

    placeholder = placeHolder

    def var(self, name: str, value=None, shape=None, init: str = "xavier",
            rng_key=None, dtype=jnp.float32) -> SDVariable:
        """Trainable variable; either an explicit value or (shape, init)."""
        if value is None:
            value = _initialize(shape, init, rng_key, dtype)
        arr = jnp.asarray(value)
        v = SDVariable(self, name, "VARIABLE", tuple(arr.shape), arr.dtype)
        self._variables[name] = arr
        self._vars[name] = v
        return v

    variable = var

    def constant(self, value, name: str = None) -> SDVariable:
        name = self._unique(name or "const")
        arr = jnp.asarray(value)
        v = SDVariable(self, name, "CONSTANT", tuple(arr.shape), arr.dtype)
        self._constants[name] = arr
        self._vars[name] = v
        return v

    def _as_var(self, x) -> SDVariable:
        if isinstance(x, SDVariable):
            return x
        return self.constant(x)

    # ------------------------------------------------------------- recording
    def _record(self, op: str, input_names: List[str], name: str = None,
                n_out: int = 1, attrs: Dict = None):
        fn = op_registry.get(op)
        return self._record_fn(op, fn, input_names, name=name, n_out=n_out,
                               attrs=attrs, registry_op=True)

    def _record_fn(self, op: str, fn: Callable, input_names: List[str],
                   name: str = None, n_out: int = 1, attrs: Dict = None,
                   registry_op: bool = False, rebuild: str = None):
        attrs = attrs or {}
        base = name or op
        out_names = [self._unique(base if n_out == 1 else f"{base}:{i}")
                     for i in range(n_out)]
        node = _Node(op, fn, list(input_names), out_names, attrs, rebuild=rebuild)
        self._nodes.append(node)
        self._invalidate()
        outs = []
        for on in out_names:
            v = SDVariable(self, on, "ARRAY")
            self._vars[on] = v
            self._producers[on] = node
            outs.append(v)
        return outs[0] if n_out == 1 else tuple(outs)

    def _record_rng(self, op: str, input_names: List[str],
                    name: str = None, params: Dict = None):
        """Record an op that consumes the per-execution RNG key and the
        train flag. The callable is rebuilt from (op, params) — both at
        record time and at load(), so RNG nodes serialize faithfully."""
        params = params or {}
        node_fn = _make_rng_fn(op, params)
        attrs = {"__rng__": True, **params}
        return self._record_fn(op, node_fn, input_names, name=name, attrs=attrs)

    # -------------------------------------------------------- shape report
    def infer_shapes(self, batch_size: int = 1) -> Dict[str, tuple]:
        """Static shape of every graph variable WITHOUT executing anything
        (ref: each DeclarableOp's shape fn feeding SameDiff.summary()).

        Abstract interpretation via jax.eval_shape per node — zero FLOPs,
        no device, no compilation. Placeholder ``None`` dims use
        ``batch_size``; those entries are reported with the substitution
        applied.
        """
        env: Dict[str, jax.ShapeDtypeStruct] = {}
        for k, v in self._variables.items():
            env[k] = jax.ShapeDtypeStruct(v.shape, v.dtype)
        for k, v in self._constants.items():
            a = jnp.asarray(v)
            env[k] = jax.ShapeDtypeStruct(a.shape, a.dtype)
        for k, (shape, dtype) in self._placeholders.items():
            if shape is None:
                # declared rank-free: shapes of everything downstream are
                # unknown (reported as None, like the reference's -1 dims)
                env[k] = None
                continue
            shape = tuple(batch_size if d in (None, -1) else int(d)
                          for d in shape)
            env[k] = jax.ShapeDtypeStruct(shape, dtype)
        key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
        shapes = {k: (tuple(s.shape) if s is not None else None)
                  for k, s in env.items()}
        for node in self._nodes:
            args = [env.get(n) for n in node.inputs]
            if any(a is None for a in args):
                for name in node.outputs:
                    env[name] = None
                    shapes[name] = None
                continue
            if node.attrs.get("__rng__"):
                out = jax.eval_shape(
                    lambda *a: node.fn(*a[:-1], a[-1], False),
                    *args, key_spec)
            else:
                out = jax.eval_shape(lambda *a: node.fn(*a, **node.attrs),
                                     *args)
            outs = (out,) if len(node.outputs) == 1 else tuple(out)
            for name, o in zip(node.outputs, outs):
                leaf = jax.tree_util.tree_leaves(o)[0]
                env[name] = jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
                shapes[name] = tuple(leaf.shape)
        return shapes

    def validate(self, batch_size: int = 1, **kw):
        """Static lint of the recorded op graph — shape propagation over
        the ``_Node`` list plus structural checks (E151 undefined input,
        E152 shape conflict, E153 bad loss variable, W151 dangling
        placeholder, W152 unused variable, W153 training config with no
        loss). Pure-static like ``model.validate()``: no trace, no
        compile, no device. Extra keywords pass through to
        ``analysis.analyze`` (``suppress=``, ``severity_overrides=``)."""
        from deeplearning4j_tpu.analysis import analyze
        return analyze(self, batch_size=batch_size, **kw)

    def summary(self, batch_size: int = 1) -> str:
        """Printable graph summary with per-variable shapes — computed by
        the shape functions / abstract interp, not by running the graph
        (ref: SameDiff.summary())."""
        shapes = self.infer_shapes(batch_size)
        lines = [f"SameDiff: {len(self._variables)} variables, "
                 f"{len(self._placeholders)} placeholders, "
                 f"{len(self._nodes)} ops",
                 f"{'name':<28} {'kind':<12} {'op':<28} shape",
                 "-" * 80]
        for k in self._placeholders:
            lines.append(f"{k:<28} {'PLACEHOLDER':<12} {'':<28} "
                         f"{shapes.get(k)}")
        for k in self._variables:
            lines.append(f"{k:<28} {'VARIABLE':<12} {'':<28} {shapes.get(k)}")
        for k in self._constants:
            if k in self._producers:
                continue  # folded node outputs appear as ops below
            lines.append(f"{k:<28} {'CONSTANT':<12} {'':<28} {shapes.get(k)}")
        for node in self._nodes:
            for o in node.outputs:
                lines.append(f"{o:<28} {'ARRAY':<12} {node.op:<28} "
                             f"{shapes.get(o)}")
        return "\n".join(lines)

    def _rename(self, old: str, new: str):
        for d in (self._variables, self._constants, self._placeholders, self._vars):
            if old in d:
                d[new] = d.pop(old)
        for node in self._nodes:
            node.inputs = [new if i == old else i for i in node.inputs]
            node.outputs = [new if o == old else o for o in node.outputs]
        if old in self._producers:
            self._producers[new] = self._producers.pop(old)
        self._loss_variables = [new if n == old else n for n in self._loss_variables]
        self._invalidate()

    def _invalidate(self):
        self._fn_cache.clear()
        self._grad_cache.clear()
        self._train_step_cache = None

    # ------------------------------------------------------------- execution
    def _needed_nodes(self, output_names: Sequence[str]) -> List[_Node]:
        needed = set()
        stack = list(output_names)
        seen = set()
        while stack:
            n = stack.pop()
            if n in seen:
                continue
            seen.add(n)
            node = self._producers.get(n)
            if node is not None:
                needed.add(id(node))
                stack.extend(node.inputs)
        return [nd for nd in self._nodes if id(nd) in needed]

    def _build_fn(self, output_names: Tuple[str, ...]) -> Callable:
        """Pure function (variables, constants, placeholders, rng_key, train)
        -> {name: array}; trace-compiled by jax."""
        nodes = self._needed_nodes(output_names)

        def fn(variables, constants, placeholders, rng_key, train):
            env = {}
            env.update(variables)
            env.update(constants)
            env.update(placeholders)
            key = rng_key
            for i, node in enumerate(nodes):
                args = [env[n] for n in node.inputs]
                if node.attrs.get("__rng__"):
                    key, sub = jax.random.split(key)
                    res = node.fn(*args, sub, train)
                else:
                    res = node.fn(*args, **node.attrs)
                if len(node.outputs) == 1:
                    env[node.outputs[0]] = res
                else:
                    for o, r in zip(node.outputs, res):
                        env[o] = r
            return {o: env[o] for o in output_names}
        return fn

    def _exec(self, placeholders: Dict[str, Any], output_names: Sequence[str],
              train: bool = False, rng_key=None):
        phs = {k: jnp.asarray(v) for k, v in placeholders.items()}
        key = tuple(output_names), tuple(sorted((k, v.shape, str(v.dtype))
                                                for k, v in phs.items())), train
        if rng_key is None:
            rng_key = jax.random.PRNGKey(self._step)
        if getattr(self, "_exec_backend", "jax") == "native":
            return self._exec_native(key, phs, output_names, train, rng_key)
        if key not in self._fn_cache:
            fn = self._build_fn(tuple(output_names))
            self._fn_cache[key] = jax.jit(fn, static_argnames=("train",))
        return self._fn_cache[key](self._variables, self._constants, phs,
                                   rng_key, train=train)

    # ------------------------------------------------------ native backend
    def setExecBackend(self, backend: str):
        """Execution backend for output()/eval: "jax" (default) or
        "native" — the latter lowers the SAME traced program to StableHLO
        and runs it through the C++ L0 runtime (native/pjrt_runtime.cc),
        the reference's NativeOpExecutioner seam (SURVEY.md §2.1 row 1 /
        §7 item 1). jax stays the tracer; the native client owns
        compilation + buffers + execution."""
        if backend not in ("jax", "native"):
            raise ValueError(f"unknown backend '{backend}'")
        self._exec_backend = backend
        return self

    def _exec_native(self, key, phs, output_names, train, rng_key):
        from deeplearning4j_tpu.native import runtime as native_rt
        cache = getattr(self, "_native_cache", None)
        if cache is None:
            cache = self._native_cache = {}
        args = (self._variables, self._constants, phs, rng_key)
        if key not in cache:
            from deeplearning4j_tpu.utils.environment import Environment
            fn = self._build_fn(tuple(output_names))
            prec = ("float32"
                    if Environment.get().matmul_precision == "float32"
                    else "bfloat16")
            # keep_unused: the XLA parameter list must match the flattened
            # pytree order exactly, even for inputs the program ignores;
            # default_matmul_precision: the env knob must govern the native
            # executable too (the jax path may run on a different backend)
            with jax.default_matmul_precision(prec):
                lowered = jax.jit(fn, static_argnames=("train",),
                                  keep_unused=True).lower(*args, train=train)
            exe = native_rt.get_runtime().compile(lowered.as_text())
            cache[key] = exe
        flat = [np.asarray(x) for x in jax.tree_util.tree_leaves(args)]
        outs = cache[key](*flat)
        treedef = jax.tree_util.tree_structure({n: 0 for n in output_names})
        return jax.tree_util.tree_unflatten(treedef, outs)

    def output(self, placeholders: Dict[str, Any], outputs: Sequence[str],
               train: bool = False) -> Dict[str, jax.Array]:
        """ref: SameDiff.output / batchOutput — ONE compiled program."""
        outputs = [o.name if isinstance(o, SDVariable) else o for o in outputs]
        return self._exec(placeholders or {}, outputs, train=train)

    def batchOutput(self):
        sd = self
        class _B:
            def __init__(self):
                self._phs = {}
                self._outs = []
            def input(self, name, arr):
                self._phs[name] = arr
                return self
            def output(self, *names):
                self._outs.extend(names)
                return self
            def execSingle(self):
                return sd.output(self._phs, self._outs)[self._outs[0]]
            def exec(self):
                return sd.output(self._phs, self._outs)
        return _B()

    # ------------------------------------------------------------- gradients
    def setLossVariables(self, *names):
        self._loss_variables = [n.name if isinstance(n, SDVariable) else n
                                for n in names]
        self._grad_cache.clear()
        self._train_step_cache = None

    def convertToVariables(self, *names):
        """Promote constants to trainable variables (ref:
        SameDiff.convertToVariables) — THE unfreeze step for fine-tuning
        an imported frozen graph: imported weights land as constants;
        promote them, attach a loss, and fit()."""
        for n in names:
            n = n.name if isinstance(n, SDVariable) else n
            if n in self._variables:
                continue
            if n not in self._constants:
                raise ValueError(f"'{n}' is not a constant")
            self._variables[n] = self._constants.pop(n)
            self._vars[n].var_type = "VARIABLE"
        self._updater_state = None       # shape of the state tree changed
        self._invalidate()
        return self

    def convertToConstants(self, *names):
        """Freeze variables into constants (ref: SameDiff.convertToConstants
        — transfer-learning freeze; frozen leaves get no updater state and
        no gradient computation)."""
        for n in names:
            n = n.name if isinstance(n, SDVariable) else n
            if n in self._constants:
                continue
            if n not in self._variables:
                raise ValueError(f"'{n}' is not a variable")
            self._constants[n] = self._variables.pop(n)
            self._vars[n].var_type = "CONSTANT"
        self._updater_state = None
        self._invalidate()
        return self

    def _total_loss_fn(self):
        loss_names = tuple(self._loss_variables)
        if not loss_names:
            raise ValueError("call setLossVariables first")
        base = self._build_fn(loss_names)

        def total(variables, constants, placeholders, rng_key, train):
            outs = base(variables, constants, placeholders, rng_key, train)
            return sum(jnp.sum(outs[n]) for n in loss_names)
        return total

    def calculateGradients(self, placeholders: Dict[str, Any],
                           wrt: Sequence[str] = None) -> Dict[str, jax.Array]:
        """ref: SameDiff.calculateGradients — here ONE reverse-mode program
        (jax.grad) instead of createGradFunction's doDiff graph walk.
        ``wrt`` may name variables AND placeholders (input gradients), like
        the reference."""
        wrt = list(wrt) if wrt else list(self._variables)
        phs = {k: jnp.asarray(v) for k, v in (placeholders or {}).items()}
        unknown = [k for k in wrt if k not in self._variables and k not in phs]
        if unknown:
            raise ValueError(f"calculateGradients: {unknown} are neither "
                             f"variables nor provided placeholders")
        key = ("grad", tuple(self._loss_variables), tuple(wrt),
               tuple(sorted((k, v.shape, str(v.dtype)) for k, v in phs.items())))
        if key not in self._grad_cache:
            total = self._total_loss_fn()
            gfn = jax.jit(jax.grad(total, argnums=(0, 2)),
                          static_argnames=("train",))
            self._grad_cache[key] = gfn
        var_g, ph_g = self._grad_cache[key](self._variables, self._constants, phs,
                                            jax.random.PRNGKey(self._step),
                                            train=False)
        merged = {**ph_g, **var_g}
        return {k: merged[k] for k in wrt}

    # ------------------------------------------------------------- training
    def setTrainingConfig(self, cfg: TrainingConfig):
        self.training_config = cfg
        self._train_step_cache = None

    def setListeners(self, *listeners):
        self._listeners = list(listeners)

    def _make_train_step(self):
        cfg = self.training_config
        updater = cfg.updater
        total = self._total_loss_fn()

        def step(variables, constants, opt_state, t_dev, placeholders):
            # t_dev: DONATED int32 device counter; rng derived on device from
            # it (no per-step host uploads — they serialize the dispatch
            # pipeline)
            rng_key = jax.random.fold_in(jax.random.PRNGKey(0), t_dev)
            t = t_dev.astype(jnp.float32)
            loss, grads = jax.value_and_grad(total)(variables, constants,
                                                    placeholders, rng_key, True)
            if cfg.l1 or cfg.l2:
                grads = {k: upd.apply_regularization(variables[k], g, cfg.l1, cfg.l2)
                         for k, g in grads.items()}
            if cfg.clip_value:
                grads = upd.clip_by_value(grads, cfg.clip_value)
            if cfg.clip_norm:
                grads = upd.clip_by_norm(grads, cfg.clip_norm)
            if cfg.clip_global_norm:
                grads = upd.clip_by_global_norm(grads, cfg.clip_global_norm)
            lr = updater.lr_at(t)
            new_vars, new_state = {}, {}
            for k, g in grads.items():
                u, s = updater.apply(g, opt_state[k], lr, t)
                if (isinstance(updater, upd.AdamW) and updater.weight_decay
                        and variables[k].ndim >= 2):
                    # decoupled decay on weight matrices only — biases and
                    # norm scales (1-D) are exempt, like the loss-side L1/L2
                    u = u + updater.weight_decay_update(variables[k], lr)
                new_vars[k] = variables[k] - u
                new_state[k] = s
            return new_vars, new_state, t_dev + 1, loss
        return jax.jit(step, donate_argnums=(0, 2, 3))

    def fit(self, data=None, epochs: int = 1, batch_size: int = None,
            iterator=None) -> History:
        """ref: SameDiff.fit(MultiDataSetIterator) → TrainingSession.

        ``data``: either an iterator yielding dicts {placeholder: array}
        (re-iterable per epoch), or a dict of full arrays (optionally
        minibatched by ``batch_size``).
        """
        if self.training_config is None:
            raise ValueError("setTrainingConfig first")
        cfg = self.training_config
        if self._updater_state is None:
            self._updater_state = {k: cfg.updater.init_state(v)
                                   for k, v in self._variables.items()}
        if self._train_step_cache is None:
            self._train_step_cache = self._make_train_step()
        train_step = self._train_step_cache
        hist = History()

        def batches():
            src = iterator if iterator is not None else data
            if isinstance(src, dict):
                n = next(iter(src.values())).shape[0]
                bs = batch_size or n
                for i in range(0, n, bs):
                    yield {k: v[i:i + bs] for k, v in src.items()}
            else:
                for b in src:
                    if isinstance(b, dict):
                        yield b
                    else:  # (features, labels) pair → map via config
                        feats, labels = b
                        out = {}
                        f_list = feats if isinstance(feats, (list, tuple)) else [feats]
                        l_list = labels if isinstance(labels, (list, tuple)) else [labels]
                        for name, arr in zip(cfg.data_set_feature_mapping, f_list):
                            out[name] = arr
                        for name, arr in zip(cfg.data_set_label_mapping, l_list):
                            out[name] = arr
                        yield out

        # the compiled step DONATES the variable buffers; copy once per fit
        # so arrays the caller passed to var(...) (or grabbed via getArr()
        # before fit) survive — only framework-owned buffers get donated
        self._variables = {k: jnp.copy(v) for k, v in self._variables.items()}
        t_dev = jnp.asarray(self._step, jnp.int32)
        for epoch in range(epochs):
            for batch in batches():
                phs = {k: jnp.asarray(v) for k, v in batch.items()}
                self._variables, self._updater_state, t_dev, loss = train_step(
                    self._variables, self._constants, self._updater_state,
                    t_dev, phs)
                # keep losses on-device during the epoch; convert in bulk at
                # the end (per-step float() blocks the pipeline on every step)
                hist.loss_curve.append(loss)
                self._step += 1
                for lst in self._listeners:
                    if hasattr(lst, "iterationDone"):
                        lst.iterationDone(self, self._step, loss)
        hist.loss_curve = [float(l) for l in jax.device_get(hist.loss_curve)]
        return hist

    # ---------------------------------------------------------- control flow
    def while_loop(self, cond_fn, body_fn, init_vars: Sequence[SDVariable],
                   name: str = None):
        """Lower to lax.while_loop (ref: interpreted Enter/Exit/Merge frames).

        Two body forms:
        - Python callables over raw jax arrays — fast to write, but the
          node cannot be serialized (no data form for a closure).
        - SameDiff subgraphs — ``cond_fn``/``body_fn`` are SameDiff
          instances whose placeholders (declaration order) are the loop
          carries; the last-recorded node output (or an explicit
          ``outputs`` list via attrs) is the result. These round-trip
          through save()/load() and are what the TF importer emits for
          StatelessWhile.
        """
        names = [self._as_var(v).name for v in init_vars]
        n = len(names)
        if isinstance(cond_fn, SameDiff) and isinstance(body_fn, SameDiff):
            attrs = {"cond": subgraph_spec(cond_fn,
                                           cond_fn._default_outputs(1)),
                     "body": subgraph_spec(body_fn,
                                           body_fn._default_outputs(n))}
            if _sub_has_rng(attrs["cond"], attrs["body"]):
                attrs["__rng__"] = True
            fn = _make_subwhile_fn(attrs)
            return self._record_fn("while_loop", fn, names, name=name,
                                   n_out=n, attrs=attrs, rebuild="subwhile")

        def fn(*args):
            def body(c):
                out = body_fn(*c)
                return tuple(out) if isinstance(out, (tuple, list)) else (out,)
            res = jax.lax.while_loop(lambda c: cond_fn(*c), body, tuple(args))
            return res[0] if n == 1 else res
        return self._record_fn("while_loop", fn, names, name=name, n_out=n)

    def cond(self, pred: SDVariable, true_fn, false_fn, operands: Sequence[SDVariable],
             name: str = None, n_out: int = 1):
        """Lower to lax.cond. Branches are Python callables (not
        serializable) or SameDiff subgraphs (round-trip; see while_loop)."""
        names = [self._as_var(pred).name] + [self._as_var(v).name for v in operands]
        if isinstance(true_fn, SameDiff) and isinstance(false_fn, SameDiff):
            attrs = {"true": subgraph_spec(true_fn,
                                           true_fn._default_outputs(n_out)),
                     "false": subgraph_spec(false_fn,
                                            false_fn._default_outputs(n_out))}
            if _sub_has_rng(attrs["true"], attrs["false"]):
                attrs["__rng__"] = True
            fn = _make_subcond_fn(attrs)
            return self._record_fn("cond", fn, names, name=name, n_out=n_out,
                                   attrs=attrs, rebuild="subcond")

        def fn(p, *args):
            return jax.lax.cond(p, lambda c: true_fn(*c), lambda c: false_fn(*c),
                                tuple(args))
        return self._record_fn("cond", fn, names, name=name)

    def invoke_subgraph(self, sub: "SameDiff", inputs: Sequence[SDVariable],
                        outputs: Sequence[str] = None, name: str = None):
        """Record a whole subgraph as ONE node (function-call inlining —
        ref: the import of PartitionedCall / FunctionDef bodies).
        Differentiable and serializable."""
        names = [self._as_var(v).name for v in inputs]
        outs = list(outputs) if outputs else sub._default_outputs(1)
        attrs = {"sub": subgraph_spec(sub, outs)}
        if _sub_has_rng(attrs["sub"]):
            attrs["__rng__"] = True
        fn = _make_subcall_fn(attrs)
        return self._record_fn("subgraph", fn, names, name=name,
                               n_out=len(outs), attrs=attrs, rebuild="subcall")

    def setOutputs(self, *names):
        """Mark this graph's result variables (used when the graph serves
        as a control-flow body / called subgraph)."""
        self._marked_outputs = [n.name if isinstance(n, SDVariable) else n
                                for n in names]
        return self

    def _default_outputs(self, n: int) -> List[str]:
        """Explicitly marked outputs, else the last n recorded outputs —
        the convention for subgraph results."""
        marked = getattr(self, "_marked_outputs", None)
        if marked:
            if len(marked) != n:
                raise ValueError(f"subgraph marks {len(marked)} outputs, "
                                 f"{n} required")
            return list(marked)
        if not self._nodes:
            # identity subgraph: outputs are the last n placeholders
            phs = list(self._placeholders)
            return phs[-n:]
        outs = [o for node in self._nodes for o in node.outputs]
        return outs[-n:]

    # ------------------------------------------------------------- utilities
    def variables(self) -> List[SDVariable]:
        return [self._vars[n] for n in self._variables]

    def getVariable(self, name: str) -> SDVariable:
        return self._vars[name]

    def hasVariable(self, name: str) -> bool:
        return name in self._vars

    # ------------------------------------------------------- save / load
    def save(self, path: str, save_updater_state: bool = True):
        """ref: SameDiff.save (FlatBuffers zip). Format: zip with graph.json
        + arrays.npz (+ updater state).

        Closure-backed nodes (attention, std/variance, getitem, RNG ops)
        serialize via a rebuild key + attrs and are reconstructed at load().
        ``while_loop``/``cond`` are explicitly NOT serializable: their bodies
        are arbitrary Python callables (the reference serializes interpreted
        Enter/Exit/Merge frames; the TPU rebuild compiles bodies to
        lax.while_loop/cond, which have no data representation) — save()
        raises with this explanation, callers must rebuild such graphs from
        code."""
        graph = {"nodes": [], "placeholders": {k: [list(v[0]) if v[0] else None,
                                                   str(np.dtype(v[1]) if not isinstance(v[1], str) else v[1])]
                                               for k, v in self._placeholders.items()},
                 "loss_variables": self._loss_variables,
                 "step": self._step}
        for node in self._nodes:
            graph["nodes"].append(_node_to_spec(node))
        if self.training_config is not None:
            graph["training_config"] = self.training_config.to_config()
        arrays = {f"var::{k}": np.asarray(v) for k, v in self._variables.items()}
        arrays.update({f"const::{k}": np.asarray(v) for k, v in self._constants.items()})
        if save_updater_state and self._updater_state is not None:
            flat, treedef = jax.tree_util.tree_flatten(self._updater_state)
            for i, leaf in enumerate(flat):
                arrays[f"upd::{i}"] = np.asarray(leaf)
            graph["updater_treedef"] = _treedef_to_json(self._updater_state)
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with zipfile.ZipFile(path, "w") as z:
            z.writestr("graph.json", json.dumps(graph))
            import io
            buf = io.BytesIO()
            np.savez(buf, **arrays)
            z.writestr("arrays.npz", buf.getvalue())

    @staticmethod
    def load(path: str) -> "SameDiff":
        sd = SameDiff()
        with zipfile.ZipFile(path) as z:
            graph = json.loads(z.read("graph.json"))
            import io
            arrays = np.load(io.BytesIO(z.read("arrays.npz")))
        for name, spec in graph["placeholders"].items():
            shape = tuple(spec[0]) if spec[0] else None
            sd.placeHolder(name, shape=shape, dtype=np.dtype(spec[1]))
        upd_leaves = {}
        for k in arrays.files:
            kind, _, name = k.partition("::")
            if kind == "var":
                sd.var(name, arrays[k])
            elif kind == "const":
                sd.constant(arrays[k], name=name)
            elif kind == "upd":
                upd_leaves[int(name)] = jnp.asarray(arrays[k])
        for nd_spec in graph["nodes"]:
            node = _node_from_spec(nd_spec)
            sd._nodes.append(node)
            for on in node.outputs:
                sd._vars[on] = SDVariable(sd, on, "ARRAY")
                sd._producers[on] = node
        sd._loss_variables = graph.get("loss_variables", [])
        sd._step = graph.get("step", 0)
        if "training_config" in graph:
            sd.training_config = TrainingConfig.from_config(graph["training_config"])
        if upd_leaves and "updater_treedef" in graph:
            leaves = [upd_leaves[i] for i in range(len(upd_leaves))]
            sd._updater_state = _treedef_from_json(graph["updater_treedef"], leaves)
        return sd


def _node_to_spec(node: _Node) -> dict:
    """JSON-able spec of one node (shared by save() and subgraph specs)."""
    spec = {"op": node.op, "inputs": node.inputs, "outputs": node.outputs,
            "attrs": {k: v for k, v in node.attrs.items() if k != "__rng__"},
            "rng": bool(node.attrs.get("__rng__"))}
    if node.rebuild is not None:
        spec["rebuild"] = node.rebuild
    elif not op_registry.has(node.op):
        raise ValueError(
            f"node '{node.op}' is not serializable: its body is an "
            f"arbitrary Python closure. while_loop/cond round-trip when "
            f"their bodies are SameDiff subgraphs (pass SameDiff instances "
            f"instead of Python callables); raw-callable bodies have no "
            f"data form and must be rebuilt from code after load.")
    return spec


def _node_from_spec(nd_spec: dict) -> _Node:
    """Rebuild a node (with executable fn) from its JSON spec."""
    attrs = dict(nd_spec["attrs"])
    attrs = {k: (tuple(v) if isinstance(v, list) and k != "index" else v)
             for k, v in attrs.items()}
    rebuild = nd_spec.get("rebuild")
    if rebuild is not None:
        if rebuild not in _FN_REBUILDERS and rebuild == "tf":
            # TF-imported graphs: the rebuilder registers on import
            import deeplearning4j_tpu.modelimport.tensorflow  # noqa: F401
        fn = _FN_REBUILDERS[rebuild](attrs)
        if nd_spec.get("rng"):
            # control-flow nodes whose subgraph bodies hold RNG ops still
            # receive (key, train) from the executor
            attrs["__rng__"] = True
    elif nd_spec.get("rng"):
        fn = _make_rng_fn(nd_spec["op"], attrs)
        attrs["__rng__"] = True
    else:
        fn = op_registry.get(nd_spec["op"])
    return _Node(nd_spec["op"], fn, nd_spec["inputs"], nd_spec["outputs"],
                 attrs, rebuild=rebuild)


# ------------------------------------------------------------- subgraphs
# A SameDiff graph can serve as the body of a control-flow node (while/
# cond) or a function call. The subgraph serializes to a fully
# self-contained JSON spec (arrays base64-inline — control-flow bodies
# are small), so control flow round-trips through save()/load() — the
# TPU-native answer to the reference's FlatBuffers'd Enter/Exit/Merge
# frames (SURVEY.md §2.2 SameDiff core).

def _arr_to_json(a) -> dict:
    a = np.asarray(a)
    return {"dtype": str(a.dtype), "shape": list(a.shape),
            "data": base64.b64encode(a.tobytes()).decode("ascii")}


def _arr_from_json(d) -> np.ndarray:
    return np.frombuffer(base64.b64decode(d["data"]),
                         np.dtype(d["dtype"])).reshape(d["shape"]).copy()


def subgraph_spec(sub: "SameDiff", outputs: Sequence[str]) -> dict:
    """Self-contained JSON spec of ``sub``: placeholders (in declared
    order — the call convention), variables folded to constants (subgraph
    weights are closed over, not trained), nodes, and output names."""
    return {
        "ph_order": list(sub._placeholders),
        "placeholders": {k: [list(v[0]) if v[0] else None,
                             str(np.dtype(v[1]) if not isinstance(v[1], str)
                                 else v[1])]
                         for k, v in sub._placeholders.items()},
        "consts": {k: _arr_to_json(v)
                   for k, v in {**sub._constants, **sub._variables}.items()},
        "nodes": [_node_to_spec(n) for n in sub._nodes],
        "outputs": list(outputs),
        # containing nodes thread (rng_key, train) through when True, so
        # dropout/noise inside control-flow bodies stays live in training
        "has_rng": any(n.attrs.get("__rng__") for n in sub._nodes),
    }


def subgraph_from_spec(spec: dict) -> "SameDiff":
    sub = SameDiff()
    for name in spec["ph_order"]:
        shp, dt = spec["placeholders"][name]
        sub.placeHolder(name, shape=tuple(shp) if shp else None,
                        dtype=np.dtype(dt))
    for name, d in spec["consts"].items():
        sub.constant(_arr_from_json(d), name=name)
    for nd_spec in spec["nodes"]:
        node = _node_from_spec(nd_spec)
        sub._nodes.append(node)
        for on in node.outputs:
            sub._vars[on] = SDVariable(sub, on, "ARRAY")
            sub._producers[on] = node
    return sub


def subgraph_fn(spec: dict) -> Callable:
    """Compile a subgraph spec to ``call(*args, key=None, train=False) ->
    tuple(outputs)`` with args bound to the placeholders in declared
    order. RNG nodes inside the subgraph consume ``key``/``train``."""
    sub = subgraph_from_spec(spec)
    outputs = tuple(spec["outputs"])
    ph_names = spec["ph_order"]
    base = sub._build_fn(outputs)

    def call(*args, key=None, train=False):
        k = key if key is not None else jax.random.PRNGKey(0)
        outs = base({}, sub._constants, dict(zip(ph_names, args)), k, train)
        return tuple(outs[n] for n in outputs)
    return call


def _sub_has_rng(*specs) -> bool:
    return any(s.get("has_rng") for s in specs)


def _make_subwhile_fn(attrs: dict) -> Callable:
    cond = subgraph_fn(attrs["cond"])
    body = subgraph_fn(attrs["body"])
    n = len(attrs["body"]["outputs"])

    def run(args, key, train):
        res = jax.lax.while_loop(
            lambda c: jnp.reshape(cond(*c, key=key, train=train)[0],
                                  ()).astype(bool),
            lambda c: body(*c, key=key, train=train), tuple(args))
        return res if n > 1 else res[0]

    if _sub_has_rng(attrs["cond"], attrs["body"]):
        # recorded with __rng__: _build_fn appends (key, train)
        def fn(*all_args):
            *args, key, train = all_args
            return run(args, key, train)
        return fn
    return lambda *args, **_kw: run(args, None, False)


def _make_subcond_fn(attrs: dict) -> Callable:
    tfn = subgraph_fn(attrs["true"])
    ffn = subgraph_fn(attrs["false"])
    n = len(attrs["true"]["outputs"])

    def run(p, args, key, train):
        res = jax.lax.cond(jnp.reshape(p, ()).astype(bool),
                           lambda c: tfn(*c, key=key, train=train),
                           lambda c: ffn(*c, key=key, train=train),
                           tuple(args))
        return res if n > 1 else res[0]

    if _sub_has_rng(attrs["true"], attrs["false"]):
        def fn(p, *all_args):
            *args, key, train = all_args
            return run(p, args, key, train)
        return fn
    return lambda p, *args, **_kw: run(p, args, None, False)


def _make_subcall_fn(attrs: dict) -> Callable:
    """Inline function call: one node that executes a whole subgraph
    (differentiable — jax traces straight through)."""
    sub = subgraph_fn(attrs["sub"])
    n = len(attrs["sub"]["outputs"])

    def run(args, key, train):
        res = sub(*args, key=key, train=train)
        return res if n > 1 else res[0]

    if _sub_has_rng(attrs["sub"]):
        def fn(*all_args):
            *args, key, train = all_args
            return run(args, key, train)
        return fn
    return lambda *args, **_kw: run(args, None, False)


def _make_rng_fn(op: str, params: Dict) -> Callable:
    """Build the executable closure for an RNG node from serializable
    params — used at record time AND at load() so RNG nodes round-trip."""
    inner = op_registry.get(op)
    params = {k: v for k, v in params.items() if k != "__rng__"}
    if op == "dropout":
        rate = params["rate"]
        return lambda x, key, train: inner(x, rate, key, train=train)
    shape = tuple(params.pop("shape"))
    kw = dict(params)
    return lambda key, train: inner(key, shape, **kw)


def _encode_index(idx):
    """JSON-able encoding of a numpy-style index (for serializable getitem)."""
    if isinstance(idx, tuple):
        return {"tuple": [_encode_index(i) for i in idx]}
    if isinstance(idx, slice):
        return {"slice": [idx.start, idx.stop, idx.step]}
    if idx is Ellipsis:
        return {"ellipsis": True}
    if idx is None:
        return {"newaxis": True}
    if isinstance(idx, (int, np.integer)) and not isinstance(idx, (bool, np.bool_)):
        return int(idx)
    if isinstance(idx, list) or (isinstance(idx, np.ndarray) and idx.ndim == 1
                                 and np.issubdtype(idx.dtype, np.integer)):
        return {"list": [int(i) for i in idx]}
    raise TypeError(f"unsupported index for serializable getitem: {idx!r}")


def _decode_index(spec):
    if isinstance(spec, int):
        return spec
    if "tuple" in spec:
        return tuple(_decode_index(s) for s in spec["tuple"])
    if "slice" in spec:
        return slice(*spec["slice"])
    if "ellipsis" in spec:
        return Ellipsis
    if "newaxis" in spec:
        return None
    return list(spec["list"])


def _make_getitem_fn(attrs):
    idx = _decode_index(attrs["index"])
    return lambda x, index=None: x[idx]


def _make_std_fn(attrs):
    return lambda v, axis=None: jnp.std(v, axis=axis, ddof=1)


def _make_variance_fn(attrs):
    return lambda v, axis=None: jnp.var(v, axis=axis, ddof=1)


def _make_mha_fn(attrs):
    """Rebuild the multiHeadDotProductAttention closure; the mask (when
    recorded) is a graph input, passed positionally after the six weights."""
    inner = op_registry.get("multi_head_dot_product_attention")
    if attrs.get("has_mask"):
        def fn(q, kv, wq, wk, wv, wo, m, num_heads=None, has_mask=True):
            return inner(q, kv, wq, wk, wv, wo, num_heads=num_heads, mask=m)
    else:
        def fn(q, kv, wq, wk, wv, wo, num_heads=None, has_mask=False):
            return inner(q, kv, wq, wk, wv, wo, num_heads=num_heads)
    return fn


# rebuild-key -> closure builder; save() records the key, load() calls it
_FN_REBUILDERS = {
    "getitem": _make_getitem_fn,
    "std": _make_std_fn,
    "variance": _make_variance_fn,
    "multi_head_dot_product_attention": _make_mha_fn,
    "subwhile": _make_subwhile_fn,
    "subcond": _make_subcond_fn,
    "subcall": _make_subcall_fn,
}


def _treedef_to_json(tree):
    """Structure of nested dicts (leaves -> None) for round-tripping."""
    if isinstance(tree, dict):
        return {k: _treedef_to_json(v) for k, v in sorted(tree.items())}
    return None


def _treedef_from_json(spec, leaves, _idx=None):
    if _idx is None:
        _idx = [0]
    if spec is None:
        leaf = leaves[_idx[0]]
        _idx[0] += 1
        return leaf
    return {k: _treedef_from_json(v, leaves, _idx) for k, v in sorted(spec.items())}


def _initialize(shape, init: str, rng_key=None, dtype=jnp.float32):
    """Weight init (ref: org.deeplearning4j.nn.weights.WeightInit)."""
    if rng_key is None:
        from deeplearning4j_tpu.linalg import factory
        rng_key = factory.getRandom().next_key()
    shape = tuple(shape)
    init = init.lower()
    fan_in = shape[0] if len(shape) >= 1 else 1
    fan_out = shape[-1] if len(shape) >= 2 else 1
    if len(shape) == 4:  # conv OIHW
        rf = shape[2] * shape[3]
        fan_in, fan_out = shape[1] * rf, shape[0] * rf
    if len(shape) == 5:  # conv3d OIDHW
        rf = shape[2] * shape[3] * shape[4]
        fan_in, fan_out = shape[1] * rf, shape[0] * rf
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init in ("xavier", "glorot_uniform"):
        limit = float(np.sqrt(6.0 / (fan_in + fan_out)))
        return jax.random.uniform(rng_key, shape, dtype, -limit, limit)
    if init in ("xavier_gaussian", "glorot_normal"):
        std = float(np.sqrt(2.0 / (fan_in + fan_out)))
        return std * jax.random.normal(rng_key, shape, dtype)
    if init in ("relu", "he", "he_normal"):
        std = float(np.sqrt(2.0 / fan_in))
        return std * jax.random.normal(rng_key, shape, dtype)
    if init in ("he_uniform", "relu_uniform"):
        limit = float(np.sqrt(6.0 / fan_in))
        return jax.random.uniform(rng_key, shape, dtype, -limit, limit)
    if init in ("lecun_normal",):
        std = float(np.sqrt(1.0 / fan_in))
        return std * jax.random.normal(rng_key, shape, dtype)
    if init in ("uniform",):
        a = float(1.0 / np.sqrt(fan_in))
        return jax.random.uniform(rng_key, shape, dtype, -a, a)
    if init in ("normal", "gaussian"):
        return jax.random.normal(rng_key, shape, dtype) / float(np.sqrt(fan_in))
    raise ValueError(f"unknown weight init '{init}'")
