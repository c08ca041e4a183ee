"""TF frozen-GraphDef import into the SameDiff graph engine.

Reference parity: ``nd4j/samediff-import/samediff-import-tensorflow`` —
``TensorflowFrameworkImporter.runImport`` maps a TF GraphDef node-by-node
into SameDiff via a declarative ``OpMappingRegistry`` (SURVEY.md §2.2
"TF/ONNX import", §3.3 — this is how the reference's BERT enters).

The TPU-native difference: the imported graph is not interpreted op-by-op;
it becomes a SameDiff program that compiles to ONE XLA executable.

Design (round 3):
- Every TF op maps through a **builder**: ``_BUILDERS[tf_op](params) -> fn``
  where ``params`` is a JSON-able dict extracted at import time (static
  shapes, axes, masks — resolved from Const inputs, as XLA requires).
  Imported nodes are recorded under the namespaced op name ``tf.<Op>`` with
  ``rebuild="tf"`` so they never collide with registry ops and serialize
  faithfully through ``SameDiff.save()``/``load()`` (the load path
  re-invokes the builder from the stored params).
- Const folding: a mapped node whose data inputs are all compile-time
  constants (and small) is evaluated at import time and becomes a
  Const — this collapses frozen-graph shape arithmetic (Shape→slice→Pack
  chains over static shapes) into static operands.

Scope: this is a **frozen inference graph** importer, matching the
reference's primary use (``TFGraphMapper`` on frozen .pb). Training-mode
ops (``FusedBatchNorm`` with ``is_training=True``), TF control flow
(Enter/Exit/Merge/Switch frames), and ``Shape``-dependent dynamic
reshapes are rejected with explanatory errors: a BERT *training* GraphDef
should enter through :mod:`.bert` (checkpoint import into the native
flagship transformer), not through GraphDef replay.

TensorFlow is needed only to PARSE protos (tensor decode); the mapping
and execution are TF-free.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.autodiff import samediff as _sdmod
from deeplearning4j_tpu.autodiff.samediff import SameDiff
from deeplearning4j_tpu.ops import registry as _R


class TFImportError(ValueError):
    pass


import ml_dtypes

_DTYPES = {1: np.float32, 2: np.float64, 3: np.int32, 4: np.uint8,
           6: np.int8, 7: str, 9: np.int64, 10: bool,
           14: ml_dtypes.bfloat16, 19: np.float16}

# elements threshold below which an all-const node is folded at import time
_FOLD_LIMIT = 1 << 20


def _attr(node, name, default=None):
    if name not in node.attr:
        return default
    a = node.attr[name]
    kind = a.WhichOneof("value")
    if kind == "i":
        return int(a.i)
    if kind == "f":
        return float(a.f)
    if kind == "b":
        return bool(a.b)
    if kind == "s":
        return a.s.decode("utf-8")
    if kind == "type":
        return _DTYPES.get(a.type)
    if kind == "shape":
        return [d.size for d in a.shape.dim]
    if kind == "list":
        if a.list.i:
            return [int(v) for v in a.list.i]
        if a.list.f:
            return [float(v) for v in a.list.f]
        return []
    return default


def _tensor_value(node) -> np.ndarray:
    """Decode a Const node's tensor proto (uses TF's own decoder)."""
    from tensorflow.python.framework import tensor_util
    return np.asarray(tensor_util.MakeNdarray(node.attr["value"].tensor))


def _conv_padding(node) -> str:
    p = _attr(node, "padding", "VALID")
    if p not in ("SAME", "VALID"):
        raise TFImportError(f"padding {p} unsupported ({node.name})")
    return p


def _np_dtype_name(dt) -> str:
    return np.dtype(dt).name if dt is not None else "float32"


# ------------------------------------------------------------------ builders
# _BUILDERS[tf_op](params: JSON-able dict) -> executable fn(*data_inputs).
# Builders are the single source of truth for semantics: used at import
# time AND at SameDiff.load() (rebuild="tf").

_BUILDERS: Dict[str, Callable[[dict], Callable]] = {}


def _simple(tf_op: str, fn: Callable):
    _BUILDERS[tf_op] = lambda p, _f=fn: _f


_SIMPLE_OPS = {
    "Add": lambda a, b: a + b,
    "AddV2": lambda a, b: a + b,
    "Sub": lambda a, b: a - b,
    "Mul": lambda a, b: a * b,
    "RealDiv": lambda a, b: a / b,
    "Div": lambda a, b: a / b,
    "FloorDiv": jnp.floor_divide,
    "FloorMod": jnp.mod,
    "Mod": jnp.fmod,     # TF Mod is C-truncated; FloorMod is floored
    "Maximum": jnp.maximum,
    "Minimum": jnp.minimum,
    "Pow": jnp.power,
    "SquaredDifference": lambda a, b: jnp.square(a - b),
    "Greater": lambda a, b: a > b,
    "GreaterEqual": lambda a, b: a >= b,
    "Less": lambda a, b: a < b,
    "LessEqual": lambda a, b: a <= b,
    "Equal": lambda a, b: a == b,
    "NotEqual": lambda a, b: a != b,
    "LogicalAnd": jnp.logical_and,
    "LogicalOr": jnp.logical_or,
    "LogicalNot": jnp.logical_not,
    "Relu": jax.nn.relu,
    "Relu6": lambda x: jnp.clip(x, 0, 6),
    "Elu": jax.nn.elu,
    "Selu": jax.nn.selu,
    "Sigmoid": jax.nn.sigmoid,
    "Tanh": jnp.tanh,
    "Erf": jax.lax.erf,
    "Exp": jnp.exp,
    "Log": jnp.log,
    "Log1p": jnp.log1p,
    "Sqrt": jnp.sqrt,
    "Rsqrt": jax.lax.rsqrt,
    "Square": jnp.square,
    "Neg": jnp.negative,
    "Abs": jnp.abs,
    "Sign": jnp.sign,
    "Floor": jnp.floor,
    "Ceil": jnp.ceil,
    "Round": jnp.round,       # TF rounds half-to-even; so does jnp.round
    "Rint": jnp.round,
    "Sin": jnp.sin,
    "Cos": jnp.cos,
    "Tan": jnp.tan,
    "Asin": jnp.arcsin,
    "Acos": jnp.arccos,
    "Atan": jnp.arctan,
    "Atan2": jnp.arctan2,
    "Sinh": jnp.sinh,
    "Cosh": jnp.cosh,
    "Asinh": jnp.arcsinh,
    "Acosh": jnp.arccosh,
    "Atanh": jnp.arctanh,
    "Reciprocal": jnp.reciprocal,
    "Inv": jnp.reciprocal,
    "Identity": lambda x: x,
    "Snapshot": lambda x: x,
    "StopGradient": jax.lax.stop_gradient,
    "PreventGradient": jax.lax.stop_gradient,
    "Softplus": jax.nn.softplus,
    "Softsign": jax.nn.soft_sign,
    "ZerosLike": jnp.zeros_like,
    "OnesLike": jnp.ones_like,
    "Softmax": lambda x: jax.nn.softmax(x, axis=-1),
    "LogSoftmax": lambda x: jax.nn.log_softmax(x, axis=-1),
    "Shape": lambda x: jnp.asarray(jnp.shape(x), jnp.int32),
    "Rank": lambda x: jnp.asarray(jnp.ndim(x), jnp.int32),
    "Size": lambda x: jnp.asarray(jnp.size(x), jnp.int32),
    "IsNan": jnp.isnan,
    "IsInf": jnp.isinf,
    "IsFinite": jnp.isfinite,
    # TF1 Select: a rank-1 condition selects along the FIRST axis
    "Select": lambda c, a, b: jnp.where(
        c.reshape((-1,) + (1,) * (a.ndim - 1)) if c.ndim == 1 and a.ndim > 1
        else c, a, b),
    "SelectV2": lambda c, a, b: jnp.where(c, a, b),
    "AddN": lambda *xs: sum(xs[1:], xs[0]),
    "InvertPermutation": lambda p: jnp.argsort(p),
}
for _op, _fn in _SIMPLE_OPS.items():
    _simple(_op, _fn)


def _b(tf_op: str):
    def deco(fn):
        _BUILDERS[tf_op] = fn
        return fn
    return deco


@_b("LeakyRelu")
def _b_leaky_relu(p):
    alpha = p.get("alpha", 0.2)
    return lambda x: jnp.where(x >= 0, x, alpha * x)


@_b("MatMul")
def _b_matmul(p):
    ta, tb = p.get("transpose_a", False), p.get("transpose_b", False)
    def fn(a, b):
        a = a.T if ta else a
        b = b.T if tb else b
        return a @ b
    return fn


def _b_batchmatmul(p):
    ta, tb = p.get("adj_x", False), p.get("adj_y", False)
    def fn(a, b):
        a = jnp.swapaxes(a, -1, -2) if ta else a
        b = jnp.swapaxes(b, -1, -2) if tb else b
        return jnp.matmul(a, b)
    return fn


_BUILDERS["BatchMatMul"] = _b_batchmatmul
_BUILDERS["BatchMatMulV2"] = _b_batchmatmul


def _b_reduce(jfn):
    def build(p):
        axes = tuple(p["axes"])
        keep = p.get("keep_dims", False)
        return lambda x: jfn(x, axis=axes, keepdims=keep)
    return build


for _op, _jfn in [("Mean", jnp.mean), ("Sum", jnp.sum), ("Max", jnp.max),
                  ("Min", jnp.min), ("Prod", jnp.prod), ("All", jnp.all),
                  ("Any", jnp.any)]:
    _BUILDERS[_op] = _b_reduce(_jfn)


@_b("Reshape")
def _b_reshape(p):
    shape = tuple(p["shape"])
    return lambda x: jnp.reshape(x, shape)


@_b("Transpose")
def _b_transpose(p):
    perm = tuple(p["perm"])
    return lambda x: jnp.transpose(x, perm)


@_b("ConcatV2")
def _b_concat(p):
    axis = p["axis"]
    return lambda *xs: jnp.concatenate(xs, axis=axis)


@_b("Split")
def _b_split(p):
    n, axis = p["num_split"], p["axis"]
    return lambda x: tuple(jnp.split(x, n, axis=axis))


@_b("SplitV")
def _b_splitv(p):
    sizes, axis = list(p["size_splits"]), p["axis"]
    idx = np.cumsum(sizes)[:-1].tolist()
    return lambda x: tuple(jnp.split(x, idx, axis=axis))


@_b("Unpack")
def _b_unpack(p):
    n, axis = p["num"], p.get("axis", 0)
    return lambda x: tuple(jnp.squeeze(s, axis=axis)
                           for s in jnp.split(x, n, axis=axis))


@_b("Squeeze")
def _b_squeeze(p):
    dims = p.get("squeeze_dims") or None
    return lambda x: jnp.squeeze(x, axis=tuple(dims) if dims else None)


@_b("ExpandDims")
def _b_expand_dims(p):
    return lambda x: jnp.expand_dims(x, p["axis"])


@_b("Pack")
def _b_pack(p):
    axis = p.get("axis", 0)
    return lambda *xs: jnp.stack(xs, axis=axis)


@_b("Cast")
def _b_cast(p):
    dst = np.dtype(p["dst"])  # 'bfloat16' resolves via ml_dtypes
    return lambda x: x.astype(dst)


@_b("Pad")
def _b_pad(p):
    pads = [tuple(row) for row in p["paddings"]]
    return lambda x: jnp.pad(x, pads)


@_b("PadV2")
def _b_padv2(p):
    pads = [tuple(row) for row in p["paddings"]]
    return lambda x, c: jnp.pad(x, pads, constant_values=c)


@_b("MirrorPad")
def _b_mirrorpad(p):
    pads = [tuple(row) for row in p["paddings"]]
    mode = "reflect" if p.get("mode", "REFLECT") == "REFLECT" else "symmetric"
    return lambda x: jnp.pad(x, pads, mode=mode)


@_b("Fill")
def _b_fill(p):
    dims = tuple(p["dims"])
    return lambda v: jnp.full(dims, v)


@_b("Range")
def _b_range(p):
    return lambda: jnp.arange(p["start"], p["limit"], p["delta"],
                              dtype=np.dtype(p["dtype"]))


@_b("Tile")
def _b_tile(p):
    reps = tuple(p["multiples"])
    return lambda x: jnp.tile(x, reps)


@_b("Cumsum")
def _b_cumsum(p):
    axis, excl, rev = p["axis"], p.get("exclusive", False), p.get("reverse", False)
    def fn(x):
        y = jnp.flip(x, axis) if rev else x
        if excl:
            y = jnp.cumsum(y, axis=axis) - y
        else:
            y = jnp.cumsum(y, axis=axis)
        return jnp.flip(y, axis) if rev else y
    return fn


@_b("Cumprod")
def _b_cumprod(p):
    axis, excl, rev = p["axis"], p.get("exclusive", False), p.get("reverse", False)
    def fn(x):
        y = jnp.flip(x, axis) if rev else x
        c = _exclusive_cumprod(y, axis) if excl else jnp.cumprod(y, axis=axis)
        return jnp.flip(c, axis) if rev else c
    return fn


def _exclusive_cumprod(y, axis):
    shifted = jnp.concatenate(
        [jnp.ones_like(jnp.take(y, jnp.asarray([0]), axis=axis)),
         jnp.take(y, jnp.arange(y.shape[axis] - 1), axis=axis)], axis=axis)
    return jnp.cumprod(shifted, axis=axis)


@_b("TopKV2")
def _b_topk(p):
    k = p["k"]
    def fn(x):
        v, i = jax.lax.top_k(x, k)
        return v, i.astype(jnp.int32)
    return fn


@_b("OneHot")
def _b_onehot(p):
    depth, axis = p["depth"], p.get("axis", -1)
    on, off = p.get("on_value", 1.0), p.get("off_value", 0.0)
    def fn(idx):
        oh = jax.nn.one_hot(idx, depth, axis=axis)
        return oh * (on - off) + off
    return fn


@_b("GatherV2")
def _b_gather(p):
    ax = p.get("axis", 0)
    bd = p.get("batch_dims", 0)
    if bd == 1:
        return jax.vmap(lambda pp, ii: jnp.take(pp, ii.astype(jnp.int32),
                                                axis=ax - 1))
    if bd:
        raise TFImportError("GatherV2 with batch_dims>1 not supported")
    return lambda params, indices: jnp.take(
        params, indices.astype(jnp.int32), axis=ax)


_BUILDERS["Gather"] = _BUILDERS["GatherV2"]


@_b("GatherNd")
def _b_gather_nd(p):
    def fn(params, indices):
        idx = tuple(jnp.moveaxis(indices.astype(jnp.int32), -1, 0))
        return params[idx]
    return fn


@_b("StridedSlice")
def _b_strided_slice(p):
    idx = tuple(_decode_ss_index(s) for s in p["index"])
    return lambda x: x[idx]


def _decode_ss_index(s):
    if isinstance(s, (int, np.integer)):
        return int(s)
    if s == "new":
        return None
    if s == "...":
        return Ellipsis
    return slice(*[None if v is None else int(v) for v in s])


@_b("Slice")
def _b_slice(p):
    begin, size = list(p["begin"]), list(p["size"])
    idx = tuple(slice(b, None if s == -1 else b + s)
                for b, s in zip(begin, size))
    return lambda x: x[idx]


@_b("Reverse")
def _b_reverse(p):
    axes = tuple(p["axes"])
    return lambda x: jnp.flip(x, axis=axes)


_BUILDERS["ReverseV2"] = _BUILDERS["Reverse"]


@_b("ArgMax")
def _b_argmax(p):
    axis = p.get("axis", 0)
    return lambda x: jnp.argmax(x, axis=axis)


@_b("ArgMin")
def _b_argmin(p):
    axis = p.get("axis", 0)
    return lambda x: jnp.argmin(x, axis=axis)


@_b("BiasAdd")
def _b_bias_add(p):
    if p.get("data_format", "NHWC") == "NCHW":
        return lambda x, b: x + b.reshape((1, -1) + (1,) * (x.ndim - 2))
    return lambda x, b: x + b


@_b("Conv2D")
def _b_conv2d(p):
    strides, dil, pad = p["strides"], p["dilations"], p["padding"]
    def fn(x, w):  # x NHWC, w HWIO
        return jax.lax.conv_general_dilated(
            x, w, window_strides=strides[1:3], padding=pad,
            rhs_dilation=dil[1:3],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return fn


@_b("DepthwiseConv2dNative")
def _b_depthwise(p):
    strides, pad = p["strides"], p["padding"]
    def fn(x, w):  # w [H, W, C, M] -> grouped conv with C groups
        h, wd, c, m = w.shape
        return jax.lax.conv_general_dilated(
            x, jnp.reshape(jnp.transpose(w, (0, 1, 3, 2)), (h, wd, 1, c * m)),
            window_strides=strides[1:3], padding=pad,
            feature_group_count=c,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return fn


def _b_pool(jfn, init):
    def build(p):
        ks, st, pad = p["ksize"], p["strides"], p["padding"]
        def fn(x):
            out = jax.lax.reduce_window(
                x, init, jfn, window_dimensions=ks, window_strides=st,
                padding=pad)
            if jfn is jax.lax.add:  # avg pool: divide by actual window size
                cnt = jax.lax.reduce_window(
                    jnp.ones_like(x), 0.0, jax.lax.add, window_dimensions=ks,
                    window_strides=st, padding=pad)
                out = out / cnt
            return out
        return fn
    return build


_BUILDERS["MaxPool"] = _b_pool(jax.lax.max, -np.inf)
_BUILDERS["AvgPool"] = _b_pool(jax.lax.add, 0.0)


def _b_fused_bn(p):
    eps = p.get("epsilon", 1e-3)
    def fn(x, gamma, beta, mean, var):
        inv = gamma * jax.lax.rsqrt(var + eps)
        return x * inv + (beta - mean * inv)
    return fn


_BUILDERS["FusedBatchNorm"] = _b_fused_bn
_BUILDERS["FusedBatchNormV3"] = _b_fused_bn


@_b("ClipByValue")
def _b_clip(p):
    return lambda x, lo, hi: jnp.clip(x, lo, hi)


@_b("SpaceToBatchND")
def _b_space_to_batch(p):
    bs, pads = list(p["block_shape"]), [tuple(r) for r in p["paddings"]]
    return lambda x: _space_to_batch_nd(x, bs, pads)


def _space_to_batch_nd(x, block_shape, paddings):
    pads = [(0, 0)] + list(paddings) + [(0, 0)] * (x.ndim - 1 - len(paddings))
    x = jnp.pad(x, pads)
    n = x.shape[0]
    spatial = x.shape[1:1 + len(block_shape)]
    rest = x.shape[1 + len(block_shape):]
    shp = [n]
    for s, b in zip(spatial, block_shape):
        shp += [s // b, b]
    x = x.reshape(shp + list(rest))
    perm = ([2 * i + 2 for i in range(len(block_shape))] + [0] +
            [2 * i + 1 for i in range(len(block_shape))] +
            list(range(1 + 2 * len(block_shape), x.ndim)))
    x = jnp.transpose(x, perm)
    out_n = n * int(np.prod(block_shape))
    return x.reshape([out_n] + [s // b for s, b in zip(spatial, block_shape)]
                     + list(rest))


def _tf_rebuild(attrs: dict) -> Callable:
    """``_FN_REBUILDERS['tf']`` — reconstruct an imported node's callable
    from its serialized (tf_op, params); kwargs from attrs are swallowed."""
    fn = _BUILDERS[attrs["tf_op"]](dict(attrs.get("params") or {}))
    return lambda *a, **kw: fn(*a)


_sdmod._FN_REBUILDERS["tf"] = _tf_rebuild


# ------------------------------------------------------------------- mappers
# _MAPPERS[tf_op](ctx, node, data_ins) -> (params, used_inputs, n_out)
# ``params`` must be JSON-able; consts consumed into params are dropped
# from used_inputs.

class _Ctx:
    """Per-import state handed to each op mapper."""

    def __init__(self, sd: SameDiff, library: Dict = None):
        self.sd = sd
        self.consts: Dict[str, np.ndarray] = {}   # const folding table
        # FunctionDefs by name (graph_def.library) — the bodies of
        # StatelessWhile/StatelessIf/PartitionedCall nodes
        self.library: Dict[str, Any] = library or {}
        self.report = None      # import-time lint sink (E16x/W16x), set
        #                         by importGraphDef; None inside functions

    def const_of(self, name: str) -> np.ndarray:
        if name not in self.consts:
            raise TFImportError(
                f"'{name}' must resolve to a compile-time constant in a "
                f"frozen graph (shape/axis inputs are static under XLA). "
                f"Shape-dependent dynamism does not import; re-export the "
                f"graph with static shapes.")
        return self.consts[name]


def _passthrough(n_in: Optional[int] = None):
    def m(ctx, node, ins):
        return {}, ins if n_in is None else ins[:n_in], 1
    return m


def _m_with_attrs(*attr_names, defaults=None):
    defaults = defaults or {}
    def m(ctx, node, ins):
        p = {}
        for a in attr_names:
            v = _attr(node, a, defaults.get(a))
            if v is not None:
                p[a] = v
        return p, ins, 1
    return m


def _m_matmul(ctx, node, ins):
    return {"transpose_a": _attr(node, "transpose_a", False),
            "transpose_b": _attr(node, "transpose_b", False)}, ins, 1


def _m_batchmatmul(ctx, node, ins):
    return {"adj_x": _attr(node, "adj_x", False),
            "adj_y": _attr(node, "adj_y", False)}, ins, 1


def _m_reduce(ctx, node, ins):
    axes = [int(v) for v in np.atleast_1d(ctx.const_of(ins[1]))]
    return {"axes": axes, "keep_dims": _attr(node, "keep_dims", False)}, ins[:1], 1


def _m_reshape(ctx, node, ins):
    shape = [int(v) for v in ctx.const_of(ins[1])]
    return {"shape": shape}, ins[:1], 1


def _m_transpose(ctx, node, ins):
    perm = [int(v) for v in ctx.const_of(ins[1])]
    return {"perm": perm}, ins[:1], 1


def _m_concat(ctx, node, ins):
    return {"axis": int(ctx.const_of(ins[-1]))}, ins[:-1], 1


def _m_split(ctx, node, ins):
    n = _attr(node, "num_split")
    return {"num_split": n, "axis": int(ctx.const_of(ins[0]))}, ins[1:], n


def _m_splitv(ctx, node, ins):
    # SplitV(value, size_splits, axis)
    n = _attr(node, "num_split")
    sizes = [int(v) for v in np.atleast_1d(ctx.const_of(ins[1]))]
    if -1 in sizes:
        raise TFImportError("SplitV with inferred (-1) split size needs the "
                            "input dim; re-export with explicit sizes")
    return ({"size_splits": sizes, "axis": int(ctx.const_of(ins[2]))},
            ins[:1], n)


def _m_unpack(ctx, node, ins):
    n = _attr(node, "num")
    return {"num": n, "axis": _attr(node, "axis", 0)}, ins, n


def _m_squeeze(ctx, node, ins):
    return {"squeeze_dims": _attr(node, "squeeze_dims", []) or []}, ins, 1


def _m_expand_dims(ctx, node, ins):
    return {"axis": int(ctx.const_of(ins[1]))}, ins[:1], 1


def _m_cast(ctx, node, ins):
    return {"dst": _np_dtype_name(_attr(node, "DstT"))}, ins, 1


def _m_pad(ctx, node, ins):
    pads = [[int(v) for v in row] for row in ctx.const_of(ins[1])]
    return {"paddings": pads}, ins[:1], 1


def _m_padv2(ctx, node, ins):
    pads = [[int(v) for v in row] for row in ctx.const_of(ins[1])]
    return {"paddings": pads}, [ins[0], ins[2]], 1


def _m_mirrorpad(ctx, node, ins):
    pads = [[int(v) for v in row] for row in ctx.const_of(ins[1])]
    return {"paddings": pads, "mode": _attr(node, "mode", "REFLECT")}, ins[:1], 1


def _m_fill(ctx, node, ins):
    dims = [int(v) for v in np.atleast_1d(ctx.const_of(ins[0]))]
    return {"dims": dims}, ins[1:], 1


def _m_range(ctx, node, ins):
    start = ctx.const_of(ins[0]); limit = ctx.const_of(ins[1])
    delta = ctx.const_of(ins[2])
    dt = np.result_type(start, limit, delta).name
    return ({"start": float(start), "limit": float(limit),
             "delta": float(delta), "dtype": dt}, [], 1)


def _m_tile(ctx, node, ins):
    reps = [int(v) for v in np.atleast_1d(ctx.const_of(ins[1]))]
    return {"multiples": reps}, ins[:1], 1


def _m_cum(ctx, node, ins):
    return ({"axis": int(ctx.const_of(ins[1])),
             "exclusive": _attr(node, "exclusive", False),
             "reverse": _attr(node, "reverse", False)}, ins[:1], 1)


def _m_topk(ctx, node, ins):
    return {"k": int(ctx.const_of(ins[1]))}, ins[:1], 2


def _m_onehot(ctx, node, ins):
    # OneHot(indices, depth, on_value, off_value)
    return ({"depth": int(ctx.const_of(ins[1])),
             "on_value": float(ctx.const_of(ins[2])),
             "off_value": float(ctx.const_of(ins[3])),
             "axis": _attr(node, "axis", -1)}, ins[:1], 1)


def _m_gather(ctx, node, ins):
    ax = int(ctx.const_of(ins[2])) if len(ins) > 2 else 0
    return ({"axis": ax, "batch_dims": _attr(node, "batch_dims", 0)},
            ins[:2], 1)


def _m_strided_slice(ctx, node, ins):
    begin = [int(v) for v in np.atleast_1d(ctx.const_of(ins[1]))]
    end = [int(v) for v in np.atleast_1d(ctx.const_of(ins[2]))]
    step = [int(v) for v in np.atleast_1d(ctx.const_of(ins[3]))]
    bm = _attr(node, "begin_mask", 0)
    em = _attr(node, "end_mask", 0)
    sm = _attr(node, "shrink_axis_mask", 0)
    nm = _attr(node, "new_axis_mask", 0)
    el = _attr(node, "ellipsis_mask", 0)
    index = []
    for i in range(len(begin)):
        if el & (1 << i):
            index.append("...")
        elif nm & (1 << i):
            index.append("new")
        elif sm & (1 << i):
            index.append(begin[i])
        else:
            b = None if bm & (1 << i) else begin[i]
            e = None if em & (1 << i) else end[i]
            index.append([b, e, step[i]])
    return {"index": index}, ins[:1], 1


def _m_slice(ctx, node, ins):
    begin = [int(v) for v in np.atleast_1d(ctx.const_of(ins[1]))]
    size = [int(v) for v in np.atleast_1d(ctx.const_of(ins[2]))]
    return {"begin": begin, "size": size}, ins[:1], 1


def _m_reverse(ctx, node, ins):
    axes = [int(v) for v in np.atleast_1d(ctx.const_of(ins[1]))]
    return {"axes": axes}, ins[:1], 1


def _m_arg(ctx, node, ins):
    ax = int(ctx.const_of(ins[1])) if len(ins) > 1 else 0
    return {"axis": ax}, ins[:1], 1


def _m_conv2d(ctx, node, ins):
    if _attr(node, "data_format", "NHWC") != "NHWC":
        raise TFImportError("only NHWC TF convs import")
    return ({"strides": _attr(node, "strides", [1, 1, 1, 1]),
             "dilations": _attr(node, "dilations", [1, 1, 1, 1]),
             "padding": _conv_padding(node)}, ins, 1)


def _m_depthwise(ctx, node, ins):
    return ({"strides": _attr(node, "strides", [1, 1, 1, 1]),
             "padding": _conv_padding(node)}, ins, 1)


def _m_pool(ctx, node, ins):
    return ({"ksize": _attr(node, "ksize", [1, 1, 1, 1]),
             "strides": _attr(node, "strides", [1, 1, 1, 1]),
             "padding": _conv_padding(node)}, ins, 1)


def _m_fused_bn(ctx, node, ins):
    if _attr(node, "is_training", True):
        raise TFImportError("only inference-mode FusedBatchNorm imports "
                            "(freeze the graph); import TRAINING checkpoints "
                            "via modelimport.bert / modelimport.keras instead")
    return {"epsilon": _attr(node, "epsilon", 1e-3)}, ins, 1


def _m_space_to_batch(ctx, node, ins):
    bs = [int(v) for v in np.atleast_1d(ctx.const_of(ins[1]))]
    pads = [[int(v) for v in row] for row in ctx.const_of(ins[2])]
    return {"block_shape": bs, "paddings": pads}, ins[:1], 1


# --------------------------------------------------------------- r4 builders
# Breadth push toward the reference importer's op coverage (VERDICT r3 #3):
# scatter, image, segment, 3-D conv/pool, linalg, einsum, special functions.

_SIMPLE_OPS_R4 = {
    "Erfc": jax.lax.erfc,
    "Expm1": jnp.expm1,
    "Lgamma": jax.scipy.special.gammaln,
    "Digamma": jax.scipy.special.digamma,
    "Igamma": jax.scipy.special.gammainc,
    "Igammac": jax.scipy.special.gammaincc,
    "Polygamma": lambda n, x: jax.scipy.special.polygamma(
        n.astype(jnp.int32), x),
    "Zeta": jax.scipy.special.zeta,
    "Betainc": jax.scipy.special.betainc,
    "DivNoNan": lambda a, b: _R.get("divide_no_nan")(a, b),
    "Xdivy": lambda a, b: jnp.where(a == 0, 0.0,
                                    a / jnp.where(a == 0, 1.0, b)),
    "Xlogy": lambda a, b: jnp.where(a == 0, 0.0,
                                    a * jnp.log(jnp.where(a == 0, 1.0, b))),
    "Xlog1py": lambda a, b: jnp.where(a == 0, 0.0,
                                      a * jnp.log1p(jnp.where(a == 0, 0.0, b))),
    "L2Loss": lambda x: jnp.sum(jnp.square(x)) / 2.0,
    "Cholesky": jnp.linalg.cholesky,
    "MatrixSolve": jnp.linalg.solve,
    # batched diag: apply per trailing vector (jnp.diag itself is 1-D/2-D only)
    "MatrixDiag": lambda d: (jnp.apply_along_axis(jnp.diag, -1, d)
                             if d.ndim > 1 else jnp.diag(d)),
    "MatrixDiagPart": lambda x: jnp.diagonal(x, axis1=-2, axis2=-1),
    "RGBToHSV": lambda x: _R.get("rgb_to_hsv")(x),
    "HSVToRGB": lambda x: _R.get("hsv_to_rgb")(x),
    "AdjustContrastv2": lambda x, f: _R.get("adjust_contrast")(x, f),
    "AdjustHue": lambda x, d: _R.get("adjust_hue")(x, d),
    "AdjustSaturation": lambda x, f: _R.get("adjust_saturation")(x, f),
    "TensorScatterUpdate": lambda t, i, u: _R.get("scatter_nd_update")(t, i, u),
    "TensorScatterAdd": lambda t, i, u: _R.get("scatter_nd_add")(t, i, u),
    "TensorScatterSub": lambda t, i, u: _R.get("scatter_nd_sub")(t, i, u),
    "SquaredDifference": lambda a, b: _R.get("squared_difference")(a, b),
}
for _op, _fn in _SIMPLE_OPS_R4.items():
    _simple(_op, _fn)


@_b("MatrixSetDiag")
def _b_matrix_set_diag(p):
    return lambda x, d: _R.get("matrix_set_diag")(x, d)


_BUILDERS["MatrixSetDiagV3"] = _BUILDERS["MatrixSetDiag"]
_BUILDERS["MatrixDiagPartV3"] = _BUILDERS["MatrixDiagPart"]
_BUILDERS["MatrixDiagV3"] = _BUILDERS["MatrixDiag"]


@_b("BroadcastArgs")
def _b_broadcast_args(p):
    """Broadcast-shape arithmetic over two shape vectors — shows up in
    frozen tf.linspace/broadcast chains. Trace-safe (the output length
    depends only on input lengths) so the importer's const-fold size
    check can eval_shape it, then fold it to a concrete Const."""
    def fn(s0, s1):
        s0 = jnp.asarray(s0).astype(jnp.int32)
        s1 = jnp.asarray(s1).astype(jnp.int32)
        n = max(s0.shape[0], s1.shape[0])
        a = jnp.concatenate([jnp.ones((n - s0.shape[0],), jnp.int32), s0])
        b = jnp.concatenate([jnp.ones((n - s1.shape[0],), jnp.int32), s1])
        return jnp.maximum(a, b)
    return fn


@_b("MatrixBandPart")
def _b_band_part(p):
    lo, hi = p["num_lower"], p["num_upper"]
    return lambda x: _R.get("matrix_band_part")(x, lo, hi)


@_b("ScatterNd")
def _b_scatter_nd(p):
    shape = tuple(p["shape"])
    return lambda idx, upd: _R.get("scatter_nd")(idx, upd, shape)


@_b("ResizeBilinear")
def _b_resize_bilinear(p):
    size = tuple(p["size"])
    return lambda x: jax.image.resize(
        x, (x.shape[0],) + size + (x.shape[-1],), "bilinear")


@_b("ResizeNearestNeighbor")
def _b_resize_nn(p):
    size = tuple(p["size"])
    return lambda x: jax.image.resize(
        x, (x.shape[0],) + size + (x.shape[-1],), "nearest")


@_b("CropAndResize")
def _b_crop_and_resize(p):
    size = tuple(p["crop_size"])
    extrap = float(p.get("extrapolation_value", 0.0))
    return lambda img, boxes, bi: _R.get("crop_and_resize")(
        img, boxes, bi, size, extrapolation_value=extrap)


@_b("SpaceToDepth")
def _b_space_to_depth(p):
    bs = p["block_size"]
    fmt = p.get("data_format", "NHWC")
    from deeplearning4j_tpu.ops import convolution as _c
    return lambda x: _c.space_to_depth(x, bs, data_format=fmt)


@_b("DepthToSpace")
def _b_depth_to_space(p):
    bs = p["block_size"]
    fmt = p.get("data_format", "NHWC")
    from deeplearning4j_tpu.ops import convolution as _c
    return lambda x: _c.depth_to_space(x, bs, data_format=fmt)


@_b("BatchToSpaceND")
def _b_batch_to_space(p):
    bs, crops = p["block_shape"], p["crops"]
    if len(set(bs)) != 1:
        raise TFImportError("only uniform BatchToSpaceND block shapes import")
    return lambda x: _R.get("batch_to_space")(x, bs[0], crops)


@_b("Conv2DBackpropInput")
def _b_conv2d_backprop_input(p):
    """Deconvolution as TF frames it: gradient of Conv2D w.r.t. input."""
    strides = p["strides"]
    out_shape = tuple(p["input_sizes"])
    padding = p["padding"]

    def fn(w, dy):
        # w: [kH, kW, inC, outC]; dy: [N, oH, oW, outC] -> [N, H, W, inC]
        # transpose_kernel=True makes lax flip spatial dims and swap the
        # kernel's in/out channel axes itself — pass w in fwd orientation
        return jax.lax.conv_transpose(
            dy, w, strides[1:3], padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            transpose_kernel=True)[:, :out_shape[1], :out_shape[2], :]
    return fn


@_b("Conv3D")
def _b_conv3d(p):
    strides = tuple(p["strides"][1:4])
    padding = p["padding"]

    def fn(x, w):
        # x: NDHWC, w: [kD,kH,kW,inC,outC]
        return jax.lax.conv_general_dilated(
            x, w, strides, padding,
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
    return fn


def _b_pool3d(kind):
    def build(p):
        ks = tuple(p["ksize"][1:4])
        st = tuple(p["strides"][1:4])
        padding = p["padding"]

        def fn(x):
            window = (1,) + ks + (1,)
            strides = (1,) + st + (1,)
            if kind == "max":
                return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                             window, strides, padding)
            s = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides,
                                      padding)
            if padding == "SAME":
                c = jax.lax.reduce_window(jnp.ones_like(x), 0.0, jax.lax.add,
                                          window, strides, padding)
                return s / c
            return s / float(np.prod(ks))
        return fn
    return build


_BUILDERS["MaxPool3D"] = _b_pool3d("max")
_BUILDERS["AvgPool3D"] = _b_pool3d("avg")


@_b("Dilation2D")
def _b_dilation2d(p):
    strides = tuple(p["strides"][1:3])
    rates = tuple(p["rates"][1:3])
    padding = p["padding"]

    def fn(x, f):
        if padding == "SAME":
            kh = (f.shape[0] - 1) * rates[0] + 1
            kw = (f.shape[1] - 1) * rates[1] + 1
            oh = -(-x.shape[1] // strides[0])
            ow = -(-x.shape[2] // strides[1])
            ph = max((oh - 1) * strides[0] + kh - x.shape[1], 0)
            pw = max((ow - 1) * strides[1] + kw - x.shape[2], 0)
            x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                            (pw // 2, pw - pw // 2), (0, 0)),
                        constant_values=-jnp.inf)
        return _R.get("dilation2d")(x, f, stride=strides, rate=rates)
    return fn


def _b_segment(jfn_name):
    def build(p):
        n = p["num_segments"]
        return lambda data, ids: _R.get(jfn_name)(data, ids, num_segments=n)
    return build


for _op, _name in [("SegmentSum", "segment_sum"),
                   ("SegmentMean", "segment_mean"),
                   ("SegmentMax", "segment_max"),
                   ("SegmentMin", "segment_min"),
                   ("SegmentProd", "segment_prod"),
                   ("UnsortedSegmentSum", "unsorted_segment_sum"),
                   ("UnsortedSegmentMean", "unsorted_segment_mean"),
                   ("UnsortedSegmentMax", "unsorted_segment_max"),
                   ("UnsortedSegmentMin", "unsorted_segment_min"),
                   ("UnsortedSegmentProd", "unsorted_segment_prod")]:
    _BUILDERS[_op] = _b_segment(_name)


@_b("LRN")
def _b_lrn(p):
    from deeplearning4j_tpu.ops import normalization as _n
    return lambda x: _n.lrn(x, depth=2 * p.get("depth_radius", 5) + 1,
                            alpha=p.get("alpha", 1.0),
                            beta=p.get("beta", 0.5),
                            bias=p.get("bias", 1.0), data_format="NHWC")


@_b("Einsum")
def _b_einsum(p):
    eq = p["equation"]
    return lambda *xs: jnp.einsum(eq, *xs)


@_b("Roll")
def _b_roll(p):
    shift, axis = p["shift"], p["axis"]
    return lambda x: jnp.roll(x, shift, axis=axis)


@_b("ReverseSequence")
def _b_reverse_sequence(p):
    sa, ba = p.get("seq_dim", 1), p.get("batch_dim", 0)
    return lambda x, lens: _R.get("reverse_sequence")(
        x, lens, seq_axis=sa, batch_axis=ba)


@_b("BroadcastTo")
def _b_broadcast_to(p):
    shape = tuple(p["shape"])
    return lambda x: jnp.broadcast_to(x, shape)


@_b("LinSpace")
def _b_linspace(p):
    n = p["num"]
    return lambda start, stop: jnp.linspace(start, stop, n)


@_b("Bincount")
def _b_bincount(p):
    n = p["size"]
    return lambda arr, w: _R.get("bincount")(
        arr, weights=None if (hasattr(w, "size") and w.size == 0) else w,
        length=n)


_BUILDERS["DenseBincount"] = _BUILDERS["Bincount"]


# ------------------------------------------------------------- r4 mappers

def _m_set_diag_v3(ctx, node, ins):
    k = int(np.atleast_1d(ctx.const_of(ins[2]))[0]) if len(ins) > 2 else 0
    if k != 0:
        raise TFImportError("MatrixSetDiagV3 with k != 0 does not import")
    return {}, ins[:2], 1


def _m_diag_part_v3(ctx, node, ins):
    k = int(np.atleast_1d(ctx.const_of(ins[1]))[0]) if len(ins) > 1 else 0
    if k != 0:
        raise TFImportError("MatrixDiagPartV3 with k != 0 does not import")
    return {}, ins[:1], 1


def _m_matrix_diag_v3(ctx, node, ins):
    # inputs: (diagonal, k, num_rows, num_cols, padding_value) — main
    # diagonal with default sizing/padding only; anything else must fail
    # loudly rather than silently dropping the sizing inputs
    if len(ins) > 1 and int(np.atleast_1d(ctx.const_of(ins[1]))[0]) != 0:
        raise TFImportError("MatrixDiagV3 with k != 0 does not import")
    if len(ins) > 2:
        nr = int(np.atleast_1d(ctx.const_of(ins[2]))[0])
        nc = int(np.atleast_1d(ctx.const_of(ins[3]))[0]) if len(ins) > 3 \
            else -1
        if nr != -1 or nc != -1:
            raise TFImportError(
                "MatrixDiagV3 with explicit num_rows/num_cols does not "
                "import (square main-diagonal form only)")
    if len(ins) > 4 and float(np.atleast_1d(ctx.const_of(ins[4]))[0]) != 0.0:
        raise TFImportError(
            "MatrixDiagV3 with non-zero padding_value does not import")
    return {}, ins[:1], 1


def _m_batch_to_space(ctx, node, ins):
    bs = [int(v) for v in np.atleast_1d(ctx.const_of(ins[1]))]
    crops = [[int(v) for v in row] for row in ctx.const_of(ins[2])]
    return {"block_shape": bs, "crops": crops}, ins[:1], 1


def _m_scatter_nd(ctx, node, ins):
    shape = [int(v) for v in ctx.const_of(ins[2])]
    return {"shape": shape}, ins[:2], 1


def _m_resize(ctx, node, ins):
    if _attr(node, "align_corners", False) or \
            not _attr(node, "half_pixel_centers", False):
        raise TFImportError(
            "only half_pixel_centers resize imports (the TF2 default); "
            "align_corners / TF1 asymmetric scaling would silently produce "
            "different pixels under jax.image.resize — re-export with "
            "tf.image.resize (TF2)")
    size = [int(v) for v in ctx.const_of(ins[1])]
    return {"size": size}, ins[:1], 1


def _m_crop_and_resize(ctx, node, ins):
    size = [int(v) for v in ctx.const_of(ins[3])]
    return ({"crop_size": size,
             "extrapolation_value": _attr(node, "extrapolation_value", 0.0)},
            ins[:3], 1)


def _m_band_part(ctx, node, ins):
    return ({"num_lower": int(ctx.const_of(ins[1])),
             "num_upper": int(ctx.const_of(ins[2]))}, ins[:1], 1)


def _m_conv3d(ctx, node, ins):
    if _attr(node, "data_format", "NDHWC") != "NDHWC":
        raise TFImportError("only NDHWC Conv3D imports")
    return ({"strides": _attr(node, "strides", [1] * 5),
             "padding": _conv_padding(node)}, ins, 1)


def _m_pool3d(ctx, node, ins):
    return ({"ksize": _attr(node, "ksize", [1] * 5),
             "strides": _attr(node, "strides", [1] * 5),
             "padding": _conv_padding(node)}, ins, 1)


def _m_conv2d_backprop(ctx, node, ins):
    # Conv2DBackpropInput(input_sizes, filter, out_backprop)
    sizes = [int(v) for v in ctx.const_of(ins[0])]
    return ({"input_sizes": sizes,
             "strides": _attr(node, "strides", [1, 1, 1, 1]),
             "padding": _conv_padding(node)}, ins[1:], 1)


def _m_dilation2d(ctx, node, ins):
    return ({"strides": _attr(node, "strides", [1, 1, 1, 1]),
             "rates": _attr(node, "rates", [1, 1, 1, 1]),
             "padding": _conv_padding(node)}, ins, 1)


def _m_segment(ctx, node, ins):
    ids = np.atleast_1d(ctx.const_of(ins[1]))
    return {"num_segments": int(ids.max()) + 1}, ins, 1


def _m_unsorted_segment(ctx, node, ins):
    n = int(ctx.const_of(ins[2]))
    return {"num_segments": n}, ins[:2], 1


def _m_roll(ctx, node, ins):
    shift = [int(v) for v in np.atleast_1d(ctx.const_of(ins[1]))]
    axis = [int(v) for v in np.atleast_1d(ctx.const_of(ins[2]))]
    if len(shift) == 1:
        shift, axis = shift[0], axis[0]
    return {"shift": shift, "axis": axis}, ins[:1], 1


def _m_broadcast_to(ctx, node, ins):
    return {"shape": [int(v) for v in ctx.const_of(ins[1])]}, ins[:1], 1


def _m_linspace(ctx, node, ins):
    return {"num": int(ctx.const_of(ins[2]))}, ins[:2], 1


def _m_bincount(ctx, node, ins):
    return {"size": int(ctx.const_of(ins[1]))}, [ins[0], ins[2]], 1


_MAPPERS_R4 = {
    "MatrixBandPart": _m_band_part,
    "MatrixSetDiagV3": _m_set_diag_v3,
    "MatrixDiagPartV3": _m_diag_part_v3,
    "MatrixDiagV3": _m_matrix_diag_v3,
    "BroadcastArgs": _passthrough(2),
    "DenseBincount": _m_bincount,
    "ScatterNd": _m_scatter_nd,
    "TensorScatterUpdate": _passthrough(3),
    "TensorScatterAdd": _passthrough(3),
    "TensorScatterSub": _passthrough(3),
    "ResizeBilinear": _m_resize,
    "ResizeNearestNeighbor": _m_resize,
    "CropAndResize": _m_crop_and_resize,
    "SpaceToDepth": _m_with_attrs("block_size", "data_format"),
    "DepthToSpace": _m_with_attrs("block_size", "data_format"),
    "BatchToSpaceND": _m_batch_to_space,
    "Conv2DBackpropInput": _m_conv2d_backprop,
    "Conv3D": _m_conv3d,
    "MaxPool3D": _m_pool3d,
    "AvgPool3D": _m_pool3d,
    "Dilation2D": _m_dilation2d,
    "SegmentSum": _m_segment, "SegmentMean": _m_segment,
    "SegmentMax": _m_segment, "SegmentMin": _m_segment,
    "SegmentProd": _m_segment,
    "UnsortedSegmentSum": _m_unsorted_segment,
    "UnsortedSegmentMean": _m_unsorted_segment,
    "UnsortedSegmentMax": _m_unsorted_segment,
    "UnsortedSegmentMin": _m_unsorted_segment,
    "UnsortedSegmentProd": _m_unsorted_segment,
    "LRN": _m_with_attrs("depth_radius", "bias", "alpha", "beta"),
    "Einsum": _m_with_attrs("equation"),
    "Roll": _m_roll,
    "ReverseSequence": _m_with_attrs("seq_dim", "batch_dim"),
    "BroadcastTo": _m_broadcast_to,
    "LinSpace": _m_linspace,
    "Bincount": _m_bincount,
}


_MAPPERS: Dict[str, Callable] = {
    "MatMul": _m_matmul,
    "BatchMatMul": _m_batchmatmul,
    "BatchMatMulV2": _m_batchmatmul,
    "BiasAdd": _m_with_attrs("data_format"),
    "LeakyRelu": _m_with_attrs("alpha", defaults={"alpha": 0.2}),
    "Mean": _m_reduce, "Sum": _m_reduce, "Max": _m_reduce,
    "Min": _m_reduce, "Prod": _m_reduce, "All": _m_reduce, "Any": _m_reduce,
    "Reshape": _m_reshape,
    "Transpose": _m_transpose,
    "ConcatV2": _m_concat,
    "Split": _m_split,
    "SplitV": _m_splitv,
    "Unpack": _m_unpack,
    "Squeeze": _m_squeeze,
    "ExpandDims": _m_expand_dims,
    "Pack": _m_with_attrs("axis", defaults={"axis": 0}),
    "Cast": _m_cast,
    "Pad": _m_pad,
    "PadV2": _m_padv2,
    "MirrorPad": _m_mirrorpad,
    "Fill": _m_fill,
    "Range": _m_range,
    "Tile": _m_tile,
    "Cumsum": _m_cum,
    "Cumprod": _m_cum,
    "TopKV2": _m_topk,
    "OneHot": _m_onehot,
    "Conv2D": _m_conv2d,
    "DepthwiseConv2dNative": _m_depthwise,
    "MaxPool": _m_pool,
    "AvgPool": _m_pool,
    "FusedBatchNorm": _m_fused_bn,
    "FusedBatchNormV3": _m_fused_bn,
    "GatherV2": _m_gather,
    "Gather": _m_gather,
    "GatherNd": _passthrough(2),
    "StridedSlice": _m_strided_slice,
    "Slice": _m_slice,
    "Reverse": _m_reverse,
    "ReverseV2": _m_reverse,
    "ArgMax": _m_arg,
    "ArgMin": _m_arg,
    "ClipByValue": _passthrough(3),
    "SpaceToBatchND": _m_space_to_batch,
}
_MAPPERS.update(_MAPPERS_R4)
for _op in list(_SIMPLE_OPS) + list(_SIMPLE_OPS_R4):
    if _op not in _MAPPERS:
        _MAPPERS[_op] = _passthrough()


def _var_name(ref: str) -> str:
    """TF input ref 'name', 'name:0', 'name:k' -> our variable name."""
    if ":" in ref:
        base, idx = ref.rsplit(":", 1)
        return base if idx == "0" else f"{base}:{idx}"
    return ref


class TFGraphImport:
    """ref: TensorflowFrameworkImporter (samediff-import-tensorflow)."""

    @staticmethod
    def importGraphDef(graph_def) -> SameDiff:
        """Frozen GraphDef (or path to a binary .pb) -> SameDiff."""
        if isinstance(graph_def, (str, bytes)) and not hasattr(graph_def, "node"):
            from tensorflow.core.framework import graph_pb2
            gd = graph_pb2.GraphDef()
            with open(graph_def, "rb") as f:
                gd.ParseFromString(f.read())
            graph_def = gd

        from deeplearning4j_tpu.analysis import imports as _imp
        sd = SameDiff.create()
        library = {f.signature.name: f
                   for f in graph_def.library.function} \
            if graph_def.HasField("library") else {}
        ctx = _Ctx(sd, library)
        ctx.report = _imp.ValidationReport(subject="TF import")
        nodes = list(graph_def.node)
        if any(n.op in _V1_CF_OPS for n in nodes):
            nodes = _topo_sort(nodes)
            skip, plans = _plan_deframe(nodes)
            # frame-collapsed order: every frame imports as ONE unit, after
            # all its outer inputs and before every consumer of its Exits
            for item in _collapsed_order(nodes, plans):
                if isinstance(item, str):
                    _apply_deframe_plan(ctx, plans[item])
                elif item.name not in skip:
                    _import_one(ctx, item, _var_name)
        else:
            for node in nodes:
                _import_one(ctx, node, _var_name)
        # W161 from the recorded placeholders, then the findings the
        # import loop itself collected (E163 consts, W163 folds)
        report = _imp.samediff_import_report(sd)
        report.extend(ctx.report.diagnostics)
        sd.import_report = report
        return sd


def _import_one(ctx: _Ctx, node, resolver):
    """Import one NodeDef into ctx.sd (shared by the GraphDef loop and
    FunctionDef bodies; ``resolver`` maps the container's input-ref syntax
    to variable names)."""
    data_ins = [resolver(i) for i in node.input if not i.startswith("^")]
    if node.op == "Const":
        val = _tensor_value(node)
        if ctx.report is not None:
            from deeplearning4j_tpu.analysis import imports as _imp
            ctx.report.extend(_imp.lint_narrowed_array(
                val, f"const '{node.name}'"))
        ctx.consts[node.name] = val
        ctx.sd.constant(val, name=node.name)
    elif node.op == "Placeholder":
        shape = _attr(node, "shape")
        shape = tuple(None if d in (-1, 0) and i == 0 else
                      (None if d == -1 else d)
                      for i, d in enumerate(shape or []))
        dt = _attr(node, "dtype") or np.float32
        ctx.sd.placeHolder(node.name, shape=shape or None, dtype=dt)
    elif node.op == "NoOp":
        return
    elif node.op in _MAPPERS:
        params, used, n_out = _MAPPERS[node.op](ctx, node, data_ins)
        _record_tf_node(ctx, node, params, used, n_out)
    else:
        raise TFImportError(
            f"unmapped TF op '{node.op}' (node '{node.name}') — add "
            f"a mapper to modelimport.tensorflow._MAPPERS. (TF1 "
            f"Enter/Exit/Merge control-flow frames and training-mode ops "
            f"intentionally do not import; TF2 functional control flow "
            f"(StatelessWhile/StatelessIf/While/If) does.)")


def _fn_var_name(ref: str) -> str:
    """FunctionDef-body input ref -> variable name: 'arg' stays, a
    'node:field:k' output ref collapses to the GraphDef ':k' convention."""
    parts = ref.split(":")
    if len(parts) == 1:
        return parts[0]
    if len(parts) == 3:
        return parts[0] if parts[2] == "0" else f"{parts[0]}:{parts[2]}"
    return _var_name(ref)


def _import_function(ctx: _Ctx, fname: str):
    """FunctionDef -> (sub-SameDiff, output names). Function args become
    placeholders in signature order — the subgraph call convention
    (autodiff.samediff.subgraph_fn)."""
    if fname not in ctx.library:
        raise TFImportError(f"function '{fname}' not in graph library")
    fdef = ctx.library[fname]
    sub = SameDiff.create()
    sctx = _Ctx(sub, ctx.library)
    for arg in fdef.signature.input_arg:
        sub.placeHolder(arg.name, shape=None,
                        dtype=_DTYPES.get(arg.type, np.float32))
    for node in fdef.node_def:
        _import_one(sctx, node, _fn_var_name)
    outs = [_fn_var_name(fdef.ret[o.name])
            for o in fdef.signature.output_arg]
    return sub, outs


def _m_functional_while(ctx, node, ins):
    """TF2 functional while (ref: the interpreted Enter/Exit/Merge frame
    loop in SURVEY §3.3, re-designed as lax.while_loop over compiled
    subgraph bodies)."""
    cond_sd, cond_outs = _import_function(ctx, node.attr["cond"].func.name)
    body_sd, body_outs = _import_function(ctx, node.attr["body"].func.name)
    if len(body_outs) != len(ins):
        raise TFImportError(
            f"While '{node.name}': body returns {len(body_outs)} values "
            f"for {len(ins)} loop vars")
    params = {"cond": _sdmod.subgraph_spec(cond_sd, cond_outs),
              "body": _sdmod.subgraph_spec(body_sd, body_outs)}
    return params, ins, len(ins)


def _m_functional_if(ctx, node, ins):
    then_sd, then_outs = _import_function(
        ctx, node.attr["then_branch"].func.name)
    else_sd, else_outs = _import_function(
        ctx, node.attr["else_branch"].func.name)
    params = {"then": _sdmod.subgraph_spec(then_sd, then_outs),
              "else": _sdmod.subgraph_spec(else_sd, else_outs)}
    return params, ins, len(then_outs)


def _m_partitioned_call(ctx, node, ins):
    sub, outs = _import_function(ctx, node.attr["f"].func.name)
    return {"sub": _sdmod.subgraph_spec(sub, outs)}, ins, len(outs)


_MAPPERS["StatelessWhile"] = _m_functional_while
_MAPPERS["While"] = _m_functional_while
_MAPPERS["StatelessIf"] = _m_functional_if
_MAPPERS["If"] = _m_functional_if
_MAPPERS["PartitionedCall"] = _m_partitioned_call
_MAPPERS["StatefulPartitionedCall"] = _m_partitioned_call

_BUILDERS["StatelessWhile"] = lambda p: _sdmod._make_subwhile_fn(p)
_BUILDERS["While"] = lambda p: _sdmod._make_subwhile_fn(p)
_BUILDERS["StatelessIf"] = lambda p: _sdmod._make_subcond_fn(
    {"true": p["then"], "false": p["else"]})
_BUILDERS["If"] = _BUILDERS["StatelessIf"]
_BUILDERS["PartitionedCall"] = lambda p: _sdmod._make_subcall_fn(p)
_BUILDERS["StatefulPartitionedCall"] = _BUILDERS["PartitionedCall"]


# ---------------------------------------------------- v1 frame deframing
# The reference INTERPRETS Enter/Exit/Merge/Switch frames at runtime
# (SURVEY.md §3.3). XLA cannot — TF's own XLA bridge refuses v1 frames —
# so default-frozen graphs with loops are DEFRAMED here: each while frame
# is reconstructed into functional cond/body subgraphs and imported
# exactly like a StatelessWhile.

_V1_CF_OPS = {"Enter", "Exit", "Merge", "Switch", "NextIteration",
              "LoopCond"}


def _topo_sort(nodes):
    """Topological order by data edges (GraphDef order is NOT guaranteed
    topological once the lowering pass has rewritten control flow; the
    recorded SameDiff node order must be executable top-down). Merge's
    NextIteration back-edge is ignored — it is the one legal cycle."""
    by_name = {n.name: n for n in nodes}
    indeg = {n.name: 0 for n in nodes}
    consumers: Dict[str, List[str]] = {n.name: [] for n in nodes}
    for n in nodes:
        for ref in n.input:
            if ref.startswith("^"):
                continue
            p = ref.split(":")[0]
            if p in by_name and not (
                    n.op == "Merge" and by_name[p].op == "NextIteration"):
                indeg[n.name] += 1
                consumers[p].append(n.name)
    from collections import deque
    q = deque(n.name for n in nodes if indeg[n.name] == 0)
    out = []
    while q:
        name = q.popleft()
        out.append(by_name[name])
        for c in consumers[name]:
            indeg[c] -= 1
            if indeg[c] == 0:
                q.append(c)
    if len(out) != len(nodes):            # a real cycle: keep input order
        return list(nodes)
    return out


def _collapsed_order(nodes, plans):
    """Topological order with each frame collapsed to one super-node.
    Yields NodeDefs and frame keys (strings)."""
    member_of = {}
    for key, plan in plans.items():
        for m in plan["members"]:
            member_of[m] = key
    by_name = {n.name: n for n in nodes}
    items = [n.name for n in nodes if n.name not in member_of] + list(plans)
    indeg = {i: 0 for i in items}
    consumers = {i: [] for i in items}

    def item_of(name):
        return member_of.get(name, name)

    seen_edges = set()
    for n in nodes:
        dst = item_of(n.name)
        for ref in n.input:
            if ref.startswith("^"):      # control edges don't gate data
                continue
            p = ref.split(":")[0]
            if p not in by_name:
                continue
            src = item_of(p)
            if src == dst or (src, dst) in seen_edges:
                continue
            seen_edges.add((src, dst))
            indeg[dst] += 1
            consumers[src].append(dst)
    from collections import deque
    q = deque(i for i in items if indeg[i] == 0)
    out = []
    while q:
        i = q.popleft()
        out.append(i if i in plans else by_name[i])
        for c in consumers[i]:
            indeg[c] -= 1
            if indeg[c] == 0:
                q.append(c)
    if len(out) != len(items):
        raise TFImportError(
            "cyclic dependency between v1 control-flow frames — re-export "
            "with lower_control_flow=False")
    return out


def _plan_deframe(nodes):
    """Group v1 control-flow nodes into while-frame plans.

    Returns (skip: names the main loop must not import, plans: frame
    key -> plan); the import loop runs frames via _collapsed_order."""
    by_name = {n.name: n for n in nodes}

    def producer(ref):
        return by_name.get(ref.split(":")[0].lstrip("^"))

    frames: Dict[str, List] = {}
    for n in nodes:
        if n.op == "Enter":
            frames.setdefault(_attr(n, "frame_name"), []).append(n)
    # Merge/Switch outside any while frame = the v1 tf.cond idiom
    framed_merges = set()
    for f, enters in frames.items():
        for n in nodes:
            if n.op == "Merge" and any(
                    producer(i) in enters for i in n.input):
                framed_merges.add(n.name)
    framed_switches = set()
    for f, enters in frames.items():
        for n in nodes:
            if n.op == "Switch" and any(
                    producer(i) is not None
                    and producer(i).name in framed_merges
                    for i in n.input):
                framed_switches.add(n.name)
    for n in nodes:
        if (n.op == "Merge" and n.name not in framed_merges) or (
                n.op == "Switch" and n.name not in framed_switches):
            raise TFImportError(
                "v1 Switch/Merge conditional frames do not import "
                "(XLA has no representation for them) — re-export "
                "with lower_control_flow=False, which keeps "
                "functional StatelessIf nodes")

    skip, plans = set(), {}
    for frame, enters in frames.items():
        plan = _plan_one_frame(frame, enters, nodes, by_name, producer)
        skip |= plan["members"]
        plans[frame] = plan
    return skip, plans


def _plan_one_frame(frame, enters, nodes, by_name, producer):
    merges = [n for n in nodes if n.op == "Merge"
              and any(producer(i) in enters for i in n.input)]
    loopconds = {producer(s.input[1]).name for s in nodes
                 if s.op == "Switch"
                 and producer(s.input[0]) in merges}
    if len(loopconds) != 1:
        raise TFImportError(
            f"while frame '{frame}': expected one LoopCond, found "
            f"{len(loopconds)} (nested/irregular frames do not import — "
            f"re-export with lower_control_flow=False)")
    loopcond = by_name[next(iter(loopconds))]

    carries = []          # (enter, merge, switch, nextit, exit_or_None)
    for m in merges:
        enter = next(producer(i) for i in m.input
                     if producer(i) in enters)
        nextit = next((producer(i) for i in m.input
                       if producer(i) is not None
                       and producer(i).op == "NextIteration"), None)
        switch = next((s for s in nodes if s.op == "Switch"
                       and producer(s.input[0]) is m), None)
        if nextit is None or switch is None:
            raise TFImportError(
                f"while frame '{frame}': irregular Merge "
                f"'{m.name}' (no NextIteration/Switch pair)")
        ex = next((e for e in nodes if e.op == "Exit"
                   and producer(e.input[0]) is switch), None)
        carries.append((enter, m, switch, nextit, ex))
    const_enters = [e for e in enters if _attr(e, "is_constant", False)]

    # interior sets: ancestors of the cond output / body outputs, stopping
    # at the frame boundary (merges for cond, switch:1 for body)
    def interior(seeds, stop_names):
        seen, out = set(), set()
        stack = [s.split(":")[0] for s in seeds]
        while stack:
            name = stack.pop()
            if name in seen or name in stop_names:
                continue
            seen.add(name)
            n = by_name.get(name)
            if n is None:
                continue
            if n.op in _V1_CF_OPS:
                if n in const_enters:
                    continue          # invariant: resolved at build time
                raise TFImportError(
                    f"while frame '{frame}': nested v1 control flow does "
                    f"not import — re-export with lower_control_flow=False")
            out.add(name)
            stack.extend(i.split(":")[0].lstrip("^") for i in n.input
                         if not i.startswith("^"))
        return out

    merge_names = {c[1].name for c in carries}
    switch_names = {c[2].name for c in carries}
    cond_nodes = interior([loopcond.input[0]], merge_names)
    body_nodes = interior([c[3].input[0] for c in carries], switch_names)
    members = ({n.name for n in enters} | merge_names | switch_names
               | {c[3].name for c in carries}
               | {c[4].name for c in carries if c[4] is not None}
               | {loopcond.name} | cond_nodes | body_nodes)
    return {"frame": frame, "carries": carries, "loopcond": loopcond,
            "cond_nodes": cond_nodes, "body_nodes": body_nodes,
            "const_enters": const_enters, "members": members,
            "nodes": nodes, "by_name": by_name}


def _apply_deframe_plan(ctx: _Ctx, plan):
    """Build cond/body subgraphs from the frame interior and record ONE
    functional while node in place of the whole frame."""
    carries = plan["carries"]
    by_name = plan["by_name"]
    base = f"{plan['frame']}_deframed"

    # carry list: loop vars first, then invariants (is_constant Enters +
    # any interior ref produced outside the frame) — same order in init/
    # cond/body, with invariants carried through unchanged
    invariants: List[str] = []          # outer refs, discovery order

    def build_sub(node_names, boundary):
        """Import a frame interior into a fresh subgraph. Invariant
        placeholders are declared LATER (same order on both subs);
        _record_fn only stores input names, so forward references to the
        not-yet-declared ``inv{i}`` placeholders are fine."""
        sub = SameDiff.create()
        sctx = _Ctx(sub, ctx.library)
        ph = {ref: f"carry{i}" for i, ref in enumerate(boundary)}
        for i in range(len(boundary)):
            sub.placeHolder(f"carry{i}", shape=None, dtype=np.float32)

        def resolve(ref):
            if ref in ph:
                return ph[ref]
            if ref.split(":")[0] in node_names:
                return _var_name(ref)
            # produced outside the frame: invariant carry
            for e in plan["const_enters"]:
                if ref.split(":")[0] == e.name:
                    ref = e.input[0]
                    break
            if ref not in invariants:
                invariants.append(ref)
            return f"inv{invariants.index(ref)}"

        ordered = [n for n in plan["nodes"] if n.name in node_names]
        for n in ordered:
            _import_one(sctx, n, resolve)
        return sub, resolve

    cond_boundary = [c[1].name for c in carries]
    body_boundary = [f"{c[2].name}:1" for c in carries]
    cond_sub, cond_resolve = build_sub(plan["cond_nodes"], cond_boundary)
    cond_out = cond_resolve(plan["loopcond"].input[0])
    body_sub, body_resolve = build_sub(plan["body_nodes"], body_boundary)
    body_outs = [body_resolve(c[3].input[0]) for c in carries]

    # invariants become trailing carries on BOTH subs, identical order
    for i in range(len(invariants)):
        iv = f"inv{i}"
        cond_sub.placeHolder(iv, shape=None, dtype=np.float32)
        body_sub.placeHolder(iv, shape=None, dtype=np.float32)
        body_outs.append(iv)

    params = {"cond": _sdmod.subgraph_spec(cond_sub, [cond_out]),
              "body": _sdmod.subgraph_spec(body_sub, body_outs)}
    init_refs = [_var_name(c[0].input[0]) for c in carries] \
        + [_var_name(r) for r in invariants]
    fn = _sdmod._make_subwhile_fn(params)
    wrapped = (lambda _f: lambda *a, **kw: _f(*a))(fn)
    n_out = len(init_refs)
    ctx.sd._record_fn("tf.While", wrapped, init_refs, name=base,
                      n_out=n_out, rebuild="tf",
                      attrs={"tf_op": "While", "params": params})
    # route each Exit node's name onto the matching while output
    for i, c in enumerate(carries):
        if c[4] is not None:
            out_name = base if (i == 0 and n_out == 1) else f"{base}:{i}"
            ctx.sd._rename(out_name, c[4].name)


def _fold_output_size_ok(fn, ins: List[np.ndarray]) -> bool:
    """Bound the FOLDED result size without materializing it (Fill/Tile/
    OneHot have tiny inputs but unbounded outputs)."""
    try:
        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in ins]
        out = jax.eval_shape(lambda *xs: fn(*xs), *specs)
        total = sum(int(np.prod(o.shape))
                    for o in jax.tree_util.tree_leaves(out))
        return total <= _FOLD_LIMIT
    except Exception:
        return False


def _record_tf_node(ctx: _Ctx, node, params: dict, used: List[str],
                    n_out: int):
    fn = _BUILDERS[node.op](params)

    # const-fold: all data inputs known at import time, inputs AND outputs
    # bounded (collapses frozen-graph shape arithmetic into static operands)
    if used and all(u in ctx.consts for u in used) and \
            sum(ctx.consts[u].size for u in used) <= _FOLD_LIMIT and \
            _fold_output_size_ok(fn, [ctx.consts[u] for u in used]):
        res = fn(*[ctx.consts[u] for u in used])
        outs = res if n_out > 1 else (res,)
        if ctx.report is not None:
            from deeplearning4j_tpu.analysis import imports as _imp
            ctx.report.extend(_imp.fold_overflow_diags(
                node.op, node.name, [np.asarray(r) for r in outs]))
        for i, r in enumerate(outs):
            name = node.name if (i == 0 and n_out == 1) else f"{node.name}:{i}"
            arr = np.asarray(r)
            ctx.consts[name] = arr
            ctx.sd.constant(arr, name=name)
        if n_out > 1:   # downstream ':0' refs collapse to the bare name
            ctx.consts[node.name] = ctx.consts[f"{node.name}:0"]
            ctx.sd._rename(f"{node.name}:0", node.name)
        return

    if node.op == "Range" and not used:
        # all inputs const by construction; length bounded before folding
        n_elem = int(max(0, np.ceil((params["limit"] - params["start"])
                                    / params["delta"])))
        if n_elem > _FOLD_LIMIT:
            raise TFImportError(
                f"Range '{node.name}' would materialize {n_elem} elements")
        arr = np.asarray(fn())
        ctx.consts[node.name] = arr
        ctx.sd.constant(arr, name=node.name)
        return

    wrapped = (lambda _f: lambda *a, **kw: _f(*a))(fn)
    ctx.sd._record_fn(f"tf.{node.op}", wrapped, used, name=node.name,
                      n_out=n_out, rebuild="tf",
                      attrs={"tf_op": node.op, "params": params})
    if n_out > 1:
        # TF refs 'name:0' collapse to the bare name in _var_name; align
        # output 0 with that convention (advisor r2 medium: Split naming)
        ctx.sd._rename(f"{node.name}:0", node.name)


importTensorflowGraph = TFGraphImport.importGraphDef
