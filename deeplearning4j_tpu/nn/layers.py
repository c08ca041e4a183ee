"""Layer configurations + functional forward passes — the DL4J layer zoo.

Reference parity: ``org.deeplearning4j.nn.conf.layers.*`` (configs) and
``org.deeplearning4j.nn.layers.*`` (implementations) — SURVEY.md §2.2
"DL4J layers". Weight layouts match the reference: dense W [nIn, nOut],
bias [nOut]; conv W [nOut, nIn, kH, kW]; recurrent input W [nIn, 4H].
Recurrent data layout is the reference's [N, channels, T] (NCW).

TPU-native: NO hand-written ``backpropGradient`` anywhere — each layer is
a pure ``apply(params, state, x, train, key)`` traced into the network's
single compiled step; autodiff is program-level (SURVEY.md §7 item 4).
Layer-level ``dropout`` follows the reference's semantics: the value is
the RETAIN probability, applied to the layer's input.
"""

from __future__ import annotations

import difflib
import functools
import inspect
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import profiler as _prof
from deeplearning4j_tpu.autodiff.samediff import _initialize
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.ops import activations as act
from deeplearning4j_tpu.ops import attention as attention_ops
from deeplearning4j_tpu.ops import convolution as conv_ops
from deeplearning4j_tpu.ops import losses as loss_ops
from deeplearning4j_tpu.ops import normalization as norm_ops
from deeplearning4j_tpu.ops import recurrent as rnn_ops
from deeplearning4j_tpu.profiler import stepprogram as _stepprogram


def _pair(v):
    if isinstance(v, (tuple, list)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


_KNOWN_KWARGS_CACHE: Dict[type, frozenset] = {}


def _known_kwargs(cls) -> frozenset:
    """Every keyword a layer class's constructor chain accepts (collected
    over the MRO so subclass kwargs and base Layer kwargs both count)."""
    cached = _KNOWN_KWARGS_CACHE.get(cls)
    if cached is not None:
        return cached
    keys = set()
    for c in cls.__mro__:
        init = c.__dict__.get("__init__")
        if init is None:
            continue
        for name, p in inspect.signature(init).parameters.items():
            if name == "self" or p.kind in (p.VAR_KEYWORD, p.VAR_POSITIONAL):
                continue
            keys.add(name)
    cached = _KNOWN_KWARGS_CACHE[cls] = frozenset(keys)
    return cached


def _reject_unknown_kwargs(cls, extra: Dict[str, Any]) -> None:
    """Typo'd/unknown config keys fail loudly with a did-you-mean instead
    of an opaque TypeError (or, worse, silently configuring nothing)."""
    if not extra:
        return
    known = sorted(_known_kwargs(cls))
    parts = []
    for k in sorted(extra):
        close = difflib.get_close_matches(k, known, n=1)
        parts.append(f"'{k}'" + (f" (did you mean '{close[0]}'?)"
                                 if close else ""))
    raise TypeError(f"{cls.__name__}: unknown config key(s) "
                    f"{', '.join(parts)}; known keys: {', '.join(known)}")


class Layer:
    """Base layer config. Subclasses define params + forward."""

    input_kind: Optional[str] = "ff"
    has_params = True
    #: compute layout for spatial (4-D) inputs. "NCHW" is the reference's
    #: public layout everywhere; the networks' ``setComputeLayout("NHWC")``
    #: stamps layout-aware layers with an instance attribute so conv/pool/
    #: BN/LRN paths run channels-minor on the MXU while the public API
    #: (weights [O,I,kH,kW], inputs/outputs NCHW) is unchanged — the
    #: forward transposes once at each layout boundary.
    data_format = "NCHW"

    def __init__(self, nOut: int = None, nIn: int = None, activation: str = None,
                 weightInit: str = None, biasInit: float = 0.0,
                 dropOut: float = 0.0, l1: float = None, l2: float = None,
                 name: str = None, tiedWith: str = None,
                 dataType: str = None, **extra):
        _reject_unknown_kwargs(type(self), extra)
        self.nOut = nOut
        self.nIn = nIn
        self.activation = activation
        self.weight_init = weightInit
        self.bias_init = biasInit
        self.dropout = dropOut       # RETAIN probability (reference semantics)
        self.l1 = l1
        self.l2 = l2
        self.name = name or type(self).__name__
        # weight-tie group label: layers sharing one group must land on
        # the same pipeline stage (analysis/distribution.py E103)
        self.tied_with = tiedWith
        # per-layer dtype override under a PrecisionPolicy: "float32"
        # declares an explicit fp32 island, anything contradicting the
        # network policy is the analysis pass's E301/W301 material
        if dataType is not None:
            from deeplearning4j_tpu.nn.precision import normalize_dtype
            dataType = normalize_dtype(dataType)
        self.dtype_override = dataType

    # -- config plumbing --
    def set_defaults(self, base):
        if self.activation is None:
            self.activation = base.activation
        if self.weight_init is None:
            self.weight_init = base.weight_init
        if self.l1 is None:
            self.l1 = base.l1
        if self.l2 is None:
            self.l2 = base.l2

    def infer_nin(self, it: InputType):
        if self.nIn is None and it.kind in ("ff", "cnn_flat"):
            self.nIn = it.arrayElementsPerExample()
        elif self.nIn is None and it.kind == "cnn":
            self.nIn = it.channels
        elif self.nIn is None and it.kind == "rnn":
            self.nIn = it.size

    def expected_nin(self, it: InputType) -> Optional[int]:
        """Declared-shape hook for ``analysis/``: the nIn this layer's
        ``infer_nin`` would derive from ``it``, computed on a throwaway
        copy so the static linter can compare a user-declared nIn against
        the propagated input WITHOUT mutating the config. May raise —
        subclasses' infer_nin validates geometry (the analyzer maps the
        exception to a diagnostic)."""
        import copy
        probe = copy.deepcopy(self)
        probe.nIn = None
        probe.infer_nin(it)
        return probe.nIn

    def mxu_lane_dims(self):
        """Declared-shape hook for the TPU layout lints: the lane
        (minor-most) dims of this layer's MXU matmuls. Default: nOut for
        any param-bearing layer; elementwise param layers override to []
        and gated recurrent layers report their fused gate width."""
        return [self.nOut] if self.has_params and self.nOut else []

    def param_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """Declared parameter shapes WITHOUT initializing anything — the
        jax-free static hook ``analysis/distribution.py`` sizes shards,
        HBM footprints, and FLOP estimates from. Dense-ish default
        (W [nIn, nOut] + optional b [nOut]); geometry-bearing subclasses
        override to match their ``initialize``. Returns {} while
        nIn/nOut are unresolved."""
        if not self.has_params or not self.nIn or not self.nOut:
            return {}
        shapes = {"W": (self.nIn, self.nOut)}
        if getattr(self, "has_bias", True):
            shapes["b"] = (self.nOut,)
        return shapes

    def output_type(self, it: InputType) -> InputType:
        return InputType.feedForward(self.nOut)

    # -- runtime --
    def initialize(self, key) -> Tuple[Dict, Dict]:
        return {}, {}

    def apply(self, params, state, x, train: bool, key):
        raise NotImplementedError

    def _maybe_dropout(self, x, train, key):
        if self.dropout and self.dropout < 1.0:
            return norm_ops.dropout(x, 1.0 - self.dropout, key, train=train)
        return x

    def n_params(self, params) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))

    # -- serialization --
    def to_config(self):
        d = {"@class": type(self).__name__}
        for k, v in self.__dict__.items():
            if isinstance(v, tuple):
                v = list(v)
            d[k] = v
        return d

    @classmethod
    def from_config(cls, d):
        obj = cls.__new__(cls)
        for k, v in d.items():
            if k == "@class":
                continue
            if isinstance(v, list) and k in ("kernel", "stride", "padding",
                                             "dilation", "scale", "crop",
                                             "dims"):
                v = tuple(v)
            setattr(obj, k, v)
        return obj

    def __repr__(self):
        return f"{type(self).__name__}(nIn={self.nIn}, nOut={self.nOut})"


class DenseLayer(Layer):
    """ref: layers.feedforward.dense.DenseLayer — W [nIn, nOut], out = act(xW + b)."""

    def __init__(self, nOut=None, hasBias: bool = True, **kw):
        super().__init__(nOut=nOut, **kw)
        self.has_bias = hasBias

    def initialize(self, key):
        params = {"W": _initialize((self.nIn, self.nOut), self.weight_init, key)}
        if self.has_bias:
            params["b"] = jnp.full((self.nOut,), self.bias_init, jnp.float32)
        return params, {}

    def apply(self, params, state, x, train, key):
        x = self._maybe_dropout(x, train, key)
        z = x @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        return act.get(self.activation)(z), state


class EmbeddingLayer(Layer):
    """ref: layers.feedforward.embedding.EmbeddingLayer — int indices [N] or
    one-hot rows -> embedding vectors [N, nOut]."""

    def __init__(self, nOut=None, hasBias: bool = False, **kw):
        super().__init__(nOut=nOut, **kw)
        self.has_bias = hasBias

    def initialize(self, key):
        params = {"W": _initialize((self.nIn, self.nOut), self.weight_init, key)}
        if self.has_bias:
            params["b"] = jnp.full((self.nOut,), self.bias_init, jnp.float32)
        return params, {}

    def apply(self, params, state, x, train, key):
        if jnp.issubdtype(x.dtype, jnp.floating) and x.ndim == 2 and x.shape[1] == self.nIn:
            out = x @ params["W"]  # one-hot rows
        else:
            idx = x.astype(jnp.int32)
            if idx.ndim == 2 and idx.shape[1] == 1:
                idx = idx[:, 0]
            out = jnp.take(params["W"], idx, axis=0)
        if self.has_bias:
            out = out + params["b"]
        return act.get(self.activation)(out), state


class EmbeddingSequenceLayer(Layer):
    """ref: EmbeddingSequenceLayer — [N, T] int -> [N, nOut, T] (NCW)."""

    input_kind = None

    def initialize(self, key):
        return {"W": _initialize((self.nIn, self.nOut), self.weight_init, key)}, {}

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        return {"W": (self.nIn, self.nOut)}

    def apply(self, params, state, x, train, key):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3:  # [N, 1, T]
            idx = idx[:, 0, :]
        emb = jnp.take(params["W"], idx, axis=0)  # [N, T, nOut]
        return jnp.transpose(emb, (0, 2, 1)), state

    def output_type(self, it: InputType) -> InputType:
        t = it.dims.get("timesteps", -1) if it.kind == "rnn" else it.dims.get("size", -1)
        return InputType.recurrent(self.nOut, t)


class ConvolutionLayer(Layer):
    """ref: layers.convolution.ConvolutionLayer — NCHW, W [nOut, nIn, kH, kW]."""

    input_kind = "cnn"

    def __init__(self, kernelSize=(3, 3), stride=(1, 1), padding=(0, 0),
                 nOut=None, dilation=(1, 1), convolutionMode: str = "truncate",
                 hasBias: bool = True, **kw):
        super().__init__(nOut=nOut, **kw)
        self.kernel = _pair(kernelSize)
        self.stride = _pair(stride)
        self.padding = _pair(padding)
        self.dilation = _pair(dilation)
        self.mode = convolutionMode
        self.has_bias = hasBias

    class Builder:
        def __init__(self, *kernel):
            self._kw = {"kernelSize": kernel if kernel else (3, 3)}

        def nIn(self, v): self._kw["nIn"] = v; return self
        def nOut(self, v): self._kw["nOut"] = v; return self
        def stride(self, *s): self._kw["stride"] = s; return self
        def padding(self, *p): self._kw["padding"] = p; return self
        def activation(self, a): self._kw["activation"] = a; return self
        def convolutionMode(self, m): self._kw["convolutionMode"] = m; return self
        def weightInit(self, w): self._kw["weightInit"] = w; return self
        def name(self, n): self._kw["name"] = n; return self
        def build(self): return ConvolutionLayer(**self._kw)

    def initialize(self, key):
        shape = (self.nOut, self.nIn) + self.kernel
        params = {"W": _initialize(shape, self.weight_init, key)}
        if self.has_bias:
            params["b"] = jnp.full((self.nOut,), self.bias_init, jnp.float32)
        return params, {}

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        shapes = {"W": (self.nOut, self.nIn) + tuple(self.kernel)}
        if self.has_bias:
            shapes["b"] = (self.nOut,)
        return shapes

    def apply(self, params, state, x, train, key, *, skip_bias=False):
        x = self._maybe_dropout(x, train, key)
        out = conv_ops.conv2d(x, params["W"],
                              None if skip_bias else params.get("b"),
                              stride=self.stride, pad=self.padding,
                              dilation=self.dilation, mode=self.mode,
                              data_format=self.data_format)
        return act.get(self.activation)(out), state

    def output_type(self, it: InputType) -> InputType:
        h = conv_ops.conv_output_size(it.height, self.kernel[0], self.stride[0],
                                      self.padding[0], self.dilation[0], self.mode)
        w = conv_ops.conv_output_size(it.width, self.kernel[1], self.stride[1],
                                      self.padding[1], self.dilation[1], self.mode)
        return InputType.convolutional(h, w, self.nOut)


class Deconvolution2D(ConvolutionLayer):
    """ref: layers.convolution.Deconvolution2DLayer."""

    def apply(self, params, state, x, train, key):
        out = conv_ops.deconv2d(x, params["W"], params.get("b"),
                                stride=self.stride, pad=self.padding,
                                mode=self.mode, data_format=self.data_format)
        return act.get(self.activation)(out), state

    def output_type(self, it: InputType) -> InputType:
        if self.mode.lower() == "same":
            h, w = it.height * self.stride[0], it.width * self.stride[1]
        else:
            h = (it.height - 1) * self.stride[0] + self.kernel[0] - 2 * self.padding[0]
            w = (it.width - 1) * self.stride[1] + self.kernel[1] - 2 * self.padding[1]
        return InputType.convolutional(h, w, self.nOut)


class DepthwiseConvolution2D(ConvolutionLayer):
    """ref: DepthwiseConvolution2DLayer — W [mult, nIn, kH, kW]."""

    def __init__(self, depthMultiplier: int = 1, **kw):
        super().__init__(**kw)
        self.depth_multiplier = depthMultiplier

    def infer_nin(self, it):
        super().infer_nin(it)
        if self.nOut is None:
            self.nOut = self.nIn * self.depth_multiplier

    def initialize(self, key):
        shape = (self.depth_multiplier, self.nIn) + self.kernel
        params = {"W": _initialize(shape, self.weight_init, key)}
        if self.has_bias:
            params["b"] = jnp.full((self.nOut,), self.bias_init, jnp.float32)
        return params, {}

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        shapes = {"W": (self.depth_multiplier, self.nIn) + tuple(self.kernel)}
        if self.has_bias:
            shapes["b"] = (self.nOut,)
        return shapes

    def apply(self, params, state, x, train, key):
        out = conv_ops.depthwise_conv2d(x, params["W"], params.get("b"),
                                        stride=self.stride, pad=self.padding,
                                        dilation=self.dilation, mode=self.mode,
                                        data_format=self.data_format)
        return act.get(self.activation)(out), state


class SeparableConvolution2D(ConvolutionLayer):
    """ref: SeparableConvolution2DLayer — depthwise + pointwise."""

    def __init__(self, depthMultiplier: int = 1, **kw):
        super().__init__(**kw)
        self.depth_multiplier = depthMultiplier

    def initialize(self, key):
        k1, k2 = jax.random.split(key)
        params = {
            "Wd": _initialize((self.depth_multiplier, self.nIn) + self.kernel,
                              self.weight_init, k1),
            "Wp": _initialize((self.nOut, self.nIn * self.depth_multiplier, 1, 1),
                              self.weight_init, k2),
        }
        if self.has_bias:
            params["b"] = jnp.full((self.nOut,), self.bias_init, jnp.float32)
        return params, {}

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        shapes = {"Wd": (self.depth_multiplier, self.nIn) + tuple(self.kernel),
                  "Wp": (self.nOut, self.nIn * self.depth_multiplier, 1, 1)}
        if self.has_bias:
            shapes["b"] = (self.nOut,)
        return shapes

    def apply(self, params, state, x, train, key):
        out = conv_ops.separable_conv2d(x, params["Wd"], params["Wp"],
                                        params.get("b"), stride=self.stride,
                                        pad=self.padding, dilation=self.dilation,
                                        mode=self.mode,
                                        data_format=self.data_format)
        return act.get(self.activation)(out), state


class SubsamplingLayer(Layer):
    """ref: layers.subsampling.SubsamplingLayer (max/avg/pnorm pooling)."""

    input_kind = "cnn"
    has_params = False

    def __init__(self, poolingType: str = "max", kernelSize=(2, 2), stride=(2, 2),
                 padding=(0, 0), convolutionMode: str = "truncate", pnorm: int = 2, **kw):
        super().__init__(**kw)
        self.pooling = poolingType.lower()
        self.kernel = _pair(kernelSize)
        self.stride = _pair(stride)
        self.padding = _pair(padding)
        self.mode = convolutionMode
        self.pnorm = pnorm

    class Builder:
        def __init__(self, poolingType="max", *kernel):
            self._kw = {"poolingType": poolingType}
            if kernel:
                self._kw["kernelSize"] = kernel

        def kernelSize(self, *k): self._kw["kernelSize"] = k; return self
        def stride(self, *s): self._kw["stride"] = s; return self
        def padding(self, *p): self._kw["padding"] = p; return self
        def build(self): return SubsamplingLayer(**self._kw)

    def infer_nin(self, it):
        self.nIn = self.nOut = it.channels

    def apply(self, params, state, x, train, key):
        fn = {"max": conv_ops.maxpool2d, "avg": conv_ops.avgpool2d,
              "pnorm": conv_ops.pnormpool2d}[self.pooling]
        kw = {"kernel": self.kernel, "stride": self.stride, "pad": self.padding,
              "mode": self.mode, "data_format": self.data_format}
        if self.pooling == "pnorm":
            kw["pnorm"] = self.pnorm
        return fn(x, **kw), state

    def output_type(self, it: InputType) -> InputType:
        h = conv_ops.conv_output_size(it.height, self.kernel[0], self.stride[0],
                                      self.padding[0], 1, self.mode)
        w = conv_ops.conv_output_size(it.width, self.kernel[1], self.stride[1],
                                      self.padding[1], 1, self.mode)
        return InputType.convolutional(h, w, it.channels)


class BatchNormalization(Layer):
    """ref: layers.normalization.BatchNormalization — running stats carried
    functionally in layer state (decay default 0.9 like the reference)."""

    input_kind = None
    has_params = True

    def __init__(self, decay: float = 0.9, eps: float = 1e-5, **kw):
        super().__init__(**kw)
        self.decay = decay
        self.eps = eps

    def infer_nin(self, it: InputType):
        if it.kind == "cnn":
            self.nIn = self.nOut = it.channels
        else:
            self.nIn = self.nOut = it.arrayElementsPerExample()

    def initialize(self, key):
        n = self.nIn
        params = {"gamma": jnp.ones((n,)), "beta": jnp.zeros((n,))}
        state = {"mean": jnp.zeros((n,)), "var": jnp.ones((n,))}
        return params, state

    def mxu_lane_dims(self):
        return []   # elementwise scale/shift — no matmul

    def param_shapes(self):
        if not self.nIn:
            return {}
        return {"gamma": (self.nIn,), "beta": (self.nIn,)}

    def _channel_axis(self, x) -> int:
        if x.ndim == 4 and self.data_format == "NHWC":
            return x.ndim - 1
        return 1 if x.ndim >= 3 else x.ndim - 1

    def apply(self, params, state, x, train, key):
        # mixed-precision island handled inside the ops: stats accumulate
        # fp32, the normalize is an FMA in x.dtype (no fp32 activation copy)
        axis = self._channel_axis(x)
        if train:
            out, new_mean, new_var = norm_ops.batch_norm_train(
                x, params["gamma"], params["beta"], state["mean"], state["var"],
                eps=self.eps, decay=self.decay, axis=axis)
            return out, {"mean": new_mean, "var": new_var}
        out = norm_ops.batch_norm(x, params["gamma"], params["beta"],
                                  state["mean"], state["var"], eps=self.eps,
                                  axis=axis)
        return out, state

    def output_type(self, it: InputType) -> InputType:
        return it


class LocalResponseNormalization(Layer):
    """ref: layers.normalization.LocalResponseNormalization."""

    input_kind = "cnn"
    has_params = False

    def __init__(self, n: int = 5, alpha: float = 1e-4, beta: float = 0.75,
                 k: float = 2.0, **kw):
        super().__init__(**kw)
        self.n = n
        self.alpha = alpha
        self.beta = beta
        self.k = k

    def infer_nin(self, it):
        self.nIn = self.nOut = it.channels

    def apply(self, params, state, x, train, key):
        return norm_ops.lrn(x, depth=self.n, alpha=self.alpha, beta=self.beta,
                            bias=self.k, data_format=self.data_format), state

    def output_type(self, it):
        return it


class ActivationLayer(Layer):
    """ref: layers.ActivationLayer."""

    input_kind = None
    has_params = False

    def __init__(self, activation="relu", **kw):
        super().__init__(activation=activation, **kw)

    def set_defaults(self, base):
        pass  # keeps its own activation

    def infer_nin(self, it):
        self.nIn = self.nOut = it.arrayElementsPerExample()

    def apply(self, params, state, x, train, key):
        return act.get(self.activation)(x), state

    def output_type(self, it):
        return it


class DropoutLayer(Layer):
    """ref: layers.DropoutLayer — dropOut value is the RETAIN probability."""

    input_kind = None
    has_params = False

    def __init__(self, dropOut=0.5, **kw):
        super().__init__(dropOut=dropOut, **kw)

    def infer_nin(self, it):
        self.nIn = self.nOut = it.arrayElementsPerExample()

    def apply(self, params, state, x, train, key):
        return self._maybe_dropout(x, train, key), state

    def output_type(self, it):
        return it


class SpatialDropoutLayer(Layer):
    """Channel dropout: zeroes WHOLE feature maps per example (ref:
    SpatialDropout in the reference's dropout family / Keras
    SpatialDropout1D-3D semantics). ``rate`` is the DROP probability.
    Input layout [N, C, *spatial]."""

    input_kind = None
    has_params = False

    def __init__(self, rate=0.5, **kw):
        super().__init__(**kw)
        self.rate = float(rate)

    def infer_nin(self, it):
        self.nIn = self.nOut = it.arrayElementsPerExample()

    def apply(self, params, state, x, train, key):
        if not train or self.rate <= 0.0:
            return x, state
        keep = 1.0 - self.rate
        shape = (x.shape[0], x.shape[1]) + (1,) * (x.ndim - 2)
        mask = jax.random.bernoulli(key, keep, shape).astype(x.dtype)
        return x * mask / keep, state

    def output_type(self, it):
        return it


class ZeroPaddingLayer(Layer):
    """ref: layers.ZeroPaddingLayer."""

    input_kind = "cnn"
    has_params = False

    def __init__(self, padding=(1, 1), **kw):
        super().__init__(**kw)
        if isinstance(padding, int):
            self.pad = (padding, padding)
        elif all(isinstance(p, (int, np.integer)) for p in padding):
            self.pad = tuple(int(p) for p in padding)
        else:   # asymmetric ((top, bottom), (left, right))
            self.pad = tuple(tuple(int(v) for v in p) for p in padding)

    def infer_nin(self, it):
        self.nIn = self.nOut = it.channels

    def apply(self, params, state, x, train, key):
        return conv_ops.zero_padding2d(x, self.pad,
                                       data_format=self.data_format), state

    def output_type(self, it):
        p = self.pad
        if isinstance(p[0], int):
            return InputType.convolutional(it.height + 2 * p[0], it.width + 2 * p[1],
                                           it.channels)
        return InputType.convolutional(it.height + sum(p[0]), it.width + sum(p[1]),
                                       it.channels)


class Upsampling2D(Layer):
    """ref: layers.Upsampling2D."""

    input_kind = "cnn"
    has_params = False

    def __init__(self, size=2, **kw):
        super().__init__(**kw)
        self.scale = _pair(size)

    def infer_nin(self, it):
        self.nIn = self.nOut = it.channels

    def apply(self, params, state, x, train, key):
        return conv_ops.upsampling2d(x, self.scale,
                                     data_format=self.data_format), state

    def output_type(self, it):
        return InputType.convolutional(it.height * self.scale[0],
                                       it.width * self.scale[1], it.channels)


class Cropping2D(Layer):
    """ref: layers.convolutional.Cropping2D."""

    input_kind = "cnn"
    has_params = False

    def __init__(self, crop=(1, 1), **kw):
        super().__init__(**kw)
        self.crop = tuple(crop)

    def infer_nin(self, it):
        self.nIn = self.nOut = it.channels

    def apply(self, params, state, x, train, key):
        return conv_ops.cropping2d(x, self.crop,
                                   data_format=self.data_format), state

    def output_type(self, it):
        c = self.crop
        if isinstance(c[0], int):
            return InputType.convolutional(it.height - 2 * c[0], it.width - 2 * c[1],
                                           it.channels)
        return InputType.convolutional(it.height - sum(c[0]), it.width - sum(c[1]),
                                       it.channels)


class GlobalPoolingLayer(Layer):
    """ref: layers.pooling.GlobalPoolingLayer — cnn [N,C,H,W] -> [N,C] or
    rnn [N,C,T] -> [N,C]; supports masks for rnn input."""

    input_kind = None
    has_params = False

    def __init__(self, poolingType: str = "max", **kw):
        super().__init__(**kw)
        self.pooling = poolingType.lower()

    def infer_nin(self, it):
        self.nIn = self.nOut = it.channels if it.kind in ("cnn", "cnn3d") \
            else it.size if it.kind == "rnn" else it.arrayElementsPerExample()

    def apply(self, params, state, x, train, key, mask=None):
        # the NHWC stamp only applies to spatial input; rnn [N,C,T] input
        # stays channels-second regardless of the compute layout
        fmt = self.data_format if x.ndim == 4 else "NCHW"
        return conv_ops.global_pool(x, self.pooling, data_format=fmt,
                                    mask=mask), state

    def output_type(self, it):
        n = it.channels if it.kind in ("cnn", "cnn3d") else it.size
        return InputType.feedForward(n)


# ------------------------------------------------------------------ recurrent
class LSTM(Layer):
    """ref: layers.recurrent.LSTM — input [N, nIn, T] -> [N, nOut, T].
    Forget-gate bias initialized to 1.0 like the reference."""

    input_kind = "rnn"

    def __init__(self, nOut=None, forgetGateBiasInit: float = 1.0, **kw):
        super().__init__(nOut=nOut, **kw)
        self.forget_bias = forgetGateBiasInit
        if self.activation is None:
            self.activation = "tanh"

    def set_defaults(self, base):
        super().set_defaults(base)
        if self.activation == "identity":
            self.activation = "tanh"

    def initialize(self, key):
        k1, k2 = jax.random.split(key)
        H = self.nOut
        b = np.zeros((4 * H,), np.float32)
        b[H:2 * H] = self.forget_bias  # gate order [i, f, g, o]
        params = {
            "W": _initialize((self.nIn, 4 * H), self.weight_init, k1),
            "RW": _initialize((H, 4 * H), self.weight_init, k2),
            "b": jnp.asarray(b),
        }
        return params, {}

    def mxu_lane_dims(self):
        return [4 * self.nOut] if self.nOut else []   # fused [i,f,g,o] gates

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        H = self.nOut
        return {"W": (self.nIn, 4 * H), "RW": (H, 4 * H), "b": (4 * H,)}

    def apply(self, params, state, x, train, key, mask=None):
        x_tnc = jnp.transpose(x, (2, 0, 1))  # [N,C,T] -> [T,N,C]
        mask_tn = jnp.transpose(mask, (1, 0)) if mask is not None else None
        outs, _ = rnn_ops.lstm(x_tnc, params["W"], params["RW"], params["b"],
                               mask_tn=mask_tn)
        return jnp.transpose(outs, (1, 2, 0)), state  # [T,N,H] -> [N,H,T]

    def apply_with_state(self, params, x, rnn_state, mask=None):
        """Streaming forward carrying (h, c) across calls
        (ref: MultiLayerNetwork.rnnTimeStep state keeping)."""
        x_tnc = jnp.transpose(x, (2, 0, 1))
        mask_tn = jnp.transpose(mask, (1, 0)) if mask is not None else None
        h0 = c0 = None
        if rnn_state is not None:
            h0, c0 = rnn_state
        outs, (hT, cT) = rnn_ops.lstm(x_tnc, params["W"], params["RW"],
                                      params["b"], h0=h0, c0=c0, mask_tn=mask_tn)
        return jnp.transpose(outs, (1, 2, 0)), (hT, cT)

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))


class GRU(Layer):
    """ref: layers.recurrent.GRU (gruCell op underneath) — input
    [N, nIn, T] -> [N, nOut, T], gate order [r, z, n] like the reference's
    libnd4j gruCell (and torch)."""

    input_kind = "rnn"

    def __init__(self, nOut=None, **kw):
        super().__init__(nOut=nOut, **kw)
        if self.activation in (None, "identity"):
            self.activation = "tanh"

    def set_defaults(self, base):
        super().set_defaults(base)
        if self.activation == "identity":
            self.activation = "tanh"

    def initialize(self, key):
        k1, k2 = jax.random.split(key)
        H = self.nOut
        params = {
            "W": _initialize((self.nIn, 3 * H), self.weight_init, k1),
            "RW": _initialize((H, 3 * H), self.weight_init, k2),
            "b": jnp.zeros((3 * H,), jnp.float32),
            "bR": jnp.zeros((3 * H,), jnp.float32),
        }
        return params, {}

    def mxu_lane_dims(self):
        return [3 * self.nOut] if self.nOut else []   # fused [r,z,n] gates

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        H = self.nOut
        return {"W": (self.nIn, 3 * H), "RW": (H, 3 * H),
                "b": (3 * H,), "bR": (3 * H,)}

    def apply(self, params, state, x, train, key, mask=None):
        x_tnc = jnp.transpose(x, (2, 0, 1))
        mask_tn = jnp.transpose(mask, (1, 0)) if mask is not None else None
        outs, _ = rnn_ops.gru(x_tnc, params["W"], params["RW"], params["b"],
                              params["bR"], mask_tn=mask_tn)
        return jnp.transpose(outs, (1, 2, 0)), state

    def apply_with_state(self, params, x, rnn_state, mask=None):
        x_tnc = jnp.transpose(x, (2, 0, 1))
        mask_tn = jnp.transpose(mask, (1, 0)) if mask is not None else None
        outs, hT = rnn_ops.gru(x_tnc, params["W"], params["RW"], params["b"],
                               params["bR"], h0=rnn_state, mask_tn=mask_tn)
        return jnp.transpose(outs, (1, 2, 0)), hT

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))


class ConvLSTM2D(Layer):
    """Convolutional LSTM over image sequences (ref: the reference's
    KerasConvLSTM2D import target). Input [N, C, T, H, W] (cnn3d layout,
    depth = time); output [N, nOut, H', W'] (last state) or
    [N, nOut, T, H', W'] with ``returnSequences``. Input convs use the
    configured padding/stride; recurrent convs are SAME-padded on the
    state grid (Keras semantics). Gate order [i, f, g, o]."""

    input_kind = "cnn3d"

    def __init__(self, nOut=None, kernelSize=(3, 3), stride=(1, 1),
                 convolutionMode: str = "truncate",
                 returnSequences: bool = False,
                 forgetGateBiasInit: float = 1.0, **kw):
        super().__init__(nOut=nOut, **kw)
        self.kernel = _pair(kernelSize)
        self.stride = _pair(stride)
        self.mode = convolutionMode
        self.return_sequences = returnSequences
        self.forget_bias = forgetGateBiasInit

    def infer_nin(self, it: InputType):
        self.nIn = it.channels

    def mxu_lane_dims(self):
        return [4 * self.nOut] if self.nOut else []

    def param_shapes(self):
        """Gate convs, matching ``initialize`` exactly — the base class's
        dense [nIn, nOut] guess undercounted both the HBM footprint and
        the W105 FLOP estimate for conv-LSTM stages."""
        if not self.nIn or not self.nOut:
            return {}
        H = self.nOut
        return {"W": (4 * H, self.nIn) + self.kernel,
                "RW": (4 * H, H) + self.kernel,
                "b": (4 * H,)}

    def initialize(self, key):
        k1, k2 = jax.random.split(key)
        H = self.nOut
        b = np.zeros((4 * H,), np.float32)
        b[H:2 * H] = self.forget_bias
        params = {
            "W": _initialize((4 * H, self.nIn) + self.kernel,
                             self.weight_init, k1),
            "RW": _initialize((4 * H, H) + self.kernel,
                              self.weight_init, k2),
            "b": jnp.asarray(b),
        }
        return params, {}

    def apply(self, params, state, x, train, key):
        H = self.nOut
        x_t = jnp.moveaxis(x, 2, 0)              # [T, N, C, H, W]
        # hoist the time-parallel input convs out of the recurrence
        T, N = x_t.shape[0], x_t.shape[1]
        xg = conv_ops.conv2d(
            x_t.reshape((T * N,) + x_t.shape[2:]), params["W"], params["b"],
            stride=self.stride, pad=(0, 0), mode=self.mode)
        xg = xg.reshape((T, N) + xg.shape[1:])   # [T, N, 4H, H', W']
        sp = xg.shape[3:]

        ret_seq = self.return_sequences

        def step(carry, g_in):
            h, c = carry
            gates = g_in + conv_ops.conv2d(h, params["RW"], None,
                                           stride=(1, 1), mode="same")
            i, f, g, o = jnp.split(gates, 4, axis=1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            # only stack per-step outputs when the caller wants sequences
            # (a [T, N, H, H', W'] stack is T x the necessary memory)
            return (h, c), (h if ret_seq else None)

        h0 = jnp.zeros((N, H) + sp, xg.dtype)
        (h_last, _), hs = jax.lax.scan(step, (h0, h0), xg)
        if ret_seq:
            return jnp.moveaxis(hs, 0, 2), state  # [N, H, T, H', W']
        return h_last, state

    def output_type(self, it: InputType) -> InputType:
        h = conv_ops.conv_output_size(it.height, self.kernel[0],
                                      self.stride[0], 0, 1, self.mode)
        w = conv_ops.conv_output_size(it.width, self.kernel[1],
                                      self.stride[1], 0, 1, self.mode)
        if self.return_sequences:
            return InputType.convolutional3D(it.depth, h, w, self.nOut)
        return InputType.convolutional(h, w, self.nOut)


class Convolution1D(Layer):
    """ref: layers.convolution.Convolution1DLayer — input [N, nIn, T]
    (NCW), W [nOut, nIn, k]; supports causal mode like the reference."""

    input_kind = "rnn"

    def __init__(self, kernelSize: int = 3, stride: int = 1, padding: int = 0,
                 nOut=None, dilation: int = 1, convolutionMode: str = "same",
                 hasBias: bool = True, **kw):
        super().__init__(nOut=nOut, **kw)
        self.kernel = int(kernelSize if not isinstance(kernelSize, (tuple, list))
                          else kernelSize[0])
        self.stride = int(stride if not isinstance(stride, (tuple, list))
                          else stride[0])
        self.padding = int(padding if not isinstance(padding, (tuple, list))
                           else padding[0])
        self.dilation = int(dilation if not isinstance(dilation, (tuple, list))
                            else dilation[0])
        self.mode = convolutionMode
        self.has_bias = hasBias

    def initialize(self, key):
        params = {"W": _initialize((self.nOut, self.nIn, self.kernel),
                                   self.weight_init, key)}
        if self.has_bias:
            params["b"] = jnp.full((self.nOut,), self.bias_init, jnp.float32)
        return params, {}

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        shapes = {"W": (self.nOut, self.nIn, self.kernel)}
        if self.has_bias:
            shapes["b"] = (self.nOut,)
        return shapes

    def apply(self, params, state, x, train, key, mask=None):
        out = conv_ops.conv1d(x, params["W"], params.get("b"),
                              stride=self.stride, pad=self.padding,
                              dilation=self.dilation, mode=self.mode)
        return act.get(self.activation)(out), state

    def output_type(self, it: InputType) -> InputType:
        t = it.dims.get("timesteps", -1)
        if t and t > 0:
            t = conv_ops.conv_output_size(t, self.kernel, self.stride,
                                          self.padding, self.dilation,
                                          self.mode)
        return InputType.recurrent(self.nOut, t)


class GravesLSTM(LSTM):
    """ref: layers.recurrent.GravesLSTM (legacy peephole variant; the
    peephole connections are omitted — reference deprecated it in favor of
    LSTM, and their effect is negligible; kept for API parity)."""


class SimpleRnn(Layer):
    """ref: layers.recurrent.SimpleRnn."""

    input_kind = "rnn"

    def __init__(self, nOut=None, **kw):
        super().__init__(nOut=nOut, **kw)
        if self.activation is None:
            self.activation = "tanh"

    def set_defaults(self, base):
        super().set_defaults(base)
        if self.activation == "identity":
            self.activation = "tanh"

    def initialize(self, key):
        k1, k2 = jax.random.split(key)
        params = {
            "W": _initialize((self.nIn, self.nOut), self.weight_init, k1),
            "RW": _initialize((self.nOut, self.nOut), self.weight_init, k2),
            "b": jnp.zeros((self.nOut,)),
        }
        return params, {}

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        return {"W": (self.nIn, self.nOut), "RW": (self.nOut, self.nOut),
                "b": (self.nOut,)}

    def apply(self, params, state, x, train, key, mask=None):
        x_tnc = jnp.transpose(x, (2, 0, 1))
        mask_tn = jnp.transpose(mask, (1, 0)) if mask is not None else None
        outs, _ = rnn_ops.simple_rnn(x_tnc, params["W"], params["RW"], params["b"],
                                     mask_tn=mask_tn,
                                     activation=act.get(self.activation))
        return jnp.transpose(outs, (1, 2, 0)), state

    def apply_with_state(self, params, x, rnn_state, mask=None):
        x_tnc = jnp.transpose(x, (2, 0, 1))
        mask_tn = jnp.transpose(mask, (1, 0)) if mask is not None else None
        h0 = rnn_state
        outs, hT = rnn_ops.simple_rnn(x_tnc, params["W"], params["RW"],
                                      params["b"], h0=h0, mask_tn=mask_tn,
                                      activation=act.get(self.activation))
        return jnp.transpose(outs, (1, 2, 0)), hT

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))


class Bidirectional(Layer):
    """ref: layers.recurrent.Bidirectional — wraps a recurrent layer,
    merge modes CONCAT/ADD/MUL/AVERAGE."""

    input_kind = "rnn"

    def __init__(self, rnn_layer: Layer, mode: str = "concat", **kw):
        super().__init__(**kw)
        self.fwd = rnn_layer
        import copy
        self.bwd = copy.deepcopy(rnn_layer)
        self.mode = mode.lower()

    def set_defaults(self, base):
        self.fwd.set_defaults(base)
        self.bwd.set_defaults(base)

    def infer_nin(self, it):
        self.fwd.infer_nin(it)
        self.bwd.infer_nin(it)
        self.nIn = self.fwd.nIn
        self.nOut = self.fwd.nOut * (2 if self.mode == "concat" else 1)

    def mxu_lane_dims(self):
        return self.fwd.mxu_lane_dims() + self.bwd.mxu_lane_dims()

    def param_shapes(self):
        out = {f"fwd/{k}": v for k, v in self.fwd.param_shapes().items()}
        out.update({f"bwd/{k}": v for k, v in self.bwd.param_shapes().items()})
        return out

    def initialize(self, key):
        k1, k2 = jax.random.split(key)
        pf, _ = self.fwd.initialize(k1)
        pb, _ = self.bwd.initialize(k2)
        return {"fwd": pf, "bwd": pb}, {}

    def apply(self, params, state, x, train, key, mask=None):
        yf, _ = self.fwd.apply(params["fwd"], {}, x, train, key, mask=mask)
        x_rev = jnp.flip(x, axis=2)
        mask_rev = jnp.flip(mask, axis=1) if mask is not None else None
        yb, _ = self.bwd.apply(params["bwd"], {}, x_rev, train, key, mask=mask_rev)
        yb = jnp.flip(yb, axis=2)
        if self.mode == "concat":
            return jnp.concatenate([yf, yb], axis=1), state
        if self.mode == "add":
            return yf + yb, state
        if self.mode == "mul":
            return yf * yb, state
        if self.mode == "average":
            return 0.5 * (yf + yb), state
        raise ValueError(self.mode)

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))

    def to_config(self):
        # class-aware: subclasses (BidirectionalLastStep) round-trip intact
        return {"@class": type(self).__name__, "mode": self.mode,
                "fwd": self.fwd.to_config(), "bwd": self.bwd.to_config(),
                "name": self.name, "nIn": self.nIn, "nOut": self.nOut}

    @classmethod
    def from_config(cls, d):
        inner = layer_from_config(d["fwd"])
        obj = cls(inner, mode=d["mode"])
        if "bwd" in d:    # independently-weighted directions (Keras import)
            obj.bwd = layer_from_config(d["bwd"])
        obj.nIn, obj.nOut = d.get("nIn"), d.get("nOut")
        return obj


class BidirectionalLastStep(Bidirectional):
    """Bidirectional collapsed to one step with KERAS semantics: the
    forward direction's LAST output merged with the backward direction's
    FINAL state (which corresponds to input position 0). NOTE this differs
    from LastTimeStep(Bidirectional(...)), which takes position T-1 of
    both directions (the reference's composition); this class exists for
    Keras model import parity."""

    def apply(self, params, state, x, train, key, mask=None):
        if mask is not None:
            raise ValueError("BidirectionalLastStep does not support "
                             "sequence masks (imported-model inference "
                             "path); pad-free batches only")
        yf, _ = self.fwd.apply(params["fwd"], {}, x, train, key, mask=None)
        x_rev = jnp.flip(x, axis=2)
        yb, _ = self.bwd.apply(params["bwd"], {}, x_rev, train, key,
                               mask=None)
        f = yf[:, :, -1]
        b = yb[:, :, -1]       # last step of reversed run = state at t=0
        if self.mode == "concat":
            return jnp.concatenate([f, b], axis=1), state
        if self.mode == "add":
            return f + b, state
        if self.mode == "mul":
            return f * b, state
        return (f + b) / 2.0, state

    def output_type(self, it: InputType) -> InputType:
        return InputType.feedForward(self.nOut)


class LastTimeStep(Layer):
    """ref: layers.recurrent.LastTimeStep — wraps an RNN layer, returns
    its final (mask-aware) timestep as feedforward output."""

    input_kind = "rnn"

    def __init__(self, rnn_layer: Layer, **kw):
        super().__init__(**kw)
        self.inner = rnn_layer

    def set_defaults(self, base):
        self.inner.set_defaults(base)

    def infer_nin(self, it):
        self.inner.infer_nin(it)
        self.nIn, self.nOut = self.inner.nIn, self.inner.nOut

    def initialize(self, key):
        return self.inner.initialize(key)

    def apply(self, params, state, x, train, key, mask=None):
        y, state = self.inner.apply(params, state, x, train, key, mask=mask)
        if mask is not None:
            # index of last active timestep per example
            idx = jnp.maximum(jnp.sum(mask > 0, axis=1).astype(jnp.int32) - 1, 0)
            return y[jnp.arange(y.shape[0]), :, idx], state
        return y[:, :, -1], state

    def output_type(self, it: InputType) -> InputType:
        return InputType.feedForward(self.inner.nOut)

    def to_config(self):
        return {"@class": "LastTimeStep", "inner": self.inner.to_config(),
                "name": self.name, "nIn": self.nIn, "nOut": self.nOut}

    @classmethod
    def from_config(cls, d):
        obj = LastTimeStep(layer_from_config(d["inner"]))
        obj.nIn, obj.nOut = d.get("nIn"), d.get("nOut")
        return obj


# ------------------------------------------------------------------- outputs
class BaseOutputLayer(Layer):
    """Common loss plumbing (ref: BaseOutputLayer)."""

    def __init__(self, lossFunction: str = "mcxent", **kw):
        super().__init__(**kw)
        self.loss_fn = lossFunction

    def compute_loss(self, labels, preds, mask=None):
        # the stable fused path when activation is softmax/sigmoid + matching loss
        return loss_ops.get(self.loss_fn)(labels, preds, mask=mask)


class OutputLayer(BaseOutputLayer):
    """ref: layers.OutputLayer — dense + activation + loss."""

    def __init__(self, nOut=None, lossFunction="mcxent", hasBias: bool = True, **kw):
        super().__init__(lossFunction=lossFunction, nOut=nOut, **kw)
        self.has_bias = hasBias
        if self.activation is None:
            self.activation = "softmax"

    class Builder:
        def __init__(self, lossFunction="mcxent"):
            self._kw = {"lossFunction": lossFunction}

        def nIn(self, v): self._kw["nIn"] = v; return self
        def nOut(self, v): self._kw["nOut"] = v; return self
        def activation(self, a): self._kw["activation"] = a; return self
        def build(self): return OutputLayer(**self._kw)

    def set_defaults(self, base):
        super().set_defaults(base)
        if self.activation == "identity":
            self.activation = "softmax"

    def initialize(self, key):
        params = {"W": _initialize((self.nIn, self.nOut), self.weight_init, key)}
        if self.has_bias:
            params["b"] = jnp.full((self.nOut,), self.bias_init, jnp.float32)
        return params, {}

    def apply(self, params, state, x, train, key):
        x = self._maybe_dropout(x, train, key)
        z = x @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        return act.get(self.activation)(z), state

    def pre_activation(self, params, x):
        z = x @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        return z


class LossLayer(BaseOutputLayer):
    """ref: layers.LossLayer — activation + loss, no params."""

    has_params = False
    input_kind = None

    def __init__(self, lossFunction="mcxent", **kw):
        super().__init__(lossFunction=lossFunction, **kw)
        if self.activation is None:
            self.activation = "identity"

    def infer_nin(self, it):
        self.nIn = self.nOut = it.arrayElementsPerExample()

    def apply(self, params, state, x, train, key):
        return act.get(self.activation)(x), state

    def output_type(self, it):
        return it


class RnnOutputLayer(BaseOutputLayer):
    """ref: layers.recurrent.RnnOutputLayer — per-timestep dense + loss.
    Input [N, nIn, T] -> [N, nOut, T]."""

    input_kind = "rnn"

    def __init__(self, nOut=None, lossFunction="mcxent", **kw):
        super().__init__(lossFunction=lossFunction, nOut=nOut, **kw)
        if self.activation is None:
            self.activation = "softmax"

    def set_defaults(self, base):
        super().set_defaults(base)
        if self.activation == "identity":
            self.activation = "softmax"

    def initialize(self, key):
        return {"W": _initialize((self.nIn, self.nOut), self.weight_init, key),
                "b": jnp.zeros((self.nOut,))}, {}

    def apply(self, params, state, x, train, key):
        # [N, C, T]: per-timestep projection = einsum over C
        z = jnp.einsum("nct,ch->nht", x, params["W"]) + params["b"][None, :, None]
        a = act.get(self.activation)(z, axis=1) if self.activation in ("softmax", "logsoftmax") \
            else act.get(self.activation)(z)
        return a, state

    def compute_loss(self, labels, preds, mask=None):
        """labels/preds [N, C, T]; mask [N, T]. The reference sums each
        example's per-timestep losses and divides by the minibatch size N
        (NOT by N*T) — preserved here so LR settings transfer from reference
        configs. Flattens time into batch for the loss kernel, then rescales
        the per-row mean back to sum-over-time / N."""
        n = labels.shape[0]
        lab = jnp.reshape(jnp.transpose(labels, (0, 2, 1)), (-1, labels.shape[1]))
        pre = jnp.reshape(jnp.transpose(preds, (0, 2, 1)), (-1, preds.shape[1]))
        m = jnp.reshape(mask, (-1,)) if mask is not None else None
        per_row_mean = loss_ops.get(self.loss_fn)(lab, pre, mask=m)
        n_rows = jnp.maximum(jnp.sum(m), 1.0) if m is not None else lab.shape[0]
        return per_row_mean * n_rows / n

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))


class PReLULayer(Layer):
    """ref: layers.feedforward.PReLULayer."""

    input_kind = None

    def infer_nin(self, it):
        self.nIn = self.nOut = it.arrayElementsPerExample()

    def mxu_lane_dims(self):
        return []   # elementwise slope — no matmul

    def param_shapes(self):
        return {"alpha": (self.nIn,)} if self.nIn else {}

    def initialize(self, key):
        return {"alpha": jnp.full((self.nIn,), 0.25)}, {}

    def apply(self, params, state, x, train, key):
        a = params["alpha"]
        if x.ndim == 4:  # NCHW: alpha per channel plane
            a = a.reshape(1, -1, 1, 1) if a.size == x.shape[1] else a.reshape((1,) + x.shape[1:])
        return jnp.where(x >= 0, x, a * x), state

    def output_type(self, it):
        return it


class Subsampling1DLayer(Layer):
    """ref: layers.subsampling.Subsampling1DLayer — [N, C, T] pooling.

    LIMITATION: sequence masks are not downsampled through the pool (the
    reference downsamples the mask alongside); a masked fit() with a
    strided pool before a mask-aware layer fails loudly on the length
    mismatch rather than silently mis-pooling padding."""

    input_kind = "rnn"
    has_params = False

    def __init__(self, poolingType: str = "max", kernelSize: int = 2,
                 stride: int = None, padding: int = 0,
                 convolutionMode: str = "truncate", **kw):
        super().__init__(**kw)
        self.pooling = poolingType.lower()
        self.kernel = int(kernelSize if not isinstance(kernelSize, (tuple, list))
                          else kernelSize[0])
        self.stride = int(stride if stride is not None else self.kernel)
        self.padding = int(padding)
        self.mode = convolutionMode

    def infer_nin(self, it):
        self.nIn = self.nOut = it.size

    def apply(self, params, state, x, train, key, mask=None):
        fn = conv_ops.maxpool1d if self.pooling == "max" else conv_ops.avgpool1d
        return fn(x, kernel=self.kernel, stride=self.stride,
                  pad=self.padding, mode=self.mode), state

    def output_type(self, it: InputType) -> InputType:
        t = it.dims.get("timesteps", -1)
        if t and t > 0:
            t = conv_ops.conv_output_size(t, self.kernel, self.stride,
                                          self.padding, 1, self.mode)
        return InputType.recurrent(it.size, t)


class LayerNorm(Layer):
    """ref: layers.LayerNorm (a.k.a. Keras LayerNormalization) — per-sample
    normalization over the feature axis with learned gain/bias. Feature
    axis: -1 for [N, D], the CHANNEL axis (1) for [N, C, T]."""

    input_kind = None
    has_params = True

    def __init__(self, eps: float = 1e-5, **kw):
        super().__init__(**kw)
        self.eps = eps

    def infer_nin(self, it: InputType):
        if it.kind == "cnn":
            raise ValueError(
                "LayerNorm supports dense [N, D] and recurrent [N, C, T] "
                "inputs; 4-D CNN feature maps are not supported")
        self.nIn = self.nOut = it.size if it.kind == "rnn" \
            else it.arrayElementsPerExample()

    def mxu_lane_dims(self):
        return []   # elementwise gain/bias — no matmul

    def param_shapes(self):
        return {"gamma": (self.nIn,), "beta": (self.nIn,)} if self.nIn else {}

    def initialize(self, key):
        return {"gamma": jnp.ones((self.nIn,), jnp.float32),
                "beta": jnp.zeros((self.nIn,), jnp.float32)}, {}

    def _ln(self, x, params):
        # resolve through the registry so Pallas platform overrides apply
        from deeplearning4j_tpu.ops import registry as _registry
        return _registry.get("layer_norm")(x, params["gamma"],
                                           params["beta"], eps=self.eps)

    def apply(self, params, state, x, train, key, mask=None):
        if x.ndim == 3:   # [N, C, T]: normalize the channel axis
            xt = jnp.swapaxes(x, 1, 2)         # [N, T, C]
            return jnp.swapaxes(self._ln(xt, params), 1, 2), state
        return self._ln(x, params), state

    def output_type(self, it: InputType) -> InputType:
        return it


class GroupNorm(Layer):
    """Group normalization over channel groups (ref: the reference's
    GroupNormalization keras-import target; layout [N, C, *spatial],
    normalize within each of ``groups`` channel groups + spatial dims)."""

    input_kind = None
    has_params = True

    def __init__(self, groups: int = 32, eps: float = 1e-3, **kw):
        super().__init__(**kw)
        self.groups = int(groups)
        self.eps = eps

    def infer_nin(self, it: InputType):
        self.nIn = self.nOut = it.channels if it.kind in ("cnn", "cnn3d") \
            else it.size if it.kind == "rnn" else it.arrayElementsPerExample()
        if self.groups == -1:           # Keras shorthand: instance norm
            self.groups = self.nIn
        if self.groups < 1 or self.nIn % self.groups:
            raise ValueError(f"GroupNorm: {self.nIn} channels not divisible "
                             f"by {self.groups} groups")

    def mxu_lane_dims(self):
        return []   # elementwise gain/bias — no matmul

    def param_shapes(self):
        return {"gamma": (self.nIn,), "beta": (self.nIn,)} if self.nIn else {}

    def initialize(self, key):
        return {"gamma": jnp.ones((self.nIn,), jnp.float32),
                "beta": jnp.zeros((self.nIn,), jnp.float32)}, {}

    def apply(self, params, state, x, train, key):
        N, C = x.shape[0], x.shape[1]
        G = self.groups
        xg = x.reshape((N, G, C // G) + x.shape[2:]).astype(jnp.float32)
        axes = tuple(range(2, xg.ndim))
        m = jnp.mean(xg, axis=axes, keepdims=True)
        v = jnp.mean(jnp.square(xg - m), axis=axes, keepdims=True)
        y = ((xg - m) * jax.lax.rsqrt(v + self.eps)).reshape(x.shape)
        shape = (1, C) + (1,) * (x.ndim - 2)
        y = y * params["gamma"].reshape(shape) + params["beta"].reshape(shape)
        return y.astype(x.dtype), state

    def output_type(self, it: InputType) -> InputType:
        return it


class UnitNormLayer(Layer):
    """L2-normalize the channel/feature axis (Keras UnitNormalization)."""

    input_kind = None
    has_params = False

    def __init__(self, **kw):
        super().__init__(**kw)

    def infer_nin(self, it: InputType):
        self.nIn = self.nOut = it.channels if it.kind in ("cnn", "cnn3d") \
            else it.size if it.kind == "rnn" else it.arrayElementsPerExample()

    def apply(self, params, state, x, train, key):
        axis = 1 if x.ndim > 2 else -1
        n = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axis,
                             keepdims=True))
        return (x / jnp.maximum(n, 1e-12).astype(x.dtype)), state

    def output_type(self, it: InputType) -> InputType:
        return it


class Permute(Layer):
    """ref: Keras Permute — reorder NON-batch axes (1-based dims)."""

    input_kind = None
    has_params = False

    def __init__(self, dims=(2, 1), **kw):
        super().__init__(**kw)
        self.dims = tuple(int(d) for d in dims)

    def infer_nin(self, it):
        self.nIn = self.nOut = None

    def apply(self, params, state, x, train, key):
        perm = (0,) + self.dims
        return jnp.transpose(x, perm), state

    def output_type(self, it: InputType) -> InputType:
        if it.kind == "rnn" and self.dims == (2, 1):
            return InputType.recurrent(it.dims.get("timesteps", -1), it.size)
        return it


class RepeatVector(Layer):
    """ref: Keras RepeatVector — [N, D] -> [N, D, n] (NCW layout)."""

    input_kind = "ff"
    has_params = False

    def __init__(self, n: int = 2, **kw):
        super().__init__(**kw)
        self.n = int(n)

    def infer_nin(self, it):
        self.nIn = self.nOut = it.arrayElementsPerExample()

    def apply(self, params, state, x, train, key):
        return jnp.repeat(x[:, :, None], self.n, axis=2), state

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, self.n)


_LAYER_CLASSES = {}
for _cls in [DenseLayer, EmbeddingLayer, EmbeddingSequenceLayer, ConvolutionLayer,
             Convolution1D, Subsampling1DLayer, LayerNorm, Permute,
             RepeatVector, Deconvolution2D, DepthwiseConvolution2D, SeparableConvolution2D,
             SubsamplingLayer, BatchNormalization, LocalResponseNormalization,
             ActivationLayer, DropoutLayer, ZeroPaddingLayer, Upsampling2D,
             Cropping2D, GlobalPoolingLayer, LSTM, GravesLSTM, GRU, SimpleRnn,
             Bidirectional, BidirectionalLastStep, LastTimeStep,
             OutputLayer, LossLayer, RnnOutputLayer,
             PReLULayer]:
    _LAYER_CLASSES[_cls.__name__] = _cls


def layer_from_config(d: Dict) -> Layer:
    cls = _LAYER_CLASSES[d["@class"]]
    return cls.from_config(d)


# ------------------------------------------------------------- dtype policy
# "bf16 plumbing" in the nn/ stack: master
# parameters stay fp32 (updater math, BatchNorm statistics, losses), while
# matmul/conv/pool layers compute in bfloat16 — the MXU-native dtype
# (SURVEY.md §6). Enabled per-network via NeuralNetConfiguration.dataType
# ("bfloat16"); the cast happens inside the compiled step so XLA fuses it
# into the consuming convolution.

# Param-side fp32 islands: BatchNorm/LRN keep fp32 params and cast
# internally (activations stay bf16 through them); output/loss layers get
# fp32 activations AND fp32 params (softmax + loss numerics).
_POLICY_FP32_PARAM_LAYERS = (BatchNormalization, LocalResponseNormalization,
                             BaseOutputLayer)


def compute_dtype_of(conf_dtype) -> Optional[Any]:
    """None = no policy (pure fp32); jnp.bfloat16 = mixed-precision."""
    if str(conf_dtype).lower() in ("bfloat16", "bf16"):
        return jnp.bfloat16
    return None


#: a float32 matrix leaf of at least this many elements whose products run
#: over more than :data:`UPDATE_APART_ROWS` rows of activations hands its
#: gradient to the updater apart from the product that makes it
#: (:func:`_cast_apart`). Fused, the compiler puts Adam's update of the
#: leaf into the weight gradient's product as a multi-output epilogue that
#: reads and writes p, m and v; at 32,768 rows those float32 tiles shrink
#: the product's output windows (1x64x3 against 1x256x4 apart for the
#: dense MLP's [2048, 11776]) and the rows are streamed more often.
#: Measured on a v5e, ms a call, fused / apart, forward + both gradients
#: + Adam (``benchmarks/probe_update_apart.py``):
#:
#:     rows a step         4,096        8,192       16,384       32,768
#:     x @ [2048, 512]   .35/.35      .35/.34      .45/.51      .80/.80
#:     x @ [2048, 2048]  .51/.52      .86/.88     1.61/1.66    3.56/3.08
#:     x @ [2048, 6144] 1.28/1.62    2.39/2.84    4.78/4.88    9.93/9.15
#:     x @ [2048,11776] 2.27/3.21    4.82/5.21    8.86/9.41   19.99/18.08
#:     GatedMLP, 11776 11.10/11.83  20.21/20.46  40.24/40.10  87.59/80.07
#:
#: fused is right up to 16,384 rows and wrong at 32,768, where a
#: [2048, 512] leaf is a wash.
UPDATE_APART_ELEMENTS = 2048 * 2048
UPDATE_APART_ROWS = 16384

_UPDATE_APART_LOWERED = _prof.get_registry().counter(
    "dl4j_update_apart_lowered_total",
    "Float32 leaves cast to the compute dtype (one a leaf a lowering of "
    "each call site, not one a step) by where their gradient meets the "
    "update: apart (the gradient leaves its product's fusion in the "
    "compute dtype, the update is a fusion of its own) or fused",
    labelnames=("path",))


def update_apart(shape, rows) -> bool:
    """Whether a leaf of ``shape`` whose products run over ``rows`` rows of
    activations hands its gradient over apart (:data:`UPDATE_APART_ROWS`):
    a matrix (a convolution kernel is 4-D, an expert stack 3-D) of at least
    :data:`UPDATE_APART_ELEMENTS` elements."""
    return len(shape) == 2 and math.prod(shape) >= UPDATE_APART_ELEMENTS \
        and rows > UPDATE_APART_ROWS


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _cast_apart(w, dtype):
    """``w.astype(dtype)`` whose gradient crosses an optimization barrier
    in ``dtype`` before it is widened to ``w``'s float32: the product that
    makes it ends there, and the update cannot ride in its fusion. The
    gradient is the product's result in ``dtype``, as the program states
    it in both forms; fused, a TPU compiler may hand the update the
    product's float32 accumulator instead (excess precision), so there the
    two forms can differ by ``dtype``'s rounding of the gradient."""
    return w.astype(dtype)


def _cast_apart_fwd(w, dtype):
    return w.astype(dtype), None


def _cast_apart_bwd(dtype, _, g):
    return (jax.lax.optimization_barrier(g).astype(jnp.float32),)


_cast_apart.defvjp(_cast_apart_fwd, _cast_apart_bwd)


def policy_cast(layer, params, x, compute_dt):
    """Cast (params, input) for one layer under the dtype policy.

    A per-layer ``dataType=`` override refines the policy: "float32"
    declares an explicit fp32 island (params and activations stay/return
    to fp32 through this layer); an override matching the compute dtype
    is a no-op.  Overrides that contradict the policy are the analysis
    pass's E301 — the runtime honors fp32 islands and policy-matching
    overrides only."""
    if compute_dt is None:
        return params, x
    override = getattr(layer, "dtype_override", None)
    if override == "float32" and not isinstance(layer, BaseOutputLayer):
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != jnp.float32:
            x = x.astype(jnp.float32)
        elif x.dtype == jnp.uint8:
            x = x.astype(jnp.float32)
        return params, x
    if getattr(layer, "loss_from_input", False):
        # fp32 master params, activations as they come: the layer casts
        # its matmul operands itself and keeps float32 logits
        return params, x
    if isinstance(layer, BaseOutputLayer):
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != jnp.float32:
            x = x.astype(jnp.float32)
        return params, x
    if isinstance(layer, _POLICY_FP32_PARAM_LAYERS) \
            or getattr(layer, "fp32_params", False):
        return params, x
    if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != compute_dt:
        x = x.astype(compute_dt)
    elif x.dtype == jnp.uint8:
        # image bytes straight off the host pipeline: cast ON DEVICE (fused
        # into the first conv program) so the host ships 1/4 the bandwidth
        # and never pays a float conversion (data/pipeline.py)
        x = x.astype(compute_dt)
    if params:
        # a layer's ``fp32_leaves`` stay masters (a router, a norm's gain
        # inside an attention layer): the layer casts where it uses them
        keep = getattr(layer, "fp32_leaves", ())
        rows = x.size // x.shape[-1] \
            if jnp.issubdtype(x.dtype, jnp.floating) and x.ndim > 1 else 0

        def cast(a):
            if getattr(a, "dtype", None) != jnp.float32:
                return a
            apart = update_apart(a.shape, rows)
            _UPDATE_APART_LOWERED.labels("apart" if apart else "fused").inc()
            return _cast_apart(a, compute_dt) if apart \
                else a.astype(compute_dt)
        params = jax.tree_util.tree_map(cast, params) if not keep else {
            k: v if k in keep else jax.tree_util.tree_map(cast, v)
            for k, v in params.items()}
    return params, x


# ----------------------------------------------------------- compute layout
# NHWC seam (ISSUE 14): image convs on TPU want channels on the lane
# (minor-most) axis — XLA's NCHW lowering transposes internally per op or
# runs channel-padded tiles (the W101 story). The networks'
# ``setComputeLayout("NHWC")`` keeps the PUBLIC layout NCHW (inputs,
# outputs, weights [O,I,kH,kW], checkpoints) and transposes once at each
# layout boundary inside the compiled step; layout-aware layers carry a
# ``data_format`` stamp their apply reads.

#: layers whose apply computes natively in NHWC when stamped (the conv
#: family covers Deconvolution/Depthwise/Separable via subclassing)
LAYOUT_AWARE = (ConvolutionLayer, SubsamplingLayer, BatchNormalization,
                LocalResponseNormalization, ZeroPaddingLayer, Upsampling2D,
                Cropping2D, GlobalPoolingLayer)

#: elementwise layers that keep whatever layout flows in (no transpose)
LAYOUT_TRANSPARENT = (ActivationLayer, DropoutLayer)


def to_nhwc(x):
    return jnp.transpose(x, (0, 2, 3, 1))


def to_nchw(x):
    return jnp.transpose(x, (0, 3, 1, 2))


def rank_of(x) -> int:
    """Rank of an array, or of one pass of a loop's passes."""
    if isinstance(x, tuple):
        return rank_of(x[0])
    return getattr(x, "ndim", 0)


def to_public(x):
    """Compute layout back to the public one: NHWC -> NCHW for an image
    map, feature-last [N, T, C] -> the reference's [N, C, T] for a
    sequence, each pass of a loop's passes alike."""
    if isinstance(x, tuple):
        return tuple(to_public(a) for a in x)
    return to_nchw(x) if x.ndim == 4 else jnp.swapaxes(x, 1, 2)


def layout_step(layer, x, cur_nhwc: bool, nhwc_active: bool,
                sequences: bool = False):
    """THE transpose-at-boundary rule, one layer at a time: returns
    ``(x, now_nhwc)``. Aware layers pull spatial input into NHWC,
    transparent layers keep whatever flows in, everything else (dense,
    output heads, preprocess boundaries) forces NCHW back. Shared by the
    compiled forwards, ``feedForward``, the sanitizer's eager replay
    walkers, and the devicetime bridge so the mirrors cannot drift.

    ``sequences`` (the graph's forwards): a 3-D [N, C, T] sequence is
    held feature-last, [N, T, C], through the layers that compute that
    way (:data:`SEQUENCE_LAST`, always: the contracted axis belongs on
    the lanes, there is nothing to choose) and the transparent ones, and
    turned back for any other layer; a loop's passes turn together."""
    if sequences and isinstance(x, tuple):
        steps = [layout_step(layer, a, cur_nhwc, nhwc_active, True)
                 for a in x]
        return tuple(a for a, _ in steps), steps[0][1]
    if sequences and getattr(x, "ndim", 0) == 3 \
            and jnp.issubdtype(x.dtype, jnp.floating):
        want = isinstance(layer, SEQUENCE_LAST) or \
            (cur_nhwc and isinstance(layer, LAYOUT_TRANSPARENT))
        return (jnp.swapaxes(x, 1, 2) if want != cur_nhwc else x), want
    if getattr(x, "ndim", 0) != 4:
        return x, False
    want = (nhwc_active and isinstance(layer, LAYOUT_AWARE)) or \
        (cur_nhwc and isinstance(layer, LAYOUT_TRANSPARENT))
    if want and not cur_nhwc:
        return to_nhwc(x), True
    if not want and cur_nhwc:
        return to_nchw(x), False
    return x, cur_nhwc


def stamp_layout(layers, fmt: str) -> None:
    """Stamp ``data_format`` on every layout-aware layer (instance attr,
    so it round-trips through to_config/from_config). ``"NCHW"`` removes
    the stamp, restoring the class default."""
    if fmt not in ("NCHW", "NHWC"):
        raise ValueError(f"compute layout must be 'NCHW' or 'NHWC', "
                         f"got {fmt!r}")
    for layer in layers:
        if isinstance(layer, LAYOUT_AWARE):
            if fmt == "NHWC":
                layer.data_format = "NHWC"
            elif "data_format" in layer.__dict__:
                del layer.data_format


# --------------------------------------------------------- fused epilogues
# bias+BN+activation epilogue fusion (ISSUE 14): the conv stacks' hot
# non-matmul block is BatchNorm followed by relu/leaky-relu. Fused here
# into ONE scale_shift_act op — batch statistics stay the fp32
# reductions of norm_ops.batch_norm_train, the normalize+activation
# becomes a single FMA+select the 'scale_shift_act' registry op executes
# (composed jnp, bit-identical to the unfused batch_norm+activation path;
# the compiler fuses it into the neighbouring convolution). A preceding
# identity-activation conv's bias folds into the shift algebraically
# (BN subtracts the mean, so the bias cancels in train mode and shifts
# the recorded running mean; inference un-shifts it from the running
# stats) — the conv itself dispatches bias-less.


def activation_alpha(layer) -> Optional[float]:
    """The epilogue slope for an ActivationLayer: 0.0 for relu, the leak
    for leakyrelu, None for anything else (not fusable)."""
    if type(layer) is not ActivationLayer or layer.dropout:
        return None
    name = str(layer.activation or "").lower()
    if name == "relu":
        return 0.0
    if name == "leakyrelu":
        return 0.01      # ops.activations.leakyrelu default slope
    return None


def fusable_conv(layer) -> bool:
    """A plain ConvolutionLayer whose own epilogue is empty (identity
    activation, no dropout) and whose bias can therefore fold into the
    following BN's shift."""
    return (type(layer) is ConvolutionLayer
            and str(layer.activation or "identity").lower() == "identity"
            and not layer.dropout)


def fusable_bn(layer) -> bool:
    return type(layer) is BatchNormalization and not layer.dropout


def fused_bn_act(bn, params, state, x, train, alpha: float, bias=None):
    """BatchNorm + relu/leaky epilogue (+ optional folded conv bias) as
    one ``scale_shift_act`` dispatch. Returns ``(out, new_bn_state)``.

    Statistics are bit-identical to ``norm_ops.batch_norm_train`` (fp32
    accumulate); with ``bias`` the batch stats run over the BIAS-LESS
    conv output (variance is bias-invariant; the recorded running mean
    adds the bias back so inference-mode behaviour matches the unfused
    stack).
    """
    from deeplearning4j_tpu.ops import registry as _registry
    axis = bn._channel_axis(x)
    gamma, beta = params["gamma"], params["beta"]
    b32 = bias.astype(jnp.float32) if bias is not None else None
    if train:
        axes = tuple(i for i in range(x.ndim) if i != axis)
        m = jnp.mean(x, axis=axes, dtype=jnp.float32)
        m2 = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axes)
        v = jnp.maximum(m2 - jnp.square(m), 0.0)
        m_rec = m + b32 if b32 is not None else m
        new_state = {"mean": bn.decay * state["mean"] + (1.0 - bn.decay) * m_rec,
                     "var": bn.decay * state["var"] + (1.0 - bn.decay) * v}
        mean_eff = m        # the folded bias cancels against the batch mean
    else:
        mean_eff = state["mean"] - b32 if b32 is not None else state["mean"]
        v = state["var"]
        new_state = state
    inv = jax.lax.rsqrt(v.astype(jnp.float32) + bn.eps)
    scale = (gamma * inv).astype(x.dtype)
    shift = (beta - gamma * mean_eff * inv).astype(x.dtype)
    out = _registry.get("scale_shift_act")(x, scale, shift, alpha=alpha,
                                           axis=axis)
    return out, new_state


def conv_bias_add(layer, out, b):
    """Re-attach a conv bias to a ``skip_bias=True`` conv output,
    bit-identical to the unfused path: ``conv_ops.conv2d`` applies its
    bias as this exact broadcast add AFTER the conv and the output dtype
    cast (and a fusable conv's activation is identity), so
    ``conv_bias_add(layer, conv_no_bias, b) == conv2d(..., b=b)`` to the
    bit.  Used when a folded conv's output also feeds consumers OUTSIDE
    its fused BN epilogue: they read the re-biased tensor while the BN
    consumes the bias-less one (the bias rides in its shift)."""
    return out + conv_ops._bias_reshape(b, 2, layer.data_format)


def build_epilogue_plan(layers, preprocessors=()) -> Dict[int, Tuple[int, bool, float]]:
    """Static fusion plan over a sequential layer list:
    ``{start_index: (n_layers_consumed, conv_leads, alpha)}`` —
    3 for conv(identity,bias)+BN+act triples (bias folds), 2 for BN+act
    pairs. Built once at step-compile time; fit dispatch consults it.

    ``preprocessors`` are the layer indices carrying an input
    preprocessor: a block whose INTERIOR index has one cannot fuse (the
    fused dispatch jumps straight through and would drop it); one at the
    block's start is fine — it runs before the block either way."""
    plan: Dict[int, Tuple[int, bool, float]] = {}
    pre = frozenset(preprocessors)
    i = 0
    while i < len(layers):
        if (i + 2 < len(layers) and fusable_conv(layers[i])
                and layers[i].has_bias and fusable_bn(layers[i + 1])
                and activation_alpha(layers[i + 2]) is not None
                and not (pre & {i + 1, i + 2})):
            plan[i] = (3, True, activation_alpha(layers[i + 2]))
            i += 3
            continue
        if (i + 1 < len(layers) and fusable_bn(layers[i])
                and activation_alpha(layers[i + 1]) is not None
                and i + 1 not in pre):
            plan[i] = (2, False, activation_alpha(layers[i + 1]))
            i += 2
            continue
        i += 1
    return plan


class SelfAttentionLayer(Layer):
    """ref: layers.samediff.SelfAttentionLayer — multi-head dot-product
    self-attention over a time series [N, nIn, T] -> [N, nOut, T].

    ``projectInput=True`` (required when nHeads > 1 or nOut != nIn) learns
    Wq/Wk/Wv: [nIn, nHeads*headSize] and Wo: [nHeads*headSize, nOut];
    without projection it is plain scaled dot-product attention and
    nOut == nIn. Masking: padded timesteps neither attend nor are
    attended to (reference semantics)."""

    input_kind = "rnn"

    def __init__(self, nOut=None, nHeads: int = 1, headSize: int = None,
                 projectInput: bool = True, useBias: bool = False, **kw):
        super().__init__(nOut=nOut, **kw)
        self.n_heads = nHeads
        self.head_size = headSize
        self.project = projectInput
        self.use_bias = useBias

    def infer_nin(self, it: InputType):
        super().infer_nin(it)
        if self.nOut is None:
            self.nOut = self.nIn
        if self.head_size is None:
            self.head_size = self.nOut // self.n_heads
        if not self.project:
            if self.n_heads != 1 or self.nOut != self.nIn:
                raise ValueError(
                    "SelfAttentionLayer: projectInput=False requires "
                    f"nHeads=1 and nOut==nIn (got nHeads={self.n_heads}, "
                    f"nIn={self.nIn}, nOut={self.nOut})")

    def param_shapes(self):
        if not self.project or not self.nIn or not self.nOut \
                or not self.head_size:
            return {}
        E = self.n_heads * self.head_size
        shapes = {"Wq": (self.nIn, E), "Wk": (self.nIn, E),
                  "Wv": (self.nIn, E), "Wo": (E, self.nOut)}
        if getattr(self, "use_bias", False):
            shapes.update({"bq": (E,), "bk": (E,), "bv": (E,),
                           "bo": (self.nOut,)})
        return shapes

    def initialize(self, key):
        if not self.project:
            return {}, {}
        E = self.n_heads * self.head_size
        ks = jax.random.split(key, 4)
        params = {"Wq": _initialize((self.nIn, E), self.weight_init, ks[0]),
                  "Wk": _initialize((self.nIn, E), self.weight_init, ks[1]),
                  "Wv": _initialize((self.nIn, E), self.weight_init, ks[2]),
                  "Wo": _initialize((E, self.nOut), self.weight_init, ks[3])}
        if getattr(self, "use_bias", False):
            params.update({"bq": jnp.zeros((E,)), "bk": jnp.zeros((E,)),
                           "bv": jnp.zeros((E,)),
                           "bo": jnp.zeros((self.nOut,))})
        return params, {}

    def _project_attend(self, params, q_btc, kv_btc, m):
        """Projected multi-head attention with nIn != nHeads*headSize
        allowed (the mha registry op assumes square E x E projections)."""
        B, Tq = q_btc.shape[0], q_btc.shape[1]
        H, hs = self.n_heads, self.head_size

        def proj(x, w, b):
            y = x @ w
            if b is not None:
                y = y + b
            return y.reshape(x.shape[0], x.shape[1], H, hs)
        qh = proj(q_btc, params["Wq"], params.get("bq"))
        kh = proj(kv_btc, params["Wk"], params.get("bk"))
        vh = proj(kv_btc, params["Wv"], params.get("bv"))
        if m is None and Tq >= 1024:
            # long unmasked sequences: the fused flash path (Pallas kernel
            # when installed, scan formulation otherwise) avoids the
            # [T, T] score matrix
            ctx = attention_ops.flash_attention(qh, kh, vh)
        else:
            ctx = attention_ops.dot_product_attention(qh, kh, vh, mask=m)
        out = ctx.reshape(B, Tq, H * hs) @ params["Wo"]
        if params.get("bo") is not None:
            out = out + params["bo"]
        return out

    def _attend(self, params, x, mask):
        x_btc = jnp.transpose(x, (0, 2, 1))            # [N, T, C]
        m = None
        if mask is not None:
            # block attention TO padded keys; padded queries zeroed after
            m = mask[:, None, None, :]                 # [N, 1, 1, Tk]
        if self.project:
            y = self._project_attend(params, x_btc, x_btc, m)
        else:
            q = x_btc[:, :, None, :]
            y = attention_ops.dot_product_attention(q, q, q, mask=m)[:, :, 0]
        if mask is not None:
            y = y * mask[:, :, None]
        return jnp.transpose(y, (0, 2, 1))             # [N, nOut, T]

    def apply(self, params, state, x, train, key, mask=None):
        return self._attend(params, x, mask), state

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))


class LearnedSelfAttentionLayer(SelfAttentionLayer):
    """ref: layers.samediff.LearnedSelfAttentionLayer — attention with
    nQueries LEARNED query vectors instead of per-timestep queries:
    [N, nIn, T] -> [N, nOut, nQueries] (a fixed-size summary of a
    variable-length sequence)."""

    def __init__(self, nOut=None, nQueries: int = 1, **kw):
        super().__init__(nOut=nOut, **kw)
        self.n_queries = nQueries

    def initialize(self, key):
        params, state = super().initialize(key)
        kq = jax.random.fold_in(key, 7)
        params["Q"] = _initialize((self.n_queries, self.nIn),
                                  self.weight_init, kq)
        return params, state

    def apply(self, params, state, x, train, key, mask=None):
        x_btc = jnp.transpose(x, (0, 2, 1))            # [N, T, C]
        q_bqc = jnp.broadcast_to(params["Q"][None],
                                 (x.shape[0],) + params["Q"].shape)
        m = mask[:, None, None, :] if mask is not None else None
        if self.project:
            y = self._project_attend(params, q_bqc, x_btc, m)
        else:
            q = q_bqc[:, :, None, :]
            kv = x_btc[:, :, None, :]
            y = attention_ops.dot_product_attention(q, kv, kv, mask=m)[:, :, 0]
        return jnp.transpose(y, (0, 2, 1)), state      # [N, nOut, nQueries]

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, self.n_queries)


class RecurrentAttentionLayer(Layer):
    """ref: layers.samediff.RecurrentAttentionLayer — recurrent cell whose
    per-step input is augmented with attention over the WHOLE sequence,
    queried by the previous hidden state:

        a_t = attention(q = y_{t-1}, keys = values = x)        # [N, nIn]
        y_t = activation(W x_t + R a_t + b)                    # [N, nOut]

    Input [N, nIn, T] -> [N, nOut, T]. Sequential by construction (scan
    over T) — the reference documents the same O(T) dependency."""

    input_kind = "rnn"

    def __init__(self, nOut=None, nHeads: int = 1, **kw):
        super().__init__(nOut=nOut, **kw)
        self.n_heads = nHeads
        if self.activation is None:
            self.activation = "tanh"

    def set_defaults(self, base):
        super().set_defaults(base)
        if self.activation == "identity":
            self.activation = "tanh"

    def initialize(self, key):
        ks = jax.random.split(key, 4)
        return {"W": _initialize((self.nIn, self.nOut), self.weight_init, ks[0]),
                "R": _initialize((self.nIn, self.nOut), self.weight_init, ks[1]),
                "Wq": _initialize((self.nOut, self.nIn), self.weight_init, ks[2]),
                "b": jnp.zeros((self.nOut,))}, {}

    def apply(self, params, state, x, train, key, mask=None):
        x_tnc = jnp.transpose(x, (2, 0, 1))            # [T, N, C]
        act_fn = act.get(self.activation)
        keys_btc = jnp.transpose(x, (0, 2, 1))         # [N, T, C]
        key_mask = mask                                 # [N, T] or None
        H = self.n_heads
        if self.nIn % H:
            raise ValueError(f"RecurrentAttentionLayer: nIn={self.nIn} not "
                             f"divisible by nHeads={H}")
        hd = self.nIn // H
        keys_h = keys_btc.reshape(keys_btc.shape[0], keys_btc.shape[1], H, hd)

        def step(y_prev, x_t):
            q = (y_prev @ params["Wq"]).reshape(-1, H, hd)   # [N, H, hd]
            scores = jnp.einsum("nhd,nthd->nht", q, keys_h) \
                / np.sqrt(hd).astype(np.float32)
            if key_mask is not None:
                scores = jnp.where(key_mask[:, None, :] > 0, scores, -1e30)
            w = jax.nn.softmax(scores, axis=-1)
            a_t = jnp.einsum("nht,nthd->nhd", w, keys_h).reshape(
                -1, self.nIn)
            y_t = act_fn(x_t @ params["W"] + a_t @ params["R"] + params["b"])
            return y_t, y_t

        y0 = jnp.zeros((x.shape[0], self.nOut), x.dtype)
        _, ys = jax.lax.scan(step, y0, x_tnc)          # [T, N, H]
        out = jnp.transpose(ys, (1, 2, 0))
        if mask is not None:
            out = out * mask[:, None, :]
        return out, state

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))


class SameDiffLayer(Layer):
    """ref: nn.conf.layers.samediff.SameDiffLayer — the extensibility
    escape hatch: define a layer as a GRAPH FRAGMENT instead of a new
    Layer subclass with hand-written forward/backward.

    Subclass and override:
    - ``defineParameters() -> {name: shape}``
    - ``defineLayer(sd, layerInput, paramTable, mask) -> SDVariable``

    The fragment is recorded ONCE into a private SameDiff instance and
    its traced function is inlined into the enclosing network's compiled
    step — gradients flow through it via jax.grad like any other layer
    (the reference gets this for free from SameDiff autodiff; here both
    the layer fragment and the host network are the same jax program).
    """

    def defineParameters(self) -> Dict[str, Tuple[int, ...]]:
        raise NotImplementedError

    def defineLayer(self, sd, layerInput, paramTable, mask=None):
        raise NotImplementedError

    def infer_nin(self, it: InputType):
        super().infer_nin(it)
        if self.nOut is None:
            self.nOut = self.nIn

    def initialize(self, key):
        shapes = self.defineParameters()
        keys = jax.random.split(key, max(len(shapes), 1))
        params = {name: _initialize(tuple(shape), self.weight_init, k)
                  for (name, shape), k in zip(shapes.items(), keys)}
        self._fragment = None
        return params, {}

    def _build_fragment(self, params, x_shape):
        from deeplearning4j_tpu.autodiff.samediff import SameDiff
        sd = SameDiff.create()
        xv = sd.placeHolder("layer_input", shape=x_shape)
        pvs = {k: sd.placeHolder(k, shape=tuple(v.shape))
               for k, v in params.items()}
        out = self.defineLayer(sd, xv, pvs, None)
        return sd._build_fn((out.name,)), out.name

    def apply(self, params, state, x, train, key):
        if getattr(self, "_fragment", None) is None:
            self._fragment = self._build_fragment(params, tuple(x.shape))
        fn, out_name = self._fragment
        feeds = {"layer_input": x, **params}
        res = fn({}, {}, feeds, key, train)
        return res[out_name], state


class Convolution3D(Layer):
    """ref: layers.convolution.Convolution3D — NCDHW, W [nOut, nIn, kD, kH, kW]."""

    input_kind = "cnn3d"

    def __init__(self, kernelSize=(3, 3, 3), stride=(1, 1, 1),
                 padding=(0, 0, 0), nOut=None,
                 convolutionMode: str = "truncate", hasBias: bool = True,
                 **kw):
        super().__init__(nOut=nOut, **kw)
        self.kernel = tuple(kernelSize) if isinstance(kernelSize, (tuple, list)) \
            else (kernelSize,) * 3
        self.stride = tuple(stride) if isinstance(stride, (tuple, list)) \
            else (stride,) * 3
        self.padding = tuple(padding) if isinstance(padding, (tuple, list)) \
            else (padding,) * 3
        self.mode = convolutionMode
        self.has_bias = hasBias

    def infer_nin(self, it: InputType):
        if self.nIn is None:
            self.nIn = it.channels

    def initialize(self, key):
        shape = (self.nOut, self.nIn) + self.kernel
        params = {"W": _initialize(shape, self.weight_init, key)}
        if self.has_bias:
            params["b"] = jnp.full((self.nOut,), self.bias_init, jnp.float32)
        return params, {}

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        shapes = {"W": (self.nOut, self.nIn) + tuple(self.kernel)}
        if self.has_bias:
            shapes["b"] = (self.nOut,)
        return shapes

    def apply(self, params, state, x, train, key):
        x = self._maybe_dropout(x, train, key)
        out = conv_ops.conv3d(x, params["W"],
                              params.get("b") if self.has_bias else None,
                              stride=self.stride, pad=self.padding,
                              mode=self.mode)
        return act.get(self.activation)(out), state

    def output_type(self, it: InputType) -> InputType:
        dims = [conv_ops.conv_output_size(s, self.kernel[i], self.stride[i],
                                          self.padding[i], 1, self.mode)
                for i, s in enumerate((it.depth, it.height, it.width))]
        return InputType.convolutional3D(dims[0], dims[1], dims[2], self.nOut)


class Subsampling3DLayer(Layer):
    """ref: layers.convolution.Subsampling3DLayer — NCDHW pooling."""

    input_kind = "cnn3d"
    has_params = False

    def __init__(self, poolingType: str = "max", kernelSize=(2, 2, 2),
                 stride=None, padding=(0, 0, 0), **kw):
        super().__init__(**kw)
        self.pooling = poolingType.lower()
        self.kernel = tuple(kernelSize) if isinstance(kernelSize, (tuple, list)) \
            else (kernelSize,) * 3
        self.stride = tuple(stride) if stride is not None else self.kernel
        self.padding = tuple(padding) if isinstance(padding, (tuple, list)) \
            else (padding,) * 3

    def infer_nin(self, it: InputType):
        self.nIn = self.nOut = it.channels

    def initialize(self, key):
        return {}, {}

    def apply(self, params, state, x, train, key):
        fn = conv_ops.maxpool3d if self.pooling == "max" else conv_ops.avgpool3d
        return fn(x, kernel=self.kernel, stride=self.stride,
                  pad=self.padding), state

    def output_type(self, it: InputType) -> InputType:
        dims = [conv_ops.conv_output_size(s, self.kernel[i], self.stride[i],
                                          self.padding[i], 1, "truncate")
                for i, s in enumerate((it.depth, it.height, it.width))]
        return InputType.convolutional3D(dims[0], dims[1], dims[2], it.channels)


def _triple_pads(spec):
    """int | (a, b, c) | ((lo, hi), ...) -> three (lo, hi) pairs."""
    if isinstance(spec, (int, np.integer)):
        spec = (spec,) * 3
    return tuple((int(p), int(p)) if isinstance(p, (int, np.integer))
                 else (int(p[0]), int(p[1])) for p in spec)


class ZeroPadding3DLayer(Layer):
    """ref: layers.convolution.ZeroPadding3DLayer — NCDHW."""

    input_kind = "cnn3d"
    has_params = False

    def __init__(self, padding=(1, 1, 1), **kw):
        super().__init__(**kw)
        self.pad = _triple_pads(padding)

    def infer_nin(self, it):
        self.nIn = self.nOut = it.channels

    def apply(self, params, state, x, train, key):
        return jnp.pad(x, [(0, 0), (0, 0)] + list(self.pad)), state

    def output_type(self, it):
        d, h, w = ((s + sum(p)) for s, p in
                   zip((it.depth, it.height, it.width), self.pad))
        return InputType.convolutional3D(d, h, w, it.channels)


class Cropping3D(Layer):
    """ref: layers.convolution.Cropping3D — NCDHW."""

    input_kind = "cnn3d"
    has_params = False

    def __init__(self, crop=(1, 1, 1), **kw):
        super().__init__(**kw)
        self.crop = _triple_pads(crop)

    def infer_nin(self, it):
        self.nIn = self.nOut = it.channels

    def apply(self, params, state, x, train, key):
        (d0, d1), (h0, h1), (w0, w1) = self.crop
        D, H, W = x.shape[2:]
        return x[:, :, d0:D - d1, h0:H - h1, w0:W - w1], state

    def output_type(self, it):
        d, h, w = ((s - sum(c)) for s, c in
                   zip((it.depth, it.height, it.width), self.crop))
        return InputType.convolutional3D(d, h, w, it.channels)


class Upsampling3D(Layer):
    """ref: layers.convolution.Upsampling3D — nearest repeat, NCDHW."""

    input_kind = "cnn3d"
    has_params = False

    def __init__(self, size=2, **kw):
        super().__init__(**kw)
        self.scale = tuple(size) if isinstance(size, (tuple, list)) \
            else (int(size),) * 3

    def infer_nin(self, it):
        self.nIn = self.nOut = it.channels

    def apply(self, params, state, x, train, key):
        for ax, s in zip((2, 3, 4), self.scale):
            if s != 1:
                x = jnp.repeat(x, s, axis=ax)
        return x, state

    def output_type(self, it):
        return InputType.convolutional3D(it.depth * self.scale[0],
                                         it.height * self.scale[1],
                                         it.width * self.scale[2],
                                         it.channels)


class Upsampling1D(Layer):
    """ref: layers.convolution.Upsampling1D — [N, C, T] repeat along T."""

    input_kind = "rnn"
    has_params = False

    def __init__(self, size: int = 2, **kw):
        super().__init__(**kw)
        self.size = int(size)

    def infer_nin(self, it):
        self.nIn = self.nOut = it.size

    def initialize(self, key):
        return {}, {}

    def apply(self, params, state, x, train, key):
        return jnp.repeat(x, self.size, axis=2), state

    def output_type(self, it: InputType) -> InputType:
        t = it.dims.get("timesteps", -1)
        return InputType.recurrent(it.size, t * self.size if t > 0 else -1)


class ZeroPadding1DLayer(Layer):
    """ref: layers.convolution.ZeroPadding1DLayer — pad along T."""

    input_kind = "rnn"
    has_params = False

    def __init__(self, padding=1, **kw):
        super().__init__(**kw)
        self.pad = tuple(padding) if isinstance(padding, (tuple, list)) \
            else (int(padding), int(padding))

    def infer_nin(self, it):
        self.nIn = self.nOut = it.size

    def initialize(self, key):
        return {}, {}

    def apply(self, params, state, x, train, key):
        return jnp.pad(x, [(0, 0), (0, 0), tuple(self.pad)]), state

    def output_type(self, it: InputType) -> InputType:
        t = it.dims.get("timesteps", -1)
        return InputType.recurrent(it.size,
                                   t + sum(self.pad) if t > 0 else -1)


class Cropping1D(Layer):
    """ref: layers.convolution.Cropping1D."""

    input_kind = "rnn"
    has_params = False

    def __init__(self, cropping=1, **kw):
        super().__init__(**kw)
        self.crop = tuple(cropping) if isinstance(cropping, (tuple, list)) \
            else (int(cropping), int(cropping))

    def infer_nin(self, it):
        self.nIn = self.nOut = it.size

    def initialize(self, key):
        return {}, {}

    def apply(self, params, state, x, train, key):
        t = x.shape[2]
        return x[:, :, self.crop[0]:t - self.crop[1]], state

    def output_type(self, it: InputType) -> InputType:
        t = it.dims.get("timesteps", -1)
        return InputType.recurrent(it.size,
                                   t - sum(self.crop) if t > 0 else -1)


class MaskZeroLayer(Layer):
    """ref: layers.recurrent.MaskZeroLayer / Keras Masking — zero out
    timesteps whose EVERY feature equals ``maskValue`` (the mask itself
    flows separately; this matches Keras Masking's forward zeroing)."""

    input_kind = "rnn"
    has_params = False

    def __init__(self, maskValue: float = 0.0, **kw):
        super().__init__(**kw)
        self.mask_value = float(maskValue)

    def infer_nin(self, it):
        self.nIn = self.nOut = it.size

    def initialize(self, key):
        return {}, {}

    def apply(self, params, state, x, train, key):
        keep = jnp.any(x != self.mask_value, axis=1, keepdims=True)
        return jnp.where(keep, x, 0.0), state

    def output_type(self, it: InputType) -> InputType:
        return it


class GaussianNoiseLayer(Layer):
    """ref/Keras: GaussianNoise — additive N(0, stddev) noise, train only."""

    input_kind = None
    has_params = False

    def __init__(self, stddev: float = 0.1, **kw):
        super().__init__(**kw)
        self.stddev = float(stddev)

    def infer_nin(self, it):
        self.nIn = self.nOut = it.arrayElementsPerExample()

    def initialize(self, key):
        return {}, {}

    def apply(self, params, state, x, train, key):
        if not train:
            return x, state
        return x + self.stddev * jax.random.normal(key, x.shape, x.dtype), state

    def output_type(self, it):
        return it


class GaussianDropoutLayer(GaussianNoiseLayer):
    """ref/Keras: GaussianDropout — multiplicative N(1, rate/(1-rate))."""

    def __init__(self, rate: float = 0.1, **kw):
        super(GaussianNoiseLayer, self).__init__(**kw)
        self.rate = float(rate)

    def apply(self, params, state, x, train, key):
        if not train or self.rate <= 0:
            return x, state
        stddev = float(np.sqrt(self.rate / (1.0 - self.rate)))
        noise = 1.0 + stddev * jax.random.normal(key, x.shape, x.dtype)
        return x * noise, state


class AlphaDropoutLayer(GaussianNoiseLayer):
    """ref/Keras: AlphaDropout — SELU self-normalizing dropout."""

    def __init__(self, rate: float = 0.1, **kw):
        super(GaussianNoiseLayer, self).__init__(**kw)
        self.rate = float(rate)

    def apply(self, params, state, x, train, key):
        if not train or self.rate <= 0:
            return x, state
        from deeplearning4j_tpu.ops import registry as _R
        return _R.get("alpha_dropout")(key, x, self.rate), state


class TimeDistributed(Layer):
    """ref/Keras: TimeDistributed(Dense) — the wrapped dense applied at
    every timestep of [N, C, T] (DL4J expresses this as DenseLayer with
    RnnToFF/FFToRnn preprocessors; here it is one einsum)."""

    input_kind = "rnn"

    def __init__(self, inner: "DenseLayer" = None, nOut=None, **kw):
        if inner is not None and not isinstance(inner, DenseLayer):
            raise ValueError("TimeDistributed supports a Dense inner layer")
        super().__init__(nOut=nOut if nOut is not None
                         else (inner.nOut if inner else None), **kw)
        if inner is not None and self.activation is None:
            self.activation = inner.activation
        self.has_bias = inner.has_bias if inner is not None else True

    def initialize(self, key):
        params = {"W": _initialize((self.nIn, self.nOut), self.weight_init,
                                   key)}
        if self.has_bias:
            params["b"] = jnp.full((self.nOut,), self.bias_init, jnp.float32)
        return params, {}

    def apply(self, params, state, x, train, key):
        z = jnp.einsum("nct,ch->nht", x, params["W"])
        if self.has_bias:
            z = z + params["b"][None, :, None]
        a = act.get(self.activation)(z, axis=1) \
            if self.activation in ("softmax", "logsoftmax") \
            else act.get(self.activation)(z)
        return a, state

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))


# ------------------------------------------- feature-last sequence layers
# The blocks of a decoder-only language model. Inside a ComputationGraph
# they hold a sequence feature-last, [N, T, C] (``layout_step`` turns the
# reference's [N, C, T] once at each boundary, as it does for NHWC), so
# every matmul contracts the minor axis and no layer transposes.

def _feature_last(layer, x):
    """The layers below are handed [N, T, C] by the graph's forwards; a
    caller that hands them the public [N, C, T] gets told, not garbage."""
    if x.shape[-1] != layer.nIn:
        raise ValueError(
            f"{type(layer).__name__} '{layer.name}' computes feature-last "
            f"([N, T, {layer.nIn}]) and got {tuple(x.shape)}: it runs "
            f"inside a ComputationGraph, whose forward keeps sequences "
            f"in that layout")
    return x


def _sequence_size(it: InputType) -> int:
    return it.size if it.kind == "rnn" else it.arrayElementsPerExample()


class RMSNorm(Layer):
    """Root-mean-square norm over the feature axis with a learned gain,
    ``x / sqrt(mean(x^2) + eps) * gain`` (Zhang & Sennrich 2019); no mean,
    no bias. Statistics in float32 whatever the compute dtype, the gain a
    float32 parameter under a precision policy (as BatchNorm's)."""

    input_kind = None
    fp32_params = True

    def __init__(self, eps: float = 1e-6, **kw):
        super().__init__(**kw)
        self.eps = float(eps)

    def infer_nin(self, it: InputType):
        if it.kind not in ("rnn", "ff"):
            raise ValueError(f"RMSNorm supports [N, D] and sequence "
                             f"inputs, not {it}")
        self.nIn = self.nOut = _sequence_size(it)

    def mxu_lane_dims(self):
        return []

    def param_shapes(self):
        return {"gain": (self.nIn,)} if self.nIn else {}

    def initialize(self, key):
        return {"gain": jnp.ones((self.nIn,), jnp.float32)}, {}

    def apply(self, params, state, x, train, key):
        x = _feature_last(self, x)
        out = norm_ops.rms_norm(x.astype(jnp.float32),
                                params["gain"].astype(jnp.float32),
                                eps=self.eps)
        return out.astype(x.dtype), state

    def output_type(self, it: InputType) -> InputType:
        return it


class CausalSelfAttentionLayer(Layer):
    """Causal multi-head self-attention with rotary positions, as
    decoder-only language models run it: ``q, k, v = x Wq, x Wk, x Wv``
    (``nHeads`` heads of ``headSize``, chosen apart from nIn; no bias),
    rotary embedding on q and k over the whole head
    (``ops.attention.rotary_embedding``, base ``ropeTheta``),
    ``softmax(q k^T / sqrt(headSize) + causal mask) v``, then ``Wo``. The
    attention core (``ops.attention.causal_attention``) has a backward of
    its own that keeps the output and the row log-sum-exp: no [T, T]
    tensor is kept for it, in a rematerialised stretch or outside one.

    ``nKVHeads`` < ``nHeads`` is grouped-query attention: ``Wk``, ``Wv``
    project onto ``nKVHeads`` heads and query head ``h`` reads key/value
    head ``h // (nHeads / nKVHeads)``; the core takes them as they are.
    ``qkNorm``: each head of q and of k goes through an RMS norm over its
    ``headSize`` (gains ``qn``, ``kn`` [headSize], shared by the heads,
    float32 under a precision policy) before the rotary embedding. The
    defaults are plain multi-head attention with neither."""

    input_kind = "rnn"
    # a configuration saved before these existed has none of them
    n_kv_heads = None
    qk_norm = False
    qk_norm_eps = 1e-6

    def __init__(self, nOut=None, nHeads: int = 1, headSize: int = None,
                 ropeTheta: float = 10000.0, nKVHeads: int = None,
                 qkNorm: bool = False, qkNormEps: float = 1e-6, **kw):
        super().__init__(nOut=nOut, **kw)
        self.n_heads = int(nHeads)
        self.head_size = headSize
        self.rope_theta = float(ropeTheta)
        if nKVHeads is not None:
            self.n_kv_heads = int(nKVHeads)
            if self.n_kv_heads < 1 or self.n_heads % self.n_kv_heads:
                raise ValueError(
                    f"CausalSelfAttentionLayer: nHeads={self.n_heads} query "
                    f"heads do not divide over nKVHeads={nKVHeads}")
        if qkNorm:
            self.qk_norm, self.qk_norm_eps = True, float(qkNormEps)

    @property
    def fp32_leaves(self):
        return ("qn", "kn") if self.qk_norm else ()

    def infer_nin(self, it: InputType):
        super().infer_nin(it)
        if self.nOut is None:
            self.nOut = self.nIn
        if self.head_size is None:
            if self.nIn % self.n_heads:
                raise ValueError(
                    f"CausalSelfAttentionLayer: nIn={self.nIn} does not "
                    f"divide into nHeads={self.n_heads}; give headSize")
            self.head_size = self.nIn // self.n_heads
        if self.head_size % 2:
            raise ValueError(f"CausalSelfAttentionLayer: rotary positions "
                             f"need an even headSize, got {self.head_size}")

    def param_shapes(self):
        if not self.nIn or not self.nOut or not self.head_size:
            return {}
        E = self.n_heads * self.head_size
        Ek = (self.n_kv_heads or self.n_heads) * self.head_size
        shapes = {"Wq": (self.nIn, E), "Wk": (self.nIn, Ek),
                  "Wv": (self.nIn, Ek), "Wo": (E, self.nOut)}
        if self.qk_norm:
            shapes.update(qn=(self.head_size,), kn=(self.head_size,))
        return shapes

    def initialize(self, key):
        ks = jax.random.split(key, 4)
        shapes = self.param_shapes()
        out = {name: _initialize(shapes[name], self.weight_init, k)
               for name, k in zip(("Wq", "Wk", "Wv", "Wo"), ks)}
        if self.qk_norm:
            out.update(qn=jnp.ones((self.head_size,), jnp.float32),
                       kn=jnp.ones((self.head_size,), jnp.float32))
        return out, {}

    def apply(self, params, state, x, train, key):
        x = self._maybe_dropout(_feature_last(self, x), train, key)
        N, T = x.shape[0], x.shape[1]
        H, hs = self.n_heads, self.head_size
        Hk = self.n_kv_heads or H
        q = (x @ params["Wq"]).reshape(N, T, H, hs)
        k = (x @ params["Wk"]).reshape(N, T, Hk, hs)
        v = (x @ params["Wv"]).reshape(N, T, Hk, hs)
        if self.qk_norm:
            q = _rms(q, params["qn"], self.qk_norm_eps)
            k = _rms(k, params["kn"], self.qk_norm_eps)
        q = attention_ops.rotary_embedding(q, self.rope_theta)
        k = attention_ops.rotary_embedding(k, self.rope_theta)
        with jax.named_scope(_stepprogram.ATTN_CORE_SCOPE):
            o = attention_ops.causal_attention(q, k, v)
        return o.reshape(N, T, H * hs) @ params["Wo"], state

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))


class GatedMLP(Layer):
    """Gated feed-forward block (SwiGLU with the default ``swish``):
    ``(act(x Wg) * (x Wu)) Wd`` with ``nHidden`` inner units, no bias."""

    input_kind = None

    def __init__(self, nOut=None, nHidden: int = None,
                 activation: str = "swish", **kw):
        super().__init__(nOut=nOut, activation=activation, **kw)
        if not nHidden:
            raise ValueError("GatedMLP needs nHidden, its inner width")
        self.n_hidden = int(nHidden)

    def infer_nin(self, it: InputType):
        self.nIn = _sequence_size(it)
        if self.nOut is None:
            self.nOut = self.nIn

    def mxu_lane_dims(self):
        return [self.n_hidden, self.nOut]

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        return {"Wg": (self.nIn, self.n_hidden),
                "Wu": (self.nIn, self.n_hidden),
                "Wd": (self.n_hidden, self.nOut)}

    def initialize(self, key):
        ks = jax.random.split(key, 3)
        return {name: _initialize(shape, self.weight_init, k)
                for (name, shape), k in zip(self.param_shapes().items(),
                                            ks)}, {}

    def apply(self, params, state, x, train, key):
        x = self._maybe_dropout(_feature_last(self, x), train, key)
        gate = act.get(self.activation)(x @ params["Wg"])
        return (gate * (x @ params["Wu"])) @ params["Wd"], state

    def output_type(self, it: InputType) -> InputType:
        if it.kind == "rnn":
            return InputType.recurrent(self.nOut,
                                       it.dims.get("timesteps", -1))
        return InputType.feedForward(self.nOut)


_SHORTCONV_LOWERED = _prof.get_registry().counter(
    "dl4j_shortconv_lowered_total",
    "Traces of nn.layers.GatedShortConvLayer (one a lowering of each call "
    "site, not one a step) by what runs the causal depthwise convolution",
    labelnames=("path",))


class GatedShortConvLayer(Layer):
    """A gated short convolution over a feature-last sequence, the token
    mixer of the LFM2 family's ``conv`` layers: ``[B | G | z] = x Win``
    (three ``nOut``-wide parts, in that order), ``p = B * z``, a causal
    depthwise convolution ``c_t = sum_j Wc[j] * p_{t - (K-1) + j}`` over
    the ``K = kernelSize`` last positions of the SAME sequence (``p`` is
    nought before its first token; one K-vector a channel), and ``y = (G *
    c) Wout``. The gates and the convolution run in the stream's dtype,
    the ``K`` taps summed in float32; the taps are ``K`` shifted
    multiply-adds that ride in one fusion (no [N, T, K, C] window tensor,
    no convolution op: at K = 3 a depthwise convolution is three reads of
    a tensor the gates read anyway). Everything runs under
    ``dl4j_shortconv``."""

    input_kind = "rnn"

    def __init__(self, nOut=None, kernelSize: int = 3, **kw):
        super().__init__(nOut=nOut, **kw)
        self.kernel_size = int(kernelSize)
        if self.kernel_size < 1:
            raise ValueError(f"GatedShortConvLayer: kernelSize must be at "
                             f"least 1, got {kernelSize}")

    def infer_nin(self, it: InputType):
        super().infer_nin(it)
        if self.nOut is None:
            self.nOut = self.nIn

    def mxu_lane_dims(self):
        return [3 * self.nOut, self.nOut]

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        return {"Win": (self.nIn, 3 * self.nOut),
                "Wc": (self.kernel_size, self.nOut),
                "Wout": (self.nOut, self.nOut)}

    def initialize(self, key):
        shapes = self.param_shapes()
        return {name: _initialize(shapes[name], self.weight_init, k)
                for name, k in zip(("Win", "Wc", "Wout"),
                                   jax.random.split(key, 3))}, {}

    def apply(self, params, state, x, train, key):
        x = self._maybe_dropout(_feature_last(self, x), train, key)
        T, C, K = x.shape[1], self.nOut, self.kernel_size
        with jax.named_scope(_stepprogram.SHORTCONV_SCOPE):
            _SHORTCONV_LOWERED.labels("shifted_taps").inc()
            bgz = x @ params["Win"]
            p = bgz[..., :C] * bgz[..., 2 * C:]
            # p_{t - d} for d = K-1 .. 0: the sequence moved right, noughts
            # before its first token; a batch's rows never meet
            late = jnp.pad(p, ((0, 0), (K - 1, 0), (0, 0)))
            wc = params["Wc"].astype(jnp.float32)
            c = sum(wc[j] * jax.lax.slice_in_dim(late, j, j + T, axis=1)
                    .astype(jnp.float32) for j in range(K))
            y = (bgz[..., C:2 * C] * c.astype(x.dtype)) @ params["Wout"]
        return y, state

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))


@jax.custom_vjp
def _logits(h, w):
    """``h @ w`` with float32 logits from operands in ``h``'s dtype (the
    master ``w`` is cast here). The backward pass rounds the logits'
    cotangent to that dtype for its two products, as every other layer's
    does; ``w``'s gradient is accumulated and handed back in float32."""
    return jnp.dot(h, w.astype(h.dtype), preferred_element_type=jnp.float32)


def _logits_fwd(h, w):
    return _logits(h, w), (h, w)


def _logits_bwd(res, g):
    h, w = res
    g = g.astype(h.dtype)
    dh = jnp.dot(g, w.astype(h.dtype).T)
    dw = jnp.einsum("...d,...v->dv", h, g,
                    preferred_element_type=jnp.float32)
    return dh, dw.astype(w.dtype)


_logits.defvjp(_logits_fwd, _logits_bwd)


#: bytes of the float32 logits of one tile, rows x columns, that
#: :func:`blocked_cross_entropy` has in flight at once: positions and
#: vocabulary are taken a tile and a pass after the other, and the compiler
#: then keeps a tile's logits, probabilities and their rounded cotangent on
#: chip instead of in HBM (the finding of ``ops.attention.
#: CAUSAL_SCORE_BYTES``, on the one tensor of a language model's step that
#: is larger than any score block).
#: Measured on a v5e, forward + backward of four passes [1, 4096, 2048]
#: bf16 under a float32 head [2048, 49152], ms a call (PR 32; the
#: checkpointed autodiff path replaced: 112.9, a pass alone 28.2):
#:
#:     bytes a block        8 MiB   16 MiB   32 MiB   64 MiB
#:     columns a block        512    1,024    2,048    4,096
#:     one call, 4 passes              77.4     78.5     79.2
#:     a pass alone          21.0     20.4     20.2     20.9
#:
#: flat, since at every width the 16 products run at 82-97% of the MXU's
#: peak; 32 MiB makes half as many blocks to compile as 16.
#: The same, one pass bf16[4, 8192, 2048] under a float32 table
#: [8192, 2048] (PR 38, ``benchmarks/probe_head_tiles.py``, one chip call;
#: the products alone take 22.4 ms at the peak):
#:
#:     rows x columns a tile   32,768 x 256    8,192 x 1,024   4,096 x 2,048
#:     bytes a tile                  32 MiB           32 MiB          32 MiB
#:     ms a call                      46.70            24.36           24.28
#:     temporaries, MiB               265.9            181.1            93.1
#:
#:     rows x columns a tile  2,048 x 4,096    4,096 x 1,024
#:     bytes a tile                  32 MiB           16 MiB
#:     ms a call                      24.53            24.09
#:     temporaries, MiB                21.5             89.4
#:
#: all rows in a block (the first, what every shape took before PR 38)
#: carries a float32 ``dh`` of all rows through HBM once a block; once the
#: rows go in blocks the shape of a tile no longer matters to a quarter of
#: a millisecond, and :func:`_head_tile` takes the squarest.
HEAD_LOGIT_BYTES = 32 << 20

_HEAD_LOWERED = _prof.get_registry().counter(
    "dl4j_head_loss_lowered_total",
    "Traces of nn.layers.blocked_cross_entropy (one a lowering of each "
    "call site, not one a step) by the path its shapes took: tiled (row "
    "blocks, each over its vocabulary blocks), blocked (all rows, "
    "vocabulary blocks), or single (all rows, one block because nOut is "
    "no multiple of the block)",
    labelnames=("path",))


def _head_tile(rows, n_out):
    """``(rows, columns)`` of a tile for ``rows`` positions under ``n_out``
    outputs: one path, whose tile counts follow from what it is handed.
    A tile's ``r x c`` float32 logits are the budget, :data:`HEAD_LOGIT_
    BYTES`. Every column block adds into a row block's float32 ``dh`` and
    every row block into a column block's float32 ``dw``, so ``dh``'s
    traffic grows with ``n_out / c``, ``dw``'s with ``rows / r`` and their
    sum is least near ``r = c``: a tile takes no more rows than the power
    of two nearest the square's side (4,096 at 32 MiB, whose side is
    2,896), all of them where there are no more, and the power of two of
    columns that then fits (an ``n_out`` that is no multiple of it: all
    columns)."""
    budget = HEAD_LOGIT_BYTES // 4
    r = min(rows, 1 << (budget.bit_length() // 2))
    fit = max(budget // r, 1)
    c = 1 << (fit.bit_length() - 1)
    return r, (c if c < n_out and n_out % c == 0 else n_out)


#: the head's three products by the axis of ``w`` the vocabulary lies on:
#: 1 for a head [nIn, nOut], 0 for an embedding's table [nOut, nIn] that a
#: tied head reads as it lies (no transposed copy of it, or of its gradient)
_CE_PRODUCTS = {1: ("ntd,dv->ntv", "ntd,ntv->dv", "ntv,dv->ntd"),
                0: ("ntd,vd->ntv", "ntd,ntv->vd", "ntv,vd->ntd")}


def _block_logits(h, w, labels, v0, blk, axis=1):
    """``(w block, logits, label mask)`` of the columns ``v0..``: float32
    ``h @ w[:, v0:v0 + blk]`` from operands in ``h``'s dtype, as
    :func:`_logits` gives them (the slice and the cast of the master ``w``
    ride in the product's fusion), and where a row's label is."""
    wb = jax.lax.dynamic_slice_in_dim(w, v0, blk, axis=axis).astype(h.dtype)
    z = jnp.einsum(_CE_PRODUCTS[axis][0], h, wb,
                   preferred_element_type=jnp.float32)
    return wb, z, (labels - v0)[..., None] == jnp.arange(blk)


# The two functions below are jitted for what a jit shares, not for a
# dispatch (as ``ops.attention._fwd_heads`` is): a step calls each once a
# block and a pass (96 times at 4 passes of 24 blocks) with the block's
# first column as a value, JAX traces and lowers each once, and XLA
# inlines the calls under their callers' scopes and folds the column in
# (warm ``setup_s`` 37.0 s against the parent's 34.1 with every block
# traced anew, PR 32). Neither loops on the device: a ``while`` in a step
# is listed beside its body's ops and a reader of op time counts it twice.
@functools.partial(jax.jit, static_argnums=(4, 5))
def _ce_fwd_block(h, w, labels, v0, blk, axis=1):
    """A block's float32 row log-sum-exp and the label's logit where the
    block holds it (0 elsewhere), [N, T] each."""
    _, z, hit = _block_logits(h, w, labels, v0, blk, axis)
    return (jax.nn.logsumexp(z, axis=-1),
            jnp.sum(jnp.where(hit, z, 0.0), axis=-1))


@functools.partial(jax.jit, static_argnums=(8, 9))
def _ce_bwd_block(h, w, labels, lse, g, dh, dw, v0, blk, axis=1):
    """``dh`` and ``dw`` with a block's share added: the block's logits
    again, ``dz = (exp(z - lse) - onehot) g`` rounded to ``h``'s dtype for
    its two products exactly as :func:`_logits_bwd` rounds it, each
    product's fusion adding into its float32 accumulator (``dw``'s columns
    ``v0..`` in place)."""
    wb, z, hit = _block_logits(h, w, labels, v0, blk, axis)
    dz = ((jnp.exp(z - lse[..., None]) - hit) * g[..., None]).astype(h.dtype)
    _, to_w, to_h = _CE_PRODUCTS[axis]
    dwb = jax.lax.dynamic_slice_in_dim(dw, v0, blk, axis=axis) + jnp.einsum(
        to_w, h, dz, preferred_element_type=jnp.float32)
    return (dh + jnp.einsum(to_h, dz, wb,
                            preferred_element_type=jnp.float32),
            jax.lax.dynamic_update_slice_in_dim(dw, dwb, v0, axis=axis))


def _row_block(x, r0, rows):
    """Rows ``r0..`` of ``x`` [1, all rows, ..] (the last block may be
    short); ``x`` itself, whatever its leading sizes, where a tile takes
    all there are."""
    return x if x.shape[0] * x.shape[1] <= rows \
        else jax.lax.slice_in_dim(x, r0, min(r0 + rows, x.shape[1]), axis=1)


def _whole(blocks):
    """The row blocks' results end to end."""
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)


def _ce_fwd(hs, w, labels, tile, axis=1):
    """``(ce, lse)`` [P, N, T] float32 of the passes ``hs``, a tile and a
    pass after the other; nothing of [rows, nOut] leaves a tile."""
    (rows, blk), parts = tile, []
    for t, h in enumerate(hs):
        with jax.named_scope(_stepprogram.pass_scope(t + 1)):
            for r0 in range(0, labels.shape[1], rows):
                yb = _row_block(labels, r0, rows)
                for v0 in range(0, w.shape[axis], blk):
                    if parts:
                        # the next tile starts once the last has finished:
                        # one tile's [rows, block] tensors are alive at a
                        # time
                        h, parts[-1] = attention_ops._then(h, parts[-1])
                    parts.append(_ce_fwd_block(_row_block(h, r0, rows), w,
                                               yb, v0, blk, axis))
    ces, lses = [], []
    n, a_pass = w.shape[axis] // blk, len(parts) // len(hs)
    for t in range(len(hs)):
        with jax.named_scope(_stepprogram.pass_scope(t + 1)):
            ce, lse = [], []
            for i in range(t * a_pass, (t + 1) * a_pass, n):
                lse.append(jax.nn.logsumexp(
                    jnp.stack([z for z, _ in parts[i:i + n]]), axis=0))
                ce.append(lse[-1]
                          - sum(picked for _, picked in parts[i:i + n]))
            lses.append(_whole(lse))
            ces.append(_whole(ce))
    return jnp.stack(ces), jnp.stack(lses)


def _ce_bwd(hs, w, labels, lses, gs, tile, axis=1):
    """``(dhs, dw)`` from the cotangents ``gs`` [P, N, T] of the
    cross-entropies: a row block's ``dh`` summed over its column blocks in
    float32 and rounded once, ``dw`` summed over tiles and passes in ONE
    float32 buffer."""
    rows, blk = tile
    dw = jnp.zeros(w.shape, jnp.float32)
    dhs = []
    for t, h in enumerate(hs):
        with jax.named_scope(_stepprogram.pass_scope(t + 1)):
            done = []
            for r0 in range(0, labels.shape[1], rows):
                yb = _row_block(labels, r0, rows)
                dh = jnp.zeros(yb.shape + h.shape[-1:], jnp.float32)
                for v0 in range(0, w.shape[axis], blk):
                    if t or r0 or v0:
                        h, (dh, dw) = attention_ops._then(h, (dh, dw))
                    # (a pass's ``lses`` and ``gs`` are indexed a tile
                    # at a time: indexed once a row block, one row block
                    # no longer lowers to the text tests/test_looped_lm.py
                    # pins by hash, and two cells' compiled steps with it)
                    hb, lse, g = (_row_block(a, r0, rows)
                                  for a in (h, lses[t], gs[t]))
                    dh, dw = _ce_bwd_block(hb, w, yb, lse, g, dh, dw, v0,
                                           blk, axis)
                done.append(dh.astype(h.dtype))
            dhs.append(_whole(done))
    return tuple(dhs), dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _blocked_ce(hs, w, labels, tile, axis):
    return _ce_fwd(hs, w, labels, tile, axis)[0]


def _blocked_ce_fwd(hs, w, labels, tile, axis):
    ce, lse = _ce_fwd(hs, w, labels, tile, axis)
    return ce, (hs, w, labels, lse)


def _blocked_ce_bwd(tile, axis, res, g):
    return _ce_bwd(*res, g, tile, axis) + (None,)


_blocked_ce.defvjp(_blocked_ce_fwd, _blocked_ce_bwd)


def blocked_cross_entropy(hs, w, labels, table: bool = False):
    """Cross-entropy [P, N, T] float32 of the logits ``h @ w`` of every
    pass ``h`` [N, T, nIn] in the tuple ``hs`` against the INTEGER
    ``labels`` [N, T], under the master head ``w`` [nIn, nOut] (``table``:
    ``w`` is an embedding's table [nOut, nIn] and the logits ``h @ w^T``,
    the products taking it as it lies): a forward and a backward written
    by hand (``jax.custom_vjp``) over tiles of rows x columns sized by
    :func:`_head_tile` from ``labels.size`` and ``nOut`` under
    :data:`HEAD_LOGIT_BYTES`, each pass's ops under its ``dl4j_ut<t>``
    scope. Up to 4,096 positions a tile takes them all and is a
    vocabulary block; beyond, the positions of all sequences end to end
    go in row blocks (the last may be short), each over its vocabulary
    blocks; an ``nOut`` that is no multiple of the block runs the same
    pair with all columns in a tile. What the backward keeps is ``hs``,
    ``w``, the labels and the float32 row log-sum-exp [P, N, T], and it
    runs a tile's product again next to the two that consume it, so no
    [rows, nOut] tensor outlives a tile; ``h``'s gradient is summed in
    float32 over the column blocks of ONE row block and rounded once, and
    ``w``'s gradient is one float32 buffer for all tiles and passes.
    Logits, log-sum-exp and loss are float32 from operands in ``h``'s
    dtype; ``w``'s gradient is float32."""
    axis = 0 if table else 1
    rows, blk = tile = _head_tile(labels.size, w.shape[axis])
    _HEAD_LOWERED.labels("tiled" if rows < labels.size else "blocked"
                         if blk < w.shape[axis] else "single").inc()
    if rows == labels.size:
        return _blocked_ce(tuple(hs), w, labels, tile, axis)
    ce = _blocked_ce(tuple(h.reshape(1, -1, h.shape[-1]) for h in hs), w,
                     labels.reshape(1, -1), tile, axis)
    return ce.reshape(len(hs), *labels.shape)


class LoopedLMOutputLayer(BaseOutputLayer):
    """Language-model head over the passes of a :class:`~deeplearning4j_
    tpu.nn.graph.LoopVertex`, with the exit-weighted loss of looped
    language models (Zhu et al. 2025, arXiv:2510.25741, first-stage
    objective). After pass t: logits ``z_t = h_t W`` and an exit gate
    ``lambda_t = sigmoid(h_t . gate_w + gate_b)``; a token's exit
    distribution is ``p_t = lambda_t prod_{j<t}(1 - lambda_j)``, the last
    pass taking what is left; the loss is the mean over positions of
    ``sum_t p_t CE(z_t, y) - beta H(p)``.

    Labels are INTEGER token ids [N, T]. The loss is worked out from the
    layer's input, never from probabilities, by :func:`blocked_cross_
    entropy`: float32 logits a tile at a time (all positions x a
    vocabulary block up to 4,096 positions, row blocks of them beyond),
    of which the backward pass keeps only the row log-sum-exp [P, N, T]
    beside the hidden states, the head and the labels, and runs a tile's
    product again where it needs it. Its state carries the batch
    means of ``p_t`` and ``CE(z_t, y)`` of the last step, for the gauges
    ``dl4j_loop_exit_mass`` / ``dl4j_loop_pass_loss``. ``apply`` (the
    inference forward) gives the last pass's logits. Fed by an ordinary
    layer it is a one-pass head with plain cross-entropy."""

    input_kind = None
    loss_from_input = True

    def __init__(self, nOut=None, beta: float = 0.1, **kw):
        super().__init__(lossFunction="sparse_mcxent", nOut=nOut, **kw)
        self.beta = float(beta)
        self.n_passes = 1

    def infer_nin(self, it: InputType):
        self.nIn = _sequence_size(it)
        self.n_passes = int(it.dims.get("passes", 1))

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        return {"W": (self.nIn, self.nOut), "gate_w": (self.nIn,),
                "gate_b": (1,)}

    def initialize(self, key):
        k1, k2 = jax.random.split(key)
        zeros = jnp.zeros((self.n_passes,), jnp.float32)
        return ({"W": _initialize((self.nIn, self.nOut), self.weight_init,
                                  k1),
                 "gate_w": _initialize((self.nIn, 1), self.weight_init,
                                       k2)[:, 0],
                 "gate_b": jnp.zeros((1,), jnp.float32)},
                {"exit_mass": zeros, "pass_loss": zeros})

    def apply(self, params, state, x, train, key):
        h = x[-1] if isinstance(x, tuple) else x
        return _logits(_feature_last(self, h), params["W"]), state

    def loss_from(self, params, x, labels, mask=None):
        """``(loss, state)`` from the layer's input: every pass's hidden
        states (a tuple off a LoopVertex) or one array."""
        passes = tuple(_feature_last(self, h)
                       for h in (x if isinstance(x, tuple) else (x,)))
        labels = labels.astype(jnp.int32)
        with jax.named_scope(_stepprogram.HEAD_LOSS_SCOPE):
            ce = blocked_cross_entropy(passes, params["W"], labels)
            gates = []
            for t, h in enumerate(passes):
                with jax.named_scope(_stepprogram.pass_scope(t + 1)):
                    gates.append(
                        jnp.einsum("ntd,d->nt", h.astype(jnp.float32),
                                   params["gate_w"].astype(jnp.float32))
                        + params["gate_b"][0])
            log_p = exit_log_distribution(jnp.stack(gates))
            p = jnp.exp(log_p)
            per_token = jnp.sum(p * ce, axis=0) \
                + self.beta * jnp.sum(p * log_p, axis=0)
            if mask is None:
                weight = jnp.full(per_token.shape, 1.0 / per_token.size)
            else:
                m = mask.astype(jnp.float32)
                weight = m / jnp.maximum(jnp.sum(m), 1.0)
            loss = jnp.sum(per_token * weight)
            state = {"exit_mass": jnp.sum(p * weight, axis=(1, 2)),
                     "pass_loss": jnp.sum(ce * weight, axis=(1, 2))}
        return loss, jax.lax.stop_gradient(state)

    def compute_loss(self, labels, preds, mask=None):
        raise ValueError(
            "LoopedLMOutputLayer works its loss out from its input "
            "(loss_from), not from predictions")

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))


def exit_log_distribution(gate_logits):
    """``log p_t`` over the leading (pass) axis from the gates' logits:
    ``p_t = lambda_t prod_{j<t}(1 - lambda_j)`` for t < P, the last pass
    taking ``prod_{j<P}(1 - lambda_j)``, so the p_t sum to one. In logs,
    from ``log_sigmoid`` alone: nothing under- or overflows."""
    log_stay = jax.nn.log_sigmoid(-gate_logits)         # log(1 - lambda)
    log_exit = jax.nn.log_sigmoid(gate_logits)
    before = jnp.cumsum(log_stay, axis=0) - log_stay    # sum over j < t
    return jnp.concatenate([(log_exit + before)[:-1], before[-1:]], axis=0)


# ---------------------------------- hyper-connections, latent attention,
# ---------------------------------- sparse experts, multi-token prediction
# The blocks of a DeepSeek-V3-style sparse decoder under manifold-
# constrained hyper-connections (Xie et al. 2025, arXiv:2512.24880, over
# Zhu et al. 2024, arXiv:2409.19606): the residual stream is ``nStreams``
# copies of the width, held feature-last as [N, T, nStreams * C]; every
# sub-block reads one [N, T, C] mix of them and writes its result back
# through per-token maps. A layer that takes several inputs says so with
# ``n_inputs`` and is handed a tuple.

_HIGHEST = jax.lax.Precision.HIGHEST


def _rms(x, gain, eps):
    """RMS norm with a float32 gain, statistics in float32, the result in
    ``x``'s dtype (what :class:`RMSNorm` computes, inside another layer)."""
    return norm_ops.rms_norm(x.astype(jnp.float32), gain.astype(jnp.float32),
                             eps=eps).astype(x.dtype)


def _steps(it) -> int:
    """Positions a sequence input has (1 where it declares none): what a
    layer's ``forward_flops(it)`` multiplies its per-token products by;
    ``analysis.distribution._approx_flops`` asks a layer that has one."""
    t = int(getattr(it, "dims", {}).get("timesteps", -1) or -1) \
        if it is not None else -1
    return t if t > 0 else 1


def sinkhorn(m, iters: int, eps: float, row_axis: int = -1,
             col_axis: int = -2):
    """``iters`` rounds of "divide each row by its sum, then each column
    by its" of a positive ``m`` (+ ``eps`` in every divisor; a row runs
    along ``row_axis``, a column along ``col_axis``): the Sinkhorn-Knopp
    projection towards the doubly stochastic matrices. The columns sum to
    one after the last round, the rows nearly."""
    for _ in range(int(iters)):
        m = m / (jnp.sum(m, axis=row_axis, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=col_axis, keepdims=True) + eps)
    return m


def _split3(a):
    """Three bfloat16 pieces of a float32 ``a`` that sum to it at float32's
    precision (8 + 8 + 8 bits of mantissa): side by side in ONE bfloat16
    product against an operand that is exact in bfloat16 they give the
    float32 product in one pass of the MXU (3 x 24 columns at most fit
    one 128-lane tile), where ``Precision.HIGHEST`` takes six over float32
    copies of both operands."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = a.astype(bf16)
    mid = (a - hi.astype(f32)).astype(bf16)
    lo = (a - hi.astype(f32) - mid.astype(f32)).astype(bf16)
    return hi, mid, lo


def _stream_slices(x, n):
    """The ``n`` streams of [N, T, n * C] in ``x``'s dtype, [N, T, C] each:
    a slice is converted inside the fusion that uses it."""
    C = x.shape[-1] // n
    return [x[..., i * C:(i + 1) * C] for i in range(n)]


def _stream_products(x, phi, eps):
    """``((x~ phi)^T [K, N, T], the norm's divisor [N, T])``, float32 (a
    map's numbers lead, the tokens lie on the lanes: a [N, T, 4, 4] tensor
    would be padded 64-fold on the chip), ``x~`` the RMS norm without a
    gain of the streams ``x`` [N, T, F] and ``phi`` float32 [F, K]; the
    product at float32's precision whatever ``x``'s dtype (bfloat16
    streams are exact in it: :func:`_split3`). The divisor is applied
    after the product. Two passes over the streams; what the backward
    rules keep of them is these two small results."""
    f32 = jnp.float32
    K = phi.shape[1]
    if x.dtype == jnp.bfloat16:
        z = jnp.dot(x, jnp.concatenate(_split3(phi.astype(f32)), axis=1),
                    preferred_element_type=f32)
        z = z[..., :K] + z[..., K:2 * K] + z[..., 2 * K:]
    else:
        z = jnp.dot(x.astype(f32), phi.astype(f32), precision=_HIGHEST)
    x32 = x.astype(f32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1) + eps)      # [N, T]
    return jnp.moveaxis(z, -1, 0) * inv, inv


def _stream_products_bwd(x, phi, zt, inv, dzt):
    """From the cotangent ``dzt`` [K, N, T] of :func:`_stream_products`'
    first result: ``phi``'s gradient ``x~^T dzt`` float32 [F, K] (one pass
    over the streams, on the MXU; float32's precision in one pass of
    bfloat16 streams, the cotangent in :func:`_split3`'s pieces), and
    what the streams' cotangent gets from the maps, as the two factors
    the caller's ONE pass that writes it needs: ``dz = dzt / divisor``
    [K, N, T], to be multiplied by ``phi^T``, and the divisor's own
    ``c`` [N, T], to be multiplied by ``x``."""
    f32 = jnp.float32
    K = phi.shape[1]
    dz = dzt * inv
    c = jnp.sum(dzt * zt, axis=0) * inv * inv * (-1.0 / x.shape[-1])
    cols = jnp.moveaxis(dz, 0, -1)                            # [N, T, K]
    if x.dtype == jnp.bfloat16:
        dphi = jnp.einsum("ntf,ntk->fk", x,
                          jnp.concatenate(_split3(cols), axis=-1),
                          preferred_element_type=f32)
        dphi = dphi[:, :K] + dphi[:, K:2 * K] + dphi[:, 2 * K:]
    else:
        dphi = jnp.einsum("ntf,ntk->fk", x.astype(f32), cols,
                          precision=_HIGHEST)
    return dphi, dz, c


def _stream_maps(x, phis, eps):
    """``[(x~ phi)^T for phi in phis]``, float32 [k, N, T] each:
    :func:`_stream_products` of the ``phis`` [F, k] side by side. The
    layers' rules call that one; this is for :meth:`HyperConnectionWrite.
    maps` and whoever wants the maps alone (plain ``jax.numpy``: autodiff
    derives its gradient, ``phi``'s rounded to bfloat16 under bfloat16
    streams)."""
    zt, _ = _stream_products(x, jnp.concatenate(phis, axis=1), eps)
    return jnp.split(zt, np.cumsum([p.shape[1] for p in phis])[:-1].tolist())


_MHC_LOWERED = _prof.get_registry().counter(
    "dl4j_mhc_lowered_total",
    "Traces of a hyper-connection read or write (one a lowering of each "
    "call site, not one a step) by the path it took: the hand-written "
    "forward/backward pair, for every dtype and shape",
    labelnames=("path",))


# The read and the write are each a ``jax.custom_vjp`` pair (as
# ``ops.attention.causal_attention`` and :func:`blocked_cross_entropy`
# are). What is written by hand is every pass over a stream-sized tensor:
# slices of ``X``, ``y`` and the cotangents are read in their own dtype and
# converted inside the fusion that uses them, every multiply, sum and
# per-token reduction is float32, a result is rounded once on its way out
# (but for ``H_res^T dX'``, which the write's backward hands to its last
# product rounded: see :func:`_write_bwd`). What autodiff still derives is
# the small tensors' part, ONE function of [k, N, T] arrays a layer
# (``_read_maps`` / ``_write_maps``: sigmoid, clamp, exp, Sinkhorn's
# rounds, ``alpha``, ``b``) by ``jax.vjp`` inside the backward rule. What
# a rule keeps is ``X``, ``y``, the parameters, the maps before their
# activation [k, N, T] and the norm's divisor [N, T]: in a rematerialised
# stretch the forward rule is run again for those two (the products and
# the sum of squares; the write's ``X'`` and its Sinkhorn are dead there)
# and the backward rule runs the small function once, under its
# ``jax.vjp``. The four rule bodies are jitted for what a jit shares, not
# for a dispatch (as ``_ce_fwd_block``): a step's 24 layers have two
# shapes, JAX traces and lowers each body once, XLA inlines the calls
# under their callers' scopes.
def _read_maps(zt, alpha, b):
    """``H_pre`` [n, N, T]."""
    return jax.nn.sigmoid(alpha[0] * zt + b[:, None, None])


@functools.partial(jax.jit, static_argnums=4)
def _read_fwd(x, phi, alpha, b, eps):
    """``(u, (maps before the sigmoid, divisor))``: products, sum of
    squares and ONE pass that reads ``X`` and writes ``u``."""
    zt, inv = _stream_products(x, phi, eps)
    h = _read_maps(zt, alpha, b)
    u = sum(h[i][..., None] * xi.astype(jnp.float32)
            for i, xi in enumerate(_stream_slices(x, phi.shape[1])))
    return u.astype(x.dtype), (zt, inv)


@functools.partial(jax.jit, static_argnums=7)
def _read_bwd(x, phi, alpha, b, zt, inv, du, eps):
    """Cotangents of ``(x, phi, alpha, b)``. (a) one pass over ``du`` and
    ``X`` gives ``dH_pre[i] = sum_C du x_i``; (b) the small function's
    ``jax.vjp``; (c) ``phi``'s gradient; (d) one pass that writes ``dX =
    H_pre du + the maps' two terms``: the read's product has one column a
    stream, so ``dz phi^T`` is that many multiply-adds an element and
    rides in the pass (the write's has ``n + n * n`` and goes to the
    MXU)."""
    f32 = jnp.float32
    xs, du32 = _stream_slices(x, phi.shape[1]), du.astype(f32)
    h, back = jax.vjp(_read_maps, zt, alpha, b)
    dzt, dalpha, db = back(jnp.stack(
        [jnp.sum(du32 * xi.astype(f32), axis=-1) for xi in xs]))
    dphi, dz, c = _stream_products_bwd(x, phi, zt, inv, dzt)
    phi_t = phi.astype(f32).T                                 # [n, F]
    C = du.shape[-1]
    dx = jnp.concatenate(
        [h[j][..., None] * du32 + c[..., None] * xj.astype(f32)
         + sum(dz[k][..., None] * phi_t[k, j * C:(j + 1) * C]
               for k in range(phi.shape[1]))
         for j, xj in enumerate(xs)], axis=-1)
    return dx.astype(x.dtype), dphi.astype(phi.dtype), dalpha, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _hc_read(x, phi, alpha, b, eps):
    return _read_fwd(x, phi, alpha, b, eps)[0]


def _hc_read_fwd(x, phi, alpha, b, eps):
    u, kept = _read_fwd(x, phi, alpha, b, eps)
    return u, (x, phi, alpha, b, *kept)


def _hc_read_bwd(eps, res, du):
    return _read_bwd(*res, du, eps)


_hc_read.defvjp(_hc_read_fwd, _hc_read_bwd)


def _write_maps(post, res, p, iters, eps, clamp):
    """``(H_post [n, N, T], H_res [n, n, N, T])`` from the maps before
    their activation (``post`` [n, N, T], ``res`` [n, n, N, T]: the maps'
    numbers lead) and the write's small parameters ``p``."""
    h_post = 2.0 * jax.nn.sigmoid(p["alpha_post"][0] * post
                                  + p["b_post"][:, None, None])
    res = p["alpha_res"][0] * res + p["b_res"][:, :, None, None]
    return h_post, sinkhorn(jnp.exp(jnp.clip(res, *clamp)), iters, eps,
                            row_axis=1, col_axis=0)


_WRITE_SMALL = ("alpha_post", "alpha_res", "b_post", "b_res")


def _write_phi(p):
    return jnp.concatenate([p["phi_post"], p["phi_res"]], axis=1)


def _post_res(zt, n):
    """The [n + n * n, N, T] rows of the write's product as ``(post [n, N,
    T], res [n, n, N, T])``: apart from the function autodiff derives, so
    that the backward rule can hold the two behind a barrier. Without one
    the compiler carries the reshape down through all of Sinkhorn's
    rounds and lays every round's divisor out anew for it, forward and
    transposed (160 standing ops of 5 us a layer on a v5e)."""
    return zt[:n], zt[n:].reshape((n, n) + zt.shape[1:])


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _write_fwd(x, y, p, iters, eps, clamp):
    """``(X', (maps before their activation, divisor))``: products, sum of
    squares and ONE pass that reads ``X`` and ``y`` and writes ``X'``."""
    f32 = jnp.float32
    n = p["b_post"].shape[0]
    zt, inv = _stream_products(x, _write_phi(p), eps)
    h_post, h_res = _write_maps(*_post_res(zt, n), p, iters, eps, clamp)
    xs, y32 = _stream_slices(x, n), y.astype(f32)
    mixed = [h_post[i][..., None] * y32
             + sum(h_res[i, j][..., None] * xj.astype(f32)
                   for j, xj in enumerate(xs)) for i in range(n)]
    return jnp.concatenate(mixed, axis=-1).astype(x.dtype), (zt, inv)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _write_bwd(x, y, p, zt, inv, dxo, iters, eps, clamp):
    """Cotangents of ``(x, y, p)``. (a) over ``dX'``, ``X`` and ``y``:
    ``dH_res[i, j] = sum_C dX'_i x_j`` and ``dH_post[i] = sum_C dX'_i y``
    in one pass, ``dy = H_post dX'`` and ``H_res^T dX'`` (rounded to the
    streams' dtype: a mix over stream slices does not ride in a product's
    fusion, the compiler would write it out in float32 first) in a
    second; (b) the small function's ``jax.vjp``; (c) ``phi``'s gradient;
    (d) one product whose fusion adds ``c x`` and ``H_res^T dX'`` to the
    maps' term and writes ``dX``."""
    f32 = jnp.float32
    n = p["b_post"].shape[0]
    (h_post, h_res), back = jax.vjp(
        lambda post, res, small: _write_maps(post, res, small, iters, eps,
                                             clamp),
        *jax.lax.optimization_barrier(_post_res(zt, n)),
        {k: p[k] for k in _WRITE_SMALL})
    xs, gs, y32 = _stream_slices(x, n), _stream_slices(dxo, n), y.astype(f32)
    d_post = jnp.stack([jnp.sum(g.astype(f32) * y32, axis=-1) for g in gs])
    d_res = jnp.stack([jnp.stack(
        [jnp.sum(g.astype(f32) * xj.astype(f32), axis=-1) for xj in xs])
        for g in gs])
    dy = sum(h_post[i][..., None] * g.astype(f32) for i, g in enumerate(gs))
    mixed = jnp.concatenate(
        [sum(h_res[i, j][..., None] * g.astype(f32)
             for i, g in enumerate(gs)) for j in range(n)],
        axis=-1).astype(x.dtype)
    d_post, d_res, dsmall = back((d_post, d_res))
    dzt = jnp.concatenate([d_post, d_res.reshape((n * n,) + d_res.shape[2:])])
    phi = _write_phi(p).astype(f32)
    dphi, dz, c = _stream_products_bwd(x, phi, zt, inv, dzt)
    cols = jnp.moveaxis(dz, 0, -1)                            # [N, T, K]
    if x.dtype == jnp.bfloat16:
        d0, d1, d2 = _split3(cols)
        p0, p1, p2 = _split3(phi)
        dx = jnp.einsum("ntk,fk->ntf",
                        jnp.concatenate([d0, d0, d0, d1, d1, d2], axis=-1),
                        jnp.concatenate([p0, p1, p2, p0, p1, p0], axis=1),
                        preferred_element_type=f32)
    else:
        dx = jnp.einsum("ntk,fk->ntf", cols, phi, precision=_HIGHEST)
    dx = dx + c[..., None] * x.astype(f32) + mixed.astype(f32)
    dphi = dphi.astype(p["phi_post"].dtype)
    return dx.astype(x.dtype), dy.astype(y.dtype), dict(
        dsmall, phi_post=dphi[:, :n], phi_res=dphi[:, n:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _hc_write(x, y, p, iters, eps, clamp):
    return _write_fwd(x, y, p, iters, eps, clamp)[0]


def _hc_write_fwd(x, y, p, iters, eps, clamp):
    out, kept = _write_fwd(x, y, p, iters, eps, clamp)
    return out, (x, y, p, *kept)


def _hc_write_bwd(iters, eps, clamp, res, dxo):
    return _write_bwd(*res, dxo, iters, eps, clamp)


_hc_write.defvjp(_hc_write_fwd, _hc_write_bwd)


class _HyperConnection(Layer):
    """What the four hyper-connection layers share: the stream count, a
    float32 island (streams come and go in the compute dtype and are held
    in it: no float32 copy of a stream-sized tensor is made or kept; the
    maps, the mixing and every per-token reduction are float32), the
    feature-last layout. Stream ``i`` is the features ``i * C .. (i + 1) *
    C`` of [N, T, n * C]. The read and the write are forward/backward
    pairs written by hand over the stream-sized tensors (the comment above
    :func:`_read_maps` says what they keep, what is run again and what
    autodiff still derives); copying in and summing out are autodiff's."""

    input_kind = None
    fp32_params = True

    def __init__(self, nStreams: int = 4, eps: float = 1e-6, **kw):
        super().__init__(**kw)
        self.n_streams = int(nStreams)
        self.eps = float(eps)

    def mxu_lane_dims(self):
        return []

    def _width(self, it: InputType) -> int:
        size = _sequence_size(it)
        if size % self.n_streams:
            raise ValueError(
                f"{type(self).__name__} '{self.name}': {size} features do "
                f"not divide into nStreams={self.n_streams}")
        return size // self.n_streams

    def infer_nin(self, it: InputType):
        self.nIn = self.nOut = _sequence_size(it)

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))

    def forward_flops(self, it) -> int:
        """The maps' products and the mixing, a token: thin next to any
        sub-block."""
        maps = sum(math.prod(shape) for shape in self.param_shapes().values()
                   if len(shape) == 2 and shape[0] == self.nIn)
        return _steps(it) * 2 * (maps + self.n_streams * max(
            self.nIn or 0, self.nOut or 0))


class HyperConnectionIn(_HyperConnection):
    """[N, T, C] -> ``nStreams`` copies side by side, [N, T, n * C]."""

    has_params = False

    def infer_nin(self, it: InputType):
        self.nIn = _sequence_size(it)
        self.nOut = self.nIn * self.n_streams

    def apply(self, params, state, x, train, key):
        x = _feature_last(self, x)
        with jax.named_scope(_stepprogram.MHC_SCOPE):
            # (not ``jnp.tile``: its [N, T, n, C] on the way is laid out
            # anew on the chip, there and back)
            return jnp.concatenate([x] * self.n_streams, axis=-1), state


class HyperConnectionOut(_HyperConnection):
    """The streams summed, [N, T, n * C] -> [N, T, C]."""

    has_params = False

    def infer_nin(self, it: InputType):
        self.nIn = _sequence_size(it)
        self.nOut = self._width(it)

    def apply(self, params, state, x, train, key):
        x = _feature_last(self, x)
        with jax.named_scope(_stepprogram.MHC_SCOPE):
            return sum(s.astype(jnp.float32) for s in
                       _stream_slices(x, self.n_streams)).astype(x.dtype), \
                state


class HyperConnectionRead(_HyperConnection):
    """A sub-block's input under hyper-connections: ``u = H_pre X`` with
    ``H_pre = sigmoid(alpha_pre * (x~ phi_pre) + b_pre)`` [N, T, n], ``x~``
    the RMS norm (no gain) of the flattened streams; one weight a stream
    and a token. A forward and a backward written by hand
    (:func:`_read_fwd`, :func:`_read_bwd`): kept for the backward are
    ``X``, the three parameters, ``x~ phi_pre`` [n, N, T] and the norm's
    divisor [N, T]; a rematerialised stretch runs the forward again (the
    product, the sum of squares, and ``u`` for the sub-block); autodiff
    derives the sigmoid's, ``alpha_pre``'s and ``b_pre``'s part on the
    [n, N, T] maps, the rule everything that touches ``X``, ``u`` and
    their cotangents."""

    def infer_nin(self, it: InputType):
        self.nIn = _sequence_size(it)
        self.nOut = self._width(it)

    def param_shapes(self):
        if not self.nIn:
            return {}
        return {"phi_pre": (self.nIn, self.n_streams), "alpha_pre": (1,),
                "b_pre": (self.n_streams,)}

    def initialize(self, key):
        n = self.n_streams
        return {"phi_pre": _initialize((self.nIn, n), self.weight_init, key),
                "alpha_pre": jnp.full((1,), 0.01, jnp.float32),
                "b_pre": jnp.zeros((n,), jnp.float32)}, {}

    def apply(self, params, state, x, train, key):
        x = _feature_last(self, x)
        _MHC_LOWERED.labels("pair").inc()
        with jax.named_scope(_stepprogram.MHC_SCOPE):
            return _hc_read(x, params["phi_pre"], params["alpha_pre"],
                            params["b_pre"], self.eps), state


class HyperConnectionWrite(_HyperConnection):
    """A sub-block's output written back: inputs ``(X, y)``, ``X' = H_res X
    + H_post^T y`` with ``H_post = 2 sigmoid(alpha_post * (x~ phi_post) +
    b_post)`` [N, T, n] and ``H_res = sinkhorn(exp(clamp(alpha_res * mat(x~
    phi_res) + b_res)))`` [N, T, n, n], the manifold constraint of
    arXiv:2512.24880: a (nearly) doubly stochastic mixing of the streams,
    so that neither a forward signal nor a gradient grows through the
    depth. ``x~`` is of the streams BEFORE the sub-block, as the read's.
    A forward and a backward written by hand (:func:`_write_fwd`,
    :func:`_write_bwd`): kept for the backward are ``X``, ``y``, the six
    parameters, ``x~ [phi_post | phi_res]`` [n + n * n, N, T] and the
    norm's divisor [N, T]; a rematerialised stretch runs only the product
    and the sum of squares again (``X'`` is dead there) and the backward
    rule the small function; autodiff derives that function's part
    (sigmoid, clamp, exp, Sinkhorn's rounds, ``alpha_*``, ``b_*``), the
    rule everything that touches ``X``, ``y``, ``X'`` and their
    cotangents."""

    n_inputs = 2

    def __init__(self, sinkhornIters: int = 20, clampMin: float = -30.0,
                 clampMax: float = 30.0, **kw):
        super().__init__(**kw)
        self.sinkhorn_iters = int(sinkhornIters)
        self.clamp = (float(clampMin), float(clampMax))

    def param_shapes(self):
        if not self.nIn:
            return {}
        n = self.n_streams
        return {"phi_post": (self.nIn, n), "phi_res": (self.nIn, n * n),
                "alpha_post": (1,), "alpha_res": (1,), "b_post": (n,),
                "b_res": (n, n)}

    def initialize(self, key):
        n = self.n_streams
        k1, k2 = jax.random.split(key)
        small = lambda: jnp.full((1,), 0.01, jnp.float32)  # noqa: E731
        return {"phi_post": _initialize((self.nIn, n), self.weight_init, k1),
                "phi_res": _initialize((self.nIn, n * n), self.weight_init,
                                       k2),
                "alpha_post": small(), "alpha_res": small(),
                "b_post": jnp.zeros((n,), jnp.float32),
                # H_res opens at the identity (nearly: exp(3) to 1)
                "b_res": 3.0 * jnp.eye(n, dtype=jnp.float32)}, {}

    def maps(self, params, x):
        """``(H_post [N, T, n], H_res [N, T, n, n])``: a token leads."""
        (zt,) = _stream_maps(x, [_write_phi(params)], self.eps)
        h_post, h_res = _write_maps(*_post_res(zt, self.n_streams), params,
                                    self.sinkhorn_iters, self.eps,
                                    self.clamp)
        return jnp.moveaxis(h_post, 0, -1), \
            jnp.moveaxis(jnp.moveaxis(h_res, 0, -1), 0, -1)

    def apply(self, params, state, x, train, key):
        x, y = x
        x = _feature_last(self, x)
        _MHC_LOWERED.labels("pair").inc()
        with jax.named_scope(_stepprogram.MHC_SCOPE):
            return _hc_write(x, y, dict(params), self.sinkhorn_iters,
                             self.eps, tuple(self.clamp)), state


class LatentAttentionLayer(Layer):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1):
    queries through a ``qLoraRank`` bottleneck with an RMS norm in it,
    keys and values through ONE ``kvLoraRank`` latent a token (normed)
    plus one rotary key of ``qkRopeHeadDim`` shared by all heads; a head's
    query and key are ``[nope | rope]`` (``qkNopeHeadDim + qkRopeHeadDim``),
    its value ``vHeadDim``. Rotary positions on the rope parts only,
    rotate-half pairs, YaRN frequencies where ``ropeScaling`` gives them;
    the scores' scale is ``mscale^2 / sqrt(qk head size)`` with YaRN's
    ``mscale`` (``mscale_all_dim``). No bias. Training computes the keys
    and values of every head from the latent (the cache-free form); the
    core is :func:`ops.attention.causal_attention`."""

    input_kind = "rnn"
    fp32_leaves = ("q_gain", "kv_gain")

    def __init__(self, nOut=None, nHeads: int = 1, qLoraRank: int = None,
                 kvLoraRank: int = None, qkNopeHeadDim: int = None,
                 qkRopeHeadDim: int = None, vHeadDim: int = None,
                 ropeTheta: float = 10000.0, ropeScaling: dict = None,
                 eps: float = 1e-6, **kw):
        super().__init__(nOut=nOut, **kw)
        self.n_heads = int(nHeads)
        self.q_lora_rank, self.kv_lora_rank = int(qLoraRank), int(kvLoraRank)
        self.qk_nope, self.qk_rope = int(qkNopeHeadDim), int(qkRopeHeadDim)
        self.v_head = int(vHeadDim)
        self.rope_theta = float(ropeTheta)
        self.rope_scaling = dict(ropeScaling) if ropeScaling else None
        self.eps = float(eps)
        if self.qk_rope % 2:
            raise ValueError(f"LatentAttentionLayer: rotary positions need "
                             f"an even qkRopeHeadDim, got {self.qk_rope}")

    def infer_nin(self, it: InputType):
        super().infer_nin(it)
        if self.nOut is None:
            self.nOut = self.nIn

    def mxu_lane_dims(self):
        return [self.n_heads * (self.qk_nope + self.qk_rope), self.nOut]

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        H, ql, kvl = self.n_heads, self.q_lora_rank, self.kv_lora_rank
        return {"Wqa": (self.nIn, ql), "q_gain": (ql,),
                "Wqb": (ql, H * (self.qk_nope + self.qk_rope)),
                "Wkva": (self.nIn, kvl + self.qk_rope), "kv_gain": (kvl,),
                "Wkvb": (kvl, H * (self.qk_nope + self.v_head)),
                "Wo": (H * self.v_head, self.nOut)}

    def initialize(self, key):
        out = {}
        for name, shape in self.param_shapes().items():
            key, sub = jax.random.split(key)
            out[name] = jnp.ones(shape, jnp.float32) if len(shape) == 1 \
                else _initialize(shape, self.weight_init, sub)
        return out, {}

    def forward_flops(self, it) -> int:
        """The five projections and the whole square of the core (what a
        plain lowering executes; the benchmark's requirement counts the
        causal half)."""
        t = _steps(it)
        proj = sum(math.prod(s) for s in self.param_shapes().values()
                   if len(s) == 2)
        return 2 * t * proj + 2 * t * t * self.n_heads * (
            self.qk_nope + self.qk_rope + self.v_head)

    def inv_freq(self):
        """The rope part's inverse frequencies, and the softmax scale."""
        rs = self.rope_scaling
        d = self.qk_nope + self.qk_rope
        if not rs:
            return None, d ** -0.5
        inv = attention_ops.yarn_inv_freq(
            self.qk_rope, self.rope_theta, rs["factor"],
            rs["original_max_position_embeddings"],
            rs.get("beta_fast", 32), rs.get("beta_slow", 1))
        m = attention_ops.yarn_mscale(rs["factor"],
                                      rs.get("mscale_all_dim", 0) or 0)
        return inv, m * m * d ** -0.5

    def apply(self, params, state, x, train, key):
        x = self._maybe_dropout(_feature_last(self, x), train, key)
        N, T, H = x.shape[0], x.shape[1], self.n_heads
        dn, dr, dv = self.qk_nope, self.qk_rope, self.v_head
        kvl = self.kv_lora_rank
        inv, scale = self.inv_freq()
        q = (_rms(x @ params["Wqa"], params["q_gain"], self.eps)
             @ params["Wqb"]).reshape(N, T, H, dn + dr)
        ckv = x @ params["Wkva"]
        kv = (_rms(ckv[..., :kvl], params["kv_gain"], self.eps)
              @ params["Wkvb"]).reshape(N, T, H, dn + dv)
        rot = functools.partial(attention_ops.rotary_embedding,
                                theta=self.rope_theta, inv_freq=inv)
        k_rope = rot(ckv[..., kvl:].reshape(N, T, 1, dr))
        q = jnp.concatenate([q[..., :dn], rot(q[..., dn:])], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (N, T, H, dr))], -1)
        with jax.named_scope(_stepprogram.ATTN_CORE_SCOPE):
            o = attention_ops.causal_attention(q, k, kv[..., dn:],
                                               scale=scale)
        return o.reshape(N, T, H * dv) @ params["Wo"], state

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))


_MOE_LOWERED = _prof.get_registry().counter(
    "dl4j_moe_lowered_total",
    "Traces of nn.layers.SparseExpertsLayer's routed path (one a lowering "
    "of each call site, not one a step) by what runs the grouped products "
    "over the experts held",
    labelnames=("path",))

#: Rows a pass of the routed path works over, as a multiple of the share
#: of the ``tokens * nExpertsPerTok`` pairs that uniform routing sends to
#: the experts held here. 2: the held share is not stationary (only the
#: held experts' outputs reach the loss, so the router turns toward them:
#: 12.9% of the pairs at the first step of ``lfm2-fit-s8192-b4``, 19.5%
#: after 27 at 8 of 64 held, PERF.md section 6) and a selection bias moves
#: it by the seed (10-16% in ``xing4-fit-s4096-b1``); what a step sends
#: beyond takes a further pass, so the value costs time, never a token.
ROUTED_ROWS_OVER_UNIFORM = 2

#: a pass's rows are a multiple of this (a float32 tile's sublanes)
_ROUTED_ROW_TILE = 8


def _routed_rows(pairs, held, experts):
    """Rows a pass takes of ``pairs`` = tokens x experts a token, where
    ``held`` of the router's ``experts`` are here: all of them where that
    is no more (a layer that holds every expert: one pass, no
    conditional)."""
    rows = -(-ROUTED_ROWS_OVER_UNIFORM * pairs * held // experts)
    rows = -(-rows // _ROUTED_ROW_TILE) * _ROUTED_ROW_TILE
    return min(pairs, rows)


def _routed_passes(held, experts):
    """The most passes any batch takes (a state is shaped before the
    batch is known)."""
    return max(-(-experts // (ROUTED_ROWS_OVER_UNIFORM * held)), 1)


def _pass_index(order, inv, load, lo, rows, k):
    """What a pass over the sorted pairs ``[lo, lo + rows)`` needs of
    integers, all of ``rows``, ``tokens`` or ``tokens * k`` int32: the
    pass's pair ids and which of its rows hold a held pair, the experts'
    group sizes inside it, the permutation that brings its rows into
    token order with each row's token there (``tokens`` where the row
    holds no pair), a token's first row there, and which pairs the pass
    holds."""
    pairs, held_pairs = inv.shape[0], jnp.sum(load)
    at = lo + jnp.arange(rows, dtype=jnp.int32)
    idx = jax.lax.dynamic_slice_in_dim(order, lo, rows)
    valid = at < held_pairs
    ends = jnp.clip(jnp.cumsum(load) - lo, 0, rows)
    sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    key, perm = jax.lax.sort(
        (jnp.where(valid, idx, pairs), jnp.arange(rows, dtype=jnp.int32)),
        num_keys=1, is_stable=True)
    # a tile of rows that hold no pair past the last: the row of zeros a
    # token with no pair in the pass takes
    key = jnp.pad(key, (0, _ROUTED_ROW_TILE), constant_values=pairs)
    perm = jnp.pad(perm, (0, _ROUTED_ROW_TILE))
    here = (inv >= lo) & (inv < jnp.minimum(lo + rows, held_pairs))
    count = jnp.sum(here.reshape(-1, k), axis=1, dtype=jnp.int32)
    first = jnp.where(count > 0, jnp.cumsum(count) - count, rows)
    return idx, valid, sizes, perm, key // k, first, here


def _to_tokens(y, w, perm, token, first, k):
    """``D^T``: rows ``y`` [rows, C] of a pass, each weighted by ``w``
    [rows] float32 (0 where a row holds no pair), summed back to their
    tokens [tokens, C] with no scatter: the rows in token order (one
    gather), a token's at most ``k`` adjacent rows added in float32 by
    ``k - 1`` shifted masked adds, one row a token (the second gather)."""
    yt, wt = y.at[perm].get(mode="promise_in_bounds"), w[perm]
    held = token < first.shape[0]
    total = jnp.where(held[:, None], yt.astype(jnp.float32) * wt[:, None], 0)
    for c in range(1, k):
        same = (jnp.roll(token, -c) == token).at[-c:].set(False) & held
        total = total + jnp.where(
            same[:, None], jnp.roll(yt, -c, axis=0).astype(jnp.float32)
            * jnp.roll(wt, -c)[:, None], 0)
    return total.astype(y.dtype).at[first].get(mode="promise_in_bounds")


@functools.partial(jax.jit, static_argnums=(9, 10))
def _pass_fwd(x, gate, eg, eu, ed, order, inv, load, lo, rows, f):
    """One pass of the routed path: what the held experts add to the
    tokens [tokens, C] from the sorted pairs ``[lo, lo + rows)``, and what
    its backward keeps: the gathered rows and the two products before the
    activation, all of ``rows`` rows."""
    k = gate.shape[1]
    idx, valid, sizes, perm, token, first, _ = _pass_index(
        order, inv, load, lo, rows, k)
    xr = x.at[idx // k].get(mode="promise_in_bounds")          # D
    with jax.named_scope(_stepprogram.MOE_EXPERTS_SCOPE):
        a = jax.lax.ragged_dot(xr, eg, sizes)
        b = jax.lax.ragged_dot(xr, eu, sizes)
        ys = jax.lax.ragged_dot(f(a) * b, ed, sizes)
    w = jnp.where(valid, gate.reshape(-1)[idx], 0)
    return _to_tokens(ys, w, perm, token, first, k), (xr, a, b)


@functools.partial(jax.jit, static_argnums=(12, 13))
def _pass_bwd(gate, eg, eu, ed, order, inv, load, lo, xr, a, b, g, rows, f):
    """Cotangents ``(dx, dgate, dEg, dEu, dEd)`` of one pass from what
    :func:`_pass_fwd` kept. ``D`` of the output's cotangent goes into the
    last product's transposes as it is and the gate is put on the other
    side of each (``dEd = (gate h)^T g``, ``dh = gate (g Ed^T)``, the
    gate's own cotangent the row-wise ``h . (g Ed^T)``), so no weighted
    copy of the cotangent is written and the experts' outputs are not
    kept; the grouped products' transposes are autodiff's, of each
    product alone (no forward product runs again); ``D^T`` of the rows'
    cotangent."""
    f32, k = jnp.float32, gate.shape[1]
    idx, valid, sizes, perm, token, first, here = _pass_index(
        order, inv, load, lo, rows, k)
    gr = g.at[idx // k].get(mode="promise_in_bounds")          # D
    w = jnp.where(valid, gate.reshape(-1)[idx], 0)[:, None]
    with jax.named_scope(_stepprogram.MOE_EXPERTS_SCOPE):
        h, gated = jax.vjp(lambda a, b: f(a) * b, a, b)
        dh, ded = jax.vjp(
            lambda hw, ed: jax.lax.ragged_dot(hw, ed, sizes),
            (h.astype(f32) * w).astype(h.dtype), ed)[1](gr)
        dh = dh.astype(f32)
        dw = jnp.where(valid, jnp.sum(dh * h.astype(f32), axis=-1), 0)
        dxr, deg, deu = jax.vjp(
            lambda xr, eg, eu: (jax.lax.ragged_dot(xr, eg, sizes),
                                jax.lax.ragged_dot(xr, eu, sizes)),
            xr, eg, eu)[1](gated((dh * w).astype(h.dtype)))
    dgate = jnp.where(here, dw[jnp.clip(inv - lo, 0, rows - 1)], 0)
    dx = _to_tokens(dxr, valid.astype(f32), perm, token, first, k)
    return dx, dgate.reshape(gate.shape), deg, deu, ded


def _further(load, rows, passes, acc, one):
    """``acc`` with what the passes after the first add; ``one(lo)`` is a
    pass's share, a tree like ``acc``. ONE conditional a call site: where
    the step's held pairs fit the first pass's rows (the common step) it
    hands ``acc`` through, else a ``while`` runs the further passes the
    pairs reach. A pass written out under a conditional of its own for
    each of the (at most four) passes put three more copies of the pass
    at every call site: 270 grouped-product kernels in
    ``xing4-fit-s4096-b1``'s step for the parent's 60, and its warm
    ``setup_s`` 36.2 -> 40.4 s (my chip run, PR 36); this way there is
    one. The ``while`` alone, with no conditional around it, kept 0.28
    GiB more alive at that step's peak (sandbox compile). The barrier
    keeps what reads the result out of the branches: without it the
    compiler moves the float32 casts of the experts' gradients into
    both, and the conditional hands Adam, at the step's end, a second,
    float32 copy of every expert leaf (1.7 GiB of that step)."""
    if passes == 1:
        return acc
    held = jnp.sum(load)

    def rest(acc):
        return jax.lax.while_loop(
            lambda c: c[0] * rows < held,
            lambda c: (c[0] + 1, jax.tree_util.tree_map(
                jnp.add, c[1], one(c[0] * rows))),
            (jnp.int32(1), acc))[1]
    return jax.lax.optimization_barrier(
        jax.lax.cond(held > rows, rest, lambda acc: acc, acc))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _routed(x, gate, eg, eu, ed, order, inv, load, rows, f):
    """``sum_{pairs held} gate * E(x)`` [tokens, C] of tokens ``x``
    [tokens, C], their gates [tokens, k] float32 and the selected pairs
    sorted by held expert (``order``, its inverse ``inv``, the experts'
    ``load``): the held pairs are the first ``sum(load)`` of ``order``,
    worked over in passes of ``rows`` rows. The first pass always runs;
    a further one only where a step's held pairs reach it, so no pair is
    left out whatever the router does. One forward and one backward rule
    with the conditional INSIDE them (:func:`_further`): autodiff of
    ``lax.cond`` would have every branch write zeros for the other
    branch's residuals, stream-sized ones on the common path for passes
    that never run. The first pass keeps its rows for the backward; a
    further pass keeps nothing and its backward runs its forward again,
    inside the backward rule's own conditional. The further passes are a
    ``while`` inside the conditional's branch: the map's readers list a
    ``while`` beside its body (PERF.md section 7 k), so a step that takes
    a second pass reads twice that pass's time in ``routed_device_ms``;
    the common step never enters it."""
    return _routed_fwd(x, gate, eg, eu, ed, order, inv, load, rows, f)[0]


# (the two rules are jitted whole as well as the pass: a step's expert
# layers trace the conditional and the ``while`` once for all their call
# sites, 0.4 s less tracing and lowering of ``xing4-fit-s4096-b1``'s step,
# fifteen call sites, than a call site at a time: sandbox, PR 36)
@functools.partial(jax.jit, static_argnums=(8, 9))
def _routed_fwd(x, gate, eg, eu, ed, order, inv, load, rows, f):
    passes = -(-inv.shape[0] // rows)
    # (the last pass's rows may end past the pairs)
    order = jnp.pad(order, (0, passes * rows - inv.shape[0]))
    args = (x, gate, eg, eu, ed, order, inv, load)
    out, kept = _pass_fwd(*args, jnp.int32(0), rows, f)
    out = _further(load, rows, passes, out,
                   lambda lo: _pass_fwd(*args, lo, rows, f)[0])
    return out, (args, kept)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _routed_bwd(rows, f, res, g):
    args, kept = res
    inv, load = args[6:]
    passes = -(-inv.shape[0] // rows)
    first = _pass_bwd(*args[1:], jnp.int32(0), *kept, g, rows, f)
    return _further(
        load, rows, passes, first,
        lambda lo: _pass_bwd(*args[1:], lo,
                             *_pass_fwd(*args, lo, rows, f)[1], g, rows, f)
    ) + (None, None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


class SparseExpertsLayer(Layer):
    """A sparse-expert feed-forward layer that is TOLD which experts it
    holds (DeepSeek-V3's routing, arXiv:2412.19437 §2.1.2): a router over
    all ``nExperts`` scores a token ``s = sigmoid(x Wr)`` in float32, the
    ``nExpertsPerTok`` largest of ``s + select_bias`` are selected (the
    bias, a layer STATE with no gradient, enters the selection only), the
    gates are ``routedScalingFactor * s_i / sum_selected s_j`` (the sum
    over ALL selected experts, held here or not), and the output is
    ``shared(x) + sum_{i selected and held} g_i E_i(x)``, every expert and
    the one shared expert a SwiGLU MLP of inner width ``nHidden``
    (``nSharedExperts=0``: no shared expert, no ``Sg``/``Su``/``Sd``, the
    routed sum alone).
    ``heldExperts`` lists the ids held (default: all): the chip's share
    under expert parallelism. What the absent experts would add is left
    out; no code stands in for their exchange.

    No token is dropped, and only the pairs a held expert takes are
    moved: the selected (token, expert) pairs are sorted by held expert,
    so that the held ones are the first ``sum(load)`` of the order, and
    :func:`_routed` works over them in passes of ``R`` rows, ``R`` =
    :data:`ROUTED_ROWS_OVER_UNIFORM` (2) times the share of the ``tokens
    * nExpertsPerTok`` pairs that uniform routing sends to the experts
    held (:func:`_routed_rows`; all the pairs where every expert is
    held). A pass gathers its rows by token, runs the held experts'
    products as grouped matrix products over them
    (``jax.lax.ragged_dot``, group sizes the experts' loads inside the
    pass, under ``dl4j_moe_experts`` inside ``dl4j_moe``) and sums the
    gated rows back to their tokens without a scatter. The first pass
    always runs; a step whose held pairs exceed ``R`` takes a further
    pass for each ``R`` more, inside a conditional, so whatever the
    router does every selected and held pair is computed. The state
    carries the loads of the last step (``expert_load`` [held]) for the
    gauges ``dl4j_moe_expert_load`` / ``dl4j_moe_held_pairs``, how many
    steps took 1, 2, .. passes (``pass_steps``, gauge
    ``dl4j_moe_pass_steps``), and with ``keepSelected=rows`` the ids it
    selected for the first ``rows`` tokens (``selected`` [rows, k] int32,
    -1 beyond the tokens): which experts a token takes is a discrete
    choice that rounding moves (the k-th and the next score of 64 lie
    close), so a comparison with a float32 reference has the reference
    follow the program's choice and judges the choice by its margin."""

    input_kind = None
    fp32_leaves = ("Wr",)
    n_shared = 1        # (a configuration saved before the key has one)

    def __init__(self, nOut=None, nExperts: int = None,
                 nExpertsPerTok: int = 1, nHidden: int = None,
                 heldExperts=None, routedScalingFactor: float = 1.0,
                 keepSelected: int = 0, nSharedExperts: int = 1, **kw):
        super().__init__(nOut=nOut, activation="swish", **kw)
        self.keep_selected = int(keepSelected)
        if nSharedExperts not in (0, 1):
            raise ValueError(f"SparseExpertsLayer: one shared expert or "
                             f"none, got nSharedExperts={nSharedExperts}")
        if not nSharedExperts:
            self.n_shared = 0
        if not nExperts or not nHidden:
            raise ValueError("SparseExpertsLayer needs nExperts, the "
                             "router's width, and nHidden, an expert's")
        self.n_experts, self.top_k = int(nExperts), int(nExpertsPerTok)
        self.n_hidden = int(nHidden)
        self.held = [int(e) for e in (range(self.n_experts)
                                      if heldExperts is None
                                      else heldExperts)]
        if len(set(self.held)) != len(self.held) or not all(
                0 <= e < self.n_experts for e in self.held):
            raise ValueError(f"SparseExpertsLayer: heldExperts must be "
                             f"distinct ids below {self.n_experts}, got "
                             f"{self.held}")
        self.scaling = float(routedScalingFactor)

    def infer_nin(self, it: InputType):
        self.nIn = _sequence_size(it)
        if self.nOut is None:
            self.nOut = self.nIn

    def mxu_lane_dims(self):
        return [self.n_hidden, self.nOut]

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        E, F = len(self.held), self.n_hidden
        shapes = {"Wr": (self.nIn, self.n_experts),
                  "Eg": (E, self.nIn, F), "Eu": (E, self.nIn, F),
                  "Ed": (E, F, self.nOut)}
        if self.n_shared:
            shapes.update(Sg=(self.nIn, F), Su=(self.nIn, F),
                          Sd=(F, self.nOut))
        return shapes

    def initialize(self, key):
        out = {}
        for name, shape in self.param_shapes().items():
            key, sub = jax.random.split(key)
            if len(shape) == 3:     # an expert a row: each its own fan
                out[name] = jnp.stack([
                    _initialize(shape[1:], self.weight_init, k)
                    for k in jax.random.split(sub, shape[0])])
            else:
                out[name] = _initialize(shape, self.weight_init, sub)
        state = {"select_bias": jnp.zeros((self.n_experts,), jnp.float32),
                 "expert_load": jnp.zeros((len(self.held),), jnp.float32),
                 "pass_steps": jnp.zeros(
                     (_routed_passes(len(self.held), self.n_experts),),
                     jnp.float32)}
        if self.keep_selected:
            state["selected"] = jnp.full((self.keep_selected, self.top_k),
                                         -1, jnp.int32)
        return out, state

    def forward_flops(self, it) -> int:
        """Router, the shared expert where there is one, and the routed
        products at the load uniform routing gives the experts held:
        ``nExpertsPerTok * held / nExperts`` experts a token."""
        per_expert = 3 * self.nIn * self.n_hidden
        share = self.top_k * len(self.held) / self.n_experts
        return int(_steps(it) * 2 * (
            self.nIn * self.n_experts
            + per_expert * (self.n_shared + share)))

    def route(self, x32, wr, select_bias):
        """``(selected ids [M, k], gates [M, k])`` of float32 tokens."""
        s = jax.nn.sigmoid(jnp.dot(x32, wr.astype(jnp.float32),
                                   precision=_HIGHEST))
        _, sel = jax.lax.top_k(s + select_bias, self.top_k)
        picked = jnp.take_along_axis(s, sel, axis=-1)
        return sel, picked / jnp.sum(picked, axis=-1, keepdims=True) \
            * self.scaling

    def apply(self, params, state, x, train, key):
        x = self._maybe_dropout(_feature_last(self, x), train, key)
        f = act.get(self.activation)
        xf = x.reshape(-1, x.shape[-1])
        M, k, E = xf.shape[0], self.top_k, len(self.held)
        rows = _routed_rows(M * k, E, self.n_experts)
        with jax.named_scope(_stepprogram.MOE_SCOPE):
            sel, gate = self.route(xf.astype(jnp.float32), params["Wr"],
                                   state["select_bias"])
            # a selected expert's row among the held ones; E = not held
            local = jnp.full((self.n_experts,), E, jnp.int32).at[
                jnp.asarray(self.held, jnp.int32)].set(
                    jnp.arange(E, dtype=jnp.int32))[sel].reshape(-1)
            order = jnp.argsort(local, stable=True)
            inv = jnp.argsort(order)
            load = jnp.sum(local[:, None] == jnp.arange(E),
                           axis=0, dtype=jnp.int32)
            _MOE_LOWERED.labels("compact").inc()
            routed = _routed(xf, gate, params["Eg"], params["Eu"],
                             params["Ed"], order, inv, load, rows, f)
            ran = jnp.clip(-(-jnp.sum(load) // rows), 1, None)
        out = routed + (f(xf @ params["Sg"]) * (xf @ params["Su"])) \
            @ params["Sd"] if self.n_shared else routed
        new_state = {"select_bias": state["select_bias"],
                     "expert_load": jax.lax.stop_gradient(
                         load.astype(jnp.float32)),
                     "pass_steps": state["pass_steps"] + (
                         jnp.arange(state["pass_steps"].shape[0])
                         == ran - 1)}
        if self.keep_selected:
            keep = self.keep_selected
            new_state["selected"] = jnp.pad(
                sel[:keep].astype(jnp.int32),
                ((0, max(keep - M, 0)), (0, 0)), constant_values=-1)
        return out.reshape(x.shape[:-1] + (self.nOut,)).astype(x.dtype), \
            new_state

    def output_type(self, it: InputType) -> InputType:
        if it.kind == "rnn":
            return InputType.recurrent(self.nOut,
                                       it.dims.get("timesteps", -1))
        return InputType.feedForward(self.nOut)


class MTPJoinLayer(Layer):
    """Where a multi-token-prediction module starts (DeepSeek-V3,
    arXiv:2412.19437 §2.2): inputs ``(h, e)``, the main model's hidden
    states before its final norm and the embedding of the NEXT token;
    ``[RMSNorm(h) ; RMSNorm(e)] W`` with ``W`` [2 C, C], each norm with a
    gain of its own."""

    input_kind = None
    n_inputs = 2
    fp32_leaves = ("h_gain", "e_gain")

    def __init__(self, nOut=None, eps: float = 1e-6, **kw):
        super().__init__(nOut=nOut, **kw)
        self.eps = float(eps)

    def infer_nin(self, it: InputType):
        self.nIn = _sequence_size(it)
        if self.nOut is None:
            self.nOut = self.nIn

    def param_shapes(self):
        if not self.nIn or not self.nOut:
            return {}
        return {"h_gain": (self.nIn,), "e_gain": (self.nIn,),
                "W": (2 * self.nIn, self.nOut)}

    def initialize(self, key):
        ones = lambda: jnp.ones((self.nIn,), jnp.float32)   # noqa: E731
        return {"h_gain": ones(), "e_gain": ones(),
                "W": _initialize((2 * self.nIn, self.nOut),
                                 self.weight_init, key)}, {}

    def apply(self, params, state, x, train, key):
        h, e = (_feature_last(self, a) for a in x)
        joined = jnp.concatenate(
            [_rms(h, params["h_gain"], self.eps),
             _rms(e, params["e_gain"], self.eps).astype(h.dtype)], -1)
        return joined @ params["W"], state

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))


class MTPLMOutputLayer(BaseOutputLayer):
    """A language-model head shared by the main model and its
    multi-token-prediction modules (DeepSeek-V3, arXiv:2412.19437 §2.2):
    inputs ``(h_0, h_1, ..)``, the main model's normed hidden states and
    each module's; module ``d``'s state at position ``i`` has seen token
    ``i + d`` and predicts token ``i + d + 1``. With the labels ``y_i`` =
    token ``i + 1`` the loss is ``CE(h_0 W, y) + mtpWeight * sum_d
    CE(h_d[i] W, y[i + d])``, each a mean over its own unmasked positions
    (a module's last ``d`` have no label). One head ``W`` for all:
    :func:`blocked_cross_entropy` over the inputs as its passes, a
    module's states moved ``d`` places right so that every pass meets the
    same labels. A block of it is a tile of rows x columns chosen from
    ``labels.size`` and ``nOut``: one sequence of 4,096 positions is one
    row block over 2,048-column blocks, four of 8,192 go in eight row
    blocks of 4,096, each over its column blocks. The backward keeps the
    heads' inputs, ``W``, the labels and the float32 row log-sum-exp, sums
    an input's gradient in float32 a row block at a time (rounded once a
    row block) and ``W``'s in one float32 buffer, and holds no float32
    value of all rows x nIn. Labels are INTEGER ids [N, T]; the state carries each
    head's loss of the last step (``dl4j_lm_loss``). ``apply`` gives the
    main model's logits. Fed one array it is a plain head. ``tiedWith`` an
    embedding it has no ``W`` of its own: the logits are ``h Emb^T``, the
    embedding's table [nOut, nIn] read as it lies, and the table's
    gradient is the sum of both uses'."""

    input_kind = None
    loss_from_input = True
    n_inputs = None             # as many as the graph wires in

    def __init__(self, nOut=None, mtpWeight: float = 0.3, **kw):
        super().__init__(lossFunction="sparse_mcxent", nOut=nOut, **kw)
        self.mtp_weight = float(mtpWeight)
        self.n_heads = 1

    def infer_nin(self, it: InputType):
        self.nIn = _sequence_size(it)

    def set_input_count(self, n: int):
        """The graph says how many heads' inputs it wired in."""
        self.n_heads = int(n)

    def forward_flops(self, it) -> int:
        return _steps(it) * 2 * (self.nIn or 0) * (self.nOut or 0) \
            * self.n_heads

    def param_shapes(self):
        if not self.nIn or not self.nOut or self.tied_with:
            return {}
        return {"W": (self.nIn, self.nOut)}

    def initialize(self, key):
        return ({} if self.tied_with else
                {"W": _initialize((self.nIn, self.nOut), self.weight_init,
                                  key)},
                {"head_loss": jnp.zeros((self.n_heads,), jnp.float32)})

    def apply(self, params, state, x, train, key):
        h = x[0] if isinstance(x, tuple) else x
        w = params["W"].T if self.tied_with else params["W"]
        return _logits(_feature_last(self, h), w), state

    def loss_from(self, params, x, labels, mask=None):
        """``(loss, state)`` from the heads' inputs."""
        hs = tuple(_feature_last(self, h)
                   for h in (x if isinstance(x, tuple) else (x,)))
        labels = labels.astype(jnp.int32)
        T = labels.shape[1]
        with jax.named_scope(_stepprogram.HEAD_LOSS_SCOPE):
            ce = blocked_cross_entropy(
                tuple(h if d == 0 else jnp.roll(h, d, axis=1)
                      for d, h in enumerate(hs)), params["W"], labels,
                table=bool(self.tied_with))
            m = jnp.ones(labels.shape, jnp.float32) if mask is None \
                else mask.astype(jnp.float32)
            losses = []
            for d in range(len(hs)):
                md = m * (jnp.arange(T) >= d)
                losses.append(jnp.sum(ce[d] * md)
                              / jnp.maximum(jnp.sum(md), 1.0))
            losses = jnp.stack(losses)
            loss = losses[0] + self.mtp_weight * jnp.sum(losses[1:])
        return loss, {"head_loss": jax.lax.stop_gradient(losses)}

    def compute_loss(self, labels, preds, mask=None):
        raise ValueError(
            "MTPLMOutputLayer works its loss out from its inputs "
            "(loss_from), not from predictions")

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.nOut, it.dims.get("timesteps", -1))



#: layers that compute on [N, T, C] (see ``layout_step``)
SEQUENCE_LAST = (RMSNorm, CausalSelfAttentionLayer, GatedMLP,
                 GatedShortConvLayer, LoopedLMOutputLayer, HyperConnectionIn, HyperConnectionOut,
                 HyperConnectionRead, HyperConnectionWrite,
                 LatentAttentionLayer, SparseExpertsLayer, MTPJoinLayer,
                 MTPLMOutputLayer)

for _cls in SEQUENCE_LAST:
    _LAYER_CLASSES[_cls.__name__] = _cls
