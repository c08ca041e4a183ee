"""Normalization ops: batchnorm, layernorm, LRN, dropout.

Reference parity: libnd4j ``batchnorm`` / ``layer_norm`` / ``lrn`` /
``dropout`` declarable ops and DL4J's ``BatchNormalization`` /
``LocalResponseNormalization`` / ``DropoutLayer`` (SURVEY.md §2.2).

TPU-native: pure functions; train-mode batchnorm returns updated running
stats functionally (no mutation), so the whole step stays jittable.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def batch_norm(x, gamma, beta, mean, var, *, eps: float = 1e-5,
               axis: int = 1) -> jnp.ndarray:
    """Inference-mode batchnorm (ref: libnd4j ``batchnorm``).

    ``axis`` is the channel axis (1 for NCHW — the reference's default).
    Folded into one fused-multiply-add in the INPUT dtype: under the bf16
    policy the (small, per-channel) scale/shift are computed in fp32 and
    cast once, so no fp32 copy of the activation is ever materialized.
    """
    shape = [1] * x.ndim
    shape[axis] = -1
    g = gamma if gamma is not None else jnp.ones_like(mean)
    b = beta if beta is not None else jnp.zeros_like(mean)
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    scale = (g * inv).astype(x.dtype)
    shift = (b - g * mean * inv).astype(x.dtype)
    return x * jnp.reshape(scale, shape) + jnp.reshape(shift, shape)


def batch_norm_train(x, gamma, beta, running_mean, running_var, *,
                     eps: float = 1e-5, decay: float = 0.9, axis: int = 1
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Training-mode batchnorm: normalize by batch stats, return
    (out, new_running_mean, new_running_var).

    ``decay`` matches DL4J's BatchNormalization ``decay`` (default 0.9):
    new_running = decay * running + (1-decay) * batch_stat.

    TPU-native precision split: statistics ACCUMULATE in fp32
    (``jnp.mean(..., dtype=f32)`` — a bf16 mean over a 224^2 plane loses
    ~5 bits) while the normalize stays an FMA in the input dtype, so the
    activation tensor is never copied to fp32 (26% ResNet-50 step-time
    measured on v5e for the fp32-copy formulation it replaces).
    """
    axes = tuple(i for i in range(x.ndim) if i != axis)
    m = jnp.mean(x, axis=axes, dtype=jnp.float32)
    # square in fp32 INSIDE the reduction: XLA fuses the convert into the
    # reduce (no fp32 activation copy) while avoiding the bf16-rounded
    # squares that would make E[x^2]-E[x]^2 cancellation-noise for
    # channels with |mean| >> std
    m2 = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axes)
    v = jnp.maximum(m2 - jnp.square(m), 0.0)
    out = batch_norm(x, gamma, beta, m, v, eps=eps, axis=axis)
    new_mean = decay * running_mean + (1.0 - decay) * m
    new_var = decay * running_var + (1.0 - decay) * v
    return out, new_mean, new_var


def scale_shift_act(x, scale, shift, *, alpha: float = 0.0, axis: int = 1):
    """Fused per-channel FMA + relu/leaky epilogue: ``act(x*scale+shift)``
    with ``scale``/``shift`` broadcast along ``axis`` (the bias+BN+ReLU
    block of the conv stacks, pre-folded into scale/shift by the
    caller — see ``nn.layers.fused_bn_act``). ``alpha`` is the negative
    slope: 0.0 = relu, 0.01 = the reference's leaky-relu.

    Bit-identical to ``batch_norm`` followed by the activation, and the
    only lowering: between two convolutions the TPU's compiler folds the
    FMA+select into their fusions, which a kernel's ``custom-call`` can
    take no part in (PERF.md, PR 27)."""
    shape = [1] * x.ndim
    shape[axis] = -1
    y = x * jnp.reshape(scale.astype(x.dtype), shape) \
        + jnp.reshape(shift.astype(x.dtype), shape)
    if alpha == 0.0:
        return jax.nn.relu(y)
    return jnp.where(y >= 0, y, alpha * y)


def layer_norm(x, gain, bias=None, *, axis=-1, eps: float = 1e-5):
    """Layer norm (ref: libnd4j ``layer_norm``)."""
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    m = jnp.mean(x, axis=axes, keepdims=True)
    v = jnp.var(x, axis=axes, keepdims=True)
    out = (x - m) * jax.lax.rsqrt(v + eps)
    if gain is not None:
        out = out * gain
    if bias is not None:
        out = out + bias
    return out


def rms_norm(x, gain, *, axis=-1, eps: float = 1e-6):
    """RMSNorm — TPU-era extension used by the transformer zoo models."""
    ms = jnp.mean(jnp.square(x), axis=axis, keepdims=True)
    out = x * jax.lax.rsqrt(ms + eps)
    return out * gain if gain is not None else out


def lrn(x, *, depth: int = 5, alpha: float = 1e-4, beta: float = 0.75,
        bias: float = 1.0, data_format: str = "NCHW"):
    """Local response normalization across channels (ref: libnd4j ``lrn``,
    DL4J LocalResponseNormalization; AlexNet uses this)."""
    c_axis = 1 if data_format.upper().startswith("NC") else x.ndim - 1
    sq = jnp.square(x)
    # sum over a window of `depth` channels centred at each channel
    half = depth // 2
    pad_cfg = [(0, 0)] * x.ndim
    pad_cfg[c_axis] = (half, depth - 1 - half)
    padded = jnp.pad(sq, pad_cfg)
    window = [1] * x.ndim
    window[c_axis] = depth
    summed = jax.lax.reduce_window(padded, 0.0, jax.lax.add,
                                   tuple(window), (1,) * x.ndim,
                                   [(0, 0)] * x.ndim)
    return x / (bias + alpha * summed) ** beta


def dropout(x, rate: float, rng_key, *, train: bool = True):
    """Inverted dropout (ref: DL4J ``Dropout`` — NOTE the reference's
    Dropout(p) keeps with probability p; here ``rate`` is the DROP
    probability, the modern convention; the nn layer adapts)."""
    if not train or rate <= 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng_key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def alpha_dropout(x, rate: float, rng_key, *, train: bool = True):
    """SELU-compatible alpha dropout (ref: DL4J ``AlphaDropout``)."""
    if not train or rate <= 0.0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng_key, keep, x.shape)
    a = (keep + alpha_p ** 2 * keep * (1 - keep)) ** -0.5
    b = -a * alpha_p * (1 - keep)
    return (a * jnp.where(mask, x, alpha_p) + b).astype(x.dtype)


def gaussian_dropout(x, rate: float, rng_key, *, train: bool = True):
    """(ref: DL4J ``GaussianDropout``)"""
    if not train or rate <= 0.0:
        return x
    stddev = (rate / (1.0 - rate)) ** 0.5
    noise = 1.0 + stddev * jax.random.normal(rng_key, x.shape, x.dtype)
    return x * noise


def gaussian_noise(x, stddev: float, rng_key, *, train: bool = True):
    """(ref: DL4J ``GaussianNoise``)"""
    if not train or stddev <= 0.0:
        return x
    return x + stddev * jax.random.normal(rng_key, x.shape, x.dtype)
