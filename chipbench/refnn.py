"""Plain reference arithmetic for the benchmark's configurations.

Straightforward ``jax.numpy`` in float32 with every matmul and
convolution at ``Precision.HIGHEST``: no kernels, no layout tricks, no
fusion seams, nothing imported from the program. Each configuration's
``reference.py`` writes its forward pass and loss with these functions;
``train_steps`` drives them through the first update steps.

``Ops("bf16")`` is a witness, not a control: operands and every
convolution's, BatchNorm's and dense layer's output rounded to bfloat16,
which is where the configurations' policy rounds; it shows how far bf16
alone moves each number compared. ``Ops("fp8")`` is the control of "How
correct is decided", the step that would tempt a later PR: the bf16
arithmetic with every matmul/convolution operand rounded to
``float8_e4m3fn`` under a per-tensor scale (amax / 448), the nearest
precision below the bf16 the configurations state. Gradients pass straight
through the operand rounding, so the backward matmuls see the rounded
operands too.

Departures from the published descriptions, all following DL4J (the system
the program re-implements): BatchNorm uses the biased batch variance;
Adam folds both bias corrections into the step size,
``lr*sqrt(1-b2^t)/(1-b1^t) * m/(sqrt(v)+eps)`` (Kingma & Ba, section 2,
the "efficient" form).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8_round(a):
    """Round to float8_e4m3fn under a per-tensor scale; straight-through
    gradient."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return a + lax.stop_gradient(q - a)


@jax.custom_vjp
def _bf16_round(a):
    """Round to bfloat16, the incoming gradient too."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


_bf16_round.defvjp(lambda a: (_bf16_round(a), None),
                   lambda _, g: (_bf16_round(g),))


class Ops:
    """The arithmetic at one precision: ``"f32"`` (the reference),
    ``"fp8"`` (the control) or ``"bf16"`` (a witness)."""

    def __init__(self, precision: str = "f32"):
        if precision not in ("f32", "fp8", "bf16"):
            raise ValueError(f"unknown reference precision {precision!r}")
        self.precision = precision

    def _operand(self, a):
        a = a.astype(jnp.float32)
        if self.precision == "fp8":
            return _fp8_round(a)
        return self._stored(a)

    def _stored(self, a):
        return a if self.precision == "f32" else _bf16_round(a)

    def conv(self, x, w, b=None, stride=1, pad=0):
        """NCHW input, OIHW weights, symmetric explicit padding."""
        out = lax.conv_general_dilated(
            self._operand(x), self._operand(w), (stride, stride),
            [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)
        if b is not None:
            out = out + b.reshape(1, -1, 1, 1)
        return self._stored(out)

    def dense(self, x, w, b=None):
        out = jnp.matmul(self._operand(x), self._operand(w),
                         precision=HIGHEST)
        return self._stored(out if b is None else out + b)

    def batch_norm(self, x, gamma, beta, eps):
        """Training-mode BatchNorm over N, H, W of an NCHW tensor."""
        mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
        xhat = (x - mean) / jnp.sqrt(var + eps)
        return self._stored(xhat * gamma.reshape(1, -1, 1, 1)
                            + beta.reshape(1, -1, 1, 1))

    @staticmethod
    def relu(x):
        return jnp.maximum(x, 0.0)

    @staticmethod
    def leaky(x, alpha=0.01):
        return jnp.where(x >= 0, x, alpha * x)

    @staticmethod
    def max_pool(x, k, stride, pad_lo=0, pad_hi=0):
        return lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 1, k, k), (1, 1, stride, stride),
            [(0, 0), (0, 0), (pad_lo, pad_hi), (pad_lo, pad_hi)])

    @staticmethod
    def global_avg_pool(x):
        return jnp.mean(x, axis=(2, 3))

    @staticmethod
    def softmax_xent(logits, onehot):
        """Mean over the batch of -sum_c y_c log softmax(z)_c."""
        logp = jax.nn.log_softmax(logits, axis=-1)
        return jnp.mean(-jnp.sum(onehot * logp, axis=-1))


def scale_pixels(x_u8):
    """uint8 pixels to [0, 1], as the cells' on-device scaling does."""
    return x_u8.astype(jnp.float32) / 255.0


def adam_step(params, grads, m, v, t, hp):
    """One Adam update on flat ``{name: array}`` dicts; ``t`` counts from
    1. Returns (params, m, v)."""
    b1, b2, eps, lr = hp["beta1"], hp["beta2"], hp["eps"], hp["lr"]
    alpha = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    new_p, new_m, new_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        new_m[k] = b1 * m[k] + (1.0 - b1) * g
        new_v[k] = b2 * v[k] + (1.0 - b2) * jnp.square(g)
        new_p[k] = p - alpha * new_m[k] / (jnp.sqrt(new_v[k]) + eps)
    return new_p, new_m, new_v


@functools.lru_cache(maxsize=None)
def _jitted_step(loss_fn, precision: str, hp_items: tuple):
    ops, hp = Ops(precision), dict(hp_items)

    def step(params, m, v, t, x_u8, y):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, x_u8, y, ops))(params)
        new_p, new_m, new_v = adam_step(params, grads, m, v, t, hp)
        return new_p, new_m, new_v, loss, grads
    return jax.jit(step, donate_argnums=(0, 1, 2))


def train_steps(loss_fn, params, batches, hp, precision: str = "f32",
                rows=None, devices=None):
    """Follow the first ``len(batches)`` update steps from ``params``.

    ``devices``: where a cell runs on several chips its batch does not fit
    one, so the rows are spread over these devices and the parameters
    copied to each; the arithmetic is the same one program, which the
    compiler partitions (BatchNorm's means are over all the rows). Rows
    that do not divide among the devices stay on one.

    ``loss_fn(params, x_u8, y, ops)`` is a configuration's reference loss;
    ``rows`` (a slice) keeps only those rows of every batch, the mean taken
    over them: the planted "half of the batch left out" fault.
    Returns ``{"losses": [...], "first_grads": {name: array} (first
    step), "params": {name: array} (after the last step)}``.
    """
    params = {k: jnp.array(a, jnp.float32) for k, a in params.items()}
    place = jnp.asarray
    n_rows = len(batches[0][0][rows] if rows is not None else batches[0][0])
    if devices is not None and len(devices) > 1 \
            and n_rows % len(devices) == 0:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(list(devices), ("rows",))
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
        by_rows = NamedSharding(mesh, PartitionSpec("rows"))
        place = lambda a: jax.device_put(a, by_rows)     # noqa: E731
    m = {k: jnp.zeros_like(a) for k, a in params.items()}
    v = {k: jnp.zeros_like(a) for k, a in params.items()}
    step = _jitted_step(loss_fn, precision, tuple(sorted(hp.items())))
    losses, first_grads = [], None
    for i, (x, y) in enumerate(batches):
        if rows is not None:
            x, y = x[rows], y[rows]
        params, m, v, loss, grads = step(
            params, m, v, jnp.float32(i + 1), place(x), place(y))
        losses.append(float(loss))
        if first_grads is None:
            first_grads = grads
    return {"losses": losses, "first_grads": first_grads, "params": params}
