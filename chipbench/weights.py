"""Weights made on the device from ``--seed`` in one jitted call.

A configuration's ``model.param_spec(cfg)`` lists its leaves as
``(name, shape, kind, fan_in)``; kinds: ``he`` (normal, std
sqrt(2/fan_in)), ``he_small`` (a tenth of that: a detection head, see the
Tiny YOLO configuration), ``gamma`` (1 + 0.1 N), ``gamma_last`` (0.25 +
0.025 N: a residual block's last BatchNorm, see the ResNet-50
configuration), ``small`` (0.1 N). One normal draw covers all the leaves,
so the program is short to trace and to load. The program's
net and the plain reference are both handed these arrays, so neither takes
anything the other has made.
"""

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.lru_cache(maxsize=None)
def _maker(spec: tuple):
    sizes = [math.prod(shape) for _n, shape, _k, _f in spec]

    def make(key):
        flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, start = {}, 0
        for (name, shape, kind, fan_in), size in zip(spec, sizes):
            z = flat[start:start + size].reshape(shape)
            start += size
            if kind == "he":
                out[name] = z * (2.0 / fan_in) ** 0.5
            elif kind == "he_small":
                out[name] = z * 0.1 * (2.0 / fan_in) ** 0.5
            elif kind == "gamma":
                out[name] = 1.0 + 0.1 * z
            elif kind == "gamma_last":
                out[name] = 0.25 + 0.025 * z
            elif kind == "small":
                out[name] = 0.1 * z
            else:
                raise ValueError(f"unknown init kind {kind!r} for {name}")
        return out
    return jax.jit(make)


def make_weights(spec, seed: int):
    """``{name: float32 array}`` on the default device."""
    spec = tuple((n, tuple(s), k, f) for n, s, k, f in spec)
    return _maker(spec)(seed_key(seed))
