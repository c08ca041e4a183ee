"""On-device image augmentation compiled into the jitted train step.

The host pipeline (data/pipeline.py) ships raw decoded uint8 NCHW; the
crop/flip/normalize work the reference runs on host threads (the
``ImageTransform`` hierarchy) happens HERE, as a prelude fused into the
compiled train step — the host never pays a float conversion or an
augment pass, and the H2D link carries 1/4 the bytes. Augmentation RNG
derives from ``fold_in(PRNGKey(aug_seed), t)`` on the device-resident
step counter, so it is bit-reproducible per seed, exact-resume stable,
and identical inside a ``lax.scan`` megastep (each scanned step sees
its own ``t``).

Fixed shapes: every op maps a ``[B, C, H, W]`` batch to a fixed output
shape (random crop picks a random *offset* into a fixed ``[H-c, W-c]``
window rather than the host path's variable-margin crop), so the train
step compiles exactly once — the zero-steady-state-recompile property
the W201 churn detector pins.

Use :meth:`DeviceAugmentation.from_transforms` to compile the
``ImageTransform`` presets that have device kernels — Flip, Crop, Scale,
Brightness, ColorConversion, Resize (``jax.image.resize`` bilinear), and
Rotate (inverse-mapped bilinear gather) all do. Transforms without one
(probabilistic/shuffled pipelines) raise — keep those on the host path
(``decode(transform=...)``), which remains fully supported::

    aug = (DeviceAugmentation(seed=7)
           .crop(4)                  # random 4px crop -> [H-4, W-4]
           .flip(1)                  # deterministic horizontal flip
           .scale_to(0.0, 1.0))      # pixel [0,255] -> [0,1] on device
    net.fit(it, epochs=5, steps_per_dispatch=4, augment=aug)

    # or compile host presets:
    aug = DeviceAugmentation.from_transforms(
        [FlipImageTransform(1), ScaleImageTransform(1 / 255.0)], seed=7)

Deterministic ops (fixed-mode flip, scale, normalize, grayscale) are
numerically identical to their host counterparts on uint8 input — the
loss-parity tests pin this. Random ops (crop, random flip, random
brightness) draw from the device PRNG and therefore differ draw-by-draw
from the host numpy RNG while matching its distribution (the crop
differs as noted above).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.profiler.stepprogram import AUGMENT_SCOPE


class DeviceAugmentation:
    """A chain of fixed-shape augmentation ops applied inside the jitted
    train step. Chainable builder; :meth:`signature` is a hashable
    identity the networks use to know when a recompile is actually
    needed (same-signature augmentations reuse the compiled step)."""

    def __init__(self, seed: int = 0):
        self.seed = int(seed)
        self._ops: List[Tuple[Tuple, callable]] = []   # (sig, fn)

    # ------------------------------------------------------------ builders
    def flip(self, mode: int = 1) -> "DeviceAugmentation":
        """Deterministic flip (host ``FlipImageTransform`` codes):
        1 = horizontal, 0 = vertical, -1 = both."""
        if mode not in (0, 1, -1):
            raise ValueError(f"flip mode must be 0, 1 or -1, got {mode}")

        def op(x, key):
            if mode in (1, -1):
                x = x[..., ::-1]
            if mode in (0, -1):
                x = x[..., ::-1, :]
            return x
        self._ops.append((("flip", mode), op))
        return self

    def random_flip(self) -> "DeviceAugmentation":
        """Per-image random flip: one of {vertical, horizontal, both},
        uniformly (host ``FlipImageTransform(None)`` semantics)."""

        def op(x, key):
            mode = jax.random.randint(key, (x.shape[0],), 0, 3)
            hor = ((mode == 1) | (mode == 2))[:, None, None, None]
            ver = ((mode == 0) | (mode == 2))[:, None, None, None]
            x = jnp.where(hor, x[..., ::-1], x)
            return jnp.where(ver, x[..., ::-1, :], x)
        self._ops.append((("random_flip",), op))
        return self

    def crop(self, crop: int) -> "DeviceAugmentation":
        """Per-image random crop to the fixed shape ``[H-crop, W-crop]``
        (random offset in ``[0, crop]`` per side). Fixed output shape is
        what keeps the compiled step signature stable; the host
        ``CropImageTransform`` draws each margin independently and emits
        variable shapes, which would recompile every step."""
        c = int(crop)
        if c < 0:
            raise ValueError("crop must be >= 0")

        def op(x, key):
            b, ch, h, w = x.shape
            off = jax.random.randint(key, (b, 2), 0, c + 1)

            def one(img, o):
                return jax.lax.dynamic_slice(img, (0, o[0], o[1]),
                                             (ch, h - c, w - c))
            return jax.vmap(one)(x, off)
        self._ops.append((("crop", c), op))
        return self

    def scale(self, factor: float) -> "DeviceAugmentation":
        """Multiply pixel values (host ``ScaleImageTransform``)."""
        f = float(factor)
        self._ops.append((("scale", f), lambda x, key: x * f))
        return self

    def scale_to(self, a: float = 0.0, b: float = 1.0) -> "DeviceAugmentation":
        """Pixel ``[0, 255] -> [a, b]`` (host ``ImagePreProcessingScaler``
        moved on device)."""
        a, b = float(a), float(b)
        self._ops.append((("scale_to", a, b),
                          lambda x, key: x / 255.0 * (b - a) + a))
        return self

    def normalize(self, mean: Sequence[float],
                  std: Sequence[float]) -> "DeviceAugmentation":
        """Per-channel ``(x - mean) / std`` (the NormalizerStandardize
        image case, fused on device)."""
        m = tuple(float(v) for v in mean)
        s = tuple(float(v) for v in std)

        def op(x, key):
            mm = jnp.asarray(m, x.dtype).reshape(1, -1, 1, 1)
            ss = jnp.asarray(s, x.dtype).reshape(1, -1, 1, 1)
            return (x - mm) / ss
        self._ops.append((("normalize", m, s), op))
        return self

    def brightness(self, delta: float,
                   random: bool = False) -> "DeviceAugmentation":
        """Add ``delta`` (or a per-image uniform draw in ``[-delta,
        delta]``) and clip to ``[0, 255]`` (host ``BrightnessTransform``)."""
        d = float(delta)

        def op(x, key):
            if random:
                dd = jax.random.uniform(key, (x.shape[0], 1, 1, 1),
                                        minval=-d, maxval=d)
            else:
                dd = d
            return jnp.clip(x + dd, 0.0, 255.0)
        self._ops.append((("brightness", d, bool(random)), op))
        return self

    def resize(self, height: int, width: int) -> "DeviceAugmentation":
        """Bilinear resize to a fixed ``[height, width]`` (host
        ``ResizeImageTransform`` moved on device via ``jax.image.resize``
        — same bilinear family as the host PIL kernel; edge-sample
        weights differ by implementation, so parity is distributional,
        like the random ops)."""
        h, w = int(height), int(width)
        if h <= 0 or w <= 0:
            raise ValueError("resize dims must be positive")

        def op(x, key):
            b, c = x.shape[0], x.shape[1]
            return jax.image.resize(x, (b, c, h, w), "linear")
        self._ops.append((("resize", h, w), op))
        return self

    def rotate(self, angle: float, random: bool = False
               ) -> "DeviceAugmentation":
        """Rotate about the image center by ``angle`` degrees (or a
        per-image uniform draw in ``[-angle, angle]`` when ``random``) —
        host ``RotateImageTransform`` moved on device: inverse-mapped
        coordinate grid + bilinear gather, out-of-bounds filled with 0
        (PIL's fill). Output shape is unchanged, so the compiled step
        signature stays stable."""
        a = float(angle)

        def op(x, key):
            b, c, h, w = x.shape
            if random:
                deg = jax.random.uniform(key, (b,), minval=-a, maxval=a)
            else:
                deg = jnp.full((b,), a, jnp.float32)
            # PIL rotates counter-clockwise; inverse-map each output
            # pixel back into the source image (hence the negated angle)
            rad = -deg * (jnp.pi / 180.0)
            cy, cx = (h - 1) / 2.0, (w - 1) / 2.0
            yy = jnp.arange(h, dtype=jnp.float32)[:, None] - cy   # [H,1]
            xx = jnp.arange(w, dtype=jnp.float32)[None, :] - cx   # [1,W]
            cos = jnp.cos(rad)[:, None, None]
            sin = jnp.sin(rad)[:, None, None]
            sy = cos * yy - sin * xx + cy                         # [B,H,W]
            sx = sin * yy + cos * xx + cx

            y0 = jnp.floor(sy)
            x0 = jnp.floor(sx)
            wy = sy - y0
            wx = sx - x0
            y0i = y0.astype(jnp.int32)
            x0i = x0.astype(jnp.int32)

            def corner(img, yi, xi):
                """img [C,H,W], yi/xi [H,W] -> gathered [C,H,W] with
                out-of-bounds as 0."""
                inb = (yi >= 0) & (yi < h) & (xi >= 0) & (xi < w)
                yc = jnp.clip(yi, 0, h - 1)
                xc = jnp.clip(xi, 0, w - 1)
                g = img[:, yc, xc]
                return jnp.where(inb[None], g, 0.0)

            def one(img, y0i, x0i, wy, wx):
                tl = corner(img, y0i, x0i)
                tr = corner(img, y0i, x0i + 1)
                bl = corner(img, y0i + 1, x0i)
                br = corner(img, y0i + 1, x0i + 1)
                top = tl * (1 - wx) + tr * wx
                bot = bl * (1 - wx) + br * wx
                return top * (1 - wy) + bot * wy
            return jax.vmap(one)(x, y0i, x0i, wy, wx).astype(x.dtype)
        self._ops.append((("rotate", a, bool(random)), op))
        return self

    def grayscale(self) -> "DeviceAugmentation":
        """RGB -> luma, kept 3-channel (host ``ColorConversionTransform``)."""

        def op(x, key):
            if x.shape[1] != 3:
                return x
            g = (0.299 * x[:, 0] + 0.587 * x[:, 1] + 0.114 * x[:, 2])
            return jnp.stack([g, g, g], axis=1)
        self._ops.append((("grayscale",), op))
        return self

    # ----------------------------------------------------- host-preset map
    @classmethod
    def from_transforms(cls, transforms, seed: int = 0
                        ) -> "DeviceAugmentation":
        """Compile host ``ImageTransform`` presets (and
        ``ImagePreProcessingScaler``) into a device chain. Raises
        ``ValueError`` for a transform with no device kernel — catch it
        and keep that transform on the host path
        (``decode(transform=...)``), which stays fully supported."""
        from deeplearning4j_tpu.data.dataset import ImagePreProcessingScaler
        from deeplearning4j_tpu.data import image as _img
        aug = cls(seed=seed)

        def add(t):
            if isinstance(t, _img.PipelineImageTransform):
                if t.shuffle or any(p < 1.0 for _, p in t.steps):
                    raise ValueError(
                        "PipelineImageTransform with shuffle/probabilistic "
                        "steps has no device kernel (the device chain is "
                        "unconditional); keep it on the host path")
                for sub, _ in t.steps:
                    add(sub)
            elif isinstance(t, _img.FlipImageTransform):
                if t.mode is None:
                    aug.random_flip()
                else:
                    aug.flip(t.mode)
            elif isinstance(t, _img.CropImageTransform):
                aug.crop(t.crop)
            elif isinstance(t, _img.ResizeImageTransform):
                aug.resize(t.height, t.width)
            elif isinstance(t, _img.RotateImageTransform):
                aug.rotate(t.angle, t.random)
            elif isinstance(t, _img.ScaleImageTransform):
                aug.scale(t.scale)
            elif isinstance(t, _img.BrightnessTransform):
                aug.brightness(t.delta, t.random)
            elif isinstance(t, _img.ColorConversionTransform):
                aug.grayscale()
            elif isinstance(t, ImagePreProcessingScaler):
                aug.scale_to(t.a, t.b)
            else:
                raise ValueError(
                    f"{type(t).__name__} has no device kernel; keep it on "
                    f"the host path (decode(transform=...))")
        for t in (transforms if isinstance(transforms, (list, tuple))
                  else [transforms]):
            add(t)
        return aug

    # -------------------------------------------------------------- apply
    def signature(self) -> Tuple:
        """Hashable identity: op chain + seed. Two augmentations with
        equal signatures compile to the same program."""
        return (self.seed,) + tuple(sig for sig, _ in self._ops)

    def apply(self, x, key):
        """Run the chain on one batch inside the compiled step: uint8
        input is cast to float32 first (fused by XLA into the chain and
        the consuming conv), each op gets ``fold_in(key, op_index)``."""
        if x.dtype == jnp.uint8:
            x = x.astype(jnp.float32)
        for i, (_, op) in enumerate(self._ops):
            x = op(x, jax.random.fold_in(key, i))
        return x

    def step_key(self, t):
        """The per-step augmentation key: ``fold_in(PRNGKey(seed), t)``
        on the device-resident iteration counter — reproducible per seed,
        independent of the dropout stream."""
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), t)

    def output_hw(self, height: int, width: int) -> Tuple[int, int]:
        """Static output spatial dims for declared input dims (crops
        shrink them, resizes replace them) — what the model's InputType
        should declare."""
        for sig, _ in self._ops:
            if sig[0] == "crop":
                height, width = height - sig[1], width - sig[1]
            elif sig[0] == "resize":
                height, width = sig[1], sig[2]
        return height, width

    def __repr__(self):
        ops = ", ".join(".".join(map(str, sig)) for sig, _ in self._ops)
        return f"DeviceAugmentation(seed={self.seed}, ops=[{ops}])"


def maybe_augment(augment: Optional[DeviceAugmentation], x, t):
    """The train-step prelude hook both network classes call: identity
    when no augmentation is attached, else the seeded device chain.
    Only 4-D (NCHW image) inputs are augmented — a ComputationGraph with
    mixed inputs augments its image inputs and passes the rest through."""
    if augment is None or getattr(x, "ndim", 0) != 4:
        return x
    with jax.named_scope(AUGMENT_SCOPE):
        return augment.apply(x, augment.step_key(t))
