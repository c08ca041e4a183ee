"""Raw-jax chip-bound probes for the CNN BASELINE rows (TinyYOLO, VGG16).

Methodology: hand-write the exact train step in minimal jax,
measure it at the bench config, and vary ONE axis at a time:

  A. backbone fwd+bwd with a trivial MSE head  — the honest conv bound
  B. A + the real YOLOv2 loss                  — loss formulation cost
  C. NCHW vs NHWC layouts                      — layout/transpose cost
  D. bf16 vs fp32                              — precision cost

The framework path (zoo.TinyYOLO / zoo.VGG16 via MultiLayerNetwork.fit) is
then compared against the best raw variant; the gap is framework overhead.

FLOP accounting: per-conv 2*K*K*Cin*Cout*oH*oW, summed over the actual
architecture (NOT the nominal 3.5/15.5 GFLOP figures, which are MAC
counts). The helpers are imported from bench.py so
the probe and the shipped bench can never disagree on the basis.
Backward = 2x forward as usual.

Run: python benchmarks/probe_cnn.py [yolo|vgg] [--steps N]
"""

import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# single source of truth for FLOP accounting: bench.py at the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import PEAK_TFLOPS, darknet_tiny_flops, vgg16_flops  # noqa: E402

# darknet-tiny conv plan
DARKNET_TINY = [16, 32, 64, 128, 256, 512, 1024, 1024]
VGG16_PLAN = [(2, 64), (2, 128), (3, 256), (3, 512), (3, 512)]


# ------------------------------------------------------------------ raw nets
def _conv(x, w, stride=1, fmt="NHWC"):
    dims = (fmt, "HWIO" if fmt == "NHWC" else "OIHW", fmt)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=dims)


def _maxpool(x, k=2, s=2, fmt="NHWC", same=False, via="reduce_window"):
    if via == "slices" and k == 2 and s == 2 and not same:
        # 2x2/2 maxpool as elementwise max of 4 strided slices: the backward
        # is a fused select chain instead of XLA SelectAndScatter
        if fmt == "NHWC":
            return jnp.maximum(
                jnp.maximum(x[:, ::2, ::2], x[:, 1::2, ::2]),
                jnp.maximum(x[:, ::2, 1::2], x[:, 1::2, 1::2]))
        return jnp.maximum(
            jnp.maximum(x[:, :, ::2, ::2], x[:, :, 1::2, ::2]),
            jnp.maximum(x[:, :, ::2, 1::2], x[:, :, 1::2, 1::2]))
    if via == "slices" and k == 2 and s == 1 and same:
        # stride-1 SAME 2x2 maxpool = max of x and its +1 shifts (edge-pad)
        if fmt == "NHWC":
            xp = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)), mode="edge")
            return jnp.maximum(
                jnp.maximum(xp[:, :-1, :-1], xp[:, 1:, :-1]),
                jnp.maximum(xp[:, :-1, 1:], xp[:, 1:, 1:]))
        xp = jnp.pad(x, ((0, 0), (0, 0), (0, 1), (0, 1)), mode="edge")
        return jnp.maximum(
            jnp.maximum(xp[:, :, :-1, :-1], xp[:, :, 1:, :-1]),
            jnp.maximum(xp[:, :, :-1, 1:], xp[:, :, 1:, 1:]))
    if fmt == "NHWC":
        window, strides = (1, k, k, 1), (1, s, s, 1)
    else:
        window, strides = (1, 1, k, k), (1, 1, s, s)
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window, strides,
                                 "SAME" if same else "VALID")


def init_darknet(key, n_classes=20, n_boxes=5, fmt="NHWC", dtype=jnp.bfloat16):
    params = []
    c_in = 3
    for c_out in DARKNET_TINY:
        key, k1 = jax.random.split(key)
        shape = (3, 3, c_in, c_out) if fmt == "NHWC" else (c_out, c_in, 3, 3)
        w = jax.random.normal(k1, shape, dtype) * float(2.0 / np.sqrt(9 * c_in))
        scale = jnp.ones((c_out,), dtype)
        bias = jnp.zeros((c_out,), dtype)
        params.append((w, scale, bias))
        c_in = c_out
    key, k1 = jax.random.split(key)
    head_c = n_boxes * (5 + n_classes)
    shape = (1, 1, c_in, head_c) if fmt == "NHWC" else (head_c, c_in, 1, 1)
    params.append((jax.random.normal(k1, shape, dtype) / float(np.sqrt(c_in)),))
    return params


def darknet_fwd(params, x, fmt="NHWC", pool_via="reduce_window",
                bn_fp32=True):
    """conv+BN(inference-form scale/bias)+leaky, pools per darknet-tiny."""
    for i, (w, scale, bias) in enumerate(params[:-1]):
        x = _conv(x, w, 1, fmt)
        # batch-norm in the fused mean/var formulation (the 26% ResNet
        # finding): normalize with batch stats computed in fp32
        axes = (0, 1, 2) if fmt == "NHWC" else (0, 2, 3)
        xf = x.astype(jnp.float32) if bn_fp32 else x
        mean = jnp.mean(xf, axes, keepdims=True)
        var = jnp.mean(jnp.square(xf), axes, keepdims=True) - jnp.square(mean)
        sh = (1, 1, 1, -1) if fmt == "NHWC" else (1, -1, 1, 1)
        x = ((xf - mean) * jax.lax.rsqrt(var + 1e-5)).astype(x.dtype)
        x = x * scale.reshape(sh) + bias.reshape(sh)
        x = jnp.where(x > 0, x, 0.1 * x)
        if i < 5:
            x = _maxpool(x, 2, 2, fmt, via=pool_via)
        elif i == 5:
            x = _maxpool(x, 2, 1, fmt, same=True, via=pool_via)
    return _conv(x, params[-1][0], 1, fmt)


def yolo_loss(out, labels, anchors, fmt="NHWC", n_classes=20):
    """Same formulation as nn/objdetect.py compute_loss, on [N,H,W,B,5+C]."""
    if fmt == "NHWC":
        N, H, W, ch = out.shape
        B = anchors.shape[0]
        p = out.reshape(N, H, W, B, 5 + n_classes).astype(jnp.float32)
        p = jnp.moveaxis(p, 3, 1)  # [N,B,H,W,5+C] -> match NCHW math below
        p = jnp.moveaxis(p, 4, 2)  # [N,B,5+C,H,W]
    else:
        N, ch, H, W = out.shape
        B = anchors.shape[0]
        p = out.reshape(N, B, 5 + n_classes, H, W).astype(jnp.float32)
    pred_xy = jax.nn.sigmoid(p[:, :, 0:2])
    pred_wh = anchors[None, :, :, None, None] * jnp.exp(p[:, :, 2:4])
    pred_conf = jax.nn.sigmoid(p[:, :, 4])
    pred_cls = jax.nn.softmax(p[:, :, 5:], axis=2)

    lab_box = labels[:, 0:4]
    lab_cls = labels[:, 4:]
    obj_mask = (jnp.sum(lab_cls, axis=1) > 0).astype(jnp.float32)
    gx1, gy1, gx2, gy2 = (lab_box[:, i] for i in range(4))
    gt_w = jnp.maximum(gx2 - gx1, 1e-6)
    gt_h = jnp.maximum(gy2 - gy1, 1e-6)
    cell_x = jnp.arange(W)[None, None, :]
    cell_y = jnp.arange(H)[None, :, None]
    gt_cx = (gx1 + gx2) / 2 - cell_x
    gt_cy = (gy1 + gy2) / 2 - cell_y
    inter = jnp.minimum(anchors[:, 0][None, :, None, None], gt_w[:, None]) * \
        jnp.minimum(anchors[:, 1][None, :, None, None], gt_h[:, None])
    union = anchors[:, 0][None, :, None, None] * anchors[:, 1][None, :, None, None] \
        + (gt_w * gt_h)[:, None] - inter
    best = jnp.argmax(inter / jnp.maximum(union, 1e-9), axis=1)
    resp = jax.nn.one_hot(best, B, axis=1) * obj_mask[:, None]
    xy_loss = jnp.sum(resp[:, :, None] * jnp.square(
        pred_xy - jnp.stack([gt_cx, gt_cy], axis=1)[:, None]), axis=2)
    wh_loss = jnp.sum(resp[:, :, None] * jnp.square(
        jnp.sqrt(jnp.maximum(pred_wh, 1e-9)) -
        jnp.sqrt(jnp.stack([gt_w, gt_h], axis=1)[:, None])), axis=2)
    pcx = pred_xy[:, :, 0] + cell_x[None]
    pcy = pred_xy[:, :, 1] + cell_y[None]
    px1, px2 = pcx - pred_wh[:, :, 0] / 2, pcx + pred_wh[:, :, 0] / 2
    py1, py2 = pcy - pred_wh[:, :, 1] / 2, pcy + pred_wh[:, :, 1] / 2
    ix = jnp.maximum(0.0, jnp.minimum(px2, gx2[:, None]) - jnp.maximum(px1, gx1[:, None]))
    iy = jnp.maximum(0.0, jnp.minimum(py2, gy2[:, None]) - jnp.maximum(py1, gy1[:, None]))
    inter_a = ix * iy
    area_p = jnp.maximum(px2 - px1, 0) * jnp.maximum(py2 - py1, 0)
    iou = inter_a / jnp.maximum(area_p + (gt_w * gt_h)[:, None] - inter_a, 1e-9)
    conf_obj = jnp.square(pred_conf - jax.lax.stop_gradient(iou)) * resp
    conf_noobj = jnp.square(pred_conf) * (1.0 - resp)
    cls_loss = -jnp.sum(lab_cls[:, None] * jnp.log(jnp.maximum(pred_cls, 1e-9)),
                        axis=2) * resp
    return (5.0 * jnp.sum(xy_loss + wh_loss) + jnp.sum(conf_obj)
            + 0.5 * jnp.sum(conf_noobj) + jnp.sum(cls_loss)) / N


def _sync(out):
    """Device sync: materialize on the host a scalar that depends on the
    result."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(jnp.sum(leaf.astype(jnp.float32)))


def time_step(step, args, steps, warmup=2):
    out = None
    for _ in range(warmup):
        out = step(*args)
        args = (out[0],) + args[1:]
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = step(*args)
        args = (out[0],) + args[1:]
    _sync(out)
    return (time.perf_counter() - t0) / steps


def probe_yolo(steps=20, batch=32, hw=416):
    anchors_np = np.asarray([[1.08, 1.19], [3.42, 4.41], [6.63, 11.38],
                             [9.42, 5.11], [16.62, 10.52]], np.float32)
    fwd_flops = darknet_tiny_flops(hw)
    print(f"darknet-tiny actual fwd GFLOP/img @ {hw}: {fwd_flops/1e9:.2f}")
    grid = hw // 32
    rng = np.random.RandomState(0)
    labels = jnp.zeros((batch, 24, grid, grid), jnp.float32)
    results = {}
    for fmt in ("NHWC", "NCHW"):
        xs = (batch, hw, hw, 3) if fmt == "NHWC" else (batch, 3, hw, hw)
        x = jnp.asarray(rng.randn(*xs).astype(np.float32)).astype(jnp.bfloat16)
        params = init_darknet(jax.random.PRNGKey(0), fmt=fmt)
        anchors = jnp.asarray(anchors_np)

        def mk_loss(kind, pool_via, bn_fp32):
            def lossfn(p, x, *extra):
                out = darknet_fwd(p, x, fmt, pool_via=pool_via, bn_fp32=bn_fp32)
                if kind == "mse":
                    return jnp.mean(jnp.square(out.astype(jnp.float32)))
                return yolo_loss(out, extra[0], anchors, fmt)
            return lossfn

        variants = [
            ("mse/rw", mk_loss("mse", "reduce_window", True), ()),
            ("mse/slices", mk_loss("mse", "slices", True), ()),
            ("mse/slices/bf16bn", mk_loss("mse", "slices", False), ()),
            ("yolo/rw", mk_loss("yolo", "reduce_window", True), (labels,)),
            ("yolo/slices", mk_loss("yolo", "slices", True), (labels,)),
        ]
        for name, lossfn, extra in variants:
            # donate params: matches the framework step
            @partial(jax.jit, donate_argnums=0)
            def step(p, x, *e, _f=lossfn):
                g = jax.grad(_f)(p, x, *e)
                return jax.tree_util.tree_map(lambda a, b: a - 1e-6 * b.astype(a.dtype), p, g), 0

            fresh = jax.tree_util.tree_map(jnp.copy, params)
            dt = time_step(step, (fresh, x) + extra, steps)
            ips = batch / dt
            mfu = ips * 3 * fwd_flops / PEAK_TFLOPS
            results[f"{fmt}_{name}"] = (ips, mfu)
            print(f"  {fmt} {name:18s}: {ips:8.1f} img/s  MFU {mfu:.4f}")

        # fwd-only bound (inference-shaped): how much is backward?
        @jax.jit
        def fwd_only(p, x):
            return jnp.sum(darknet_fwd(p, x, fmt, pool_via="slices")
                           .astype(jnp.float32))
        dt = time_step(lambda p, x: (p, fwd_only(p, x)), (params, x), steps)
        ips = batch / dt
        print(f"  {fmt} {'fwd-only/slices':18s}: {ips:8.1f} img/s  "
              f"(fwd MFU {ips * fwd_flops / PEAK_TFLOPS:.4f})")
    return results


def probe_vgg(steps=12, batch=64, hw=224, n_classes=1000):
    fwd_flops = vgg16_flops(hw, n_classes)
    print(f"vgg16 actual fwd GFLOP/img @ {hw}: {fwd_flops/1e9:.2f}")
    rng = np.random.RandomState(0)
    y = jnp.asarray(np.eye(n_classes, dtype=np.float32)[
        rng.randint(0, n_classes, batch)])
    results = {}
    for fmt in ("NHWC", "NCHW"):
        xs = (batch, hw, hw, 3) if fmt == "NHWC" else (batch, 3, hw, hw)
        x = jnp.asarray(rng.randn(*xs).astype(np.float32)).astype(jnp.bfloat16)
        key = jax.random.PRNGKey(0)
        params = []
        c_in = 3
        for n_convs, c_out in VGG16_PLAN:
            for _ in range(n_convs):
                key, k1 = jax.random.split(key)
                shape = (3, 3, c_in, c_out) if fmt == "NHWC" else (c_out, c_in, 3, 3)
                params.append((jax.random.normal(k1, shape, jnp.bfloat16)
                               * float(2.0 / np.sqrt(9 * c_in)),
                               jnp.zeros((c_out,), jnp.bfloat16)))
                c_in = c_out
        size = hw // 32
        feat = c_in * size * size
        for i, (a, b) in enumerate([(feat, 4096), (4096, 4096), (4096, n_classes)]):
            key, k1 = jax.random.split(key)
            params.append((jax.random.normal(k1, (a, b), jnp.bfloat16) / float(np.sqrt(a)),
                           jnp.zeros((b,), jnp.bfloat16)))

        def fwd(p, x):
            i = 0
            for n_convs, c_out in VGG16_PLAN:
                for _ in range(n_convs):
                    w, bi = p[i]
                    i += 1
                    sh = (1, 1, 1, -1) if fmt == "NHWC" else (1, -1, 1, 1)
                    x = jnp.maximum(_conv(x, w, 1, fmt) + bi.reshape(sh), 0)
                x = _maxpool(x, 2, 2, fmt)
            if fmt == "NCHW":
                x = x.reshape(x.shape[0], -1)
            else:
                x = jnp.moveaxis(x, -1, 1).reshape(x.shape[0], -1)
            for j in range(3):
                w, bi = p[i]
                i += 1
                x = x @ w + bi
                if j < 2:
                    x = jnp.maximum(x, 0)
            return x

        def lossfn(p, x, y):
            logits = fwd(p, x).astype(jnp.float32)
            return -jnp.mean(jnp.sum(y * jax.nn.log_softmax(logits), -1))

        @partial(jax.jit, donate_argnums=0)
        def step(p, x, y):
            g = jax.grad(lossfn)(p, x, y)
            return jax.tree_util.tree_map(
                lambda a, b: a - 1e-6 * b.astype(a.dtype), p, g), 0

        dt = time_step(step, (params, x, y), steps)
        ips = batch / dt
        mfu = ips * 3 * fwd_flops / PEAK_TFLOPS
        results[fmt] = (ips, mfu)
        print(f"  {fmt}: {ips:8.1f} img/s  MFU {mfu:.4f}")
    return results


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "yolo"
    if which in ("yolo", "all"):
        probe_yolo()
    if which in ("vgg", "all"):
        probe_vgg()
