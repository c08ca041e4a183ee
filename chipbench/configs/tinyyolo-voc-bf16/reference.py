"""Tiny YOLO v2 for VOC (darknet ``yolov2-tiny-voc.cfg``), plain float32
reference.

Nine convolutions: eight 3x3 with BatchNorm and a leaky ReLU, the first
five each followed by a 2x2/2 max-pool, the sixth by a 2x2/1 max-pool padded
right and below, and a 1x1 head of 5 x (5 + classes) channels. The loss is
YOLOv2's as DL4J's ``Yolo2OutputLayer`` defines it (see ``assumed`` in
config.json). Each convolution block is rematerialised in the backward pass
so that batch 256 at 416x416 in float32 fits on a 16 GB chip.
"""

import jax
import jax.numpy as jnp

from chipbench.refnn import scale_pixels


def yolo2_loss(cfg, t, labels):
    """``t`` [N, B*(5+C), H, W] raw head output; ``labels`` [N, 4+C, H, W]
    (x1, y1, x2, y2 in grid units in the responsible cell, then a one-hot
    class; all zero where no object)."""
    anchors = jnp.asarray(cfg["anchors"], jnp.float32)        # [B, 2]
    n_box = anchors.shape[0]
    n, ch, gh, gw = t.shape
    n_cls = ch // n_box - 5
    t = t.reshape(n, n_box, 5 + n_cls, gh, gw)
    aw = anchors[:, 0].reshape(1, n_box, 1, 1)
    ah = anchors[:, 1].reshape(1, n_box, 1, 1)
    px, py = jax.nn.sigmoid(t[:, :, 0]), jax.nn.sigmoid(t[:, :, 1])
    pw, ph = aw * jnp.exp(t[:, :, 2]), ah * jnp.exp(t[:, :, 3])
    pconf = jax.nn.sigmoid(t[:, :, 4])
    pcls = jax.nn.softmax(t[:, :, 5:], axis=2)

    x1, y1, x2, y2 = (labels[:, i][:, None] for i in range(4))  # [N,1,H,W]
    onehot = labels[:, 4:]                                      # [N,C,H,W]
    has_obj = (jnp.sum(onehot, axis=1) > 0).astype(jnp.float32)[:, None]
    gw_, gh_ = jnp.maximum(x2 - x1, 1e-6), jnp.maximum(y2 - y1, 1e-6)
    col = jnp.arange(gw, dtype=jnp.float32).reshape(1, 1, 1, gw)
    row = jnp.arange(gh, dtype=jnp.float32).reshape(1, 1, gh, 1)
    gx, gy = (x1 + x2) / 2 - col, (y1 + y2) / 2 - row   # offset in its cell

    # the responsible anchor: best IoU with the object by shape alone
    inter = jnp.minimum(aw, gw_) * jnp.minimum(ah, gh_)
    shape_iou = inter / jnp.maximum(aw * ah + gw_ * gh_ - inter, 1e-9)
    resp = jax.nn.one_hot(jnp.argmax(shape_iou, axis=1), n_box, axis=1) \
        * has_obj                                               # [N,B,H,W]

    coord = jnp.square(px - gx) + jnp.square(py - gy) \
        + jnp.square(jnp.sqrt(jnp.maximum(pw, 1e-9)) - jnp.sqrt(gw_)) \
        + jnp.square(jnp.sqrt(jnp.maximum(ph, 1e-9)) - jnp.sqrt(gh_))

    cx, cy = px + col, py + row
    ix = jnp.maximum(0.0, jnp.minimum(cx + pw / 2, x2)
                     - jnp.maximum(cx - pw / 2, x1))
    iy = jnp.maximum(0.0, jnp.minimum(cy + ph / 2, y2)
                     - jnp.maximum(cy - ph / 2, y1))
    iou = ix * iy / jnp.maximum(pw * ph + gw_ * gh_ - ix * iy, 1e-9)
    iou = jax.lax.stop_gradient(iou)
    conf_obj = jnp.square(pconf - iou) * resp
    conf_noobj = jnp.square(pconf) * (1.0 - resp)
    cls = -jnp.sum(onehot[:, None] * jnp.log(jnp.maximum(pcls, 1e-9)),
                   axis=2) * resp
    total = (cfg["lambda_coord"] * jnp.sum(coord * resp) + jnp.sum(conf_obj)
             + cfg["lambda_noobj"] * jnp.sum(conf_noobj) + jnp.sum(cls))
    return total / n


def make_loss(cfg):
    """``loss(params, x_u8, labels, ops)`` for this configuration."""
    eps = cfg["batch_norm"]["eps"]
    slope = cfg["leaky_slope"]
    n_blocks = len(cfg["backbone"])
    pooled = cfg["pooled_blocks"]

    def loss(params, x_u8, labels, ops):
        x = scale_pixels(x_u8)
        for i in range(n_blocks):
            def block(p, x, i=i):
                y = ops.conv(x, p["W"], p["b"], 1, 1)
                y = ops.leaky(ops.batch_norm(y, p["gamma"], p["beta"], eps),
                              slope)
                if i < pooled:
                    y = ops.max_pool(y, 2, 2)
                elif i == pooled:
                    y = ops.max_pool(y, 2, 1, 0, 1)
                return y
            p = {"W": params[f"conv{i}/W"], "b": params[f"conv{i}/b"],
                 "gamma": params[f"bn{i}/gamma"],
                 "beta": params[f"bn{i}/beta"]}
            x = jax.checkpoint(block)(p, x)
        t = ops.conv(x, params["head/W"], params["head/b"], 1, 0)
        return yolo2_loss(cfg, t, labels)
    return loss
