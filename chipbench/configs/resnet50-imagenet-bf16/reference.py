"""ResNet-50 (He et al. 2015, Table 1, 50-layer), plain float32 reference.

Bottleneck blocks 1x1 -> 3x3 -> 1x1 with BatchNorm after every convolution,
a projection shortcut on each stage's first block, the stage's stride on
the first 1x1 (v1, as DL4J's zoo builds it), global average pooling and a
1000-way softmax cross-entropy. Each block is rematerialised in the
backward pass so that batch 256 in float32 fits beside nothing else on a
16 GB chip; that changes no value.
"""

import jax

from chipbench.refnn import scale_pixels


def _block(ops, eps, stride, has_proj, p, x):
    def cbn(x, conv, bn, stride=1, pad=0):
        y = ops.conv(x, p[conv + "/W"], p[conv + "/b"], stride, pad)
        return ops.batch_norm(y, p[bn + "/gamma"], p[bn + "/beta"], eps)

    y = ops.relu(cbn(x, "c1", "bn1", stride))
    y = ops.relu(cbn(y, "c2", "bn2", 1, 1))
    y = cbn(y, "c3", "bn3")
    shortcut = cbn(x, "sc", "scbn", stride) if has_proj else x
    return ops.relu(y + shortcut)


def make_loss(cfg):
    """``loss(params, x_u8, onehot, ops)`` for this configuration."""
    eps = cfg["batch_norm"]["eps"]
    stem = cfg["stem"]
    stages = [tuple(s) for s in cfg["stages"]]

    def loss(params, x_u8, onehot, ops):
        x = scale_pixels(x_u8)
        x = ops.conv(x, params["stem_conv/W"], params["stem_conv/b"],
                     stem["stride"], stem["pad"])
        x = ops.relu(ops.batch_norm(x, params["stem_bn/gamma"],
                                    params["stem_bn/beta"], eps))
        x = ops.max_pool(x, stem["pool_kernel"], stem["pool_stride"],
                         stem["pool_pad"], stem["pool_pad"])
        for si, (blocks, _mid, _out, first_stride) in enumerate(stages):
            for bi in range(blocks):
                pref = f"s{si}b{bi}_"
                p = {k[len(pref):]: v for k, v in params.items()
                     if k.startswith(pref)}
                stride = first_stride if bi == 0 else 1
                block = jax.checkpoint(
                    lambda p, x, stride=stride, proj=(bi == 0):
                    _block(ops, eps, stride, proj, p, x))
                x = block(p, x)
        x = ops.global_avg_pool(x)
        logits = ops.dense(x, params["fc/W"], params["fc/b"])
        return ops.softmax_xent(logits, onehot)
    return loss
