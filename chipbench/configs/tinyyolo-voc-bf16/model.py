"""Tiny YOLO as the program runs it: the zoo's ``MultiLayerNetwork`` with
the benchmark's weights put in, and this configuration's sizes as
functions.
"""


def _convs(cfg):
    """(name, c_in, c_out, kernel, out_hw) of every convolution."""
    c_in, size, _ = cfg["input_shape"]
    out = []
    for i, c_out in enumerate(cfg["backbone"]):
        out.append((f"conv{i}", c_in, c_out, 3, size))
        c_in = c_out
        if i < cfg["pooled_blocks"]:
            size //= 2
    n_out = len(cfg["anchors"]) * (5 + cfg["num_classes"])
    out.append(("head", c_in, n_out, 1, size))
    return out


def param_spec(cfg):
    spec = []
    for name, c_in, c_out, k, _o in _convs(cfg):
        # the head starts at a tenth of He's scale, as detection heads do:
        # at the full scale exp(t_w) multiplies the loss by 20 to 5,000 in
        # the first Adam(1e-3) update (reference on the chip, 12 seeds,
        # PR 25), and nothing after the first step is steady to compare
        kind = "he_small" if name == "head" else "he"
        spec.append((f"{name}/W", (c_out, c_in, k, k), kind, c_in * k * k))
        spec.append((f"{name}/b", (c_out,), "small", 0))
        if name != "head":
            bn = "bn" + name[len("conv"):]
            spec.append((f"{bn}/gamma", (c_out,), "gamma", 0))
            spec.append((f"{bn}/beta", (c_out,), "small", 0))
    return spec


def flops_per_sample(cfg) -> float:
    """Forward FLOPs of every convolution for one image (6.97 GFLOP at
    416x416 with 20 classes and 5 boxes)."""
    return float(sum(2 * k * k * c_in * c_out * o * o
                     for _n, c_in, c_out, k, o in _convs(cfg)))


def n_matmuls(cfg) -> int:
    """Convolutions a forward pass runs."""
    return len(_convs(cfg))


def _layer_index(net):
    """Benchmark layer name -> index in the program's layer list, by the
    order convolutions and BatchNorms appear."""
    from deeplearning4j_tpu.nn.layers import (BatchNormalization,
                                              ConvolutionLayer)
    convs = [i for i, l in enumerate(net.layers)
             if isinstance(l, ConvolutionLayer)]
    bns = [i for i, l in enumerate(net.layers)
           if isinstance(l, BatchNormalization)]
    index = {f"conv{j}": i for j, i in enumerate(convs[:-1])}
    index["head"] = convs[-1]
    index.update({f"bn{j}": i for j, i in enumerate(bns)})
    return index


def build(cfg, weights, chips: int = 1):
    from deeplearning4j_tpu.models import zoo
    net = zoo.TinyYOLO(num_classes=cfg["num_classes"],
                       input_shape=tuple(cfg["input_shape"])).init()
    put_weights(net, weights)
    return net


def put_weights(net, weights):
    index = _layer_index(net)
    filled = set()
    for name, w in weights.items():
        layer, leaf = name.split("/")
        i = index[layer]
        cur = net._params[i][leaf]
        if cur.shape != w.shape or cur.dtype != w.dtype:
            raise ValueError(f"{name}: the zoo's TinyYOLO holds "
                             f"{cur.shape} {cur.dtype}, not {w.shape}")
        net._params[i] = {**net._params[i], leaf: w}
        filled.add((i, leaf))
    have = {(i, leaf) for i, p in enumerate(net._params) for leaf in p}
    if have != filled:
        raise ValueError("the zoo's TinyYOLO parameters do not match this "
                         "configuration's param_spec")


def read_leaves(net, what: str):
    out = {}
    for layer, i in _layer_index(net).items():
        for leaf in net._params[i]:
            out[f"{layer}/{leaf}"] = net._params[i][leaf] \
                if what == "params" else net._opt_state[i][leaf][what]
    return out
