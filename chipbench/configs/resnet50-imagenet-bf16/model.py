"""ResNet-50 as the program runs it: the zoo's ``ComputationGraph`` with the
benchmark's weights put in, and this configuration's sizes as functions.
"""



def _convs(cfg):
    """Every convolution as (name, c_in, c_out, kernel, stride, pad,
    out_hw), and the dense head, from the configuration's sizes."""
    c, hw, _ = cfg["input_shape"]
    stem = cfg["stem"]
    out = []
    size = (hw + 2 * stem["pad"] - stem["kernel"]) // stem["stride"] + 1
    out.append(("stem_conv", c, stem["channels"], stem["kernel"],
                stem["stride"], stem["pad"], size))
    size = (size + 2 * stem["pool_pad"] - stem["pool_kernel"]) \
        // stem["pool_stride"] + 1
    c_in = stem["channels"]
    for si, (blocks, mid, c_out, first_stride) in enumerate(cfg["stages"]):
        for bi in range(blocks):
            stride = first_stride if bi == 0 else 1
            o = (size - 1) // stride + 1
            p = f"s{si}b{bi}_"
            out.append((p + "c1", c_in, mid, 1, stride, 0, o))
            out.append((p + "c2", mid, mid, 3, 1, 1, o))
            out.append((p + "c3", mid, c_out, 1, 1, 0, o))
            if bi == 0:
                out.append((p + "sc", c_in, c_out, 1, stride, 0, o))
            c_in, size = c_out, o
    return out, c_in


_BN_OF = {"stem_conv": "stem_bn", "c1": "bn1", "c2": "bn2", "c3": "bn3",
          "sc": "scbn"}


def _bn_name(conv: str) -> str:
    if conv == "stem_conv":
        return "stem_bn"
    pref, leaf = conv.rsplit("_", 1)
    return f"{pref}_{_BN_OF[leaf]}"


def param_spec(cfg):
    convs, c_last = _convs(cfg)
    spec = []
    for name, c_in, c_out, k, _s, _p, _o in convs:
        spec.append((f"{name}/W", (c_out, c_in, k, k), "he", c_in * k * k))
        spec.append((f"{name}/b", (c_out,), "small", 0))
        bn = _bn_name(name)
        # a block's last BatchNorm starts small (Goyal et al. 2017 start it
        # at zero): each block then opens near the identity, and rounding
        # is not amplified block by block into a different gradient
        last = name.endswith("_c3")
        spec.append((f"{bn}/gamma", (c_out,),
                     "gamma_last" if last else "gamma", 0))
        spec.append((f"{bn}/beta", (c_out,), "small", 0))
    n = cfg["num_classes"]
    spec.append(("fc/W", (c_last, n), "he", c_last))
    spec.append(("fc/b", (n,), "small", 0))
    return spec


def flops_per_sample(cfg) -> float:
    """Forward FLOPs of every convolution and the dense head for one
    image: 2*K*K*Cin*Cout*oH*oW each (7.72 GFLOP at 224x224)."""
    convs, c_last = _convs(cfg)
    f = sum(2 * k * k * c_in * c_out * o * o
            for _n, c_in, c_out, k, _s, _p, o in convs)
    return float(f + 2 * c_last * cfg["num_classes"])


def n_matmuls(cfg) -> int:
    """Convolutions and dense layers a forward pass runs."""
    return len(_convs(cfg)[0]) + 1


def build(cfg, weights, chips: int = 1):
    """The program's net, set as the configuration says, holding
    ``weights``."""
    from deeplearning4j_tpu.models import zoo
    net = zoo.ResNet50(num_classes=cfg["num_classes"],
                       input_shape=tuple(cfg["input_shape"])).init()
    put_weights(net, weights)
    return net


def put_weights(net, weights):
    import jax
    tree = {}
    for name, w in weights.items():
        layer, leaf = name.split("/")
        tree.setdefault(layer, {})[leaf] = w
    have = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)),
                                  {k: v for k, v in net._params.items() if v})
    want = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), tree)
    if have != want:
        raise ValueError("the zoo's ResNet50 parameters do not match this "
                         "configuration's param_spec")
    for layer, leaves in tree.items():
        net._params[layer] = dict(leaves)


def read_leaves(net, what: str):
    """``{name: array}`` of the program's parameters (``"params"``) or of
    Adam's first moment (``"m"``), still on the device."""
    out = {}
    for layer, leaves in net._params.items():
        for leaf in leaves:
            if what == "params":
                out[f"{layer}/{leaf}"] = net._params[layer][leaf]
            else:
                out[f"{layer}/{leaf}"] = net._opt_state[layer][leaf][what]
    return out
