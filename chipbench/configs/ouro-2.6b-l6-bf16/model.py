"""Ouro as the program runs it: the zoo's ``ComputationGraph`` (a
``LoopVertex`` over ``num_layers`` decoder layers) with the benchmark's
weights put in, and this configuration's sizes as functions.
"""

#: leaves of one decoder layer as (program layer, leaf); the benchmark
#: holds each as ONE array over the layers, ``stack/<layer>.<leaf>`` of
#: shape [num_layers, ...], so that the plain reference can scan over the
#: layers (its program is one layer long, not 24)
_LAYER_LEAVES = (("n1", "gain"), ("attn", "Wq"), ("attn", "Wk"),
                 ("attn", "Wv"), ("attn", "Wo"), ("n2", "gain"),
                 ("n3", "gain"), ("mlp", "Wg"), ("mlp", "Wu"),
                 ("mlp", "Wd"), ("n4", "gain"))
STACK = "stack/"


def _sizes(cfg):
    d = cfg["hidden_size"]
    return (d, cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"])


def param_spec(cfg):
    d, e, f, v = _sizes(cfg)
    n = cfg["num_layers"]
    shape = {"Wq": (d, e), "Wk": (d, e), "Wv": (d, e), "Wo": (e, d),
             "Wg": (d, f), "Wu": (d, f), "Wd": (f, d), "gain": (d,)}
    # an embedding row is what a token hands to the first norm: its scale
    # is He's for a fan-in of the hidden size
    spec = [("embed/W", (v, d), "he", d)]
    for layer, leaf in _LAYER_LEAVES:
        if leaf == "gain":
            # the norm AFTER a sub-block starts small: each block then
            # opens near the identity, and rounding is not amplified
            # through 24 layer applications into another gradient
            kind, fan_in = ("gamma_last" if layer in ("n2", "n4")
                            else "gamma"), 0
        else:
            kind, fan_in = "he", shape[leaf][0]
        spec.append((f"{STACK}{layer}.{leaf}", (n,) + shape[leaf], kind,
                     fan_in))
    spec.append(("fnorm/gain", (d,), "gamma", 0))
    spec.append(("lm/W", (d, v), "he", d))
    spec.append(("lm/gate_w", (d,), "small", 0))
    spec.append(("lm/gate_b", (1,), "small", 0))
    return spec


def layer_matmul_params(cfg) -> int:
    """Weights of one decoder layer's seven matrix products (51,380,224
    as published)."""
    d, e, f, _v = _sizes(cfg)
    return 3 * d * e + e * d + 3 * d * f


def attention_flops(cfg) -> float:
    """Required forward FLOPs of ONE layer application's attention core
    for one sequence: the causal half of ``q k^T`` and of the weighted
    sum, ``2 * S^2 * heads * head_dim`` (6.87e10 at S = 4,096); the
    masked half of the square is nobody's requirement."""
    s = cfg["seq_len"]
    return 2.0 * s * s * cfg["num_attention_heads"] * cfg["head_dim"]


def attention_bytes(cfg, itemsize: int = 2) -> float:
    """HBM bytes one application's attention core has to move forward: q,
    k, v in and the weighted sum out, once each, in the compute dtype."""
    return 4.0 * cfg["seq_len"] * cfg["num_attention_heads"] \
        * cfg["head_dim"] * itemsize


def layer_applications(cfg) -> int:
    return cfg["num_layers"] * cfg["total_ut_steps"]


def flops_per_sample(cfg) -> float:
    """Forward FLOPs of one sequence: every layer application's seven
    matrix products and causal attention core, and a head after every
    pass (15.05 TFLOP at S = 4,096 with 6 layers and 4 passes)."""
    s = cfg["seq_len"]
    d, _e, _f, v = _sizes(cfg)
    per_layer = 2.0 * s * layer_matmul_params(cfg) + attention_flops(cfg)
    return layer_applications(cfg) * per_layer \
        + cfg["total_ut_steps"] * 2.0 * s * d * v


def n_matmuls(cfg) -> int:
    """Matrix products a forward pass executes: seven weight products and
    the attention core's two a layer application, one head a pass (the
    gate is a matrix-vector product and is not counted)."""
    return layer_applications(cfg) * 9 + cfg["total_ut_steps"]


def build(cfg, weights, chips: int = 1):
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.train import updaters
    u = cfg["updater"]
    net = zoo.Ouro(
        num_layers=cfg["num_layers"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], total_ut_steps=cfg["total_ut_steps"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        seq_len=cfg["seq_len"], beta=cfg["beta"],
        updater=updaters.Adam(u["lr"], beta1=u["beta1"], beta2=u["beta2"],
                              epsilon=u["eps"])).conf_builder()
    put_weights(net, weights)
    return net


def put_weights(net, weights):
    """The net's parameters ARE the benchmark's arrays, a stacked leaf
    cut into its layers (no ``init()``: it would draw and then drop 2 GB
    at the real size); layer states as ``initialize`` declares them."""
    import jax
    tree = {}
    for name, w in weights.items():
        if name.startswith(STACK):
            layer, leaf = name[len(STACK):].split(".")
            for i in range(w.shape[0]):
                tree.setdefault(f"l{i}_{layer}", {})[leaf] = w[i]
        else:
            layer, leaf = name.split("/")
            tree.setdefault(layer, {})[leaf] = w
    net._params, net._states = {}, {}
    for node in net.conf.topo:
        if node.kind != "layer":
            continue
        want = node.obj.param_shapes()
        have = {k: tuple(a.shape) for k, a in tree.get(node.name, {}).items()}
        if have != {k: tuple(s) for k, s in want.items()}:
            raise ValueError(
                f"{node.name}: the zoo's Ouro wants {want}, this "
                f"configuration's param_spec gives {have}")
        net._params[node.name] = dict(tree.get(node.name, {}))
        net._states[node.name] = jax.tree_util.tree_map(
            lambda a: jax.numpy.zeros(a.shape, a.dtype),
            jax.eval_shape(node.obj.initialize, jax.random.PRNGKey(0))[1])
    net._initialized = True


def read_leaves(net, what: str):
    """``{name: array}`` of the program's parameters (``"params"``) or of
    Adam's first moment (``"m"``), still on the device, the looped layers'
    leaves stacked as ``param_spec`` names them."""
    import jax.numpy as jnp

    def leaf_of(layer, leaf):
        return net._params[layer][leaf] if what == "params" \
            else net._opt_state[layer][leaf][what]
    n = sum(1 for name in net._params if name.endswith("_attn"))
    out = {}
    for layer, leaf in _LAYER_LEAVES:
        out[f"{STACK}{layer}.{leaf}"] = jnp.stack(
            [leaf_of(f"l{i}_{layer}", leaf) for i in range(n)])
    for layer, leaves in net._params.items():
        if not layer.startswith("l") or "_" not in layer:
            for leaf in leaves:
                out[f"{layer}/{leaf}"] = leaf_of(layer, leaf)
    return out
