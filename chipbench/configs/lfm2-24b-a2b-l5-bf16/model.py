"""LFM2-24B-A2B as the program runs it: the zoo's ``ComputationGraph``
with the benchmark's weights put in, and this configuration's sizes as
functions: parameters, required FLOPs and bytes of the whole step and of
each new part (what the rooflines of ``chipbench/metrics/`` divide by).

Leaves are named ``<node>/<leaf>`` after the graph's nodes; a layer's
routed experts are ONE array a leaf over the experts held, [held, ...].
The head has no leaf: it is the embedding's table."""

import math

# a tree without the gated short-convolution layer cannot run this
# configuration: it fails here, as the cell is loaded, before any weights
from deeplearning4j_tpu.nn.layers import GatedShortConvLayer  # noqa: F401


def _sizes(cfg):
    H = cfg["num_attention_heads"]
    return {"C": cfg["hidden_size"], "H": H,
            "Hk": cfg["num_key_value_heads"], "D": cfg["hidden_size"] // H,
            "K": cfg["conv_L_cache"], "F": cfg["intermediate_size"],
            "Fe": cfg["moe_intermediate_size"],
            "E": cfg["published"]["num_experts"],
            "held": len(cfg["held_experts"]),
            "k": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
            "S": cfg["seq_len"]}


def layers_of(cfg):
    """``[(node prefix, is conv, is dense)]`` of the layers held: the n-th
    is ``l<n>_`` and keeps the kind of the published layer it is."""
    return [(f"l{n}_", cfg["layer_types"][i] == "conv",
             n < cfg["num_dense_layers"])
            for n, i in enumerate(cfg["held_layers"])]


def _mixer_leaves(z, conv):
    C, D = z["C"], z["D"]
    if conv:
        return [("conv/Win", (C, 3 * C), "he", C),
                ("conv/Wc", (z["K"], C), "he", z["K"]),
                ("conv/Wout", (C, C), "he", C)]
    return [("attn/Wq", (C, z["H"] * D), "he", C),
            ("attn/Wk", (C, z["Hk"] * D), "he", C),
            ("attn/Wv", (C, z["Hk"] * D), "he", C),
            ("attn/Wo", (z["H"] * D, C), "he", z["H"] * D),
            ("attn/qn", (D,), "gamma", 0), ("attn/kn", (D,), "gamma", 0)]


def param_spec(cfg):
    """``[(name, shape, kind, fan_in)]``; kinds as ``chipbench.weights``
    has them."""
    z = _sizes(cfg)
    C = z["C"]
    spec = [("embed/W", (z["V"], C), "he", C)]
    for pre, conv, dense in layers_of(cfg):
        spec.append((f"{pre}n1/gain", (C,), "gamma", 0))
        spec += [(pre + leaf, shape, kind, fan)
                 for leaf, shape, kind, fan in _mixer_leaves(z, conv)]
        spec.append((f"{pre}n2/gain", (C,), "gamma", 0))
        if dense:
            spec += [(f"{pre}mlp/Wg", (C, z["F"]), "he", C),
                     (f"{pre}mlp/Wu", (C, z["F"]), "he", C),
                     (f"{pre}mlp/Wd", (z["F"], C), "he", z["F"])]
        else:
            held, Fe = z["held"], z["Fe"]
            spec += [(f"{pre}moe/Wr", (C, z["E"]), "he", C),
                     (f"{pre}moe/Eg", (held, C, Fe), "he", C),
                     (f"{pre}moe/Eu", (held, C, Fe), "he", C),
                     (f"{pre}moe/Ed", (held, Fe, C), "he", Fe)]
    return spec + [("fnorm/gain", (C,), "gamma", 0)]


def state_spec(cfg):
    """Layer states the seed fixes: every expert layer's 64 selection
    biases (no gradient, never updated), ``select_bias_std`` N = 0.01 N
    (``assumed.select_bias`` in the configuration's file says why not
    0.1). ``chipbench.weights`` has no kind that takes a std: ``he`` at
    fan-in ``2 / std**2`` is ``std`` N."""
    fan_in = round(2 / cfg["select_bias_std"] ** 2)
    return [(f"{layer}/select_bias", (_sizes(cfg)["E"],), "he", fan_in)
            for layer in expert_layers_of(cfg)]


def n_params(cfg) -> int:
    return sum(math.prod(s) for _n, s, _k, _f in param_spec(cfg))


# ------------------------------------------------ required work, forward
def shortconv_flops(cfg) -> float:
    """Required forward FLOPs of ONE gated short-convolution mixer for
    one token: the two projections (``C x 3C`` and ``C x C``) and the
    ``K`` taps a channel; the three gate products are not counted."""
    z = _sizes(cfg)
    return 2.0 * 4 * z["C"] ** 2 + 2.0 * z["K"] * z["C"]


def shortconv_mixers(cfg) -> int:
    return sum(1 for _p, conv, _d in layers_of(cfg) if conv)


def attention_projection_params(cfg) -> int:
    return sum(math.prod(s) for _l, s, _k, _f in
               _mixer_leaves(_sizes(cfg), False) if len(s) == 2)


def core_flops(cfg) -> float:
    """Required forward FLOPs of ONE layer application's attention core
    for one sequence: the causal half of ``q k^T`` and of the weighted
    sum over 64, ``2 * S^2 * H * D``; the masked half is nobody's
    requirement, and grouped key/value heads save bytes, not products."""
    z = _sizes(cfg)
    return 2.0 * float(z["S"]) ** 2 * z["H"] * z["D"]


def attention_applications(cfg) -> int:
    return sum(1 for _p, conv, _d in layers_of(cfg) if not conv)


def expert_product_flops(cfg, pairs: float) -> float:
    """Forward FLOPs of one expert layer's grouped products for ``pairs``
    routed (token, expert) pairs at held experts: three products of
    [pairs, C] x [C, Fe] size."""
    z = _sizes(cfg)
    return 6.0 * pairs * z["C"] * z["Fe"]


def expert_product_bytes(cfg, pairs: float, itemsize: int = 2) -> float:
    """HBM bytes one expert layer's three grouped products have to move
    forward, whatever implements them: the held experts' three matrices
    once and the pairs' rows once in and once out, in the compute dtype
    (the [pairs, Fe] tensors between the products need not reach HBM).
    A backward pass has as much to move twice over: the matrices read
    again for the input gradient, and their gradient written."""
    z = _sizes(cfg)
    weights = 3.0 * z["held"] * z["C"] * z["Fe"]
    return itemsize * (weights + 2.0 * pairs * z["C"])


def expected_pairs(cfg, batch: int = 1) -> float:
    """Routed pairs a layer's held experts meet a step of ``batch``
    sequences under uniform routing."""
    z = _sizes(cfg)
    return batch * z["S"] * z["k"] * z["held"] / z["E"]


def flops_by_part(cfg) -> dict:
    """Forward FLOPs of one sequence by part, required work only: every
    mixer's projections (and taps, and the causal core), the dense MLP,
    router and routed products (the routed ones at the EXPECTED load of
    uniform routing, ``k * held / E`` = 0.5 held experts a token: the real
    load moves a few percent a step with the router), the tied head."""
    z = _sizes(cfg)
    S, C = z["S"], z["C"]
    parts = dict.fromkeys(("shortconv", "attn_proj", "attn_core",
                           "dense_mlp", "router", "experts", "head"), 0.0)
    for _pre, conv, dense in layers_of(cfg):
        if conv:
            parts["shortconv"] += S * shortconv_flops(cfg)
        else:
            parts["attn_proj"] += 2.0 * S * attention_projection_params(cfg)
            parts["attn_core"] += core_flops(cfg)
        if dense:
            parts["dense_mlp"] += 2.0 * S * 3 * C * z["F"]
        else:
            parts["router"] += 2.0 * S * C * z["E"]
            parts["experts"] += expert_product_flops(cfg,
                                                     expected_pairs(cfg))
    parts["head"] = 2.0 * S * C * z["V"]
    return parts


def flops_per_sample(cfg) -> float:
    """Forward FLOPs of one sequence (3.325 TFLOP at S = 8,192)."""
    return sum(flops_by_part(cfg).values())


def n_matmuls(cfg) -> int:
    """Matrix products a forward pass executes as XLA ``dot``s, counted
    low: two projections a short convolution, four and the core's two an
    attention, three a dense MLP, the router, the head once. Left out: the
    grouped products over the experts held (the compiler's own kernel, a
    ``custom-call`` that the trace's conv class does not hold)."""
    return 1 + sum((2 if conv else 6) + (3 if dense else 1)
                   for _p, conv, dense in layers_of(cfg))


# ---------------------------------------------------------------- the net
def build(cfg, weights, chips: int = 1, states=None, batch: int = 1):
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.train import updaters
    u = cfg["updater"]
    if not cfg["norm_topk_prob"] or not cfg["use_expert_bias"] \
            or cfg["conv_bias"] \
            or cfg["rope_parameters"]["rope_type"] != "default":
        raise ValueError("the program normalises the gates over the "
                         "selected experts, selects by score + bias, "
                         "convolves without a bias and turns q and k by "
                         "the default rotary embedding")
    net = zoo.LFM2(
        layer_types=cfg["layer_types"], layers=cfg["held_layers"],
        num_dense_layers=cfg["num_dense_layers"],
        hidden_size=cfg["hidden_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        conv_L_cache=cfg["conv_L_cache"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["num_experts"],
        held_experts=cfg["held_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_eps=cfg["norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        vocab_size=cfg["vocab_size"], seq_len=cfg["seq_len"],
        # every expert layer keeps which experts each token took at the
        # last step: the reference follows the program's choice
        keep_selected=int(batch) * cfg["seq_len"],
        updater=updaters.Adam(u["lr"], beta1=u["beta1"], beta2=u["beta2"],
                              epsilon=u["eps"])).conf_builder()
    put_weights(net, weights, states or {})
    return net


def put_weights(net, weights, states):
    """The net's parameters ARE the benchmark's arrays (no ``init()``: it
    would draw and then drop 1.9 GB at the real size); layer states as
    ``initialize`` declares them, the seeded ones put in."""
    import jax
    tree = {}
    for name, w in weights.items():
        node, leaf = name.split("/")
        tree.setdefault(node, {})[leaf] = w
    net._params, net._states = {}, {}
    for node in net.conf.topo:
        if node.kind != "layer":
            continue
        tied = net.conf.param_owner[node.name] != node.name
        want = {} if tied else node.obj.param_shapes()
        have = {k: tuple(a.shape) for k, a in tree.get(node.name, {}).items()}
        if have != {k: tuple(s) for k, s in want.items()}:
            raise ValueError(
                f"{node.name}: the zoo's LFM2 wants {want}, this "
                f"configuration's param_spec gives {have}")
        net._params[node.name] = dict(tree.get(node.name, {}))
        net._states[node.name] = jax.tree_util.tree_map(
            lambda a: jax.numpy.zeros(a.shape, a.dtype),
            jax.eval_shape(node.obj.initialize, jax.random.PRNGKey(0))[1])
    for name, a in states.items():
        node, leaf = name.split("/")
        net._states[node][leaf] = a
    net._initialized = True


def expert_layers_of(cfg):
    """The sparse-expert layers' node names, in the order they run."""
    return [pre + "moe" for pre, _conv, dense in layers_of(cfg) if not dense]


def routed_leaves(cfg):
    """The routed experts' weights: the leaves whose gradients a gate
    scales (``grad_routed_gap`` of the lean driver)."""
    return [f"{layer}/{leaf}" for layer in expert_layers_of(cfg)
            for leaf in ("Eg", "Eu", "Ed")]


def read_selected(net):
    """``{expert layer: int32 [tokens, k]}``: the experts each token took
    at the last step, still on the device."""
    return {name: state["selected"] for name, state in net._states.items()
            if isinstance(state, dict) and "selected" in state}


def read_leaves(net, what: str):
    """``{name: array}`` of the program's parameters (``"params"``) or of
    Adam's first moment (``"m"``), still on the device."""
    out = {}
    for node, leaves in net._params.items():
        for leaf in leaves:
            out[f"{node}/{leaf}"] = leaves[leaf] if what == "params" \
                else net._opt_state[node][leaf][what]
    return out
