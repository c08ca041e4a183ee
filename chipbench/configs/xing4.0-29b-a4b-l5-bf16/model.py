"""Xing4.0-29B-A4B as the program runs it: the zoo's ``ComputationGraph``
with the benchmark's weights put in, and this configuration's sizes as
functions: parameters, required FLOPs and bytes of the whole step and of
each new part (what the rooflines of ``chipbench/metrics/`` divide by).

Leaves are named ``<node>/<leaf>`` after the graph's nodes; a layer's
routed experts are ONE array a leaf over the experts held, [held, ...]."""

import math


def _sizes(cfg):
    return {
        "C": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "ql": cfg["q_lora_rank"], "kvl": cfg["kv_lora_rank"],
        "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "F": cfg["intermediate_size"],
        "Fe": cfg["moe_intermediate_size"],
        "E": cfg["published"]["n_routed_experts"],
        "held": len(cfg["held_experts"]), "k": cfg["num_experts_per_tok"],
        "n": cfg["hc_mult"], "V": cfg["vocab_size"], "S": cfg["seq_len"],
        "shared": cfg["n_shared_experts"]}


def layers_of(cfg):
    """``[(node prefix, is dense)]``: the layers held, the
    multi-token-prediction module's expert layer last."""
    out = [(f"l{i}_", i < cfg["first_k_dense_replace"])
           for i in range(cfg["num_layers"])]
    if cfg["num_nextn_predict_layers"]:
        out.append(("mtp_", False))
    return out


def _attention_leaves(z):
    C, H = z["C"], z["H"]
    return [("Wqa", (C, z["ql"]), "he", C), ("q_gain", (z["ql"],), "gamma", 0),
            ("Wqb", (z["ql"], H * (z["dn"] + z["dr"])), "he", z["ql"]),
            ("Wkva", (C, z["kvl"] + z["dr"]), "he", C),
            ("kv_gain", (z["kvl"],), "gamma", 0),
            ("Wkvb", (z["kvl"], H * (z["dn"] + z["dv"])), "he", z["kvl"]),
            ("Wo", (H * z["dv"], C), "he", H * z["dv"])]


def _read_write_leaves(z, pre, tag):
    nC, n = z["n"] * z["C"], z["n"]
    return [(f"{pre}hr{tag}/phi_pre", (nC, n), "he", nC),
            (f"{pre}hr{tag}/alpha_pre", (1,), "alpha", 0),
            (f"{pre}hr{tag}/b_pre", (n,), "small", 0),
            (f"{pre}n{tag}/gain", (z["C"],), "gamma", 0),
            (f"{pre}hw{tag}/phi_post", (nC, n), "he", nC),
            (f"{pre}hw{tag}/phi_res", (nC, n * n), "he", nC),
            (f"{pre}hw{tag}/alpha_post", (1,), "alpha", 0),
            (f"{pre}hw{tag}/alpha_res", (1,), "alpha", 0),
            (f"{pre}hw{tag}/b_post", (n,), "small", 0),
            (f"{pre}hw{tag}/b_res", (n, n), "near_identity", 0)]


def param_spec(cfg):
    """``[(name, shape, kind, fan_in)]``; kinds as ``chipbench.weights``
    has them, and two of this configuration's that the lean driver's
    maker knows: ``alpha`` (0.01) and ``near_identity`` (3 I + 0.1 N)."""
    z = _sizes(cfg)
    C, V = z["C"], z["V"]
    spec = [("embed/W", (V, C), "he", C)]
    for pre, dense in layers_of(cfg):
        if pre == "mtp_":
            spec += [("mtp_join/h_gain", (C,), "gamma", 0),
                     ("mtp_join/e_gain", (C,), "gamma", 0),
                     ("mtp_join/W", (2 * C, C), "he", 2 * C)]
        spec += _read_write_leaves(z, pre, "1")
        spec += [(f"{pre}attn/{leaf}", shape, kind, fan)
                 for leaf, shape, kind, fan in _attention_leaves(z)]
        spec += _read_write_leaves(z, pre, "2")
        if dense:
            spec += [(f"{pre}mlp/Wg", (C, z["F"]), "he", C),
                     (f"{pre}mlp/Wu", (C, z["F"]), "he", C),
                     (f"{pre}mlp/Wd", (z["F"], C), "he", z["F"])]
        else:
            held, Fe, S = z["held"], z["Fe"], z["Fe"] * z["shared"]
            spec += [(f"{pre}moe/Wr", (C, z["E"]), "he", C),
                     (f"{pre}moe/Eg", (held, C, Fe), "he", C),
                     (f"{pre}moe/Eu", (held, C, Fe), "he", C),
                     (f"{pre}moe/Ed", (held, Fe, C), "he", Fe),
                     (f"{pre}moe/Sg", (C, S), "he", C),
                     (f"{pre}moe/Su", (C, S), "he", C),
                     (f"{pre}moe/Sd", (S, C), "he", S)]
    spec += [("fnorm/gain", (C,), "gamma", 0), ("lm/W", (C, V), "he", C)]
    return spec


def state_spec(cfg):
    """Layer states the seed fixes: every expert layer's 64 selection
    biases (no gradient, never updated)."""
    z = _sizes(cfg)
    return [(f"{pre}moe/select_bias", (z["E"],), "small", 0)
            for pre, dense in layers_of(cfg) if not dense]


def n_params(cfg) -> int:
    return sum(math.prod(s) for _n, s, _k, _f in param_spec(cfg))


# ------------------------------------------------ required work, forward
def attention_projection_params(cfg) -> int:
    z = _sizes(cfg)
    return sum(math.prod(s) for _l, s, _k, _f in _attention_leaves(z)
               if len(s) == 2)


def core_flops(cfg) -> float:
    """Required forward FLOPs of ONE layer application's attention core
    for one sequence: the causal half of ``q k^T`` over 192 and of the
    weighted sum over 128, ``S^2 * H * (192 + 128)``; the masked half is
    nobody's requirement."""
    z = _sizes(cfg)
    return float(z["S"]) ** 2 * z["H"] * (z["dn"] + z["dr"] + z["dv"])


def attention_applications(cfg) -> int:
    return len(layers_of(cfg))


def expert_layers(cfg) -> int:
    return sum(1 for _p, dense in layers_of(cfg) if not dense)


def sub_blocks(cfg) -> int:
    return 2 * len(layers_of(cfg))


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


def stream_bytes(cfg, itemsize: int = 2) -> float:
    """Bytes of the residual streams of one sequence in the compute
    dtype; a sub-block's hyper-connection has to read them once and write
    them once, forward and backward (the cotangent in, the cotangent
    out)."""
    z = _sizes(cfg)
    return float(itemsize) * z["S"] * z["n"] * z["C"]


def expected_pairs(cfg) -> float:
    """Routed pairs a layer's held experts meet under uniform routing."""
    z = _sizes(cfg)
    return z["S"] * z["k"] * z["held"] / z["E"]


def flops_per_sample(cfg) -> float:
    """Forward FLOPs of one sequence, required work only: every layer's
    attention projections and causal core, the dense MLP or the shared
    expert, router and routed products (the routed ones at the EXPECTED
    load of uniform routing, ``k * held / E`` = 0.5 held experts a token:
    the real load moves a few percent a step with the router), the
    hyper-connection maps, the joining projection and both heads (5.13
    TFLOP at S = 4,096 with the multi-token-prediction module)."""
    z = _sizes(cfg)
    S, C = z["S"], z["C"]
    total = 0.0
    for pre, dense in layers_of(cfg):
        total += 2.0 * S * attention_projection_params(cfg) + core_flops(cfg)
        total += 2 * 2.0 * S * z["n"] * C * (2 * z["n"] + z["n"] ** 2)
        if dense:
            total += 2.0 * S * 3 * C * z["F"]
        else:
            total += 2.0 * S * (3 * C * z["Fe"] * z["shared"] + C * z["E"]) \
                + expert_product_flops(cfg, expected_pairs(cfg))
        if pre == "mtp_":
            total += 2.0 * S * 2 * C * C
    heads = 1 + (1 if cfg["num_nextn_predict_layers"] else 0)
    return total + heads * 2.0 * S * C * z["V"]


def n_matmuls(cfg) -> int:
    """Matrix products a forward pass executes as XLA ``dot``s, counted
    low: five projections and the core's two an attention, three a dense
    MLP or shared expert, the router, the joining projection, a head
    each. Left out: the grouped products over the experts held (the
    compiler's own kernel, a ``custom-call`` that the trace's conv class
    does not hold) and the hyper-connection maps (three thin products a
    sub-block that the compiler may turn into reductions)."""
    n = 0
    for pre, dense in layers_of(cfg):
        n += 7 + (3 if dense else 4) + (1 if pre == "mtp_" else 0)
    return n + 1 + (1 if cfg["num_nextn_predict_layers"] else 0)


# ---------------------------------------------------------------- the net
def build(cfg, weights, chips: int = 1, states=None, batch: int = 1):
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.train import updaters
    u = cfg["updater"]
    if cfg["n_shared_experts"] != 1 or not cfg["norm_topk_prob"]:
        raise ValueError("the expert layer has one shared expert and gates "
                         "normalised over the selected experts")
    net = zoo.Xing4(
        num_layers=cfg["num_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        held_experts=cfg["held_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        hc_mult=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        mhc_h_res_clamp=(cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]),
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_scaling=cfg["rope_scaling"], vocab_size=cfg["vocab_size"],
        seq_len=cfg["seq_len"],
        num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
        mtp_weight=cfg["mtp_loss_weight"],
        # every expert layer keeps which experts each token took at the
        # last step: the reference follows the program's choice
        keep_selected=int(batch) * cfg["seq_len"],
        updater=updaters.Adam(u["lr"], beta1=u["beta1"], beta2=u["beta2"],
                              epsilon=u["eps"])).conf_builder()
    put_weights(net, weights, states or {})
    return net


def put_weights(net, weights, states):
    """The net's parameters ARE the benchmark's arrays (no ``init()``: it
    would draw and then drop 3.7 GB at the real size); layer states as
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
                f"{node.name}: the zoo's Xing4 wants {want}, this "
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
    return [pre + "moe" for pre, dense in layers_of(cfg) if not dense]


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
