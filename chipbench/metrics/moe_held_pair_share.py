"""Of the routed (token, expert) pairs of the last step, over all
sparse-expert layers, the share that met an expert held on this chip
(gauge ``dl4j_moe_held_pairs`` over tokens x experts a token x layers):
``held / routed experts`` under uniform routing, 12.5% at 8 of 64."""

from chipbench import xingmarks as xm


def read(ctx):
    pairs = xm.held_pairs(ctx)
    if not pairs:
        return None
    routed = ctx.result["batch"] * ctx.cfg["seq_len"] \
        * ctx.cfg["num_experts_per_tok"] * len(pairs)
    return 100.0 * sum(pairs.values()) / routed
