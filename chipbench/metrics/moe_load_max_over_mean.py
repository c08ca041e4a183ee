"""The busiest held expert's load over the mean load of its layer's held
experts at the last step, the largest over the sparse-expert layers
(gauge ``dl4j_moe_expert_load``): 1 is balance; the grouped products'
time follows the sum, a deployment's step the maximum."""

from chipbench import xingmarks as xm


def read(ctx):
    if not ctx.result.get("traced"):
        return None
    load = xm.gauge("dl4j_moe_expert_load")
    if not load:
        return None
    layers = {}
    for (layer, _expert), n in load.items():
        layers.setdefault(layer, []).append(n)
    ratios = [max(ns) * len(ns) / sum(ns) for ns in layers.values()
              if sum(ns) > 0]
    return max(ratios) if ratios else None
