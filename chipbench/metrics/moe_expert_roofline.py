"""The grouped products over the experts held against the chip's peaks:
for each expert layer the larger of the time its three products' FLOPs
need at the MXU's peak and the time their bytes need at HBM's
(``model.expert_product_flops`` / ``expert_product_bytes`` of the routed
pairs that met a held expert at the last step, the gauge
``dl4j_moe_held_pairs``), three times that a step (forward, input
gradient, weight gradient), over the device time a step of the ops under
``dl4j_moe_experts``. At a few hundred tokens an expert (the sparse
decoder's 256) the bytes bound it: the experts' weights are read whatever
the load; at the hybrid decoder's 2,048 the FLOPs do. The same work
whatever implements the products; what a rematerialised stretch runs
again is not required work."""

from chipbench import xingmarks as xm


def read(ctx):
    ms = xm.ms_or_none(ctx, xm.in_experts)
    pairs = xm.held_pairs(ctx)
    if ms is None or not pairs \
            or not hasattr(ctx.model, "expert_product_flops"):
        return None
    need = sum(max(ctx.model.expert_product_flops(ctx.cfg, n)
                   / ctx.peak["flops_per_s"],
                   ctx.model.expert_product_bytes(ctx.cfg, n)
                   / ctx.peak["hbm_bytes_per_s"])
               for n in pairs.values())
    return 100.0 * 3.0 * need / ctx.result["chips"] / (ms * 1e-3)
