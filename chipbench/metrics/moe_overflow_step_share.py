"""Of the layer-steps the sparse-expert layers have run (one layer, one
step), the share whose held pairs did not fit the first pass's rows and
took a further pass (gauge ``dl4j_moe_pass_steps{layer,passes}``, the
layers' ``pass_steps`` states: 100 x the steps counted under ``passes``
above 1 over all of them). 0 is the healthy reading: a step that takes a
second pass is still right, and still cheaper than the ``tokens x
experts a token`` buffer, but it pays the rows twice. The program
publishes the gauge when a ``fit`` returns with its instrumentation on,
which only a traced run turns on: this sees the TRACED run's steps, the
first ones of set-up and its own shorter window, not the steps of the
untraced runs that ``img_per_s_per_chip`` is taken from. Of this
configuration's expert layers alone where its ``model.py`` names them
(the registry is the process's)."""

from chipbench import xingmarks as xm


def read(ctx):
    if not ctx.result.get("traced"):
        return None
    steps = xm.gauge("dl4j_moe_pass_steps")
    if not steps:
        return None
    mine = getattr(ctx.model, "expert_layers_of", None)
    if mine is not None:
        layers = set(mine(ctx.cfg))
        steps = {k: n for k, n in steps.items() if k[0] in layers}
    total = sum(steps.values())
    if total <= 0:
        return None
    return 100.0 * sum(n for (_layer, passes), n in steps.items()
                       if int(passes) > 1) / total
