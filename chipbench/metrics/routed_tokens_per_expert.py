"""Routed (token, expert) pairs an expert held on this chip met at the
last step, the mean over THIS configuration's expert layers and their held
experts (gauge ``dl4j_moe_held_pairs`` over the experts held; the
registry is the process's, so a layer another model left there is not
counted): tokens x experts a token / routed experts under uniform
routing, 2,048 at 32,768 tokens, top-4 of 64; the deployment's experts
meet 4,096. The load is the LAST step's and is not stationary: only the
held experts' outputs reach the loss, so the router turns toward them as
it trains (2,131 at step 1, 2,255 at a traced run's 12th, 3,200 after 27:
the builder's chip runs, PR 35)."""

from chipbench import xingmarks as xm


def read(ctx):
    pairs = xm.held_pairs(ctx)
    layers = getattr(ctx.model, "expert_layers_of", lambda cfg: [])(ctx.cfg)
    mine = [pairs[k] for k in layers if k in pairs] if pairs else []
    if not mine:
        return None
    return sum(mine) / len(mine) / len(ctx.cfg["held_experts"])
