"""Token ids whose step the fit loop dispatched in the window
(``dl4j_train_tokens_total``, counted by ``stage_batch`` while the
program's instrumentation is on, which a traced run turns on for exactly
the window), over the window's seconds, over the cell's chips."""

from chipbench import programspans as ps


def read(ctx):
    r = ctx.result
    if not r.get("traced") or not r["window_s"]:
        return None
    tokens = ps.counter_total("dl4j_train_tokens_total")
    if not tokens:
        return None
    return tokens / r["window_s"] / r["chips"]
