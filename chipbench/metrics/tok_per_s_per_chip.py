"""Token ids a second a chip over the traced stretch: the ids a step the
fit loop dispatched (``dl4j_train_tokens_total``, counted by
``stage_batch`` while the program's instrumentation is on, which a traced
run turns on for exactly the window, over the window's steps), times the
traced step programs, over the traced stretch's seconds on the trace's
own clock, over the cell's chips. Not over the traced run's window: that
also holds the profiler's start and stop and the trace's collection, four
fifths of it in the language models (about 2,000 of the looped cell's
9,750 tokens/s/chip), so the reading contradicted the end-to-end rate it
should confirm."""

from chipbench import programspans as ps


def read(ctx):
    r, red = ctx.result, ctx.reduced
    if not r.get("traced") or not r["steps"] or red is None \
            or not red.steps:
        return None
    tokens = ps.counter_total("dl4j_train_tokens_total")
    if not tokens:
        return None
    return tokens / r["steps"] * red.steps / red.window_s / r["chips"]
