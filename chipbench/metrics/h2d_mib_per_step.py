"""Host bytes the program placed on the device a step of the window:
``dl4j_train_h2d_bytes_total`` (``stage_batch`` in the fit loop) plus
``dl4j_prefetch_h2d_bytes_total`` (a ``DevicePrefetcher``'s worker), over
the window's steps. Both count only while the program's instrumentation is
on, which a traced run turns on for exactly the window."""

from chipbench import programspans as ps


def read(ctx):
    if not ctx.result.get("traced") or not ctx.result["steps"]:
        return None
    staged = ps.counter_total("dl4j_train_h2d_bytes_total")
    if staged is None:
        return None
    staged += ps.counter_total("dl4j_prefetch_h2d_bytes_total") or 0.0
    return staged / ctx.result["steps"] / 2 ** 20
