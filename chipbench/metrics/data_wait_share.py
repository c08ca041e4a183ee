"""Share of the window the fit loop spent waiting for its next batch: the
sum of the program's ``dl4j_train_data_wait_seconds`` over the window. The
program records it only while its profiling mode is on, which a traced run
turns on; otherwise there is nothing to read."""


def read(ctx):
    c = ctx.result["counters"]
    if not c.get("data_wait_recorded"):
        return None
    return 100.0 * c["data_wait_s"] / ctx.result["window_s"]
