"""Process start to the first timed step, less the seconds the TPU runtime
took to hand over the chips (the first ``jax.devices()``): imports, host
batches and weights from the seed, the net's ``init()``, the compile or
cache load of the step program, and the first update steps the comparison
reads. The runtime's own start is 9 to 15 s, drifts by minutes on one
machine and is moved by nothing in this repo (PERF.md, PR 25); it is shown
on standard error as ``reach_chip``."""


def read(ctx):
    return ctx.result["setup_s"]
