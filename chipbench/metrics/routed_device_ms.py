"""Device time a traced step of the sparse-expert layers' routed path
(router, top-k, sort, dispatch, the grouped products over the experts
held, combine: everything under ``dl4j_moe``) at 2,048 tokens an expert:
``moe_device_ms``' reading under this cell's name."""

from chipbench.metrics.moe_device_ms import read  # noqa: F401
