"""The busiest held expert's load over the mean load of its layer's held
experts at the last step, the largest over the sparse-expert layers
(gauge ``dl4j_moe_expert_load``): ``moe_load_max_over_mean``'s reading
under this cell's name."""

from chipbench.metrics.moe_load_max_over_mean import read  # noqa: F401
