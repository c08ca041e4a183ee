"""The grouped-query attention core against the MXU's peak: the FLOPs the
causal half requires (``model.core_flops``: ``2 * S^2 * H * D`` a sequence
a layer application, forward; three times that a step) over the peak,
over the core's device time a step (``gqa_core_device_ms``). Bound:
compute. ``mla_core_roofline``'s rule, the configuration's own counts."""

from chipbench.metrics.mla_core_roofline import read  # noqa: F401
