"""The grouped products over the experts held against the chip's peaks at
2,048 tokens an expert, where their FLOPs and not the experts' bytes
should bound them: ``moe_expert_roofline``'s rule (the larger of FLOP time
and byte time of the held pairs' three products, three times a step, over
the device time under ``dl4j_moe_experts``), the configuration's own
counts.

The time is WIDER than the requirement: ``dl4j_moe_experts`` also holds
the gated products (``act(g) * u``) and the backward's ``add_any`` over
all tokens x 4 rows of the no-drop buffer, of which an eighth hold a pair
that met a held expert. In this cell that is 33 of 74.5 ms, so the share
read 27% where the 48 ``ragged-dot`` kernels alone stood at 51% (the
builder's chip run, PR 35): a change that moves only the kernels moves
this share by less than it moves them (PERF.md section 7 p)."""

from chipbench.metrics.moe_expert_roofline import read  # noqa: F401
