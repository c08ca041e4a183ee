"""Device time a traced step of the grouped-query attention core (scores,
mask, softmax, weighted sum of 32 query heads over 8 key/value heads of
64: ``dl4j_attn_core``), forward, rematerialised and backward, whatever
implements it: ``mla_core_device_ms``' reading under this cell's name."""

from chipbench.metrics.mla_core_device_ms import read  # noqa: F401
