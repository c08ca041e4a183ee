"""Largest over the cell's devices of ``peak_bytes_in_use +
peak_bytes_reserved`` after the window, in GiB."""

from chipbench.trace import memory_peak_bytes


def read(ctx):
    return memory_peak_bytes(ctx.result["memory_stats"]) / 2.0 ** 30
