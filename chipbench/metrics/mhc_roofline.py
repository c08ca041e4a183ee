"""The hyper-connections against HBM's peak: a sub-block has to read the
residual streams once and write them once, forward, and their cotangent
likewise backward (``model.stream_bytes`` in the compute dtype, times two
times two times ``model.sub_blocks``), over 819 GB/s, over
``mhc_device_ms``. Bound: memory; the maps' FLOPs are a thousandth of the
step's."""

from chipbench import xingmarks as xm


def read(ctx):
    ms = xm.ms_or_none(ctx, xm.in_mhc)
    nbytes = getattr(ctx.model, "stream_bytes", None)
    if ms is None or nbytes is None:
        return None
    required = 4.0 * nbytes(ctx.cfg) * ctx.model.sub_blocks(ctx.cfg) \
        * ctx.result["batch"] / ctx.result["chips"]
    return 100.0 * required / ctx.peak["hbm_bytes_per_s"] / (ms * 1e-3)
