"""Probe: is TinyYOLO's 416² BN+leaky plateau physics or lowering?
(VERDICT r4 weak #7.)

Method: the suspect op chain is training-mode BatchNorm (per-channel
mean/var over N,H,W) followed by leaky-relu on [N, C, 416, 416]
activations. Its arithmetic intensity is ~5 flops per element against
~6 bytes of HBM traffic per element (read for stats + read for apply +
write) — deeply bandwidth-bound. So the question "can a Pallas kernel
beat XLA here?" reduces to "does XLA's lowering already run at the HBM
roofline?" — measured directly below as achieved GB/s vs the v5e's
~819 GB/s peak. If the achieved fraction is high, the plateau is
physics and no kernel can improve it; a fused Pallas kernel could only
remove the stats read (3 passes -> 2) for a <=1.5x ceiling.

Run: python benchmarks/probe_bn_leaky.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

HBM_PEAK_GBPS = 819.0       # public v5e figure


def measured_stream_gbps(x, iters=30):
    """Achievable streaming bandwidth ON THIS CHIP (read+write axpy) —
    the roofline the BN+activation chains are judged against."""
    def chained(x0):
        def body(i, acc):
            return acc * 1.0000001 + 0.5
        return jnp.sum(jax.lax.fori_loop(0, iters, body, x0)
                       .astype(jnp.float32))
    g = jax.jit(chained)
    float(g(x))
    t0 = time.perf_counter()
    float(g(x))
    dt = (time.perf_counter() - t0) / iters
    return 2 * x.size * x.dtype.itemsize / dt / 1e9


def bn_leaky(x, gamma, beta, alpha=0.1, eps=1e-5):
    m = jnp.mean(x.astype(jnp.float32), axis=(0, 2, 3), keepdims=True)
    v = jnp.mean(jnp.square(x.astype(jnp.float32) - m), axis=(0, 2, 3),
                 keepdims=True)
    y = (x.astype(jnp.float32) - m) * jax.lax.rsqrt(v + eps)
    y = y * gamma[None, :, None, None] + beta[None, :, None, None]
    return jnp.where(y > 0, y, alpha * y).astype(x.dtype)


def two_pass_bytes(x):
    # stats read + apply read + write, in x's dtype
    return 3 * x.size * x.dtype.itemsize


def pallas_bn_leaky(x2d, gamma, beta, alpha=0.1, eps=1e-5,
                    rows=416, cols=1664):
    """Fused two-kernel BN+leaky over x [C, M] (M = N*H*W): per-channel
    grid with big CONTIGUOUS [rows, cols] blocks (the [C, bc] layout
    gathers C strided rows per DMA — measured 0.8x of XLA; this layout
    streams one channel's memory linearly), then an apply pass —
    exactly the 3 HBM passes the roofline allows, bf16 end-to-end."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    C, M = x2d.shape
    x3 = x2d.reshape(C, M // cols, cols)
    nb = (M // cols) // rows

    def stats_kernel(x_ref, out_ref, s_ref, q_ref):
        c, j = pl.program_id(0), pl.program_id(1)

        @pl.when(j == 0)
        def _():
            s_ref[:] = jnp.zeros_like(s_ref)
            q_ref[:] = jnp.zeros_like(q_ref)
        blk = x_ref[0].astype(jnp.float32)          # [rows, cols]
        s_ref[:] += jnp.sum(blk, axis=0, keepdims=True)
        q_ref[:] += jnp.sum(blk * blk, axis=0, keepdims=True)

        @pl.when(j == nb - 1)
        def _():
            out_ref[pl.ds(c, 1)] = jnp.full((1, 128),
                                            jnp.sum(s_ref[...]))
            out_ref[pl.ds(C + c, 1)] = jnp.full((1, 128),
                                                jnp.sum(q_ref[...]))

    sums = pl.pallas_call(
        stats_kernel,
        grid=(C, nb),
        in_specs=[pl.BlockSpec((1, rows, cols), lambda c, j: (c, j, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((2 * C, 128), lambda c, j: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((2 * C, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, cols), jnp.float32),
                        pltpu.VMEM((1, cols), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(x3)
    mean = sums[:C, :1] / M
    var = sums[C:, :1] / M - mean * mean
    scale = (gamma[:, None] * jax.lax.rsqrt(var + eps)).astype(jnp.float32)
    shift = (beta[:, None] - mean * scale).astype(jnp.float32)

    def apply_kernel(x_ref, sc_ref, sh_ref, o_ref):
        c = pl.program_id(0)
        sc = sc_ref[pl.ds(c, 1)][0, 0]
        sh = sh_ref[pl.ds(c, 1)][0, 0]
        y = x_ref[0].astype(jnp.float32) * sc + sh
        o_ref[0] = jnp.where(y > 0, y, alpha * y).astype(o_ref.dtype)

    y = pl.pallas_call(
        apply_kernel,
        grid=(C, nb),
        in_specs=[
            pl.BlockSpec((1, rows, cols), lambda c, j: (c, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, 128), lambda c, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, 128), lambda c, j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, rows, cols), lambda c, j: (c, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((C, M // cols, cols), x2d.dtype),
    )(x3, jnp.broadcast_to(scale, (C, 128)),
      jnp.broadcast_to(shift, (C, 128)))
    return y.reshape(C, M)


def main():
    N, C, H, W = 32, 16, 416, 416
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(N, C, H, W), jnp.bfloat16)
    gamma = jnp.ones((C,), jnp.float32)
    beta = jnp.zeros((C,), jnp.float32)
    ITERS = 30

    def chained(x0):
        def body(i, acc):
            return bn_leaky(acc, gamma, beta)
        return jnp.sum(jax.lax.fori_loop(0, ITERS, body, x0)
                       .astype(jnp.float32))

    g = jax.jit(chained)
    float(g(x))                                   # compile
    t0 = time.perf_counter()
    r = g(x)
    float(r)
    dt = (time.perf_counter() - t0) / ITERS
    stream = measured_stream_gbps(jnp.ravel(x))
    gbps = two_pass_bytes(x) / dt / 1e9
    print(f"measured stream roofline: {stream:.0f} GB/s "
          f"(= {stream / HBM_PEAK_GBPS:.1%} of the public 819 GB/s)")
    print(f"XLA bn+leaky [32,16,416,416] bf16: {dt * 1e3:.3f} ms/iter, "
          f"{gbps:.0f} GB/s = {gbps / stream:.0%} of the measured roofline")

    # fused Pallas version over the channels-major 2-D view
    x2d = jnp.reshape(jnp.transpose(x, (1, 0, 2, 3)), (C, N * H * W))
    ref = np.asarray(bn_leaky(x, gamma, beta), np.float32)
    got = np.asarray(pallas_bn_leaky(x2d, gamma, beta), np.float32)
    got4 = got.reshape(C, N, H, W).transpose(1, 0, 2, 3)
    err = np.abs(got4 - ref).max()
    print("pallas vs XLA max|err|:", err)
    assert err < 0.05, err

    def chained_pl(x0):
        def body(i, acc):
            return pallas_bn_leaky(acc, gamma, beta)
        return jnp.sum(jax.lax.fori_loop(0, ITERS, body, x0)
                       .astype(jnp.float32))

    gp = jax.jit(chained_pl)
    float(gp(x2d))
    t0 = time.perf_counter()
    r = gp(x2d)
    float(r)
    dtp = (time.perf_counter() - t0) / ITERS
    gbpsp = two_pass_bytes(x) / dtp / 1e9
    print(f"Pallas fused:                      {dtp * 1e3:.3f} ms/iter, "
          f"{gbpsp:.0f} GB/s = {gbpsp / stream:.0%} of the measured "
          f"roofline, {dt / dtp:.2f}x vs XLA")
    xla_frac = gbps / stream
    speedup = dt / dtp
    if xla_frac > 0.7 and speedup < 1.15:
        print(f"verdict: PHYSICS — XLA's lowering runs at {xla_frac:.0%} "
              f"of this chip's measured streaming bandwidth and the fused "
              f"kernel is {speedup:.2f}x; the plateau is set by effective "
              f"HBM bandwidth, not by XLA's lowering.")
    elif speedup >= 1.15:
        print(f"verdict: LOWERING — the fused kernel is {speedup:.2f}x "
              f"over XLA here; promote it to a platform override.")
    else:
        print(f"verdict: INCONCLUSIVE — XLA at {xla_frac:.0%} of the "
              f"measured stream, kernel {speedup:.2f}x; neither is near "
              f"the roofline, so something else (dispatch, layout) "
              f"dominates at this shape.")


if __name__ == "__main__":
    main()
