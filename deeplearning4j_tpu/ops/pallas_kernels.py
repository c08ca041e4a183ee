"""Pallas TPU kernels — platform overrides for memory-bound hot ops.

Reference parity: libnd4j's ``platform/{mkldnn,cudnn}`` PlatformHelpers —
vendor-optimized implementations that SHADOW the generic op at dispatch
time (SURVEY.md §2.1). The TPU equivalent is a Pallas kernel registered
through :func:`ops.registry.register_platform_override`.

The wins here are memory-bound fusions XLA cannot always do in one VMEM
round-trip: row-wise layer_norm and softmax read the activation ONCE,
keep the row statistics in registers, and write the result once.

Kernels are written against the (sublane, lane) = (8, 128) fp32 tiling;
:func:`supported` gates dispatch — unsupported shapes/dtypes fall back to
the generic lowering (the PlatformHelper contract). ``interpret=True``
runs the same kernels on CPU for tests.

Gradients: the overrides carry ``jax.custom_vjp`` with composed-jnp
backward passes, so SameDiff graphs and eager ``jax.grad`` work through
the kernel unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROW_BLOCK = 256
#: bytes the pipelined row tiles of one call may hold in VMEM: the input
#: and output tile, each double-buffered. Half the v5e's 16 MiB scoped
#: limit; the rest is the kernel's own f32 temporaries.
_TILE_BYTES = 8 * 2 ** 20


def _row_block(n: int, d: int, itemsize: int) -> int:
    """Rows per grid step for an [n, d] array: the largest divisor of
    ``n`` reached by halving from ``_ROW_BLOCK`` whose tiles fit
    ``_TILE_BYTES`` — the block shrinks with the width, so every width
    the gates admit compiles."""
    block = min(_ROW_BLOCK, n)
    while n % block or (block > 8 and 4 * block * d * itemsize > _TILE_BYTES):
        block //= 2
    return max(block, 8)


def supported(x, axis: int = -1) -> bool:
    """Shapes this kernel family accepts: 2-D fp32/bf16, normalized axis
    last, lane dim a multiple of 128, rows a multiple of 8."""
    if x.ndim != 2 or axis not in (-1, 1, x.ndim - 1):
        return False
    n, d = x.shape
    if d % 128 != 0 or n % 8 != 0:
        return False
    if d > 4096:        # row block must fit VMEM (in + out buffers)
        return False
    return x.dtype in (jnp.float32, jnp.bfloat16)


# ------------------------------------------------------------- layer_norm

def _layer_norm_kernel(x_ref, g_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    m = jnp.mean(x, axis=1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=1, keepdims=True)
    y = (x - m) * jax.lax.rsqrt(v + eps)
    o_ref[:] = (y * g_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _layer_norm_fwd_pallas(x, gain, bias, eps: float, interpret: bool):
    n, d = x.shape
    block = _row_block(n, d, x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_layer_norm_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
        name="dl4j_layer_norm",
    )(x, gain.reshape(1, d), bias.reshape(1, d))


def make_layer_norm_override(interpret: bool = False):
    """Build the layer_norm platform override (signature-compatible with
    ops.normalization.layer_norm for axis=-1 2-D inputs; other calls fall
    back to the generic op)."""
    from deeplearning4j_tpu.ops import normalization as norm_ops

    # eps is static: the kernel closes over it, and a pallas_call may
    # capture no traced value
    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def _ln(x, gain, bias, eps):
        return _layer_norm_fwd_pallas(x, gain, bias, eps, interpret)

    def _fwd(x, gain, bias, eps):
        return _ln(x, gain, bias, eps), (x, gain)

    def _bwd(eps, res, ct):
        x, gain = res
        x32 = x.astype(jnp.float32)
        g32 = ct.astype(jnp.float32)
        m = jnp.mean(x32, axis=1, keepdims=True)
        v = jnp.mean(jnp.square(x32 - m), axis=1, keepdims=True)
        inv = jax.lax.rsqrt(v + eps)
        xhat = (x32 - m) * inv
        gy = g32 * gain.astype(jnp.float32)
        dx = inv * (gy - jnp.mean(gy, axis=1, keepdims=True)
                    - xhat * jnp.mean(gy * xhat, axis=1, keepdims=True))
        dgain = jnp.sum(g32 * xhat, axis=0)
        dbias = jnp.sum(g32, axis=0)
        return (dx.astype(x.dtype), dgain.astype(gain.dtype),
                dbias.astype(gain.dtype))

    _ln.defvjp(_fwd, _bwd)

    def layer_norm(x, gain, bias=None, *, axis=-1, eps: float = 1e-5):
        if gain is None or bias is None or \
                not supported(jnp.asarray(x),
                              axis if isinstance(axis, int) else -2):
            return norm_ops.layer_norm(x, gain, bias, axis=axis, eps=eps)
        return _ln(jnp.asarray(x), jnp.asarray(gain), jnp.asarray(bias),
                   float(eps))

    return layer_norm


# ---------------------------------------------------------------- softmax

def _softmax_kernel(x_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)
    m = jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[:] = (e / jnp.sum(e, axis=1, keepdims=True)).astype(o_ref.dtype)


def _softmax_fwd_pallas(x, interpret: bool):
    n, d = x.shape
    block = _row_block(n, d, x.dtype.itemsize)
    return pl.pallas_call(
        _softmax_kernel,
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        grid=(n // block,),
        in_specs=[pl.BlockSpec((block, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
        name="dl4j_softmax",
    )(x)


def make_softmax_override(interpret: bool = False):
    @jax.custom_vjp
    def _sm(x):
        return _softmax_fwd_pallas(x, interpret)

    def _fwd(x):
        y = _sm(x)
        return y, y

    def _bwd(y, ct):
        y32 = y.astype(jnp.float32)
        g = ct.astype(jnp.float32)
        dx = y32 * (g - jnp.sum(g * y32, axis=1, keepdims=True))
        return (dx.astype(y.dtype),)

    _sm.defvjp(_fwd, _bwd)

    def softmax(x, axis: int = -1):
        xa = jnp.asarray(x)
        if not supported(xa, axis):
            return jax.nn.softmax(xa, axis=axis)
        return _sm(xa)

    return softmax


# ------------------------------------------------------- flash attention

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *,
                      scale: float, causal: bool, bq: int, bk: int,
                      nk: int):
    """One (batch*head, q-block, k-block) grid step of the FlashAttention
    forward: online-softmax accumulation in VMEM scratch. The k dimension
    is the sequential ('arbitrary') grid axis, so scratch persists across
    k steps for a fixed q block."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # causal: skip k blocks strictly in the future of this q block
    run = (not causal) or (j * bk <= i * bq + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0]                              # [bq, D] native dtype:
        k = k_ref[0]                              # bf16 feeds the MXU at
        v = v_ref[0]                              # full rate, f32 accum
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)
        m_prev = m_ref[:, :1]                     # [bq, 1] (lanes replicated)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                    # [bq, bk]
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _final():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[:] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _flash_fwd_pallas(q, k, v, *, causal: bool, bq: int, bk: int,
                      interpret: bool):
    """q, k, v: [BH, T, D] -> (o [BH, T, D], lse [BH, T, 128])."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    nq, nk = Tq // bq, Tk // bk
    scale = 1.0 / np.sqrt(D)
    kernel = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            # row statistics: lanes replicated to the 128 minimum tile
            pl.BlockSpec((bq, 128), lambda b, i, j: (b * nq + i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((BH * nq * bq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),      # acc
            pltpu.VMEM((bq, 128), jnp.float32),    # running max
            pltpu.VMEM((bq, 128), jnp.float32),    # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dl4j_flash_attention",
    )(q, k, v)
    return o, lse.reshape(BH, Tq, 128)[:, :, 0]


def _flash_bwd_blockwise(q, k, v, o, lse, ct, *, causal: bool, bk: int):
    """Flash backward from saved (o, lse): blockwise over k so the [T, T]
    score matrix never materializes. Plain jnp inside lax.scan — XLA fuses
    it; memory per step is [BH, Tq, bk]."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    nk = Tk // bk
    scale = 1.0 / np.sqrt(D)
    qf = q.astype(jnp.float32)
    ctf = ct.astype(jnp.float32)
    delta = jnp.sum(ctf * o.astype(jnp.float32), axis=-1)     # [BH, Tq]
    q_pos = jnp.arange(Tq)

    kb = jnp.moveaxis(k.astype(jnp.float32).reshape(BH, nk, bk, D), 1, 0)
    vb = jnp.moveaxis(v.astype(jnp.float32).reshape(BH, nk, bk, D), 1, 0)

    def body(dq, inp):
        kj, vj, jidx = inp                                    # [BH, bk, D]
        s = jnp.einsum("bqd,bkd->bqk", qf, kj) * scale
        if causal:
            cols = jidx * bk + jnp.arange(bk)
            s = jnp.where(q_pos[:, None] >= cols[None, :], s, -jnp.inf)
        p = jnp.exp(s - lse[..., None])                       # [BH, Tq, bk]
        dv_j = jnp.einsum("bqk,bqd->bkd", p, ctf)
        dp = jnp.einsum("bqd,bkd->bqk", ctf, vj)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, kj) * scale
        dk_j = jnp.einsum("bqk,bqd->bkd", ds, qf) * scale
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((BH, Tq, D), jnp.float32)
    dq, (dk, dv) = lax_scan_bwd(body, dq0, (kb, vb, jnp.arange(nk)))
    dk = jnp.moveaxis(dk, 0, 1).reshape(BH, Tk, D)
    dv = jnp.moveaxis(dv, 0, 1).reshape(BH, Tk, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def lax_scan_bwd(body, init, xs):
    return jax.lax.scan(body, init, xs)


def flash_supported(q, k, bq: int, bk: int) -> bool:
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    return (q.dtype in (jnp.float32, jnp.bfloat16)
            and D % 64 == 0 and D <= 256
            and Tq % min(bq, Tq) == 0 and Tk % min(bk, Tk) == 0
            and min(bq, Tq) % 8 == 0 and min(bk, Tk) % 128 == 0)


def make_flash_attention_override(interpret: bool = False,
                                  bq: int = 256, bk: int = 256):
    """Fused FlashAttention kernel as the ``flash_attention`` platform
    override (VERDICT r4 #5; SURVEY.md §5 "splash-attention Pallas
    kernel"): q/k/v block tiles in VMEM, online softmax in scratch,
    custom_vjp backward from the saved log-sum-exp. Falls back to the
    scan-based formulation for masks/unsupported shapes."""
    from deeplearning4j_tpu.ops import attention as attn_ops

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def _fa(q, k, v, causal):
        o, _ = _fwd_inner(q, k, v, causal)
        return o

    def _fwd_inner(q, k, v, causal):
        B, Tq, H, D = q.shape
        Tk = k.shape[1]
        to_bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(
            B * H, x.shape[1], D)
        cbq = min(bq, Tq)
        cbk = min(bk, Tk)
        o, lse = _flash_fwd_pallas(to_bh(q), to_bh(k), to_bh(v),
                                   causal=causal, bq=cbq, bk=cbk,
                                   interpret=interpret)
        return (jnp.transpose(o.reshape(B, H, Tq, D), (0, 2, 1, 3)),
                lse.reshape(B, H, Tq))

    def _vjp_fwd(q, k, v, causal):
        o, lse = _fwd_inner(q, k, v, causal)
        return o, (q, k, v, o, lse)

    def _vjp_bwd(causal, res, ct):
        q, k, v, o, lse = res
        B, Tq, H, D = q.shape
        Tk = k.shape[1]
        to_bh = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(
            B * H, x.shape[1], D)
        dq, dk, dv = _flash_bwd_blockwise(
            to_bh(q), to_bh(k), to_bh(v), to_bh(o),
            lse.reshape(B * H, Tq), to_bh(ct),
            causal=causal, bk=min(bk, Tk))
        back = lambda x, T: jnp.transpose(x.reshape(B, H, T, D), (0, 2, 1, 3))
        return back(dq, Tq), back(dk, Tk), back(dv, Tk)

    _fa.defvjp(_vjp_fwd, _vjp_bwd)

    def flash_attention(q, k, v, *, mask=None, is_causal: bool = False,
                        block_size: int = 512):
        q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
        if mask is not None or not flash_supported(q, k, bq, bk):
            return attn_ops._flash_attention_scan(
                q, k, v, mask=mask, is_causal=is_causal,
                block_size=block_size)
        return _fa(q, k, v, bool(is_causal))

    return flash_attention


# ------------------------------------------------------------ installation

def install_platform_overrides(interpret: bool = False):
    """Register the Pallas kernels over their generic ops (ref: the
    PlatformHelper loader). The kernels are compiled for the TPU; only a
    caller that says ``interpret=True`` (the CPU tests) gets the Pallas
    interpreter — the backend found at run time never decides, so a chip
    run cannot land in the interpreter and a CPU run claims no kernel."""
    from deeplearning4j_tpu.ops import registry
    registry.register_platform_override(
        "layer_norm", make_layer_norm_override(interpret))
    registry.register_platform_override(
        "softmax", make_softmax_override(interpret))
    registry.register_platform_override(
        "flash_attention", make_flash_attention_override(interpret))


def uninstall_platform_overrides():
    from deeplearning4j_tpu.ops import registry
    registry.clear_platform_override("layer_norm")
    registry.clear_platform_override("softmax")
    registry.clear_platform_override("flash_attention")
