"""Attention ops.

Reference parity: libnd4j ``dot_product_attention`` /
``multi_head_dot_product_attention`` declarable ops and SameDiff
``sd.nn.multiHeadDotProductAttention`` (SURVEY.md §5 "Long-context" —
the reference's attention is vanilla/unblocked).

TPU-native additions beyond the reference: a blockwise (flash-style)
attention path that never materializes the [T, T] score matrix — the
long-context building block (ring attention in ``parallel/`` shards its
KV blocks over the mesh; see parallel/sequence.py). Layouts here are
modern [B, T, H, D]; the reference-layout wrappers live at the bottom.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu import profiler as _prof


def dot_product_attention(q, k, v, *, mask=None, scaled: bool = True,
                          is_causal: bool = False):
    """Scaled dot-product attention over [B, T, H, D] tensors.

    (ref: libnd4j ``dot_product_attention``; normalization = 1/sqrt(d).)
    mask: broadcastable to [B, H, Tq, Tk]; 1 = attend, 0 = block.
    """
    B, Tq, H, D = q.shape
    scale = (1.0 / jnp.sqrt(D)).astype(q.dtype) if scaled else jnp.asarray(1.0, q.dtype)
    # [B, H, Tq, Tk]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        scores = jnp.where(mask > 0, scores, jnp.asarray(-1e30, scores.dtype))
    if is_causal:
        causal = jnp.tril(jnp.ones((Tq, k.shape[1]), bool))
        scores = jnp.where(causal[None, None], scores, jnp.asarray(-1e30, scores.dtype))
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def multi_head_attention(x_q, x_kv, wq, wk, wv, wo, *, num_heads: int,
                         mask=None, is_causal: bool = False,
                         bq=None, bk=None, bv=None, bo=None,
                         use_flash: bool = False, block_size: int = 512):
    """Full multi-head attention with projections
    (ref: libnd4j ``multi_head_dot_product_attention``).

    x_q: [B, Tq, E], x_kv: [B, Tk, E]; w*: [E, E]; returns [B, Tq, E].
    """
    B, Tq, E = x_q.shape
    D = E // num_heads
    def proj(x, w, b):
        y = x @ w
        if b is not None:
            y = y + b
        return y.reshape(x.shape[0], x.shape[1], num_heads, D)
    q = proj(x_q, wq, bq)
    k = proj(x_kv, wk, bk)
    v = proj(x_kv, wv, bv)
    if use_flash:
        ctx = flash_attention(q, k, v, mask=mask, is_causal=is_causal,
                              block_size=block_size)
    else:
        ctx = dot_product_attention(q, k, v, mask=mask, is_causal=is_causal)
    out = ctx.reshape(B, Tq, E) @ wo
    if bo is not None:
        out = out + bo
    return out


def flash_attention(q, k, v, *, mask=None, is_causal: bool = False,
                    block_size: int = 512):
    """Blockwise attention with online softmax — O(T) memory.

    Dispatch: a Pallas fused kernel registered as the platform override
    (``ops.pallas_kernels.make_flash_attention_override``) takes the call
    when installed; otherwise the ``lax.scan`` formulation below runs.
    Shapes: q [B, Tq, H, D]; k, v [B, Tk, H, D]; mask broadcastable to
    [B, H, Tq, Tk].
    """
    from deeplearning4j_tpu.ops import registry as _reg
    ov = _reg._PLATFORM_OVERRIDES.get("flash_attention")
    if ov is not None:
        return ov(q, k, v, mask=mask, is_causal=is_causal,
                  block_size=block_size)
    return _flash_attention_scan(q, k, v, mask=mask, is_causal=is_causal,
                                 block_size=block_size)


def _flash_attention_scan(q, k, v, *, mask=None, is_causal: bool = False,
                          block_size: int = 512):
    """The portable scan formulation (fp32 accumulation; runs on any
    backend — also the fallback for shapes/masks the kernel rejects)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    blk = min(block_size, Tk)
    # pad Tk to a multiple of blk
    pad = (-Tk) % blk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblk = (Tk + pad) // blk
    scale = 1.0 / jnp.sqrt(D)

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32).reshape(B, nblk, blk, H, D)
    vf = v.astype(jnp.float32).reshape(B, nblk, blk, H, D)

    q_pos = jnp.arange(Tq)
    neg = jnp.float32(-1e30)

    def body(carry, inp):
        m_run, l_run, acc = carry          # [B,H,Tq], [B,H,Tq], [B,H,Tq,D]
        kb, vb, bidx = inp                 # [B,blk,H,D], [B,blk,H,D], scalar
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb)  # [B,H,Tq,blk]
        k_pos = bidx * blk + jnp.arange(blk)
        valid = (k_pos < Tk)[None, None, None, :]
        s = jnp.where(valid, s, neg)
        if is_causal:
            cm = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(cm[None, None], s, neg)
        if mask is not None:
            full = jnp.broadcast_to(mask, (B, H, Tq, Tk))
            if pad:
                full = jnp.pad(full, ((0, 0), (0, 0), (0, 0), (0, pad)))
            mb = lax.dynamic_slice_in_dim(full, bidx * blk, blk, axis=3)
            s = jnp.where(mb > 0, s, neg)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vb)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, H, Tq), neg)
    l0 = jnp.zeros((B, H, Tq))
    acc0 = jnp.zeros((B, H, Tq, D))
    kb = jnp.moveaxis(kf, 1, 0)  # [nblk, B, blk, H, D]
    vb = jnp.moveaxis(vf, 1, 0)
    (m_f, l_f, acc), _ = lax.scan(body, (m0, l0, acc0),
                                  (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B,Tq,H,D]


# ------------------------------------------- causal training attention
def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN's inverse frequencies [dim/2] (Peng et al. 2023,
    arXiv:2309.00071, as DeepSeek-V2's rotary embedding computes them):
    the pair ``i`` turns at ``theta^(-2i/dim)``, divided by ``factor``
    where its wavelength is longer than the original context allows
    ``beta_slow`` turns of, unchanged where it makes more than
    ``beta_fast``, a linear ramp over the pairs between. Host arithmetic
    (numpy, float64 rounded once): a constant of the program."""
    import numpy as np
    half = dim // 2
    base = np.float64(theta) ** (np.arange(half, dtype=np.float64)
                                 * 2.0 / dim)

    def correction_dim(turns):
        return dim * np.log(original_max_position / (turns * 2.0 * np.pi)) \
            / (2.0 * np.log(np.float64(theta)))
    low = max(int(np.floor(correction_dim(beta_fast))), 0)
    high = min(int(np.ceil(correction_dim(beta_slow))), dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = (1.0 / (factor * base)) * ramp + (1.0 / base) * (1.0 - ramp)
    return inv.astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1`` (1
    for ``factor`` <= 1); a softmax scale takes its square."""
    import math
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_embedding(x, theta: float = 10000.0, inv_freq=None):
    """Rotary position embedding over the whole head size of ``x``
    [B, T, H, D] at positions 0..T-1: rotate-half pairs ``(i, i + D/2)``,
    angle ``pos * theta^(-2i/D)`` (Su et al. 2021, as the released
    language models apply it), or ``pos * inv_freq[i]`` where the caller
    hands the D/2 inverse frequencies in (:func:`yarn_inv_freq`). Angles
    and the rotation are float32; the result has ``x``'s dtype."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-2.0 * jnp.log(jnp.float32(theta)) / D)) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


#: query rows a block of :func:`causal_attention` takes. A block reads only
#: the keys at or before its last row, so of the square above the diagonal
#: it computes 1/(2*blocks). Measured on a v5e at [1, 4096, 16, 128] bf16,
#: forward + rematerialised forward + backward of one call (PR 30; the
#: probe is PR 28's), heads a group from :data:`CAUSAL_SCORE_BYTES`:
#:
#:     rows a block    128     256     512    1,024   autodiff, 256 rows
#:     heads a group    16       8       4       2    (the path replaced)
#:     ms a call       5.42    3.31    3.30    3.53        6.74
#:
#: 512 and 256 tie; 512 makes half as many blocks to compile.
CAUSAL_QUERY_BLOCK = 512

#: bytes of the float32 scores [B, heads of a group, block, keys] the core
#: has in flight at once: the heads are taken in the largest groups whose
#: scores fit, one group and one block after the other, and the compiler
#: then keeps a block's scores, probabilities and the dk/dv accumulators
#: on chip instead of in HBM. Same probe, 512 rows: 16 MiB (2 heads)
#: 3.34 ms, 32 MiB (4) 3.30, 64 MiB (8) 4.17; all 16 heads at 256 rows
#: (64 MiB) 5.84, with the barrier between blocks or without.
CAUSAL_SCORE_BYTES = 32 << 20

_CORE_LOWERED = _prof.get_registry().counter(
    "dl4j_attn_core_lowered_total",
    "Traces of ops.attention.causal_attention (one a lowering of each "
    "call site, not one a step) by the path its shapes took: query "
    "blocks, or one block because T is no multiple of the block",
    labelnames=("path",))

_QK, _PV, _PTX = "bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd", "bhqk,bqhd->bkhd"
# the same three with fewer key/value heads than query heads: ``q`` is
# [B, T, Hk, G, D], the ``G`` query heads that read key/value head ``h``
# side by side; the products over ``q`` rows sum over the readers too
_QK_G, _PV_G, _PTX_G = ("bqhgd,bkhd->bhgqk", "bhgqk,bkhd->bqhgd",
                        "bhgqk,bqhgd->bkhd")


def _fits(B, g, blk, T):
    return 4 * B * g * blk * T <= CAUSAL_SCORE_BYTES


def _causal_plan(B, T, H, Hk=None):
    """``(block, heads a group)`` for a call's shapes: one path, whose
    block and group counts follow from what it is handed. A group of
    query heads carries its key/value heads with it, so with ``Hk`` <
    ``H`` key/value heads a group holds a multiple of ``H / Hk`` heads;
    where not even the smallest group's scores fit
    :data:`CAUSAL_SCORE_BYTES` the block is halved (to no fewer than 128
    rows: with ``H / Hk`` readers a key/value head a product still has
    512)."""
    blk = CAUSAL_QUERY_BLOCK if T % CAUSAL_QUERY_BLOCK == 0 else T
    least = H // (Hk or H)
    while blk < T and blk > 128 and not _fits(B, least, blk, T):
        blk //= 2
    fit = [g for g in range(least, H + 1, least)
           if H % g == 0 and _fits(B, g, blk, T)]
    return blk, max(fit, default=least)


def _plan_of(q, k):
    """``(block, heads a group, sequences a group)`` of a call: all the
    sequences at once, unless not even the smallest head group's scores
    then fit :data:`CAUSAL_SCORE_BYTES` (rows of a batch never meet in
    the core); :func:`_causal_plan` for what a group of them is handed."""
    (B, T, H), Hk = q.shape[:3], k.shape[2]
    blk = CAUSAL_QUERY_BLOCK if T % CAUSAL_QUERY_BLOCK == 0 else T
    bg = max((b for b in range(1, B + 1)
              if B % b == 0 and _fits(b, H // Hk, blk, T)), default=1)
    return (*_causal_plan(bg, T, H, Hk), bg)


def _then(nxt, done):
    """Both unchanged, but ``nxt`` exists only once ``done`` does: the
    compiler may not start the next block before the last has finished,
    so one block's [q, k] tensors are alive at a time and stay on chip."""
    return lax.optimization_barrier((nxt, done))


def _rows(x, i, e, axis=1):
    return lax.slice_in_dim(x, i, e, axis=axis)


def _scores(q, k, first_row, scale=None):
    """float32 ``q k^T * scale`` (``1 / sqrt(D)`` unless handed in) of the query rows ``first_row..``
    against the keys ``0..``, the keys after a row at -1e30. The mask
    rides in the product's own fusion over the whole block: cut to the
    diagonal tile it costs a concatenation of the scores (v5e: 11.1 ms
    against 5.8 for the probe above)."""
    s = jnp.einsum(_QK if q.ndim == 4 else _QK_G, q, k,
                   preferred_element_type=jnp.float32) \
        * (q.shape[-1] ** -0.5 if scale is None else scale)
    rows = first_row + jnp.arange(q.shape[1])
    return jnp.where(rows[:, None] >= jnp.arange(k.shape[1])[None, :],
                     s, jnp.float32(-1e30))


def _over_head_groups(fn, hg, args, head_axes, out_axes):
    """``fn`` on groups of ``hg`` query heads, one after the other; an
    argument with fewer heads (keys, values and their gradients under
    grouped key/value heads) is cut into as many groups of its own."""
    H = args[0].shape[2]
    if hg == H:
        return fn(*args)
    parts = []
    for h in range(0, H, hg):
        xs = [_rows(x, h * x.shape[ax] // H, (h + hg) * x.shape[ax] // H, ax)
              for x, ax in zip(args, head_axes)]
        if parts:
            xs, parts[-1] = _then(xs, parts[-1])
        parts.append(fn(*xs))
    return tuple(jnp.concatenate([p[n] for p in parts], ax)
                 for n, ax in enumerate(out_axes))


def _over_groups(fn, hg, bg, args, head_axes, out_axes):
    """:func:`_over_head_groups` on groups of ``bg`` sequences, one after
    the other (rows of a batch never meet in the core)."""
    B = args[0].shape[0]
    if bg == B:
        return _over_head_groups(fn, hg, args, head_axes, out_axes)
    parts = []
    for b in range(0, B, bg):
        xs = [_rows(x, b, b + bg, 0) for x in args]
        if parts:
            xs, parts[-1] = _then(xs, parts[-1])
        parts.append(_over_head_groups(fn, hg, xs, head_axes, out_axes))
    return tuple(jnp.concatenate([p[n] for p in parts], 0)
                 for n in range(len(out_axes)))


def _readers(q, k):
    """``q``-shaped [B, T, H, D] as [B, T, Hk, G, D] where ``k`` has fewer
    heads (query head ``h`` reads key/value head ``h // G``: a reshape, no
    copy); as it is where every head has its own."""
    H, Hk = q.shape[2], k.shape[2]
    return q if H == Hk else q.reshape(q.shape[:2] + (Hk, H // Hk,
                                                      q.shape[-1]))


# The two functions below are jitted for what a jit shares, not for a
# dispatch: a step calls them once a head group, a layer application, a
# pass and a phase (96 times each in a step of 4 passes of 6 layers), and
# JAX then traces and lowers each once and calls it by name; XLA inlines
# the calls, each under its caller's scopes, so the compiled step and its
# map are what they would be written out (PR 30: set-up 83 s against the
# parent's 38 with every block of every call traced anew).
@functools.partial(jax.jit, static_argnums=(3, 4))
def _fwd_heads(q, k, v, blk, scale=None):
    """``(O, lse)`` of these heads: the output and the float32 row
    log-sum-exp [B, H, T] of the scaled, masked scores, a block of ``blk``
    query rows after the other; nothing of [T, T] leaves it. With fewer
    key/value heads than query heads a product takes the readers of a
    key/value head together (:func:`_readers`)."""
    shape, q = q.shape[:3] + v.shape[-1:], _readers(q, k)
    pv = _PV if q.ndim == 4 else _PV_G
    outs, lses = [], []
    for i in range(0, q.shape[1], blk):
        e = i + blk
        qi = _rows(q, i, e)
        if outs:
            qi, outs[-1] = _then(qi, outs[-1])
        s = _scores(qi, _rows(k, 0, e), i, scale)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum(pv, p.astype(v.dtype), _rows(v, 0, e),
                       preferred_element_type=jnp.float32)
        outs.append((o / jnp.moveaxis(l, -1, 1)[..., None]).astype(q.dtype))
        lses.append(m + jnp.log(l))
    return (jnp.concatenate(outs, 1).reshape(shape),
            jnp.concatenate(lses, -1).reshape(shape[0], shape[2], shape[1]))


@functools.partial(jax.jit, static_argnums=(6, 7))
def _bwd_heads(q, k, v, o, lse, do, blk, scale=None):
    """``(dq, dk, dv)`` of these heads, FlashAttention's backward (Dao et
    al. 2022, algorithm 4) in plain matmuls: ``p`` again from the scores
    and ``lse``, ``delta = rowsum(dO * O)`` over [T, D], ``ds = p * (dO
    v^T - delta) / sqrt(D)``; bf16 operands, float32 products, ``dk`` and
    ``dv`` summed in float32 over the query blocks (and, with fewer
    key/value heads than query heads, over a key/value head's readers
    inside the products) and rounded once."""
    softmax_scale, scale = scale, \
        (q.shape[-1] ** -0.5 if scale is None else scale)
    shape, q, o, do = q.shape, *(_readers(x, k) for x in (q, o, do))
    qk, pv, ptx = (_QK, _PV, _PTX) if q.ndim == 4 else (_QK_G, _PV_G, _PTX_G)
    last = q.ndim - 2       # the rows' axis of [B, H, (G,) T] statistics
    lse = lse.reshape(q.shape[0], *q.shape[2:-1], q.shape[1])
    delta = jnp.moveaxis(jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), -1), 1, -1)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    dqs = []
    for i in range(0, q.shape[1], blk):
        e = i + blk
        qi, doi, ki, vi = (_rows(q, i, e), _rows(do, i, e),
                           _rows(k, 0, e), _rows(v, 0, e))
        if dqs:
            (qi, doi), (dqs[-1], dk, dv) = _then(
                (qi, doi), (dqs[-1], dk, dv))
        p = jnp.exp(_scores(qi, ki, i, softmax_scale)
                    - _rows(lse, i, e, last)[..., None])
        dvi = jnp.einsum(ptx, p.astype(v.dtype), doi,
                         preferred_element_type=jnp.float32)
        dp = jnp.einsum(qk, doi, vi, preferred_element_type=jnp.float32)
        ds = (p * (dp - _rows(delta, i, e, last)[..., None]) * scale) \
            .astype(q.dtype)
        dqs.append(jnp.einsum(pv, ds, ki, preferred_element_type=jnp.float32)
                   .astype(q.dtype))
        dki = jnp.einsum(ptx, ds, qi, preferred_element_type=jnp.float32)
        dk = lax.dynamic_update_slice_in_dim(dk, _rows(dk, 0, e) + dki, 0, 1)
        dv = lax.dynamic_update_slice_in_dim(dv, _rows(dv, 0, e) + dvi, 0, 1)
    return (jnp.concatenate(dqs, 1).reshape(shape), dk.astype(k.dtype),
            dv.astype(v.dtype))


def _causal_fwd(q, k, v, scale=None):
    """``(O, lse)`` of every head, a group after the other."""
    blk, hg, bg = _plan_of(q, k)
    return _over_groups(lambda *xs: _fwd_heads(*xs, blk, scale), hg, bg,
                        (q, k, v), (2, 2, 2), (2, 1))


def _causal_bwd(scale, res, do):
    """``(dq, dk, dv)`` from what the forward kept and ``dO``."""
    blk, hg, bg = _plan_of(*res[:2])
    return _over_groups(lambda *xs: _bwd_heads(*xs, blk, scale), hg, bg,
                        (*res, do), (2, 2, 2, 2, 1, 2), (2, 2, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _causal_core(q, k, v, scale):
    return _causal_fwd(q, k, v, scale)[0]


def _causal_core_fwd(q, k, v, scale):
    o, lse = _causal_fwd(q, k, v, scale)
    return o, (q, k, v, o, lse)


_causal_core.defvjp(_causal_core_fwd, _causal_bwd)


def causal_attention(q, k, v, scale: float = None):
    """Causal scaled dot-product attention for a training step, ``q``
    [B, T, H, D], ``k`` [B, T, Hk, D], ``v`` [B, T, Hk, Dv] (latent
    attention's value heads are narrower than its query/key heads; the
    output has ``v``'s; with ``Hk`` < ``H``, grouped-query attention, query
    head ``h`` reads key/value head ``h // (H / Hk)``, no copy of ``k`` or
    ``v`` a reader is made, and ``dk``, ``dv`` are summed over the readers
    in float32 inside the backward's products), the
    scores times ``scale`` (``1 / sqrt(D)`` unless handed in: YaRN's
    temperature rides in it): a forward and a backward written by hand (``jax.custom_vjp``)
    in plain XLA matmuls over query blocks of :data:`CAUSAL_QUERY_BLOCK`
    rows, each against the keys up to its own last row, the heads in
    groups sized by :data:`CAUSAL_SCORE_BYTES`; a ``T`` that is no
    multiple of the block runs the same pair as one block. What the
    backward keeps is ``q, k, v``, the output and the float32 row
    log-sum-exp, so no [T, T] tensor outlives the call, inside a
    rematerialised stretch or outside one. Scores, softmax statistics
    and every accumulator are float32; the probabilities are rounded
    once, to ``v``'s dtype, before a product."""
    if q.shape[2] % k.shape[2] or v.shape[2] != k.shape[2]:
        raise ValueError(
            f"causal_attention: {q.shape[2]} query heads do not divide "
            f"over {k.shape[2]} key and {v.shape[2]} value heads")
    blk = _plan_of(q, k)[0]
    _CORE_LOWERED.labels("blocked" if blk < q.shape[1] else "single").inc()
    return _causal_core(q, k, v, None if scale is None else float(scale))


# --------------------------------------------------- reference-layout shims
def dot_product_attention_ncw(q_ncw, k_ncw, v_ncw, mask=None, scaled=True):
    """Reference layout: queries [B, E, Tq], keys/values [B, E, Tk]
    (ref: DL4J attention ops use the NCW time-series layout)."""
    q = jnp.transpose(q_ncw, (0, 2, 1))[:, :, None, :]  # [B,Tq,1,E]
    k = jnp.transpose(k_ncw, (0, 2, 1))[:, :, None, :]
    v = jnp.transpose(v_ncw, (0, 2, 1))[:, :, None, :]
    m = None
    if mask is not None:  # [B, Tk] -> [B,1,1,Tk]
        m = mask[:, None, None, :]
    out = dot_product_attention(q, k, v, mask=m, scaled=scaled)
    return jnp.transpose(out[:, :, 0, :], (0, 2, 1))  # back to [B, E, Tq]
