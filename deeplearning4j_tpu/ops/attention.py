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


import jax
import jax.numpy as jnp
from jax import lax


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
def rotary_embedding(x, theta: float = 10000.0):
    """Rotary position embedding over the whole head size of ``x``
    [B, T, H, D] at positions 0..T-1: rotate-half pairs ``(i, i + D/2)``,
    angle ``pos * theta^(-2i/D)`` (Su et al. 2021, as the released
    language models apply it). Angles and the rotation are float32; the
    result has ``x``'s dtype."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-2.0 * jnp.log(jnp.float32(theta)) / D))
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
#: forward + rematerialised forward + backward of one call (PR 28): the
#: whole square 22.5 ms, blocks of 2,048 17.5, 1,024 13.7, 512 8.2, 256
#: 5.7; this repo's Pallas flash forward with its blockwise backward 9.8,
#: jax's Pallas TPU flash attention 19.7. So plain matmuls in blocks of
#: 256, and no kernel, whatever the backend.
CAUSAL_QUERY_BLOCK = 256


def _causal_block(q, k, v, first_row: int):
    """Rows ``first_row..`` of ``q`` against the keys ``k`` (all at or
    before the block's last row): float32 scores and softmax, the
    probabilities rounded to ``v``'s dtype for the weighted sum."""
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    rows = first_row + jnp.arange(q.shape[1])
    s = jnp.where(rows[:, None] >= jnp.arange(k.shape[1])[None, :],
                  s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _causal_blocks(q, k, v):
    T = q.shape[1]
    blk = CAUSAL_QUERY_BLOCK if T % CAUSAL_QUERY_BLOCK == 0 else T
    outs = [_causal_block(q[:, i:i + blk], k[:, :i + blk], v[:, :i + blk], i)
            for i in range(0, T, blk)]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def causal_attention(q, k, v, *, remat: bool = True):
    """Causal scaled dot-product attention for a training step, [B, T, H,
    D] each. Plain XLA matmuls over query blocks of
    :data:`CAUSAL_QUERY_BLOCK` rows, each against the keys up to its own
    last row. ``remat``: rematerialise the call in the backward pass, so
    that no [T, T] probability tensor outlives it; a caller that already
    runs inside a rematerialised stretch passes False, or the core would
    be computed a third time for nothing."""
    fn = jax.checkpoint(_causal_blocks) if remat else _causal_blocks
    return fn(q, k, v)


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
