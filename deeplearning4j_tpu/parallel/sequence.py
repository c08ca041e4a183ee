"""Sequence/context parallelism: ring attention over the mesh ``seq`` axis.

Reference parity: ABSENT in the reference (SURVEY.md §5 "Long-context /
sequence parallelism: Absent... green-field") — this is the
capability-parity-plus long-context subsystem the rebuild adds: shard the
sequence dimension across devices; keys/values rotate around the ring via
``ppermute`` over ICI while each device accumulates its queries' attention
with an online-softmax (flash-style) update. Memory per device is
O(T/ring) and the KV transfer overlaps with compute.

Layout inside shard_map: q, k, v are [B, T_local, H, D] per-device shards
of a [B, T_global, H, D] tensor sharded on axis 1.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _block_attend(q, kb, vb, q_off, k_off, is_causal, m, l, acc, scale):
    """One flash-style accumulation step against a single KV block.
    q [B,Tq,H,D]; kb,vb [B,Tk,H,D]; returns updated (m, l, acc)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kb).astype(jnp.float32) * scale
    if is_causal:
        q_pos = q_off + jnp.arange(q.shape[1])
        k_pos = k_off + jnp.arange(kb.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, jnp.float32(-1e30))
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
    return m_new, l_new, acc_new


def _ring_attention_local(q, k, v, *, axis_name: str, is_causal: bool,
                          varying_axes=()):
    """Runs INSIDE shard_map: each device owns one sequence block."""
    B, Tl, H, D = q.shape
    size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    q_off = my_idx * Tl

    perm = [(j, (j + 1) % size) for j in range(size)]

    def body(i, carry):
        m, l, acc, kb, vb = carry
        # block currently held originated at rank (my_idx - i) mod size
        src = (my_idx - i) % size
        k_off = src * Tl
        m, l, acc = _block_attend(q, kb, vb, q_off, k_off, is_causal,
                                  m, l, acc, scale)
        # rotate KV around the ring (ICI neighbour exchange)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return m, l, acc, kb, vb

    m0 = jnp.full((B, H, Tl), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    acc0 = jnp.zeros((B, H, Tl, D), jnp.float32)
    # mark the accumulators as device-varying so the loop carry type matches
    # (jax's shard_map varying-manual-axes tracking)
    if varying_axes:
        m0, l0, acc0 = jax.tree_util.tree_map(
            lambda x: lax.pcast(x, tuple(varying_axes), to="varying"),
            (m0, l0, acc0))
    m, l, acc, _, _ = lax.fori_loop(0, size, body, (m0, l0, acc0, k, v))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B,Tl,H,D]


def ring_attention(q, k, v, mesh: Mesh, *, axis_name: str = "seq",
                   is_causal: bool = False, batch_axis: str = "data",
                   head_axis: str = None):
    """Ring attention over a [B, T, H, D] tensor sharded on T.

    q, k, v: global arrays (or shardings compatible with) [B, T, H, D];
    T is split over ``axis_name``; B over ``batch_axis``. Pass
    ``head_axis='model'`` under tensor parallelism so heads stay sharded
    (otherwise GSPMD would allgather QKV over the model axis).
    """
    spec = P(batch_axis, axis_name, head_axis, None)
    varying = tuple(a for a in (batch_axis, axis_name, head_axis) if a)
    fn = shard_map(
        partial(_ring_attention_local, axis_name=axis_name, is_causal=is_causal,
                varying_axes=varying),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def ring_attention_reference(q, k, v, is_causal: bool = False):
    """Single-device reference for tests: exact attention."""
    from deeplearning4j_tpu.ops.attention import dot_product_attention
    return dot_product_attention(q, k, v, is_causal=is_causal)
