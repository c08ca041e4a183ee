"""Row moves by DMA: the routed path's dispatch and combine as Pallas
kernels (``nn.layers.SparseExpertsLayer``).

A row gather of ``[N, C]`` bfloat16 rows is what the compiler's own
``gather`` does slowly on a TPU: an array's rows are interleaved in
tiles of 8 (pairs of bfloat16 rows share each 32-bit word), so no copy
can take one row of them, and a gather of 4 KB rows reads far more than
it keeps. Here the source is first laid out one row a group of whole
tiles (``[N, planes, 128]``, :func:`_as_planes`: a row's bytes
contiguous), one copy by the compiler; the kernels then start one
asynchronous copy a row from the index they read, straight from HBM, and
write whole blocks of rows in the ordinary layout.

- :func:`gather_rows` is ``src.at[idx].get()``: blocks of output rows,
  the next block's copies in flight while the current one is written.
- :func:`combine_rows` is the routed path's ``D^T`` in one pass: for
  each token its at most ``k`` rows, each times its float32 weight,
  added in float32 in the order of the rows (the first row, then the
  next), cast once, one row a token; zeros for a token with no row.

Both equal their ``jnp`` forms bit for bit (``tests/test_row_kernels.py``).
The kernels are named ``dl4j_moe_rows_gather`` / ``dl4j_moe_rows_combine``
(a trace's step map finds them by that prefix). They are written for the TPU: the CPU tests run them under
``pltpu.force_tpu_interpret_mode()``. The indices they read live in
scalar memory whole, and a pass of few rows keeps the compiler's gathers
(:func:`supported`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: VMEM the blocks of one call may hold: row buffers, the output block
#: (double-buffered by the pipeline) and the block's temporaries
_BLOCK_BYTES = 12 * 2 ** 20
#: the scoped VMEM limit the kernels ask for (a v5e core holds 128 MiB)
_VMEM_LIMIT = 32 * 2 ** 20
_MAX_BLOCK = 512
#: scalar memory the index vectors of one call may take (of 1 MiB)
_SMEM_BYTES = 768 * 2 ** 10
#: the least a pass's rows may hold for the kernels to pay. Each call
#: first lays its source out anew (a copy of it), and starts, waits and
#: prefetches its indices whatever its size; the compiler's gather costs
#: by the row. One sparse-expert layer, forward + run again + backward,
#: the compiler's gathers -> the kernels (v5e):
#:   32,768 rows x 2048 bf16 (128 MiB)  36.89 -> 31.81 ms
#:    4,096 rows x 3584 bf16 ( 28 MiB)   5.93 ->  6.28 ms
#: (no layer was measured between the two)
_MIN_BYTES = 64 * 2 ** 20

GATHER_NAME = "dl4j_moe_rows_gather"
COMBINE_NAME = "dl4j_moe_rows_combine"


def supported(width: int, rows: int, tokens: int, itemsize: int) -> bool:
    """Whether the kernels move ``rows`` rows of ``width`` elements of
    ``itemsize`` bytes to and from ``tokens`` tokens, and pay for it: a
    whole number of 128-lane groups, the combine's five index vectors
    (two of ``tokens``, two of ``rows`` and a few, one a block) in scalar
    memory, and rows enough (:data:`_MIN_BYTES`)."""
    return width % LANES == 0 \
        and 4 * (2 * tokens + 2 * rows + tokens // 8 + 32) <= _SMEM_BYTES \
        and rows * width * itemsize >= _MIN_BYTES


def _planes(width):
    """Sublane rows of one row laid out as whole tiles ``[planes, 128]``:
    ``width / 128`` rounded up to 8 (a tile's rows, for 32 and 16 bits
    alike)."""
    return -(-width // (8 * LANES)) * 8


def _as_planes(a):
    """``a`` [n, width] as [n, planes, 128], zeros past ``width``: the
    one copy of the source the kernels need."""
    n, width = a.shape
    planes = _planes(width)
    if planes * LANES != width:
        a = jnp.pad(a, ((0, 0), (0, planes * LANES - width)))
    return a.reshape(n, planes, LANES)


def _block(count, per_row):
    """Rows a block: the largest power of two up to ``_MAX_BLOCK`` whose
    buffers fit ``_BLOCK_BYTES``, under twice ``count``, at least 8."""
    block = _MAX_BLOCK
    while block > 8 and (block * per_row > _BLOCK_BYTES
                         or block >= 2 * count):
        block //= 2
    return block


def _call(kernel, out_shape, grid, scalars, operand, block, scratch, name):
    """The two kernels' call: ``scalars`` in scalar memory whole, the
    row source ``operand`` in HBM, blocks of ``block`` output rows."""
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, out_shape.shape[1]),
                                   lambda b, *_: (b, 0)),
            scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=name,
    )(*scalars, operand)


def _each(count, body):
    """``body(i)`` for each ``i < count``, eight a turn of the loop."""
    def eight(j, carry):
        for u in range(8):
            body(j * 8 + u)
        return carry
    jax.lax.fori_loop(0, count // 8, eight, 0)

    def one(i, carry):
        body(count // 8 * 8 + i)
        return carry
    jax.lax.fori_loop(0, count % 8, one, 0)


def _wait(buf, sem, count):
    """Wait for ``count`` one-row copies into ``buf`` [rows, ...] under
    ``sem``. A copy's semaphore counts bytes, so a descriptor of 64 rows
    waits for 64 of them: 64 rows a wait, then 8, then one."""
    for size in (64, 8, 1):
        if size > buf.shape[0]:
            continue
        rows = buf.at[pl.ds(0, size)]

        def one(i, carry):
            pltpu.make_async_copy(rows, rows, sem).wait()
            return carry
        jax.lax.fori_loop(0, count // size, one, 0)
        count = count % size


# ------------------------------------------------------------- gather

def _gather_kernel(idx_ref, src_ref, out_ref, buf, sem, *, block, last):
    b, nb = pl.program_id(0), pl.num_programs(0)
    slot = b % 2

    def start(j, into, count):
        _each(count, lambda r: pltpu.make_async_copy(
            src_ref.at[idx_ref[j * block + r]], buf.at[into, r],
            sem.at[into]).start())

    @pl.when(b == 0)
    def _():
        start(0, 0, block if nb > 1 else last)

    @pl.when(b + 1 < nb)
    def _():
        start(b + 1, 1 - slot, jnp.where(b + 2 < nb, block, last))

    _wait(buf.at[slot], sem.at[slot], jnp.where(b + 1 < nb, block, last))
    out_ref[...] = buf[slot].reshape(block, -1)[:, :out_ref.shape[1]]


def gather_rows(src, idx):
    """``src[idx]`` [M, C] of rows ``src`` [N, C] and ``idx`` [M] int32,
    each in ``[0, N)`` (unchecked, as ``mode="promise_in_bounds"``)."""
    width, m, planes = src.shape[1], idx.shape[0], _planes(src.shape[1])
    block = _block(m, (2 * planes * LANES + 3 * width) * src.dtype.itemsize)
    nb = -(-m // block)
    return _call(
        functools.partial(_gather_kernel, block=block,
                          last=m - (nb - 1) * block),
        jax.ShapeDtypeStruct((m, width), src.dtype), (nb,),
        (idx.astype(jnp.int32),), _as_planes(src), block,
        [pltpu.VMEM((2, block, planes, LANES), src.dtype),
         pltpu.SemaphoreType.DMA((2,))],
        GATHER_NAME)


# ------------------------------------------------------------ combine

def _combine_kernel(starts_ref, perm_ref, wp_ref, first_ref, count_ref,
                    y_ref, out_ref, buf, term, res, sem, *, k, block,
                    tokens):
    b, nb = pl.program_id(0), pl.num_programs(0)
    slot = b % 2
    cap = buf.shape[1]

    # block j's rows are the positions [starts[j], starts[j + 1]) of the
    # token order, copied to buf[slot] in that order
    def start(j, into):
        lo = starts_ref[j]
        _each(starts_ref[j + 1] - lo, lambda q: pltpu.make_async_copy(
            y_ref.at[perm_ref[lo + q]], buf.at[into, q],
            sem.at[into]).start())

    @pl.when(b == 0)
    def _():
        start(0, 0)

    @pl.when(b + 1 < nb)
    def _():
        start(b + 1, 1 - slot)

    lo, f32 = starts_ref[b], jnp.float32
    _wait(buf.at[slot], sem.at[slot], starts_ref[b + 1] - lo)

    # _to_tokens's sum, term for term: where(row c is there, row c times
    # its weight, 0) added from c = 0 up, for all k rows of every token
    # (a branch a token costs more than the rows it would skip). Each
    # term is stored before it is added: a product that fed the add
    # directly could be fused with it into one rounding (a CPU's
    # compiler does so)
    def token(t):
        g = jnp.minimum(b * block + t, tokens - 1)
        n, at = count_ref[g], first_ref[g]
        for c in range(k):
            row = buf[slot, jnp.clip(at - lo + c, 0, cap - 1)]
            term[c] = jnp.where(c < n, row.astype(f32) * wp_ref[at + c], 0.0)
        total = term[0]
        for c in range(1, k):
            total = total + term[c]
        res[t] = total.astype(res.dtype)
    _each(block, token)
    out_ref[...] = res[...].reshape(block, -1)[:, :out_ref.shape[1]]


def combine_rows(y, w, perm, token, first, count, *, k):
    """``D^T`` [tokens, C] of the rows ``y`` [rows, C], each weighted by
    ``w`` [rows] float32, as :func:`nn.layers._to_tokens` has it:
    ``perm`` brings the rows into token order, ``token`` is each
    position's token there (``tokens`` past the held ones), a token's
    ``count`` rows (at most ``k``) start at position ``first``. Rows are
    added in float32 from the first up and cast to ``y``'s dtype; zeros
    where a token has none. ``perm`` and ``token`` hold at least ``k``
    positions past the last held one."""
    width, tokens = y.shape[1], first.shape[0]
    planes = (_planes(width), LANES)
    block = _block(tokens, ((2 * k + 1) * planes[0] * LANES + 3 * width)
                   * y.dtype.itemsize)
    nb = -(-tokens // block)
    i32 = jnp.int32
    # a block's rows in token order: those of its tokens (the last block's
    # end is the tokens' end, past which lie the rows that hold no pair)
    ends = jnp.minimum(jnp.arange(nb + 1, dtype=i32) * block, tokens)
    starts = jnp.searchsorted(token, ends, method="compare_all").astype(i32)
    return _call(
        functools.partial(_combine_kernel, k=k, block=block, tokens=tokens),
        jax.ShapeDtypeStruct((tokens, width), y.dtype), (nb,),
        (starts, perm.astype(i32), w[perm].astype(jnp.float32),
         first.astype(i32), count.astype(i32)), _as_planes(y), block,
        [pltpu.VMEM((2, k * block) + planes, y.dtype),
         pltpu.VMEM((k,) + planes, jnp.float32),
         pltpu.VMEM((block,) + planes, y.dtype),
         pltpu.SemaphoreType.DMA((2,))],
        COMBINE_NAME)
