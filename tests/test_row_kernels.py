"""The routed path's DMA row kernels (``ops/row_kernels.py``) on the CPU,
under Pallas' TPU interpreter: ``gather_rows`` against ``src.at[idx]``,
``combine_rows`` against ``nn.layers._to_tokens``, and ``_routed`` through
both paths, all bit for bit; which path a CPU lowers (the counter)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu import profiler
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.ops import row_kernels as rk

K, EXPERTS, HELD = 4, 16, 4


def same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and np.array_equal(a.view(np.uint8),
                                                 b.view(np.uint8))


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 16 rows, so that a few dozen rows take several blocks
    and a last one that is not full (the interpreter's time goes with the
    rows)."""
    monkeypatch.setattr(rk, "_MAX_BLOCK", 16)


def forget_routed_traces():
    for fn in (L._pass_fwd, L._pass_bwd, L._routed_fwd, L._routed_bwd):
        fn.clear_cache()


def routing(tokens, seed, all_held=0, none_held=0):
    """``(order, inv, load)`` of top-4 choices over 16 experts of which
    the first 4 are held; the first ``all_held`` tokens take the 4 held
    ones, the next ``none_held`` none of them."""
    rng = np.random.default_rng(seed)
    sel = np.stack([rng.permutation(EXPERTS)[:K] for _ in range(tokens)])
    sel[:all_held] = np.arange(HELD)
    sel[all_held:all_held + none_held] = HELD + np.arange(K)
    local = np.where(sel < HELD, sel, HELD).reshape(-1)
    order = np.argsort(local, kind="stable").astype(np.int32)
    load = np.bincount(local, minlength=HELD + 1)[:HELD].astype(np.int32)
    return (jnp.asarray(order), jnp.asarray(np.argsort(order)
                                            .astype(np.int32)),
            jnp.asarray(load))


def a_pass(tokens, seed, lo_pass, **kw):
    """One pass's indices (``_pass_index``) as ``_routed_fwd`` makes them:
    ``order`` padded past the pairs, rows a pass by ``_routed_rows``."""
    order, inv, load = routing(tokens, seed, **kw)
    rows = L._routed_rows(tokens * K, HELD, EXPERTS)
    order = jnp.pad(order, (0, 2 * rows))
    return rows, L._pass_index(order, inv, load, jnp.int32(lo_pass * rows),
                               rows, K)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("width", [2048, 3584])
def test_gather_rows_is_the_compilers_gather_bit_for_bit(small_blocks,
                                                         dtype, width):
    rng = np.random.default_rng(width)
    src = jnp.asarray(rng.standard_normal((96, width)), dtype)
    # 40 rows: two blocks of 16 and one of 8; a pass's rows past its
    # pairs (index 0 of the padded order) among them
    rows, (idx, *_) = a_pass(16, 1, 0)
    at = jnp.concatenate([idx // K, jnp.asarray(
        rng.integers(0, 96, 40 - rows), jnp.int32)])
    with pltpu.force_tpu_interpret_mode():
        got = rk.gather_rows(src, at)
    assert same(got, src.at[at].get(mode="promise_in_bounds"))


@pytest.mark.parametrize("weights", ["gates", "ones"])
@pytest.mark.parametrize("lo_pass", [0, 1])
def test_combine_rows_is_to_tokens_bit_for_bit(small_blocks, weights,
                                               lo_pass):
    # 60 tokens (three blocks of 16 and one of 12): the first take all 4
    # held experts, the next four none; 28 such tokens fill the first
    # pass's 120 rows and leave some to the second, where a token has one
    # row (its last held expert's)
    tokens = 60
    rows, (idx, valid, _, perm, token, first, count, _) = a_pass(
        tokens, 2, lo_pass, all_held=4 + 24 * lo_pass, none_held=4)
    counts = np.bincount(np.asarray(count), minlength=K + 1)
    assert counts[0] and counts[1] and (lo_pass or counts[K])
    assert not np.asarray(valid).all()          # rows past the held pairs
    rng = np.random.default_rng(3)
    y = jnp.asarray(rng.standard_normal((rows, 256)), jnp.bfloat16)
    gate = jnp.asarray(rng.random((tokens, K)), jnp.float32)
    w = jnp.where(valid, gate.reshape(-1)[idx], 0) if weights == "gates" \
        else valid.astype(jnp.float32)
    with pltpu.force_tpu_interpret_mode():
        got = rk.combine_rows(y, w, perm, token, first, count, k=K)
    assert same(got, L._to_tokens(y, w, perm, token, first, K))


def test_combine_rows_adds_in_float32_as_to_tokens_does(small_blocks):
    tokens = 60
    rows, (idx, valid, _, perm, token, first, count, _) = a_pass(
        tokens, 4, 0, all_held=12)
    rng = np.random.default_rng(5)
    y = jnp.asarray(rng.standard_normal((rows, 384)), jnp.float32)
    y = y.at[3].set(-0.0)                   # -0.0 x w + 0.0 is +0.0
    w = jnp.where(valid, jnp.asarray(rng.random(rows), jnp.float32), 0)
    with pltpu.force_tpu_interpret_mode():
        got = rk.combine_rows(y, w, perm, token, first, count, k=K)
    assert same(got, L._to_tokens(y, w, perm, token, first, K))


def test_supported_shapes():
    # the hybrid cell's pass, 32,768 rows of 2048 bf16: the kernels
    assert rk.supported(2048, 32768, 32768, 2)
    assert rk.supported(3584, 16384, 16384, 2)
    # the sparse cell's, 4,096 rows of 3584: too few rows to pay
    assert not rk.supported(3584, 4096, 4096, 2)
    assert not rk.supported(3584, 4096, 4096, 4)
    assert not rk.supported(2000, 32768, 32768, 2)
    # every expert held: the combine's indices outgrow scalar memory
    assert not rk.supported(2048, 4 * 32768, 32768, 2)


def test_a_tpu_takes_the_kernels_for_the_hybrid_cell_alone(monkeypatch):
    monkeypatch.setattr(L.jax, "default_backend", lambda: "tpu")
    assert L._rows_by_kernel(2048, 32768, 32768, jnp.bfloat16)
    assert not L._rows_by_kernel(3584, 4096, 4096, jnp.bfloat16)


@pytest.mark.parametrize("passes", [1, 2])
def test_routed_through_the_kernels_is_the_jnp_path(small_blocks, passes,
                                                   monkeypatch):
    tokens, width, hidden = 32, 128, 32
    layer = L.SparseExpertsLayer(nExperts=EXPERTS, nExpertsPerTok=K,
                                 nHidden=hidden, heldExperts=range(HELD),
                                 nSharedExperts=0, weightInit="xavier")
    layer.infer_nin(InputType.recurrent(width, tokens))
    params, _ = layer.initialize(jax.random.PRNGKey(0))
    rng = np.random.default_rng(passes)
    x = jnp.asarray(rng.standard_normal((tokens, width)), jnp.bfloat16)
    # a step that takes two passes: held pairs past the first pass's rows
    order, inv, load = routing(tokens, passes, all_held=20 * (passes - 1))
    rows = L._routed_rows(tokens * K, HELD, EXPERTS)
    assert (-(-int(load.sum()) // rows)) == passes
    gate = jnp.asarray(rng.random((tokens, K)), jnp.float32)
    ws = [params[n].astype(jnp.bfloat16) for n in ("Eg", "Eu", "Ed")]
    f = jax.nn.silu
    cot = jnp.asarray(rng.standard_normal((tokens, width)), jnp.bfloat16)

    def run():
        out, back = jax.vjp(lambda x, gate, *ws: L._routed(
            x, gate, *ws, order, inv, load, rows, f), x, gate, *ws)
        return out, back(cot)
    want = run()
    # the path is chosen while the jitted passes trace: a fresh trace
    # under the TPU's choice, and none of it left for later callers
    try:
        with monkeypatch.context() as m:
            m.setattr(L, "_rows_by_kernel", lambda *shape: True)
            forget_routed_traces()
            with pltpu.force_tpu_interpret_mode():
                got = run()
                moved = str(jax.make_jaxpr(run)())
    finally:
        forget_routed_traces()
    assert rk.GATHER_NAME in moved and rk.COMBINE_NAME in moved
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert same(a, b)


def test_a_cpu_lowers_the_compilers_gathers_and_counts_it():
    assert not L._rows_by_kernel(2048, 32768, 32768, jnp.bfloat16)
    layer = L.SparseExpertsLayer(nExperts=EXPERTS, nExpertsPerTok=K,
                                 nHidden=16, heldExperts=range(HELD),
                                 nSharedExperts=0, weightInit="xavier")
    layer.infer_nin(InputType.recurrent(128, 8))
    params, state = layer.initialize(jax.random.PRNGKey(0))
    xla = L._MOE_ROWS_LOWERED.labels("xla").value
    kernel = L._MOE_ROWS_LOWERED.labels("kernel").value
    jax.jit(lambda p, x: layer.apply(p, state, x, True, None)[0]).lower(
        params, jnp.zeros((1, 8, 128), jnp.float32))
    assert L._MOE_ROWS_LOWERED.labels("xla").value == xla + 1
    assert L._MOE_ROWS_LOWERED.labels("kernel").value == kernel
    assert profiler.get_registry().get("dl4j_moe_rows_lowered_total") \
        is L._MOE_ROWS_LOWERED
