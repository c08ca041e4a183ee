"""Pipeline parallelism: GPipe-style microbatched stage execution over a
``pipe`` mesh axis.

Reference parity: the reference has NO pipeline parallelism (SURVEY.md
§2.3 marks it "optional later") — this is capability the TPU-native
framework adds. Design follows the scaling-book recipe rather than
GPipe's original per-device threading: stage weights are sharded over the
``pipe`` axis of the same ``jax.sharding.Mesh`` every other strategy
uses, the schedule is ONE ``lax.fori_loop`` inside ``shard_map``, and
stage-to-stage transfer is ``lax.ppermute`` riding ICI. Reverse-mode
autodiff through the loop + ppermute yields the GPipe backward schedule
automatically — no hand-written backward pipeline.

Schedule (P stages, M microbatches, T = M + P - 1 ticks):

    tick t: stage 0 injects microbatch t (while t < M); every stage s
    runs its block on the activation it holds; results ppermute s -> s+1;
    stage P-1's result for microbatch t-(P-1) lands in the output buffer.

The bubble fraction is (P-1)/T, exactly GPipe's; raise M to amortize.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import DeviceMesh


def microbatch(x, n_micro: int):
    """[B, ...] -> [n_micro, B/n_micro, ...]."""
    B = x.shape[0]
    if B % n_micro:
        raise ValueError(f"batch {B} not divisible by n_micro={n_micro}")
    return x.reshape((n_micro, B // n_micro) + x.shape[1:])


def unmicrobatch(x):
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def stack_stage_params(layer_params_list):
    """List of per-layer pytrees (identical structure) -> one pytree whose
    leaves gain a leading layer dim [L, ...] — the shape ``pipe`` shards."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                  *layer_params_list)


def pipeline_apply(stage_fn: Callable, stage_params: Any, x, mesh: DeviceMesh,
                   axis: str = "pipe", data_axis: Optional[str] = "data"):
    """Run ``x`` through all pipeline stages.

    ``stage_fn(local_params, act) -> act``: applied once per stage; it
    receives this stage's slice of ``stage_params`` (leading layer dim
    L/P — scan over it for multi-layer stages) and must preserve ``act``'s
    shape. ``stage_params`` leaves are [L, ...] sharded over ``axis`` on
    dim 0. ``x`` is [n_micro, mb, ...] (microbatch the batch first);
    returns the same shape. Differentiable end-to-end.
    """
    m = mesh.mesh
    n_pipe = mesh.size(axis)
    n_micro = x.shape[0]
    if n_micro < n_pipe:
        raise ValueError(f"n_micro={n_micro} < pipeline depth {n_pipe}: "
                         f"every stage needs at least one microbatch")
    other = tuple(a for a in m.axis_names if a != axis)
    p_params = P(axis)
    # microbatch dim replicated; per-microbatch batch dim data-sharded
    p_x = P(None, data_axis) if data_axis in other else P()

    @partial(shard_map, mesh=m, in_specs=(p_params, p_x),
             out_specs=p_x, check_vma=False)
    def run(local_params, xs):
        stage = jax.lax.axis_index(axis)
        ticks = n_micro + n_pipe - 1
        state = jnp.zeros_like(xs[0])            # activation held by stage
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outs = carry
            inject = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, n_micro - 1), axis=0, keepdims=False)
            act = jnp.where(stage == 0, inject, state)
            y = stage_fn(local_params, act)
            # last stage banks microbatch t-(P-1) once the fill completes
            slot = jnp.clip(t - (n_pipe - 1), 0, n_micro - 1)
            write = (stage == n_pipe - 1) & (t >= n_pipe - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, slot, axis=0,
                                               keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, y, cur), slot, axis=0)
            # hand activations downstream (stage P-1's output retires)
            state = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_pipe - 1)])
            return (state, outs), None

        # scan (not fori_loop): the schedule must be reverse-differentiable
        # — backprop through it IS the GPipe backward pipeline
        (_, outs), _ = jax.lax.scan(tick, (state, outs),
                                    jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast over the pipe
        # axis so downstream (head/loss) code sees them everywhere
        outs = jax.lax.psum(
            jnp.where(stage == n_pipe - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    return run(stage_params, x)


# --------------------------------------------------------- flagship wiring

def pipeline_param_shardings(cfg, mesh: DeviceMesh, axis: str = "pipe"):
    """Shardings for ``pipeline_params``: blocks [L, ...] split over the
    pipe axis, embeddings/head replicated (they run data-parallel outside
    the pipeline region)."""
    m = mesh.mesh
    s = lambda *spec: NamedSharding(m, P(*spec))
    blocks = {
        "ln1": {"g": s(axis), "b": s(axis)},
        "wqkv": s(axis), "bqkv": s(axis),
        "wo": s(axis), "bo": s(axis),
        "ln2": {"g": s(axis), "b": s(axis)},
        "w1": s(axis), "b1": s(axis),
        "w2": s(axis), "b2": s(axis),
    }
    out = {"embed": {"tok": s(), "pos": s()},
           "final_norm": {"g": s(), "b": s()},
           "blocks": blocks}
    return out


def to_pipeline_params(params):
    """models.transformer.init_params layout -> pipeline layout: the
    per-layer list becomes stacked [L, ...] leaves."""
    out = {k: v for k, v in params.items() if k != "layers"}
    out["blocks"] = stack_stage_params(params["layers"])
    return out


def _block(lp, x, cfg):
    """One pre-LN transformer block on a microbatch (the body
    models.transformer.forward runs per layer, minus mesh constraints —
    sharding inside shard_map is explicit)."""
    from deeplearning4j_tpu.ops import attention as attn_ops
    from deeplearning4j_tpu.ops import normalization as norm_ops
    B, T, E = x.shape
    H = cfg.n_heads
    ln = lambda v, p: norm_ops.layer_norm(
        v.astype(jnp.float32), p["g"].astype(jnp.float32),
        p["b"].astype(jnp.float32)).astype(cfg.dtype)
    h = ln(x, lp["ln1"])
    qkv = h @ lp["wqkv"] + lp["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    ctx = attn_ops.dot_product_attention(
        q.reshape(B, T, H, E // H), k.reshape(B, T, H, E // H),
        v.reshape(B, T, H, E // H), is_causal=cfg.causal)
    x = x + (ctx.reshape(B, T, E) @ lp["wo"] + lp["bo"])
    h = ln(x, lp["ln2"])
    h = jax.nn.gelu(h @ lp["w1"] + lp["b1"])
    return x + (h @ lp["w2"] + lp["b2"])


def pipeline_loss_fn(params, tokens, targets, cfg, mesh: DeviceMesh,
                     n_micro: int, axis: str = "pipe"):
    """Transformer LM loss with the L blocks executed as a pipeline.
    Embedding + head run data-parallel outside the pipeline region."""
    from deeplearning4j_tpu.ops import normalization as norm_ops
    B, T = tokens.shape
    x = jnp.take(params["embed"]["tok"], tokens, axis=0) \
        + params["embed"]["pos"][:T][None]
    x = x.astype(cfg.dtype)

    def stage_fn(local_blocks, act):
        def body(a, lp):
            return _block(lp, a, cfg), None
        out, _ = jax.lax.scan(body, act, local_blocks)
        return out

    xm = microbatch(x, n_micro)
    ym = pipeline_apply(stage_fn, params["blocks"], xm, mesh, axis=axis)
    x = unmicrobatch(ym)
    x = norm_ops.layer_norm(x.astype(jnp.float32),
                            params["final_norm"]["g"].astype(jnp.float32),
                            params["final_norm"]["b"].astype(jnp.float32))
    head = params["embed"]["tok"].T
    logits = (x.astype(cfg.dtype) @ head.astype(cfg.dtype)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    return jnp.mean(nll)


def make_pipeline_train_step(cfg, updater, mesh: DeviceMesh, n_micro: int,
                             axis: str = "pipe"):
    """Compiled fwd+bwd+update with pipelined blocks (GPipe backward via
    reverse-mode through the schedule)."""

    def step(params, opt_state, t, tokens, targets):
        loss, grads = jax.value_and_grad(pipeline_loss_fn)(
            params, tokens, targets, cfg, mesh, n_micro, axis)
        tf = t.astype(jnp.float32)
        lr = updater.lr_at(tf)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = treedef.flatten_up_to(grads)
        s_leaves = treedef.flatten_up_to(opt_state)
        new_p, new_s = [], []
        for pv, gv, sv in zip(leaves, g_leaves, s_leaves):
            u, s2 = updater.apply(gv.astype(jnp.float32), sv, lr, tf)
            new_p.append((pv.astype(jnp.float32) - u).astype(pv.dtype))
            new_s.append(s2)
        return (jax.tree_util.tree_unflatten(treedef, new_p),
                jax.tree_util.tree_unflatten(treedef, new_s), t + 1, loss)

    return jax.jit(step, donate_argnums=(0, 1, 2))
