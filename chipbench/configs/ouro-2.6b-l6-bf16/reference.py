"""Plain reference for Ouro, a looped language model: embedding, then
``total_ut_steps`` passes over the SAME ``num_layers`` decoder layers
(sandwich RMSNorm, rotary causal attention, SwiGLU), the final norm
closing every pass, an untied head and an exit gate after each, and the
exit-weighted loss (arXiv:2510.25741, first-stage objective). Straight
``jax.numpy`` in float32 through ``chipbench.refnn.Ops``: every matrix
product through ``ops.dense`` (the two of the attention core too, so
``Ops("fp8")`` rounds them as well), the full [S, S] masked softmax a
head. Nothing of the program is imported.

The loops over passes and over layers are ``lax.scan``s (the layers'
weights come stacked, ``stack/<layer>.<leaf>`` of shape [num_layers,
...]): written as Python loops the same arithmetic is a program of 24
layers and four heads whose float32 compile took three minutes in every
run of the cell on the chip (PR 28); scanned it is one layer and one head
long. ``jax.checkpoint`` around each layer application, each attention
head and each block of a pass's head changes no value; it is there so
that the float32 step fits one chip once the program's state is freed (24
applications' [16, S, S] scores would not, nor four [S, V] logits with
their gradients beside Adam's state). For the same reason the attention
heads of a layer, and the rows of a pass's head in blocks of
``HEAD_ROWS``, go through ``lax.map``: one at a time.

``make_loss(cfg, fault=...)`` plants one of two faults the comparison must
catch: ``"three_passes"`` runs one pass fewer than the configuration says;
``"last_pass_grad"`` takes the gradient of the looped layers' weights from
their last use alone (the earlier uses see them through
``stop_gradient``), which is what a loop that forgot to sum its per-use
gradients computes.
"""

import jax
import jax.numpy as jnp

FAULTS = (None, "three_passes", "last_pass_grad")
HEAD_ROWS = 512     # rows of [S, V] logits alive at a time


def rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def rope(x, theta):
    """[S, H, D]: rotate-half pairs (i, i + D/2), angle pos * theta^(-2i/D)."""
    s, _h, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def exit_distribution(lam):
    """[T, ...] gate values to exit probabilities over the first axis."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)


def make_loss(cfg, fault=None):
    if fault not in FAULTS:
        raise ValueError(f"unknown planted fault {fault!r}")
    n_passes = cfg["total_ut_steps"]
    if fault == "three_passes":
        n_passes -= 1
    heads, head_dim = cfg["num_attention_heads"], cfg["head_dim"]
    eps, theta, beta = cfg["rms_norm_eps"], float(cfg["rope_theta"]), \
        cfg["beta"]

    def layer(p, h, ops):
        """One decoder layer on one sequence [S, d]; ``p`` its weights."""
        s = h.shape[0]
        a = rms(h, p["n1/gain"], eps)
        q = rope(ops.dense(a, p["attn/Wq"]).reshape(s, heads, head_dim),
                 theta)
        k = rope(ops.dense(a, p["attn/Wk"]).reshape(s, heads, head_dim),
                 theta)
        v = ops.dense(a, p["attn/Wv"]).reshape(s, heads, head_dim)
        causal = jnp.tril(jnp.ones((s, s), bool))

        def one_head(qkv):
            qh, kh, vh = qkv                              # [S, D] each
            scores = ops.dense(qh, kh.T) / jnp.sqrt(jnp.float32(head_dim))
            w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return ops.dense(w, vh)
        o = jax.lax.map(jax.checkpoint(one_head),
                        tuple(jnp.swapaxes(t, 0, 1)
                              for t in (q, k, v)))             # [H, S, D]
        o = jnp.swapaxes(o, 0, 1).reshape(s, heads * head_dim)
        h = h + rms(ops.dense(o, p["attn/Wo"]), p["n2/gain"], eps)
        m = rms(h, p["n3/gain"], eps)
        gated = jax.nn.silu(ops.dense(m, p["mlp/Wg"])) \
            * ops.dense(m, p["mlp/Wu"])
        return h + rms(ops.dense(gated, p["mlp/Wd"]), p["n4/gain"], eps)

    def head(h, w, gate_w, gate_b, y, ops):
        """(cross-entropy [S], gate value [S]) of one pass's output."""
        def rows(hy):
            hb, yb = hy
            logp = jax.nn.log_softmax(ops.dense(hb, w), axis=-1)
            return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
        blk = HEAD_ROWS if h.shape[0] % HEAD_ROWS == 0 else h.shape[0]
        ce = jax.lax.map(jax.checkpoint(rows),
                         (h.reshape(-1, blk, h.shape[1]),
                          y.reshape(-1, blk))).reshape(-1)
        gate = jnp.dot(h, gate_w, precision=jax.lax.Precision.HIGHEST)
        return ce, jax.nn.sigmoid(gate + gate_b[0])

    def sequence_loss(params, tokens, labels, ops):
        stack = {k[len("stack/"):].replace(".", "/"): v
                 for k, v in params.items() if k.startswith("stack/")}

        def one_pass(h, layers):
            """All layers, the final norm, then the pass's head."""
            h, _ = jax.lax.scan(
                lambda h, p: (jax.checkpoint(
                    lambda p, h: layer(p, h, ops))(p, h), None), h, layers)
            h = rms(h, params["fnorm/gain"], eps)
            return h, jax.checkpoint(
                lambda h, w, gw, gb: head(h, w, gw, gb, labels, ops))(
                    h, params["lm/W"], params["lm/gate_w"],
                    params["lm/gate_b"])

        def passes(h, layers, n):
            return jax.lax.scan(lambda h, _: one_pass(h, layers), h, None,
                                length=n)

        h = params["embed/W"][tokens]
        if fault == "last_pass_grad":
            h, (ces, lams) = passes(h, jax.lax.stop_gradient(stack),
                                    n_passes - 1)
            _, (ce, lam) = one_pass(h, stack)
            ces = jnp.concatenate([ces, ce[None]])
            lams = jnp.concatenate([lams, lam[None]])
        else:
            _, (ces, lams) = passes(h, stack, n_passes)
        p_exit = exit_distribution(lams)
        entropy = -jnp.sum(p_exit * jnp.log(jnp.maximum(p_exit, 1e-30)),
                           axis=0)
        return jnp.mean(jnp.sum(p_exit * ces, axis=0) - beta * entropy)

    def loss(params, tokens, labels, ops):
        tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
        per_sequence = [sequence_loss(params, tokens[b], labels[b], ops)
                        for b in range(tokens.shape[0])]
        return sum(per_sequence) / len(per_sequence)

    return loss
