"""Plain reference for LFM2-24B-A2B as this configuration cuts it: a
hybrid decoder of gated short-convolution layers among grouped-query
attention layers, sparse experts without a shared expert after the
leading dense layer, a tied head. Straight ``jax.numpy`` in float32, one
sequence at a time, every weight product through
``chipbench.refnn.Ops.dense`` (the two of the attention core and the head
too, so ``Ops("fp8")`` rounds them as well); the router's product is
float32 at ``Precision.HIGHEST`` whatever the ``Ops``: that is the
precision the configuration states for it. Nothing of the program is
imported.

The equations (``C`` hidden, eps = ``norm_eps``, no bias anywhere):

1. layer ``l``: ``r = h + Mix_l(RMS_op(h))``, ``h' = r + FF_l(RMS_ffn(r))``;
   ``Mix_l`` the short convolution where ``layer_types[l] == "conv"``,
   attention where ``"full_attention"``; ``FF_l`` a SwiGLU MLP for the
   leading dense layers, the expert layer after. After the last layer one
   RMS norm, the head ``h Emb^T`` (tied), cross-entropy over next tokens,
   a mean over positions.
2. gated short convolution: ``[B | G | z] = u W_in``, ``p = B * z``,
   ``c_t = sum_{j=0..K-1} w_j * p_{t-(K-1)+j}`` (``p`` nought before the
   sequence's first token: an explicit sum over K shifted copies), ``y =
   (G * c) W_out``.
3. attention: ``q = u W_q`` (32 heads of 64), ``k = u W_k``, ``v = u W_v``
   (8 heads of 64); ``q`` and ``k`` through an RMS norm over the 64 of a
   head (one gain [64] each); rotary embedding over the whole head,
   rotate-half pairs ``(i, i + 32)``; query head ``h`` reads key/value
   head ``h // 4`` (``k``, ``v`` repeated four times: the plainest form);
   scores ``q . k / sqrt(64)``, causal softmax, ``W_o``.
4. expert layer: ``s = sigmoid(u W_r)``; selected = top-4 of ``s + b``;
   gate ``s_i / sum_selected s_j`` (times ``routed_scaling_factor`` = 1);
   ``y = sum_{i selected and held} g_i E_i(u)``: every held expert run on
   every token and weighted by its gate (0 where not selected); no shared
   expert.

``jax.checkpoint`` around every sequence, every sub-block, every attention
head, every expert and every block of the head's rows changes no value: it
is there so that the float32 step of 4 x 8,192 tokens fits one chip beside
its own gradients.

Which experts a token takes is a discrete choice that rounding moves, so
``loss(..., forced=...)`` takes the program's own choice (``{expert layer:
int32 [tokens, k]}``) and follows it, gates and all from its own float32
scores, and hands back beside the loss what it saw at every expert layer:
its own selection scores ``s + b`` [tokens, 64] and the experts it
followed [tokens, k] (the driver judges the choice from them:
``route_flip_share``, ``route_worst_margin``). Without ``forced`` the
reference chooses itself.

``make_loss(cfg, fault=...)`` plants a fault the comparison must catch:
``"top3"`` selects three experts for four; ``"held_divisor"`` normalises
the gates over the held selected experts only; ``"kv_head_mod"`` has query
head ``h`` read key/value head ``h % 8``; ``"no_qk_norm"`` leaves q and k
unnormed; ``"conv_acausal"`` takes the taps ``t..t+2``; ``"no_out_gate"``
leaves ``G`` out; ``"untied_head"`` gives the embedding no gradient from
the head (a head with a table of its own).
"""

import jax
import jax.numpy as jnp

FAULTS = (None, "top3", "held_divisor", "kv_head_mod", "no_qk_norm",
          "conv_acausal", "no_out_gate", "untied_head")
HEAD_ROWS = 512     # rows of [S, V] logits alive at a time
HI = jax.lax.Precision.HIGHEST


def rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def rope(x, theta):
    """[S, H, D]: rotate-half pairs (i, i + D/2), angle pos *
    theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layers_of(cfg):
    """``[(node prefix, is conv, is dense)]`` of the layers held."""
    return [(f"l{n}_", cfg["layer_types"][i] == "conv",
             n < cfg["num_dense_layers"])
            for n, i in enumerate(cfg["held_layers"])]


def make_loss(cfg, fault=None):
    if fault not in FAULTS:
        raise ValueError(f"unknown planted fault {fault!r}")
    C, H = cfg["hidden_size"], cfg["num_attention_heads"]
    Hk, D = cfg["num_key_value_heads"], cfg["hidden_size"] \
        // cfg["num_attention_heads"]
    K, eps = cfg["conv_L_cache"], cfg["norm_eps"]
    theta = cfg["rope_parameters"]["rope_theta"]
    top_k = cfg["num_experts_per_tok"] - (1 if fault == "top3" else 0)
    held = list(cfg["held_experts"])
    routed_scale = float(cfg["routed_scaling_factor"])

    def short_conv(p, pre, u, ops):
        g = lambda leaf: p[f"{pre}conv/{leaf}"]       # noqa: E731
        s = u.shape[0]
        bgz = ops.dense(u, g("Win"))
        pz = bgz[:, :C] * bgz[:, 2 * C:]
        if fault == "conv_acausal":
            moved = jnp.pad(pz, ((0, K - 1), (0, 0)))
        else:
            moved = jnp.pad(pz, ((K - 1, 0), (0, 0)))
        c = sum(g("Wc")[j] * moved[j:j + s] for j in range(K))
        return ops.dense(c if fault == "no_out_gate"
                         else bgz[:, C:2 * C] * c, g("Wout"))

    def attention(p, pre, u, ops):
        g = lambda leaf: p[f"{pre}attn/{leaf}"]       # noqa: E731
        s = u.shape[0]
        q = ops.dense(u, g("Wq")).reshape(s, H, D)
        k = ops.dense(u, g("Wk")).reshape(s, Hk, D)
        v = ops.dense(u, g("Wv")).reshape(s, Hk, D)
        if fault != "no_qk_norm":
            q, k = rms(q, g("qn"), eps), rms(k, g("kn"), eps)
        q, k = rope(q, theta), rope(k, theta)
        if fault == "kv_head_mod":
            k, v = (jnp.tile(t, (1, H // Hk, 1)) for t in (k, v))
        else:
            k, v = (jnp.repeat(t, H // Hk, axis=1) for t in (k, v))
        causal = jnp.tril(jnp.ones((s, s), bool))

        def one_head(qkv):
            qh, kh, vh = qkv
            scores = ops.dense(qh, kh.T) * D ** -0.5
            w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return ops.dense(w, vh)
        o = jax.lax.map(jax.checkpoint(one_head),
                        tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v)))
        return ops.dense(jnp.swapaxes(o, 0, 1).reshape(s, H * D), g("Wo"))

    def swiglu(u, wg, wu, wd, ops):
        return ops.dense(jax.nn.silu(ops.dense(u, wg)) * ops.dense(u, wu),
                         wd)

    def experts(p, pre, u, bias, forced, ops):
        """``(y, (selection scores [S, 64], experts followed [S, k]))``;
        ``forced`` [S, k] or ``None``."""
        g = lambda leaf: p[f"{pre}moe/{leaf}"]       # noqa: E731
        s = jax.nn.sigmoid(jnp.dot(u, g("Wr"), precision=HI))    # [S, 64]
        select = s + bias
        if forced is None:
            _, chosen = jax.lax.top_k(select, cfg["num_experts_per_tok"])
        else:
            chosen = forced
        report = (jax.lax.stop_gradient(select), chosen)
        sel = chosen[:, :top_k]
        picked = jnp.take_along_axis(s, sel, axis=-1)
        if fault == "held_divisor":
            here = jnp.isin(sel, jnp.asarray(held))
            div = jnp.sum(jnp.where(here, picked, 0.0), -1, keepdims=True)
            gate = routed_scale * picked / jnp.maximum(div, 1e-30)
        else:
            gate = routed_scale * picked \
                / jnp.sum(picked, axis=-1, keepdims=True)
        # [held, S]: the gate of each held expert for each token, 0 where
        # the token did not select it
        weight = jnp.stack([jnp.sum(jnp.where(sel == e, gate, 0.0), -1)
                            for e in held])

        def one_expert(ew):
            wg, wu, wd, w = ew
            return swiglu(u, wg, wu, wd, ops) * w[:, None]
        return jnp.sum(jax.lax.map(
            jax.checkpoint(one_expert),
            (g("Eg"), g("Eu"), g("Ed"), weight)), axis=0), report

    def layer(p, pre, h, conv, dense, bias, forced, ops):
        """``(h', report)`` of one decoder layer."""
        mix = short_conv if conv else attention
        r = jax.checkpoint(lambda p, h: h + mix(
            p, pre, rms(h, p[f"{pre}n1/gain"], eps), ops))(p, h)

        def ff(p, r):
            u = rms(r, p[f"{pre}n2/gain"], eps)
            if dense:
                return r + swiglu(u, p[f"{pre}mlp/Wg"], p[f"{pre}mlp/Wu"],
                                  p[f"{pre}mlp/Wd"], ops), ()
            y, report = experts(p, pre, u, bias, forced, ops)
            return r + y, report
        return jax.checkpoint(ff)(p, r)

    def head_ce(h, w, y, ops):
        """Cross-entropy [S] of ``h @ w`` against ``y``."""
        def rows(hy):
            hb, yb = hy
            logp = jax.nn.log_softmax(ops.dense(hb, w), axis=-1)
            return -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
        blk = HEAD_ROWS if h.shape[0] % HEAD_ROWS == 0 else h.shape[0]
        return jax.lax.map(jax.checkpoint(rows),
                           (h.reshape(-1, blk, h.shape[1]),
                            y.reshape(-1, blk))).reshape(-1)

    def sequence_loss(params, biases, tokens, labels, forced, ops):
        p = params
        reports = {}
        only = lambda prefix: {k: v for k, v in p.items()     # noqa: E731
                               if k.startswith(prefix)}
        h = p["embed/W"][tokens]
        for pre, conv, dense in layers_of(cfg):
            h, report = layer(only(pre), pre, h, conv, dense,
                              biases.get(f"{pre}moe/select_bias"),
                              forced.get(f"{pre}moe"), ops)
            if report:
                reports[f"{pre}moe"] = report
        emb = p["embed/W"]
        if fault == "untied_head":
            emb = jax.lax.stop_gradient(emb)
        ce = head_ce(rms(h, p["fnorm/gain"], eps), emb.T, labels, ops)
        return jnp.mean(ce), reports

    def loss(params, biases, tokens, labels, ops, forced=None):
        """``(loss, {expert layer: (scores [tokens, 64], experts followed
        [tokens, k])})``."""
        tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
        s = tokens.shape[1]
        one = jax.checkpoint(
            lambda params, x, y, f: sequence_loss(params, biases, x, y, f,
                                                  ops))
        per_sequence = [one(
            params, tokens[b], labels[b],
            {k: jnp.asarray(v)[b * s:(b + 1) * s]
             for k, v in (forced or {}).items()})
            for b in range(tokens.shape[0])]
        seen = {k: tuple(jnp.concatenate([r[k][i] for _l, r in per_sequence])
                         for i in (0, 1)) for k in per_sequence[0][1]}
        return sum(l for l, _r in per_sequence) / len(per_sequence), seen

    return loss
