"""Plain reference for Xing4.0-29B-A4B as this configuration cuts it: a
DeepSeek-V3-style sparse decoder under manifold-constrained hyper-
connections, with one multi-token-prediction module. Straight
``jax.numpy`` in float32, one sequence at a time, every weight product
through ``chipbench.refnn.Ops.dense`` (the two of the attention core too,
so ``Ops("fp8")`` rounds them as well); the router's and the hyper-
connection maps' small products are float32 at ``Precision.HIGHEST``
whatever the ``Ops``: that is the precision the configuration states for
them. Nothing of the program is imported.

The equations (``C`` hidden, ``n`` streams, ``H`` heads):

1. streams ``X0 = [e, e, e, e]``, ``e = Emb[token]``; after the last layer
   ``h = sum_i X_i``, the final RMS norm, the head.
2. a sub-block ``F`` under mHC (arXiv:2512.24880): ``x~ = RMS(vec X)``
   (no gain); ``H_pre = sigmoid(a_pre x~ phi_pre + b_pre)``; ``H_post = 2
   sigmoid(a_post x~ phi_post + b_post)``; ``H_res = SK(exp(clamp(a_res
   mat(x~ phi_res) + b_res)))``, ``SK`` = 20 rounds of rows then columns
   divided by their sums (+ eps); ``u = H_pre X``, ``y = F(RMSNorm(u))``,
   ``X' = H_res X + H_post^T y``.
3. latent attention (arXiv:2405.04434): ``c_q = RMS(u W_qa)``, ``[q_nope
   | q_rope] = c_q W_qb``; ``[c_kv | k_rope] = u W_kva``, ``c_kv =
   RMS(c_kv)``, ``[k_nope | v] = c_kv W_kvb``; rotary (YaRN frequencies)
   on ``q_rope`` and the one ``k_rope``; scores ``q . k * m^2 /
   sqrt(192)``, ``m = 0.1 ln(64) + 1``; causal softmax; ``W_o``.
4. expert layer (arXiv:2412.19437): ``s = sigmoid(u W_r)``; selected =
   top-4 of ``s + b``; gate ``2 s_i / sum_selected s_j``; ``y = Shared(u)
   + sum_{i selected and held} g_i E_i(u)``: every held expert run on
   every token and weighted by its gate (0 where not selected), the
   plainest form there is.
5. multi-token prediction: ``h' = [RMS(h) ; RMS(Emb[y])] W_eh``, one
   expert layer on its own streams, the shared final norm and head,
   predicting ``y_{i+1}``; loss ``CE_main + lambda CE_mtp``.

``jax.checkpoint`` around every sub-block, every attention head and every
block of a head's rows changes no value: it is there so that the float32
step fits one chip beside its own gradients.

Which experts a token takes is a discrete choice that rounding moves:
the fourth and the fifth of 64 scores lie close, and a program whose
activations carry bfloat16's rounding chooses otherwise for several
tokens in a hundred, after which that token's whole gradient is another
(measured on the chip, PERF.md). So ``loss(..., forced=...)`` takes the
program's own choice (``{expert layer: int32 [tokens, k]}``) and follows
it, gates and all from its own float32 scores, and hands back beside the
loss what it saw at every expert layer: its own selection scores ``s + b``
[tokens, 64] and the experts it followed [tokens, k]. The driver judges
the choice from them (``route_flip_share``, ``route_worst_margin``): a
choice far below the line between the reference's k-th and next score is
a wrong choice, not rounding, and the cell's limits hold it. Without
``forced`` the reference chooses itself.

``make_loss(cfg, fault=...)`` plants a fault the comparison must catch:
``"top3"`` selects three experts for four; ``"held_divisor"`` normalises
the gates over the held selected experts only; ``"no_sinkhorn"`` leaves
``H_res = exp(clamp(.))`` unprojected; ``"no_mtp_loss"`` drops the second
loss; ``"no_yarn_scale"`` scales the scores by ``1 / sqrt(192)`` alone;
``"no_select_bias"`` selects (and judges a followed choice) by ``s``
without the bias; ``"bias_in_gates"`` takes the gates from ``s + b``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = (None, "top3", "held_divisor", "no_sinkhorn", "no_mtp_loss",
          "no_yarn_scale", "no_select_bias", "bias_in_gates")
HEAD_ROWS = 512     # rows of [S, V] logits alive at a time
HI = jax.lax.Precision.HIGHEST


def rms(x, gain, eps):
    out = x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return out if gain is None else out * gain


def yarn_inv_freq(dim, theta, rs):
    """DeepSeek-V2's YaRN inverse frequencies [dim / 2]."""
    half = dim // 2
    base = float(theta) ** (np.arange(half, dtype=np.float64) * 2.0 / dim)

    def correction_dim(turns):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(float(theta)))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    inv = ramp / (rs["factor"] * base) + (1.0 - ramp) / base
    return jnp.asarray(inv, jnp.float32)


def rope(x, inv):
    """[S, H, D]: rotate-half pairs (i, i + D/2), angle pos * inv[i]."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sinkhorn(m, iters, eps):
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def make_loss(cfg, fault=None):
    if fault not in FAULTS:
        raise ValueError(f"unknown planted fault {fault!r}")
    C, H, n = cfg["hidden_size"], cfg["num_attention_heads"], cfg["hc_mult"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    kvl = cfg["kv_lora_rank"]
    eps, hc_eps = cfg["rms_norm_eps"], cfg["hc_eps"]
    rs = cfg["rope_scaling"]
    inv = yarn_inv_freq(dr, cfg["rope_theta"], rs)
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    scale = (dn + dr) ** -0.5 * (1.0 if fault == "no_yarn_scale" else m * m)
    top_k = cfg["num_experts_per_tok"] - (1 if fault == "top3" else 0)
    held = list(cfg["held_experts"])
    routed_scale = float(cfg["routed_scaling_factor"])
    lo, hi = cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]
    sk_iters = cfg["hc_sinkhorn_iters"]
    n_dense, n_layers = cfg["first_k_dense_replace"], cfg["num_layers"]
    n_mtp = cfg["num_nextn_predict_layers"]
    lam = 0.0 if fault == "no_mtp_loss" else cfg["mtp_loss_weight"]

    def hi_dot(a, b):
        return jnp.dot(a, b, precision=HI)

    def attention(p, pre, u, ops):
        s = u.shape[0]
        g = lambda leaf: p[f"{pre}attn/{leaf}"]      # noqa: E731
        q = ops.dense(rms(ops.dense(u, g("Wqa")), g("q_gain"), eps),
                      g("Wqb")).reshape(s, H, dn + dr)
        ckv = ops.dense(u, g("Wkva"))
        kv = ops.dense(rms(ckv[:, :kvl], g("kv_gain"), eps),
                       g("Wkvb")).reshape(s, H, dn + dv)
        k_rope = rope(ckv[:, kvl:].reshape(s, 1, dr), inv)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], inv)], -1)
        k = jnp.concatenate([kv[..., :dn],
                             jnp.broadcast_to(k_rope, (s, H, dr))], -1)
        v = kv[..., dn:]
        causal = jnp.tril(jnp.ones((s, s), bool))

        def one_head(qkv):
            qh, kh, vh = qkv
            scores = ops.dense(qh, kh.T) * scale
            w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
            return ops.dense(w, vh)
        o = jax.lax.map(jax.checkpoint(one_head),
                        tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v)))
        return ops.dense(jnp.swapaxes(o, 0, 1).reshape(s, H * dv), g("Wo"))

    def swiglu(u, wg, wu, wd, ops):
        return ops.dense(jax.nn.silu(ops.dense(u, wg)) * ops.dense(u, wu),
                         wd)

    def experts(p, pre, u, bias, forced, ops):
        """``(y, (selection scores [S, 64], experts followed [S, k]))``;
        ``forced`` [S, k] or ``None``."""
        g = lambda leaf: p[f"{pre}moe/{leaf}"]       # noqa: E731
        s = jax.nn.sigmoid(hi_dot(u, g("Wr")))               # [S, 64]
        select = s if fault == "no_select_bias" else s + bias
        if forced is None:
            _, chosen = jax.lax.top_k(select, cfg["num_experts_per_tok"])
        else:
            chosen = forced
        report = (jax.lax.stop_gradient(select), chosen)
        sel = chosen[:, :top_k]
        picked = jnp.take_along_axis(
            s + bias if fault == "bias_in_gates" else s, sel, axis=-1)
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
        routed = jnp.sum(jax.lax.map(
            jax.checkpoint(one_expert),
            (g("Eg"), g("Eu"), g("Ed"), weight)), axis=0)
        return routed + swiglu(u, g("Sg"), g("Su"), g("Sd"), ops), report

    def sub_block(p, pre, tag, X, f):
        """``(X', report)``: ``X`` [S, n, C] through one sub-block ``f``
        under mHC; ``f`` gives ``(y, report)``."""
        r = lambda leaf: p[f"{pre}hr{tag}/{leaf}"]       # noqa: E731
        w = lambda leaf: p[f"{pre}hw{tag}/{leaf}"]       # noqa: E731
        s = X.shape[0]
        xt = rms(X.reshape(s, n * C), None, hc_eps)
        h_pre = jax.nn.sigmoid(r("alpha_pre")[0] * hi_dot(xt, r("phi_pre"))
                               + r("b_pre"))
        y, report = f(rms(jnp.einsum("sn,snc->sc", h_pre, X, precision=HI),
                          p[f"{pre}n{tag}/gain"], eps))
        h_post = 2.0 * jax.nn.sigmoid(
            w("alpha_post")[0] * hi_dot(xt, w("phi_post")) + w("b_post"))
        res = jnp.exp(jnp.clip(
            w("alpha_res")[0] * hi_dot(xt, w("phi_res")).reshape(s, n, n)
            + w("b_res"), lo, hi))
        h_res = res if fault == "no_sinkhorn" \
            else sinkhorn(res, sk_iters, hc_eps)
        return jnp.einsum("sij,sjc->sic", h_res, X, precision=HI) \
            + h_post[:, :, None] * y[:, None, :], report

    def layer(p, pre, X, dense, bias, forced, ops):
        """``(X', report)`` of one decoder layer."""
        none = ()
        X, _ = jax.checkpoint(lambda p, X: sub_block(
            p, pre, "1", X,
            lambda u: (attention(p, pre, u, ops), none)))(p, X)
        if dense:
            f = lambda p, u: (swiglu(                         # noqa: E731
                u, p[f"{pre}mlp/Wg"], p[f"{pre}mlp/Wu"], p[f"{pre}mlp/Wd"],
                ops), none)
        else:
            f = lambda p, u: experts(p, pre, u, bias,         # noqa: E731
                                     forced, ops)
        return jax.checkpoint(lambda p, X: sub_block(
            p, pre, "2", X, lambda u: f(p, u)))(p, X)

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
        X = jnp.tile(p["embed/W"][tokens][:, None, :], (1, n, 1))
        for i in range(n_layers):
            pre = f"l{i}_"
            X, report = layer(only(pre), pre, X, i < n_dense,
                              biases.get(f"{pre}moe/select_bias"),
                              forced.get(f"{pre}moe"), ops)
            if report:
                reports[f"{pre}moe"] = report
        h = jnp.sum(X, axis=1)
        w, fg = p["lm/W"], p["fnorm/gain"]
        loss = jnp.mean(head_ce(rms(h, fg, eps), w, labels, ops))
        if n_mtp:
            joined = jnp.concatenate(
                [rms(h, p["mtp_join/h_gain"], eps),
                 rms(p["embed/W"][labels], p["mtp_join/e_gain"], eps)], -1)
            X = jnp.tile(ops.dense(joined, p["mtp_join/W"])[:, None, :],
                         (1, n, 1))
            X, report = layer(only("mtp_"), "mtp_", X, False,
                              biases["mtp_moe/select_bias"],
                              forced.get("mtp_moe"), ops)
            reports["mtp_moe"] = report
            hm = rms(jnp.sum(X, axis=1), fg, eps)
            # position i has seen token i + 1 and predicts token i + 2 =
            # labels[i + 1]; the last position has no label
            ce = head_ce(hm, w, jnp.roll(labels, -1), ops)
            loss = loss + lam * jnp.mean(ce[:-1])
        return loss, reports

    def loss(params, biases, tokens, labels, ops, forced=None):
        """``(loss, {expert layer: (scores [tokens, 64], experts followed
        [tokens, k])})``."""
        tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
        s = tokens.shape[1]
        per_sequence = [sequence_loss(
            params, biases, tokens[b], labels[b],
            {k: jnp.asarray(v)[b * s:(b + 1) * s]
             for k, v in (forced or {}).items()}, ops)
            for b in range(tokens.shape[0])]
        seen = {k: tuple(jnp.concatenate([r[k][i] for _l, r in per_sequence])
                         for i in (0, 1)) for k in per_sequence[0][1]}
        return sum(l for l, _r in per_sequence) / len(per_sequence), seen

    return loss
