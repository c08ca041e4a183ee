"""Transformer encoder/decoder — the flagship distributed model.

Reference parity: the BERT workload (BASELINE config #4) enters the
reference via TF-GraphDef import into SameDiff and runs op-by-op
(SURVEY.md §3.3). Here the transformer is a first-class zoo model built
TPU-first; the importer (modelimport/) can map BERT weights onto it.

Sharding design (dp × tp × sp over the mesh from parallel/mesh.py — the
scaling-book recipe):
- embeddings / LM head: vocab-sharded on ``model``
- attention QKV projections column-sharded, output row-sharded on
  ``model`` (Megatron-style TP: one allreduce per block, emitted by GSPMD)
- MLP in column-sharded, out row-sharded on ``model``
- activations sharded [data, seq, -] between blocks; attention over the
  ``seq`` axis runs RING ATTENTION (parallel/sequence.py) so the full
  sequence never materializes on one chip — long-context first-class.
- bf16 params/activations, fp32 softmax/loss accumulation (MXU policy).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.ops import attention as attn_ops
from deeplearning4j_tpu.ops import normalization as norm_ops
from deeplearning4j_tpu.parallel.mesh import DeviceMesh
from deeplearning4j_tpu.parallel.sequence import ring_attention


@dataclass
class TransformerConfig:
    vocab_size: int = 30522          # bert-base vocab
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    max_len: int = 512
    causal: bool = False             # False = BERT-style encoder, True = GPT-style
    dtype: Any = jnp.bfloat16
    use_ring_attention: bool = False
    # fused flash-attention path (Pallas platform override when installed;
    # scan formulation otherwise) — no [T, T] score matrix
    use_flash_attention: bool = False
    tie_embeddings: bool = True
    # "preln" = the TPU-first training layout (pre-LN, approximate gelu);
    # "postln_bert" = faithful BERT layout (post-LN residuals, embedding
    # LayerNorm, token-type embeddings, exact-erf gelu) — the layout real
    # BERT checkpoints import onto (modelimport/bert.py)
    arch: str = "preln"
    type_vocab_size: int = 0
    layer_norm_eps: float = 1e-5     # BERT checkpoints use 1e-12

    @staticmethod
    def bert_base(**kw):
        return TransformerConfig(**kw)

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=1024, d_model=64, n_heads=4, n_layers=2,
                 d_ff=128, max_len=128)
        d.update(kw)
        return TransformerConfig(**d)


def init_params(cfg: TransformerConfig, key) -> Dict:
    """Initialize parameters. Layout chosen for TP sharding rules below."""
    E, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    std = 0.02
    keys = jax.random.split(key, 4 + cfg.n_layers)
    dt = cfg.dtype

    def norm(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dt)

    params = {
        "embed": {"tok": norm(keys[0], (V, E)),
                  "pos": norm(keys[1], (cfg.max_len, E))},
        "final_norm": {"g": jnp.ones((E,), dt), "b": jnp.zeros((E,), dt)},
        "layers": [],
    }
    if cfg.type_vocab_size:
        params["embed"]["type"] = norm(keys[3], (cfg.type_vocab_size, E))
    if cfg.arch == "postln_bert":
        params["emb_norm"] = {"g": jnp.ones((E,), dt), "b": jnp.zeros((E,), dt)}
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(keys[2], (E, V))
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[4 + i], 8)
        params["layers"].append({
            "ln1": {"g": jnp.ones((E,), dt), "b": jnp.zeros((E,), dt)},
            "wqkv": norm(k[0], (E, 3 * E)),
            "bqkv": jnp.zeros((3 * E,), dt),
            "wo": norm(k[1], (E, E)),
            "bo": jnp.zeros((E,), dt),
            "ln2": {"g": jnp.ones((E,), dt), "b": jnp.zeros((E,), dt)},
            "w1": norm(k[2], (E, F)),
            "b1": jnp.zeros((F,), dt),
            "w2": norm(k[3], (F, E)),
            "b2": jnp.zeros((E,), dt),
        })
    return params


def param_shardings(cfg: TransformerConfig, mesh: DeviceMesh):
    """NamedShardings matching init_params structure (Megatron TP layout)."""
    m = mesh.mesh
    s = lambda *spec: NamedSharding(m, P(*spec))
    layer = {
        "ln1": {"g": s(), "b": s()},
        "wqkv": s(None, "model"),      # column parallel
        "bqkv": s("model"),
        "wo": s("model", None),        # row parallel
        "bo": s(),
        "ln2": {"g": s(), "b": s()},
        "w1": s(None, "model"),
        "b1": s("model"),
        "w2": s("model", None),
        "b2": s(),
    }
    out = {
        "embed": {"tok": s("model", None), "pos": s()},
        "final_norm": {"g": s(), "b": s()},
        "layers": [layer] * cfg.n_layers,
    }
    if cfg.type_vocab_size:
        out["embed"]["type"] = s()
    if cfg.arch == "postln_bert":
        out["emb_norm"] = {"g": s(), "b": s()}
    if not cfg.tie_embeddings:
        out["lm_head"] = s(None, "model")
    return out


def _attention(x, lp, cfg: TransformerConfig, mesh: Optional[DeviceMesh],
               attn_mask=None):
    B, T, E = x.shape
    H = cfg.n_heads
    D = E // H
    qkv = x @ lp["wqkv"] + lp["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, D)
    k = k.reshape(B, T, H, D)
    v = v.reshape(B, T, H, D)
    if cfg.use_ring_attention and mesh is not None and mesh.size("seq") > 1:
        assert attn_mask is None, \
            "padding masks are not yet supported on the ring-attention path"
        ctx = ring_attention(q, k, v, mesh.mesh, axis_name="seq",
                             is_causal=cfg.causal, batch_axis="data",
                             head_axis="model" if mesh.size("model") > 1 else None)
    elif cfg.use_flash_attention and attn_mask is None:
        ctx = attn_ops.flash_attention(q, k, v, is_causal=cfg.causal)
    else:
        m = attn_mask[:, None, None, :] if attn_mask is not None else None
        ctx = attn_ops.dot_product_attention(q, k, v, mask=m,
                                             is_causal=cfg.causal)
    out = ctx.reshape(B, T, E) @ lp["wo"] + lp["bo"]
    return out


def _constrain(x, mesh: Optional[DeviceMesh], *spec):
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh.mesh, P(*spec)))


def encode(params, tokens, cfg: TransformerConfig,
           mesh: Optional[DeviceMesh] = None, token_type_ids=None,
           attn_mask=None):
    """Faithful post-LN BERT encoder: tokens [B, T] -> hidden [B, T, E]
    (fp32). Matches the reference's imported-BERT semantics (SURVEY.md §3.3):
    embedding LayerNorm, post-LN residuals, exact-erf gelu."""
    B, T = tokens.shape
    x = jnp.take(params["embed"]["tok"], tokens, axis=0) \
        + params["embed"]["pos"][:T][None]
    if "type" in params["embed"]:
        tt = token_type_ids if token_type_ids is not None \
            else jnp.zeros((B, T), jnp.int32)
        x = x + jnp.take(params["embed"]["type"], tt, axis=0)
    ln = lambda v, p: norm_ops.layer_norm(
        v.astype(jnp.float32), p["g"].astype(jnp.float32),
        p["b"].astype(jnp.float32), eps=cfg.layer_norm_eps)
    x = ln(x, params["emb_norm"]).astype(cfg.dtype)
    x = _constrain(x, mesh, "data", "seq", None)
    for lp in params["layers"]:
        a = _attention(x, lp, cfg, mesh, attn_mask=attn_mask)
        x = ln(x + a, lp["ln1"]).astype(cfg.dtype)
        h = jax.nn.gelu(x @ lp["w1"] + lp["b1"], approximate=False)
        h = h @ lp["w2"] + lp["b2"]
        x = ln(x + h, lp["ln2"]).astype(cfg.dtype)
        x = _constrain(x, mesh, "data", "seq", None)
    return x.astype(jnp.float32)


def forward(params, tokens, cfg: TransformerConfig,
            mesh: Optional[DeviceMesh] = None):
    """tokens [B, T] int32 -> logits [B, T, V] (fp32)."""
    if cfg.arch == "postln_bert":
        x = encode(params, tokens, cfg, mesh)
        head = params["embed"]["tok"].T if cfg.tie_embeddings else params["lm_head"]
        return (x.astype(cfg.dtype) @ head.astype(cfg.dtype)).astype(jnp.float32)
    B, T = tokens.shape
    x = jnp.take(params["embed"]["tok"], tokens, axis=0) \
        + params["embed"]["pos"][:T][None]
    x = x.astype(cfg.dtype)
    x = _constrain(x, mesh, "data", "seq", None)
    for lp in params["layers"]:
        h = norm_ops.layer_norm(x.astype(jnp.float32), lp["ln1"]["g"].astype(jnp.float32),
                                lp["ln1"]["b"].astype(jnp.float32)).astype(cfg.dtype)
        x = x + _constrain(_attention(h, lp, cfg, mesh), mesh, "data", "seq", None)
        h = norm_ops.layer_norm(x.astype(jnp.float32), lp["ln2"]["g"].astype(jnp.float32),
                                lp["ln2"]["b"].astype(jnp.float32)).astype(cfg.dtype)
        h = jax.nn.gelu(h @ lp["w1"] + lp["b1"])
        h = h @ lp["w2"] + lp["b2"]
        x = x + _constrain(h, mesh, "data", "seq", None)
    x = norm_ops.layer_norm(x.astype(jnp.float32),
                            params["final_norm"]["g"].astype(jnp.float32),
                            params["final_norm"]["b"].astype(jnp.float32))
    head = params["embed"]["tok"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x.astype(cfg.dtype) @ head.astype(cfg.dtype)
    return logits.astype(jnp.float32)


def loss_fn(params, tokens, targets, cfg: TransformerConfig,
            mesh: Optional[DeviceMesh] = None, target_mask=None):
    """Masked-LM / causal-LM token cross-entropy (fp32)."""
    logits = forward(params, tokens, cfg, mesh)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    if target_mask is not None:
        return jnp.sum(nll * target_mask) / jnp.maximum(jnp.sum(target_mask), 1.0)
    return jnp.mean(nll)


def make_train_step(cfg: TransformerConfig, updater,
                    mesh: Optional[DeviceMesh] = None):
    """One compiled step: fwd + bwd + updater, shard-annotated."""

    def step(params, opt_state, t, tokens, targets, target_mask):
        """``t`` is a DONATED int32 device scalar, incremented in-program and
        returned — per-step host scalar uploads serialize the dispatch
        pipeline (see nn.multilayer._ensure_clock)."""
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets, cfg,
                                                  mesh, target_mask)
        tf = t.astype(jnp.float32)
        lr = updater.lr_at(tf)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = treedef.flatten_up_to(grads)
        s_leaves = treedef.flatten_up_to(opt_state)
        new_p, new_s = [], []
        for pv, gv, sv in zip(leaves, g_leaves, s_leaves):
            # optimizer math in fp32 even for bf16 params
            u, s2 = updater.apply(gv.astype(jnp.float32), sv, lr, tf)
            new_p.append((pv.astype(jnp.float32) - u).astype(pv.dtype))
            new_s.append(s2)
        return (jax.tree_util.tree_unflatten(treedef, new_p),
                jax.tree_util.tree_unflatten(treedef, new_s), t + 1, loss)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def init_opt_state(params, updater):
    return jax.tree_util.tree_map(
        lambda p: updater.init_state(p.astype(jnp.float32)), params,
        is_leaf=lambda x: isinstance(x, jax.Array))


class TransformerLM:
    """Convenience wrapper used by the zoo / benchmarks."""

    def __init__(self, cfg: TransformerConfig, mesh: DeviceMesh = None,
                 seed: int = 0):
        self.cfg = cfg
        self.mesh = mesh
        self.params = init_params(cfg, jax.random.PRNGKey(seed))
        if mesh is not None:
            shardings = param_shardings(cfg, mesh)
            self.params = jax.tree_util.tree_map(
                lambda p, s: jax.device_put(p, s), self.params, shardings,
                is_leaf=lambda x: isinstance(x, jax.Array))
        self._fwd = None

    def logits(self, tokens):
        if self._fwd is None:
            self._fwd = jax.jit(lambda p, t: forward(p, t, self.cfg, self.mesh))
        return self._fwd(self.params, jnp.asarray(tokens, jnp.int32))

    def n_params(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))
