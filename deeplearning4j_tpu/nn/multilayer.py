"""MultiLayerNetwork — the sequential network and its training loop.

Reference parity: ``org.deeplearning4j.nn.multilayer.MultiLayerNetwork``
and the Solver/StochasticGradientDescent step driver + TrainingListener
bus (SURVEY.md §2.2 "Networks", call stack §3.1).

TPU-native: ``fit`` compiles ONE XLA program per batch signature doing
forward + loss + backward + regularization + clipping + updater — the
reference's hundreds of JNI crossings per step become one dispatch
(SURVEY.md §3.1 "the TPU rebuild amortizes it to ~1 crossing per step").
Params/updater-state are pytrees; there is also a ``params()`` view
returning the reference's single flat contiguous parameter vector.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import profiler as _prof
from deeplearning4j_tpu.analysis import churn as _churn
from deeplearning4j_tpu.profiler import devicetime as _devicetime
from deeplearning4j_tpu.profiler import sanitizer as _sanitizer
from deeplearning4j_tpu.profiler import stepprogram as _stepprogram
from deeplearning4j_tpu.data.dataset import (AsyncDataSetIterator, DataSet,
                                             DataSetIterator,
                                             IterableDataSetIterator)
from deeplearning4j_tpu.evaluation.evaluation import Evaluation, RegressionEvaluation
from deeplearning4j_tpu.nn import augment as _augment_mod
from deeplearning4j_tpu.nn import compilecache as _cc
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
from deeplearning4j_tpu.train import stepping as _stepping
from deeplearning4j_tpu.train import updaters as upd
from deeplearning4j_tpu.utils import environment as _environment

_MASK_AWARE = (L.LSTM, L.SimpleRnn, L.Bidirectional, L.LastTimeStep,
               L.GlobalPoolingLayer, L.SelfAttentionLayer,
               L.RecurrentAttentionLayer)


_EVAL_PULL_CHUNK = 64  # batches of on-device predictions held at once


def _predict_batches(output_fn, iterator, chunk: int = _EVAL_PULL_CHUNK,
                     prefetch: bool = True):
    """Dispatch ``output_fn`` for every batch WITHOUT pulling each result:
    predictions stay on device and come back in bulk jax.device_get pulls
    of up to ``chunk`` batches — a per-batch np.asarray would block the
    whole link round trip every batch, while an unbounded accumulation
    would hold the entire dataset's predictions in device memory. Plain
    (non-async) iterators are wrapped in AsyncDataSetIterator so host
    batch prep overlaps the dispatched forwards. A generator: yields
    (labels, preds, labels_mask) per batch, preds as host numpy — at
    most ``chunk`` batches live on either side of the link at once.
    ``prefetch=False`` consumes the iterator synchronously on the calling
    thread (thread-affine data sources)."""
    it, owns = _ensure_eval_iterator(iterator, prefetch)
    pending = []

    def drain():
        preds = jax.device_get([p for _, p, _ in pending])
        out = [(labels, np.asarray(p), mask)
               for (labels, _, mask), p in zip(pending, preds)]
        pending.clear()
        return out

    try:
        if not owns:
            it.reset()
        while it.hasNext():
            ds = it.next()
            pending.append((ds.labels, output_fn(ds.features),
                            ds.labels_mask))
            if len(pending) >= chunk:
                yield from drain()
        if pending:
            yield from drain()
    except BaseException:
        # already unwinding (forward error, consumer abandoning the
        # generator): close defensively without letting a buffered
        # worker error mask the primary exception
        if owns:
            try:
                it.close()
            except BaseException:
                pass
        raise
    else:
        if owns:
            it.close()      # clean exit: an undelivered worker error
                            # (close() re-raises it) must surface here


def _ensure_eval_iterator(iterator, prefetch: bool = True):
    """evaluate()'s input adapter: plain DataSetIterators (and any python
    iterable of DataSets) are wrapped in AsyncDataSetIterator so batch
    prep overlaps the forward dispatches — unless ``prefetch=False``,
    which keeps consumption on the calling thread. Returns (iterator,
    owns) — ``owns`` means we created an async wrapper and must close()
    it."""
    if isinstance(iterator, AsyncDataSetIterator):
        return iterator, False
    base = iterator if isinstance(iterator, DataSetIterator) \
        else IterableDataSetIterator(iterator)
    if not prefetch:
        return base, False
    return AsyncDataSetIterator(base), True


def _maybe_attach_env_profiler(model):
    """DL4J_TPU_PROFILING=1 auto-attaches a ProfilingListener writing to
    DL4J_TPU_PROFILE_DIR (the env registry's advertised behaviour)."""
    if not _environment.Environment.get().profiling:
        return
    from deeplearning4j_tpu.train.listeners import ProfilingListener
    if not any(isinstance(l, ProfilingListener) for l in model._listeners):
        model._listeners.append(ProfilingListener())


def _process_and_apply_grads(base, updater, params, grads, opt_state, t):
    """Shared per-step gradient path: gradientNormalization clipping, then
    updater.apply per leaf with AdamW decoupled decay gated to weight
    matrices (leaf names W/RW), matching the loss-side L1/L2 gating.
    Used by BOTH the regular and the TBPTT compiled steps (advisor r2:
    tBPTT previously skipped clipping + AdamW decay)."""
    with jax.named_scope(_stepprogram.UPDATER_SCOPE):
        if base.grad_norm == "clip_value":
            grads = upd.clip_by_value(grads, base.grad_norm_threshold)
        elif base.grad_norm == "clip_l2":
            grads = upd.clip_by_norm(grads, base.grad_norm_threshold)
        elif base.grad_norm == "clip_global":
            grads = upd.clip_by_global_norm(grads, base.grad_norm_threshold)
        elif base.grad_norm == "renorm":
            grads = upd.renormalize_l2(grads)
        lr = updater.lr_at(t)
        path_leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        g_leaves = treedef.flatten_up_to(grads)
        s_leaves = treedef.flatten_up_to(opt_state)
        new_p, new_s = [], []
        for (path, pv), gv, sv in zip(path_leaves, g_leaves, s_leaves):
            u, s2 = updater.apply(gv, sv, lr, t)
            leaf_name = str(getattr(path[-1], "key", path[-1]))
            if (isinstance(updater, upd.AdamW) and updater.weight_decay
                    and leaf_name.startswith(("W", "RW"))):
                u = u + updater.weight_decay_update(pv, lr)
            new_p.append(pv - u)
            new_s.append(s2)
        return (jax.tree_util.tree_unflatten(treedef, new_p),
                jax.tree_util.tree_unflatten(treedef, new_s))


def _grads_all_finite(grads):
    """Scalar bool: no gradient leaf overflowed/NaN'd — the dynamic
    loss-scaling overflow detector (shared by both network classes)."""
    with jax.named_scope(_stepprogram.UPDATER_SCOPE):
        ok = jnp.asarray(True)
        for g in jax.tree_util.tree_leaves(grads):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
        return ok


def _unscale_grads(grads, inv):
    """The loss scale divided back out of the gradients, under the
    updater's scope: the first thing the update does with them."""
    with jax.named_scope(_stepprogram.UPDATER_SCOPE):
        return jax.tree_util.tree_map(lambda g: g * inv, grads)


def _dynamic_scale_next(pol, scale_state, ok):
    """One tick of the grow/backoff loss-scale automaton: clean step
    advances the good-step counter (growing the scale by
    ``growth_factor`` after ``growth_interval`` clean steps, capped at
    ``max_loss_scale``); an overflow multiplies by ``backoff_factor``
    (floored at ``min_loss_scale``) and zeroes the counter. Pure jnp —
    traced inside the compiled step, shared by both network classes."""
    scale = scale_state[0]
    good = scale_state[1] + 1.0
    grew = good >= float(pol.growth_interval)
    grown = jnp.where(
        grew,
        jnp.minimum(scale * float(pol.growth_factor),
                    float(pol.max_loss_scale)),
        scale)
    new_scale = jnp.where(
        ok, grown,
        jnp.maximum(scale * float(pol.backoff_factor),
                    float(pol.min_loss_scale)))
    new_good = jnp.where(jnp.logical_and(ok, jnp.logical_not(grew)),
                         good, 0.0)
    return jnp.stack([new_scale, new_good])


def _select_update(ok, new, old):
    """Per-leaf ``jnp.where(ok, new, old)`` over matching pytrees — how
    an overflowed dynamic-scaling step drops its update without a
    host round trip."""
    with jax.named_scope(_stepprogram.UPDATER_SCOPE):
        return jax.tree_util.tree_map(lambda n, o: jnp.where(ok, n, o),
                                      new, old)


class MultiLayerNetwork:
    """Sequential network (ref: MultiLayerNetwork)."""

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        self._params: List[Dict] = []
        self._states: List[Dict] = []
        self._opt_state = None
        self._iteration = 0
        self._t_dev = None  # device-resident iteration counter (see _ensure_clock)
        self._epoch = 0
        self._listeners: List[Any] = []
        self._train_step_cache = {}
        self._megastep_cache = {}
        self._tbptt_step_cache = {}
        self._fwd_cache = None
        self._augment = None    # DeviceAugmentation (see setDeviceAugmentation)
        self._precision = None  # PrecisionPolicy (see setPrecisionPolicy)
        self._sharding_plan = None  # ShardedTrainingPlan (see setShardingPlan)
        self._scale_state = None  # dynamic loss scale [scale, good_steps]
        self._score = float("nan")
        self._initialized = False
        # NHWC compute-layout seam + fused epilogues (ISSUE 14) — both
        # opt-in; public API/layouts stay NCHW either way
        self._compute_layout = "NCHW"
        self._fuse_epilogues = False
        self._epilogue_plan = None
        fmt = getattr(conf.base, "compute_layout", None)
        if fmt and fmt != "NCHW":
            self.setComputeLayout(fmt)

    # ------------------------------------------------------------ validation
    def validate(self, batch_size: int = None, data_devices: int = None,
                 **kw):
        """Static lint of this network: the configuration analysis
        (shape/dtype propagation + structural diagnostics + TPU layout
        lints) plus model-level findings (frozen-layer/updater pairing,
        accumulated recompile-churn W201s). Returns a
        ``deeplearning4j_tpu.analysis.ValidationReport``; no jax work.
        Extra keywords pass through to ``analysis.analyze``: ``mesh=``,
        ``sharding=``, ``pipeline=``, ``hbm_gb=``, ``suppress=``,
        ``severity_overrides=``."""
        from deeplearning4j_tpu.analysis import analyze
        return analyze(self, batch_size=batch_size,
                       data_devices=data_devices, **kw)

    # ------------------------------------------------------------------ init
    def init(self, seed: int = None, strict: bool = False):
        """Initialize parameters (ref: MultiLayerNetwork.init).
        ``strict=True`` runs the static analyzer first and raises
        ``ModelValidationError`` on any E-code diagnostic."""
        if strict:
            self.validate().raise_if_errors()
        seed = self.conf.base.seed if seed is None else seed
        # net:init: the cause of the small programs made below
        with _cc.cause_span(_cc.NET_INIT) as made:
            key = jax.random.PRNGKey(seed)
            self._params, self._states = [], []
            for layer in self.layers:
                key, sub = jax.random.split(key)
                p, s = layer.initialize(sub)
                self._params.append(p)
                self._states.append(s)
            made["parameters"] = self.numParams()
            made["leaves"] = len(
                jax.tree_util.tree_leaves(self._params))
        self._opt_state = None
        self._train_step_cache = {}
        self._megastep_cache = {}
        self._tbptt_step_cache = {}
        self._fwd_cache = None
        self._scale_state = None
        self._initialized = True
        _sanitizer.invalidate(self)   # re-init = out-of-band state reset
        return self

    # --------------------------------------------------------------- forward
    def _compute_dtype(self):
        """Effective compute dtype under the precision seam: an attached
        :class:`~deeplearning4j_tpu.nn.precision.PrecisionPolicy` wins,
        else the configuration's ``dataType`` drives the legacy policy
        (bf16 -> mixed, anything else -> no casts)."""
        pol = self._precision
        if pol is not None:
            return pol.compute_jnp()
        return L.compute_dtype_of(self.conf.base.dtype)

    def _forward(self, params, states, x, train: bool, key, fmask=None):
        cdt = self._compute_dtype()
        if cdt is None and getattr(x, "dtype", None) == jnp.uint8:
            x = x.astype(jnp.float32)   # on-device image-byte cast (fp32 nets)
        nhwc = self._compute_layout == "NHWC"
        plan = self._ensure_epilogue_plan() if self._fuse_epilogues else {}
        new_states = [None] * len(self.layers)
        cur_nhwc = False
        i = 0
        while i < len(self.layers):
            layer = self.layers[i]
            fuse = plan.get(i)
            # every op of a layer, the preprocessor, layout step and casts
            # before its apply included, carries the layer's scope: the
            # step-program map (profiler.stepprogram) reads layer and
            # phase off it
            with jax.named_scope(_devicetime.scope_name(
                    i, getattr(layer, "name", None)
                    or type(layer).__name__)):
                if i in self.conf.preprocessors:
                    if cur_nhwc:
                        x, cur_nhwc = L.to_nchw(x), False
                    x = self.conf.preprocessors[i](x)
                x, cur_nhwc = L.layout_step(layer, x, cur_nhwc, nhwc)
                if fuse is not None:
                    n_used, conv_leads, alpha = fuse
                    # one RNG split per consumed layer keeps the key stream
                    # identical to the unfused forward (downstream dropout
                    # draws the same bits — the parity pins rely on it)
                    subs = []
                    for _ in range(n_used):
                        key, sub = jax.random.split(key)
                        subs.append(sub)
                    bn_idx = i
                    bias = None
                    if conv_leads:
                        p = params[i]
                        if cdt is not None:
                            p, x = L.policy_cast(layer, p, x, cdt)
                        x, new_states[i] = layer.apply(
                            p, states[i], x, train, subs[0], skip_bias=True)
                        bias = p.get("b")
                        bn_idx = i + 1
                    bn = self.layers[bn_idx]
                    pbn = params[bn_idx]
                    if cdt is not None:
                        pbn, x = L.policy_cast(bn, pbn, x, cdt)
                    x, new_states[bn_idx] = L.fused_bn_act(
                        bn, pbn, states[bn_idx], x, train, alpha, bias=bias)
                    for j in range(bn_idx + 1, i + n_used):
                        new_states[j] = states[j]   # the folded activation
                    i += n_used
                    continue
                p = params[i]
                if cdt is not None:
                    p, x = L.policy_cast(layer, p, x, cdt)
                key, sub = jax.random.split(key)
                if isinstance(layer, _MASK_AWARE):
                    x, ns = layer.apply(p, states[i], x, train, sub,
                                        mask=fmask)
                else:
                    x, ns = layer.apply(p, states[i], x, train, sub)
            new_states[i] = ns
            i += 1
        if cur_nhwc and getattr(x, "ndim", 0) == 4:
            x = L.to_nchw(x)
        return x, new_states

    def feedForward(self, x, train: bool = False):
        """All layer activations (ref: feedForward returns list). The
        returned activations are PUBLIC-layout (NCHW) even under the
        NHWC compute seam."""
        x = jnp.asarray(x)
        acts = [x]
        key = jax.random.PRNGKey(0)
        cur = x
        nhwc = self._compute_layout == "NHWC"
        cur_nhwc = False
        for i, layer in enumerate(self.layers):
            if i in self.conf.preprocessors:
                if cur_nhwc:
                    cur, cur_nhwc = L.to_nchw(cur), False
                cur = self.conf.preprocessors[i](cur)
            cur, cur_nhwc = L.layout_step(layer, cur, cur_nhwc, nhwc)
            key, sub = jax.random.split(key)
            if isinstance(layer, _MASK_AWARE):
                cur, _ = layer.apply(self._params[i], self._states[i], cur, train, sub, mask=None)
            else:
                cur, _ = layer.apply(self._params[i], self._states[i], cur, train, sub)
            cur_nhwc = cur_nhwc and getattr(cur, "ndim", 0) == 4
            acts.append(L.to_nchw(cur) if cur_nhwc else cur)
        return acts

    def output(self, x, train: bool = False):
        """Inference forward (ref: MultiLayerNetwork.output)."""
        out, _ = self._jit_forward()(self._params, self._states, jnp.asarray(x),
                                     jax.random.PRNGKey(0))
        return out

    def _jit_forward(self):
        if self._fwd_cache is None:
            def fwd(params, states, x, key):
                return self._forward(params, states, x, False, key)
            # behind the compile-cache seam: serving warmup (bucketed
            # shapes, possibly under a mesh context) AOT-compiles this
            # program ahead of the first request
            self._fwd_cache = _cc.cached_dispatch(fwd, "mln:forward")
        return self._fwd_cache

    def _warm_forward(self, x) -> "MultiLayerNetwork":
        """AOT-compile the inference forward for ``x``'s signature
        without executing it (the ``compilecache.warmup`` seam)."""
        self._jit_forward().warm(self._params, self._states, jnp.asarray(x),
                                 jax.random.PRNGKey(0))
        return self

    def _step_for(self, sig, steps: int = 1):
        """(compiled step, dummy mask) for one mask signature × dispatch
        K — THE single lookup `_fit_one`, `_fit_mega`, and
        `_warm_dispatch` share, so a warmed signature can never drift
        from what the real dispatch path builds."""
        if steps > 1:
            if (sig, steps) not in self._megastep_cache:
                self._megastep_cache[(sig, steps)] = \
                    self._make_train_step(*sig, steps=steps)
            return self._megastep_cache[(sig, steps)], jnp.zeros((steps, 1))
        if sig not in self._train_step_cache:
            self._train_step_cache[sig] = self._make_train_step(*sig)
        return self._train_step_cache[sig], jnp.zeros((1,))

    def _warm_dispatch(self, x, y, fmask=None, lmask=None,
                       steps: int = 1) -> "MultiLayerNetwork":
        """AOT-compile the train step (or K-step megastep) for this batch
        signature without executing it — no params/opt/RNG state is
        touched (``CachedDispatch.warm`` only lowers and compiles).
        ``steps>1`` expects ``[K, B, ...]`` stacked arrays."""
        self._ensure_opt_state()
        x = jnp.asarray(x)
        y = jnp.asarray(y)
        fmask = jnp.asarray(fmask) if fmask is not None else None
        lmask = jnp.asarray(lmask) if lmask is not None else None
        sig = (fmask is not None, lmask is not None)
        step, dummy = self._step_for(sig, steps)
        clock = jnp.asarray(self._iteration, jnp.int32)
        args = [self._params, self._states, self._opt_state, clock]
        if self._dynamic_scaling():
            args.append(self._ensure_scale_state())
        args += [x, y, fmask if fmask is not None else dummy,
                 lmask if lmask is not None else dummy]
        step.warm(*args)
        return self

    # ------------------------------------------------------------------ loss
    def _loss_and_reg(self, params, states, x, y, train, key, fmask, lmask):
        out, new_states = self._forward(params, states, x, train, key, fmask)
        out_layer = self.layers[-1]
        if not isinstance(out_layer, L.BaseOutputLayer):
            raise ValueError("last layer must be an output/loss layer for fit()")
        with jax.named_scope(_stepprogram.LOSS_SCOPE):
            loss = out_layer.compute_loss(y, out, mask=lmask)
            reg = 0.0
            for layer, p in zip(self.layers, params):
                l1 = layer.l1 or 0.0
                l2 = layer.l2 or 0.0
                if not p or (l1 == 0.0 and l2 == 0.0):
                    continue
                for name, w in p.items():
                    if not name.startswith(("W", "RW")):
                        continue  # reference: regularization applies to weights only
                    if l2:
                        reg = reg + 0.5 * l2 * jnp.sum(jnp.square(w))
                    if l1:
                        reg = reg + l1 * jnp.sum(jnp.abs(w))
            return loss + reg, new_states

    # ------------------------------------------------------------------- fit
    def _make_train_step(self, with_fmask: bool, with_lmask: bool,
                         steps: int = 1):
        """Compile the train step. ``steps=1``: the classic one-dispatch-
        per-step program. ``steps=K``: ONE lax.scan program performing K
        full update steps over ``[K, B, ...]`` stacked batches — the SAME
        ``step`` body, so the two are numerically equivalent."""
        base = self.conf.base
        updater = base.updater

        # frozen layers (transfer learning, ref: FrozenLayer) keep their
        # params/opt-state; handled inside the jit so buffer donation and
        # XLA DCE of the unused updates both apply
        frozen = getattr(self, "_frozen_layers", None) or set()
        seed = base.seed

        augment = self._augment
        # static loss scaling (nn.precision): the loss is scaled INSIDE
        # value_and_grad and the grads divided straight back out, so the
        # tiny fp16 gradient tail survives the backward pass while the
        # updater still sees true-magnitude fp32 gradients
        pol = self._precision
        if pol is not None and pol.is_dynamic:
            return self._make_dynamic_train_step(steps=steps,
                                                 with_fmask=with_fmask,
                                                 with_lmask=with_lmask)
        loss_scale = pol.loss_scale if pol is not None else None
        # GSPMD plan (distributed.gspmd): output sharding constraints so
        # model-sharded params / ZeRO-sharded updater state STAY sharded
        # across steps — (None, None) for pure replication, where the
        # compiled program is byte-identical to the wrapper path
        plan = self._sharding_plan
        psh, osh = (None, None) if plan is None \
            else plan.step_constraints(self)

        def step(params, states, opt_state, t, x, y, fmask, lmask):
            # per-step RNG derived ON DEVICE from the (donated) iteration
            # counter: a fresh host-built PRNGKey per step costs a full
            # host->device round trip through high-latency links, and
            # fold_in(base, t) keeps dropout deterministic per iteration
            # (and therefore exact-resume stable)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
            # on-device augmentation prelude (nn.augment): uint8 pixels
            # off the staged pipeline are cast + crop/flip/normalized
            # HERE, seeded by fold_in(aug_seed, t) — bit-reproducible per
            # seed and identical under the scanned megastep
            x = _augment_mod.maybe_augment(augment, x, t)
            tf = t.astype(jnp.float32)

            def loss_fn(p):
                loss, ns = self._loss_and_reg(p, states, x, y, True, key,
                                              fmask if with_fmask else None,
                                              lmask if with_lmask else None)
                if loss_scale:
                    loss = loss * loss_scale
                return loss, ns
            (loss, new_states), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            if loss_scale:
                inv = 1.0 / loss_scale
                loss = loss * inv           # listeners/score see true loss
                grads = _unscale_grads(grads, inv)
            new_params, new_opt = _process_and_apply_grads(
                base, updater, params, grads, opt_state, tf)
            if frozen:
                new_params = [params[i] if i in frozen else new_params[i]
                              for i in range(len(params))]
                new_opt = [opt_state[i] if i in frozen else new_opt[i]
                           for i in range(len(opt_state))]
            new_params = _stepping.constrain_tree(new_params, psh)
            new_opt = _stepping.constrain_tree(new_opt, osh)
            return new_params, new_states, new_opt, t + 1, loss
        # donate params/states/opt_state/t: consumed and replaced each step,
        # so dependent dispatches queue without a host round trip. The jit sits
        # behind the compile-cache seam (nn.compilecache): plain jit
        # dispatch until a warmup engages the AOT path.
        if steps > 1:
            return _cc.cached_dispatch(
                _stepping.scan_megastep(step, 4), "mln:megastep",
                donate_argnums=(0, 1, 2, 3))
        return _cc.cached_dispatch(step, "mln:train_step",
                                   donate_argnums=(0, 1, 2, 3))

    def _make_dynamic_train_step(self, steps: int, with_fmask: bool,
                                 with_lmask: bool):
        """The train step under ``PrecisionPolicy(loss_scale="dynamic")``
        — the fp16 survival kit upgraded from a fixed constant to the
        standard grow/backoff automaton, entirely inside the compiled
        program (no per-step host sync):

        - grads come back through the scaled backward; a non-finite
          gradient anywhere means the scale overflowed the fp16 range —
          the update (params, opt state, layer states) is DROPPED via
          ``jnp.where`` selects and the scale multiplies by
          ``backoff_factor``.
        - every clean step advances a good-step counter; after
          ``growth_interval`` consecutive clean steps the scale grows by
          ``growth_factor`` (probing the headroom back).

        The scale state ``[scale, good_steps]`` is a donated carry like
        the params — it threads through the lax.scan megastep and is
        persisted/restored by resilience checkpoints. With no overflow
        and a huge growth interval this is bit-exact with the static
        scale of the same value (pinned)."""
        base = self.conf.base
        updater = base.updater
        frozen = getattr(self, "_frozen_layers", None) or set()
        seed = base.seed
        augment = self._augment
        pol = self._precision
        plan = self._sharding_plan
        psh, osh = (None, None) if plan is None \
            else plan.step_constraints(self)

        def step(params, states, opt_state, t, scale_state, x, y, fmask,
                 lmask):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
            x = _augment_mod.maybe_augment(augment, x, t)
            tf = t.astype(jnp.float32)
            scale = scale_state[0]

            def loss_fn(p):
                loss, ns = self._loss_and_reg(p, states, x, y, True, key,
                                              fmask if with_fmask else None,
                                              lmask if with_lmask else None)
                return loss * scale, ns
            (loss, new_states), grads = \
                jax.value_and_grad(loss_fn, has_aux=True)(params)
            inv = 1.0 / scale
            loss = loss * inv           # listeners/score see true loss
            grads = _unscale_grads(grads, inv)
            # overflow detection on the UNSCALED grads: any non-finite
            # leaf anywhere = the scaled backward left fp16 range
            ok = _grads_all_finite(grads)
            new_params, new_opt = _process_and_apply_grads(
                base, updater, params, grads, opt_state, tf)
            new_params = _select_update(ok, new_params, params)
            new_opt = _select_update(ok, new_opt, opt_state)
            new_states = _select_update(ok, new_states, states)
            if frozen:
                new_params = [params[i] if i in frozen else new_params[i]
                              for i in range(len(params))]
                new_opt = [opt_state[i] if i in frozen else new_opt[i]
                           for i in range(len(opt_state))]
            new_params = _stepping.constrain_tree(new_params, psh)
            new_opt = _stepping.constrain_tree(new_opt, osh)
            return (new_params, new_states, new_opt, t + 1,
                    _dynamic_scale_next(pol, scale_state, ok), loss)
        if steps > 1:
            return _cc.cached_dispatch(
                _stepping.scan_megastep(step, 5), "mln:megastep",
                donate_argnums=(0, 1, 2, 3, 4))
        return _cc.cached_dispatch(step, "mln:train_step",
                                   donate_argnums=(0, 1, 2, 3, 4))

    def _dynamic_scaling(self) -> bool:
        pol = self._precision
        return pol is not None and pol.is_dynamic

    def _ensure_scale_state(self):
        """Device-resident ``[scale, good_steps]`` carry for dynamic loss
        scaling (donated/replaced by the compiled step, persisted by
        resilience checkpoints)."""
        if self._scale_state is None:
            s = jnp.asarray(
                [float(self._precision.loss_scale_init), 0.0], jnp.float32)
            if self._sharding_plan is not None:  # see _ensure_clock
                s = jax.device_put(s, self._sharding_plan.mesh.replicated())
            self._scale_state = s
        return self._scale_state

    def current_loss_scale(self):
        """The live dynamic loss scale (host float), or the static scale,
        or None when the attached policy scales nothing."""
        if self._dynamic_scaling():
            if self._scale_state is None:
                return float(self._precision.loss_scale_init)
            return float(np.asarray(jax.device_get(self._scale_state))[0])
        pol = self._precision
        return pol.loss_scale if pol is not None else None

    def _ensure_opt_state(self):
        if self._opt_state is None:
            updater = self.conf.base.updater
            self._opt_state = jax.tree_util.tree_map(
                lambda p: updater.init_state(p), self._params,
                is_leaf=lambda x: isinstance(x, jax.Array))

    def _ensure_clock(self):
        """Device-resident iteration counter (int32 scalar). The compiled
        step donates it and returns t+1, so steady-state training uploads
        NOTHING per step — uploading a fresh host scalar each iteration
        serializes the dispatch pipeline on high-latency device links.
        Under a GSPMD plan the fresh clock commits replicated onto the
        plan's mesh so the FIRST dispatch already carries the
        steady-state signature (one compile, not compile-then-retrace
        when the returned clock comes back committed)."""
        if self._t_dev is None:
            t = jnp.asarray(self._iteration, jnp.int32)
            if self._sharding_plan is not None:
                t = jax.device_put(t, self._sharding_plan.mesh.replicated())
            self._t_dev = t
        return self._t_dev

    def setComputeLayout(self, fmt: str) -> "MultiLayerNetwork":
        """Compute layout for the conv stacks: ``"NHWC"`` runs conv/pool/
        BN/LRN channels-minor inside the compiled step (the MXU-preferred
        layout W101 points at) with ONE transpose at each layout
        boundary; the public API — inputs, outputs, weights
        ``[O,I,kH,kW]``, checkpoints — stays NCHW and is bit-compatible.
        ``"NCHW"`` (default) restores the reference layout. Changing the
        layout busts the compiled step caches (one recompile); steady
        state stays at zero recompiles either way."""
        if fmt not in ("NCHW", "NHWC"):
            raise ValueError(f"compute layout must be 'NCHW' or 'NHWC', "
                             f"got {fmt!r}")
        if fmt != getattr(self, "_compute_layout", "NCHW"):
            self._train_step_cache = {}
            self._megastep_cache = {}
            self._fwd_cache = None
        self._compute_layout = fmt
        # recorded on the config too, so save/load round-trips the seam
        # (the per-layer stamps alone would deserialize into an NCHW
        # forward feeding NHWC-stamped layers)
        self.conf.base.compute_layout = fmt
        L.stamp_layout(self.layers, fmt)
        return self

    def setEpilogueFusion(self, enabled: bool = True) -> "MultiLayerNetwork":
        """Fuse conv-bias+BN+relu (and BN+leaky-relu) blocks into ONE
        ``scale_shift_act`` dispatch — one registry op in composed jnp,
        bit-identical to the unfused stack, which the compiler fuses into
        the neighbouring convolution. Opt-in; busts the step caches when
        toggled."""
        enabled = bool(enabled)
        if enabled != self._fuse_epilogues:
            self._train_step_cache = {}
            self._megastep_cache = {}
            self._fwd_cache = None
            self._epilogue_plan = None
        self._fuse_epilogues = enabled
        return self

    def _ensure_epilogue_plan(self):
        if self._epilogue_plan is None:
            self._epilogue_plan = L.build_epilogue_plan(
                self.layers, self.conf.preprocessors)
        return self._epilogue_plan

    def setDeviceAugmentation(self, augment) -> "MultiLayerNetwork":
        """Attach (or detach with ``None``) a
        :class:`~deeplearning4j_tpu.nn.augment.DeviceAugmentation`: the
        chain runs as a seeded prelude INSIDE the compiled train step, so
        uint8 pixels off the staged pipeline are cast + augmented on
        device. A chain with a different :meth:`signature` invalidates
        the compiled step caches (one recompile); re-attaching an equal
        chain keeps them — steady state stays at zero recompiles."""
        cur = getattr(self, "_augment", None)
        same = (augment.signature() if augment is not None else None) == \
            (cur.signature() if cur is not None else None)
        self._augment = augment
        if not same:
            self._train_step_cache.clear()
            self._megastep_cache.clear()
        return self

    def setShardingPlan(self, plan) -> "MultiLayerNetwork":
        """Attach (or detach with ``None``) a
        :class:`~deeplearning4j_tpu.distributed.gspmd.
        ShardedTrainingPlan`: params/updater state are placed per the
        plan's NamedShardings (``plan.apply``/``ensure_placed``),
        batches stage per its batch PartitionSpec, and the compiled
        step pins sharded outputs with ``with_sharding_constraint`` —
        ONE ``jax.jit`` program covering data/model/seq axes. A plan
        with a different :meth:`~deeplearning4j_tpu.distributed.gspmd.
        ShardedTrainingPlan.signature` invalidates the compiled step
        caches (one recompile); re-attaching an equal plan keeps them —
        steady state stays at zero recompiles."""
        cur = self._sharding_plan
        same = (plan.signature() if plan is not None else None) == \
            (cur.signature() if cur is not None else None)
        self._sharding_plan = plan
        if not same:
            self._train_step_cache.clear()
            self._megastep_cache.clear()
            self._fwd_cache = None
            self._t_dev = None  # the device clock moves to the plan's mesh
        return self

    def setPrecisionPolicy(self, policy) -> "MultiLayerNetwork":
        """Attach (or detach with ``None``) a
        :class:`~deeplearning4j_tpu.nn.precision.PrecisionPolicy` (or a
        dtype string like ``"bf16"``): non-island layers compute in the
        policy's dtype inside the compiled step while master params and
        updater state stay fp32, and ``loss_scale`` (fp16) is applied/
        removed around the backward pass.  A policy with a different
        :meth:`signature` invalidates the compiled step caches (one
        recompile); re-attaching an equal policy keeps them — steady
        state stays at zero recompiles.  Low-precision master params
        are rejected (the E301 hazard class)."""
        from deeplearning4j_tpu.nn.precision import (PrecisionPolicy,
                                                     runtime_check)
        policy = PrecisionPolicy.coerce(policy)
        if policy is not None:
            runtime_check(policy)
        cur = self._precision
        same = (policy.signature() if policy is not None else None) == \
            (cur.signature() if cur is not None else None)
        self._precision = policy
        if not same:
            self._train_step_cache.clear()
            self._megastep_cache.clear()
            self._tbptt_step_cache = {}
            self._fwd_cache = None
            self._scale_state = None    # dynamic loss scale restarts with
        return self                     # its policy's init value

    def fit(self, data, labels=None, epochs: int = 1,
            steps_per_dispatch: int = 1, prefetch: int = 2,
            checkpoint=None, nan_policy=None, faults=None, augment=None,
            precision=None, tune=None):
        """ref: MultiLayerNetwork.fit(DataSetIterator) — accepts an
        iterator, a DataSet, or (features, labels) arrays.

        ``tune="auto"`` consults the autotuner record store
        (``tune.records``) for this (model, mesh, backend, jax version)
        and applies the winning :class:`~deeplearning4j_tpu.tune.space.
        TuningPlan` — layout/fusion/precision seams plus the plan's
        ``steps_per_dispatch``/``prefetch`` wherever the caller left the
        defaults (explicit arguments, including ``precision=``, win).
        No record -> one warning, defaults stand.  A ``TuningPlan``
        instance applies directly, bypassing the store.

        ``precision=PrecisionPolicy("bfloat16")`` (or just ``"bf16"``)
        attaches the mixed-precision policy for this and later fits —
        see :meth:`setPrecisionPolicy`.

        ``steps_per_dispatch=K`` batches K consecutive same-signature
        minibatches into ONE compiled ``lax.scan`` program performing K
        full update steps per host dispatch, with the next megabatch
        staged onto the device by a background DevicePrefetcher while the
        current one computes (``prefetch`` = staging queue depth;
        ``prefetch=0`` keeps iterator consumption and staging synchronous
        on the calling thread — required for thread-affine data sources
        like sqlite cursors). Numerically equivalent to K single-step
        fits; listeners observe the K per-step losses after each
        dispatch.

        A configuration built with ``backpropType('tbptt',
        tBPTTLength=L)`` trains truncated: every sequence batch
        ([N, C, T] features) is segmented into length-L windows via the
        compiled TBPTT step, identical to calling ``fitTBPTT(ds, L)``
        per batch (pinned by an equivalence test). The TBPTT path keeps
        its segment-level dispatch — ``steps_per_dispatch`` does not
        apply to it (megastep x TBPTT composition is a ROADMAP item).
        Checkpoint/resume and NaN policies DO compose with TBPTT:
        segment steps count as update steps, recovery and checkpoints
        act at batch boundaries (where no RNN segment state is carried),
        and resume is bit-exact.

        Fault tolerance (``train.resilience``): ``checkpoint=
        CheckpointConfig(dir, every_steps=..., resume=True)`` gives the
        fit periodic atomic checkpoints and auto-resume from the newest
        validated one; ``nan_policy=NanPolicy.{RAISE, SKIP_STEP,
        BACKOFF_LR, ROLLBACK}`` (or a ``NanRecovery``) turns a
        non-finite loss into recovery instead of a dead job; ``faults=
        FaultPlan(...)`` injects deterministic failures for testing.
        SIGTERM/SIGINT during a checkpointed fit finishes the in-flight
        (mega)step, writes a checkpoint marked ``"preempted"``, and
        returns cleanly.

        ``augment=DeviceAugmentation(...)`` compiles crop/flip/normalize
        into the train step itself (see :meth:`setDeviceAugmentation`).
        A staged iterator whose ``megabatch_steps`` matches
        ``steps_per_dispatch`` feeds the fit through its native
        ``dispatch_stream()`` — whole contiguous ``[K, B, ...]`` uint8
        megabatches, ONE H2D transfer per dispatch instead of K
        per-batch copies + stacks (resilience sessions keep the
        per-batch path: their cursors are recorded at pull granularity)."""
        if not self._initialized:
            self.init()
        self._ensure_opt_state()
        if tune is not None:
            steps_per_dispatch, prefetch = _stepping.apply_tuned_plan(
                self, tune, steps_per_dispatch, prefetch)
        if augment is not None:
            self.setDeviceAugmentation(augment)
        if precision is not None:
            self.setPrecisionPolicy(precision)
        _maybe_attach_env_profiler(self)
        tbptt_len = self._tbptt_length()
        session = None
        if checkpoint is not None or nan_policy is not None \
                or faults is not None:
            from deeplearning4j_tpu.train import resilience as _resilience
            session, data = _resilience.begin_session(
                self, data, checkpoint, nan_policy, faults)
            # resume cold-start killer: AOT-warm the step the restored
            # checkpoint recorded (persistent-cache-gated no-op otherwise)
            session.warm_after_resume(steps_per_dispatch)

        def batches():
            if isinstance(data, DataSetIterator):
                if session is None or not session.consume_skip_reset():
                    data.reset()
                if _stepping.use_dispatch_stream(data, steps_per_dispatch,
                                                 session):
                    yield from data.dispatch_stream()
                    return
                while data.hasNext():
                    yield data.next()
            elif isinstance(data, DataSet):
                yield data
            elif isinstance(data, (list, tuple)) and data and isinstance(data[0], DataSet):
                yield from data
            else:
                yield DataSet(np.asarray(data), np.asarray(labels))

        def epoch_stream():
            return session.wrap_batches(batches()) if session is not None \
                else batches()

        from deeplearning4j_tpu.train.resilience import fit_scope
        with fit_scope(session, self, epochs) as n_epochs:
            for _ in range(n_epochs):
                with _stepping.epoch_span(self):
                    # data-wait vs compute split: time spent pulling the next
                    # batch from the (possibly async) iterator is the input
                    # pipeline's bill, not the device's
                    if tbptt_len is not None:
                        for ds in _prof.iter_with_data_wait(epoch_stream(),
                                                          self):
                            if ds.features.ndim == 3:
                                self.fitTBPTT(ds, tbptt_len)
                            else:        # non-sequence batch: nothing to
                                self._fit_one(ds)     # segment (W002 case)
                    elif steps_per_dispatch > 1:
                        # GSPMD plan attached: the DevicePrefetcher stages
                        # megabatches per the plan's batch PartitionSpec
                        _stepping.fit_epoch_multistep(
                            self, epoch_stream(), steps_per_dispatch,
                            prefetch,
                            placement=_stepping.batch_placement(self))
                    else:
                        for ds in _prof.iter_with_data_wait(epoch_stream(),
                                                          self):
                            self._fit_one(ds)
                self._epoch += 1
                for lst in self._listeners:
                    if hasattr(lst, "onEpochEnd"):
                        lst.onEpochEnd(self)
                if session is not None:
                    session.on_epoch_end()
        return self

    def _fit_one(self, ds: DataSet):
        if not self._initialized:
            self.init()
        self._ensure_opt_state()
        if self._sharding_plan is not None:
            # GSPMD path: re-place params/updater state when they are not
            # on the plan's mesh (fresh init or a resilience restore)
            self._sharding_plan.ensure_placed(self)
        spans = _stepping.step_spans(self)
        spans.phase(_stepping.FIT_STAGE)
        x = _stepping.stage_batch(self, ds.features)
        y = _stepping.stage_batch(self, ds.labels)
        fmask = _stepping.stage_batch(self, ds.features_mask)
        lmask = _stepping.stage_batch(self, ds.labels_mask)
        spans.phase(_stepping.FIT_PREPARE)
        # recompile-churn seam: every distinct (shape, dtype) signature
        # here is one XLA compile of the train step
        new_sig = _churn.get_churn_detector().record(
            "MultiLayerNetwork.fit",
            _churn.array_fingerprint(x, y, fmask, lmask), owner=self)
        sig = (fmask is not None, lmask is not None)
        step, dummy = self._step_for(sig)
        # fence read at dispatch ENTRY: any elastic recovery landing after
        # this point voids the whole dispatch, hooks included
        gen = _stepping.fence_generation(self)
        res = getattr(self, "_resilience", None)
        if res is not None:
            res.before_step()
        # provenance sanitizer (profiler.sanitizer): one enum read when
        # OFF; under NAN_PANIC/INF_PANIC snapshots pre-step state so a
        # nonfinite loss can be attributed to its first (layer, op, step).
        # Placed AFTER the resilience hook so injected layer poisons are
        # part of the snapshot.
        tok = _sanitizer.snapshot(self, "single", x=x, y=y, fmask=fmask,
                                  lmask=lmask)
        spans.phase(_stepping.FIT_LISTENERS, "start")
        for lst in self._listeners:
            if hasattr(lst, "onIterationStart"):
                # 1-based, matching iterationDone: hook pair refers to the
                # same step number
                lst.onIterationStart(self, self._iteration + 1)
        if _prof.instrumentation_active():
            # keep the amortization-factor gauge consistent with the
            # histogram samples this block records (a megastep may have
            # left it at K)
            _stepping.STEPS_PER_DISPATCH.set(1)
            _stepping.TRAIN_ITERATIONS.inc()
        dyn = self._dynamic_scaling()
        # fit:dispatch is the host's time to ENQUEUE the compiled step
        # (the loss stays on device; an asynchronous backend runs it while
        # the host goes on to the next batch): set beside the device
        # trace, its end says whether the device ever waited for the host
        spans.phase(_stepping.FIT_DISPATCH)
        args = [self._params, self._states, self._opt_state,
                self._ensure_clock()]
        if dyn:     # dynamic loss scale: an extra donated carry
            args.append(self._ensure_scale_state())
        args += [x, y, fmask if fmask is not None else dummy,
                 lmask if lmask is not None else dummy]
        out = _stepping.dispatch(self, step, args, spans,
                                 "MultiLayerNetwork.fit", new_sig)
        spans.phase(_stepping.FIT_COMMIT)
        with _stepping.dispatch_commit(self, gen) as ok:
            if not ok:      # elastic recovery rolled this step back while
                spans.done()    # the dispatch was hung: discard, no
                return          # bookkeeping
            if dyn:
                (self._params, self._states, self._opt_state, self._t_dev,
                 self._scale_state, loss) = out
            else:
                self._params, self._states, self._opt_state, self._t_dev, \
                    loss = out
        # keep the loss on-device: a float() here would block on the whole
        # step through the (high-latency) host<->device link every iteration;
        # score() converts lazily when someone actually asks
        self._score = loss
        _sanitizer.check(self, tok, loss,
                         context=f"loss at iteration {self._iteration}")
        self._last_batch_size = int(ds.features.shape[0])
        self._iteration += 1
        spans.phase(_stepping.FIT_LISTENERS, "done")
        for lst in self._listeners:
            if hasattr(lst, "iterationDone"):
                lst.iterationDone(self, self._iteration, self._epoch)
        spans.done()
        if res is not None:
            res.after_step()

    def _fit_mega(self, mb):
        """One multi-step dispatch (ISSUE 2 tentpole): K stacked batches
        through the compiled lax.scan megastep. Host bookkeeping runs once
        per dispatch — listeners see the K per-step losses AFTER it (the
        losses return as one device vector; each remains lazy until a
        listener actually converts)."""
        if not self._initialized:
            self.init()
        self._ensure_opt_state()
        if self._sharding_plan is not None:
            self._sharding_plan.ensure_placed(self)  # see _fit_one
        k = mb.steps
        spans = _stepping.step_spans(self, k)
        spans.phase(_stepping.FIT_STAGE)
        x = _stepping.stage_batch(self, mb.features, mega=True)
        y = _stepping.stage_batch(self, mb.labels, mega=True)
        fmask = _stepping.stage_batch(self, mb.features_mask, mega=True)
        lmask = _stepping.stage_batch(self, mb.labels_mask, mega=True)
        spans.phase(_stepping.FIT_PREPARE)
        new_sig = _churn.get_churn_detector().record(
            "MultiLayerNetwork.megastep",
            _churn.array_fingerprint(x, y, fmask, lmask), owner=self)
        sig = (fmask is not None, lmask is not None)
        step, dummy = self._step_for(sig, k)
        gen = _stepping.fence_generation(self)  # dispatch entry (see _fit_one)
        res = getattr(self, "_resilience", None)
        if res is not None:
            res.before_dispatch()
        tok = _sanitizer.snapshot(self, "mega", x=x, y=y, fmask=fmask,
                                  lmask=lmask)   # see _fit_one
        if _prof.instrumentation_active():
            _stepping.STEPS_PER_DISPATCH.set(k)
        dyn = self._dynamic_scaling()
        spans.phase(_stepping.FIT_DISPATCH)
        args = [self._params, self._states, self._opt_state,
                self._ensure_clock()]
        if dyn:     # dynamic loss scale: an extra scanned carry
            args.append(self._ensure_scale_state())
        args += [x, y, fmask if fmask is not None else dummy,
                 lmask if lmask is not None else dummy]
        out = _stepping.dispatch(self, step, args, spans,
                                 "MultiLayerNetwork.megastep", new_sig, k)
        spans.phase(_stepping.FIT_COMMIT)
        with _stepping.dispatch_commit(self, gen) as ok:
            if not ok:
                spans.done()
                return      # abandoned dispatch: see dispatch_commit
            if dyn:
                (self._params, self._states, self._opt_state, self._t_dev,
                 self._scale_state, losses) = out
            else:
                self._params, self._states, self._opt_state, self._t_dev, \
                    losses = out
        _stepping.record_megastep(self, losses, k, int(x.shape[1]),
                                  san_token=tok, spans=spans)

    # ----------------------------------------------------------------- score
    def score(self, ds: DataSet = None) -> float:
        """Last minibatch score, or score of a given DataSet (ref: score())."""
        if ds is None:
            if self._score is not None and not isinstance(self._score, float):
                self._score = float(self._score)
            return self._score
        loss, _ = self._loss_and_reg(
            self._params, self._states, jnp.asarray(ds.features),
            jnp.asarray(ds.labels), False, jax.random.PRNGKey(0),
            jnp.asarray(ds.features_mask) if ds.features_mask is not None else None,
            jnp.asarray(ds.labels_mask) if ds.labels_mask is not None else None)
        return float(loss)

    # ------------------------------------------------------------- evaluation
    def evaluate(self, iterator, evaluation=None,
                 pull_chunk: int = _EVAL_PULL_CHUNK,
                 prefetch: bool = True) -> Evaluation:
        """ref: MultiLayerNetwork.evaluate(DataSetIterator); also accepts
        any plain iterable of DataSets. ``pull_chunk`` bounds how many
        batches of predictions stay on device between bulk D2H pulls —
        lower it for very large per-batch outputs. ``prefetch=False``
        keeps iterator consumption on the calling thread (thread-affine
        data sources)."""
        ev = evaluation or Evaluation()
        for labels, preds, mask in _predict_batches(self.output, iterator,
                                                    pull_chunk, prefetch):
            ev.eval(labels, preds, mask=mask)
        return ev

    def evaluateRegression(self, iterator,
                           pull_chunk: int = _EVAL_PULL_CHUNK,
                           prefetch: bool = True) -> RegressionEvaluation:
        ev = RegressionEvaluation()
        for labels, preds, mask in _predict_batches(self.output, iterator,
                                                    pull_chunk, prefetch):
            ev.eval(labels, preds, mask=mask)
        return ev

    # ------------------------------------------------------------ param views
    def params(self) -> jnp.ndarray:
        """The reference's single flat contiguous param vector
        (ref: MultiLayerNetwork.params()). Heterogeneously-sharded
        leaves (a GSPMD plan) are gathered to host BEFORE
        concatenation: a device-side ``jnp.concatenate`` over
        differently-sharded arrays silently misassembles the result on
        this jax version (values, not just layout). Uniformly-sharded
        leaves keep the device-side fast path."""
        leaves = jax.tree_util.tree_leaves(self._params)
        if not leaves:
            return jnp.zeros((0,))
        if len({getattr(p, "sharding", None) for p in leaves}) > 1:
            host = jax.device_get(leaves)
            return jnp.asarray(np.concatenate([np.ravel(p) for p in host]))
        return jnp.concatenate([jnp.ravel(p) for p in leaves])

    def setParams(self, flat):
        flat = jnp.asarray(flat)
        leaves, treedef = jax.tree_util.tree_flatten(self._params)
        out, pos = [], 0
        for p in leaves:
            n = int(np.prod(p.shape))
            out.append(jnp.reshape(flat[pos:pos + n], p.shape).astype(p.dtype))
            pos += n
        self._params = jax.tree_util.tree_unflatten(treedef, out)

    def numParams(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self._params))

    def getLayer(self, i: int):
        return self.layers[i]

    def getParam(self, i: int, name: str):
        return self._params[i][name]

    def setListeners(self, *listeners):
        self._listeners = list(listeners)

    def addListeners(self, *listeners):
        self._listeners.extend(listeners)

    def getIterationCount(self):
        return self._iteration

    def getEpochCount(self):
        return self._epoch

    def summary(self) -> str:
        lines = ["=" * 70,
                 f"{'LayerName (Type)':<36}{'nIn,nOut':<16}{'Params':<10}",
                 "=" * 70]
        total = 0
        for i, layer in enumerate(self.layers):
            n = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(self._params[i])) \
                if self._initialized else 0
            total += n
            lines.append(f"{f'{i}_{layer.name} ({type(layer).__name__})':<36}"
                         f"{f'{layer.nIn},{layer.nOut}':<16}{n:<10}")
        lines.append("-" * 70)
        lines.append(f"Total params: {total}")
        lines.append("=" * 70)
        return "\n".join(lines)

    # ------------------------------------------------------------ save / load
    def save(self, path: str, save_updater: bool = True):
        """ref: ModelSerializer.writeModel — zip(config JSON, params,
        updater state)."""
        from deeplearning4j_tpu.train.serializer import ModelSerializer
        ModelSerializer.writeModel(self, path, save_updater)

    @staticmethod
    def load(path: str, load_updater: bool = True) -> "MultiLayerNetwork":
        from deeplearning4j_tpu.train.serializer import ModelSerializer
        return ModelSerializer.restoreMultiLayerNetwork(path, load_updater)

    # --------------------------------------------------- streaming RNN state
    def rnnTimeStep(self, x):
        """Streaming inference carrying RNN state across calls
        (ref: MultiLayerNetwork.rnnTimeStep; SURVEY.md §5 tBPTT section).
        x: [N, C, T_chunk] (or [N, C] for a single step)."""
        x = jnp.asarray(x)
        single = x.ndim == 2
        if single:
            x = x[:, :, None]
        if not hasattr(self, "_rnn_states") or self._rnn_states is None:
            self._rnn_states = [None] * len(self.layers)
        cur = x
        key = jax.random.PRNGKey(0)
        for i, layer in enumerate(self.layers):
            if i in self.conf.preprocessors:
                cur = self.conf.preprocessors[i](cur)
            key, sub = jax.random.split(key)
            if hasattr(layer, "apply_with_state"):
                cur, self._rnn_states[i] = layer.apply_with_state(
                    self._params[i], cur, self._rnn_states[i])
            elif isinstance(layer, _MASK_AWARE):
                cur, _ = layer.apply(self._params[i], self._states[i], cur,
                                     False, sub, mask=None)
            else:
                cur, _ = layer.apply(self._params[i], self._states[i], cur,
                                     False, sub)
        if single and cur.ndim == 3:
            cur = cur[:, :, -1]
        return cur

    def rnnClearPreviousState(self):
        """ref: MultiLayerNetwork.rnnClearPreviousState."""
        self._rnn_states = None

    def rnnGetPreviousState(self, layer_idx: int):
        states = getattr(self, "_rnn_states", None)
        return states[layer_idx] if states else None

    def _tbptt_length(self):
        """Configured truncation length when the config declares TBPTT
        (``backpropType('tbptt') + tBPTTLength``), else None — ``fit()``
        segments sequence batches automatically when set."""
        bp = str(getattr(self.conf, "backprop_type", "standard")
                 or "standard").lower()
        if bp in ("tbptt", "truncatedbptt", "truncated_bptt") \
                and getattr(self.conf, "tbptt_length", None):
            return int(self.conf.tbptt_length)
        return None

    def fitTBPTT(self, ds: DataSet, tbptt_length: int):
        """Truncated BPTT (ref: BackpropType.TruncatedBPTT + tBPTTLength):
        the sequence is split into segments; RNN state carries across
        segments (detached), gradients stop at segment boundaries.

        Resilience (ISSUE 6 carried follow-up): one BATCH is the
        recovery unit — ``ceil(T/L)`` segment update steps dispatch as a
        group, then the session hooks see all segment losses at once
        (segment-level step accounting, batch-level cursor accounting:
        ``pulls=1``). Checkpoints therefore land on batch boundaries,
        where the carried RNN segment state is empty, which is what
        makes a TBPTT resume bit-exact."""
        T = ds.features.shape[2]
        res = getattr(self, "_resilience", None)
        if res is not None:
            res.before_dispatch()
        seg_states = [None] * len(self.layers)
        losses = []
        for start in range(0, T, tbptt_length):
            sl = slice(start, start + tbptt_length)
            feats = ds.features[:, :, sl]
            labels = ds.labels[:, :, sl] if ds.labels.ndim == 3 else ds.labels
            fmask = ds.features_mask[:, sl] if ds.features_mask is not None else None
            lmask = ds.labels_mask[:, sl] if ds.labels_mask is not None else None
            seg_states = self._fit_one_tbptt(
                DataSet(feats, labels, fmask, lmask), seg_states)
            losses.append(self._score)
        if res is not None:
            res.after_dispatch(jnp.stack([jnp.asarray(l) for l in losses]),
                               len(losses), pulls=1)
        return self

    def _make_tbptt_step(self, with_lmask: bool):
        """Compiled TBPTT segment step (one XLA program, cached — the jit
        retraces only when the carried-state pytree structure changes, i.e.
        once after the first segment materializes RNN states).

        An attached :class:`~deeplearning4j_tpu.nn.precision.
        PrecisionPolicy` is honored per segment exactly like the plain
        train step: ``policy_cast`` on every layer (the state-carrying
        RNN layers included), the loss scaled inside ``value_and_grad``
        and divided straight back out. A dynamic policy threads the
        ``[scale, good_steps]`` carry through the segment with the same
        drop-on-overflow selects — the carried RNN segment state comes
        from the forward pass (old params, stop_gradient'd), so it stays
        valid whether or not the update applies."""
        base = self.conf.base
        updater = base.updater
        seed = base.seed
        pol = self._precision
        dynamic = pol is not None and pol.is_dynamic
        loss_scale = None if (pol is None or dynamic) else pol.loss_scale
        cdt = self._compute_dtype()

        def forward_loss(p, states, t, x, y, lmask, seg_states, scale):
            cur = x
            key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
            new_seg = []
            for i, layer in enumerate(self.layers):
                if i in self.conf.preprocessors:
                    cur = self.conf.preprocessors[i](cur)
                key, sub = jax.random.split(key)
                p_i = p[i]
                if cdt is not None:
                    p_i, cur = L.policy_cast(layer, p_i, cur, cdt)
                if hasattr(layer, "apply_with_state"):
                    cur, s_new = layer.apply_with_state(p_i, cur,
                                                        seg_states[i])
                    new_seg.append(jax.tree_util.tree_map(
                        jax.lax.stop_gradient, s_new))
                else:
                    if isinstance(layer, _MASK_AWARE):
                        cur, _ = layer.apply(p_i, states[i], cur,
                                             True, sub, mask=None)
                    else:
                        cur, _ = layer.apply(p_i, states[i], cur,
                                             True, sub)
                    new_seg.append(None)
            loss = self.layers[-1].compute_loss(
                y, cur, mask=lmask if with_lmask else None)
            if scale is not None:           # dynamic: current carry value
                return loss * scale, new_seg
            if loss_scale:
                return loss * loss_scale, new_seg
            return loss, new_seg

        if dynamic:
            def step(params, states, opt_state, t, scale_state, x, y,
                     lmask, seg_states):
                scale = scale_state[0]
                (loss, new_seg), grads = jax.value_and_grad(
                    lambda p: forward_loss(p, states, t, x, y, lmask,
                                           seg_states, scale),
                    has_aux=True)(params)
                inv = 1.0 / scale
                loss = loss * inv       # listeners/score see true loss
                grads = _unscale_grads(grads, inv)
                ok = _grads_all_finite(grads)
                new_params, new_opt = _process_and_apply_grads(
                    base, updater, params, grads, opt_state,
                    t.astype(jnp.float32))
                new_params = _select_update(ok, new_params, params)
                new_opt = _select_update(ok, new_opt, opt_state)
                return (new_params, new_opt, t + 1,
                        _dynamic_scale_next(pol, scale_state, ok), loss,
                        new_seg)
            donate = (0, 2, 3, 4)
        else:
            def step(params, states, opt_state, t, x, y, lmask,
                     seg_states):
                (loss, new_seg), grads = jax.value_and_grad(
                    lambda p: forward_loss(p, states, t, x, y, lmask,
                                           seg_states, None),
                    has_aux=True)(params)
                if loss_scale:
                    inv = 1.0 / loss_scale
                    loss = loss * inv   # listeners/score see true loss
                    grads = _unscale_grads(grads, inv)
                new_params, new_opt = _process_and_apply_grads(
                    base, updater, params, grads, opt_state,
                    t.astype(jnp.float32))
                return new_params, new_opt, t + 1, loss, new_seg
            donate = (0, 2, 3)
        # params/opt_state/t (and the dynamic scale carry) are consumed
        # and replaced (states is read-only here — the segment threads
        # seg_states instead, which retrace-safely starts as a list of
        # None). Behind the compile-cache seam like every other compiled
        # step, so AOT warmup applies.
        return _cc.cached_dispatch(step, "mln:tbptt_step",
                                   donate_argnums=donate)

    def _fit_one_tbptt(self, ds: DataSet, seg_states):
        """One TBPTT segment: like _fit_one but threading initial RNN state
        in and detached final state out."""
        x = jnp.asarray(ds.features)
        y = jnp.asarray(ds.labels)
        self._ensure_opt_state()
        lmask = jnp.asarray(ds.labels_mask) if ds.labels_mask is not None else None
        sig = lmask is not None
        if sig not in self._tbptt_step_cache:
            self._tbptt_step_cache[sig] = self._make_tbptt_step(sig)
        step = self._tbptt_step_cache[sig]
        # recompile-churn seam (mirrors _fit_one): one extra signature per
        # batch's first segment is expected (the carried-state pytree goes
        # None -> materialized); anything beyond that is churn
        _churn.get_churn_detector().record(
            "MultiLayerNetwork.tbptt",
            _churn.array_fingerprint(x, y, lmask)
            + (seg_states[0] is None,), owner=self)
        # provenance (profiler.sanitizer): the segment dispatch retains
        # its carried RNN state so a nonfinite loss attributes to the
        # (layer, op, step) — including a poisoned carry crossing the
        # segment boundary
        tok = _sanitizer.snapshot(self, "tbptt", x=x, y=y, lmask=lmask,
                                  seg_states=seg_states)
        for lst in self._listeners:
            if hasattr(lst, "onIterationStart"):
                lst.onIterationStart(self, self._iteration + 1)
        lm = lmask if lmask is not None else jnp.zeros((1,))
        if self._dynamic_scaling():
            (self._params, self._opt_state, self._t_dev, self._scale_state,
             loss, new_seg) = step(
                self._params, self._states, self._opt_state,
                self._ensure_clock(), self._ensure_scale_state(), x, y,
                lm, seg_states)
        else:
            self._params, self._opt_state, self._t_dev, loss, new_seg = \
                step(self._params, self._states, self._opt_state,
                     self._ensure_clock(), x, y, lm, seg_states)
        self._score = loss  # on-device; score() converts lazily
        _sanitizer.check(self, tok, loss,
                         context=f"tBPTT loss at iteration {self._iteration}")
        self._iteration += 1
        return new_seg

    def clone(self) -> "MultiLayerNetwork":
        net = MultiLayerNetwork(self.conf)
        net.init()
        # deep-copy buffers: the compiled train steps DONATE params/states,
        # so an aliasing clone would have its arrays deleted by the donor's
        # next fit() (and vice versa)
        net._params = jax.tree_util.tree_map(jnp.copy, self._params)
        net._states = jax.tree_util.tree_map(jnp.copy, self._states)
        return net
