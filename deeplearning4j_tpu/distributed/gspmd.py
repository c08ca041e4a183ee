"""GSPMD-native fit path (tier 1): NamedSharding end-to-end.

``ParallelWrapper`` made multi-chip training a *wrapper* — replicate
params, shard the batch, let XLA allreduce — and anything beyond pure
data parallelism (tensor/sequence axes, sharded updater state) lived in
separate code paths or static lints. This module makes sharding a
*declaration* instead: a :class:`ShardedTrainingPlan` maps a
:class:`~deeplearning4j_tpu.parallel.mesh.DeviceMesh` plus per-parameter
:class:`~deeplearning4j_tpu.parallel.mesh.ShardingRule`\\ s to
``NamedSharding`` placements on params, updater state, and the batch,
and the networks' EXISTING compiled step/megastep runs under ONE
``jax.jit`` with those shardings (SNIPPETS.md [2]/[3]: mesh +
PartitionSpec annotations, let XLA insert the collectives). Data,
model, and sequence axes are one code path; the CachedDispatch/compile-
cache seam, precision policy, device augmentation, and churn detector
all carry through unchanged because the step body IS unchanged — the
only additions are committed input shardings and (when a
:class:`~deeplearning4j_tpu.distributed.zero.ZeroPlan` or model-axis
rules are declared) ``with_sharding_constraint`` on the step outputs so
XLA cannot silently gather the sharded state back to replicated.

Replication semantics: a plan with no rules and no ZeRO compiles the
byte-identical program the ``ParallelWrapper`` path compiles (same
replicated params, same batch sharding), which is what the bit-exact
parity pins in ``tests/test_distributed.py`` rely on.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu import profiler as _prof
from deeplearning4j_tpu.data.dataset import DataSetIterator as _DSIterator
from deeplearning4j_tpu.distributed.zero import ZeroPlan, updater_hbm_bytes
from deeplearning4j_tpu.parallel.mesh import DeviceMesh, ShardingRule


def _coerce_rules(rules) -> Optional[ShardingRule]:
    if rules is None or isinstance(rules, ShardingRule):
        return rules
    if isinstance(rules, dict):
        return ShardingRule(rules)
    raise TypeError(f"cannot interpret {rules!r} as sharding rules "
                    "(use ShardingRule or a {regex: spec-tuple} dict)")


class ShardedTrainingPlan:
    """Declarative mapping from a mesh to end-to-end shardings.

    - ``rules``: {param-name-regex: partition-spec-tuple} (or a
      :class:`ShardingRule`) matched against ``"<layer-or-node-name>/
      <param>"`` — the same naming the static distribution lints use.
      Unmatched params replicate.
    - ``batch_axes``: mesh axes the batch dim shards over (default
      ``("data",)``). On a model/seq-axis mesh the batch PartitionSpec
      replicates over the non-batch axes automatically — this is what
      the DevicePrefetcher placement derives from (the PR-2 carried
      follow-up: no more hard-coded ``(None, 'data')`` layout).
    - ``zero``: a :class:`~deeplearning4j_tpu.distributed.zero.
      ZeroPlan` (or ``True``) sharding updater state across the data
      axis.
    """

    def __init__(self, mesh: DeviceMesh, rules=None,
                 batch_axes: Tuple[str, ...] = ("data",), zero=None):
        self.mesh = mesh
        self.rules = _coerce_rules(rules)
        self.batch_axes = tuple(batch_axes)
        for a in self.batch_axes:
            if a not in mesh.mesh.axis_names:
                raise ValueError(f"batch axis {a!r} is not a mesh axis "
                                 f"{tuple(mesh.mesh.axis_names)}")
        self.zero = ZeroPlan.coerce(zero)

    # ------------------------------------------------------------ identity
    def signature(self):
        """Hashable identity for the compiled-step cache keys: mesh
        shape AND device ids (an equal-shaped mesh over different
        devices must bust the caches — the step's sharding-constraint
        closures are mesh-bound), rule patterns, batch axes, and the
        ZeRO declaration."""
        rules = None
        if self.rules is not None:
            rules = tuple((pat.pattern, tuple(spec))
                          for pat, spec in self.rules.rules)
        return ("gspmd", tuple(dict(self.mesh.mesh.shape).items()),
                tuple(d.id for d in self.mesh.devices), rules,
                self.batch_axes,
                self.zero.signature() if self.zero is not None else None)

    def data_shards(self) -> int:
        """How many ways the batch dim splits (the pad-to multiple)."""
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.size(a)
        return n

    def mesh_spec(self, **kw):
        """Jax-free declaration for the static analyzer: the mesh with
        this plan's sharding rules AND ZeRO declaration attached, so
        E104 accounts sharded updater state and W109 stays quiet."""
        kw.setdefault("sharding", self.rules)
        if self.zero is not None:
            kw.setdefault("zero", self.zero.declare())
        return self.mesh.spec(**kw)

    # ------------------------------------------------------- param naming
    def _leaf_param_name(self, model, path) -> str:
        """``"<layer-or-node-name>/<param>"`` for a params/opt-state leaf
        path — SequenceKey index (MultiLayerNetwork list) resolves to the
        layer's name, DictKey (ComputationGraph dict) is the node name."""
        first = path[0]
        pname = str(getattr(path[1], "key", path[1]))
        idx = getattr(first, "idx", None)
        layers = getattr(model, "layers", None)
        if idx is not None and layers is not None:
            layer = layers[idx]
            lname = getattr(layer, "name", None) or type(layer).__name__
        else:
            lname = str(getattr(first, "key", first))
        return f"{lname}/{pname}"

    def _param_spec(self, model, path, leaf) -> P:
        if self.rules is None:
            return P()
        name = self._leaf_param_name(model, path)
        return self.rules.spec_for(name, np.ndim(leaf))

    # ------------------------------------------------------- sharding trees
    def param_shardings(self, model):
        """NamedSharding pytree matching ``model._params``."""
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: NamedSharding(
                self.mesh.mesh, self._param_spec(model, path, leaf)),
            model._params)

    def opt_shardings(self, model):
        """NamedSharding pytree matching ``model._opt_state``: each
        param-shaped state tensor composes the param's spec with the
        ZeRO data-axis partitioning (when declared)."""
        n_axis = self.mesh.size(self.zero.axis) \
            if self.zero is not None and self.zero.axis in self.mesh.mesh.axis_names \
            else 1

        def spec_of(path, leaf):
            pspec = self._param_spec(model, path, leaf)
            if self.zero is not None:
                itemsize = np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
                spec = self.zero.state_spec(tuple(pspec),
                                            getattr(leaf, "shape", ()),
                                            itemsize, n_axis)
            else:
                spec = pspec
            return NamedSharding(self.mesh.mesh, spec)
        return jax.tree_util.tree_map_with_path(spec_of, model._opt_state)

    def step_constraints(self, model):
        """(param shardings, opt-state shardings) for
        ``with_sharding_constraint`` on the compiled step's outputs —
        or ``(None, None)`` for a pure-replication plan, where no
        constraint is needed and the compiled program stays
        byte-identical to the ParallelWrapper path (the bit-exact
        parity pins)."""
        if self.rules is None and self.zero is None:
            return None, None
        model._ensure_opt_state()
        return self.param_shardings(model), self.opt_shardings(model)

    # ----------------------------------------------------- batch placement
    def batch_spec(self, ndim: int, mega: bool = False) -> P:
        """The batch PartitionSpec: dim 0 (dim 1 under a ``[K, B, ...]``
        megabatch) shards over ``batch_axes``; everything else — and
        every other mesh axis — replicates."""
        if ndim == 0:
            return P()
        axes = self.batch_axes if len(self.batch_axes) > 1 \
            else self.batch_axes[0]
        if mega:
            if ndim == 1:
                return P(None)
            return P(None, axes, *([None] * (ndim - 2)))
        return P(axes, *([None] * (ndim - 1)))

    def batch_sharding(self, ndim: int, mega: bool = False) -> NamedSharding:
        return NamedSharding(self.mesh.mesh, self.batch_spec(ndim, mega))

    def place(self, a, mega: bool = False):
        """Stage one batch array onto the mesh per :meth:`batch_spec` —
        the DevicePrefetcher ``placement`` hook and the fit functions'
        staging call. A no-op copy-wise when ``a`` is already placed
        with this sharding."""
        if a is None:
            return None
        return jax.device_put(a, self.batch_sharding(np.ndim(a), mega))

    # ------------------------------------------------------------ lifecycle
    def apply(self, model):
        """Place params, layer states, and updater state onto the mesh
        per this plan, and refresh the ``dl4j_updater_hbm_bytes``
        gauge. Idempotent (device_put with an unchanged sharding is a
        no-op)."""
        if not model._initialized:
            model.init()
        model._ensure_opt_state()
        with _prof.trace_span("collective:place_params",
                              devices=self.mesh.size()):
            self.place_params(model)
            model._opt_state = jax.tree_util.tree_map(
                jax.device_put, model._opt_state, self.opt_shardings(model))
        model._t_dev = None     # rebuild the device clock on this mesh
        updater_hbm_bytes(model._opt_state)
        return model

    def place_params(self, model):
        """Place params + layer states (NOT updater state) per this
        plan — the serving-staging entry: an inference-only load must
        not allocate 2-3x its parameter bytes of never-used optimizer
        moments on the serving mesh. A live dynamic loss-scale carry
        moves with the params (its signature must match the mesh)."""
        model._params = jax.tree_util.tree_map(
            jax.device_put, model._params, self.param_shardings(model))
        model._states = self.mesh.replicate(model._states)
        if getattr(model, "_scale_state", None) is not None:
            model._scale_state = jax.device_put(model._scale_state,
                                                self.mesh.replicated())
        return model

    def ensure_placed(self, model) -> None:
        """Cheap per-dispatch guard: re-place the model when its arrays
        are not on this plan's mesh (fresh init, a resilience restore
        that swapped in host arrays, or a plan change)."""
        if model._opt_state is None:
            self.apply(model)
            return
        for tree in (model._params, model._opt_state):
            leaves = jax.tree_util.tree_leaves(tree)
            if not leaves:
                continue
            sh = getattr(leaves[0], "sharding", None)
            if getattr(sh, "mesh", None) != self.mesh.mesh:
                self.apply(model)
                return

    def __repr__(self):
        return (f"ShardedTrainingPlan(mesh={dict(self.mesh.mesh.shape)}, "
                f"rules={'yes' if self.rules else None}, "
                f"batch_axes={self.batch_axes}, zero={self.zero})")


# --------------------------------------------------------------- trainer
class GSPMDTrainer:
    """The one-``jit``-with-shardings fit driver.

    Where :class:`~deeplearning4j_tpu.parallel.wrapper.ParallelWrapper`
    is replicate-and-shard-the-batch only, this trainer applies a full
    :class:`ShardedTrainingPlan` — so the same ``fit()`` call covers
    pure DP, tensor-parallel rules, ZeRO updater-state sharding, and
    combinations, with resilience (``checkpoint=``/``nan_policy=``/
    ``faults=``) and megasteps composing unchanged (they ride the
    network's own fit loop).
    """

    def __init__(self, model, plan: ShardedTrainingPlan,
                 prefetch_buffer: int = 2):
        self.model = model
        self.plan = plan
        self.prefetch = prefetch_buffer

    @property
    def mesh(self) -> DeviceMesh:
        return self.plan.mesh

    def validate(self, batch_size: int = None, **kw):
        """Static lint against this plan's mesh + sharding + ZeRO
        declaration (E1xx/W10x incl. the ZeRO-aware E104 and W109)."""
        kw.setdefault("mesh", self.plan.mesh_spec())
        return self.model.validate(batch_size=batch_size, **kw)

    def warmup(self, shapes, *, steps_per_dispatch: int = 1, dtype=None,
               label_dtype=None, policy=None):
        """AOT-warm the model's programs under this plan's placements
        through the PR-13 compile-cache seam — same contract as
        ``ParallelWrapper.warmup`` (batch dims pad up to the plan's
        data-shard multiple exactly like ``fit`` pads real batches)."""
        from deeplearning4j_tpu.nn import compilecache as _cc
        model = self.model
        model.setShardingPlan(self.plan)
        if not model._initialized:
            model.init()
        self.plan.apply(model)
        n = self.plan.data_shards()

        def pad_shape(shape):
            shape = tuple(int(d) for d in shape)
            b = shape[0]
            if b % n:
                b += n - b % n
            return (b,) + shape[1:]

        padded = []
        for spec in shapes:
            if (isinstance(spec, (tuple, list)) and len(spec) == 2
                    and isinstance(spec[0], (tuple, list))):
                padded.append((pad_shape(spec[0]), pad_shape(spec[1])))
            else:
                padded.append(pad_shape(spec))
        k = max(int(steps_per_dispatch), 1)
        if k > 1 and any(not (isinstance(s, (tuple, list)) and len(s) == 2
                              and isinstance(s[0], (tuple, list)))
                         for s in padded):
            # same guard as ParallelWrapper.warmup: the placement hook
            # stages per the megabatch layout when k>1, which would
            # shard a bare forward shape's FEATURE dim over the data axis
            raise ValueError(
                "steps_per_dispatch>1 warms the megastep from "
                "(features, labels) pairs; bare forward shapes cannot "
                "be megabatched — warm them in a separate call")
        _cc.warmup(model, padded, policy=policy, steps_per_dispatch=k,
                   dtype=dtype, label_dtype=label_dtype,
                   placement=lambda a: self.plan.place(a, k > 1))
        return model

    def fit(self, data, epochs: int = 1, steps_per_dispatch: int = 1,
            checkpoint=None, nan_policy=None, faults=None,
            prefetch: int = None):
        """Fit through the network's own loop with this plan attached:
        batches pad up to the data-shard multiple with zero-weight
        examples (gradients exactly match the unpadded batch), stage
        onto the mesh per the plan's batch PartitionSpec, and every
        dispatch runs the ONE compiled step with the plan's shardings."""
        from deeplearning4j_tpu.data.dataset import (DataSet,
                                                     DataSetIterator,
                                                     MultiDataSet)
        model = self.model
        model.setShardingPlan(self.plan)
        if not model._initialized:
            model.init()
        self.plan.apply(model)
        n = self.plan.data_shards()
        if n > 1:
            from deeplearning4j_tpu.parallel.data import pad_to_data_axis
            if isinstance(data, DataSetIterator):
                data = _PaddingIterator(data, n)
            elif isinstance(data, (DataSet, MultiDataSet)):
                data = pad_to_data_axis(data, n)
            elif isinstance(data, (list, tuple)) and data \
                    and isinstance(data[0], (DataSet, MultiDataSet)):
                data = [pad_to_data_axis(ds, n) for ds in data]
        return model.fit(
            data, epochs=epochs, steps_per_dispatch=steps_per_dispatch,
            prefetch=self.prefetch if prefetch is None else prefetch,
            checkpoint=checkpoint, nan_policy=nan_policy, faults=faults)


class _PaddingIterator(_DSIterator):
    """DataSetIterator proxy padding every batch up to the plan's
    data-shard multiple (zero-weight tail examples — see
    ``parallel.data.pad_to_data_axis``). Forwards the checkpoint
    cursor protocol so resilience sessions compose."""

    def __init__(self, base: _DSIterator, n: int):
        self.base = base
        self.n = int(n)

    def next(self):
        from deeplearning4j_tpu.parallel.data import pad_to_data_axis
        return pad_to_data_axis(self.base.next(), self.n)

    def hasNext(self):
        return self.base.hasNext()

    def reset(self):
        self.base.reset()

    def batch(self):
        return self.base.batch()

    def cursor(self):
        return self.base.cursor()

    def seek(self, cursor):
        self.base.seek(cursor)


# ------------------------------------------------------- HLO accounting
_SHAPE_RE = re.compile(r"([a-z]+[0-9]+)\[([0-9,]*)\]")
_COLLECTIVE_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z]+[0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute)"
    r"(?:-start)?\(")
_DTYPE_BYTES = {"f64": 8, "u64": 8, "s64": 8,
                "f32": 4, "u32": 4, "s32": 4,
                "bf16": 2, "f16": 2, "u16": 2, "s16": 2,
                "u8": 1, "s8": 1, "pred": 1}


def hlo_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-kind output-tensor byte counts of the collective ops in a
    compiled (post-SPMD-partitioning) HLO module — the measured side of
    the W107 collective-volume characterization. Keys: ``all-reduce``,
    ``all-gather``, ``reduce-scatter``, ``collective-permute`` (absent
    kinds omitted); values are the summed per-device output bytes of
    each op's shape."""
    out: Dict[str, int] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        total = 0
        for dt, dims in _SHAPE_RE.findall(shape_str):
            size = _DTYPE_BYTES.get(dt, 4)
            for d in dims.split(","):
                if d:
                    size *= int(d)
            total += size
        if total:
            out[kind] = out.get(kind, 0) + total
    return out


def compiled_train_step_hlo(model, features, labels, steps: int = 1) -> str:
    """Compiled HLO text of the model's train step for this batch
    signature under the attached sharding plan (either network class,
    one input and one output; ``steps>1`` lowers the megastep over
    ``[K, B, ...]`` stacks). Nothing executes — the program is lowered
    and compiled only, which is exactly what
    ``benchmarks/probe_collectives.py``, the ``--virtual-mesh`` scaling
    bench and ``chip_smoke.py`` need to read collectives and kernels off
    the program the fit loop dispatches."""
    model._ensure_opt_state()
    plan = getattr(model, "_sharding_plan", None)
    x, y = features, labels
    if plan is not None:
        plan.ensure_placed(model)
        x = plan.place(x, steps > 1)
        y = plan.place(y, steps > 1)
    clock = jnp.asarray(model._iteration, jnp.int32)
    args = [model._params, model._states, model._opt_state, clock]
    if model._dynamic_scaling():
        args.append(model._ensure_scale_state())
    graph_inputs = getattr(model.conf, "graph_inputs", None)
    if graph_inputs is not None:            # ComputationGraph
        step, dummy = model._step_for(False, steps, 1)
        args += [{graph_inputs[0]: x}, [y], dummy]
    else:
        step, dummy = model._step_for((False, False), steps)
        args += [x, y, dummy, dummy]
    return step._jit.lower(*args).compile().as_text()
