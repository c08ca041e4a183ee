"""Per-layer device timing — which of the model's layers the chip's time
went to.

The op histograms PR 1 added (``dl4j_op_dispatch_seconds``) measure host
dispatch: on an async backend they time the enqueue, not the chip. This
module gives per-config-layer tables from two sources, and says which:

- **trace** (``source="trace"``) — device time. A run is captured with
  ``jax.profiler`` and read with ``jax.profiler.ProfileData``; every
  device op is joined by its instruction's name to the program's own map
  (:mod:`profiler.stepprogram`: the ``dl4j_L<i>_<name>``
  ``jax.named_scope`` both network forwards emit, as the compiled program
  carries it), which says the layer and whether the op is forward or
  backward work. The default run is the jitted forward; a caller's
  ``trace_run`` (a ``fit`` of a few batches) gives both columns.
- **sync** (``source="sync"``) — the everywhere fallback (CPU tests,
  backends whose profiler exports no device plane): each layer's
  ``apply`` re-dispatched as its own jitted program between hard
  ``block_until_ready`` fences, min-of-reps. That is host wall time of a
  layer *dispatched alone*: it holds one dispatch overhead a layer and
  sees no cross-layer fusion, so it ranks layers and is not what the
  layer costs inside the compiled step.

Attribution: per-layer forward FLOPs come from the SAME jax-free
declared-shape model the analyzer's W105 stage-balance lint uses
(``analysis.distribution._approx_flops`` over the config's propagated
InputTypes), times batch, times the bench's train factor (backward = 2x
forward, so train = 3x). ``DeviceTimeTable`` rows carry (layer, op,
seconds, flops, mfu, share) and, from a trace, ``backward_seconds``;
``top_offenders`` names the layers burning the most time at the worst
MFU — the list ``bench.py`` prints so a bench run names the bottleneck
instead of one aggregate number.

Metrics: :meth:`DeviceTimeTable.export_metrics` publishes
``dl4j_op_device_seconds{model,layer,op}``. Export is gated on
:func:`profiler.instrumentation_active` — OFF-mode records nothing
(pinned), and plain fits never touch this module at all (the bridge is
pull-based: only an explicit ``measure()`` call dispatches anything).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from deeplearning4j_tpu import profiler as _prof
from deeplearning4j_tpu.profiler import modes as _modes
from deeplearning4j_tpu.profiler import stepprogram as _stepprogram

#: scope-name prefix both network forwards emit per layer; the compiled
#: program carries it in every instruction's op_name
SCOPE_PREFIX = "dl4j_L"
_SCOPE_RE = re.compile(r"dl4j_L(\d+)_")

#: public v5e per-chip bf16 peak (Google Cloud documentation, "TPU v5e")
#: — callers override for other parts
DEFAULT_PEAK_FLOPS = 197e12


def scope_name(index: int, name: str) -> str:
    """The per-layer named_scope string: ``dl4j_L<i>_<sanitized-name>``."""
    safe = re.sub(r"[^A-Za-z0-9_.\-]", "-", str(name))
    return f"{SCOPE_PREFIX}{index}_{safe}"


# ------------------------------------------------------------ FLOP model
def op_kind(layer) -> str:
    """Coarse op family for the metric label / table row."""
    cls = type(layer).__name__
    kinds = (("Separable", "conv2d"), ("Depthwise", "conv2d"),
             ("Deconvolution", "conv2d"), ("Convolution3D", "conv3d"),
             ("Convolution1D", "conv1d"), ("Convolution", "conv2d"),
             ("Subsampling", "pool"), ("GlobalPooling", "pool"),
             ("BatchNormalization", "batch_norm"),
             ("LocalResponseNormalization", "lrn"),
             ("LayerNorm", "layer_norm"), ("GroupNorm", "group_norm"),
             ("Embedding", "gather"), ("LSTM", "rnn"), ("GRU", "rnn"),
             ("Rnn", "rnn"), ("Attention", "attention"),
             ("Activation", "activation"), ("Dropout", "dropout"),
             ("Output", "loss_head"), ("Loss", "loss_head"),
             ("Yolo2", "loss_head"), ("Dense", "matmul"))
    for frag, kind in kinds:
        if frag in cls:
            return kind
    return cls.lower()


def layer_flop_model(conf) -> List[Tuple[str, str, int]]:
    """Per-example forward FLOPs per layer from declared config shapes —
    the analyzer's W105 model (jax-free) applied to a sequential config
    OR a graph config. Returns ``[(layer_name, op_kind, flops), ...]``
    in forward order; layers whose InputType propagation failed report
    0 FLOPs rather than raising (attribution degrades, never breaks)."""
    from deeplearning4j_tpu.analysis.distribution import (_approx_flops,
                                                          loop_steps)
    rows: List[Tuple[str, str, int]] = []
    if hasattr(conf, "graph_inputs"):            # ComputationGraph config
        types = getattr(conf, "types", {}) or {}
        for node in conf.topo:
            if node.kind != "layer":
                continue
            it = types.get(node.inputs[0]) if node.inputs else None
            out = types.get(node.name)
            try:
                # a looped layer runs ``steps`` times a forward pass
                f = _approx_flops(node.obj, it, out) * loop_steps(conf, node)
            except Exception:
                f = 0
            rows.append((node.name, op_kind(node.obj), int(f)))
        return rows
    in_types = list(getattr(conf, "layer_input_types", []) or [])
    for i, layer in enumerate(conf.layers):
        it = in_types[i] if i < len(in_types) else None
        out = None
        try:
            out = layer.output_type(it) if it is not None else None
        except Exception:
            out = None
        try:
            f = _approx_flops(layer, it, out)
        except Exception:
            f = 0
        name = getattr(layer, "name", None) or type(layer).__name__
        if name == type(layer).__name__:
            name = f"{name.lower()}_{i}"
        rows.append((name, op_kind(layer), int(f)))
    return rows


# --------------------------------------------------------------- results
class LayerTime:
    """One attribution row: device seconds + FLOP-model MFU for a layer."""

    __slots__ = ("layer", "op", "seconds", "flops", "mfu", "share",
                 "backward_seconds")

    def __init__(self, layer: str, op: str, seconds: float, flops: float,
                 mfu: Optional[float], share: float,
                 backward_seconds: Optional[float] = None):
        self.layer = layer
        self.op = op
        self.seconds = seconds          # forward
        self.flops = flops
        self.mfu = mfu
        self.share = share
        self.backward_seconds = backward_seconds    # from a trace only

    def as_dict(self) -> dict:
        out = {"layer": self.layer, "op": self.op,
               "device_ms": round(self.seconds * 1e3, 4),
               "gflops": round(self.flops / 1e9, 3),
               "mfu": None if self.mfu is None else round(self.mfu, 4),
               "time_share": round(self.share, 4)}
        if self.backward_seconds is not None:
            out["backward_ms"] = round(self.backward_seconds * 1e3, 4)
        return out

    def __repr__(self):
        return (f"LayerTime({self.layer}, {self.op}, "
                f"{self.seconds * 1e3:.3f}ms, mfu={self.mfu})")


class DeviceTimeTable:
    """Per-layer time and MFU attribution for one model + batch:
    device time when ``source == "trace"``, host wall time of each layer
    dispatched alone when ``source == "sync"``."""

    def __init__(self, rows: List[LayerTime], source: str,
                 batch: int, peak_flops: float, train_factor: float):
        self.rows = rows
        self.source = source          # "trace" | "sync"
        self.batch = batch
        self.peak_flops = peak_flops
        self.train_factor = train_factor

    @property
    def total_seconds(self) -> float:
        return sum(r.seconds + (r.backward_seconds or 0.0)
                   for r in self.rows)

    def top_offenders(self, n: int = 3) -> List[dict]:
        """The layers burning the most device time, worst first — what a
        bench run should name instead of one aggregate MFU number."""
        return self.as_rows(n)

    def as_rows(self, n: Optional[int] = None) -> List[dict]:
        ranked = sorted(
            self.rows,
            key=lambda r: -(r.seconds + (r.backward_seconds or 0.0)))
        if n is not None:
            ranked = ranked[:n]
        return [r.as_dict() for r in ranked]

    def export_metrics(self, model_name: str) -> bool:
        """Publish ``dl4j_op_device_seconds{model,layer,op}``. Gated on
        the profiling mode: OFF records nothing (the bridge is an
        explicit measurement tool, not ambient overhead)."""
        if not _prof.instrumentation_active():
            return False
        c = _prof.get_registry().counter(
            "dl4j_op_device_seconds",
            "Per-layer seconds attributed by the devicetime bridge "
            "(device time from a trace joined to the step-program map, "
            "or host wall time of each layer dispatched alone where no "
            "trace exists)",
            labelnames=("model", "layer", "op"))
        for r in self.rows:
            c.labels(model=model_name, layer=r.layer, op=r.op).inc(r.seconds)
        return True


# ------------------------------------------------ trace joined to the map
def device_events(path: str):
    """``[(module, instruction, seconds)]`` of every device op in an
    ``.xplane.pb``, read with ``jax.profiler.ProfileData``: the events of
    the ``XLA Ops`` line of the first device plane that has one (under
    data parallelism every chip runs the same program), named by the
    instruction's head (``fusion.12``), each with the program of the
    ``XLA Modules`` line it ran inside (``jit_step``)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if out:
            break
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        modules = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns,
             ev.name.partition("(")[0])
            for ev in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        starts = [m[0] for m in modules]
        for ev in lines["XLA Ops"].events:
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            module = modules[i][2] if i >= 0 \
                and ev.start_ns < modules[i][1] else None
            head = ev.name.partition(" = ")[0].lstrip("%")
            out.append((module, head, ev.duration_ns * 1e-9))
    return out


def layer_seconds(events, programs) -> Dict[int, Dict[str, float]]:
    """``{layer index: {"forward": s, "backward": s}}`` from
    :func:`device_events` and ``{module: step-program map}``. An op the
    map gives no ``dl4j_L<i>`` layer (the updater, the loss, layout
    copies) is in no row; a ``mixed`` fusion counts where its convolution
    does."""
    out: Dict[int, Dict[str, float]] = {}
    for module, head, seconds in events:
        entry = programs.get(module, {}).get(head)
        if entry is None or entry.phase not in ("forward", "backward"):
            continue
        m = _SCOPE_RE.match(entry.layer or "")
        if m is None:
            continue
        row = out.setdefault(int(m.group(1)),
                             {"forward": 0.0, "backward": 0.0})
        row[entry.phase] += seconds
    return out


def _trace_layer_seconds(run_fn, programs=None
                         ) -> Optional[Dict[int, Dict[str, float]]]:
    """Capture ``run_fn()`` under ``jax.profiler`` and return per-layer
    device seconds, or None when the backend exported no device plane
    (callers fall back to sync timing). ``programs`` are the maps of what
    ``run_fn`` dispatches; without them the run is made with
    instrumentation on, so that the fit loops note their step functions,
    and the maps are the program's own (``stepprogram.maps()``)."""
    import jax
    d = tempfile.mkdtemp(prefix="dl4j_devicetime_")
    was = _modes._OVERRIDE
    try:
        if programs is None and not _prof.instrumentation_active():
            _prof.set_profiling_mode(_prof.ProfilingMode.BASIC)
        jax.profiler.start_trace(d)
        try:
            run_fn()
        finally:
            jax.profiler.stop_trace()
            if _modes._OVERRIDE is not was:
                _prof.set_profiling_mode(was)
        if programs is None:
            programs = _stepprogram.maps()
        events = [e for path in glob.glob(
            os.path.join(d, "**", "*.xplane.pb"), recursive=True)
            for e in device_events(path)]
        return layer_seconds(events, programs) or None
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ------------------------------------------------------- sync fallback
def _walk_layers(model, x):
    """Yield ``(index, name, layer, input_array, extra)`` in forward
    order with eagerly materialized inputs — shared by the sync timer.
    Handles both network classes; preprocessors/vertices run untimed
    between layers. Inputs are presented in the layout the layer is
    configured to compute in (the NHWC seam's ``data_format`` stamp)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn import layers as L

    cdt = model._compute_dtype()
    nhwc = getattr(model, "_compute_layout", "NCHW") == "NHWC"
    key = jax.random.PRNGKey(0)

    def run(layer, p, s, a, sub):
        if cdt is not None:
            p, a = L.policy_cast(layer, p, a, cdt)
        return layer.apply(p, s, a, False, sub)[0]

    if hasattr(model.conf, "graph_inputs"):      # ComputationGraph
        env = {model.conf.graph_inputs[0]: jnp.asarray(x)} \
            if not isinstance(x, dict) else {k: jnp.asarray(v)
                                             for k, v in x.items()}
        fmt = {k: False for k in env}
        for i, node in enumerate(model.conf.topo):
            if node.kind != "layer":
                xs = [L.to_nchw(env[n]) if fmt[n] else env[n]
                      for n in node.inputs]
                env[node.name] = node.obj.apply(*xs)
                fmt[node.name] = False
                continue
            a = env[node.inputs[0]]
            cur_nhwc = fmt[node.inputs[0]]
            if node.name in model.conf.preprocessors:
                if cur_nhwc:
                    a, cur_nhwc = L.to_nchw(a), False
                a = model.conf.preprocessors[node.name](a)
            a, cur_nhwc = L.layout_step(node.obj, a, cur_nhwc, nhwc)
            key, sub = jax.random.split(key)
            yield i, node.name, node.obj, a, sub
            out = run(node.obj, model._params[node.name],
                      model._states[node.name], a, sub)
            env[node.name] = out
            fmt[node.name] = cur_nhwc and getattr(out, "ndim", 0) == 4
        return

    cur = jnp.asarray(x)
    cur_nhwc = False
    for i, layer in enumerate(model.layers):
        if i in model.conf.preprocessors:
            if cur_nhwc:
                cur, cur_nhwc = L.to_nchw(cur), False
            cur = model.conf.preprocessors[i](cur)
        cur, cur_nhwc = L.layout_step(layer, cur, cur_nhwc, nhwc)
        name = getattr(layer, "name", None) or type(layer).__name__
        if name == type(layer).__name__:
            name = f"{name.lower()}_{i}"
        key, sub = jax.random.split(key)
        yield i, name, layer, cur, sub
        cur = run(layer, model._params[i], model._states[i], cur, sub)
        cur_nhwc = cur_nhwc and getattr(cur, "ndim", 0) == 4


def _sync_layer_seconds(model, x, reps: int = 3) -> Dict[int, float]:
    """Per-layer forward seconds by dispatching each layer's apply as its
    own jitted program with a block_until_ready fence, min of ``reps``
    (first call compiles, then timed reps): host wall time of the layer
    alone, one dispatch overhead included — not device time."""
    import jax
    from deeplearning4j_tpu.nn import layers as L

    cdt = model._compute_dtype()
    out: Dict[int, float] = {}
    for i, _name, layer, a, sub in _walk_layers(model, x):
        p = model._params[i] if isinstance(model._params, list) \
            else model._params[_name]
        s = model._states[i] if isinstance(model._states, list) \
            else model._states[_name]

        def fn(p, s, a, sub, _layer=layer):
            if cdt is not None:
                p, a = L.policy_cast(_layer, p, a, cdt)
            r = _layer.apply(p, s, a, False, sub)
            return r[0]
        jf = jax.jit(fn)
        jax.block_until_ready(jf(p, s, a, sub))      # compile
        best = float("inf")
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            jax.block_until_ready(jf(p, s, a, sub))
            best = min(best, time.perf_counter() - t0)
        out[i] = best
    return out


# --------------------------------------------------------------- measure
def measure(model, features, *, reps: int = 3, mode: str = "auto",
            peak_flops: float = DEFAULT_PEAK_FLOPS,
            train_factor: float = 3.0,
            trace_run=None) -> DeviceTimeTable:
    """Measure per-layer time for one batch and attribute MFU per layer
    against the analyzer's FLOP model.

    ``mode``: ``"trace"`` reads a ``jax.profiler`` capture of
    ``trace_run()`` (default: the model's jitted forward on ``features``)
    joined to the step-program map — device time, with a backward column
    when ``trace_run`` trains (``lambda: net.fit(batches)``); ``"sync"``
    times each layer's own dispatch on the host's clock; ``"auto"``
    tries trace on TPU backends and falls back to sync — so the same call
    works on the CPU test backend. The table's ``source`` says which.

    ``train_factor`` converts forward seconds/FLOPs into the training
    MFU convention the bench uses (backward = 2x forward → 3.0); pass
    1.0 for inference attribution."""
    import jax
    import jax.numpy as jnp

    x = features if isinstance(features, dict) else jnp.asarray(features)
    batch = (next(iter(x.values())) if isinstance(x, dict) else x).shape[0]
    flops_rows = layer_flop_model(model.conf)

    per_layer: Optional[Dict[int, float]] = None
    backward: Dict[int, float] = {}
    source = "sync"
    if mode in ("trace", "auto") and (mode == "trace"
                                      or jax.default_backend() == "tpu"):
        if trace_run is not None:
            traced = _trace_layer_seconds(trace_run)
            n_runs = 1      # a caller's trace_run owns its iteration count
        else:
            # graph forwards take a name->array dict; coerce a bare array
            xin = model._as_input_dict(x) \
                if not isinstance(x, dict) \
                and hasattr(model, "_as_input_dict") else x
            n_runs = max(1, reps)
            fwd = model._jit_forward()
            args = (model._params, model._states, xin,
                    jax.random.PRNGKey(0))

            def default_run():
                for _ in range(n_runs):
                    jax.block_until_ready(fwd(*args))
            text = fwd._jit.lower(*args).compile().as_text()
            traced = _trace_layer_seconds(
                default_run, {_stepprogram.module_name(text):
                              _stepprogram.parse(text)})
        if traced is not None:
            source = "trace"
            per_layer = {k: v["forward"] / n_runs
                         for k, v in traced.items()}
            backward = {k: v["backward"] / n_runs
                        for k, v in traced.items()}
        elif mode == "trace":
            raise RuntimeError(
                "trace capture produced no device plane the step-program "
                "map joins to on this backend — use mode='sync' (or "
                "'auto')")
    if per_layer is None:
        per_layer = _sync_layer_seconds(model, x, reps=reps)

    # layer index -> (name, op, flops): sequential configs index by
    # position; graphs index by topo position of layer nodes
    if hasattr(model.conf, "graph_inputs"):
        keyed = {}
        li = 0
        for i, node in enumerate(model.conf.topo):
            if node.kind == "layer":
                keyed[i] = flops_rows[li]
                keyed[node.name] = flops_rows[li]
                li += 1
    else:
        keyed = dict(enumerate(flops_rows))

    total = (sum(per_layer.values()) + sum(backward.values())) or 1.0
    rows = []
    for idx, secs in sorted(per_layer.items()):
        name, op, fl = keyed.get(idx, (f"layer_{idx}", "unknown", 0))
        fl_total = float(fl) * batch * train_factor
        # per-layer MFU: this layer's forward FLOPs over its own forward
        # device seconds (the train-convention 3x cancels out of the
        # ratio, so forward-only measurement attributes train MFU)
        mfu = (float(fl) * batch) / (secs * peak_flops) \
            if secs > 0 and fl else None
        rows.append(LayerTime(str(name), op, secs, fl_total,
                              None if mfu is None else min(mfu, 1.0),
                              (secs + backward.get(idx, 0.0)) / total,
                              backward.get(idx) if source == "trace"
                              else None))
    return DeviceTimeTable(rows, source, batch, peak_flops, train_factor)


def attribution_detail(model, features, *, model_name: str,
                       peak_flops: float = DEFAULT_PEAK_FLOPS,
                       reps: int = 3, top: int = 8,
                       mode: str = "auto") -> dict:
    """The bench-row payload: per-layer table (top-N by device time) +
    top_offenders + capture source. Also exports the
    ``dl4j_op_device_seconds`` series when instrumentation is active."""
    table = measure(model, features, reps=reps, mode=mode,
                    peak_flops=peak_flops)
    table.export_metrics(model_name)
    return {"source": table.source,
            "device_ms_total": round(table.total_seconds * 1e3, 3),
            "per_layer": table.as_rows(top),
            "top_offenders": table.top_offenders(3)}
