"""Per-op DEVICE timing — the bridge from wall-clock to chip time.

The op histograms PR 1 added (``dl4j_op_dispatch_seconds``) measure host
dispatch: on an async backend they time the enqueue, not the chip. This
module closes that gap (the PR-1 carried follow-up) with two capture
paths and ONE attribution model:

- **trace** — wrap a run in ``jax.profiler`` trace capture and parse the
  XLA ``*.xplane.pb`` device planes directly (a ~100-line protobuf
  wire-format reader; no tensorboard/tensorflow dependency). Fused-op
  events map back to config layers through the ``dl4j_L<i>_<name>``
  ``jax.named_scope`` both network forwards now emit — XLA carries the
  scope in the op metadata, so a fusion that swallowed three layers is
  attributed to the first layer whose scope it names.
- **sync** — the everywhere fallback (CPU tests, backends whose profiler
  exports nothing): re-dispatch each layer's ``apply`` as its own jitted
  program with a hard ``block_until_ready`` fence around it, min-of-reps.
  Each per-layer dispatch is synced, so the measured seconds are device
  seconds (plus one dispatch overhead, which min-of-reps keeps honest);
  what it cannot see is cross-layer fusion — it measures each layer *as
  if dispatched alone*, which is exactly the per-layer cost model the
  MFU attribution needs.

Attribution: per-layer forward FLOPs come from the SAME jax-free
declared-shape model the analyzer's W105 stage-balance lint uses
(``analysis.distribution._approx_flops`` over the config's propagated
InputTypes), times batch, times the bench's train factor (backward = 2x
forward, so train = 3x). ``DeviceTimeTable`` rows carry (layer, op,
seconds, flops, mfu, share); ``top_offenders`` names the layers burning
the most device time at the worst MFU — the list ``bench.py`` prints so
a bench run names the bottleneck instead of one aggregate number.

Metrics: :meth:`DeviceTimeTable.export_metrics` publishes
``dl4j_op_device_seconds{model,layer,op}``. Export is gated on
:func:`profiler.instrumentation_active` — OFF-mode records nothing
(pinned), and plain fits never touch this module at all (the bridge is
pull-based: only an explicit ``measure()`` call dispatches anything).
"""

from __future__ import annotations

import glob
import os
import re
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from deeplearning4j_tpu import profiler as _prof

#: scope-name prefix both network forwards emit per layer; the trace
#: path greps XLA op metadata for it
SCOPE_PREFIX = "dl4j_L"
_SCOPE_RE = re.compile(r"dl4j_L(\d+)_([A-Za-z0-9_.\-]+)")

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
    from deeplearning4j_tpu.analysis.distribution import _approx_flops
    rows: List[Tuple[str, str, int]] = []
    if hasattr(conf, "graph_inputs"):            # ComputationGraph config
        types = getattr(conf, "types", {}) or {}
        for node in conf.topo:
            if node.kind != "layer":
                continue
            it = types.get(node.inputs[0]) if node.inputs else None
            out = types.get(node.name)
            try:
                f = _approx_flops(node.obj, it, out)
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

    __slots__ = ("layer", "op", "seconds", "flops", "mfu", "share")

    def __init__(self, layer: str, op: str, seconds: float, flops: float,
                 mfu: Optional[float], share: float):
        self.layer = layer
        self.op = op
        self.seconds = seconds
        self.flops = flops
        self.mfu = mfu
        self.share = share

    def as_dict(self) -> dict:
        return {"layer": self.layer, "op": self.op,
                "device_ms": round(self.seconds * 1e3, 4),
                "gflops": round(self.flops / 1e9, 3),
                "mfu": None if self.mfu is None else round(self.mfu, 4),
                "time_share": round(self.share, 4)}

    def __repr__(self):
        return (f"LayerTime({self.layer}, {self.op}, "
                f"{self.seconds * 1e3:.3f}ms, mfu={self.mfu})")


class DeviceTimeTable:
    """Per-layer device-time MFU attribution for one model + batch."""

    def __init__(self, rows: List[LayerTime], source: str,
                 batch: int, peak_flops: float, train_factor: float):
        self.rows = rows
        self.source = source          # "trace" | "sync"
        self.batch = batch
        self.peak_flops = peak_flops
        self.train_factor = train_factor

    @property
    def total_seconds(self) -> float:
        return sum(r.seconds for r in self.rows)

    def top_offenders(self, n: int = 3) -> List[dict]:
        """The layers burning the most device time, worst first — what a
        bench run should name instead of one aggregate MFU number."""
        ranked = sorted(self.rows, key=lambda r: -r.seconds)
        return [r.as_dict() for r in ranked[:n]]

    def as_rows(self, n: Optional[int] = None) -> List[dict]:
        ranked = sorted(self.rows, key=lambda r: -r.seconds)
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
            "Per-layer DEVICE seconds attributed by the devicetime "
            "bridge (trace-parsed XLA events, or sync-timed per-layer "
            "dispatch on backends without a trace)",
            labelnames=("model", "layer", "op"))
        for r in self.rows:
            c.labels(model=model_name, layer=r.layer, op=r.op).inc(r.seconds)
        return True


# -------------------------------------------------- xplane wire parser
# Minimal protobuf wire-format reader for the XSpace/XPlane schema
# (tsl/profiler/protobuf/xplane.proto) — enough to pull (plane name,
# line name, event name/display/duration) out of a jax.profiler capture
# without importing tensorflow. Unknown fields are skipped by wire type,
# so schema drift degrades to missing data, never a crash.

def _read_varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) over one message's bytes;
    value is an int for varint/fixed types and a bytes slice for
    length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _read_varint(buf, i)
        fno, wt = tag >> 3, tag & 7
        if wt == 0:                      # varint
            val, i = _read_varint(buf, i)
        elif wt == 2:                    # length-delimited
            ln, i = _read_varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 5:                    # 32-bit
            val = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        elif wt == 1:                    # 64-bit
            val = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        else:                            # groups: unsupported, stop
            return
        yield fno, wt, val


def _parse_event_metadata(buf: bytes) -> Tuple[int, str]:
    """XEventMetadata: id=1, name=2, metadata=3, display_name=4."""
    mid, name, display = 0, "", ""
    for fno, wt, val in _fields(buf):
        if fno == 1 and wt == 0:
            mid = val
        elif fno == 2 and wt == 2:
            name = val.decode("utf-8", "replace")
        elif fno == 4 and wt == 2:
            display = val.decode("utf-8", "replace")
    return mid, (f"{name} {display}".strip() if display else name)


def _parse_event(buf: bytes) -> Tuple[int, int]:
    """XEvent: metadata_id=1, offset_ps=2, duration_ps=3."""
    mid = dur = 0
    for fno, wt, val in _fields(buf):
        if fno == 1 and wt == 0:
            mid = val
        elif fno == 3 and wt == 0:
            dur = val
    return mid, dur


def _parse_line(buf: bytes) -> Tuple[str, List[Tuple[int, int]]]:
    """XLine: name=2, events=4."""
    name, events = "", []
    for fno, wt, val in _fields(buf):
        if fno == 2 and wt == 2:
            name = val.decode("utf-8", "replace")
        elif fno == 4 and wt == 2:
            events.append(_parse_event(val))
    return name, events


def _parse_plane(buf: bytes) -> dict:
    """XPlane: name=2, lines=3, event_metadata=4 (map<int64, meta>)."""
    plane = {"name": "", "lines": [], "event_names": {}}
    for fno, wt, val in _fields(buf):
        if fno == 2 and wt == 2:
            plane["name"] = val.decode("utf-8", "replace")
        elif fno == 3 and wt == 2:
            plane["lines"].append(_parse_line(val))
        elif fno == 4 and wt == 2:
            key, meta_name = 0, ""
            for kfno, kwt, kval in _fields(val):   # map entry {key=1, value=2}
                if kfno == 1 and kwt == 0:
                    key = kval
                elif kfno == 2 and kwt == 2:
                    mid, meta_name = _parse_event_metadata(kval)
                    key = mid or key
            plane["event_names"][key] = meta_name
    return plane


def parse_xspace(data) -> List[dict]:
    """Parse an XSpace (path or bytes) into
    ``[{name, lines: [(line_name, [(metadata_id, duration_ps)])],
    event_names: {id: name}}]``."""
    if isinstance(data, (str, os.PathLike)):
        with open(data, "rb") as f:
            data = f.read()
    planes = []
    for fno, wt, val in _fields(data):
        if fno == 1 and wt == 2:         # XSpace.planes
            planes.append(_parse_plane(val))
    return planes


def _is_device_plane(name: str) -> bool:
    n = name.lower()
    return ("/device:tpu" in n or "gpu:" in n.replace("/device:", "")
            or n.startswith("/device:gpu"))


def scope_seconds_from_xspace(planes: List[dict]) -> Dict[int, float]:
    """Aggregate device-plane event durations per ``dl4j_L<i>`` scope:
    {layer_index: seconds}. An event naming several scopes (a fusion
    that swallowed multiple layers) is attributed to the FIRST scope it
    names — deterministic, and the fused block's cost lands on the layer
    the fusion is rooted at."""
    out: Dict[int, float] = {}
    for plane in planes:
        if not _is_device_plane(plane["name"]):
            continue
        names = plane["event_names"]
        for _line_name, events in plane["lines"]:
            for mid, dur_ps in events:
                m = _SCOPE_RE.search(names.get(mid, ""))
                if m is None:
                    continue
                idx = int(m.group(1))
                out[idx] = out.get(idx, 0.0) + dur_ps * 1e-12
    return out


def _trace_layer_seconds(run_fn, trace_dir: Optional[str] = None
                         ) -> Optional[Dict[int, float]]:
    """Capture ``run_fn()`` under ``jax.profiler`` and return per-layer
    device seconds, or None when the backend exported no parsable device
    plane (callers fall back to sync timing)."""
    import jax
    own = trace_dir is None
    d = trace_dir or tempfile.mkdtemp(prefix="dl4j_devicetime_")
    try:
        jax.profiler.start_trace(d)
        try:
            run_fn()
        finally:
            jax.profiler.stop_trace()
        seconds: Dict[int, float] = {}
        for path in glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                              recursive=True):
            try:
                per = scope_seconds_from_xspace(parse_xspace(path))
            except Exception:
                continue
            for k, v in per.items():
                seconds[k] = seconds.get(k, 0.0) + v
        return seconds or None
    except Exception:
        return None
    finally:
        if own:
            import shutil
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
    """Per-layer forward device seconds by dispatching each layer's apply
    as its own jitted program with a block_until_ready fence, min of
    ``reps`` (first call compiles, then timed reps)."""
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
    """Measure per-layer device time for one forward batch and attribute
    MFU per layer against the analyzer's FLOP model.

    ``mode``: ``"trace"`` parses a ``jax.profiler`` capture of
    ``trace_run()`` (default: the model's jitted forward on
    ``features``), ``"sync"`` times each layer's own dispatch, and
    ``"auto"`` tries trace on TPU backends and falls back to sync —
    so the same call works on the CPU test backend.

    ``train_factor`` converts forward seconds/FLOPs into the training
    MFU convention the bench uses (backward = 2x forward → 3.0); pass
    1.0 for inference attribution."""
    import jax
    import jax.numpy as jnp

    x = features if isinstance(features, dict) else jnp.asarray(features)
    batch = (next(iter(x.values())) if isinstance(x, dict) else x).shape[0]
    flops_rows = layer_flop_model(model.conf)

    per_layer: Optional[Dict[int, float]] = None
    source = "sync"
    if mode in ("trace", "auto") and (mode == "trace"
                                      or jax.default_backend() == "tpu"):
        # graph forwards take a name->array dict; coerce a bare array
        xin = model._as_input_dict(x) \
            if not isinstance(x, dict) and hasattr(model, "_as_input_dict") \
            else x
        n_runs = max(1, reps)

        def default_run():
            for _ in range(n_runs):
                jax.block_until_ready(
                    model._jit_forward()(model._params, model._states,
                                         xin, jax.random.PRNGKey(0)))
        per_layer = _trace_layer_seconds(trace_run or default_run)
        if per_layer is not None:
            source = "trace"
            if trace_run is None:
                # only default_run repeats n_runs times; a caller-supplied
                # trace_run owns its own iteration count
                per_layer = {k: v / n_runs for k, v in per_layer.items()}
        elif mode == "trace":
            raise RuntimeError(
                "trace capture produced no parsable device plane on this "
                "backend — use mode='sync' (or 'auto')")
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

    total = sum(per_layer.values()) or 1.0
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
                              secs / total))
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
