"""From the profiler's trace and the program's counters to numbers.

``reduce_xspace`` reads the ``.xplane.pb`` a traced run writes (with
``jax.profiler.ProfileData``, nothing else) into a :class:`Reduced`: for
every device the operations that ran in the traced stretch, each sorted
into ``conv`` (convolutions, matmuls and the fusions that hold one),
``collective`` or ``other``; the step marks; and the host's spans
(``chipbench:step``, ``chipbench:pull``) on the same clock. The per-layer
metric readers under ``chipbench/metrics/`` take their numbers from it.

How an event is sorted (looked at by hand in the first TPU v5e trace of
``resnet50-fit-b256``, PR 25; pinned by ``tests/chipbench`` on a recorded
trace). A device plane ``/device:TPU:<n>`` has a line ``XLA Ops`` with one
event per executed HLO instruction, named by the instruction's whole text
(``%fusion.30 = bf16[64,3,7,7]{...} fusion(...), kind=kOutput,
calls=...``), and a line ``XLA Modules`` with one event per program run
(``jit_step(<hash>)``). ``ProfileData`` shows no ``hlo_category`` or
``tf_op`` stat on an event, so the text decides:

- an opcode that names a collective is ``collective``;
- ``convolution`` and ``dot``, and a ``fusion`` of ``kind=kOutput`` (the
  TPU compiler's fusion of a convolution or matmul with the elementwise
  and reduction work around it: in the compiled ResNet-50 and Tiny YOLO
  steps every such fusion of two operands or more holds a convolution and
  no other fusion does; a max-pool's forward ``reduce-window`` is a
  ``kOutput`` fusion of one operand), are ``conv``;
- everything else that occupies the core is ``other``: loop and input
  fusions, ``select-and-scatter``, copies, and Pallas kernels
  (``custom-call``).

The compiler folds BatchNorm's reductions and the residual adds into the
neighbouring convolution's fusion where it can, so ``conv`` time holds some
of that work: the two classes are what the chip ran, not what the model's
layers are.
"""

import glob
import gzip
import json
import os
import re
import time

STEP_SPAN = "chipbench:step"
PULL_SPAN = "chipbench:pull"

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k[A-Za-z]+)")
_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\](\{[^}]*\})?")


def compact(name: str) -> str:
    """An event's name without its operand list: ``%fusion.30 = bf16[..]
    fusion kOutput of 7`` (a fusion's kind and how many operands it has).
    A name that is not an instruction's text stays."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name
    m = _OPCODE.search(" " + rest)
    if m is None:
        return name
    out_shapes = _SHAPE.findall(rest[:m.start()])
    shape = ""
    if out_shapes:
        dt, dims, _layout = max(out_shapes, key=lambda s: _elements(s[1]))
        shape = f"{dt}[{dims}] "
    kind = _KIND.search(rest)
    if kind is None:
        return f"{head} = {shape}{m.group(1)}"
    operands = rest[m.end() - 1:kind.start()].count("%")
    return f"{head} = {shape}{m.group(1)} {kind.group(1)} of {operands}"


def hbm_bytes(name: str) -> int:
    """Bytes an instruction moves to and from HBM as it was compiled: its
    outputs and operands once each, leaving out those the compiler placed
    in on-chip memory (a layout ending in ``S(1)``). 0 for a name that is
    not an instruction's text, and for the ``-start`` and ``-done`` marks
    of an asynchronous copy: its bytes move under other operations, and
    counting them against the marks' own time would pass the peak."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return 0
    op = _OPCODE.search(" " + rest)
    if op is not None and op.group(1).endswith(("-start", "-done")):
        return 0
    kind = _KIND.search(rest)
    if kind is not None:
        rest = rest[:kind.start()]
    total = 0
    for dt, dims, layout in _SHAPE.findall(rest):
        if "S(" in layout:
            continue
        bits = "".join(ch for ch in dt if ch.isdigit())
        total += _elements(dims) * (int(bits) // 8 if bits else 1)
    return total


def _elements(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def classify(name: str) -> str:
    """``conv``, ``collective`` or ``other`` for one device event, from its
    full or compact name."""
    text = compact(name)
    head, eq, rest = text.partition(" = ")
    words = rest.split() if eq else []
    op = next((w for w in words if not _SHAPE.match(w)), "") if eq \
        else head.lstrip("%").split(".")[0]
    if any(op.startswith(c) for c in _COLLECTIVES):
        return "collective"
    if op in ("convolution", "dot"):
        return "conv"
    if op == "fusion" and "kOutput" in words and words[-1] != "1":
        return "conv"       # a convolution has two operands or more
    return "other"


def union_seconds(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """The idle (start, end) gaps between ``lo`` and ``hi``."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


class DeviceOps:
    """One device's events in the traced stretch (seconds, trace clock)."""

    def __init__(self, name):
        self.name = name
        self.ops = []       # (start, end, compact name, class, HBM bytes)
        self.modules = []   # (start, end, module name): one per step program

    def intervals(self, cls=None):
        return [(o[0], o[1]) for o in self.ops if cls is None or o[3] == cls]

    def seconds(self, cls):
        return sum(o[1] - o[0] for o in self.ops if o[3] == cls)

    def bytes(self, cls):
        return sum(o[4] for o in self.ops if o[3] == cls)

    def busy(self):
        return union_seconds(self.intervals())

    def exposed(self, cls, others):
        """Seconds in which an op of class ``cls`` runs and none of
        ``others`` does."""
        rest = [i for o in others for i in self.intervals(o)]
        return union_seconds(self.intervals(cls) + rest) \
            - union_seconds(rest)


class Reduced:
    def __init__(self):
        self.devices = []       # DeviceOps, one per chip
        self.host = {}          # span name -> [(start, end)]
        self.window = None      # (start, end) of the traced stretch
        self.steps = 0          # update steps inside it

    def busiest(self):
        return max(self.devices, key=lambda d: d.busy())

    @property
    def window_s(self):
        return self.window[1] - self.window[0]


def find_xspace(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xspace(path: str) -> dict:
    """What the reduction reads of a trace, as plain data:
    ``{"devices": {plane: {"ops": [[name, start_ns, dur_ns, hbm_bytes]],
    "modules": [[name, start_ns, dur_ns]]}}, "host": {span: [[start_ns,
    dur_ns]]}}`` with compact names."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    raw = {"devices": {}, "host": {}}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX) and \
                plane.name[len(DEVICE_PLANE_PREFIX):].isdigit():
            dev = raw["devices"].setdefault(plane.name,
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                names = {}
                for ev in line.events:
                    n = ev.name
                    if n not in names:
                        names[n] = (compact(n), hbm_bytes(n))
                    row = [names[n][0], int(ev.start_ns),
                           int(ev.duration_ns)]
                    dev[key].append(row + [names[n][1]] if key == "ops"
                                    else row)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (STEP_SPAN, PULL_SPAN):
                        raw["host"].setdefault(ev.name, []).append(
                            [int(ev.start_ns), int(ev.duration_ns)])
    return raw


def save_recording(raw: dict, path: str, steps: int = 2,
                   step_module: str = None):
    """Keep the first ``steps`` step programs of every device of ``raw``
    as a small ``.json.gz`` the tests reduce again."""
    out = {"devices": {}, "host": {}}
    hi = 0
    for plane, dev in raw["devices"].items():
        marks = _step_modules(
            [(s, s + d, n) for n, s, d in dev["modules"]], step_module)
        marks = marks[:steps]
        if not marks:
            continue
        lo, hi = marks[0][0], marks[-1][1]
        out["devices"][plane] = {
            "ops": [o for o in dev["ops"] if lo <= o[1] and o[1] + o[2] <= hi],
            "modules": [[n, s, e - s] for s, e, n in marks]}
    for span, evs in raw["host"].items():
        out["host"][span] = [e for e in evs if e[0] <= hi]
    with gzip.open(path, "wt") as f:
        json.dump(out, f, separators=(",", ":"))


def load_recording(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def reduce_xspace(path: str, step_module: str = None) -> Reduced:
    return reduce_raw(read_xspace(path), step_module)


def reduce_raw(raw: dict, step_module: str = None) -> Reduced:
    """The traced stretch runs from the first step program's start to the
    last one's end on the busiest device; ``step_module`` (a substring of
    the step program's module name) picks the step marks, and without it
    the module with the most device time does."""
    red = Reduced()
    for plane in sorted(raw["devices"]):
        data = raw["devices"][plane]
        dev = DeviceOps(plane)
        classes = {}
        for name, start, dur, nbytes in data["ops"]:
            if name not in classes:
                classes[name] = classify(name)
            dev.ops.append((start * 1e-9, (start + dur) * 1e-9, name,
                            classes[name], nbytes))
        dev.modules = _step_modules(
            [(s * 1e-9, (s + d) * 1e-9, n) for n, s, d in data["modules"]],
            step_module)
        if dev.ops:
            red.devices.append(dev)
    if not red.devices:
        raise ValueError("no device operations in the trace")
    red.host = {span: [(s * 1e-9, (s + d) * 1e-9) for s, d in evs]
                for span, evs in raw["host"].items()}
    marks = red.busiest().modules
    if marks:
        red.window = (marks[0][0], marks[-1][1])
        red.steps = len(marks)
    else:
        ops = red.busiest().ops
        red.window = (min(o[0] for o in ops), max(o[1] for o in ops))
    lo, hi = red.window
    for dev in red.devices:
        dev.ops = [o for o in dev.ops if o[0] >= lo and o[1] <= hi]
    return red


def _step_modules(modules, step_module):
    if not modules:
        return []
    if step_module is None:
        total = {}
        for s, e, n in modules:
            key = n.split("(")[0]
            total[key] = total.get(key, 0.0) + (e - s)
        step_module = max(total, key=total.get)
    return sorted(m for m in modules if step_module in m[2])


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    time, as ``<class>:<compact name>``, in seconds a traced step, and the
    longest idle gaps by what the host was doing."""
    dev = red.busiest()
    by_name = {}
    for s, e, name, cls, _b in dev.ops:
        label = f"{cls}:{name}"
        by_name[label] = by_name.get(label, 0.0) + (e - s)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    lo, hi = red.window
    named = {}
    for s, e in gaps(dev.intervals(), lo, hi):
        what = _host_doing(red, (s + e) / 2)
        named[what] = max(named.get(what, 0.0), e - s)
    idle = sorted(named.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}


def _host_doing(red: Reduced, t: float) -> str:
    for name in (PULL_SPAN, STEP_SPAN):
        for s, e in red.host.get(name, ()):
            if s <= t <= e:
                return name
    return "between_spans"


def summarize(path: str, top: int = 40) -> dict:
    """What a trace holds, for a look by hand: planes, lines, and the
    heaviest device events of each line."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"planes": []}
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            entry = {"line": line.name, "events": len(events)}
            agg = {}
            for ev in events:
                a = agg.setdefault(ev.name, [0.0, 0])
                a[0] += ev.duration_ns * 1e-9
                a[1] += 1
            heavy = sorted(agg.items(), key=lambda kv: -kv[1][0])[:top]
            entry["heaviest"] = [
                {"name": compact(k)[:160], "class": classify(k),
                 "seconds": v[0], "count": v[1]} for k, v in heavy]
            lines.append(entry)
        out["planes"].append({"plane": plane.name, "lines": lines})
    return out


# --------------------------------------------------------- program counters
def memory_peak_bytes(stats) -> int:
    """Peak on the fullest chip: live buffers plus what compiled programs
    reserve for their temporaries (``peak_bytes_in_use`` alone misses the
    temporaries on this backend, PERF.md PR 22)."""
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)) for s in stats)


class ProgramCounters:
    """The program's own counters around the window: seconds the fit loop
    waited for a batch (``dl4j_train_data_wait_seconds``, recorded only
    while the program's profiling mode is on, which a traced run turns
    on), distinct signatures its churn detector saw
    (``dl4j_recompiles_total``), and JAX's own backend compiles."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.compiles = 0
        self._listening = False
        self._t0 = {}
        self._t1 = {}
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if self._listening and event.endswith("backend_compile_duration"):
            self.compiles += 1

    @staticmethod
    def _snapshot():
        from deeplearning4j_tpu.profiler import get_registry
        reg = get_registry()
        wait = reg.histogram("dl4j_train_data_wait_seconds",
                             "Host wait for the next training batch")
        churn = reg.counter(
            "dl4j_recompiles_total",
            "Distinct jit signatures compiled per dispatch site (a value "
            "that keeps growing during steady-state training is churn)",
            labelnames=("site",))
        total = sum(s[2] for child in list(churn._children.values())
                    for s in child._samples())
        return {"data_wait_s": float(wait.sum), "recompiles": float(total)}

    def start(self):
        if self.traced:
            from deeplearning4j_tpu.profiler import set_profiling_mode
            set_profiling_mode("basic")
        self._t0 = self._snapshot()
        self._listening = True
        self._clock = time.perf_counter()

    def stop(self):
        self._listening = False
        self._t1 = self._snapshot()
        if self.traced:
            from deeplearning4j_tpu.profiler import set_profiling_mode
            set_profiling_mode(None)

    def read(self) -> dict:
        out = {k: self._t1[k] - self._t0[k] for k in self._t0}
        out["jax_compiles"] = self.compiles
        out["data_wait_recorded"] = self.traced
        return out
