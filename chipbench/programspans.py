"""The program's own instruments beside the device trace.

Two things the program records while its instrumentation is on (which a
traced run turns on around the window, ``chipbench.trace.ProgramCounters``)
and the metric readers under ``chipbench/metrics/`` join to a
:class:`chipbench.trace.Reduced`:

- the **step-program map** (``deeplearning4j_tpu.profiler.stepprogram``):
  ``{module: {instruction: (phase, layer, kernel, mixed)}}``, joined to the
  trace's device ops by the instruction's name (``%fusion.1827``);
- the **fit-loop spans** in the program's tracer ring (``fit:pull``,
  ``fit:stage``, ``fit:prepare``, ``fit:listeners``, ``fit:dispatch``,
  ``fit:commit``, ``host:gc``), each with its ``iteration``, on the host's
  ``time.perf_counter()`` clock.

The functions here are pure: they take a ``Reduced``, spans as plain dicts
(``{"name", "t0", "t1", "iteration", "when", "bytes"}``, seconds) and maps
as plain data, so the CPU tests feed them recordings. :func:`from_program`
is the one place that asks the live program, and it answers ``None`` for a
program that has no such instruments (the parent of the PR that brought
them) — every reader then reports nothing, never a guess.

The two clocks. The trace has its own clock; the spans are on the host's.
``chipbench:step`` k (trace clock) is opened inside the program's
``fit:listeners(start)`` of one iteration and closed inside its
``fit:listeners(done)`` (host clock), so every traced step brackets the
offset between the clocks from both sides, to the few microseconds the
listener takes. :func:`clock_offset` intersects the brackets and refuses
(``None``, one line on standard error) when they do not agree to 0.5 ms.
"""

import bisect
import statistics
import sys

from chipbench.trace import STEP_SPAN, gaps

PHASES = ("forward", "backward", "updater")
MIXED, OTHER = "mixed", "other"
UNSURE = (MIXED, OTHER)     # what the map cannot give one phase
CLOCK_TOLERANCE_S = 0.5e-3


# ------------------------------------------------------ the live program
def from_program():
    """``(spans, maps)`` of the program in this process, or ``(None,
    None)`` where it has no step-program map or no spans on a convertible
    clock."""
    try:
        from deeplearning4j_tpu.profiler import (get_tracer,
                                                 perf_counter_seconds,
                                                 stepprogram)
    except ImportError:
        return None, None
    spans = []
    for ev in get_tracer().events():
        args = ev.get("args") or {}
        spans.append({"name": ev["name"],
                      "t0": perf_counter_seconds(ev["ts"]),
                      "t1": perf_counter_seconds(ev["ts"] + ev["dur"]),
                      "iteration": args.get("iteration"),
                      "when": args.get("when"),
                      "bytes": args.get("bytes")})
    maps = {module: {name: list(entry) for name, entry in m.items()}
            for module, m in stepprogram.maps().items()}
    return spans, maps


class Joined:
    """What the readers share of one run: the program's spans and maps,
    the traced iterations (host clock alone) and the offset that puts
    them on the trace's clock (``None`` each where there is nothing to
    read, or the clocks cannot be joined)."""

    def __init__(self, red, traced, spans, maps):
        self.traced, self.spans, self.maps = traced, spans, maps
        self.iterations = traced_iterations(spans, traced)
        self.offset = clock_offset(red, self.iterations)


def of(ctx) -> Joined:
    """The run's :class:`Joined`, made by the first reader that asks: of
    the live program in a traced run, of nothing otherwise."""
    if getattr(ctx, "programspans", None) is None:
        traced = ctx.result.get("traced")
        spans, maps = from_program() \
            if ctx.reduced is not None and traced else (None, None)
        ctx.programspans = Joined(ctx.reduced, traced, spans, maps)
    return ctx.programspans


def counter_total(name):
    """A program counter's value, or ``None`` where the program has no
    counter of that name."""
    from deeplearning4j_tpu.profiler import get_registry
    metric = get_registry().get(name)
    return None if metric is None else float(metric.value)


# ------------------------------------------------- device ops by the map
def head(op_name: str) -> str:
    """``fusion.1827`` from a compact device-op name."""
    return op_name.partition(" = ")[0].lstrip("%")


def step_map(red, maps):
    """The map of the step program the trace's step marks belong to."""
    if red is None or not maps or not red.steps:
        return None
    module = red.busiest().modules[0][2].partition("(")[0]
    return maps.get(module)


def phase_of(entry) -> str:
    """``forward``, ``backward`` or ``updater`` for an op the map is sure
    of; :data:`MIXED` for one fusion doing two phases' work (a
    weight-gradient convolution fused with Adam's update), :data:`OTHER`
    for one the map does not list or lists as ``other``."""
    if entry is None or entry[0] not in PHASES:
        return OTHER
    return MIXED if entry[3] else entry[0]


def phase_seconds(red, maps):
    """``{phase: [seconds in each traced step]}`` on the busiest device,
    for the three phases, :data:`MIXED` and :data:`OTHER`: every op inside
    a step program's run counts once, under what the map says of it."""
    smap = step_map(red, maps)
    if smap is None:
        return None
    dev = red.busiest()
    starts = [m[0] for m in dev.modules]
    out = {p: [0.0] * len(dev.modules) for p in PHASES + UNSURE}
    for s, e, name, _cls, _b in dev.ops:
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or e > dev.modules[k][1]:
            continue
        out[phase_of(smap.get(head(name)))][k] += e - s
    return out


def phase_ms(red, maps, phase):
    per_step = phase_seconds(red, maps)
    if per_step is None:
        return None
    return 1e3 * statistics.median(per_step[phase])


def unsure_share(red, maps):
    """Device-op time the map cannot give one phase (:data:`UNSURE`), over
    all device-op time of the traced stretch on the busiest device, in
    %."""
    smap = step_map(red, maps)
    if smap is None:
        return None
    total = unsure = 0.0
    for s, e, name, _cls, _b in red.busiest().ops:
        total += e - s
        if phase_of(smap.get(head(name))) in UNSURE:
            unsure += e - s
    return 100.0 * unsure / total if total > 0 else None


# ------------------------------------------------ spans of traced steps
def traced_iterations(spans, traced):
    """The iterations of the traced stretch, in order, each as ``{span
    name or "listeners_start" / "listeners_done": span}``. ``traced`` is
    the driver's ``(host start, host end, …)`` of the stretch: an
    iteration belongs to it when its ``fit:listeners(start)`` ended after
    the profile was started (the listener starts it) and its
    ``fit:listeners(done)`` began before the profile was stopped."""
    if not spans or not traced:
        return []
    by_iter = {}
    for sp in spans:
        if sp["iteration"] is None or not sp["name"].startswith("fit:"):
            continue
        key = sp["name"]
        if key == "fit:listeners":
            key = "listeners_" + str(sp["when"])
        by_iter.setdefault(sp["iteration"], {})[key] = sp
    t_start, t_stop = traced[0], traced[1]
    out = []
    for it in sorted(by_iter):
        got = by_iter[it]
        a, b = got.get("listeners_start"), got.get("listeners_done")
        if a is None or b is None or "fit:dispatch" not in got:
            continue
        if a["t1"] >= t_start and b["t0"] <= t_stop:
            out.append(got)
    return out


def clock_offset(red, iterations):
    """Trace clock minus host clock, in seconds, from the brackets the
    traced steps give: ``chipbench:step`` k starts inside
    ``fit:listeners(start)`` of iteration k and ends inside its
    ``fit:listeners(done)``, so the offset lies in ``[S - a1, S - a0]``
    and in ``[E - b1, E - b0]``. The offset is the middle of what all
    brackets leave; where they leave nothing, by more than 0.5 ms, the
    clocks cannot be joined and there is no reading."""
    if red is None or not iterations:
        return None
    steps = sorted(red.host.get(STEP_SPAN, ()))
    n = min(len(steps), len(iterations))
    if n == 0:
        return None
    lo, hi = float("-inf"), float("inf")
    for (s, e), got in zip(steps[:n], iterations[:n]):
        a, b = got["listeners_start"], got["listeners_done"]
        lo = max(lo, s - a["t1"], e - b["t1"])
        hi = min(hi, s - a["t0"], e - b["t0"])
    if lo - hi > CLOCK_TOLERANCE_S:
        print(f"chipbench programspans: the {n} step brackets leave no "
              f"common clock offset (they disagree by "
              f"{1e3 * (lo - hi):.3f} ms); no span metric is read",
              file=sys.stderr)
        return None
    return (lo + hi) / 2.0


# ------------------------------------------------------ the span metrics
def host_step_ms(iterations):
    """Median host time of one iteration, ``fit:pull``'s start to
    ``fit:listeners(done)``'s end."""
    vals = [got["listeners_done"]["t1"] - got["fit:pull"]["t0"]
            for got in iterations if "fit:pull" in got]
    return 1e3 * statistics.median(vals) if vals else None


def stage_ms(iterations):
    vals = [got["fit:stage"]["t1"] - got["fit:stage"]["t0"]
            for got in iterations if "fit:stage" in got]
    return 1e3 * statistics.median(vals) if vals else None


def dispatch_lead_ms(red, iterations, offset):
    """Median over the traced steps of (device start of step k's program
    minus the end of ``fit:dispatch`` k): how far ahead of the device the
    host runs. Negative where the device had to wait for the host."""
    mods = red.busiest().modules
    n = min(len(mods), len(iterations))
    if n == 0:
        return None
    return 1e3 * statistics.median(
        mods[k][0] - (iterations[k]["fit:dispatch"]["t1"] + offset)
        for k in range(n))


def idle_host_bound_share(red, iterations, offset):
    """Of the busiest device's idle time in the traced stretch, the share
    in gaps that began before the host had finished dispatching the step
    the device ran next (the device waited for the host), in %. The rest
    is the device idle with its next step already queued: waiting for an
    input's transfer, or the runtime."""
    dev = red.busiest()
    mods = dev.modules
    n = min(len(mods), len(iterations))
    if n == 0:
        return None
    ends = [m[1] for m in mods]
    idle = bound = 0.0
    for s, e in gaps(dev.intervals(), *red.window):
        idle += e - s
        k = bisect.bisect_right(ends, s)    # the step running or next
        if k < n and s < iterations[k]["fit:dispatch"]["t1"] + offset:
            bound += e - s
    return 100.0 * bound / idle if idle > 0 else 0.0


def gc_pause_ms_per_step(red, spans, traced):
    """``host:gc`` time inside the traced stretch, a step."""
    if not red.steps:
        return None
    t_start, t_stop = traced[0], traced[1]
    total = sum(min(sp["t1"], t_stop) - max(sp["t0"], t_start)
                for sp in spans if sp["name"] == "host:gc"
                and sp["t1"] > t_start and sp["t0"] < t_stop)
    return 1e3 * total / red.steps


# ------------------------------------------------------------ recordings
def save_beside(path, spans, maps, traced, red):
    """Keep what the readers need of one traced run beside a trace
    recording, as a small ``.json.gz``: the spans of the traced
    iterations and ``host:gc``, the map entries of the ops the recording
    holds, and the stretch's host times."""
    import gzip
    import json
    its = traced_iterations(spans, traced)
    keep = [sp for got in its for sp in got.values()]
    keep += [sp for sp in spans if sp["name"] == "host:gc"]
    names = {head(o[2]) for dev in red.devices for o in dev.ops}
    small = {module: {k: v for k, v in m.items() if k in names}
             for module, m in maps.items()}
    with gzip.open(path, "wt") as f:
        json.dump({"spans": keep, "maps": small, "traced": list(traced)},
                  f, separators=(",", ":"))


def load_beside(path):
    import gzip
    import json
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    return data["spans"], data["maps"], data["traced"]
