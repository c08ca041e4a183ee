"""The seconds before the first step, from the program's own build spans.

The program records, whatever its profiling mode, a span for every program
it builds (``deeplearning4j_tpu.nn.compilecache.watch_builds``):
``compile:trace``, ``compile:lower`` and ``compile:backend`` as JAX reports
them, each with the ``program`` it built, the span that caused it
(``cause``: the ``fit:build`` around a dispatch that built its step, the
``net:init`` around a network's ``init()``, or ``None``), and on
``compile:backend`` whether JAX's persistent cache answered (``cache``:
``hit`` | ``miss`` | ``off``). The seven ``setup_*`` readers under
``chipbench/metrics/`` report what :func:`split` makes of them.

:func:`split` is pure: it takes the tracer ring's events as plain dicts
(``{"name", "ts", "dur", "tid", "args"}``, microseconds on the ring's
clock) and the window's start on that clock, so the CPU tests feed it
recordings. :func:`from_program` is the one place that asks the live
program. A tree without such spans (the parent of the PR that brought
them) reads ``None`` and every reader reports nothing; a set-up that built
nothing leaves its ``net:init`` and reads ``0.0``.

What counts once. A function jitted inside the step is traced while the
step's trace is open, so ``compile:trace`` spans nest (by ``ts`` and
``dur``); the parts below are measures of unions of intervals, taken in the
order backend, lower, trace, so an interval counts once however its spans
nest, and the four parts of the step's build add up to the ``fit:build``
total by construction.
"""

NET_INIT = "net:init"
FIT_BUILD = "fit:build"
TRACE, LOWER, BACKEND = "compile:trace", "compile:lower", "compile:backend"
KINDS = (NET_INIT, FIT_BUILD, TRACE, LOWER, BACKEND)
# a traced run turns the program's instrumentation on for exactly the
# window; set-up's fits leave neither of these
WINDOW_MARKS = ("fit:epoch", "fit:pull")


def from_program():
    """The tracer ring of the program in this process, oldest first, or
    ``None`` where it has no tracer."""
    try:
        from deeplearning4j_tpu.profiler import get_tracer
    except ImportError:
        return None
    return get_tracer().events()


def window_start(events):
    """The earliest ``fit:epoch`` / ``fit:pull`` span's start, or ``None``
    where the ring holds neither."""
    marks = [ev["ts"] for ev in events if ev["name"] in WINDOW_MARKS]
    return min(marks) if marks else None


def covered(intervals):
    """Measure of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def split(events, start=None):
    """Set-up's builds: a dict of seconds (``init_s``, ``step_trace_s``,
    ``step_lower_s``, ``step_backend_s``, ``step_build_self_s``,
    ``step_build_s``) and counts (``programs_built``, ``cache_misses``)
    over the spans that began before ``start`` (every span where it is
    ``None``); ``None`` where ``events`` holds no span of these kinds."""
    known = [ev for ev in events or () if ev["name"] in KINDS]
    if not known:
        return None
    mine = [ev for ev in known if start is None or ev["ts"] < start]
    builds = [ev for ev in mine if ev["name"] == FIT_BUILD]
    backends = [ev for ev in mine if ev["name"] == BACKEND]
    by_kind = {TRACE: [], LOWER: [], BACKEND: []}
    build_us = 0.0
    for b in builds:
        b0, b1 = b["ts"], b["ts"] + b["dur"]
        build_us += b["dur"]
        for ev in mine:
            if ev["name"] in by_kind and ev.get("tid") == b.get("tid") \
                    and (ev.get("args") or {}).get("cause") == FIT_BUILD \
                    and b0 <= ev["ts"] + ev["dur"] and ev["ts"] <= b1:
                by_kind[ev["name"]].append((max(ev["ts"], b0),
                                            min(ev["ts"] + ev["dur"], b1)))
    backend = covered(by_kind[BACKEND])
    lowered = covered(by_kind[BACKEND] + by_kind[LOWER])
    all_three = covered(by_kind[BACKEND] + by_kind[LOWER] + by_kind[TRACE])
    return {
        "init_s": 1e-6 * sum(ev["dur"] for ev in mine
                             if ev["name"] == NET_INIT),
        "step_trace_s": 1e-6 * (all_three - lowered),
        "step_lower_s": 1e-6 * (lowered - backend),
        "step_backend_s": 1e-6 * backend,
        "step_build_self_s": 1e-6 * (build_us - all_three),
        "step_build_s": 1e-6 * build_us,
        "programs_built": len(backends),
        "cache_misses": sum(
            1 for ev in backends
            if (ev.get("args") or {}).get("cache") == "miss"),
    }


def of(ctx):
    """The run's :func:`split`, made by the first reader that asks: of the
    live program's ring in a traced run, cut at its window; ``None``
    otherwise."""
    if not hasattr(ctx, "buildspans"):
        events = from_program() if ctx.result.get("traced") else None
        ctx.buildspans = None if events is None \
            else split(events, window_start(events))
    return ctx.buildspans


def reading(ctx, key):
    """One number of :func:`of`, or ``None`` where there is nothing to
    read."""
    got = of(ctx)
    return None if got is None else got[key]
