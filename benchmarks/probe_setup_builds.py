"""Probe: where a benchmark cell's set-up seconds go, span by span.

Runs one run of ``--workload`` exactly as ``chipbench.run`` does (traced or
not), then reads the program's own build spans out of the tracer's ring
(``nn.compilecache.watch_builds``: ``net:init``, ``fit:build``,
``compile:trace / lower / backend``), which the seven ``setup_*`` metrics
only sum: spans and seconds by kind and cause, the programs whose traces
lie INSIDE the step's trace by their total seconds (which rule's call
sites the trace time belongs to), the programs the backend took longest
over, how often the listener was called before the window and what one
call costs here (timed on planted events after the run). One JSON line a
run, appended to ``chiprun_out/setup_builds.jsonl``; the harness's own
result line goes to stdout as always.

    chiprun --timeout 1800 -- python3 benchmarks/probe_setup_builds.py \\
        --workload xing4-fit-s4096-b1 --seed 2147483777 --seconds 10 \\
        --trace 1
"""

import io
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOP = 12


class _Tee(io.TextIOBase):
    """Standard error, with the harness's ``chipbench run:`` line kept."""

    def __init__(self, out):
        self.out, self.kept = out, []

    def write(self, text):
        self.kept.append(text)
        return self.out.write(text)

    def flush(self):
        self.out.flush()


def _listener_call_us(n=10000):
    """Microseconds one call of the duration listener takes here: a
    ``compile:trace`` event heard and put into the ring (whose set-up
    spans have been read by now)."""
    import jax
    t0 = time.perf_counter()
    for _ in range(n):
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/jaxpr_trace_duration", 1e-4,
            fun_name="planted")
    return (time.perf_counter() - t0) / n * 1e6


def _top(totals):
    return [[name, round(s, 4), n] for name, (s, n) in sorted(
        totals.items(), key=lambda kv: -kv[1][0])[:TOP]]


def nested(traces):
    """Of ``compile:trace`` spans, those that lie inside another of their
    thread: by start, the longer first, against the ends still open."""
    inside, around = [], {}
    for ev in sorted(traces, key=lambda ev: (ev["ts"], -ev["dur"])):
        ends = around.setdefault(ev.get("tid"), [])
        while ends and ends[-1] < ev["ts"] + ev["dur"] - 1.0:
            ends.pop()
        if ends:
            inside.append(ev)
        ends.append(ev["ts"] + ev["dur"])
    return inside


def summary(events, start):
    """What the ring says of the spans that began before ``start``."""
    from chipbench import buildspans as bs
    mine = [ev for ev in events if ev["name"] in bs.KINDS
            and (start is None or ev["ts"] < start)]
    by = {}
    inner, backend = {}, {}
    for ev in mine:
        args = ev.get("args") or {}
        key = f"{ev['name']} <- {args.get('cause')}"
        s, n = by.get(key, (0.0, 0))
        by[key] = (s + ev["dur"] * 1e-6, n + 1)
        if ev["name"] == bs.BACKEND:
            s, n = backend.get(args.get("program"), (0.0, 0))
            backend[args.get("program")] = (s + ev["dur"] * 1e-6, n + 1)
    for ev in nested([ev for ev in mine if ev["name"] == bs.TRACE and
                      (ev.get("args") or {}).get("cause") == bs.FIT_BUILD]):
        s, n = inner.get(ev["args"]["program"], (0.0, 0))
        inner[ev["args"]["program"]] = (s + ev["dur"] * 1e-6, n + 1)
    return {"by_kind_and_cause": {k: [round(s, 4), n]
                                  for k, (s, n) in sorted(by.items())},
            "listener_calls": sum(1 for ev in mine
                                  if ev["name"].startswith("compile:")),
            "nested_traces_top": _top(inner),
            "backend_top": _top(backend),
            "builds": [ev.get("args") | {"seconds": ev["dur"] * 1e-6}
                       for ev in mine if ev["name"] == bs.FIT_BUILD]}


def main(argv=None) -> int:
    from chipbench import buildspans as bs
    from chipbench import peaks
    from chipbench import run as runmod
    from chipbench.manifest import Manifest
    args = runmod.parse_args(argv)
    manifest = Manifest()
    t_reach = time.perf_counter()
    try:
        devices, peak = runmod.find_chips(
            manifest.workload(args.workload)["chips"])
    except peaks.UnknownDevice as e:
        print(f"probe_setup_builds: {e}", file=sys.stderr)
        return 2
    args.reach_chip_s = time.perf_counter() - t_reach
    runmod.place_cache()
    tee = sys.stderr = _Tee(sys.stderr)
    try:
        line = runmod.run_cell(manifest, args, devices, peak)
    finally:
        sys.stderr = tee.out
    said = [ln for ln in "".join(tee.kept).splitlines()
            if ln.startswith("chipbench run: ")]
    ran = json.loads(said[-1][len("chipbench run: "):])
    events = bs.from_program()
    # an untraced run leaves no mark of its window: its set-up's builds
    # are then all the builds there are but a recompile in the window
    start = bs.window_start(events)
    out = {"workload": args.workload, "seed": args.seed,
           "trace": args.trace, "correct": line["correct"],
           "setup_s": ran["setup_s"], "setup_phases": ran["setup_phases"],
           "split": bs.split(events, start), **summary(events, start),
           "metrics": {k: v["value"] for k, v in line["metrics"].items()
                       if k.startswith("setup_") or k.startswith("compiles")
                       or k.startswith("img_")}}
    out["listener_call_us"] = round(_listener_call_us(), 3)
    out["listener_total_s"] = out["listener_calls"] \
        * out["listener_call_us"] * 1e-6
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/setup_builds.jsonl", "a") as fh:
        fh.write(json.dumps(out) + "\n")
    print("probe_setup_builds: " + json.dumps(out), file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
