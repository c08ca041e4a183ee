"""The measuring command.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One run of one cell of ``BENCHMARK.json`` on the machine it is started on.
It fails (exit 2, nothing on standard output) when JAX finds no TPU, fewer
or more chips than the cell asks for, or a ``device_kind`` the peaks table
lacks; it never falls back to the CPU. The last line of standard output is
the result: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` in a traced run), then ``checks``, every number compared
beside its limit; the same numbers are the last lines of standard error.
"""

import time

CLOCK0 = time.perf_counter()    # process start, as near as Python can say

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

from chipbench import peaks, trace as trace_mod       # noqa: E402
from chipbench.manifest import ROOT, Manifest          # noqa: E402

TRACE_ROOT = os.path.join(ROOT, ".chipbench_trace")


class Ctx:
    """What a metric reader may look at."""

    def __init__(self, cell, result, peak, reduced=None):
        self.cfg = cell["cfg"]
        self.model = cell["model"]
        self.result = result
        self.peak = peak
        self.reduced = reduced


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(chips: int):
    """The devices of this run, or a refusal."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise peaks.UnknownDevice(
            f"no TPU: jax found {devices[0].platform} "
            f"{devices[0].device_kind!r}")
    if len(devices) != chips:
        raise peaks.UnknownDevice(
            f"the cell asks for {chips} chip(s), jax found {len(devices)}")
    return devices, peaks.peaks_for(devices[0].device_kind)


def place_cache():
    """JAX's persistent compilation cache where the program's own helper
    puts it (``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache`` in
    the checkout), keeping every program however quick its compile: the
    net's ``init()`` alone compiles some fifty small programs, which JAX's
    default threshold of one second would compile again in every run."""
    import jax
    from deeplearning4j_tpu.utils.environment import place_jax_compile_cache
    place_jax_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run_cell(manifest, args, devices, peak, interpret_kernels=False,
             fit=None):
    """Everything after the look for a chip: the run, the metrics, the
    comparison. Returns the result line as a dict."""
    import jax
    cell = manifest.cell(args.workload)
    args.trace_dir = os.path.join(TRACE_ROOT, args.workload)
    if args.trace:
        shutil.rmtree(args.trace_dir, ignore_errors=True)
    kw = {} if fit is None else {"fit": fit}
    result = cell["driver"].run(cell, args, CLOCK0,
                                interpret_kernels=interpret_kernels, **kw)
    kind = devices[0].device_kind
    reduced = None
    if args.trace:
        reduced = trace_mod.reduce_xspace(
            trace_mod.find_xspace(args.trace_dir),
            cell["traffic"].get("step_module"))
        shutil.rmtree(args.trace_dir, ignore_errors=True)
    ctx = Ctx(cell, result, peak, reduced)
    metrics = {}
    which = "per_layer" if args.trace else "end_to_end"
    for m in manifest.metrics_for(args.workload, which):
        value = manifest.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": trace_mod.memory_peak_bytes(
                  result["memory_stats"])}
    if reduced is not None:
        device["busy_s"] = sum(d.busy() for d in reduced.devices) \
            / len(reduced.devices)
        device["window_s"] = reduced.window_s

    # the program's state is freed and the peak is read: now the reference
    correct, checks = cell["driver"].check(cell, result, args.seed)
    line = {"correct": bool(correct and not result["failed"]),
            "attempted": result["attempted"],
            "failed": result["failed"] if correct else result["attempted"],
            "metrics": metrics, "device": device}
    if reduced is not None:
        line["breakdown"] = trace_mod.breakdown(reduced)
    line["checks"] = checks
    print("chipbench run: " + json.dumps(
        {"steps": result["steps"], "window_s": result["window_s"],
         "setup_s": result["setup_s"], "counters": result["counters"],
         "setup_phases": result["phases"]}),
        file=sys.stderr)
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    manifest = Manifest()
    chips = manifest.workload(args.workload)["chips"]
    t_reach = time.perf_counter()
    try:
        devices, peak = find_chips(chips)
    except peaks.UnknownDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    # the TPU runtime's own start: 9 to 15 s, drifting over minutes on one
    # machine and moved by nothing in this repo; kept out of ``setup_s``
    args.reach_chip_s = time.perf_counter() - t_reach
    place_cache()
    line = run_cell(manifest, args, devices, peak)
    for name, check in line["checks"].items():
        print(f"chipbench check {name}: {json.dumps(check)}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
