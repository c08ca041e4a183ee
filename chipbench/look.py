"""A look by hand at one cell's trace: run the cell traced, keep the
``.xplane.pb``'s summary (planes, lines, heaviest device events with their
categories and stats) and the reduction's view of it as JSON.

    python3 -m chipbench.look --workload <name> --seed <n> --seconds <s> \
        --out chiprun_out/look_<name>.json

Not part of a check: the measuring command is ``chipbench.run``.
"""

import argparse
import json
import os
import shutil
import sys

from chipbench import run as runmod, trace as trace_mod
from chipbench.manifest import Manifest


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--record", default=None,
                    help="keep the first two traced steps as a recording "
                    "(.json.gz) for tests/chipbench")
    a = ap.parse_args(argv)
    manifest = Manifest()
    devices, _peak = runmod.find_chips(manifest.workload(a.workload)["chips"])
    runmod.place_cache()
    cell = manifest.cell(a.workload)
    a.trace = 1
    a.trace_dir = os.path.join(runmod.TRACE_ROOT, a.workload + ".look")
    shutil.rmtree(a.trace_dir, ignore_errors=True)
    result = cell["driver"].run(cell, a, runmod.CLOCK0)
    path = trace_mod.find_xspace(a.trace_dir)
    out = {"xspace_bytes": os.path.getsize(path),
           "summary": trace_mod.summarize(path),
           "traced": result["traced"], "steps": result["steps"],
           "window_s": result["window_s"], "setup_s": result["setup_s"]}
    try:
        raw = trace_mod.read_xspace(path)
        if a.record:
            trace_mod.save_recording(raw, a.record, steps=2)
        red = trace_mod.reduce_raw(raw)
        dev = red.busiest()
        out["reduced"] = {
            "devices": [d.name for d in red.devices], "steps": red.steps,
            "window_s": red.window_s, "busy_s": dev.busy(),
            "seconds": {c: dev.seconds(c)
                        for c in ("conv", "other", "collective")},
            "events_per_step": {
                c: len(dev.intervals(c)) / max(red.steps, 1)
                for c in ("conv", "other", "collective")},
            "hbm_gb": {c: dev.bytes(c) / 1e9
                       for c in ("conv", "other", "collective")},
            "exposed_collective_s": dev.exposed("collective",
                                                ("conv", "other")),
            "modules": sorted({m[2] for m in dev.modules}),
            "host_spans": {k: len(v) for k, v in red.host.items()},
            "breakdown": trace_mod.breakdown(red)}
    except Exception as e:      # the look is for finding out why
        out["reduce_error"] = repr(e)
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=1)
    shutil.rmtree(a.trace_dir, ignore_errors=True)
    print(json.dumps({k: out[k] for k in out if k != "summary"})[:3000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
