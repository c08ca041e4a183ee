"""Probe: what the sparse-expert layers' states read after the WHOLE
measured window of a benchmark cell.

The harness's per-layer metrics read the program's gauges, which only a
traced run publishes (its shorter window); this runs one untraced run of
``--workload`` exactly as ``chipbench.run`` does, with the window's
``fit`` call wrapped so that the layers' ``expert_load`` and
``pass_steps`` are read after every call returns: after each of
set-up's first steps and after the window's last. One JSON line a run,
appended to ``chiprun_out/routed_window.jsonl``; the harness's own result
line goes to stdout as always.

    chiprun --timeout 1800 -- python3 benchmarks/probe_routed_window.py \\
        --workload lfm2-fit-s8192-b4 --seed 2147483777 --seconds 10
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
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
        print(f"probe_routed_window: {e}", file=sys.stderr)
        return 2
    args.reach_chip_s = time.perf_counter() - t_reach
    runmod.place_cache()
    import jax
    inner = manifest.cell(args.workload)["driver"].fit_call
    calls = []

    def fit(net, iterator):
        inner(net, iterator)
        jax.block_until_ready(net._params)
        calls.append({
            name: {k: [float(v) for v in jax.device_get(state[k])]
                   for k in ("expert_load", "pass_steps") if k in state}
            for name, state in net._states.items()
            if isinstance(state, dict) and "expert_load" in state})

    line = runmod.run_cell(manifest, args, devices, peak, fit=fit)
    out = {"workload": args.workload, "seed": args.seed,
           "fit_calls": len(calls),
           "after_first_call": calls[0], "after_window": calls[-1],
           "correct": line["correct"],
           "metrics": {k: v["value"] for k, v in line["metrics"].items()}}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/routed_window.jsonl", "a") as fh:
        fh.write(json.dumps(out) + "\n")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
