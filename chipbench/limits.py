"""Readings for the limits of ``correct``, many seeds in one process.

    python3 -m chipbench.limits --workload <name> --seeds 11,12,... \
        --out chiprun_out/limits_<name>.json

For every seed: the program's first update steps (the driver's own
``first_steps``, at the cell's own size), the plain reference, and beside
them the control (the reference in fp8), a witness (the reference rounded
where bf16 rounds) and the planted faults a training cell can have, each
as ``chipbench.compare.numbers`` against the reference. Not part of a
check: the measuring command is ``chipbench.run``; ``PERF.md`` gives the
limits set from these readings.
"""

import argparse
import gc
import json
import os
import sys
import time

from chipbench import compare, run as runmod
from chipbench.manifest import Manifest
from chipbench.weights import make_weights


def variants(cell):
    """name -> (precision, rows kept of every batch)."""
    batch, chips = int(cell["traffic"]["batch"]), int(cell["chips"])
    out = {"control_fp8": ("fp8", None), "witness_bf16": ("bf16", None),
           "fault_half_batch": ("f32", slice(0, batch // 2))}
    if chips > 1:   # chips that never exchange each train on their own rows
        out["fault_no_exchange"] = ("f32", slice(0, batch // chips))
    return out


def read_seed(cell, seed, which, dump=False):
    """``{name: numbers}`` for one seed; ``which`` names the variants
    wanted besides ``program``."""
    import jax
    from chipbench.drivers import fit_iterator as drv
    cfg, model, traffic = cell["cfg"], cell["model"], cell["traffic"]
    n = int(traffic["check_steps"])
    batches = drv.make_batches(cfg, {**traffic, "pool": n}, seed)
    net = drv.configure(model.build(
        cfg, make_weights(model.param_spec(cfg), seed),
        chips=int(cell["chips"])), cfg)
    listener = drv.StepListener()
    net.setListeners(listener)
    program = drv.first_steps(net, model, cfg, traffic, batches, seed,
                              listener, drv.fit_of(model))
    net.setListeners()
    del net, listener
    gc.collect()
    reference = drv.reference_numbers(cell, batches, seed)
    out = {"program": compare.numbers(program, reference)}
    out["reference_losses"] = reference["losses"]
    raw = {"program": per_leaf(program, reference)}
    for name, (precision, rows) in variants(cell).items():
        if name not in which:
            continue
        try:
            got = drv.reference_numbers(cell, batches, seed, precision, rows)
            out[name] = compare.numbers(got, reference)
            raw[name] = per_leaf(got, reference)
        except Exception as e:      # a control that crashes has failed
            out[name] = {"error": repr(e)[:400]}
    if dump:
        out["raw"] = raw
    return out


def per_leaf(got, reference):
    """Every leaf's gaps, for choosing what to compare."""
    ref_g = {k: compare.norm(g) for k, g in reference["first_grads"].items()}
    got_g = {k: compare.norm(g) for k, g in got["first_grads"].items()}
    return {"losses": got["losses"], "ref_grad_norms": ref_g,
            "grad": compare.leaf_gaps(got_g, ref_g),
            "graddir": compare.direction_gaps(got["first_grads"],
                                              reference["first_grads"]),
            "change": compare.leaf_gaps(got["change_norms"],
                                        reference["change_norms"])}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="control_fp8,witness_bf16,"
                    "fault_half_batch,fault_no_exchange")
    ap.add_argument("--variant-seeds", type=int, default=3,
                    help="how many of the seeds also read the variants")
    ap.add_argument("--dump", type=int, default=0,
                    help="1: keep every leaf's norms of every variant")
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    manifest = Manifest()
    runmod.find_chips(manifest.workload(a.workload)["chips"])
    runmod.place_cache()
    cell = manifest.cell(a.workload)
    if cell["cfg"]["settings"].get("pallas_overrides"):
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        pk.install_platform_overrides()
    which = [v for v in a.variants.split(",") if v]
    readings = []
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        r = read_seed(cell, seed, which if i < a.variant_seeds else (),
                      dump=bool(a.dump))
        r["seed"], r["seconds"] = seed, time.perf_counter() - t0
        readings.append(r)
        print(json.dumps({"seed": seed, "seconds": r["seconds"],
                          **{k: {n: v for n, v in r[k].items()
                                 if not n.endswith("_leaf")}
                             for k in r if isinstance(r[k], dict)
                             and k != "raw"}}),
              file=sys.stderr, flush=True)
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"workload": a.workload, "readings": readings}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
