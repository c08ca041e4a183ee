"""Probe: which ops of a language-model cell's train step take the time.

Builds a benchmark configuration's net at its real size (or a cut of it),
runs ``net.fit`` steps on the attached device, traces a few, and joins
every device op to the compiled step's own map
(``profiler.stepprogram.parse``: phase, part, rematerialised) and to the
instruction's ``op_name`` and HBM bytes as compiled (a fusion parameter
read only through slices counts its slices). What a ``perf_opt`` on
``xing4-fit-s4096-b1``, ``ouro-fit-s4096-b1`` or ``lfm2-fit-s8192-b4``
(``--config lfm2-24b-a2b-l5-bf16 --batch 4``) reads before and after a
change; the harness's ``mhc_device_ms`` and its like are sums over this
table (PR 34: the probe reads the ledger's 64.99 as 64.987).

One table a run, under ``chiprun_out/``: totals by (part, phase), then the
ops in order of time. Runs the tree it is pointed at, so one chip call can
hold the parent (``git archive`` into ``.scratch_checkout/parent``) and
the change:

    chiprun --timeout 1800 -- bash -c '
      python3 benchmarks/probe_step_ops.py --root .scratch_checkout/parent --tag parent &&
      python3 benchmarks/probe_step_ops.py --tag change'

``--cut '{"num_layers": 2, "num_nextn_predict_layers": 0}'`` overrides
keys of the configuration (a first step in a minute instead of four), but
a cut is laid out differently by the compiler (PERF.md section 6, PR 34):
its times are no guide to the cell's. On the CPU it runs through at a
tiny ``--cut`` and writes a table without device times (a rehearsal).
"""

import argparse
import collections
import glob
import importlib.util
import json
import os
import re
import sys
import time

_DT = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "f16": 2,
       "s8": 1, "u8": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2, "u16": 2}
_SHAPE = re.compile(r"\b(%s)\[([\d,]*)\]" % "|".join(_DT))
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][a-z0-9\-]*)\((.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def nbytes(shapes: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(shapes):
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n * _DT[dtype]
    return total


def computations(text: str):
    """``({computation: [instruction lines]}, the entry's name)``."""
    comps, entry, cur = {}, None, None
    for line in text.split("\n"):
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
                if line.startswith("ENTRY"):
                    entry = m.group(1)
        elif line.startswith("}"):
            cur = None
        else:
            cur.append(line)
    return comps, entry


def fusion_read_bytes(body) -> int:
    """Bytes a fused computation reads: a parameter whose every user is a
    slice counts what the slices take."""
    params, users = {}, collections.defaultdict(list)
    for line in body:
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        if op == "parameter":
            params[name] = nbytes(shape)
        for operand in re.findall(r"%([\w.\-]+)",
                                  rest.split(", metadata=")[0]):
            users[operand].append((op, shape))
    total = 0
    for name, size in params.items():
        used = users.get(name, [])
        if used and all(op in ("slice", "dynamic-slice") for op, _ in used):
            size = min(size, sum(nbytes(shape) for _, shape in used))
        total += size
    return total


def instruction_table(text: str):
    """``{instruction: (opcode, result shapes, op_name, bytes)}`` of the
    entry computation."""
    comps, entry = computations(text)
    out = {}
    for line in comps[entry]:
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        size = nbytes(shape)
        called = _CALLS.search(rest)
        if op == "fusion" and called and called.group(1) in comps:
            size += fusion_read_bytes(comps[called.group(1)])
        op_name = _OP_NAME.search(line)
        out[name] = (op, shape, op_name.group(1) if op_name else "", size)
    return out


def device_op_ns(trace_dir: str):
    """``{instruction: nanoseconds}`` over the first TPU's ``XLA Ops``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    took = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    took[re.match(r"%?([\w.\-]+)", ev.name).group(1)] \
                        += ev.duration_ns
    return took


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to run")
    ap.add_argument("--config", default="xing4.0-29b-a4b-l5-bf16")
    ap.add_argument("--cut", default="{}", help="JSON of overridden keys")
    ap.add_argument("--tag", default="change")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--traced", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--batch", type=int, default=1,
                    help="sequences a step (lfm2-fit-s8192-b4: 4)")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)

    import jax
    import numpy as np
    from chipbench import weights as plain
    from chipbench.drivers import fit_iterator as base
    from chipbench.drivers import fit_tokens_lean as lean
    from deeplearning4j_tpu import profiler
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.profiler import stepprogram

    cfg_dir = os.path.join(root, "chipbench", "configs", args.config)
    spec = importlib.util.spec_from_file_location(
        "probe_model", os.path.join(cfg_dir, "model.py"))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    cfg = json.load(open(os.path.join(cfg_dir, "config.json")))
    cfg.update(json.loads(args.cut))
    out_dir = os.path.join(os.getcwd(), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    lean_driven = hasattr(model, "state_spec")     # the sparse decoder
    make_weights = lean.make_weights if lean_driven else plain.make_weights
    weights = jax.block_until_ready(
        make_weights(model.param_spec(cfg), args.seed))
    build = {"states": lean.make_states(model, cfg, args.seed),
             "batch": args.batch} if lean_driven else {}
    net = base.configure(model.build(cfg, weights, chips=1, **build), cfg)
    del weights
    rng = np.random.default_rng(0)

    def batch():
        rows = rng.integers(0, cfg["vocab_size"],
                            (args.batch, cfg["seq_len"] + 1), dtype=np.int32)
        return DataSet(rows[:, :-1].copy(), rows[:, 1:].copy())

    def fit(n):
        for _ in range(n):
            net.fit(batch())
        jax.block_until_ready(net._params)

    profiler.set_profiling_mode("basic")
    stepprogram.clear()
    t0 = time.perf_counter()
    fit(1)
    print(args.tag, "first step s", round(time.perf_counter() - t0, 1),
          "paths lowered", [line for line in profiler.get_registry()
                            .exposition().split("\n")
                            if "_lowered_total{" in line], flush=True)
    # the step as compiled, with this tree's scopes in it (what
    # ``stepprogram.flush`` would parse when the mode is left)
    text = stepprogram._compiled_text(*stepprogram._PENDING[0])
    entries = stepprogram.parse(text)
    profiler.set_profiling_mode(None)
    fit(3)
    t0 = time.perf_counter()
    fit(args.steps)
    step_ms = (time.perf_counter() - t0) / args.steps * 1e3
    print(args.tag, "step ms (host clock)", round(step_ms, 2), "loss",
          float(net._score), flush=True)
    trace_dir = os.path.join(os.getcwd(), ".chipbench_trace",
                             "probe_" + args.tag)
    jax.profiler.start_trace(trace_dir)
    fit(args.traced)
    jax.profiler.stop_trace()

    took, table = device_op_ns(trace_dir), instruction_table(text)
    rows, totals = [], collections.Counter()
    for name, ns in took.items():
        entry = entries.get(name)
        op, shape, op_name, size = table.get(name, ("?", "", "", 0))
        part = (entry.part if entry else None) or "-"
        phase = ("remat" if entry.remat else entry.phase) if entry else "?"
        us = ns / 1e3 / args.traced
        totals[part, phase] += us
        totals["all", "all"] += us
        rows.append((us, name, part, phase, size, shape[:60],
                     op_name[-110:]))
    with open(os.path.join(out_dir, f"probe_{args.tag}.txt"), "w") as f:
        f.write(f"{args.tag} step_ms_host {step_ms:.2f}\n")
        for (part, phase), us in sorted(totals.items(),
                                        key=lambda kv: -kv[1]):
            f.write(f"TOTAL {part:12s} {phase:8s} {us / 1e3:9.3f} ms\n")
        for us, name, part, phase, size, shape, op_name in sorted(
                rows, reverse=True):
            if us >= 5:
                f.write(f"{us:9.1f} us {size / 1e6:8.1f} MB "
                        f"{size / us / 1e3:6.0f} GB/s {part:8s} {phase:6s} "
                        f"{name:30s} {shape} | {op_name}\n")
    by_part = collections.Counter()
    for (part, phase), us in totals.items():
        if part != "all":
            by_part[part] += us
    print(args.tag, "device ms a step by part",
          {k: round(v / 1e3, 2) for k, v in by_part.most_common()},
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
